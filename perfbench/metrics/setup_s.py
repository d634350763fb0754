"""Process start to the first timed release: init, synthesis, compile
or cache load, and the warm-up release (host clock)."""


def read(run):
    return run["setup_s"]

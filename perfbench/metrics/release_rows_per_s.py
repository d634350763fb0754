"""Input rows of the releases the window completed, over the window's
seconds (host clock). Fresh, resident and mesh releases alike."""


def read(run):
    if not run["rows_released"]:
        return None
    return run["rows_released"] / run["window_s"]

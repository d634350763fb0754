"""Seconds of backend compilation during set-up, loads from the
persistent compilation cache included (jax.monitoring)."""


def read(run):
    return run["setup_compile_s"]

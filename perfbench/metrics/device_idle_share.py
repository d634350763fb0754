"""Share of the traced window, in percent, in which no operation ran on
the device: 1 - (union of the device's op intervals / window), from the
profiler trace; on several chips the mean over the chips (each chip's
own is on standard error)."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

"""Share of the traced window, in percent, that the device spends in
collective operations (the cross-chip combine), mean over the chips."""


def read(run):
    t = run["trace"]
    if t is None or t["collective_s"] <= 0.0:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]

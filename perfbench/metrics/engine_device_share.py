"""Share of the window, in percent, in the fused device program and its
transfers: the releases' ``timings["device_s"]`` (the program's
``engine.device`` span) summed, over the window."""


def read(run):
    spent = [r["timings"]["device_s"] for r in run["releases"]
             if "device_s" in r["timings"]]
    if not spent:
        return None
    return 100.0 * sum(spent) / run["window_s"]

"""Share of the window, in percent, in the host release: the releases'
``timings["host_decode_s"]`` (the program's ``engine.release`` span)
summed, over the window."""


def read(run):
    spent = [r["timings"]["host_decode_s"] for r in run["releases"]
             if "host_decode_s" in r["timings"]]
    if not spent:
        return None
    return 100.0 * sum(spent) / run["window_s"]

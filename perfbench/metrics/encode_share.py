"""Share of the window, in percent, in host encode: the releases'
``timings["host_encode_s"]`` (the program's ``engine.encode`` span)
summed, over the window."""


def read(run):
    spent = [r["timings"]["host_encode_s"] for r in run["releases"]
             if "host_encode_s" in r["timings"]]
    if not spent:
        return None
    return 100.0 * sum(spent) / run["window_s"]

"""Share of the window, in percent, in moving the rows to the device:
the releases' ``timings["h2d_s"]`` (the program's ``engine.h2d`` span:
the transfer and the on-device padding, until the arrays have landed;
~0 where the device cache holds the columns) summed, over the window."""


def read(run):
    spent = [r["timings"]["h2d_s"] for r in run["releases"]
             if "h2d_s" in r["timings"]]
    if not spent:
        return None
    return 100.0 * sum(spent) / run["window_s"]

"""Share of the window, in percent, from the fused program's dispatch
to the end of the blocking fetch of its outputs: the releases'
``timings["fetch_s"]`` (the program's ``engine.fetch`` span: the wait
for the device program, the compaction and the device-to-host copy)
summed, over the window."""


def read(run):
    spent = [r["timings"]["fetch_s"] for r in run["releases"]
             if "fetch_s" in r["timings"]]
    if not spent:
        return None
    return 100.0 * sum(spent) / run["window_s"]

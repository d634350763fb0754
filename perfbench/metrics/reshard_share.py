"""Share of the window, in percent, in the mesh's host re-shard: the
releases' ``timings["reshard_s"]`` (the program's ``engine.reshard``
span: hash, argsort and per-shard copies of the rows on the host)
summed, over the window."""


def read(run):
    spent = [r["timings"]["reshard_s"] for r in run["releases"]
             if "reshard_s" in r["timings"]]
    if not spent:
        return None
    return 100.0 * sum(spent) / run["window_s"]

"""Share of the releases, in percent, whose quantile walk overflowed the
bottom compaction's static prefix and counted its subtrees with
full-row scatters: 100 x the releases' ``timings["walk_full_scatter"]``
(0 or 1, fetched with the release's outputs) summed, over the releases
that carry it. None where none does."""


def read(run):
    flags = [r["timings"]["walk_full_scatter"] for r in run["releases"]
             if "walk_full_scatter" in r["timings"]]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)

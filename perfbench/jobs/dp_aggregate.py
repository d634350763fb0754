"""One release as users run it: ``DPEngine(NaiveBudgetAccountant,
JaxBackend).aggregate(ArrayDataset, AggregateParams, DataExtractors())``,
then ``compute_budgets()``, then iterating the result. Nothing below
that entry is called.

``traffic["dataset"]``: ``fresh`` builds a new ``ArrayDataset`` over
the same host columns for every release (a one-shot job: encode, the
transfer to the device, the device program, the release); ``resident``
reuses one, whose encoding and device columns the system caches (the
multi-query path).
"""

from jax.profiler import TraceAnnotation

import reference
import synth


def _metric(pdp, name: str):
    if name.startswith("PERCENTILE("):
        return pdp.Metrics.PERCENTILE(float(name[len("PERCENTILE("):-1]))
    return getattr(pdp.Metrics, name)


def aggregate_params(pdp, config: dict):
    g = config["guarantees"]
    return pdp.AggregateParams(
        metrics=[_metric(pdp, m) for m in config["query"]["metrics"]],
        noise_kind=getattr(pdp.NoiseKind, g["noise_kind"]),
        max_partitions_contributed=g["max_partitions_contributed"],
        max_contributions_per_partition=g["max_contributions_per_partition"],
        min_value=g["min_value"], max_value=g["max_value"],
        partition_selection_strategy=getattr(
            pdp.PartitionSelectionStrategy,
            g.get("partition_selection_strategy", "TRUNCATED_GEOMETRIC")))


def release_seed(seed: int, i: int) -> int:
    """The engine's noise seed of release ``i``: distinct per release,
    and inside the 31 bits the engine's PRNG key takes."""
    return (seed * 1_000_003 + i) % (2**31 - 1)


class Job:

    def __init__(self, config: dict, traffic: dict, seed: int, mesh=None):
        if traffic["dataset"] not in ("fresh", "resident"):
            raise ValueError(f"unknown dataset reuse {traffic['dataset']!r}")
        import pipelinedp_tpu as pdp
        from pipelinedp_tpu.backends import JaxBackend
        self._pdp = pdp
        self._backend = JaxBackend
        self._config = config
        self._seed = seed
        self._mesh = mesh
        with TraceAnnotation("bench.synthesize"):
            self._columns = synth.columns(config["dataset"], seed)
        self.rows = len(self._columns[1])
        self._params = aggregate_params(pdp, config)
        self._fields = reference.field_names(config["query"])
        self._resident = (self._dataset()
                          if traffic["dataset"] == "resident" else None)

    def _dataset(self):
        pid, pk, values = self._columns
        with TraceAnnotation("bench.dataset"):
            return self._pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk,
                                          values=values)

    def release(self, i: int):
        """Run release ``i``; returns (its rows, its ``timings``)."""
        pdp = self._pdp
        g = self._config["guarantees"]
        ds = self._resident if self._resident is not None else self._dataset()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=g["release_epsilon"],
                                        total_delta=g["delta"])
        backend = self._backend(mesh=self._mesh,
                                rng_seed=release_seed(self._seed, i))
        with TraceAnnotation("bench.aggregate"):
            result = pdp.DPEngine(acc, backend).aggregate(
                ds, self._params, pdp.DataExtractors())
        with TraceAnnotation("bench.compute_budgets"):
            acc.compute_budgets()
        with TraceAnnotation("bench.iterate"):
            rows = list(result)
        return rows, dict(getattr(result, "timings", None) or {})

    def free(self) -> None:
        """Drop what holds device memory before the reference runs."""
        self._resident = None

    def _exact(self):
        return reference.Exact(*self._columns,
                               self._config["dataset"]["partitions"],
                               self._config["query"])

    def check(self, outputs) -> dict:
        """The worst of each compared number over the releases."""
        ref = self._exact()
        return reference.merge(
            reference.compare(*reference.released_columns(rows, self._fields),
                              ref, self._config) for rows in outputs)

    def control(self) -> dict:
        """The compared numbers of the bfloat16 control in the program's
        place, over the same columns."""
        keys, cols = reference.control_release(*self._columns,
                                               self._config["query"])
        return reference.compare(keys, cols, self._exact(), self._config)


"""Real-serialization proof for the cluster adapters.

Beam/Spark runners pickle the engine's closures to ship them to workers
(reference ``private_beam``/Beam ``CombinePerKey``; SURVEY.md §3.3). The
two-phase budget protocol exists precisely for this: ``MechanismSpec``
objects are mutated in place by ``compute_budgets()`` BEFORE the runner
serializes the graph, so the pickled copies must carry final budgets and
compute identical results on a worker. These tests exercise that pickling
dimension with the stdlib pickler (the structural fakes in
``fake_beam``/``fake_spark`` execute in-process and cannot catch it); the
CI ``cluster-adapters`` job additionally runs the TestRealBeam/Spark
suites on genuine runners.
"""

import operator
import pickle

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import combiners as dp_combiners
from pipelinedp_tpu.ops import noise as noise_ops


def _build_compound(metrics, eps=1e5, delta=1e-2, **kw):
    params = pdp.AggregateParams(
        metrics=metrics, max_partitions_contributed=2,
        max_contributions_per_partition=3, **kw)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    compound = dp_combiners.create_compound_combiner(params, acc)
    return compound, acc


class TestCombinerPickling:

    def test_compound_combiner_round_trips_after_budgets(self):
        # The worker-side object: a compound combiner whose specs were
        # filled in place before serialization. The unpickled copy must
        # produce the same metrics (huge eps -> noise negligible).
        noise_ops.seed_host_rng(0)
        compound, acc = _build_compound(
            [pdp.Metrics.COUNT, pdp.Metrics.MEAN], min_value=0.0,
            max_value=10.0)
        acc.compute_budgets()
        blob = pickle.dumps(compound)
        worker = pickle.loads(blob)
        accumulator = worker.create_accumulator([1.0, 5.0, 9.0])
        merged = worker.merge_accumulators(
            accumulator, worker.create_accumulator([2.0]))
        local = compound.compute_metrics(
            compound.merge_accumulators(
                compound.create_accumulator([1.0, 5.0, 9.0]),
                compound.create_accumulator([2.0])))
        remote = worker.compute_metrics(merged)
        assert remote._fields == local._fields
        for f in remote._fields:
            assert getattr(remote, f) == pytest.approx(
                getattr(local, f), rel=1e-3, abs=0.5)

    def test_spec_values_survive_pickling(self):
        compound, acc = _build_compound([pdp.Metrics.COUNT])
        acc.compute_budgets()
        worker = pickle.loads(pickle.dumps(compound))
        spec = worker._combiners[0]._params.mechanism_spec
        assert spec.eps == pytest.approx(1e5)

    def test_pickle_before_budgets_still_lazy(self):
        # Serializing BEFORE compute_budgets yields a DISCONNECTED copy:
        # in-place mutation cannot reach it. The copy must loudly refuse
        # to compute rather than silently run with no budget — the
        # behavior the two-phase protocol's ordering contract relies on.
        compound, acc = _build_compound([pdp.Metrics.COUNT])
        worker = pickle.loads(pickle.dumps(compound))
        acc.compute_budgets()
        accumulator = worker.create_accumulator([1.0])
        with pytest.raises(AssertionError, match="compute_budgets"):
            worker.compute_metrics(accumulator)

    def test_quantile_combiner_accumulator_round_trips(self):
        # Quantile accumulators serialize the host tree to bytes
        # (reference combiners.py:420-432).
        noise_ops.seed_host_rng(0)
        compound, acc = _build_compound(
            [pdp.Metrics.PERCENTILE(50)], min_value=0.0, max_value=100.0)
        acc.compute_budgets()
        worker = pickle.loads(pickle.dumps(compound))
        accumulator = worker.create_accumulator([10.0, 50.0, 90.0])
        blob = pickle.dumps(accumulator)  # the shuffled payload
        merged = worker.merge_accumulators(pickle.loads(blob),
                                           worker.create_accumulator([50.0]))
        out = worker.compute_metrics(merged)
        assert 0.0 <= out.percentile_50 <= 100.0

    def test_metrics_tuple_round_trips(self):
        # The output rows Beam re-shuffles downstream (custom __reduce__).
        mt = dp_combiners._create_named_tuple_instance(
            "MetricsTuple", ("count", "sum"), (3.0, 7.5))
        back = pickle.loads(pickle.dumps(mt))
        assert back.count == 3.0 and back.sum == 7.5
        assert back == mt


class TestEngineClosurePickling:

    def test_selection_filter_closure_pickles(self):
        # The private-partition-selection filter ships to workers as a
        # functools.partial over module-level functions (reference
        # dp_engine.py:350-357) — it must survive the stdlib pickler.
        from pipelinedp_tpu import dp_engine as engine_mod

        captured = {}

        class CapturingBackend(pdp.LocalBackend):
            def filter(self, col, fn, stage_name=None):
                captured.setdefault("fns", []).append(fn)
                return super().filter(col, fn, stage_name)

        noise_ops.seed_host_rng(0)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1e5,
                                        total_delta=1e-2)
        engine = pdp.DPEngine(acc, CapturingBackend())
        data = [(u, "a", 1.0) for u in range(50)]
        ex = pdp.DataExtractors(
            privacy_id_extractor=operator.itemgetter(0),
            partition_extractor=operator.itemgetter(1),
            value_extractor=operator.itemgetter(2))
        result = engine.aggregate(
            data,
            pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                max_partitions_contributed=1,
                                max_contributions_per_partition=1), ex)
        acc.compute_budgets()
        out = dict(result)
        assert "a" in out
        assert captured["fns"], "selection filter was never constructed"
        for fn in captured["fns"]:
            clone = pickle.loads(pickle.dumps(fn))
            row = ("a", next(iter([(50, ())])))  # (pk, accumulator) shape
            # The clone must behave like the original on the same input.
            sample = ("a", (50, ()))
            assert clone(sample) == fn(sample)

    def test_accountant_itself_not_required_on_workers(self):
        # Workers receive specs, never the accountant; a pickled compound
        # must not drag the whole accountant (and its mechanism registry)
        # into the closure.
        compound, acc = _build_compound([pdp.Metrics.COUNT])
        acc.compute_budgets()
        blob = pickle.dumps(compound)
        import pickletools
        ops = {op.name for op, arg, pos in pickletools.genops(blob)}
        # Sanity: it unpickles standalone with the accountant deleted.
        del acc
        worker = pickle.loads(blob)
        assert worker.metrics_names() == ["count"]

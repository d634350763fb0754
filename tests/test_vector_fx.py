"""Fixed-point vector aggregation + device vector noise (ISSUE 17).

PARITY row 39: under the ``fx`` vector accumulator, VECTOR_SUM's
coordinates quantize against the static norm clip bound into 24-bit
fixed-point int32 lanes and reduce as exact integer sums — so released
vectors are bit-identical across kernel backends (pallas vs xla), on a
single device AND the 8-device mesh, and through the streamed pass-A
path; the wide-D Pallas kernel dispatches on the int32 operand.

PARITY row 40: per-coordinate vector noise draws on device through
``ops/counter_rng.py`` keyed by (partition vocab index, coordinate).
This is a seeded SEAM, not a bit-twin of the numpy reference — the
draw order and generator differ — so the assertions are key-
determinism (same (seed, partition, coordinate) -> same draw on every
release path) plus released-value distribution checks against the
calibrated per-coordinate scale, not bit-parity against numpy.
"""

import operator

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import jax_engine as je
from pipelinedp_tpu import obs
from pipelinedp_tpu import plan as plan_mod
from pipelinedp_tpu.aggregate_params import NoiseKind
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.ops import noise as noise_ops
from pipelinedp_tpu.ops import vector_noise
from pipelinedp_tpu.plan import knobs as knobs_mod

ACC_SPEC = knobs_mod.BY_NAME["vector_accumulator"]
TILE_SPEC = knobs_mod.BY_NAME["segsum_wide_d_block"]

D = 64
PARTS = 5


def extractors():
    return pdp.DataExtractors(privacy_id_extractor=operator.itemgetter(0),
                              partition_extractor=operator.itemgetter(1),
                              value_extractor=operator.itemgetter(2))


def vec_params(d=D, norm=4.0, noise=pdp.NoiseKind.GAUSSIAN):
    return pdp.AggregateParams(
        noise_kind=noise, metrics=[pdp.Metrics.VECTOR_SUM],
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        vector_size=d, vector_max_norm=norm,
        vector_norm_kind=pdp.NormKind.L2)


def make_data(n_users=400, d=D, seed=0):
    rng = np.random.default_rng(seed)
    return [(u, f"p{u % PARTS}", rng.normal(size=d))
            for u in range(n_users)]


def run_vector(data, params, accum, backend="xla", mesh=None,
               chunk=None, seed=7, eps=1e5, public=True):
    """One aggregation under (accumulator, kernel backend, mesh,
    stream chunk); returns {pk: released [D] float64 vector}."""
    import os
    old = os.environ.get("PIPELINEDP_TPU_STREAM_CHUNK")
    if chunk is not None:
        os.environ["PIPELINEDP_TPU_STREAM_CHUNK"] = str(chunk)
    try:
        with plan_mod.seam_override("vector_accumulator", accum), \
             plan_mod.seam_override("kernel_backend", backend):
            noise_ops.seed_host_rng(0)
            kw = {}
            if mesh:
                from pipelinedp_tpu.parallel import make_mesh
                kw["mesh"] = make_mesh(mesh)
            acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                            total_delta=1e-6)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed, **kw))
            pub = ([f"p{i}" for i in range(PARTS)] if public else None)
            res = engine.aggregate(data, params, extractors(),
                                   public_partitions=pub)
            acc.compute_budgets()
            out = dict(res)
    finally:
        if old is None:
            os.environ.pop("PIPELINEDP_TPU_STREAM_CHUNK", None)
        else:
            os.environ["PIPELINEDP_TPU_STREAM_CHUNK"] = old
    return {k: np.asarray(v.vector_sum) for k, v in out.items()}


class TestVectorFxParity:
    """PARITY row 39: one set of released bits for every execution
    geometry of the same fx request."""

    def _assert_same(self, base, other, label):
        assert set(base) == set(other), label
        for k in base:
            np.testing.assert_array_equal(base[k], other[k],
                                          err_msg=f"{label} pk={k}")

    def test_pallas_bit_identical_and_dispatches(self):
        data = make_data()
        params = vec_params()
        base = run_vector(data, params, "fx", "xla")
        obs.reset()
        pal = run_vector(data, params, "fx", "pallas")
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("kernel.pallas_dispatches", 0) >= 1
        self._assert_same(base, pal, "pallas")

    def test_mesh_bit_identical_both_backends(self):
        data = make_data()
        params = vec_params()
        base = run_vector(data, params, "fx", "xla")
        self._assert_same(base, run_vector(data, params, "fx", "xla",
                                           mesh=8), "mesh/xla")
        self._assert_same(base, run_vector(data, params, "fx", "pallas",
                                           mesh=8), "mesh/pallas")

    def test_streamed_bit_identical_both_backends(self):
        data = make_data()
        params = vec_params()
        base = run_vector(data, params, "fx", "xla")
        self._assert_same(base, run_vector(data, params, "fx", "xla",
                                           chunk=50), "stream/xla")
        self._assert_same(base, run_vector(data, params, "fx", "pallas",
                                           chunk=50), "stream/pallas")

    def test_private_selection_paths_agree(self):
        """The compact release path (private selection keeps a subset
        of rows) must key vector noise by the GLOBAL vocab index, so
        pallas/xla stay bit-identical there too."""
        data = make_data(n_users=800)
        params = vec_params()
        base = run_vector(data, params, "fx", "xla", public=False,
                          eps=50.0)
        assert base  # selection keeps a non-empty set
        pal = run_vector(data, params, "fx", "pallas", public=False,
                         eps=50.0)
        self._assert_same(base, pal, "private/pallas")

    def test_fx_tracks_f32_within_quantization_error(self):
        """The accumulators are different mechanisms (fx clamps each
        coordinate at +-bound while quantizing), but on data inside
        the bound they agree to quantization error — the retired
        'Scaling limits' caveat's replacement property."""
        rng = np.random.default_rng(3)
        data = [(u, f"p{u % PARTS}", rng.uniform(-0.3, 0.3, D))
                for u in range(400)]
        params = vec_params()
        f32 = run_vector(data, params, "f32", "xla")
        fx = run_vector(data, params, "fx", "xla")
        for k in f32:
            np.testing.assert_allclose(fx[k], f32[k], atol=1e-3)

    def test_laplace_noise_kind_also_bit_identical(self):
        data = make_data(n_users=200)
        params = vec_params(noise=pdp.NoiseKind.LAPLACE)
        base = run_vector(data, params, "fx", "xla")
        pal = run_vector(data, params, "fx", "pallas")
        self._assert_same(base, pal, "laplace/pallas")


class TestVectorKnobs:
    """The two ISSUE-17 knobs ride the registry like every other."""

    def test_vector_accumulator_is_dp_unsafe(self):
        assert not ACC_SPEC.dp_safe
        assert ACC_SPEC.kind is str
        assert ACC_SPEC.default == "f32"
        assert ACC_SPEC.choices == ("f32", "fx")
        assert ACC_SPEC.env_var == "PIPELINEDP_TPU_VECTOR_ACCUMULATOR"

    def test_env_flips_the_accumulator(self, monkeypatch):
        monkeypatch.setenv(ACC_SPEC.env_var, "fx")
        assert knobs_mod.resolve_value(ACC_SPEC) == ("fx", "env")

    def test_wide_d_block_is_dp_safe_int(self):
        assert TILE_SPEC.dp_safe
        assert TILE_SPEC.kind is int
        assert TILE_SPEC.default == 0
        assert TILE_SPEC.env_var == "PIPELINEDP_TPU_SEGSUM_WIDE_D_BLOCK"

    def test_config_resolves_accumulator_only_for_vector_requests(self):
        with plan_mod.seam_override("vector_accumulator", "fx"):
            scalar = je.FusedConfig.from_params(
                pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                    max_partitions_contributed=1,
                                    max_contributions_per_partition=1),
                public=True)
            vector = je.FusedConfig.from_params(vec_params(), public=True)
        # Scalar configs stay byte-identical to the pre-ISSUE shape —
        # the knob never perturbs their compile cache keys.
        assert scalar.vector_accumulator == "f32"
        assert scalar.wide_d_block == 0
        assert vector.vector_accumulator == "fx"


class TestDeviceVectorNoise:
    """PARITY row 40: the seeded vector-noise seam."""

    def test_draws_keyed_by_content_not_position(self):
        """Row i's noise depends on pk_index[i], not i: a compact
        release (kept subset) draws exactly the rows the full release
        would — the property every execution geometry stands on."""
        full = vector_noise.unit_noise_block(
            NoiseKind.GAUSSIAN, 5, np.arange(10), 16)
        sub = vector_noise.unit_noise_block(
            NoiseKind.GAUSSIAN, 5, np.array([3, 7]), 16)
        np.testing.assert_array_equal(sub, full[[3, 7]])

    def test_streams_are_label_separated(self):
        """The vector stream (0x7ec) must not collide with the raw
        engine key or the quantile-tree stream — same seed, different
        draws per kind as well (laplace and gaussian transform the
        same counters differently)."""
        g = vector_noise.unit_noise_block(NoiseKind.GAUSSIAN, 5,
                                          np.arange(8), 8)
        l = vector_noise.unit_noise_block(NoiseKind.LAPLACE, 5,
                                          np.arange(8), 8)
        assert np.abs(g - l).max() > 1e-6

    def test_seeds_decorrelate(self):
        a = vector_noise.unit_noise_block(NoiseKind.GAUSSIAN, 0,
                                          np.arange(64), 64)
        b = vector_noise.unit_noise_block(NoiseKind.GAUSSIAN, 1,
                                          np.arange(64), 64)
        assert np.abs(a - b).max() > 1e-3
        corr = np.corrcoef(a.ravel(), b.ravel())[0, 1]
        assert abs(corr) < 0.05

    def test_gaussian_unit_distribution(self):
        block = vector_noise.unit_noise_block(
            NoiseKind.GAUSSIAN, 11, np.arange(400), 256)
        draws = block.ravel()  # 102,400 draws
        assert abs(draws.mean()) < 0.02
        assert abs(draws.std() - 1.0) < 0.02
        # Tail sanity: a gaussian, not something bounded.
        assert (np.abs(draws) > 3).mean() == pytest.approx(0.0027,
                                                           abs=0.0015)

    def test_laplace_unit_distribution(self):
        block = vector_noise.unit_noise_block(
            NoiseKind.LAPLACE, 12, np.arange(400), 256)
        draws = block.ravel()
        assert abs(draws.mean()) < 0.02
        # Unit-scale Laplace: variance 2.
        assert draws.std() == pytest.approx(np.sqrt(2.0), abs=0.05)

    def test_released_noise_matches_calibrated_sigma(self):
        """End to end: empty public partitions release pure noise, so
        their released vectors sample the calibrated per-coordinate
        gaussian directly — mean 0, std gaussian_sigma(eps/D, delta/D,
        l2_sens)."""
        eps, delta, d = 2.0, 1e-6, 32
        params = vec_params(d=d)
        data = [(u, "live", np.ones(d) * 0.01) for u in range(20)]
        noise_ops.seed_host_rng(0)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                        total_delta=delta)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=3))
        public = ["live"] + [f"empty{i}" for i in range(300)]
        res = engine.aggregate(data, params, extractors(),
                               public_partitions=public)
        acc.compute_budgets()
        out = dict(res)
        draws = np.concatenate(
            [np.asarray(out[k].vector_sum) for k in public[1:]])
        sigma = noise_ops.gaussian_sigma(
            eps / d, delta / d,
            noise_ops.compute_l2_sensitivity(
                params.max_partitions_contributed,
                params.max_contributions_per_partition))
        assert draws.shape == (300 * d,)
        assert abs(draws.mean()) < 0.1 * sigma
        assert draws.std() == pytest.approx(sigma, rel=0.05)

    def test_released_laplace_noise_matches_calibrated_scale(self):
        eps, d = 2.0, 32
        params = vec_params(d=d, noise=pdp.NoiseKind.LAPLACE)
        data = [(u, "live", np.ones(d) * 0.01) for u in range(20)]
        noise_ops.seed_host_rng(0)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=4))
        public = ["live"] + [f"empty{i}" for i in range(300)]
        res = engine.aggregate(data, params, extractors(),
                               public_partitions=public)
        acc.compute_budgets()
        out = dict(res)
        draws = np.concatenate(
            [np.asarray(out[k].vector_sum) for k in public[1:]])
        scale = noise_ops.laplace_scale(
            eps / d,
            noise_ops.compute_l1_sensitivity(
                params.max_partitions_contributed,
                params.max_contributions_per_partition))
        assert draws.std() == pytest.approx(scale * np.sqrt(2.0),
                                            rel=0.05)

    def test_release_deterministic_in_engine_seed(self):
        data = make_data(n_users=100)
        params = vec_params()
        a = run_vector(data, params, "fx", seed=21, eps=2.0)
        b = run_vector(data, params, "fx", seed=21, eps=2.0)
        c = run_vector(data, params, "fx", seed=22, eps=2.0)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
        assert any(np.abs(a[k] - c[k]).max() > 1e-9 for k in a)

    def test_secure_host_noise_keeps_the_numpy_path(self, monkeypatch):
        """The hardened release never enters the device seam: with
        secure host noise on (and no explicit rng — the same
        ``secure and rng is None`` convention as the scalar
        mechanisms), VECTOR_SUM still flows through
        dp_computations.add_noise_vector."""
        from pipelinedp_tpu import dp_computations
        calls = []

        def spy(vec, params, rng):
            calls.append(np.shape(vec))
            return np.asarray(vec, dtype=np.float64)

        monkeypatch.setattr(dp_computations, "add_noise_vector", spy)
        monkeypatch.setattr(noise_ops, "_secure_host_noise", True)
        data = make_data(n_users=50)
        run_vector(data, vec_params(), "f32", seed=None)
        assert calls

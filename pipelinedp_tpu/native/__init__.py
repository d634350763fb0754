"""Native host library: hardened noise for DP releases.

The on-device (TPU) path draws noise with ``jax.random`` — statistically
correct, and safe for the aggregate pipelines this framework targets, but
a textbook floating-point Laplace leaks information through the noise
sample's low-order bits (Mironov, CCS 2012). The reference delegates its
host noise to the C++ google/differential-privacy library, which hardens
against this; this package is the TPU framework's native twin:

* ``snapping_laplace(values, scale, bound)`` — Mironov's snapping
  mechanism over a ChaCha20 CSPRNG,
* ``discrete_laplace(counts, scale)`` — exact two-sided geometric noise
  for integer releases (no float noise bits at all),
* ``discrete_gaussian(counts, sigma)`` — discrete-Gaussian noise
  (Canonne–Kamath–Steinke sampler) for integer releases; the support is
  exactly the integers, and the acceptance probabilities are realized
  to 2^-53 (double-precision Bernoulli coins) rather than CKS's exact
  rationals — a deviation below any expressible (eps, delta),
* ``secure_gaussian(values, sigma, bound)`` — granularity-snapped
  discrete-Gaussian release for real values (the Gaussian twin of the
  snapping mechanism),
* ``seed(n)`` / ``seed_from_os()`` — deterministic seeding for tests,
  OS entropy otherwise.

The C++ source (``secure_noise.cc``) is compiled on first use with the
toolchain's ``g++`` into a cached shared library next to this file (or
``$PIPELINEDP_TPU_NATIVE_CACHE``), keyed by a hash of its source.
Environments without a compiler get ``NativeUnavailableError`` and
callers fall back to the NumPy path — ``ops/noise.py`` documents the
resulting security posture.

Two host hot paths have libraries of their own, built the same way:
``factorize_i64`` (``encode.cc``, the ingest encoder) and
``shard_rows`` (``shard.cc``, the mesh path's re-shard of rows by
privacy id).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

import numpy as np

_LOCK = threading.Lock()


class NativeUnavailableError(RuntimeError):
    """The native library could not be built/loaded on this host."""


def _cache_dir() -> str:
    override = os.environ.get("PIPELINEDP_TPU_NATIVE_CACHE")
    if override:
        os.makedirs(override, exist_ok=True)
        return override
    d = os.path.dirname(__file__)
    return d if os.access(d, os.W_OK) else tempfile.gettempdir()


def _build_shared_lib(src: str, out_name: str) -> str:
    """Compile ``src`` into the cache dir on first use. The library's
    file name carries a hash of the source and of the build command, so
    a library is only ever loaded for the exact ``.cc`` it was built
    from — never one built from another checkout and copied along."""
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src]
    with open(src, "rb") as fh:
        digest = hashlib.sha256(fh.read() + " ".join(cmd[:-1]).encode())
    stem, ext = os.path.splitext(out_name)
    out = os.path.join(_cache_dir(),
                       f"{stem}-{digest.hexdigest()[:16]}{ext}")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp{os.getpid()}"
    proc = subprocess.run(cmd + ["-o", tmp], capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise NativeUnavailableError(
            f"g++ failed building {os.path.basename(src)}: "
            f"{proc.stderr[-500:]}")
    os.replace(tmp, out)  # atomic: concurrent builders race harmlessly
    return out


class _Loader:
    """Builds and loads one ``.cc`` of this package on first call, then
    returns the same library; a failed build is remembered and raised
    again as ``NativeUnavailableError``. ``declare`` sets the
    functions' ctypes signatures."""

    def __init__(self, src_name: str, lib_name: str, declare):
        self._src = os.path.join(os.path.dirname(__file__), src_name)
        self._lib_name = lib_name
        self._declare = declare
        self._lib: Optional[ctypes.CDLL] = None
        self._error: Optional[str] = None

    @property
    def loaded(self) -> bool:
        """True iff the library is loaded; never triggers a build."""
        return self._lib is not None

    def __call__(self) -> ctypes.CDLL:
        if self._lib is not None:
            return self._lib
        with _LOCK:
            if self._error is not None:
                raise NativeUnavailableError(self._error)
            if self._lib is None:
                try:
                    lib = ctypes.CDLL(_build_shared_lib(self._src,
                                                        self._lib_name))
                except (OSError, NativeUnavailableError) as e:
                    self._error = str(e)
                    raise NativeUnavailableError(self._error) from e
                self._declare(lib)
                self._lib = lib
            return self._lib


def _declare_noise(lib: ctypes.CDLL) -> None:
    lib.sn_seed.argtypes = [ctypes.c_uint64]
    lib.sn_seed_from_os.argtypes = []
    lib.sn_snapping_laplace.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64, ctypes.c_double, ctypes.c_double]
    lib.sn_snapping_laplace.restype = ctypes.c_double
    lib.sn_uniform.argtypes = [ctypes.POINTER(ctypes.c_double),
                               ctypes.c_int64]
    lib.sn_discrete_laplace.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_double]
    lib.sn_discrete_gaussian.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_double]
    lib.sn_discrete_gaussian.restype = ctypes.c_int32
    lib.sn_secure_gaussian.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64, ctypes.c_double, ctypes.c_double]
    lib.sn_secure_gaussian.restype = ctypes.c_double


_lib = _Loader("secure_noise.cc", "_secure_noise.so", _declare_noise)


def available() -> bool:
    """True when the native library can be (or was) built and loaded.
    May spawn a g++ build on first call — use :func:`is_loaded` for a
    side-effect-free check."""
    try:
        _lib()
        return True
    except NativeUnavailableError:
        return False


def is_loaded() -> bool:
    """True iff the library is already loaded in this process. Never
    triggers a build."""
    return _lib.loaded


def seed(n: int) -> None:
    """Deterministic CSPRNG seeding — tests only."""
    _lib().sn_seed(ctypes.c_uint64(n & (2**64 - 1)))


def seed_from_os() -> None:
    """Re-key from OS entropy (e.g. after fork)."""
    _lib().sn_seed_from_os()


def snapping_laplace(values, scale: float,
                     bound: Optional[float] = None) -> np.ndarray:
    """Snapping-Laplace release of ``values`` with noise scale ``scale``.

    Returns values + Laplace(scale) noise, rounded to the snapping
    resolution Lambda (smallest power of two >= scale) and clamped to
    [-bound, bound]. The default bound is 2^46 * max(Lambda, 1): Mironov's
    analysis wants B/Lambda bounded (the clamp is part of the mechanism),
    and the max(..., 1) floor keeps small noise scales from shrinking the
    representable release range below realistic aggregates. Callers whose
    releases can legitimately exceed ~7e13 must pass an explicit bound;
    inputs that the clamp actually truncates raise a UserWarning.
    """
    if scale <= 0:
        raise ValueError("scale must be positive")
    vals = np.asarray(values, dtype=np.float64)
    # ascontiguousarray promotes 0-d to 1-d: keep the true shape.
    shape = vals.shape
    flat = np.ascontiguousarray(vals).ravel()
    out = np.empty_like(flat)
    if bound is None:
        lam = 2.0**np.ceil(np.log2(scale))
        bound = float(max(lam, 1.0) * 2.0**46)
    if flat.size and float(np.max(np.abs(flat))) > bound:
        import warnings
        warnings.warn(
            "snapping_laplace: input magnitude exceeds the clamp bound "
            f"({bound:.3g}); the release is clamped. Pass an explicit "
            "bound sized to the query range.", UserWarning)
    _lib().sn_snapping_laplace(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        flat.size, float(scale), float(bound))
    return out.reshape(shape)


def discrete_laplace(counts, scale: float) -> np.ndarray:
    """Integer release: counts + two-sided-geometric noise of scale
    ``scale`` (decay exp(-1/scale)) — no floating-point noise bits."""
    if scale <= 0:
        raise ValueError("scale must be positive")
    vals = np.asarray(counts, dtype=np.int64)
    shape = vals.shape
    flat = np.ascontiguousarray(vals).ravel()
    out = np.empty_like(flat)
    _lib().sn_discrete_laplace(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        flat.size, float(scale))
    return out.reshape(shape)


def discrete_gaussian(counts, sigma: float) -> np.ndarray:
    """Integer release: counts + discrete-Gaussian noise of standard
    deviation ~``sigma`` (Canonne–Kamath–Steinke sampler) — no
    floating-point noise bits in the RELEASE (the support is exactly
    the integers). The sampler's acceptance coins are double-precision
    Bernoullis, so acceptance probabilities are realized to 2^-53
    rather than CKS's exact rationals (see ``secure_noise.cc``) — the
    distributional deviation is negligible for any expressible
    (eps, delta). ``sigma`` must be in (0, 2^40)."""
    if not 0 < sigma < 2.0**40:
        raise ValueError("sigma must be in (0, 2^40)")
    vals = np.asarray(counts, dtype=np.int64)
    shape = vals.shape
    flat = np.ascontiguousarray(vals).ravel()
    out = np.empty_like(flat)
    rc = _lib().sn_discrete_gaussian(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        flat.size, float(sigma))
    if rc != 0:
        raise ValueError(f"sn_discrete_gaussian rejected sigma={sigma}")
    return out.reshape(shape)


def secure_gaussian(values, sigma: float,
                    bound: Optional[float] = None) -> np.ndarray:
    """Hardened Gaussian release of ``values`` with noise std ``sigma``:
    the value is snapped to a power-of-two granularity g (sized so
    sigma/g is in (2^39, 2^40]) and g-scaled discrete-Gaussian noise
    (integer-supported; acceptance coins realized to 2^-53 — see
    :func:`discrete_gaussian`) is added, so the release's support is
    the g-grid — the
    Gaussian twin of :func:`snapping_laplace`, replacing the reference's
    PyDP secure GaussianMechanism (reference
    ``pipeline_dp/dp_computations.py:127-143``). Same default clamp
    bound policy as the snapping mechanism."""
    if sigma <= 0:
        raise ValueError("sigma must be positive")
    vals = np.asarray(values, dtype=np.float64)
    shape = vals.shape
    flat = np.ascontiguousarray(vals).ravel()
    out = np.empty_like(flat)
    if bound is None:
        lam = 2.0**np.ceil(np.log2(sigma))
        bound = float(max(lam, 1.0) * 2.0**46)
    if flat.size and float(np.max(np.abs(flat))) > bound:
        import warnings
        warnings.warn(
            "secure_gaussian: input magnitude exceeds the clamp bound "
            f"({bound:.3g}); the release is clamped. Pass an explicit "
            "bound sized to the query range.", UserWarning)
    g = _lib().sn_secure_gaussian(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        flat.size, float(sigma), float(bound))
    if g <= 0:
        raise ValueError(f"sn_secure_gaussian rejected sigma={sigma}")
    return out.reshape(shape)


def uniform(n: int) -> np.ndarray:
    """Raw uniforms in (0, 1] from the CSPRNG — for statistical tests."""
    out = np.empty(n, dtype=np.float64)
    _lib().sn_uniform(out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                      n)
    return out


# ---------------------------------------------------------------------------
# Ingest acceleration: hash-based integer factorization (encode.cc)
# ---------------------------------------------------------------------------

def _declare_encode(lib: ctypes.CDLL) -> None:
    lib.pdp_factorize_i64.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64)]
    lib.pdp_factorize_i64.restype = ctypes.c_int64


_enc_lib = _Loader("encode.cc", "_encode.so", _declare_encode)


def encode_available() -> bool:
    """True when the native factorizer can be (or was) built and loaded."""
    try:
        _enc_lib()
        return True
    except NativeUnavailableError:
        return False


def factorize_i64(arr: np.ndarray):
    """``np.unique(arr, return_inverse=True)`` for integer arrays, via a
    grow-as-needed open-addressing hash: O(N + U log U) instead of the
    full O(N log N) sort — the ingest hot path when the vocabulary is
    (much) smaller than the data, which keyed DP datasets are. When an
    early sample finds mostly-distinct keys the C++ side bails and this
    falls back to np.unique, which wins that regime. Returns
    (sorted uniques int64, inverse int32); bit-identical to np.unique."""
    arr = np.asarray(arr)
    if (arr.dtype.kind == "u" and arr.size and
            int(arr.max()) > np.iinfo(np.int64).max):
        raise ValueError(
            "factorize_i64: uint64 values above int64 max would wrap; "
            "use np.unique for this input")
    flat = np.ascontiguousarray(arr, dtype=np.int64).ravel()
    n = flat.size
    inverse = np.empty(n, dtype=np.int32)
    uniq = np.empty(n, dtype=np.int64)
    if n == 0:
        return uniq[:0], inverse
    u = _enc_lib().pdp_factorize_i64(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        inverse.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        uniq.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    if u == -2:  # mostly-distinct sample: sort-based wins
        nu, ni = np.unique(flat, return_inverse=True)
        return nu, ni.astype(np.int32)
    if u < 0:
        raise NativeUnavailableError(
            "pdp_factorize_i64 failed (allocation or id overflow)")
    return uniq[:u].copy(), inverse


# ---------------------------------------------------------------------------
# Mesh re-shard: stable counting scatter of rows by shard (shard.cc)
# ---------------------------------------------------------------------------

#: Shard ids are one byte on the native side.
MAX_SHARDS = 256


def _declare_shard(lib: ctypes.CDLL) -> None:
    lib.pdp_shard_assign.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p]
    lib.pdp_shard_assign.restype = ctypes.c_int32
    lib.pdp_shard_scatter.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_void_p]
    lib.pdp_shard_scatter.restype = None


_shard_lib = _Loader("shard.cc", "_shard.so", _declare_shard)


def shard_rows(pid: np.ndarray, columns, n_dev: int, pad_rows):
    """Groups rows by shard ``fmix32(pid) % n_dev`` and pads each shard
    to one length, in O(n): one pass hashes and counts, one pass copies
    each row of every column to the next free row of its shard.

    Returns ``(sharded, counts, per_shard)``. ``per_shard`` is
    ``pad_rows(counts.max())`` (``pad_rows(1)`` with no rows);
    ``sharded[k]`` is ``columns[k]`` laid out as ``n_dev`` blocks of
    ``per_shard`` rows, block ``d`` holding shard ``d``'s rows in input
    order (what a stable argsort of the shard ids gives) and then zeros
    (``False`` for bool columns). ``pid`` is read as uint32, as
    ``fmix32(pid.astype(np.uint32))`` reads it. Columns may have any
    trailing shape. Raises ``NativeUnavailableError`` when the library
    cannot be built or ``n_dev`` is outside ``[1, MAX_SHARDS]``."""
    if not 1 <= n_dev <= MAX_SHARDS:
        raise NativeUnavailableError(
            f"shard ids are one byte: {n_dev} shards is outside "
            f"[1, {MAX_SHARDS}]")
    lib = _shard_lib()
    pid = np.asarray(pid)
    if pid.ndim != 1:
        raise ValueError(f"shard_rows: pid must be 1-D, not {pid.shape}")
    n = pid.shape[0]
    if pid.dtype.kind in "iu" and pid.dtype.itemsize == 4:
        keys = np.ascontiguousarray(pid).view(np.uint32)
    else:
        keys = np.ascontiguousarray(pid.astype(np.uint32))
    shard = np.empty(n, np.uint8)
    counts = np.zeros(n_dev, np.int64)
    lib.pdp_shard_assign(keys.ctypes.data, n, n_dev, shard.ctypes.data,
                         counts.ctypes.data)
    per_shard = pad_rows(int(counts.max()) if n else 1)
    start = np.arange(n_dev, dtype=np.int64) * per_shard
    columns = [np.ascontiguousarray(col) for col in columns]
    for col in columns:
        if col.shape[0] != n or col.dtype.hasobject:
            raise ValueError(
                f"shard_rows: a column of shape {col.shape} and dtype "
                f"{col.dtype} does not hold {n} plain rows")
    sharded = [np.empty((n_dev * per_shard,) + col.shape[1:], col.dtype)
               for col in columns]
    k = len(columns)
    width = np.array([col.itemsize * int(np.prod(col.shape[1:]))
                      for col in columns], np.int64)
    lib.pdp_shard_scatter(
        (ctypes.c_void_p * k)(*[col.ctypes.data for col in columns]),
        (ctypes.c_void_p * k)(*[out.ctypes.data for out in sharded]),
        width.ctypes.data, k, n, shard.ctypes.data, n_dev,
        start.ctypes.data)
    for out in sharded:
        for d in range(n_dev):
            out[start[d] + counts[d]:start[d] + per_shard] = 0
    return sharded, counts, per_shard

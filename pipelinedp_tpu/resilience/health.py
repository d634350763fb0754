"""Device-health probing with timeout and bounded retry, plus the
library's opt-in, flagged degradation to CPU.

The failure mode this guards is a *wedged* accelerator runtime:
``jax.devices()`` blocks forever inside backend init, so a process that
has not initialized a backend yet probes in a subprocess it can kill.
A process that already holds its backend — it took its devices
through :func:`hold_backend`, as ``JaxBackend`` and the entry points
do, or probed in-process before — probes in-process: the chip belongs
to one process at a time, so a child could never get it.

:func:`probe_with_retry` only reports. :func:`ensure_device_or_degrade`
(``JaxBackend(health_policy=...)``) and :func:`resilient_make_mesh` are
the explicit, flagged library API that falls back to CPU on exhaustion;
results produced in that mode carry ``degraded=True``. The entry points
(``bench.py``, ``chip_smoke.py``) never degrade: without a healthy TPU
they exit nonzero.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import threading
from typing import List, Optional

from pipelinedp_tpu.resilience import faults
from pipelinedp_tpu.resilience.clock import Clock, SystemClock
from pipelinedp_tpu.resilience.retry import (RetriesExhausted, RetryPolicy,
                                             call_with_retry)

#: Per-attempt probe timeout.
PROBE_TIMEOUT_ENV = "PIPELINEDP_TPU_PROBE_TIMEOUT"
DEFAULT_PROBE_TIMEOUT_S = 300.0

#: Poll beat while waiting on the probe subprocess: each beat checks
#: the watchdog-cancel event, so a stalled probe dies at the stall
#: deadline instead of the full timeout.
_PROBE_POLL_S = 0.25

#: Set by :func:`cancel_active_probe` (the obs monitor's stall action):
#: the in-flight probe attempt is killed and reported as cancelled.
#: Cleared at the start of every probe attempt.
_PROBE_CANCEL = threading.Event()


def cancel_active_probe() -> None:
    """Abort the in-flight device probe attempt, if any. This is the
    stall watchdog's hook (bench wires it as its ``on_stall`` action):
    a probe that has emitted no span activity past the stall deadline
    is almost certainly a wedged runtime — kill it NOW, emit the flight
    record, and let the retry machinery take over, instead of sitting
    silently through the remaining minutes of probe timeout."""
    _PROBE_CANCEL.set()

#: Set (alongside ``JAX_PLATFORMS=cpu``) when degradation steered this
#: process to CPU. It keeps the fallback HONEST process-wide: every
#: later ``JaxBackend`` reports ``degraded=True`` (the platform override
#: outlives the backend that triggered it), and the next probe strips
#: the override so it tests the REAL accelerator — a recovered device
#: clears both vars instead of reporting a vacuous CPU "healthy".
DEGRADED_ENV = "PIPELINEDP_TPU_DEGRADED"

DEFAULT_HEALTH_POLICY = RetryPolicy(max_attempts=3, base_delay_s=2.0,
                                    multiplier=2.0, max_delay_s=60.0,
                                    jitter=0.1, seed=0)


@dataclasses.dataclass
class HealthReport:
    """Outcome of a probe-with-retry (or mesh/init recovery) sequence."""

    healthy: bool
    #: True when execution fell back to CPU — NEVER silently: callers
    #: must propagate this flag into their results.
    degraded: bool
    attempts: int
    #: the backoff delays actually slept (the honored schedule).
    backoff_s: List[float]
    detail: str = ""


def probe_timeout_s() -> float:
    return float(os.environ.get(PROBE_TIMEOUT_ENV,
                                DEFAULT_PROBE_TIMEOUT_S))


#: Whether this process took its jax backend through the library
#: (:func:`hold_backend` or an in-process probe). JAX has no public
#: query for it, so the library keeps its own.
_backend_held = False


def hold_backend():
    """This process's jax devices (``jax.devices()`` initializes the
    backend, and on a TPU takes the chip). From here on every probe of
    this process runs in-process. Returns the device list."""
    global _backend_held
    import jax
    devices = jax.devices()
    _backend_held = True
    return devices


def probe_devices(timeout_s: Optional[float] = None,
                  clock: Optional[Clock] = None):
    """One device probe. Before this process has a backend, run
    ``jax.devices()`` in a killable subprocess (a wedged runtime blocks
    *inside* backend init — an in-process call could never time out);
    the wait polls in short beats so the stall watchdog's
    :func:`cancel_active_probe` can cut a wedged probe short at the
    stall deadline instead of the full timeout. A process that already
    holds its backend (:func:`hold_backend`) probes in-process. Returns
    ``(ok, detail)``."""
    timeout_s = probe_timeout_s() if timeout_s is None else timeout_s
    clock = clock or SystemClock()
    _PROBE_CANCEL.clear()
    if faults.wedged("device.probe"):
        plan = faults.active()
        if plan is not None and plan.wedged_hold:
            # The REAL blocked window, on the injectable clock: burn
            # the probe timeout in cancellable beats so the watchdog
            # path is exercised end to end (a FakeClock burns it in
            # zero wall time; the bench e2e uses a small real timeout).
            step = min(0.05, timeout_s) if timeout_s > 0 else 0.0
            waited = 0.0
            while waited < timeout_s and step > 0:
                if _PROBE_CANCEL.is_set():
                    return False, (
                        "device probe cancelled by the stall watchdog "
                        f"after {waited:.1f}s (injected wedge)")
                clock.sleep(step)
                waited += step
        return False, (f"device probe did not return within {timeout_s:g}s"
                       " (injected wedge)")
    if _backend_held:
        try:
            hold_backend()
        except RuntimeError as e:
            return False, f"{type(e).__name__}: {e}"[-300:]
        return True, "ok"
    probe_env = dict(os.environ)
    if probe_env.get(DEGRADED_ENV):
        # A prior degradation forced JAX_PLATFORMS=cpu; the probe must
        # test the real accelerator, not vacuously succeed on the
        # fallback it itself installed.
        probe_env.pop("JAX_PLATFORMS", None)
    try:
        # stderr goes to a temp FILE, not a pipe: nobody drains a pipe
        # during the poll loop, so a chatty child (verbose TPU/grpc
        # init logging) would fill the OS buffer, block on write, and
        # read as a wedge. A file has no such backpressure.
        import tempfile
        with tempfile.TemporaryFile() as errf:
            proc = subprocess.Popen(
                [sys.executable, "-c", "import jax; jax.devices()"],
                stdout=subprocess.DEVNULL, stderr=errf, env=probe_env)
            waited = 0.0
            while True:
                try:
                    proc.wait(timeout=_PROBE_POLL_S)
                    break
                except subprocess.TimeoutExpired:
                    waited += _PROBE_POLL_S
                    cancelled = _PROBE_CANCEL.is_set()
                    if cancelled or waited >= timeout_s:
                        proc.kill()
                        proc.wait()
                        if cancelled:
                            return False, (
                                "device probe cancelled by the stall "
                                f"watchdog after {waited:.1f}s (wedged "
                                "runtime?)")
                        return False, (f"device probe did not return "
                                       f"within {timeout_s:g}s")
            errf.seek(0)
            err = errf.read().decode("utf-8", errors="replace")
        if proc.returncode == 0:
            return True, "ok"
        return False, err[-300:]
    except OSError as e:
        return False, f"{type(e).__name__}: {e}"


class _ProbeFailed(Exception):
    """One probe attempt failed; ``str()`` is the probe detail."""


# --- mesh supervision (elastic multi-process recovery) ----------------

#: Directory of per-process liveness beat files. Set (by the harness
#: that launches the workers) to arm :func:`supervisor_from_env`; unset,
#: multi-process streaming runs exactly as before — a lost peer wedges
#: the collective until an outer deadline kills the run.
MESH_DIR_ENV = "PIPELINEDP_TPU_MESH_DIR"
#: Seconds a peer's beat may lag before the supervisor declares it lost
#: (only consulted while its pid is still alive — a dead pid is an
#: immediate loss verdict).
MESH_STALL_ENV = "PIPELINEDP_TPU_MESH_STALL_S"
DEFAULT_MESH_STALL_S = 60.0

#: Poll beat while waiting on peers (rides the injectable clock).
_MESH_POLL_S = 0.02


class MeshParticipantLost(Exception):
    """A mesh peer process died (or silently stalled) mid-stream. The
    elastic wrapper in ``streaming.py`` treats this like an injected
    :class:`faults.DeviceLost`: re-form the mesh from the survivors and
    resume from the last checkpoint."""

    def __init__(self, msg: str, process_id: int = -1, beat: int = -1,
                 reason: str = ""):
        super().__init__(msg)
        self.process_id = int(process_id)
        self.beat = int(beat)
        self.reason = reason


class MeshSupervisor:
    """File-based liveness rendezvous for a multi-process mesh.

    Every participant writes an atomic member file
    ``mesh-<process_id>.json`` = ``{"process_id", "pid", "beat"}`` into
    the shared :data:`MESH_DIR_ENV` directory, bumping ``beat`` ONCE
    per collective dispatch (``gate()``), IMMEDIATELY BEFORE enqueueing
    the collective. Before dispatching, each participant waits until
    every peer has reached the same beat — so a peer that died at
    dispatch ``n`` is detected by the survivors AT dispatch ``n``,
    before they enqueue the collective that would wedge on it:

    * peer pid no longer alive -> :class:`MeshParticipantLost` NOW;
    * peer beat stalled past the stall deadline -> the same, with
      ``reason="stalled"`` (heartbeat silence, not a clean death).

    The wait polls on the injectable clock (never ``time.sleep``), so
    chaos tests drive the stall verdict on a ``FakeClock``. The beat
    counter is GLOBAL and monotonic per process — pass A and pass B
    share it, matching the forced-serial dispatch order every process
    replays identically."""

    def __init__(self, mesh_dir: str, process_id: int, n_processes: int,
                 stall_s: Optional[float] = None,
                 clock: Optional[Clock] = None):
        from pipelinedp_tpu.resilience import checkpoint as ckpt_mod
        self._ckpt_mod = ckpt_mod
        self.mesh_dir = str(mesh_dir)
        self.process_id = int(process_id)
        self.n_processes = int(n_processes)
        self.stall_s = (float(os.environ.get(MESH_STALL_ENV,
                                             DEFAULT_MESH_STALL_S))
                        if stall_s is None else float(stall_s))
        self.clock = clock or SystemClock()
        self.beat = 0
        self.state = "forming"
        os.makedirs(self.mesh_dir, exist_ok=True)
        self._write()
        self.state = "formed"

    def _member_path(self, process_id: int) -> str:
        return os.path.join(self.mesh_dir, f"mesh-{process_id}.json")

    def _write(self) -> None:
        self._ckpt_mod.atomic_write_json(
            self._member_path(self.process_id),
            {"process_id": self.process_id, "pid": os.getpid(),
             "beat": self.beat})

    @staticmethod
    def _pid_alive(pid: int) -> bool:
        try:
            os.kill(pid, 0)
        except OSError:
            return False
        return True

    def _peer(self, process_id: int):
        try:
            return self._ckpt_mod.read_json(self._member_path(process_id))
        except ValueError:
            return None  # torn write in flight; next poll re-reads

    def gate(self) -> None:
        """One collective dispatch: publish my beat, then wait until
        every peer reached it. Raises :class:`MeshParticipantLost` the
        moment a peer is provably gone — BEFORE this process enqueues
        the collective that would wedge on the dead peer."""
        self.beat += 1
        self._write()
        deadline = self.clock.monotonic() + self.stall_s
        while True:
            waiting = []
            for p in range(self.n_processes):
                if p == self.process_id:
                    continue
                doc = self._peer(p)
                if doc is None:
                    waiting.append((p, None))
                    continue
                if int(doc.get("beat", 0)) >= self.beat:
                    continue
                if not self._pid_alive(int(doc.get("pid", -1))):
                    self._lost(p, "process died",
                               beat=int(doc.get("beat", 0)))
                waiting.append((p, doc))
            if not waiting:
                return
            if self.clock.monotonic() >= deadline:
                p, doc = waiting[0]
                self._lost(p, "stalled",
                           beat=int(doc.get("beat", 0)) if doc else -1)
            self.clock.sleep(_MESH_POLL_S)

    def _lost(self, process_id: int, reason: str, beat: int):
        from pipelinedp_tpu import obs
        self.state = "lost"
        obs.inc("mesh.participant_lost")
        obs.event("mesh.participant_lost", process_id=int(process_id),
                  reason=reason, beat=int(beat),
                  at_beat=int(self.beat))
        raise MeshParticipantLost(
            f"mesh participant {process_id} lost at beat {self.beat} "
            f"({reason})", process_id=process_id, beat=beat,
            reason=reason)


#: Substrings that mark a runtime error as a FAILED CROSS-PROCESS
#: COLLECTIVE (XLA:CPU gloo transport wording; TPU DCN failures carry
#: "collective"). Matched case-insensitively against ``str(exc)``.
_COLLECTIVE_FAILURE_MARKERS = (
    "gloo", "all-reduce", "allreduce", "all-gather", "allgather",
    "collective", "connection reset", "connection closed", "preamble")

#: How long a survivor waits for a suspected-dead peer's pid to
#: actually exit before deciding the collective failure was NOT a
#: participant loss (a dying peer drains, prints and exits within
#: milliseconds; transient transport errors never produce a dead pid).
_COLLECTIVE_LOSS_CONFIRM_S = 10.0


def collective_failure_to_loss(exc, mesh,
                               clock: Optional[Clock] = None
                               ) -> Optional[MeshParticipantLost]:
    """Map a runtime error out of a FAILED cross-process collective to
    :class:`MeshParticipantLost` — only when a peer's member file
    proves the peer actually died.

    The supervisor's ``gate()`` catches a peer that died BETWEEN
    collective dispatches; a peer that dies while the survivor is
    already blocked INSIDE a matching collective surfaces on the
    survivor as an ``XlaRuntimeError`` from the transport (connection
    reset / closed) instead. That error alone is ambiguous — a
    transient network fault must NOT silently shrink the mesh — so
    this confirms against the beat files: some peer's recorded pid
    must be gone (polled briefly: the survivor often observes the
    reset a beat before the dying peer's ``os._exit`` lands). Returns
    None (caller re-raises) when unarmed, single-process, the error
    does not read like a collective failure, or every peer is alive.
    """
    mesh_dir = os.environ.get(MESH_DIR_ENV)
    if not mesh_dir or not getattr(mesh, "is_multi_process", False):
        return None
    msg = str(exc).lower()
    if not any(m in msg for m in _COLLECTIVE_FAILURE_MARKERS):
        return None
    import jax

    from pipelinedp_tpu import obs
    from pipelinedp_tpu.resilience import checkpoint as ckpt_mod
    clock = clock or SystemClock()
    me = int(jax.process_index())
    deadline = clock.monotonic() + _COLLECTIVE_LOSS_CONFIRM_S
    while True:
        for p in range(int(jax.process_count())):
            if p == me:
                continue
            try:
                doc = ckpt_mod.read_json(
                    os.path.join(mesh_dir, f"mesh-{p}.json"))
            except ValueError:
                continue  # torn write in flight
            if doc is None:
                continue
            pid = int(doc.get("pid", -1))
            if pid > 0 and not MeshSupervisor._pid_alive(pid):
                beat = int(doc.get("beat", 0))
                obs.inc("mesh.participant_lost")
                obs.event("mesh.participant_lost", process_id=p,
                          reason="collective_failure", beat=beat,
                          at_beat=-1)
                return MeshParticipantLost(
                    f"mesh participant {p} died mid-collective "
                    f"({str(exc)[:300]})", process_id=p, beat=beat,
                    reason="collective_failure")
        if clock.monotonic() >= deadline:
            return None
        clock.sleep(_MESH_POLL_S)


def supervisor_from_env(mesh) -> Optional[MeshSupervisor]:
    """Build a :class:`MeshSupervisor` for a multi-process ``mesh``
    when :data:`MESH_DIR_ENV` is armed; None otherwise (including for
    every single-process mesh — a lost local device surfaces as an
    injected ``DeviceLost``, not heartbeat silence)."""
    mesh_dir = os.environ.get(MESH_DIR_ENV)
    if not mesh_dir or not getattr(mesh, "is_multi_process", False):
        return None
    import jax
    return MeshSupervisor(mesh_dir, jax.process_index(),
                          jax.process_count())


def probe_with_retry(policy: Optional[RetryPolicy] = None,
                     clock: Optional[Clock] = None,
                     timeout_s: Optional[float] = None) -> HealthReport:
    """Probe the accelerator with bounded retry + backoff. Only reports:
    it never raises and never changes the platform. On exhaustion the
    report is ``healthy=False`` with the last probe's detail."""
    policy = policy or DEFAULT_HEALTH_POLICY
    attempts = [0]
    backoffs: List[float] = []

    from pipelinedp_tpu import obs

    def attempt():
        attempts[0] += 1
        # The span makes the probe VISIBLE to the live monitor: its
        # open registers activity (re-arming the stall watchdog for
        # this attempt), and a probe that then blocks ages as an
        # active span the watchdog can diagnose — and cancel.
        with obs.tracer().span("health.device_probe", cat="health",
                               attempt=attempts[0]):
            ok, detail = probe_devices(timeout_s, clock=clock)
        if not ok:
            raise _ProbeFailed(detail)
        return detail

    try:
        detail = call_with_retry(
            attempt, policy, clock, retry_on=(_ProbeFailed,),
            on_retry=lambda a, d, e: backoffs.append(d),
            label="health.device_probe")
    except RetriesExhausted as e:
        return HealthReport(healthy=False, degraded=False,
                            attempts=attempts[0], backoff_s=backoffs,
                            detail=str(e.last_error))
    return HealthReport(healthy=True, degraded=False,
                        attempts=attempts[0], backoff_s=backoffs,
                        detail=detail)


def ensure_device_or_degrade(policy: Optional[RetryPolicy] = None,
                             clock: Optional[Clock] = None,
                             timeout_s: Optional[float] = None,
                             env=None) -> HealthReport:
    """:func:`probe_with_retry`; on exhaustion degrade to CPU by setting
    ``JAX_PLATFORMS=cpu`` in ``env`` (effective only if jax has not
    initialized its backend in this process yet) and report
    ``degraded=True``. Never raises: the caller always gets a usable
    platform and an honest report. The library's opt-in API
    (``JaxBackend(health_policy=...)``); the entry points never call it.

    ``env`` defaults to ``os.environ`` — the only mapping jax (and the
    probe subprocess) actually reads. Passing a custom mapping is for
    TESTS ONLY: it records what the function *would* install without
    touching process state, so the returned report describes the
    simulated outcome, not an applied one."""
    env = os.environ if env is None else env
    from pipelinedp_tpu import obs

    report = probe_with_retry(policy, clock, timeout_s)
    if report.healthy:
        if env.get(DEGRADED_ENV):
            # The accelerator recovered: lift the degradation override
            # we installed (the CPU pin only, never a user's own
            # setting). If jax already initialized on CPU in this
            # process, a fresh process is still needed to use the
            # device — but the flags stop lying about it.
            env.pop(DEGRADED_ENV, None)
            if env.get("JAX_PLATFORMS") == "cpu":
                env.pop("JAX_PLATFORMS")
            obs.event("health.recovered", attempts=report.attempts)
        return report
    env["JAX_PLATFORMS"] = "cpu"
    env[DEGRADED_ENV] = "1"
    # A formerly-silent branch (the caller saw only the report): the
    # degradation is a first-class ledger event.
    obs.inc("health.degradations")
    obs.event("health.degraded", target="cpu_platform",
              attempts=report.attempts, detail=report.detail)
    return dataclasses.replace(report, degraded=True)


def resilient_make_mesh(n_devices: Optional[int] = None,
                        axis_name: str = "data",
                        policy: Optional[RetryPolicy] = None,
                        clock: Optional[Clock] = None):
    """``parallel.sharded.make_mesh`` under bounded retry; on exhaustion
    fall back to a mesh over the host CPU devices. Returns
    ``(mesh, HealthReport)`` — a degraded mesh is still a correct mesh
    (the sharded kernels are platform-agnostic), just slow, and the
    report says so.

    Each attempt first runs :func:`probe_devices` (a killable
    subprocess while this process has no backend): a wedged runtime
    blocks *inside* ``jax.devices()``, so calling ``make_mesh`` directly
    could hang forever — the probe times out instead and the
    retry/fallback machinery keeps control. (A runtime that wedges in
    the window between a passing probe and the in-process call can
    still hang; the probe shrinks that window, it cannot close it.)
    Deterministic errors (bad axis name, shape mismatch) are NOT
    retried or masked by the CPU fallback — they propagate immediately."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    from pipelinedp_tpu.parallel import sharded

    policy = policy or DEFAULT_HEALTH_POLICY
    attempts = [0]

    def attempt():
        attempts[0] += 1
        if faults.wedged("mesh.init"):
            raise TimeoutError(
                "injected wedge: mesh construction did not return")
        ok, detail = probe_devices()
        if not ok:
            raise TimeoutError(detail)
        mesh = sharded.make_mesh(n_devices, axis_name)
        hold_backend()
        return mesh

    from pipelinedp_tpu import obs

    backoffs: List[float] = []
    try:
        mesh = call_with_retry(
            attempt, policy, clock,
            retry_on=(RuntimeError, TimeoutError),
            on_retry=lambda a, d, e: backoffs.append(d),
            label="health.make_mesh")
        return mesh, HealthReport(healthy=True, degraded=False,
                                  attempts=attempts[0],
                                  backoff_s=backoffs, detail="ok")
    except RetriesExhausted as e:
        cpu = jax.devices("cpu")
        if n_devices is not None:
            cpu = cpu[:n_devices]
        mesh = Mesh(np.asarray(cpu), (axis_name,))
        obs.inc("health.degradations")
        obs.event("health.degraded", target="cpu_mesh",
                  n_devices=int(mesh.devices.size),
                  attempts=attempts[0], detail=str(e.last_error))
        return mesh, HealthReport(healthy=False, degraded=True,
                                  attempts=attempts[0],
                                  backoff_s=backoffs,
                                  detail=str(e.last_error))


def resilient_distributed_initialize(coordinator_address: str,
                                     num_processes: int,
                                     process_id: int,
                                     policy: Optional[RetryPolicy] = None,
                                     clock: Optional[Clock] = None,
                                     **initialize_kwargs) -> None:
    """``jax.distributed.initialize`` under bounded retry (coordinator
    handshakes lose races on busy hosts). The jitter seed folds in the
    process id so coworker processes do not retry in lockstep. Raises
    ``RetriesExhausted`` when the coordinator never answers — a hard
    deadline, not a hang.

    Extra keyword arguments are forwarded to
    ``jax.distributed.initialize``. ``heartbeat_timeout_seconds``
    matters for elastic recovery: by default the coordination service
    FATALLY terminates every surviving client ~100s after any peer
    stops heartbeating — exactly the window in which the mesh
    supervisor is re-forming the mesh and resuming."""
    import jax

    policy = policy or RetryPolicy(max_attempts=2, base_delay_s=1.0,
                                   multiplier=2.0, max_delay_s=10.0,
                                   jitter=0.25, seed=process_id)

    def _initialize():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id,
            **initialize_kwargs)

    def attempt():
        faults.check_coordinator()
        try:
            _initialize()
        except Exception:
            # A timed-out handshake can leave the global distributed
            # client assigned; without a shutdown every retry would
            # fail instantly with "already initialized", masking the
            # real error and defeating the backoff.
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            raise

    call_with_retry(attempt, policy, clock,
                    retry_on=(RuntimeError, TimeoutError,
                              faults.CoordinatorTimeout),
                    label="health.distributed_initialize")

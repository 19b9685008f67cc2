"""Distributed resilience: heartbeats, collective hang detection, cross-host
consistency guards.

PR 1 made a SINGLE process survive NaNs, corrupt checkpoints, and SIGTERM;
this module covers the failure modes only a fleet has (LlamaRL / PipelineRL
treat them as routine, PAPERS.md):

- **Heartbeats** — each host's `Heartbeat` thread writes an atomic
  ``heartbeats/host_<idx>.json`` (last step, phase, progress timestamp)
  every ``train.heartbeat_interval`` seconds. Progress is stamped by
  ``beat()`` calls from the train loop / orchestrator, so a host that is
  alive-but-stuck is distinguishable from one making progress.
- **Collective hang guard** — ``collective_guard(name)`` wraps every
  blocking host↔host collective (``allgather_host``, ``to_local_host``,
  ``barrier`` — see parallel/mesh.py). A collective that outlives
  ``train.collective_deadline`` seconds means a peer died or wedged: the
  guard prints a ``CollectiveTimeout`` diagnostic naming the step and the
  slowest host (from the heartbeat files) and hard-aborts the process with
  exit code ``EXIT_COLLECTIVE_TIMEOUT`` — a deadline'd abort every
  supervisor can restart, instead of an NCCL-style forever-hang. (A hung
  collective blocks the Python thread inside the runtime, so an exception
  cannot be raised into it — the abort has to come from the timer thread.)
- **Cross-host consistency guard** — ``host_fingerprint`` condenses a
  host's view of the run (step counter, crc32 of the local copy of a
  replicated param leaf, RNG key crc) into three ints;
  ``verify_fingerprints`` allgathers and compares them every
  ``train.desync_check_interval`` steps and raises ``HostDesync`` naming
  the offending host — instead of silently training diverged replicas.
- **Drill support** — ``perturb_local_replicas`` skews ONE host's local
  copy of a replicated param (the desync signature of a flaky DMA / bad
  host) for the ``host_desync`` fault; faults ``host_hang`` / ``host_kill``
  / ``slow_host`` (resilience/faults.py) complete the 2-process CPU drill
  (tests/test_distributed_resilience.py).
"""

import json
import os
import threading
import time
import zlib
from typing import Callable, Dict, Optional

import numpy as np

from trlx_tpu.resilience.checkpoint import atomic_write_text
from trlx_tpu.utils import sanitize

# Distinct exit code for a deadline'd collective abort — supervisors (and the
# 2-process drill) can tell "peer hang detected" from an ordinary crash.
EXIT_COLLECTIVE_TIMEOUT = 117


class CollectiveTimeout(RuntimeError):
    """A host↔host collective exceeded train.collective_deadline — some host
    died or wedged inside it. The message names the collective, the step,
    and the slowest host (from heartbeat files)."""


class HostDesync(RuntimeError):
    """Hosts disagree on the run state (step counter / param replica crc /
    RNG key) — training would silently continue on diverged replicas. The
    message names the offending host(s) and the mismatched component."""


# ------------------------------------------------------------------ heartbeat


class Heartbeat:
    """Per-host liveness + progress file.

    ``beat(step, phase)`` is hot-path cheap (attribute stores, no I/O); a
    daemon thread flushes the latest beat to
    ``<directory>/host_<idx>.json`` (atomic write) every ``interval``
    seconds. ``written_t`` advancing while ``progress_t`` freezes is the
    signature of alive-but-stuck — exactly what the hang diagnostic needs
    to name the culprit."""

    def __init__(self, directory: str, interval: float, process_index: Optional[int] = None):
        import jax

        self.directory = directory
        self.interval = float(interval)
        self.process_index = (
            int(process_index) if process_index is not None else jax.process_index()
        )
        # step/phase/progress_t are written by beat() on whichever thread
        # makes progress and read by the writer thread's _write(): without a
        # lock the JSON record can tear across the three fields (step from
        # beat N, phase from beat N+1) — exactly what the stall diagnostic
        # must not misread. GL008's finding; sanitize.make_lock also enrolls
        # the accesses in race-mode lockset tracking.
        self._beat_lock = sanitize.make_lock("Heartbeat._beat_lock")
        self.step = 0
        self.phase = "init"
        self.progress_t = time.time()
        # Monotonic twin of progress_t: wall clocks across hosts can step
        # (NTP slews), so graftfleet's skew estimation needs both bases in
        # the payload — wall for cross-host comparison, monotonic for
        # drift-proof ages on this host.
        self.progress_mono = time.monotonic()
        self._stop = threading.Event()
        self._thread = None

    @property
    def path(self) -> str:
        return os.path.join(self.directory, f"host_{self.process_index}.json")

    def beat(self, step: Optional[int] = None, phase: Optional[str] = None):
        with self._beat_lock:
            sanitize.race_access(self, "beat_state", write=True)
            if step is not None:
                self.step = int(step)
            if phase is not None:
                self.phase = phase
            self.progress_t = time.time()
            self.progress_mono = time.monotonic()

    def _write(self):
        with self._beat_lock:
            sanitize.race_access(self, "beat_state")
            payload = json.dumps(
                {
                    "process": self.process_index,
                    "step": self.step,
                    "phase": self.phase,
                    "progress_t": self.progress_t,
                    "progress_mono": self.progress_mono,
                    "written_t": time.time(),
                    "written_mono": time.monotonic(),
                }
            )
        atomic_write_text(self.path, payload)

    def start(self):
        os.makedirs(self.directory, exist_ok=True)
        self._write()
        if self.interval <= 0:
            return self

        def run():
            while not self._stop.wait(self.interval):
                try:
                    self._write()
                except OSError:
                    pass  # heartbeat must never kill the run it monitors

        self._thread = threading.Thread(target=run, name="trlx-heartbeat", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval + 1.0)
            self._thread = None
        try:
            self._write()  # final state on disk (e.g. phase="exited")
        except OSError:
            pass


def read_heartbeats(directory: str) -> Dict[int, dict]:
    """All hosts' heartbeat records, keyed by process index. Torn/unreadable
    files are skipped (atomic writes make that rare; a half-provisioned
    fleet makes it normal)."""
    out = {}
    if not os.path.isdir(directory):
        return out
    for fname in os.listdir(directory):
        if not (fname.startswith("host_") and fname.endswith(".json")):
            continue
        try:
            with open(os.path.join(directory, fname)) as f:
                rec = json.load(f)
            out[int(rec["process"])] = rec
        except (OSError, ValueError, KeyError):
            continue
    return out


def stall_report(directory: str, collective: str, now: Optional[float] = None) -> str:
    """Name the slowest host from the heartbeat files.

    Hosts whose phase shows them INSIDE the timed-out collective are the
    waiters; the culprit is a host that never entered it — pick the one with
    the oldest progress stamp (tie-broken by lowest step). Falls back to
    oldest-progress over all hosts when every phase looks entered (or no
    heartbeats exist)."""
    now = now if now is not None else time.time()
    beats = read_heartbeats(directory)
    if not beats:
        return "no heartbeat files found — enable train.heartbeat_interval for host-level diagnostics"
    stragglers = {
        i: r for i, r in beats.items() if r.get("phase") != f"collective:{collective}"
    } or beats
    culprit = min(
        stragglers.values(), key=lambda r: (r.get("progress_t", 0), r.get("step", 0))
    )
    age = now - culprit.get("progress_t", now)
    lines = ", ".join(
        f"host {i}: step {r.get('step')} phase {r.get('phase')!r} "
        f"({now - r.get('progress_t', now):.1f}s since progress)"
        for i, r in sorted(beats.items())
    )
    return (
        f"slowest host: host {culprit.get('process')} (last progress at step "
        f"{culprit.get('step')}, phase {culprit.get('phase')!r}, {age:.1f}s ago) — [{lines}]"
    )


# ----------------------------------------------------------- collective guard

# Process-global guard configuration, set once by the trainer from train.*
# knobs. Deadline <= 0 keeps every guard a no-op (the default — single-host
# runs and existing multihost tests see zero behavior change).
_CONFIG = {
    "deadline": 0.0,
    "heartbeat": None,  # Optional[Heartbeat]
    "step_provider": None,  # Optional[Callable[[], int]]
    "on_timeout": None,  # Optional[Callable[[CollectiveTimeout], None]] (tests)
}


def configure(
    deadline: float = 0.0,
    heartbeat: Optional[Heartbeat] = None,
    step_provider: Optional[Callable[[], int]] = None,
    on_timeout: Optional[Callable] = None,
):
    """Arm (or disarm, deadline=0) the process-global collective guard."""
    _CONFIG["deadline"] = float(deadline)
    _CONFIG["heartbeat"] = heartbeat
    _CONFIG["step_provider"] = step_provider
    _CONFIG["on_timeout"] = on_timeout


def _default_on_timeout(exc: CollectiveTimeout):
    """Print the diagnostic and hard-abort. os._exit, not sys.exit: the main
    thread is wedged inside the runtime's collective and will never unwind a
    SystemExit; only the timer thread can end the process."""
    import sys
    import traceback

    print(f"[trlx_tpu.resilience] FATAL: {exc}", file=sys.stderr, flush=True)
    traceback.print_stack(file=sys.stderr)
    os._exit(EXIT_COLLECTIVE_TIMEOUT)


class collective_guard:
    """Deadline watchdog around one blocking collective.

    ``with collective_guard("allgather_host"): <blocking call>`` — if the
    body outlives the deadline, the timer thread fires CollectiveTimeout
    handling (default: diagnostic + process abort). Explicit ``deadline`` /
    ``on_timeout`` override the process-global config (unit tests)."""

    def __init__(
        self,
        name: str,
        deadline: Optional[float] = None,
        on_timeout: Optional[Callable] = None,
        detail: Optional[Callable] = None,
    ):
        self.name = name
        self.deadline = _CONFIG["deadline"] if deadline is None else float(deadline)
        self.on_timeout = on_timeout or _CONFIG["on_timeout"] or _default_on_timeout
        # Optional zero-arg callable returning extra forensic fields for the
        # incident bundle (e.g. the engine's in-flight slot states on a
        # mid-decode peer death). Evaluated only on the timeout path.
        self.detail = detail
        self._timer = None

    def _fire(self):
        step = None
        provider = _CONFIG["step_provider"]
        if provider is not None:
            try:
                step = provider()
            except Exception:
                step = None
        extra = {}
        if self.detail is not None:
            try:
                extra = dict(self.detail())
            except Exception:  # noqa: BLE001 — forensics must not block abort
                extra = {}
        # Observability last-gasp: an instant on this thread's span lane plus
        # a best-effort incident bundle (thread stacks name the wedged peer
        # collective) BEFORE on_timeout — the default handler os._exit()s.
        try:
            from trlx_tpu.observability import anomaly as _obs_anomaly
            from trlx_tpu.observability import spans as _obs_spans

            _obs_spans.instant(
                "collective_timeout", collective=self.name, deadline_s=self.deadline
            )
            _obs_spans.flush()  # the default handler never reaches a boundary
            _obs_anomaly.emergency_capture(
                "collective_timeout", detail={"collective": self.name, **extra}
            )
        except Exception:  # noqa: BLE001 — the abort path must still abort
            pass
        try:
            # Fleet forensics (graftfleet armed): every reachable host's span
            # tail + heartbeat record into incidents/<step>/host<k>/ — the
            # wedged peer can't dump, so THIS host collects from the shared
            # checkpoint dir. One dict load when disarmed.
            from trlx_tpu.observability import fleet as _obs_fleet

            _obs_fleet.incident_bundle(
                step, "collective_timeout",
                detail={
                    "collective": self.name,
                    "deadline_s": self.deadline,
                    **extra,
                },
            )
        except Exception:  # noqa: BLE001 — the abort path must still abort
            pass
        hb = _CONFIG["heartbeat"]
        detail = (
            stall_report(hb.directory, self.name)
            if hb is not None
            else "no heartbeat configured — set train.heartbeat_interval to name the slow host"
        )
        self.on_timeout(
            CollectiveTimeout(
                f"collective {self.name!r} exceeded train.collective_deadline="
                f"{self.deadline:g}s at step {step} — a peer host died or hung; "
                f"{detail}. Aborting so the supervisor can restart and resume "
                "from the last coordinated checkpoint."
            )
        )

    def __enter__(self):
        self._span = None
        self._fleet_t0 = None
        # Fleet arrival stamp BEFORE the deadline gate: straggler attribution
        # works even on guards left at deadline 0. One dict load disarmed.
        from trlx_tpu.observability import fleet as _obs_fleet

        if _obs_fleet.armed():
            self._fleet_t0 = time.time()
        if self.deadline <= 0:
            return self
        from trlx_tpu.observability import spans as _obs_spans

        # A lane of collective/<name> boxes per host: the waiters' spans
        # stretch toward the deadline, the culprit's never starts. The guard
        # is the `with` block; it carries the span through.
        self._span = _obs_spans.trace_span(f"collective/{self.name}")
        self._span.__enter__()
        hb = _CONFIG["heartbeat"]
        if hb is not None:
            # Mark this host as INSIDE the collective: the stall report can
            # then separate waiters from the host that never arrived.
            hb.beat(phase=f"collective:{self.name}")
        self._timer = threading.Timer(self.deadline, self._fire)
        self._timer.daemon = True
        self._timer.start()
        return self

    def __exit__(self, *exc_info):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self._fleet_t0 is not None:
            from trlx_tpu.observability import fleet as _obs_fleet

            # Per-host arrival record for this (site, seq) occurrence — the
            # cross-host skew join happens at read time over the shared
            # checkpoint dir, so no collective rides on the hot path.
            _obs_fleet.collective_complete(self.name, self._fleet_t0, time.time())
            self._fleet_t0 = None
        if self._span is not None:
            self._span.__exit__(*exc_info)
            self._span = None
        return False


# ------------------------------------------------------- consistency guard


def _crc_of(array) -> int:
    return zlib.crc32(np.ascontiguousarray(np.asarray(array)).tobytes())


def _replicated_float_leaf(params):
    """The first float param leaf whose value is replicated on every device
    (layer-norm scales under the production partition rules; everything on a
    pure-dp mesh). Its LOCAL copy should be bit-identical across hosts — a
    crc mismatch means a host's replica silently diverged. Returns None when
    every float leaf is sharded (then the crc component is skipped)."""
    import jax
    import jax.numpy as jnp

    for leaf in jax.tree_util.tree_leaves(params):
        if not hasattr(leaf, "dtype") or not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        if not isinstance(leaf, jax.Array):
            return leaf  # host numpy: trivially "replicated"
        if leaf.is_fully_replicated:
            return leaf
    return None


def host_fingerprint(step: int, params, rng=None) -> np.ndarray:
    """This host's view of the run as int64[3]: [step, param replica crc,
    RNG key crc]. Cheap by construction — one replicated leaf (not the whole
    tree) crosses to host, and only every train.desync_check_interval steps."""
    import jax

    leaf = _replicated_float_leaf(params)
    if leaf is None:
        param_crc = 0
    elif isinstance(leaf, jax.Array):
        param_crc = _crc_of(leaf.addressable_data(0))
    else:
        param_crc = _crc_of(leaf)
    rng_crc = 0 if rng is None else _crc_of(jax.device_get(rng))
    return np.asarray([int(step), param_crc, rng_crc], dtype=np.int64)


_FINGERPRINT_FIELDS = ("step counter", "param replica crc32", "rng key crc32")


def compare_fingerprints(gathered: np.ndarray) -> None:
    """Raise HostDesync when any host's fingerprint row differs from host 0's.

    ``gathered`` is the allgathered (n_hosts, 3) matrix — identical input on
    every host, so every host raises the identical error (a one-sided raise
    would itself desync the fleet)."""
    gathered = np.asarray(gathered).reshape(-1, len(_FINGERPRINT_FIELDS))
    reference = gathered[0]
    problems = []
    for host in range(1, gathered.shape[0]):
        bad = [
            f"{_FINGERPRINT_FIELDS[j]} {gathered[host, j]} != {reference[j]}"
            for j in range(gathered.shape[1])
            if gathered[host, j] != reference[j]
        ]
        if bad:
            problems.append(f"host {host}: " + ", ".join(bad))
    if problems:
        raise HostDesync(
            "cross-host consistency check failed vs host 0 — "
            + "; ".join(problems)
            + ". Replicas have silently diverged (flaky host, torn restore, "
            "or non-deterministic host code); restart and resume every host "
            "from the last coordinated checkpoint."
        )


def verify_fingerprints(fingerprint: np.ndarray) -> None:
    """Allgather this host's fingerprint and compare across the fleet.
    Single process: trivially consistent. The gather rides the guarded
    allgather_host, so a host that died before the check surfaces as
    CollectiveTimeout rather than a hang."""
    import jax

    if jax.process_count() == 1:
        return
    from trlx_tpu.parallel.mesh import allgather_host

    compare_fingerprints(allgather_host(fingerprint[None, :]))


def verify_engine_schedule(schedule_crc: int, phase: Optional[int] = None) -> None:
    """Cross-host check that every host's slot manager made the SAME
    admission/harvest decisions this rollout phase (the engine's rolling
    schedule crc — see RolloutEngine.schedule_fingerprint()). In a
    multi-process engine run, a host whose slot schedule diverged would
    dispatch a decode program with different live rows and hang the fleet
    inside a collective; this check catches it by host name at the phase
    boundary instead. Single process: trivially consistent.

    Drill hook: ``TRLX_TPU_ENGINE_SCHEDULE_SKEW`` (a nonzero int) XORs THIS
    host's reported crc — the injection signature of a desynced slot
    manager, same idiom as ``perturb_local_replicas`` (a real divergence
    would wedge in the decode collective before any check could run, so the
    drill skews the report, not the schedule)."""
    import jax

    if jax.process_count() == 1:
        return
    from trlx_tpu.parallel.mesh import allgather_host

    crc = int(schedule_crc) & 0xFFFFFFFF
    skew = int(os.environ.get("TRLX_TPU_ENGINE_SCHEDULE_SKEW", "0") or "0")
    if skew:
        crc ^= skew & 0xFFFFFFFF
    row = np.asarray([int(phase or 0), crc], dtype=np.int64)
    gathered = np.asarray(allgather_host(row[None, :])).reshape(-1, 2)
    reference = gathered[0]
    problems = []
    fields = ("engine phase counter", "slot schedule crc32")
    for host in range(1, gathered.shape[0]):
        bad = [
            f"{fields[j]} {gathered[host, j]} != {reference[j]}"
            for j in range(gathered.shape[1])
            if gathered[host, j] != reference[j]
        ]
        if bad:
            problems.append(f"host {host}: " + ", ".join(bad))
    if problems:
        raise HostDesync(
            "engine slot-schedule check failed vs host 0 — "
            + "; ".join(problems)
            + ". The slot managers made different admission/harvest "
            "decisions (non-deterministic host code or skewed prompt "
            "data); the next decode dispatch would hang the fleet in a "
            "collective. Restart the phase with identical per-host inputs."
        )


# ------------------------------------------------------------- drill support


def perturb_local_replicas(params, scale: float = 1e-3):
    """Skew THIS host's local copy of the first replicated float param leaf
    (other hosts keep theirs) — the on-device signature of a flaky host that
    the desync guard must catch. Fault-injection only (``host_desync@step``);
    rebuilds the leaf from its own per-device buffers, so no collective runs
    and the other hosts never see the change."""
    import jax

    target = _replicated_float_leaf(params)
    if target is None or not isinstance(target, jax.Array):
        return params

    def rebuild(leaf):
        if leaf is not target:
            return leaf
        bufs = [
            jax.device_put(np.asarray(shard.data) * (1.0 + scale), shard.device)
            for shard in leaf.addressable_shards
        ]
        return jax.make_array_from_single_device_arrays(leaf.shape, leaf.sharding, bufs)

    return jax.tree_util.tree_map(rebuild, params)

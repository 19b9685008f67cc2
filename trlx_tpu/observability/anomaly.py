"""Stall recording, anomaly detection + one-shot incident capture.

**The flight recorder, always on.** A stalled step or rollout comes once in
some hundreds, in runs nobody armed. So every run keeps its last spans in
memory (observability/spans.py, the ring), counts what the process and the
host did over each step and each rollout (``proc_counters``, the ``Ticker``),
and holds each logged ``step_time`` and each rollout's ``time/generate_s``
against ``STALL_FACTOR`` x its rolling median (``AnomalyDetector``). Every
step and phase record says what it found (``stall/*``, ``proc/*``); on a
breach, and only then, ONE line goes to ``<checkpoint_dir>/stalls.jsonl``
(``StallLog``) with the ring's spans over the interval. What the readings
tell apart (RUNBOOK section 8): a tick gap near the excess, the host (or the
interpreter) stopped; the excess inside the waits with a quiet process and no
gap, the device or its runtime; the excess on the host side with involuntary
switches up, the process was kept off the CPU.

**The bundle, opt-in.** A slow step on a pod is gone by the time anyone looks:
metrics.jsonl shows a step_time spike, but the thread stacks, device-memory
state, and profiler evidence that would explain it were never recorded. When
a step exceeds ``train.anomaly_factor × rolling-p50`` (the same detector and
the same median as the stall record's, a second threshold), or when a
resilience event fires — guard skip, watchdog rollback, collective timeout —
a self-contained incident bundle lands under
``<checkpoint_dir>/incidents/<step>/``:

- ``incident.json``  — reason, step, trigger measurements, wall time;
- ``threads.txt``    — a faulthandler-style stack dump of EVERY live Python
  thread (the ``trlx-*`` pipeline threads are the interesting lanes: a
  producer parked in ``next_store`` vs wedged in a reward_fn looks identical
  in metrics but completely different here);
- ``memory.json``    — device-memory gauges + the monitored-program registry
  (which program's temp buffers were live);
- ``last_metrics.json`` — the tail of metrics.jsonl (the run's recent
  trajectory, so the bundle is readable without the full log);
- ``profile/``       — a short ``jax.profiler`` programmatic trace window
  around a probe dispatch (skipped when the trainer's own profiling window
  is active — two concurrent traces would corrupt each other);
- ``stall.json``     — a slow step's stall record, as in ``stalls.jsonl``.

Capture is bounded (``max_incidents`` per run) and BEST-EFFORT: every
section is individually guarded, because an observability crash during an
anomaly would convert a slow step into a dead run.

Drillable on CPU: ``TRLX_TPU_FAULTS=slow_step@N`` stalls the host between
step N's dispatch and its log-boundary sync, inflating the measured
step_time past any sane threshold — the detector fires and the bundle lands,
no TPU required (tests/test_observability.py).
"""

import gc
import json
import os
import resource
import sys
import threading
import time
import traceback
import warnings
from collections import deque, namedtuple

from trlx_tpu.observability import spans
from trlx_tpu.utils import jsonl

__all__ = [
    "AnomalyDetector",
    "Breach",
    "IncidentCapture",
    "ProcWindow",
    "StallLog",
    "Ticker",
    "proc_counters",
    "stall_record",
    "register_emergency",
    "emergency_capture",
    "STALL_FACTOR",
    "STALLS_FILENAME",
    "MAX_STALLS",
    "REGIME_BREACHES",
]

# A step or a rollout this many times its rolling median is a stall. One
# value and one window for every run: no step's place in its iteration passes
# it in a quiet run of any cell measured (PERF.md section 6, PR 49).
STALL_FACTOR = 1.5
STALLS_FILENAME = "stalls.jsonl"
MAX_STALLS = 64  # lines a run may write
# This many breaches in a row are no stall but how the run goes now: the
# window starts over from them, and the records read 0.0 again.
REGIME_BREACHES = 8

# What observe() found over a threshold. `excess_s`: seconds over the median
# where the observation passed `factor` (else 0.0); `wait_excess_s`: the part
# of it inside the waits for the device; `bundle`: it passed `bundle_factor`.
Breach = namedtuple("Breach", "p50 excess_s wait_p50 wait_excess_s bundle")


class AnomalyDetector:
    """Rolling-median breach detector: one window, two thresholds.

    ``observe(seconds, waited)`` returns a ``Breach`` when the observation
    exceeds ``factor × p50`` of the trailing window (the stall record's
    threshold) or ``bundle_factor × p50`` (``train.anomaly_factor``'s,
    0 = off), else None — AFTER ``min_samples`` observations, so
    compilation-tainted first steps never both seed and trip the baseline. A
    breaching observation is NOT added to the window, so one stall does not
    hide the next; ``REGIME_BREACHES`` of them in a row are a change of
    regime (a longer batch, a neighbour that stays), and the window starts
    over from those: the detector runs in every run, and a week-long one
    must not report its whole second half as one stall.

    ``waited`` is the part of ``seconds`` the caller spent waiting for the
    device; its median is kept beside the other, and a breach says how much
    of its excess lies inside the waits."""

    def __init__(self, factor: float, window: int = 64, min_samples: int = 5, bundle_factor: float = 0.0):
        self.factor = float(factor)
        self.bundle_factor = float(bundle_factor)
        self.min_samples = max(2, int(min_samples))
        window = max(self.min_samples, int(window))
        self._times, self._waits = deque(maxlen=window), deque(maxlen=window)
        self._breaches = []  # the (seconds, waited) of the breaches since the last quiet observation

    @staticmethod
    def _median(values):
        if not values:
            return None
        ordered = sorted(values)
        return ordered[len(ordered) // 2]

    def p50(self):
        return self._median(self._times)

    def observe(self, seconds: float, waited: float = 0.0):
        seconds, waited = float(seconds), float(waited)
        thresholds = [f for f in (self.factor, self.bundle_factor) if f > 0]
        if not thresholds:
            return None
        if len(self._times) >= self.min_samples:
            p50 = self._median(self._times)
            if seconds > min(thresholds) * p50:
                wait_p50 = self._median(self._waits)
                self._breaches.append((seconds, waited))
                if len(self._breaches) >= max(REGIME_BREACHES, self.min_samples):
                    self._times.clear()
                    self._waits.clear()
                    self._times.extend(s for s, _ in self._breaches)
                    self._waits.extend(w for _, w in self._breaches)
                    self._breaches = []
                excess = seconds - p50 if 0 < self.factor and seconds > self.factor * p50 else 0.0
                return Breach(
                    p50=p50,
                    excess_s=excess,
                    wait_p50=wait_p50,
                    wait_excess_s=min(excess, max(0.0, waited - wait_p50)),
                    bundle=0 < self.bundle_factor and seconds > self.bundle_factor * p50,
                )
        self._breaches = []
        self._times.append(seconds)
        self._waits.append(waited)
        return None


# ------------------------------------------------- the process and the host

_GC = {"seconds": 0.0, "t0": None}


def _gc_clock(phase, info):
    if phase == "start":
        _GC["t0"] = time.perf_counter()
    elif _GC["t0"] is not None:
        _GC["seconds"] += time.perf_counter() - _GC["t0"]
        _GC["t0"] = None


def time_collections(on: bool):
    """Install (or remove) the ``gc.callbacks`` pair behind ``proc_counters()``'
    ``gc_s``; `learn()` does both."""
    if on and _gc_clock not in gc.callbacks:
        gc.callbacks.append(_gc_clock)
    elif not on and _gc_clock in gc.callbacks:
        gc.callbacks.remove(_gc_clock)


def proc_counters() -> dict:
    """Cumulative readings of what the process has done, one ``getrusage``
    and a dict lookup; a window's story is the difference of two readings
    (``ProcWindow``): ``nivcsw`` (the scheduler took the CPU away), ``nvcsw``
    (it gave the CPU up: a wait), ``majflt``, ``cpu_s`` (user + system, every
    thread's), and ``gc_s`` (seconds inside the collector,
    ``time_collections``). The host's own files (``/proc/pressure/*``, the
    ``steal`` column of ``/proc/stat``) are not read: the chip's host shows
    no pressure files and its steal column read 0.0 through every stall
    caught (PERF.md section 6, PR 49); what tells a stopped host is the
    ticker."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return {
        "nivcsw": float(usage.ru_nivcsw),
        "nvcsw": float(usage.ru_nvcsw),
        "majflt": float(usage.ru_majflt),
        "cpu_s": usage.ru_utime + usage.ru_stime,
        "gc_s": _GC["seconds"],
    }


class Ticker:
    """A daemon thread, ``trlx-obs-tick``, that sleeps ``period`` seconds and
    notes how late each wake came. While the main thread sits in
    ``block_until_ready`` the interpreter lock is free, so a ticker that kept
    time says the host was alive; one that woke seconds late says the host
    (or the interpreter: a native call that kept the lock) stopped.

    A wake more than ``late`` seconds late is an instant ``host/tick_gap``
    (seconds) in the spans' ring; the largest lateness since each reader
    last asked is kept a reader (``take(reader)``), so the step's window and
    the rollout's do not eat each other's reading."""

    READERS = ("step", "rollout")

    def __init__(self, period: float = 0.05, late: float = 0.15, clock=time.monotonic):
        self.period, self.late, self._clock = float(period), float(late), clock
        self._lock = threading.Lock()  # _max and _due: written by the ticker's thread and by its readers
        self._max = dict.fromkeys(self.READERS, 0.0)
        self._due = None
        self._stop = threading.Event()
        self._thread = None

    def note(self):
        """One wake: lateness against the time it was due, into every reader's
        maximum; the next wake is due a period from now."""
        now = self._clock()
        with self._lock:
            gap = 0.0 if self._due is None else max(0.0, now - self._due)
            self._due = now + self.period
            for reader in self._max:
                self._max[reader] = max(self._max[reader], gap)
        if gap > self.late:
            spans.instant("host/tick_gap", seconds=gap)
        return gap

    def take(self, reader: str) -> float:
        """The largest lateness since `reader` last asked, in seconds."""
        with self._lock:
            gap, self._max[reader] = self._max[reader], 0.0
        return gap

    def start(self):
        if self._thread is None:
            self._stop.clear()
            with self._lock:
                self._due = None  # a restarted ticker is not late against its last life

            def run():
                while True:
                    self.note()
                    if self._stop.wait(self.period):
                        break

            self._thread = threading.Thread(target=run, name="trlx-obs-tick", daemon=True)
            self._thread.start()
        return self

    def stop(self):
        """Stop and join (a leaked ``trlx-*`` thread fails the drills' thread checks)."""
        thread, self._thread = self._thread, None
        if thread is not None:
            self._stop.set()
            thread.join(timeout=5.0)


class ProcWindow:
    """What the process did and how the host kept time over one window of the
    loop: the deltas of ``proc_counters()``, compile requests, and the
    ticker's largest gap, from ``open()`` to ``close()``. ``close()`` opens
    the next window too: a step's window runs from the previous stats read to
    its own."""

    def __init__(self, ticker: Ticker, reader: str):
        self._ticker, self._reader = ticker, reader
        self._c0 = self._t0_ns = None

    @staticmethod
    def _read():
        return dict(proc_counters(), compiles=spans.compile_requests()), time.time_ns()

    def open(self):
        self._c0, self._t0_ns = self._read()
        self._ticker.take(self._reader)

    def close(self) -> dict:
        """``{"t0_ns", "t1_ns", <counter>: delta ..., "tick_gap_max_s"}``; an
        unopened window opens here and reads zeros."""
        if self._c0 is None:
            self.open()
        c0, t0_ns = self._c0, self._t0_ns
        self._c0, self._t0_ns = self._read()
        out = {k: v - c0[k] for k, v in self._c0.items()}
        out.update(t0_ns=t0_ns, t1_ns=self._t0_ns, tick_gap_max_s=self._ticker.take(self._reader))
        return out


def stall_record(kind: str, breach: Breach, proc: dict, **fields) -> tuple:
    """One line of stalls.jsonl, less its spans, and the interval to take them
    over: the caller's `fields` (step, iter, t0, t1, seconds, wait_s, host_ms,
    compiles ...), what the detector found, and every delta of the window's
    counters `proc` (``ProcWindow.close()``) as ``proc/<name>``."""
    record = dict(
        fields,
        kind=kind,
        p50=breach.p50,
        excess_s=breach.excess_s,
        wait_p50_s=breach.wait_p50,
        wait_excess_s=breach.wait_excess_s,
    )
    record.update((f"proc/{k}", v) for k, v in proc.items() if not k.endswith("_ns") and k != "compiles")
    record["proc/window_s"] = (proc["t1_ns"] - proc["t0_ns"]) * 1e-9
    return record, (int(fields["t0"] * 1e9), int(fields["t1"] * 1e9) + 1)


class StallLog:
    """``<checkpoint_dir>/stalls.jsonl``: one line a breach, ``MAX_STALLS`` a
    run at most, each ONE ``write(2)`` (utils/jsonl: a killed process tears at
    most the last line). The file appears with the first stall; an I/O error
    disarms the log with one warning and the run goes on."""

    def __init__(self, checkpoint_dir: str, process_index: int = 0):
        self.path = os.path.join(os.path.abspath(checkpoint_dir), STALLS_FILENAME)
        self.pid = int(process_index)
        self.written = 0
        self._file = None
        self._armed = True

    def write(self, record: dict, window, keep: bool = False):
        """Append `record` with the ring's events over `window` (``(t0_ns,
        t1_ns)``) as ``spans`` and the late ticks among them as ``tick_gaps``.
        Call it after the spans of interest have ended, never inside them.
        Returns the full record, or None where the log takes no more lines
        (``MAX_STALLS`` written, or disarmed) and no bundle wants it (`keep`):
        the ring is not read for a record nobody gets."""
        live = self._armed and self.written < MAX_STALLS
        if not (live or keep):
            return None
        events = spans.recent(*window, pid=self.pid)
        record = dict(
            record,
            pid=self.pid,
            tick_gaps=[[e["ts"] * 1e-6, e["args"]["seconds"]] for e in events if e["name"] == "host/tick_gap"],
            spans=events,
        )
        if live:
            try:
                if self._file is None:
                    self._file = jsonl.open_line_atomic(self.path)
                jsonl.write_record(self._file, record)
                self.written += 1
            except (OSError, ValueError, TypeError):  # TypeError: a span's args json cannot write
                self._armed = False
                warnings.warn(
                    f"stall log disabled: writing {self.path} failed "
                    "(disk full / closed file?): the run continues unrecorded",
                    stacklevel=2,
                )
        return record

    def close(self):
        file, self._file = self._file, None
        if file is not None:
            try:
                file.close()
            except OSError:
                pass


def dump_all_threads() -> str:
    """faulthandler-style stack dump of every live Python thread, with the
    thread NAMES resolved (faulthandler itself only prints idents — useless
    for telling trlx-score-worker from trlx-prefetch)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    lines = []
    for ident, frame in sorted(sys._current_frames().items()):
        lines.append(f"--- thread {names.get(ident, '?')} (ident {ident}) ---")
        lines.extend(line.rstrip("\n") for line in traceback.format_stack(frame))
        lines.append("")
    return "\n".join(lines)


class IncidentCapture:
    """Writes bounded, best-effort incident bundles for one run."""

    def __init__(
        self,
        checkpoint_dir: str,
        monitor=None,
        metrics_path=None,
        max_incidents: int = 4,
        last_n_metrics: int = 50,
        profiling_active=None,
    ):
        self.directory = os.path.join(os.path.abspath(checkpoint_dir), "incidents")
        self.monitor = monitor  # Optional[DeviceMonitor]
        self.metrics_path = metrics_path
        self.max_incidents = int(max_incidents)
        self.last_n_metrics = int(last_n_metrics)
        # Callable -> bool: is the trainer's own jax.profiler window open?
        self.profiling_active = profiling_active or (lambda: False)
        self.captured = 0
        self._lock = threading.Lock()

    def capture(self, step: int, reason: str, detail=None, stall=None) -> str:
        """Capture one bundle; returns its directory ('' when rate-limited).
        `stall`: a slow step's stall record, kept as ``stall.json``.
        Reentrancy-safe: concurrent triggers (detector on the main thread,
        a collective-guard timer thread) serialize on the lock and spend the
        incident budget once each."""
        with self._lock:
            if self.captured >= self.max_incidents:
                return ""
            self.captured += 1
        bundle = os.path.join(self.directory, str(int(step)))
        n = 1
        while os.path.exists(os.path.join(bundle, "incident.json")):
            # a second incident at the same step (another detector, another
            # thread) keeps the first one's evidence: <step>.2, <step>.3 ...
            n += 1
            bundle = os.path.join(self.directory, f"{int(step)}.{n}")
        os.makedirs(bundle, exist_ok=True)

        t0 = time.time()
        sections = {}

        def guard(name, fn):
            try:
                fn()
                sections[name] = "ok"
            except Exception as e:  # noqa: BLE001 — best-effort by design
                sections[name] = f"{type(e).__name__}: {e}"[:300]

        def write_threads():
            with open(os.path.join(bundle, "threads.txt"), "w") as f:
                f.write(dump_all_threads())

        def write_memory():
            from trlx_tpu.observability.devicemon import device_memory_gauges

            payload = {"gauges": device_memory_gauges()}
            if self.monitor is not None:
                payload["programs"] = self.monitor.snapshot()
            with open(os.path.join(bundle, "memory.json"), "w") as f:
                json.dump(payload, f, indent=1)

        def write_metrics_tail():
            if not self.metrics_path or not os.path.exists(self.metrics_path):
                return
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # a torn tail is fine here
                records = jsonl.read_jsonl(self.metrics_path)
            with open(os.path.join(bundle, "last_metrics.json"), "w") as f:
                json.dump(records[-self.last_n_metrics :], f, indent=1)

        def write_profile():
            # A short programmatic trace window around a probe dispatch: on
            # TPU this snapshots queued-program state and device activity
            # around the anomaly's tail; on CPU it proves the plumbing. Never
            # nested inside the trainer's own profiling window.
            if self.profiling_active():
                sections["profile"] = "skipped: trainer profiling window active"
                return
            import jax
            import jax.numpy as jnp

            profile_dir = os.path.join(bundle, "profile")
            jax.profiler.start_trace(profile_dir)
            try:
                jnp.zeros((8,)).block_until_ready()
            finally:
                jax.profiler.stop_trace()

        def write_stall():
            with open(os.path.join(bundle, "stall.json"), "w") as f:
                json.dump(stall, f, indent=1)

        guard("threads", write_threads)
        guard("memory", write_memory)
        guard("metrics_tail", write_metrics_tail)
        guard("profile", write_profile)
        if stall is not None:
            guard("stall", write_stall)

        manifest = {
            "step": int(step),
            "reason": reason,
            "detail": detail,
            "time": t0,
            "capture_seconds": round(time.time() - t0, 3),
            "sections": sections,
        }
        try:
            with open(os.path.join(bundle, "incident.json"), "w") as f:
                json.dump(manifest, f, indent=1)
        except OSError:
            return ""

        spans.instant("incident", step=int(step), reason=reason)
        spans.flush()  # an incident is a boundary: its span tail is evidence
        print(
            f"[trlx_tpu.observability] incident captured at step {step} "
            f"({reason}) -> {bundle}",
            file=sys.stderr,
            flush=True,
        )
        return bundle


# Emergency hook: the collective-guard timeout path runs on a timer thread
# microseconds before os._exit — it has no trainer reference, so the trainer
# registers its IncidentCapture here (mirrors resilience.distributed._CONFIG).
_EMERGENCY = {"capture": None, "step_provider": None}


def register_emergency(capture, step_provider=None):
    _EMERGENCY["capture"] = capture
    _EMERGENCY["step_provider"] = step_provider


def emergency_capture(reason: str, detail=None):
    """Best-effort capture from contexts that may be about to abort the
    process (collective timeout). Silently a no-op when nothing registered."""
    capture = _EMERGENCY["capture"]
    if capture is None:
        return
    step = 0
    provider = _EMERGENCY["step_provider"]
    if provider is not None:
        try:
            step = int(provider())
        except Exception:  # noqa: BLE001
            step = 0
    try:
        capture.capture(step, reason, detail=detail)
    except Exception:  # noqa: BLE001 — the abort path must still abort
        pass

"""Anomaly detection + one-shot incident capture.

A slow step on a pod is gone by the time anyone looks: metrics.jsonl shows a
step_time spike, but the thread stacks, device-memory state, and profiler
evidence that would explain it were never recorded. This module watches the
per-step wall time the trainer already measures at log boundaries and, when
a step exceeds ``k × rolling-p50`` (or when a resilience event fires — guard
skip, watchdog rollback, collective timeout), captures a self-contained
incident bundle under ``<checkpoint_dir>/incidents/<step>/``:

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
  is active — two concurrent traces would corrupt each other).

Capture is bounded (``max_incidents`` per run) and BEST-EFFORT: every
section is individually guarded, because an observability crash during an
anomaly would convert a slow step into a dead run.

Drillable on CPU: ``TRLX_TPU_FAULTS=slow_step@N`` stalls the host between
step N's dispatch and its log-boundary sync, inflating the measured
step_time past any sane threshold — the detector fires and the bundle lands,
no TPU required (tests/test_observability.py).
"""

import json
import os
import sys
import threading
import time
import traceback
from collections import deque

__all__ = ["AnomalyDetector", "IncidentCapture", "register_emergency", "emergency_capture"]


class AnomalyDetector:
    """Rolling-median step-time breach detector.

    ``observe(seconds)`` returns True when the observation exceeds
    ``factor × p50`` of the trailing window — AFTER ``min_samples``
    observations, so compilation-tainted first steps never both seed and
    trip the baseline. The breaching observation is NOT added to the
    window: a genuine regime change trips repeatedly (each breach is an
    incident candidate; the capture side rate-limits) instead of silently
    re-baselining."""

    def __init__(self, factor: float, window: int = 64, min_samples: int = 5):
        self.factor = float(factor)
        self.min_samples = max(2, int(min_samples))
        self._times = deque(maxlen=max(self.min_samples, int(window)))

    def p50(self):
        if not self._times:
            return None
        ordered = sorted(self._times)
        return ordered[len(ordered) // 2]

    def observe(self, seconds: float) -> bool:
        seconds = float(seconds)
        if self.factor <= 0:
            return False
        if len(self._times) >= self.min_samples:
            p50 = self.p50()
            if p50 is not None and seconds > self.factor * p50:
                return True
        self._times.append(seconds)
        return False


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

    def capture(self, step: int, reason: str, detail=None) -> str:
        """Capture one bundle; returns its directory ('' when rate-limited).
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
            import warnings

            from trlx_tpu.utils.jsonl import read_jsonl

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # a torn tail is fine here
                records = read_jsonl(self.metrics_path)
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

        guard("threads", write_threads)
        guard("memory", write_memory)
        guard("metrics_tail", write_metrics_tail)
        guard("profile", write_profile)

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

        from trlx_tpu.observability import spans

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

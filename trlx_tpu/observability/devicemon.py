"""Device telemetry: compiled-cost capture, real-FLOPs MFU, routing gauges.

An analytic FLOP model (counted from a configuration's shapes) is fine for a
benchmark that knows its own shapes, useless for a live run whose programs
(fused vs dense head, packed vs padded batches, per-bucket score fns) are
picked by routing logic at runtime. This module instead asks XLA: every
jitted program the trainer dispatches is wrapped by a ``DeviceMonitor``
proxy that, at its FIRST dispatch per input signature, captures the
compiled executable's ``cost_analysis()`` (FLOPs, bytes accessed) and
``memory_analysis()`` (argument/output/temp bytes). Per-window gauges then
follow from bookkeeping the wrapper already does:

    obs/train_mfu_pct = 100 * (train-program FLOPs dispatched in the window)
                        / train-phase seconds / peak per-chip FLOP/s

``cost_analysis`` on an SPMD-partitioned program reports the PER-DEVICE
module cost, so the MFU needs no device-count division — it is directly the
per-chip utilization ``train_tflops / peak``.

Capture cost and safety:

- The capture runs ``fn.lower(*args).compile()`` synchronously at first
  dispatch, BEFORE calling ``fn`` (donated buffers are still alive then).
  Tracing is shared with the call path (the jaxpr cache), so no re-trace;
  the AOT ``compile()`` may duplicate the executable build once per program
  — a one-time cost that the persistent compile cache
  (utils/compile_cache.py) absorbs. Programs whose capture fails (e.g. a
  fn that is not lowerable) record the error and keep running unmonitored.
- The wrapper delegates attribute access to the wrapped fn, so decorated
  closures keep their public surface (``make_generate_fn``'s ``num_traces``
  / ``traced_shapes`` counters remain visible through the proxy).

Routing gauges (``kernel_routing_gauges``) read the fused log-prob head's
probe cache (ops/fused_logprob.py): an entry that is False means an ELIGIBLE
shape was refused by the static tile check and took log_softmax — visible in
metrics.jsonl within one window, not only as a one-time stderr warning.
"""

import json
import os
import threading

import numpy as np

__all__ = [
    "DeviceMonitor",
    "CHIP_PEAKS",
    "chip_peaks",
    "detect_peak_flops",
    "kernel_routing_gauges",
    "device_memory_gauges",
    "PROGRAMS_FILENAME",
]

PROGRAMS_FILENAME = "programs.json"

# The one table of chip peaks, keyed by the
# device_kind JAX reports: (dense bf16 TFLOP/s, HBM GB/s) per chip, from
# Google Cloud's per-generation TPU documentation. A TPU that is not here is
# an error, not a default — add its row with its source.
CHIP_PEAKS = {
    "TPU v2": (45.0, 700),
    "TPU v3": (123.0, 900),
    "TPU v4": (275.0, 1228),
    "TPU v5 lite": (197.0, 819),  # v5e, as jax 0.9 / libtpu 0.0.34 name it
    "TPU v5e": (197.0, 819),
    "TPU v5p": (459.0, 2765),
    "TPU v6 lite": (918.0, 1638),  # v6e (Trillium)
    "TPU v6e": (918.0, 1638),
}


def chip_peaks(device_kind: str):
    """(bf16 TFLOP/s, HBM GB/s) for a device_kind; raises on an unknown one."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} is not in devicemon.CHIP_PEAKS "
            f"(known: {sorted(CHIP_PEAKS)}) — add its published peaks; an MFU "
            "against a guessed peak is not a measurement"
        ) from None


def detect_peak_flops():
    """Peak per-chip FLOP/s of this process's device: from CHIP_PEAKS on a
    TPU (an unknown kind raises), None on any other platform — a CPU has no
    row, so no MFU gauge is derived there.

    ``TRLX_TPU_PEAK_TFLOPS`` overrides both — the only way to get an MFU
    gauge on CPU smoke runs."""
    env = os.environ.get("TRLX_TPU_PEAK_TFLOPS")
    if env:
        try:
            return float(env) * 1e12
        except ValueError:
            pass
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return chip_peaks(device.device_kind)[0] * 1e12


def _signature(args, kwargs) -> tuple:
    """Hashable (shape, dtype) signature of the array leaves. Cheap relative
    to any dispatch that reaches it (one host tree-flatten per call of a
    program that runs milliseconds-to-seconds on device)."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
        else (type(leaf).__name__, str(leaf))
        for leaf in leaves
    )


class _MonitoredFn:
    """Transparent callable proxy: counts dispatches, captures compiled cost
    at the first dispatch of each input signature, then calls through."""

    def __init__(self, monitor, name, fn):
        self._monitor = monitor
        self._name = name
        self._fn = fn

    def __call__(self, *args, **kwargs):
        self._monitor._on_dispatch(self._name, self._fn, args, kwargs)
        return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        # Only reached for names not on the proxy — live delegation keeps
        # wrapped closures' counters (num_traces etc.) readable and current.
        return getattr(self._fn, item)


class DeviceMonitor:
    """Registry of monitored jitted programs + per-window FLOP accounting.

    ``wrap(name, fn, phase=...)`` assigns the program to an accounting phase
    ("train", "rollout", "score") matching PhaseTimer's lanes; ``window()``
    drains the per-window dispatch counters into gauge scalars."""

    # Don't capture unboundedly many signatures per program (prompt-bucketed
    # score fns are per-bucket NAMES already; this caps pathological cases).
    MAX_SIGNATURES_PER_PROGRAM = 8

    def __init__(self, peak_flops=None, programs_path=None):
        self.peak_flops = peak_flops if peak_flops is not None else detect_peak_flops()
        self.programs_path = programs_path
        self.programs = {}  # name -> {phase, dispatches, signatures: {sig -> rec}}
        self._lock = threading.Lock()
        self._window_flops = {}  # phase -> flops dispatched since last window()
        self._dirty = False

    def wrap(self, name, fn, phase: str = "train"):
        with self._lock:
            self.programs.setdefault(
                name, {"phase": phase, "dispatches": 0, "signatures": {}}
            )
        return _MonitoredFn(self, name, fn)

    # ------------------------------------------------------------- dispatch

    def _on_dispatch(self, name, fn, args, kwargs):
        prog = self.programs[name]
        sig = _signature(args, kwargs)
        with self._lock:
            prog["dispatches"] += 1
            rec = prog["signatures"].get(sig)
            if rec is None and len(prog["signatures"]) < self.MAX_SIGNATURES_PER_PROGRAM:
                rec = prog["signatures"][sig] = {"flops": None}
                capture = True
            else:
                capture = False
        if capture:
            self._capture(name, fn, args, kwargs, rec)
        if rec is not None and rec.get("flops"):
            with self._lock:
                self._window_flops[prog["phase"]] = (
                    self._window_flops.get(prog["phase"], 0.0) + rec["flops"]
                )

    def _capture(self, name, fn, args, kwargs, rec):
        # Before fn(*args): donated inputs are still alive. Synchronous and
        # one-time per (program, signature) — see the module docstring for
        # the cost argument.
        try:
            compiled = fn.lower(*args, **kwargs).compile()
            cost = compiled.cost_analysis()
            rec["flops"] = float(cost.get("flops", 0.0) or 0.0)
            rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0) or 0.0)
            mem = compiled.memory_analysis()
            for field in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes"):
                value = getattr(mem, field, None)
                if value is not None:
                    rec[field] = int(value)
        except Exception as e:  # noqa: BLE001 — telemetry must not kill the run
            rec["flops"] = 0.0
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        self._dirty = True
        self._persist()

    def _persist(self):
        """Write the registry to <ckpt_dir>/programs.json (atomic overwrite)
        so report.py can render the program table after the run ends."""
        if not self.programs_path or not self._dirty:
            return
        try:
            from trlx_tpu.resilience.checkpoint import atomic_write_text

            atomic_write_text(self.programs_path, json.dumps(self.snapshot(), indent=1))
            self._dirty = False
        except OSError:
            pass

    # -------------------------------------------------------------- reading

    def snapshot(self) -> dict:
        """JSON-ready registry view: per program, the phase, total dispatch
        count, and each captured signature's cost/memory record."""
        with self._lock:
            out = {}
            for name, prog in self.programs.items():
                out[name] = {
                    "phase": prog["phase"],
                    "dispatches": prog["dispatches"],
                    "variants": [
                        {"signature": [list(map(str, s)) for s in sig], **rec}
                        for sig, rec in prog["signatures"].items()
                    ],
                }
            return out

    def window(self, phase_seconds: dict) -> dict:
        """Drain the per-window FLOP counters into gauges.

        ``phase_seconds`` maps PhaseTimer lanes to measured seconds for the
        window: ``{"train": ..., "wall": ...}``. Emits per-chip TFLOP/s
        always, and MFU percentages when the peak is known."""
        with self._lock:
            flops, self._window_flops = self._window_flops, {}
        stats = {}
        train_flops = flops.get("train", 0.0)
        total_flops = sum(flops.values())
        train_s = float(phase_seconds.get("train", 0.0) or 0.0)
        wall_s = float(phase_seconds.get("wall", 0.0) or 0.0)
        if train_flops > 0 and train_s > 0:
            tflops = train_flops / train_s / 1e12
            stats["obs/train_tflops_per_chip"] = tflops
            if self.peak_flops:
                stats["obs/train_mfu_pct"] = 100.0 * tflops * 1e12 / self.peak_flops
        if total_flops > 0 and wall_s > 0:
            tflops = total_flops / wall_s / 1e12
            stats["obs/iter_tflops_per_chip"] = tflops
            if self.peak_flops:
                stats["obs/iter_mfu_pct"] = 100.0 * tflops * 1e12 / self.peak_flops
        # Window boundaries refresh the persisted registry so its DISPATCH
        # counts track the run (captures alone only write at first dispatch).
        self._dirty = bool(self.programs)
        self._persist()
        return stats

    def flush(self):
        """Force-persist the registry (run exit: the final steps after the
        last window boundary must still land in programs.json)."""
        self._dirty = bool(self.programs)
        self._persist()

    # Method aliases of the module-level gauges: window-boundary callers
    # (JaxBaseTrainer._flush_device_telemetry) hold the monitor, not the
    # module.
    def kernel_routing_gauges(self) -> dict:
        return kernel_routing_gauges()

    def device_memory_gauges(self) -> dict:
        return device_memory_gauges()


# ------------------------------------------------------------------- gauges


def kernel_routing_gauges() -> dict:
    """Live kernel-routing state from the fused log-prob head's probe cache.

    - ``*_active``: 1.0 when at least one shape probed OK (the kernel is
      actually serving dispatches);
    - ``*_fallback``: 1.0 when at least one ELIGIBLE shape was refused by
      its probe — the silent-fallback condition that used to be one stderr
      warning, now a gauge a dashboard can alarm on."""
    from trlx_tpu.ops import fused_logprob as fl

    values = list(fl._PROBE_CACHE.values())
    return {
        "obs/fused_logprob_active": 1.0 if any(values) else 0.0,
        "obs/fused_logprob_fallback": 1.0 if any(not ok for ok in values) else 0.0,
    }


def device_memory_gauges() -> dict:
    """Live device-memory occupancy in GiB.

    TPU/GPU backends expose allocator stats per device; the CPU backend
    returns None, so the fallback censuses ``jax.live_arrays()`` — host-side
    and approximate, but it moves when buffers leak, which is what the gauge
    is for."""
    import jax

    stats = {}
    per_device = []
    peak = []
    for device in jax.local_devices():
        mem = device.memory_stats()
        if not mem:
            per_device = []
            break
        per_device.append(mem.get("bytes_in_use", 0))
        peak.append(mem.get("peak_bytes_in_use", 0))
    if per_device:
        stats["obs/device_mem_gib"] = max(per_device) / 2**30
        if any(peak):
            stats["obs/device_mem_peak_gib"] = max(peak) / 2**30
    else:
        try:
            live = sum(
                int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.live_arrays()
            )
            stats["obs/live_array_gib"] = live / 2**30
        except Exception:  # noqa: BLE001 — gauge only
            pass
    return stats

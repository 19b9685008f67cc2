"""graftscope: device-time attribution ledger + run forensics.

PR 5's overlap pipeline and PR 10's continuous-batching engine made wall
clock a function of how well phases hide each other, but the telemetry so
far only answers "what was the overlap fraction" — not "where did every
device-second of this window go", and not "why did a killed bench run leave
nothing to diagnose". This module adds both halves:

- **Device-time attribution ledger.** Every DeviceMonitor-wrapped dispatch
  hands its output here (``track_dispatch``); a drain thread takes the
  completion-fence timestamp by blocking on the SMALLEST output leaf — off
  the dispatch path, so nothing ever blocks inside the overlap window. Host
  lanes (producer/score/train/prefetch) report their busy intervals via
  :func:`host_interval`. :meth:`GraftScope.window` folds both interval sets
  into the conservation ledger ``device_busy + host + bubble == wall`` by
  interval-union arithmetic (device time is the union of fence intervals
  clipped to the window; host time is the union of lane intervals minus the
  device union; bubble is the residual — so the identity holds by
  construction and ``obs/ledger_error_frac`` measures only clipping bugs).
- **Pipeline-bubble accounting.** Per-lane idle gaps between consecutive
  busy intervals feed ``obs/bubble_fraction`` and per-lane gap histograms;
  report.py renders the top time sinks with a suggested knob each.
- **Engine slot rollups.** The rollout engine reports slot refill waits and
  per-slot harvests (:meth:`record_refill` / :meth:`record_harvest`); the
  window rolls them into refill-latency quantiles and straggler attribution
  by prompt bucket width for the /metrics endpoint.
- **Crash-proof run forensics.** :class:`RunManifest` is the line-atomic
  (utils/jsonl) run journal bench.py / bench_smoke.py keep open: begin
  record, per-phase heartbeats, per-child rc + stderr tail, partial
  metrics, end record. A SIGKILLed run tears at most the final line, so
  ``RunManifest.read`` can always say *when* and *during what* the run
  died — bench_trajectory.py surfaces that instead of ``no_data``.

Armed by ``train.graftscope`` / ``TRLX_TPU_GRAFTSCOPE``, off by default.
Disabled, every hook is one module-dict load: no
clock read, no allocation — the serial path is byte-identical. Armed, the
ledger must never take down the run it observes: fence failures (donated
buffers already consumed by the next step) are counted and dropped, and
snapshot I/O errors disarm persistence with a warning.

Import stays jax-free (jax is imported lazily inside the drain machinery)
so :class:`RunManifest` is usable from thin driver scripts.
"""

import contextlib
import json
import os
import queue
import threading
import time
import warnings

from trlx_tpu.utils import jsonl, sanitize

# The crash-proof run journal lives in utils/manifest.py (jax-free, so that
# bench.py's parent process can journal without touching the accelerator
# stack); re-exported here, its canonical import point for the library.
from trlx_tpu.utils.manifest import MANIFEST_FILENAME, RunManifest  # noqa: F401

__all__ = [
    "GraftScope",
    "RunManifest",
    "configure",
    "shutdown",
    "armed",
    "scope",
    "host_interval",
    "lane_span",
    "SNAPSHOT_FILENAME",
    "LANES",
    "MANIFEST_FILENAME",
]

SNAPSHOT_FILENAME = "graftscope.json"
DRAIN_THREAD_NAME = "trlx-graftscope-drain"

#: host lanes of the overlapped pipeline, in ledger order.
LANES = ("train", "producer", "score", "prefetch")

#: histogram bucket edges (exporter ``le`` labels) for the /metrics endpoint.
REFILL_WAIT_MS_BUCKETS = (1.0, 5.0, 20.0, 50.0, 100.0, 250.0, 1000.0, 5000.0)
LANE_GAP_S_BUCKETS = (0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 1.0, 5.0)
STRAGGLER_STEPS_BUCKETS = (4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)
SPEC_ACCEPT_RATE_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


def _merge_intervals(intervals):
    """Union of ``(t0, t1)`` intervals → sorted disjoint list."""
    out = []
    for t0, t1 in sorted(intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out


def _clip(intervals, lo, hi):
    """Clip ``(t0, t1, *tail)`` tuples to ``[lo, hi]``, dropping empties."""
    out = []
    for item in intervals:
        t0, t1 = max(item[0], lo), min(item[1], hi)
        if t1 > t0:
            out.append((t0, t1) + tuple(item[2:]))
    return out


def _subtract(intervals, cover):
    """Total length of ``intervals`` (disjoint) not covered by ``cover``
    (disjoint, sorted) — the host-minus-device term of the ledger."""
    total = 0.0
    for a, b in intervals:
        cursor = a
        for c0, c1 in cover:
            if c1 <= cursor:
                continue
            if c0 >= b:
                break
            if c0 > cursor:
                total += c0 - cursor
            cursor = max(cursor, c1)
            if cursor >= b:
                break
        if cursor < b:
            total += b - cursor
    return total


def _pct(values, q):
    """Percentile with linear interpolation — stdlib only (no numpy import
    on the manifest-reader path)."""
    vals = sorted(values)
    if not vals:
        return 0.0
    pos = (len(vals) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def _smallest_leaf(out):
    """Cheapest completion fence for a dispatch result: the smallest array
    leaf (usually a non-donated scalar like the loss), so the drain thread
    retains as little device memory as possible while it waits."""
    import jax  # lazy: keep module import jax-free for RunManifest users

    best = None
    best_size = None
    for leaf in jax.tree_util.tree_leaves(out):
        size = getattr(leaf, "size", None)
        if size is None or not hasattr(leaf, "block_until_ready"):
            continue
        if best_size is None or size < best_size:
            best, best_size = leaf, size
    return best


class GraftScope:
    """Per-process attribution ledger: device fence intervals + host lane
    intervals + engine slot rollups, folded per phase window."""

    def __init__(self, snapshot_path=None, top_k=8, max_windows=64):
        self.snapshot_path = snapshot_path
        self.top_k = int(top_k)
        self.max_windows = int(max_windows)
        self._lock = sanitize.make_lock("GraftScope._lock")
        self._device = []  # (t0, t1, name) completed fence intervals
        self._host = []  # (t0, t1, lane)
        self._refill_wait_ms = []
        self._straggler = {}  # width -> [steps, ...] this window
        self._spec_accept = {}  # width -> [accept rates, ...] this window
        self._pool_used = []  # paged-KV pool used-block fractions this window
        self._pool_last = None  # latest paged-KV pool occupancy snapshot
        self._slot_rows = {}  # slot -> {"busy_s", "episodes", "last_width"}
        self._fences_dropped = 0
        self._pending = queue.SimpleQueue()
        self._drain = None
        self._win_t0 = time.time()
        self._windows = []
        self._programs_s = {}
        self._lane_busy_s = {lane: 0.0 for lane in LANES}
        self._lane_gap_s = {lane: 0.0 for lane in LANES}
        self._totals = {"wall_s": 0.0, "device_busy_s": 0.0, "host_s": 0.0, "bubble_s": 0.0}
        self._refill_wait_total_ms = 0.0
        self._last_samples = None
        self._snapshot_failed = False

    # ------------------------------------------------------------ ingestion

    def track_dispatch(self, name, phase, out):
        """Called by DeviceMonitor right after a wrapped dispatch returns.
        Queues (program, submit-time, smallest output leaf) for the drain
        thread — nothing here or there blocks the dispatching thread."""
        leaf = _smallest_leaf(out)
        if leaf is None:
            return
        # Always under the lock: track_dispatch runs on every dispatching
        # thread (main + producer), and close() swaps _drain out under the
        # same lock — the old lock-free fast-path read could see a
        # half-published thread object.
        with self._lock:
            if self._drain is None:
                t = threading.Thread(
                    target=self._drain_loop, name=DRAIN_THREAD_NAME, daemon=True
                )
                self._drain = t
                t.start()
        self._pending.put((name, phase, time.time(), leaf))

    def _drain_loop(self):
        while True:
            item = self._pending.get()
            if item is None:
                return
            name, _phase, t_submit, leaf = item
            try:
                leaf.block_until_ready()
            except Exception:
                # Donated/deleted buffer (the next step consumed it before
                # the fence landed) — drop the sample, never the run.
                with self._lock:
                    sanitize.race_access(self, "_fences_dropped", write=True)
                    self._fences_dropped += 1
                continue
            t_ready = time.time()
            with self._lock:
                sanitize.race_access(self, "_device", write=True)
                self._device.append((t_submit, t_ready, name))

    def host_interval(self, lane, t0, t1):
        if t1 > t0:
            with self._lock:
                sanitize.race_access(self, "_host", write=True)
                self._host.append((t0, t1, lane))

    # --------------------------------------------------------- engine slots

    def record_refill(self, slot, width, wait_s):
        """A slot was (re)admitted; ``wait_s`` is how long it sat free
        (None for the very first admission — nothing waited)."""
        with self._lock:
            row = self._slot_rows.setdefault(
                int(slot), {"busy_s": 0.0, "episodes": 0, "last_width": 0}
            )
            row["last_width"] = int(width)
            if wait_s is not None:
                self._refill_wait_ms.append(max(0.0, wait_s) * 1e3)

    def record_harvest(self, slot, width, steps, busy_s):
        """A slot finished an episode after ``steps`` decode steps spanning
        ``busy_s`` of wall clock — the occupancy-flamegraph row source and
        the straggler-attribution sample (keyed by prompt bucket width)."""
        with self._lock:
            row = self._slot_rows.setdefault(
                int(slot), {"busy_s": 0.0, "episodes": 0, "last_width": 0}
            )
            row["busy_s"] += max(0.0, busy_s)
            row["episodes"] += 1
            row["last_width"] = int(width)
            self._straggler.setdefault(int(width), []).append(int(steps))

    def record_spec_accept(self, slot, width, rate):
        """A spec-decode slot finished an episode with ``rate`` of its verify
        window positions accepted (accepted tokens / (dispatches * spec_k)) —
        the per-bucket-width accept-rate histogram sample for /metrics, same
        keying as the straggler samples."""
        with self._lock:
            self._spec_accept.setdefault(int(width), []).append(
                max(0.0, min(1.0, float(rate)))
            )

    def record_pool(self, used, cached, free, total, frag, hits_total, saved_total):
        """Paged-KV pool occupancy sample (one per engine sync boundary):
        ``used`` blocks referenced by live slots, ``cached`` warm prefix
        blocks, ``free`` unowned, out of ``total`` (incl. the trash block);
        ``frag`` is the internal-fragmentation fraction of the used span and
        the two totals are the engine's lifetime prefix-cache counters. The
        last sample of a window becomes the slot-timeline pool row."""
        with self._lock:
            denom = max(1, int(total) - 1)  # trash block is never allocatable
            self._pool_used.append(min(1.0, int(used) / denom))
            self._pool_last = {
                "used_blocks": int(used),
                "cached_blocks": int(cached),
                "free_blocks": int(free),
                "total_blocks": int(total),
                "frag_frac": float(frag),
                "prefix_hits_total": int(hits_total),
                "prefill_tokens_saved_total": int(saved_total),
            }

    # -------------------------------------------------------------- windows

    def window(self):
        """Close the current phase window: drain both interval sets, compute
        the conservation ledger, and return the gauge dict. Histogram raw
        samples go to :meth:`drain_samples` (exporter + tracker feeds)."""
        t1w = time.time()
        with self._lock:
            t0w = self._win_t0
            self._win_t0 = t1w
            sanitize.race_access(self, "_device", write=True)
            device, self._device = self._device, []
            sanitize.race_access(self, "_host", write=True)
            host, self._host = self._host, []
            refill, self._refill_wait_ms = self._refill_wait_ms, []
            straggler, self._straggler = self._straggler, {}
            spec_accept, self._spec_accept = self._spec_accept, {}
            pool_used, self._pool_used = self._pool_used, []
            pool_last = self._pool_last
            sanitize.race_access(self, "_fences_dropped")
            fences_dropped = self._fences_dropped
        wall = max(t1w - t0w, 1e-9)

        device = _clip(device, t0w, t1w)
        host = _clip(host, t0w, t1w)
        dev_union = _merge_intervals([(a, b) for a, b, _ in device])
        dev_s = float(sum(b - a for a, b in dev_union))
        host_union = _merge_intervals([(a, b) for a, b, _ in host])
        host_s = _subtract(host_union, dev_union)
        residual = wall - dev_s - host_s
        bubble_s = max(0.0, residual)
        err = abs(dev_s + host_s + bubble_s - wall) / wall

        programs = {}
        for a, b, name in device:
            programs[name] = programs.get(name, 0.0) + (b - a)
        lane_busy = {lane: 0.0 for lane in LANES}
        lane_ivs = {lane: [] for lane in LANES}
        for a, b, lane in host:
            if lane in lane_busy:
                lane_busy[lane] += b - a
                lane_ivs[lane].append((a, b))
        lane_gaps = {}
        for lane, ivs in lane_ivs.items():
            if not ivs:
                continue
            merged = _merge_intervals(ivs)
            gaps = [merged[0][0] - t0w] if merged[0][0] > t0w else []
            gaps += [n0 - p1 for (_, p1), (n0, _) in zip(merged, merged[1:])]
            if t1w > merged[-1][1]:
                gaps.append(t1w - merged[-1][1])
            lane_gaps[lane] = [g for g in gaps if g > 0.0]

        gauges = {
            "obs/ledger_device_busy_s": dev_s,
            "obs/ledger_host_s": host_s,
            "obs/ledger_bubble_s": bubble_s,
            "obs/ledger_wall_s": wall,
            "obs/ledger_error_frac": err,
            "obs/bubble_fraction": bubble_s / wall,
            "obs/graftscope_fences_dropped_total": float(fences_dropped),
        }
        for lane in LANES:
            gauges["obs/lane_busy_" + lane + "_s"] = lane_busy[lane]
        if refill:
            gauges["engine/refill_wait_ms_p50"] = _pct(refill, 0.50)
            gauges["engine/refill_wait_ms_p95"] = _pct(refill, 0.95)
            gauges["engine/refill_wait_ms_max"] = max(refill)
        if pool_used:
            gauges["engine/pool_used_frac_p50"] = _pct(pool_used, 0.50)
            gauges["engine/pool_used_frac_max"] = max(pool_used)

        top = sorted(programs.items(), key=lambda kv: -kv[1])[: self.top_k]
        record = {
            "t0": t0w,
            "t1": t1w,
            "wall_s": wall,
            "device_busy_s": dev_s,
            "host_s": host_s,
            "bubble_s": bubble_s,
            "bubble_fraction": bubble_s / wall,
            "error_frac": err,
            "lane_busy_s": lane_busy,
            "top_programs": [[name, round(sec, 6)] for name, sec in top],
        }
        if pool_last is not None:
            record["pool"] = dict(pool_last)
        with self._lock:
            self._windows.append(record)
            del self._windows[: -self.max_windows]
            for name, sec in programs.items():
                self._programs_s[name] = self._programs_s.get(name, 0.0) + sec
            for lane in LANES:
                self._lane_busy_s[lane] += lane_busy[lane]
                self._lane_gap_s[lane] += sum(lane_gaps.get(lane, []))
            self._totals["wall_s"] += wall
            self._totals["device_busy_s"] += dev_s
            self._totals["host_s"] += host_s
            self._totals["bubble_s"] += bubble_s
            self._refill_wait_total_ms += sum(refill)
            self._last_samples = {
                "lane_gaps": lane_gaps,
                "refill_wait_ms": refill,
                "straggler_steps": straggler,
                "spec_accept": spec_accept,
                "pool_used_frac": pool_used,
            }
        return gauges

    def drain_samples(self):
        """Raw samples from the last closed window (lane gaps, refill waits,
        straggler steps per width) — consumed once per window by the trainer
        to feed exporter histograms and tracker histogram records."""
        with self._lock:
            samples, self._last_samples = self._last_samples, None
        return samples

    # ---------------------------------------------------------- persistence

    def snapshot(self):
        with self._lock:
            slots = [
                {"slot": slot, **row} for slot, row in sorted(self._slot_rows.items())
            ]
            top = sorted(self._programs_s.items(), key=lambda kv: -kv[1])
            return {
                "totals": dict(self._totals),
                "bubble_fraction": (
                    self._totals["bubble_s"] / self._totals["wall_s"]
                    if self._totals["wall_s"]
                    else 0.0
                ),
                "programs_s": {k: round(v, 6) for k, v in top[: self.top_k]},
                "lane_busy_s": {k: round(v, 6) for k, v in self._lane_busy_s.items()},
                "lane_gap_s": {k: round(v, 6) for k, v in self._lane_gap_s.items()},
                "slots": slots,
                "pool": dict(self._pool_last) if self._pool_last else None,
                "refill_wait_total_ms": round(self._refill_wait_total_ms, 3),
                "fences_dropped": self._fences_dropped,
                "windows": list(self._windows),
            }

    def flush(self):
        """Persist the snapshot atomically (tmp + rename) — called per
        window flush and at teardown; I/O failure warns once and stops
        persisting, never the run."""
        if not self.snapshot_path or self._snapshot_failed:
            return
        try:
            tmp = self.snapshot_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.snapshot(), f, indent=1)
            os.replace(tmp, self.snapshot_path)
        except OSError:
            self._snapshot_failed = True
            warnings.warn(
                f"graftscope: writing {self.snapshot_path} failed — the run "
                "continues without ledger snapshots",
                stacklevel=2,
            )

    def close(self):
        """Stop the drain thread (processing anything already queued) and
        write the final snapshot."""
        with self._lock:
            drain, self._drain = self._drain, None
        if drain is not None:
            self._pending.put(None)
            drain.join(timeout=30.0)
            if not drain.is_alive():
                # Drain is gone: its accesses are fully ordered before ours.
                sanitize.race_forget(self)
        self.flush()


# Process-global scope, armed once by the trainer — a module global (the
# spans.py idiom) because the reporting sites span pipeline threads, the
# engine, and DeviceMonitor, which do not all hold a trainer reference.
_STATE = {"scope": None}


def configure(snapshot_path=None):
    """Arm the process-global scope (closing any previous one). Pass the
    graftscope.json path on the main process, None elsewhere."""
    old, _STATE["scope"] = _STATE["scope"], None
    if old is not None:
        old.close()
    _STATE["scope"] = GraftScope(snapshot_path=snapshot_path)
    return _STATE["scope"]


def shutdown():
    old, _STATE["scope"] = _STATE["scope"], None
    if old is not None:
        old.close()


def armed() -> bool:
    return _STATE["scope"] is not None


def scope():
    return _STATE["scope"]


def host_interval(lane, t0, t1):
    """Report a host-busy interval on ``lane`` — one dict load when
    disarmed (the serial path stays byte-identical)."""
    s = _STATE["scope"]
    if s is not None:
        s.host_interval(lane, t0, t1)


@contextlib.contextmanager
def lane_span(lane):
    """``with lane_span("score"):`` convenience over :func:`host_interval`
    for sites that do not already hold a start timestamp."""
    s = _STATE["scope"]
    if s is None:
        yield
        return
    t0 = time.time()
    try:
        yield
    finally:
        s.host_interval(lane, t0, time.time())

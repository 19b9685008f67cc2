"""Host spans: the one way the main path times host work.

``with trace_span(name, **args) as s:`` is the only form. Every span, armed or
not,

- enters a ``jax.profiler.TraceAnnotation(name)``: a no-op unless a profiler
  session is open, and then the span lies on the profiler's own timeline
  beside the device's operations, so an idle gap of the device reads as the
  span the host was in (benchmark/trace.py labels gaps this way);
- reads the clock twice (``time.time_ns()``, the clock the profiler stamps its
  events with); ``s.seconds`` is available after exit;
- knows the span that encloses it on the same thread (its cause) and the
  **iteration id** the loop last set with ``set_iteration(n)`` (PPO: one per
  rollout + the training on it; ILQL: one per step);
- adds its **self-seconds** (duration minus its children's) to a process-wide
  accumulator keyed by name, and a top-level span adds its duration to its
  thread's total. ``drain()`` hands both to the loop, which writes them into
  the phase-window record it already writes (``time/generate_s``,
  ``time/boundary_s``, ``time/unspanned_s`` ...). Worker threads accumulate
  under their own span names;
- leaves ONE record, the tuple ``(name, lane, t0_ns, t1_ns, id, parent id,
  iter, args)``, in a ring of the last ``RING_SPANS`` records of every thread
  (an instant leaves the same tuple with no end and no id). ``recent(t0_ns,
  t1_ns)`` hands back, as Chrome trace events, what overlaps an interval:
  the stall record's ``spans`` (observability/anomaly.py), in runs nobody
  armed.

**Only when armed** (``configure(path=...)``: ``train.trace_spans`` /
``TRLX_TPU_SPANS=1``) the same records are also kept for the file and land as
Chrome trace events (``ph:"X"``) in ``<checkpoint_dir>/spans.jsonl``, with
``pid`` = the JAX process index, ``tid`` = the thread's lane, and
``args.id`` / ``args.parent`` / ``args.iter`` beside the site's own args, so
Perfetto (https://ui.perfetto.dev opens JSONL event streams directly)
renders one lane per thread per host. The events are built and the file is
written by ``flush()`` at iteration boundaries (PPO: the end of
``post_epoch_callback``; ILQL: the log boundary), at an incident and at
``shutdown()``: one ``write(2)`` per batch of lines, never from inside a span.

File contracts, as for metrics.jsonl:

- **Crash-tolerant.** The file is opened unbuffered in O_APPEND mode and a
  batch is ONE ``write(2)`` of whole lines: a process killed mid-run can
  tear at most the final line, which ``read_spans`` tolerates, and concurrent
  appenders (multiple hosts sharing a checkpoint dir) never interleave
  mid-record. What a killed process loses is the spans since the last
  boundary.
- **Never kill the run it observes.** An I/O error disarms the writer with
  one warning instead of propagating into the train loop.

Event vocabulary (the Chrome trace-event format's subset we emit):

- ``ph:"X"`` complete spans, ``ts``/``dur`` in microseconds of wall clock
  (Unix epoch, so multi-host lanes align on real time);
- ``ph:"i"`` instants: point events (collective timeouts, watchdog fires,
  ``compile`` with the program's name and seconds);
- ``ph:"M"`` metadata: one ``thread_name`` record per (pid, tid), emitted
  at the thread's first event, so lanes carry the ``trlx-*`` names.
"""

import itertools
import json
from collections import deque
import os
import re
import threading
import time
import warnings

import jax

from trlx_tpu.observability import device_scopes
from trlx_tpu.utils import jsonl

__all__ = [
    "configure",
    "shutdown",
    "enabled",
    "flush",
    "trace_span",
    "instant",
    "set_iteration",
    "iteration",
    "drain",
    "recent",
    "install_compile_listener",
    "compile_requests",
    "take_compiles",
    "read_spans",
    "read_fleet_spans",
    "host_spans_filename",
    "SPANS_FILENAME",
    "FLEET_CLOCK_FILENAME",
    "TID_STRIDE",
    "RING_SPANS",
]

SPANS_FILENAME = "spans.jsonl"
# graftfleet clock-offset history (trlx_tpu/observability/fleet.py appends
# one record per estimate); read_fleet_spans applies the last record's
# per-host offsets when merging lanes.
FLEET_CLOCK_FILENAME = "fleet_clock.jsonl"
# Per-host tid remap stride for the merged fleet trace: synthetic tids are
# small thread counters (a handful per host), so host k's lane t becomes
# k * TID_STRIDE + t and overlapping tids across hosts can never collide
# even if a file's pid tags are missing or wrong.
TID_STRIDE = 1000
# Records the ring keeps: some 400 train steps' spans, or the last rollout and
# the steps around it; under 1 MB of tuples.
RING_SPANS = 4096
# The event jax.monitoring reports each backend compile request under (cache
# retrievals included); benchmark/harness.py's CompileLog listens to the same.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_HOST_SPANS_RE = re.compile(r"^spans\.host(\d+)\.jsonl$")


def host_spans_filename(process_index: int) -> str:
    """Per-host spans file for fleet federation: ``spans.host<k>.jsonl``.
    Unlike the shared SPANS_FILENAME (every host appends to one file), one
    file per host survives a non-shared filesystem and lets the merge
    reader tolerate a torn tail PER HOST."""
    return f"spans.host{int(process_index)}.jsonl"


def _lane() -> int:
    """The calling thread's lane: a synthetic id per thread OBJECT, stored
    thread-locally. Raw thread.ident would be simpler but the OS reuses
    idents: a rollout producer starting after an epoch's prefetch thread
    exits can inherit its ident, and the stale thread_name metadata would
    then mislabel (and merge) the two lanes in the viewer."""
    lane = getattr(_LOCAL, "lane", None)
    if lane is None:
        lane = _LOCAL.lane = next(_LANES)
        # A lane is named before its first record enters the ring, so the
        # newest RING_SPANS names cover every lane the ring can hold.
        with _ACC_LOCK:  # once a thread: two threads' first spans may meet here
            _LANE_NAMES[lane] = threading.current_thread().name
            while len(_LANE_NAMES) > RING_SPANS:
                del _LANE_NAMES[next(iter(_LANE_NAMES))]
    return lane


def _lane_event(lane: int, pid: int) -> dict:
    return {"name": "thread_name", "ph": "M", "pid": pid, "tid": lane,
            "args": {"name": _LANE_NAMES.get(lane, "?")}}


def _event(record, pid: int) -> dict:
    """The Chrome trace event of one record: a span's ``ph:"X"`` or, where the
    record has no end, an instant's ``ph:"i"``."""
    name, lane, t0, t1, span_id, parent, iteration, args = record
    if t1 is None:
        event = {"name": name, "ph": "i", "s": "t", "pid": pid, "tid": lane, "ts": t0 // 1000}  # thread-scoped
        if args:
            event["args"] = args
        return event
    return {
        "name": name,
        "ph": "X",
        "pid": pid,
        "tid": lane,
        "ts": t0 // 1000,
        "dur": max(0, (t1 - t0) // 1000),
        "args": dict(args, id=span_id, parent=parent, iter=iteration),
    }


class SpanTracer:
    """Keeps the records of an armed run and appends them, as Chrome trace
    events, to one JSONL file a batch at a time."""

    def __init__(self, path: str, process_index: int = 0):
        self.path = path
        self.pid = int(process_index)
        self._file = jsonl.open_line_atomic(path)
        self._records = []  # list.append is atomic under the GIL
        self._named = set()  # lanes whose thread_name record this file has

    def record(self, record):
        self._records.append(record)

    def flush(self):
        records, self._records = self._records, []
        if not records:
            return
        events = []
        for record in records:
            if record[1] not in self._named:  # one thread_name per lane, ahead of its first event
                self._named.add(record[1])
                events.append(_lane_event(record[1], self.pid))
            events.append(_event(record, self.pid))
        try:
            # ONE write call per batch of whole lines -> line-atomic under O_APPEND.
            self._file.write("".join(json.dumps(e) + "\n" for e in events).encode("utf-8"))
        except (OSError, ValueError):
            # ValueError: write on a closed file (late flush during teardown).
            # Tracing must never take down the run it observes: disarm.
            _disarm_on_error(self)

    def close(self):
        self.flush()
        try:
            self._file.close()
        except OSError:
            pass


# Process-global state, armed once by the trainer. Module globals (not trainer
# attributes) because the emitting sites span orchestrators, pipeline
# threads, and resilience guards that do not all hold a trainer reference.
_STATE = {"tracer": None, "iter": 0, "compiles": 0, "compiles_taken": 0, "listening": False}
# .stack: this thread's open spans, outermost first; .top_ns: nanoseconds it
# spent inside top-level spans since it last drained; .lane: its lane id
_LOCAL = threading.local()
_IDS = itertools.count(1)  # next() is atomic under the GIL
_LANES = itertools.count(1)
_LANE_NAMES = {}  # lane -> thread name, the newest RING_SPANS lanes
_RING = deque(maxlen=RING_SPANS)  # the last records of every thread; append is atomic under the GIL
_RING_APPEND = _RING.append
_ACC_LOCK = threading.Lock()
_SELF_S = {}  # span name -> self-seconds since the last drain(), every thread's


def _disarm_on_error(tracer):
    if _STATE["tracer"] is tracer:
        _STATE["tracer"] = None
        warnings.warn(
            f"span tracing disabled: writing {tracer.path} failed "
            "(disk full / closed file?): the run continues untraced",
            stacklevel=3,
        )


def configure(path=None, process_index=0):
    """Arm (path given) or disarm (path=None) the span file.

    ``process_index`` becomes the trace's ``pid`` lane group: pass
    ``jax.process_index()`` so multi-host runs sharing a checkpoint dir get
    one lane group per host."""
    device_scopes.flush(closing=True)  # a traced run's table, if no boundary wrote it
    old, _STATE["tracer"] = _STATE["tracer"], None
    if old is not None:
        old.close()
    if path:
        _STATE["tracer"] = SpanTracer(path, process_index=process_index)


def shutdown():
    configure(None)


def enabled() -> bool:
    return _STATE["tracer"] is not None


def flush():
    """Write the spans kept since the last flush (armed only). The loops call
    this at their iteration boundary, never from inside a step. Armed or not,
    the boundary after a profiler session has closed also writes
    ``device_scopes.json`` (device_scopes.py)."""
    tracer = _STATE["tracer"]
    if tracer is not None:
        tracer.flush()
    device_scopes.flush()


def set_iteration(n: int):
    """The id every span records until the next call, on every thread."""
    _STATE["iter"] = int(n)


def iteration() -> int:
    return _STATE["iter"]


class _Span:
    __slots__ = ("name", "args", "id", "parent", "iter", "t0", "t1", "_children_ns", "_annotation")

    def __init__(self, name, args):
        self.name = name
        self.args = args
        self.t1 = None

    def __enter__(self):
        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self.parent = stack[-1] if stack else None
        self.id = next(_IDS)
        self.iter = _STATE["iter"]
        self._children_ns = 0
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.time_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        _LOCAL.stack.pop()
        duration = self.t1 - self.t0
        with _ACC_LOCK:
            _SELF_S[self.name] = _SELF_S.get(self.name, 0.0) + (duration - self._children_ns) * 1e-9
        if self.parent is None:
            _LOCAL.top_ns = getattr(_LOCAL, "top_ns", 0) + duration
        else:
            self.parent._children_ns += duration
        # the record, inline: a call or two less on the path every span takes
        try:
            lane = _LOCAL.lane
        except AttributeError:
            lane = _lane()
        parent = self.parent
        record = (self.name, lane, self.t0, self.t1, self.id, None if parent is None else parent.id, self.iter,
                  self.args if exc_type is None else dict(self.args, error=exc_type.__name__))
        _RING_APPEND(record)
        tracer = _STATE["tracer"]
        if tracer is not None:
            tracer.record(record)
        return False

    @property
    def start_s(self) -> float:
        """Start, in seconds of wall clock (``time.time()``'s scale)."""
        return self.t0 * 1e-9

    @property
    def end_s(self) -> float:
        return self.t1 * 1e-9

    @property
    def seconds(self) -> float:
        """Duration; readable after exit."""
        return (self.t1 - self.t0) * 1e-9


def trace_span(name: str, **args):
    """``with trace_span("rollout/decode", step=n) as s:`` times one interval
    of host work on the calling thread (module docstring)."""
    return _Span(name, args)


def drain() -> dict:
    """Hand over and reset the accumulators: ``{"self_s": {name: self-seconds
    of every span that ended since the last drain, on any thread}, "top_s":
    seconds the CALLING thread spent inside its top-level spans}``."""
    with _ACC_LOCK:
        self_s = dict(_SELF_S)
        _SELF_S.clear()
    top_ns, _LOCAL.top_ns = getattr(_LOCAL, "top_ns", 0), 0
    return {"self_s": self_s, "top_s": top_ns * 1e-9}


def instant(name: str, **args):
    """Emit a point event (watchdog fired, collective timed out, incident, a
    compile, a late tick): into the ring always, into the file when armed."""
    record = (name, _lane(), time.time_ns(), None, None, None, _STATE["iter"], args)
    _RING_APPEND(record)
    tracer = _STATE["tracer"]
    if tracer is not None:
        tracer.record(record)


def recent(t0_ns: int, t1_ns: int, pid: int = 0) -> list:
    """The ring's records that overlap ``[t0_ns, t1_ns]`` (a span by any part
    of it, an instant by its stamp), every thread's, as the file's own Chrome
    trace events, oldest first, each lane's ``thread_name`` ahead of them.
    Spans still open are not in the ring: ask after the span of interest has
    ended."""
    records = [r for r in tuple(_RING) if r[2] <= t1_ns and (r[2] if r[3] is None else r[3]) >= t0_ns]
    lanes = sorted({r[1] for r in records})
    return [_lane_event(lane, pid) for lane in lanes] + [_event(r, pid) for r in records]


def _on_duration(event, duration, **kw):
    if event == COMPILE_EVENT:
        _STATE["compiles"] += 1
        instant("compile", fun_name=str(kw.get("fun_name", "?")), seconds=float(duration))


def install_compile_listener():
    """Count the process's backend compile requests (jax.monitoring) and mark
    each as a ``compile`` instant; once per process, whoever calls."""
    if not _STATE["listening"]:
        _STATE["listening"] = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_requests() -> int:
    """Compile requests the listener has seen, in all (a window's count is
    the difference of two readings)."""
    return _STATE["compiles"]


def take_compiles() -> int:
    """Compile requests since the last call (the step record's ``obs/compiles``)."""
    seen = _STATE["compiles"]
    n, _STATE["compiles_taken"] = seen - _STATE["compiles_taken"], seen
    return n


def read_spans(path: str):
    """Parse a spans.jsonl, tolerating a torn final line — the shared
    utils.jsonl contract (a killed writer tears at most the tail; mid-file
    corruption still raises)."""
    return jsonl.read_jsonl(path)


def _last_clock_record(checkpoint_dir: str):
    """Freshest clock-offset record (or None): fleet_clock.jsonl is an
    append-only history, last line wins. Torn tails are routine post-kill."""
    path = os.path.join(checkpoint_dir, FLEET_CLOCK_FILENAME)
    if not os.path.exists(path):
        return None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        records = jsonl.read_jsonl(path)
    return records[-1] if records else None


def read_fleet_spans(checkpoint_dir: str) -> dict:
    """Merge every host's span file into ONE Chrome trace with per-host
    process lanes and a stated clock-alignment bound.

    - ``spans.host<k>.jsonl`` files (graftfleet armed) are each read with
      per-file torn-tail tolerance; a plain ``spans.jsonl`` (fleet off, or a
      pre-fleet run) merges as whatever pids its events carry.
    - Every event from host k is forced onto pid k with its tid remapped to
      ``k * TID_STRIDE + tid`` — overlapping synthetic tids across hosts can
      never collide in the merged view.
    - When a ``fleet_clock.jsonl`` estimate exists, host k's timestamps are
      shifted by −offset_k into host 0's clock frame, and each host lane's
      process_name states its offset and the alignment-error bound
      (estimate uncertainty + drift bound — see fleet.py).

    Returns ``{"traceEvents": [...], "hosts": [...], "clock": {...} | None,
    "alignment_error_s": float}``.
    """
    checkpoint_dir = os.path.abspath(checkpoint_dir)
    files = []  # (host_index or None, path)
    try:
        names = sorted(os.listdir(checkpoint_dir))
    except OSError:
        names = []
    for name in names:
        m = _HOST_SPANS_RE.match(name)
        if m:
            files.append((int(m.group(1)), os.path.join(checkpoint_dir, name)))
    if not files and SPANS_FILENAME in names:
        files.append((None, os.path.join(checkpoint_dir, SPANS_FILENAME)))

    clock = _last_clock_record(checkpoint_dir)
    offsets = list(clock.get("offsets_s", [])) if clock else []
    bound = 0.0
    if clock:
        bound = float(clock.get("uncertainty_s", 0.0)) + float(clock.get("drift_s", 0.0))

    events, hosts = [], []
    for host, path in sorted(files, key=lambda kv: (kv[0] is None, kv[0])):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # torn tails tolerated PER FILE
            try:
                host_events = jsonl.read_jsonl(path)
            except (OSError, ValueError):
                continue
        if host is None:
            # Legacy shared file: trust the recorded pids, no remap.
            events.extend(host_events)
            hosts.extend(sorted({e.get("pid", 0) for e in host_events}))
            continue
        hosts.append(host)
        shift_us = int(offsets[host] * 1e6) if host < len(offsets) else 0
        for event in host_events:
            event = dict(event)
            event["pid"] = host
            if "tid" in event:
                event["tid"] = host * TID_STRIDE + int(event["tid"])
            if shift_us and "ts" in event:
                event["ts"] = int(event["ts"]) - shift_us
            events.append(event)
        label = f"host{host}"
        if host < len(offsets):
            label += f" (clock offset {offsets[host] * 1e3:+.3f}ms ± {bound * 1e3:.3f}ms)"
        events.append(
            {"name": "process_name", "ph": "M", "pid": host, "args": {"name": label}}
        )
    return {
        "traceEvents": events,
        "hosts": sorted(set(hosts)),
        "clock": clock,
        "alignment_error_s": bound,
    }

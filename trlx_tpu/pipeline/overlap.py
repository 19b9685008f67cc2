"""Overlapped rollout/train pipeline primitives.

The PPO loop has three phases — device generation, host reward scoring, and
the jitted train steps — that the serial schedule runs back-to-back, so the
accelerator idles during reward scoring and the host idles during training.
The pipeline-RLHF line of work (PAPERS.md: OPPO, PipelineRL) recovers most of
that dead time by overlapping the phases; this module provides the
machinery:

- ``PhaseTimer``     thread-safe per-phase wall accumulators feeding the
                     ``time/rollout_s`` / ``time/score_s`` / ``time/train_s``
                     / ``time/overlap_fraction`` metrics.
- ``ScoreWorker``    a single background thread running host scoring
                     (decode + reward_fn) off the rollout loop, fed by a
                     bounded FIFO queue.
- ``PrefetchIterator`` / ``SerialFeed``
                     batch feed for the epoch loop: the host→device
                     ``put_batch`` for batch k+1 runs while ``train_step(k)``
                     executes.
- ``RolloutProducer`` double-buffered experience production with a
                     counter-based staleness gate (``method.max_staleness``).

Everything here is plain ``threading`` over the existing phase code — no new
dependencies, and ALL of it is off unless the method config sets
``rollout_overlap`` / ``max_staleness`` (the serial schedule stays the
byte-compatible default).

Process scope: the producer here double-buffers WITHIN one process, but
that process may be one controller of a multi-host world. Every host runs
the identical producer schedule (chunk boundaries and handoff points are
pure functions of the config and device-synced values), and the
phase-boundary fingerprint checks (resilience.distributed) turn any
divergence into a named HostDesync rather than a hung collective — which
is what lets the multi-host guard in trainer/ppo.py stay lifted. The
disaggregated rollout/learner fleet (trlx_tpu/fleet,
method.fleet_disaggregate) runs the same staleness gate — shared via
:func:`staleness_gate_open` — across two separate jobs (each possibly its
own multi-host submesh) coupled by an episode stream and a versioned
weight broadcast.
"""

import queue
import threading
import time
from collections import deque
from contextlib import contextmanager

from trlx_tpu.observability.spans import trace_span
from trlx_tpu.utils import sanitize


def staleness_gate_open(index: int, consumed: int, max_staleness: int) -> bool:
    """THE staleness gate, shared by RolloutProducer (in-process double
    buffering) and the fleet rollout worker (cross-job episode stream):
    production of store/batch ``index`` may start iff the consumer is at most
    ``max_staleness`` iterations behind it. Pure counters — deterministic, so
    every participant derives the identical schedule. At max_staleness=0 the
    producer and consumer strictly alternate: the exact serial schedule."""
    return index - consumed <= max(0, int(max_staleness))


class PhaseTimer:
    """Thread-safe per-phase wall accumulators.

    Phases: ``rollout`` (device generation + device scoring + store pushes,
    blocked wall), ``score`` (host decode + reward_fn wall, possibly on the
    worker thread), ``train`` (main-thread wall around dispatched train
    steps, eval excluded). ``window()`` drains the accumulators and derives
    ``overlap_fraction`` — the share of phase seconds hidden behind other
    phases within the window's wall clock: ~0 when the phases ran serially,
    > 0 when they overlapped (they summed to more than the wall)."""

    PHASES = ("rollout", "score", "train")

    def __init__(self):
        self._lock = threading.Lock()
        self._acc = {p: 0.0 for p in self.PHASES}
        self._t0 = time.time()

    def add(self, phase: str, seconds: float):
        with self._lock:
            self._acc[phase] = self._acc.get(phase, 0.0) + float(seconds)

    @contextmanager
    def timed(self, phase: str):
        t0 = time.time()
        try:
            yield
        finally:
            self.add(phase, time.time() - t0)

    def window(self) -> dict:
        """Per-phase seconds since the previous window() + the derived
        overlap fraction; resets the accumulators."""
        now = time.time()
        with self._lock:
            acc = dict(self._acc)
            wall = now - self._t0
            for p in self._acc:
                self._acc[p] = 0.0
            self._t0 = now
        total = sum(acc.values())
        overlap = max(0.0, min(1.0, (total - wall) / total)) if total > 1e-9 else 0.0
        out = {f"time/{p}_s": acc.get(p, 0.0) for p in self.PHASES}
        out["time/window_wall_s"] = wall
        out["time/overlap_fraction"] = overlap
        return out


class ScoreWorker:
    """Background host scoring: one worker thread, bounded FIFO in-queue.

    - FIFO by construction: results come back in submission order, so the
      store push order — and the orchestrator's reward-call numbering that
      the retry/fault bookkeeping keys on — is identical to the serial path.
    - Bounded: ``submit`` blocks once ``depth`` chunks are queued unscored
      (backpressure caps the host memory held in decoded-but-unscored
      chunks).
    - Exceptions from the scoring fn (e.g. a reward_fn timeout after its
      retries) are re-raised by ``result()`` on the caller thread; the
      worker itself keeps draining, so ``close()`` never deadlocks."""

    _STOP = object()

    def __init__(self, fn, depth: int = 2):
        self._fn = fn
        self._in = queue.Queue(maxsize=max(1, int(depth)))
        self._out = queue.Queue()
        self.busy_s = 0.0  # wall inside fn; written only by the worker thread
        self._thread = threading.Thread(
            target=self._run, name="trlx-score-worker", daemon=True
        )
        self._thread.start()

    def _run(self):
        while True:
            item = self._in.get()
            if item is self._STOP:
                return
            t0 = time.time()
            try:
                with trace_span("score/host"):
                    self._out.put(("ok", self._fn(item)))
            except BaseException as e:  # noqa: BLE001 — delivered via result()
                self._out.put(("err", e))
            finally:
                t1 = time.time()
                sanitize.race_access(self, "busy_s", write=True)
                self.busy_s += t1 - t0

    def submit(self, item):
        self._in.put(item)

    def ready(self) -> bool:
        return not self._out.empty()

    def result(self, timeout=None):
        kind, payload = self._out.get(timeout=timeout)
        if kind == "err":
            raise payload
        return payload

    def close(self):
        """Signal and join. Safe on error paths: queued items still drain
        (their results land on the unbounded out-queue, unread), then the
        worker exits."""
        self._in.put(self._STOP)
        self._thread.join()
        # Joined: busy_s ownership transfers to the caller (the orchestrator
        # reads it for the reward-phase accounting) — a real happens-before
        # edge the lockset model cannot see.
        sanitize.race_forget(self)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()


class SerialFeed:
    """Depth-0 stand-in for PrefetchIterator: the transform runs inline on
    ``__next__`` — the exact serial schedule — behind the same close()
    protocol, so the learn loop has one feed interface."""

    def __init__(self, source, transform=None):
        self._it = iter(source)
        self._transform = transform if transform is not None else (lambda x: x)

    def __iter__(self):
        return self

    def __next__(self):
        return self._transform(next(self._it))

    def close(self):
        pass


class PrefetchIterator:
    """Run ``transform`` (host→device ``put_batch``) up to ``depth`` items
    ahead on a background thread, so the transfer for batch k+1 overlaps the
    train step on batch k.

    Ordering is the source iterable's; exhaustion raises StopIteration
    exactly once; a transform/source exception re-raises at the
    corresponding ``__next__``. ``close()`` is idempotent and unblocks+joins
    the worker even when the consumer abandons mid-epoch (the preemption
    return paths)."""

    def __init__(self, source, transform=None, depth: int = 1):
        self._transform = transform if transform is not None else (lambda x: x)
        self._q = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(
            target=self._run, args=(iter(source),), name="trlx-prefetch", daemon=True
        )
        self._thread.start()

    def _put(self, item) -> bool:
        # Bounded put that close() can always unblock: poll the stop flag
        # instead of parking forever on a full queue.
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, it):
        try:
            for item in it:
                if self._stop.is_set():
                    return
                with trace_span("prefetch/stage"):
                    staged = ("ok", self._transform(item))
                if not self._put(staged):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at __next__
            self._put(("err", e))
            return
        self._put(("end", None))

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        kind, payload = self._q.get()
        if kind == "end":
            self._done = True
            raise StopIteration
        if kind == "err":
            self._done = True
            raise payload
        return payload

    def close(self):
        self._stop.set()
        try:  # drain so a blocked _put wakes and sees the stop flag
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10)
        self._done = True


class RolloutProducer:
    """Double-buffered experience production with an on-policy staleness
    gate.

    A background thread fills a FRESH rollout store for training iteration n
    (n >= 1; iteration 0's store is the pre-learn fill) while the trainer
    consumes iteration n-1's. The gate is pure counters — deterministic, so
    every host in a pod would run the identical chunk schedule:

        production of store n may START  ⇔  n - consumed <= max_staleness

    - ``max_staleness=0``: store n only starts once n-1 iterations are fully
      consumed; the trainer then blocks in ``next_store()`` for the whole
      phase — today's fully-on-policy schedule, merely running on the
      producer thread (and therefore bitwise-identical in results).
    - ``max_staleness=S``: the producer runs up to S iterations ahead off
      the latest param SNAPSHOT handed over at each consume boundary — the
      jitted train step donates the TrainState buffers, so a background
      reader of the live state would touch deleted arrays.

    ``produce(store, index, snapshot, staleness, stop_fn)`` receives the
    store's staleness (index - consumed at production start, in training
    iterations) for the per-sample staleness column, and a ``stop_fn`` to
    poll between chunks so ``shutdown()`` drains promptly. A producer
    exception is re-raised (same object) by the next ``next_store()``."""

    def __init__(self, produce, new_store, max_staleness: int = 0):
        self._produce = produce
        self._new_store = new_store
        self.max_staleness = max(0, int(max_staleness))
        self._cv = sanitize.make_condition("RolloutProducer._cv")
        self._consumed = 0  # training iterations fully consumed
        self._ready = deque()  # completed stores, FIFO
        # Per-completed-store lineage (bounded): the store's index, the
        # staleness it was produced at, and the weight version of the
        # snapshot it read (None when reading live state). The health
        # monitor's per-chunk records carry the same facts per chunk; this
        # is the producer-side summary the incident thread dumps can be
        # cross-referenced against.
        self.history = deque(maxlen=64)
        self._snapshot = None
        self._error = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="trlx-rollout-producer", daemon=True
        )

    def start(self, snapshot=None):
        # Under the cv even though the thread starts just below: Thread.start
        # is the happens-before edge for __init__ writes only — this write
        # races with the worker's first snapshot read without it.
        with self._cv:
            sanitize.race_access(self, "_snapshot", write=True)
            self._snapshot = snapshot
        self._thread.start()
        return self

    def _should_stop(self) -> bool:
        return self._stop.is_set()

    def _run(self):
        index = 1
        while True:
            with self._cv:
                while not self._stop.is_set() and not staleness_gate_open(
                    index, self._consumed, self.max_staleness
                ):
                    self._cv.wait(timeout=0.5)
                if self._stop.is_set():
                    return
                sanitize.race_access(self, "_snapshot")
                sanitize.race_access(self, "_consumed")
                snapshot = self._snapshot
                staleness = index - self._consumed
            store = self._new_store()
            try:
                with trace_span("rollout/produce", index=index, staleness=staleness):
                    self._produce(store, index, snapshot, staleness, self._should_stop)
            except BaseException as e:  # noqa: BLE001 — re-raised in next_store()
                with self._cv:
                    sanitize.race_access(self, "_error", write=True)
                    self._error = e
                    self._cv.notify_all()
                return
            with self._cv:
                if self._stop.is_set():
                    return  # aborted mid-phase: the partial store is dropped
                sanitize.race_access(self, "_ready", write=True)
                self._ready.append(store)
                self.history.append(
                    {
                        "index": index,
                        "staleness": staleness,
                        "version": (
                            snapshot.get("version")
                            if isinstance(snapshot, dict)
                            else None
                        ),
                    }
                )
                self._cv.notify_all()
            index += 1

    def consume_done(self, snapshot=None):
        """Mark one training iteration fully consumed, optionally handing
        the producer the boundary snapshot to generate the next store from."""
        with self._cv:
            sanitize.race_access(self, "_consumed", write=True)
            self._consumed += 1
            if snapshot is not None:
                sanitize.race_access(self, "_snapshot", write=True)
                self._snapshot = snapshot
            self._cv.notify_all()

    def next_store(self, timeout=None):
        """Block until the next completed store (FIFO). Re-raises a producer
        failure; raises TimeoutError past ``timeout`` seconds."""
        deadline = None if timeout is None else time.time() + timeout
        with self._cv:
            while True:
                sanitize.race_access(self, "_ready")
                if self._ready:
                    sanitize.race_access(self, "_ready", write=True)
                    return self._ready.popleft()
                sanitize.race_access(self, "_error")
                if self._error is not None:
                    e, self._error = self._error, None
                    raise e
                if not self._thread.is_alive():
                    raise RuntimeError(
                        "rollout producer thread exited without a completed store"
                    )
                if deadline is not None and time.time() >= deadline:
                    raise TimeoutError("timed out waiting for the rollout producer")
                self._cv.wait(timeout=0.5)

    @property
    def pending(self) -> int:
        with self._cv:
            return len(self._ready)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def shutdown(self, timeout: float = 60.0):
        """Stop and join. A mid-phase producer exits at its next between-chunk
        stop poll; the thread is a daemon, so a truly wedged produce fn (e.g.
        hung user code past its own timeouts) cannot block process exit."""
        with self._cv:
            self._stop.set()
            self._cv.notify_all()
        if self._thread.ident is not None and self._thread.is_alive():
            self._thread.join(timeout=timeout)
        if not self._thread.is_alive():
            # Joined (or never started): remaining state is single-owner.
            sanitize.race_forget(self)

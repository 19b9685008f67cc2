"""Observability layer (trlx_tpu/observability/): span tracing, device
telemetry, anomaly-triggered incident capture, and the report renderer.

Unit tier: SpanTracer lane/metadata semantics (including OS-ident reuse),
torn-tail + concurrent-writer file contracts, AnomalyDetector baseline math,
IncidentCapture bundle contents and budget, DeviceMonitor compiled-cost
capture and the MFU arithmetic cross-check against the formula by hand.

Integration tier (CPU): the acceptance run — a short overlapped PPO run at
max_staleness=1 with spans + telemetry + anomaly armed and the
``slow_step`` fault drill produces a Perfetto-loadable spans.jsonl with the
producer/score/train threads on distinct lanes and visible overlap, MFU
gauges in metrics.jsonl, an incident bundle with thread stacks, and a
report that renders every section.
"""

import json
import os
import sys
import threading
import time
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import trlx_tpu  # noqa: E402
from randomwalks import base_config, generate_random_walks  # noqa: E402
from trlx_tpu.observability import anomaly as obs_anomaly  # noqa: E402
from trlx_tpu.observability import devicemon, report  # noqa: E402
from trlx_tpu.observability import spans as obs_spans  # noqa: E402


@pytest.fixture(autouse=True)
def _span_isolation():
    """The tracer is a process global armed by trainers/tests — always disarm
    so one test's spans.jsonl (in a deleted tmp_path) never leaks forward."""
    obs_spans.drain()
    yield
    obs_spans.shutdown()
    obs_anomaly.register_emergency(None)


# ------------------------------------------------------------------- spans


def _xs(events, name=None):
    return [e for e in events if e["ph"] == "X" and (name is None or e["name"] == name)]


def test_unarmed_span_times_and_accumulates_without_a_file(tmp_path):
    """Unarmed is not off: a span still reads the clock twice, knows its
    parent and feeds the accumulators; only the file is missing."""
    obs_spans.shutdown()
    obs_spans.drain()
    assert not obs_spans.enabled()
    with obs_spans.trace_span("x", step=1) as s:
        time.sleep(0.005)
    assert 0.004 < s.seconds < 1.0 and s.parent is None and s.args == {"step": 1}
    assert abs(s.end_s - time.time()) < 5.0  # wall clock, the profiler's
    obs_spans.instant("w")  # into the ring; a file only when armed
    obs_spans.flush()  # nothing to write, nowhere to write it
    acc = obs_spans.drain()
    assert acc["self_s"] == {"x": pytest.approx(s.seconds)}
    assert acc["top_s"] == pytest.approx(s.seconds)
    assert obs_spans.drain() == {"self_s": {}, "top_s": 0.0}  # drained
    assert not hasattr(obs_spans, "complete")  # the retroactive form is gone


def test_span_lanes_survive_os_thread_ident_reuse(tmp_path):
    """Sequential threads commonly REUSE the OS thread ident; lanes are keyed
    by synthetic per-thread-object tids so each thread still gets its own
    lane + thread_name metadata (the bug this guards: a rollout producer
    inheriting a dead prefetch thread's ident and lane label)."""
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    with obs_spans.trace_span("main/work", step=1):
        pass

    def worker():
        with obs_spans.trace_span("bg/work"):
            time.sleep(0.01)

    for name in ("lane-a", "lane-b"):  # b starts only after a exits
        t = threading.Thread(target=worker, name=name)
        t.start()
        t.join()
    obs_spans.instant("tick", step=2)
    obs_spans.shutdown()

    events = obs_spans.read_spans(path)
    meta = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert len(meta) == 3  # three threads -> three lanes, no merging
    assert {"MainThread", "lane-a", "lane-b"} <= set(meta.values())
    assert len({e["tid"] for e in _xs(events, "bg/work")}) == 2
    (main_span,) = _xs(events, "main/work")
    assert main_span["args"]["step"] == 1
    assert meta[main_span["tid"]] == "MainThread"
    instant = next(e for e in events if e["ph"] == "i")
    assert instant["s"] == "t" and instant["tid"] == main_span["tid"]


def test_span_exit_on_exception_annotates_error(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    with pytest.raises(ValueError):
        with obs_spans.trace_span("outer"):
            with obs_spans.trace_span("rollout/decode", step=3):
                raise ValueError("boom")
    with obs_spans.trace_span("after"):
        pass
    obs_spans.shutdown()
    events = obs_spans.read_spans(path)
    (inner,) = _xs(events, "rollout/decode")
    (outer,) = _xs(events, "outer")
    assert inner["args"]["step"] == 3 and inner["args"]["error"] == "ValueError"
    assert outer["args"]["error"] == "ValueError" and inner["args"]["parent"] == outer["args"]["id"]
    # the stack unwound: the next span is top-level again and carries no error
    (after,) = _xs(events, "after")
    assert after["args"]["parent"] is None and "error" not in after["args"]


def test_span_file_torn_tail_tolerated_like_metrics(tmp_path):
    """Both JSONL writers (Tracker's metrics.jsonl, SpanTracer's spans.jsonl)
    share one reader contract: a writer killed mid-append tears at most the
    final line, which readers drop with a warning; mid-file garbage raises."""
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    for i in range(3):
        with obs_spans.trace_span("train/step", step=i):
            pass
    obs_spans.shutdown()
    with open(path, "ab") as f:
        f.write(b'{"name": "train/step", "ph": "X", "ts": 12')  # torn mid-record
    with pytest.warns(UserWarning, match="torn final record"):
        events = obs_spans.read_spans(path)
    assert sum(e["ph"] == "X" for e in events) == 3

    # the SAME torn file mid-stream is corruption, not a tear
    with open(path, "ab") as f:
        f.write(b'\n{"name": "later", "ph": "i", "ts": 13}\n')
    with pytest.raises(json.JSONDecodeError):
        obs_spans.read_spans(path)


def test_concurrent_span_writers_never_interleave(tmp_path):
    """Line-atomicity under contention: many threads making spans while
    others flush (unbuffered O_APPEND, one write(2) per batch of whole lines)
    must yield a file where EVERY line parses and no span is lost or
    written twice."""
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    n_threads, n_spans = 8, 200

    def hammer(k):
        for i in range(n_spans):
            with obs_spans.trace_span("stress/span", writer=k, i=i):
                pass
            if i % 50 == k:
                obs_spans.flush()

    threads = [threading.Thread(target=hammer, args=(k,), name=f"stress-{k}") for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    obs_spans.shutdown()

    with open(path, "rb") as f:
        lines = [ln for ln in f.read().split(b"\n") if ln.strip()]
    events = [json.loads(ln) for ln in lines]  # raises if any line tore
    xs = _xs(events)
    assert len(xs) == n_threads * n_spans
    assert len({e["args"]["id"] for e in xs}) == len(xs)  # ids are unique across threads
    assert len({e["tid"] for e in xs}) == n_threads
    acc = obs_spans.drain()
    assert set(acc["self_s"]) == {"stress/span"} and acc["top_s"] == 0.0  # worker threads' names; not this thread's wall


def test_span_writer_disarms_on_io_error_instead_of_raising(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    # simulate the disk going away mid-run: close the fd under the tracer
    obs_spans._STATE["tracer"]._file.close()
    obs_spans.instant("after_close")
    with pytest.warns(UserWarning, match="span tracing disabled"):
        obs_spans.flush()
    assert not obs_spans.enabled()
    obs_spans.instant("noop")  # disarmed: silent no-op, run continues
    with obs_spans.trace_span("still/timed") as s:
        pass
    assert s.seconds >= 0.0


@pytest.mark.parametrize("where", ["main", "worker"])
def test_span_records_parent_and_iteration(tmp_path, where):
    """A span names the span that encloses it ON ITS OWN THREAD and the
    iteration the loop last set, which worker threads share."""
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)

    def work():
        with obs_spans.trace_span("parent"):
            with obs_spans.trace_span("child"):
                with obs_spans.trace_span("grandchild"):
                    pass
            with obs_spans.trace_span("sibling"):
                pass

    obs_spans.set_iteration(7)
    assert obs_spans.iteration() == 7
    with obs_spans.trace_span("main/outer"):  # open on the main thread all along
        if where == "main":
            work()
        else:
            t = threading.Thread(target=work, name="trlx-worker")
            t.start()
            t.join()
    obs_spans.set_iteration(8)
    with obs_spans.trace_span("next"):
        pass
    obs_spans.shutdown()
    by_name = {e["name"]: e["args"] for e in _xs(obs_spans.read_spans(path))}
    outer = by_name["main/outer"]["id"]
    # another thread's open span is not a cause
    assert by_name["parent"]["parent"] == (outer if where == "main" else None)
    assert by_name["child"]["parent"] == by_name["parent"]["id"]
    assert by_name["grandchild"]["parent"] == by_name["child"]["id"]
    assert by_name["sibling"]["parent"] == by_name["parent"]["id"]
    assert {by_name[n]["iter"] for n in ("main/outer", "parent", "child", "grandchild", "sibling")} == {7}
    assert by_name["next"]["iter"] == 8 and by_name["next"]["parent"] is None


def test_span_self_time_and_accumulators_drain_into_the_record():
    """Self time is duration minus children; the accumulators sum to the
    top-level wall; a worker thread accumulates under its own names and does
    not count towards the draining thread's top-level seconds."""
    obs_spans.drain()

    def work():
        with obs_spans.trace_span("score/host"):
            time.sleep(0.01)

    with obs_spans.trace_span("p") as p:
        with obs_spans.trace_span("c") as c1:
            time.sleep(0.01)
        with obs_spans.trace_span("c") as c2:
            time.sleep(0.01)
        t = threading.Thread(target=work)
        t.start()
        t.join()
    acc = obs_spans.drain()
    children = c1.seconds + c2.seconds
    assert acc["self_s"]["c"] == pytest.approx(children)
    assert acc["self_s"]["p"] == pytest.approx(p.seconds - children)
    assert acc["self_s"]["score/host"] >= 0.01
    assert acc["top_s"] == pytest.approx(p.seconds)  # the worker's wall is not this thread's
    assert sum(v for k, v in acc["self_s"].items() if k != "score/host") == pytest.approx(p.seconds)


@pytest.mark.parametrize("boundary", ["flush", "shutdown"])
def test_armed_spans_are_written_only_at_a_boundary(tmp_path, boundary):
    """No write(2) from inside the step loop: spans wait in memory until the
    loop's boundary flush (or shutdown), then land as one batch."""
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    for i in range(50):
        with obs_spans.trace_span("train/step", step=i):
            with obs_spans.trace_span("train/dispatch"):
                pass
        obs_spans.instant("tick", i=i)
    assert os.path.getsize(path) == 0
    getattr(obs_spans, boundary)()
    events = obs_spans.read_spans(path)
    assert len(_xs(events, "train/step")) == 50 and len(_xs(events, "train/dispatch")) == 50
    assert sum(e["ph"] == "i" for e in events) == 50
    size = os.path.getsize(path)
    obs_spans.flush()  # nothing new: nothing written
    assert os.path.getsize(path) == size
    obs_spans.shutdown()


def test_spans_lie_on_the_profilers_host_plane_and_clock(tmp_path):
    """Under a profiler session every span is a TraceAnnotation on the host
    plane's `python` line (where benchmark/trace.py looks for the label of an
    idle gap), armed or not, and the span's own clock reads agree with the
    profiler's event to well under a millisecond."""
    import jax
    from jax.profiler import ProfileData

    obs_spans.shutdown()  # unarmed: the annotation does not depend on the file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with obs_spans.trace_span("train/step") as step:
            with obs_spans.trace_span("train/dispatch") as dispatch:
                time.sleep(0.003)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = [os.path.join(b, f) for b, _, fs in os.walk(str(tmp_path)) for f in fs if f.endswith(".xplane.pb")]
    found = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python":
                    found.update({e.name: e for e in line.events if e.name.startswith("train/")})
    assert set(found) == {"train/step", "train/dispatch"}
    for name, span in (("train/step", step), ("train/dispatch", dispatch)):
        assert abs(found[name].duration_ns - (span.t1 - span.t0)) < 200_000, name
    # one clock: the child starts as long after its parent on both
    on_profiler = found["train/dispatch"].start_ns - found["train/step"].start_ns
    assert abs(on_profiler - (dispatch.t0 - step.t0)) < 200_000


def test_compile_listener_counts_requests_and_marks_them(tmp_path):
    import jax
    import jax.numpy as jnp

    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path)
    obs_spans.install_compile_listener()
    obs_spans.install_compile_listener()  # once per process, whoever asks
    x = jnp.ones((3,)).block_until_ready()
    obs_spans.take_compiles()

    def a_new_program(x):
        return x * 3 + 1

    jax.jit(a_new_program)(x).block_until_ready()
    assert obs_spans.take_compiles() == 1
    assert obs_spans.take_compiles() == 0
    obs_spans.shutdown()
    marks = [e for e in obs_spans.read_spans(path) if e["name"] == "compile"]  # the input's programs too
    (mark,) = [e for e in marks if "a_new_program" in e["args"]["fun_name"]]
    assert mark["ph"] == "i" and mark["args"]["seconds"] > 0


def test_ring_keeps_the_last_records_unarmed_and_recent_returns_what_overlaps():
    """The flight recorder's ring: every span and instant of every thread,
    armed or not, bounded at RING_SPANS; `recent` hands back only what
    overlaps the interval, in the file's own vocabulary."""
    obs_spans.shutdown()
    for i in range(10_000):
        with obs_spans.trace_span("filler", i=i):
            pass
    assert len(obs_spans._RING) == obs_spans.RING_SPANS
    with obs_spans.trace_span("before"):
        time.sleep(0.002)
    t0 = time.time_ns()
    with obs_spans.trace_span("outer", step=3) as outer:
        obs_spans.instant("mark", seconds=0.25)
        worker = threading.Thread(target=lambda: obs_spans.trace_span("on_worker").__enter__().__exit__(None, None, None),
                                  name="trlx-unit-worker")
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
    t1 = time.time_ns()
    time.sleep(0.002)
    with obs_spans.trace_span("after"):
        pass
    events = obs_spans.recent(t0, t1, pid=3)
    assert {e["name"] for e in events if e["ph"] != "M"} == {"outer", "mark", "on_worker"}
    assert all(e["pid"] == 3 for e in events)
    (x,) = _xs(events, "outer")
    assert x["args"] == {"step": 3, "id": outer.id, "parent": None, "iter": obs_spans.iteration()}
    (mark,) = [e for e in events if e["name"] == "mark"]
    assert mark["ph"] == "i" and mark["args"] == {"seconds": 0.25}
    lanes = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert lanes[x["tid"]] == threading.current_thread().name
    assert lanes[_xs(events, "on_worker")[0]["tid"]] == "trlx-unit-worker"
    # a span that only touches the interval's edge by overlapping it is in; one wholly outside is not
    assert [e["name"] for e in obs_spans.recent(outer.t0 + 1, outer.t0 + 2) if e["ph"] == "X"] == ["outer"]
    assert obs_spans.recent(t1 + 10**12, t1 + 2 * 10**12) == []
    assert len(obs_spans._RING) == obs_spans.RING_SPANS


def test_the_ring_and_the_file_hold_one_record_form(tmp_path):
    """Armed, the file's events are built at flush from the tuples the ring
    holds: `recent` over the run and the file agree event for event."""
    path = str(tmp_path / "spans.jsonl")
    obs_spans.configure(path, process_index=0)
    t0 = time.time_ns()
    with obs_spans.trace_span("a", k=1):
        with obs_spans.trace_span("b"):
            obs_spans.instant("c", n=2)
    with pytest.raises(KeyError):
        with obs_spans.trace_span("d"):
            raise KeyError("x")
    t1 = time.time_ns()
    obs_spans.shutdown()
    in_file = obs_spans.read_spans(path)
    assert in_file == obs_spans.recent(t0, t1)
    assert _xs(in_file, "d")[0]["args"]["error"] == "KeyError"


# ------------------------------------------------------------------ anomaly


def test_anomaly_detector_baseline_seed_and_breach():
    det = obs_anomaly.AnomalyDetector(factor=3.0, window=16, min_samples=5)
    # seeding: nothing may trip before min_samples observations, even spikes
    for _ in range(4):
        assert not det.observe(1.0)
    assert not det.observe(50.0)  # 5th observation still seeds
    assert det.p50() == 1.0
    # breach: > factor * p50 trips, and is NOT absorbed into the baseline
    assert det.observe(4.0)
    assert det.p50() == 1.0
    assert not det.observe(2.9)  # under 3x median: normal


def test_anomaly_detector_factor_zero_disables():
    det = obs_anomaly.AnomalyDetector(factor=0.0)
    assert not any(det.observe(x) for x in [0.1] * 10 + [1000.0])


@pytest.mark.parametrize("case", ["host_side", "wait_side", "regime_change", "second_threshold"])
def test_detector_splits_a_stall_by_its_side_on_one_median_with_two_thresholds(case):
    """One detector, one rolling median, two thresholds: the stall record's
    (STALL_FACTOR) and `train.anomaly_factor`'s; the excess inside the waits
    is told apart from the host's; a stall never enters the window, a change
    of regime starts it over."""
    det = obs_anomaly.AnomalyDetector(obs_anomaly.STALL_FACTOR, window=64, min_samples=5, bundle_factor=3.0)
    for i in range(40):  # (step_time, waited): a step of 1.0 s waits 0.9 s for the device
        assert det.observe(1.0 + 0.01 * (i % 3), 0.9) is None
    assert det.p50() == pytest.approx(1.01)
    if case == "host_side":  # the host stopped outside the waits
        b = det.observe(3.0, 0.9)
        assert b.excess_s == pytest.approx(1.99) and b.wait_excess_s == 0.0 and not b.bundle
        assert b.p50 == pytest.approx(1.01) and b.wait_p50 == 0.9
    elif case == "wait_side":  # the main thread waited 2 s longer for the step's stats
        b = det.observe(3.0, 2.9)
        assert b.excess_s == pytest.approx(1.99) and b.wait_excess_s == pytest.approx(1.99)
        b = det.observe(3.0, 3.5)  # never more than the excess
        assert b.wait_excess_s == pytest.approx(b.excess_s)
    elif case == "regime_change":
        for _ in range(obs_anomaly.REGIME_BREACHES - 1):  # a run of stalls: each judged against the old median
            assert det.observe(2.0, 1.9).p50 == pytest.approx(1.01)
        assert det.observe(1.0, 0.9) is None  # a quiet step between: the count starts over
        for _ in range(obs_anomaly.REGIME_BREACHES):
            assert det.observe(2.0, 1.9).excess_s == pytest.approx(0.99)
        assert det.p50() == 2.0  # that many in a row: the window started over from them
        assert det.observe(2.0, 1.9) is None and det.observe(2.9, 2.8) is None  # 2 s is how the run goes now
        assert det.observe(3.1, 3.0).p50 == 2.0
        return
    else:
        assert det.observe(1.4, 0.9) is None  # under both
        b = det.observe(2.0, 0.9)  # over 1.5x, under 3x: a stall record, no bundle
        assert b.excess_s > 0 and not b.bundle
        b = det.observe(3.5, 0.9)  # over both, against the SAME median
        assert b.bundle and b.p50 == pytest.approx(1.01)
        only_bundle = obs_anomaly.AnomalyDetector(0.0, min_samples=2, bundle_factor=2.0)
        assert [only_bundle.observe(x) for x in (1.0, 1.0)] == [None, None]
        b = only_bundle.observe(2.5)  # the stall threshold off: no excess, the bundle still trips
        assert b.bundle and b.excess_s == 0.0
    assert det.p50() == pytest.approx(1.01)  # a stall never enters the window


def test_ticker_keeps_each_readers_largest_gap_on_an_injected_clock():
    now = [100.0]
    ticker = obs_anomaly.Ticker(period=0.05, late=0.15, clock=lambda: now[0])
    t0 = time.time_ns()

    def wake(after):
        now[0] += after
        return ticker.note()

    assert wake(0.0) == 0.0  # the first wake has nothing to be late against
    assert wake(0.05) == pytest.approx(0.0) and wake(0.06) == pytest.approx(0.01)
    assert ticker.take("step") == pytest.approx(0.01)
    assert wake(0.05 + 4.88) == pytest.approx(4.88)  # the host stopped (PR 44 saw 4.88 s at TPU start-up)
    assert wake(0.05 + 0.10) == pytest.approx(0.10)  # late, under `late`: no instant
    assert ticker.take("step") == pytest.approx(4.88) and ticker.take("step") == 0.0
    assert ticker.take("rollout") == pytest.approx(4.88)  # the readers do not eat each other's reading
    gaps = [e for e in obs_spans.recent(t0, time.time_ns()) if e["name"] == "host/tick_gap"]
    assert [e["args"]["seconds"] for e in gaps] == [pytest.approx(4.88)]


def test_ticker_thread_runs_and_is_joined():
    ticker = obs_anomaly.Ticker(period=0.005)
    ticker.start()
    assert any(t.name == "trlx-obs-tick" for t in threading.enumerate())
    time.sleep(0.05)
    ticker.stop()
    assert not any(t.name == "trlx-obs-tick" for t in threading.enumerate())
    assert 0.0 <= ticker.take("step") < 5.0
    ticker.stop()  # twice is fine


@pytest.mark.parametrize("key", ["nivcsw", "nvcsw", "majflt", "cpu_s", "gc_s"])
def test_proc_counters_are_cumulative_and_open_no_file(monkeypatch, key):
    """Holds on a host without /proc/pressure, or without /proc: a reading is
    one getrusage and the collector's clock."""

    def refuse(*args, **kwargs):
        raise AssertionError("proc_counters() opened a file")

    monkeypatch.setattr(os, "open", refuse)
    monkeypatch.setattr("builtins.open", refuse)
    first = obs_anomaly.proc_counters()
    t = time.process_time()
    while time.process_time() - t < 0.02:
        sum(range(1000))
    second = obs_anomaly.proc_counters()
    assert set(first) == set(second) == {"nivcsw", "nvcsw", "majflt", "cpu_s", "gc_s"}
    assert second[key] >= first[key] and second["cpu_s"] > first["cpu_s"]


def test_proc_window_reads_deltas_and_collections_are_timed():
    import gc

    ticker = obs_anomaly.Ticker(clock=lambda: 0.0)
    window = obs_anomaly.ProcWindow(ticker, "step")
    obs_anomaly.time_collections(True)
    try:
        window.open()
        t = time.process_time()
        while time.process_time() - t < 0.05:  # burn CPU: cpu_s moves
            sum(range(1000))
        gc.collect()
        first = window.close()
        second = window.close()  # the next window opened where the first closed
    finally:
        obs_anomaly.time_collections(False)
    assert obs_anomaly._gc_clock not in gc.callbacks
    assert first["cpu_s"] >= 0.04 and first["gc_s"] > 0.0 and first["nivcsw"] >= 0 and first["tick_gap_max_s"] == 0.0
    assert first["t0_ns"] < first["t1_ns"] == second["t0_ns"] <= second["t1_ns"]
    assert second["cpu_s"] < first["cpu_s"] and second["compiles"] == 0


def test_stall_log_writes_whole_lines_within_its_budget_and_disarms_on_io_error(tmp_path, monkeypatch):
    log = obs_anomaly.StallLog(str(tmp_path), process_index=2)
    assert not os.path.exists(log.path)  # the file appears with the first stall
    t0 = time.time_ns()
    with obs_spans.trace_span("train/step", step=7):
        obs_spans.instant("host/tick_gap", seconds=1.25)
    window = (t0, time.time_ns())
    for i in range(obs_anomaly.MAX_STALLS):
        full = log.write({"kind": "train_step", "step": i}, window)
    assert full["pid"] == 2 and [g[1] for g in full["tick_gaps"]] == [1.25]
    with monkeypatch.context() as m:  # the budget spent: the ring is read only for a bundle's stall.json
        m.setattr(obs_spans, "recent", lambda *a, **k: pytest.fail("read the ring for a record nobody gets"))
        assert log.write({"kind": "train_step", "step": 99}, window) is None
    kept = log.write({"kind": "train_step", "step": 100}, window, keep=True)
    assert kept["step"] == 100 and [e["name"] for e in kept["spans"] if e["ph"] == "X"] == ["train/step"]
    records = obs_spans.read_spans(log.path)  # the shared jsonl contract
    assert len(records) == obs_anomaly.MAX_STALLS == log.written
    assert [e["name"] for e in records[0]["spans"] if e["ph"] == "X"] == ["train/step"]
    log.close()

    broken = obs_anomaly.StallLog(str(tmp_path / "x"))
    broken.write({"step": 1}, window)
    broken._file.close()  # a closed file: the next write raises inside
    with pytest.warns(UserWarning, match="stall log disabled"):
        broken.write({"step": 2}, window)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # one warning, then silence
        assert broken.write({"step": 3}, window) is None
    assert broken.written == 1


def test_incident_capture_bundle_contents_and_budget(tmp_path):
    metrics = tmp_path / "metrics.jsonl"
    metrics.write_text('{"loss": 1.0, "step": 1}\n{"loss": 0.5, "step": 2}\n')
    cap = obs_anomaly.IncidentCapture(
        str(tmp_path), metrics_path=str(metrics), max_incidents=2, last_n_metrics=1
    )
    bundle = cap.capture(7, "unit_drill", detail={"step_time": 9.9})
    assert bundle.endswith(os.path.join("incidents", "7"))

    with open(os.path.join(bundle, "incident.json")) as f:
        manifest = json.load(f)
    assert manifest["step"] == 7 and manifest["reason"] == "unit_drill"
    assert manifest["detail"] == {"step_time": 9.9}
    assert manifest["sections"]["threads"] == "ok"
    assert manifest["sections"]["memory"] == "ok"
    with open(os.path.join(bundle, "threads.txt")) as f:
        assert "MainThread" in f.read()
    with open(os.path.join(bundle, "memory.json")) as f:
        assert "gauges" in json.load(f)
    with open(os.path.join(bundle, "last_metrics.json")) as f:
        assert json.load(f) == [{"loss": 0.5, "step": 2}]  # tail only

    assert cap.capture(8, "unit_drill")
    assert cap.capture(9, "unit_drill") == ""  # budget spent: rate-limited


def test_emergency_capture_hook_roundtrip(tmp_path):
    cap = obs_anomaly.IncidentCapture(str(tmp_path), max_incidents=1)
    obs_anomaly.emergency_capture("collective_timeout")  # nothing registered: no-op
    obs_anomaly.register_emergency(cap, step_provider=lambda: 42)
    obs_anomaly.emergency_capture("collective_timeout", detail={"op": "psum"})
    with open(os.path.join(str(tmp_path), "incidents", "42", "incident.json")) as f:
        assert json.load(f)["reason"] == "collective_timeout"


# ---------------------------------------------------------------- devicemon


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("TRLX_TPU_PEAK_TFLOPS", "0.5")
    assert devicemon.detect_peak_flops() == pytest.approx(0.5e12)


def test_device_monitor_capture_dispatch_accounting_and_mfu():
    """The acceptance arithmetic: the MFU gauge must match the formula
    (100 * flops / seconds / peak) computed by hand from the SAME captured
    cost_analysis FLOPs, to 2%."""
    import jax
    import jax.numpy as jnp

    peak = 1e9  # pinned synthetic peak: CPU has no table entry
    mon = devicemon.DeviceMonitor(peak_flops=peak)
    step = mon.wrap("train/step", jax.jit(lambda x: x @ x), phase="train")
    x = jnp.ones((64, 64), jnp.float32)
    for _ in range(3):
        step(x).block_until_ready()

    prog = mon.snapshot()["train/step"]
    assert prog["phase"] == "train" and prog["dispatches"] == 3
    assert len(prog["variants"]) == 1  # one signature -> ONE capture
    flops = prog["variants"][0]["flops"]
    assert flops > 0

    train_s = 2.0
    stats = mon.window({"train": train_s, "wall": train_s})
    expected_mfu = 100.0 * (3 * flops) / train_s / peak
    assert stats["obs/train_mfu_pct"] == pytest.approx(expected_mfu, rel=0.02)
    assert stats["obs/iter_mfu_pct"] == pytest.approx(expected_mfu, rel=0.02)
    assert stats["obs/train_tflops_per_chip"] == pytest.approx(3 * flops / train_s / 1e12, rel=0.02)

    assert mon.window({"train": 1.0, "wall": 1.0}) == {}  # counters drained

    step(jnp.ones((32, 32), jnp.float32)).block_until_ready()  # new shape
    assert len(mon.snapshot()["train/step"]["variants"]) == 2


def test_monitored_fn_delegates_attributes_and_survives_capture_failure():
    mon = devicemon.DeviceMonitor(peak_flops=None)

    def fn(x):
        return x + 1

    fn.num_traces = 7  # the closure counters make_generate_fn exposes
    wrapped = mon.wrap("rollout/generate", fn, phase="rollout")
    assert wrapped.num_traces == 7
    assert wrapped(1) == 2  # plain fn: .lower() fails, call still goes through
    variant = mon.snapshot()["rollout/generate"]["variants"][0]
    assert variant["flops"] == 0.0 and "error" in variant


def test_routing_and_memory_gauges_have_stable_keys():
    routing = devicemon.kernel_routing_gauges()
    assert set(routing) == {
        "obs/fused_logprob_active",
        "obs/fused_logprob_fallback",
    }
    assert all(v in (0.0, 1.0) for v in routing.values())
    memory = devicemon.device_memory_gauges()
    assert memory  # CPU backend: live_array census fallback
    assert all(k.startswith("obs/") and v >= 0 for k, v in memory.items())


def test_rollup_is_identity_valued_on_single_process():
    """hostmean/hostmax of a one-host gather are the host's own values (pods
    exercise the real allgather; the keys are identical either way)."""
    stats = {"obs/train_mfu_pct": 12.5, "time/train_s": 3.0, "skip_me": "str"}
    assert report.rollup_window_stats(stats) == {
        "obs/train_mfu_pct/hostmean": 12.5,
        "obs/train_mfu_pct/hostmax": 12.5,
        "time/train_s/hostmean": 3.0,
        "time/train_s/hostmax": 3.0,
    }
    assert report.rollup_window_stats({}) == {}


# ------------------------------------------------------------ e2e acceptance


@pytest.fixture(scope="module")
def task():
    return generate_random_walks(n_nodes=15, max_length=8, n_walks=60, seed=1000)


def test_e2e_overlapped_run_spans_telemetry_incident_report(task, tmp_path, monkeypatch):
    """The PR's acceptance run: overlapped PPO (max_staleness=1) with every
    observability surface armed and the slow_step drill injected."""
    monkeypatch.setenv("TRLX_TPU_FAULTS", "slow_step@6")
    monkeypatch.setenv("TRLX_TPU_SLOW_STEP_SECONDS", "1.5")
    monkeypatch.setenv("TRLX_TPU_PEAK_TFLOPS", "0.01")  # only way to get MFU on CPU

    _, logit_mask, metric_fn, reward_fn = task
    config = base_config("ppo", 15, 8)
    config.train.total_steps = 8
    config.train.epochs = 4
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.checkpoint_dir = str(tmp_path)
    config.train.trace_spans = True
    config.train.device_telemetry = True
    config.train.anomaly_factor = 3.0
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    config.method.max_staleness = 1
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[1]],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert not any(t.name.startswith("trlx-") for t in threading.enumerate())

    # --- spans.jsonl: valid Chrome trace events on distinct thread lanes ---
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no torn tail on a clean shutdown
        events = obs_spans.read_spans(os.path.join(str(tmp_path), "spans.jsonl"))
    assert events and {e["ph"] for e in events} <= {"X", "i", "M"}
    lanes = {e["args"]["name"]: e["tid"] for e in events if e["ph"] == "M"}
    assert "MainThread" in lanes
    assert "trlx-rollout-producer" in lanes
    assert "trlx-score-worker" in lanes
    assert len(set(lanes.values())) == len(lanes)  # one lane per thread

    xs = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {
        "train/step", "rollout/produce", "rollout/generate", "rollout/decode",
        "rollout/reward_fn", "score/host", "ckpt/save",
    } <= names

    producer = [e for e in xs if e["name"] == "rollout/produce"]
    train = [e for e in xs if e["name"] == "train/step"]
    assert {e["tid"] for e in producer} == {lanes["trlx-rollout-producer"]}
    assert {e["tid"] for e in train} == {lanes["MainThread"]}
    # a fresh score worker spawns per experience window — every score/host
    # span must sit on SOME trlx-score-worker lane (and never the main lane)
    lane_names = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    score_lanes = {lane_names[e["tid"]] for e in xs if e["name"] == "score/host"}
    assert score_lanes == {"trlx-score-worker"}

    def overlap_us(a, b):
        return min(a["ts"] + a["dur"], b["ts"] + b["dur"]) - max(a["ts"], b["ts"])

    # staleness=1: the producer builds store N+1 WHILE the trainer steps on N
    assert any(overlap_us(p, t) > 0 for p in producer for t in train)

    # --- metrics.jsonl: compiled-cost MFU + kernel-routing gauges ---------
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    mfu = [r["obs/train_mfu_pct"] for r in records if "obs/train_mfu_pct" in r]
    assert mfu and all(m > 0 for m in mfu)
    routed = [r for r in records if "obs/fused_logprob_active" in r]
    assert routed
    assert "obs/fused_logprob_fallback" in routed[-1]
    stale = [r["staleness/mean"] for r in records if "staleness/mean" in r]
    assert stale and stale[-1] == 1.0  # the pipeline genuinely ran ahead

    # programs.json: registry for the report's program table
    with open(os.path.join(str(tmp_path), "programs.json")) as f:
        programs = json.load(f)
    assert "train/step" in programs
    assert programs["train/step"]["dispatches"] >= 8

    # --- incident bundle from the slow_step drill -------------------------
    incidents_dir = os.path.join(str(tmp_path), "incidents")
    bundles = os.listdir(incidents_dir)
    assert "6" in bundles, "slow_step drill produced no incident bundle for step 6"
    with open(os.path.join(incidents_dir, "6", "incident.json")) as f:
        manifest = json.load(f)
    assert manifest["reason"] == "slow_step"
    assert manifest["detail"]["step_time"] > 1.0  # the injected stall
    assert manifest["sections"]["threads"] == "ok"
    with open(os.path.join(incidents_dir, "6", "threads.txt")) as f:
        assert "trlx-" in f.read()  # the pipeline threads ARE in the dump
    # the bundle and the stall record come from ONE detector and one median
    with open(os.path.join(incidents_dir, "6", "stall.json")) as f:
        stall = json.load(f)
    stalls = obs_spans.read_spans(os.path.join(str(tmp_path), "stalls.jsonl"))
    (line,) = [r for r in stalls if r["kind"] == "train_step" and r["step"] == 6]  # a busy CPU may add another
    assert stall == line and stall["p50"] == manifest["detail"]["p50"]
    assert manifest["detail"]["factor"] == 3.0 and manifest["sections"]["stall"] == "ok"

    # --- report renders every section ------------------------------------
    md = report.build_report(str(tmp_path))
    for heading in (
        "# Performance report",
        "## Phase breakdown (per window)",
        "## MFU / FLOP throughput",
        "## Kernel routing",
        "### Monitored programs",
        "## Span lanes",
        "## Incidents",
    ):
        assert heading in md
    assert "slow_step" in md
    assert "trlx-rollout-producer" in md

    out_md = tmp_path / "report.md"
    trace_out = tmp_path / "trace.json"
    assert report.main([str(tmp_path), "-o", str(out_md), "--trace-out", str(trace_out)]) == 0
    assert "slow_step" in out_md.read_text()
    assert json.loads(trace_out.read_text())["traceEvents"]


def _main_lane_coverage(events):
    """Share of the main thread's wall, first span start to last span end,
    that lies inside its top-level spans (they never overlap on one thread)."""
    main_tid = next(e["tid"] for e in events if e["ph"] == "M" and e["args"]["name"] == "MainThread")
    top = [e for e in _xs(events) if e["tid"] == main_tid and e["args"]["parent"] is None]
    wall = max(e["ts"] + e["dur"] for e in top) - min(e["ts"] for e in top)
    return sum(e["dur"] for e in top) / wall


@pytest.mark.parametrize("method", ["ppo", "ilql"])
def test_e2e_main_path_writes_span_keys_and_leaves_little_unspanned(task, tmp_path, method):
    """The main path of each method, tiny, on the CPU: the step records carry
    `time/step_host_ms` and `obs/compiles`, PPO's phase-window records the
    keys drained from the spans' accumulators with under 5% of the window's
    wall outside every span, and the armed file groups spans by parent and
    iteration."""
    walks, logit_mask, metric_fn, reward_fn = task
    config = base_config(method, 15, 8)
    config.train.total_steps = 12
    config.train.epochs = 6
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.checkpoint_interval = 0
    config.train.checkpoint_dir = str(tmp_path)
    config.train.trace_spans = True
    if method == "ppo":
        config.method.num_rollouts = 16
        config.method.chunk_size = 16

        def slow_reward_fn(rows):  # a reward model takes its time; 12 calls
            time.sleep(0.05)
            return reward_fn(rows)

        prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
        trlx_tpu.train(reward_fn=slow_reward_fn, prompts=prompts, eval_prompts=[[1]], metric_fn=metric_fn,
                       config=config, logit_mask=logit_mask)
    else:
        trlx_tpu.train(dataset=(walks, metric_fn(walks)["lengths"]), eval_prompts=[[1]], metric_fn=metric_fn,
                       config=config, logit_mask=logit_mask)
    obs_spans.shutdown()

    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    steps = [r for r in records if "step_time" in r]
    assert len(steps) == 12
    for r in steps:
        assert r["time/step_host_ms"] >= 0.0 and r["obs/compiles"] >= 0
        # at most the wall since the previous record (plus that record's own log)
        assert r["time/step_host_ms"] < 1e3 * (r["step_time"] + 0.1)
        assert "step_gap" not in r  # went in PR 49: time/step_host_ms took its place in PR 23
    assert steps[0]["obs/compiles"] >= 1 and steps[-1]["obs/compiles"] == 0  # the step that compiled says so

    events = obs_spans.read_spans(os.path.join(str(tmp_path), "spans.jsonl"))
    spans_by_id = {e["args"]["id"]: e for e in _xs(events)}
    names = {e["name"] for e in spans_by_id.values()}
    assert {"train/data_wait", "train/batch", "train/step", "train/dispatch", "train/stats_read", "train/log"} <= names
    for e in spans_by_id.values():
        if e["name"] in ("train/dispatch", "train/stats_read", "train/log", "train/polyak_sync"):
            assert spans_by_id[e["args"]["parent"]]["name"] == "train/step"
        if e["name"] == "train/stats_wait":
            assert spans_by_id[e["args"]["parent"]]["name"] == "train/stats_read"
    train_steps = sorted(_xs(events, "train/step"), key=lambda e: e["args"]["step"])
    assert [e["args"]["step"] for e in train_steps] == list(range(1, 13))
    assert _main_lane_coverage(events) > 0.95

    windows = [r for r in records if "time/window_wall_s" in r]
    if method == "ilql":
        assert not windows and "train/polyak_sync" in names
        assert [e["args"]["iter"] for e in train_steps] == list(range(1, 13))  # ILQL: one step, one iteration
        return
    assert {"rollout/generate", "rollout/generate_dispatch", "rollout/pull", "rollout/decode", "rollout/reward_fn",
            "rollout/score_device", "rollout/push", "boundary/kl_flush", "boundary/refresh_weights",
            "boundary/store_clear", "boundary/loader", "boundary/phase_log"} <= names
    assert [e["args"]["iter"] for e in train_steps] == [i // 4 for i in range(12)]  # 4 steps an iteration
    assert len(windows) == 2  # after iterations 0 and 1 (the run ends inside the third)
    for w in windows:
        for key in ("time/generate_s", "time/score_device_s", "time/push_s", "time/boundary_s", "time/unspanned_s"):
            assert w[key] >= 0.0, key
        assert w["rollout/decode_steps"] == 7.0  # max_length 8, one-token prompts
        assert w["rollout/kv_read_share"] == 1.0  # a cache of 8 slots is one branch: read whole
        assert "rollout/kv_scale_mults_per_key" not in w  # the cache is not int8: nothing to scale
        assert w["time/unspanned_s"] < 0.05 * w["time/window_wall_s"]
        # the old keys keep their meaning: rollout = generate + device scoring + push
        assert w["time/rollout_s"] == pytest.approx(
            w["time/generate_s"] + w["time/score_device_s"] + w["time/push_s"], rel=0.02, abs=2e-3)
        assert w["time/score_s"] >= 0.05  # host decode + reward_fn, the sleep inside


@pytest.mark.parametrize("method", ["ppo", "ilql"])
def test_e2e_unarmed_run_records_a_stalled_step_and_nothing_else(task, tmp_path, monkeypatch, method):
    """The flight recorder with NOTHING armed: every step record and every
    PPO phase record carries `stall/*` and `proc/*`; the `slow_step` drill
    leaves ONE `train_step` line in stalls.jsonl, with the sleep as its excess,
    none of it inside the waits (the sleep is host time), the step's own
    `train/step` among its spans; no spans.jsonl, no incident bundle, and the
    ticker's thread is gone when `learn()` returns."""
    from trlx_tpu.trainer.base import JaxBaseTrainer

    monkeypatch.setenv("TRLX_TPU_FAULTS", "slow_step@11")
    monkeypatch.setenv("TRLX_TPU_SLOW_STEP_SECONDS", "1.0")
    monkeypatch.delenv("TRLX_TPU_SPANS", raising=False)
    monkeypatch.delenv("TRLX_TPU_ANOMALY_FACTOR", raising=False)
    fire = JaxBaseTrainer._fire_host_faults

    def steady(self):  # 60 ms of host time a step: a busy CPU's jitter on a 10 ms step is no stall here
        time.sleep(0.06)
        fire(self)

    monkeypatch.setattr(JaxBaseTrainer, "_fire_host_faults", steady)
    walks, logit_mask, metric_fn, reward_fn = task
    config = base_config(method, 15, 8)
    config.train.total_steps = 20
    config.train.epochs = 10
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.checkpoint_interval = 0
    config.train.checkpoint_dir = str(tmp_path)
    assert config.train.anomaly_factor == 0 and not config.train.trace_spans
    if method == "ppo":
        from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator

        config.method.num_rollouts = 16
        config.method.chunk_size = 16
        generate, calls = PPOOrchestrator._generate_next_chunk, []

        def stalled_fifth(self, **kw):  # the rollout after step 16 dispatches a second late
            calls.append(1)
            if len(calls) == 5:
                time.sleep(1.0)
            return generate(self, **kw)

        monkeypatch.setattr(PPOOrchestrator, "_generate_next_chunk", stalled_fifth)
        prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
        trlx_tpu.train(reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]], metric_fn=metric_fn,
                       config=config, logit_mask=logit_mask)
    else:
        trlx_tpu.train(dataset=(walks, metric_fn(walks)["lengths"]), eval_prompts=[[1]], metric_fn=metric_fn,
                       config=config, logit_mask=logit_mask)
    assert not any(t.name.startswith("trlx-") for t in threading.enumerate())
    assert sorted(os.listdir(str(tmp_path))) == ["metrics.jsonl", "stalls.jsonl"]  # no spans.jsonl, no incidents/

    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == list(range(1, 21))
    for n, r in steps.items():
        assert {"stall/excess_s", "stall/wait_excess_s", "proc/nivcsw", "proc/cpu_s", "proc/tick_gap_max_s"} <= set(r)
        assert r["proc/cpu_s"] > 0 and r["proc/nivcsw"] >= 0 and r["proc/tick_gap_max_s"] >= 0
        # 0.0 but for step 11; on a CPU that other processes share a step may
        # lose 50 ms of its 70 to them, and then its record says so: nothing
        # is flagged that did not pass 1.5x the median it was held against
        assert r["stall/excess_s"] == 0.0 or r["step_time"] > 1.5 * (r["step_time"] - r["stall/excess_s"])
        assert 0.0 <= r["stall/wait_excess_s"] <= r["stall/excess_s"]
    assert steps[11]["stall/excess_s"] > 0
    windows = [r for r in records if "time/window_wall_s" in r]
    assert len(windows) == (4 if method == "ppo" else 0)
    for w in windows:
        assert {"stall/rollout_excess_s", "proc/rollout_nivcsw", "proc/rollout_cpu_s",
                "proc/rollout_tick_gap_max_s"} <= set(w)
        assert w["proc/rollout_cpu_s"] > 0  # the orchestrator closed the rollout's window

    stalls = obs_spans.read_spans(os.path.join(str(tmp_path), "stalls.jsonl"))
    assert [r["step"] for r in stalls if r["kind"] == "train_step"] == [n for n, r in steps.items() if r["stall/excess_s"] > 0]
    (stall,) = [r for r in stalls if r["kind"] == "train_step" and r["step"] == 11]
    assert stall["compiles"] == 0
    # the sleep, within 0.2 s on a quiet CPU; a CPU under other load moves the step and its median by tenths
    assert 0.8 < stall["excess_s"] < 1.5 and stall["excess_s"] == steps[11]["stall/excess_s"]
    assert stall["wait_excess_s"] < 0.1 * stall["excess_s"] and steps[11]["stall/wait_excess_s"] == stall["wait_excess_s"]
    assert stall["seconds"] == steps[11]["step_time"] and stall["seconds"] - stall["p50"] == pytest.approx(stall["excess_s"])
    assert stall["host_ms"] > 900 and stall["proc/window_s"] >= stall["seconds"]
    assert {"proc/nivcsw", "proc/nvcsw", "proc/majflt", "proc/cpu_s", "proc/gc_s", "proc/tick_gap_max_s"} <= set(stall)
    (own,) = [e for e in _xs(stall["spans"], "train/step") if e["args"]["step"] == 11]
    assert own["dur"] >= 1e6 and own["ts"] * 1e-6 == pytest.approx(stall["t0"], abs=1e-3)
    assert {"train/dispatch", "train/stats_read", "train/stats_wait"} <= {e["name"] for e in stall["spans"]}
    # the sleep gave the interpreter lock up: the ticker kept time, so the record says the host was alive
    assert stall["proc/tick_gap_max_s"] < 0.8 * stall["excess_s"]
    assert all(gap < 0.8 * stall["excess_s"] for _, gap in stall["tick_gaps"])

    md = report.build_report(str(tmp_path))
    assert "## Stalls" in md and "| train_step | 11 |" in md and "train/step (" in md
    if method == "ppo":
        # the rollout's detector: the first two rollouts compiled (skipped), two seeded it, the fifth is the drill's
        (late,) = [r for r in stalls if r["kind"] == "rollout" and r["step"] == 16]
        assert 0.8 < late["excess_s"] < 1.5 and late["wait_excess_s"] < 0.1 * late["excess_s"]
        assert late["excess_s"] == windows[3]["stall/rollout_excess_s"] and late["compiles"] == 0
        assert "rollout/generate_dispatch" in {e["name"] for e in late["spans"]}
        assert "| rollout | 16 |" in md and "rollout/generate_dispatch (" in md

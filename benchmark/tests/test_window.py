"""The PPO window: two warm-up iterations, whole iterations cut from the
program's step records, the run ended on the iteration that fills --seconds."""

import json
import time
import types

import pytest

from benchmark import harness

SPI = 4  # train steps an iteration


def log_steps(path, ends):
    """metrics.jsonl as the program writes it: one record a step, the last
    step of iteration k at ends[k - 1] seconds."""
    with open(path, "w") as f:
        for k, t in enumerate(ends, start=1):
            for step in range((k - 1) * SPI + 1, k * SPI + 1):
                f.write(json.dumps({"step": step, "t": t - 0.1 * (k * SPI - step), "step_time": 0.1}) + "\n")


def test_iteration_seconds_needs_every_boundary():
    steps = {4: {"t": 30.0}, 8: {"t": 42.0}, 12: {"t": 52.0}}
    assert harness.iteration_seconds(steps, SPI, 1, 3) == [12.0, 10.0]
    assert harness.iteration_seconds(steps, SPI, 2, 2) == []
    assert harness.iteration_seconds(steps, SPI, 2, 4) is None


def test_run_ends_on_the_iteration_that_fills_the_seconds(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    stop = harness.IterationStop(path, SPI, seconds=35.0, tracer=None)
    stop.trainer = types.SimpleNamespace(total_steps=10**9)
    ends = [30.0, 42.0]  # warm-up: 30 s of compiles, then 12 s with the one-off; later iterations take 10 s
    rows = [[1, 2, 3]] * 8
    for i in range(1, 9):
        log_steps(path, ends)  # rollout i arrives when i - 1 iterations are logged
        stop.on_rollout(rows)
        ends.append(ends[-1] + 10.0)
        if stop.last_iteration is not None:
            break
    # 35 s hold three iterations of 10 s: 3, 4 and 5, the warm-up's 12 s not counted
    assert stop.last_iteration == 5 and stop.trainer.total_steps == 5 * SPI
    assert [c[1:] for c in stop.calls] == [(24, 8)] * 5
    stop.on_rollout(rows)  # learn()'s closing evaluate() is not a rollout
    assert len(stop.calls) == 5


class FakeTracer:
    def __init__(self):
        self.t_start = self.t_stop = None
        self.started_at_step = None
        self.trainer = None

    def start(self):
        self.started_at_step, self.t_start = self.trainer.iter_count, 1.0

    def stop(self):
        self.t_stop = 2.0


def test_a_whole_traced_cycle_runs_from_reward_call_four_to_five(tmp_path):
    tracer = FakeTracer()
    stop = harness.IterationStop(str(tmp_path / "metrics.jsonl"), SPI, seconds=35.0, tracer=tracer)
    stop.trainer = tracer.trainer = types.SimpleNamespace(total_steps=10**9, iter_count=0)
    started = []
    for i in range(1, 6):
        stop.on_rollout([[1, 2, 3]] * 8)
        started.append(tracer.t_start is not None)
    assert started == [False, False, False, True, True] and tracer.t_stop is not None
    assert stop.traced_calls == (4, 5) and stop.step_trace is None and stop.trainer.total_steps == 5 * SPI


def test_a_train_steps_cycle_traces_the_last_steps_of_the_first_measured_iteration(tmp_path):
    """`traced_cycle: train_steps`: at reward call 3 the run is set to end with
    iteration 3; the profiler starts from beside `learn()` when the first of
    that iteration's last steps (eight at most, never its first) is
    dispatched, not inside the reward call (whose seconds the program books on
    the phase record before), and no later call touches it."""
    tracer = FakeTracer()
    stop = harness.IterationStop(str(tmp_path / "metrics.jsonl"), SPI, 35.0, tracer, traced_cycle="train_steps")
    trainer = stop.trainer = tracer.trainer = types.SimpleNamespace(total_steps=10**9, iter_count=0)
    for i in (1, 2):
        stop.on_rollout([[1, 2, 3]] * 8)
        trainer.iter_count = i * SPI
    assert stop.step_trace is None and trainer.total_steps == 10**9
    stop.on_rollout([[1, 2, 3]] * 8)
    assert trainer.total_steps == 3 * SPI and stop.last_iteration == 3 and stop.traced_calls is None
    time.sleep(0.05)
    assert tracer.t_start is None  # still at the reward call: step 8 of 12
    trainer.iter_count = 2 * SPI + 1
    time.sleep(0.05)
    assert tracer.t_start is None  # an iteration of four steps: the last three
    trainer.iter_count = 2 * SPI + 2
    stop.step_trace.join(timeout=5)
    assert tracer.started_at_step == 2 * SPI + 2 and not stop.step_trace.is_alive()
    stop.step_trace.check()
    stop.on_rollout([[1, 2, 3]] * 8)  # learn()'s closing evaluate()
    assert len(stop.calls) == 3
    long = harness.IterationStop(str(tmp_path / "metrics.jsonl"), 16, 35.0, FakeTracer(), traced_cycle="train_steps")
    long.trainer = long.tracer.trainer = types.SimpleNamespace(total_steps=10**9, iter_count=0)
    for _ in range(3):
        long.on_rollout([[1, 2, 3]] * 8)
    long.step_trace.done.set()
    assert long.step_trace.first_step == 41 and long.trainer.total_steps == 48  # steps 41..48 of 33..48


def test_a_train_steps_cycle_that_traced_nothing_fails(tmp_path):
    tracer = FakeTracer()
    stop = harness.IterationStop(str(tmp_path / "metrics.jsonl"), SPI, 35.0, tracer, traced_cycle="train_steps")
    stop.trainer = tracer.trainer = types.SimpleNamespace(total_steps=10**9, iter_count=0)
    for _ in range(3):
        stop.on_rollout([[1, 2, 3]] * 8)
    stop.step_trace.done.set()
    stop.step_trace.join(timeout=5)
    with pytest.raises(harness.BenchFailure, match="nothing was traced"):
        stop.step_trace.check()

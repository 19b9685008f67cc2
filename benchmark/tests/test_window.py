"""The PPO window: two warm-up iterations, whole iterations cut from the
program's step records, the run ended on the iteration that fills --seconds."""

import json
import types

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

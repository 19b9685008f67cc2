"""The reader PR 49 added for the flight recorder's keys (`record_reduce`): on
hand-made windows, with the five metrics' own files as the specs, and the
manifest with their entries."""

import pytest

from benchmark.manifest import Manifest
from benchmark.readers import record_reduce, step_log

NEW = ["train_stall_share", "train_stall_wait_share", "rollout_stall_share", "host_tick_gap_max_s",
       "proc_involuntary_switches_per_step"]


def ctx(steps=(), phases=()):
    return {"window": {"steps": list(steps), "phases": list(phases)}}


def step(step_time, excess=0.0, wait=0.0, gap=0.001, nivcsw=2.0):
    return {"step_time": step_time, "stall/excess_s": excess, "stall/wait_excess_s": wait,
            "proc/tick_gap_max_s": gap, "proc/nivcsw": nivcsw, "proc/cpu_s": 0.2}


def phase(generate_s, excess=0.0, gap=0.002):
    return {"time/generate_s": generate_s, "stall/rollout_excess_s": excess, "proc/rollout_tick_gap_max_s": gap,
            "time/window_wall_s": 20.0}


@pytest.fixture(scope="module")
def specs():
    m = Manifest().validate()
    return {name: m.layer_metric(name) for name in NEW}


def test_a_quiet_window_reads_zero_and_a_program_without_the_keys_reads_nothing(specs):
    quiet = ctx([step(0.5), step(0.51), step(0.49)], [phase(4.0), phase(4.1)])
    assert record_reduce.read(quiet, specs["train_stall_share"]) == 0.0
    assert record_reduce.read(quiet, specs["train_stall_wait_share"]) == 0.0
    assert record_reduce.read(quiet, specs["rollout_stall_share"]) == 0.0
    assert record_reduce.read(quiet, specs["host_tick_gap_max_s"]) == 0.002
    assert step_log.read(quiet, specs["proc_involuntary_switches_per_step"]) == 2.0
    parent = ctx([{"step_time": 0.5}], [{"time/generate_s": 4.0}])  # the parent of PR 49: no such key
    for name in NEW[:4]:
        assert record_reduce.read(parent, specs[name]) is None
    assert step_log.read(parent, specs[NEW[4]]) is None
    ilql = ctx([step(0.17), step(0.17)], [])  # no phase record: the rollout's metric has nothing to read
    assert record_reduce.read(ilql, specs["rollout_stall_share"]) is None
    assert record_reduce.read(ilql, specs["host_tick_gap_max_s"]) == 0.001


def test_a_stall_reads_as_its_share_and_its_side(specs):
    # one step of 2.5 s among steps of 0.5: 2.0 s over the median, all of it inside the wait
    stalled = ctx([step(0.5), step(2.5, excess=2.0, wait=2.0, gap=0.004), step(0.5), step(0.5)],
                  [phase(4.0), phase(5.1, excess=1.1, gap=1.05)])
    assert record_reduce.read(stalled, specs["train_stall_share"]) == pytest.approx(0.5)
    assert record_reduce.read(stalled, specs["train_stall_wait_share"]) == pytest.approx(0.5)
    assert record_reduce.read(stalled, specs["rollout_stall_share"]) == pytest.approx(1.1 / 9.1)
    assert record_reduce.read(stalled, specs["host_tick_gap_max_s"]) == 1.05  # the rollout's: the host stopped there
    host_side = ctx([step(0.5), step(2.5, excess=2.0, wait=0.0, gap=1.9)])
    assert record_reduce.read(host_side, specs["train_stall_wait_share"]) == 0.0
    assert record_reduce.read(host_side, specs["host_tick_gap_max_s"]) == 1.9


def test_the_five_metrics_are_in_the_manifest_for_their_cells(specs):
    m = Manifest()
    cells = list(m.cells)
    ppo = [c for c in cells if m.cell(c)["method"] == "ppo"]
    for name in NEW:
        entry = m.per_layer[name]
        assert entry["source"] == "program_counter" and entry["moves"] == "samples_per_s_chip"
        assert entry["workloads"] == (ppo if name == "rollout_stall_share" else cells)
        assert entry["layer"] == ("device" if name == "host_tick_gap_max_s" else "orchestration")
        assert specs[name]["workloads"] == entry["workloads"]
    assert [p["name"] for p in m.doc["per_layer"]][-5:] == NEW  # appended, nothing moved

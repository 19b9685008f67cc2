"""The readers PR 23 added for the program's spans: each on a hand-made
`ctx`, `idle_by_span` also on the recorded v5e trace, the manifest with the
six entries, and the names a CPU rehearsal reports (control flow only)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import trace
from benchmark.manifest import Manifest
from benchmark.readers import idle_by_span, loop_step_time, program_log, program_log_ratio, step_log

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PATTERNS = json.load(open(os.path.join(os.path.dirname(HERE), "trace_patterns.json")))
NEW = ["idle_attributed_pct", "train_host_ms_per_step", "generate_s_per_iter", "iter_boundary_host_s",
       "span_coverage_pct", "decode_ms_per_step"]
PPO_CELLS = ["gptj6b-l8.ppo-768x256", "gptj6b-l8.ppo-128x896", "gptneo1.3b.ppo-256x256"]


def ctx(reduction=None, traced=None, steps=(), phases=()):
    return {"reduction": reduction, "traced": traced, "window": {"steps": list(steps), "phases": list(phases)},
            "trace": trace}


def test_idle_by_span_shares_by_the_host_part_of_the_label():
    spec = {"unattributed": "unattributed"}
    gaps = {"train/log [jit_train_step -> jit_train_step]": 0.6,
            "np.asarray(jax.Array) [inside jit_train_step]": 0.3,
            "unattributed [jit_train_step -> jit_quantize_weights]": 0.1}
    assert idle_by_span.read(ctx({"idle_by_label": gaps}), spec) == pytest.approx(90.0)
    # the parent of PR 23: every gap unattributed is a reading of 0, not a missing one
    assert idle_by_span.read(ctx({"idle_by_label": {"unattributed [a -> b]": 0.2}}), spec) == 0.0
    # a span whose name merely starts like the marker is a span
    assert idle_by_span.read(ctx({"idle_by_label": {"unattributed_thing [a -> b]": 0.2}}), spec) == 100.0
    for nothing in (None, {}, {"idle_by_label": {}}):
        assert idle_by_span.read(ctx(nothing), spec) is None


def test_idle_by_span_on_the_recorded_v5e_trace():
    """The 21 ms sleep lies inside the harness's `bench/reward_fn` annotation,
    which is its label; the short gaps between the programs around it have
    no host event over them."""
    reduction = trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"), PATTERNS)
    labelled = reduction["idle_by_label"]
    named = sum(s for k, s in labelled.items() if not k.startswith("unattributed ["))
    assert labelled["bench/reward_fn [jit_train_step -> jit_traced]"] == pytest.approx(21.06e-3, rel=0.01)
    value = idle_by_span.read(ctx(reduction), {"unattributed": "unattributed"})
    assert value == pytest.approx(100.0 * named / sum(labelled.values()))
    assert 90.0 < value <= 100.0


def test_step_log_is_the_median_over_the_windows_step_records():
    spec = {"key": "time/step_host_ms"}
    steps = [{"step_time": 0.5, "time/step_host_ms": v} for v in (9.0, 11.0, 3000.0)] + [{"step_time": 0.5}]
    assert step_log.read(ctx(steps=steps), spec) == 11.0  # one step held up does not move it
    assert step_log.read(ctx(steps=[{"step_time": 0.5}]), spec) is None  # the parent logs no such key
    assert step_log.read(ctx(), spec) is None


def test_program_log_reads_the_new_phase_keys_and_nothing_on_the_parent():
    phases = [{"time/rollout_s": 2.7, "time/generate_s": 2.4, "time/boundary_s": 0.05},
              {"time/rollout_s": 2.8, "time/generate_s": 2.6, "time/boundary_s": 0.07}]
    assert program_log.read(ctx(phases=phases), {"key": "time/generate_s"}) == pytest.approx(2.5)
    assert program_log.read(ctx(phases=phases), {"key": "time/boundary_s"}) == pytest.approx(0.06)
    assert program_log.read(ctx(phases=[{"time/rollout_s": 2.7}]), {"key": "time/generate_s"}) is None


def test_program_log_ratio_is_the_share_the_part_leaves():
    spec = {"part": "time/unspanned_s", "whole": "time/window_wall_s"}
    phases = [{"time/unspanned_s": 0.11, "time/window_wall_s": 11.0},
              {"time/unspanned_s": 0.011, "time/window_wall_s": 11.0},
              {"time/unspanned_s": 0.022, "time/window_wall_s": 11.0}]
    assert program_log_ratio.read(ctx(phases=phases), spec) == pytest.approx(99.8)
    assert program_log_ratio.read(ctx(phases=[{"time/window_wall_s": 11.0}]), spec) is None
    assert program_log_ratio.read(ctx(phases=[{"time/unspanned_s": 0.0, "time/window_wall_s": 0.0}]), spec) is None
    assert program_log_ratio.read(ctx(), spec) is None


def test_loop_step_time_takes_the_largest_loop_of_the_program():
    spec = {"programs": "^jit_traced$", "select": r"^\S+ while -> ", "steps_key": "rollout/decode_steps"}
    row = lambda seconds, label, container=True: {"seconds": seconds, "calls": 1, "label": label, "container": container,
                                                  "text": "%cut = (s8[32,1024,16,256], s8[32,1024,16,256], bf16[32,10"}
    ops = {
        "jit_traced/while.1": row(1.792, "while while -> (s8[32,1024,16,256], s8[32,1024,16,256], bf16[32,10"),
        "jit_traced/while.7": row(0.6, "while while -> (s32[], bf16[8])"),  # a scan inside it
        "jit_traced/call.2": row(2.0, "call call -> bf16[8]"),  # a container, not a loop
        "jit_traced/fusion.4": row(2.2, "while_fusion fusion -> bf16[8]", container=False),
        "jit_train_step/while.2": row(5.0, "while while -> (s32[])"),  # another program
    }
    phases = [{"rollout/decode_steps": 256.0}, {"rollout/decode_steps": 256.0}]
    got = loop_step_time.read(ctx({"ops": ops}, {"iterations": 1}, phases=phases), spec)
    assert got == pytest.approx(7.0)  # 1.792 s over 256 steps
    assert loop_step_time.read(ctx({"ops": ops}, {"iterations": 2}, phases=phases), spec) == pytest.approx(3.5)
    # nothing to read: no step count logged (the parent), no loop, no trace, an ILQL run's traced steps
    assert loop_step_time.read(ctx({"ops": ops}, {"iterations": 1}, phases=[{"time/rollout_s": 2.7}]), spec) is None
    assert loop_step_time.read(ctx({"ops": {}}, {"iterations": 1}, phases=phases), spec) is None
    assert loop_step_time.read(ctx(None, None, phases=phases), spec) is None


def test_loop_step_time_on_the_recorded_v5e_trace():
    """`jit_traced` there holds one four-step loop."""
    reduction = trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"), PATTERNS)
    spec = Manifest(ROOT).layer_metric("decode_ms_per_step")
    got = loop_step_time.read(ctx(reduction, {"iterations": 1}, phases=[{"rollout/decode_steps": 4.0}]), spec)
    assert got == pytest.approx(1000.0 * reduction["ops"]["jit_traced/while"]["seconds"] / 4)
    assert 0.0 < got < reduction["programs"]["jit_traced"]["total_s"] * 1000.0 / 4


def test_manifest_is_valid_with_the_six_entries_together():
    m = Manifest(ROOT).validate()
    names = [e["name"] for e in m.doc["per_layer"]]
    assert names[names.index(NEW[0]):][:6] == NEW  # PR 25 appended its two after them
    for name in NEW:
        spec, entry = m.layer_metric(name), m.per_layer[name]
        assert callable(m.reader(spec["reader"]))
        assert spec["workloads"] == entry["workloads"]
        assert set(entry["workloads"]) >= set(PPO_CELLS)
    assert [c for c in m.cells if "idle_attributed_pct" in {x["name"] for x in m.metrics_for(c, "per_layer")}] == list(m.cells)
    ilql = {x["name"] for x in m.metrics_for("gptneo1.3b.ilql-256", "per_layer")}
    assert ilql & set(NEW) == {"idle_attributed_pct", "train_host_ms_per_step"}
    assert m.per_layer["decode_ms_per_step"]["moves"] == "tokens_per_s_chip"


@pytest.mark.parametrize("cell, expected", [
    ("gptj6b-l8.ppo-768x256",
     {"train_host_ms_per_step", "generate_s_per_iter", "iter_boundary_host_s", "span_coverage_pct"}),
    ("gptneo1.3b.ilql-256", {"train_host_ms_per_step"}),
])
def test_rehearsal_names_the_metrics_the_program_logs(cell, expected):
    """A CPU rehearsal has no device plane, so the two `device_trace` metrics
    have nothing to read there; those from the program's own records appear."""
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "TRLX_TPU_SPANS", "TRLX_TPU_GRAFTSCOPE")}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
                          "--rehearsal", "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 3, out.stderr[-2000:]
    (line,) = [ln for ln in out.stdout.splitlines() if ln.startswith("[bench] rehearsal ")]
    named = set(json.loads(line[len("[bench] rehearsal "):])["metrics_named"])
    assert expected <= named

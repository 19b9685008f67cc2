"""The reader PR 35 added (`scope_time`: device seconds by the program's own
scope names) on a hand-made reduction and table: each `reduce`, containers
left out, rows without a table entry counted as unattributed, nothing
without the program or without the program's module, four planes averaged;
and each of its metric files against its BENCHMARK.json entry."""

import json
import os
import sys
import types

import pytest

from benchmark import trace
from benchmark.manifest import Manifest
from benchmark.readers import scope_time

HERE = os.path.dirname(os.path.abspath(__file__))
PATTERNS = json.load(open(os.path.join(os.path.dirname(HERE), "trace_patterns.json")))
NEW = ["scope_attributed_pct", "prefill_device_s_per_iter", "decode_kv_read_s_per_iter", "train_recompute_ms_per_step",
       "train_flash_attn_ms_per_step", "train_lm_head_ms_per_step", "ssm_scan_ms_per_step", "moe_experts_ms_per_step"]

TABLES = [
    {"module": "jit_train_step", "ops": {
        "fusion.1": ["lm_head", "fwd"], "fusion.2": ["lm_head", "bwd"], "fusion.3": ["attn_full/flash_attn", "recompute"],
        "fusion.4": ["", "recompute"], "fusion.5": ["moe_experts/moe_grouped_ffn", "bwd"], "while.1": ["ssm_scan", "fwd"],
        "fusion.6": ["ssm_scan", "fwd"]}},
    {"module": "jit_traced", "ops": {
        "fusion.1": ["prefill/attn_full/kv_read", "fwd"], "fusion.2": ["prefill", "fwd"], "while.3": ["decode_loop", "fwd"],
        "conditional.4": ["decode_loop/attn_full/kv_read", "fwd"], "fusion.5": ["decode_loop/attn_full/kv_read", "fwd"],
        "fusion.6": ["decode_loop/sample", "fwd"]}},
    # the same function noted at a second shape: an instruction's first entry stands
    {"module": "jit_traced", "ops": {"fusion.6": ["decode_loop/kv_read", "fwd"]}},
]


def row(seconds, container=False):
    return {"seconds": seconds, "calls": 1, "container": container, "label": "", "text": "", "mosaic": False}


REDUCTION = {
    "n_devices": 1, "busy_s": 10.0, "window_s": 11.0,
    "programs": {"jit_train_step": {"count": 4, "total_s": 4.0, "median_s": 1.0},
                 "jit_traced": {"count": 1, "total_s": 6.0, "median_s": 6.0},
                 "jit_convert_element_type": {"count": 3, "total_s": 0.1, "median_s": 0.03}},
    "ops": {
        "jit_train_step/fusion.1": row(0.4), "jit_train_step/fusion.2": row(0.8), "jit_train_step/fusion.3": row(0.2),
        "jit_train_step/fusion.4": row(0.6), "jit_train_step/fusion.5": row(0.5), "jit_train_step/while.1": row(0.9, True),
        "jit_train_step/fusion.6": row(0.7), "jit_train_step/copy.9": row(0.3),  # the compiler's own: no entry
        "jit_traced/fusion.1": row(0.05), "jit_traced/fusion.2": row(0.25), "jit_traced/while.3": row(5.5, True),
        "jit_traced/conditional.4": row(2.2, True), "jit_traced/fusion.5": row(2.0), "jit_traced/fusion.6": row(1.0),
        "jit_convert_element_type/fusion.1": row(0.1),  # a program outside the funnel: no table
    },
}


@pytest.fixture
def program(monkeypatch):
    """`trlx_tpu.observability.device_scopes`, as far as the reader uses it."""
    fake = types.ModuleType("trlx_tpu.observability.device_scopes")
    fake.tables = lambda: TABLES
    import trlx_tpu.observability as package

    monkeypatch.setitem(sys.modules, fake.__name__, fake)
    monkeypatch.setattr(package, "device_scopes", fake, raising=False)
    return fake


def ctx(reduction=REDUCTION, traced={"iterations": 2}):
    return {"reduction": reduction, "traced": traced, "trace": trace, "window": {"phases": []}}


def spec(name):
    return json.load(open(os.path.join(os.path.dirname(HERE), "layer_metrics", f"{name}.json")))


def test_each_reduce_on_the_hand_made_trace(program):
    read = lambda name: scope_time.read(ctx(), spec(name))
    # four executions of the train step: milliseconds a step, every pass of the scope, containers left out
    assert read("train_lm_head_ms_per_step") == pytest.approx(1000 * (0.4 + 0.8) / 4)
    assert read("train_flash_attn_ms_per_step") == pytest.approx(1000 * 0.2 / 4)  # a scope stands for what is under it
    assert read("moe_experts_ms_per_step") == pytest.approx(1000 * 0.5 / 4)
    assert read("ssm_scan_ms_per_step") == pytest.approx(1000 * 0.7 / 4)  # the loop is a container: its body counts
    assert read("train_recompute_ms_per_step") == pytest.approx(1000 * (0.2 + 0.6) / 4)  # by pass, scope or none
    # the generate program, over the two traced iterations
    assert read("prefill_device_s_per_iter") == pytest.approx((0.05 + 0.25) / 2)
    assert read("decode_kv_read_s_per_iter") == pytest.approx(2.0 / 2)  # the prefill's read and the conditional are not in it
    # every program; no entry (copy.9, the program outside the funnel) or no scope (fusion.4) is unattributed
    named = 0.4 + 0.8 + 0.2 + 0.5 + 0.7 + 0.05 + 0.25 + 2.0 + 1.0
    assert read("scope_attributed_pct") == pytest.approx(100 * named / (named + 0.6 + 0.3 + 0.1))


def test_nothing_without_the_program_a_trace_or_the_module(program, monkeypatch):
    lm_head = spec("train_lm_head_ms_per_step")
    only_generate = dict(REDUCTION, programs={"jit_traced": REDUCTION["programs"]["jit_traced"]},
                         ops={k: v for k, v in REDUCTION["ops"].items() if k.startswith("jit_traced/")})
    assert scope_time.read(ctx(only_generate), lm_head) is None  # a trace of the rollout alone holds no train step
    assert scope_time.read(ctx(None), lm_head) is None and scope_time.read(ctx(traced=None), lm_head) is None
    # the parent of PR 35 has no such module: the reader returns nothing and does not raise
    monkeypatch.setitem(sys.modules, "trlx_tpu.observability.device_scopes", None)
    monkeypatch.delattr(sys.modules["trlx_tpu.observability"], "device_scopes")
    assert scope_time.read(ctx(), lm_head) is None


def test_four_planes_are_one_chips_seconds_and_one_chips_executions(program):
    """`reduce_planes` divides an operation's seconds by the planes and counts
    a program's executions on every plane: a step's milliseconds stay a step's."""
    four = dict(REDUCTION, n_devices=4,
                programs={**REDUCTION["programs"], "jit_train_step": {"count": 16, "total_s": 4.0, "median_s": 1.0}})
    assert scope_time.read(ctx(four), spec("train_lm_head_ms_per_step")) == pytest.approx(1000 * 1.2 / 4)


def test_on_the_recorded_v5e_trace_without_a_table_everything_is_unattributed(program):
    program.tables = lambda: []
    reduction = trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"), PATTERNS)
    assert scope_time.read(ctx(reduction, {"iterations": 1}), spec("scope_attributed_pct")) == 0.0
    assert scope_time.read(ctx(reduction, {"iterations": 1}), spec("train_lm_head_ms_per_step")) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_metric_file_matches_its_manifest_entry(name):
    manifest = Manifest()
    entry, file = manifest.per_layer[name], manifest.layer_metric(name)
    assert file["reader"] == "scope_time" and file["source"] == entry["source"] == "device_trace"
    assert file["workloads"] == entry["workloads"] and set(entry["workloads"]) <= set(manifest.cells)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert file["reduce"] in ("attributed_pct", "ms_per_train_step", "s_per_iteration")
    assert [m["name"] for m in manifest.doc["per_layer"]][-len(NEW):] == NEW  # appended, in this order

"""The trace reduction on a small trace recorded on a TPU v5e (PR 22,
.scratch/tiny_trace.py of that session): inside one `bench/learn`
annotation, three executions of a jitted `train_step` holding one flash
forward kernel, a 20 ms sleep inside `bench/reward_fn`, then one execution of
a jitted `traced` holding a four-step loop. Expected values were read from
the trace by hand."""

import json
import os

import pytest

from benchmark import flops, trace
from benchmark.readers import kernel_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
PATTERNS = json.load(open(os.path.join(os.path.dirname(HERE), "trace_patterns.json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def reduction():
    return trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"), PATTERNS)


def test_programs_and_their_device_time(reduction):
    assert reduction["n_devices"] == 1
    programs = reduction["programs"]
    assert sorted(programs) == ["jit_traced", "jit_train_step"]  # the fingerprint in brackets is stripped
    assert programs["jit_train_step"]["count"] == 3 and programs["jit_traced"]["count"] == 1
    assert programs["jit_train_step"]["median_s"] == pytest.approx(10.897e-6, rel=1e-3)
    assert trace.program_rows(reduction, "^jit_train_step$") == [programs["jit_train_step"]]


def test_busy_is_the_union_and_idle_is_the_rest(reduction):
    # 41 us of operations in a 22.5 ms window: the 20 ms sleep is idle time
    assert reduction["busy_s"] == pytest.approx(41.0e-6, rel=0.01)
    assert reduction["window_s"] == pytest.approx(22.5e-3, rel=0.01)
    assert reduction["busy_s"] < sum(p["total_s"] for p in reduction["programs"].values()) * 1.01
    label, seconds = reduction["idle_gaps"][0]
    assert label == "bench/reward_fn [jit_train_step -> jit_traced]" and seconds == pytest.approx(21.06e-3, rel=0.01)
    assert all(seconds >= 1e-6 for _, seconds in reduction["idle_gaps"])
    assert sum(reduction["idle_by_label"].values()) == pytest.approx(
        reduction["window_s"] - reduction["busy_s"], rel=0.01)
    # a caller's own window (host clock) replaces first-to-last device event
    again = trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"), PATTERNS, window_ns=30e6)
    assert again["window_s"] == pytest.approx(0.03) and again["busy_s"] == reduction["busy_s"]


def test_operations_belong_to_their_program_and_loops_are_containers(reduction):
    ops = reduction["ops"]
    kernel = ops["jit_train_step/train_step.1"]
    assert kernel["mosaic"] and kernel["calls"] == 3 and not kernel["container"]
    assert kernel["label"] == "train_step mosaic-kernel -> (bf16[4,256,128], f32[4,1,256])"
    assert "{" not in kernel["text"].split("custom_call_target")[0]  # layouts are stripped
    loop = ops["jit_traced/while"]
    assert loop["container"] and ops["jit_traced/fusion.8"]["calls"] == 4  # the body ran four times inside it
    assert [r["label"] for r in trace.op_rows(reduction, {"mosaic": True})] == [kernel["label"]]
    assert trace.op_rows(reduction, {"programs": "^jit_traced$", "select": "while"}) == []  # loops are left out
    top = reduction["device_ops"][0]
    assert top[0] == "jit_train_step/" + kernel["label"] and top[1] == pytest.approx(26.0e-6, rel=0.01)


def test_kernel_roofline_reads_the_calls_own_shapes(reduction):
    kernel = reduction["ops"]["jit_train_step/train_step.1"]
    kind, shape = kernel_roofline.flash_shape(kernel["text"])
    assert kind == "fwd" and shape == {"b": 1, "t": 256, "n_head": 4, "head_dim": 128}
    spec = {"function": "flash", "mosaic": True, "select": r"bf16\[\d+,\d+,\d+\]"}
    ctx = {"reduction": reduction, "peaks": PEAKS, "flops": flops, "trace": trace,
           "arch": {"n_layer": 1, "d_model": 256, "n_head": 2}}
    floor = flops.least_seconds(*flops.flash_call("fwd", **shape), PEAKS)[0]
    assert kernel_roofline.read(ctx, spec) == pytest.approx(100 * 3 * floor / kernel["seconds"])
    assert kernel_roofline.read(ctx, dict(spec, function="logprob_head", exclude=spec["select"], select="")) is None
    # the fused head's three kernels, as the v5e trace of gptneo1.3b.ilql-256 names them
    fwd = ("%jvp__.3 = (f32[2048,1], f32[2048,1], f32[2048,1]) custom-call(f32[2048,4096] %a, "
           "bf16[4096,50257] %b, bf16[1,50257] %c, s32[2048,1] %d)")
    dx = "%transformer.4 = bf16[2048,2048] custom-call(bf16[2048,2048] %pad.9, bf16[50257,2048] %w, s32[2048,1] %y)"
    dw = "%transformer.5 = bf16[50257,2048] custom-call(bf16[2048,2048] %pad.9, bf16[50257,2048] %w, s32[2048,1] %y)"
    assert kernel_roofline.head_shape(fwd) == ("fwd", {"n": 2048, "d": 4096, "v": 50257})
    assert kernel_roofline.head_shape(dx) == ("bwd_dx", {"n": 2048, "d": 2048, "v": 50257})
    assert kernel_roofline.head_shape(dw) == ("bwd_dw", {"n": 2048, "d": 2048, "v": 50257})
    dq = "%attn.40 = bf16[128,1024,256] custom-call(f32[1,1] %c, f32[8,1,1024] %m, bf16[128,1024,256] %q)"
    dkv = "%attn.41 = (bf16[128,1024,256], bf16[128,1024,256]) custom-call(f32[1,1] %c, bf16[128,1024,256] %q)"
    assert kernel_roofline.flash_shape(dq)[0] == "bwd_dq" and kernel_roofline.flash_shape(dkv)[0] == "bwd_dkv"

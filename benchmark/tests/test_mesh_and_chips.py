"""What PR 25 taught the harness: a cell's mesh comes from its file, a
configuration may bring its own count of operations, and a reader that
divides work by device seconds takes one chip's share of the work. On one
chip every reading is what it was, to the digit."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import flops, harness, trace
from benchmark.manifest import ROOT, Manifest, ManifestError
from benchmark.readers import kernel_roofline, op_share, tokens_per_program_second, train_mfu

HERE = os.path.dirname(os.path.abspath(__file__))
PATTERNS = json.load(open(os.path.join(os.path.dirname(HERE), "trace_patterns.json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FSDP4 = "gptj6b-l28.ppo-768x256.fsdp4"
ARCH = {"n_layer": 2, "d_model": 256, "n_head": 2, "vocab_size": 512}
SHAPES = {"method": "ppo", "batch": 8, "seq": 64, "prompt": 48, "response": 16, "unfrozen": 1, "two_qs": False}


def copy_of_the_benchmark(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root, json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def write_doc(root, doc):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)


# ---- the mesh ------------------------------------------------------------------

def test_a_cells_mesh_is_read_from_its_file_and_absent_means_data_parallel():
    m = Manifest(ROOT).validate()
    cell = m.cell(FSDP4)
    assert cell["chips"] == 4 and cell["mesh"] == [1, 4, 1, 1] and harness.cell_mesh(cell) == [1, 4, 1, 1]
    for name in m.cells:
        if name != FSDP4:
            one = m.cell(name)
            assert "mesh" not in one and harness.cell_mesh(one) == [one["chips"], 1, 1, 1]
    assert harness.cell_mesh({"chips": 4}) == [4, 1, 1, 1]
    config, _ = harness.build_config(cell, m.config(cell["config"]), 3, "/nonexistent", True)
    assert config.train.mesh == [1, 4, 1, 1]


@pytest.mark.parametrize("mesh", [[1, 2, 1, 1], [4, 4, 1, 1], [1, 4, 1], [1, 4, 1, 1, 1], [1, -4, -1, 1], [1, 4.0, 1, 1], "fsdp4"])
def test_a_mesh_that_is_not_the_cells_chips_is_refused(tmp_path, mesh):
    root, doc = copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmark", "workloads", f"{FSDP4}.json")
    cell = json.load(open(path))
    cell["mesh"] = mesh
    json.dump(cell, open(path, "w"))
    write_doc(root, doc)
    with pytest.raises(ManifestError, match="mesh"):
        Manifest(root).validate()


def test_a_traced_cycle_is_whole_or_train_steps(tmp_path):
    m = Manifest(ROOT).validate()
    assert m.cell(FSDP4)["traced_cycle"] == "train_steps"
    assert all("traced_cycle" not in m.cell(name) for name in m.cells if name != FSDP4)
    # what a train-steps trace cannot hold is not promised for the cell
    named = {x["name"] for x in m.metrics_for(FSDP4, "per_layer")}
    assert not named & {"rollout_tokens_per_s", "decode_ms_per_step", "score_device_s_per_iter"}
    assert {"train_step_device_ms", "train_mfu_pct", "collective_share_pct", "generate_s_per_iter"} <= named
    root, doc = copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmark", "workloads", f"{FSDP4}.json")
    cell = json.load(open(path))
    cell["traced_cycle"] = "half"
    json.dump(cell, open(path, "w"))
    write_doc(root, doc)
    with pytest.raises(ManifestError, match="traced_cycle"):
        Manifest(root).validate()


# ---- a configuration's own count ------------------------------------------------

def test_a_configurations_flops_name_resolves_to_counts_and_falls_back_to_flops(tmp_path):
    root, doc = copy_of_the_benchmark(tmp_path)
    with open(os.path.join(root, "benchmark", "counts", "half_dense.py"), "w") as f:
        f.write("from benchmark.flops import *  # noqa\n"
                "from benchmark import flops as dense\n\n"
                "def ppo_train_step_flops(*a, **kw):\n    return dense.ppo_train_step_flops(*a, **kw) // 2\n")
    path = os.path.join(root, "benchmark", "configs", "gptneo-1.3b.json")
    config = json.load(open(path))
    config["flops"] = "half_dense"
    json.dump(config, open(path, "w"))
    write_doc(root, doc)
    sys.path.insert(0, root)  # `benchmark.counts` is a package of the checkout the manifest reads
    try:
        for name in [k for k in sys.modules if k == "benchmark.counts" or k.startswith("benchmark.counts.")]:
            del sys.modules[name]
        import benchmark

        benchmark.__path__.insert(0, os.path.join(root, "benchmark"))
        m = Manifest(root).validate()
        own = m.counts(m.config("gptneo-1.3b").get("flops"))
        assert own.__name__ == "benchmark.counts.half_dense"
        args = (ARCH, 8, 48, 16, 1)
        assert own.ppo_train_step_flops(*args) == flops.ppo_train_step_flops(*args) // 2
        for fn in ("ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call", "least_seconds"):
            assert callable(getattr(own, fn))
        assert m.counts(m.config("gptj-6b").get("flops")) is flops  # names none: the dense block
        with pytest.raises(ManifestError):
            m.counts("no_such_count")
        with pytest.raises(ManifestError):
            m.counts("../flops")
    finally:
        sys.path.remove(root)
        benchmark.__path__.remove(os.path.join(root, "benchmark"))
        for name in [k for k in sys.modules if k.startswith("benchmark.counts")]:
            del sys.modules[name]
    assert all("flops" not in Manifest(ROOT).config(c) for c in Manifest(ROOT).configs)  # the hook only, PR 25


# ---- per chip ------------------------------------------------------------------

def plane(name, lines):
    """A stand-in for the profiler's plane: lines of (name, start_ns, duration_ns) events."""
    event = lambda n, s, d: types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=line, events=[event(*e) for e in events]) for line, events in lines.items()])


def device_plane(i, step_ns=1_000_000, gen_ns=4_000_000, extra_ops=()):
    mods = [("jit_train_step(1)", 0, step_ns), ("jit_train_step(1)", 2 * step_ns, step_ns),
            ("jit_traced(2)", 4 * step_ns, gen_ns)]
    ops = [("%fusion.1 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p)", 0, step_ns),
           ("%fusion.1 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p)", 2 * step_ns, step_ns),
           ("%fusion.2 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p)", 4 * step_ns, gen_ns)]
    return plane(f"/device:TPU:{i}", {"XLA Modules": mods, "XLA Ops": ops + list(extra_ops)})


def reader_ctx(reduction, chips):
    return {"reduction": reduction, "peaks": PEAKS, "flops": flops, "trace": trace, "arch": ARCH, "shapes": SHAPES,
            "chips": chips, "traced": {"iterations": 1, "generated_tokens": 4096, "train_steps": 2}}


def test_on_four_planes_mfu_and_token_rate_read_a_quarter_of_the_global_figure():
    one = trace.reduce_planes([device_plane(0)], PATTERNS)
    four = trace.reduce_planes([device_plane(i) for i in range(4)], PATTERNS)
    assert one["n_devices"] == 1 and four["n_devices"] == 4
    # the same programs on every chip: seconds are the mean over the planes, so they do not move
    assert four["busy_s"] == pytest.approx(one["busy_s"])
    assert four["programs"]["jit_train_step"]["median_s"] == one["programs"]["jit_train_step"]["median_s"] == 1e-3
    assert four["programs"]["jit_traced"]["total_s"] == pytest.approx(one["programs"]["jit_traced"]["total_s"])
    spec = {"programs": "^jit_train_step$"}
    whole = flops.ppo_train_step_flops(ARCH, 8, 48, 16, 1)
    assert train_mfu.read(reader_ctx(one, 1), spec) == 100.0 * whole / (1e-3 * 197e12)
    assert train_mfu.read(reader_ctx(four, 4), spec) == pytest.approx(100.0 * (whole / 4) / (1e-3 * 197e12))
    assert train_mfu.read(reader_ctx(four, 4), spec) == pytest.approx(train_mfu.read(reader_ctx(one, 1), spec) / 4)
    gen = {"programs": "^jit_traced$"}
    assert tokens_per_program_second.read(reader_ctx(one, 1), gen) == 4096 / 4e-3
    assert tokens_per_program_second.read(reader_ctx(four, 4), gen) == pytest.approx(1024 / 4e-3)
    # one slow chip moves the mean, not the first chip's alone
    uneven = trace.reduce_planes([device_plane(0)] + [device_plane(i, gen_ns=8_000_000) for i in (1, 2, 3)], PATTERNS)
    assert tokens_per_program_second.read(reader_ctx(uneven, 4), gen) == pytest.approx(1024 / 7e-3)


def test_on_the_recorded_one_chip_trace_the_readers_read_what_they_read_before():
    """The arithmetic of the readers as they stood at PR 24, written out."""
    red = trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"), PATTERNS)
    ctx = reader_ctx(red, 1)
    seconds = trace.median_execution_seconds(red, "^jit_train_step$")
    before = 100.0 * flops.ppo_train_step_flops(ARCH, 8, 48, 16, 1) / (seconds * PEAKS["bf16_flops_per_s"])
    assert train_mfu.read(ctx, {"programs": "^jit_train_step$"}) == before
    total = sum(r["total_s"] for r in trace.program_rows(red, "^jit_traced$"))
    assert tokens_per_program_second.read(ctx, {"programs": "^jit_traced$"}) == 4096 / total
    kernel = red["ops"]["jit_train_step/train_step.1"]
    shape = {"b": 1, "t": 256, "n_head": 4, "head_dim": 128}
    floor = flops.least_seconds(*flops.flash_call("fwd", **shape), PEAKS)[0]
    spec = {"function": "flash", "mosaic": True, "select": r"bf16\[\d+,\d+,\d+\]"}
    assert kernel_roofline.read(ctx, spec) == 100.0 * (kernel["calls"] * floor) / kernel["seconds"]


def test_a_kernels_roofline_does_not_grow_with_the_planes():
    """A row's seconds are the mean over the chips and its calls the sum."""
    call = ("%flash_fwd.1 = (bf16[4,256,128]{2,1,0}, f32[4,1,256]{2,1,0}) custom-call(bf16[4,256,128]{2,1,0} %q), "
            "custom_call_target=\"tpu_custom_call\"", 500_000, 100_000)
    one = trace.reduce_planes([device_plane(0, extra_ops=[call])], PATTERNS)
    four = trace.reduce_planes([device_plane(i, extra_ops=[call]) for i in range(4)], PATTERNS)
    spec = {"function": "flash", "mosaic": True, "select": r"bf16\[\d+,\d+,\d+\]"}
    assert four["ops"]["jit_train_step/flash_fwd.1"]["calls"] == 4
    assert kernel_roofline.read(reader_ctx(four, 4), spec) == pytest.approx(kernel_roofline.read(reader_ctx(one, 1), spec))
    assert 0 < kernel_roofline.read(reader_ctx(four, 4), spec) < 100


# ---- the host line and the collectives -----------------------------------------

def test_the_python3_host_line_is_labelled():
    host = lambda line: plane("/host:CPU", {line: [("train/stats_read", 900_000, 1_200_000)], "main/288": [("noise", 0, 9_000_000)]})
    for line, expected in (("python3", "train/stats_read"), ("python", "train/stats_read"), ("python3.12", "train/stats_read"),
                           ("pythonic-worker", "unattributed")):
        red = trace.reduce_planes([device_plane(0), host(line)], PATTERNS)
        assert red["idle_by_label"][f"{expected} [jit_train_step -> jit_train_step]"] == pytest.approx(1e-3), line


COLLECTIVE = json.load(open(os.path.join(os.path.dirname(HERE), "layer_metrics", "collective_share_pct.json")))


def test_collective_share_counts_an_asynchronous_pair_once():
    """The `XLA Ops` line carries the -start and the -done event (issuing,
    waiting); the span between them, with compute under it, is on the line
    `Async XLA Ops`, which the reduction does not read."""
    ops = [("%all-gather-start.3 = (bf16[2,64]{1,0}, bf16[8,64]{1,0}) all-gather-start(bf16[2,64]{1,0} %w)", 1_000_000, 10_000),
           ("%all-gather-done.3 = bf16[8,64]{1,0} all-gather-done((bf16[2,64]{1,0}, bf16[8,64]{1,0}) %all-gather-start.3)", 1_900_000, 90_000),
           ("%all-reduce.7 = f32[64]{0} all-reduce(f32[64]{0} %g), replica_groups={{0,1,2,3}}", 3_000_000, 100_000),
           ("%fusion.9 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %all-gather-done.3), kind=kLoop", 3_100_000, 50_000)]
    planes = [device_plane(i, extra_ops=ops) for i in range(4)]
    for p in planes:
        p.lines.append(types.SimpleNamespace(name="Async XLA Ops", events=[
            types.SimpleNamespace(name="%all-gather-start.3", start_ns=1_000_000, duration_ns=990_000)]))
    red = trace.reduce_planes(planes, PATTERNS)
    got = op_share.read(reader_ctx(red, 4), COLLECTIVE)
    assert got == pytest.approx(100.0 * (10_000 + 90_000 + 100_000) / 1e9 / red["busy_s"])
    assert 0 < got < 100
    names = sorted(r["label"].split(" ")[0] for r in trace.op_rows(red, COLLECTIVE))
    assert names == ["all-gather-done", "all-gather-start", "all-reduce"]  # not the fusion that reads the result
    assert op_share.read(reader_ctx(trace.reduce_planes([device_plane(0)], PATTERNS), 1), COLLECTIVE) == 0.0


def test_what_a_fusion_calls_survives_the_cut_of_its_text():
    """A row keeps 1,200 characters of an operation's text; a reduce-scatter
    or an overlapped all-gather is known only by the `calls=` after its
    operand list (at most at character 886 in the 28-layer train step as
    compiled for v5e:2x2, PR 25), so that attribute is kept wherever it lies."""
    operands = ", ".join(f"bf16[8,1024,16384]{{2,1,0:T(8,128)(2,1)}} %get-tuple-element.{i}" for i in range(40))
    long = f"%fusion.5779 = (bf16[2,1024,16384]{{2,1,0}}, u32[]{{:S(2)}}) fusion({operands}), kind=kCustom, calls=%async_collective_fusion.12"
    assert trace.LAYOUT.sub("", long).index(", calls=") > trace.TEXT_KEPT
    kept = trace.kept_text(long)
    assert kept.endswith(", calls=%async_collective_fusion.12") and len(kept) < trace.TEXT_KEPT + 40
    short = "%fusion.77 = bf16[528,2048]{1,0} fusion(bf16[2048,2048]{1,0} %fusion.4), kind=kCustom, calls=%all-reduce-scatter.1"
    assert trace.kept_text(short) == trace.LAYOUT.sub("", short)  # nothing added where nothing was cut
    red = trace.reduce_planes([device_plane(0, extra_ops=[(long, 1_000_000, 50_000), (short, 2_000_000, 25_000)])], PATTERNS)
    assert sorted(r["seconds"] for r in trace.op_rows(red, COLLECTIVE)) == pytest.approx([25e-6, 50e-6])


def test_the_recorded_four_chip_trace():
    """tiny_v5e_x4.xplane.pb, recorded on four v5e chips in PR 25 by a script
    started as `python3`: inside one `bench/learn` annotation, three
    executions of a jitted `train_step` over mesh [1,4,1,1] (weights and rows
    sharded over fsdp), a 20 ms sleep inside `bench/reward_fn`, then one
    execution of a jitted `traced` with a four-step loop. Read by hand."""
    red = trace.reduce_file(os.path.join(HERE, "data", "tiny_v5e_x4.xplane.pb"), PATTERNS)
    assert red["n_devices"] == 4
    assert red["programs"]["jit_train_step"]["count"] == 12 and red["programs"]["jit_traced"]["count"] == 4  # x 4 planes
    assert red["programs"]["jit_train_step"]["median_s"] == pytest.approx(292.9e-6, rel=1e-3)
    assert red["busy_s"] == pytest.approx(1082.3e-6, rel=1e-3)  # the mean over the chips, not their sum
    assert red["busy_s"] < sum(p["total_s"] for p in red["programs"].values()) * 1.01
    # the host line is `python3`: the sleep is labelled by the annotation around it
    label, seconds = red["idle_gaps"][0]
    assert label == "bench/reward_fn [jit_train_step -> jit_traced]" and seconds == pytest.approx(21.5e-3, rel=0.01)
    assert not any(k.startswith("unattributed") for k in red["idle_by_label"])
    # collectives as a v5e names them: plain, -start/-done, and a reduce-scatter as a fusion that calls one
    rows = trace.op_rows(red, COLLECTIVE)
    assert sorted({r["label"].split(" -> ")[0] for r in rows}) == [
        "all-gather all-gather", "collective-permute-done collective-permute-done",
        "collective-permute-start collective-permute-start", "fusion fusion"]
    assert all("calls=%all-reduce-scatter" in r["text"] for r in rows if r["label"].startswith("fusion"))
    assert len(rows) == 7 and all(r["calls"] in (12, 16) for r in rows)
    share = op_share.read(reader_ctx(red, 4), COLLECTIVE)
    assert share == pytest.approx(88.19, rel=1e-3) and share == pytest.approx(100 * sum(r["seconds"] for r in rows) / red["busy_s"])
    # per chip: 4,096 tokens over four chips, over the mean seconds of the generate program
    assert tokens_per_program_second.read(reader_ctx(red, 4), {"programs": "^jit_traced$"}) == pytest.approx(
        1024 / red["programs"]["jit_traced"]["total_s"])
    assert red["programs"]["jit_traced"]["total_s"] == pytest.approx(357.1e-6, rel=1e-3)  # one execution, not four


def test_the_benchmark_lifts_a_cap_on_the_compile_cache():
    """Under `JAX_COMPILATION_CACHE_MAX_SIZE` (the chip tool's machine sets 192
    MiB) JAX evicts least-recently-used entries; the four-chip cell's programs
    are larger, so no second run ever hit (PERF.md section 6, PR 25)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_MAX_SIZE="201326592", JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    code = ("import jax; before = jax.config.jax_compilation_cache_max_size\n"
            "from benchmark import harness; where = harness.setup_cache()\n"
            "print(before, jax.config.jax_compilation_cache_max_size, where)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    before, after, where = out.stdout.split()[-3:]
    assert (before, after) == ("201326592", "-1")
    assert where == os.environ.get("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))


# ---- the cell, end to end on the CPU --------------------------------------------

def test_the_four_chip_cells_rehearsal_ends_correct():
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "TRLX_TPU_SPANS", "TRLX_TPU_GRAFTSCOPE")}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", FSDP4,
                          "--rehearsal", "--trace", "1", "--seed", "2147483659"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-2000:]
    lines = {ln.split(" ", 2)[1]: json.loads(ln.split(" ", 2)[2]) for ln in out.stdout.splitlines() if ln.startswith("[bench] ")}
    assert lines["device"]["count"] == 4
    assert all(lines["rehearsal"]["checks"].values()), lines["rehearsal"]["checks"]
    assert {"kv_read_share", "generate_s_per_iter"} <= set(lines["rehearsal"]["metrics_named"])


def test_the_control_fails_the_limits_the_program_passes():
    """benchmark/control.py at the rehearsal size (four forced CPU devices,
    fsdp 4): the reference at the cell's yardstick with every matmul fed int8
    (scaled per tensor), put in the program's place, must come out as not
    correct on every seed, and the program as correct. The chip readings at
    the cell's own size are in PERF.md section 2."""
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "TRLX_TPU_SPANS", "TRLX_TPU_GRAFTSCOPE")}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "control.py"), "--workload", FSDP4,
                          "--rehearsal", "--seeds", "3"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(ln[len("[control] "):]) for ln in out.stdout.splitlines()
            if ln.startswith("[control] {")]
    (verdict,) = [json.loads(ln[len("[control] verdict "):]) for ln in out.stdout.splitlines()
                  if ln.startswith("[control] verdict ")]
    assert len(rows) == 3 and verdict["separates"]
    for r in rows:
        assert r["program_passes"] and not r["control_passes"]
        assert r["yardstick"] == "bfloat16_stream" and r["rel_rms"] <= r["limit"] < r["controls"]["int8"]
    assert verdict["control_rel_rms_min"] > 3 * verdict["program_rel_rms_max"]
    # `--reference-only`, one device and no trainer, reads the reference's side on the same weights and sample
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "control.py"), "--workload", FSDP4, "--rehearsal",
                          "--reference-only", "--seeds", "3"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    alone = [json.loads(ln[len("[control] "):]) for ln in out.stdout.splitlines() if ln.startswith("[control] {")]
    assert [r["seed"] for r in alone] == [r["seed"] for r in rows] and all("rel_rms" not in r for r in alone)
    for a, r in zip(alone, rows):
        assert a["ref_rms"] == r["ref_rms"]
        assert a["bf16_reference_rel_rms"] == pytest.approx(r["bf16_reference_rel_rms"], rel=1e-3)
        assert a["controls"]["int8"] == pytest.approx(r["controls"]["int8"], rel=1e-2)

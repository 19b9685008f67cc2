"""The count module of the hybrid state-space / attention configuration
(benchmark/counts/ssm_hybrid.py) against parameters counted from the
program's own tree and sums made by hand; the configuration's, the cell's and
the reference's files (the cases ISSUE 32 asked for in test_manifest.py and
test_reference.py live here: a PR edits no file the benchmark has); and the
cell's rehearsal run. By hand, as the rest of benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.counts import ssm_hybrid
from benchmark.flops import kept_pairs, mlp_head_flops
from benchmark.manifest import ROOT, Manifest
from benchmark.references import ssm_hybrid_decoder

CELL, CONFIG = "granite4hmicro.ppo-128x896", "granite-4.0-h-micro"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tree_sizes(arch):
    """{path: parameters} of the configuration's trunk, shapes only."""
    from trlx_tpu.models.lm import LMConfig, TransformerLM

    model = TransformerLM(LMConfig.from_dict(arch))
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = jax.eval_shape(lambda r: model.init(r, ids, jnp.ones_like(ids))["params"], jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(p): int(np.prod(leaf.shape)) for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_built_tree_has_the_published_parameter_count():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    sizes = _tree_sizes(arch)
    of = lambda *parts: sum(n for k, n in sizes.items() if all(part in k for part in parts))
    counted = ssm_hybrid.parameters(arch)
    assert of("'h_0'") == counted["mamba"] == 76_182_976  # a state-space layer
    assert of("'h_5'") == counted["attention"] == 60_821_504  # an attention layer
    assert of("'wte'") == counted["table"] == 205_520_896 == 100352 * 2048  # tied: the head is the table
    assert sum(sizes.values()) == counted["trunk"] == 3_191_396_096
    assert of("'h_0'", "in_proj") == 2048 * 8512 and of("'h_0'", "out_proj") == 4096 * 2048
    assert of("'h_0'", "conv_") == 4352 * 4 + 4352 and of("'h_0'", "norm_scale") == 4096
    assert of("'h_0'", "dt_bias") == of("'h_0'", "A_log") == of("'h_0'", "'D'") == 64
    assert ssm_hybrid.ssm_matmul_params(arch) == 2048 * 8512 + 4096 * 2048
    assert ssm_hybrid.attention_params(arch) == of("'h_5'", "'attn'") == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert ssm_hybrid.mlp_params(arch) == of("'h_0'", "'mlp'") == 3 * 2048 * 8192
    assert not any("lm_head" in k or "wpe" in k for k in sizes)
    assert ssm_hybrid.GROUP == arch["n_head"] // arch["n_kv_head"] == 4 and ssm_hybrid.head_dim(arch) == 64
    assert ssm_hybrid.layer_windows(arch) == [0, 0, 0, 0]
    assert [i for i, kind in enumerate(arch["mixer_layers"]) if kind == "attention"] == [5, 15, 25, 35]


def test_counts_against_sums_made_by_hand():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    # one layer's scan over a train batch, [8, 1024]: 4 chunks of 256 a row
    ops, moved = ssm_hybrid.ssd_scan_call(arch, 8, 1024)
    half = 256 * 257 // 2
    a_chunk = half * 128 + 64 * half * 64 + 2 * 64 * 256 * 64 * 128 + 64 * 64 * 128
    assert ops == 2 * 8 * 4 * a_chunk and 3.1e6 < ops / 8192 < 3.3e6  # some 3.2 MFLOP a token a layer
    assert moved == 8 * 1024 * ((2 * 4096 + 2 * 128) * 2 + 64 * 4)
    # a prefill of 128 tokens is one short chunk
    assert ssm_hybrid.ssd_scan_call(arch, 32, 128)[0] == 2 * 32 * (
        128 * 129 // 2 * 128 + 64 * (128 * 129 // 2) * 64 + 2 * 64 * 128 * 64 * 128 + 64 * 64 * 128)
    # one train step of the cell: batch 8, 128 + 896, the top two blocks (both state-space) train
    n = 8 * 1024
    dense_s, dense_a = 2 * n * (2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192), 2 * n * (10_485_760 + 3 * 2048 * 8192)
    attn = 2 * 2 * 8 * 32 * 64 * kept_pairs(1024)
    trunk = 34 * (2 * dense_s + 3 * ops) + 4 * (2 * dense_a + 3 * attn) + 2 * (3 * dense_s + 3 * ops)
    assert ssm_hybrid.trunk_train_flops(arch, 8, 1024, 2) == trunk
    head = 3 * 2 * 8 * 896 * 2048 * 100352
    assert ssm_hybrid.ppo_train_step_flops(arch, 8, 128, 896, 2) == trunk + head + 3 * mlp_head_flops(8 * 896, 2048, 1)
    assert 100e12 < ssm_hybrid.ppo_train_step_flops(arch, 8, 128, 896, 2) < 125e12
    with pytest.raises(NotImplementedError):
        ssm_hybrid.ilql_train_step_flops(arch, 8, 1024, 2)
    # a decode step over 32 rows reading 576 slots: weights once, the state twice, the keys
    state = 36 * 32 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert ssm_hybrid.state_bytes(arch, 32) == state and 2.44e9 < state < 2.46e9
    needed, rw = ssm_hybrid.decode_step_bytes(arch, 32, 576)
    assert rw == 2 * state and needed == 3_191_396_096 * 2 + 2 * state + 4 * 32 * 576 * 2 * 8 * 64 * 2
    assert 0.42 < rw / needed < 0.44  # the new mechanism's share of a step's bytes
    # the flash floor at this family's group and TRUE head width: the call the reader parses is padded to 128
    from benchmark.readers.kernel_roofline import flash_shape
    kind, shape = flash_shape("%flash_bwd_dkv.3 = (bf16[64,1024,128], bf16[64,1024,128]) custom-call(")
    assert kind == "bwd_dkv" and shape == dict(b=1, t=1024, n_head=64, head_dim=128)  # 8 rows x 8 K/V heads
    assert ssm_hybrid.flash_call(kind, **shape) == ssm_hybrid.flash_call("bwd_dkv", 8, 1024, 8, 64) == (
        2 * 2 * 8 * 32 * 64 * kept_pairs(1024), 8 * 1024 * 64 * (2 * 32 + 4 * 8) * 2)
    assert ssm_hybrid.HEAD == ssm_hybrid.head_dim(arch)


def test_the_program_s_own_counters_agree_with_the_count_module():
    from trlx_tpu.models.lm import LMConfig, cache_bytes, cache_bytes_per_token, decode_step_bytes, state_bytes

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    assert state_bytes(cfg, 32) == ssm_hybrid.state_bytes(arch, 32)
    assert cache_bytes(cfg, 32, 1024) - state_bytes(cfg, 32) == 4 * 32 * 1024 * 2 * 8 * 64 * 2  # the four attention layers
    assert cache_bytes_per_token(cfg) == 4 * 2 * 8 * 64 * 2
    weights = 2 * ssm_hybrid.parameters(arch)["trunk"]
    assert decode_step_bytes(cfg, 32, 576, weights) == ssm_hybrid.decode_step_bytes(arch, 32, 576)


def test_the_configuration_is_the_catalog_s_row_with_nothing_reduced():
    m = Manifest(ROOT).validate()
    assert len(m.doc["workloads"]) >= 8 and sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    spec = m.config(CONFIG)
    entry = m.configs[CONFIG]
    assert spec["reduced"] == {} and entry["reduced"] == []
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged
        assert spec[key] == value, key
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == CONFIG]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["n_layer"], arch["n_head"], arch["n_kv_head"], arch["d_ff"], arch["vocab_size"],
            arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"], arch["ssm_conv"], arch["ssm_chunk"],
            arch["embedding_multiplier"], arch["attention_multiplier"], arch["residual_multiplier"],
            arch["logits_scaling"], arch["ln_eps"], arch["tie_word_embeddings"]) == (
        published["hidden_size"], published["num_hidden_layers"], published["num_attention_heads"],
        published["num_key_value_heads"], published["shared_intermediate_size"], published["vocab_size"],
        published["mamba_n_heads"], published["mamba_d_head"], published["mamba_d_state"], published["mamba_d_conv"],
        published["mamba_chunk_size"], published["embedding_multiplier"], published["attention_multiplier"],
        published["residual_multiplier"], published["logits_scaling"], published["rms_norm_eps"],
        published["tie_word_embeddings"])
    assert arch["mixer_layers"] == published["layer_types"] and arch["pos_type"] == "none"
    assert arch["ssm_heads"] * arch["ssm_head_dim"] == published["mamba_expand"] * published["hidden_size"]
    assert arch["head_width"] * arch["n_head"] == published["hidden_size"]
    assert {"weights", "embedding", "A_log", "D", "dt_bias", "conv", "state", "conv_state", "products", "padding"} <= set(spec["assumed"])
    assert spec["serving"] == {"param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": False,
                               "decode_weight_quant": False, "remat": True}
    cell = m.cell(CELL)
    assert cell["traffic_params"] == m.cell("kexaone-l5.ppo-128x896")["traffic_params"] == m.cell("gptj6b-l8.ppo-128x896")["traffic_params"]
    assert cell["recipe"] == {"model": {"num_layers_unfrozen": 2}, "train": {"batch_size": 8},
                              "method": {"chunk_size": 32, "num_rollouts": 32, "ppo_epochs": 4}}
    assert cell["expect_kernels"] == m.cell("kexaone-l5.ppo-128x896")["expect_kernels"]  # flash (heads padded to 128) and the fused head
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert {"ssm_state_gb", "ssm_state_rw_share", "ssm_decode_roofline", "ssm_scan_share_pct", "ssm_scan_roofline",
            "rollout_cache_gb", "kv_read_share", "generate_s_per_iter", "logprob_head_roofline", "train_mfu_pct",
            "train_step_device_ms"} <= named
    # the traced run holds the first measured iteration's last train steps: no rollout, no scoring in it
    assert cell["traced_cycle"] == "train_steps"
    assert not {"decode_ms_per_step", "rollout_tokens_per_s", "score_device_s_per_iter"} & named
    assert {"flash_roofline", "flash_kept_pair_share"} <= named and "expert_ffn_roofline" not in named
    for name in ("ssm_state_gb", "ssm_state_rw_share", "ssm_decode_roofline", "ssm_scan_share_pct", "ssm_scan_roofline"):
        assert m.per_layer[name]["workloads"] == [CELL]


def test_the_reference_matches_the_program_at_the_rehearsal_widths():
    from trlx_tpu.models.lm import LMConfig, TransformerLM

    arch = Manifest(ROOT).config(CONFIG)["rehearsal_arch"]
    model = TransformerLM(LMConfig.from_dict({**arch, "dtype": "float32", "param_dtype": "float32", "attn_impl": "xla"}))
    ids = jnp.asarray(np.random.default_rng(0).integers(2, arch["vocab_size"], size=(2, 48)), jnp.int32)
    mask = np.ones((2, 48), np.int32)
    mask[1, :16] = 0  # a left-padded row: the reference runs it unpadded
    params = model.init(jax.random.PRNGKey(1), ids, jnp.asarray(mask))["params"]
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids, jnp.asarray(mask))["logits"][:, -24:]
    want = ssm_hybrid_decoder.forward(params, arch, ids, mask, last=24)
    assert want.shape == (2, 24, arch["vocab_size"]) and want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=1e-4)
    # the coarser reruns stand in their order, and a bf16 state is one step below the stream
    far = {name: float(jnp.sqrt(jnp.mean((ssm_hybrid_decoder.forward(params, arch, ids, mask, 24, precision=name) - want) ** 2)))
           for name in ssm_hybrid_decoder.PRECISIONS}
    assert far["highest"] == 0.0
    assert 0 < far["bfloat16"] < far["bfloat16_stream"] < min(far["int8_dense"], far["int8"]), far
    assert far["bfloat16_state"] > far["bfloat16_stream"], far
    with pytest.raises(ValueError, match="precision"):
        ssm_hybrid_decoder.forward(params, arch, ids, mask, 24, precision="float8")
    with pytest.raises(ValueError, match="ssm_hybrid_decoder is the reference"):
        ssm_hybrid_decoder.forward(params, dict(arch, pos_type="rotary"), ids, mask, 24)


def test_rehearsal_names_every_new_metric():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    assert {"ssm_state_gb", "ssm_state_rw_share", "rollout_cache_gb", "kv_read_share"} <= set(said["metrics_named"])
    summary = json.load(open(os.path.join(ROOT, "benchmark_out", CELL, "summary.json")))
    # four state-space layers: a float32 state [8, 16, 16] and a window [3, 160] in the run's dtype, 8 rows
    itemsize = 2
    state = 4 * 8 * (8 * 16 * 16 * 4 + 3 * 160 * itemsize)
    assert summary["metrics"]["ssm_state_gb"]["value"] == pytest.approx(state / 1e9)
    assert summary["metrics"]["rollout_cache_gb"]["value"] == pytest.approx((state + 8 * 32 * 2 * 2 * 16 * itemsize) / 1e9)
    assert 0 < summary["metrics"]["ssm_state_rw_share"]["value"] < 1
    assert summary["metrics"]["kv_read_share"]["value"] == pytest.approx(1.0)


def test_the_scan_s_two_metrics_read_one_select():
    """`ssm_scan_roofline` names `ssm_scan_share_pct` under `operations_of` and reads that file's `select`."""
    from benchmark import trace
    from benchmark.readers import op_share, scan_roofline

    m = Manifest(ROOT)
    share, roofline = m.layer_metric("ssm_scan_share_pct"), m.layer_metric("ssm_scan_roofline")
    assert roofline["operations_of"] == "ssm_scan_share_pct" and "select" not in roofline and share["select"]
    row = lambda text, seconds: {"container": False, "mosaic": False, "text": text, "seconds": seconds, "calls": 1}
    reduction = {"busy_s": 2.0, "ops": {
        "jit_step/%fusion.1": row("%fusion.1 = f32[8,4,256,64,64] fusion(...)", 0.5),  # the scan's: [rows, chunks, Q, 64, 64]
        "jit_step/%fusion.2": row("%fusion.2 = bf16[8,1024,8512] fusion(...)", 1.0)}}  # in_proj's output: not the scan's
    arch = m.config(CONFIG)["model_arch"]
    ctx = {"reduction": reduction, "peaks": m.peaks("TPU v5 lite"), "flops": ssm_hybrid, "arch": arch, "trace": trace,
           "chips": 1, "cell": m.cell(CELL), "traced": {"iterations": 1, "generated_tokens": 0, "train_steps": 8},
           "shapes": {"batch": 8, "seq": 1024, "prompt": 128, "unfrozen": 2}}
    assert op_share.read(ctx, share) == pytest.approx(25.0)
    least = 8 * 3 * 36 * ssm_hybrid.least_seconds(*ssm_hybrid.ssd_scan_call(arch, 8, 1024), ctx["peaks"])[0]
    assert scan_roofline.read(ctx, roofline) == pytest.approx(100 * least / 0.5)
    assert scan_roofline.read({**ctx, "reduction": {"busy_s": 2.0, "ops": {}}}, roofline) is None


def test_state_parity_rehearses_and_names_its_limit():
    """benchmark/state_parity.py, the state check: the control flow at the rehearsal widths, and the cell's limit."""
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "state_parity.py"), "--workload", CELL,
                          "--rehearsal"], capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-2000:]
    said = json.loads(next(l for l in out.stdout.splitlines() if l.startswith("[state_parity] ")).split(" ", 1)[1])
    assert said["state_leaf"] == [[2, 8, 16, 16], "float32"] and said["layer"] == 0 and said["passes"]
    assert 0 < said["state_rel_rms"] < said["tol_decode_state_rel_rms"]
    assert said["reference_state_rel_rms"]["bfloat16_stream"] < said["reference_state_rel_rms"]["bfloat16_state"]
    assert Manifest(ROOT).cell(CELL)["tolerances"]["decode_state_rel_rms"] < 0.05

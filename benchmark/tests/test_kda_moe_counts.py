"""The count module of the gated delta-rule / latent-attention / sparse-expert
configuration (benchmark/counts/kda_moe.py) against parameters counted from the
program's own tree and sums made by hand; the configuration's, the cell's and
the reference's files; the reader that divides a count by scope time; and the
cell's rehearsal run. By hand, as the rest of benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.counts import kda_moe
from benchmark.flops import kept_pairs, mlp_head_flops
from benchmark.manifest import ROOT, Manifest
from benchmark.references import kda_mla_moe_decoder
from trlx_tpu.models import kda

CELL, CONFIG = "kimilinear-l13.ppo-128x896", "kimi-linear-48b-ep32-l13"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("kda_state_gb", "kda_state_rw_share", "kda_decode_roofline", "kda_scan_ms_per_step", "kda_scan_roofline")


def _tree_sizes(arch):
    """{path: parameters} of the configuration's trunk, shapes only."""
    from trlx_tpu.models.lm import LMConfig, TransformerLM

    model = TransformerLM(LMConfig.from_dict(arch))
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = jax.eval_shape(lambda r: model.init(r, ids, jnp.ones_like(ids))["params"], jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(p): int(np.prod(leaf.shape)) for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_built_tree_has_the_counted_parameters():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    sizes = _tree_sizes(arch)
    of = lambda *parts: sum(n for k, n in sizes.items() if all(part in k for part in parts))
    counted = kda_moe.parameters(arch)
    d, inner = 2304, 4096
    assert of("'h_0'", "'kda'") == counted["kda"] == 4 * d * inner + 2 * (d * 128 + 128 * inner) + d * 32 + 3 * 4 * inner + 32 + 2 * inner + 128
    assert counted["kda"] == 39_518_368  # ISSUE 39's table rounds the mixer to 39.7 M
    assert of("'h_3'", "'attn'") == counted["attention"] == d * 32 * 192 + d * 576 + 512 * 32 * 256 + inner * d + 512 == 29_114_880
    assert of("'h_0'", "'mlp'") == counted["dense"] == 3 * d * 9216
    assert of("'h_1'", "'moe'") == counted["experts"] == 9 * 3 * d * 1024 + d * 256 + 256  # 8 held + the shared one, router, bias
    assert of("'h_1'", "'shared'") == of("'h_1'", "experts_gate") * 3 // 8 == 3 * d * 1024
    assert of("'wte'") == of("'lm_head'") == counted["table"] == counted["head"] == 20480 * d
    assert of("'h_1'") == counted["kda"] + counted["experts"] + counted["norms"] == 103_814_048  # a kda expert layer
    assert of("'h_3'") == counted["attention"] + counted["experts"] + counted["norms"] == 93_410_560  # a latent expert layer
    assert of("'h_0'") == counted["kda"] + counted["dense"] + counted["norms"] == 103_223_968  # the leading dense layer
    assert sum(sizes.values()) == counted["trunk"] == 1_412_156_224  # ISSUE 39 reckoned 1,414 M with the rounded mixer
    assert kda_moe.kda_matmul_params(arch) == of("'h_0'", "'kda'", "kernel")
    assert kda_moe.attention_params(arch) == of("'h_3'", "'attn'", "kernel")
    assert [i + 1 for i, kind in enumerate(arch["mixer_layers"]) if kind == "attention"] == [4, 8, 12]
    assert kda_moe.layer_windows(arch) == [0, 0, 0] and not any("wpe" in k or "q_a_proj" in k for k in sizes)
    assert (kda_moe.QK_WIDTH, kda_moe.V_WIDTH) == (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], arch["v_head_dim"])
    assert kda_moe.held_share(arch) == 8 / 256


def test_counts_against_sums_made_by_hand():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    # one layer's pass over a train batch, [8, 1024]: 16 chunks of 64 a row, 32 heads of 128
    assert kda_moe.KDA_CHUNK == kda.CHUNK == 64  # the count follows the program's chunk
    ops, moved = kda_moe.kda_scan_call(arch, 8, 1024)
    lower, half = 64 * 63 // 2, 64 * 65 // 2
    a_chunk_head = 128 * (lower + half + 2 * lower + half) + 3 * 64 * 128 * 128 + 128 * 128
    assert ops == 2 * 8 * 16 * 32 * a_chunk_head and 4.4e6 < ops / 8192 < 4.5e6  # some 4.5 MFLOP a token a layer
    assert moved == 8 * 1024 * 32 * (128 * (4 * 2 + 4) + 4) and 403e6 < moved < 405e6
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = kda_moe.least_seconds(ops, moved, peaks)
    assert bound == "memory" and 0.49e-3 < seconds < 0.50e-3
    assert kda_moe.kda_scan_call(arch, 32, 128)[0] == 2 * 32 * 2 * 32 * a_chunk_head  # a prefill: two chunks a row
    # one train step of the cell: batch 8, 128 + 896, the top four blocks (kda, kda, latent, kda) train
    n = 8 * 1024
    experts = 2304 * 256 + 3 * 2304 * 1024 + 8 * (8 / 256) * 3 * 2304 * 1024
    dense_k = lambda ffn: 2 * n * (kda_moe.kda_matmul_params(arch) + ffn)
    dense_a = 2 * n * (kda_moe.attention_params(arch) + experts)
    attn = 2 * 8 * 32 * (192 + 128) * kept_pairs(1024)
    trunk = (2 * dense_k(3 * 2304 * 9216) + 3 * ops) + 6 * (2 * dense_k(experts) + 3 * ops) + 2 * (2 * dense_a + 3 * attn) \
        + 3 * (3 * dense_k(experts) + 3 * ops) + (3 * dense_a + 3 * attn)
    assert kda_moe.trunk_train_flops(arch, 8, 1024, 4) == trunk
    head = 3 * 2 * 8 * 896 * 2304 * 20480
    assert kda_moe.ppo_train_step_flops(arch, 8, 128, 896, 4) == trunk + head + 3 * mlp_head_flops(8 * 896, 2304, 1)
    with pytest.raises(NotImplementedError):
        kda_moe.ilql_train_step_flops(arch, 8, 1024, 4)
    # a decode step over 32 rows reading 576 slots: weights once but the embedding, the state twice, the latent slots
    state = 10 * 32 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert kda_moe.state_bytes(arch, 32) == state and 0.69e9 < state < 0.70e9
    needed, rw = kda_moe.decode_step_bytes(arch, 32, 576)
    assert rw == 2 * state
    assert needed == (1_412_156_224 - 20480 * 2304) * 2 + 32 * 2304 * 2 + 2 * state + 3 * 32 * 576 * 576 * 2
    assert 0.32 < rw / needed < 0.35 and 4.1e9 < needed < 4.2e9
    # the flash floor at this family's TRUE widths: the call the reader parses is padded to 256
    from benchmark.readers.kernel_roofline import flash_shape
    kind, shape = flash_shape("%flash_bwd_dq.3 = bf16[256,1024,256] custom-call(")  # 8 rows x 32 heads
    assert kind == "bwd_dq" and shape == dict(b=1, t=1024, n_head=256, head_dim=256)
    assert kda_moe.flash_call(kind, **shape) == (2 * 256 * (192 + 128) * kept_pairs(1024), 256 * 1024 * (3 * 192 + 2 * 128) * 2)
    assert kda_moe.expert_ffn_call(2048, 8, 2304, 1024) == (3 * 2 * 2048 * 2304 * 1024, (8 * 3 * 2304 * 1024 + 2048 * (2 * 2304 + 3 * 1024)) * 2)


def test_the_program_s_own_counters_agree_with_the_count_module():
    from trlx_tpu.models.lm import LMConfig, cache_bytes, cache_bytes_per_token, decode_step_bytes, state_bytes

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    assert state_bytes(cfg, 32) == kda_moe.state_bytes(arch, 32)
    assert cache_bytes(cfg, 32, 1024) - state_bytes(cfg, 32) == 3 * 32 * 1024 * 576 * 2  # the three latent layers
    assert cache_bytes_per_token(cfg) == 3 * 576 * 2
    count = kda_moe.parameters(arch)
    weights = 2 * (count["trunk"] - count["table"])
    needed, rw = kda_moe.decode_step_bytes(arch, 32, 576)
    assert decode_step_bytes(cfg, 32, 576, weights) == (needed - 32 * 2304 * 2, rw)  # the count adds the looked-up rows


def test_the_configuration_is_the_catalog_s_row_with_the_stated_cuts():
    m = Manifest(ROOT).validate()
    assert len(m.doc["workloads"]) == 10 and len(m.doc["configs"]) == 8 and len(m.doc["per_layer"]) == 49
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    spec, entry = m.config(CONFIG), m.configs[CONFIG]
    reduced = {"num_hidden_layers": 13, "num_experts": 8, "vocab_size": 20480}
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted([*reduced, "num_layers_unfrozen"])
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged but for the stated cuts
        assert spec[key] == reduced.get(key, value), key
    assert (published["num_hidden_layers"], published["num_experts"], published["vocab_size"]) == (27, 256, 163840)
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch, linear = spec["model_arch"], published["linear_attn_config"]
    assert (arch["d_model"], arch["d_ff"], arch["n_head"], arch["expert_d_ff"], arch["n_experts"], arch["experts_per_token"],
            arch["n_shared_experts"], arch["routed_scaling_factor"], arch["kv_lora_rank"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"], arch["ln_eps"], arch["tie_word_embeddings"]) == (
        published["hidden_size"], published["intermediate_size"], published["num_attention_heads"],
        published["moe_intermediate_size"], published["num_experts"], published["num_experts_per_token"],
        published["num_shared_experts"], published["routed_scaling_factor"], published["kv_lora_rank"],
        published["qk_nope_head_dim"], published["qk_rope_head_dim"], published["v_head_dim"], published["rms_norm_eps"],
        published["tie_word_embeddings"])
    assert (arch["kda_heads"], arch["kda_head_dim"], arch["kda_conv"]) == (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
    assert arch["q_lora_rank"] == 0 and published["q_lora_rank"] is None and published["mla_use_nope"] and arch["pos_type"] == "none"
    assert arch["mixer_layers"] == ["kda" if i + 1 in linear["kda_layers"] else "attention" for i in range(13)]
    assert arch["ffn_layers"] == ["dense"] * published["first_k_dense_replace"] + ["experts"] * 12
    assert (arch["n_layer"], arch["vocab_size"], arch["experts_held"]) == (13, 20480, [0, 8])
    assert {"linear_attn_config", "kda_bottleneck", "kda_conv", "A_log", "dt_bias", "state", "weights", "embedding",
            "e_score_correction_bias", "deployment", "decode_weight_quant", "kv_cache_quant"} <= set(spec["assumed"])
    assert "32 chips" in spec["deployment"] and spec["serving"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": False, "decode_weight_quant": False, "remat": True}
    cell = m.cell(CELL)
    assert cell["traffic_params"] == m.cell("kimik2.5-l5.ppo-128x896")["traffic_params"] == m.cell("gptj6b-l8.ppo-128x896")["traffic_params"]
    assert cell["recipe"]["method"] == {"chunk_size": 32, "num_rollouts": 32, "ppo_epochs": 4}
    assert cell["expect_kernels"] == m.cell("kimik2.5-l5.ppo-128x896")["expect_kernels"]  # flash (192/128 padded to 256) and the fused head
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | {"rollout_cache_gb", "kv_read_share", "generate_s_per_iter", "logprob_head_roofline", "flash_roofline",
                               "train_mfu_pct", "train_step_device_ms", "scope_attributed_pct", "expert_ffn_roofline",
                               "moe_experts_ms_per_step", "moe_held_slot_share", "experts_touched_per_step"} <= named
    assert not {"ssm_state_gb", "ssm_scan_roofline", "loop_decode_roofline", "collective_share_pct"} & named
    # the traced run holds the first measured iteration's last train steps (a whole traced cycle passes 360 s): no rollout in it
    assert cell["traced_cycle"] == "train_steps"
    assert not {"decode_ms_per_step", "rollout_tokens_per_s", "score_device_s_per_iter", "prefill_device_s_per_iter",
                "decode_kv_read_s_per_iter"} & named
    assert cell["tolerances"] == {**cell["tolerances"], "logits_yardstick": "bfloat16_stream", "logits_rel_rms": 0.089,
                                  "logits_vs_bf16_reference": 1.76, "mean_ratio": 0.0067, "decode_state_rel_rms": 0.0069}
    for name in NEW_METRICS:
        assert m.per_layer[name]["workloads"] == [CELL]


def test_the_reference_matches_the_program_at_the_rehearsal_widths():
    from trlx_tpu.models.lm import LMConfig, TransformerLM

    arch = Manifest(ROOT).config(CONFIG)["rehearsal_arch"]
    model = TransformerLM(LMConfig.from_dict({**arch, "dtype": "float32", "param_dtype": "float32", "attn_impl": "xla"}))
    ids = jnp.asarray(np.random.default_rng(0).integers(2, arch["vocab_size"], size=(2, 48)), jnp.int32)
    mask = np.ones((2, 48), np.int32)
    mask[1, :16] = 0  # a left-padded row: the reference runs it unpadded
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids, jnp.asarray(mask))["params"]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply({"params": p}, ids, jnp.asarray(mask))["logits"][:, -24:])(params)
    want = kda_mla_moe_decoder.forward(params, arch, ids, mask, last=24)
    assert want.shape == (2, 24, arch["vocab_size"]) and want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-4)
    far = {name: float(jnp.sqrt(jnp.mean((kda_mla_moe_decoder.forward(params, arch, ids, mask, 24, precision=name) - want) ** 2)))
           for name in kda_mla_moe_decoder.PRECISIONS}
    assert far["highest"] == 0.0
    assert 0 < far["bfloat16"] and 0 < far["bfloat16_stream"] < min(far["int8_dense"], far["int8"]), far
    assert far["bfloat16_state"] != far["bfloat16_stream"], far
    with pytest.raises(ValueError, match="precision"):
        kda_mla_moe_decoder.forward(params, arch, ids, mask, 24, precision="float8")
    with pytest.raises(ValueError, match="kda_mla_moe_decoder is the reference"):
        kda_mla_moe_decoder.forward(params, dict(arch, pos_type="rotary"), ids, mask, 24)


def test_the_roofline_divides_the_count_by_scope_time(monkeypatch):
    """`scope_roofline`: three passes a kda layer a train step of `kda_scan_call`'s floor over `scope_time`'s
    milliseconds under the scope; nothing without the peaks, the count or anything under the scope."""
    from benchmark.readers import scope_roofline, scope_time

    m = Manifest(ROOT)
    arch, spec = m.config(CONFIG)["model_arch"], m.layer_metric("kda_scan_roofline")
    assert (spec["reader"], spec["scopes"], spec["count"], spec["mixer"]) == ("scope_roofline", ["kda_scan"], "kda_scan_call", "kda")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"flops": kda_moe, "peaks": peaks, "arch": arch, "shapes": {"batch": 8, "seq": 1024}}
    asked = []
    monkeypatch.setattr(scope_time, "read", lambda ctx, spec: asked.append(spec) or 250.0)
    floor = kda_moe.least_seconds(*kda_moe.kda_scan_call(arch, 8, 1024), peaks)[0]
    assert scope_roofline.read(ctx, spec) == pytest.approx(100 * 3 * 10 * floor / 0.25) and 5 < scope_roofline.read(ctx, spec) < 7
    assert asked[0]["reduce"] == "ms_per_train_step" and asked[0]["programs"] == "^jit_train_step$"
    assert scope_roofline.read({**ctx, "peaks": None}, spec) is None  # a rehearsal
    assert scope_roofline.read({**ctx, "arch": {**arch, "mixer_layers": ["attention"] * 13}}, spec) is None
    assert scope_roofline.read({**ctx, "flops": object()}, spec) is None
    monkeypatch.setattr(scope_time, "read", lambda ctx, spec: None)  # the parent: nothing under the scope
    assert scope_roofline.read(ctx, spec) is None


def test_rehearsal_names_every_new_metric():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    assert {"kda_state_gb", "kda_state_rw_share", "rollout_cache_gb", "kv_read_share", "moe_held_slot_share"} <= set(said["metrics_named"])
    summary = json.load(open(os.path.join(ROOT, "benchmark_out", CELL, "summary.json")))
    # four kda layers: a float32 state [4, 16, 16] and a window [3, 192] in the run's dtype, 8 rows; one latent layer
    itemsize = 2
    state = 4 * 8 * (4 * 16 * 16 * 4 + 3 * 192 * itemsize)
    assert summary["metrics"]["kda_state_gb"]["value"] == pytest.approx(state / 1e9)
    assert summary["metrics"]["rollout_cache_gb"]["value"] == pytest.approx((state + 8 * 32 * 24 * itemsize) / 1e9)
    assert 0 < summary["metrics"]["kda_state_rw_share"]["value"] < 1
    assert summary["metrics"]["kv_read_share"]["value"] == pytest.approx(1.0)

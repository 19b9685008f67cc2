"""The count module of the grouped-key, windowed, sparse-expert
configuration (benchmark/counts/gqa_moe.py) against parameters counted from
the program's own tree, the cell's files, and its rehearsal run. By hand, as
the rest of benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.counts import gqa_moe
from benchmark.flops import kept_pairs
from benchmark.manifest import ROOT, Manifest
from benchmark.readers.kernel_roofline import flash_shape

CELL, CONFIG = "kexaone-l5.ppo-128x896", "k-exaone-236b-ep16-l5"


def _tree_sizes(arch):
    """{path: parameters} of the configuration's trunk, shapes only."""
    from trlx_tpu.models.lm import LMConfig, TransformerLM

    model = TransformerLM(LMConfig.from_dict(arch))
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = jax.eval_shape(lambda r: model.init(r, ids, jnp.ones_like(ids))["params"], jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(p): int(jnp.prod(jnp.array(leaf.shape))) for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_counts_against_the_tree_at_published_widths():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    sizes = _tree_sizes(arch)
    of = lambda *parts: sum(n for k, n in sizes.items() if all(part in k for part in parts))
    norms = of("'h_1'", "'attn'", "norm")
    assert norms == 2 * 128
    assert gqa_moe.attention_params(arch) == of("'h_1'", "'attn'") - norms == 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    assert gqa_moe.ffn_active_params(arch, "dense") == of("'h_0'", "'mlp'") == 3 * 6144 * 18432
    assert gqa_moe.expert_params(arch) == of("'h_1'", "'shared'") == of("'h_1'", "experts_") // 8 == 37_748_736
    # active in an expert layer: router + shared + 8 slots a token x 8/128 held
    assert gqa_moe.ffn_active_params(arch, "experts") == pytest.approx(6144 * 128 + 37_748_736 * (1 + 8 * 8 / 128))
    assert of("'h_1'") == pytest.approx(453.8e6, rel=1e-3) and of("'h_0'") == pytest.approx(453.0e6, rel=1e-3)
    # the whole chip's share: 2,504 M parameters, as the configuration's file reckons (+ the value head's 75.5 M in the trainer)
    assert sum(sizes.values()) == 2_504_068_864
    # the group the flash reader cannot see is the file's
    assert gqa_moe.GROUP == arch["n_head"] // arch["n_kv_head"] == 8 and gqa_moe.head_dim(arch) == 128
    assert gqa_moe.layer_windows(arch) == [128, 128, 128, 0, 128]
    # one train step of the cell: batch 4, 128 + 896, one block unfrozen
    n = 4 * 1024
    attn = lambda w: gqa_moe.attention_flops(arch, 4, 1024, w)
    lead = 2 * n * (gqa_moe.attention_params(arch) + 3 * 6144 * 18432)
    expert = 2 * n * (gqa_moe.attention_params(arch) + gqa_moe.ffn_active_params(arch, "experts"))
    trunk = (2 * lead + 3 * attn(128)) + 2 * (2 * expert + 3 * attn(128)) + (2 * expert + 3 * attn(0)) + (3 * expert + 3 * attn(128))
    assert gqa_moe.trunk_train_flops(arch, 4, 1024, 1) == pytest.approx(trunk)
    assert 24e12 < gqa_moe.ppo_train_step_flops(arch, 4, 128, 896, 1) < 26e12
    assert gqa_moe.expert_ffn_call(256 * 8, 8, 6144, 2048) == (
        6 * 2048 * 6144 * 2048, (8 * 3 * 6144 * 2048 + 2048 * (2 * 6144 + 3 * 2048)) * 2)


def test_the_flash_floor_counts_the_query_heads_in_every_kernel():
    """The reader takes the head count from a call's result: 4 x 64 for the
    forward and dq, 4 x 8 for the grouped dk/dv; the floor's operations are
    the 256 query heads' in all three, K and V moved once a group."""
    texts = {
        "fwd": "%flash_fwd.3 = (bf16[256,1024,128], f32[256,1,1024]) custom-call(",
        "bwd_dq": "%flash_bwd_dq.3 = bf16[256,1024,128] custom-call(",
        "bwd_dkv": "%flash_bwd_dkv.3 = (bf16[32,1024,128], bf16[32,1024,128]) custom-call(",
    }
    q_bytes, kv_bytes = 1024 * 128 * 256 * 2, 1024 * 128 * 32 * 2
    moved = {"fwd": 2 * q_bytes + 2 * kv_bytes, "bwd_dq": 3 * q_bytes + 2 * kv_bytes, "bwd_dkv": 2 * q_bytes + 4 * kv_bytes}
    for kind, text in texts.items():
        parsed_kind, shape = flash_shape(text)
        assert parsed_kind == kind and shape["n_head"] == (32 if kind == "bwd_dkv" else 256)
        for window in (0, 128):
            assert gqa_moe.flash_call(kind, window=window, **shape) == (2 * 2 * 256 * 128 * kept_pairs(1024, window), moved[kind])


def test_the_cell_validates_and_lists_its_metrics():
    m = Manifest(ROOT).validate()
    assert len(m.doc["workloads"]) == 7 and sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert {"rollout_cache_gb", "expert_ffn_roofline", "expert_ffn_share_pct", "moe_held_slot_share", "moe_max_expert_load",
            "experts_touched_per_step", "flash_roofline", "flash_kept_pair_share", "logprob_head_roofline", "kv_read_share",
            "decode_ms_per_step", "train_mfu_pct"} <= named
    assert {x["name"] for x in m.doc["per_layer"] if CELL in x.get("workloads", ())} >= {
        x["name"] for x in m.doc["per_layer"] if "kimik2.5-l5.ppo-128x896" in x.get("workloads", ())}
    spec = m.config(CONFIG)
    assert sorted(spec["reduced"]) == ["num_experts", "num_hidden_layers", "num_layers_unfrozen", "vocab_size"]
    assert {"qk_norm", "rotary_by_layer_kind", "pre_norm", "e_score_correction_bias", "mtp_block"} <= set(spec["assumed"])
    published = spec["published"]
    for key, value in published.items():  # every published key, unchanged unless named in `reduced`
        assert spec[key] == value or key in spec["reduced"], key
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["n_head"], arch["n_kv_head"], arch["head_width"], arch["d_ff"], arch["expert_d_ff"],
            arch["n_experts"], arch["experts_per_token"], arch["window_size"], arch["routed_scaling_factor"],
            arch["rope_theta"], arch["n_shared_experts"], arch["ln_eps"]) == (
        published["hidden_size"], published["num_attention_heads"], published["num_key_value_heads"], published["head_dim"],
        published["intermediate_size"], published["moe_intermediate_size"], published["num_experts"],
        published["num_experts_per_tok"], published["sliding_window"], published["routed_scaling_factor"],
        published["rope_parameters"]["rope_theta"], published["num_shared_experts"], published["rms_norm_eps"])
    kinds = {"sliding_attention": "local", "full_attention": "global", "sparse": "experts", "dense": "dense"}
    assert arch["attention_layers"] == [kinds[k] for k in published["layer_types"][:5]]
    assert arch["ffn_layers"] == [kinds[k] for k in published["mlp_layer_types"][:5]]
    cell = m.cell(CELL)
    kimi = m.cell("kimik2.5-l5.ppo-128x896")
    assert cell["traffic_params"] == kimi["traffic_params"] and cell["expect_kernels"] == kimi["expect_kernels"]


def test_rehearsal_names_every_new_metric():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    assert {"rollout_cache_gb", "moe_held_slot_share", "moe_max_expert_load", "experts_touched_per_step",
            "kv_read_share"} <= set(said["metrics_named"])
    summary = json.load(open(os.path.join(ROOT, "benchmark_out", CELL, "summary.json")))
    # four rings of 8 and one span of 32, K and V, 8 rows, 2 heads of 16, bf16
    assert summary["metrics"]["rollout_cache_gb"]["value"] == pytest.approx(8 * (4 * 8 + 32) * 2 * 2 * 16 * 2 / 1e9)
    assert summary["metrics"]["kv_read_share"]["value"] == pytest.approx((4 * 8 + 32) / (5 * 32))

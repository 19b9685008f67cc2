"""The count module of the grouped-key, windowed configuration whose router
stands ahead of attention (benchmark/counts/gqa_early_router_moe.py) against
parameters counted from the program's own tree, the cell's files, and its
rehearsal run. By hand, as the rest of benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.counts import gqa_early_router_moe as counts
from benchmark.flops import kept_pairs
from benchmark.manifest import ROOT, Manifest
from benchmark.readers.kernel_roofline import flash_shape

CELL, CONFIG = "smallthinker-ep4.ppo-4096x2048", "smallthinker-21b-ep4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("moe_router_ms_per_step", "moe_rows_per_held_expert", "ring_decode_roofline", "ring_cache_share")


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
    count = counts.parameters(arch)
    assert count["attention"] == of("'h_1'", "'attn'") == 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560 == 20_971_520
    assert count["router"] == of("'h_1'", "'router'") == 163_840
    assert count["expert"] == of("'h_1'", "experts_") // 16 == 5_898_240
    assert not of("e_score_correction_bias") and not of("'shared'") and not of("'mlp'")  # no buffer, no shared expert, no dense layer
    assert count["layer"] == of("'h_0'") == of("'h_7'") == 115_512_320
    assert count["layer_whole"] == 398_627_840  # the layer the four chips share
    assert 52 * count["layer_whole"] + 2 * 151936 * 2560 + 2560 == 21_506_562_560  # the published model
    assert count["table"] == count["head"] == of("'wte'") == of("'lm_head'") == 37984 * 2560
    assert count["trunk"] == sum(sizes.values()) == 8 * 115_512_320 + 2 * 97_239_040 + 2560 == 1_118_579_200
    # active in a layer's feed-forward: the router + 6 slots a token x 16/64 held
    assert counts.ffn_active_params(arch) == pytest.approx(163_840 + 6 * 0.25 * 5_898_240)
    # the group the flash reader cannot see is the file's
    assert counts.GROUP == arch["n_head"] // arch["n_kv_head"] == 7 and counts.head_dim(arch) == 128
    assert counts.layer_windows(arch) == [0, 4096, 4096, 4096] * 2
    cell = Manifest(ROOT).cell(CELL)
    batch, seq, unfrozen = cell["recipe"]["train"]["batch_size"], 6144, cell["recipe"]["model"]["num_layers_unfrozen"]
    n = batch * seq
    attn = lambda w: counts.attention_flops(arch, batch, seq, w)
    dense = 2 * n * (counts.attention_params(arch) + counts.ffn_active_params(arch))
    frozen = lambda w: 2 * dense + 3 * attn(w)
    trunk = (frozen(0) + 3 * frozen(4096)) + (3 * dense + 3 * attn(0)) + 3 * (3 * dense + 3 * attn(4096))
    assert (batch, unfrozen) == (2, 4) and counts.trunk_train_flops(arch, batch, seq, unfrozen) == pytest.approx(trunk)
    assert 29e12 < counts.ppo_train_step_flops(arch, batch, 4096, 2048, unfrozen) < 30e12
    assert 1 - kept_pairs(6144, 4096) / kept_pairs(6144) == pytest.approx(0.111, abs=1e-3)  # what a window cuts of a layer's pairs
    assert counts.expert_ffn_call(384 * 16, 16, 2560, 768) == (
        6 * 6144 * 2560 * 768, (16 * 3 * 2560 * 768 + 6144 * (2 * 2560 + 3 * 768)) * 2)


def test_the_flash_floor_counts_the_query_heads_in_every_kernel():
    """The reader takes the head count from a call's result: 2 x 28 for the
    forward and dq of a two-row train step, 2 x 4 for the grouped dk/dv; the
    floor's operations are the 56 query heads' in all three, K and V moved
    once a group of 7."""
    texts = {
        "fwd": "%flash_fwd.3 = (bf16[56,6144,128], f32[56,1,6144]) custom-call(",
        "bwd_dq": "%flash_bwd_dq.3 = bf16[56,6144,128] custom-call(",
        "bwd_dkv": "%flash_bwd_dkv.3 = (bf16[8,6144,128], bf16[8,6144,128]) custom-call(",
    }
    q_bytes, kv_bytes = 6144 * 128 * 56 * 2, 6144 * 128 * 8 * 2
    moved = {"fwd": 2 * q_bytes + 2 * kv_bytes, "bwd_dq": 3 * q_bytes + 2 * kv_bytes, "bwd_dkv": 2 * q_bytes + 4 * kv_bytes}
    for kind, text in texts.items():
        parsed_kind, shape = flash_shape(text)
        assert parsed_kind == kind and shape["n_head"] == (8 if kind == "bwd_dkv" else 56)
        for window in (0, 4096):
            assert counts.flash_call(kind, window=window, **shape) == (2 * 2 * 56 * 128 * kept_pairs(6144, window), moved[kind])
    # the prefill: 16 rows of 4,096, the window as wide as the block: every causal pair
    _, shape = flash_shape("%flash_fwd.9 = (bf16[448,4096,128], f32[448,1,4096]) custom-call(")
    assert counts.flash_call("fwd", window=4096, **shape)[0] == 2 * 2 * 448 * 128 * (4096 * 4097 // 2)


def test_a_decode_step_s_bytes_and_the_program_s_own_counters():
    from trlx_tpu.models.lm import LMConfig, cache_bytes, cache_bytes_per_token, ring_cache_bytes
    from trlx_tpu.ops.kv_read import kv_keys_read, kv_read_bucket, kv_read_ranges

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    slot = 2 * 4 * 128 * 2  # K and V, 4 heads of 128, bf16
    assert cache_bytes_per_token(cfg) == 8 * slot
    assert cache_bytes(cfg, 16, 6144) == 16 * (6 * 4096 + 2 * 6144) * slot == 1_207_959_552  # 1.21 GB; a span everywhere: 1.61
    assert ring_cache_bytes(cfg, 16, 6144) * 3 == cache_bytes(cfg, 16, 6144) * 2
    # the ranged read of a full layer: buckets of 2,048, three branches; every decode step (4,096 .. 6,143) is in the last
    assert kv_read_bucket(6144) == 2048 and kv_read_ranges(6144) == ((0, 2048), (0, 4096), (0, 6144))
    read, full = kv_keys_read(6144, 4096, 2048, counts.layer_windows(arch), [0, 4096, 4096, 4096] * 2)
    assert (read, full) == (2048 * (6 * 4096 + 2 * 6144), 2048 * 8 * 6144)
    keys = read / full * 6144  # what the reader hands over: the mean slots a layer, 4,608
    needed, cache = counts.decode_step_bytes(arch, 16, keys)
    count = counts.parameters(arch)
    assert cache == 16 * (6 * 4096 + 2 * 6144) * slot == cache_bytes(cfg, 16, 6144)  # every step reads every slot there is
    assert needed == (count["trunk"] - count["table"]) * 2 + 16 * 2560 * 2 + cache
    assert needed == pytest.approx(3.25e9, rel=5e-3)  # 2.04 GB of weights, 1.21 GB of keys


def test_the_configuration_is_the_catalog_s_row_with_the_stated_cuts():
    m = Manifest(ROOT).validate()
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1 and CELL in m.cells and CONFIG in m.configs
    spec, entry = m.config(CONFIG), m.configs[CONFIG]
    reduced = {"num_hidden_layers": 8, "moe_num_primary_experts": 16, "vocab_size": 37984}
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted([*reduced, "num_layers_unfrozen"])
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged but for the stated cuts
        assert spec[key] == reduced.get(key, value), key
    assert (published["num_hidden_layers"], published["moe_num_primary_experts"], published["vocab_size"]) == (52, 64, 151936)
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "SmallThinker-21BA3B-Instruct"]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["n_head"], arch["n_kv_head"], arch["head_width"], arch["expert_d_ff"], arch["n_experts"],
            arch["experts_per_token"], arch["window_size"], arch["rope_theta"], arch["ln_eps"], arch["tie_word_embeddings"]) == (
        published["hidden_size"], published["num_attention_heads"], published["num_key_value_heads"], published["head_dim"],
        published["moe_ffn_hidden_size"], published["moe_num_primary_experts"], published["moe_num_active_primary_experts"],
        published["sliding_window_size"], published["rope_theta"], published["rms_norm_eps"], published["tie_word_embeddings"])
    assert published["sliding_window_layout"] == published["rope_layout"] == [0, 1, 1, 1] * 13
    assert arch["attention_layers"] == ["local" if w else "global" for w in published["sliding_window_layout"][:8]]
    assert arch["ffn_layers"] == ["experts"] * 8 and "n_shared_experts" not in arch and "routed_scaling_factor" not in arch
    assert (arch["router_scoring"], arch["router_input"], arch["activation"], arch["mlp"]) == ("softmax", "block", "relu", "gated")
    assert (arch["n_layer"], arch["vocab_size"], arch["experts_held"], arch["max_position"], arch["window_cache"]) == (8, 37984, [0, 16], 6144, "ring")
    assert {"router_input", "router_scoring", "activation", "no_bias", "rotary_layout", "weights", "embedding", "balance",
            "eos_token_id", "value_head", "deployment", "decode_weight_quant", "kv_cache_quant"} <= set(spec["assumed"])
    assert "of 4 that share each layer" in spec["deployment"] and spec["serving"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": False, "decode_weight_quant": False, "remat": True}
    rehearsal = spec["rehearsal_arch"]
    assert rehearsal["n_head"] // rehearsal["n_kv_head"] == 7 and rehearsal["attention_layers"] == arch["attention_layers"]
    cell = m.cell(CELL)
    assert cell["traffic_params"]["n_prompts"] == cell["recipe"]["method"]["chunk_size"] == cell["recipe"]["method"]["num_rollouts"] == 16
    assert cell["traffic_params"]["prompt_length"] == {"distribution": "uniform", "min": 2048, "max": 4096, "placement": "seeded"}
    assert cell["traffic_params"]["new_tokens"] == 2048 and cell["recipe"]["method"]["ppo_epochs"] == 4
    assert cell["recipe"]["train"]["batch_size"] == 2 and cell["recipe"]["model"]["num_layers_unfrozen"] == 4
    assert cell["expect_kernels"] == m.cell("kexaone-l5.ppo-128x896")["expect_kernels"]
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | {"rollout_cache_gb", "kv_read_share", "generate_s_per_iter", "logprob_head_roofline", "flash_roofline",
                               "train_mfu_pct", "train_step_device_ms", "scope_attributed_pct", "expert_ffn_roofline",
                               "expert_ffn_share_pct", "moe_experts_ms_per_step", "moe_held_slot_share", "moe_max_expert_load",
                               "moe_first_buffer_share", "experts_touched_per_step"} <= named
    assert not {"ssm_state_gb", "kda_state_gb", "loop_decode_roofline", "collective_share_pct"} & named
    for name in NEW_METRICS:  # each new metric lists this cell alone
        assert m.per_layer[name]["workloads"] == [CELL]
    rollout_trace = {"rollout_tokens_per_s", "decode_ms_per_step", "score_device_s_per_iter", "prefill_device_s_per_iter",
                     "decode_kv_read_s_per_iter"}
    assert (cell["traced_cycle"] == "whole") == (rollout_trace <= named) and (rollout_trace <= named or not rollout_trace & named)


def test_rehearsal_names_every_new_counter():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    assert {"moe_rows_per_held_expert", "ring_cache_share", "rollout_cache_gb", "moe_held_slot_share", "moe_max_expert_load",
            "experts_touched_per_step", "kv_read_share"} <= set(said["metrics_named"])
    summary = json.load(open(os.path.join(ROOT, "benchmark_out", CELL, "summary.json")))
    # six rings of 8 and two spans of 32, K and V, 8 rows, 2 heads of 16, bf16
    assert summary["metrics"]["rollout_cache_gb"]["value"] == pytest.approx(8 * (6 * 8 + 2 * 32) * 2 * 2 * 16 * 2 / 1e9)
    assert summary["metrics"]["ring_cache_share"]["value"] == pytest.approx(6 * 8 / (6 * 8 + 2 * 32))
    assert summary["metrics"]["kv_read_share"]["value"] == pytest.approx((6 * 8 + 2 * 32) / (8 * 32))
    share = summary["metrics"]["moe_held_slot_share"]["value"]
    assert summary["metrics"]["moe_rows_per_held_expert"]["value"] == pytest.approx(share * 4 * 32 * 3 / 4)  # 4 rows of 32, 3 a token, 4 held

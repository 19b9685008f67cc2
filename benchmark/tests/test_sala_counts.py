"""The count module of the lightning / block-selected sparse attention
configuration (benchmark/counts/sala.py) against parameters counted from the
program's own tree and sums made by hand; the rule of the choice restated
there against the program's; the configuration's and the cell's files; the
reader that sums scope times. By hand, as the rest of benchmark/tests."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.counts import sala
from benchmark.flops import mlp_head_flops
from benchmark.manifest import ROOT, Manifest
from trlx_tpu.models import lightning, sparse

CELL, CONFIG = "minicpmsala-l4.ppo-10240x2048", "minicpm-sala-9b-l4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("lightning_scan_ms_per_step", "sparse_attn_ms_per_step", "lightning_scan_roofline", "sparse_attn_roofline",
               "sala_decode_roofline", "sparse_kept_pair_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
    counted = sala.parameters(arch)
    d = 4096
    assert of("'h_0'", "'lightning'") == counted["lightning"] == 5 * d * d + 2 * 128 + d == 83_890_432
    assert of("'h_3'", "'attn'") == counted["attention"] == 3 * d * d + 2 * d * 256 + 2 * 128 == 52_429_056
    assert of("'h_0'", "'mlp'") == counted["dense"] == 3 * d * 16384 == 201_326_592
    assert of("'wte'") == of("'lm_head'") == counted["table"] == counted["head"] == 9181 * d
    assert of("'h_0'") == counted["lightning"] + counted["dense"] + counted["norms"] == 285_225_216
    assert of("'h_3'") == counted["attention"] + counted["dense"] + counted["norms"] == 253_763_840
    assert sum(sizes.values()) == counted["trunk"] == 1_109_439_488 + 75_210_752 + 4_096
    assert sala.lightning_matmul_params(arch) == of("'h_0'", "'lightning'", "kernel")
    assert sala.sparse_matmul_params(arch) == of("'h_3'", "'attn'", "kernel")
    assert sala.layer_windows(arch) == [0] and not any("wpe" in k for k in sizes)


def test_the_rule_of_the_choice_is_the_program_s():
    from trlx_tpu.models.lm import LMConfig

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict(arch)
    at = np.concatenate([np.arange(0, 300, 7), np.arange(2000, 2200, 7), np.arange(6100, 6400), np.arange(12000, 12288, 5)])
    # the traced choice's own count, at the published sizes, on seeded queries and compressed keys
    q = jax.random.normal(jax.random.PRNGKey(0), (1, at.size, arch["n_head"], arch["head_width"]))
    kc = jax.random.normal(jax.random.PRNGKey(1), (1, sparse.compressed_slots(cfg, 12288), arch["n_kv_head"], arch["head_width"]))
    chosen = sparse.choose_blocks(q, kc, jnp.asarray(at, jnp.int32)[None], cfg, 192)  # [1, G, Q, 192]
    for group in range(arch["n_kv_head"]):
        assert [sala.chosen_blocks(arch, int(t)) for t in at] == np.asarray(chosen.sum(-1))[0, group].tolist()
    assert [sala.chosen_pairs(arch, t) for t in (0, 63, 64, 6207, 6208)] == [1, 64, 65, 6208, 98 * 64 - 63]
    assert sala.chosen_blocks(arch, 6207) == 97 == sparse.dense_blocks(cfg) and sala.chosen_pairs(arch, 6207) == 6208  # every block
    assert sala.chosen_blocks(arch, 6208) == 98 and sala.chosen_blocks(arch, 6271) == 97 and sala.chosen_blocks(arch, 12287) == 97
    assert sala.chosen_blocks(arch, 12000) == 98 == sparse.gathered_blocks(cfg, 192)  # 1 + 33 + 64: 64 does not divide 12,001
    assert [sala.existing_compressed(arch, t) for t in (0, 30, 31, 46, 47, 12287)] == [0, 0, 1, 1, 2, 767]


def test_counts_against_sums_made_by_hand():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    # one lightning layer's pass over the train batch, [1, 12288]: 96 chunks of 128, 32 heads of 128
    assert sala.LIGHTNING_CHUNK == lightning.CHUNK == 128  # the count follows the program's chunk
    ops, moved = sala.lightning_scan_call(arch, 1, 12288)
    half = 128 * 129 // 2
    a_chunk_head = 2 * half * 128 + 2 * 128 * 128 * 128 + 128 * 128
    assert ops == 2 * 96 * 32 * a_chunk_head and 38.8e9 < ops < 39.0e9
    assert moved == 12288 * 4096 * (3 * 2 + 4) and 503e6 < moved < 504e6
    seconds, bound = sala.least_seconds(ops, moved, PEAKS)
    assert bound == "memory" and 0.61e-3 < seconds < 0.62e-3
    # the sparse layer: the scores over the compressed keys that exist, and the chosen pairs
    select_ops, select_moved = sala.sparse_select_call(arch, 1, 12288)
    scored = sum(max(0, (t - 31) // 16 + 1) for t in range(31, 12288))
    assert select_ops == 2 * 32 * 128 * scored and select_moved == 12288 * 34 * 128 * 2
    attn_ops, attn_moved = sala.sparse_attn_call(arch, 1, 12288)
    pairs = sum(sala.chosen_pairs(arch, t) for t in range(12288))
    assert attn_ops == 2 * 2 * 32 * 128 * pairs and attn_moved == 12288 * 2 * 34 * 128 * 2
    causal = 12288 * 12289 // 2
    assert 0.755 < pairs / causal < 0.765  # a full row of 12,288 keeps 76% of its causal pairs; one of 10,240 keeps 85%
    assert 0.84 < sum(sala.chosen_pairs(arch, t) for t in range(10240)) / (10240 * 10241 // 2) < 0.85
    assert sala.least_seconds(attn_ops, attn_moved, PEAKS)[1] == "compute" and select_ops < 0.05 * attn_ops
    # one train step of the cell: batch 1, 10,240 + 2,048, the top two blocks (lightning, sparse) train
    n, ffn = 12288, 3 * 4096 * 16384
    dense_l = 2 * n * (5 * 4096 * 4096 + ffn)
    dense_s = 2 * n * (3 * 4096 * 4096 + 2 * 4096 * 256 + ffn)
    mix_s = select_ops + attn_ops
    trunk = 2 * (2 * dense_l + 3 * ops) + (3 * dense_l + 3 * ops) + (3 * dense_s + 3 * mix_s)
    assert sala.trunk_train_flops(arch, 1, 12288, 2) == trunk
    head = 3 * 2 * 2048 * 4096 * 9181
    total = sala.ppo_train_step_flops(arch, 1, 10240, 2048, 2)
    assert total == trunk + head + 3 * mlp_head_flops(2048, 4096, 1) and 71e12 < total < 73e12  # 72 TFLOP a step: 0.37 s at the peak
    with pytest.raises(NotImplementedError):
        sala.ilql_train_step_flops(arch, 1, 12288, 2)
    # a decode step over 4 rows gathering 98 blocks of 64: weights once but the table, three states twice, the slots
    state = 3 * 4 * 32 * 128 * 128 * 4
    assert sala.state_bytes(arch, 4) == state and 25.1e6 < state < 25.2e6
    needed, rw = sala.decode_step_bytes(arch, 4, 6272)
    assert rw == 2 * state
    assert needed == (1_184_654_336 - 9181 * 4096) * 2 + 4 * 4096 * 2 + 2 * state + 4 * (2 * 6272 + 767) * 2 * 128 * 2
    assert 2.3e9 < needed < 2.4e9 and rw / needed < 0.03


def test_the_program_s_own_counters_agree_with_the_count_module():
    from trlx_tpu.models.lm import (LMConfig, cache_bytes, cache_bytes_per_token, compressed_key_bytes, decode_step_bytes,
                                    state_bytes)

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    assert state_bytes(cfg, 4) == sala.state_bytes(arch, 4)
    assert cache_bytes_per_token(cfg) == 2 * 2 * 128 * 2 == 1024  # 1 KB a token, in the one sparse layer
    assert compressed_key_bytes(cfg, 4, 12288) == 4 * 767 * 2 * 128 * 2
    assert cache_bytes(cfg, 4, 12288) == 4 * 12288 * 1024 + state_bytes(cfg, 4) + compressed_key_bytes(cfg, 4, 12288)
    count = sala.parameters(arch)
    weights = 2 * (count["trunk"] - count["table"])
    needed, rw = sala.decode_step_bytes(arch, 4, 6272)
    # the count adds the looked-up rows and the compressed keys to what the program's shape count holds
    assert decode_step_bytes(cfg, 4, 6272, weights) == (needed - 4 * 4096 * 2 - compressed_key_bytes(cfg, 4, 12288), rw)


def test_the_configuration_is_the_catalog_s_row_with_the_stated_cuts():
    m = Manifest(ROOT).validate()
    assert len(m.doc["workloads"]) == 13 and len(m.doc["configs"]) == 11 and len(m.doc["per_layer"]) == 77
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    spec, entry = m.config(CONFIG), m.configs[CONFIG]
    reduced = {"num_hidden_layers": 4, "vocab_size": 9181}
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted([*reduced, "num_layers_unfrozen"])
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged but for the stated cuts
        assert spec[key] == reduced.get(key, value), key
    assert (published["num_hidden_layers"], published["vocab_size"]) == (32, 73448)
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "MiniCPM-SALA"]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["d_ff"], arch["n_head"], arch["n_kv_head"], arch["head_width"], arch["lightning_heads"],
            arch["lightning_head_dim"], arch["ln_eps"], arch["rope_theta"], arch["tie_word_embeddings"], arch["qk_norm"]) == (
        published["hidden_size"], published["intermediate_size"], published["num_attention_heads"],
        published["num_key_value_heads"], published["head_dim"], published["lightning_nh"], published["lightning_head_dim"],
        published["rms_norm_eps"], published["rope_theta"], published["tie_word_embeddings"], published["qk_norm"])
    assert arch["embedding_multiplier"] == published["scale_emb"] and arch["logits_scaling"] == 4096 / published["dim_model_base"]
    assert arch["residual_multiplier"] == pytest.approx(published["scale_depth"] / 32 ** 0.5)
    assert arch["mixer_layers"] == ["lightning" if k == "lightning-attn" else "attention" for k in published["mixer_types"][13:17]]
    assert (arch["n_layer"], arch["vocab_size"]) == (4, 9181) and 8 * 9181 == published["vocab_size"]
    assert {"sparse_config", "block_pooling", "topk_counts", "compressed_key_causality", "lightning_decay", "qk_norm",
            "output_norm", "output_gates", "mup_denominator", "dense_len", "initialisation", "value_head"} <= set(spec["assumed"])
    assert "8 slices" in spec["deployment"] and spec["serving"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": False, "decode_weight_quant": False, "remat": True}
    cell = m.cell(CELL)
    assert cell["traffic_params"]["prompt_length"] == {"distribution": "uniform", "min": 8192, "max": 10240, "placement": "seeded"}
    assert cell["traffic_params"]["new_tokens"] == 2048 and cell["recipe"]["train"]["batch_size"] == 1
    assert cell["expect_kernels"] == ["fused_logprob._fwd_kernel", "fused_logprob._bwd_dx_kernel", "fused_logprob._bwd_dw_kernel"]
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | {"rollout_cache_gb", "kv_read_share", "generate_s_per_iter", "logprob_head_roofline",
                               "train_mfu_pct", "train_step_device_ms", "scope_attributed_pct", "rotary_ms_per_step"} <= named
    assert not {"ssm_state_gb", "kda_scan_roofline", "flash_roofline", "flash_kept_pair_share", "collective_share_pct",
                "expert_ffn_roofline"} & named
    assert cell["traced_cycle"] == "train_steps"
    assert not {"decode_ms_per_step", "rollout_tokens_per_s", "score_device_s_per_iter", "prefill_device_s_per_iter",
                "decode_kv_read_s_per_iter"} & named


def test_the_scopes_reader_sums_its_parts():
    """`scopes_roofline` over a made-up reduction: the need of each part times its passes over the sum of the parts' scope
    times; nothing for another attention kind, without peaks, or where no scope has time."""
    from benchmark.readers import scope_time, scopes_roofline

    m = Manifest(ROOT)
    arch, spec = m.config(CONFIG)["model_arch"], m.layer_metric("sparse_attn_roofline")
    times = {"sparse_select": 20.0, "sparse_attn": 180.0}
    ctx = {"flops": sala, "peaks": PEAKS, "arch": arch, "shapes": {"batch": 1, "seq": 12288}}
    original = scope_time.read
    scope_time.read = lambda ctx, s: times.get(s["scopes"][0])
    try:
        select = sala.least_seconds(*sala.sparse_select_call(arch, 1, 12288), PEAKS)[0]
        attend = sala.least_seconds(*sala.sparse_attn_call(arch, 1, 12288), PEAKS)[0]
        assert scopes_roofline.read(ctx, spec) == pytest.approx(100.0 * (select + 3 * attend) / 0.2)
        assert scopes_roofline.read({**ctx, "peaks": None}, spec) is None
        assert scopes_roofline.read({**ctx, "arch": {**arch, "attention": "mha"}}, spec) is None
        times.clear()
        assert scopes_roofline.read(ctx, spec) is None
    finally:
        scope_time.read = original

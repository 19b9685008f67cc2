"""The count module of the indexed latent-attention, sparse-expert
configuration (benchmark/counts/dsa_mla_moe.py) against parameters counted
from the program's own tree and sums made by hand at the cell's shapes; the
rule of the choice restated there against the program's; the configuration's
and the cell's files; the new metric files through the readers that exist.
By hand, as the rest of benchmark/tests."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.counts import dsa_mla_moe as counts
from benchmark.flops import mlp_head_flops
from benchmark.manifest import ROOT, Manifest

CELL, CONFIG = "glm5-l5.ppo-6144x2048", "glm-5-ep32-tp4-l5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("dsa_index_ms_per_step", "dsa_attn_ms_per_step", "dsa_index_roofline", "dsa_attn_roofline",
               "dsa_decode_roofline", "dsa_kept_pair_share", "dsa_keys_read_share")
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
    counted = counts.parameters(arch)
    d = 6144
    assert of("'h_0'", "'indexer'") == counted["indexer"] == 2048 * 32 * 128 + d * (128 + 32) + 2 * 128 == 9_371_904
    mla = d * 2048 + 2048 * 16 * 256 + d * 576 + 512 * 16 * 448 + 16 * 256 * d + 2048 + 512
    assert of("'h_0'", "'attn'") == counted["attention"] == mla + 9_371_904 == 62_720_768
    assert of("'h_0'", "'mlp'") == counted["dense"] == 3 * d * 12288 == 226_492_416
    assert of("'h_1'", "'moe'") == counted["experts"] == 9 * 3 * d * 2048 + d * 256 + 256 == 341_311_744
    assert of("'wte'") == of("'lm_head'") == counted["table"] == counted["head"] == 19360 * d
    assert of("'h_0'") == counted["attention"] + counted["dense"] + counted["norms"] == 289_225_472
    assert of("'h_4'") == counted["attention"] + counted["experts"] + counted["norms"] == 404_044_800
    assert sum(sizes.values()) == counted["trunk"] == 2_218_828_545 - 75_522_049  # ISSUE 53's count less the value head
    assert counts.attention_params(arch) + counts.indexer_params(arch) == of("'h_0'", "'attn'", "kernel") + of("'h_0'", "'attn'", "_proj']['w")
    assert counts.layer_windows(arch) == [0] * 5 and not any("wpe" in k for k in sizes)


def test_the_rule_of_the_choice_is_the_program_s():
    """`chosen_pairs` against the mask `models/indexer.py choose_keys` makes, at a small top-k over random scores."""
    from trlx_tpu.models import indexer

    arch = {"index_topk": 16}
    scores = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 96))
    seen = jnp.tril(jnp.ones((96, 96), bool))[None]
    chosen = np.asarray(indexer.choose_keys(scores, seen, 16))
    assert int(chosen.sum()) == counts.chosen_pairs(arch, 96) == 16 * 17 // 2 + 80 * 16
    assert counts.chosen_pairs(arch, 10) == 55  # inside index_topk: every causal pair


def test_counts_by_hand_at_the_cells_shapes():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    causal = 8192 * 8193 // 2
    chosen = counts.chosen_pairs(arch, 8192)
    assert (chosen, causal) == (14_681_088, 33_558_528) and chosen / causal == pytest.approx(0.4375, abs=1e-4)  # ISSUE 53
    assert counts.chosen_pairs(arch, 6144) / (6144 * 6145 // 2) == pytest.approx(0.5555, abs=1e-3)
    # the index scores of one layer over the train batch [1, 8192]: 32 heads of 128 over every causal pair, once
    ops, moved = counts.dsa_index_call(arch, 1, 8192)
    assert ops == 2 * 32 * 128 * causal and moved == 8192 * (33 * 128 * 2 + 32 * 4)
    seconds, bound = counts.least_seconds(ops, moved, PEAKS)
    assert bound == "compute" and 1.39e-3 < seconds < 1.40e-3
    # attention over the chosen pairs: 16 heads, scores 256 wide and values 256 wide
    attn_ops, attn_moved = counts.dsa_attn_call(arch, 1, 8192)
    assert attn_ops == 2 * 16 * 512 * chosen and attn_moved == 8192 * 16 * 2 * 512 * 2
    assert counts.least_seconds(attn_ops, attn_moved, PEAKS) == (attn_ops / 197e12, "compute")
    assert attn_ops / ops == pytest.approx(0.875, abs=1e-3)  # 8,192 multiply-adds a pair each, over 44% of the pairs
    # one train step of the cell: batch 1, 6,144 + 2,048, the top block trains
    n = 8192
    mla = counts.attention_params(arch)
    index = 2 * n * counts.indexer_params(arch) + ops
    dense0 = 2 * n * (mla + 3 * 6144 * 12288)
    dense1 = 2 * n * (mla + 6144 * 256 + 3 * 6144 * 2048 + 8 * (8 / 256) * 3 * 6144 * 2048)
    trunk = (2 * dense0 + 3 * attn_ops + index) + 3 * (2 * dense1 + 3 * attn_ops + index) + (3 * dense1 + 3 * attn_ops + index)
    assert counts.trunk_train_flops(arch, 1, 8192, 1) == trunk
    head = 3 * 2 * 2048 * 6144 * 19360
    total = counts.ppo_train_step_flops(arch, 1, 6144, 2048, 1)
    assert total == trunk + head + 3 * mlp_head_flops(2048, 6144, 1) and 32e12 < total < 33e12  # 0.16 s at the peak
    with pytest.raises(NotImplementedError):
        counts.ilql_train_step_flops(arch, 1, 8192, 1)
    # a decode step over 4 rows: the weights once but the table, each row's index keys, its 2,048 chosen entries
    needed, state = counts.decode_step_bytes(arch, 4, 2048)
    weights = (2_143_306_496 - 19360 * 6144) * 2 + 4 * 6144 * 2
    assert state == 0 and needed == weights + 5 * 4 * (2048 * 576 * 2 + 8192 * 128 * 2)
    assert (needed - weights) / needed < 0.03 and 4.1e9 < needed < 4.2e9  # the cache's part is 89 MB beside 4.05 GB of weights


def test_the_program_s_own_counters_agree_with_the_count_module():
    from trlx_tpu.models.lm import LMConfig, cache_bytes, cache_bytes_per_token, decode_step_bytes, index_key_bytes

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    assert cache_bytes_per_token(cfg) == 5 * 704 * 2 == 7040  # ISSUE 53: 704 numbers a token a layer
    assert index_key_bytes(cfg, 4, 8192) == 5 * 4 * 8192 * 128 * 2 and cache_bytes(cfg, 4, 8192) == 4 * 8192 * 7040
    count = counts.parameters(arch)
    weights = 2 * (count["trunk"] - count["table"])
    needed, _ = counts.decode_step_bytes(arch, 4, 2048)
    # the count adds the looked-up rows to what the program's shape count holds
    assert decode_step_bytes(cfg, 4, 2048, weights, cache_len=8192)[0] == needed - 4 * 6144 * 2


def test_the_configuration_is_the_catalog_s_row_with_the_stated_cuts():
    m = Manifest(ROOT).validate()
    assert len(m.doc["workloads"]) >= 14 and len(m.doc["configs"]) >= 12 and len(m.doc["per_layer"]) >= 85
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    spec, entry = m.config(CONFIG), m.configs[CONFIG]
    reduced = {"num_hidden_layers": 5, "n_routed_experts": 8, "num_attention_heads": 16, "num_key_value_heads": 16, "vocab_size": 19360}
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted([*reduced, "num_layers_unfrozen"])
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged but for the stated cuts
        assert spec[key] == reduced.get(key, value), key
    assert (published["num_hidden_layers"], published["n_routed_experts"], published["num_attention_heads"],
            published["vocab_size"]) == (78, 256, 64, 154880)
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "GLM-5"]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["d_ff"], arch["q_lora_rank"], arch["kv_lora_rank"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"], arch["index_n_heads"], arch["index_head_dim"], arch["index_topk"],
            arch["n_experts"], arch["experts_per_token"], arch["expert_d_ff"], arch["n_shared_experts"],
            arch["routed_scaling_factor"], arch["ln_eps"], arch["rope_theta"], arch["tie_word_embeddings"]) == (
        published["hidden_size"], published["intermediate_size"], published["q_lora_rank"], published["kv_lora_rank"],
        published["qk_nope_head_dim"], published["qk_rope_head_dim"], published["v_head_dim"], published["index_n_heads"],
        published["index_head_dim"], published["index_topk"], 256, published["num_experts_per_tok"],
        published["moe_intermediate_size"], published["n_shared_experts"], published["routed_scaling_factor"],
        published["rms_norm_eps"], published["rope_parameters"]["rope_theta"], published["tie_word_embeddings"])
    assert "rope_scaling" not in arch and arch["ffn_layers"] == ["dense"] + ["experts"] * 4
    assert (arch["n_layer"], arch["n_head"], arch["experts_held"], arch["vocab_size"]) == (5, 16, [0, 8], 19360)
    assert 8 * 19360 == published["vocab_size"] and 4 * 16 == published["num_attention_heads"] and 32 * 8 == 256
    assert {"indexer", "indexer_rope", "indexer_hadamard_fp8", "indexer_training", "same_choice_every_pass", "mtp", "rope",
            "weights", "e_score_correction_bias", "value_head", "deployment"} <= set(spec["assumed"])
    assert all("if a copy of the family's code turns up it decides" in spec["assumed"][k]
               for k in ("indexer", "indexer_rope", "indexer_hadamard_fp8"))
    assert "32 chips share each layer" in spec["deployment"] and spec["serving"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": False, "decode_weight_quant": False, "remat": True}
    cell = m.cell(CELL)
    assert cell["traffic_params"]["prompt_length"] == {"distribution": "uniform", "min": 4096, "max": 6144, "placement": "seeded"}
    assert cell["traffic_params"]["new_tokens"] == 2048 and cell["recipe"]["train"]["batch_size"] == 1
    assert cell["recipe"]["model"]["num_layers_unfrozen"] == 1 and cell["chips"] == 1
    assert cell["expect_kernels"] == ["fused_logprob._fwd_kernel", "fused_logprob._bwd_dx_kernel", "fused_logprob._bwd_dw_kernel"]
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | {"rollout_cache_gb", "kv_read_share", "generate_s_per_iter", "logprob_head_roofline",
                               "train_mfu_pct", "train_step_device_ms", "scope_attributed_pct", "expert_ffn_roofline",
                               "moe_held_slot_share"} <= named
    assert not {"ssm_state_gb", "kda_scan_roofline", "flash_roofline", "flash_kept_pair_share", "collective_share_pct",
                "sparse_attn_roofline"} & named
    assert cell["traced_cycle"] == "train_steps"
    assert not {"decode_ms_per_step", "rollout_tokens_per_s", "score_device_s_per_iter", "prefill_device_s_per_iter",
                "decode_kv_read_s_per_iter"} & named
    for name in NEW_METRICS:
        assert m.per_layer[name]["workloads"] == [CELL]


def test_the_new_metric_files_through_the_readers_that_exist():
    """`scopes_roofline` over a made-up reduction: each part's need times its passes over the sum of its scopes' times,
    times the five layers; nothing without peaks or where no scope has time (the parent: no such scope)."""
    from benchmark.readers import decode_bytes_roofline, scope_time, scopes_roofline

    m = Manifest(ROOT)
    arch = m.config(CONFIG)["model_arch"]
    times = {"dsa_index": 30.0, "dsa_select": 10.0, "dsa_attn": 110.0}
    ctx = {"flops": counts, "peaks": PEAKS, "arch": arch, "shapes": {"batch": 1, "seq": 8192}}
    original = scope_time.read
    scope_time.read = lambda ctx, s: times.get(s["scopes"][0])  # one scope a part: the reader asks for rows under ALL of a part's names
    try:
        index = counts.least_seconds(*counts.dsa_index_call(arch, 1, 8192), PEAKS)[0]
        attend = counts.least_seconds(*counts.dsa_attn_call(arch, 1, 8192), PEAKS)[0]
        assert scopes_roofline.read(ctx, m.layer_metric("dsa_index_roofline")) == pytest.approx(100.0 * 5 * index / 0.030)
        select = counts.least_seconds(*counts.dsa_select_call(arch, 1, 8192), PEAKS)
        assert select == (8192 * 8193 // 2 * 5 / 819e9, "memory")
        assert all(len(part["scopes"]) == 1 for part in m.layer_metric("dsa_attn_roofline")["parts"])
        assert scopes_roofline.read(ctx, m.layer_metric("dsa_attn_roofline")) == pytest.approx(100.0 * 5 * (select[0] + 3 * attend) / 0.120)
        assert scopes_roofline.read({**ctx, "peaks": None}, m.layer_metric("dsa_attn_roofline")) is None
        times.clear()
        assert scopes_roofline.read(ctx, m.layer_metric("dsa_index_roofline")) is None
    finally:
        scope_time.read = original
    phases = [{"time/generate_s": 14.0, "rollout/decode_steps": 2048, "rollout/kv_read_share": 0.25}] * 2
    ctx = {"flops": counts, "peaks": PEAKS, "arch": arch, "shapes": {"seq": 8192}, "window": {"phases": phases},
           "cell": m.cell(CELL)}
    needed = counts.decode_step_bytes(arch, 4, 2048)[0]
    assert decode_bytes_roofline.read(ctx, m.layer_metric("dsa_decode_roofline")) == pytest.approx(
        100.0 * (needed / 819e9) / (14.0 / 2048))
    assert m.layer_metric("dsa_kept_pair_share")["key"] == "dsa/kept_pair_share"
    assert m.layer_metric("dsa_keys_read_share")["key"] == "rollout/dsa_keys_read_share"

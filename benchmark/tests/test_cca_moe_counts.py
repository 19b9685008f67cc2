"""The count module of the configuration whose attention runs behind two causal
convolutions and whose router is an MLP with a carried state
(benchmark/counts/cca_moe.py) against parameters counted from the program's
own tree, the cell's files, and its rehearsal run. By hand, as the rest of
benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.counts import cca_moe as counts
from benchmark.flops import kept_pairs
from benchmark.manifest import ROOT, Manifest
from benchmark.readers.kernel_roofline import flash_shape

CELL, CONFIG = "zaya1-ep2.ppo-4096x2048", "zaya1-8b-ep2-l8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("cca_mix_ms_per_step", "cca_mix_roofline", "cca_decode_roofline", "cca_cache_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
    assert counts.projection_params(arch) == 2048 * (1024 + 256 + 256) + 1024 * 2048 == 5_242_880
    assert counts.conv_params(arch) == (3_840, 328_960)
    assert count["attention"] == of("'h_1'", "'attn'") == 5_575_682
    assert count["router"] == of("'h_1'", "'router'") + of("'h_1'", "e_score_correction_bias") == 660_736 + 16
    assert counts.router_matmul_params(arch) == 2048 * 256 + 2 * 256 * 256 + 256 * 16 == 659_456
    assert count["expert"] == of("'h_1'", "experts_") // 8 == 12_582_912
    assert not of("'shared'") and not of("'mlp'") and not of("'lm_head'")  # no shared expert, no dense layer, a tied head
    assert of("'h_1'", "'res_") == 16_384
    assert count["layer"] == of("'h_0'") == of("'h_7'") == 106_920_210
    assert count["layer_whole"] == 207_583_506  # the layer the two chips share
    assert 40 * count["layer_whole"] + 262272 * 2048 + 2048 == 8_840_475_344  # the published model: 8.30 B of layers, the table
    assert count["table"] == of("'wte'") == 131136 * 2048 == 268_566_528
    assert count["trunk"] == sum(sizes.values()) == 8 * 106_920_210 + 268_566_528 + 2048 == 1_123_930_256
    # active in a layer's feed-forward: the router's four products + one slot a token x 8/16 held
    assert counts.ffn_active_params(arch) == pytest.approx(659_456 + 0.5 * 12_582_912)
    assert counts.attention_matmul_params(arch) == 5_242_880 + 2 * 10 * 128 * 128
    # the group the flash reader cannot see is the file's
    assert counts.GROUP == arch["n_head"] // arch["n_kv_head"] == 4 and counts.heads(arch) == (8, 2, 128)
    assert counts.layer_windows(arch) == [0] * 8
    cell = Manifest(ROOT).cell(CELL)
    batch, seq, unfrozen = cell["recipe"]["train"]["batch_size"], 6144, cell["recipe"]["model"]["num_layers_unfrozen"]
    n = batch * seq
    attn = 2 * 2 * batch * 8 * 128 * kept_pairs(seq)
    dense = 2 * n * (counts.attention_matmul_params(arch) + counts.ffn_active_params(arch))
    trunk = 6 * (2 * dense + 3 * attn) + 2 * (3 * dense + 3 * attn)
    assert (batch, unfrozen) == (2, 2) and counts.trunk_train_flops(arch, batch, seq, unfrozen) == pytest.approx(trunk)
    assert attn / (attn + dense) == pytest.approx(0.335, abs=5e-3)  # "a third of a layer's arithmetic" at a mean span of 3,072
    head = 3 * 2 * batch * 2048 * 2048 * 131136
    assert counts.ppo_train_step_flops(arch, batch, 4096, 2048, unfrozen) == pytest.approx(trunk + head + 3 * 2 * 4096 * (2048 * 4096 + 4096))
    assert 15.5e12 < counts.ppo_train_step_flops(arch, batch, 4096, 2048, unfrozen) < 16.5e12
    assert counts.expert_ffn_call(256 * 8, 8, 2048, 2048) == (6 * 2048 * 2048 * 2048, (8 * 3 * 2048 * 2048 + 2048 * (2 * 2048 + 3 * 2048)) * 2)


def test_the_mixing_s_floor_by_hand():
    """One layer, forward, over the train batch of 2 x 6,144: the four
    projections into the latent, two 128 x 128 products a token a head, two
    multiply-adds a channel; the input, q, k, v and the weights moved once."""
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    n = 2 * 6144
    ops, moved = counts.cca_mix_call(arch, 2, 6144)
    assert ops == 2 * n * (2048 * 1536 + 2 * 10 * 128 * 128 + 2 * 1280) == 85_425_389_568
    assert moved == (n * (2048 + 1536) + 2048 * 1536 + 3_840 + 328_960) * 2 == 95_037_440
    seconds, bound = counts.least_seconds(ops, moved, PEAKS)
    assert bound == "compute" and seconds == pytest.approx(0.434e-3, rel=5e-3)


def test_the_flash_floor_counts_the_query_heads_in_every_kernel():
    """The reader takes the head count from a call's result: 2 x 8 for the
    forward and dq of a two-row train step, 2 x 2 for the grouped dk/dv; the
    floor's operations are the 16 query heads' in all three, K and V moved
    once a group of 4."""
    texts = {
        "fwd": "%flash_fwd.3 = (bf16[16,6144,128], f32[16,1,6144]) custom-call(",
        "bwd_dq": "%flash_bwd_dq.3 = bf16[16,6144,128] custom-call(",
        "bwd_dkv": "%flash_bwd_dkv.3 = (bf16[4,6144,128], bf16[4,6144,128]) custom-call(",
    }
    q_bytes, kv_bytes = 6144 * 128 * 16 * 2, 6144 * 128 * 4 * 2
    moved = {"fwd": 2 * q_bytes + 2 * kv_bytes, "bwd_dq": 3 * q_bytes + 2 * kv_bytes, "bwd_dkv": 2 * q_bytes + 4 * kv_bytes}
    for kind, text in texts.items():
        parsed_kind, shape = flash_shape(text)
        assert parsed_kind == kind and shape["n_head"] == (4 if kind == "bwd_dkv" else 16)
        assert counts.flash_call(kind, window=0, **shape) == (2 * 2 * 16 * 128 * kept_pairs(6144), moved[kind])


def test_a_decode_step_s_bytes_and_the_program_s_own_counters():
    from trlx_tpu.models.lm import LMConfig, cache_bytes, cache_bytes_per_token, cca_state_bytes
    from trlx_tpu.ops.kv_read import kv_keys_read, kv_read_bucket, kv_read_ranges

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    slot = 2 * 2 * 128 * 2  # K and V, 2 heads of 128, bf16: 1 KB a token a layer
    fixed = (2 * 1280 + 128) * 2  # a window of two positions of 1,280 channels, one shifted value of 128
    assert cache_bytes_per_token(cfg) == 8 * slot and cca_state_bytes(cfg, 16) == 16 * 8 * fixed == counts.state_bytes(arch, 16)
    assert cache_bytes(cfg, 16, 6144) == 16 * 8 * (6144 * slot + fixed) == 805_994_496  # 0.81 GB
    wide = 16 * 6144 * 8 * 2 * 2048 * 2  # keys and values 2,048 wide
    assert cache_bytes(cfg, 16, 6144) / wide == pytest.approx(0.125 + fixed / (6144 * 2 * 2048 * 2))
    # the ranged read: buckets of 2,048, three branches; every decode step (4,096 .. 6,143) is in the last
    assert kv_read_bucket(6144) == 2048 and kv_read_ranges(6144) == ((0, 2048), (0, 4096), (0, 6144))
    read, full = kv_keys_read(6144, 4096, 2048, counts.layer_windows(arch), [0] * 8)
    assert read == full == 2048 * 8 * 6144
    needed, cache = counts.decode_step_bytes(arch, 16, 6144.0)
    assert cache == 16 * 8 * 6144 * slot
    assert needed == 1_123_930_256 * 2 + cache + 2 * 16 * 8 * fixed
    assert needed == pytest.approx(3.055e9, rel=5e-3)  # 2.25 GB of weights, 0.81 GB of keys


def test_the_configuration_is_the_catalog_s_row_with_the_stated_cuts():
    m = Manifest(ROOT).validate()
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1 and CELL in m.cells and CONFIG in m.configs
    assert (len(m.doc["workloads"]), len(m.doc["configs"]), len(m.doc["per_layer"])) == (12, 10, 60)
    spec, entry = m.config(CONFIG), m.configs[CONFIG]
    reduced = {"num_hidden_layers": 8, "num_experts": 8, "vocab_size": 131136}
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted([*reduced, "num_layers_unfrozen"])
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged but for the stated cuts
        assert spec[key] == reduced.get(key, value), key
    assert (published["num_hidden_layers"], published["num_experts"], published["vocab_size"]) == (40, 16, 262272)
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "ZAYA1-8B"]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["n_head"], arch["n_kv_head"], arch["head_width"], arch["expert_d_ff"], arch["n_experts"],
            arch["experts_per_token"], arch["router_hidden"], arch["cca_time0"], arch["cca_time1"], arch["ln_eps"],
            arch["tie_word_embeddings"]) == (
        published["hidden_size"], published["num_attention_heads"], published["num_key_value_heads"], published["head_dim"],
        published["moe_intermediate_size"], published["num_experts"], published["num_experts_per_tok"],
        published["router_hidden_size"], published["cca_time0"], published["cca_time1"], published["rms_norm_eps"],
        published["tie_word_embeddings"])
    rope = published["rope_parameters"]["hybrid"]
    assert (arch["rotary_dim"], arch["rope_theta"]) == (int(published["head_dim"] * rope["partial_rotary_factor"]), rope["rope_theta"])
    assert set(published["layer_types"]) == {"hybrid"} and arch["ffn_layers"] == ["experts"] * 8
    assert (arch["attention"], arch["router_scoring"], arch["router_kind"], arch["router_carry"], arch["residual_scaling"],
            arch["activation"], arch["mlp"]) == ("cca", "softmax_all", "mlp", True, True, "silu", "gated")
    assert (arch["n_layer"], arch["vocab_size"], arch["experts_held"], arch["max_position"]) == (8, 131136, [0, 8], 6144)
    assert {"convolutions", "qk_mean", "value_shift", "score_norm", "rotary", "router", "choice", "skip_expert",
            "residual_scaling", "experts", "weights", "embedding", "balance", "eos_token_id", "value_head", "deployment",
            "decode_weight_quant", "kv_cache_quant"} <= set(spec["assumed"])
    assert "NOT built" in spec["assumed"]["skip_expert"]
    assert sum("if a copy of the family's code turns up it decides" in v for v in spec["assumed"].values()) >= 6
    assert "of 2 that share each layer" in spec["deployment"] and spec["serving"] == {
        "param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": False, "decode_weight_quant": False, "remat": True}
    cell = m.cell(CELL)
    assert cell["traffic_params"] == m.cell("smallthinker-ep4.ppo-4096x2048")["traffic_params"]  # the same rows, on purpose
    assert cell["traffic_params"]["n_prompts"] == cell["recipe"]["method"]["chunk_size"] == cell["recipe"]["method"]["num_rollouts"] == 16
    assert cell["recipe"]["method"]["ppo_epochs"] == 4 and cell["recipe"]["train"]["batch_size"] == 2
    assert cell["recipe"]["model"]["num_layers_unfrozen"] == 2 and cell["traced_cycle"] == "train_steps"
    assert cell["expect_kernels"] == m.cell("kexaone-l5.ppo-128x896")["expect_kernels"]
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | {"rollout_cache_gb", "kv_read_share", "generate_s_per_iter", "logprob_head_roofline", "flash_roofline",
                               "train_mfu_pct", "train_step_device_ms", "scope_attributed_pct", "expert_ffn_roofline",
                               "expert_ffn_share_pct", "moe_experts_ms_per_step", "moe_router_ms_per_step",
                               "moe_rows_per_held_expert", "moe_held_slot_share", "moe_max_expert_load",
                               "moe_first_buffer_share", "experts_touched_per_step", "flash_pad_dead_chunk_share"} <= named
    assert not {"ssm_state_gb", "kda_state_gb", "loop_decode_roofline", "collective_share_pct", "ring_decode_roofline",
                "ring_cache_share", "rollout_tokens_per_s", "decode_ms_per_step", "score_device_s_per_iter"} & named
    for name in NEW_METRICS:  # each new metric lists this cell alone
        assert m.per_layer[name]["workloads"] == [CELL]
    smallthinker = {x["name"] for x in m.metrics_for("smallthinker-ep4.ppo-4096x2048", "per_layer")}
    assert smallthinker - named == {"ring_decode_roofline", "ring_cache_share"} and named - smallthinker == set(NEW_METRICS)


def test_the_reader_counts_the_layers_by_the_attention_kind():
    """`attention_scope_roofline` over a made-up context: 8 "cca" layers,
    three passes each, against the milliseconds its scope took; nothing for
    another attention kind, without peaks, or without the scope."""
    from benchmark.readers import attention_scope_roofline as reader
    from benchmark.readers import scope_time

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    spec = json.load(open(os.path.join(ROOT, "benchmark", "layer_metrics", "cca_mix_roofline.json")))
    ctx = {"flops": counts, "peaks": PEAKS, "arch": arch, "shapes": {"batch": 2, "seq": 6144}, "reduction": None, "traced": None}
    assert reader.read(ctx, spec) is None  # no trace
    original = scope_time.read
    try:
        scope_time.read = lambda ctx, spec: 40.0 if spec["scopes"] == ["cca_mix"] and spec["reduce"] == "ms_per_train_step" else None
        floor = counts.least_seconds(*counts.cca_mix_call(arch, 2, 6144), PEAKS)[0]
        assert reader.read(ctx, spec) == pytest.approx(100 * 3 * 8 * floor / 0.040) == pytest.approx(26.0, abs=0.2)
        assert reader.read({**ctx, "arch": {**arch, "attention": "mha"}}, spec) is None
        assert reader.read({**ctx, "peaks": None}, spec) is None
        scope_time.read = lambda ctx, spec: None
        assert reader.read(ctx, spec) is None
    finally:
        scope_time.read = original


def test_rehearsal_names_every_new_counter():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    assert {"cca_cache_share", "rollout_cache_gb", "moe_rows_per_held_expert", "moe_held_slot_share", "moe_max_expert_load",
            "experts_touched_per_step", "kv_read_share", "moe_sum_rows_per_token"} <= set(said["metrics_named"])
    summary = json.load(open(os.path.join(ROOT, "benchmark_out", CELL, "summary.json")))
    # four layers, 8 rows of 32 slots, K and V at 2 heads of 16, bf16; beside them two positions of (4 + 2) x 16 channels and 16 shifted
    slots, fixed = 8 * 4 * 32 * 2 * 2 * 16 * 2, 8 * 4 * (2 * 96 + 16) * 2
    assert summary["metrics"]["rollout_cache_gb"]["value"] == pytest.approx((slots + fixed) / 1e9)
    assert summary["metrics"]["cca_cache_share"]["value"] == pytest.approx((slots + fixed) / (8 * 32 * 4 * 2 * 64 * 2))
    records = [json.loads(l) for l in open(os.path.join(ROOT, "benchmark_out", CELL, "run", "metrics.jsonl"))]
    phases = [r for r in records if "rollout/cca_state_bytes" in r]
    assert phases and all(r["rollout/cca_state_bytes"] == fixed for r in phases)
    steps = [r for r in records if "moe/top1_weight_mean" in r]
    assert steps and all(1 / 8 < r["moe/top1_weight_mean"] < 1 for r in steps)
    share = summary["metrics"]["moe_held_slot_share"]["value"]
    assert summary["metrics"]["moe_rows_per_held_expert"]["value"] == pytest.approx(share * 4 * 32 / 4)  # 4 rows of 32, one a token, 4 held

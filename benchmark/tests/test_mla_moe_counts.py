"""The count module of the latent-attention, sparse-expert configuration
(benchmark/counts/mla_moe.py) against parameters counted from the program's
own tree, the cell's files, and its rehearsal run. By hand, as the rest of
benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.counts import mla_moe
from benchmark.manifest import ROOT, Manifest

CELL, CONFIG = "kimik2.5-l5.ppo-128x896", "kimi-k2.5-ep48-l5"


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
    assert mla_moe.attention_params(arch) == of("'h_1'", "'attn'") - norms == 101_122_048
    assert mla_moe.ffn_active_params(arch, "dense") == of("'h_0'", "'mlp'") == 3 * 7168 * 18432
    assert mla_moe.expert_params(arch) == of("'h_1'", "'shared'") == of("'h_1'", "experts_") // 8 == 44_040_192
    # active in an expert layer: router + shared + 8 slots a token x 8/384 held
    assert mla_moe.ffn_active_params(arch, "experts") == pytest.approx(7168 * 384 + 44_040_192 * (1 + 8 * 8 / 384))
    # the whole chip's share: 2.89 B parameters, as the configuration's file reckons
    assert sum(sizes.values()) == pytest.approx(2.792e9, rel=2e-3)  # + the value head's 102.8 M in the trainer
    # the flash reader hands the padded width; the module keeps the true ones
    assert (mla_moe.QK_WIDTH, mla_moe.V_WIDTH) == (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], arch["v_head_dim"])
    ops, moved = mla_moe.flash_call("fwd", 1, 1024, 512, 256)
    assert ops == 2 * 512 * (192 + 128) * (1024 * 1025 // 2)
    assert moved == 1024 * 512 * (2 * 192 + 2 * 128) * 2
    # one train step of the cell: batch 4, 128 + 896, one block unfrozen
    n, attn = 4 * 1024, mla_moe.attention_flops(arch, 4, 1024)
    lead = 2 * n * (101_122_048 + 3 * 7168 * 18432)
    expert = 2 * n * (101_122_048 + mla_moe.ffn_active_params(arch, "experts"))
    trunk = (2 * lead + 3 * attn) + 3 * (2 * expert + 3 * attn) + (3 * expert + 3 * attn)
    assert mla_moe.trunk_train_flops(arch, 4, 1024, 1) == pytest.approx(trunk)
    assert mla_moe.expert_ffn_call(171 * 8, 8, 7168, 2048) == (
        6 * 1368 * 7168 * 2048, (8 * 3 * 7168 * 2048 + 1368 * (2 * 7168 + 3 * 2048)) * 2)


def test_the_cell_validates_and_lists_its_metrics():
    m = Manifest(ROOT).validate()
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert {"expert_ffn_roofline", "expert_ffn_share_pct", "moe_held_slot_share", "moe_max_expert_load",
            "experts_touched_per_step", "flash_roofline", "logprob_head_roofline", "kv_read_share", "decode_ms_per_step",
            "train_mfu_pct"} <= named
    spec = m.config(CONFIG)
    assert sorted(spec["reduced"]) == ["n_routed_experts", "num_hidden_layers", "num_layers_unfrozen", "vocab_size"]
    published = spec["published"]
    for key, value in published.items():  # every published width unchanged
        if key not in spec["reduced"]:
            assert spec[key] == value, key
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["n_head"], arch["d_ff"], arch["expert_d_ff"], arch["n_experts"], arch["experts_per_token"]) == (
        published["hidden_size"], published["num_attention_heads"], published["intermediate_size"],
        published["moe_intermediate_size"], published["n_routed_experts"], published["num_experts_per_tok"])
    assert (arch["q_lora_rank"], arch["kv_lora_rank"], arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]) == (
        published["q_lora_rank"], published["kv_lora_rank"], published["qk_nope_head_dim"], published["qk_rope_head_dim"],
        published["v_head_dim"])


def test_rehearsal_names_every_new_metric():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    assert {"moe_held_slot_share", "moe_max_expert_load", "experts_touched_per_step", "kv_read_share"} <= set(said["metrics_named"])

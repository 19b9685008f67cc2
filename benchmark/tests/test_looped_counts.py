"""The count module of the looped configuration (benchmark/counts/looped.py)
against parameters counted from the program's own tree and sums made by hand;
the configuration's, the cell's and the reference's files (the cases ISSUE 37
asked for live here: a PR edits no file the benchmark has); and the cell's
rehearsal run. By hand, as the rest of benchmark/tests."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.counts import looped
from benchmark.flops import kept_pairs, mlp_head_flops
from benchmark.manifest import ROOT, Manifest
from benchmark.references import looped_decoder

CELL, CONFIG = "ouro2.6b-l12.ppo-128x896", "ouro-2.6b-l12"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BLOCK, STACK, TABLE, TRUNK, A_TOKEN = 51_388_416, 616_660_992, 100_663_296, 817_991_681, 202_752


def _tree_sizes(arch):
    """{path: parameters} of the configuration's trunk, shapes only."""
    from trlx_tpu.models.lm import LMConfig, TransformerLM

    model = TransformerLM(LMConfig.from_dict(arch))
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = jax.eval_shape(lambda r: model.init(r, ids, jnp.ones_like(ids))["params"], jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(p): int(np.prod(leaf.shape)) for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_built_tree_keeps_the_blocks_once_and_has_the_issue_s_count():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    sizes = _tree_sizes(arch)
    of = lambda *parts: sum(n for k, n in sizes.items() if all(part in k for part in parts))
    counted = looped.parameters(arch)
    assert of("'h_0'") == counted["block"] == BLOCK == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048
    assert sum(of(f"'h_{i}'") for i in range(12)) == counted["stack"] == STACK and not any("'h_12'" in k for k in sizes)
    assert of("'wte'") == of("'lm_head'") == counted["table"] == counted["head"] == TABLE == 49152 * 2048  # untied
    assert of("'ln_f'") == counted["ln_f"] == 2048 and of("'exit_gate'") == counted["gate"] == 2049
    assert sum(sizes.values()) == counted["trunk"] == TRUNK  # four loops add no parameter
    assert of("'h_0'", "ln_1_out") == of("'h_0'", "ln_2_out") == 2048  # the sandwich norms
    assert looped.block_matmul_params(arch) == of("'h_0'", "kernel") == 51_380_224
    assert looped.loops(arch) == 4 and looped.head_dim(arch) == 128 and looped.layer_windows(arch) == [0] * 12
    # the published model whole: 48 blocks, the card's "2.6B"
    assert looped.parameters({**arch, "n_layer": 48})["trunk"] == 2_667_974_657


def test_counts_against_sums_made_by_hand():
    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    # one train step of the cell: batch 8, 128 + 896, the top two blocks train; every block counted in every loop
    n = 8 * 1024
    dense = 2 * n * 51_380_224
    attn = 2 * 2 * 8 * 16 * 128 * kept_pairs(1024)
    applications = 4 * 12
    trunk = applications * (2 * dense + 3 * attn) + 4 * 2 * dense  # weight gradients: k R = 8 applications
    assert looped.trunk_train_flops(arch, 8, 1024, 2) == trunk
    assert looped.trunk_train_flops({**arch, "n_loops": 1}, 8, 1024, 2) * 4 == trunk
    head = 3 * 2 * 8 * 896 * 2048 * 49152
    assert looped.ppo_train_step_flops(arch, 8, 128, 896, 2) == trunk + head + 3 * mlp_head_flops(8 * 896, 2048, 1)
    assert 90e12 < looped.ppo_train_step_flops(arch, 8, 128, 896, 2) < 100e12
    with pytest.raises(NotImplementedError):
        looped.ilql_train_step_flops(arch, 8, 1024, 2)
    # the cache: K and V of every (loop, layer) pair, int8 with a float32 scale a key a head
    assert looped.cache_bytes_per_token(arch) == A_TOKEN == 4 * 12 * 2 * (2048 + 16 * 4)
    assert looped.cache_bytes_per_token(arch, int8=False) == 4 * 12 * 2 * 2048 * 2
    assert 6.6e9 < 32 * 1024 * A_TOKEN < 6.7e9  # the cell's rollout cache
    # a decode step over 32 rows reading 576 slots: the stack once a LOOP, the head once, the keys of every entry
    needed, stack = looped.decode_step_bytes(arch, 32, 576)
    assert stack == 4 * STACK * 2 and needed == stack + (TABLE + 2048) * 2 + 32 * 576 * A_TOKEN
    assert looped.weight_read_share(arch, 32, 576) == stack / needed and 0.5 < stack / needed < 0.6
    one_pass, _ = looped.decode_step_bytes({**arch, "n_loops": 1}, 32, 576)
    assert needed - one_pass == 3 * STACK * 2 + 3 * 32 * 576 * A_TOKEN // 4  # what the loops add to a step


def test_the_program_s_own_counters_agree_with_the_count_module():
    from trlx_tpu.models.lm import LMConfig, cache_bytes, cache_bytes_per_token, decode_step_bytes

    arch = Manifest(ROOT).config(CONFIG)["model_arch"]
    cfg = LMConfig.from_dict({**arch, "dtype": "bfloat16", "param_dtype": "bfloat16", "kv_cache_quant": True})
    assert cfg.cache_entries == 48
    assert cache_bytes_per_token(cfg) == looped.cache_bytes_per_token(arch) == A_TOKEN
    assert cache_bytes_per_token(cfg.replace(kv_cache_quant=False)) == looped.cache_bytes_per_token(arch, int8=False)
    assert cache_bytes(cfg, 32, 1024) == 32 * 1024 * A_TOKEN
    # the orchestrator hands the program what a step reads once (the blocks, the final norm, the untied head) and the blocks
    read_once, stack = 2 * (STACK + 2048 + TABLE), 2 * STACK
    assert decode_step_bytes(cfg, 32, 576, read_once, stack) == (looped.decode_step_bytes(arch, 32, 576)[0], 0)


def test_the_configuration_is_the_catalog_s_row_at_a_quarter_of_its_depth():
    m = Manifest(ROOT).validate()
    assert len(m.doc["workloads"]) >= 9 and sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    spec, entry = m.config(CONFIG), m.configs[CONFIG]
    assert list(spec["reduced"]) == entry["reduced"] == ["num_hidden_layers"]
    published = spec["published"]
    for key, value in published.items():  # every published key at the top level, unchanged but the depth
        assert spec[key] == (12 if key == "num_hidden_layers" else value), key
    if os.path.isfile(CATALOG):
        (row,) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "Ouro-2.6B"]
        assert published == row["config"] and entry["source"] == spec["source"] == row["source_url"]
    arch = spec["model_arch"]
    assert (arch["d_model"], arch["n_head"], arch["d_ff"], arch["vocab_size"], arch["rope_theta"], arch["ln_eps"],
            arch["tie_word_embeddings"], arch["n_loops"], arch["exit_threshold"], arch["activation"]) == (
        published["hidden_size"], published["num_attention_heads"], published["intermediate_size"],
        published["vocab_size"], published["rope_theta"], published["rms_norm_eps"], published["tie_word_embeddings"],
        published["total_ut_steps"], published["early_exit_threshold"], published["hidden_act"])
    assert arch["d_model"] // arch["n_head"] == published["head_dim"] and arch["n_layer"] == spec["num_hidden_layers"] == 12
    assert published["num_key_value_heads"] == published["num_attention_heads"] and "n_kv_head" not in arch
    assert arch["sandwich_norm"] and arch["exit_gate"] and arch["extra"] == {"neox_rotary": True}
    assert set(published["layer_types"]) == {"full_attention"}
    assert {"sandwich_norm", "loop_norm", "exit_gate", "loss", "weights", "embedding", "rotary", "eos_token_id", "cache"} <= set(spec["assumed"])
    assert spec["serving"] == {"param_dtype": "bfloat16", "dtype": "bfloat16", "kv_cache_quant": True,
                               "decode_weight_quant": False, "remat": True}
    rehearsal = spec["rehearsal_arch"]
    assert (rehearsal["d_model"], rehearsal["n_layer"], rehearsal["n_loops"]) == (64, 3, 4)
    assert {k: v for k, v in rehearsal.items() if k not in ("vocab_size", "n_layer", "n_head", "d_model", "d_ff", "max_position")} == {
        k: v for k, v in arch.items() if k not in ("vocab_size", "n_layer", "n_head", "d_model", "d_ff", "max_position")}
    cell = m.cell(CELL)
    assert cell["traffic_params"] == m.cell("gptj6b-l8.ppo-128x896")["traffic_params"] == m.cell("granite4hmicro.ppo-128x896")["traffic_params"]
    assert cell["recipe"]["model"] == {"num_layers_unfrozen": 2}
    assert cell["recipe"]["method"] == {"chunk_size": 32, "num_rollouts": 32, "ppo_epochs": 4}
    assert cell["recipe"]["train"]["batch_size"] in (8, 4)  # the issue's 8, halved once by its own rule if it does not fit
    assert cell["expect_kernels"] == m.cell("granite4hmicro.ppo-128x896")["expect_kernels"]  # flash and the fused head
    named = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert {"loop_decode_roofline", "loop_weight_read_share", "rollout_cache_gb", "kv_read_share", "generate_s_per_iter",
            "flash_roofline", "logprob_head_roofline", "train_mfu_pct", "train_step_device_ms"} <= named
    for name in ("loop_decode_roofline", "loop_weight_read_share"):
        assert m.per_layer[name]["workloads"] == [CELL] and m.per_layer[name]["moves"] == "tokens_per_s_chip"
    assert not {n for n in named if n.startswith(("ssm_", "moe_", "expert"))}
    tol = cell["tolerances"]
    assert tol["logits_yardstick"] == "bfloat16_stream" and "R - 1" in tol["why"] and "int8_dense" in tol["why"]


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
    want = looped_decoder.forward(params, arch, ids, mask, last=24)
    assert want.shape == (2, 24, arch["vocab_size"]) and want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=0)
    rms = float(jnp.sqrt(jnp.mean(want**2)))
    far = {name: float(jnp.sqrt(jnp.mean((looped_decoder.forward(params, arch, ids, mask, 24, precision=name) - want) ** 2))) / rms
           for name in looped_decoder.PRECISIONS}
    assert far["highest"] == 0.0
    assert 0 < far["bfloat16"] < far["bfloat16_stream"] < min(far["int8_dense"], far["int8"]), far
    # the control this architecture invites: one loop fewer is another model, farther off than any rounding
    fewer = float(jnp.sqrt(jnp.mean((looped_decoder.forward(params, arch, ids, mask, 24, loops=3) - want) ** 2))) / rms
    assert fewer > 2 * max(far.values()), (fewer, far)
    with pytest.raises(ValueError, match="precision"):
        looped_decoder.forward(params, arch, ids, mask, 24, precision="float8")
    with pytest.raises(ValueError, match="looped_decoder is the reference"):
        looped_decoder.forward(params, dict(arch, mlp="dense"), ids, mask, 24)


def test_rehearsal_names_the_new_counters_and_metrics():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--rehearsal",
                          "--trace", "1"], capture_output=True, text=True, timeout=1500)
    assert out.returncode == 3, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("[bench] rehearsal "))
    said = json.loads(line.split("[bench] rehearsal ", 1)[1])
    assert all(said["checks"].values()), said
    # loop_decode_roofline needs the chip's peaks: a rehearsal has none and leaves it out
    assert {"loop_weight_read_share", "rollout_cache_gb", "kv_read_share"} <= set(said["metrics_named"])
    summary = json.load(open(os.path.join(ROOT, "benchmark_out", CELL, "summary.json")))
    # 12 (loop, layer) entries, K and V, 4 heads x 16 in int8 with a float32 scale a head, 8 rows x 32 slots
    a_token = 4 * 3 * 2 * (4 * 16 + 4 * 4)
    assert summary["metrics"]["rollout_cache_gb"]["value"] == pytest.approx(8 * 32 * a_token / 1e9)
    assert 0 < summary["metrics"]["loop_weight_read_share"]["value"] < 1


def test_the_loop_control_rehearses():
    """benchmark/loop_control.py: the reference with R - 1 loops in the program's place fails check (a)."""
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "loop_control.py"), "--workload", CELL,
                          "--seeds", "2", "--rehearsal"], capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-2000:]
    said = json.loads(next(l for l in out.stdout.splitlines() if l.startswith("[loop_control] verdict ")).split("verdict ", 1)[1])
    assert said["loops"] == [3, 4] and said["seeds"] == 2 and said["every_control_fails"]
    assert said["control_rel_rms_min"] > said["limit_max"]

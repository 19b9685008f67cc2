"""The ZAYA1 kinds (PR 47): attention "cca" (queries and keys behind two causal
convolutions, the q-k mean, a shifted value, L2-normalised scores with a
learned temperature), one expert a token by an MLP router whose state crosses
the depth of the stack (`router_kind` "mlp", `router_carry`, `router_scoring`
"softmax_all"), learned residual scaling. The program against the plain
reference `benchmark/references/cca_mlp_router_moe_decoder.py`, which runs
each row unpadded; every piece of the reference knocked out fails the same
comparison; the cache, the frozen branch's second input, the shares, the
counts at the published widths, and every refusal by name.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import cca_mlp_router_moe_decoder as reference
from trlx_tpu.models import cca, moe
from trlx_tpu.models.heads import LMWithValueHead, extract_branch_params, trainable_mask
from trlx_tpu.models.lm import (LMConfig, TransformerLM, cache_bytes, cache_bytes_per_token, cache_partition_spec,
                                cca_state_bytes, init_cache, init_paged_cache)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "benchmark", "configs")

# 4 query heads over 2 K/V heads of 16 (n_head * head_width = 64 is not
# d_model = 32), rotary on 8 of 16; three layers, each 8 SiLU experts of which
# [0, 4) are held, one a token, an MLP router 16 wide with its carried state.
ARCH = dict(
    vocab_size=96, n_layer=3, n_head=4, n_kv_head=2, head_width=16, d_model=32, max_position=256, eos_token_id=0,
    pos_type="rotary", rotary_dim=8, rope_theta=5000000, extra={"neox_rotary": True}, norm="rmsnorm", mlp="gated",
    attention="cca", cca_time0=2, cca_time1=2, activation="silu", ln_eps=1e-5, parallel_residual=False,
    tie_word_embeddings=True, fused_qkv=False, qkv_bias=False, out_bias=False, ffn_layers=["experts"] * 3, n_experts=8,
    experts_per_token=1, expert_d_ff=32, router_scoring="softmax_all", router_kind="mlp", router_hidden=16,
    router_carry=True, residual_scaling=True, experts_held=[0, 4], embed_init_std=1.0, draw_dtype="float32", logits_scaling=4.0,
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 2, 24


def _model(arch=ARCH, seed=0, pad=5, **over):
    cfg = LMConfig.from_dict({**arch, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :pad].set(0)  # row 1 is left-padded
    params = model.init(jax.random.PRNGKey(seed), ids, mask)["params"]
    return cfg, model, params, ids * mask, mask


def _distance(got, want):
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want**2)))


# ---- the program against the reference -------------------------------------------------------------


@pytest.mark.parametrize("widths", [(2, 2), (3, 2), (1, 3)], ids=["2 and 2", "3 and 2", "1 and 3"])
def test_logits_match_the_reference_padded_and_unpadded_rows(widths):
    arch = {**ARCH, "cca_time0": widths[0], "cca_time1": widths[1]}
    cfg, model, params, ids, mask = _model(arch)
    got = model.apply({"params": params}, ids, mask)["logits"]
    want = reference.forward(params, arch, ids, mask, T - 5)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got[:, 5:], want, atol=5e-5, rtol=1e-4)  # row 0 whole, row 1 from its first token


def test_the_flash_kernels_take_the_attention_core():
    """`attn_impl: flash` (interpreted here): the core of a "cca" layer goes
    where an "mha" layer's goes, heads of 16 padded to the kernels' 128."""
    cfg, model, params, ids, mask = _model(attn_impl="flash")
    from trlx_tpu.models.lm import flash_eligible

    assert flash_eligible(cfg, T, has_cache=False)
    got = model.apply({"params": params}, ids, mask)["logits"]
    np.testing.assert_allclose(got[:, 5:], reference.forward(params, ARCH, ids, mask, T - 5), atol=5e-5, rtol=1e-4)


def _ppo_shaped_loss(logits, ids, mask, old, advantages):
    """The clipped surrogate over the response half of each row."""
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), ids[:, 1:, None], axis=-1)[..., 0]
    ratio = jnp.exp(logp - old)
    surrogate = jnp.maximum(-advantages * ratio, -advantages * jnp.clip(ratio, 0.8, 1.2))
    return jnp.sum(surrogate * mask[:, 1:])


@pytest.mark.parametrize("remat", [False, True], ids=["", "under remat"])
def test_gradients_of_a_ppo_shaped_loss_match_the_reference(remat):
    """Every parameter's gradient: the convolutions', the temperature's, the
    router's MLP and carry (through the un-renormalised weight; the choice
    itself has none), the four vectors of each residual sum. Remat on and
    off give the same gradients: both match the reference's."""
    cfg, model, params, ids, mask = _model(remat=remat, pad=0)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    old = -4.0 + 0.3 * jax.random.normal(keys[0], (B, T - 1))
    advantages = jax.random.normal(keys[1], (B, T - 1))
    got = jax.grad(lambda p: _ppo_shaped_loss(model.apply({"params": p}, ids, mask)["logits"], ids, mask, old, advantages))(params)
    want = jax.grad(lambda p: _ppo_shaped_loss(reference.forward(p, ARCH, ids, mask, T), ids, mask, old, advantages))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(w).max())
        if moe.BIAS_NAME in name or "['h_0']['moe']['router']['carry_scale']" in name:
            assert float(jnp.abs(g).max()) == scale == 0.0, name  # a buffer; nothing lies below the first layer
            continue
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-6, rtol=3e-3, err_msg=name)


@pytest.mark.parametrize("piece", reference.PIECES)
def test_each_piece_knocked_out_of_the_reference_fails_the_comparison(piece):
    """No piece is decorative: the reference without it is far from the
    program, by the same measure check (a) takes (relative RMS distance),
    where the whole reference is within rounding."""
    cfg, model, params, ids, mask = _model()
    got = model.apply({"params": params}, ids, mask)["logits"][:, 5:]
    assert _distance(got, reference.forward(params, ARCH, ids, mask, T - 5)) < 1e-5
    assert _distance(got, reference.forward(params, ARCH, ids, mask, T - 5, drop=(piece,))) > 3e-3


# ---- through the cache ---------------------------------------------------------------------------------


def _decode(cfg, model, params, ids, mask, prompt):
    """Prefill `prompt` positions, then teacher-forced decode of the rest
    through the cache, one scalar traced write offset a step: [B, T - prompt + 1, V]."""
    total = ids.shape[1]
    cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, total - prompt), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prompt], mask[:, :prompt], cache=init_cache(cfg, B, total),
                      cache_index=0, cache_mask=cache_mask)
    step = jax.jit(lambda cache, index, cache_mask, token: model.apply(
        {"params": params}, token, jnp.ones((B, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask))
    cache, rows = out["cache"], [out["logits"][:, -1]]
    for i in range(prompt, total):
        cache_mask = cache_mask.at[:, i].set(1)
        out = step(cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
        cache = out["cache"]
        rows.append(out["logits"][:, 0])
    return jnp.stack(rows, axis=1), cache


@pytest.mark.parametrize("remat", [False, True], ids=["", "under remat"])
@pytest.mark.parametrize("prompt, pad", [(8, 0), (8, 3), (8, 7), (12, 5), (1, 0)],
                         ids=["no padding", "padded by 3", "a prompt of one token behind 7 pads", "12 padded by 5",
                              "a prefill of one position"])
def test_prefill_then_decode_matches_the_reference_s_full_pass(prompt, pad, remat):
    """The prefill hands the decode loop the window and the shifted value as of
    each row's last position, and the steps advance them: every step's logits
    are the reference's, which ran row 1 unpadded in one pass with no cache.
    A row with no padding beside one padded down to a prompt of ONE token;
    convolutions 3 and 2 wide keep a window of three positions."""
    arch = {**ARCH, "cca_time0": 3}
    cfg, model, params, _, _ = _model(arch, remat=remat)
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :pad].set(0)
    decoded, cache = _decode(cfg, model, params, ids * mask, mask, prompt)
    want = reference.forward(params, arch, ids * mask, mask, T - prompt + 1)
    np.testing.assert_allclose(decoded, want, atol=5e-5, rtol=1e-4)
    assert [leaf.shape for leaf in cache[0]] == [(B, T, 2, 16), (B, T, 2, 16), (B, 3, 96), (B, 1, 16)]


def test_the_cache_by_its_own_shapes():
    """Keys and values at the 2 K/V heads a slot, and beside them what does not
    grow: a window of cca_time0 + cca_time1 - 2 positions of the (4 + 2) x 16
    channels, and one shifted value of half the value heads."""
    cfg = LMConfig.from_dict({**ARCH, "dtype": "bfloat16"})
    slot, fixed = 2 * 2 * 16 * 2, (2 * 96 + 16) * 2
    assert cache_bytes_per_token(cfg) == 3 * slot and cca_state_bytes(cfg, 5) == 5 * 3 * fixed
    assert cache_bytes(cfg, 5, 40) == 5 * 3 * (40 * slot + fixed)
    assert cca.cache_shapes(cfg, 5, 40)[2][0] == (5, 2, 96)
    specs = {ndim: tuple(cache_partition_spec(cfg, ndim)) for ndim in (3, 4)}
    assert specs[3][1:] == (None, None) and specs[4][1] is None and specs[4][2] is not None  # heads over tp; the window whole
    mha = LMConfig.from_dict({k: v for k, v in ARCH.items() if not k.startswith("cca")} | {"attention": "mha"})
    assert cca_state_bytes(mha, 5) == 0


# ---- the frozen branch's second input ------------------------------------------------------------------


def test_the_frozen_branch_replays_from_the_branch_point_s_input_and_router_state():
    """`forward_branch` from block N - k's input AND the router state block
    N - k - 1 handed on equals the full pass; without the state (zeros in its
    place) it does not, and the trunk refuses a replay that brings none."""
    cfg = LMConfig.from_dict({**ARCH, **F32})
    model = LMWithValueHead(cfg, branch_layer=1)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :5].set(0)
    params = model.init(jax.random.PRNGKey(0), ids, mask)["params"]
    full = model.apply({"params": params}, ids, mask, collect_branch_hidden=True)
    state = full["branch_router_state"]
    assert state.shape == (B, T, 16) and state.dtype == jnp.float32 and float(jnp.abs(state).max()) > 0.1
    branch = extract_branch_params(params, cfg, 1)
    assert sorted(branch["transformer"]) == ["h_1", "h_2", "ln_f", "wte"]
    replay = model.apply({"params": branch}, full["branch_hidden"], mask, router_state=state, method="forward_branch")
    np.testing.assert_allclose(replay, full["logits"], atol=1e-5)
    stale = model.apply({"params": branch}, full["branch_hidden"], mask, router_state=jnp.zeros_like(state), method="forward_branch")
    assert _distance(stale, full["logits"]) > 3e-3
    with pytest.raises(ValueError, match="router_carry.*router state of the block below"):
        model.apply({"params": branch}, full["branch_hidden"], mask, method="forward_branch")
    with pytest.raises(ValueError, match="router_carry"):  # a pass from the first block takes none
        TransformerLM(cfg).apply({"params": params["transformer"]}, ids, mask, router_state=state)
    assert model.apply({"params": params}, ids, mask)["branch_router_state"] is None  # not asked for
    assert 1 / 8 < float(full["router_top_weight"]) < 1.0  # the mean weight of the tokens' one expert: the largest of eight probabilities


def test_the_router_s_weight_is_the_chosen_probability_itself():
    """One expert a token: the weight is softmax(m)[e*], e* the argmax of
    probability + bias; it is not renormalised (it would be the constant 1),
    and a bias large enough moves the choice without moving the weight's rule."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    probs = jax.nn.softmax(logits, axis=-1)
    ids, weights = moe.choose(logits, jnp.zeros(8), 1, 1.0, "softmax_all")
    np.testing.assert_array_equal(ids[:, 0], jnp.argmax(probs, axis=-1))
    np.testing.assert_allclose(weights[:, 0], jnp.max(probs, axis=-1), rtol=1e-6)
    assert float(weights.max()) < 1.0
    bias = jnp.zeros(8).at[3].set(1.0)
    ids, weights = moe.choose(logits, bias, 1, 1.0, "softmax_all")
    assert bool(jnp.all(ids == 3))
    np.testing.assert_allclose(weights[:, 0], probs[:, 3], rtol=1e-6)
    two, w2 = moe.choose(logits, jnp.zeros(8), 2, 1.0, "softmax_all")  # the rule at k = 2: still the probabilities themselves
    np.testing.assert_allclose(w2, jnp.take_along_axis(probs, two, axis=-1), rtol=1e-6)
    grad = jax.grad(lambda m: jnp.sum(moe.choose(m, bias, 1, 1.0, "softmax_all")[1]))(logits)
    assert float(jnp.abs(grad).max()) > 0  # the router learns through the weight


# ---- the shares of an expert-parallel layer ------------------------------------------------------------


@pytest.mark.parametrize("path", ["small call", "large call"])
def test_the_two_shares_add_up_to_the_uncut_layer(monkeypatch, path):
    """Experts [0, 4) and [4, 8) of the same routing: a token's one expert
    lies in exactly one share, and the two results add up to the layer that
    holds all eight."""
    if path == "large call":
        monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 8)
    whole_cfg = LMConfig.from_dict({**ARCH, **F32, "experts_held": []})
    h = jax.random.normal(jax.random.PRNGKey(4), (B, T, whole_cfg.d_model))
    layer = moe.ExpertLayer(whole_cfg)
    params = layer.init(jax.random.PRNGKey(5), h)["params"]
    whole, counts = layer.apply({"params": params}, h)
    assert int(counts.sum()) == B * T  # one expert a token
    total, seen = jnp.zeros_like(whole), 0
    for first in (0, 4):
        cfg = whole_cfg.replace(experts_held=(first, 4))
        part = {**params, **{name: params[name][first:first + 4] for name in ("experts_gate", "experts_up", "experts_down")}}
        y, share_counts = moe.ExpertLayer(cfg).apply({"params": part}, h)
        np.testing.assert_array_equal(share_counts, counts[first:first + 4])
        total, seen = total + y, seen + int(share_counts.sum())
    assert seen == B * T
    np.testing.assert_allclose(total, whole, atol=1e-5)


# ---- what trains, what is counted ---------------------------------------------------------------------


def test_trainable_mask_leaves_the_balancing_bias_out():
    cfg, model, params, _, _ = _model()
    mask = trainable_mask({"transformer": params}, cfg, 1)["transformer"]
    assert set(jax.tree_util.tree_leaves(mask["h_1"])) == {False}
    top = mask["h_2"]
    assert top["moe"][moe.BIAS_NAME] is False
    assert set(jax.tree_util.tree_leaves({k: v for k, v in top["moe"].items() if k != moe.BIAS_NAME})) == {True}
    assert set(jax.tree_util.tree_leaves({k: v for k, v in top.items() if k != "moe"})) == {True}  # convolutions, theta, the eight vectors
    assert params["h_2"]["moe"][moe.BIAS_NAME].shape == (8,)


ZAYA = json.load(open(os.path.join(CONFIGS, "zaya1-8b-ep2-l8.json")))


def _leaf_counts(tree):
    return {k: sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(v)) for k, v in tree.items()}


def test_parameter_counts_at_the_published_widths():
    """By `jax.eval_shape`, nothing allocated: the numbers of ISSUE 47. Whole
    (16 experts, 262,272 rows): a layer 207,583,506, 40 layers 8.30 B, the
    table 537,133,056; as the cell runs (8 of 16 held, half the vocabulary): a
    layer 106,920,210, the table 268,566,528, 8 layers 1,123.9 M."""
    from trlx_tpu.models.hf_import import lm_config_from_hf

    whole = lm_config_from_hf(types.SimpleNamespace(**ZAYA["published"]))
    ids = jnp.zeros((1, 2), jnp.int32)
    shapes = jax.eval_shape(TransformerLM(whole.replace(n_layer=2, ffn_layers=("experts",) * 2)).init, jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    layer = _leaf_counts(shapes["h_1"])
    assert layer["attn"] == 5_575_682 == 5_242_880 + 3_840 + 328_960 + 2
    moe_parts = _leaf_counts(shapes["h_1"]["moe"])
    assert moe_parts["router"] == 660_736 and moe_parts[moe.BIAS_NAME] == 16
    assert moe_parts["experts_gate"] + moe_parts["experts_up"] + moe_parts["experts_down"] == 16 * 12_582_912 == 201_326_592
    assert layer["ln_1"] + layer["ln_2"] == 4_096 and sum(v for k, v in layer.items() if k.startswith("res_")) == 16_384
    assert sum(layer.values()) == 207_583_506 and _leaf_counts(shapes["h_0"]) == layer
    assert _leaf_counts(shapes)["wte"] == 537_133_056 and "lm_head" not in shapes
    assert 40 * 207_583_506 + 537_133_056 + 2_048 == 8_840_475_344  # "8.3B" of layers beside the table
    active = 207_583_506 - 15 * 12_582_912
    assert 0.74e9 < 40 * active < 0.76e9  # a token's 0.75 B: "A0.8B"

    cell = LMConfig.from_dict(ZAYA["model_arch"])
    shapes = jax.eval_shape(TransformerLM(cell.replace(n_layer=1, ffn_layers=("experts",))).init, jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    assert sum(_leaf_counts(shapes["h_0"]).values()) == 106_920_210 and _leaf_counts(shapes)["wte"] == 268_566_528
    assert 8 * 106_920_210 + 268_566_528 + 2_048 == 1_123_930_256


def test_the_configuration_keeps_every_published_width():
    arch, published = ZAYA["model_arch"], ZAYA["published"]
    cfg = LMConfig.from_dict(arch)
    assert (cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (2048, 8, 2, 128)
    assert (cfg.expert_d_ff, cfg.n_experts, cfg.router_hidden, cfg.experts_per_token) == (2048, 16, 256, 1)
    assert (cfg.cca_time0, cfg.cca_time1, cfg.rotary_dim, cfg.rope_theta) == (2, 2, 64, 5e6)
    assert (cfg.n_layer, cfg.held_experts, cfg.vocab_size) == (8, (0, 8), 131136) and cfg.tie_word_embeddings
    assert sorted(ZAYA["reduced"]) == ["num_experts", "num_hidden_layers", "num_layers_unfrozen", "vocab_size"]
    for key, value in published.items():  # every catalogued key stands in the file as run, but the three that were cut
        if key in ("num_hidden_layers", "num_experts", "vocab_size", "layer_types"):
            continue
        assert ZAYA[key] == value, key
    assert (ZAYA["num_hidden_layers"], ZAYA["num_experts"], ZAYA["vocab_size"]) == (8, 8, 131136)
    rehearsal = LMConfig.from_dict(ZAYA["rehearsal_arch"])
    same = ("attention", "router_kind", "router_scoring", "router_carry", "residual_scaling", "cca_time0", "cca_time1",
            "experts_per_token", "tie_word_embeddings", "activation", "norm", "mlp", "pos_type")
    assert all(getattr(rehearsal, k) == getattr(cfg, k) for k in same)


CUT = ("n_layer", "ffn_layers", "vocab_size", "experts_held", "max_position", "embed_init_std", "draw_dtype", "logits_scaling")  # the cell's own


def test_lm_config_from_the_published_keys():
    from trlx_tpu.models.hf_import import lm_config_from_hf

    cfg = lm_config_from_hf(types.SimpleNamespace(**ZAYA["published"]))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.vocab_size) == (40, 2048, 8, 2, 128, 262272)
    assert (cfg.attention, cfg.cca_time0, cfg.cca_time1, cfg.rotary_dim, cfg.rope_theta) == ("cca", 2, 2, 64, 5e6)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_d_ff, cfg.n_shared_experts) == (16, 1, 2048, 0)
    assert (cfg.router_scoring, cfg.router_kind, cfg.router_hidden, cfg.router_carry) == ("softmax_all", "mlp", 256, True)
    assert cfg.residual_scaling and cfg.tie_word_embeddings and (cfg.activation, cfg.mlp, cfg.norm) == ("silu", "gated", "rmsnorm")
    from_arch = LMConfig.from_dict({"n_layer": 12, "ffn_layers": ["experts"] * 12} | {k: v for k, v in ZAYA["model_arch"].items()
                                    if k not in CUT})
    for key in LMConfig.__dataclass_fields__:
        if key not in CUT:
            assert getattr(from_arch, key) == getattr(cfg, key), key


@pytest.mark.parametrize("change, message", [
    ({"layer_types": ["hybrid"] * 39 + ["hybrid_sliding"]}, "a layer type other than 'hybrid'"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"hidden_act": "relu"}, "hidden_act 'relu'"),
    ({"zaya_use_mod": True}, "zero-compute 'skip' expert"),
    ({"attention_bias": True}, "attention_bias"),
])
def test_lm_config_from_hf_refuses_by_name(change, message):
    from trlx_tpu.models.hf_import import lm_config_from_hf

    with pytest.raises(ValueError, match=f"zaya: not built: .*{message}"):
        lm_config_from_hf(types.SimpleNamespace(**{**ZAYA["published"], **change}))


# ---- what is refused --------------------------------------------------------------------------------------


@pytest.mark.parametrize("bad, message", [
    ({"attention": "cca2"}, "unknown attention"),
    ({"router_kind": "conv"}, "unknown router_kind"),
    ({"cca_time0": 0}, "attention 'cca' needs cca_time0"),
    ({"cca_time0": 1, "cca_time1": 1}, "attention 'cca' needs cca_time0"),
    ({"n_kv_head": 1}, "even n_kv_head"),
    ({"attention": "mha", "residual_scaling": False}, "cca_time0 and cca_time1 describe attention 'cca'"),
    ({"fused_qkv": True}, "grouped keys|attention 'cca' is not built with fused_qkv"),
    ({"qkv_bias": True}, "attention 'cca' is not built with qkv_bias or out_bias"),
    ({"kv_cache_quant": True}, "attention 'cca' is not built with kv_cache_quant"),
    ({"n_soft_tokens": 4}, "soft prompts"),
    ({"sp_size": 2}, "sp ring"),
    ({"attention_layers": ["global", "local", "local"], "window_size": 8}, "attention 'cca' is not built with windowed"),
    ({"n_loops": 2}, "looped stack"),
    ({"qk_norm": True}, "attention 'cca' is not built with qk_norm"),
    ({"router_hidden": 0}, "router_kind 'mlp' needs router_hidden"),
    ({"router_kind": "linear"}, "router_kind 'mlp' needs router_hidden"),
    ({"router_kind": "linear", "router_hidden": 0}, "router_carry needs"),
    ({"router_input": "block"}, "router_kind 'mlp' is built for router_input 'ffn'"),
    ({"ffn_layers": ["experts", "dense", "experts"], "d_ff": 64}, "router_carry.*'dense' layer"),
    ({"ffn_layers": [], "d_ff": 64}, "describe expert layers"),
    ({"routed_scaling_factor": 2.0}, "takes no scale"),
    ({"parallel_residual": True}, "residual_scaling|router_carry"),
    ({"sandwich_norm": True}, "residual_scaling is built for the plain sequential residual"),
    ({"residual_multiplier": 0.5}, "residual_scaling is built for the plain sequential residual"),
    ({"cca_window": 2}, "unknown architecture key"),
])
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **bad})


def test_each_kind_builds_beside_the_older_ones():
    """The kinds are independent: "cca" under a dense feed-forward, an MLP
    router without the carry under "mha", residual scaling on a GPT block."""
    plain = {k: v for k, v in ARCH.items() if not k.startswith(("router", "residual", "cca", "ffn", "n_experts", "expert"))}
    for arch in (
        {**plain, "attention": "cca", "cca_time0": 2, "cca_time1": 2, "d_ff": 64},
        {**ARCH, "router_carry": False},
        {**{k: v for k, v in ARCH.items() if not k.startswith("cca")}, "attention": "mha"},
        {**plain, "attention": "mha", "residual_scaling": True, "d_ff": 64},
    ):
        cfg, model, params, ids, mask = _model(arch)
        out = model.apply({"params": params}, ids, mask, collect_hidden_at=1)
        assert bool(jnp.isfinite(out["logits"]).all())
        assert (out["branch_router_state"] is not None) == cfg.router_carry
        assert (out["router_top_weight"] is not None) == (cfg.router_scoring == "softmax_all")


def test_the_paths_that_are_not_built_refuse_by_name():
    from trlx_tpu.models.hf_export import validate_exportable
    from trlx_tpu.models.hf_import import load_hf_trunk

    cfg, model, params, ids, mask = _model()
    cache = init_cache(cfg, B, T)
    with pytest.raises(NotImplementedError, match="attention 'cca' takes a pass with no cache"):  # a per-row write offset
        model.apply({"params": params}, ids[:, :1], mask[:, :1], cache=cache, cache_index=jnp.zeros((B,), jnp.int32),
                    cache_mask=jnp.ones((B, T), jnp.int32))
    with pytest.raises(NotImplementedError, match="attention 'cca' takes a pass with no cache"):  # a verify window
        model.apply({"params": params}, ids[:, :4], mask[:, :4], cache=cache, cache_index=jnp.int32(3),
                    cache_mask=jnp.ones((B, T), jnp.int32))
    with pytest.raises(NotImplementedError, match="attention 'cca' takes a pass with no cache"):  # packed segments
        model.apply({"params": params}, ids, mask, segment_ids=jnp.zeros((B, T), jnp.int32))
    with pytest.raises(NotImplementedError, match="paged pool is not built for attention 'cca'"):
        init_paged_cache(cfg, 4, 8)
    with pytest.raises(NotImplementedError, match="zaya"):
        load_hf_trunk("/nowhere", cfg)
    with pytest.raises(ValueError, match="zaya"):
        validate_exportable(cfg.replace(logits_scaling=1.0), "gptj")  # (with a logits multiplier that refusal comes first)
    from trlx_tpu.engine.rollout_engine import RolloutEngine

    with pytest.raises(NotImplementedError, match="rollout engine .* attention 'cca'"):
        RolloutEngine(types.SimpleNamespace(cfg=cfg), None, n_slots=2, prompt_width=8)


# ---- the normal path --------------------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` -> orchestrator -> ops/generate.py (the four-leaf cache,
    the router state collected at the branch point a decode step) ->
    make_experience (scoring: the frozen branch replayed from both inputs) ->
    learn(): the fresh-step PPO ratio compares the decode path's own log-probs
    (a window and a shifted value advanced a step) with the train forward,
    expert choices included; the counters report what the cache holds beside
    its slots and the weight of a token's one expert."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "examples"))
    import trlx_tpu
    from randomwalks import base_config

    config = base_config("ppo", ARCH["vocab_size"], 16)
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 1
    config.train.batch_size, config.train.total_steps, config.train.epochs = 8, 4, 4  # dp 8 over the test devices
    config.train.eval_interval, config.train.log_interval = 100, 1
    config.train.seq_length = 28
    config.train.checkpoint_dir = str(tmp_path)
    config.method.num_rollouts = config.method.chunk_size = 8
    config.method.ppo_epochs = 2
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 20, "min_new_tokens": 20, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, ARCH["vocab_size"], size=int(n)).tolist() for n in rng.integers(1, 9, size=8)]
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 96 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    cfg = trainer.model.cfg
    assert trainer.fused_rollout and (cfg.attention, cfg.router_carry, cfg.kv_heads) == ("cca", True, 2)
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == [1, 2, 3, 4]
    for first in (1, 3):  # the first step of each iteration: the policy has not moved since it sampled
        assert abs(steps[first]["mean_ratio"] - 1.0) < 1e-3, steps[first]["mean_ratio"]
        assert abs(steps[first]["policy/approx_kl"] if "policy/approx_kl" in steps[first] else 0.0) < 1e-3
    for r in steps.values():
        assert 0.0 < r["moe/held_slot_share"] < 1.0 and r["moe/first_buffer_share"] == 1.0
        assert 1 / 8 < r["moe/top1_weight_mean"] < 1.0  # the largest of eight probabilities
        assert r["moe/rows_per_held_expert"] == pytest.approx(r["moe/held_slot_share"] * 8 * 28 / 4)
    phases = [r for r in records if "time/window_wall_s" in r]
    itemsize = cfg.compute_dtype.itemsize
    fixed = 8 * 3 * (2 * 96 + 16) * itemsize
    assert phases and all(p["rollout/cca_state_bytes"] == fixed for p in phases)
    assert all(p["rollout/cache_bytes"] == 8 * 3 * 28 * 2 * 2 * 16 * itemsize + fixed for p in phases)

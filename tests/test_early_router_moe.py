"""The grouped-key, windowed block family whose router stands ahead of
attention (models/lm.py `Block` with `router_input: block`; models/moe.py
`route` with `router_scoring: softmax`, ReGLU experts; a group of 7 query
heads a K/V head; a ring the prefill fills exactly) against the plain
reference `benchmark/references/gqa_window_early_router_moe_decoder.py`:
seeded random weights, tiny sizes, float32, CPU. The kinds are the
SmallThinker configuration's rehearsal kinds (ISSUE 44).
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import gqa_window_early_router_moe_decoder as reference
from trlx_tpu.models import moe
from trlx_tpu.models.lm import (ACTIVATIONS, Block, LMConfig, TransformerLM, cache_bytes, init_cache, make_attn_bias,
                                ring_cache_bytes, rope_tables)
from trlx_tpu.ops.flash_attention import FlashBlocks, flash_attention
from trlx_tpu.ops.kv_read import attend

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "benchmark", "configs")

# Three periods of full span, window, window, window; 14 query heads over 2
# K/V heads of 16 (a group of 7; n_head * head_width = 224 is not d_model =
# 64); a window of 8; every layer 16 ReGLU experts of which [4, 8) are held,
# 3 a token, chosen by a softmax router that reads the block's input.
ARCH = dict(
    vocab_size=96, n_layer=12, n_head=14, n_kv_head=2, head_width=16, d_model=64, max_position=256, eos_token_id=0,
    pos_type="rotary", rotary_layers="local", rope_theta=1500000, extra={"neox_rotary": True}, norm="rmsnorm", mlp="gated",
    attention="mha", activation="relu", ln_eps=1e-6, parallel_residual=False, tie_word_embeddings=False, fused_qkv=False,
    qkv_bias=False, out_bias=False, attention_layers=["global", "local", "local", "local"] * 3, window_size=8,
    window_cache="ring", ffn_layers=["experts"] * 12, n_experts=16, experts_per_token=3, expert_d_ff=32,
    router_scoring="softmax", router_input="block", experts_held=[4, 4], embed_init_std=1.0,
)
SHORT = {**ARCH, "n_layer": 4, "attention_layers": ["global", "local", "local", "local"], "ffn_layers": ["experts"] * 4}
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 2, 24


def _model(arch=SHORT, seed=0, **over):
    cfg = LMConfig.from_dict({**arch, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :5].set(0)  # row 1 is left-padded
    params = model.init(jax.random.PRNGKey(seed), ids, mask)["params"]
    return cfg, model, params, ids * mask, mask


# ---- the program against the reference -------------------------------------------------------------


@pytest.mark.parametrize("arch", [SHORT, ARCH], ids=["one period", "three periods"])
@pytest.mark.parametrize("attn_impl", ["xla", "flash"], ids=["einsum", "flash kernels, interpreted"])
def test_logits_match_the_reference_padded_and_unpadded_rows(attn_impl, arch):
    cfg, model, params, ids, mask = _model(arch, attn_impl=attn_impl)
    got = model.apply({"params": params}, ids, mask)["logits"]
    want = reference.forward(params, arch, ids, mask, T)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got[0], want[0], atol=5e-5, rtol=1e-4)  # no padding
    np.testing.assert_allclose(got[1, 5:], want[1, 5:], atol=5e-5, rtol=1e-4)  # its real positions


@pytest.mark.parametrize("remat", [False, True], ids=["", "under remat"])
@pytest.mark.parametrize("attn_impl", ["xla", "flash"], ids=["einsum", "flash kernels, interpreted"])
def test_gradients_of_a_scalar_loss_match_the_reference(attn_impl, remat):
    """Every parameter's gradient: the router's (through the softmax over the
    chosen; the choice itself has none), the ReGLU experts', the grouped K
    and V projections' (summed over a group's 7 query heads)."""
    cfg, model, params, ids, mask = _model(attn_impl=attn_impl, remat=remat)
    weight = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.vocab_size)) * mask[:, :, None]
    got = jax.grad(lambda p: jnp.sum(model.apply({"params": p}, ids, mask)["logits"] * weight))(params)
    want = jax.grad(lambda p: jnp.sum(reference.forward(p, SHORT, ids, mask, T) * weight))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale + 1e-6, rtol=2e-3, err_msg=name)


def _decode(cfg, model, params, ids, mask, prompt):
    """Prefill `prompt` tokens, then teacher-forced decode of the rest
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


@pytest.mark.parametrize("prompt", [4, 8, 12], ids=["prompt inside the window", "prompt fills the ring exactly",
                                                    "prompt longer than the window"])
@pytest.mark.parametrize("window_cache, remat", [("ring", False), ("ring", True), ("span", False)],
                         ids=["ring", "ring under remat", "span"])
def test_prefill_then_decode_past_the_window_matches_the_full_forward(window_cache, remat, prompt):
    """16 to 24 decode steps on a window of 8: the ring wraps and every slot is
    overwritten; a prompt of 8 fills each ring exactly (the cell's case: every
    decode step wraps), a prompt of 12 leaves the prefill's last 8 positions,
    rolled to their slots. The routing of a decode step is made from the one
    token's block input, ahead of its read of the cache. The full-span cache
    with the window in the bias gives the same logits as the ring, and both
    the reference's full forward."""
    total = 28
    cfg, model, params, _, _ = _model(window_cache=window_cache, remat=remat)  # remat: a block sees its offset traced
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, total), 2, cfg.vocab_size)
    mask = jnp.ones((B, total), jnp.int32).at[1, :3].set(0)
    decoded, cache = _decode(cfg, model, params, ids * mask, mask, prompt)
    want = reference.forward(params, SHORT, ids * mask, mask, total - prompt + 1)
    np.testing.assert_allclose(decoded, want, atol=5e-5, rtol=1e-4)
    lengths = [int(layer[0].shape[1]) for layer in cache]
    assert lengths == ([total, 8, 8, 8] if window_cache == "ring" else [total] * 4)
    assert all(layer[0].shape[2:] == (2, 16) for layer in cache)  # K and V at the 2 K/V heads


def test_the_frozen_branch_replay_routes_from_the_branch_point():
    """`start_layer=k` over the hidden state entering block k (the hydra
    reference branch): that state IS block k's input, which its router reads,
    so the replay's logits are the full pass's."""
    cfg, model, params, ids, mask = _model()
    full = model.apply({"params": params}, ids, mask, collect_hidden_at=2)
    replay = model.apply({"params": params}, inputs_embeds=full["branch_hidden"], attention_mask=mask, start_layer=2)
    np.testing.assert_allclose(replay["logits"], full["logits"], atol=1e-5)
    assert replay["expert_counts"].shape == (2, 4) and full["expert_counts"].shape == (4, 4)
    np.testing.assert_array_equal(replay["expert_counts"], full["expert_counts"][2:])


# ---- the router in isolation -------------------------------------------------------------------------


def test_top_k_then_softmax_is_softmax_then_renormalise():
    key_x, key_w = jax.random.split(jax.random.PRNGKey(0))
    x, router = jax.random.normal(key_x, (40, 64)), jax.random.normal(key_w, (64, 16)) / 8
    ids, weights = moe.route(x, router, None, 3, 1.0, "softmax")
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    order = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(ids, axis=-1), np.sort(order, axis=-1))
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)  # a softmax over all 16 ...
    chosen = np.take_along_axis(probs, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1, keepdims=True), rtol=2e-5)  # ... renormalised over the chosen
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
    # the sigmoid rule behind the same signature, as it was: scores + bias choose, the scores renormalised and scaled weigh
    bias = jnp.zeros(16).at[5].set(10.0)
    ids_s, weights_s = moe.route(x, router, bias, 3, 2.5)
    assert bool(jnp.all(jnp.any(ids_s == 5, axis=-1)))
    np.testing.assert_allclose(weights_s.sum(-1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("router_input", ["block", "ffn"])
def test_the_router_reads_the_block_s_input(router_input):
    """One block, every expert held, so `counts` shows the whole choice:
    scaling `ln_1` (attention's input norm, and with it what the feed-forward
    sees) leaves a `block` router's choice as it was and moves an `ffn`
    router's; moving x moves both."""
    cfg = LMConfig.from_dict({**SHORT, **F32, "experts_held": [], "router_input": router_input})
    block = Block(cfg, "experts")
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model))
    bias, positions = make_attn_bias(jnp.ones((B, T), jnp.int32), T, 0), rope_tables(cfg, jnp.broadcast_to(jnp.arange(T), (B, T)))
    params = block.init(jax.random.PRNGKey(2), x, bias, positions)["params"]
    assert moe.BIAS_NAME not in params["moe"] and sorted(params["moe"]) == ["experts_down", "experts_gate", "experts_up", "router"]
    run = lambda p, x: block.apply({"params": p}, x, bias, positions)
    y, _, counts, *_ = run(params, x)
    assert int(counts.sum()) == B * T * 3
    wave = 1.0 + jnp.sin(jnp.arange(cfg.d_model))  # not a uniform factor: RMSNorm_2 would undo one
    scaled = {**params, "ln_1": {"scale": params["ln_1"]["scale"] * wave}}
    y_scaled, _, counts_scaled, *_ = run(scaled, x)
    assert float(jnp.abs(y_scaled - y).max()) > 1e-3  # attention did change
    assert bool(jnp.all(counts_scaled == counts)) == (router_input == "block")
    _, _, counts_moved, *_ = run(params, x + 0.5 * jax.random.normal(jax.random.PRNGKey(3), x.shape))
    assert not bool(jnp.all(counts_moved == counts))


def test_the_expert_layer_takes_the_router_s_input_or_the_routing_itself():
    cfg = LMConfig.from_dict({**SHORT, **F32})
    layer = moe.ExpertLayer(cfg)
    h, x = (jax.random.normal(jax.random.PRNGKey(i), (B, T, cfg.d_model)) for i in (4, 5))
    params = layer.init(jax.random.PRNGKey(6), h)["params"]
    given, counts = layer.apply({"params": params}, h, x)  # router_input
    routed = layer.apply({"params": params}, x, method=layer.routing)
    np.testing.assert_array_equal(layer.apply({"params": params}, h, routed=routed)[0], given)
    own, own_counts = layer.apply({"params": params}, h)
    assert not bool(jnp.all(own_counts == counts)) and float(jnp.abs(own - given).max()) > 1e-3


# ---- ReGLU experts on every path of `held_experts_ffn` -----------------------------------------------

LARGE_CALL_PATHS = {  # 48 tokens, 3 a token, a share of 4 of 16 experts: some 36 held slots of 144
    "small call": None,
    "large call, the buffer from the shapes": "shapes",
    "large call, a buffer that holds the slots": 96,
    "large call, in token chunks": "chunks",
    "large call, every held expert over every token": 8,
    "large call, a buffer of 32 rows a choice summed back by a gather": 96,
    "large call, in token chunks of 12 rows a choice summed back by a gather": "chunks",
}
GATHER_FROM = {  # `GATHER_ROWS_PER_CHOICE` for the paths that land on the gather's side of it (PR 45); the others keep the module's
    "large call, a buffer of 32 rows a choice summed back by a gather": 32,
    "large call, in token chunks of 12 rows a choice summed back by a gather": 12,
}


def _paths(monkeypatch, path):
    if LARGE_CALL_PATHS[path] is not None:
        monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0)
    if path in GATHER_FROM:
        monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", GATHER_FROM[path])
    if isinstance(LARGE_CALL_PATHS[path], int):
        monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: LARGE_CALL_PATHS[path])
    if LARGE_CALL_PATHS[path] == "chunks":
        monkeypatch.setattr(moe, "NARROW_PASS_TOKENS", 12), monkeypatch.setattr(moe, "WIDE_PASS_TOKENS", 12)  # 48 tokens: four passes


@pytest.mark.parametrize("path", LARGE_CALL_PATHS)
def test_gated_relu_experts_against_a_plain_loop(monkeypatch, path):
    """down(relu(gate h) * up h), weighted and summed over the held experts a
    token chose: the small-call form, the grouped products, the token chunks
    and `dense_held_ffn` against a loop over tokens and choices in numpy, and
    the gradient of a scalar through each against the small call's. The rows
    go back onto the tokens by the 0/1 product or by a gather, as the
    buffer's rows a choice say."""
    _paths(monkeypatch, path)
    forms = []
    for form in ("sum_rows_product", "sum_rows_gather"):
        monkeypatch.setattr(moe, form, lambda *a, form=form, fn=getattr(moe, form): forms.append(form) or fn(*a))
    n, d, f, first, held, k = 48, 64, 32, 4, 4, 3
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (n, d))
    gate, up = (jax.random.normal(key, (held, d, f)) / 8 for key in keys[1:3])
    down = jax.random.normal(keys[3], (held, f, d)) / 6
    ids, weights = moe.route(x, jax.random.normal(keys[4], (d, 16)) / 8, None, k, 1.0, "softmax")
    run = lambda x, gate, up, down: moe.held_experts_ffn(x, ids, weights, first, 16, gate, up, down, ACTIVATIONS["relu"])
    y, counts = run(x, gate, up, down)
    want, want_counts = np.zeros((n, d)), np.zeros(held, np.int64)
    xs, g, u, dn = (np.asarray(a, np.float64) for a in (x, gate, up, down))
    for t in range(n):
        for c in range(k):
            e = int(ids[t, c]) - first
            if 0 <= e < held:
                want[t] += float(weights[t, c]) * ((np.maximum(xs[t] @ g[e], 0.0) * (xs[t] @ u[e])) @ dn[e])
                want_counts[e] += 1
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(counts, want_counts)
    if LARGE_CALL_PATHS[path] == 8:
        assert int(counts.sum()) > 8  # the buffer of 8 rows could not hold the slots: the dense path ran
    probe = jax.random.normal(keys[5], (n, d))
    grads = jax.grad(lambda *a: jnp.sum(run(*a)[0] * probe), argnums=(0, 1, 2, 3))(x, gate, up, down)
    small = jax.grad(lambda *a: jnp.sum(moe.experts_over_tokens(a[0], ids, weights, first, *a[1:], ACTIVATIONS["relu"]) * probe),
                     argnums=(0, 1, 2, 3))(x, gate, up, down)
    for got, ref in zip(grads, small):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=1e-3)
    # forward, and as `take_rows`' transpose in the backward pass: one form throughout, none in a small call
    assert set(forms) == (set() if path == "small call" else {"sum_rows_gather"} if path in GATHER_FROM else {"sum_rows_product"})
    assert len(forms) != 1


ONE_PASS_BUFFERS = {  # rows of the slot buffer of a pass of n tokens (3 choices of 16 experts a token, 4 held: 0.75 n held slots at an even router)
    "a wide buffer, three rows a token: no overflow branch": lambda n: 3 * n,
    "a buffer of a row a token, under the cond": lambda n: n,
    "a buffer the held slots pass: the dense path": lambda n: n // 4,
}


@pytest.mark.parametrize("buffer", ONE_PASS_BUFFERS)
def test_one_pass_over_a_call_equals_the_same_call_in_three(monkeypatch, buffer):
    """The expert layer over one call of 48 tokens in ONE pass (what
    `pass_tokens` gives a train batch where the buffer is wide, PR 48) against
    the same call forced into three passes of 16: the layer's output, the
    held experts' counts, and the gradients of the tokens, the router and the
    three stacks. One grouped contraction over the call's rows where three
    were summed over the passes: the same rows, the same products."""
    monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0)
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", 1)  # summed back by the gather, as a wide buffer is
    monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: ONE_PASS_BUFFERS[buffer](n))
    placed = []
    monkeypatch.setattr(moe, "place_slots", lambda ids, *a, fn=moe.place_slots: placed.append(ids.shape[0]) or fn(ids, *a))
    cfg = LMConfig.from_dict({**SHORT, **F32})
    h, x = (jax.random.normal(jax.random.PRNGKey(i), (B, T, cfg.d_model)) for i in (5, 9))
    layer = moe.ExpertLayer(cfg)
    params = layer.init(jax.random.PRNGKey(6), h, x)["params"]
    probe = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.d_model))

    def run(params, h, x):
        y, counts = layer.apply({"params": params}, h, x)
        return jnp.sum(y * probe), (y, counts)

    results = {}
    for passes in (1, 3):
        monkeypatch.setattr(moe, "NARROW_PASS_TOKENS", B * T // passes), monkeypatch.setattr(moe, "WIDE_PASS_TOKENS", B * T // passes)
        del placed[:]
        (_, (y, counts)), grads = jax.value_and_grad(run, argnums=(0, 1, 2), has_aux=True)(params, h, x)
        assert set(placed) == {B * T // passes}  # the tokens of every grouped call traced
        results[passes] = (y, counts, grads)
    (y, counts, grads), (y3, counts3, grads3) = results[1], results[3]
    assert int(counts.sum()) > B * T // 4 and float(jnp.abs(y).max()) > 0.1
    np.testing.assert_array_equal(counts, counts3)
    np.testing.assert_allclose(y, y3, atol=2e-6, rtol=1e-5)
    assert set(grads[0]) == {"router", "experts_gate", "experts_up", "experts_down"}
    for (path, g), g3 in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads3)):
        assert float(jnp.abs(g).max()) > 1e-3, jax.tree_util.keystr(path)  # the block's input reaches the loss through the router alone
        np.testing.assert_allclose(g, g3, atol=2e-5, rtol=1e-4, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("path", ["small call", "large call, the buffer from the shapes", "large call, in token chunks"])
def test_the_four_shares_add_up_to_the_uncut_layer(monkeypatch, path):
    """The guide's share test at the published counts: 64 experts, 6 a token,
    four chips of 16. The parts the shares [0,16) .. [48,64) give from ONE
    routing (the router counted once: every share holds the same router and
    chooses over all 64) add up to the layer that holds all 64."""
    _paths(monkeypatch, path)
    whole_cfg = LMConfig.from_dict({**SHORT, **F32, "n_experts": 64, "experts_per_token": 6, "experts_held": []})
    h, x = (jax.random.normal(jax.random.PRNGKey(i), (B, T, whole_cfg.d_model)) for i in (5, 9))
    layer = moe.ExpertLayer(whole_cfg)
    whole = layer.init(jax.random.PRNGKey(6), h)["params"]
    want, counts = layer.apply({"params": whole}, h, x)
    assert int(counts.sum()) == B * T * 6 and whole["router"].shape == (64, 64)
    total, chosen = jnp.zeros_like(want), 0
    for first in range(0, 64, 16):
        cfg = whole_cfg.replace(experts_held=(first, 16))
        part = {"router": whole["router"], **{name: whole[name][first:first + 16] for name in ("experts_gate", "experts_up", "experts_down")}}
        y, share_counts = moe.ExpertLayer(cfg).apply({"params": part}, h, x)
        np.testing.assert_array_equal(share_counts, counts[first:first + 16])
        total, chosen = total + y, chosen + int(share_counts.sum())
    assert chosen == B * T * 6
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


# ---- a group of 7 through the flash kernels -----------------------------------------------------------


@pytest.mark.parametrize("window", [0, 64], ids=["full span", "a window the length passes"])
@pytest.mark.parametrize("blocks", [(128, 256, 128), (64, 128, 64)], ids=["resident", "major pieces"])
def test_flash_with_a_group_of_7_matches_the_einsum(window, blocks):
    """Forward, dq and the grouped dk/dv (summed over the 7 query heads of a
    group inside the kernel, its innermost grid axis 7 x the major pieces),
    interpreted, against `attend` over the same grouped operands, at a length
    four times the window; the sequence resident and in pieces."""
    b, t, h, h_kv, d = 2, 256, 14, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, t, h, d))
    k, v = (jax.random.normal(key, (b, t, h_kv, d)) for key in keys[1:3])
    mask = jnp.ones((b, t)).at[1, :40].set(0)
    weight = jax.random.normal(keys[3], (b, t, h, d)) * mask[:, :, None, None]
    flash = lambda q, k, v: flash_attention(q, k, v, mask, scale=0.2, causal=True, window=window,
                                            blocks=FlashBlocks(*blocks), interpret=True)
    plain = lambda q, k, v: attend(q, k, v, make_attn_bias(mask, t, 0, window=window), 0.2, jnp.float32)
    np.testing.assert_allclose(flash(q, k, v) * mask[:, :, None, None], plain(q, k, v) * mask[:, :, None, None], atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert got[1].shape == got[2].shape == (b, t, h_kv, d)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


# ---- what is refused, and what takes the buffer's absence ---------------------------------------------


@pytest.mark.parametrize("bad, message", [
    ({"router_scoring": "softmax_some"}, "unknown router_scoring"),
    ({"router_input": "attention"}, "unknown router_input"),
    ({"routed_scaling_factor": 2.5}, "takes no scale"),
    ({"parallel_residual": True}, "router_input 'block'.*parallel_residual"),
    ({"sandwich_norm": True}, "router_input 'block'.*sandwich_norm"),
    ({"ffn_layers": [], "attention_layers": [], "window_cache": "span", "rotary_layers": "all", "n_loops": 2,
      "router_scoring": "sigmoid"}, "describe expert layers"),
    ({"ffn_layers": ["dense"] * 4, "d_ff": 64}, "describe expert layers"),
    ({"mixer_layers": ["kda", "attention", "attention", "attention"], "kda_heads": 2, "kda_head_dim": 16,
      "attention_layers": [], "window_cache": "span", "rotary_layers": "all"}, "router_input 'block'.*'kda' layer"),
    ({"activation": "reglu"}, "unknown activation"),
    ({"moe_enable_early_router": True}, "unknown architecture key"),
])
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**SHORT, **bad})


def test_lmconfig_builds_each_kind_beside_the_other():
    """The two kinds are independent: a softmax router after attention, a
    sigmoid router (with its buffer) ahead of it."""
    for scoring, reads in (("softmax", "ffn"), ("sigmoid", "block"), ("sigmoid", "ffn")):
        cfg, model, params, ids, mask = _model(router_scoring=scoring, router_input=reads)
        assert (moe.BIAS_NAME in params["h_0"]["moe"]) == (scoring == "sigmoid")
        assert bool(jnp.isfinite(model.apply({"params": params}, ids, mask)["logits"]).all())


PUBLISHED = json.load(open(os.path.join(CONFIGS, "smallthinker-21b-ep4.json")))["published"]


def test_lm_config_from_the_published_keys():
    from trlx_tpu.models.hf_import import lm_config_from_hf

    cfg = lm_config_from_hf(types.SimpleNamespace(**PUBLISHED))  # the published file carries `model_name`, no `model_type`
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.vocab_size) == (52, 2560, 28, 4, 128, 151936)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_d_ff, cfg.n_shared_experts) == (64, 6, 768, 0)
    assert (cfg.router_scoring, cfg.router_input, cfg.activation, cfg.mlp, cfg.norm) == ("softmax", "block", "relu", "gated", "rmsnorm")
    assert cfg.attention_layers == ("global", "local", "local", "local") * 13 and cfg.ffn_layers == ("experts",) * 52
    assert (cfg.window_size, cfg.rope_theta, cfg.ln_eps, cfg.rotary_layers, cfg.pos_type) == (4096, 1.5e6, 1e-6, "local", "rotary")
    assert cfg.extra == {"neox_rotary": True} and not (cfg.tie_word_embeddings or cfg.qkv_bias or cfg.out_bias or cfg.fused_qkv)
    assert cfg.held_experts == (0, 64) and cfg.max_position == 16384
    held = lm_config_from_hf(types.SimpleNamespace(model_type="smallthinker", **PUBLISHED), experts_held=(16, 16), window_cache="ring")
    assert held.held_experts == (16, 16) and held.window_cache == "ring"
    late = lm_config_from_hf(types.SimpleNamespace(**{**PUBLISHED, "moe_enable_early_router": False}))
    assert late.router_input == "ffn"


@pytest.mark.parametrize("change, message", [
    ({"rope_layout": [1] + PUBLISHED["rope_layout"][1:]}, "rope_layout and sliding_window_layout that disagree"),
    ({"sliding_window_layout": PUBLISHED["sliding_window_layout"][:-1]}, "disagree|another length"),
    ({"moe_layer_layout": [0] + [1] * 51}, "a dense layer"),
    ({"moe_primary_router_apply_softmax": False}, "moe_primary_router_apply_softmax false"),
    ({"norm_topk_prob": False}, "norm_topk_prob false"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
])
def test_lm_config_from_hf_refuses_by_name(change, message):
    from trlx_tpu.models.hf_import import lm_config_from_hf

    with pytest.raises(ValueError, match=f"smallthinker: not built: .*({message})"):
        lm_config_from_hf(types.SimpleNamespace(**{**PUBLISHED, **change}))


def test_what_names_the_bias_buffer_takes_its_absence():
    """`trainable_mask` (no buffer to freeze: everything in an unfrozen block
    trains), the sharding rules (every leaf has a spec), and the two refusals
    by name: weights are not imported and not exported for this family."""
    from trlx_tpu.models.heads import trainable_mask
    from trlx_tpu.models.hf_export import validate_exportable
    from trlx_tpu.models.hf_import import load_hf_trunk
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel.mesh import AXIS_FSDP, AXIS_TP
    from trlx_tpu.parallel.sharding import lm_partition_rules, match_partition_rules

    cfg, model, params, _, _ = _model()
    mask = trainable_mask({"transformer": params}, cfg, 1)["transformer"]
    assert set(jax.tree_util.tree_leaves(mask["h_3"])) == {True} and set(jax.tree_util.tree_leaves(mask["h_2"])) == {False}
    assert set(mask["h_3"]["moe"]) == {"router", "experts_gate", "experts_up", "experts_down"}
    specs = match_partition_rules(lm_partition_rules(), params)["h_3"]["moe"]
    assert specs == {"router": P(AXIS_FSDP, None), "experts_gate": P(None, AXIS_FSDP, AXIS_TP),
                     "experts_up": P(None, AXIS_FSDP, AXIS_TP), "experts_down": P(None, AXIS_TP, AXIS_FSDP)}
    with pytest.raises(NotImplementedError, match="smallthinker"):
        load_hf_trunk("/nowhere", cfg)
    with pytest.raises(ValueError, match="smallthinker"):
        validate_exportable(cfg, "gptj")


def test_counters_by_hand():
    # a train step of the cell: 12,288 tokens, 6 of 64 a token, 16 held, ONE pass where the buffer is wide (PR 48; three of 4,096
    # before): 1,152 rows a call at an even router
    assert moe.pass_tokens(12288, 6, 16, 64) == 12288
    assert moe.rows_per_held_expert(16 / 64, 12288, 6, 16, 64) == pytest.approx(1152.0)
    assert moe.rows_per_held_expert(16 / 64, 4096, 6, 16, 64) == pytest.approx(384.0)  # a third of the tokens
    # twice the even share of 6,144 slots, whole tiles: three rows a token (SLOTS_PER_TOKEN; at two the chip passed the buffer, PR 44)
    assert moe.slot_capacity(4096, 6, 16, 64) == 12288 == moe.SLOTS_PER_TOKEN * 4096
    assert moe.slot_capacity(12288, 6, 16, 64) == 36864 == moe.SLOTS_PER_TOKEN * 12288  # the train batch's one buffer: the three pooled
    # 2,048 rows of buffer a choice: the rows are summed back by a gather of a token's own six, in a pass and in a train batch alike (PR 45)
    assert moe.sums_by_gather(12288, 6) and moe.sum_rows_per_token(4096, 6, 16, 64) == moe.sum_rows_per_token(12288, 6, 16, 64) == 6
    # the prefill of 16 prompts: 4 passes of 16,384 (16 of 4,096 before); the scoring pass over 16 rollouts of 6,144: 6 (24)
    assert not moe.sums_by_gather(4096, 8) and moe.sum_rows_per_token(65536, 6, 16, 64) == 6
    assert moe.pass_tokens(65536, 6, 16, 64) == moe.pass_tokens(98304, 6, 16, 64) == 16384 == moe.WIDE_PASS_TOKENS
    even = jnp.full((8, 16), 1152, jnp.int32)  # a train step's counts at an even router: 1,152 rows an expert of a buffer of 36,864
    assert float(moe.first_buffer_share(even, 12288, 6, 64)) == 1.0 and float(moe.first_buffer_share(even * 2 + 1, 12288, 6, 64)) == 0.0 and float(moe.first_buffer_share(even * 3 // 2, 12288, 6, 64)) == 1.0
    # the cache of the cell's rollout: six rings of 4,096 and two spans of 6,144, K and V, 4 heads of 128, bf16
    big = LMConfig.from_dict({**json.load(open(os.path.join(CONFIGS, "smallthinker-21b-ep4.json")))["model_arch"], "dtype": "bfloat16"})
    slot = 2 * 4 * 128 * 2
    assert ring_cache_bytes(big, 16, 6144) == 16 * 6 * 4096 * slot and cache_bytes(big, 16, 6144) == 16 * (6 * 4096 + 2 * 6144) * slot
    assert ring_cache_bytes(big, 16, 6144) / cache_bytes(big, 16, 6144) == pytest.approx(2 / 3)
    span = big.replace(window_cache="span")
    assert ring_cache_bytes(span, 16, 6144) == 0 and cache_bytes(span, 16, 6144) == 16 * 8 * 6144 * slot


# ---- what stands: the three older expert configurations' trees -----------------------------------------

PARENT_TREES = json.load(open(os.path.join(HERE, "data", "rehearsal_param_trees_pr43.json")))


@pytest.mark.parametrize("name", sorted(PARENT_TREES))
def test_with_the_new_kinds_at_their_defaults_an_expert_configuration_s_tree_is_the_parent_s(name):
    """Leaf for leaf what commit 62eddf8 (PR 43) builds from the same
    `rehearsal_arch` and key: path, shape, dtype and the draw itself (the sum
    of magnitudes of each leaf; recorded there into tests/data's file). The
    expert layer declares its parameters in `setup` now, in the order it
    declared them inline."""
    spec = json.load(open(os.path.join(CONFIGS, f"{name}.json")))
    cfg = LMConfig.from_dict(spec["rehearsal_arch"])
    assert (cfg.router_scoring, cfg.router_input) == ("sigmoid", "ffn")
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = jax.jit(TransformerLM(cfg).init)(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    got = {jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    want = PARENT_TREES[name]
    assert sorted(got) == sorted(want)
    assert any(moe.BIAS_NAME in path for path in got)
    for path, (shape, dtype, magnitude) in want.items():
        leaf = got[path]
        assert (list(leaf.shape), str(leaf.dtype)) == (shape, dtype), path
        np.testing.assert_allclose(float(jnp.sum(jnp.abs(leaf.astype(jnp.float32)))), magnitude, rtol=1e-6, err_msg=path)


# ---- the normal path --------------------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` -> orchestrator -> ops/generate.py (ring and full-span
    caches, a group of 7, the router ahead of each cache read) ->
    make_experience (scoring, the frozen branch) -> learn(): the fresh-step
    PPO ratio compares the decode path's own log-probs with the train forward,
    expert choices included; the counters report what the cache holds and
    what an expert takes."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "examples"))
    import trlx_tpu
    from randomwalks import base_config

    config = base_config("ppo", SHORT["vocab_size"], 16)
    config.model.model_arch = dict(SHORT)
    config.model.num_layers_unfrozen = 1
    config.train.batch_size, config.train.total_steps, config.train.epochs = 8, 4, 4  # dp 8 over the test devices
    config.train.eval_interval, config.train.log_interval = 100, 1
    config.train.seq_length = 28  # the train batch's positions, as the benchmark sets it: prompt + new tokens
    config.train.checkpoint_dir = str(tmp_path)
    config.method.num_rollouts = config.method.chunk_size = 8
    config.method.ppo_epochs = 2
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 20, "min_new_tokens": 20, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, SHORT["vocab_size"], size=int(n)).tolist() for n in rng.integers(4, 9, size=8)]
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 96 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    cfg = trainer.model.cfg
    assert trainer.fused_rollout and (cfg.window_cache, cfg.kv_heads, cfg.router_input) == ("ring", 2, "block")
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == [1, 2, 3, 4]
    for first in (1, 3):  # the first step of each iteration: the policy has not moved since it sampled
        assert abs(steps[first]["mean_ratio"] - 1.0) < 1e-3, steps[first]["mean_ratio"]
    for r in steps.values():
        assert 0.0 < r["moe/held_slot_share"] < 1.0 and r["moe/first_buffer_share"] == 1.0
        # 8 rows of 28 tokens, 3 a token, 4 held, one pass
        assert r["moe/rows_per_held_expert"] == pytest.approx(r["moe/held_slot_share"] * 8 * 28 * 3 / 4)
        assert r["moe/sum_rows_per_token"] == 4  # 672 token-slots: a small call, one result a held expert
        assert r["moe/passes"] == 1
    phases = [r for r in records if "time/window_wall_s" in r]
    itemsize = cfg.compute_dtype.itemsize
    assert phases and all(p["rollout/cache_bytes"] == 8 * (3 * 8 + 28) * 2 * 2 * 16 * itemsize for p in phases)
    assert all(p["rollout/ring_cache_share"] == pytest.approx(3 * 8 / (3 * 8 + 28)) for p in phases)  # three rings of 8, one span of 28

"""The MiniCPM-SALA kinds (PR 51): a "lightning" mixer (constant-decay linear
attention, qk-norm and rotary inside it, an output norm and gate) beside
attention "sparse" (every query chooses its key blocks by scores over
compressed keys the cache keeps), the MiniCPM scale constants. The program
against the plain reference `benchmark/references/sala_decoder.py`, which runs
each row unpadded, the lightning layers as their recurrence; every piece of
the reference knocked out fails the same comparison; the chunked pass against
the recurrence, the cache, the choice without a gradient, the counts at the
published widths, and every refusal by name.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.counts import sala as counts
from benchmark.references import sala_decoder as reference
from trlx_tpu.models import lightning, sparse
from trlx_tpu.models.heads import LMWithValueHead, extract_branch_params
from trlx_tpu.models.lm import (KDA_SCAN_OUT, SPAN_PASS_OUT, SPARSE_CHOSEN, LMConfig, TransformerLM, cache_bytes,
                                cache_bytes_per_token, cache_partition_spec, compressed_key_bytes, flash_eligible,
                                init_cache, init_paged_cache, state_bytes)

HERE = os.path.dirname(os.path.abspath(__file__))
SALA = json.load(open(os.path.join(os.path.dirname(HERE), "benchmark", "configs", "minicpm-sala-9b-l4.json")))

# 4 query heads over 2 K/V heads of 8 in the sparse layer (stride 2, kernel 4, block 8, top-2, a window of 16: a row
# past 1 + 2 + 2 = 5 blocks = 40 tokens chooses), 4 lightning heads of 8; L L M: both kinds, the sparse layer last.
ARCH = dict(
    vocab_size=96, n_layer=3, n_head=4, n_kv_head=2, head_width=8, d_model=32, d_ff=64, max_position=256, eos_token_id=0,
    pos_type="rotary", rotary_layers="lightning", rope_theta=10000, extra={"neox_rotary": True}, norm="rmsnorm", mlp="gated",
    attention="sparse", sparse_kernel=4, sparse_stride=2, sparse_block=8, sparse_topk=2, sparse_window=16, sparse_init_blocks=1,
    attn_output_gate=True, mixer_layers=["lightning", "lightning", "attention"], lightning_heads=4, lightning_head_dim=8,
    lightning_output_gate=True, qk_norm=True, activation="silu", ln_eps=1e-6, parallel_residual=False,
    tie_word_embeddings=False, fused_qkv=False, qkv_bias=False, out_bias=False, embedding_multiplier=12.0,
    residual_multiplier=0.2474873734152916, logits_scaling=2.0, embed_init_std=1 / 12, draw_dtype="float32",
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T, PAD = 2, 96, 13  # 12 blocks a row: the choice bites; 13 pads: neither the stride nor the block divides them


def _model(arch=ARCH, seed=0, pad=PAD, length=T, **over):
    cfg = LMConfig.from_dict({**arch, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, length), 2, cfg.vocab_size)
    mask = jnp.ones((B, length), jnp.int32).at[1, :pad].set(0)  # row 1 is left-padded
    params = model.init(jax.random.PRNGKey(seed), ids[:, :8], mask[:, :8])["params"]
    return cfg, model, params, ids * mask, mask


def _distance(got, want):
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want**2)))


# ---- the program against the reference -------------------------------------------------------------


@pytest.mark.parametrize("pad", [0, 13, 40], ids=["no padding", "padded by 13", "padded by 40"])
def test_logits_match_the_reference_padded_and_unpadded_rows(pad):
    cfg, model, params, ids, mask = _model(pad=pad)
    out = model.apply({"params": params}, ids, mask)
    want = reference.forward(params, ARCH, ids, mask, T - pad)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(out["logits"][:, pad:], want, atol=5e-5, rtol=1e-4)  # row 0 whole, row 1 from its first token
    kept, causal, blocks, queries = out["sparse_sums"]
    assert 0.5 < float(kept / causal) < 0.9 and 1.0 < float(blocks / queries) <= 6.0  # the choice bit, from the choice itself


def _ppo_shaped_loss(logits, ids, mask, old, advantages):
    """The clipped surrogate over every position of each row."""
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), ids[:, 1:, None], axis=-1)[..., 0]
    ratio = jnp.exp(logp - old)
    surrogate = jnp.maximum(-advantages * ratio, -advantages * jnp.clip(ratio, 0.8, 1.2))
    return jnp.sum(surrogate * mask[:, 1:])


@pytest.mark.parametrize("remat", [False, True], ids=["", "under remat"])
def test_gradients_of_a_ppo_shaped_loss_match_the_reference(remat):
    """Every parameter's gradient: both layers' projections, norms and gates;
    the decay is a buffer and the choice has none. Remat on and off give the
    same gradients: both match the reference's."""
    cfg, model, params, ids, mask = _model(remat=remat, pad=0, length=64)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    old = -4.0 + 0.3 * jax.random.normal(keys[0], (B, 63))
    advantages = jax.random.normal(keys[1], (B, 63))
    got = jax.grad(lambda p: _ppo_shaped_loss(model.apply({"params": p}, ids, mask)["logits"], ids, mask, old, advantages))(params)
    want = jax.grad(lambda p: _ppo_shaped_loss(reference.forward(p, ARCH, ids, mask, 64), ids, mask, old, advantages))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-6, rtol=3e-3, err_msg=name)


PIECES = ("lightning_qk_norm", "lightning_rotary", "lightning_decay", "lightning_out_norm", "lightning_gate",
          "sparse_compress", "sparse_softmax", "sparse_pool", "sparse_topk", "sparse_choice", "sparse_gate")


@pytest.mark.parametrize("piece", PIECES)
def test_each_piece_knocked_out_of_the_reference_fails_the_comparison(piece):
    """No piece is decorative: the reference without it is far from the
    program, by the same measure check (a) takes (relative RMS distance),
    where the whole reference is within rounding. The norms' weights are drawn
    off 1 so that a missing norm is not a norm at its initial value."""
    cfg, model, params, ids, mask = _model()
    shaken = lambda path, leaf: leaf * (1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(len(str(path))), leaf.shape)) \
        if "norm" in jax.tree_util.keystr(path) else leaf
    params = jax.tree_util.tree_map_with_path(shaken, params)
    got = model.apply({"params": params}, ids, mask)["logits"][:, PAD:]
    assert _distance(got, reference.forward(params, ARCH, ids, mask, T - PAD)) < 1e-5
    assert _distance(got, reference.forward(params, ARCH, ids, mask, T - PAD, drop=(piece,))) > 1e-3


# ---- the lightning mixer's two forms ---------------------------------------------------------------


@pytest.mark.parametrize("length, chunk", [(64, 16), (50, 16), (37, 8), (128, 128), (130, 128), (7, 16)])
def test_the_chunked_pass_is_the_recurrence(length, chunk):
    """The chunked form against the step folded over the tokens, at chunk
    sizes and lengths the chunk does not divide (the padding goes to the
    FRONT: the state after the last position is the recurrence's)."""
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    q, k, v = (jax.random.normal(key, (2, length, 4, 8)) for key in keys)
    rates = jnp.asarray(lightning.decay_rates(4))
    np.testing.assert_allclose(np.exp(-np.asarray(rates)), [np.exp(-2.0 ** (-8 * h / 4)) for h in (1, 2, 3, 4)], rtol=1e-6)
    o, last = lightning.lightning_chunked(q, k, v, rates, chunk, jnp.float32)

    def token(state, inputs):
        out, state = lightning.lightning_step(state, *inputs, rates)
        return state, out

    state, want = jax.lax.scan(token, jnp.zeros((2, 4, 8, 8)), tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    np.testing.assert_allclose(o, jnp.moveaxis(want, 0, 1), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(last, state, atol=2e-5, rtol=1e-4)


def test_a_large_batch_goes_through_the_chunked_pass_in_row_groups(monkeypatch):
    cfg, model, params, ids, mask = _model()
    want = model.apply({"params": params}, ids, mask)["logits"]
    monkeypatch.setattr(lightning, "SCAN_TOKENS", T)  # one row a group
    np.testing.assert_allclose(model.apply({"params": params}, ids, mask)["logits"], want, atol=1e-5)


# ---- the cache --------------------------------------------------------------------------------------


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


@pytest.mark.parametrize("prompt, pad, remat", [(64, 0, False), (64, 13, False), (64, 63, False), (64, 13, True), (24, 5, False)],
                         ids=["no padding", "padded by 13", "a prompt of one token behind 63 pads", "padded by 13 under remat",
                              "a prompt inside the dense length"])
def test_prefill_then_decode_matches_the_reference_s_full_pass(prompt, pad, remat):
    """The prefill hands the decode loop each lightning state as of the row's
    last position and the compressed keys its prompt completes, in the row's
    own grid; the steps update the state, complete a compressed key every
    `stride` tokens and gather the chosen blocks: every step's logits are the
    reference's, which ran each row unpadded in one pass with no cache."""
    cfg, model, params, _, _ = _model(remat=remat)
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :pad].set(0)
    decoded, cache = _decode(cfg, model, params, ids * mask, mask, prompt)
    want = reference.forward(params, ARCH, ids * mask, mask, T - prompt + 1)
    np.testing.assert_allclose(decoded, want, atol=5e-5, rtol=1e-4)
    assert [leaf.shape for leaf in cache[0]] == [(B, 4, 8, 8)] and cache[0][0].dtype == jnp.float32
    assert [leaf.shape for leaf in cache[2]] == [(B, T, 2, 8), (B, T, 2, 8), (B, 47, 2, 8)]
    state = reference.layer_state(params, ARCH, (ids * mask)[1, pad:], 0)
    np.testing.assert_allclose(cache[0][0][1], state, atol=2e-5, rtol=1e-4)
    # every compressed key the steps completed is the mean of its four keys, in the row's own grid
    keys, _, compressed = cache[2]
    want = sparse.compress_keys(sparse.align_rows(keys, jnp.asarray([0, pad])), cfg)
    for row, real in enumerate((T, T - pad)):
        held = sparse.compressed_slots(cfg, real)
        np.testing.assert_allclose(compressed[row, :held], want[row, :held], atol=1e-6)


def test_the_cache_by_its_own_shapes():
    cfg = LMConfig.from_dict({**ARCH, "dtype": "bfloat16"})
    slot, state, compressed = 2 * 2 * 8 * 2, 4 * 8 * 8 * 4, 2 * 8 * 2
    assert cache_bytes_per_token(cfg) == slot and state_bytes(cfg, 5) == 5 * 2 * state
    assert compressed_key_bytes(cfg, 5, 40) == 5 * 19 * compressed  # (40 - 4) // 2 + 1 compressed keys a row
    assert cache_bytes(cfg, 5, 40) == 5 * (40 * slot + 2 * state + 19 * compressed)
    assert lightning.cache_shapes(cfg, 5) == (((5, 4, 8, 8), jnp.dtype(jnp.float32)),)
    specs = {layer: tuple(cache_partition_spec(cfg, 4, layer)) for layer in (0, 2)}
    assert specs[0][1] is not None and specs[0][2:] == (None, None)  # the state's heads over tp
    assert specs[2][1] is None and specs[2][2] is not None  # keys, values and compressed keys: the K/V heads over tp


def test_the_choice_by_the_rule_on_the_host():
    """The yardstick's count of a query's blocks (`counts/sala.py chosen_blocks`, from the rule) against the
    traced choice's own."""
    cfg = LMConfig.from_dict({**ARCH, **F32})
    q = jax.random.normal(jax.random.PRNGKey(0), (1, T, 4, 8))
    kc = jax.random.normal(jax.random.PRNGKey(1), (1, 47, 2, 8))
    t = jnp.arange(T, dtype=jnp.int32)[None]
    chosen = sparse.choose_blocks(q, kc, t, cfg, T // 8)  # [1, 2, T, 12]
    assert np.asarray(chosen.sum(-1))[0, 0].tolist() == [counts.chosen_blocks(ARCH, t) for t in range(T)]
    assert [counts.chosen_blocks(ARCH, t) for t in (0, 7, 8, 39, 40, 95)] == [1, 1, 2, 5, 6, 5]
    assert [counts.chosen_pairs(ARCH, t) for t in (0, 39, 40, 95)] == [1, 40, 41, 40]
    assert sparse.dense_blocks(cfg) == 5 and sparse.gathered_blocks(cfg, 12) == 6 and sparse.gathered_blocks(cfg, 4) == 4


@pytest.mark.parametrize("pad", [0, 13], ids=["no padding", "padded by 13"])
def test_a_decode_step_counts_the_slots_it_read_and_chooses_as_the_many_token_pass_does(pad):
    """`sparse_read` is the step's own count (the mask its softmax applied, over the slots its rows have filled):
    the rule's `chosen_pairs / (t + 1)` a row and K/V head, under 1 once the choice bites; a step that read every
    slot would count 1. The blocks a step chose (sown) are the train pass's for the same query."""
    cfg, model, params, ids, mask = _model(pad=pad)
    layer = sparse.SparseAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.d_model))
    p = layer.init(jax.random.PRNGKey(8), x[:, :8], token_mask=mask[:, :8])["params"]
    prompt = 64
    occupancy = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    cache = tuple(jnp.zeros(shape, dtype) for shape, dtype in sparse.cache_shapes(cfg, B, T))
    _, cache, stats = layer.apply({"params": p}, x[:, :prompt], cache, 0, mask[:, :prompt])
    assert stats is None  # a prefill counts nothing
    whole, _, sums = layer.apply({"params": p}, x, token_mask=mask)
    assert len(sums) == 4
    q, k = (x @ p[name]["kernel"] for name in ("q_proj", "k_proj"))
    q, k = (z / jnp.sqrt(jnp.mean(z * z, -1, keepdims=True) + cfg.ln_eps) * p[norm]["scale"] for z, norm in (
        (q.reshape(B, T, 4, 8), "q_norm"), (k.reshape(B, T, 2, 8), "k_norm")))
    first = np.array([0, pad])
    kc = sparse.compress_keys(sparse.align_rows(k, jnp.asarray(first, jnp.int32)), cfg)
    for i in range(prompt, T):
        occupancy = occupancy.at[:, i].set(1)
        (out, cache, (share, count)), sown = layer.apply({"params": p}, x[:, i:i + 1], cache, jnp.int32(i), occupancy,
                                                         mutable=["intermediates"])
        at = i - first
        want = sum(cfg.kv_heads * counts.chosen_pairs(ARCH, int(t)) / (t + 1.0) for t in at)
        np.testing.assert_allclose(float(share), want, rtol=1e-6)
        assert float(count) == B * cfg.kv_heads and float(share) < float(count)
        np.testing.assert_allclose(out[:, 0], whole[:, i], atol=2e-5, rtol=1e-4)
        train_choice = sparse.choose_blocks(q[:, i:i + 1], kc, jnp.asarray(at, jnp.int32)[:, None], cfg, T // 8)[:, :, 0]
        np.testing.assert_array_equal(sown["intermediates"]["chosen"][0], train_choice)


# ---- the sparse layer where it is dense, and its gradient ------------------------------------------


@pytest.mark.parametrize("length, impl", [(40, "xla"), (24, "flash")])
def test_within_the_dense_length_the_sparse_layer_is_dense_grouped_attention(length, impl):
    """At most 1 + window / block + topk = 5 blocks: every query chooses every
    block, so the layer equals the reference with the choice knocked out and
    keeps every causal pair, by the one masked pass it has at every length (no
    flash kernel takes it, whatever `attn_impl` asks)."""
    cfg, model, params, ids, mask = _model(length=length, pad=5, attn_impl=impl)
    assert not flash_eligible(cfg, length, has_cache=False)
    out = model.apply({"params": params}, ids, mask)
    kept, causal, _, _ = out["sparse_sums"]
    assert float(kept) == float(causal) > 0
    for drop in ((), ("sparse_choice",)):
        np.testing.assert_allclose(out["logits"][:, 5:], reference.forward(params, ARCH, ids, mask, length - 5, drop=drop),
                                   atol=5e-5, rtol=1e-4)


def test_the_choice_carries_no_gradient():
    """The compressed keys and the scores over them reach the output only
    through the chosen set: the gradient of the many-token pass with respect
    to q and k is that of the same pass with the set held fixed."""
    cfg = LMConfig.from_dict({**ARCH, **F32})
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = jax.random.normal(keys[0], (1, T, 4, 8)), jax.random.normal(keys[1], (1, T, 2, 8)), jax.random.normal(keys[2], (1, T, 2, 8))
    mask = jnp.ones((1, T), jnp.int32)
    loss = lambda q, k: jnp.sum(jnp.sin(sparse.sparse_attention(q, k, v, mask, cfg, jnp.float32)[0]))
    got = jax.grad(loss, argnums=(0, 1))(q, k)
    kc = sparse.compress_keys(k, cfg)
    chosen = sparse.choose_blocks(q, kc, jnp.arange(T, dtype=jnp.int32)[None], cfg, T // 8)  # held fixed below
    keep = jnp.repeat(chosen, 8, axis=-1) & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None]

    def fixed(q, k):
        scores = jnp.einsum("bqghd,bkgd->bghqk", q.reshape(1, T, 2, 2, 8), k) * 8 ** -0.5
        probs = jax.nn.softmax(jnp.where(keep[:, :, None], scores, -1e9), axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bghqk,bkgd->bqghd", probs, v).reshape(1, T, 4, 8)))

    want = jax.grad(fixed, argnums=(0, 1))(q, k)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-4)


# ---- the many-token pass in spans: each against the keys up to its own end, the choice made once ----


@pytest.mark.parametrize("pad", [13, 63], ids=["padded by 13", "padded by 63"])
@pytest.mark.parametrize("spans", [2, 3, sparse.SPANS], ids=lambda n: f"{n} spans")
def test_the_spanned_pass_is_the_one_span_pass(monkeypatch, spans, pad):
    """`sparse_attention` over [2, 100] in chunks of one block (13 of them, Tp = 104 > T: a last span shorter than
    the others at every count; the first spans hold at most topk blocks and choose every started one), row 1
    left-padded by a count that neither the stride nor the block divides: the output and its gradients in q, k and
    v as with ONE span (every chunk against all keys), compressed keys and the four sums exactly."""
    cfg, length = LMConfig.from_dict({**ARCH, **F32}), 100
    monkeypatch.setattr(sparse, "SCORE_BYTES", 1)
    assert sparse.query_chunk(cfg, B, length, 4) == 8
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v = (jax.random.normal(key, (B, length, heads, 8)) for key, heads in zip(keys, (4, 2, 2)))
    weight = jax.random.normal(keys[3], (B, length, 4, 8))
    mask = jnp.ones((B, length), jnp.int32).at[1, :pad].set(0)

    def run(n):
        monkeypatch.setattr(sparse, "SPANS", n)
        edges = sparse.span_edges(13)
        loss = lambda q, k, v: jnp.sum(sparse.sparse_attention(q, k, v, mask, cfg, jnp.float32)[0] * weight)
        return edges, sparse.sparse_attention(q, k, v, mask, cfg, jnp.float32), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    edges, (out, kc, sums), grads = run(spans)
    one, (want, want_kc, want_sums), want_grads = run(1)
    assert one == ((0, 13),) and len(edges) == -(-13 // -(-13 // spans)) > 1
    assert edges[0][0] == 0 and edges[-1][1] == 13 and all(a[1] == b[0] for a, b in zip(edges, edges[1:]))
    assert edges[-1][1] - edges[-1][0] < edges[0][1] - edges[0][0]  # the last span is the short one
    np.testing.assert_allclose(out, want, atol=1e-6, rtol=0)
    for g, w in zip(grads, want_grads):
        assert float(jnp.abs(w).max()) > 0.1
        np.testing.assert_allclose(g, w, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(kc, want_kc)
    assert len(sums) == 4 and [float(x) for x in sums] == [float(x) for x in want_sums]
    assert 0 < float(sums[0]) < float(sums[1])  # the choice bit


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _dots(jaxpr):
    """(widest operand axis, multiply-adds) of every `dot_general` under `jaxpr`."""
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "dot_general":
            (contract, _), (lhs, rhs) = eqn.params["dimension_numbers"][0], (v.aval.shape for v in eqn.invars)
            yield max(lhs + rhs), int(np.prod(eqn.outvars[0].aval.shape)) * int(np.prod([lhs[i] for i in contract]))


def test_the_train_pass_stops_at_each_spans_causal_extent_and_chooses_once():
    """The cell's train shape ([1, 12288], 32 heads over 2 of 128; abstract, nothing runs). Forward: a span's two
    loops (the choice, the attention) hold no product with an axis past the span's keys, the attention's products
    are `computed_pairs` and at most 0.65 of the one-span pass's. Differentiated under the block's remat policy:
    one `top_k` a span that chooses (three before: forward, the block's recomputation, the chunk's backward)."""
    arch = {**SALA["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16", "n_layer": 2, "mixer_layers": ["lightning", "attention"]}
    cfg = LMConfig.from_dict({**arch, "remat": True})
    b, length, H, G, D = 1, 12288, cfg.n_head, cfg.kv_heads, cfg.head_dim
    chunk = sparse.query_chunk(cfg, b, length, H)
    edges = sparse.span_edges(length // chunk)
    assert (chunk, len(edges), H, G, D) == (256, sparse.SPANS, 32, 2, 128)
    a = lambda heads: jax.ShapeDtypeStruct((b, length, heads, D), jnp.bfloat16)
    forward = jax.make_jaxpr(lambda q, k, v, mask: sparse.sparse_attention(q, k, v, mask, cfg, jnp.bfloat16))(
        a(H), a(G), a(G), jax.ShapeDtypeStruct((b, length), jnp.int32)).jaxpr
    loops = [list(_dots(eqn.params["jaxpr"].jaxpr)) for eqn in forward.eqns if eqn.primitive.name == "scan"]
    assert len(loops) == 2 * len(edges)
    pairs = 0
    for (lo, hi), select, attend in zip(edges, loops[::2], loops[1::2]):
        extent = hi * chunk
        chooses = extent // cfg.sparse_block > cfg.sparse_topk
        assert [width for width, _ in select] == [sparse.compressed_slots(cfg, extent)] * chooses
        assert [width for width, _ in attend] == [extent, extent]  # the scores, the values: no key past the span's end
        pairs += (hi - lo) * sum(work for _, work in attend)
    computed = sparse.computed_pairs(cfg, b, length, H)
    assert pairs == 2 * computed * (H // G) * D  # two products a pair, each over a group's heads and D
    assert computed == 9 * b * G * length**2 // 16 <= 0.65 * b * G * length**2
    names = [eqn.primitive.name for eqn in _eqns(forward)]
    assert names.count("top_k") == sum(hi * chunk // cfg.sparse_block > cfg.sparse_topk for _, hi in edges) == 6
    assert "pallas_call" not in names

    model = TransformerLM(cfg)
    ids = jnp.zeros((b, 4), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"])
    loss = lambda p, ids, mask: model.apply({"params": p}, ids, mask, compute_logits=False)["hidden"].astype(jnp.float32).sum()
    tokens = jax.ShapeDtypeStruct((b, length), jnp.int32)
    names = [eqn.primitive.name for eqn in _eqns(jax.make_jaxpr(jax.grad(loss))(params, tokens, tokens).jaxpr)]
    assert names.count("top_k") == 6 and "pallas_call" not in names


def test_a_remat_d_block_keeps_the_pass_s_output_by_name(monkeypatch):
    """A trained remat'd block holds the pass's joined output (`SPAN_PASS_OUT`), so its recomputation does not run the
    spans' forward loops again: a span's `attend` runs forward twice a step (the forward, its chunks' own backward)
    and not three times (the softmax's `exp` counts its runs), and the loss and every gradient are, bit for bit, those
    of a block that keeps the choice alone."""
    cfg, model, params, ids, mask = _model(remat=True, length=128)
    monkeypatch.setattr(sparse, "SCORE_BYTES", 1)  # chunks of one block: 16 a row
    monkeypatch.setattr(sparse, "SPANS", 2)
    assert len(sparse.span_edges(128 // sparse.query_chunk(cfg, B, 128, cfg.n_head))) == 2

    def step(kept):
        monkeypatch.setattr(sparse, "SPAN_PASS_OUT", SPAN_PASS_OUT if kept else "kept by no policy")
        loss = lambda p: jnp.sum(jnp.sin(model.apply({"params": p}, ids, mask)["logits"][:, PAD:]))
        traced = jax.jit(jax.value_and_grad(loss)).trace(params)
        softmaxes = sum(eqn.primitive.name == "exp" and "sparse_attn" in str(eqn.source_info.name_stack) for eqn in _eqns(traced.jaxpr.jaxpr))
        return softmaxes, traced.lower().compile()(params)

    (passes, (loss, grads)), (passes_before, (loss_before, grads_before)) = step(True), step(False)
    assert (passes, passes_before) == (2 * 2, 3 * 2)  # two spans of one sparse layer
    assert float(loss) == float(loss_before)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_before)):
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))
        assert "sparse" not in jax.tree_util.keystr(path) or float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("config, named", [
    ("gptj-6b-l8", []), ("kimi-linear-48b-ep32-l13", [KDA_SCAN_OUT]),
    ("minicpm-sala-9b-l4", [SPARSE_CHOSEN, SPAN_PASS_OUT]), ("glm-5-ep32-tp4-l5", [SPARSE_CHOSEN, SPAN_PASS_OUT])],
    ids=lambda value: value if isinstance(value, str) else "")
def test_what_a_remat_d_block_keeps_by_name(monkeypatch, config, named):
    """The names a cell's remat policy keeps, at its published widths and as served (abstract; the trace stops where
    the trunk wraps its blocks): none in a GPT cell, the delta-rule pass's output in Kimi-Linear's as before, and the
    choice with the pass's joined output where a layer's queries choose their keys."""
    import flax.linen as nn

    spec = json.load(open(os.path.join(os.path.dirname(HERE), "benchmark", "configs", f"{config}.json")))
    cfg = LMConfig.from_dict({**spec["model_arch"], **{k: spec["serving"][k] for k in ("dtype", "param_dtype", "remat")}})
    assert cfg.remat

    class Wrapped(Exception):
        pass

    def remat(block, policy=None, **_):
        raise Wrapped(policy)

    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: list(names))
    monkeypatch.setattr(nn, "remat", remat)
    tokens = jnp.zeros((1, 4096), jnp.int32)  # past GLM-5's index_topk: its queries choose
    with pytest.raises(Wrapped) as wrapped:
        jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens)))
    assert wrapped.value.args == (named or None,)


# ---- the frozen branch, the counts, the configuration ----------------------------------------------


def test_the_frozen_branch_replays_blocks_of_both_kinds():
    cfg = LMConfig.from_dict({**ARCH, **F32})
    model = LMWithValueHead(cfg, branch_layer=1)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :PAD].set(0)
    params = model.init(jax.random.PRNGKey(0), ids[:, :8], mask[:, :8])["params"]
    full = model.apply({"params": params}, ids, mask, collect_branch_hidden=True)
    branch = extract_branch_params(params, cfg, 1)
    assert sorted(branch["transformer"]) == ["h_1", "h_2", "lm_head", "ln_f"]
    replay = model.apply({"params": branch}, full["branch_hidden"], mask, method="forward_branch")
    np.testing.assert_allclose(replay[:, PAD:], full["logits"][:, PAD:], atol=1e-5)


def _leaf_counts(tree):
    return {k: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(v)) for k, v in tree.items()}


def test_parameter_counts_at_the_published_widths():
    """By `jax.eval_shape`, nothing allocated: the numbers of ISSUE 51. Whole
    (32 layers, 73,448 rows): a lightning mixer 83,890,432, a sparse one
    52,429,056, the feed-forward 201,326,592: 9,477,206,016, the published
    "9B"; as the cell runs (L L L M, 9,181 rows, the value head in):
    1,218,225,153."""
    from trlx_tpu.models.hf_import import lm_config_from_hf

    whole = lm_config_from_hf(types.SimpleNamespace(**SALA["published"]))
    assert whole.mixer_layers.count("lightning") == 24 and whole.mixer_layers.count("attention") == 8
    ids = jnp.zeros((1, 2), jnp.int32)
    two = whole.replace(n_layer=2, mixer_layers=("lightning", "attention"))
    shapes = jax.eval_shape(TransformerLM(two).init, jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    lightning_layer, sparse_layer = _leaf_counts(shapes["h_0"]), _leaf_counts(shapes["h_1"])
    assert lightning_layer["lightning"] == 83_890_432 and sparse_layer["attn"] == 52_429_056
    assert lightning_layer["mlp"] == sparse_layer["mlp"] == 201_326_592
    assert sum(lightning_layer.values()) == 285_225_216 and sum(sparse_layer.values()) == 253_763_840
    table = _leaf_counts(shapes)
    assert table["wte"] == table["lm_head"] == 300_843_008 and table["ln_f"] == 4096
    assert 24 * 285_225_216 + 8 * 253_763_840 == 8_875_515_904
    assert 8_875_515_904 + 2 * 300_843_008 + 4096 == 9_477_206_016

    cell = LMConfig.from_dict(SALA["model_arch"])
    shapes = jax.eval_shape(LMWithValueHead(cell).init, jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    assert sum(_leaf_counts(shapes).values()) == 1_218_225_153 and _leaf_counts(shapes)["v_head"] == 33_570_817


def test_the_configuration_keeps_every_published_width():
    arch, published = SALA["model_arch"], SALA["published"]
    cfg = LMConfig.from_dict(arch)
    assert (cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.ff_dim) == (4096, 32, 2, 128, 16384)
    assert (cfg.lightning_heads, cfg.lightning_head_dim, cfg.rope_theta, cfg.rotary_layers) == (32, 128, 1e4, "lightning")
    assert (cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block, cfg.sparse_topk, cfg.sparse_window,
            cfg.sparse_init_blocks) == (32, 16, 64, 64, 2048, 1)
    assert (cfg.embedding_multiplier, cfg.logits_scaling, cfg.ln_eps) == (12.0, 16.0, 1e-6) and not cfg.tie_word_embeddings
    assert cfg.residual_multiplier == pytest.approx(1.4 / 32 ** 0.5) and cfg.qk_norm
    assert cfg.attn_output_gate and cfg.lightning_output_gate
    assert cfg.mixer_layers == ("lightning", "lightning", "lightning", "attention")
    assert [("lightning" if kind == "lightning-attn" else "attention") for kind in published["mixer_types"][13:17]] == list(cfg.mixer_layers)
    assert sorted(SALA["reduced"]) == ["num_hidden_layers", "num_layers_unfrozen", "vocab_size"]
    for key, value in published.items():  # every catalogued key stands in the file as run, but the two that were cut
        if key not in ("num_hidden_layers", "vocab_size"):
            assert SALA[key] == value, key
    assert (SALA["num_hidden_layers"], SALA["vocab_size"], published["vocab_size"] // 8) == (4, 9181, 9181)
    assert all("dense_len" in SALA["assumed"] and SALA["assumed"][k] for k in SALA["assumed"])
    rehearsal = LMConfig.from_dict(SALA["rehearsal_arch"])
    same = ("attention", "mixer_layers", "rotary_layers", "qk_norm", "attn_output_gate", "lightning_output_gate",
            "tie_word_embeddings", "activation", "norm", "mlp", "pos_type", "embedding_multiplier", "residual_multiplier")
    assert all(getattr(rehearsal, k) == getattr(cfg, k) for k in same)
    assert (rehearsal.sparse_kernel, rehearsal.sparse_stride, rehearsal.sparse_block, rehearsal.sparse_topk,
            rehearsal.sparse_window) == (4, 2, 8, 2, 16)


CUT = ("n_layer", "mixer_layers", "vocab_size", "max_position", "embed_init_std", "draw_dtype")  # the cell's own


def test_lm_config_from_the_published_keys():
    from trlx_tpu.models.hf_import import lm_config_from_hf

    cfg = lm_config_from_hf(types.SimpleNamespace(**SALA["published"]))
    assert (cfg.n_layer, cfg.d_model, cfg.vocab_size, cfg.max_position) == (32, 4096, 73448, 524288)
    from_arch = LMConfig.from_dict({"n_layer": 32, "mixer_layers": cfg.mixer_layers, "vocab_size": 73448, "max_position": 524288}
                                   | {k: v for k, v in SALA["model_arch"].items() if k not in CUT})
    for key in LMConfig.__dataclass_fields__:
        if key not in ("embed_init_std", "draw_dtype"):
            assert getattr(from_arch, key) == pytest.approx(getattr(cfg, key)) if isinstance(getattr(cfg, key), float) \
                else getattr(from_arch, key) == getattr(cfg, key), key


@pytest.mark.parametrize("change, message", [
    ({"mixer_types": ["minicpm4"] * 31 + ["mamba"]}, "a mixer type other than"),
    ({"mixer_types": ["minicpm4"] * 31}, "mixer_types of another length"),
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"lightning_use_rope": False}, "lightning_use_rope false"),
    ({"lightning_nkv": 8}, "grouped lightning keys"),
    ({"hidden_act": "relu"}, "hidden_act 'relu'"),
    ({"attention_bias": True}, "attention_bias"),
])
def test_lm_config_from_hf_refuses_by_name(change, message):
    from trlx_tpu.models.hf_import import lm_config_from_hf

    with pytest.raises(ValueError, match=f"minicpm_sala: not built: .*{message}"):
        lm_config_from_hf(types.SimpleNamespace(**{**SALA["published"], **change}))


# ---- what is refused --------------------------------------------------------------------------------


@pytest.mark.parametrize("bad, message", [
    ({"attention": "sparse2"}, "unknown attention"),
    ({"rotary_layers": "sparse"}, "unknown rotary_layers"),
    ({"mixer_layers": ["lightning", "lightning", "lightning2"]}, "mixer_layers must name"),
    ({"sparse_kernel": 3}, "attention 'sparse' needs sparse_kernel"),
    ({"sparse_topk": 0}, "attention 'sparse' needs sparse_kernel"),
    ({"sparse_window": 20}, "attention 'sparse' needs sparse_kernel"),
    ({"sparse_kernel": 16, "sparse_stride": 2}, "holding a kernel"),
    ({"attention": "mha"}, "describe attention 'sparse'"),
    ({"fused_qkv": True}, "grouped keys|attention 'sparse' is not built with fused_qkv"),
    ({"qkv_bias": True}, "attention 'sparse' is not built with qkv_bias or out_bias"),
    ({"kv_cache_quant": True}, "not built with kv_cache_quant"),
    ({"n_soft_tokens": 4}, "soft prompts"),
    ({"sp_size": 2}, "sp ring"),
    ({"attention_layers": ["global", "local", "local"], "window_size": 8}, "not built with windowed"),
    ({"n_loops": 2}, "looped stack"),
    ({"rotary_layers": "all"}, "rotary_layers 'all'|rotary in the layer"),
    ({"rotary_dim": 4}, "rotary_layers 'lightning' needs"),
    ({"mixer_layers": ["attention"] * 3}, "rotary_layers 'lightning' needs|describe 'lightning' layers"),
    ({"lightning_heads": 0}, "a 'lightning' layer needs lightning_heads"),
    ({"lightning_head_dim": 7}, "even lightning_head_dim|rotary_layers 'lightning' needs"),
    ({"mixer_layers": ["lightning", "mamba", "attention"], "ssm_heads": 2, "ssm_head_dim": 8, "ssm_state": 4}, "a 'mamba' layer"),
    ({"ffn_layers": ["dense", "experts", "dense"], "n_experts": 4, "experts_per_token": 1, "expert_d_ff": 8}, "expert layers"),
    ({"parallel_residual": True}, "parallel_residual"),
    ({"attention_multiplier": 0.5}, "attention_multiplier"),
    ({"sparse_dense_len": 8192}, "unknown architecture key"),
])
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **bad})


def test_each_kind_builds_beside_the_older_ones():
    """The kinds are independent: lightning layers beside plain grouped "mha"
    (which then rotates nothing), and "sparse" attention in every layer with no
    position signal at all."""
    plain = {k: v for k, v in ARCH.items() if not k.startswith(("sparse", "attn_output"))}
    alone = {k: v for k, v in ARCH.items() if not k.startswith(("lightning", "mixer", "rotary_layers", "rope", "extra"))}
    for arch in ({**plain, "attention": "mha"}, {**alone, "pos_type": "none"}):
        cfg, model, params, ids, mask = _model(arch)
        out = model.apply({"params": params}, ids, mask)
        assert bool(jnp.isfinite(out["logits"]).all())
        assert (out["sparse_sums"] is not None) == (cfg.attention == "sparse")


def test_the_paths_that_are_not_built_refuse_by_name():
    from trlx_tpu.models.hf_export import validate_exportable
    from trlx_tpu.models.hf_import import load_hf_trunk

    cfg, model, params, ids, mask = _model()
    cache = init_cache(cfg, B, T)
    with pytest.raises(NotImplementedError, match="lightning layer takes a pass with no cache"):  # a per-row write offset
        model.apply({"params": params}, ids[:, :1], mask[:, :1], cache=cache, cache_index=jnp.zeros((B,), jnp.int32),
                    cache_mask=jnp.ones((B, T), jnp.int32))
    with pytest.raises(NotImplementedError, match="lightning layer takes a pass with no cache"):  # packed segments
        model.apply({"params": params}, ids, mask, segment_ids=jnp.zeros((B, T), jnp.int32))
    alone = LMConfig.from_dict({**{k: v for k, v in ARCH.items() if not k.startswith(("lightning", "mixer", "rotary_layers", "rope", "extra"))},
                                "pos_type": "none", **F32})
    lone = TransformerLM(alone)
    lone_params = lone.init(jax.random.PRNGKey(0), ids[:, :8], mask[:, :8])["params"]
    with pytest.raises(NotImplementedError, match="attention 'sparse' takes a pass with no cache"):  # a verify window
        lone.apply({"params": lone_params}, ids[:, :4], mask[:, :4], cache=init_cache(alone, B, T), cache_index=jnp.int32(3),
                   cache_mask=jnp.ones((B, T), jnp.int32))
    with pytest.raises(NotImplementedError, match="paged pool is not built for attention 'sparse'"):
        init_paged_cache(cfg, 4, 8)
    with pytest.raises(NotImplementedError, match="minicpm_sala"):
        load_hf_trunk("/nowhere", cfg)
    with pytest.raises(ValueError, match="lightning"):
        validate_exportable(cfg, "gptj")
    from trlx_tpu.engine.rollout_engine import RolloutEngine

    with pytest.raises(NotImplementedError, match="rollout engine .* lightning layer"):
        RolloutEngine(types.SimpleNamespace(cfg=cfg), None, n_slots=2, prompt_width=8)
    with pytest.raises(NotImplementedError, match="rollout engine .* attention 'sparse'"):
        RolloutEngine(types.SimpleNamespace(cfg=alone), None, n_slots=2, prompt_width=8)


# ---- the normal path --------------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` -> orchestrator -> ops/generate.py (the state leaf and
    the three-leaf cache through the fused rollout) -> make_experience (scoring:
    the frozen branch replayed over a lightning and a sparse block) -> learn():
    the fresh-step PPO ratio compares the decode path's own log-probs (a state
    updated a token, compressed keys completed on the way, gathered blocks)
    with the train forward's chunked and masked passes; the counters report
    what the choice kept and what the cache holds."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "examples"))
    import trlx_tpu
    from randomwalks import base_config

    config = base_config("ppo", ARCH["vocab_size"], 16)
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 2
    config.train.batch_size, config.train.total_steps, config.train.epochs = 8, 4, 4  # dp 8 over the test devices
    config.train.eval_interval, config.train.log_interval = 100, 1
    config.train.seq_length = 80
    config.train.checkpoint_dir = str(tmp_path)
    config.method.num_rollouts = config.method.chunk_size = 8
    config.method.ppo_epochs = 2
    config.method.gen_kwargs = {"prompt_length": 56, "max_new_tokens": 24, "min_new_tokens": 24, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, ARCH["vocab_size"], size=int(n)).tolist() for n in rng.integers(30, 57, size=8)]
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 96 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    cfg = trainer.model.cfg
    assert trainer.fused_rollout and (cfg.attention, cfg.has_lightning, cfg.kv_heads) == ("sparse", True, 2)
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == [1, 2, 3, 4]
    for first in (1, 3):  # the first step of each iteration: the policy has not moved since it sampled
        assert abs(steps[first]["mean_ratio"] - 1.0) < 1e-3, steps[first]["mean_ratio"]
    for r in steps.values():
        assert 0.5 < r["sparse/kept_pair_share"] < 1.0 and 3.0 < r["sparse/chosen_blocks_mean"] <= 6.0
        # one sparse layer over [8, 80]: two chunks of 64 in two spans, 64 x 64 + 64 x 128 pairs a row and K/V head,
        # over the causal pairs of rows of 54-80 real tokens
        assert 12288 / 3240 <= r["sparse/computed_pair_share"] <= 12288 / 1485
        assert "flash/kept_pair_share" not in r
    phases = [r for r in records if "time/window_wall_s" in r and "rollout/state_bytes" in r]
    assert phases
    for p in phases:
        assert p["rollout/state_bytes"] == 8 * 2 * 4 * 8 * 8 * 4 and p["rollout/state_bytes_per_row"] == 2 * 4 * 8 * 8 * 4
        assert p["rollout/compressed_key_bytes"] == 8 * 39 * 2 * 8 * cfg.compute_dtype.itemsize  # (80 - 4) // 2 + 1 a row
        assert 0.55 < p["rollout/sparse_keys_read_share"] < 1.0 and p["rollout/kv_read_share"] == 6 * 8 / 80
        assert 0.0 < p["lightning/state_rw_share"] < 1.0


def test_a_cache_of_no_more_blocks_than_topk_reads_every_block():
    """A cache of two blocks under top-2: the choice is every started block, one set a K/V group, and the
    decode steps through it are the reference's."""
    cfg, model, params, _, _ = _model()
    ids = jax.random.randint(jax.random.PRNGKey(4), (B, 14), 2, cfg.vocab_size)
    mask = jnp.ones((B, 14), jnp.int32).at[1, :4].set(0)
    decoded, _ = _decode(cfg, model, params, ids * mask, mask, 8)
    np.testing.assert_allclose(decoded, reference.forward(params, ARCH, ids * mask, mask, 7), atol=5e-5, rtol=1e-4)

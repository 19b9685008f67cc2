"""The GLM-5 kinds (PR 53): a learned indexer on every latent-attention layer
(`LMConfig.index_*`, models/indexer.py: DeepSeek Sparse Attention), which
chooses the index_topk keys a query attends to over the latent cache. The
program against the plain reference
`benchmark/references/dsa_mla_moe_decoder.py`, which runs each row unpadded and
whole; every step of the indexer knocked out of the reference fails the same
comparison; prefill and decode through the three-leaf cache, the choice
without a gradient and out of the optimizer, the shares of the 32-chip
deployment, the counts at the published widths, and every refusal by name.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import dsa_mla_moe_decoder as reference
from trlx_tpu.models import indexer, moe, sparse
from trlx_tpu.models.heads import LMWithValueHead, extract_branch_params, trainable_mask
from trlx_tpu.models.lm import (SPAN_PASS_OUT, LatentAttention, LMConfig, TransformerLM, cache_bytes,
                                cache_bytes_per_token, cache_partition_spec, decode_step_bytes, flash_eligible,
                                index_key_bytes, init_cache, init_paged_cache, rope_tables)

HERE = os.path.dirname(os.path.abspath(__file__))
GLM = json.load(open(os.path.join(os.path.dirname(HERE), "benchmark", "configs", "glm-5-ep32-tp4-l5.json")))

# 4 heads of 8 + 8 / 8 over latents of 16, 4 index heads of 16 (8 channels rotated), top-16: a query past 16 tokens
# chooses; a dense layer and two expert layers (8 experts, 2 a token, 4 held).
ARCH = dict(
    vocab_size=96, n_layer=3, n_head=4, d_model=32, d_ff=64, max_position=256, eos_token_id=0, pos_type="rotary",
    norm="rmsnorm", mlp="gated", attention="mla", activation="silu", ln_eps=1e-5, parallel_residual=False,
    tie_word_embeddings=False, ffn_layers=["dense", "experts", "experts"], rope_theta=1000000, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4, index_head_dim=16,
    index_topk=16, n_experts=8, experts_per_token=2, expert_d_ff=16, n_shared_experts=1, routed_scaling_factor=2.5,
    experts_held=[0, 4], embed_init_std=1.0, draw_dtype="float32",
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
# the same widths two layers deep (a dense and an expert layer): where a test's cost is its compiles and depth shows it nothing more
TWO = {**ARCH, "n_layer": 2, "ffn_layers": ["dense", "experts"]}
B, T, PAD = 2, 96, 13


def _model(arch=ARCH, seed=0, pad=PAD, length=T, **over):
    cfg = LMConfig.from_dict({**arch, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, length), 2, cfg.vocab_size)
    mask = jnp.ones((B, length), jnp.int32).at[1, :pad].set(0)  # row 1 is left-padded
    params = model.init(jax.random.PRNGKey(seed), ids[:, :8], mask[:, :8])["params"]
    return cfg, model, params, ids * mask, mask


def _distance(got, want):
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want**2)))


def _kept(length, topk=16):
    """(chosen pairs, causal pairs) of one row of `length` real tokens, by the rule."""
    return sum(min(i + 1, topk) for i in range(length)), length * (length + 1) // 2


# ---- the program against the reference -------------------------------------------------------------


@pytest.mark.parametrize("pad", [0, 13], ids=["no padding", "padded by 13"])
def test_logits_match_the_reference_padded_and_unpadded_rows(pad):
    cfg, model, params, ids, mask = _model(pad=pad)
    out = jax.jit(lambda p: model.apply({"params": p}, ids, mask))(params)
    want = jax.jit(lambda p: reference.forward(p, ARCH, ids, mask, T - pad))(params)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(out["logits"][:, pad:], want, atol=5e-5, rtol=1e-4)  # row 0 whole, row 1 from its first token
    kept, causal = out["dsa_sums"]  # from the mask the pass applied, summed over the three layers
    rows = [_kept(T), _kept(T - pad)]
    assert (float(kept), float(causal)) == (3 * sum(r[0] for r in rows), 3 * sum(r[1] for r in rows))


def _ppo_shaped_loss(logits, ids, mask, old, advantages):
    """The clipped surrogate over every position of each row."""
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), ids[:, 1:, None], axis=-1)[..., 0]
    ratio = jnp.exp(logp - old)
    surrogate = jnp.maximum(-advantages * ratio, -advantages * jnp.clip(ratio, 0.8, 1.2))
    return jnp.sum(surrogate * mask[:, 1:])


@pytest.mark.parametrize("remat", [True], ids=["under remat"])
def test_gradients_of_a_ppo_shaped_loss_match_the_reference(remat):
    """Every parameter's gradient, under the block's remat policy as the cell runs it; the indexer's are zero on both sides (the choice is a set) and
    the correction bias is a buffer."""
    cfg, model, params, ids, mask = _model(arch=TWO, remat=remat, pad=0, length=64)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    old = -4.0 + 0.3 * jax.random.normal(keys[0], (B, 63))
    advantages = jax.random.normal(keys[1], (B, 63))
    got = jax.jit(jax.grad(lambda p: _ppo_shaped_loss(model.apply({"params": p}, ids, mask)["logits"], ids, mask, old, advantages)))(params)
    want = jax.jit(jax.grad(lambda p: _ppo_shaped_loss(reference.forward(p, TWO, ids, mask, 64), ids, mask, old, advantages)))(params)
    seen_indexer = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(w).max())
        if "indexer" in name or name.endswith("['e_score_correction_bias']"):
            assert scale == 0 and float(jnp.abs(g).max()) == 0, name
            seen_indexer += "indexer" in name
            continue
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-6, rtol=3e-3, err_msg=name)
    assert seen_indexer == 2 * 5  # two layers: W^I_q, W^I_k, the LayerNorm's two, W^I_w


PIECES = ("index_layernorm", "index_rope", "index_relu", "index_weights", "index_topk", "index_choice")


@pytest.mark.parametrize("piece", PIECES)
def test_each_step_of_the_indexer_knocked_out_of_the_reference_fails_the_comparison(piece):
    """No step is decorative: the reference without it (the LayerNorm, the rope on the index heads and key, the ReLU,
    the head weights, the top-k itself replaced by "newest k", the choice altogether) is far from the program, by the
    measure check (a) takes, where the whole reference is within rounding. The norms are drawn off their initial
    values so that a missing norm is not a norm at weight 1 and bias 0."""
    cfg, model, params, ids, mask = _model()

    def shaken(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" not in name:
            return leaf
        noise = jax.random.normal(jax.random.PRNGKey(len(name)), leaf.shape)
        return leaf + 0.5 * noise if name.endswith("['bias']") else leaf * (1.0 + 0.5 * noise)

    params = jax.tree_util.tree_map_with_path(shaken, params)
    got = model.apply({"params": params}, ids, mask)["logits"][:, PAD:]
    assert _distance(got, reference.forward(params, ARCH, ids, mask, T - PAD)) < 1e-5
    assert _distance(got, reference.forward(params, ARCH, ids, mask, T - PAD, drop=(piece,))) > 1e-3


# ---- the cache of three leaves ---------------------------------------------------------------------


def _decode(cfg, model, params, ids, mask, prompt, collect=False):
    """Prefill `prompt` positions, then teacher-forced decode of the rest through the cache, one scalar traced write
    offset a step: [B, T - prompt + 1, V], the cache, and with `collect` each step's (sparse_read, the slots each
    layer's step chose: `indexer.choose_slots` over what its indexer gave, the cache it left and its occupancy)."""
    total = ids.shape[1]
    cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, total - prompt), jnp.int32)], axis=1)
    out = jax.jit(lambda cache: model.apply({"params": params}, ids[:, :prompt], mask[:, :prompt], cache=cache, cache_index=0,
                                            cache_mask=cache_mask))(init_cache(cfg, B, total))
    step = jax.jit(lambda cache, index, cache_mask, token: model.apply(
        {"params": params}, token, jnp.ones((B, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask,
        **({"mutable": ["intermediates"], "capture_intermediates": lambda m, _: isinstance(m, indexer.Indexer)} if collect else {})))
    cache, rows, seen = out["cache"], [out["logits"][:, -1]], []
    for i in range(prompt, total):
        cache_mask = cache_mask.at[:, i].set(1)
        out = step(cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
        if collect:
            out, sown = out
            chose = []
            for layer, leaves in enumerate(out["cache"]):
                (q_idx, _, w), = sown["intermediates"][f"h_{layer}"]["attn"]["indexer"]["__call__"]
                chose.append(indexer.choose_slots(q_idx, w, leaves[2], cache_mask, cfg.index_topk))
            seen.append((out["sparse_read"], chose))
        cache = out["cache"]
        rows.append(out["logits"][:, 0])
    return jnp.stack(rows, axis=1), cache, seen


@pytest.mark.parametrize("prompt, pad, remat", [(64, 0, False), (64, 13, False), (64, 63, False), (64, 13, True), (12, 5, False)],
                         ids=["no padding", "padded by 13", "a prompt of one token behind 63 pads", "padded by 13 under remat",
                              "a prompt inside index_topk"])
def test_prefill_then_decode_matches_the_reference_s_full_pass(prompt, pad, remat):
    """The prefill writes the latent, the rotated shared key and the rotated index key of every prompt position; the
    steps score the row's index keys, take the top-k and gather the chosen latent entries: every step's logits are
    the reference's, which ran each row unpadded in one pass with no cache. A prompt inside index_topk is prefilled
    by latent attention's own path (every key is chosen there) and still hands the steps its index keys."""
    cfg, model, params, _, _ = _model(remat=remat)
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :pad].set(0)
    decoded, cache, _ = _decode(cfg, model, params, ids * mask, mask, prompt)
    want = reference.forward(params, ARCH, ids * mask, mask, T - prompt + 1)
    np.testing.assert_allclose(decoded, want, atol=5e-5, rtol=1e-4)
    assert [[leaf.shape for leaf in layer] for layer in cache] == [[(B, T, 16), (B, T, 8), (B, T, 16)]] * 3


def test_the_cache_by_its_own_shapes():
    cfg = LMConfig.from_dict({**ARCH, "dtype": "bfloat16"})
    assert cache_bytes_per_token(cfg) == 3 * (16 + 8 + 16) * 2 and index_key_bytes(cfg, 5, 40) == 3 * 5 * 40 * 16 * 2
    assert cache_bytes(cfg, 5, 40) == 5 * 40 * cache_bytes_per_token(cfg)
    assert all(cache_partition_spec(cfg, 3, layer) == cache_partition_spec(cfg, 3) for layer in range(3))
    # a decode step: the chosen latent entries and every slot's index key
    assert decode_step_bytes(cfg, 4, 16, 1000, cache_len=96)[0] == 1000 + 4 * 3 * (16 * (16 + 8) + 96 * 16) * 2
    plain = LMConfig.from_dict({**{k: v for k, v in ARCH.items() if not k.startswith("index_")}, "dtype": "bfloat16"})
    assert cache_bytes_per_token(plain) == 3 * (16 + 8) * 2 and index_key_bytes(plain, 5, 40) == 0
    assert len(init_cache(plain, 1, 4)[0]) == 2 and len(init_cache(cfg, 1, 4)[0]) == 3
    whole = LMConfig.from_dict(GLM["model_arch"])
    assert cache_bytes_per_token(whole) == 7040 == 5 * 704 * 2  # ISSUE 53: 704 numbers a token a layer


@pytest.mark.parametrize("pad", [0, 13], ids=["no padding", "padded by 13"])
def test_a_decode_step_counts_the_slots_it_read_and_chooses_as_the_many_token_pass_does(pad):
    """The steps' own count is the rule's (min(t + 1, 16) of t + 1 filled slots, a row a layer), and the slots each
    step gathered are the keys the reference's whole-row pass chose for that query, in every layer."""
    cfg, model, params, _, _ = _model()
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :pad].set(0)
    _, _, seen = _decode(cfg, model, params, ids * mask, mask, 64, collect=True)
    want = [[reference.chosen_sets(params, ARCH, (ids * mask)[row, first:], layer) for layer in range(3)]
            for row, first in enumerate((0, pad))]
    for step, (read, chose) in enumerate(seen):
        at = 64 + step
        filled = [at + 1, at + 1 - pad]
        assert float(read[1]) == 3 * B
        np.testing.assert_allclose(float(read[0]), 3 * sum(16 / n for n in filled), rtol=1e-6)
        for layer in range(3):
            slots, taken = chose[layer]
            assert bool(taken.all())
            for row, first in enumerate((0, pad)):
                chosen = np.zeros(T - first, bool)
                chosen[np.asarray(slots[row]) - first] = True
                np.testing.assert_array_equal(chosen[: at - first + 1], np.asarray(want[row][layer][at - first, : at - first + 1]))


# ---- the choice ------------------------------------------------------------------------------------


@pytest.mark.parametrize("topk", [1, 5, 16])
def test_the_choice_is_the_top_k_with_earlier_keys_first_among_equals(topk):
    """`choose_keys` (the k-th largest by bisection over the scores' bits) against `lax.top_k`, on scores with many
    exact ties, negative scores and zeros of both signs: the same sets, of equal scores the earlier key."""
    rng = np.random.default_rng(topk)
    scores = np.round(rng.normal(size=(2, 40, 48)) * 2).astype(np.float32) / 2  # a few distinct values: ties everywhere
    scores[0, :, ::7] = -0.0
    seen = (np.arange(48)[None, None, :] <= (np.arange(40) + 3)[None, :, None]) & (rng.random((2, 1, 48)) > 0.1)
    got = np.asarray(indexer.choose_keys(jnp.asarray(scores), jnp.asarray(seen), topk))
    _, best = jax.lax.top_k(jnp.where(seen, jnp.asarray(scores) + 0.0, -jnp.inf), topk)  # + 0.0: -0.0 and 0.0 tie in top_k too
    want = np.zeros_like(seen)
    np.put_along_axis(want, np.asarray(best), True, axis=-1)
    want &= seen
    assert (got & ~seen).sum() == 0 and (got.sum(-1) == np.minimum(seen.sum(-1), topk)).all()
    differ = (got != want).any(-1)
    # the bits order -0.0 under 0.0 and the floats do not: where the two differ, -0.0 met 0.0 at the threshold and
    # both sets hold topk keys of the same scores
    for b, q in zip(*np.nonzero(differ)):
        assert sorted(np.abs(scores[b, q][got[b, q]])) == sorted(np.abs(scores[b, q][want[b, q]]))
    assert differ.mean() < 0.2


def test_at_index_topk_no_shorter_than_the_row_the_layer_is_unindexed_latent_attention():
    """Every key is chosen, bit for bit: `choose_keys` hands back the causal mask where a query has no more keys than
    index_topk (through the bisection too), and a pass no longer than index_topk takes latent attention's own path, so
    its output is the un-indexed layer's on the same weights."""
    scores = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 24))
    seen = jnp.broadcast_to(jnp.tril(jnp.ones((24, 24), bool))[None] & (jnp.arange(24) >= 3)[None, None, :], (2, 24, 24))
    np.testing.assert_array_equal(indexer.choose_keys(scores, seen, 24), seen)  # K <= topk: no search
    np.testing.assert_array_equal(indexer.choose_keys(scores, seen, 21), seen)  # K > topk, 21 seen keys at most: the search
    cfg, model, params, ids, mask = _model(index_topk=T)
    plain_arch = {k: v for k, v in ARCH.items() if not k.startswith("index_")}
    plain = TransformerLM(LMConfig.from_dict({**plain_arch, **F32}))
    strip = lambda tree: {k: strip(v) for k, v in tree.items() if k != "indexer"} if isinstance(tree, dict) else tree
    out = model.apply({"params": params}, ids, mask)
    want = plain.apply({"params": strip(params)}, ids, mask)["logits"]
    np.testing.assert_allclose(out["logits"][0], want[0], atol=1e-6)
    np.testing.assert_allclose(out["logits"][1, PAD:], want[1, PAD:], atol=1e-6)  # row 1 from its first token
    assert out["dsa_sums"] is None and out["sparse_sums"] is None and flash_eligible(cfg.replace(attn_impl="flash"), T, False)
    assert not flash_eligible(cfg.replace(attn_impl="flash", index_topk=16), T, False)  # past index_topk: no causal band
    assert flash_eligible(cfg.replace(attn_impl="flash", index_topk=16), 16, False)
    np.testing.assert_allclose(out["logits"][:, PAD:], reference.forward(params, {**ARCH, "index_topk": T}, ids, mask, T - PAD),
                               atol=5e-5, rtol=1e-4)


def test_the_choice_carries_no_gradient_and_the_indexer_does_not_train():
    cfg = LMConfig.from_dict({**TWO, **F32})
    model = LMWithValueHead(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, 40), 2, cfg.vocab_size)
    mask = jnp.ones((B, 40), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:, :8], mask[:, :8])["params"]
    grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(model.apply({"params": p}, ids, mask)["logits"]))))(params)
    masks = trainable_mask(params, cfg, 1)
    seen = 0
    for (path, g), trains in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(masks)):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            seen += 1
            assert not trains and float(jnp.abs(g).max()) == 0, name
        elif "['h_1']" in name and "correction_bias" not in name:
            assert trains and float(jnp.abs(g).max()) > 0, name
    assert seen == 10
    assert not any(jax.tree_util.tree_leaves(trainable_mask(params, cfg, 0)["transformer"]["h_1"]["attn"]["indexer"]))


@pytest.mark.parametrize("spans", [3], ids=lambda n: f"{n} spans")
def test_the_spanned_pass_is_the_one_span_pass(monkeypatch, spans):
    """The query chunks in spans (models/sparse.py `over_spans`, shared with attention "sparse"), each against the keys
    up to its own end, give what one span over every key gives: logits, gradients and the counts."""
    cfg, model, params, ids, mask = _model(arch={**ARCH, "n_layer": 1, "ffn_layers": ["dense"]}, length=128, pad=63)
    monkeypatch.setattr(indexer, "MIN_CHUNK", 16)
    monkeypatch.setattr(sparse, "SCORE_BYTES", 1)  # chunks of 16: eight a row

    def run(n):
        monkeypatch.setattr(sparse, "SPANS", n)
        loss = lambda p: jnp.sum(jnp.sin(model.apply({"params": p}, ids, mask)["logits"][:, 63:]))
        return jax.jit(lambda p: model.apply({"params": p}, ids, mask))(params), jax.jit(jax.grad(loss))(params)

    (one, one_grads), (many, many_grads) = run(1), run(spans)
    np.testing.assert_allclose(many["logits"][:, 63:], one["logits"][:, 63:], atol=2e-5)
    np.testing.assert_array_equal(many["dsa_sums"], one["dsa_sums"])
    for a, b in zip(jax.tree_util.tree_leaves(many_grads), jax.tree_util.tree_leaves(one_grads)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_the_train_pass_stops_at_each_spans_causal_extent_and_chooses_once():
    """The cell's train shape ([1, 8192], 16 heads of 256 / 256, 32 index heads of 128; abstract, nothing runs).
    Forward: a span's two loops (the choice, the attention) hold no product with an axis past the span's keys.
    Differentiated under the block's remat policy: one search for the k-th largest score a span that chooses (a
    `cumsum` each; three before: forward, the block's recomputation, the chunk's backward), and no Pallas call."""
    arch = {**GLM["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16", "n_layer": 2, "ffn_layers": ["dense", "dense"]}
    cfg = LMConfig.from_dict({**arch, "remat": True})
    b, length, h = 1, 8192, cfg.n_head
    chunk = sparse.query_chunk_from(indexer.MIN_CHUNK, b, length, max(h, cfg.index_n_heads))
    edges = sparse.span_edges(length // chunk)
    assert (chunk, len(edges)) == (512, sparse.SPANS)
    a = lambda *shape: jax.ShapeDtypeStruct((b, length) + shape, jnp.bfloat16)
    forward = jax.make_jaxpr(lambda q, k, v, qi, w, ki, mask: indexer.indexed_attention(q, k, v, qi, w, ki, mask, cfg, 1 / 16, jnp.bfloat16))(
        a(h, 256), a(h, 256), a(h, 256), a(32, 128), jax.ShapeDtypeStruct((b, length, 32), jnp.float32), a(128),
        jax.ShapeDtypeStruct((b, length), jnp.int32)).jaxpr
    loops = [eqn for eqn in forward.eqns if eqn.primitive.name == "scan"]
    assert len(loops) == 2 * len(edges)
    for (lo, hi), select, attend in zip(edges, loops[::2], loops[1::2]):
        extent = hi * chunk
        for loop, products in ((select, 2), (attend, 2)):
            dots = [eqn for eqn in _eqns(loop.params["jaxpr"].jaxpr) if eqn.primitive.name == "dot_general"]
            assert len(dots) == products
            assert all(max(max(v.aval.shape) for v in eqn.invars) == max(extent, 512) for eqn in dots)  # no key past the span's end
    names = [eqn.primitive.name for eqn in _eqns(forward)]
    chooses = sum(hi * chunk > cfg.index_topk for _, hi in edges)
    assert names.count("cumsum") == chooses == 6 and "pallas_call" not in names and "top_k" not in names

    model = TransformerLM(cfg)
    ids = jnp.zeros((b, 4), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"])
    loss = lambda p, ids, mask: model.apply({"params": p}, ids, mask, compute_logits=False)["hidden"].astype(jnp.float32).sum()
    tokens = jax.ShapeDtypeStruct((b, length), jnp.int32)
    names = [eqn.primitive.name for eqn in _eqns(jax.make_jaxpr(jax.grad(loss))(params, tokens, tokens).jaxpr)]
    assert names.count("cumsum") == 2 * chooses + 1 and "pallas_call" not in names  # two layers, once each; and the positions from the mask


def test_a_remat_d_block_keeps_the_pass_s_output_by_name(monkeypatch):
    """As attention "sparse" (tests/test_sala.py): a remat'd block holds the pass's joined output, so a span's `attend`
    runs forward twice a step in each indexed layer and not three times (the softmax's `exp` counts its runs), and the
    loss and every gradient are, bit for bit, those of a block that keeps the choice alone."""
    cfg, model, params, ids, mask = _model(arch=TWO, remat=True, length=128)
    monkeypatch.setattr(indexer, "MIN_CHUNK", 16)
    monkeypatch.setattr(sparse, "SCORE_BYTES", 1)  # chunks of 16: eight a row
    monkeypatch.setattr(sparse, "SPANS", 2)

    def step(kept):
        monkeypatch.setattr(indexer, "SPAN_PASS_OUT", SPAN_PASS_OUT if kept else "kept by no policy")
        loss = lambda p: jnp.sum(jnp.sin(model.apply({"params": p}, ids, mask)["logits"][:, PAD:]))
        traced = jax.jit(jax.value_and_grad(loss)).trace(params)
        softmaxes = sum(eqn.primitive.name == "exp" and "dsa_attn" in str(eqn.source_info.name_stack) for eqn in _eqns(traced.jaxpr.jaxpr))
        return softmaxes, traced.lower().compile()(params)

    (passes, (loss, grads)), (passes_before, (loss_before, grads_before)) = step(True), step(False)
    assert (passes, passes_before) == (2 * 2 * cfg.n_layer, 3 * 2 * cfg.n_layer)  # two spans a layer
    assert float(loss) == float(loss_before)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_before)):
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


# ---- the deployment's shares -----------------------------------------------------------------------


def test_the_shares_of_the_deployment_add_up_to_the_uncut_layer():
    """Guide section 4: over all 32 expert shares (one expert each here) and all 4 head shares (two heads each), with
    what every chip computes alike counted once (the shared expert; the latents, their norms and the whole indexer, so
    the choice), the parts the PROGRAM computes add up to what the uncut REFERENCE gives for the whole layer."""
    arch = {**ARCH, "n_head": 8, "n_experts": 32, "experts_held": [0, 32]}
    cfg, model, params, ids, mask = _model(arch=arch, pad=0)
    x = jax.random.normal(jax.random.PRNGKey(7), (T, 32))

    # the expert layer: 32 shares of one expert
    whole = params["h_1"]["moe"]
    want = reference._expert_ffn(x[None], whole, arch, "highest")[0]

    def expert_share(first, n_shared):
        part = {**whole, **{f"experts_{m}": whole[f"experts_{m}"][first:first + 1] for m in ("gate", "up", "down")}}
        layer = moe.ExpertLayer(cfg.replace(experts_held=(first, 1), n_shared_experts=n_shared))
        return layer.apply({"params": part}, x[None])[0][0]

    shared_once = expert_share(0, 1) - expert_share(0, 0)
    np.testing.assert_allclose(sum(expert_share(first, 0) for first in range(32)) + shared_once, want, atol=2e-5, rtol=1e-4)

    # the attention: 4 shares of two heads; W_qb and W_kvb by columns, W_o by rows, everything else whole
    attn = params["h_1"]["attn"]
    want = reference.indexed_layer(attn, arch, x)
    held = cfg.replace(n_head=2)
    rope = rope_tables(held, jnp.arange(T)[None])

    def head_share(s):
        cut = lambda kernel, width: kernel.reshape(kernel.shape[0], 8, width)[:, 2 * s:2 * s + 2].reshape(kernel.shape[0], -1)
        part = {**attn, "q_b_proj": {"kernel": cut(attn["q_b_proj"]["kernel"], 16)},
                "kv_b_proj": {"kernel": cut(attn["kv_b_proj"]["kernel"], 16)},
                "c_proj": {"kernel": attn["c_proj"]["kernel"].reshape(8, 8, 32)[2 * s:2 * s + 2].reshape(16, 32)}}
        out, _, stats = LatentAttention(held).apply({"params": part}, x[None], None, rope, token_mask=jnp.ones((1, T), jnp.int32))
        assert tuple(float(v) for v in stats) == _kept(T)  # every share makes the same choice
        return out[0]

    np.testing.assert_allclose(sum(head_share(s) for s in range(4)), want, atol=2e-5, rtol=1e-4)
    assert float(jnp.abs(head_share(0) - want).max()) > 1e-2


# ---- the published widths --------------------------------------------------------------------------


def _leaf_counts(tree):
    return {k: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(v)) for k, v in tree.items()}


def test_parameter_counts_at_the_published_widths():
    """By `jax.eval_shape`, nothing allocated: the numbers of ISSUE 53. Whole (78 layers, 64 heads, 256 experts,
    154,880 rows): latent attention 165,022,208 and its indexer 9,371,904, a dense layer 400,898,816, an expert
    37,748,736: 743,911,218,432, the published "744B"; as the cell runs (1 + 4 layers, 16 heads, 8 experts, 19,360
    rows, the value head in): 2,218,828,545."""
    from trlx_tpu.models.hf_import import lm_config_from_hf

    whole = lm_config_from_hf(types.SimpleNamespace(**GLM["published"]))
    assert whole.ffn_layers == ("dense",) * 3 + ("experts",) * 75 and (whole.n_head, whole.n_experts) == (64, 256)
    ids = jnp.zeros((1, 2), jnp.int32)
    two = whole.replace(n_layer=2, ffn_layers=("dense", "experts"))
    shapes = jax.eval_shape(TransformerLM(two).init, jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    dense, experts = _leaf_counts(shapes["h_0"]), _leaf_counts(shapes["h_1"])
    attention = _leaf_counts(shapes["h_0"]["attn"])
    assert attention["indexer"] == 9_371_904 and dense["attn"] - attention["indexer"] == 165_022_208
    assert sum(dense.values()) == 400_898_816 and experts["moe"] == 256 * 37_748_736 + 37_748_736 + 6144 * 256 + 256
    table = _leaf_counts(shapes)
    assert table["wte"] == table["lm_head"] == 951_582_720 and table["ln_f"] == 6144
    assert 3 * sum(dense.values()) + 75 * sum(experts.values()) + 2 * 951_582_720 + 6144 == 743_911_218_432

    cell = LMConfig.from_dict(GLM["model_arch"])
    shapes = jax.eval_shape(LMWithValueHead(cell).init, jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    counts = _leaf_counts(shapes)
    blocks = _leaf_counts(shapes["transformer"])
    assert blocks["h_0"] == 289_225_472 and blocks["h_4"] == 404_044_800 and counts["v_head"] == 75_522_049
    assert sum(counts.values()) == 2_218_828_545
    trains = trainable_mask(shapes, cell, 1)
    trainable = sum(int(np.prod(leaf.shape)) for leaf, on in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(trains)) if on)
    assert trainable == 717_468_417 - 9_371_904  # the issue's upper bound (the indexer counted in) less the indexer


def test_the_configuration_keeps_every_published_width():
    arch, published = GLM["model_arch"], GLM["published"]
    cfg = LMConfig.from_dict(arch)
    assert (cfg.d_model, cfg.ff_dim, cfg.q_lora_rank, cfg.kv_lora_rank) == (6144, 12288, 2048, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (192, 64, 256)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (32, 128, 2048)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_d_ff, cfg.n_shared_experts, cfg.routed_scaling_factor) == (256, 8, 2048, 1, 2.5)
    assert (cfg.rope_theta, cfg.rope_scaling, cfg.ln_eps, cfg.router_scoring) == (1e6, None, 1e-5, "sigmoid") and not cfg.tie_word_embeddings
    assert (cfg.n_layer, cfg.n_head, cfg.held_experts, cfg.vocab_size) == (5, 16, (0, 8), 19360)
    assert cfg.ffn_layers == ("dense",) + ("experts",) * 4
    cut = ["n_routed_experts", "num_attention_heads", "num_hidden_layers", "num_key_value_heads", "num_layers_unfrozen", "vocab_size"]
    assert sorted(GLM["reduced"]) == cut
    for key, value in published.items():  # every catalogued key stands in the file as run, but those that were cut
        if key not in cut:
            assert GLM[key] == value, key
    assert (GLM["num_hidden_layers"], GLM["n_routed_experts"], GLM["num_attention_heads"], GLM["num_key_value_heads"],
            GLM["vocab_size"]) == (5, 8, 16, 16, published["vocab_size"] // 8)
    for key in ("indexer", "indexer_rope", "indexer_hadamard_fp8", "indexer_training", "same_choice_every_pass", "mtp",
                "e_score_correction_bias", "weights", "value_head"):
        assert GLM["assumed"][key], key
    rehearsal = LMConfig.from_dict(GLM["rehearsal_arch"])
    same = ("attention", "norm", "mlp", "activation", "pos_type", "tie_word_embeddings", "rope_theta", "routed_scaling_factor",
            "router_scoring", "n_shared_experts")
    assert all(getattr(rehearsal, k) == getattr(cfg, k) for k in same)
    assert (rehearsal.index_n_heads, rehearsal.index_head_dim, rehearsal.index_topk) == (4, 16, 16)


def test_lm_config_from_the_published_keys():
    from trlx_tpu.models.hf_import import lm_config_from_hf

    cfg = lm_config_from_hf(types.SimpleNamespace(**GLM["published"]))
    assert (cfg.n_layer, cfg.d_model, cfg.vocab_size, cfg.max_position, cfg.rope_theta) == (78, 6144, 154880, 202752, 1e6)
    cell = LMConfig.from_dict(GLM["model_arch"])
    own = ("n_layer", "n_head", "ffn_layers", "vocab_size", "max_position", "embed_init_std", "draw_dtype", "experts_held")
    for field in LMConfig.__dataclass_fields__:
        if field not in own:
            assert getattr(cfg, field) == getattr(cell, field), field


@pytest.mark.parametrize("change, message", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_group": 8}, "n_group"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_type"),
    ({"indexer_rope_interleave": False}, "rope_interleave"),
    ({"num_key_value_heads": 8}, "grouped keys"),
    ({"q_lora_rank": None}, "query bottleneck"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_lm_config_from_hf_refuses_by_name(change, message):
    from trlx_tpu.models.hf_import import lm_config_from_hf

    with pytest.raises(ValueError, match=f"glm_moe_dsa: not built: .*{message}"):
        lm_config_from_hf(types.SimpleNamespace(**{**GLM["published"], **change}))


@pytest.mark.parametrize("bad, message", [
    ({"index_topk": 0}, "index_n_heads, index_head_dim and index_topk"),
    ({"index_n_heads": 0}, "index_n_heads, index_head_dim and index_topk"),
    ({"index_head_dim": 4}, "index_head_dim at least"),
    ({"q_lora_rank": 0}, "query bottleneck"),
    ({"pos_type": "none"}, "pos_type 'rotary'"),
    ({"attention": "mha", "fused_qkv": False}, "describe an indexer on attention 'mla'"),
    ({"rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1, "original_max_position_embeddings": 64}},
     "without rope_scaling"),
    ({"kv_cache_quant": True}, "kv_cache_quant"),
    ({"n_soft_tokens": 4}, "soft prompts"),
    ({"n_loops": 2}, "looped stack"),
    ({"index_rope_dim": 8}, "unknown architecture key"),
])
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **bad})


def test_the_paths_that_are_not_built_refuse_by_name():
    from trlx_tpu.engine.rollout_engine import RolloutEngine
    from trlx_tpu.models.hf_export import validate_exportable
    from trlx_tpu.models.hf_import import load_hf_trunk

    cfg, model, params, ids, mask = _model()
    cache = init_cache(cfg, B, T)
    ones = jnp.ones((B, T), jnp.int32)
    with pytest.raises(NotImplementedError, match="indexed latent layer .* takes a pass with no cache"):  # a per-row write offset
        model.apply({"params": params}, ids[:, :1], mask[:, :1], cache=cache, cache_index=jnp.zeros((B,), jnp.int32), cache_mask=ones)
    with pytest.raises(NotImplementedError, match="indexed latent layer .* takes a pass with no cache"):  # a verify window
        model.apply({"params": params}, ids[:, :4], mask[:, :4], cache=cache, cache_index=jnp.int32(3), cache_mask=ones)
    with pytest.raises(NotImplementedError, match="indexed latent layer .* takes a pass with no cache"):  # packed segments
        model.apply({"params": params}, ids, mask, segment_ids=jnp.zeros((B, T), jnp.int32))
    with pytest.raises(NotImplementedError, match="paged pool is not built for attention 'mla'"):
        init_paged_cache(cfg, 4, 8)
    with pytest.raises(NotImplementedError, match="glm_moe_dsa"):
        load_hf_trunk("/nowhere", cfg)
    with pytest.raises(ValueError, match="glm_moe_dsa"):
        validate_exportable(cfg, "gptj")
    with pytest.raises(NotImplementedError, match="rollout engine .* indexed latent layer"):
        RolloutEngine(types.SimpleNamespace(cfg=cfg), None, n_slots=2, prompt_width=8)
    with pytest.raises(ValueError, match="sp ring|grouped keys|sp_size"):
        LMConfig.from_dict({**ARCH, "sp_size": 2, "n_kv_head": 2})


def test_the_frozen_branch_replays_an_indexed_block():
    cfg = LMConfig.from_dict({**ARCH, **F32})
    model = LMWithValueHead(cfg, branch_layer=2)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :PAD].set(0)
    params = model.init(jax.random.PRNGKey(0), ids[:, :8], mask[:, :8])["params"]
    full = model.apply({"params": params}, ids, mask, collect_branch_hidden=True)
    branch = extract_branch_params(params, cfg, 2)
    assert sorted(branch["transformer"]) == ["h_2", "lm_head", "ln_f"]
    replay = model.apply({"params": branch}, full["branch_hidden"], mask, method="forward_branch")
    np.testing.assert_allclose(replay[:, PAD:], full["logits"][:, PAD:], atol=1e-5)


# ---- the normal path --------------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path, monkeypatch):
    """`trlx_tpu.train` -> orchestrator -> ops/generate.py (the three-leaf cache through the fused rollout) ->
    make_experience (scoring: the frozen branch replayed over an indexed expert block) -> learn(): the fresh-step PPO
    ratio compares the decode path's own log-probs (index scores over the cache, the gathered absorbed read) with
    the train forward's chunked and masked unabsorbed pass; the counters report what the choice kept and what the
    steps read; the indexer's parameters move by nothing."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "examples"))
    import trlx_tpu
    from randomwalks import base_config

    config = base_config("ppo", ARCH["vocab_size"], 16)
    config.model.model_arch = dict(TWO)
    config.model.num_layers_unfrozen = 1
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
    from trlx_tpu.trainer.ppo import PPOTrainer

    drawn, learn = {}, PPOTrainer.learn

    def learn_after_a_copy(self):
        drawn.update(jax.device_get(self.state.params["transformer"]))
        return learn(self)

    monkeypatch.setattr(PPOTrainer, "learn", learn_after_a_copy)
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 96 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    cfg = trainer.model.cfg
    assert trainer.fused_rollout and (cfg.attention, cfg.index_topk) == ("mla", 16)
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == [1, 2, 3, 4]
    for first in (1, 3):  # the first step of each iteration: the policy has not moved since it sampled
        assert abs(steps[first]["mean_ratio"] - 1.0) < 1e-3, steps[first]["mean_ratio"]
    lengths = [len(p) + 24 for p in prompts]
    share = sum(_kept(n)[0] for n in lengths) / sum(_kept(n)[1] for n in lengths)
    for r in steps.values():
        assert r["dsa/kept_pair_share"] == pytest.approx(share, rel=1e-5)  # from the choice itself: the rule's, to the pair
        assert "flash/kept_pair_share" not in r and "sparse/kept_pair_share" not in r
    phases = [r for r in records if "time/window_wall_s" in r and "rollout/index_key_bytes" in r]
    assert phases
    for p in phases:
        assert p["rollout/index_key_bytes"] == 2 * 8 * 80 * 16 * cfg.compute_dtype.itemsize
        assert p["rollout/cache_bytes_per_token"] == 2 * 40 * cfg.compute_dtype.itemsize
        assert p["rollout/kv_read_share"] == 16 / 80 and 0.2 < p["rollout/dsa_keys_read_share"] < 0.6
        assert p["rollout/step_bytes_needed"] > 0
    # the indexer moved by nothing over the run, while the block around it trained
    moved = lambda path: [float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) for a, b in zip(
        jax.tree_util.tree_leaves(path(trainer.state.params["transformer"])), jax.tree_util.tree_leaves(path(drawn)))]
    for layer in ("h_0", "h_1"):
        assert max(moved(lambda t: t[layer]["attn"]["indexer"])) == 0.0
    assert min(moved(lambda t: {k: v for k, v in t["h_1"]["attn"].items() if k != "indexer"})) > 0.0


@pytest.mark.parametrize("indexed", [True, False], ids=["an indexed stack", "latent attention without an indexer"])
def test_a_padded_position_takes_no_routed_expert(indexed):
    """Pads are all one token and would all choose the same experts (1,792 of them on one held expert passed the slot
    buffer on the chip): in every expert stack they take none, which leaves every real position as it was."""
    cfg, model, params, ids, mask = _model(arch=TWO if indexed else {k: v for k, v in TWO.items() if not k.startswith("index_")}, pad=40)
    out = model.apply({"params": params}, ids, mask)
    counts = np.asarray(out["expert_counts"])  # [1 expert layer, 4 held]
    every = np.asarray(model.apply({"params": params}, ids, jnp.ones_like(mask))["expert_counts"])
    assert counts.sum() < every.sum() and counts.sum() <= 2 * (2 * T - 40)  # two choices a real token at most
    layer = moe.ExpertLayer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, 32))
    p = params["h_1"]["moe"]
    with_pads, _ = layer.apply({"params": p}, x)
    without, held = layer.apply({"params": p}, x, token_mask=mask)
    np.testing.assert_allclose(without[:, 40:], with_pads[:, 40:], atol=1e-6)
    np.testing.assert_allclose(without[0], with_pads[0], atol=1e-6)
    assert float(jnp.abs(without[1, :40] - layer.apply({"params": {**p}}, x, token_mask=jnp.zeros_like(mask))[0][1, :40]).max()) == 0.0

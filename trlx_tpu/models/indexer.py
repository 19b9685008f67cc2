"""A learned indexer over the latent cache (DeepSeek Sparse Attention, as the
DeepSeek-V3.2-Exp report gives it; `LMConfig.index_*`): every query of a
latent-attention layer chooses the `index_topk` keys it attends to, by scores
of the layer's own small index heads, the same keys for all attention heads.

Per layer, on the block's normed input x [b, T, d_model] and the normed query
latent c_q [b, T, q_lora_rank] of `LatentAttention`, H_I = index_n_heads heads
of D_I = index_head_dim, the first qk_rope_head_dim channels rotated in
interleaved pairs by the pass's positions (a row's REAL tokens count):

    1. q^I_{t,h} = RoPE(W^I_q c_q,t)                        [H_I, D_I]
    2. k^I_j = RoPE(LayerNorm(W^I_k x_j)), eps 1e-6          [D_I], one a token
    3. w_{t,h} = (W^I_w x_t)_h * H_I^-1/2 * D_I^-1/2          float32
    4. I_{t,j} = sum_h w_{t,h} ReLU(q^I_{t,h} . k^I_j)       real j <= t, float32
    5. S_t = the index_topk keys j of largest I_{t,j}; every real j <= t where
       there are no more than that; of equal scores the earlier key.

The choice carries no gradient and is a set: nothing reaches the indexer's
parameters from the PPO loss, and `models/heads.py trainable_mask` keeps them
out of the optimizer. No Hadamard rotation and no FP8: bf16 operands, float32
sums (an orthogonal rotation of q^I and k^I alike leaves every product as it
was; the published code rotates so that it can round both to FP8).

*Many tokens* (`indexed_attention`: the train step, scoring, the frozen branch,
the prefill; a pass longer than index_topk, a shorter one chooses every key and
takes `LatentAttention`'s own path): by slot, the query chunks of
`models/sparse.py` in its spans (`over_spans`): steps 4-5 a chunk give the
chosen keys as a mask (no gradient; a remat'd block keeps it by name), then
UNABSORBED latent attention a chunk with the mask on the scores, each chunk
recomputed in its own backward pass; the joined output is kept by name too, so
the block's recomputation does not run the pass again. Every pair of a chunk up
to its span's end is arithmetic, chosen or not. The k-th largest score is found
by bisection over the scores' bits (32 counts a chunk), so the choice is a mask
from the start and no index is scattered.
*One token* (a decode step over a cache longer than index_topk): I_t over the
row's index keys, `lax.top_k`, a gather of the chosen (c_kv, k_rope) entries,
the absorbed read over the gathered [b, index_topk, ...]: sparse in bytes.

The cache of a layer is `(c_kv [b, T, kv_lora_rank], k_rope [b, T,
qk_rope_head_dim], k_idx [b, T, index_head_dim])`.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from trlx_tpu.models.lm import SPAN_PASS_OUT, LMConfig, QDense, apply_rotary
from trlx_tpu.models.sparse import over_spans, query_chunk_from
from trlx_tpu.ops.kv_read import attend_latent

# Queries of the smallest chunk of the many-token pass (it doubles while the scores fit `sparse.SCORE_BYTES`)
MIN_CHUNK = 128
LAYERNORM_EPS = 1e-6  # the index key's LayerNorm, as the published code sets it


def index_rope(rope, width: int):
    """The pass's rotary tables [b, t, 1, qk_rope_head_dim] widened to an index head: the channels past the rotated
    ones take C = 1, S = 0."""
    rest = ((0, 0),) * 3 + ((0, width - rope[0].shape[-1]),)
    return jnp.pad(rope[0], rest, constant_values=1.0), jnp.pad(rope[1], rest)


class Indexer(nn.Module):
    """Steps 1-3: (q_idx [b, t, H_I, D_I], k_idx [b, t, D_I], w [b, t, H_I] float32) from the block's normed
    input `x` and the normed query latent `c_q`; `rope` the pass's tables (`rope_tables`). No gradient leaves it."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, c_q, rope):
        cfg = self.cfg
        dtype, heads, width = cfg.compute_dtype, cfg.index_n_heads, cfg.index_head_dim
        b, t, _ = x.shape
        dense = lambda feats, name: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=False,
                                           draw_dtype=cfg.draw_dtype, name=name)
        x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
        tables = index_rope(rope, width)
        rotate = lambda part: apply_rotary(part, tables, cfg.qk_rope_head_dim, False)
        q_idx = rotate(dense(heads * width, "q_proj")(c_q).reshape(b, t, heads, width))
        k_norm = nn.LayerNorm(epsilon=LAYERNORM_EPS, dtype=dtype, param_dtype=cfg.params_dtype, name="k_norm")
        k_idx = rotate(k_norm(dense(width, "k_proj")(x))[:, :, None, :])[:, :, 0]
        w = dense(heads, "w_proj")(x).astype(jnp.float32) * (heads ** -0.5 * width ** -0.5)
        return q_idx, k_idx, w


def index_scores(q_idx, w, k_idx):
    """Step 4. q_idx [b, Q, H_I, D_I], w [b, Q, H_I] float32, k_idx [b, K, D_I] -> I [b, Q, K] float32."""
    per_head = jnp.einsum("bqhd,bkd->bhqk", q_idx, k_idx, preferred_element_type=jnp.float32)
    return jnp.einsum("bhqk,bqh->bqk", jax.nn.relu(per_head), w)


def sortable(scores):
    """float32 -> uint32 in the floats' order, every finite score above 0."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def choose_keys(scores, seen, topk: int):
    """Step 5 as a mask. scores [b, Q, K] float32, seen [b, Q, K] bool (the real keys at or before each query) ->
    bool [b, Q, K]: the `topk` seen keys of largest score, all of them where there are no more; of equal scores the
    earlier. The k-th largest is built bit by bit from counts (no sort), the ties at it taken in order."""
    if scores.shape[-1] <= topk:
        return seen
    keys = jnp.where(seen, sortable(scores), jnp.uint32(0))
    count = lambda m: jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32)

    def refine(i, kth):
        higher = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(count(keys >= higher) >= topk, higher, kth)

    kth = jax.lax.fori_loop(0, 32, refine, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))
    above = keys > kth
    tied = (keys == kth) & seen
    return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= topk - count(above)))


def chosen_softmax(scores, chosen):
    """Softmax of float32 `scores` [..., K] over the keys `chosen` marks. The row maximum is handed over behind an
    optimization barrier: fused with the subtraction that follows it, the v5e compiler turns the reduction and its
    broadcast into ONE reduce-window 2 K - 1 wide over the whole score array, 23.6 ms a [16, 512, 8192] chunk where
    the two-pass form takes under 2 (PERF.md section 6, PR 53)."""
    scores = jnp.where(chosen, scores, -1e9)
    top = jax.lax.optimization_barrier(jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True)))
    weights = jnp.exp(scores - top)
    return weights / jnp.sum(weights, axis=-1, keepdims=True)


def indexed_attention(q, k, v, q_idx, w, k_idx, mask, cfg: LMConfig, scale, dtype):
    """The many-token pass, by slot. q [b, T, h, dn + dr], k [b, T, h, dn + dr], v [b, T, h, dv] (the unabsorbed
    heads), q_idx [b, T, H_I, D_I], w [b, T, H_I], k_idx [b, T, D_I], mask [b, T] the rows' real tokens. Returns
    (out [b, T, h, dv], stats: (chosen pairs, causal pairs) float32 scalars over the real queries)."""
    f32 = jnp.float32
    b, T, h, _ = q.shape
    chunk = query_chunk_from(MIN_CHUNK, b, T, max(h, cfg.index_n_heads))
    n_chunks = -(-T // chunk)
    Tp = n_chunks * chunk
    grid = lambda a: jnp.pad(a, ((0, 0), (0, Tp - T)) + ((0, 0),) * (a.ndim - 2))
    q, k, v, q_idx, w, k_idx, real = (grid(a) for a in (q, k, v, q_idx, w, k_idx, mask.astype(bool)))

    def seen_keys(start, real_s):
        t = start + jnp.arange(chunk, dtype=jnp.int32)
        return (jnp.arange(real_s.shape[1])[None, None, :] <= t[None, :, None]) & real_s[:, None, :]  # [b, chunk, extent]

    def select(q_c, qi_c, w_c, start, ki_s, real_s):
        with jax.named_scope("dsa_index"):
            scores = index_scores(qi_c, w_c, ki_s)
        with jax.named_scope("dsa_select"):
            return choose_keys(scores, seen_keys(start, real_s), cfg.index_topk)

    def attend(q_c, qi_c, w_c, start, chosen, k_s, v_s, real_s):
        with jax.named_scope("dsa_attn"):
            scores = jnp.einsum("bqhd,bkhd->bhqk", q_c, k_s, preferred_element_type=f32)
            probs = chosen_softmax(scores * scale, chosen[:, None]).astype(dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_s, preferred_element_type=f32).astype(dtype)
        is_query = jax.lax.dynamic_slice_in_dim(real_s, start, chunk, axis=1)[:, :, None]  # the chunk lies inside its span
        stats = (jnp.sum(chosen & is_query, dtype=f32), jnp.sum(seen_keys(start, real_s) & is_query, dtype=f32))
        return out, stats

    chunked = lambda a: jnp.moveaxis(a.reshape((b, n_chunks, chunk) + a.shape[2:]), 1, 0)
    xs = (chunked(q), chunked(q_idx), chunked(w), jnp.arange(n_chunks, dtype=jnp.int32) * chunk)
    span_args = lambda extent: ((k_idx[:, :extent], real[:, :extent]), (k[:, :extent], v[:, :extent], real[:, :extent]))
    outs, sums = over_spans(xs, chunk, span_args, select, attend)
    out = jnp.moveaxis(jnp.concatenate(outs), 0, 1).reshape(b, Tp, h, -1)[:, :T]
    return checkpoint_name(out, SPAN_PASS_OUT), tuple(jnp.sum(jnp.concatenate(s)) for s in zip(*sums))


def choose_slots(q_idx, w, k_idx, filled, topk: int):
    """Steps 4-5 of a decode step. q_idx [b, 1, H_I, D_I], w [b, 1, H_I], k_idx [b, T, D_I] the row's index keys
    with this token's written, filled [b, T] the cache's occupancy -> (slots [b, topk] int32, taken [b, topk] bool:
    fewer filled slots than `topk` and the rest of the list is no key)."""
    with jax.named_scope("dsa_index"):
        scores = index_scores(q_idx, w, k_idx)[:, 0]  # [b, T]
    with jax.named_scope("dsa_select"):
        best, slots = jax.lax.top_k(jnp.where(filled.astype(bool), scores, -jnp.inf), topk)
        return slots, best > -jnp.inf


def indexed_read(q_lat, q_rope, q_idx, w, cache, filled, cfg: LMConfig, scale, dtype):
    """A decode step. q_lat [b, 1, h, rank], q_rope [b, 1, h, dr] (the absorbed query), q_idx [b, 1, H_I, D_I], w
    [b, 1, H_I], cache the layer's three leaves with this token written, filled [b, T] the cache's occupancy (this
    token's slot in it). Returns (o_lat [b, 1, h, rank], stats: (the share of its filled slots the step read,
    summed over rows; their count))."""
    c_kv, k_rope, k_idx = cache
    slots, taken = choose_slots(q_idx, w, k_idx, filled, cfg.index_topk)
    with jax.named_scope("dsa_attn"), jax.named_scope("kv_read"):
        gather = lambda leaf: jnp.take_along_axis(leaf, slots[:, :, None], axis=1)
        bias = jnp.where(taken, 0.0, -1e9).astype(jnp.float32)[:, None, None, :]
        o_lat = attend_latent(q_lat, q_rope, gather(c_kv), gather(k_rope), bias, scale, dtype)
    read = jnp.sum(taken, axis=-1).astype(jnp.float32) / jnp.maximum(jnp.sum(filled, axis=-1).astype(jnp.float32), 1.0)
    return o_lat, (jnp.sum(read), jnp.float32(read.shape[0]))

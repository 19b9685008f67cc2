"""Block-selected sparse attention (attention "sparse": InfLLM-V2 as MiniCPM4
and MiniCPM-SALA's `minicpm4` layers use it): every query chooses which key
blocks it reads, by scores over compressed keys the cache keeps beside keys
and values.

Per layer, on the block's normed input x [b, T, d_model], H = n_head query
heads over G = n_kv_head key/value heads of D = head_dim (a group of H / G),
NO rotary, with the constants kernel, stride, block, topk, window,
init_blocks (`LMConfig.sparse_*`), positions counting a row's REAL tokens
(block 0 starts at a row's first real token, wherever left padding put it):

    1. q = W_q x, k = W_k x, v = W_v x; qk-norm per head (`qk_norm`).
    2. kc_j = mean(k_{stride j} .. k_{stride j + kernel - 1}) per key head; it
       exists for a query t when stride j + kernel - 1 <= t.
    3. p_{t,h,.} = softmax_j(q_{t,h} . kc_j / sqrt(D)) over the j that exist,
       float32; a_{t,g,j} = the sum of p over the heads of group g.
    4. A_{t,g,b} = max of a over the compressed keys whose tokens touch block
       b = tokens [block b, block (b + 1)): a max-pool of width block / stride
       + kernel / stride - 1, stride block / stride, kernel / stride - 1
       positions of padding.
    5. The chosen set: the first init_blocks blocks; the blocks that hold
       tokens t - window + 1 .. t; and the topk blocks of largest A among the
       other blocks that start at or before t (all of them where fewer; of
       blocks with equal A the earlier). The choice carries no gradient.
    6. o_{t,h} = sum_s softmax_s(q_{t,h} . k_s / sqrt(D)) v_s over s <= t in
       a chosen block, float32 softmax.
    7. o = o * sigmoid(W_g x) (`attn_output_gate`); out = W_o o.

A pass of at most `dense_blocks` = init_blocks + window / block + topk blocks
chooses every block: the layer IS dense grouped attention there, by the same
masked pass (one path at every length: no benchmark cell runs a pass that
short, so none would measure a second one).

*Many tokens* (`sparse_attention`: the train step, scoring, the prefill): the
rows are rolled into their own grid (token r at index r), the compressed keys
made once, and the query chunks cut into at most SPANS consecutive spans. A
span's queries see no key at or past its end, so its keys, values, compressed
keys and blocks are static slices up to there and every array of its chunks is
that wide: with S equal spans (S + 1) / 2S of the chunks-by-all-keys pairs,
9/16 at 8. A span runs two `lax.map`s over its chunks: steps 3-5, which give
the chosen sets (no gradient; a remat'd block keeps them by name, so a train
step chooses once), then step 6, each chunk recomputed in its own backward
pass (`jax.checkpoint`) with its chosen sets as an argument, applied to the
scores as a mask. A remat'd block keeps the joined output by name as well, so
a train step runs step 6 forward twice (the forward, the chunks' backward) and
not a third time in the block's recomputation. Within a span every pair up to
its end is computed: what is masked there (the triangle above the diagonal
inside the span, the unchosen fifth of the causal pairs) is still arithmetic;
`computed_pairs` counts it.
*One token* (a decode step): the scores
over the cache's compressed keys, the choice, a gather of the chosen blocks
(`ops/kv_read.py attend_selected`): sparse in bytes. The step counts the
slots its softmax saw (`attend_selected`'s own mask) over the slots its rows
have filled: the rollout's `rollout/sparse_keys_read_share`.

The cache of a layer is `(k, v [b, T, G, D], kc [b, J, G, D])`, J = (T -
kernel) // stride + 1: keys and values by slot, the compressed keys in each
ROW'S OWN grid (entry j covers the row's tokens from stride j). The prefill
writes those its prompt completes; a decode step completes one every `stride`
tokens, from the last `kernel` keys.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from trlx_tpu.models.lm import SPAN_PASS_OUT, SPARSE_CHOSEN, LMConfig, QDense, qk_normed, write_cache
from trlx_tpu.ops.kv_read import attend_selected

# Bytes of one query chunk's float32 scores [b, H, chunk, T] in the many-token pass
SCORE_BYTES = 1 << 29
# Spans the many-token pass cuts its query chunks into, each against the keys up to its own end (`span_edges`)
SPANS = 8


def dense_blocks(cfg: LMConfig) -> int:
    """Blocks a pass may hold and still choose every one: 1 + 32 + 64 = 97 as published."""
    return cfg.sparse_init_blocks + cfg.sparse_window // cfg.sparse_block + cfg.sparse_topk


def gathered_blocks(cfg: LMConfig, n_blocks: int) -> int:
    """Blocks a decode step gathers from a cache of `n_blocks`: the most a query chooses
    (a window that 'block' does not divide at the query touches one block more)."""
    return min(n_blocks, dense_blocks(cfg) + 1)


def compressed_slots(cfg: LMConfig, length: int) -> int:
    """J: compressed keys of a row of `length` tokens."""
    return max(0, (length - cfg.sparse_kernel) // cfg.sparse_stride + 1)


def cache_shapes(cfg: LMConfig, batch: int, max_len: int):
    """((shape, dtype), ...) of one layer's (k, v, kc) leaves."""
    slot = (batch, max_len, cfg.kv_heads, cfg.head_dim)
    return ((slot, cfg.compute_dtype), (slot, cfg.compute_dtype),
            ((batch, max(1, compressed_slots(cfg, max_len)), cfg.kv_heads, cfg.head_dim), cfg.compute_dtype))


def align_rows(a, first):
    """`a` [b, T, ...] with every row rolled so that slot `first` [b] lands at index 0: the row's own grid."""
    return jax.vmap(lambda row, s: jnp.roll(row, -s, axis=0))(a, first)


def compress_keys(k, cfg: LMConfig):
    """Step 2. k [b, T, G, D] in the rows' own grid -> [b, J, G, D] in k's dtype, float32 sums; T >= kernel."""
    b, T, G, D = k.shape
    stride, pieces = cfg.sparse_stride, cfg.sparse_kernel // cfg.sparse_stride
    whole = T // stride
    parts = jnp.sum(k[:, :whole * stride].astype(jnp.float32).reshape(b, whole, stride, G, D), axis=2)
    J = whole - pieces + 1
    return (sum(parts[:, i:i + J] for i in range(pieces)) / cfg.sparse_kernel).astype(k.dtype)


def choose_blocks(q, kc, t, cfg: LMConfig, n_blocks: int):
    """Steps 3-5. q [b, Q, H, D], kc [b, J, G, D] (the rows' own grid), t [b, Q] int32 the queries' positions in
    their rows -> bool [b, G, Q, n_blocks]: the blocks each query's group chooses. No gradient."""
    f32 = jnp.float32
    b, Q, H, D = q.shape
    J, G = kc.shape[1], kc.shape[2]
    stride, kernel, block = cfg.sparse_stride, cfg.sparse_kernel, cfg.sparse_block
    blk, at = jnp.arange(n_blocks), t[:, None, :, None]
    started = blk * block <= at
    if n_blocks <= cfg.sparse_topk:  # every block that has started, whatever the scores say
        return jnp.broadcast_to(started, (b, G, Q, n_blocks))
    q, kc = jax.lax.stop_gradient(q), jax.lax.stop_gradient(kc)
    scores = jnp.einsum("bqghd,bjgd->bghqj", q.reshape(b, Q, G, H // G, D), kc, preferred_element_type=f32) * D ** -0.5
    exists = ((stride * jnp.arange(J) + kernel - 1)[None, None, :] <= t[:, :, None])[:, None, None]  # [b, 1, 1, Q, J]
    scores = jnp.where(exists, scores, -1e30)
    p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True)) * exists
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    a = jnp.sum(p, axis=2)  # [b, G, Q, J]: the heads of a group choose together
    ratio, pieces = block // stride, kernel // stride
    need = ratio * n_blocks + pieces - 1
    a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (pieces - 1, max(0, need - (pieces - 1) - J))))[..., :need]
    pooled = jax.lax.reduce_window(a, -jnp.inf, jax.lax.max, (1, 1, 1, ratio + pieces - 1), (1, 1, 1, ratio), "VALID")
    forced = started & ((blk < cfg.sparse_init_blocks) | ((blk + 1) * block - 1 >= at - cfg.sparse_window + 1))
    others = started & ~forced
    # two neighbouring blocks share the compressed key between them, so equal scores are common: of equal
    # blocks the earlier one is chosen (`lax.top_k` puts the lower index first), by index and not by a threshold
    _, best = jax.lax.top_k(jnp.where(others, pooled, -1.0), cfg.sparse_topk)  # [b, G, Q, topk]
    return forced | (others & jnp.any(best[..., None] == blk, axis=-2))


def query_chunk_from(least: int, b: int, T: int, H: int) -> int:
    """Queries of one chunk of a many-token pass: `least` doubled while the float32 scores [b, H, chunk, T] fit SCORE_BYTES."""
    chunk = least
    while chunk * 2 <= min(T, 512) and b * H * chunk * 2 * T * 4 <= SCORE_BYTES:
        chunk *= 2
    return chunk


def query_chunk(cfg: LMConfig, b: int, T: int, H: int) -> int:
    """Queries of one chunk of the many-token pass: a power of two of whole blocks whose float32 scores fit SCORE_BYTES."""
    return query_chunk_from(cfg.sparse_block, b, T, H)


def span_edges(n_chunks: int):
    """((first chunk, past the last), ...) of the many-token pass's spans: at most SPANS runs of equally many whole
    chunks (the last may be shorter), a function of the pass's shapes alone."""
    per = -(-n_chunks // SPANS)
    return tuple((lo, min(lo + per, n_chunks)) for lo in range(0, n_chunks, per))


def over_spans(xs, chunk: int, span_args, select, attend):
    """The two maps of a many-token pass whose queries choose their keys (here and models/indexer.py). `xs`: arrays
    [n_chunks, ...], one entry a query chunk, cut into `span_edges`' spans. A span's queries see no key at or past
    its end, `extent`: `span_args(extent)` gives what its chunks read up to there, (select's, attend's). A span runs
    `select(*chunk's xs, *select's)` over its chunks, which gives what they chose (no gradient; named SPARSE_CHOSEN,
    so a remat'd block keeps it and a train step chooses once and not three times), then `attend(*chunk's xs,
    chosen, *attend's)` -> (out, stats), each chunk recomputed in its own backward pass. Returns (the spans' outs,
    the spans' stats), each [chunks of the span, ...]. The callers join the outs into the rows' own order and name
    that ONE array SPAN_PASS_OUT: a remat'd block keeps it, so its recomputation does not run these loops again."""
    outs, sums = [], []
    for lo, hi in span_edges(xs[0].shape[0]):
        span = tuple(x[lo:hi] for x in xs)
        for_select, for_attend = span_args(hi * chunk)
        chosen = jax.lax.map(lambda c: select(*c, *for_select), span)
        chosen = checkpoint_name(chosen, SPARSE_CHOSEN)
        out, stats = jax.lax.map(lambda c: jax.checkpoint(attend)(*c, *for_attend), (*span, chosen))
        outs.append(out)
        sums.append(stats)
    return outs, sums


def computed_pairs(cfg: LMConfig, b: int, T: int, H: int) -> int:
    """Query/key pairs a K/V head the many-token pass computes over [b, T], summed over its G heads: every chunk
    of a span against the keys up to the span's end (against the causal pairs `sparse_attention` sums: 1.0 would
    be none above the diagonal)."""
    chunk = query_chunk(cfg, b, T, H)
    return sum((hi - lo) * chunk * hi * chunk for lo, hi in span_edges(-(-T // chunk))) * cfg.kv_heads * b


def sparse_attention(q, k, v, mask, cfg: LMConfig, dtype):
    """The many-token pass. q [b, T, H, D], k, v [b, T, G, D] by slot, mask [b, T] the rows' real tokens
    (contiguous in a row). Returns (out [b, T, H, D], the compressed keys [b, J, G, D] in the rows' grid,
    stats: (kept pairs, causal pairs, chosen blocks, query-groups) float32 scalars over the real queries)."""
    f32 = jnp.float32
    b, T, H, D = q.shape
    G, block = k.shape[2], cfg.sparse_block
    first = jnp.argmax(mask, axis=1).astype(jnp.int32)
    n_real = jnp.sum(mask, axis=1).astype(jnp.int32)
    chunk = query_chunk(cfg, b, T, H)
    Tp = -(-T // chunk) * chunk
    grid = lambda a: jnp.pad(align_rows(a, first), ((0, 0), (0, Tp - T)) + ((0, 0),) * (a.ndim - 2))
    q, k, v = grid(q), grid(k), grid(v)
    with jax.named_scope("sparse_select"):
        kc = compress_keys(k, cfg)
    scale = D ** -0.5
    positions = lambda start: start + jnp.arange(chunk, dtype=jnp.int32)

    def select(q_c, start, kc, n_blocks):
        with jax.named_scope("sparse_select"):
            return choose_blocks(q_c, kc, jnp.broadcast_to(positions(start), (b, chunk)), cfg, n_blocks)

    def attend(q_c, start, chosen, k, v):
        t, keys = positions(start), jnp.arange(k.shape[1])
        with jax.named_scope("sparse_attn"):
            seen = (keys[None, None, :] <= t[None, :, None]) & (keys[None, None, :] < n_real[:, None, None])  # [b, chunk, extent]
            kept = seen[:, None] & jnp.repeat(chosen, block, axis=-1)  # [b, G, chunk, extent]
            scores = jnp.einsum("bqghd,bkgd->bghqk", q_c.reshape(b, chunk, G, H // G, D), k, preferred_element_type=f32)
            probs = jax.nn.softmax(jnp.where(kept[:, :, None], scores * scale, -1e9), axis=-1).astype(dtype)
            out = jnp.einsum("bghqk,bkgd->bqghd", probs, v, preferred_element_type=f32).astype(dtype)
        real = (t[None, :] < n_real[:, None]).astype(f32)  # [b, chunk]
        stats = (jnp.sum(jnp.sum(kept, axis=-1).astype(f32) * real[:, None]), G * jnp.sum((t[None, :] + 1.0) * real),
                 jnp.sum(jnp.sum(chosen, axis=-1).astype(f32) * real[:, None]), G * jnp.sum(real))
        return out.reshape(b, chunk, H, D), stats

    chunks = jnp.moveaxis(q.reshape(b, Tp // chunk, chunk, H, D), 1, 0)
    starts = jnp.arange(Tp // chunk, dtype=jnp.int32) * chunk
    # a span's queries see no key at or past its end: every array of its chunks stops there, by static slices
    def span_args(extent):
        k_s, v_s, kc_s = k[:, :extent], v[:, :extent], kc[:, :compressed_slots(cfg, extent)]
        return (kc_s, extent // block), (k_s, v_s)

    outs, sums = over_spans((chunks, starts), chunk, span_args, select, attend)
    out = jnp.moveaxis(jnp.concatenate(outs), 0, 1).reshape(b, Tp, H, D)[:, :T]
    return checkpoint_name(align_rows(out, -first), SPAN_PASS_OUT), kc, tuple(jnp.sum(jnp.concatenate(s)) for s in zip(*sums))


class SparseAttention(nn.Module):
    """Attention "sparse" (module docstring). `token_mask`: the real tokens of
    `x` [b, q_len] in a pass over many tokens; the cache's occupancy [b, T] in
    a decode step (one token at one write offset for the whole batch), which
    tells each row's first slot. Returns (out, new cache, stats). `stats`: the
    four sums of `sparse_attention` in a pass with no cache; in a decode step
    (the share of its filled slots that the step's softmax saw, summed over
    rows and K/V heads; their count), float32 scalars; None in a prefill. A
    decode step sows the blocks it chose, bool [b, G, n_blocks], as
    `intermediates/chosen` (read by benchmark/sparse_layer_parity.py; nothing
    unless the caller makes the collection mutable)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, cache=None, cache_index=None, token_mask=None):
        cfg = self.cfg
        dtype = cfg.compute_dtype
        b, q_len, _ = x.shape
        H, G, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=False,
                                           draw_dtype=cfg.draw_dtype, name=name)
        q = dense(H * D, "q_proj")(x).reshape(b, q_len, H, D)
        k = dense(G * D, "k_proj")(x).reshape(b, q_len, G, D)
        v = dense(G * D, "v_proj")(x).reshape(b, q_len, G, D)
        gate = dense(H * D, "g_proj")(x) if cfg.attn_output_gate else None
        if cfg.qk_norm:
            q, k = qk_normed(cfg, q, k)
        scale, block = D ** -0.5, cfg.sparse_block
        new_cache = stats = None
        if cache is not None and q_len == 1:
            slots_total = cache[0].shape[1]
            first = jnp.argmax(token_mask, axis=1).astype(jnp.int32)
            t = cache_index - first  # [b]: the token's position in its row
            keys, values = write_cache(cache[0], k, cache_index), write_cache(cache[1], v, cache_index)
            with jax.named_scope("sparse_select"):
                # the compressed key this token completes, if it completes one: the mean of the last `kernel` keys
                last = jax.lax.dynamic_slice_in_dim(keys, cache_index - (cfg.sparse_kernel - 1), cfg.sparse_kernel, axis=1)
                mean = jnp.mean(last.astype(jnp.float32), axis=1).astype(cache[2].dtype)
                done = (t >= cfg.sparse_kernel - 1) & ((t - (cfg.sparse_kernel - 1)) % cfg.sparse_stride == 0)
                at = jnp.clip((t - (cfg.sparse_kernel - 1)) // cfg.sparse_stride, 0, cache[2].shape[1] - 1)
                rows = jnp.arange(b)
                compressed = cache[2].at[rows, at].set(jnp.where(done[:, None, None], mean, cache[2][rows, at]))
                n_blocks = -(-slots_total // block)
                chosen = choose_blocks(q, compressed, t[:, None], cfg, n_blocks)[:, :, 0]  # [b, G, n_blocks]
                taken, which = jax.lax.top_k(chosen.astype(jnp.float32), gathered_blocks(cfg, n_blocks))
            self.sow("intermediates", "chosen", chosen)
            new_cache = (keys, values, compressed)

            def keep(slots):
                at_row = slots - first[:, None, None, None]
                return ((taken > 0)[..., None] & (at_row // block == which[..., None]) & (at_row >= 0)
                        & (at_row <= t[:, None, None, None]))

            with jax.named_scope("sparse_attn"), jax.named_scope("kv_read"):
                out, seen = attend_selected(q, keys, values, first[:, None, None] + which * block, keep, block, scale, dtype)
            stats = (jnp.sum(seen.astype(jnp.float32) / (t[:, None] + 1.0)), jnp.float32(b * G))
        else:
            mask = token_mask if token_mask is not None else jnp.ones((b, q_len), jnp.int32)
            out, compressed, stats = sparse_attention(q, k, v, mask, cfg, dtype)
            stats = stats if cache is None else None
            if cache is not None:  # the prefill, at write offset 0: the compressed keys its prompt completes
                compressed = compressed[:, :cache[2].shape[1]].astype(cache[2].dtype)
                new_cache = (write_cache(cache[0], k, cache_index), write_cache(cache[1], v, cache_index),
                             jax.lax.dynamic_update_slice(cache[2], compressed, (0, 0, 0, 0)))
        out = out.reshape(b, q_len, H * D)
        if gate is not None:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
        return dense(cfg.d_model, "c_proj")(out), new_cache, stats

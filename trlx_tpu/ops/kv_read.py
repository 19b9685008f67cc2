"""The einsum read of a fixed KV cache, over only the keys the bias can admit.

On the static generate path (`ops/generate.py`: prompts are left-padded, so
every row writes slot `P + step`) the additive bias admits key `j` for the
query at slot `c` only if `j <= c` (and `j > c - window` on a local layer).
The full-cache read contracts every slot and lets the bias
zero the rest: a masked key contributes `exp(-1e9 - max)`, which is exactly 0
in float32, so leaving it out of the read is the same mathematics on fewer
bytes.

Two pieces, both pure:

- `kv_read_ranges(cache_len, window)`: the static `[lo, hi)` of each branch;
  branch `k` serves every `cache_index` in `[k * bucket, (k + 1) * bucket)`.
- `attend_range(q, cache, attn_bias, lo, hi, ...)`: slice K, V, their scales
  and the bias to the range BEFORE the two contractions, so XLA fuses the
  slice into the operand load as it fuses the int8 convert.

On one device an int8 cache is never dequantized, ranged or whole
(`attend_quantized`): a key's scale is a constant of the key, so it multiplies
the key's score after q.K and the key's probability before probs.V, once a
key, and both contractions take the int8 values converted and nothing else.
On a `partitioned()` mesh the read dequantizes as it did (the one place the
expression is written).

`ranged_read` is the rule `Attention.__call__` asks: which of its calls take
the ranged read at all. The cache WRITE is not this module's business: it
stays one `dynamic_update_slice` on the whole buffer.
"""

from functools import partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from trlx_tpu.parallel.mesh import partitioned

# The bucket is a function of the cache length alone: 128 keys (one lane tile
# of the bias row and of the scales, so every slice edge is tile-aligned),
# doubled until at most 4 branches cover the cache. Swept once on the v5e at
# the benchmark's rollout shapes (PERF.md, PR 24): finer buckets read fewer
# keys but each branch is another copy of the read's code, and past four the
# loop's other operations are scheduled worse than the keys saved are worth
# (cache 1024: 256 keys beat 128 and 64; cache 512: 128 keys). Not a config
# field: the right value depends on the chip and the compiler, not on the
# model or the run.
KV_READ_BUCKET = 128
KV_READ_MAX_BRANCHES = 4


def kv_read_bucket(cache_len: int) -> int:
    bucket = KV_READ_BUCKET
    while -(-cache_len // bucket) > KV_READ_MAX_BRANCHES:
        bucket *= 2
    return bucket


def kv_read_ranges(cache_len: int, window: int = 0) -> Tuple[Tuple[int, int], ...]:
    """Static `[lo, hi)` per branch; branch `k` is `cache_index // bucket`.

    Every key the bias admits at any `cache_index` of branch `k` lies inside
    its range: `hi` is the branch's last frontier + 1 (clipped to the cache),
    `lo` is 0 on a global layer and, on a local one, the bucket edge at or
    below the first key the window can still see from the branch's first
    frontier. A cache of one bucket or less has one branch: the whole cache.
    """
    bucket = kv_read_bucket(cache_len)
    ranges = []
    for k in range(-(-cache_len // bucket)):
        lo = max(0, k * bucket - window) // bucket * bucket if window > 0 else 0
        ranges.append((lo, min((k + 1) * bucket, cache_len)))
    return tuple(ranges)


def attend(q, k, v, attn_bias, scale, dtype):
    """Softmax attention of `q` [b, q, h, d] over `k`/`v` [b, kv, h_kv, d].
    Grouped keys (h_kv < h): K/V head j serves query heads [j * g, (j + 1) * g),
    g = h // h_kv, by a reshape of the query heads; K and V are read as they
    are, never repeated."""
    h, h_kv = q.shape[2], k.shape[2]
    if h != h_kv:
        b, q_len, _, d = q.shape
        grouped = q.astype(jnp.float32).reshape(b, q_len, h_kv, h // h_kv, d)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", grouped, k.astype(jnp.float32)) * scale
        probs = jax.nn.softmax(scores + attn_bias[:, :, None], axis=-1).astype(dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(dtype)).reshape(b, q_len, h, d)
    # [b, n_head, q, kv] scores in fp32 for a stable softmax.
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    scores = scores + attn_bias  # additive -inf mask [b, 1, q, kv]
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(dtype))


def attend_quantized(q, k_i8, v_i8, k_scale, v_scale, attn_bias, scale, dtype):
    """`attend` over an int8 cache: `k_i8`/`v_i8` [b, kv, h_kv, d] hold
    key = k_i8 x k_scale and value = v_i8 x v_scale, the scales [b, kv, h_kv]
    one a key and head (models/lm.py `quantize_kv`). A key's scale leaves its
    sum over `d`: sum_d q_d (s k_d) = s sum_d q_d k_d, and sum_k p_k (s_k v_kd)
    = sum_k (p_k s_k) v_kd. So q contracts with the int8 K as it is and the
    scale multiplies the [.., q, kv] scores; the probabilities take V's scale,
    in float32, before they contract with the int8 V as it is. An int8 value
    is exact in `dtype` and both products accumulate in float32: the sums are
    those of float32 operands, and no dequantized K or V is ever rounded.
    Grouped keys as in `attend`: one form, g = 1 where h_kv = h. One rule
    stands before it, the one `ranged_read` asks: a `partitioned()` mesh."""
    if partitioned():
        # On a mesh the read still dequantizes, element by element in `dtype`:
        # restated, the four-chip cell's generate program ran 13% faster and
        # reserved 0.2 GB more a chip, past the bound on its peak (PERF.md
        # section 6, PR 33; section 7 has what a cure must keep).
        k = k_i8.astype(dtype) * k_scale[..., None].astype(dtype)
        v = v_i8.astype(dtype) * v_scale[..., None].astype(dtype)
        return attend(q, k, v, attn_bias, scale, dtype)
    b, q_len, h, d = q.shape
    h_kv = k_i8.shape[2]
    # [b, kv, h_kv] -> [b, h_kv, 1, 1, kv]: one factor a key, for its g query heads
    per_key = lambda s: jnp.transpose(s.astype(jnp.float32), (0, 2, 1))[:, :, None, None, :]
    grouped = q.astype(dtype).reshape(b, q_len, h_kv, h // h_kv, d)
    scores = jnp.einsum("bkhd,bqhgd->bhgqk", k_i8.astype(dtype), grouped, preferred_element_type=jnp.float32)
    scores = scores * (per_key(k_scale) * scale) + attn_bias[:, :, None]
    weights = (jax.nn.softmax(scores, axis=-1) * per_key(v_scale)).astype(dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v_i8.astype(dtype), preferred_element_type=jnp.float32)
    return out.astype(dtype).reshape(b, q_len, h, d)


def attend_cache(q, cache, attn_bias, scale, dtype):
    """Softmax attention of `q` over a whole cache view: `(k, v)`, or the int8
    `(k, v, k_scale, v_scale)` (what the code sees in its input, no setting)."""
    return (attend_quantized if len(cache) == 4 else attend)(q, *cache, attn_bias, scale, dtype)


def attend_range(q, cache, attn_bias, lo: int, hi: int, scale, dtype):
    """`attend_cache` over cache slots `[lo, hi)`; `cache` is `(k, v)` or the
    int8 `(k, v, k_scale, v_scale)`, each whole."""
    cut = lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=1)
    return attend_cache(q, tuple(cut(a) for a in cache), jax.lax.slice_in_dim(attn_bias, lo, hi, axis=3), scale, dtype)


def attend_latent(q_lat, q_rope, c_kv, k_rope, attn_bias, scale, dtype):
    """Latent attention's absorbed read (models/lm.py `LatentAttention`):
    `q_lat` [b, q, h, rank] is the query with the key up-projection absorbed,
    `q_rope` [b, q, h, r] its rotary part; the keys are the cache itself,
    `c_kv` [b, kv, rank] and `k_rope` [b, kv, r], one row a token for all
    heads. Returns softmax . c_kv, [b, q, h, rank]: the value up-projection is
    the caller's."""
    scores = jnp.einsum("bqhc,bkc->bhqk", q_lat.astype(jnp.float32), c_kv.astype(jnp.float32))
    scores = scores + jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    probs = jax.nn.softmax(scores * scale + attn_bias, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkc->bqhc", probs, c_kv.astype(dtype))


def attend_latent_range(q, cache, attn_bias, lo: int, hi: int, scale, dtype):
    """`attend_latent` over cache slots `[lo, hi)`; `q` is `(q_lat, q_rope)`,
    `cache` is `(c_kv, k_rope)`, each whole."""
    cut = lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=1)
    return attend_latent(*q, cut(cache[0]), cut(cache[1]), jax.lax.slice_in_dim(attn_bias, lo, hi, axis=3), scale, dtype)


def _read_branch(q, cache, attn_bias, *, lo, hi, scale, dtype, attend_range, slot_major):
    # A conditional's operands take the default, batch-major layout, and with
    # them the cache the loop carries. Outside a conditional XLA keeps it
    # slot-major ([T, h, b, d]: a slice of slots is one contiguous block, a
    # [b, d] tile is full), and batch-major the GPT-Neo decode loop took 5.71 s
    # against 3.56 s (PERF.md, PR 24): ask for slot-major. A latent cache
    # ([b, T, rank], no head axis) keeps the default: a row's slice of slots
    # is one contiguous block as it is.
    if slot_major:
        cache = tuple(
            with_layout_constraint(a, Layout(major_to_minor=(1, 2, 0, 3)[: a.ndim])) for a in cache
        )
    return attend_range(q, cache, attn_bias, lo, hi, scale, dtype)


def ranged_read(cache_len: int, q_len: int, cache_index, window: int = 0, *,
                attend_range: Callable = attend_range, slot_major: bool = True) -> Optional[Callable]:
    """The read for one decode step on a fixed cache with ONE traced write
    offset for the whole batch, as `read(q, cache, attn_bias, scale, dtype)`:
    a `lax.switch` over `kv_read_ranges`, around the read only. Its operands
    are read-only and its result is `[b, 1, h, d]`, so no branch returns (or
    copies) a cache. None where the caller keeps its full read: more than one
    query token, a per-row (vector) offset, a cache of a single branch, or a
    `partitioned()` mesh (the layout request in `_read_branch` is a custom
    call, and GSPMD replicates what it cannot partition, so every branch
    would all-gather the cache). `attend_range` is the read of one branch
    (`attend_range`: per-head K and V; `attend_latent_range`: the latent
    cache), `slot_major` whether the branch asks for the slot-major layout.
    """
    scalar = not isinstance(cache_index, (int, np.integer)) and jnp.ndim(cache_index) == 0
    ranges = kv_read_ranges(cache_len, window)
    if q_len != 1 or not scalar or len(ranges) == 1 or partitioned():
        return None
    bucket = kv_read_bucket(cache_len)

    def read(q, cache, attn_bias, scale, dtype):
        branches = [
            partial(_read_branch, lo=lo, hi=hi, scale=scale, dtype=dtype,
                    attend_range=attend_range, slot_major=slot_major)
            for lo, hi in ranges
        ]
        with jax.named_scope("kv_read"):
            return jax.lax.switch(cache_index // bucket, branches, q, tuple(cache), attn_bias)

    return read


def kv_scale_mults_per_key(n_head: int, kv_heads: int, head_dim: int) -> float:
    """Scale multiplications a decode step's read of an int8 cache performs
    for each key it reads (one slot of one K/V head of one row): one on the
    key's score and one on its probability, for each of the `n_head //
    kv_heads` query heads the key serves (`attend_quantized`); the read that
    dequantizes K and V, which a `partitioned()` mesh keeps, `2 x head_dim`.
    The counter `rollout/kv_scale_mults_per_key`, absent where the cache is
    not int8."""
    return 2.0 * (head_dim if partitioned() else n_head // kv_heads)


def kv_keys_read(
    cache_len: int, first_index: int, steps: int, windows: Sequence[int], rings: Sequence[int] = ()
) -> Tuple[int, int]:
    """(keys read, keys a full-span read would have touched) by the decode
    steps of one rollout, summed over layers (`windows`: each layer's window,
    0 = global; `rings`: each layer's ring slots, 0 or absent = a full-span
    cache) and over `steps` steps writing slots `first_index`,
    `first_index + 1`, ... A ring layer reads its ring, every step, on any
    mesh. Counted on the host from shapes alone; their ratio over a rollout
    phase is the counter `rollout/kv_read_share`."""
    rings = tuple(rings) or (0,) * len(windows)
    full = cache_len * steps * len(windows)
    branch = (first_index + np.arange(steps)) // kv_read_bucket(cache_len)
    read = 0
    for window, ring in zip(windows, rings):
        if ring:
            read += ring * steps
        elif partitioned():
            read += cache_len * steps
        else:
            width = np.array([hi - lo for lo, hi in kv_read_ranges(cache_len, window)])
            read += int(width[branch].sum())
    return read, full


def take_blocks(cache, starts, block: int):
    """`block` consecutive slots of `cache` [b, T, G, D] from each of `starts`
    [b, G, n] (one list a row and K/V head, every slice inside the buffer), as
    [b, G, n, block, D]."""
    heads_first = jnp.swapaxes(cache, 1, 2)  # [b, G, T, D]
    one_head = lambda rows, at: jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(rows, s, block, axis=0))(at)
    return jax.vmap(jax.vmap(one_head))(heads_first, starts)


def attend_selected(q, k_cache, v_cache, starts, keep, block: int, scale, dtype):
    """A decode step's read of the key blocks it chose (models/sparse.py):
    `q` [b, 1, h, d] over `block` slots from each of `starts` [b, G, n] of the
    caches [b, T, G, d], gathered, never the whole cache. A start too near the
    end of the buffer is moved back so that the slice fits; `keep(slots)` says
    which of the slots [b, G, n, block] that were gathered the query may see
    (its own blocks' filled slots): the caller masks by the slots it is handed,
    never by the starts it asked for. Grouped keys as in `attend`: K/V head j
    serves query heads [j * g, (j + 1) * g). Softmax in float32. Returns (out
    [b, 1, h, d], the slots the softmax saw a row and K/V head, int32 [b, G]:
    the count of the mask it applied)."""
    b, _, h, d = q.shape
    groups = k_cache.shape[2]
    starts = jnp.clip(starts, 0, k_cache.shape[1] - block)
    slots = starts[..., None] + jnp.arange(block, dtype=starts.dtype)
    keys, values = take_blocks(k_cache, starts, block), take_blocks(v_cache, starts, block)
    grouped = q.astype(dtype).reshape(b, groups, h // groups, d)
    scores = jnp.einsum("bghd,bgnkd->bghnk", grouped, keys.astype(dtype), preferred_element_type=jnp.float32) * scale
    kept = keep(slots)
    scores = jnp.where(kept[:, :, None], scores, -1e9)
    probs = jax.nn.softmax(scores.reshape(scores.shape[:3] + (-1,)), axis=-1).reshape(scores.shape).astype(dtype)
    out = jnp.einsum("bghnk,bgnkd->bghd", probs, values.astype(dtype), preferred_element_type=jnp.float32)
    return out.astype(dtype).reshape(b, 1, h, d), jnp.sum(kept, axis=(-1, -2), dtype=jnp.int32)

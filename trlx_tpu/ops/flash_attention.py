"""Pallas TPU flash attention: fused, online-softmax, O(T) memory.

The hot op of every forward/rollout/train step. The reference leans on
torch/HF SDPA CUDA kernels (reference: trlx/model/nn/ppo_models.py:171-189
replays HF GPT-2 blocks); here the kernel is ours, built for the MXU. One
algorithm in three kernels (forward; dq; dk/dv), each doing only the work the
mask leaves:

- a grid step owns one `block` of rows (queries in the forward and dq, keys
  in dk/dv) and keeps the other side RESIDENT in VMEM, `major` rows of it
  (the whole sequence wherever it fits, so the grid has one step per block and
  K/V are fetched once per head; a longer sequence walks `major`-sized pieces
  in the innermost grid dimension, carrying the softmax state in scratch);
- inside the step a loop walks the resident side in `chunk`-row pieces, and
  only over the LIVE ones: the loop's bounds come from the mask (`live_chunks`:
  the causal diagonal, the gpt-neo local window, the ring path's traced
  offset) and from the batch row's own padding (`key_range`: its first and
  one-past-last valid key, two int32 scalars a row in SMEM; `valid_chunks`
  drops the key chunks of the forward and dq that hold no valid key,
  `valid_block` empties the loop of a dk/dv block that holds none), so a dead
  chunk costs neither a fetch nor a step. A query block the band gave chunks
  keeps at least one, so padding never turns a finite log-sum-exp into
  M_INIT. EXACT: a skipped chunk held only scores of MASK_VAL, and for a query
  with a valid key in its band their weight is `exp(-1e9 - m)`, 0 in float32
  (in the forward `alpha` wipes `l` and `acc` when the first real chunk
  arrives; in the backward `p = exp(-1e9 - lse)` is 0): output, lse, dq, dk,
  dv are what the band's bounds alone give, to the bit. A query that sees
  nothing but padding emitted a meaningless uniform mix before and emits
  another one (over fewer masked keys); no loss reads it and its cotangents
  are zero (tests/test_flash_blocks.py holds both claims);
- every live chunk takes the one body: the band's compare-and-select on the
  f32 scores (a second, mask-free body for the chunks the mask keeps whole
  measured SLOWER: three loops a step for one, PERF.md §6, PR 27), `scale` on
  the f32 scores, key
  validity (left padding in PPO, right padding in ILQL) as one additive f32
  row per chunk;
- custom VJP with two backward kernels that recompute P from the saved
  log-sum-exp instead of storing probabilities; dk/dv works on the TRANSPOSED
  score tile (keys down the sublanes), so its products need no transposed
  operand and the per-query log-sum-exp is a row.

The sizes follow the call's length (`pick_block`; the measurements behind its
rule are in PERF.md §6, PR 27). All matmuls ACCUMULATE in fp32 via
preferred_element_type (multiplies run at the MXU's native bf16 granularity,
same precision class as XLA's default einsum path on TPU); inputs may be
bf16. Interpret mode (CPU) is auto-selected off-TPU so the same code path is
unit-testable in CI (tests/test_flash_blocks.py).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.parallel.mesh import partitioned

M_INIT = -1e30  # running-max init (finite: fully-masked rows degrade to
# uniform attention exactly like the XLA path's -1e9 bias)
MASK_VAL = -1e9
RESIDENT_ROWS = 2048  # rows of the resident side of a grid step (K, V; Q, dO): 1 MiB an operand at 256-wide bf16 heads


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def one_device_tpu() -> bool:
    """The rule every model-layer kernel gate in ops/ shares: a TPU backend
    and a mesh that is not `partitioned()`. No pallas_call here is under
    shard_map, and a jit over more than one device refuses to lower one
    ("Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map", jax 0.9) — so there the XLA paths stand until the
    kernels are wrapped (ROADMAP A6). Ring attention calls the flash kernel
    per shard inside its own shard_map and gates on ``auto_flash_ok``
    alone."""
    return jax.default_backend() == "tpu" and not partitioned()


class FlashBlocks(NamedTuple):
    """The three sizes of a call. `block`: rows one grid step owns (queries in
    the forward and dq, keys in dk/dv). `major`: rows of the other side a
    step holds resident. `chunk`: rows of it one loop step computes."""

    block: int
    major: int
    chunk: int


def pick_block(q_len: int) -> FlashBlocks:
    """The sizes of a call, from its length alone. A length that 128 does not
    divide keeps one whole-length chunk. Otherwise block = chunk = the widest
    of 512/256/128 that divides the length, and the other side resident up to
    RESIDENT_ROWS. Measured (PERF.md §6, PR 27): a tile's time is mostly its
    two row reductions and the latency around them, not its products, so a
    wide chunk is as fast as a narrow one or faster even where it computes
    more pairs above the diagonal."""
    if q_len % 128:
        return FlashBlocks(q_len, q_len, q_len)
    size = next(s for s in (512, 256, 128) if q_len % s == 0)
    major = max(m for m in range(size, min(RESIDENT_ROWS, q_len) + 1, size) if q_len % m == 0)
    return FlashBlocks(size, major, size)


def auto_flash_ok(q_len: int) -> bool:
    """The shared auto-routing gate: a real TPU backend (interpret-mode
    pallas is far slower than einsum) and a long 128-aligned sequence. Used
    by both the model layer (which adds ``one_device_tpu``) and the
    ring-attention per-chunk path so the eligibility rule and the block
    choice cannot drift apart."""
    return jax.default_backend() == "tpu" and q_len >= 256 and q_len % 128 == 0


# ---------------------------------------------------------------------------
# Liveness: which chunks a block's loop visits
# ---------------------------------------------------------------------------


def mask_band(doff, causal: bool, window: int, keys_own_block: bool = False):
    """The mask as a band: a pair is kept iff lower <= c - r <= upper, with r
    the row of the side that owns the grid step's block and c the row of the
    side the loop chunks; None is unbounded. `doff` shifts key positions into
    the query frame (k_global = k + doff): zero for ordinary self-attention,
    the chunk displacement for ring-attention blocks, then a traced scalar.
    Forward and dq own queries (c a key: c + doff <= r, c + doff > r -
    window); dk/dv owns keys (c a query)."""
    if keys_own_block:
        return (doff if causal else None), (doff + window - 1 if window > 0 else None)
    return (1 - window - doff if window > 0 else None), (-doff if causal else None)


def _max(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _min(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _div0(x, d: int, ceil: bool = False):
    """floor or ceil of max(x, 0) / d, for a python int or a traced scalar."""
    x = _max(x, 0) + (d - 1 if ceil else 0)
    return x // d if isinstance(x, int) else jax.lax.div(x, jnp.int32(d))


def live_chunks(start, band, *, block: int, chunk: int, count: int):
    """(lo, hi) for the block whose first row is `start`, counted from the
    first row of chunk 0: of the chunks [0, count) exactly those in [lo, hi)
    hold a pair the band keeps. Exact: c - r takes every value between the
    rectangle's corners. The one rule the kernels' loop bounds,
    `kept_pair_share` and the tests share."""
    lower, upper = band
    lo, hi = 0, count
    if upper is not None:
        hi = _min(_div0(start + block + upper, chunk, ceil=True), hi)
    if lower is not None:
        lo = _min(_div0(start + lower, chunk), hi)
    return lo, hi


def key_range(kmask):
    """[2, b] int32 from the key validity [b, 1, T]: each batch row's first
    valid key and one past its last (left padding in PPO raises the first,
    right padding in ILQL lowers the second; a hole in between stays the
    bias's business, so the range is conservative for any mask). A row with
    no valid key gets the whole [0, T): the band's bounds alone. The one
    place the kernels' bounds read the mask: the tests force it whole to get
    the kernels without the skip."""
    valid = kmask[:, 0, :] > 0.5
    T = valid.shape[-1]
    pos = jnp.arange(T, dtype=jnp.int32)
    first = jnp.min(jnp.where(valid, pos, T), axis=-1)
    end = jnp.max(jnp.where(valid, pos + 1, 0), axis=-1)
    return jnp.stack([jnp.where(end > 0, first, 0), jnp.where(end > 0, end, T)]).astype(jnp.int32)


def valid_chunks(bounds, first, end, *, chunk: int):
    """The band's [lo, hi) of a block that owns queries (forward, dq),
    narrowed to the chunks that hold a valid key: keys [first, end) are the
    row's valid range, counted like `live_chunks`' start from the first row of
    chunk 0. `lo` rises to the chunk of the first valid key, `hi` falls to the
    chunk after the last — but a block the band gave chunks keeps at least
    one, so no row that had a finite log-sum-exp loses it (a row left with
    lse = M_INIT would meet exp(s + 1e30) in a backward kernel whose
    rectangle differs). No chunk with a kept pair on a valid key is ever
    outside the result."""
    lo, hi = bounds
    lo_valid = _min(_max(lo, _div0(first, chunk)), _max(hi - 1, lo))
    hi_valid = _max(_min(hi, _div0(end, chunk, ceil=True)), _min(lo_valid + 1, hi))
    return lo_valid, hi_valid


def valid_block(bounds, start, first, end, *, block: int):
    """The band's [lo, hi) of a block that owns keys [start, start + block)
    (dk/dv), emptied where none of them is in the valid range [first, end):
    every term of dk and dv there is a product with an exact 0. The loop over
    the query chunks is otherwise the band's: a padding query still sees
    valid keys under right padding."""
    lo, hi = bounds
    holds_valid = (first < start + block) & (end > start)
    if isinstance(holds_valid, (bool, int)):
        return (lo, hi) if holds_valid else (lo, lo)
    return lo, jnp.where(holds_valid, hi, lo)


def pad_dead_chunks(first, end, q_len: int, blocks: FlashBlocks, causal: bool = True, window: int = 0):
    """(dead, live) for the forward of one call at offset 0: the chunks the
    band keeps live over the call's blocks and `major` pieces, and how many of
    them the valid key range [first, end) takes out — by `live_chunks` and
    `valid_chunks`, as the kernel's loop counts them. `first` and `end` may be
    python ints or traced [b] vectors (then `dead` is one a row)."""
    block, major, chunk = blocks
    band = mask_band(0, causal, window)
    dead = live = 0
    for piece in range(0, q_len, major):
        for start in range(0, q_len, block):
            lo, hi = live_chunks(start - piece, band, block=block, chunk=chunk, count=major // chunk)
            if hi > lo:
                lo_valid, hi_valid = valid_chunks((lo, hi), first - piece, end - piece, chunk=chunk)
                live += hi - lo
                dead = dead + (hi - lo) - (hi_valid - lo_valid)
    return dead, live


def kept_pair_share(q_len: int, blocks: FlashBlocks, causal: bool = True, window: int = 0) -> float:
    """Pairs the mask keeps over pairs the live chunks compute, for one call
    at offset 0 (padding is data, so it does not count). By symmetry the
    same for the three kernels. A host float from shapes."""
    band = mask_band(0, causal, window)
    computed = 0
    for start in range(0, q_len, blocks.block):
        lo, hi = live_chunks(start, band, block=blocks.block, chunk=blocks.chunk, count=q_len // blocks.chunk)
        computed += blocks.block * (hi - lo) * blocks.chunk
    span = window if 0 < window < q_len else q_len  # keys the last query sees
    kept = (span * (span + 1) // 2 + (q_len - span) * span) if causal else q_len * q_len
    return kept / computed


# ---------------------------------------------------------------------------
# Pallas plumbing
# ---------------------------------------------------------------------------


def _vmem_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _smem_spec():
    """A whole small operand in SMEM: the (1, 1) traced ring-chunk offset, the
    [2, b] valid key range of the batch rows."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _compiler_params(interpret, semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=None):
    """Mark the (bh, block) grid dims parallel so Mosaic pipelines across
    grid steps instead of serializing them; only the innermost dim (the walk
    over `major` pieces, one step where the sequence is resident) is
    order-dependent. The fused-logprob kernels pass their own two-dim
    semantics. `vmem_limit_bytes` lifts the compiler's scoped-VMEM
    limit (16 MiB) for a kernel whose blocks need more; None leaves the
    default."""
    if interpret:
        return {}
    extra = {} if vmem_limit_bytes is None else {"vmem_limit_bytes": int(vmem_limit_bytes)}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=semantics, **extra)}


# ---------------------------------------------------------------------------
# What the three kernels share
# ---------------------------------------------------------------------------


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    """a @ b.T: both operands contract their head dimension."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _band_setup(off_ref, range_ref, i_block, i_major, *, scale, causal, window, blocks, heads, keys_own_block):
    """The block's live chunks [lo, hi) within this step's `major` piece (the
    band's, less what the valid key range of the step's batch row takes out:
    grid dimension 0 walks `heads` heads a row), and
    `scores(x_block, x_chunk, bias, j)`: the [block, chunk] f32 scores of
    local chunk j, scaled, plus the additive key-validity bias (a [1, chunk]
    row where the chunk side is keys, a [block, chunk] tile where the block
    side is), with the pairs outside the band at MASK_VAL — shared by the
    three kernels so their masking can never desynchronize."""
    block, major, chunk = blocks
    doff = off_ref[0, 0].astype(jnp.int32)
    lower, upper = band = mask_band(doff, causal, window, keys_own_block)
    # the block's first row, counted from the first row of this major piece
    start = i_block * block - i_major * major
    bounds = live_chunks(start, band, block=block, chunk=chunk, count=major // chunk)
    row = jax.lax.div(pl.program_id(0), jnp.int32(heads))
    first, end = range_ref[0, row], range_ref[1, row]
    if keys_own_block:
        bounds = valid_block(bounds, i_block * block, first, end, block=block)
    else:
        bounds = valid_chunks(bounds, first - i_major * major, end - i_major * major, chunk=chunk)
    # c - r of a tile is this constant difference of iotas plus a scalar
    rel = jax.lax.broadcasted_iota(jnp.int32, (block, chunk), 1) - jax.lax.broadcasted_iota(
        jnp.int32, (block, chunk), 0
    )

    def scores(x_block, x_chunk, bias, j):
        s = _mm_nt(x_block, x_chunk) * scale + bias
        shift = j * chunk - start
        if lower is not None:
            s = jnp.where(rel >= lower - shift, s, MASK_VAL)
        if upper is not None:
            s = jnp.where(rel <= upper - shift, s, MASK_VAL)
        return s

    return bounds, scores


def _across_major(i_major, n_major, scratch, fresh, run, finish):
    """Carry `run`'s state over the `major` pieces of a long sequence in
    scratch; a resident sequence (one piece) needs none."""
    if n_major == 1:
        finish(run(fresh))
        return

    @pl.when(i_major == 0)
    def _():
        for ref, val in zip(scratch, fresh):
            ref[...] = val

    out = run(tuple(ref[...] for ref in scratch))
    for ref, val in zip(scratch, out):
        ref[...] = val
    pl.when(i_major == n_major - 1)(lambda: finish(out))


# Index maps over the grid (bh, block, major piece): the side that owns the
# step's block, the resident side, and the block side's [BH, 1, T] row vectors.
def _own(bh, i, im):
    return bh, i, 0


def _resident(bh, i, im):
    return bh, im, 0


def _own_row(bh, i, im):
    return bh, 0, i


def _resident_kv(group: int):
    """K and V as the forward and dq hold them resident: query head `bh` reads
    K/V head `bh // group`. Consecutive grid steps of one group name the same
    block, so it is fetched once a group. A group of 1 is `_resident` itself."""
    return _resident if group == 1 else (lambda bh, i, im: (bh // group, im, 0))


def _rows(j, chunk):
    return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(off_ref, range_ref, kbias_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                scale, causal, window, blocks, n_major, heads):
    block, major, chunk = blocks
    i_major = pl.program_id(2)
    bounds, scores = _band_setup(
        off_ref, range_ref, pl.program_id(1), i_major, scale=scale, causal=causal, window=window, blocks=blocks,
        heads=heads, keys_own_block=False,
    )
    q = q_ref[0]

    def step(j, carry):
        m_prev, l_prev, acc = carry
        rows = _rows(j, chunk)
        s = scores(q, k_ref[0, rows, :], kbias_ref[0, j], j)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        return m_cur, l_cur, acc * alpha + _mm(p.astype(v_ref.dtype), v_ref[0, rows, :])

    def finish(carry):
        # Rows whose every chunk was dead (an entirely-future ring chunk)
        # have l == 0: emit zeros with lse = M_INIT so the chunk vanishes
        # from any log-sum-exp combination instead of NaN-ing.
        m, l, acc = carry
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc * (1.0 / l_safe)).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l[:, 0] > 0, m[:, 0] + jnp.log(l_safe[:, 0]), M_INIT)

    fresh = (
        jnp.full((block, 1), M_INIT, jnp.float32),
        jnp.zeros((block, 1), jnp.float32),
        jnp.zeros((block, q_ref.shape[-1]), jnp.float32),
    )
    _across_major(i_major, n_major, scratch, fresh, lambda c: jax.lax.fori_loop(*bounds, step, c), finish)


def _geometry(T, blocks):
    block, major, chunk = blocks
    if T % block or T % major or major % chunk:
        raise ValueError(f"seq len {T} not divisible by blocks {tuple(blocks)}")
    return T // block, T // major, major // chunk


def _key_bias(kmask):
    """[b, 1, T] f32: 0 at a valid key, MASK_VAL at padding."""
    return jnp.where(kmask > 0.5, 0.0, MASK_VAL).astype(jnp.float32)


def _by_chunk(x, chunk):
    """[n, 1, T] rows as [n, T // chunk, 1, chunk]: a loop step's row is
    `ref[0, j]`, an index on an untiled dimension."""
    return x.reshape(x.shape[0], x.shape[-1] // chunk, 1, chunk)


def _check_layout(BH, T, D, blocks, interpret):
    if not interpret:
        # GL006 provenance: the _vmem_spec shapes of the three kernels must
        # agree with the canonical tiling.flash_block_layout description —
        # validating the layout before compiling keeps wrapper and validator
        # from drifting (the PR 3 Mosaic tile-rule crash class). Interpret
        # mode has no Mosaic tile constraints, so tiny CPU test shapes stay
        # legal.
        from trlx_tpu.ops.tiling import check_layout, flash_block_layout

        check_layout(flash_block_layout(BH, T, D, blocks))


def _fwd(q, k, v, kmask, off, scale, causal, window, blocks, interpret):
    BH, T, D = q.shape
    block, major, chunk = blocks
    n_block, n_major, per_major = _geometry(T, blocks)
    H = BH // kmask.shape[0]
    kv = _resident_kv(BH // k.shape[0])
    _check_layout(BH, T, D, blocks, interpret)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, blocks=blocks, n_major=n_major, heads=H
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",  # how a device trace names the call
        grid=(BH, n_block, n_major),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((1, per_major, 1, chunk), lambda bh, i, im: (bh // H, im, 0, 0)),
            _vmem_spec((1, block, D), _own),
            _vmem_spec((1, major, D), kv),
            _vmem_spec((1, major, D), kv),
        ],
        out_specs=[_vmem_spec((1, block, D), _own), _vmem_spec((1, 1, block), _own_row)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[_scratch((block, 1)), _scratch((block, 1)), _scratch((block, D))] if n_major > 1 else [],
        interpret=interpret,
        **_compiler_params(interpret),
    )(off, key_range(kmask), _by_chunk(_key_bias(kmask), chunk), q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(off_ref, range_ref, kbias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *scratch, scale, causal, window, blocks, n_major, heads):
    block, major, chunk = blocks
    i_major = pl.program_id(2)
    bounds, scores = _band_setup(
        off_ref, range_ref, pl.program_id(1), i_major, scale=scale, causal=causal, window=window, blocks=blocks,
        heads=heads, keys_own_block=False,
    )
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]

    def step(j, carry):
        rows = _rows(j, chunk)
        k = k_ref[0, rows, :]
        s = scores(q, k, kbias_ref[0, j], j)
        p = jnp.exp(s - lse)
        dp = _mm_nt(do, v_ref[0, rows, :])
        ds = p * (dp - delta)
        return (carry[0] + _mm(ds.astype(k.dtype), k),)

    def finish(carry):
        dq_ref[0] = (carry[0] * scale).astype(dq_ref.dtype)

    fresh = (jnp.zeros((block, q_ref.shape[-1]), jnp.float32),)
    _across_major(i_major, n_major, scratch, fresh, lambda c: jax.lax.fori_loop(*bounds, step, c), finish)


def _bwd_dkv_kernel(off_ref, range_ref, kbias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *scratch, scale, causal, window, blocks, n_major, heads, group):
    """The mirror image: a key block resident, a loop over the live query
    chunks, on the transposed score tile [keys, queries]. Grouped keys: the
    innermost grid dimension walks the group's query heads (and within each
    its major pieces), and dk, dv sum over them in the same scratch."""
    block, major, chunk = blocks
    i_inner = pl.program_id(2)
    i_major = i_inner if group == 1 else 0 if n_major == 1 else jax.lax.rem(i_inner, jnp.int32(n_major))
    bounds, scores = _band_setup(
        off_ref, range_ref, pl.program_id(1), i_major, scale=scale, causal=causal, window=window, blocks=blocks,
        heads=heads, keys_own_block=True,
    )
    k = k_ref[0]
    v = v_ref[0]
    kbias = jnp.broadcast_to(kbias_ref[0, 0][:, None], (block, chunk))

    def step(j, carry):
        dk_acc, dv_acc = carry
        rows = _rows(j, chunk)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        s = scores(k, q, kbias, j)  # [keys, queries]
        p = jnp.exp(s - lse_ref[0, j])
        dp = _mm_nt(v, do)
        ds = p * (dp - delta_ref[0, j])
        return dk_acc + _mm(ds.astype(q.dtype), q), dv_acc + _mm(p.astype(do.dtype), do)

    def finish(carry):
        dk_ref[0] = (carry[0] * scale).astype(dk_ref.dtype)
        dv_ref[0] = carry[1].astype(dv_ref.dtype)

    fresh = (jnp.zeros((block, k_ref.shape[-1]), jnp.float32),) * 2
    _across_major(i_inner, group * n_major, scratch, fresh, lambda c: jax.lax.fori_loop(*bounds, step, c), finish)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_lse(q, k, v, kmask, off, scale, causal, window, blocks, interpret):
    """Fused attention returning (o, lse). Exposing lse makes per-chunk calls
    exactly combinable (ring attention): downstream use of lse feeds a dlse
    cotangent which the backward folds into delta."""
    return _fwd(q, k, v, kmask, off, scale, causal, window, blocks, interpret)


def _flash_lse_fwd(q, k, v, kmask, off, scale, causal, window, blocks, interpret):
    o, lse = _fwd(q, k, v, kmask, off, scale, causal, window, blocks, interpret)
    return (o, lse), (q, k, v, kmask, off, o, lse)


def _flash_lse_bwd(scale, causal, window, blocks, interpret, res, cts):
    do, dlse = cts
    q, k, v, kmask, off, o, lse = res
    BH, T, D = q.shape
    H = BH // kmask.shape[0]
    group = BH // k.shape[0]
    kv = _resident_kv(group)
    block, major, chunk = blocks
    n_block, n_major, per_major = _geometry(T, blocks)
    # d s_ij = p_ij (dp_ij - delta_i); with lse also an output,
    # d lse / d s_ij = p_ij, so delta picks up an extra -dlse_i term.
    delta = (
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]
        - dlse.astype(jnp.float32)
    )  # [BH, 1, T]
    _check_layout(BH, T, D, blocks, interpret)

    common = dict(scale=scale, causal=causal, window=window, blocks=blocks, n_major=n_major)
    grid = (BH, n_block, n_major)
    scratch = lambda n, pieces=n_major: [_scratch((block, D))] * n if pieces > 1 else []

    valid = key_range(kmask)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, heads=H, **common),
        name="flash_bwd_dq",
        grid=grid,
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((1, per_major, 1, chunk), lambda bh, i, im: (bh // H, im, 0, 0)),
            _vmem_spec((1, block, D), _own),
            _vmem_spec((1, major, D), kv),
            _vmem_spec((1, major, D), kv),
            _vmem_spec((1, block, D), _own),
            _vmem_spec((1, 1, block), _own_row),
            _vmem_spec((1, 1, block), _own_row),
        ],
        out_specs=[_vmem_spec((1, block, D), _own)],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), q.dtype)],
        scratch_shapes=scratch(1),
        interpret=interpret,
        **_compiler_params(interpret),
    )(off, valid, _by_chunk(_key_bias(kmask), chunk), q, k, v, do, lse, delta)[0]

    # k-side: a step owns a key block; queries, dO and their row vectors
    # (one [1, chunk] row a loop step: `ref[0, j]`) are the resident side.
    # Grouped keys: the grid's first dimension is the K/V heads, and the
    # innermost walks the `group` query heads of each (times the major pieces),
    # summing dk and dv in scratch: the result is [b * kv heads, T, D].
    if group == 1:
        resident, rows, key_heads = _resident, (lambda bh, i, im: (bh, im, 0, 0)), H
    else:
        resident = lambda bh, i, im: (bh * group + im // n_major, im % n_major, 0)
        rows = lambda bh, i, im: (bh * group + im // n_major, im % n_major, 0, 0)
        key_heads = H // group
    row_spec = _vmem_spec((1, per_major, 1, chunk), rows)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, heads=key_heads, group=group, **common),
        name="flash_bwd_dkv",
        grid=(k.shape[0], n_block, group * n_major),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((1, 1, block), lambda bh, i, im: (bh // key_heads, 0, i)),
            _vmem_spec((1, major, D), resident),
            _vmem_spec((1, block, D), _own),
            _vmem_spec((1, block, D), _own),
            _vmem_spec((1, major, D), resident),
            row_spec,
            row_spec,
        ],
        out_specs=[_vmem_spec((1, block, D), _own), _vmem_spec((1, block, D), _own)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=scratch(2, group * n_major),
        interpret=interpret,
        **_compiler_params(interpret),
    )(off, valid, _key_bias(kmask), q, k, v, do, _by_chunk(lse, chunk), _by_chunk(delta, chunk))

    return dq, dk, dv, jnp.zeros_like(kmask), jnp.zeros_like(off)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray,
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    offset=None,
    blocks: Optional[FlashBlocks] = None,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
):
    """Fused causal attention over q [b, T, n_head, head_dim] and k, v
    [b, T, kv heads, head_dim]. Grouped keys (kv heads < n_head, dividing it):
    K/V head j serves query heads [j * g, (j + 1) * g) through the kernels'
    index maps, K and V are never repeated, and dk, dv come back at the kv
    heads, summed over each group inside the dk/dv kernel.

    kv_mask: [b, T] key-slot validity (0 at left-padding). `window > 0`
    restricts keys to the trailing window (gpt-neo local layers). `offset`
    (python int or traced scalar) shifts key positions into the query frame
    — ring attention passes the visiting chunk's displacement. With
    `return_lse` the per-row log-sum-exp comes back as [b, h, T] for exact
    cross-chunk combination. `blocks` is `pick_block`'s choice unless a test
    or a microbench passes its own; the sequence length must divide by its
    block and major, and major by chunk.
    """
    b, T, h, d = q.shape
    blocks = pick_block(T) if blocks is None else FlashBlocks(*blocks)
    if interpret is None:
        interpret = _interpret_default()
    # float32 deliberately: `off` is a differentiable custom_vjp operand
    # (int32 would need float0 cotangent plumbing) and chunk displacements
    # are exact in float32 far beyond any real sequence length (2^24).
    off = jnp.asarray(0.0 if offset is None else offset, jnp.float32).reshape(1, 1)

    if h % k.shape[2] or v.shape[2] != k.shape[2]:
        raise ValueError(f"{k.shape[2]} key / {v.shape[2]} value heads do not group {h} query heads")

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], T, d)

    o, lse = _flash_lse(
        to_bh(q), to_bh(k), to_bh(v), kv_mask.astype(jnp.float32)[:, None, :],
        off, float(scale), bool(causal), int(window), blocks, bool(interpret),
    )
    o = o.reshape(b, h, T, d).transpose(0, 2, 1, 3)
    if return_lse:
        return o, lse.reshape(b, h, T)
    return o

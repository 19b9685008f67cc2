"""Pallas TPU flash-decode attention over the (int8) KV cache.

Decode is HBM-bound on cache reads. The XLA einsum path for a decode step
dequantizes the int8 cache into bf16 k/v before the contraction
(trlx_tpu/models/lm.py Attention decode branch). This kernel reads the int8
cache DIRECTLY and folds dequantization into the attention algebra, so the
HBM traffic is exactly the int8 bytes:

    scores[t] = ks[t] * dot(K_int8[t, :], q) * scale       (per-key scale
    out[d]    = sum_t softmax(scores)[t] * vs[t] * V_int8[t, d]   factors out)

A decode step is a matrix-VECTOR product per head: the query has one row,
so there is nothing for the MXU to amortize, and Mosaic has no batched
matvec (a `dot_general` whose left operand has no free dimension, or whose
batch dimension sits in the middle of the right operand, does not lower —
the failure the [bt, h, d] revision of this kernel died of on jax 0.9).
The contractions therefore run on the VPU, in the one layout that needs no
relayout of the cache:

- the cache [B, T, h, d] is viewed as [B, T, h*d] (a free reshape) and
  streams through VMEM in (bt, h*d) blocks — lane-dense for any head count,
  no int8 sublane padding of a 16-row head plane;
- head `hh` is the lane-aligned static slice [:, hh*d:(hh+1)*d] (d % 128
  == 0 is the eligibility rule), so per head the scores are a broadcast
  multiply by q and a lane reduction → a [bt, 1] column, and the value
  contraction is a lane-broadcast multiply and a sublane reduction;
- the per-key scales are read in their natural [bt, h] cache layout and the
  online softmax runs on a [bt, h] matrix (keys on sublanes, heads on
  lanes) with [1, h] running max/sum scratch.

Grid (batch, T-blocks); the T walk is the online-softmax accumulation order
and stays sequential, and the final (possibly partial) block is masked
in-kernel, so cache lengths need not be tile-aligned. The additive bias row
([B, T], the einsum path's mask) arrives as [B, 1, T] — a (1, 1, bt) block
of it is tile-legal where a (1, bt) block of [B, T] is not — and is turned
into the [bt, 1] column the score matrix needs by a masked lane reduction.
The block layouts live in tiling.decode_block_layout / paged_decode_layout:
the validator and these wrappers read the SAME description.

Routing (`*_eligible` then `*_supported`) is static: the measured verdict
DECODE_KERNEL_ROUTED (today: False — XLA's fused einsum is faster on the
chip), backend, a one-device mesh, the head layout, and the CPU-side tile
check. On a TPU backend an eligible shape that then fails to lower is an
ERROR naming the kernel and the shape (tiling.require_lowering) — never a
quiet einsum fallback.

Inference-only (decode never differentiates) — no VJP. The reference has no
counterpart (HF `generate` materializes fp16 caches, reference:
trlx/model/accelerate_base_model.py:105-116). Interpret mode keeps CPU
coverage (tests/test_decode_attention.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.flash_attention import (
    M_INIT,
    MASK_VAL,
    _interpret_default,
    _scratch,
    one_device_tpu,
)
from trlx_tpu.ops.flash_attention import _compiler_params as _grid_compiler_params
from trlx_tpu.ops.flash_attention import _vmem_spec as _vmem

# Default KV T-block: 128 slots/block is one lane tile of keys — the
# double-buffered int8 k+v blocks at the GPT-J head layout are 2 x 512 KB
# each and the per-head fp32 working set stays in vregs.
BLOCK_T = 128


def pick_t_block(cache_len: int, block_t: int = BLOCK_T) -> int:
    """T-block size for a cache of `cache_len` slots: one full block for
    short caches (a block equal to the array dim is always tile-legal, even
    unaligned), else the fixed BLOCK_T with the tail masked in-kernel."""
    return cache_len if cache_len <= block_t else block_t


# batch parallel; the T-block walk is the online-softmax accumulation order
# and must stay sequential.
_compiler_params = functools.partial(
    _grid_compiler_params, semantics=("parallel", "arbitrary")
)


def _decode_kernel(*refs, scale, T, bt, h, d, quant, paged):
    """One T-block of online-softmax decode attention, all heads.

    Refs (blocks): q [1, 1, h*d]; k/v [1, bt, h*d] (int8 or compute dtype);
    ks/vs [1, bt, h] per-key scales (quant only); bias [1, 1, bt] additive
    mask row; out [1, 1, h*d]; scratch acc [1, h*d], m/l [1, h] fp32. The
    paged variant's leading scalar-prefetched block table is consumed by
    the BlockSpec index maps, never by the body: the virtual walk `it` is
    all the tail masking needs."""
    if paged:
        refs = refs[1:]
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, bias_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, bias_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    it = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(it == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def head(hh):
        return slice(hh * d, (hh + 1) * d)

    # scores[t, hh] = sum_d k[t, hh, d] * q[hh, d]: per head a [bt, 1]
    # column, placed into lane hh of the [bt, h] score matrix.
    lane_h = jax.lax.broadcasted_iota(jnp.int32, (bt, h), 1)
    scores = jnp.zeros((bt, h), jnp.float32)
    for hh in range(h):
        k_h = k_ref[0, :, head(hh)].astype(jnp.float32)
        q_h = q_ref[0, :, head(hh)].astype(jnp.float32)
        s_col = jnp.sum(k_h * q_h, axis=-1, keepdims=True)
        scores = jnp.where(lane_h == hh, s_col, scores)
    scores = scores * scale
    if quant:
        scores = scores * ks_ref[0].astype(jnp.float32)  # factored-out k scale
    # Bias row [1, bt] → column [bt, 1]: a masked lane reduction over the
    # diagonal (exact: one term plus zeros).
    diag = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (bt, bt), 1
    )
    scores = scores + jnp.sum(jnp.where(diag, bias_ref[0], 0.0), axis=-1, keepdims=True)
    # Tail mask: slots past the cache end exist only as block padding. Their
    # memory is undefined (int8 garbage / non-finite scale garbage), so the
    # score is REPLACED, not biased, and p is re-zeroed after the exp (a
    # fully-masked row has m == MASK_VAL, where exp(MASK_VAL - m) == 1).
    ragged = T % bt != 0
    if ragged:
        in_range = it * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0) < T
        scores = jnp.where(in_range, scores, MASK_VAL)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(scores - m_cur)
    if ragged:
        p = jnp.where(in_range, p, 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
    m_ref[...] = m_cur
    if quant:
        # per-key int8 v scale, folded into the weights — zeroed on tail
        # padding, where the scale memory is undefined (0 * NaN would
        # poison the contraction that p's zeros alone cannot protect).
        vs = vs_ref[0].astype(jnp.float32)
        p = p * (jnp.where(in_range, vs, 0.0) if ragged else vs)
    # out[hh, d] += sum_t p[t, hh] * v[t, hh, d]. Tail-padding v rows are
    # undefined memory: zero them so they cannot reach the accumulator even
    # multiplied by a zero weight.
    for hh in range(h):
        v_h = v_ref[0, :, head(hh)].astype(jnp.float32)
        if ragged:
            v_h = jnp.where(in_range, v_h, 0.0)
        pv = jnp.sum(p[:, hh : hh + 1] * v_h, axis=0, keepdims=True)
        acc_ref[:, head(hh)] = acc_ref[:, head(hh)] * alpha[:, hh : hh + 1] + pv

    @pl.when(it == nt - 1)
    def _():
        # l == 0 cannot happen for in-range keys (even fully-masked rows sum
        # positive p), but guard the division like the flash kernel does.
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        for hh in range(h):
            o_ref[0, :, head(hh)] = (
                acc_ref[:, head(hh)] / l_safe[:, hh : hh + 1]
            ).astype(o_ref.dtype)


def _scratch_shapes(h, d):
    return [
        _scratch((1, h * d)),  # fp32 output accumulator
        _scratch((1, h)),  # running max
        _scratch((1, h)),  # running sum
    ]


# The routing verdict, from the chip (TPU v5 lite, PR 21; PERF.md has the
# table): at the flagship decode shape [B=32, T=1024, h=16, d=256] these
# kernels take 1585 us (int8 cache) / 2408 us (bf16) per layer-step against
# 584 / 793 us for XLA's fused dequantize-einsum, which already runs at
# 57% / 83% of the HBM floor. A VPU matvec does not win anywhere measured,
# so no shape is routed to the kernels; they stay compiled, run, compared
# and timed by chip_smoke.py so that ROADMAP A3 has a baseline to beat and
# one constant to flip.
DECODE_KERNEL_ROUTED = False


def decode_attn_eligible(n_head: int, head_dim: int, cache_len: int, quant: bool) -> bool:
    """Static routing: the DECODE_KERNEL_ROUTED verdict above, then a TPU
    backend with a one-device mesh and a head layout whose per-head lane
    slices are tile-aligned (head_dim % 128; the n_head % 8 rule keeps tiny
    test models on the einsum path). Any cache length is eligible (masked
    tail block). `cache_len`/`quant` stay in the signature as the routing
    key the caller builds."""
    return (
        DECODE_KERNEL_ROUTED
        and one_device_tpu()
        and head_dim % 128 == 0
        and n_head % 8 == 0
    )


_PROBE_CACHE = {}


def decode_attn_supported(B: int, T: int, h: int, d: int, quant: bool, dtype=jnp.bfloat16) -> bool:
    """Tile-check verdict (and, on TPU, must-lower step) for the kernel at
    this call-site shape — tiling.routing_verdict, cached per shape key for
    the life of the process."""
    from trlx_tpu.ops.tiling import decode_block_layout, routing_verdict

    def lower():
        s = jax.ShapeDtypeStruct
        kv = s((B, T, h, d), jnp.int8 if quant else dtype)
        sc = s((B, T, h), jnp.float32) if quant else None

        def probe(q, k, v, ks, vs, bias):
            return decode_attention(q, k, v, ks, vs, bias, scale=1.0, interpret=False)

        return probe, s((B, h, d), dtype), kv, kv, sc, sc, s((B, T), jnp.float32)

    return routing_verdict(
        _PROBE_CACHE,
        (B, T, h, d, bool(quant), jnp.dtype(dtype).name, jax.default_backend()),
        "decode-attention",
        f"[B={B}, T={T}, h={h}, d={d}, quant={quant}]",
        decode_block_layout(B, T, h, d, bool(quant)),
        "einsum",
        lower,
    )


def spec_verify_supported(
    n_slots: int, T: int, h: int, d: int, spec_k: int, quant: bool
) -> bool:
    """CPU-runnable legality verdict for the speculative multi-token verify
    window (tiling.spec_verify_layout over the engine's post-scratch-tail
    cache shape). There is no multi-token Pallas kernel yet — the verify
    program runs the einsum attention path, which lowers for any shape — so
    this is a layout blessing, not a routing gate: the engine calls it once
    at arm time and WARNS on an illegal layout so a future kernel port
    inherits a shape that already tiles. `pick_t_block` keeps the T-tail
    masked exactly like the single-token kernel, so any cache length stays
    legal."""
    from trlx_tpu.ops.tiling import is_tile_legal, spec_verify_layout

    return is_tile_legal(
        spec_verify_layout(n_slots, T, h, d, int(spec_k), bool(quant))
    )


def decode_attention(q, k_cache, v_cache, ks, vs, bias_row, *, scale,
                     interpret=None, block_t=None):
    """Single-token flash-decode attention over the cache.

    q: [B, h, d] (this step's query). k_cache/v_cache: [B, T, h, d] — int8
    when ks/vs (per-slot scales [B, T, h]) are given, else the compute
    dtype. bias_row: [B, T] additive fp32 mask row (0 valid / -1e9 invalid —
    the einsum path's bias, one row). Returns [B, 1, h, d] in q.dtype."""
    from trlx_tpu.ops.tiling import decode_block_layout

    B, h, d = q.shape
    T = k_cache.shape[1]
    quant = ks is not None
    interpret = _interpret_default() if interpret is None else interpret
    bt = pick_t_block(T) if block_t is None else block_t

    # The wrapper's specs come from the SAME layout description the tiling
    # validator checks (tiling.decode_block_layout).
    layout = {
        lay.name: lay for lay in decode_block_layout(B, T, h, d, quant, block_t=bt)
    }
    row = lambda b, it: (b, 0, 0)
    walk = lambda b, it: (b, it, 0)
    kv_spec = _vmem(layout["k_cache"].block_shape, walk)
    in_specs = [_vmem(layout["q"].block_shape, row), kv_spec, kv_spec]
    operands = [
        q.reshape(B, 1, h * d),
        k_cache.reshape(B, T, h * d),
        v_cache.reshape(B, T, h * d),
    ]
    if quant:
        sc_spec = _vmem(layout["k_scale"].block_shape, walk)
        in_specs += [sc_spec, sc_spec]
        operands += [ks, vs]
    in_specs.append(_vmem(layout["bias"].block_shape, lambda b, it: (b, 0, it)))
    operands.append(bias_row.astype(jnp.float32)[:, None, :])  # [B, 1, T]
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, T=T, bt=bt, h=h, d=d, quant=quant, paged=False
        ),
        grid=(B, -(-T // bt)),
        in_specs=in_specs,
        out_specs=_vmem(layout["out"].block_shape, row),
        out_shape=jax.ShapeDtypeStruct((B, 1, h * d), q.dtype),
        scratch_shapes=_scratch_shapes(h, d),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*operands)
    return out.reshape(B, 1, h, d)


def paged_decode_eligible(
    n_head: int, head_dim: int, block_size: int, blocks_per_slot: int, quant: bool
) -> bool:
    """Static routing for the block-table-indirect kernel: the same rule as
    ``decode_attn_eligible`` plus a lane-divisible block_size (the bias
    block (1, 1, block_size) is the one strict tile in the paged layout — a
    single-block table is the full-array escape hatch). `quant` stays in
    the signature as part of the routing key."""
    if not decode_attn_eligible(n_head, head_dim, block_size * blocks_per_slot, quant):
        return False
    return block_size % 128 == 0 or blocks_per_slot == 1


def paged_decode_supported(
    n_slots: int,
    n_blocks: int,
    block_size: int,
    blocks_per_slot: int,
    h: int,
    d: int,
    quant: bool,
    dtype=jnp.bfloat16,
) -> bool:
    """Mirror of ``decode_attn_supported`` for the paged kernel; the TPU
    must-lower probe additionally exercises the scalar-prefetch mapping."""
    from trlx_tpu.ops.tiling import paged_decode_layout, routing_verdict

    def lower():
        s = jax.ShapeDtypeStruct
        kv = s((n_blocks, block_size, h, d), jnp.int8 if quant else dtype)
        sc = s((n_blocks, block_size, h), jnp.float32) if quant else None

        def probe(q, k, v, ks, vs, tbl, bias):
            return paged_decode_attention(
                q, k, v, ks, vs, tbl, bias, scale=1.0, interpret=False
            )

        return (
            probe, s((n_slots, h, d), dtype), kv, kv, sc, sc,
            s((n_slots, blocks_per_slot), jnp.int32),
            s((n_slots, blocks_per_slot * block_size), jnp.float32),
        )

    return routing_verdict(
        _PROBE_CACHE,
        (
            "paged", n_slots, n_blocks, block_size, blocks_per_slot, h, d,
            bool(quant), jnp.dtype(dtype).name, jax.default_backend(),
        ),
        "paged decode-attention",
        f"[S={n_slots}, n_blocks={n_blocks}, bs={block_size}, "
        f"bps={blocks_per_slot}, h={h}, d={d}, quant={quant}]",
        paged_decode_layout(
            n_slots, n_blocks, block_size, blocks_per_slot, h, d, bool(quant)
        ),
        "gather-einsum",
        lower,
    )


def paged_decode_attention(q, k_pool, v_pool, ks_pool, vs_pool, block_tables,
                           bias_row, *, scale, interpret=None):
    """Single-token flash-decode attention through a per-slot block table.

    q: [S, h, d] (this step's query per slot). k_pool/v_pool:
    [n_blocks, block_size, h, d] — the ONE shared physical pool, int8 when
    ks_pool/vs_pool (per-token scales [n_blocks, block_size, h]) are given,
    else the compute dtype. block_tables: [S, blocks_per_slot] int32 mapping
    each slot's virtual block walk to physical pool blocks. bias_row:
    [S, T_virt] additive fp32 mask over the slot's VIRTUAL address space
    (T_virt = blocks_per_slot * block_size). Returns [S, 1, h, d] in q.dtype.

    Same kernel body as ``decode_attention``; the only new machinery is the
    scalar-prefetched table: the grid walks (slot, virtual block) and the
    K/V/scale index maps dereference `table[s, it]` so each program DMAs the
    slot's own physical block. T_virt is an exact multiple of block_size, so
    the tail-mask arithmetic in the shared body is compiled out — raggedness
    and dead virtual columns are entirely the bias row's job, exactly like
    the slot-decode path."""
    from trlx_tpu.ops.tiling import paged_decode_layout

    S, h, d = q.shape
    n_blocks, bs = k_pool.shape[:2]
    bps = block_tables.shape[1]
    quant = ks_pool is not None
    interpret = _interpret_default() if interpret is None else interpret

    layout = {
        lay.name: lay
        for lay in paged_decode_layout(S, n_blocks, bs, bps, h, d, quant)
    }
    # Index maps receive the grid indices first and the scalar-prefetched
    # table ref LAST: (s, it, tbl).
    row = lambda s, it, tbl: (s, 0, 0)
    phys = lambda s, it, tbl: (tbl[s, it], 0, 0)
    kv_spec = _vmem(layout["k_pool"].block_shape, phys)
    in_specs = [_vmem(layout["q"].block_shape, row), kv_spec, kv_spec]
    operands = [
        block_tables.astype(jnp.int32),
        q.reshape(S, 1, h * d),
        k_pool.reshape(n_blocks, bs, h * d),
        v_pool.reshape(n_blocks, bs, h * d),
    ]
    if quant:
        sc_spec = _vmem(layout["k_scale"].block_shape, phys)
        in_specs += [sc_spec, sc_spec]
        operands += [ks_pool, vs_pool]
    in_specs.append(_vmem(layout["bias"].block_shape, lambda s, it, tbl: (s, 0, it)))
    operands.append(bias_row.astype(jnp.float32)[:, None, :])  # [S, 1, T_virt]
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, T=bps * bs, bt=bs, h=h, d=d,
            quant=quant, paged=True,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, bps),
            in_specs=in_specs,
            out_specs=_vmem(layout["out"].block_shape, row),
            scratch_shapes=_scratch_shapes(h, d),
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, h * d), q.dtype),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*operands)
    return out.reshape(S, 1, h, d)


def paged_slot_decode_attention(q, k_pool, v_pool, ks_pool, vs_pool,
                                block_tables, slot_mask, *, scale,
                                interpret=None):
    """Slot-mask entry for the paged kernel, mirror of
    ``slot_decode_attention``: the per-slot virtual-cache validity mask
    ``slot_mask`` [S, T_virt] becomes the additive bias row."""
    bias_row = jnp.where(slot_mask.astype(bool), 0.0, -1e9).astype(jnp.float32)
    return paged_decode_attention(
        q, k_pool, v_pool, ks_pool, vs_pool, block_tables, bias_row,
        scale=scale, interpret=interpret,
    )


def slot_decode_attention(q, k_cache, v_cache, ks, vs, slot_mask, *, scale,
                          interpret=None, block_t=None):
    """Slot-aware decode-attention entry for the continuous-batching engine.

    Identical kernel and block layouts as ``decode_attention`` (see
    tiling.slot_decode_layout) — the batch axis is the slot axis, and the
    per-slot cache-validity mask ``slot_mask`` [S, T] (1 = valid key slot,
    covering each slot's own ragged length) is turned into the additive bias
    row the kernel consumes. One compiled program therefore serves every mix
    of live slot lengths; per-slot raggedness is pure data."""
    bias_row = jnp.where(slot_mask.astype(bool), 0.0, -1e9).astype(jnp.float32)
    return decode_attention(
        q, k_cache, v_cache, ks, vs, bias_row,
        scale=scale, interpret=interpret, block_t=block_t,
    )

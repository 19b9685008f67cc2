"""Pallas TPU fused vocab projection + label-logprob / logsumexp / entropy.

The train phase's dominant memory cost is the full [B, T, V] fp32 logits
tensor: every PPO/ILQL loss and logprob pass materializes it in HBM just to
immediately reduce it to three per-token scalars (the label's logprob, the
logsumexp, and the entropy). At the bench GPT-J shape ([8, 832, 50400] fp32
≈ 1.3 GB per forward, doubled by the backward's softmax residuals) that HBM
round-trip is pure waste — the same flash-attention insight (stream the
reduced axis through VMEM with online max/sum accumulation) applies to the
vocab axis verbatim.

This kernel fuses the final projection with the reduction:

    s_k  = x · W[:, k] (+ b_k)           one bv-wide vocab tile at a time
    m, l = online max / sum of exp(s - m)    (flash-style rescaling)
    r    = online sum of exp(s - m) · s      (for the entropy)
    lab  = s_y gathered as the tile streams past the label column

    lse = m + log l;  logprob = lab - lse;  entropy = lse - r / l

so the [N, V] score matrix only ever exists as one [bn, bv] VMEM tile.
The custom VJP recomputes p = exp(s - lse) per tile from the saved
(lse, entropy) row residuals — the analytic cotangent

    ds_k = dlp·(1[k=y] - p_k) + dlse·p_k - dent·p_k·(s_k - E),  E = lse - ent

feeds two accumulation kernels (dx with the V axis innermost; dW/db with
the N axis innermost), so the backward never materializes [N, V] either.

Grid (N-blocks, V-blocks) with the V walk sequential ("arbitrary" — it is
the online-softmax accumulation order); the weight streams in bv-wide tiles
(128-divisible, so ragged GPT-2/J vocab sizes get a partial tail block that
is masked in-kernel).

**The tiles follow the call** (`head_tiles`). A grid step holds one operand
resident and streams the other past it, so the resident tile sets how many
FLOPs each streamed byte feeds, and the chip wants 240 of them a byte
(197e12 / 819e9 on a v5e) before its MXU stops waiting for HBM:

    forward  x[bn, D] resident, W streamed once per row block: 2·bn / itemsize(W)
    dx       the same walk, two products a tile:               4·bn / itemsize(W)
    dw       W[D, bv] resident, x streamed once per vocab tile: 4·bv / itemsize(x)

At 128 rows a bf16 weight byte fed 128 FLOPs: the forward over 7,168 rows
streamed GPT-J's 413 MB head 56 times (23 GB, 28 ms) for 15 ms of products,
and took 31 ms. The rule takes the largest row tile of 512 / 256 / 128 that
(a) keeps every block of the kernel, double-buffered, with its scratch and
the score tile's temporaries inside `VMEM_BUDGET`, shrinking the vocabulary
tile to make room (weight traffic depends on the row tile alone: x's block
stays put through the vocabulary walk), (b) wastes under an eighth of the
rows in padding, and (c) is no larger than the call: fewer rows than a tile
keep the 128-row floor. It reads N, D, V, the operands' itemsizes and
`has_bias`, nothing else: no option, no environment variable. Measured on a
v5e at the benchmark's seven head shapes (PERF.md §6, PR 36): the forward
31 → 18 ms at 7,168 x 4096 x 50,400, within a fifth of its products' time;
256 rows get nearly all of it, 1,024 no more (and lose at D 7168); a
256-wide vocabulary tile costs the forward 14%, so the forward narrows it
last; dx and dw were near their products' time already and gain 4 to 18%.
The scoped-VMEM limit each kernel asks the compiler for is its own blocks'
estimate (`tiling.fused_logprob_vmem_bytes`) with headroom. Block layouts
live in tiling.fused_logprob_block_layout — the validator and this wrapper
read the SAME description, and the routing gate (fused_logprob_supported)
re-checks it at the SAME tiles before the model layer ever traces the
kernel: a tile-illegal shape takes the materialized log_softmax path by
that stated rule, and a shape that passes must lower on a TPU backend or
the run stops with an error naming the kernel and the shape.

Engagement mirrors flash attention: real TPU backend (or explicit
interpret mode for CPU CI parity tests, tests/test_losses.py); tiny test
models stay on the einsum fallback where they are faster.
"""

import functools
import threading
from contextlib import contextmanager
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from trlx_tpu.ops.flash_attention import (
    M_INIT,
    MASK_VAL,
    _interpret_default,
    _scratch,
    one_device_tpu,
)
from trlx_tpu.ops.flash_attention import _compiler_params as _grid_compiler_params
from trlx_tpu.ops.flash_attention import _vmem_spec as _vmem
from trlx_tpu.ops.tiling import fused_logprob_vmem_bytes

# The tiles a call may take. Rows: the largest of ROW_TILES the rule admits,
# ROW_TILE_FLOOR when none is (every call took the floor before the rule).
# Vocabulary: the widest of a kernel's candidates that fits beside the row
# tile; the backward kernels carry a [D, bv] fp32 dW (or [bn, D] dx)
# accumulator beside the streamed weight, so they start narrower.
ROW_TILES = (512, 256, 128)
ROW_TILE_FLOOR = ROW_TILES[-1]
BLOCK_V = 512
BLOCK_V_BWD = 256
V_TILES = {"fwd": (BLOCK_V, BLOCK_V_BWD), "dx": (BLOCK_V_BWD,), "dw": (BLOCK_V_BWD,)}

# What the rule lets one kernel's blocks, scratch and temporaries take of a
# v5e's 128 MiB of VMEM, and what the compiler grants a kernel unasked.
VMEM_BUDGET = 64 * 1024 * 1024
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


def pick_v_block(V: int, block_v: int = BLOCK_V) -> int:
    """Vocab tile width: one full block for small vocabs (a block equal to
    the array dim is always tile-legal, even unaligned), else the fixed
    width with the ragged tail masked in-kernel."""
    return V if V <= block_v else block_v


class HeadTiles(NamedTuple):
    """(row tile, vocabulary tile) of each of the three kernels."""

    fwd: Tuple[int, int]
    dx: Tuple[int, int]
    dw: Tuple[int, int]

    @property
    def rows(self) -> int:
        """What the call's rows are padded to a multiple of (the tiles are
        128 times a power of two, so the largest is every one's multiple)."""
        return max(self.fwd[0], self.dx[0], self.dw[0])

    def padded(self, N: int) -> int:
        """`N` rows padded up to a multiple of every kernel's row tile."""
        return -(-N // self.rows) * self.rows

    def weight_passes(self, N: int) -> int:
        """How many times the forward streams the whole weight from HBM."""
        return self.padded(N) // self.fwd[0]


def head_tiles(N: int, D: int, V: int, x_itemsize: int = 2, w_itemsize: int = 2,
               has_bias: bool = False, budget: int = VMEM_BUDGET) -> HeadTiles:
    """The rule (module docstring): per kernel the largest row tile that is
    no larger than the call, pads it by under an eighth, and fits `budget`
    with some vocabulary tile of the kernel's candidates, widest first. A
    call no tile above the floor admits keeps the floor and the widest
    vocabulary tile, whatever they need: what every call ran before."""

    def pick(kind):
        for bn in ROW_TILES[:-1]:
            padded = -(-N // bn) * bn
            if bn > N or (padded - N) * 8 >= padded:
                continue
            for bv in (pick_v_block(V, v) for v in V_TILES[kind]):
                if fused_logprob_vmem_bytes(kind, D, bn, bv, x_itemsize, w_itemsize, has_bias) <= budget:
                    return bn, bv
        return ROW_TILE_FLOOR, pick_v_block(V, V_TILES[kind][0])

    return HeadTiles(pick("fwd"), pick("dx"), pick("dw"))


def _compiler_params(interpret, vmem_bytes):
    """N-blocks are independent; the V walk is the online accumulation order
    and must stay sequential. The scoped-VMEM limit is the kernel's own
    estimate (tiling.fused_logprob_vmem_bytes) and a quarter of headroom,
    never under what the compiler grants unasked."""
    limit = max(DEFAULT_SCOPED_VMEM, -(-vmem_bytes * 5 // 4 // 2**20) * 2**20)
    return _grid_compiler_params(interpret, semantics=("parallel", "arbitrary"), vmem_limit_bytes=limit)


# What the traced programs' head calls chose, for the `head/*` counters: the
# dict a caller arms is filled while its program is traced and left alone by
# a dispatch that traces nothing, as parallel/schedule.count_weight_gathers.
_armed = threading.local()


@contextmanager
def count_head_calls(tally: dict):
    """While a program is traced inside, every `fused_logprob` call records
    into `tally[site]` its padded rows, the forward's tiles and
    `weight_passes`, the times the forward streams the whole weight."""
    prior = getattr(_armed, "tally", None)
    _armed.tally = tally
    try:
        yield tally
    finally:
        _armed.tally = prior


def _note_head_call(site, N, tiles):
    tally = getattr(_armed, "tally", None)
    if tally is not None:
        tally[site] = {
            "padded_rows": tiles.padded(N),
            "row_tile": tiles.fwd[0],
            "vocab_tile": tiles.fwd[1],
            "weight_passes": tiles.weight_passes(N),
        }


def take_head_call_scalars(tally: dict, program: str) -> dict:
    """`head/<program>/<site>/{weight_passes,row_tile,vocab_tile,padded_rows}`
    of the sites traced since the last take, emptying `tally`: a compiled
    program shows in the tracker's scalars once."""
    out = {f"head/{program}/{site}/{k}": float(v) for site, call in tally.items() for k, v in call.items()}
    tally.clear()
    return out


def _tile_scores(x_ref, w_ref, b_ref, j, *, V, bv, tied):
    """One [bn, bv] tile of head scores + its vocab-validity mask.

    Shared by the forward and both backward kernels so the projection and
    the ragged-tail masking can never desynchronize. The weight is cast to
    the activation dtype (the fallback path's promotion rule) and the dot
    accumulates in fp32. Tail columns past V read block padding — undefined
    memory — so their score is REPLACED with MASK_VAL, not biased."""
    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    if tied:  # w tile [bv, D] (embedding rows): s = x @ w^T
        s = jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # w tile [D, bv] (lm_head kernel): s = x @ w
        s = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    if b_ref is not None:
        s = s + b_ref[...].astype(jnp.float32)  # [1, bv] broadcasts over rows
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = col < V
    s = jnp.where(valid, s, MASK_VAL)
    return s, valid, col


def _fwd_kernel(*refs, V, bv, tied, has_bias):
    if has_bias:
        (x_ref, w_ref, b_ref, y_ref, lp_ref, lse_ref, ent_ref,
         m_ref, l_ref, r_ref, lab_ref) = refs
    else:
        (x_ref, w_ref, y_ref, lp_ref, lse_ref, ent_ref,
         m_ref, l_ref, r_ref, lab_ref) = refs
        b_ref = None
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        r_ref[...] = jnp.zeros_like(r_ref)
        lab_ref[...] = jnp.zeros_like(lab_ref)

    s, valid, col = _tile_scores(x_ref, w_ref, b_ref, j, V=V, bv=bv, tied=tied)

    # Label gather: the one column equal to y contributes its raw score.
    hit = (col == y_ref[...]) & valid
    lab_ref[...] = lab_ref[...] + jnp.sum(
        jnp.where(hit, s, 0.0), axis=1, keepdims=True
    )

    # Online max/sum/weighted-sum with flash-style rescaling.
    m_prev = m_ref[:, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
    # p is 0 at masked tail columns, so p * s (s = MASK_VAL there) is 0·finite.
    l_cur = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    r_cur = alpha * r_ref[:, :1] + jnp.sum(p * s, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
    r_ref[...] = jnp.broadcast_to(r_cur, r_ref.shape)

    @pl.when(j == nv - 1)
    def _():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        lse = m_ref[:, :1] + jnp.log(l_safe)
        lse_ref[...] = lse
        ent_ref[...] = lse - r_ref[:, :1] / l_safe
        lp_ref[...] = lab_ref[:, :1] - lse


def _ds_tile(x_ref, w_ref, b_ref, y_ref, lse_ref, ent_ref,
             dlp_ref, dlse_ref, dent_ref, j, *, V, bv, tied):
    """Recompute one [bn, bv] cotangent tile of the scores.

    p = exp(s - lse) from the saved row residuals; E (the mean score under
    p) is recovered as lse - entropy. All cotangent terms vanish on masked
    tail columns (p and the label one-hot are both zero there)."""
    s, valid, col = _tile_scores(x_ref, w_ref, b_ref, j, V=V, bv=bv, tied=tied)
    lse = lse_ref[...]  # [bn, 1]
    E = lse - ent_ref[...]
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    hit = ((col == y_ref[...]) & valid).astype(jnp.float32)
    dlp = dlp_ref[...]
    ds = dlp * (hit - p) + dlse_ref[...] * p - dent_ref[...] * p * (s - E)
    return ds


def _bwd_dx_kernel(*refs, V, bv, tied, has_bias):
    if has_bias:
        (x_ref, w_ref, b_ref, y_ref, lse_ref, ent_ref, dlp_ref, dlse_ref,
         dent_ref, dx_ref, acc_ref) = refs
    else:
        (x_ref, w_ref, y_ref, lse_ref, ent_ref, dlp_ref, dlse_ref,
         dent_ref, dx_ref, acc_ref) = refs
        b_ref = None
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ds = _ds_tile(x_ref, w_ref, b_ref, y_ref, lse_ref, ent_ref,
                  dlp_ref, dlse_ref, dent_ref, j, V=V, bv=bv, tied=tied)
    # The dx contraction runs over the vocab tile axis, so the tail block's
    # padding columns are contracted INTO the result: ds is 0 there, but the
    # weight padding is undefined memory (0 · NaN poisons the accumulator).
    # Zero them explicitly.
    w = w_ref[...]
    vocab_axis = 0 if tied else 1
    tail_valid = (
        j * bv
        + jax.lax.broadcasted_iota(jnp.int32, w.shape, vocab_axis)
        < V
    )
    w = jnp.where(tail_valid, w, 0)
    dsc = ds.astype(x_ref[...].dtype)
    if tied:  # dx += ds @ w   ([bn, bv] · [bv, D])
        pv = jax.lax.dot_general(
            dsc, w.astype(dsc.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:  # dx += ds @ w^T   ([bn, bv] · [D, bv]^T)
        pv = jax.lax.dot_general(
            dsc, w.astype(dsc.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    acc_ref[...] = acc_ref[...] + pv

    @pl.when(j == nv - 1)
    def _():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(*refs, V, bv, tied, has_bias):
    if has_bias:
        (x_ref, w_ref, b_ref, y_ref, lse_ref, ent_ref, dlp_ref, dlse_ref,
         dent_ref, dw_ref, db_ref, acc_ref, bacc_ref) = refs
    else:
        (x_ref, w_ref, y_ref, lse_ref, ent_ref, dlp_ref, dlse_ref,
         dent_ref, dw_ref, acc_ref) = refs
        b_ref = db_ref = bacc_ref = None
    j = pl.program_id(0)  # V-block (parallel)
    i = pl.program_id(1)  # N-block (sequential accumulation)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if bacc_ref is not None:
            bacc_ref[...] = jnp.zeros_like(bacc_ref)

    ds = _ds_tile(x_ref, w_ref, b_ref, y_ref, lse_ref, ent_ref,
                  dlp_ref, dlse_ref, dent_ref, j, V=V, bv=bv, tied=tied)
    x = x_ref[...]
    dsc = ds.astype(x.dtype)
    if tied:  # dw[bv, D] += ds^T @ x
        pv = jax.lax.dot_general(
            dsc, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # dw[D, bv] += x^T @ ds
        pv = jax.lax.dot_general(
            x, dsc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    acc_ref[...] = acc_ref[...] + pv
    if bacc_ref is not None:
        bacc_ref[...] = bacc_ref[...] + jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(i == ni - 1)
    def _():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)
        if db_ref is not None:
            db_ref[...] = bacc_ref[...].astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers over padded 2-D operands
# ---------------------------------------------------------------------------


def _row_spec(bn):
    return _vmem((bn, 1), lambda i, j: (i, 0))


def _operand_specs(N, D, V, bn, bv, tied, has_bias, grid_nv_outer=False):
    """BlockSpecs for (x, w, [bias], per-row columns), built from the same
    layout description the tiling validator checks. With grid_nv_outer the
    grid is (V-blocks, N-blocks) — the dW kernel — so the index-map arg
    order flips."""
    from trlx_tpu.ops.tiling import fused_logprob_block_layout

    lay = {
        l.name: l
        for l in fused_logprob_block_layout(N, D, V, bn, bv, tied, has_bias)
    }
    if grid_nv_outer:
        x_map = lambda j, i: (i, 0)
        w_map = (lambda j, i: (j, 0)) if tied else (lambda j, i: (0, j))
        b_map = lambda j, i: (0, j)
        row_map = lambda j, i: (i, 0)
    else:
        x_map = lambda i, j: (i, 0)
        w_map = (lambda i, j: (j, 0)) if tied else (lambda i, j: (0, j))
        b_map = lambda i, j: (0, j)
        row_map = lambda i, j: (i, 0)
    x_spec = _vmem(lay["x"].block_shape, x_map)
    w_spec = _vmem(lay["w"].block_shape, w_map)
    b_spec = _vmem(lay["bias"].block_shape, b_map) if has_bias else None
    row_spec = _vmem(lay["labels"].block_shape, row_map)
    return x_spec, w_spec, b_spec, row_spec


def _vmem_bytes(kind, x, w, bias, bn, bv):
    return fused_logprob_vmem_bytes(
        kind, x.shape[1], bn, bv, x.dtype.itemsize, w.dtype.itemsize, bias is not None
    )


def _fwd_call(x, w, bias, labels, tied, tile, interpret):
    N, D = x.shape
    V = w.shape[0] if tied else w.shape[1]
    bn, bv = tile
    grid = (N // bn, -(-V // bv))
    has_bias = bias is not None
    x_spec, w_spec, b_spec, row_spec = _operand_specs(N, D, V, bn, bv, tied, has_bias)
    in_specs = [x_spec, w_spec] + ([b_spec] if has_bias else []) + [row_spec]
    operands = [x, w] + ([bias] if has_bias else []) + [labels]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, V=V, bv=bv, tied=tied, has_bias=has_bias),
        name="logprob_head_fwd",  # how a device trace names the call
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32)] * 3,
        scratch_shapes=[_scratch((bn, 128)) for _ in range(4)],
        interpret=interpret,
        **_compiler_params(interpret, _vmem_bytes("fwd", x, w, bias, bn, bv)),
    )(*operands)
    return tuple(out)


def _bwd_calls(x, w, bias, labels, lse, ent, dlp, dlse, dent, tied, tiles, interpret):
    N, D = x.shape
    V = w.shape[0] if tied else w.shape[1]
    has_bias = bias is not None
    row_operands = [labels, lse, ent, dlp, dlse, dent]

    # dx: N-blocks parallel, V innermost accumulating into a [bn, D] scratch.
    bn, bv = tiles.dx
    x_spec, w_spec, b_spec, row_spec = _operand_specs(N, D, V, bn, bv, tied, has_bias)
    in_specs = [x_spec, w_spec] + ([b_spec] if has_bias else []) + [row_spec] * 6
    operands = [x, w] + ([bias] if has_bias else []) + row_operands
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, V=V, bv=bv, tied=tied, has_bias=has_bias),
        name="logprob_head_bwd_dx",
        grid=(N // bn, -(-V // bv)),
        in_specs=in_specs,
        out_specs=_vmem((bn, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[_scratch((bn, D))],
        interpret=interpret,
        **_compiler_params(interpret, _vmem_bytes("dx", x, w, bias, bn, bv)),
    )(*operands)

    # dW (+db): V-blocks parallel, N innermost accumulating [D, bv] / [bv, D].
    bn, bv = tiles.dw
    grid = (-(-V // bv), N // bn)
    params = _compiler_params(interpret, _vmem_bytes("dw", x, w, bias, bn, bv))
    x_spec, w_spec, b_spec, row_spec = _operand_specs(
        N, D, V, bn, bv, tied, has_bias, grid_nv_outer=True
    )
    in_specs = [x_spec, w_spec] + ([b_spec] if has_bias else []) + [row_spec] * 6
    dw_spec = (
        _vmem((bv, D), lambda j, i: (j, 0)) if tied else _vmem((D, bv), lambda j, i: (0, j))
    )
    dw_shape = jax.ShapeDtypeStruct(w.shape, w.dtype)
    acc_shape = (bv, D) if tied else (D, bv)
    if has_bias:
        out = pl.pallas_call(
            functools.partial(_bwd_dw_kernel, V=V, bv=bv, tied=tied, has_bias=True),
            name="logprob_head_bwd_dw",
            grid=grid,
            in_specs=in_specs,
            out_specs=[dw_spec, _vmem((1, bv), lambda j, i: (0, j))],
            out_shape=[dw_shape, jax.ShapeDtypeStruct(bias.shape, bias.dtype)],
            scratch_shapes=[_scratch(acc_shape), _scratch((1, bv))],
            interpret=interpret,
            **params,
        )(*operands)
        dw, db = out
    else:
        dw = pl.pallas_call(
            functools.partial(_bwd_dw_kernel, V=V, bv=bv, tied=tied, has_bias=False),
            name="logprob_head_bwd_dw",
            grid=grid,
            in_specs=in_specs,
            out_specs=dw_spec,
            out_shape=dw_shape,
            scratch_shapes=[_scratch(acc_shape)],
            interpret=interpret,
            **params,
        )(*operands)
        db = None
    return dx, dw, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_core(x, w, bias, labels, tied, tiles, interpret):
    return _fwd_call(x, w, bias, labels, tied, tiles.fwd, interpret)


def _fused_core_fwd(x, w, bias, labels, tied, tiles, interpret):
    lp, lse, ent = _fwd_call(x, w, bias, labels, tied, tiles.fwd, interpret)
    return (lp, lse, ent), (x, w, bias, labels, lse, ent)


def _fused_core_bwd(tied, tiles, interpret, res, g):
    x, w, bias, labels, lse, ent = res
    dlp, dlse, dent = g
    dx, dw, db = _bwd_calls(
        x, w, bias, labels, lse, ent, dlp, dlse, dent, tied, tiles, interpret
    )
    dlabels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dx, dw, db, dlabels


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_logprob(x, w, labels, bias=None, *, tied=False, interpret=None,
                  tiles=None, site="lm_head"):
    """Fused head projection + per-token (logprob, logsumexp, entropy).

    x: [..., D] hidden states (any leading shape). w: lm_head kernel [D, V]
    (tied=False) or embedding table [V, D] (tied=True). labels: [...] int.
    bias: optional [V]. Returns fp32 (logprob, lse, entropy), each shaped
    like labels; the [..., V] logits never exist outside one VMEM tile,
    forward or backward. Differentiable in x / w / bias via the custom VJP.
    `tiles` (a HeadTiles) stands in for the rule's choice in tests and
    probes; `site` names the call in the counters of `count_head_calls`.
    """
    interpret = _interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    D = x.shape[-1]
    V = w.shape[0] if tied else w.shape[1]
    N = int(np.prod(lead)) if lead else 1
    if tiles is None:
        tiles = head_tiles(N, D, V, x.dtype.itemsize, w.dtype.itemsize, bias is not None)
    _note_head_call(site, N, tiles)

    Np = tiles.padded(N)
    x2 = x.reshape(N, D)
    y2 = labels.reshape(N, 1).astype(jnp.int32)
    if Np != N:
        # Zero-padded rows stay finite end-to-end (score = bias, p well
        # defined) and their incoming cotangents are zero, so they add
        # nothing to dW/db; dx padding is sliced off below.
        x2 = jnp.pad(x2, ((0, Np - N), (0, 0)))
        y2 = jnp.pad(y2, ((0, Np - N), (0, 0)))
    b2 = None if bias is None else bias.reshape(1, V)

    lp, lse, ent = _fused_core(x2, w, b2, y2, tied, tiles, interpret)
    return tuple(v[:N, 0].reshape(lead) for v in (lp, lse, ent))


def naive_logprob(x, w, labels, bias=None, *, tied=False, mask=None):
    """The materializing reference path: head matmul (activation-dtype
    promotion, exactly like QDense / Embed.attend) → fp32 log_softmax →
    label gather + entropy. This is both the parity oracle for the kernel
    and the model layer's fallback when the kernel is ineligible. With
    `mask`, masked rows are skipped (logits zeroed before the softmax,
    outputs zeroed after — the logprobs_from_logits mask contract)."""
    wc = w.astype(x.dtype)
    logits = x @ (wc.T if tied else wc)
    if bias is not None:
        logits = logits + bias.astype(x.dtype)
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask.astype(bool)[..., None], logits, 0.0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lp = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    if mask is not None:
        m = mask.astype(jnp.float32)
        lp, lse, ent = lp * m, lse * m, ent * m
    return lp, lse, ent


# ---------------------------------------------------------------------------
# Routing: static eligibility + cached tile check (+ must-lower on TPU)
# ---------------------------------------------------------------------------


def fused_logprob_eligible(d_model: int, vocab_size: int) -> bool:
    """Static routing gate: a TPU backend with a one-device mesh
    (flash_attention.one_device_tpu) and a head layout worth tiling
    (full-[D] blocks are always tile-legal; the gate keeps tiny test models
    on the materialized path, where XLA's fused softmax is faster than grid
    overhead)."""
    return one_device_tpu() and d_model % 128 == 0 and vocab_size >= BLOCK_V


_PROBE_CACHE = {}


def fused_logprob_supported(N: int, D: int, V: int, tied: bool,
                            has_bias: bool, dtype=jnp.bfloat16, w_dtype=None) -> bool:
    """Cached verdict for a call-site shape whose static eligibility rule
    already passed (`_PROBE_CACHE`; devicemon's routing gauges read it). The
    CPU-runnable tile check over the kernels' real block layouts, at the
    tiles `head_tiles` gives the call, may refuse the shape: a stated rule,
    warned once, answered False, and the caller takes log_softmax. A shape
    that passes must, on a TPU backend, lower forward AND backward, or
    `require_lowering` raises naming it."""
    import warnings

    from trlx_tpu.ops import tiling

    dtype, w_dtype = jnp.dtype(dtype), jnp.dtype(dtype if w_dtype is None else w_dtype)
    key = (N, D, V, bool(tied), bool(has_bias), dtype.name, w_dtype.name, jax.default_backend())
    hit = _PROBE_CACHE.get(key)
    if hit is not None:
        return hit
    shape = f"[N={N}, D={D}, V={V}, tied={tied}, bias={has_bias}]"
    tiles = head_tiles(N, D, V, dtype.itemsize, w_dtype.itemsize, has_bias)
    Np = tiles.padded(N)
    issues = [
        issue
        for bn, bv in dict.fromkeys(tiles)
        for issue in tiling.layout_issues(tiling.fused_logprob_block_layout(Np, D, V, bn, bv, tied, has_bias))
    ]
    if issues:
        warnings.warn(
            f"fused-logprob kernel refused for shape {shape} by the static tile "
            f"check — falling back to the log_softmax path "
            f"({'; '.join(issues)[:300]})"
        )
    elif jax.default_backend() == "tpu":
        s = jax.ShapeDtypeStruct
        args = [s((N, D), dtype), s((V, D) if tied else (D, V), w_dtype),
                s((N,), jnp.int32)]
        if has_bias:
            args.append(s((V,), jnp.float32))

        def probe(x, w, y, *b):
            def f(x, w, *b):
                lp, lse, ent = fused_logprob(
                    x, w, y, b[0] if b else None, tied=tied, interpret=False
                )
                return jnp.sum(lp) + jnp.sum(lse) + jnp.sum(ent)

            return jax.grad(f, argnums=tuple(range(2 + len(b))))(x, w, *b)

        tiling.require_lowering("fused-logprob", shape, probe, *args)
    _PROBE_CACHE[key] = not issues
    return _PROBE_CACHE[key]


def _layout_device():
    return jax.devices()[0]


def held_vocab_major(w) -> bool:
    """Whether the device holds an untied head weight `[D, V]` with the
    vocabulary as its major axis, i.e. as the rows of `[V, D]`: the layout a
    TPU gives a two-dimensional array by default puts the axis that pads
    less to its (8, 128) tile minor, so `[4096, 50257]` and `[4096, 50400]`
    (V not a multiple of 128) are held as `[V, 4096]`, with their optimizer
    moments. A Mosaic call takes its operands row-major, so the untied
    kernels made the compiler copy such a weight whole before the forward
    and copy the new weight and both moments back after the update: six
    copies of 413 MB a GPT-J step, twelve an ILQL step. Asked of the client
    at trace time, from the shape and dtype alone; row-major where the
    client cannot say."""
    device = _layout_device()
    try:
        layout = device.client.get_default_layout(jnp.dtype(w.dtype), tuple(w.shape), device)
        return tuple(layout._xla_layout().minor_to_major()) == (0, 1)
    except (AttributeError, TypeError, NotImplementedError, jax.errors.JaxRuntimeError):
        return False


def routed_logprob(x, w, labels, bias=None, *, tied=False, mode="auto", mask=None, site="lm_head"):
    """The model layer's entry point: kernel when forced or (eligible +
    probe-supported), else the materializing naive path. `mode` is
    LMConfig.extra['fused_logprob']: 'auto' (default), 'force' (kernel
    unconditionally — interpret mode off-TPU, for CPU parity tests), or
    'off' (always the naive path). `mask` zeros masked rows on both paths
    (the kernel computes them — they are uniform work on the grid — and
    the fallback skips them in the softmax). `site` names the call site in
    the `head/*` counters (`count_head_calls`)."""
    lead = x.shape[:-1]
    N = int(np.prod(lead)) if lead else 1
    D = x.shape[-1]
    V = w.shape[0] if tied else w.shape[1]
    use_kernel = mode == "force" or (mode != "off" and fused_logprob_eligible(D, V))
    if use_kernel and not tied and held_vocab_major(w):
        # the weight as it is held: its transpose is the same bytes, read by
        # the tied kernels; dW comes back in the moments' layout
        w, tied = w.T, True
    if use_kernel and mode != "force":
        use_kernel = fused_logprob_supported(N, D, V, tied, bias is not None, x.dtype, w.dtype)
    if use_kernel:
        lp, lse, ent = fused_logprob(x, w, labels, bias, tied=tied, site=site)
        if mask is not None:
            m = mask.astype(jnp.float32)
            lp, lse, ent = lp * m, lse * m, ent * m
        return lp, lse, ent
    return naive_logprob(x, w, labels, bias, tied=tied, mask=mask)

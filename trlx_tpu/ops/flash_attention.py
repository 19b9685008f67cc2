"""Pallas TPU flash attention: fused, online-softmax, O(T) memory.

The hot op of every forward/rollout/train step. The reference leans on
torch/HF SDPA CUDA kernels (reference: trlx/model/nn/ppo_models.py:171-189
replays HF GPT-2 blocks); here the kernel is ours, built for the MXU:

- grid (batch*heads, q_blocks, k_blocks) with the k dimension innermost, so
  the softmax runs online in VMEM scratch (m/l running max/sum) and the
  [T, T] score matrix never exists in HBM;
- causal + left-padding key-validity + gpt-neo local-window masking fused
  into the score block (the XLA path materializes an additive [b,1,T,T]
  bias — see trlx_tpu.models.lm.make_attn_bias);
- fully-masked upper-diagonal k blocks are skipped (`pl.when`), recovering
  the ~2x causal FLOP saving;
- custom VJP with two backward kernels (dq; dk/dv) that recompute P from the
  saved log-sum-exp instead of storing probabilities.

All matmuls ACCUMULATE in fp32 via preferred_element_type (multiplies run at
the MXU's native bf16 granularity, same precision class as XLA's default
einsum path on TPU); inputs may be bf16. Interpret mode (CPU) is
auto-selected off-TPU so the same code path is unit-testable in CI; measured
on a v5e, the kernel matches the XLA einsum path within mutual bf16 noise
(~1e-2 at T=1024 fp32 inputs) and the parallel grid dimension_semantics are
bit-identical to sequential execution.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

M_INIT = -1e30  # running-max init (finite: fully-masked rows degrade to
# uniform attention exactly like the XLA path's -1e9 bias)
MASK_VAL = -1e9


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def one_device_tpu() -> bool:
    """The rule every model-layer kernel gate in ops/ shares: a TPU backend
    and a one-device mesh. No pallas_call here is under shard_map, and a jit
    over more than one device refuses to lower one ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map",
    jax 0.9) — so on a mesh larger than one the XLA paths stand until the
    kernels are wrapped (ROADMAP A6). Ring attention calls the flash kernel
    per shard inside its own shard_map and gates on ``auto_flash_ok``
    alone."""
    if jax.default_backend() != "tpu":
        return False
    from trlx_tpu.parallel.mesh import peek_mesh

    mesh = peek_mesh()
    return mesh is None or mesh.size == 1


def pick_block(q_len: int) -> int:
    """Largest well-measured block that divides q_len: 512x512 measured best
    on v5e (7.7ms vs einsum 10.7ms at b=4,T=2048,h=16,d=64), falling to 256/
    128, else one whole-length block."""
    for blk in (512, 256, 128):
        if q_len % blk == 0:
            return blk
    return q_len


def auto_flash_ok(q_len: int) -> bool:
    """The shared auto-routing gate: a real TPU backend (interpret-mode
    pallas is far slower than einsum) and a long 128-aligned sequence. Used
    by both the model layer (which adds ``one_device_tpu``) and the
    ring-attention per-chunk path so the eligibility rule and the block
    choice cannot drift apart."""
    return jax.default_backend() == "tpu" and q_len >= 256 and q_len % 128 == 0


def _vmem_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _smem_spec():
    """Whole (1,1) scalar operand in SMEM (the traced ring-chunk offset)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _compiler_params(interpret, semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=None):
    """Mark the (bh, outer-block) grid dims parallel so Mosaic pipelines
    across grid steps instead of serializing them; only the innermost dim
    (the online-softmax / accumulation walk) is order-dependent. Without
    this the kernel is grid-step-latency-bound: at [8,1024,16,256] the
    forward drops from ~18ms to ~3ms on a v5e. The decode and fused-logprob
    kernels pass their own two-dim semantics. `vmem_limit_bytes` lifts the
    compiler's scoped-VMEM limit (16 MiB) for a kernel whose blocks need more;
    None leaves the default."""
    if interpret:
        return {}
    extra = {} if vmem_limit_bytes is None else {"vmem_limit_bytes": int(vmem_limit_bytes)}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=semantics, **extra)}


# ---------------------------------------------------------------------------
# Shared score block
# ---------------------------------------------------------------------------

def _masked_scores(q_ref, k_ref, kmask_ref, q_start, k_start, doff, *, scale,
                   causal, window, bq, bk):
    """q@k^T (native dtype, fp32 accumulate) + causal/validity/window mask —
    shared by the forward and both backward kernels so their masking can never
    desynchronize. `doff` shifts key positions into the query frame
    (k_global = k_idx + doff); zero for ordinary self-attention, the chunk
    displacement for ring-attention blocks."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = doff + k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kmask_ref[0, 0] > 0.5)[None, :]
    if causal:
        mask = mask & (k_idx <= q_idx)
    if window > 0:
        mask = mask & (k_idx > q_idx - window)
    return jnp.where(mask, s, MASK_VAL)


def _run_if_live(compute, q_start, k_start, doff, *, bq, bk, causal, window):
    """Skip k blocks that the mask would zero out entirely: above the causal
    diagonal (in the offset frame), or (local attention) wholly below the
    trailing window."""
    conds = []
    if causal:
        conds.append(k_start + doff <= q_start + bq - 1)
    if window > 0:
        conds.append(k_start + bk - 1 + doff > q_start - window)
    if not conds:
        compute()
        return
    pred = conds[0]
    for c in conds[1:]:
        pred = pred & c
    pl.when(pred)(compute)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(off_ref, kmask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                *, scale, causal, window, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = iq * bq
    k_start = ik * bk
    doff = off_ref[0, 0].astype(jnp.int32)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, M_INIT)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    def compute():
        s = _masked_scores(q_ref, k_ref, kmask_ref, q_start, k_start, doff,
                           scale=scale, causal=causal, window=window, bq=bq, bk=bk)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _run_if_live(compute, q_start, k_start, doff, bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(ik == nk - 1)
    def _():
        # Rows whose every k block was skipped (an entirely-future ring
        # chunk) have l == 0: emit zeros with lse = M_INIT so the chunk
        # vanishes from any log-sum-exp combination instead of NaN-ing.
        l = l_scr[:, :1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l[:, 0] > 0, m_scr[:, 0] + jnp.log(l_safe[:, 0]), M_INIT
        )


def _fwd(q, k, v, kmask, off, scale, causal, window, bq, bk, interpret):
    BH, T, D = q.shape
    nq, nk = T // bq, T // bk
    H = BH // kmask.shape[0]
    if not interpret:
        # GL006 provenance: the _vmem_spec shapes below must agree with the
        # canonical tiling.flash_block_layout description — validating the
        # layout before compiling keeps wrapper and validator from drifting
        # (the PR 3 Mosaic tile-rule crash class). Interpret mode has no
        # Mosaic tile constraints, so tiny CPU test shapes stay legal.
        from trlx_tpu.ops.tiling import check_layout, flash_block_layout

        check_layout(flash_block_layout(BH, T, D, bq, bk))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",  # how a device trace names the call
        grid=(BH, nq, nk),
        in_specs=[
            _smem_spec(),
            _vmem_spec((1, 1, bk), lambda bh, iq, ik: (bh // H, 0, ik)),
            _vmem_spec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            _vmem_spec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
            _vmem_spec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=[
            _vmem_spec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            _vmem_spec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((bq, D)),
            _scratch((bq, 128)),
            _scratch((bq, 128)),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(off, kmask, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(off_ref, kmask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, window, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start, k_start = iq * bq, ik * bk
    doff = off_ref[0, 0].astype(jnp.int32)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        s = _masked_scores(q_ref, k_ref, kmask_ref, q_start, k_start, doff,
                           scale=scale, causal=causal, window=window, bq=bq, bk=bk)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _run_if_live(compute, q_start, k_start, doff, bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(off_ref, kmask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window, bq, bk):
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    q_start, k_start = iq * bq, ik * bk
    doff = off_ref[0, 0].astype(jnp.int32)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        s = _masked_scores(q_ref, k_ref, kmask_ref, q_start, k_start, doff,
                           scale=scale, causal=causal, window=window, bq=bq, bk=bk)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _run_if_live(compute, q_start, k_start, doff, bq=bq, bk=bk, causal=causal, window=window)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, kmask, off, scale, causal, window, bq, bk, interpret):
    """Fused attention returning (o, lse). Exposing lse makes per-chunk calls
    exactly combinable (ring attention): downstream use of lse feeds a dlse
    cotangent which the backward folds into delta."""
    return _fwd(q, k, v, kmask, off, scale, causal, window, bq, bk, interpret)


def _flash_lse_fwd(q, k, v, kmask, off, scale, causal, window, bq, bk, interpret):
    o, lse = _fwd(q, k, v, kmask, off, scale, causal, window, bq, bk, interpret)
    return (o, lse), (q, k, v, kmask, off, o, lse)


def _flash_lse_bwd(scale, causal, window, bq, bk, interpret, res, cts):
    do, dlse = cts
    q, k, v, kmask, off, o, lse = res
    BH, T, D = q.shape
    H = BH // kmask.shape[0]
    # d s_ij = p_ij (dp_ij - delta_i); with lse also an output,
    # d lse / d s_ij = p_ij, so delta picks up an extra -dlse_i term.
    delta = (
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]
        - dlse.astype(jnp.float32)
    )  # [BH, 1, T]
    nq, nk = T // bq, T // bk

    if not interpret:
        # GL006 provenance: the backward kernels tile the same (block, array)
        # families as the forward (q/k/v blocks plus the [BH,1,T] row
        # vectors), so the forward layout is the legality contract here too.
        from trlx_tpu.ops.tiling import check_layout, flash_block_layout

        check_layout(flash_block_layout(BH, T, D, bq, bk))

    common = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk)
    in_arrays = (off, kmask, q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        name="flash_bwd_dq",
        grid=(BH, nq, nk),
        in_specs=[
            _smem_spec(),
            _vmem_spec((1, 1, bk), lambda bh, iq, ik: (bh // H, 0, ik)),
            _vmem_spec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            _vmem_spec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
            _vmem_spec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
            _vmem_spec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            _vmem_spec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
            _vmem_spec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_specs=[_vmem_spec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), q.dtype)],
        scratch_shapes=[_scratch((bq, D))],
        interpret=interpret,
        **_compiler_params(interpret),
    )(*in_arrays)[0]

    # k-side: grid walks (bh, k_block, q_block) — q innermost so dk/dv
    # accumulate in VMEM scratch across the whole q range.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        name="flash_bwd_dkv",
        grid=(BH, nk, nq),
        in_specs=[
            _smem_spec(),
            _vmem_spec((1, 1, bk), lambda bh, ik, iq: (bh // H, 0, ik)),
            _vmem_spec((1, bq, D), lambda bh, ik, iq: (bh, iq, 0)),
            _vmem_spec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
            _vmem_spec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
            _vmem_spec((1, bq, D), lambda bh, ik, iq: (bh, iq, 0)),
            _vmem_spec((1, 1, bq), lambda bh, ik, iq: (bh, 0, iq)),
            _vmem_spec((1, 1, bq), lambda bh, ik, iq: (bh, 0, iq)),
        ],
        out_specs=[
            _vmem_spec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
            _vmem_spec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        scratch_shapes=[_scratch((bk, D)), _scratch((bk, D))],
        interpret=interpret,
        **_compiler_params(interpret),
    )(*in_arrays)

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
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
):
    """Fused causal attention over [b, T, n_head, head_dim] inputs.

    kv_mask: [b, T] key-slot validity (0 at left-padding). `window > 0`
    restricts keys to the trailing window (gpt-neo local layers). `offset`
    (python int or traced scalar) shifts key positions into the query frame
    — ring attention passes the visiting chunk's displacement. With
    `return_lse` the per-row log-sum-exp comes back as [b, h, T] for exact
    cross-chunk combination. Sequence length must divide block_q/block_k
    (the model layer guarantees this by routing unaligned shapes to the XLA
    einsum path).
    """
    b, T, h, d = q.shape
    bq, bk = min(block_q, T), min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"seq len {T} not divisible by blocks ({bq}, {bk})")
    if interpret is None:
        interpret = _interpret_default()
    # float32 deliberately: `off` is a differentiable custom_vjp operand
    # (int32 would need float0 cotangent plumbing) and chunk displacements
    # are exact in float32 far beyond any real sequence length (2^24).
    off = jnp.asarray(0.0 if offset is None else offset, jnp.float32).reshape(1, 1)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, T, d)

    o, lse = _flash_lse(
        to_bh(q), to_bh(k), to_bh(v), kv_mask.astype(jnp.float32)[:, None, :],
        off, float(scale), bool(causal), int(window), bq, bk, bool(interpret),
    )
    o = o.reshape(b, h, T, d).transpose(0, 2, 1, 3)
    if return_lse:
        return o, lse.reshape(b, h, T)
    return o

"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

The reference has NO long-context machinery (SURVEY.md §2c/§5 — max seq 64);
this is a TPU-first capability extension that the mesh design reserved the
`sp` axis for. Each device holds a [b, T/n, h, d] sequence chunk; K/V chunks
rotate around the ring via `lax.ppermute` over ICI while every device
accumulates attention of its local queries against each visiting chunk with
an online softmax (the same math as the pallas flash kernel, at chunk
granularity). Peak memory per device is O(T/n) in sequence — the [T, T]
score matrix never exists, and neither does a gathered K/V.

Differentiable by construction: `ppermute` and `scan` have exact transposes,
so `jax.grad` through a shard_map'd ring pass yields the reverse ring — no
hand-written backward needed.

Causality uses GLOBAL positions (chunk offset × chunk len + local index), so
results match single-device attention bit-for-bit up to reduction order.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map

from trlx_tpu.parallel.mesh import AXIS_SP, AXIS_TP, DATA_AXES, get_mesh

MASK_VAL = -1e9
M_INIT = -1e30


def _flash_in_ring_ok(t: int, use_flash) -> bool:
    if use_flash is not None:
        return bool(use_flash)
    from trlx_tpu.ops.flash_attention import auto_flash_ok

    return auto_flash_ok(t)


def ring_attention(q, k, v, kv_mask, *, axis_name: str, n_ring: int, scale: float,
                   causal: bool = True, window: int = 0, use_flash=None):
    """Per-device body (call inside shard_map over `axis_name`).

    q/k/v: [b, t_local, h, d] — this device's sequence chunk, rotary already
    applied. kv_mask: [b, t_local] key validity (left padding). Returns
    [b, t_local, h, d] attention outputs for the local queries.

    Two per-chunk engines: the pallas flash kernel (long aligned chunks on
    TPU; exact cross-chunk combination via the kernel's log-sum-exp output,
    with the visiting chunk's displacement passed as the kernel offset) or an
    XLA einsum online-softmax (everything else). `use_flash` forces a path.
    """
    if _flash_in_ring_ok(q.shape[1], use_flash):
        return _ring_flash(q, k, v, kv_mask, axis_name=axis_name, n_ring=n_ring,
                           scale=scale, causal=causal, window=window)
    b, t, h, d = q.shape
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * t + jnp.arange(t)  # global positions of local queries

    qf = q.astype(jnp.float32)
    m0 = jnp.full((b, h, t, 1), M_INIT, jnp.float32)
    l0 = jnp.zeros((b, h, t, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, t, d), jnp.float32)
    perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]

    def attend(k_c, v_c, mask_c, i, m, l, acc):
        src = (idx - i) % n_ring  # which chunk is visiting this step

        def live(_):
            k_pos = src * t + jnp.arange(t)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32)) * scale
            pair = mask_c[:, None, None, :] > 0
            kp = k_pos[None, None, None, :]
            qp = q_pos[None, None, :, None]
            if causal:
                pair = pair & (kp <= qp)
            if window > 0:
                pair = pair & (kp > qp - window)
            s = jnp.where(pair, s, MASK_VAL)

            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhqk,bkhd->bhqd", p, v_c.astype(jnp.float32))
            return m_new, l_new, acc_new

        def dead(_):
            return m, l, acc

        # Skip chunks the mask would zero out ENTIRELY — the einsum twin of
        # the flash engine's per-block liveness test: a causal pass never pays
        # for fully-future chunks (src > idx), a windowed pass never pays for
        # chunks wholly older than the window. The ppermute rotation still
        # runs every step (the ring must keep turning); only the O(t²·d)
        # einsum work is skipped. Under causality this contiguous layout is
        # load-imbalanced (rank r does r+1 live chunks) — the sharded entry
        # therefore routes causal, evenly-divisible shapes to the zig-zag
        # layout (ring_attention_zigzag below), which equalizes live work;
        # this body remains for non-causal and non-divisible shapes.
        dead_conds = []
        if causal:
            dead_conds.append(src > idx)
        if window > 0:
            dead_conds.append(src * t + t - 1 <= idx * t - window)
        if not dead_conds:
            return live(None)
        is_dead = dead_conds[0]
        for c in dead_conds[1:]:
            is_dead = is_dead | c
        return jax.lax.cond(is_dead, dead, live, None)

    def step(carry, i):
        k_c, v_c, mask_c, m, l, acc = carry
        m, l, acc = attend(k_c, v_c, mask_c, i, m, l, acc)
        k_nxt = jax.lax.ppermute(k_c, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_c, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_c, axis_name, perm)
        return (k_nxt, v_nxt, mask_nxt, m, l, acc), None

    # The last visiting chunk is attended OUTSIDE the scan so its rotation
    # (whose result would be discarded) is never issued.
    carry = (k, v, kv_mask, m0, l0, acc0)
    if n_ring > 1:
        carry, _ = jax.lax.scan(step, carry, jnp.arange(n_ring - 1))
    k_c, v_c, mask_c, m, l, acc = carry
    _, l, acc = attend(k_c, v_c, mask_c, jnp.asarray(n_ring - 1), m, l, acc)
    out = acc / l  # fully-masked pad rows degrade to a uniform mix, like the
    # einsum/flash paths; every loss masks them.
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_flash(q, k, v, kv_mask, *, axis_name: str, n_ring: int, scale: float,
                causal: bool, window: int):
    """Ring pass whose per-chunk attention is the pallas flash kernel.

    Each visiting chunk contributes (o_c, lse_c); outputs combine exactly via
    log-sum-exp weights. Chunks entirely in the future (src > idx under
    causality) cost nothing: every k block fails the kernel's offset-aware
    liveness test. Gradients flow through the combine into dlse, which the
    kernel backward folds into its delta term."""
    from trlx_tpu.ops.flash_attention import flash_attention

    b, t, h, d = q.shape
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]

    o0 = jnp.zeros((b, t, h, d), jnp.float32)
    lse0 = jnp.full((b, h, t), M_INIT, jnp.float32)

    def attend(k_c, v_c, mask_c, i, o, lse):
        src = (idx - i) % n_ring
        offset = ((src - idx) * t).astype(jnp.float32)
        o_c, lse_c = flash_attention(
            q, k_c, v_c, mask_c, scale=scale, causal=causal, window=window,
            offset=offset, return_lse=True,
        )
        lse_new = jnp.logaddexp(lse, lse_c)
        w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
        w_new = jnp.exp(lse_c - lse_new).transpose(0, 2, 1)[..., None]
        return o * w_old + o_c.astype(jnp.float32) * w_new, lse_new

    def step(carry, i):
        k_c, v_c, mask_c, o, lse = carry
        o, lse = attend(k_c, v_c, mask_c, i, o, lse)
        k_nxt = jax.lax.ppermute(k_c, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_c, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_c, axis_name, perm)
        return (k_nxt, v_nxt, mask_nxt, o, lse), None

    carry = (k, v, kv_mask, o0, lse0)
    if n_ring > 1:
        carry, _ = jax.lax.scan(step, carry, jnp.arange(n_ring - 1))
    k_c, v_c, mask_c, o, lse = carry
    o, _ = attend(k_c, v_c, mask_c, jnp.asarray(n_ring - 1), o, lse)
    return o.astype(q.dtype)


def _zigzag_indices(T: int, n_ring: int):
    """Permutation putting the sequence in zig-zag order: rank r's contiguous
    shard of the permuted array holds half-chunks {r, 2n−1−r} of the
    original. Returns (perm, inverse) as static numpy index vectors."""
    import numpy as np

    c = T // (2 * n_ring)
    order = []
    for r in range(n_ring):
        order.append(np.arange(r * c, (r + 1) * c))
        order.append(np.arange((2 * n_ring - 1 - r) * c, (2 * n_ring - r) * c))
    zz = np.concatenate(order)
    return zz, np.argsort(zz)


def causal_live_half_pairs(n_ring: int, layout: str):
    """Per-rank count of LIVE half-chunk attends in one full causal ring pass
    — the load-balance model the layouts are judged by (and the exact
    liveness rule ring_attention_zigzag's lax.cond gates on). Contiguous
    counts whole chunks in half-chunk units (2 halves per live visit)."""
    counts = []
    for r in range(n_ring):
        if layout == "zigzag":
            cqs = (r, 2 * n_ring - 1 - r)
            n = 0
            for src in range(n_ring):
                for ck in (src, 2 * n_ring - 1 - src):
                    n += sum(1 for cq in cqs if ck <= cq)
            counts.append(n)
        else:
            counts.append(2 * (r + 1) * 2)  # (r+1) live visits × 4 half-pairs
    return counts


def ring_attention_zigzag(q, k, v, kv_mask, *, axis_name: str, n_ring: int,
                          scale: float, window: int = 0, use_flash=None):
    """Causal ring body for the ZIG-ZAG layout: this rank's local sequence is
    [half-chunk idx ; half-chunk 2n−1−idx], each of length c = t/2 (global
    positions follow). Every (q-half, k-half) pair attends independently and
    combines exactly via log-sum-exp, with pairs failing the causal/window
    liveness test skipped by lax.cond. Causal live work is 2n+1 half-pairs on
    EVERY rank — the layout exists to equalize what the contiguous layout
    skews as r+1 live chunks on rank r."""
    b, t, h, d = q.shape
    assert t % 2 == 0, "zig-zag layout needs an even local chunk"
    c = t // 2
    idx = jax.lax.axis_index(axis_name)
    flash_engine = _flash_in_ring_ok(c, use_flash)
    if flash_engine:
        from trlx_tpu.ops.flash_attention import flash_attention

    cqs = (idx, 2 * n_ring - 1 - idx)  # chunk ids of the local q halves
    q_halves = (q[:, :c], q[:, c:])
    perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]

    o0 = jnp.zeros((b, h, c, d), jnp.float32)
    lse0 = jnp.full((b, h, c), M_INIT, jnp.float32)

    def half_pair(q_half, cq, k_half, v_half, mask_half, ck, o, lse):
        """One (q-half, k-half) attend + lse-combine, liveness-gated."""

        def live(args):
            o, lse = args
            if flash_engine:
                o_c, lse_c = flash_attention(
                    q_half, k_half, v_half, mask_half, scale=scale, causal=True,
                    window=window, offset=((ck - cq) * c).astype(jnp.float32),
                    return_lse=True,
                )
                o_c = o_c.astype(jnp.float32).transpose(0, 2, 1, 3)  # → [b,h,c,d]
            else:
                q_pos = cq * c + jnp.arange(c)
                k_pos = ck * c + jnp.arange(c)
                s = jnp.einsum(
                    "bqhd,bkhd->bhqk",
                    q_half.astype(jnp.float32),
                    k_half.astype(jnp.float32),
                ) * scale
                pair = (mask_half[:, None, None, :] > 0) & (
                    k_pos[None, None, None, :] <= q_pos[None, None, :, None]
                )
                if window > 0:
                    pair = pair & (
                        k_pos[None, None, None, :] > q_pos[None, None, :, None] - window
                    )
                s = jnp.where(pair, s, MASK_VAL)
                m_c = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m_c)
                l_c = jnp.sum(p, axis=-1, keepdims=True)
                o_c = jnp.einsum("bhqk,bkhd->bhqd", p, v_half.astype(jnp.float32)) / l_c
                lse_c = (m_c + jnp.log(l_c))[..., 0]
            lse_new = jnp.logaddexp(lse, lse_c)
            w_old = jnp.exp(lse - lse_new)[..., None]
            w_new = jnp.exp(lse_c - lse_new)[..., None]
            return o * w_old + o_c * w_new, lse_new

        is_dead = ck > cq  # wholly future under causality
        if window > 0:
            is_dead = is_dead | (ck * c + c - 1 <= cq * c - window)
        return jax.lax.cond(is_dead, lambda args: args, live, (o, lse))

    def attend(k_c, v_c, mask_c, i, carrys):
        src = (idx - i) % n_ring
        cks = (src, 2 * n_ring - 1 - src)
        k_halves = (k_c[:, :c], k_c[:, c:])
        v_halves = (v_c[:, :c], v_c[:, c:])
        m_halves = (mask_c[:, :c], mask_c[:, c:])
        out = []
        for qi in range(2):
            o, lse = carrys[qi]
            for kj in range(2):
                o, lse = half_pair(
                    q_halves[qi], cqs[qi], k_halves[kj], v_halves[kj],
                    m_halves[kj], cks[kj], o, lse,
                )
            out.append((o, lse))
        return out

    def step(carry, i):
        k_c, v_c, mask_c, oa, la, ob, lb = carry
        (oa, la), (ob, lb) = attend(k_c, v_c, mask_c, i, [(oa, la), (ob, lb)])
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        mask_c = jax.lax.ppermute(mask_c, axis_name, perm)
        return (k_c, v_c, mask_c, oa, la, ob, lb), None

    carry = (k, v, kv_mask, o0, lse0, o0, lse0)
    if n_ring > 1:
        carry, _ = jax.lax.scan(step, carry, jnp.arange(n_ring - 1))
    k_c, v_c, mask_c, oa, la, ob, lb = carry
    (oa, _), (ob, _) = attend(k_c, v_c, mask_c, jnp.asarray(n_ring - 1), [(oa, la), (ob, lb)])
    out = jnp.concatenate([oa, ob], axis=2)  # [b, h, t, d]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention_sharded(q, k, v, kv_mask, *, scale: float, causal: bool = True,
                           window: int = 0, mesh=None, use_flash=None,
                           layout: str = "auto"):
    """jit-composable entry: shard_map over the full (dp, fsdp, tp, sp) mesh.

    q/k/v: GLOBAL [b, T, h, d] logical arrays (XLA reshards at the shard_map
    boundary): batch over (dp, fsdp), sequence over sp, heads over tp.

    `layout`: "auto" picks zig-zag (balanced causal work — each rank holds
    half-chunks {r, 2n−1−r}) whenever causal and T divides 2·n_ring, else the
    contiguous layout; "zigzag"/"contiguous" force. The zig-zag permutation is
    applied and inverted HERE, so callers always see natural sequence order.

    Cost note: the permutation round-trip is 5 cross-shard gathers of O(T·h·d)
    per attention call. Attention compute is O(T²·h·d/n) per rank, so the
    movement is a ~n/T fraction of the work — noise at the long sequences sp
    targets (T ≥ 8k), but measurable at short T; pass layout="contiguous" to
    opt out there (short sequences are also where the causal imbalance being
    fixed costs the least).
    """
    from jax.sharding import PartitionSpec as P

    mesh = mesh if mesh is not None else get_mesh()
    n_ring = mesh.shape[AXIS_SP]
    qkv_spec = P(DATA_AXES, AXIS_SP, AXIS_TP, None)
    mask_spec = P(DATA_AXES, AXIS_SP)

    T = q.shape[1]
    if layout == "auto":
        zig = causal and n_ring > 1 and T % (2 * n_ring) == 0
    else:
        zig = layout == "zigzag"
    if zig:
        if not causal:
            raise ValueError("zig-zag layout is a causal-balance construct; use contiguous for non-causal")
        if T % (2 * n_ring):
            raise ValueError(f"zig-zag needs T divisible by 2*n_ring, got T={T}, n_ring={n_ring}")
        zz, inv = _zigzag_indices(T, n_ring)
        body = partial(
            ring_attention_zigzag, axis_name=AXIS_SP, n_ring=n_ring, scale=scale,
            window=window, use_flash=use_flash,
        )
        out = shard_map(
            lambda q, k, v, m: body(q, k, v, m),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )(
            jnp.take(q, zz, axis=1),
            jnp.take(k, zz, axis=1),
            jnp.take(v, zz, axis=1),
            jnp.take(kv_mask, zz, axis=1),
        )
        return jnp.take(out, inv, axis=1)

    body = partial(
        ring_attention, axis_name=AXIS_SP, n_ring=n_ring, scale=scale,
        causal=causal, window=window, use_flash=use_flash,
    )
    return shard_map(
        lambda q, k, v, m: body(q, k, v, m),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, kv_mask)

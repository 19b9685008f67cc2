"""Flash-decode kernel vs the einsum reference math (interpret mode).

The kernel's contract: bit-comparable attention output to the model layer's
einsum decode path — including the dequant-folding identity
(ks·dot(K_int8, q) == dot(K_int8·ks, q) up to fp32 reassociation) and the
additive bias masking — for int8 AND bf16 caches, tile-aligned AND ragged
cache lengths (the masked tail block), and fully-masked rows. CPU CI runs
the same kernel code via pallas interpret mode (lowering and compiling for
a TPU target: tests/test_tpu_lowering.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.lm import quantize_kv
from trlx_tpu.ops.decode_attention import (
    BLOCK_T,
    decode_attn_eligible,
    decode_attn_supported,
    decode_attention,
    pick_t_block,
)

pytestmark = pytest.mark.slow


def _reference_einsum(q, k, v, bias_row, scale):
    """The model layer's decode einsum path, verbatim math."""
    scores = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale + bias_row[:, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", probs, v.astype(jnp.float32))


def _setup(B=2, T=64, h=2, d=128, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, h, d)).astype(dtype)
    k = rng.normal(size=(B, T, h, d)).astype(dtype)
    v = rng.normal(size=(B, T, h, d)).astype(dtype)
    # validity mask with left padding + causal tail invalid
    valid = np.ones((B, T), dtype=bool)
    valid[0, : min(5, T - 1)] = False
    valid[1, T - min(8, T - 1) :] = False
    bias = np.where(valid, 0.0, -1e9).astype(np.float32)
    return q, k, v, bias


# T sweep: single full (unaligned) block, exactly one block, a ragged
# multi-block tail, and an aligned multi-block cache.
RAGGED_AND_ALIGNED_T = (64, BLOCK_T, BLOCK_T + 72, 3 * BLOCK_T)


@pytest.mark.parametrize("T", RAGGED_AND_ALIGNED_T)
def test_plain_matches_einsum(T):
    q, k, v, bias = _setup(T=T)
    out = decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), None, None,
        jnp.asarray(bias), scale=0.125, interpret=True,
    )
    ref = _reference_einsum(q, k, v, bias, 0.125)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T", RAGGED_AND_ALIGNED_T)
def test_quant_matches_dequantized_einsum(T):
    q, k, v, bias = _setup(T=T, seed=1)
    kq, ks = quantize_kv(jnp.asarray(k))
    vq, vs = quantize_kv(jnp.asarray(v))
    out = decode_attention(
        jnp.asarray(q), kq, vq, ks, vs, jnp.asarray(bias), scale=0.125, interpret=True,
    )
    # reference: dequantize then einsum — the exact model-layer fallback
    k_dq = kq.astype(jnp.float32) * ks[..., None].astype(jnp.float32)
    v_dq = vq.astype(jnp.float32) * vs[..., None].astype(jnp.float32)
    ref = _reference_einsum(q, k_dq, v_dq, bias, 0.125)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_bf16_cache_matches_einsum():
    """Non-quantized caches are the compute dtype (bf16 in production)."""
    q, k, v, bias = _setup(T=BLOCK_T + 40, seed=3)
    qb = jnp.asarray(q, jnp.bfloat16)
    kb = jnp.asarray(k, jnp.bfloat16)
    vb = jnp.asarray(v, jnp.bfloat16)
    out = decode_attention(qb, kb, vb, None, None, jnp.asarray(bias), scale=0.125, interpret=True)
    ref = _reference_einsum(qb, kb, vb, bias, 0.125)
    np.testing.assert_allclose(
        np.asarray(out[:, 0], np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("T", (64, BLOCK_T + 72))
def test_fully_masked_rows_match_einsum(T):
    """A fully-masked row degrades to softmax over the raw scores (the
    additive -1e9 bias cancels in the softmax shift) — same as einsum, and
    always finite."""
    q, k, v, bias = _setup(T=T, seed=2)
    bias[0, :] = -1e9  # every key invalid for row 0
    out = decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), None, None,
        jnp.asarray(bias), scale=0.125, interpret=True,
    )
    assert np.isfinite(np.asarray(out)).all()
    ref = _reference_einsum(q, k, v, bias, 0.125)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_bench_head_layout_ragged():
    """The flagship bench head layout [h=16, d=256] at a ragged cache length
    — the exact shape class BENCH_r05 crashed on (there with B=32)."""
    q, k, v, bias = _setup(B=2, T=832, h=16, d=256, seed=4)
    kq, ks = quantize_kv(jnp.asarray(k))
    vq, vs = quantize_kv(jnp.asarray(v))
    out = decode_attention(
        jnp.asarray(q), kq, vq, ks, vs, jnp.asarray(bias), scale=0.0625, interpret=True,
    )
    k_dq = kq.astype(jnp.float32) * ks[..., None].astype(jnp.float32)
    v_dq = vq.astype(jnp.float32) * vs[..., None].astype(jnp.float32)
    ref = _reference_einsum(q, k_dq, v_dq, bias, 0.0625)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_pick_t_block():
    assert pick_t_block(64) == 64          # short cache: one full block
    assert pick_t_block(100) == 100        # unaligned short cache is legal as-is
    assert pick_t_block(BLOCK_T) == BLOCK_T
    assert pick_t_block(BLOCK_T + 1) == BLOCK_T  # long cache streams in blocks
    assert pick_t_block(832) == BLOCK_T


def test_eligibility_gate():
    """No shape is routed to the kernel, on any backend: it compiles and
    matches on the chip but loses to XLA's fused einsum there
    (decode_attention.DECODE_KERNEL_ROUTED; the head-layout and one-device
    rules behind it are pinned in tests/test_tpu_lowering.py)."""
    assert not decode_attn_eligible(16, 256, 1024, True)
    assert not decode_attn_eligible(16, 256, 831, False)


def test_supported_probe_is_cached_and_safe_off_tpu():
    """The routing verdict must answer (and cache) without a TPU: the static
    tile check runs everywhere, the must-lower step only on a TPU backend."""
    from trlx_tpu.ops import decode_attention as da

    da._PROBE_CACHE.clear()
    assert decode_attn_supported(32, 832, 16, 256, True)
    assert len(da._PROBE_CACHE) == 1
    # second call: pure cache hit (no recomputation observable, but the key
    # count must not grow)
    assert decode_attn_supported(32, 832, 16, 256, True)
    assert len(da._PROBE_CACHE) == 1


# ------------------------------------------------------------- paged kernel


def _paged_setup(B=32, h=16, d=256, bs=32, bps=4, seed=7, share=True):
    """A shared block pool + per-row tables exercising every row class the
    engine produces: tile-aligned valid spans, ragged mid-block frontiers,
    a fully-masked (dead) row, spans crossing block boundaries, and —
    when ``share`` — two rows aliasing the SAME physical prefix block
    (the prefix-cache hit layout)."""
    rng = np.random.default_rng(seed)
    T = bps * bs
    n_blocks = 1 + B * bps  # block 0 = the engine's trash block
    q = rng.normal(size=(B, h, d)).astype(np.float32)
    k_pool = rng.normal(size=(n_blocks, bs, h, d)).astype(np.float32)
    v_pool = rng.normal(size=(n_blocks, bs, h, d)).astype(np.float32)
    tables = np.arange(1, 1 + B * bps, dtype=np.int32).reshape(B, bps)
    if share:
        # rows 1..3 alias row 0's first block — prefix-cache sharing
        tables[1:4, 0] = tables[0, 0]
    # shuffle physical placement so virtual order != physical order
    perm = rng.permutation(np.unique(tables))
    remap = dict(zip(np.unique(tables).tolist(), perm.tolist()))
    tables = np.vectorize(remap.get)(tables).astype(np.int32)
    valid = np.ones((B, T), dtype=bool)
    valid[0, : bs] = False              # left pad = exactly one block
    valid[1, : bs // 2] = False         # left pad mid-block (ragged head)
    valid[2, T - bs - 3 :] = False      # frontier crosses into the last block
    valid[3, T - 1 :] = False           # frontier one short of full
    valid[4, :] = False                 # dead slot: fully masked
    valid[5, bs - 1 : 2 * bs + 1] = False  # hole spanning a block boundary
    bias = np.where(valid, 0.0, -1e9).astype(np.float32)
    return q, k_pool, v_pool, tables, bias


def _paged_reference(q, k_pool, v_pool, tables, bias, scale):
    """Gather-then-einsum: the model layer's paged fallback math."""
    B, bps = tables.shape
    bs = k_pool.shape[1]
    k = k_pool[tables].reshape(B, bps * bs, *k_pool.shape[2:])
    v = v_pool[tables].reshape(B, bps * bs, *v_pool.shape[2:])
    return _reference_einsum(q, k, v, bias, scale)


@pytest.mark.parametrize("share", (False, True))
def test_paged_plain_matches_gathered_einsum(share):
    from trlx_tpu.ops.decode_attention import paged_decode_attention

    q, k_pool, v_pool, tables, bias = _paged_setup(share=share)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool), None, None,
        jnp.asarray(tables), jnp.asarray(bias), scale=0.0625, interpret=True,
    )
    assert np.isfinite(np.asarray(out)).all()
    ref = _paged_reference(q, k_pool, v_pool, tables, bias, 0.0625)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_quant_matches_dequantized_gathered_einsum():
    from trlx_tpu.ops.decode_attention import paged_decode_attention

    q, k_pool, v_pool, tables, bias = _paged_setup(seed=8)
    kq, ks = quantize_kv(jnp.asarray(k_pool))
    vq, vs = quantize_kv(jnp.asarray(v_pool))
    out = paged_decode_attention(
        jnp.asarray(q), kq, vq, ks, vs,
        jnp.asarray(tables), jnp.asarray(bias), scale=0.0625, interpret=True,
    )
    k_dq = np.asarray(kq.astype(jnp.float32) * ks[..., None].astype(jnp.float32))
    v_dq = np.asarray(vq.astype(jnp.float32) * vs[..., None].astype(jnp.float32))
    ref = _paged_reference(q, k_dq, v_dq, tables, bias, 0.0625)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_paged_eligibility_gate():
    from trlx_tpu.ops.decode_attention import paged_decode_eligible

    # same verdict as the fixed-cache kernel: never routed today
    assert not paged_decode_eligible(16, 256, 128, 8, True)
    assert not paged_decode_eligible(16, 256, 96, 1, False)


def test_paged_supported_probe_is_cached_and_safe_off_tpu():
    from trlx_tpu.ops import decode_attention as da

    da._PROBE_CACHE.clear()
    assert da.paged_decode_supported(32, 257, 128, 8, 16, 256, True)
    n = len(da._PROBE_CACHE)
    assert da.paged_decode_supported(32, 257, 128, 8, 16, 256, True)
    assert len(da._PROBE_CACHE) == n  # pure cache hit

"""Pallas flash attention: kernel numerics + full-model parity vs the XLA
einsum path (interpret mode on CPU — the same kernel code that runs on TPU).
The reference has no kernels of its own to test; this is new surface."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models import LMConfig, TransformerLM
from trlx_tpu.ops.flash_attention import flash_attention

pytestmark = pytest.mark.slow  # excluded from `make test-fast` (see conftest)


def ref_attn(q, k, v, kvmask, scale, window=0):
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qi = jnp.arange(T)[:, None]
    ki = jnp.arange(T)[None, :]
    m = ki <= qi
    if window:
        m = m & (ki > qi - window)
    m = m[None, None] & kvmask[:, None, None, :].astype(bool)
    s = jnp.where(m, s, -1e9)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v.astype(jnp.float32))


@pytest.mark.parametrize("window", [0, 40])
def test_kernel_forward_and_grads_match_reference(window):
    rng = np.random.default_rng(0)
    b, T, h, d = 2, 256, 2, 32
    q, k, v = (jnp.asarray(rng.standard_normal((b, T, h, d)), jnp.float32) for _ in range(3))
    kvmask = jnp.ones((b, T), jnp.int32).at[0, :17].set(0)  # left padding
    qvalid = kvmask[:, :, None, None].astype(jnp.float32)
    scale = d**-0.5

    o = flash_attention(q, k, v, kvmask, scale=scale, window=window)
    r = ref_attn(q, k, v, kvmask, scale, window)
    # Pad query rows are excluded: both paths emit meaningless (differently
    # normalized) uniform mixes there, and every loss masks them.
    np.testing.assert_allclose(np.asarray((o - r) * qvalid), 0.0, atol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)) * qvalid)

    gf = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, kvmask, scale=scale, window=window)), (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: ref_attn(q, k, v, kvmask, scale, window)), (0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


def test_model_flash_matches_xla_path():
    """Full TransformerLM (alternating local layers, rotary, left padding):
    attn_impl='flash' must reproduce attn_impl='xla' logits and grads."""
    base = dict(
        vocab_size=97,
        n_layer=2,
        n_head=2,
        d_model=32,
        max_position=512,
        pos_type="rotary",
        rotary_dim=8,
        attention_layers=("global", "local"),
        window_size=64,
        dtype="float32",
    )
    rng = np.random.default_rng(1)
    B, T = 2, 256
    ids = jnp.asarray(rng.integers(0, 97, (B, T)))
    mask = jnp.ones((B, T), jnp.int32).at[0, :13].set(0)
    fmask = mask[:, :, None].astype(jnp.float32)

    xla_model = TransformerLM(LMConfig(**base, attn_impl="xla"))
    flash_model = TransformerLM(LMConfig(**base, attn_impl="flash"))
    params = xla_model.init(jax.random.PRNGKey(0), ids, mask)["params"]

    lx = xla_model.apply({"params": params}, ids, mask)["logits"]
    lf = flash_model.apply({"params": params}, ids, mask)["logits"]
    np.testing.assert_allclose(
        np.asarray(lf * fmask), np.asarray(lx * fmask), atol=2e-4
    )

    def loss(model):
        def f(p):
            out = model.apply({"params": p}, ids, mask)["logits"]
            return jnp.sum(jnp.tanh(out) * fmask)

        return f

    from jax.flatten_util import ravel_pytree

    gx = jax.grad(loss(xla_model))(params)
    gf = jax.grad(loss(flash_model))(params)
    flat_x, _ = ravel_pytree(gx)
    flat_f, _ = ravel_pytree(gf)
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_x), atol=5e-4)


def test_prefill_flash_matches_xla_cache_path():
    """Generation prefill (cache present, write offset 0) through the flash
    kernel must reproduce the einsum-over-cache path bit-for-nearly-bit:
    logits AND the written KV cache."""
    from trlx_tpu.models.lm import init_cache

    base = dict(
        vocab_size=97,
        n_layer=2,
        n_head=2,
        d_model=32,
        max_position=512,
        pos_type="rotary",
        rotary_dim=8,
        dtype="float32",
    )
    rng = np.random.default_rng(3)
    B, P, N = 2, 128, 32
    ids = jnp.asarray(rng.integers(0, 97, (B, P)))
    mask = jnp.ones((B, P), jnp.int32).at[0, :13].set(0)  # left padding

    xla_model = TransformerLM(LMConfig(**base, attn_impl="xla"))
    flash_model = TransformerLM(LMConfig(**base, attn_impl="flash"))
    params = xla_model.init(jax.random.PRNGKey(0), ids, mask)["params"]

    def prefill(model):
        cfg = model.cfg
        cache = init_cache(cfg, B, P + N)
        cache_mask = jnp.concatenate([mask, jnp.zeros((B, N), jnp.int32)], axis=1)
        return model.apply(
            {"params": params}, ids, mask, cache=cache, cache_index=0, cache_mask=cache_mask
        )

    ox = prefill(xla_model)
    of = prefill(flash_model)
    fmask = mask[:, :, None].astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(of["logits"] * fmask), np.asarray(ox["logits"] * fmask), atol=2e-4
    )
    # The cache writes are identical at every VALID slot regardless of the
    # attention engine. (Pad-slot k/v in layers > 0 differ: each engine emits
    # a different — equally meaningless — attention mix for fully-masked pad
    # query rows, which feeds the next layer's projections there. Those slots
    # have cache_mask 0 and are never read by decode.)
    cmask = np.zeros((B, P + N, 1, 1), np.float32)
    cmask[:, :P] = np.asarray(mask, np.float32)[:, :, None, None]
    for (kf, vf), (kx, vx) in zip(of["cache"], ox["cache"]):
        np.testing.assert_allclose(np.asarray(kf) * cmask, np.asarray(kx) * cmask, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vf) * cmask, np.asarray(vx) * cmask, atol=1e-5)


def test_auto_routing_thresholds(monkeypatch):
    from trlx_tpu.models import lm as lm_mod
    from trlx_tpu.models.lm import flash_eligible

    # Off-TPU (these tests), auto NEVER picks the (interpret-mode) kernel —
    # the einsum path is far faster there.
    auto = LMConfig(attn_impl="auto")
    assert not flash_eligible(auto, 512, has_cache=False)

    # On TPU with a one-device mesh, auto takes long aligned full-sequence
    # passes only.
    from trlx_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(lm_mod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert not flash_eligible(auto, 64, has_cache=False)  # short RLHF seqs
    assert flash_eligible(auto, 512, has_cache=False)
    assert flash_eligible(auto, 768, has_cache=False)  # 128-aligned, non-512
    assert not flash_eligible(auto, 512, has_cache=True)  # mid-decode replay
    assert not flash_eligible(auto, 1, has_cache=True, prefill_at_zero=False)  # decode step
    # generation prefill at write offset 0: eligible when long + aligned
    assert flash_eligible(auto, 512, has_cache=True, prefill_at_zero=True)
    assert not flash_eligible(auto, 64, has_cache=True, prefill_at_zero=True)
    assert not flash_eligible(auto, 300, has_cache=False)  # unaligned
    # a mesh larger than one closes the auto gate (no kernel is under
    # shard_map; jax refuses to partition a Mosaic call)
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", mesh_mod.make_mesh([2, 1, 1, 1], jax.devices()[:2]))
    assert not flash_eligible(auto, 512, has_cache=False)
    forced = LMConfig(attn_impl="flash")
    assert flash_eligible(forced, 48, has_cache=False)
    assert not flash_eligible(LMConfig(attn_impl="xla"), 512, has_cache=False)
    with pytest.raises(ValueError):
        flash_eligible(LMConfig(attn_impl="pallas"), 512, has_cache=False)

    from trlx_tpu.ops.flash_attention import FlashBlocks, pick_block

    # sizes follow the call's length (tests/test_flash_blocks.py has the rule's tests)
    assert pick_block(2048) == FlashBlocks(512, 2048, 512) and pick_block(768).chunk == 256
    assert pick_block(48) == FlashBlocks(48, 48, 48)

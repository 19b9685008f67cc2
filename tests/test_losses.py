"""Loss golden-value tests: GAE against a plain-Python recurrence, PPO loss
directionality, ILQL loss against hand-computed values, math primitives."""

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.ops.modeling import masked_whiten, logprobs_from_logits, topk_mask
from trlx_tpu.ops.rl_losses import gae_advantages, kl_penalty_rewards, ppo_loss
from trlx_tpu.ops.ilql_loss import ilql_loss


def reference_gae(rewards, values, gamma, lam):
    """The reference's reversed Python loop
    (reference: trlx/model/accelerate_ppo_model.py:83-97), verbatim math."""
    R = rewards.shape[1]
    lastgaelam = np.zeros(rewards.shape[0])
    advs = []
    for t in reversed(range(R)):
        nextvalues = values[:, t + 1] if t < R - 1 else 0.0
        delta = rewards[:, t] + gamma * nextvalues - values[:, t]
        lastgaelam = delta + gamma * lam * lastgaelam
        advs.append(lastgaelam.copy())
    return np.stack(advs[::-1], axis=1)


def test_gae_matches_reference_loop():
    rng = np.random.default_rng(0)
    rewards = rng.normal(size=(4, 7)).astype(np.float32)
    values = rng.normal(size=(4, 7)).astype(np.float32)
    mask = np.ones((4, 7), np.float32)
    adv, ret = gae_advantages(jnp.asarray(rewards), jnp.asarray(values), jnp.asarray(mask), 0.98, 0.95)
    expected = reference_gae(rewards, values, 0.98, 0.95)
    np.testing.assert_allclose(np.asarray(adv), expected, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ret), expected + values, rtol=1e-5, atol=1e-5)


def test_gae_masked_tail_is_clean():
    """A sample of valid length L inside an R-padded batch must get the same
    advantages as the same sample in an exactly-L batch."""
    rng = np.random.default_rng(1)
    L, R = 4, 8
    rewards = np.zeros((1, R), np.float32)
    values = np.zeros((1, R), np.float32)
    rewards[0, :L] = rng.normal(size=L)
    values[0, :L] = rng.normal(size=L)
    mask = np.zeros((1, R), np.float32)
    mask[0, :L] = 1
    adv_padded, _ = gae_advantages(jnp.asarray(rewards), jnp.asarray(values), jnp.asarray(mask), 0.99, 0.9)
    adv_exact, _ = gae_advantages(
        jnp.asarray(rewards[:, :L]), jnp.asarray(values[:, :L]), jnp.ones((1, L), jnp.float32), 0.99, 0.9
    )
    np.testing.assert_allclose(np.asarray(adv_padded)[0, :L], np.asarray(adv_exact)[0], rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(adv_padded)[0, L:] == 0)


def test_kl_penalty_terminal_score_on_last_valid_token():
    lp = jnp.zeros((2, 5))
    rlp = jnp.zeros((2, 5))
    mask = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], jnp.int32)
    scores = jnp.asarray([2.0, 3.0])
    rewards, kl = kl_penalty_rewards(lp, rlp, mask, scores, jnp.asarray(0.1))
    rewards = np.asarray(rewards)
    assert rewards[0, 2] == 2.0 and rewards[0, 3] == 0.0  # last VALID token
    assert rewards[1, 4] == 3.0


def test_ppo_loss_direction():
    """At ratio == 1 the pg gradient w.r.t. logprobs equals −whitened_adv /
    n_tokens — positive (whitened) advantage pushes the action's logprob up."""
    from trlx_tpu.ops.modeling import masked_whiten

    rng = np.random.default_rng(2)
    b, R = 2, 4
    old_logprobs = jnp.asarray(rng.normal(size=(b, R)).astype(np.float32)) * 0.1
    old_values = jnp.zeros((b, R), jnp.float32)
    rewards = jnp.asarray(rng.normal(size=(b, R)).astype(np.float32))
    mask = jnp.ones((b, R), jnp.float32)

    def loss_of(lp):
        loss, _ = ppo_loss(lp, old_values, old_logprobs, old_values, rewards, mask,
                           gamma=1.0, lam=0.95, cliprange=0.2, cliprange_value=0.2, vf_coef=0.0)
        return loss

    g = np.asarray(jax.grad(loss_of)(old_logprobs))
    adv, _ = gae_advantages(rewards, old_values, mask, 1.0, 0.95)
    wadv = np.asarray(masked_whiten(adv, mask))
    np.testing.assert_allclose(g, -wadv / (b * R), rtol=1e-4, atol=1e-6)


def test_ppo_loss_stats_keys():
    b, R = 2, 3
    z = jnp.zeros((b, R), jnp.float32)
    loss, stats = ppo_loss(z, z, z, z, z, jnp.ones((b, R)), gamma=1.0, lam=1.0,
                           cliprange=0.2, cliprange_value=0.2, vf_coef=1.0)
    for k in ["loss", "pg_loss", "vf_loss", "mean_kl", "pg_clipfrac"]:
        assert k in stats


def test_ilql_loss_golden():
    """Hand-computable single-sample case: 2 tokens, 1 action."""
    V_vocab = 3
    logits = jnp.zeros((1, 2, V_vocab), jnp.float32)
    # one action at hidden position 0, action token = input_ids[1] = 2
    qs = (jnp.asarray([[[0.0, 0.0, 1.0]]]), jnp.asarray([[[0.0, 0.0, 0.5]]]))
    target_qs = (jnp.asarray([[[0.0, 0.0, 2.0]]]), jnp.asarray([[[0.0, 0.0, 1.5]]]))
    vs = jnp.asarray([[0.5, 9.9]])  # V(s0)=0.5; V(s1) zeroed by dones
    input_ids = jnp.asarray([[1, 2]])
    attn = jnp.ones((1, 2), jnp.int32)
    actions_ixs = jnp.asarray([[0]])
    rewards = jnp.asarray([[1.0]])
    dones = jnp.asarray([[1, 0]])
    loss, stats = ilql_loss(logits, qs, target_qs, vs, input_ids, attn, actions_ixs,
                            rewards, dones, gamma=0.9, tau=0.7, cql_scale=0.0, awac_scale=0.0)
    # Q_ = r + gamma * Vnext*done = 1.0 + 0; loss_q = (1-1)^2 + (0.5-1)^2 = 0.25
    # targetQ = min(2.0, 1.5) = 1.5 >= V=0.5 ⇒ loss_v = 0.7*(1.0)^2 = 0.7
    np.testing.assert_allclose(float(stats["losses/loss_q"]), 0.25, rtol=1e-5)
    np.testing.assert_allclose(float(stats["losses/loss_v"]), 0.7, rtol=1e-5)


def test_masked_whiten_ignores_padding():
    x = jnp.asarray([[1.0, 2.0, 3.0, 100.0]])
    mask = jnp.asarray([[1, 1, 1, 0]], jnp.float32)
    w = np.asarray(masked_whiten(x, mask))
    assert abs(w[0, :3].mean()) < 1e-5
    assert w[0, 3] == 0.0


def test_logprobs_from_logits():
    logits = jnp.asarray([[[1.0, 2.0, 3.0]]])
    labels = jnp.asarray([[2]])
    lp = float(logprobs_from_logits(logits, labels)[0, 0])
    expected = 3.0 - np.log(np.exp(1) + np.exp(2) + np.exp(3))
    np.testing.assert_allclose(lp, expected, rtol=1e-5)


def test_topk_mask():
    x = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    out = np.asarray(topk_mask(x, 2))
    assert out[0, 1] == 5.0 and out[0, 2] == 3.0
    assert np.isinf(out[0, 0]) and np.isinf(out[0, 3])


# ---------------------------------------------------------------------------
# Fused-logprob kernel parity (interpret mode on CPU) + segment-aware losses
# ---------------------------------------------------------------------------

import pytest

from trlx_tpu.ops.fused_logprob import (
    ROW_TILE_FLOOR,
    ROW_TILES,
    HeadTiles,
    fused_logprob,
    head_tiles,
    naive_logprob,
    routed_logprob,
)


def uniform_tiles(bn, bv, V):
    """One row tile and one vocabulary tile for all three kernels, the
    backward's narrowed to 256 as `head_tiles` narrows it."""
    bwd = (bn, min(bv, 256) if V > 256 else bv)
    return HeadTiles((bn, bv), bwd, bwd)


def _head_case(rng, B, T, D, V, dtype, tied, bias):
    x = jnp.asarray(rng.normal(size=(B, T, D)), dtype) * 0.3
    w = (
        jnp.asarray(rng.normal(size=(V, D) if tied else (D, V)), dtype) * 0.2
    )
    b = jnp.asarray(rng.normal(size=(V,)), jnp.float32) if bias else None
    y = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    return x, w, b, y


@pytest.mark.parametrize("tied,bias", [(True, False), (False, False), (False, True)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_logprob_matches_naive(tied, bias, dtype):
    """Interpret-mode kernel == materialized log_softmax chain, at a shape
    that exercises BOTH padded tails: N=2*19=38 (pads to 128) and V=300
    with block_v=128 (partial 44-wide vocab tail block)."""
    rng = np.random.default_rng(0)
    x, w, b, y = _head_case(rng, 2, 19, 64, 300, dtype, tied, bias)
    lp_k, lse_k, ent_k = fused_logprob(
        x, w, y, b, tied=tied, interpret=True, tiles=uniform_tiles(128, 128, 300)
    )
    lp_n, lse_n, ent_n = naive_logprob(x, w, y, b, tied=tied)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(lp_k), np.asarray(lp_n), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_n), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(ent_k), np.asarray(ent_n), rtol=tol, atol=tol)


@pytest.mark.parametrize("tied,bias", [(True, False), (False, False), (False, True)])
def test_fused_logprob_grads_match_naive(tied, bias):
    """jax.grad through the custom VJP == autodiff through the naive chain
    (fp32, a weighted sum of all three outputs so every cotangent is live)."""
    rng = np.random.default_rng(1)
    x, w, b, y = _head_case(rng, 2, 19, 64, 300, jnp.float32, tied, bias)

    def scalar(fn):
        def f(x, w, b):
            lp, lse, ent = fn(x, w, y, b, tied=tied)
            return jnp.sum(lp) + 0.5 * jnp.sum(lse) - 0.25 * jnp.sum(ent)

        return f

    fused = lambda x_, w_, y_, b_, tied: fused_logprob(
        x_, w_, y_, b_, tied=tied, interpret=True, tiles=uniform_tiles(128, 128, 300)
    )
    args = (x, w, b)
    argnums = (0, 1, 2) if bias else (0, 1)
    g_k = jax.grad(scalar(fused), argnums=argnums)(*args)
    g_n = jax.grad(scalar(naive_logprob), argnums=argnums)(*args)
    for a, bb in zip(g_k, g_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-4, atol=1e-5)


# Every row tile the rule can return, and one call whose three kernels differ.
_TILE_CASES = {
    **{f"rows{bn}": uniform_tiles(bn, 128, 300) for bn in ROW_TILES},
    "mixed": HeadTiles(fwd=(512, 256), dx=(256, 128), dw=(128, 256)),
}
_OPERAND_CASES = {  # tied, bias, rows' dtype, weight's dtype
    "untied": (False, False, jnp.float32, jnp.float32),
    "tied": (True, False, jnp.float32, jnp.float32),
    "biased": (False, True, jnp.float32, jnp.float32),
    "f32_rows_bf16_weight": (False, True, jnp.float32, jnp.bfloat16),  # ILQL's Q heads
}


@pytest.mark.parametrize("operands", sorted(_OPERAND_CASES))
@pytest.mark.parametrize("tiles", sorted(_TILE_CASES))
def test_fused_logprob_parity_at_every_row_tile(tiles, operands):
    """Forward and all three gradients against `naive_logprob` at each tile
    `head_tiles` can choose: 2 x 333 = 666 rows pad to 768 (128, 256) or
    1,024 (512), V = 300 leaves a partial vocabulary tail tile."""
    tied, bias, x_dtype, w_dtype = _OPERAND_CASES[operands]
    rng = np.random.default_rng(5)
    x, w, b, y = _head_case(rng, 2, 333, 64, 300, x_dtype, tied, bias)
    w = w.astype(w_dtype)

    def scalar(fn):
        def f(x, w, b):
            lp, lse, ent = fn(x, w, y, b, tied=tied)
            return jnp.sum(lp) + 0.5 * jnp.sum(lse) - 0.25 * jnp.sum(ent), (lp, lse, ent)

        return f

    fused = lambda *a, **k: fused_logprob(*a, interpret=True, tiles=_TILE_CASES[tiles], **k)
    argnums = (0, 1, 2) if bias else (0, 1)
    (_, out_k), g_k = jax.value_and_grad(scalar(fused), argnums=argnums, has_aux=True)(x, w, b)
    (_, out_n), g_n = jax.value_and_grad(scalar(naive_logprob), argnums=argnums, has_aux=True)(x, w, b)
    for a, bb in zip(out_k, out_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-5, atol=1e-5)
    for a, bb in zip(g_k, g_n):
        assert a.dtype == bb.dtype and a.shape == bb.shape
        tol = 2e-2 if a.dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(bb, np.float32), rtol=tol, atol=tol * 0.1)


# (N, D, V, rows' itemsize, weight's itemsize, bias) of the claimed cells' head calls
_CLAIMED_HEAD_CALLS = {
    "gptj6b-l8.ppo-128x896 train": (8 * 896, 4096, 50400, 2, 2, True),
    "gptj6b-l8.ppo-128x896 scoring": (32 * 896, 4096, 50400, 2, 2, True),
    "gptneo1.3b.ilql-256 Q head": (8 * 255, 4096, 50257, 4, 2, True),
    "gptneo1.3b.ilql-256 LM head": (8 * 255, 2048, 50257, 2, 2, False),
}


@pytest.mark.parametrize("call", sorted(_CLAIMED_HEAD_CALLS))
def test_head_tiles_takes_512_rows_at_the_claimed_cells(call):
    N, D, V, xi, wi, bias = _CLAIMED_HEAD_CALLS[call]
    tiles = head_tiles(N, D, V, xi, wi, bias)
    assert tiles.fwd[0] == 512
    assert tiles.weight_passes(N) == -(-N // 512)
    assert all(bn in ROW_TILES and bv % 128 == 0 for bn, bv in tiles)


@pytest.mark.parametrize("N,rows", [(1, 128), (38, 128), (255, 128), (256, 256), (500, 256), (512, 512),
                                    (513, 128), (577, 128), (897, 512), (1025, 128), (1200, 256), (7168, 512)])
def test_head_tiles_rule_by_row_count(N, rows):
    """Fewer rows than a tile keep the floor; a tile is refused where padding
    up to it would waste an eighth of the rows or more (513 rows at 512: half)."""
    tiles = head_tiles(N, 4096, 50400)
    assert tiles.fwd[0] == rows
    padded = -(-N // tiles.rows) * tiles.rows
    assert rows == ROW_TILE_FLOOR or (padded - N) * 8 < padded


def test_head_tiles_shrink_to_the_budget():
    """A budget too small for 512 rows gives the next tile; one too small for
    any keeps the floor with the widest vocabulary tile (the tiles every
    call had before the rule), whatever they need."""
    from trlx_tpu.ops.tiling import fused_logprob_vmem_bytes as need

    args = (7168, 4096, 50400, 2, 2, True)
    full = head_tiles(*args)
    assert need("fwd", 4096, *full.fwd, 2, 2, True) <= 64 * 2**20
    tight = head_tiles(*args, budget=need("fwd", 4096, 512, 256, 2, 2, True) - 1)
    assert tight.fwd[0] == 256
    assert head_tiles(*args, budget=1) == HeadTiles((128, 512), (128, 256), (128, 256))


def test_routed_logprob_hands_a_vocabulary_major_weight_over_transposed(monkeypatch):
    """Where the device holds an untied [D, V] weight as the rows of [V, D]
    (`held_vocab_major`), the kernels get its transpose as a tied weight:
    same results, and the gradient comes back in the weight's own shape."""
    from trlx_tpu.ops import fused_logprob as fl

    rng = np.random.default_rng(6)
    x, w, b, y = _head_case(rng, 2, 70, 64, 300, jnp.float32, False, True)
    seen = []
    real = fl.fused_logprob
    monkeypatch.setattr(fl, "fused_logprob", lambda x, w, *a, **k: seen.append((w.shape, k["tied"])) or real(x, w, *a, **k))

    def loss(x, w, b):
        lp, lse, ent = routed_logprob(x, w, y, b, tied=False, mode="force")
        return jnp.sum(lp) + 0.5 * jnp.sum(lse) - 0.25 * jnp.sum(ent)

    assert not fl.held_vocab_major(w)  # the CPU client holds arrays row-major
    plain = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)
    monkeypatch.setattr(fl, "held_vocab_major", lambda w: True)
    held = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)
    assert seen == [((64, 300), False), ((300, 64), True)]
    assert held[1][1].shape == w.shape
    for a, bb in zip(jax.tree_util.tree_leaves(held), jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["force", "off"])
def test_routed_logprob_masked_rows_are_zero_and_finite(mode):
    """Ragged masks incl. FULLY-masked rows: outputs exactly 0 there, grads
    finite everywhere, on both the kernel route and the naive fallback."""
    rng = np.random.default_rng(2)
    x, w, b, y = _head_case(rng, 2, 8, 64, 300, jnp.float32, False, True)
    mask = jnp.ones((2, 8), jnp.int32).at[0, 5:].set(0).at[1, :].set(0)  # row 1 fully masked

    lp, lse, ent = routed_logprob(x, w, y, b, tied=False, mode=mode, mask=mask)
    for v in (lp, lse, ent):
        v = np.asarray(v)
        assert np.all(np.isfinite(v))
        assert np.all(v[0, 5:] == 0) and np.all(v[1] == 0)

    def loss(x, w, b):
        lp, lse, ent = routed_logprob(x, w, y, b, tied=False, mode=mode, mask=mask)
        return jnp.sum(lp) + jnp.sum(lse) + jnp.sum(ent)

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


def test_logprobs_from_logits_mask_skips_garbage_rows():
    """Non-finite logits in masked rows must not leak NaN (the fallback's
    pad-safety contract); unmasked rows match the no-mask result."""
    logits = jnp.asarray([[[1.0, 2.0, 3.0], [np.inf, -np.inf, np.nan]]])
    labels = jnp.asarray([[2, 0]])
    mask = jnp.asarray([[1, 0]], jnp.int32)
    out = np.asarray(logprobs_from_logits(logits, labels, mask))
    assert np.isfinite(out).all() and out[0, 1] == 0.0
    np.testing.assert_allclose(
        out[0, 0], float(logprobs_from_logits(logits[:, :1], labels[:, :1])[0, 0])
    )


def test_label_logit_identity_lp_plus_lse():
    """The fused-ILQL identity: gathered label LOGIT == logprob + logsumexp
    (how the trainer reads per-action Q values out of the streaming head)."""
    rng = np.random.default_rng(3)
    x, w, b, y = _head_case(rng, 2, 6, 32, 200, jnp.float32, False, True)
    lp, lse, _ = routed_logprob(x, w, y, b, tied=False, mode="force")
    logits = (x @ w + b).astype(jnp.float32)
    gathered = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(lp + lse), np.asarray(gathered), rtol=1e-4, atol=1e-4)


def test_gae_segment_ids_match_unpacked():
    """Two episodes packed into one row == the same episodes in separate
    rows: the segment-gated recurrence resets bootstrap AND lam-carry."""
    rng = np.random.default_rng(4)
    R = 5
    r = jnp.asarray(rng.normal(size=(2, R)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, R)), jnp.float32)
    m = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 0]], jnp.float32)
    a_u, ret_u = gae_advantages(r, v, m, 0.95, 0.9)

    rp = jnp.concatenate([r[0, :3], r[1, :4]])[None]
    vp = jnp.concatenate([v[0, :3], v[1, :4]])[None]
    seg = jnp.asarray([[1, 1, 1, 2, 2, 2, 2]])
    a_p, ret_p = gae_advantages(
        rp, vp, jnp.ones((1, 7), jnp.float32), 0.95, 0.9, segment_ids=seg
    )
    np.testing.assert_allclose(
        np.asarray(a_p)[0], np.concatenate([a_u[0, :3], a_u[1, :4]]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ret_p)[0], np.concatenate([ret_u[0, :3], ret_u[1, :4]]), rtol=1e-5, atol=1e-6
    )


def test_ppo_loss_packed_per_sequence_stats():
    """mean_kl / mean_return normalize by the true episode count (n_seqs)
    in packed layout — matching the unpacked per-row means."""
    rng = np.random.default_rng(5)
    R = 5
    m = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 0]], jnp.float32)
    lp = jnp.asarray(rng.normal(size=(2, R)), jnp.float32) * 0.01 * m
    olp = lp + jnp.asarray(rng.normal(size=(2, R)), jnp.float32) * 0.01 * m
    v = jnp.asarray(rng.normal(size=(2, R)), jnp.float32) * m
    r = jnp.asarray(rng.normal(size=(2, R)), jnp.float32) * m
    kw = dict(gamma=0.95, lam=0.9, cliprange=0.2, cliprange_value=0.2, vf_coef=1.0)
    _, st_u = ppo_loss(lp, v, olp, v, r, m, **kw)

    def packrow(a):
        return jnp.concatenate([a[0, :3], a[1, :4]])[None]

    seg = jnp.asarray([[1, 1, 1, 2, 2, 2, 2]])
    mp = jnp.ones((1, 7), jnp.float32)
    _, st_p = ppo_loss(
        packrow(lp), packrow(v), packrow(olp), packrow(v), packrow(r), mp,
        segment_ids=seg, n_seqs=2, **kw,
    )
    for k in ("mean_kl", "mean_return"):
        np.testing.assert_allclose(float(st_u[k]), float(st_p[k]), rtol=1e-4, atol=1e-6)


def test_ilql_loss_terms_matches_dense_wrapper():
    """ilql_loss (dense wrapper) == ilql_loss_terms fed with manually
    gathered Q / target-Q / CQL-NLL and the AWAC scalar."""
    from trlx_tpu.ops.ilql_loss import action_tokens, ilql_loss_terms

    rng = np.random.default_rng(6)
    b, T, A, V = 2, 8, 3, 11
    logits = jnp.asarray(rng.normal(size=(b, T, V)), jnp.float32)
    qs = tuple(jnp.asarray(rng.normal(size=(b, A, V)), jnp.float32) for _ in range(2))
    tqs = tuple(jnp.asarray(rng.normal(size=(b, A, V)), jnp.float32) for _ in range(2))
    vs = jnp.asarray(rng.normal(size=(b, A + 1)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, size=(b, T)), jnp.int32)
    attn = jnp.ones((b, T), jnp.int32)
    aix = jnp.asarray([[1, 2, 3], [2, 3, 4]], jnp.int32)
    rew = jnp.asarray(rng.normal(size=(b, A)), jnp.float32)
    dones = jnp.ones((b, A + 1), jnp.float32).at[:, -1].set(0)
    kw = dict(gamma=0.9, tau=0.7, cql_scale=0.3, awac_scale=0.5)

    loss_d, st_d = ilql_loss(logits, qs, tqs, vs, ids, attn, aix, rew, dones, **kw)

    actions = action_tokens(ids, aix)
    gather = lambda q: jnp.take_along_axis(q, actions[..., None], axis=-1)[..., 0]
    nlls = [-logprobs_from_logits(q, actions) for q in qs]
    attn1 = attn[:, 1:].astype(jnp.float32)
    nll = -logprobs_from_logits(logits[:, :-1], ids[:, 1:])
    awac = jnp.sum(nll * attn1) / jnp.maximum(jnp.sum(attn1), 1.0)
    loss_t, st_t = ilql_loss_terms(
        [gather(q) for q in qs], [gather(q) for q in tqs], nlls, vs, rew, dones, awac, **kw
    )
    np.testing.assert_allclose(float(loss_d), float(loss_t), rtol=1e-6)
    for k in st_d:
        np.testing.assert_allclose(float(st_d[k]), float(st_t[k]), rtol=1e-6)

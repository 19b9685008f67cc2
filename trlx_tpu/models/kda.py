"""The gated delta-rule mixer (Kimi Delta Attention, KDA) of a "kda" layer
(`LMConfig.mixer_layers`), in its two forms.

Per layer, on the block's normed input x [b, T, d_model], with H = kda_heads,
D = kda_head_dim (keys and values alike), K = kda_conv, and m = 1 on a real
token, 0 on padding:

    q = silu(conv_K(W_q (m x))),  k = silu(conv_K(W_k (m x))),  v = silu(conv_K(W_v (m x)))
                                  depthwise, causal, no bias, zeros before the first token; each times m
    q = q / |q| * D^-1/2,  k = k / |k|                     per head, float32 (|.|^2 + 1e-6)
    g_t = -exp(A_log) softplus(W_f^ W_fv x + dt_bias) m    log-decay, one a KEY CHANNEL, float32, <= 0
    beta_t = sigmoid(W_b x) m                              one a head, float32
    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T               per head [D, D], float32, S = 0 before the first token
    o_t = S_t^T q_t
    out = W_o(w * o / sqrt(mean(o^2) + eps) * sigmoid(W_g^ W_gv x + b_g))    the mean over each head's D

A padded position has g = 0, beta = 0 and adds nothing to the convolution's
window: the state passes through it as it is. Under LEFT padding the state
before a row's first real token is 0 and the row equals its unpadded self.

*Chunked* (`kda_chunked`: a pass over many tokens: the train step, scoring,
the prefill). Inside a chunk of C positions, with G_t the sum of g up to and
including t and S the state carried into the chunk, the corrections
U_t = beta_t (v_t - S_{t-1}'^T k_t) solve a unit lower triangular system
(the WY / UT form):

    A_ts = (k_t exp(G_t - G_s)) . k_s   s < t        B_ts = (q_t exp(G_t - G_s)) . k_s   s <= t
    T = (I + Diag(beta) A)^-1
    U = T (beta V) - T (beta K exp(G)) S
    O = (Q exp(G)) S + B U
    S <- Diag(exp(G_C)) S + (K exp(G_C - G))^T U

and the chunks follow each other under a `lax.scan` that carries S. The decay
is one a key channel, so A and B do not factor into one product: exp(-G_s)
alone overflows float32 where a channel forgets fast. The chunk is cut into
sub-blocks of `SUB` positions. Between two sub-blocks the exponent is split at
the LATER block's start, exp(G_t - start) exp(start - G_s), both halves at or
below 0, one product a block row; inside a sub-block the [SUB, SUB, D]
differences are formed and summed as they are. No `exp` of a positive sum is
ever taken. T comes from forward substitution in the SUB x SUB diagonal blocks
(a row a step, every block of the call at once) and block elimination over
them in pairs (16 -> 32 -> 64, the pairs of a round at once), in float32 with
`Precision.HIGHEST` products (`unit_lower_inverse`); no series form: on keys
that share their direction those are wrong by orders of magnitude. Its
backward pass is hand-written (`jax.custom_vjp`): dM = -T^T dT T^T on the
strictly lower triangle, two products on the T the forward pass kept, not
autodiff through the substitution's steps (PERF.md section 6, PR 40).
*Recurrent* (`kda_step`: one token, a decode step): the update above, all in
float32, on the cache's state.

Decays, sums, T and the state (in the cache leaf, carried between chunks, in
the step) are float32 whatever the compute dtype; the products on the matrix
unit take operands in the compute dtype and accumulate in float32.

The cache of a layer is `(conv [b, K-1, 3 H D], state [b, H, D, D])`: the
last K-1 inputs of the three convolutions (q | k | v) in the compute dtype,
and the state in FLOAT32. No slot axis, no write offset: each step overwrites
both whole.
"""

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from trlx_tpu.models.lm import KDA_SCAN_OUT, LMConfig, QDense, drawn_in
from trlx_tpu.models.ssm import dt_bias_init

# Tokens one call of the chunked form holds at once. Its float32 arrays are
# [rows, T, H, D], 134 MB each for a train batch of 8 x 1024 at H 32, D 128,
# and the backward pass of one call keeps some thirty of them: 4.8 GB for a
# whole train batch by the compiler's own count, which with the rest of the
# train step passed the chip's 15.75 GB (PERF.md section 6, PR 39). The rows
# are independent, so a pass goes through a group of rows at a time, each
# group recomputed in its own backward pass (`jax.checkpoint`: what a group
# keeps for it is its operands). One 1,024-token row a group is also the
# fastest on the chip: a layer's forward-and-gradient 46.7 ms for 69.1 at
# 2,048 and 72.6 at 4,096 (same section).
SCAN_TOKENS = 1024
CHUNK = 64  # positions of a chunk: one triangular solve, one step of the scan that carries the state
SUB = 16  # positions of a sub-block: where the within-chunk decay is re-based
NORM_EPS = 1e-6  # of the L2 norm of q and k
HIGHEST = jax.lax.Precision.HIGHEST  # of every product that builds T or takes its gradient: float32 operands as they are


def inner_width(cfg: LMConfig) -> int:
    """Channels of q, of k and of v: every head's D."""
    return cfg.kda_heads * cfg.kda_head_dim


def cache_shapes(cfg: LMConfig, batch: int):
    """((shape, dtype), (shape, dtype)) of one layer's (conv, state) leaves."""
    return (((batch, cfg.kda_conv - 1, 3 * inner_width(cfg)), cfg.compute_dtype),
            ((batch, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.dtype(jnp.float32)))


def chunk_sizes(tokens: int, chunk: int = CHUNK):
    """(positions of a chunk, of a sub-block) over `tokens` positions: a pass
    shorter than a chunk is one chunk of whole sub-blocks."""
    size = min(chunk, -(-tokens // SUB) * SUB)
    return size, math.gcd(size, SUB)


def solve_lane_fill(tokens: int) -> float:
    """Share of a 128-lane tile the row arrays of the triangular solve's
    substitution fill in a pass over `tokens` positions: a row of a sub-block's
    inverse is a sub-block wide (`_diagonal_inverses`), 16 / 128 = 0.125. With
    the systems along the lanes it would be 1.0, and the pass took twice the
    time on the chip (PERF.md section 6, PR 40): the counter names the form,
    it is not a score."""
    sub = chunk_sizes(tokens)[1]
    return sub / (-(-sub // 128) * 128)


def a_log_init(key, shape, dtype=jnp.float32):
    """log of A drawn uniform in [1, 16) (the published initialiser)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def kda_step(state, q, k, v, g, beta):
    """One token. state [b, H, D, Dv] float32, q, k [b, H, D], v [b, H, Dv],
    g [b, H, D] float32 (the log-decay), beta [b, H] float32. Returns
    (o [b, H, Dv] float32, new state). S_t^T q is taken as
    S'^T q + beta (k . q) u, so both reads of the decayed state (with k, with
    q) are sums over one pass through it, before the one pass that writes."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    decayed = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=2))  # [b, H, Dv]
    o = jnp.sum(decayed * q[..., None], axis=2) + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


def _diagonal_inverses(diag):
    """(I + diag)^-1 of strictly lower triangular blocks [..., sub, sub] by
    forward substitution, every block at once, a row a step: row r of the
    inverse is its unit vector less row r of `diag` times the rows above it,
    written into its place in the one array the steps share."""
    sub = diag.shape[-1]
    inv = jnp.broadcast_to(jnp.eye(sub, dtype=diag.dtype), diag.shape)
    for r in range(1, sub):
        above = jnp.einsum("...s,...sc->...c", diag[..., r, :r], inv[..., :r, :], precision=HIGHEST)
        inv = inv.at[..., r, :].add(-above)
    return inv


def _eliminated(first, below, second):
    """[[F, 0], [b, S]]^-1 = [[F^-1, 0], [-S^-1 b F^-1, S^-1]] from the
    diagonal blocks' inverses `first` [..., p, p] and `second` [..., q, q]."""
    corner = -jnp.matmul(second, jnp.matmul(below, first, precision=HIGHEST), precision=HIGHEST)
    top = jnp.concatenate([first, jnp.zeros(first.shape[:-1] + second.shape[-1:], first.dtype)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([corner, second], axis=-1)], axis=-2)


def _unit_lower_inverse(m, sub: int):
    """The forward pass of `unit_lower_inverse`. The diagonal blocks' inverses
    are merged in rounds of pairs, every pair of a round at once (a chunk of
    four blocks: 16 -> 32 -> 64); a block without a partner joins the tail,
    the inverse of m's last rows and columns."""
    size = m.shape[-1]
    inv = _diagonal_inverses(jnp.stack([m[..., i:i + sub, i:i + sub] for i in range(0, size, sub)], axis=-3))
    tail, width = None, sub
    while True:
        blocks = inv.shape[-3]
        if blocks % 2:
            at, last = (blocks - 1) * width, inv[..., -1, :, :]
            tail = last if tail is None else _eliminated(last, m[..., at + width:, at:at + width], tail)
            if blocks == 1:
                return tail
            inv = inv[..., :-1, :, :]
        pairs = range(0, blocks // 2 * 2 * width, 2 * width)
        below = jnp.stack([m[..., i + width:i + 2 * width, i:i + width] for i in pairs], axis=-3)
        inv = _eliminated(inv[..., 0::2, :, :], below, inv[..., 1::2, :, :])
        width *= 2


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(m, sub: int):
    """T = (I + m)^-1 of strictly lower triangular `m` [..., C, C] float32, C a
    multiple of `sub`: forward substitution inside the sub x sub diagonal
    blocks (`_diagonal_inverses`), then block elimination:
    [[L, 0], [m21, L2]]^-1 = [[L^-1, 0], [-L2^-1 m21 L^-1, L2^-1]].
    Its backward pass is written by hand, dm = -T^T dT T^T: two products on
    the T the forward pass returns, where autodiff would walk back through
    every substitution step, slice and concatenation. Only the strictly lower
    part of `m` is read, so only that part has a gradient."""
    return _unit_lower_inverse(m, sub)


def _unit_lower_inverse_fwd(m, sub):
    inv = _unit_lower_inverse(m, sub)
    return inv, inv


def _unit_lower_inverse_bwd(sub, inv, ct):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (jnp.tril(-jnp.matmul(inv_t, jnp.matmul(ct, inv_t, precision=HIGHEST), precision=HIGHEST), -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _diagonal_blocks(q, k, cum):
    """(A, B) inside the sub-blocks. q, k, cum [..., blocks, sub, D] float32
    -> [..., blocks, sub, sub] each: sum_d k_t k_s exp(G_t - G_s) and the same
    with q_t, over s <= t of the same sub-block, 0 elsewhere. The [sub, sub, D]
    differences are summed as they are formed; recomputed in the backward pass
    (`jax.checkpoint`): kept, they would be the largest array of the layer."""
    sub = q.shape[-2]
    gap = cum[..., :, None, :] - cum[..., None, :, :]  # [..., t, s, D]
    seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    reach = k[..., None, :, :] * jnp.exp(jnp.where(seen, gap, -jnp.inf))
    return jnp.sum(k[..., :, None, :] * reach, axis=-1), jnp.sum(q[..., :, None, :] * reach, axis=-1)


def kda_chunked(q, k, v, g, beta, chunk: int, dtype):
    """The pass over [b, T] in chunks, from a zero state. q, k [b, T, H, D]
    (normalised, q scaled), v [b, T, H, Dv], g [b, T, H, D] float32 (the
    log-decay, 0 on padding), beta [b, T, H] float32 (0 on padding). Returns
    (o [b, T, H, Dv] float32, the state after position T - 1 [b, H, D, Dv]
    float32)."""
    b, T, H, D = q.shape
    f32 = jnp.float32
    C, sub = chunk_sizes(T, chunk)
    pad = -T % C
    if pad:  # g 0, beta 0: the added positions leave the state as it is
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v, g, beta))
    n, m = (T + pad) // C, C // sub
    heads_first = lambda t: jnp.swapaxes(t.reshape((b, n, C) + t.shape[2:]), 2, 3)  # [b, n, H, C, ...]
    q, k, v, g, beta = (heads_first(t.astype(f32)) for t in (q, k, v, g, beta))
    prod = lambda spec, *ops: jnp.einsum(spec, *(o.astype(dtype) for o in ops), preferred_element_type=f32)

    cum = jnp.cumsum(g, axis=3)  # [b, n, H, C, D], falling from 0
    blocks = lambda t: t.reshape(t.shape[:3] + (m, sub) + t.shape[4:])
    start = blocks(cum - g)[..., 0, :]  # [b, n, H, m, D]: the sum before each sub-block's first position
    # between sub-blocks: the exponent split at the later block's start, both halves <= 0
    within = jnp.exp(blocks(cum) - start[..., None, :])
    before = k[..., None, :, :] * jnp.exp(jnp.minimum(start[..., None, :] - cum[..., None, :, :], 0.0))  # [b, n, H, m, C, D]
    earlier = jnp.arange(C)[None, None, :] < (jnp.arange(m) * sub)[:, None, None]  # [m, 1, C]: s before block i
    between = lambda rows: jnp.where(earlier, prod("bnhmtd,bnhmsd->bnhmts", blocks(rows) * within, before), 0.0)
    a_in, b_in = jax.checkpoint(_diagonal_blocks)(blocks(q), blocks(k), blocks(cum))
    a_in = jnp.where(jnp.tril(jnp.ones((sub, sub), bool), -1), a_in, 0.0)  # s < t: a position does not correct itself
    on_diagonal = lambda t: (t[..., None, :] * jnp.eye(m, dtype=f32)[:, None, :, None]).reshape(b, n, H, C, C)
    A = between(k).reshape(b, n, H, C, C) + on_diagonal(a_in)
    B = between(q).reshape(b, n, H, C, C) + on_diagonal(b_in)

    solve = unit_lower_inverse(beta[..., None] * A, sub)  # [b, n, H, C, C]
    decay = jnp.exp(cum)
    w = prod("bnhts,bnhsd->bnhtd", solve, beta[..., None] * k * decay)
    u = prod("bnhts,bnhsv->bnhtv", solve, beta[..., None] * v)
    to_end = k * jnp.exp(cum[..., -1:, :] - cum)

    def one_chunk(state, chunk_in):
        w, u, q_in, B, to_end, end_decay = chunk_in
        corrections = u - prod("bhtd,bhdv->bhtv", w, state)
        o = prod("bhtd,bhdv->bhtv", q_in, state) + prod("bhts,bhsv->bhtv", B, corrections)
        return state * end_decay[..., None] + prod("bhtd,bhtv->bhdv", to_end, corrections), o

    per_chunk = tuple(jnp.moveaxis(t, 1, 0) for t in (w, u, q * decay, B, to_end, decay[..., -1, :]))
    last, o = jax.lax.scan(one_chunk, jnp.zeros((b, H, D, v.shape[-1]), f32), per_chunk)
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * C, H, v.shape[-1])
    return o[:, :T], last


class KDAMixer(nn.Module):
    """The mixer of a "kda" layer (module docstring). `mask` [b, T] marks the
    real tokens of `x`. `cache` None: a pass over many tokens, no state kept.
    A cache and one token: the recurrent update of the cache's state. A cache
    and a block: the prefill: the chunked form from a zero state, leaving
    each row's state and convolution window as of its last position (its last
    real token: the rollout pads on the left). Returns (out, new cache)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, mask, cache=None):
        cfg = self.cfg
        dtype, f32 = cfg.compute_dtype, jnp.float32
        b, T, _ = x.shape
        H, D, K, inner = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, inner_width(cfg)
        dense = lambda feats, name, bias=False: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=bias,
                                                       draw_dtype=cfg.draw_dtype, name=name)
        vector = lambda name, init, shape: self.param(name, init, shape, cfg.params_dtype).astype(f32)
        # torch's Conv1d default for a depthwise kernel K wide: uniform in +-1/sqrt(K)
        conv_init = drawn_in(cfg.draw_dtype, lambda key, shape, dt=f32: jax.random.uniform(
            key, shape, dt, -1.0, 1.0) / math.sqrt(K))
        m = mask.astype(f32)[..., None]

        with jax.named_scope("kda_in"):
            real = x * m.astype(x.dtype)  # no bias below: a padded position hands the convolution zeros
            qkv = jnp.concatenate([dense(inner, name)(real) for name in ("q_proj", "k_proj", "v_proj")], axis=-1)
            a = -jnp.exp(vector("A_log", a_log_init, (H,)))
            step_in = dense(inner, "f_b_proj")(dense(D, "f_a_proj")(x)).astype(f32) + vector("dt_bias", dt_bias_init, (inner,))
            g = (jax.nn.softplus(step_in) * m).reshape(b, T, H, D) * a[:, None]  # [b, T, H, D], <= 0
            beta = jax.nn.sigmoid(dense(H, "b_proj")(x).astype(f32)) * m  # [b, T, H]
            gate = dense(inner, "g_b_proj", bias=True)(dense(D, "g_a_proj")(x))

        step = cache is not None and T == 1
        with jax.named_scope("kda_conv"):
            w_conv = jnp.concatenate([vector(name, conv_init, (K, inner)) for name in ("q_conv", "k_conv", "v_conv")], axis=-1)
            # the K-1 inputs before the block: the cache's window, or zeros
            before = cache[0].astype(qkv.dtype) if step else jnp.zeros((b, K - 1, 3 * inner), qkv.dtype)
            window = jnp.concatenate([before, qkv], axis=1)  # [b, K-1+T, 3 inner]
            conv = jax.nn.silu(sum(window[:, j:j + T].astype(f32) * w_conv[j] for j in range(K))) * m
            new_conv = window[:, -(K - 1):]
            q, k, v = (conv[..., i * inner:(i + 1) * inner].reshape(b, T, H, D) for i in range(3))
            unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + NORM_EPS)
            q, k, v = (unit(q) * D ** -0.5).astype(dtype), unit(k).astype(dtype), v.astype(dtype)

        with jax.named_scope("kda_scan"):
            if step:
                o, state = kda_step(cache[1], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                group = max(1, SCAN_TOKENS // T)
                if b > group and b % group == 0:
                    split = lambda t: t.reshape((b // group, group) + t.shape[1:])
                    o, state = jax.lax.map(jax.checkpoint(lambda ops: kda_chunked(*ops, CHUNK, dtype)),
                                           tuple(split(t) for t in (q, k, v, g, beta)))
                    o, state = o.reshape((b,) + o.shape[2:]), state.reshape((b,) + state.shape[2:])
                    # a remat'd block keeps this (lm.py's policy): its recomputation then has no use for the
                    # pass above, and the groups' own recomputation is the only one (2 forwards, not 3)
                    o = checkpoint_name(o, KDA_SCAN_OUT)
                else:
                    o, state = kda_chunked(q, k, v, g, beta, CHUNK, dtype)
                if cache is not None:
                    # the prefill: nothing reads a layer's state before the decode loop, and a
                    # scheduler that therefore leaves it for last keeps its operands alive
                    # under every layer after it (models/ssm.py)
                    o, state = jax.lax.optimization_barrier((o, state))

        with jax.named_scope("kda_gate"):
            scale = vector("o_norm", nn.initializers.ones_init(), (D,))
            normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.ln_eps) * scale
            gated = (normed * jax.nn.sigmoid(gate.astype(f32)).reshape(b, T, H, D)).reshape(b, T, inner).astype(dtype)
        with jax.named_scope("kda_out"):
            out = dense(cfg.d_model, "o_proj")(gated)
        new_cache = None if cache is None else (new_conv.astype(cache[0].dtype), state)
        return out, new_cache

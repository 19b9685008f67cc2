"""The Mamba-2 state-space mixer of a "mamba" layer (`LMConfig.mixer_layers`),
in its two forms.

Per layer, on the block's normed input h [b, T, d_model], with H = ssm_heads,
P = ssm_head_dim, N = ssm_state, K = ssm_conv, one B/C group shared by all
heads, and m = 1 on a real token, 0 on padding:

    [z | xBC | dt] = W_in (m h)                       H P | H P + 2 N | H, no bias
    xBC_t = m_t silu(sum_j w[j] * xBC_{t-K+1+j} + b)  depthwise, causal, zeros before the first token
    [x | B | C] = xBC                                 H P -> [H, P] | N | N
    D_t = m_t softplus(dt_t + dt_bias)                per head, float32
    A = -exp(A_log)                                   per head, float32
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t        per head [P, N], float32, S = 0 before the first token
    y_t = S_t C_t + D x_t                             per head [P]
    out = W_out(w * g / sqrt(mean(g^2) + eps)),  g = y silu(z) in float32, the mean over all H P

Padding adds nothing to the state and nothing to the convolution's window
(m before W_in and after the convolution, as the published code masks; m on
D_t besides, so a padded position leaves the state as it is: exp(0) S + 0).
Under LEFT padding the state before a row's first real token is 0 and the
row equals its unpadded self.

*Chunked* (`ssd_chunked`: a pass over many tokens — the train step, scoring,
the prefill): the same y from cumulative sums of D_t A inside chunks of
ssm_chunk positions, one decay-masked product C B^T a chunk (shared by the
heads), each chunk's end state, a recurrence over the chunk end states, and
C_t applied to the state carried into the chunk. Decays and sums are float32;
the four products take operands in the compute dtype and accumulate in float32
(x as it arrives: the step D_t rides on the decay mask, and on the weight of
each position in its chunk's end state).
*Recurrent* (`ssd_step`: one token, a decode step): the update above, all in
float32, on the cache's state.

The cache of a layer is `(conv [b, K-1, H P + 2 N], state [b, H, P, N])`: the
last K-1 inputs of the convolution in the compute dtype, and the state in
FLOAT32 whatever the compute dtype (it is summed into once a token, 896 times
a rollout). No slot axis, no write offset: each step overwrites both whole.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from trlx_tpu.models.lm import LMConfig, QDense, drawn_in

# Tokens one call of the chunked scan holds at once: its decay masks are
# [rows, chunks, H, Q, Q] float32, 0.5 GB for a train batch of 8 x 1024 at
# H 64, Q 256. A scoring pass over a rollout chunk (32 rows) goes through a
# group of rows at a time: the rows are independent.
SCAN_TOKENS = 8192


def conv_width(cfg: LMConfig) -> int:
    """Channels the convolution runs over: x, B and C."""
    return cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_state


def cache_shapes(cfg: LMConfig, batch: int):
    """((shape, dtype), (shape, dtype)) of one layer's (conv, state) leaves."""
    return (((batch, cfg.ssm_conv - 1, conv_width(cfg)), cfg.compute_dtype),
            ((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.dtype(jnp.float32)))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniform in [0.001, 0.1] (the
    published initialiser: it sets the state's time scale)."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001)), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def a_log_init(key, shape, dtype=jnp.float32):
    """A = -(1 .. H): log(1 .. H)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(dtype)


def lane_fill(cfg: LMConfig, tokens: int) -> float:
    """Share of a 128-lane tile the chunked scan's widest float32 results
    fill over `tokens` positions: they are held positions-minor (`ssd_chunked`),
    so a chunk of 256 fills its tiles and a head's width does not matter."""
    minor = min(cfg.ssm_chunk, tokens)
    return minor / (-(-minor // 128) * 128)


def ssd_chunked(x, dt, a, B, C, chunk: int, dtype):
    """The scan over [b, T] in chunks. x [b, T, H, P] as it arrives (the
    compute dtype), dt [b, T, H] float32 (0 on padding), a [H] float32
    (negative), B, C [b, T, N]. Returns (y [b, T, H, P] float32 without the D
    skip, the state after position T - 1 [b, H, P, N] float32).

    Everything a head owns is held POSITIONS-minor, [b, c, H, P, Q]: the
    products want their contraction (the chunk's positions) there, and a head
    narrower than a 128-lane tile (P 64) would fill half of every tile it is
    minor in. x is transposed once, as it arrives; y once, as it leaves; both
    are pinned row-major on the mixer's side of that transpose, or the
    compiler hands the positions-minor layout on to the projections and pays
    for it where [b, T] splits into chunks (a relayout and a copy a pass:
    PERF.md section 6, PR 38)."""
    b, T, H, P = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:  # dt 0: the added positions leave the state as it is
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, B, C))
    c = (T + pad) // Q
    chunks = lambda t: t.reshape((b, c, Q) + t.shape[2:])
    heads_first = lambda t: jnp.swapaxes(chunks(t), 2, 3)
    rows = lambda t: with_layout_constraint(t, Layout(major_to_minor=tuple(range(t.ndim))))
    x = heads_first(rows(x.reshape(b, T + pad, H * P))).reshape(b, c, H, P, Q)
    dt, B, C = heads_first(dt), chunks(B), chunks(C)  # dt [b, c, H, Q]
    f32 = jnp.float32
    prod = lambda spec, *ops: jnp.einsum(spec, *(o.astype(dtype) for o in ops), preferred_element_type=f32)

    cum = jnp.cumsum(dt * a[:, None], axis=3)  # [b, c, H, Q], falling from 0
    # inside a chunk: position l reads s <= l through exp(cum_l - cum_s), and
    # the step dt_s rides on the mask (x goes to the products as it is)
    gap = cum[..., :, None] - cum[..., None, :]  # [b, c, H, l, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), gap, -jnp.inf))
    scores = prod("bcln,bcsn->bcls", C, B)  # one group: shared by the heads
    y = prod("bchls,bchps->bchpl", scores[:, :, None] * decay * dt[..., None, :], x)
    # each chunk's own end state, from zero
    to_end = jnp.exp(cum[..., -1:] - cum) * dt  # [b, c, H, Q]
    ends = prod("bchps,bcsn->bchpn", x.astype(f32) * to_end[:, :, :, None, :], B)
    # the recurrence over chunk end states: the state carried INTO each chunk
    chunk_decay = jnp.exp(cum[..., -1])  # [b, c, H]

    def carry(state, chunk_in):
        end, factor = chunk_in
        return state * factor[..., None, None] + end, state

    last, into = jax.lax.scan(carry, jnp.zeros((b, H, P, B.shape[-1]), f32),
                              (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    into = jnp.moveaxis(into, 0, 1)  # [b, c, H, P, N]
    y = y + prod("bcln,bchpn->bchpl", C, into) * jnp.exp(cum)[:, :, :, None, :]
    y = rows(jnp.swapaxes(y.reshape(b, c, H * P, Q), 2, 3).reshape(b, c * Q, H * P))
    return y.reshape(b, c * Q, H, P)[:, :T], last


def ssd_step(state, x, dt, a, B, C):
    """One token. state [b, H, P, N] float32, x [b, H, P], dt [b, H] float32,
    B, C [b, N]. Returns (y [b, H, P] float32 without the D skip, new state)."""
    f32 = jnp.float32
    x, B, C = x.astype(f32), B.astype(f32), C.astype(f32)
    state = (state * jnp.exp(dt * a)[..., None, None]
             + (dt[..., None] * x)[..., None] * B[:, None, None, :])
    return jnp.sum(state * C[:, None, None, :], axis=-1), state


class SSMMixer(nn.Module):
    """The mixer of a "mamba" layer (module docstring). `mask` [b, T] marks the
    real tokens of `x`. `cache` None: a pass over many tokens, no state kept.
    A cache and one token: the recurrent update of the cache's state. A cache
    and a block: the prefill — the chunked scan from a zero state, leaving
    each row's state and convolution window as of its last position (its last
    real token: the rollout pads on the left). Returns (out, new cache)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, mask, cache=None):
        cfg = self.cfg
        dtype, f32 = cfg.compute_dtype, jnp.float32
        b, T, _ = x.shape
        H, P, N, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
        inner, width = H * P, conv_width(cfg)
        dense = lambda feats, name: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=False,
                                           draw_dtype=cfg.draw_dtype, name=name)
        vector = lambda name, init, shape: self.param(name, init, shape, cfg.params_dtype).astype(f32)
        # torch's Conv1d default for a depthwise kernel K wide: uniform in +-1/sqrt(K)
        conv_init = drawn_in(cfg.draw_dtype, lambda key, shape, dt=f32: jax.random.uniform(
            key, shape, dt, -1.0, 1.0) / math.sqrt(K))
        w_conv, b_conv = vector("conv_kernel", conv_init, (K, width)), vector("conv_bias", conv_init, (width,))
        dt_bias = vector("dt_bias", dt_bias_init, (H,))
        a = -jnp.exp(vector("A_log", a_log_init, (H,)))
        skip = vector("D", nn.initializers.ones_init(), (H,))
        m = mask.astype(f32)[..., None]

        with jax.named_scope("ssm_in"):
            zxbcdt = dense(inner + width + H, "in_proj")(x * m.astype(x.dtype))
            z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:inner + width], zxbcdt[..., inner + width:]
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias) * m  # [b, T, H]

        step = cache is not None and T == 1
        with jax.named_scope("ssm_conv"):
            # the K-1 inputs before the block: the cache's window, or zeros
            before = cache[0].astype(xbc.dtype) if step else jnp.zeros((b, K - 1, width), xbc.dtype)
            window = jnp.concatenate([before, xbc], axis=1)  # [b, K-1+T, width]
            conv = sum(window[:, j:j + T].astype(f32) * w_conv[j] for j in range(K)) + b_conv
            xbc_out = (jax.nn.silu(conv) * m).astype(dtype)
            new_conv = window[:, -(K - 1):]
        xs = xbc_out[..., :inner]
        B, C = xbc_out[..., inner:inner + N], xbc_out[..., inner + N:]

        with jax.named_scope("ssm_scan"):
            heads = xs.reshape(b, T, H, P)
            if step:
                y, state = ssd_step(cache[1], heads[:, 0], dt[:, 0], a, B[:, 0], C[:, 0])
            else:
                group = max(1, SCAN_TOKENS // T)
                if b > group and b % group == 0:
                    split = lambda t: t.reshape((b // group, group) + t.shape[1:])
                    y, state = jax.lax.map(lambda ops: ssd_chunked(ops[0], ops[1], a, ops[2], ops[3], cfg.ssm_chunk, dtype),
                                           tuple(split(t) for t in (heads, dt, B, C)))
                    state = state.reshape((b,) + state.shape[2:])
                else:
                    y, state = ssd_chunked(heads, dt, a, B, C, cfg.ssm_chunk, dtype)
                if cache is not None:
                    # the prefill: nothing reads a layer's state before the decode loop, and a
                    # scheduler that therefore leaves it for last keeps its operands (this
                    # layer's x, B and projections) alive under every layer after it
                    y, state = jax.lax.optimization_barrier((y, state))
            # the D skip where a row is inner wide, every lane of a tile in use
            y = y.reshape(b, T, inner) + jnp.repeat(skip, P) * xs.astype(f32)

        with jax.named_scope("ssm_gate"):
            g = y * jax.nn.silu(z.astype(f32))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.ln_eps)
            scale = vector("norm_scale", nn.initializers.ones_init(), (inner,))
            gated = (scale * g).astype(dtype)
        with jax.named_scope("ssm_out"):
            out = dense(cfg.d_model, "out_proj")(gated)
        new_cache = None if cache is None else (new_conv.astype(cache[0].dtype), state)
        return out, new_cache

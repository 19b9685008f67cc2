"""The lightning (constant-decay linear) attention mixer of a "lightning"
layer (`LMConfig.mixer_layers`), in its two forms.

Per layer, on the block's normed input x [b, T, d_model], with H =
lightning_heads, D = lightning_head_dim, and m = 1 on a real token, 0 on
padding:

    q, k, v = W_q x, W_k x, W_v x                      H x D each, no bias, no convolution
    q = w_q * q / rms(q),  k = w_k * k / rms(k)        per head over its D channels (`qk_norm`), one weight for all heads
    q, k = RoPE(q), RoPE(k)                            all D channels (`rotary_layers` "lightning"), the row's own positions
    S_t = lambda_h S_{t-1} + m_t k_t v_t^T             per head [D, D], float32, S = 0 before the first token
    o_t = S_t^T q_t / sqrt(D)
    out = W_o(w_o * o / rms(o) * sigmoid(W_g x))       the mean over all H D channels; the gate with `lightning_output_gate`

lambda_h = exp(-2^(-8 h / H)), h = 1 .. H: one constant a head, a buffer with
no gradient (`decay_rates`). A padded position adds nothing; under LEFT
padding the state before a row's first real token is 0, so the constant
decay over the padding changes nothing and the row equals its unpadded self.

*Chunked* (`lightning_chunked`: a pass over many tokens: the train step,
scoring, the prefill). Inside a chunk of C positions, i = 0 .. C - 1, with S
the state carried into the chunk:

    O = ((Q K^T) * Lambda) V + Diag(lambda^(i+1)) Q S      Lambda_is = lambda^(i-s) for s <= i, 0 above
    S <- lambda^C S + (K * lambda^(C-1-s))^T V

Lambda is data-independent: one [C, C] table a head made from the rates, no
`exp` of a data-dependent sum and no triangular solve. Every exponent is at
or below 0. The chunks follow each other under a `lax.scan` that carries S.
A length the chunk does not divide is padded with zeros AT THE FRONT (a zero
state decays to itself), never at the end, where the padding's decay would
reach the state the prefill hands to the decode loop.
*Recurrent* (`lightning_step`: one token, a decode step): the update above,
all in float32, on the cache's state.

The state (in the cache leaf, carried between chunks, in the step), the decay
tables and every sum are float32 whatever the compute dtype; the products on
the matrix unit take operands in the compute dtype and accumulate in float32.

The cache of a layer is `(state [b, H, D, D],)` in FLOAT32: no convolution
window, no slot axis, no write offset; each step overwrites it whole.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.models.lm import LMConfig, QDense, qk_normed, rotate_heads

# Tokens one call of the chunked form holds at once: the rows are independent,
# so a pass over more goes through a group of rows at a time (`models/kda.py`'s
# rule), each group recomputed in its own backward pass. Its float32 arrays
# are the output [rows, T, H, D] and the chunk states the scan keeps for its
# backward pass.
SCAN_TOKENS = 16384
CHUNK = 128  # positions of a chunk: one [C, C] table a head, one step of the scan that carries the state


def inner_width(cfg: LMConfig) -> int:
    """Channels of q, of k and of v: every head's D."""
    return cfg.lightning_heads * cfg.lightning_head_dim


def cache_shapes(cfg: LMConfig, batch: int):
    """((shape, dtype),) of one layer's only leaf, the state."""
    return (((batch, cfg.lightning_heads, cfg.lightning_head_dim, cfg.lightning_head_dim), jnp.dtype(jnp.float32)),)


def decay_rates(heads: int) -> np.ndarray:
    """-log lambda_h = 2^(-8 h / H), h = 1 .. H (Lightning Attention-2's slopes), float32 [H]."""
    return np.exp2(-8.0 * np.arange(1, heads + 1) / heads).astype(np.float32)


def lightning_step(state, q, k, v, rates):
    """One token. state [b, H, D, Dv] float32, q, k [b, H, D], v [b, H, Dv]
    (zeros on a padded position), rates [H]. Returns (o [b, H, Dv] float32,
    new state): S = lambda S + k v^T, o = S^T q (unscaled)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    state = state * jnp.exp(-rates)[:, None, None] + k[..., None] * v[..., None, :]
    return jnp.sum(state * q[..., None], axis=2), state


def lightning_chunked(q, k, v, rates, chunk: int, dtype):
    """The pass over [b, T] in chunks, from a zero state. q, k [b, T, H, D], v
    [b, T, H, Dv] (zeros on padded positions), rates [H] float32. Returns
    (o [b, T, H, Dv] float32 (unscaled), the state after position T - 1
    [b, H, D, Dv] float32)."""
    b, T, H, D = q.shape
    f32 = jnp.float32
    C = min(chunk, T)
    pad = -T % C
    if pad:  # at the front: a zero state stays zero through it
        q, k, v = (jnp.pad(t, ((0, 0), (pad, 0), (0, 0), (0, 0))) for t in (q, k, v))
    n = (T + pad) // C
    per_chunk = lambda t: jnp.moveaxis(t.astype(dtype).reshape((b, n, C) + t.shape[2:]), 1, 0)  # [n, b, C, H, D]
    i = jnp.arange(C, dtype=f32)
    gap = i[:, None] - i[None, :]  # [C, C]: i - s
    table = jnp.where(gap >= 0, jnp.exp(-rates[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)  # [H, C, C]
    from_state = jnp.exp(-rates[None, :] * (i[:, None] + 1.0))[None, :, :, None]  # [1, C, H, 1]: lambda^(i+1)
    to_end = jnp.exp(-rates[None, :] * (C - 1.0 - i[:, None]))[None, :, :, None]  # [1, C, H, 1]: lambda^(C-1-s)
    whole = jnp.exp(-rates * C)[None, :, None, None]  # lambda^C
    prod = lambda spec, *ops: jnp.einsum(spec, *(o.astype(dtype) for o in ops), preferred_element_type=f32)

    def one_chunk(state, chunk_in):
        q_c, k_c, v_c = chunk_in
        inside = prod("bihd,bshd->bhis", q_c, k_c) * table
        o = prod("bhis,bshv->bihv", inside, v_c) + prod("bihd,bhdv->bihv", q_c.astype(f32) * from_state, state)
        return state * whole + prod("bshd,bshv->bhdv", k_c.astype(f32) * to_end, v_c), o

    last, o = jax.lax.scan(one_chunk, jnp.zeros((b, H, D, v.shape[-1]), f32), tuple(per_chunk(t) for t in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * C, H, v.shape[-1])
    return o[:, pad:], last


class LightningMixer(nn.Module):
    """The mixer of a "lightning" layer (module docstring). `mask` [b, T]
    marks the real tokens of `x`; `rope` the pass's rotary tables
    (`lm.rope_tables`, None without rotary positions). `cache` None: a pass
    over many tokens, no state kept. A cache and one token: the recurrent
    update of the cache's state. A cache and a block: the prefill: the chunked
    form from a zero state, leaving each row's state as of its last position
    (its last real token: the rollout pads on the left). Returns (out, new
    cache)."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, mask, rope, cache=None):
        cfg = self.cfg
        dtype, f32 = cfg.compute_dtype, jnp.float32
        b, T, _ = x.shape
        H, D, inner = cfg.lightning_heads, cfg.lightning_head_dim, inner_width(cfg)
        dense = lambda feats, name: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=False,
                                           draw_dtype=cfg.draw_dtype, name=name)
        rates = jnp.asarray(decay_rates(H))

        with jax.named_scope("lightning_in"):
            q, k, v = (dense(inner, name)(x).reshape(b, T, H, D) for name in ("q_proj", "k_proj", "v_proj"))
            gate = dense(inner, "g_proj")(x) if cfg.lightning_output_gate else None
            v = v * mask.astype(v.dtype)[..., None, None]  # a padded position adds nothing to the state
        if cfg.qk_norm:
            q, k = qk_normed(cfg, q, k)
        if rope is not None:
            q, k = rotate_heads(cfg, q, rope), rotate_heads(cfg, k, rope)

        step = cache is not None and T == 1
        with jax.named_scope("lightning_scan"):
            if step:
                o, state = lightning_step(cache[0], q[:, 0], k[:, 0], v[:, 0], rates)
                o = o[:, None]
            else:
                group = max(1, SCAN_TOKENS // T)
                if b > group and b % group == 0:
                    split = lambda t: t.reshape((b // group, group) + t.shape[1:])
                    o, state = jax.lax.map(jax.checkpoint(lambda ops: lightning_chunked(*ops, rates, CHUNK, dtype)),
                                           tuple(split(t) for t in (q, k, v)))
                    o, state = o.reshape((b,) + o.shape[2:]), state.reshape((b,) + state.shape[2:])
                else:
                    o, state = lightning_chunked(q, k, v, rates, CHUNK, dtype)
                if cache is not None:
                    # the prefill: nothing reads a layer's state before the decode loop, and a
                    # scheduler that therefore leaves it for last keeps its operands alive
                    # under every layer after it (models/ssm.py)
                    o, state = jax.lax.optimization_barrier((o, state))
            o = o * D ** -0.5

        with jax.named_scope("lightning_gate"):
            scale = self.param("o_norm", nn.initializers.ones_init(), (inner,), cfg.params_dtype).astype(f32)
            o = o.reshape(b, T, inner)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.ln_eps) * scale
            if gate is not None:
                o = o * jax.nn.sigmoid(gate.astype(f32))
        with jax.named_scope("lightning_out"):
            out = dense(cfg.d_model, "o_proj")(o.astype(dtype))
        return out, None if cache is None else (state,)

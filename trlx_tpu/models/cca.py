"""Compressed convolutional attention (`LMConfig.attention` "cca"): grouped
attention whose queries and keys are mixed along the sequence, by two causal
convolutions, before the scores. With H = n_head query heads over G =
n_kv_head key/value heads of d_h = head_dim, x the block's normed input:

    q~ = x W_q [H d_h],  k~ = x W_k [G d_h],  u = [q~ | k~]      H + G heads of d_h channels
    a_t = sum_j w0[j] * u_(t-K0+1+j) + b0        depthwise, K0 = cca_time0 taps a channel
    c_t = sum_j a_(t-K1+1+j) W1[j] + b1          a d_h x d_h matrix a tap a HEAD, K1 = cca_time1 taps
    q_t[h] = c_t[h] + (q~_t[h] + k~_t[h // g]) / 2              the mean of the PRE-convolution tensors, g = H / G
    k_t[j] = c_t[H + j] + (mean over the group's q~_t + k~_t[j]) / 2
    v_t = [x_t W_v | x_(t-1) W_vs]               the first G / 2 value heads see the token, the others the one before
    q^ = sqrt(d_h) q / |q|,  k^ = sqrt(d_h) exp(theta_j) k / |k|   L2 over a head, one learned theta a key head
    rotary on the first rotary_dim channels of q^ and k^, then causal grouped attention at scale 1 / sqrt(d_h)

The input is padded ONCE on the left, with (K0 - 1) + (K1 - 1) zero positions
of u: what lies before a row's first token is u = 0 (so a = b0 there), and the
previous token of the first one has value 0. A left-padded row computes what
it would alone: u and the shifted value's source are zeroed at pad positions
(`token_mask`), which is all of a pad position the first real token can see.

The cache of a layer is `(k [b, T, G, d_h], v [b, T, G, d_h], window [b, K0 +
K1 - 2, (H + G) d_h], shifted [b, 1, G / 2 d_h])`: keys and values a slot as
"mha" keeps them (after the convolutions, the norm and rotary), the last
positions of u (from which a decode step recomputes the a's it needs) and the
previous token's x W_vs. A prefill at write offset 0 leaves the window and the
shifted value as of each row's last position (rows are left-padded, so that
is the block's last); a decode step advances both.

Everything ahead of the scores is projections, two small convolutions and
element-wise work, left to XLA under the scope `cca_mix`; the attention core
goes where an "mha" layer of the same shape goes (the flash kernels, the
ranged read of a decode step, the einsum).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.models.lm import LMConfig, QDense, drawn_in, flash_core, rotate_heads, write_cache
from trlx_tpu.ops.kv_read import attend_cache, ranged_read


def channels(cfg: LMConfig) -> int:
    """Channels the convolutions run over: every query head and every key head."""
    return (cfg.n_head + cfg.kv_heads) * cfg.head_dim


def window_positions(cfg: LMConfig) -> int:
    """Positions of u a layer keeps: what both convolutions reach back over."""
    return cfg.cca_time0 + cfg.cca_time1 - 2


def shifted_width(cfg: LMConfig) -> int:
    """Value channels that come from the previous token: half the value heads."""
    return cfg.kv_heads // 2 * cfg.head_dim


def cache_shapes(cfg: LMConfig, batch: int, slots: int):
    """((shape, dtype) x 4) of one layer's (k, v, window, shifted) leaves."""
    dtype = cfg.compute_dtype
    kv = ((batch, slots, cfg.kv_heads, cfg.head_dim), dtype)
    return kv, kv, ((batch, window_positions(cfg), channels(cfg)), dtype), ((batch, 1, shifted_width(cfg)), dtype)


class CCAttention(nn.Module):
    """The mixer of an attention layer under `attention: cca` (module
    docstring). `token_mask` [b, q_len] marks the real tokens of `x`."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, attn_bias, rope, cache=None, cache_index=None, flash_mask=None, token_mask=None):
        cfg = self.cfg
        dtype, f32 = cfg.compute_dtype, jnp.float32
        b, t, _ = x.shape
        H, G, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
        g, K0, K1, C = H // G, cfg.cca_time0, cfg.cca_time1, channels(cfg)
        dense = lambda feats, name: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=False,
                                           draw_dtype=cfg.draw_dtype, name=name)
        vector = lambda name, init, shape: self.param(name, drawn_in(cfg.draw_dtype, init), shape, cfg.params_dtype)
        # Drawn from the seed, none at its neutral value: a bias of 0 or a
        # temperature of 1 would let a program that forgot it pass every comparison.
        small = nn.initializers.normal(0.02)
        w0 = vector("conv0_kernel", nn.initializers.normal(K0 ** -0.5), (K0, C)).astype(f32)
        b0 = vector("conv0_bias", small, (C,)).astype(f32)
        w1 = vector("conv1_kernel", nn.initializers.lecun_normal(in_axis=(0, 2), out_axis=3, batch_axis=(1,)),
                    (K1, H + G, hd, hd)).astype(dtype)
        b1 = vector("conv1_bias", small, (H + G, hd)).astype(f32)
        theta = vector("k_temperature", nn.initializers.normal(0.1), (G,)).astype(f32)
        m = jnp.ones((b, t, 1), dtype) if token_mask is None else token_mask.astype(dtype)[..., None]

        with jax.named_scope("cca_mix"):
            q_pre, k_pre = dense(H * hd, "q_proj")(x), dense(G * hd, "k_proj")(x)
            u = jnp.concatenate([q_pre, k_pre], axis=-1) * m
            shift_src = dense(shifted_width(cfg), "v_shift_proj")(x) * m
            if cache is None:  # nothing lies before the block: the one left padding
                before_u = jnp.zeros((b, window_positions(cfg), C), dtype)
                before_v = jnp.zeros((b, 1, shifted_width(cfg)), dtype)
            else:  # a prefill finds the zeros `init_cache` left, a decode step what the last call left
                before_u, before_v = cache[2].astype(dtype), cache[3].astype(dtype)
            padded = jnp.concatenate([before_u, u], axis=1)  # positions -(K0 - 1) - (K1 - 1) .. t - 1
            reach = t + K1 - 1  # a at positions -(K1 - 1) .. t - 1
            a = (sum(padded[:, j:j + reach].astype(f32) * w0[j] for j in range(K0)) + b0).astype(dtype)
            a = a.reshape(b, reach, H + G, hd)
            # one product a head over (tap, channel); its result in the compute dtype: the CPU client has no
            # bf16 x bf16 -> float32 product with a batch axis (a rehearsal runs there)
            taps = jnp.stack([a[:, j:j + t] for j in range(K1)], axis=2)
            c = jnp.einsum("btjhd,jhde->bthe", taps, w1).astype(f32) + b1
            qp = q_pre.astype(f32).reshape(b, t, G, g, hd)
            kp = k_pre.astype(f32).reshape(b, t, G, hd)
            q = c[:, :, :H].reshape(b, t, G, g, hd) + 0.5 * (qp + kp[:, :, :, None])
            k = c[:, :, H:] + 0.5 * (jnp.mean(qp, axis=3) + kp)
            unit = lambda z: z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-12) * np.sqrt(hd)
            q = unit(q).reshape(b, t, H, hd).astype(dtype)
            k = (unit(k) * jnp.exp(theta)[:, None]).astype(dtype)
            shifted = jnp.concatenate([before_v, shift_src[:, :-1]], axis=1)
            v = jnp.concatenate([dense(shifted_width(cfg), "v_proj")(x), shifted], axis=-1).reshape(b, t, G, hd)
            if rope is not None:
                q, k = rotate_heads(cfg, q, rope), rotate_heads(cfg, k, rope)

        new_cache = written = read = None
        if cache is not None:
            # the block's last position is every row's last token: a left-padded prefill, or the step's one token
            written = (write_cache(cache[0], k, cache_index), write_cache(cache[1], v, cache_index))
            new_cache = written + (padded[:, padded.shape[1] - window_positions(cfg):].astype(cache[2].dtype),
                                   shift_src[:, -1:].astype(cache[3].dtype))
            if flash_mask is None:  # one token at one traced offset takes the ranged read
                read = ranged_read(int(cache[0].shape[1]), t, cache_index)

        scale = 1.0 / np.sqrt(hd)
        with jax.named_scope("attn_full"):
            if flash_mask is not None:  # a pass with no cache, or a prefill: it attends over its own block, as "mha" does
                out = flash_core(q, k, v, flash_mask, scale, dtype)
            elif read is not None:
                out = read(q, written, attn_bias, scale, dtype)
            elif cache is not None:
                with jax.named_scope("kv_read"):  # the whole cache, where `ranged_read` gave none
                    out = attend_cache(q, written, attn_bias, scale, dtype)
            else:
                out = attend_cache(q, (k, v), attn_bias, scale, dtype)
        return dense(cfg.d_model, "c_proj")(out.reshape(b, t, H * hd)), new_cache

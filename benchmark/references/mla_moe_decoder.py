"""Plain reference of the latent-attention, sparse-expert decoder (Kimi-K2 /
DeepSeek-V3 family): the forward pass in straightforward float32
`jax.numpy`, matmuls at `jax.default_matmul_precision("highest")`, no
kernels, no cache, no batching tricks, attention in its UNABSORBED form only
(per-head keys and values built from the latent), the experts as a plain
loop over the held ids. Written from the published description (HF
`modeling_deepseek.py` of moonshotai/Kimi-K2, `configuration_deepseek.py`),
not from `trlx_tpu/models/`; it reads the program's parameter tree only for
the weights.

Per layer, pre-norm, sequential residual, RMSNorm (eps from the config), no
biases, untied head after a final RMSNorm:

    MLA   c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x (nope | rope)
          [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_rope = RoPE(k_r), one for all heads
          [k_nope | v] = c_kv W_kvb -> heads x (nope | v)
          scores = (q_nope . k_nope + RoPE(q_rope) . k_rope) * s, causal, softmax, . v, heads joined, W_o
          s = (nope + rope)^-1/2 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1 (YaRN)
          RoPE on YaRN-corrected frequencies, INTERLEAVED pairs (0,1), (2,3), ...: the published code
          de-interleaves q and k the same way and then rotates halves, which gives the same scores.
    FFN   a "dense" layer: W_down(silu(W_gate x) * W_up x)
          an "experts" layer: sigma = sigmoid(x W_g) over all n_experts (float32); the
          experts_per_token largest of sigma + b; w_e = sigma_e / sum_chosen sigma * routed_scaling_factor;
          y = sum over chosen of w_e Expert_e(x) + Shared(x); n_group 1, so no group limit.

Departures from the published model, each on purpose:
  * no vision tower (MoonViT): the catalog's language `config` holds none of
    its keys and this trainer's rollouts are token ids;
  * only the routed experts `experts_held = [first, first + count)` exist:
    the sum runs over chosen AND held, what the absent experts would add is
    left out (one chip's share of an expert-parallel deployment); routing is
    over all n_experts all the same;
  * the vocabulary is the slice the configuration keeps (embedding and head
    have that many rows);
  * `b` (e_score_correction_bias) and every weight are drawn from the seed;
  * positions of a left-padded row count from its first real token (the
    program's convention for rollouts).
One sub-layer's weights are cast up to float32 at a time, inside a jitted
function, so the reference fits beside a trainer that fills the chip.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt_decoder import NEG, PRECISIONS, _f32, _rounding  # the same table of coarser reruns

__all__ = ["PRECISIONS", "forward"]


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _yarn_inv_freq(dim, base, sc):
    """`DeepseekV3YarnRotaryEmbedding`: per-dimension blend of the plain and
    the interpolated frequencies over a linear ramp between two correction
    dimensions."""
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra, freq_inter = 1.0 / base**exponent, 1.0 / (sc["factor"] * base**exponent)
    find = lambda rot: dim * math.log(sc["original_max_position_embeddings"] / (rot * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(find(sc["beta_fast"])), 0), min(math.ceil(find(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return freq_inter * (1 - mask) + freq_extra * mask


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, positions, inv_freq, table_scale):
    """x [b, t, h, r]: rotate interleaved pairs by position * inv_freq."""
    ang = positions[:, :, None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    sin, cos = jnp.sin(ang)[:, :, None, :] * table_scale, jnp.cos(ang)[:, :, None, :] * table_scale
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _attention(x, ln, p, attention_mask, positions, *, arch, precision):
    a = dict(arch)
    p, ln = _f32(p), _f32(ln)
    b, t, _ = x.shape
    h, dn, dr, dv, rank = a["n_head"], a["nope"], a["rope"], a["v"], a["kv_rank"]
    r, ra, s = _rounding(precision)
    sc = dict(a["rope_scaling"]) if a["rope_scaling"] else None
    if sc:
        inv_freq = _yarn_inv_freq(dr, a["rope_theta"], sc)
        m_all = _mscale(sc["factor"], sc["mscale_all_dim"])
        scale, table = (dn + dr) ** -0.5 * m_all * m_all, _mscale(sc["factor"], sc["mscale"]) / m_all
    else:
        inv_freq = 1.0 / a["rope_theta"] ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
        scale, table = (dn + dr) ** -0.5, 1.0
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], a["eps"]))
        c_q = s(_rms_norm(lin(y, "q_a_proj"), p["q_a_norm"]["scale"], a["eps"]))
        q = lin(c_q, "q_b_proj").reshape(b, t, h, dn + dr)
        kv_a = lin(y, "kv_a_proj")
        c_kv = s(_rms_norm(kv_a[..., :rank], p["kv_a_norm"]["scale"], a["eps"]))
        kv = lin(c_kv, "kv_b_proj").reshape(b, t, h, dn + dv)
        q_rope = s(_rope(q[..., dn:], positions, inv_freq, table))
        k_rope = s(_rope(kv_a[:, :, None, rank:], positions, inv_freq, table))  # [b, t, 1, r]: one for all heads
        scores = jnp.einsum("bqhd,bkhd->bhqk", ra(q[..., :dn]), ra(kv[..., :dn]))
        scores = scores + jnp.einsum("bqhd,bkd->bhqk", ra(q_rope), ra(k_rope[:, :, 0]))
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        keep = (j <= i)[None, None] & attention_mask[:, None, None, :].astype(bool)
        probs = s(jax.nn.softmax(scores * scale + jnp.where(keep, 0.0, NEG), axis=-1))
        mixed = s(jnp.einsum("bhqk,bkhd->bqhd", ra(probs), ra(kv[..., dn:]))).reshape(b, t, h * dv)
        return s(x + lin(mixed, "c_proj"))


def _gated(y, gate, up, down, r, s):
    return s(r(s(jax.nn.silu(s(r(y) @ r(gate))) * s(r(y) @ r(up)))) @ r(down))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _normed(x, ln, *, eps, precision):
    return _rounding(precision)[2](_rms_norm(x, _f32(ln)["scale"], eps))


@functools.partial(jax.jit, static_argnames=("precision",))
def _gated_mlp(y, p, *, precision):
    p = _f32(p)
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        return _gated(y, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"], r, s)


@functools.partial(jax.jit, static_argnames=("k", "scaling", "precision"))
def _route(y, router, bias, *, k, scaling, precision):
    """(ids [b, t, k], weights [b, t, k]). The product is float32 whatever the
    stream's precision (the configuration states it so); the two int8
    controls feed it int8 like every other weight matmul."""
    r = _rounding(precision)[0] if precision.startswith("int8") else (lambda z: z)
    with jax.default_matmul_precision("highest"):
        sigma = jax.nn.sigmoid(r(y) @ r(router.astype(jnp.float32)))
    _, ids = jax.lax.top_k(sigma + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(sigma, ids, axis=-1)
    return ids, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scaling


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(y, gate, up, down, weight, *, precision):
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        return _gated(y, gate.astype(jnp.float32), up.astype(jnp.float32), down.astype(jnp.float32), r, s) * weight[..., None]


def _expert_ffn(y, p, a, precision):
    """sum over (chosen and held) of w_e Expert_e(y) + Shared(y); one expert at
    a time over every token, its weight zero where the token did not choose it."""
    ids, weights = _route(y, p["router"], p["e_score_correction_bias"], k=a["experts_per_token"],
                          scaling=float(a["routed_scaling_factor"]), precision=precision)
    first = a["experts_held"][0] if a.get("experts_held") else 0
    total = jnp.zeros_like(y)
    for j in range(p["experts_gate"].shape[0]):  # expert first + j is row j of the held tensors
        weight = jnp.sum(jnp.where(ids == first + j, weights, 0.0), axis=-1)
        total = total + _expert(y, p["experts_gate"][j], p["experts_up"][j], p["experts_down"][j], weight,
                                precision=precision)
    if a.get("n_shared_experts"):
        total = total + _gated_mlp(y, p["shared"], precision=precision)
    return _rounding(precision)[2](total)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(table, input_ids, *, precision):
    return _rounding(precision)[2](table[input_ids].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, ln_f, head, *, eps, precision):
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        x = s(_rms_norm(x, _f32(ln_f)["scale"], eps))
        return s(r(x) @ r(head["kernel"].astype(jnp.float32)))


def arch_key(a):
    sc = a.get("rope_scaling")
    return tuple(sorted({
        "n_head": a["n_head"], "nope": a["qk_nope_head_dim"], "rope": a["qk_rope_head_dim"], "v": a["v_head_dim"],
        "kv_rank": a["kv_lora_rank"], "eps": float(a.get("ln_eps", 1e-5)), "rope_theta": float(a.get("rope_theta", 10000.0)),
        "rope_scaling": tuple(sorted(sc.items())) if sc else None,
    }.items()))


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest"):
    """float32 logits [b, last, vocab] of the final `last` positions.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype),
    `model_arch` the configuration's (the program's LMConfig keys).
    `precision` names a row of PRECISIONS: "highest" is the reference; the
    others rerun it coarser."""
    a = model_arch
    if (a.get("norm"), a.get("mlp"), a.get("attention"), a.get("activation")) != ("rmsnorm", "gated", "mla", "silu") \
            or a.get("tie_word_embeddings", True) or a.get("parallel_residual", False):
        raise ValueError("mla_moe_decoder is the reference of the rmsnorm / gated silu / mla / untied-head decoder only")
    key, eps = arch_key(a), float(a.get("ln_eps", 1e-5))
    s = _rounding(precision)[2]
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=-1) - 1, 0)
    x = _embed(trunk["wte"]["embedding"], input_ids, precision=precision)
    kinds = a.get("ffn_layers") or ["dense"] * a["n_layer"]
    for i, kind in enumerate(kinds):
        p = trunk[f"h_{i}"]
        x = _attention(x, p["ln_1"], p["attn"], attention_mask, positions, arch=key, precision=precision)
        y = _normed(x, p["ln_2"], eps=eps, precision=precision)
        x = s(x + (_expert_ffn(y, p["moe"], a, precision) if kind == "experts" else _gated_mlp(y, p["mlp"], precision=precision)))
    return _head(x[:, -last:], trunk["ln_f"], trunk["lm_head"], eps=eps, precision=precision)

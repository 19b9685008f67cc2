"""Plain reference of the grouped-key, windowed, sparse-expert decoder
(K-EXAONE, `model_type: exaone_moe`): the forward pass in straightforward
float32 `jax.numpy`, matmuls at `jax.default_matmul_precision("highest")`, no
kernels, no cache, the full [T, T] masks built from the two inequalities
below, K and V REPEATED to the query heads in the open (the plain form of
grouped keys), the experts a plain loop over the held ids. Written from the
equations (ISSUE 30, the model card, `Exaone4Attention` of the dense sibling
in `transformers`), not from `trlx_tpu/models/`; it reads the program's
parameter tree only for the weights.

Token embedding, no position table; pre-norm blocks, sequential residual;
final RMSNorm; untied head, no bias. RMSNorm everywhere (scale only), no bias
in any projection. Block l:

    a = x + Attn_l(RMSNorm(x));   y = a + FFN_l(RMSNorm(a))
    Attn  q = x W_q -> [T, H, hd];  k = x W_k, v = x W_v -> [T, H_kv, hd]
          q = RMSNorm_hd(q) g_q;  k = RMSNorm_hd(k) g_k      (one g of hd for all heads, each)
          a "local" (sliding) layer: q, k = RoPE(q, k), theta, all hd dims, rotate-half pairs (i, i + hd/2)
          a "global" (full) layer:   no rotary at all (NoPE)
          scores_h = q_h . k_(h // g) / sqrt(hd), g = H / H_kv; key j admitted for query i iff j <= i,
          and on a local layer also i - j < window (window keys, the query's own included);
          float32 softmax; . v_(h // g); heads joined [T, H hd] W_o -> d
    FFN   a "dense" layer: W_down(silu(W_gate x) * W_up x)
          an "experts" layer: s = sigmoid(x W_r) over all n_experts (float32); the experts_per_token
          largest of s + b (n_group 1: no group limit); w_e = s_e / (sum over chosen of s + 1e-20) *
          routed_scaling_factor; y = sum over chosen of w_e E_e(x) + Shared(x)

Departures from the published model, each on purpose:
  * NO multi-token-prediction block (`num_nextn_predict_layers` 1): the
    published config gives its attention kind and nothing of its projection
    or feed-forward, and PPO's log-probs, values and samples are the main
    head's, so it changes no number this path produces;
  * only the routed experts `experts_held = [first, first + count)` exist:
    the sum runs over chosen AND held (one chip's share of an expert-parallel
    deployment); routing is over all n_experts all the same;
  * the vocabulary is the slice the configuration keeps;
  * `b` (the router's correction bias) and every weight are drawn from the seed;
  * positions of a left-padded row count from its first real token (the
    program's convention for rollouts).
One sub-layer's weights are cast up to float32 at a time, inside a jitted
function, so the reference fits beside a trainer that fills the chip. The
expert feed-forward, the norms, the embedding and the head are the
sparse-expert reference's own (`mla_moe_decoder`): the same equations.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt_decoder import NEG, PRECISIONS, _f32, _rounding  # the same table of coarser reruns
from benchmark.references.mla_moe_decoder import _embed, _expert_ffn, _gated_mlp, _head, _normed, _rms_norm

__all__ = ["PRECISIONS", "forward"]


def _rope_halves(x, positions, theta):
    """x [b, t, h, hd]: rotate the pairs (i, i + hd/2) by position / theta^(2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = positions[:, :, None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "rotary", "theta", "eps", "precision"))
def _attention(x, ln, p, attention_mask, positions, *, heads, kv_heads, window, rotary, theta, eps, precision):
    p, ln = _f32(p), _f32(ln)
    b, t, _ = x.shape
    r, ra, s = _rounding(precision)
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], eps))
        q, k, v = lin(y, "q_proj"), lin(y, "k_proj"), lin(y, "v_proj")
        hd = q.shape[-1] // heads
        q, k, v = q.reshape(b, t, heads, hd), k.reshape(b, t, kv_heads, hd), v.reshape(b, t, kv_heads, hd)
        q, k = s(_rms_norm(q, p["q_norm"]["scale"], eps)), s(_rms_norm(k, p["k_norm"]["scale"], eps))
        if rotary:
            q, k = s(_rope_halves(q, positions, theta)), s(_rope_halves(k, positions, theta))
        group = heads // kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head h reads K/V head h // group
        scores = jnp.einsum("bqhd,bkhd->bhqk", ra(q), ra(k)) / np.sqrt(hd)
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        keep = j <= i
        if window:
            keep = keep & (i - j < window)
        keep = keep[None, None] & attention_mask[:, None, None, :].astype(bool)
        probs = s(jax.nn.softmax(scores + jnp.where(keep, 0.0, NEG), axis=-1))
        mixed = s(jnp.einsum("bhqk,bkhd->bqhd", ra(probs), ra(v))).reshape(b, t, heads * hd)
        return s(x + lin(mixed, "c_proj"))


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest"):
    """float32 logits [b, last, vocab] of the final `last` positions.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype),
    `model_arch` the configuration's (the program's LMConfig keys).
    `precision` names a row of PRECISIONS: "highest" is the reference; the
    others rerun it coarser."""
    a = model_arch
    if (a.get("norm"), a.get("mlp"), a.get("attention", "mha"), a.get("activation")) != ("rmsnorm", "gated", "mha", "silu") \
            or a.get("tie_word_embeddings", True) or a.get("parallel_residual", False) or not a.get("qk_norm") \
            or a.get("pos_type") != "rotary" or a.get("rotary_layers") != "local" \
            or not a.get("extra", {}).get("neox_rotary") or a.get("rotary_dim") or a.get("fused_qkv", True) \
            or a.get("qkv_bias", True) or a.get("out_bias", True):
        raise ValueError("gqa_window_moe_decoder is the reference of the rmsnorm / gated silu / grouped-key qk-norm "
                         "decoder with rotate-half rotary on its window layers only, no biases, an untied head")
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=-1) - 1, 0)
    x = _embed(trunk["wte"]["embedding"], input_ids, precision=precision)
    ffn_kinds = a.get("ffn_layers") or ["dense"] * a["n_layer"]
    attention_kinds = a.get("attention_layers") or ["global"] * a["n_layer"]
    for i, (ffn, kind) in enumerate(zip(ffn_kinds, attention_kinds)):
        p, local = trunk[f"h_{i}"], kind == "local"
        x = _attention(x, p["ln_1"], p["attn"], attention_mask, positions, heads=a["n_head"],
                       kv_heads=a.get("n_kv_head") or a["n_head"], window=int(a["window_size"]) if local else 0,
                       rotary=local, theta=float(a.get("rope_theta", 10000.0)), eps=eps, precision=precision)
        y = _normed(x, p["ln_2"], eps=eps, precision=precision)
        x = s(x + (_expert_ffn(y, p["moe"], a, precision) if ffn == "experts" else _gated_mlp(y, p["mlp"], precision=precision)))
    return _head(x[:, -last:], trunk["ln_f"], trunk["lm_head"], eps=eps, precision=precision)

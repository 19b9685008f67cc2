"""Plain reference of the gated delta-rule / latent-attention / sparse-expert
decoder (Kimi-Linear, `model_type: kimi_linear`): the forward pass in
straightforward float32 `jax.numpy`, matmuls at
`jax.default_matmul_precision("highest")`, no kernels, no cache, no chunks, no
batching: ONE UNPADDED ROW AT A TIME, the delta-rule layer in its RECURRENT
form, token by token (`lax.scan` over t), latent attention UNABSORBED (per-head
keys and values built from the latent) as a plain masked softmax, the experts
as a plain loop over the held ids. Written from the equations (ISSUE 39) and
the published description (`modeling_kimi.py` of
moonshotai/Kimi-Linear-48B-A3B-Instruct, fla's `KimiDeltaAttention` that it
calls), not from `trlx_tpu/models/`; it reads the program's parameter tree only
for the weights.

Per layer, pre-norm, sequential residual, RMSNorm (eps from the config), untied
head after a final RMSNorm; H heads, keys and values D wide:

    KDA   q = silu(conv(W_q x)), k = silu(conv(W_k x)), v = silu(conv(W_v x))   depthwise, causal, no bias,
              zeros before the row; q = q / |q| * D^-1/2, k = k / |k| per head
          g_t = -exp(A_log) softplus(W_f^ W_fv x_t + dt_bias)     one a key channel
          beta_t = sigmoid(W_b x_t)                               one a head
          S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t;  S_{-1} = 0
          out = W_o(w * o / sqrt(mean(o^2) + eps) * sigmoid(W_g^ W_gv x_t + b_g)),  the mean over a head's D
    MLA   q = x W_q -> heads x (nope | rope);  [c | k_pe] = x W_kva;  c = RMSNorm(c)
          [k_nope | v] = c W_kvb -> heads x (nope | v);  k = [k_nope | k_pe, one for all heads]
          NO rotation of either rope-wide part (mla_use_nope); causal softmax at (nope + rope)^-1/2; W_o
    FFN   a "dense" layer: W_down(silu(W_gate x) * W_up x)
          an "experts" layer: sigma = sigmoid(x W_r) over all n_experts (float32); the
          experts_per_token largest of sigma + b; w_e = sigma_e / sum_chosen sigma * routed_scaling_factor;
          y = sum over chosen of w_e Expert_e(x) + Shared(x); one group, so no group limit.

Departures from the published model, each on purpose:
  * `linear_attn_config`'s `kda_layers` / `full_attn_layers` are read 1-indexed
    (layer 4 of every 4 is latent attention), which the configuration's file
    has already turned into `mixer_layers`;
  * the L2 norm of q and k adds 1e-6 under the root (fla's `l2norm`), and the
    gate's up-projection carries a bias b_g (fla's `g_proj`);
  * only the routed experts `experts_held = [first, first + count)` exist: the
    sum runs over chosen AND held, what the absent experts would add is left
    out (one chip's share of an expert-parallel deployment); routing is over
    all n_experts all the same;
  * the vocabulary is the slice the configuration keeps;
  * the top-level `head_dim` (72) sizes nothing and is not read;
  * a row is cut to its real tokens before anything is computed, so there is
    no padding; the logits land at the row's positions in the padded batch
    (rows are contiguous: padding on the left, as the rollout pads, or on the
    right);
  * `b` (e_score_correction_bias) and every weight are drawn from the seed.
One sub-layer's weights are cast up to float32 at a time, inside a jitted
function, so the reference fits beside a trainer that fills the chip; the head
is computed at the `last` positions asked for, never at all of a row's.

`precision` names a row of PRECISIONS, the table of the other references plus
`bfloat16_state`: `bfloat16_stream` with the delta-rule STATE rounded to bf16
after every token's update as well, the control of the float32 state (never a
yardstick). In the coarser reruns `r` rounds what the weight matmuls read,
`ra` what the recurrence's products and attention's two read, `s` what an
operation hands to the next; the state itself, the decays and every sum stay
float32 except under `bfloat16_state`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import gpt_decoder
from benchmark.references.gpt_decoder import NEG, _f32, _identity, _keep_bf16
from benchmark.references.mla_moe_decoder import _embed, _expert_ffn, _gated_mlp, _head, _normed, _rms_norm

PRECISIONS = {**gpt_decoder.PRECISIONS, "bfloat16_state": gpt_decoder.PRECISIONS["bfloat16_stream"]}
NORM_EPS = 1e-6  # under the root of q's and k's L2 norm

__all__ = ["PRECISIONS", "forward", "layer_state"]


def _rounding(precision):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    return PRECISIONS[precision]


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps", "precision"))
def _delta_rule(x, ln, p, *, heads, head_dim, eps, precision):
    """x [t, d]: one row, every position real; the recurrence token by token.
    Returns (the layer's output [t, d], the state after the last token [heads, head_dim, head_dim])."""
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    r, ra, s = _rounding(precision)
    keep_state = _keep_bf16 if precision == "bfloat16_state" else _identity
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))

    def conv(z, taps):
        padded = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, z.shape[1]), jnp.float32), z], axis=0)
        return s(jax.nn.silu(sum(padded[j:j + t] * taps[j] for j in range(taps.shape[0])))).reshape(t, heads, head_dim)

    unit = lambda z: z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True) + NORM_EPS)
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], eps))
        q = s(unit(conv(lin(y, "q_proj"), p["q_conv"])) * head_dim ** -0.5)
        k = s(unit(conv(lin(y, "k_proj"), p["k_conv"])))
        v = conv(lin(y, "v_proj"), p["v_conv"])
        step = jax.nn.softplus(lin(lin(y, "f_a_proj"), "f_b_proj") + p["dt_bias"]).reshape(t, heads, head_dim)
        g = -jnp.exp(p["A_log"])[:, None] * step  # [t, heads, head_dim], <= 0
        beta = jax.nn.sigmoid(lin(y, "b_proj"))  # [t, heads]
        gate = jax.nn.sigmoid(lin(lin(y, "g_a_proj"), "g_b_proj") + p["g_b_proj"]["bias"]).reshape(t, heads, head_dim)

        def token(S, inputs):
            q_t, k_t, v_t, g_t, b_t = inputs
            S = jnp.exp(g_t)[:, :, None] * S
            correction = v_t - jnp.einsum("hkv,hk->hv", S, ra(k_t))
            S = keep_state(S + ra(b_t[:, None] * k_t)[:, :, None] * ra(correction)[:, None, :])
            return S, jnp.einsum("hkv,hk->hv", S, ra(q_t))

        final, o = jax.lax.scan(token, jnp.zeros((heads, head_dim, head_dim), jnp.float32), (q, k, v, g, beta))
        o = s(o)
        normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["o_norm"]
        return s(x + lin(s(normed * gate).reshape(t, heads * head_dim), "o_proj")), final


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope", "v_dim", "rank", "eps", "precision"))
def _latent_attention(x, ln, p, *, heads, nope, rope, v_dim, rank, eps, precision):
    """x [t, d]: one row, every position real; unabsorbed, no rotation."""
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    r, ra, s = _rounding(precision)
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], eps))
        q = lin(y, "q_proj").reshape(t, heads, nope + rope)
        kv_a = lin(y, "kv_a_proj")
        c = s(_rms_norm(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps))
        kv = lin(c, "kv_b_proj").reshape(t, heads, nope + v_dim)
        shared = jnp.broadcast_to(kv_a[:, None, rank:], (t, heads, rope))  # k_pe, one for all heads, as projected
        k = jnp.concatenate([kv[..., :nope], shared], axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", ra(q), ra(k)) * (nope + rope) ** -0.5
        keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        probs = s(jax.nn.softmax(scores + jnp.where(keep, 0.0, NEG)[None], axis=-1))
        mixed = s(jnp.einsum("hqk,khd->qhd", ra(probs), ra(kv[..., nope:]))).reshape(t, heads * v_dim)
        return s(x + lin(mixed, "c_proj"))


def _row(trunk, a, ids, last, precision, state_of=None):
    """Logits [min(last, t), vocab] of the final positions of one unpadded row `ids` [t];
    with `state_of` a delta-rule layer's index: that layer's state after the row's last token, and no logits."""
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    shared = "bfloat16_stream" if precision == "bfloat16_state" else precision  # the other references' helpers know their own rows
    x = _embed(trunk["wte"]["embedding"], ids, precision=shared)
    ffn = a.get("ffn_layers") or ["dense"] * a["n_layer"]
    for i, kind in enumerate(a["mixer_layers"]):
        p = trunk[f"h_{i}"]
        if kind == "kda":
            x, final = _delta_rule(x, p["ln_1"], p["kda"], heads=a["kda_heads"], head_dim=a["kda_head_dim"], eps=eps,
                                   precision=precision)
            if i == state_of:
                return final
        else:
            x = _latent_attention(x, p["ln_1"], p["attn"], heads=a["n_head"], nope=a["qk_nope_head_dim"],
                                  rope=a["qk_rope_head_dim"], v_dim=a["v_head_dim"], rank=a["kv_lora_rank"], eps=eps,
                                  precision=precision)
        y = _normed(x, p["ln_2"], eps=eps, precision=shared)
        x = s(x + (_expert_ffn(y, p["moe"], a, shared) if ffn[i] == "experts" else _gated_mlp(y, p["mlp"], precision=shared)))
    return _head(x[-last:], trunk["ln_f"], trunk["lm_head"], eps=eps, precision=shared)


def layer_state(trunk, model_arch, ids, layer, precision="highest"):
    """float32 state [kda_heads, kda_head_dim, kda_head_dim] of delta-rule layer `layer` after the last token of
    ONE unpadded row `ids` [t]: what a decode step's cache leaf of that layer must hold then (the state check,
    benchmark/kda_state_parity.py)."""
    if model_arch["mixer_layers"][layer] != "kda":
        raise ValueError(f"layer {layer} is no kda layer")
    return _row(trunk.get("transformer", trunk), model_arch, ids, 0, precision, state_of=layer)


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest"):
    """float32 logits [b, last, vocab] of the final `last` positions of the
    padded batch; zeros where a row has no real token there.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype; the
    whole ``params`` passes too), `model_arch` the configuration's (the
    program's LMConfig keys), `attention_mask` CONCRETE (each row is cut to
    its real tokens on the host). `precision` names a row of PRECISIONS."""
    a = model_arch
    trunk = trunk.get("transformer", trunk)
    if (a.get("norm"), a.get("mlp"), a.get("attention"), a.get("activation"), a.get("pos_type")) != (
            "rmsnorm", "gated", "mla", "silu", "none") or a.get("tie_word_embeddings", True) \
            or a.get("parallel_residual", False) or a.get("q_lora_rank") or a.get("rope_scaling") \
            or set(a.get("mixer_layers", ())) - {"kda", "attention"} or "kda" not in a.get("mixer_layers", ()):
        raise ValueError("kda_mla_moe_decoder is the reference of the rmsnorm / gated silu decoder with kda and "
                         "unrotated direct-query latent-attention layers, an untied head")
    mask = np.asarray(attention_mask).astype(bool)
    total = mask.shape[1]
    rows = []
    for ids, real in zip(input_ids, mask):
        where = np.flatnonzero(real)
        first, stop = (int(where[0]), int(where[-1]) + 1) if where.size else (0, 0)
        if not real[first:stop].all():
            raise ValueError("a row's real tokens must be contiguous")
        want = max(0, stop - max(first, total - last))  # real positions inside the final `last`
        out = jnp.zeros((last, a["vocab_size"]), jnp.float32)
        if want:
            logits = _row(trunk, a, ids[first:stop], want, precision)
            out = jax.lax.dynamic_update_slice(out, logits, (last - (total - stop) - want, 0))
        rows.append(out)
    return jnp.stack(rows)

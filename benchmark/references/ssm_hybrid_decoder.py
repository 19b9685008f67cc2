"""Plain reference of the hybrid state-space / attention decoder
(granite-4.0-h, `model_type: granitemoehybrid` with `num_local_experts: 0`):
the forward pass in straightforward float32 `jax.numpy`, matmuls at
`jax.default_matmul_precision("highest")`, no kernels, no cache, no chunks, no
batching: ONE UNPADDED ROW AT A TIME, the state-space layer in its RECURRENT
form, token by token (`lax.scan` over t), attention as a plain masked softmax
with K and V repeated to the query heads in the open. Written from the
equations (ISSUE 32) and `GraniteMoeHybridMambaLayer.torch_forward` of
`transformers` 4.57.6 (`models/granitemoehybrid/modeling_granitemoehybrid.py`),
not from `trlx_tpu/models/`; it reads the program's parameter tree only for
the weights.

    x0 = embedding_multiplier * wte[ids]
    a  = x + residual_multiplier * Mixer_i(RMSNorm(x))      Mixer_i by mixer_layers[i]
    y  = a + residual_multiplier * MLP(RMSNorm(a))          MLP(h) = W_down(silu(W_gate h) * W_up h)
    logits = RMSNorm(x_L) wte^T / logits_scaling            tied

    attention: q H heads, k and v H_kv heads of head_width, no bias, NO position signal at all,
        causal, scores * attention_multiplier, float32 softmax, W_o
    state space (H_s heads of P, one B/C group of N, convolution K wide):
        [z | xBC | dt] = W_in h
        xBC_t = silu(sum_j w[j] * xBC_{t-K+1+j} + b)         depthwise, causal, zeros before the row
        [x | B | C] = xBC;  D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t           per head [P, N], S_{-1} = 0
        y_t = S_t C_t + D x_t
        out = W_out(w * g / sqrt(mean(g^2) + eps)),  g = y silu(z), the mean over all H_s P

Departures from `torch_forward`, each on purpose:
  * the recurrence runs token by token over the whole row where the published
    slow path runs its chunked form (`segment_sum`, steps 1-4) for a block and
    the token update only with a cache: the two are the same function, and the
    token form is the plain one;
  * the published clamp of D_t to (0, inf) is no clamp and is left out;
  * a row is cut to its real tokens before anything is computed, so there is
    no padding and `apply_mask_to_padding_states` has nothing to do; the
    logits land at the row's positions in the padded batch (rows are
    contiguous: padding on the left, as the rollout pads, or on the right);
  * every weight is drawn from the seed; the vocabulary, depth and widths are
    the configuration's.
One sub-layer's weights are cast up to float32 at a time, inside a jitted
function, so the reference fits beside a trainer that fills the chip; the head
is computed at the `last` positions asked for, never at all of a row's.

`precision` names a row of PRECISIONS, the table of the other references plus
`bfloat16_state`: `bfloat16_stream` with the state rounded to bf16 after
every token's update as well, the control of the float32 state (it is never a
yardstick). In the coarser reruns `r` rounds what the weight matmuls read,
`ra` what the recurrence's two products and attention's two read (D_t x_t, B_t,
C_t; q, k, probabilities, v), `s` what an operation hands to the next; the
state itself, the decays and every sum stay float32 except under
`bfloat16_state`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import gpt_decoder
from benchmark.references.gpt_decoder import NEG, _f32, _identity, _keep_bf16
from benchmark.references.mla_moe_decoder import _embed, _gated_mlp, _normed, _rms_norm

PRECISIONS = {**gpt_decoder.PRECISIONS, "bfloat16_state": gpt_decoder.PRECISIONS["bfloat16_stream"]}

__all__ = ["PRECISIONS", "forward", "layer_state"]


def _rounding(precision):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    return PRECISIONS[precision]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale", "eps", "multiplier", "precision"))
def _attention(x, ln, p, *, heads, kv_heads, scale, eps, multiplier, precision):
    """x [t, d]: one row, every position real."""
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    r, ra, s = _rounding(precision)
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], eps))
        q, k, v = lin(y, "q_proj"), lin(y, "k_proj"), lin(y, "v_proj")
        hd = q.shape[-1] // heads
        q, k, v = q.reshape(t, heads, hd), k.reshape(t, kv_heads, hd), v.reshape(t, kv_heads, hd)
        group = heads // kv_heads
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)  # query head h reads K/V head h // group
        scores = jnp.einsum("qhd,khd->hqk", ra(q), ra(k)) * scale
        keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        probs = s(jax.nn.softmax(scores + jnp.where(keep, 0.0, NEG)[None], axis=-1))
        mixed = s(jnp.einsum("hqk,khd->qhd", ra(probs), ra(v))).reshape(t, heads * hd)
        return s(x + multiplier * lin(mixed, "c_proj"))


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "state", "eps", "multiplier", "precision"))
def _state_space(x, ln, p, *, heads, head_dim, state, eps, multiplier, precision):
    """x [t, d]: one row, every position real; the recurrence token by token.
    Returns (the layer's output [t, d], the state after the last token [heads, head_dim, state])."""
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    r, ra, s = _rounding(precision)
    keep_state = _keep_bf16 if precision == "bfloat16_state" else _identity
    inner, width = heads * head_dim, heads * head_dim + 2 * state
    with jax.default_matmul_precision("highest"):
        h = s(_rms_norm(x, ln["scale"], eps))
        zxbcdt = s(r(h) @ r(p["in_proj"]["kernel"]))
        z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:inner + width], zxbcdt[:, inner + width:]
        taps = p["conv_kernel"].shape[0]
        padded = jnp.concatenate([jnp.zeros((taps - 1, width), jnp.float32), xbc], axis=0)
        conv = sum(padded[j:j + t] * p["conv_kernel"][j] for j in range(taps)) + p["conv_bias"]
        xbc = s(jax.nn.silu(conv))
        xs, B, C = xbc[:, :inner].reshape(t, heads, head_dim), xbc[:, inner:inner + state], xbc[:, inner + state:]
        delta = jax.nn.softplus(dt + p["dt_bias"])  # [t, heads]
        A = -jnp.exp(p["A_log"])

        def token(S, inputs):
            x_t, B_t, C_t, d_t = inputs
            S = jnp.exp(d_t * A)[:, None, None] * S + ra(d_t[:, None] * x_t)[:, :, None] * ra(B_t)[None, None, :]
            S = keep_state(S)
            return S, jnp.einsum("hpn,n->hp", S, ra(C_t))

        final, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, state), jnp.float32), (xs, B, C, delta))
        y = s(y + p["D"][:, None] * xs)
        g = y.reshape(t, inner) * jax.nn.silu(z)
        gated = s(p["norm_scale"] * (g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)))
        return s(x + multiplier * s(r(gated) @ r(p["out_proj"]["kernel"]))), final


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def _tied_head(x, ln_f, table, *, eps, scaling, precision):
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        x = s(_rms_norm(x, _f32(ln_f)["scale"], eps))
        return s(r(x) @ r(table.astype(jnp.float32)).T) / scaling


def _row(trunk, a, ids, last, precision, state_of=None):
    """Logits [min(last, t), vocab] of the final positions of one unpadded row `ids` [t];
    with `state_of` a state-space layer's index: that layer's state after the row's last token, and no logits."""
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    multiplier = float(a.get("residual_multiplier", 1.0))
    hd = a.get("head_width") or a["d_model"] // a["n_head"]
    shared = "bfloat16_stream" if precision == "bfloat16_state" else precision  # the other references' helpers know their own rows
    x = s(_embed(trunk["wte"]["embedding"], ids, precision=shared) * float(a.get("embedding_multiplier", 1.0)))
    for i, kind in enumerate(a["mixer_layers"]):
        p = trunk[f"h_{i}"]
        if kind == "mamba":
            x, final = _state_space(x, p["ln_1"], p["mamba"], heads=a["ssm_heads"], head_dim=a["ssm_head_dim"],
                                    state=a["ssm_state"], eps=eps, multiplier=multiplier, precision=precision)
            if i == state_of:
                return final
        else:
            x = _attention(x, p["ln_1"], p["attn"], heads=a["n_head"], kv_heads=a.get("n_kv_head") or a["n_head"],
                           scale=float(a.get("attention_multiplier") or hd ** -0.5), eps=eps, multiplier=multiplier,
                           precision=precision)
        y = _normed(x, p["ln_2"], eps=eps, precision=shared)
        x = s(x + multiplier * _gated_mlp(y, p["mlp"], precision=shared))
    return _tied_head(x[-last:], trunk["ln_f"], trunk["wte"]["embedding"], eps=eps,
                      scaling=float(a.get("logits_scaling", 1.0)), precision=precision)


def layer_state(trunk, model_arch, ids, layer, precision="highest"):
    """float32 state [ssm_heads, ssm_head_dim, ssm_state] of state-space layer `layer` after the last token of
    ONE unpadded row `ids` [t]: what a decode step's cache leaf of that layer must hold then (the state check,
    benchmark/state_parity.py)."""
    if model_arch["mixer_layers"][layer] != "mamba":
        raise ValueError(f"layer {layer} is no state-space layer")
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
    if (a.get("norm"), a.get("mlp"), a.get("attention", "mha"), a.get("activation"), a.get("pos_type")) != (
            "rmsnorm", "gated", "mha", "silu", "none") or not a.get("tie_word_embeddings", True) \
            or a.get("parallel_residual", False) or a.get("fused_qkv", True) or a.get("qkv_bias", True) \
            or a.get("out_bias", True) or a.get("qk_norm") or a.get("attention_layers") or a.get("ffn_layers") \
            or "mamba" not in a.get("mixer_layers", ()):
        raise ValueError("ssm_hybrid_decoder is the reference of the rmsnorm / gated silu decoder with state-space and "
                         "grouped-key attention layers, no position signal, no biases, a tied head")
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

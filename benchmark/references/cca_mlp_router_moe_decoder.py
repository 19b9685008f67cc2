"""Plain reference of the decoder whose attention runs in a compressed latent
behind two causal convolutions (CCA) and whose every layer routes ONE expert a
token by an MLP router that carries its state from layer to layer (ZAYA1,
`model_type: zaya`): the forward pass in straightforward float32 `jax.numpy`,
matmuls at `jax.default_matmul_precision("highest")`, no kernels, no cache,
each row run UNPADDED and alone (what lies before a row's first token is the
one left padding of the equations, nothing of another row or of a pad), K and
V repeated to the query heads in the open, the held experts a plain loop.
Written from the equations of ISSUE 47 (the catalogued config.json, arXiv
2510.04476 for the attention and arXiv 2511.17127 for the router), not from
`trlx_tpu/models/`; it reads the program's parameter tree only for the weights.

Token embedding, no position table; final RMSNorm; the head is the table
(tied), no bias; the logits divided by `logits_scaling` where the configuration
sets one (not the family's: its file says why). RMSNorm everywhere (scale only). One layer, residual r [T, d],
H query heads over G key/value heads of d_h, g = H / G:

  CCA sub-block, x = RMSNorm_1(r):
    q~ = x W_q [H d_h],  k~ = x W_k [G d_h],  u = [q~ | k~]           H + G heads of d_h channels
    the sequence padded ONCE on the left with (K0 - 1) + (K1 - 1) zero positions of u:
    a_t[c] = sum_j w0[j, c] u_(t - K0 + 1 + j)[c] + b0[c]             depthwise, K0 = cca_time0 taps
    c_t[n] = sum_j a_(t - K1 + 1 + j)[n] W1[j, n] + b1[n]             one d_h x d_h matrix a tap a head n, K1 = cca_time1
    q_t[h] = c_t[h] + (q~_t[h] + k~_t[h // g]) / 2                    the q-k mean, from the PRE-convolution tensors
    k_t[j] = c_t[H + j] + (mean over the group's q~_t[h] + k~_t[j]) / 2
    v_t    = [x_t W_v | x_(t-1) W_vs],  x_(-1) = 0                    value heads [0, G/2) see the token, [G/2, G) the one before
    q^ = sqrt(d_h) q / |q|_2,  k^ = sqrt(d_h) exp(theta_j) k / |k|_2  over a head; theta one learned scalar a key head
    RoPE on the first rotary_dim channels of q^ and k^, rotate-half pairs (i, i + rotary_dim / 2), base rope_theta
    scores_h = q^_h . k^_(h // g) / sqrt(d_h), key j admitted for query i iff j <= i; float32 softmax; . v_(h // g);
    heads joined [T, H d_h] W_o -> d
  r' = (r + beta_r) * alpha_r + (f + beta_f) * alpha_f                four learned vectors of d a sum

  expert sub-block, h = RMSNorm_2(r'):
    s = h W_d + b_d                          [T, D_r] float32
    s = s + gamma * s_below                  the layer below's s after ITS carry, before its norm; nothing for layer 0
    z = RMSNorm(s);  m = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3         [T, n_experts], erf GeLU
    p = softmax(m);  e* = argmax(p + b);  w = p[e*]                    b a buffer; w NOT renormalised
    y = w Expert_e*(h) if e* is held, else 0;  Expert(h) = (silu(h W_gate) * h W_up) W_down
  r'' = (r' + beta_r') * alpha_r' + (y + beta_y) * alpha_y

Departures from the published model, each on purpose:
  * only the routed experts `experts_held = [first, first + count)` exist: a
    token whose one expert lies on another chip gets no feed-forward part
    here; routing is over all n_experts all the same;
  * the vocabulary is the slice the configuration keeps;
  * every weight is drawn from the seed;
  * the family's optional zero-compute expert is not built.
Attention goes through in blocks of `QUERY_BLOCK` queries and one sub-layer's
weights are cast up to float32 at a time, inside a jitted function: the
reference runs beside a trainer that fills the chip. `drop` names pieces to
leave out: the tier-1 tests use it to show that the comparison sees each.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt_decoder import NEG, PRECISIONS, _f32, _rounding  # the same table of coarser reruns
from benchmark.references.gqa_window_moe_decoder import _rope_halves
from benchmark.references.mla_moe_decoder import _embed, _rms_norm

__all__ = ["PRECISIONS", "PIECES", "forward"]

QUERY_BLOCK = 256
# what `drop` may name
PIECES = ("conv0", "conv1", "qk_mean", "value_shift", "l2_norm", "temperature", "rotary", "router_carry", "router_mlp",
          "router_bias", "router_weight", "residual_scaling")


def _joined(skip, f, p, name, s, drop):
    """(skip + beta_s) * alpha_s + (f + beta_f) * alpha_f"""
    if "residual_scaling" in drop:
        return s(skip + f)
    vec = lambda part: p[f"{name}_{part}"].astype(jnp.float32)
    return s((skip + vec("skip_bias")) * vec("skip_scale") + (f + vec("branch_bias")) * vec("branch_scale"))


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "rotary_dim", "theta", "eps", "precision", "drop"))
def _cca(r_in, block, positions, *, heads, kv_heads, rotary_dim, theta, eps, precision, drop):
    """One row [1, T, d] through the CCA sub-block and its residual sum."""
    p, ln = _f32(block["attn"]), _f32(block["ln_1"])
    _, t, _ = r_in.shape
    r, ra, s = _rounding(precision)
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    H, G = heads, kv_heads
    g = H // G
    with jax.default_matmul_precision("highest"):
        x = s(_rms_norm(r_in, ln["scale"], eps))
        q_pre, k_pre = lin(x, "q_proj"), lin(x, "k_proj")
        hd = q_pre.shape[-1] // H
        u = jnp.concatenate([q_pre, k_pre], axis=-1)  # [1, T, (H + G) hd]
        w0, b0, w1, b1 = p["conv0_kernel"], p["conv0_bias"], p["conv1_kernel"], p["conv1_bias"]
        K0, K1 = w0.shape[0], w1.shape[0]
        padded = jnp.pad(u, ((0, 0), (K0 - 1 + K1 - 1, 0), (0, 0)))  # the ONE left padding
        reach = t + K1 - 1
        if "conv0" in drop:
            a = padded[:, K0 - 1:]
        else:
            a = s(sum(padded[:, j:j + reach] * w0[j] for j in range(K0)) + b0)
        a = a.reshape(1, reach, H + G, hd)
        if "conv1" in drop:
            c = a[:, K1 - 1:]
        else:
            c = s(sum(jnp.einsum("bthd,hde->bthe", r(a[:, j:j + t]), r(w1[j])) for j in range(K1)) + b1)
        qp, kp = q_pre.reshape(1, t, H, hd), k_pre.reshape(1, t, G, hd)
        q, k = c[:, :, :H], c[:, :, H:]
        if "qk_mean" not in drop:
            q = q + 0.5 * (qp + jnp.repeat(kp, g, axis=2))
            k = k + 0.5 * (jnp.mean(qp.reshape(1, t, G, g, hd), axis=3) + kp)
        v_now, v_before = lin(x, "v_proj"), lin(x, "v_shift_proj")
        if "value_shift" not in drop:
            v_before = jnp.pad(v_before, ((0, 0), (1, 0), (0, 0)))[:, :-1]  # x_(-1) = 0
        v = jnp.concatenate([v_now, v_before], axis=-1).reshape(1, t, G, hd)
        if "l2_norm" not in drop:
            unit = lambda z: np.sqrt(hd) * z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
            q, k = unit(q), unit(k)
        if "temperature" not in drop:
            k = k * jnp.exp(p["k_temperature"])[:, None]
        q, k = s(q), s(k)
        if rotary_dim and "rotary" not in drop:
            turn = lambda z: jnp.concatenate([_rope_halves(z[..., :rotary_dim], positions, theta), z[..., rotary_dim:]], axis=-1)
            q, k = s(turn(q)), s(turn(k))
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)  # query head h reads K/V head h // g
        block_q = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
        j = jnp.arange(t)[None, :]

        def queries(args):
            q_block, i = args
            scores = jnp.einsum("bqhd,bkhd->bhqk", ra(q_block), ra(k)) / np.sqrt(hd)
            probs = s(jax.nn.softmax(scores + jnp.where(j <= i[:, None], 0.0, NEG)[None, None], axis=-1))
            return s(jnp.einsum("bhqk,bkhd->bqhd", ra(probs), ra(v)))

        blocks = (jnp.moveaxis(q.reshape(1, t // block_q, block_q, H, hd), 1, 0), jnp.arange(t).reshape(t // block_q, block_q))
        mixed = jnp.moveaxis(jax.lax.map(queries, blocks), 0, 1).reshape(1, t, H * hd)
        return _joined(r_in, lin(mixed, "c_proj"), _f32({k_: v_ for k_, v_ in block.items() if k_.startswith("res_1")}),
                       "res_1", s, drop)


@functools.partial(jax.jit, static_argnames=("eps", "precision", "drop"))
def _route(h, below, router, bias, *, eps, precision, drop):
    """(e* [1, T], w [1, T], s [1, T, D_r]): float32 whatever the stream's
    precision (the configuration states it so); the two int8 controls feed
    its products int8 like every other weight matmul."""
    p = _f32(router)
    r = _rounding(precision)[0] if precision.startswith("int8") else (lambda z: z)
    gelu = lambda z: 0.5 * z * (1.0 + jax.lax.erf(z / np.sqrt(2.0)))
    lin = lambda z, name: r(z) @ r(p[name]["kernel"]) + (p[name]["bias"] if "bias" in p[name] else 0.0)
    with jax.default_matmul_precision("highest"):
        state = lin(h, "down")
        if below is not None and "router_carry" not in drop:
            state = state + p["carry_scale"] * below
        z = _rms_norm(state, p["norm"]["scale"], eps)
        if "router_mlp" not in drop:
            z = gelu(lin(gelu(lin(z, "hidden_0")), "hidden_1"))
        probs = jax.nn.softmax(lin(z, "out"), axis=-1)
    chosen = jnp.argmax(probs if "router_bias" in drop else probs + bias.astype(jnp.float32), axis=-1)
    weight = jnp.take_along_axis(probs, chosen[..., None], axis=-1)[..., 0]
    return chosen, (jnp.ones_like(weight) if "router_weight" in drop else weight), state


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(h, gate, up, down, weight, *, precision):
    r, _, s = _rounding(precision)
    gate, up, down = gate.astype(jnp.float32), up.astype(jnp.float32), down.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        hidden = s(jax.nn.silu(s(r(h) @ r(gate))) * s(r(h) @ r(up)))
        return s(r(hidden) @ r(down)) * weight[..., None]


def _experts(h, chosen, weight, p, first, precision):
    """w Expert_e*(h) where e* is held: one held expert at a time over every
    token, its weight zero where the token chose another."""
    total = jnp.zeros_like(h)
    for j in range(p["experts_gate"].shape[0]):  # expert first + j is row j of the held tensors
        total = total + _expert(h, p["experts_gate"][j], p["experts_up"][j], p["experts_down"][j],
                                jnp.where(chosen == first + j, weight, 0.0), precision=precision)
    return _rounding(precision)[2](total)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def _tied_head(x, ln_f, table, *, eps, scaling, precision):
    """logits = RMSNorm(x) . table^T / scaling (`logits_scaling`: the head's input is divided, the head is linear)."""
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        x = s(_rms_norm(x, _f32(ln_f)["scale"], eps) / scaling)
        return s(r(x) @ r(table.astype(jnp.float32)).T)


def _row(trunk, a, ids, last, precision, drop):
    """float32 logits [1, last, vocab] of one unpadded row `ids` [1, T]."""
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    positions = jnp.arange(ids.shape[1])[None, :]
    rotary_dim = (a.get("rotary_dim") or a["head_width"]) if a["pos_type"] == "rotary" else 0
    first = a["experts_held"][0] if a.get("experts_held") else 0
    x, below = _embed(trunk["wte"]["embedding"], ids, precision=precision), None
    for i in range(a["n_layer"]):
        p = trunk[f"h_{i}"]
        x = _cca(x, {k: v for k, v in p.items() if k != "moe"}, positions, heads=a["n_head"], kv_heads=a["n_kv_head"],
                 rotary_dim=rotary_dim, theta=float(a.get("rope_theta", 10000.0)), eps=eps, precision=precision, drop=drop)
        h = s(_rms_norm(x, p["ln_2"]["scale"].astype(jnp.float32), eps))
        chosen, weight, below = _route(h, below, p["moe"]["router"], p["moe"]["e_score_correction_bias"], eps=eps,
                                       precision=precision, drop=drop)
        y = _experts(h, chosen, weight, p["moe"], first, precision)
        x = _joined(x, y, _f32({k: v for k, v in p.items() if k.startswith("res_2")}), "res_2", s, drop)
    return _tied_head(x[:, -last:], trunk["ln_f"], trunk["wte"]["embedding"], eps=eps,
                      scaling=float(a.get("logits_scaling", 1.0)), precision=precision)


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest", drop=()):
    """float32 logits [b, last, vocab] of the final `last` positions.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype),
    `model_arch` the configuration's (the program's LMConfig keys).
    `precision` names a row of PRECISIONS: "highest" is the reference; the
    others rerun it coarser. Each row is cut to its real tokens (a left-padded
    row's pads are dropped) and run alone. `drop` (of PIECES): pieces left out."""
    a = model_arch
    if (a.get("norm"), a.get("mlp"), a.get("attention"), a.get("activation")) != ("rmsnorm", "gated", "cca", "silu") \
            or (a.get("router_scoring"), a.get("router_kind"), a.get("router_input", "ffn")) != ("softmax_all", "mlp", "ffn") \
            or not a.get("router_carry") or not a.get("residual_scaling") or a.get("experts_per_token") != 1 \
            or set(a.get("ffn_layers") or ["dense"]) != {"experts"} or a.get("n_shared_experts") \
            or not a.get("tie_word_embeddings", True) or a.get("parallel_residual", False) or a.get("attention_layers") \
            or a.get("pos_type") not in ("rotary", "none") or not a.get("extra", {}).get("neox_rotary") \
            or a.get("fused_qkv", True) or a.get("qkv_bias", True) or a.get("out_bias", True) or set(drop) - set(PIECES):
        raise ValueError("cca_mlp_router_moe_decoder is the reference of the rmsnorm decoder with attention 'cca' (rotate-half "
                         "rotary or none), every layer SiLU-gated experts chosen one a token by an MLP router with a carried "
                         "state under a softmax over all experts, learned residual scaling, no shared expert, no biases in "
                         "the projections, a tied head")
    mask = np.asarray(attention_mask)
    rows = []
    for row in range(mask.shape[0]):
        real = int(mask[row].sum())
        if real < last or not mask[row, -real:].all():
            raise ValueError("a row is its pads, then its tokens, at least `last` of them")
        rows.append(_row(trunk, a, jnp.asarray(input_ids)[row:row + 1, mask.shape[1] - real:], last, precision, tuple(sorted(drop))))
    return jnp.concatenate(rows, axis=0)

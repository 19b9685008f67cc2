"""Plain reference of the lightning / block-selected sparse attention decoder
(MiniCPM-SALA, `model_type: minicpm_sala`): the forward pass in
straightforward float32 `jax.numpy`, matmuls at
`jax.default_matmul_precision("highest")`, no kernels, no cache, no chunked
form, no batching: ONE UNPADDED ROW AT A TIME, a lightning layer as its
RECURRENCE, token by token (`lax.scan` over t), a sparse layer over the whole
row, the queries in blocks only so that 12,288 tokens fit. Written from the
equations of ISSUE 51 (InfLLM-V2, arXiv:2509.24663, and the MiniCPM4 report,
arXiv:2506.07900, for the sparse layers; Lightning Attention-2,
arXiv:2401.04658, for the linear ones; the catalogued keys of the family's
config.json), not from `trlx_tpu/models/`; it reads the program's parameter
tree only for the weights.

Trunk: r_0 = embedding_multiplier * E[token]; each layer r <- r + c Mixer(RMSNorm_1(r)),
r <- r + c W_down(silu(W_gate x) * W_up x) with x = RMSNorm_2(r), c = residual_multiplier;
logits = W_head RMSNorm_f(r) / logits_scaling; no bias anywhere; rms(z) = sqrt(mean(z^2) + eps).

    lightning   q, k, v = W_q x, W_k x, W_v x, H heads of D
                q = w_q q / rms(q), k = w_k k / rms(k)              per head over its D channels
                q, k = RoPE(q), RoPE(k)                             all D channels, rotate-half pairs (i, i + D/2), base rope_theta
                S_t = lambda_h S_{t-1} + k_t v_t^T                  [D, D] a head, S = 0 before the row; lambda_h = exp(-2^(-8h/H)), h = 1..H
                o_t = S_t^T q_t / sqrt(D)
                o = w_o o / rms(o) over all H D channels;  o = o * sigmoid(W_g x);  out = W_o o
    sparse      q = W_q x (H heads), k, v = W_k x, W_v x (G heads), D wide; qk-norm as above; NO rotary
                kc_j = mean(k_{stride j} .. k_{stride j + kernel - 1});  it exists for query t when stride j + kernel - 1 <= t
                p_{t,h,.} = softmax_j(q_{t,h} . kc_j / sqrt(D)) over the j that exist;  a_{t,g,j} = sum of p over the heads of group g
                A_{t,g,b} = max_{j = 4b-1 .. 4b+3} a_{t,g,j}         (block / stride = 4 and kernel / stride = 2 as published:
                                                                    the compressed keys whose tokens touch block b)
                I_{t,g} = {blocks < init_blocks} + {blocks that hold tokens t - window + 1 .. t}
                          + the topk blocks of largest A among the other blocks that start at or before t
                          (all of them where fewer; of equal scores the earlier block)
                o_{t,h} = sum_s softmax_s(q_{t,h} . k_s / sqrt(D)) v_s  over s <= t with block(s) in I_{t,g(h)}
                o = o * sigmoid(W_g x);  out = W_o o

Departures from the published model, each on purpose:
  * `dense_len` is not used: the choice runs at every length (the configuration's `assumed.dense_len`);
  * the vocabulary is the slice the configuration keeps;
  * a row is cut to its real tokens before anything is computed, so there is
    no padding; the logits land at the row's positions in the padded batch
    (rows are contiguous: padding on the left, as the rollout pads, or on the right);
  * every weight is drawn from the seed.
One sub-layer's weights are cast up to float32 at a time, inside a jitted
function, so the reference fits beside a trainer that fills the chip; the head
is computed at the `last` positions asked for, never at all of a row's.

`precision` names a row of PRECISIONS, the table of the other references plus
`bfloat16_state`: `bfloat16_stream` with the lightning STATE rounded to bf16
after every token's update as well, the control of the float32 state (never a
yardstick). In the coarser reruns `r` rounds what the weight matmuls read,
`ra` what the recurrence's products and attention's (the compressed scores'
too) read, `s` what an operation hands to the next; the state itself, the
decays and every sum stay float32 except under `bfloat16_state`.

`drop` names pieces to leave out, for the tests that show each piece is held
by the comparison: "lightning_qk_norm", "lightning_rotary", "lightning_decay"
(lambda = 1), "lightning_out_norm", "lightning_gate"; "sparse_compress" (kc_j =
k_{stride j}, no mean), "sparse_softmax" (a = the raw scores' sum), "sparse_pool"
(A_b = a_{4b}), "sparse_topk" (no block beyond the init blocks and the window),
"sparse_choice" (every block: dense attention), "sparse_gate".
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import gpt_decoder
from benchmark.references.gpt_decoder import NEG, _f32, _identity, _keep_bf16
from benchmark.references.mla_moe_decoder import _embed, _gated_mlp, _normed, _rms_norm

PRECISIONS = {**gpt_decoder.PRECISIONS, "bfloat16_state": gpt_decoder.PRECISIONS["bfloat16_stream"]}
QUERY_BLOCK = 128  # queries a step of the sparse layer holds at once (memory only: every row is whole)

__all__ = ["PRECISIONS", "forward", "layer_state", "chosen_sets", "sparse_layer"]


def _rounding(precision):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    return PRECISIONS[precision]


def _head_norm(z, weight, eps):
    return weight * z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps)


def _rope(z, theta):
    """z [t, heads, D] at positions 0 .. t - 1: all D channels, pairs (i, i + D / 2)."""
    t, _, d = z.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = z[..., : d // 2], z[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps", "theta", "multiplier", "qk_norm", "gated",
                                             "precision", "drop"))
def _lightning(x, ln, p, *, heads, head_dim, eps, theta, multiplier, qk_norm, gated, precision, drop):
    """x [t, d]: one row, every position real; the recurrence token by token.
    Returns (the layer's output [t, d], the state after the last token [heads, head_dim, head_dim])."""
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    r, ra, s = _rounding(precision)
    keep_state = _keep_bf16 if precision == "bfloat16_state" else _identity
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], eps))
        q, k, v = (lin(y, name).reshape(t, heads, head_dim) for name in ("q_proj", "k_proj", "v_proj"))
        if qk_norm and "lightning_qk_norm" not in drop:
            q, k = s(_head_norm(q, p["q_norm"]["scale"], eps)), s(_head_norm(k, p["k_norm"]["scale"], eps))
        if "lightning_rotary" not in drop:
            q, k = s(_rope(q, theta)), s(_rope(k, theta))
        decay = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads))
        if "lightning_decay" in drop:
            decay = jnp.ones_like(decay)

        def token(S, inputs):
            q_t, k_t, v_t = inputs
            S = keep_state(decay[:, None, None] * S + ra(k_t)[:, :, None] * ra(v_t)[:, None, :])
            return S, jnp.einsum("hkv,hk->hv", S, ra(q_t)) * head_dim ** -0.5

        final, o = jax.lax.scan(token, jnp.zeros((heads, head_dim, head_dim), jnp.float32), (q, k, v))
        o = s(o).reshape(t, heads * head_dim)
        if "lightning_out_norm" not in drop:
            o = _head_norm(o, p["o_norm"], eps)
        if gated and "lightning_gate" not in drop:
            o = o * jax.nn.sigmoid(lin(y, "g_proj"))
        return s(x + multiplier * lin(s(o), "o_proj")), final


def _choose(q, kc, at, *, sizes, drop, ra):
    """Steps 3-5 for the queries `q` [n, G, g, D] at positions `at` [n] over the row's compressed keys `kc`
    [J, G, D] -> bool [n, G, blocks]: the chosen set of each query's group."""
    kernel, stride, block, topk, window, init, blocks = sizes
    n, groups = q.shape[0], q.shape[1]
    ratio, pieces = block // stride, kernel // stride
    b = jnp.arange(blocks)
    started = (b * block)[None, :] <= at[:, None]  # [n, blocks]
    forced = started & ((b < init)[None, :] | (((b + 1) * block - 1)[None, :] >= (at - window + 1)[:, None]))
    if "sparse_choice" in drop:
        return jnp.broadcast_to(started[:, None, :], (n, groups, blocks))
    others = started & ~forced
    J = kc.shape[0]
    if "sparse_topk" in drop or J == 0:
        return jnp.broadcast_to(forced[:, None, :], (n, groups, blocks))
    scores = jnp.einsum("nghd,jgd->nghj", ra(q), ra(kc)) * q.shape[-1] ** -0.5
    exists = ((stride * jnp.arange(J) + kernel - 1)[None, :] <= at[:, None])[:, None, None, :]  # [n, 1, 1, J]
    if "sparse_softmax" in drop:
        p = jnp.where(exists, scores, 0.0)
    else:
        p = jnp.where(exists, jax.nn.softmax(jnp.where(exists, scores, NEG), axis=-1), 0.0)
    a = jnp.sum(p, axis=2)  # [n, G, J]
    # the compressed keys whose tokens touch block b: j = ratio b - (pieces - 1) .. ratio b + ratio - 1
    touching = ratio * b[:, None] - (pieces - 1) + jnp.arange(ratio + pieces - 1)[None, :]  # [blocks, width]
    if "sparse_pool" in drop:
        touching = ratio * b[:, None]
    inside = (touching >= 0) & (touching < J)
    lowest = -jnp.inf if "sparse_softmax" in drop else 0.0
    pooled = jnp.max(jnp.where(inside, a[..., jnp.clip(touching, 0, J - 1)], lowest), axis=-1)  # [n, G, blocks]
    ranked = jnp.where(others[:, None, :], pooled, -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)  # of equal scores the earlier block first
    rank = jnp.argsort(order, axis=-1, stable=True)
    return forced[:, None, :] | (others[:, None, :] & (rank < topk))


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "multiplier", "qk_norm", "gated", "sizes",
                                             "precision", "drop", "choices", "mixer_only"))
def _sparse(x, ln, p, *, heads, kv_heads, eps, multiplier, qk_norm, gated, sizes, precision, drop, choices=False,
            mixer_only=False):
    """x [t, d]: one row, every position real. `sizes` = (kernel, stride, block, topk, window, init_blocks).
    With `choices`: the chosen sets bool [t, G, blocks] in place of the layer's output. With `mixer_only`: `x` is
    the mixer's own (normed) input and the mixer's output is returned alone, no norm ahead and no residual sum."""
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    r, ra, s = _rounding(precision)
    kernel, stride, block, topk, window, init = sizes
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = x if mixer_only else s(_rms_norm(x, ln["scale"], eps))
        q, k, v = lin(y, "q_proj"), lin(y, "k_proj"), lin(y, "v_proj")
        d = q.shape[-1] // heads
        group = heads // kv_heads
        q, k, v = q.reshape(t, kv_heads, group, d), k.reshape(t, kv_heads, d), v.reshape(t, kv_heads, d)
        if qk_norm:
            q, k = s(_head_norm(q, p["q_norm"]["scale"], eps)), s(_head_norm(k, p["k_norm"]["scale"], eps))
        J = max(0, (t - kernel) // stride + 1)
        starts = stride * jnp.arange(J)
        if "sparse_compress" in drop:
            kc = k[starts] if J else jnp.zeros((0, kv_heads, d), jnp.float32)
        else:
            kc = s(sum(k[starts + i] for i in range(kernel)) / kernel) if J else jnp.zeros((0, kv_heads, d), jnp.float32)
        blocks = -(-t // block)
        key_block = jnp.arange(t) // block
        pad = -t % QUERY_BLOCK
        q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, kv_heads, group, d)
        at_blocks = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)

        def query_block(inputs):
            q_b, at = inputs
            chosen = _choose(q_b, kc, at, sizes=sizes + (blocks,), drop=drop, ra=ra)  # [n, G, blocks]
            if choices:
                return chosen
            keep = chosen[:, :, key_block] & (jnp.arange(t)[None, :] <= at[:, None])[:, None, :]  # [n, G, t]
            scores = jnp.einsum("nghd,kgd->nghk", ra(q_b), ra(k)) * d ** -0.5
            probs = s(jax.nn.softmax(jnp.where(keep[:, :, None, :], scores, NEG), axis=-1))
            return s(jnp.einsum("nghk,kgd->nghd", ra(probs), ra(v)))

        out = jax.lax.map(query_block, (q_blocks, at_blocks))
        out = out.reshape((t + pad,) + out.shape[2:])[:t]
        if choices:
            return out
        o = out.reshape(t, heads * d)
        if gated and "sparse_gate" not in drop:
            o = s(o * jax.nn.sigmoid(lin(y, "g_proj")))
        return lin(o, "c_proj") if mixer_only else s(x + multiplier * lin(o, "c_proj"))


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def _scaled_head(x, ln_f, head, *, eps, scaling, precision):
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        x = s(_rms_norm(x, _f32(ln_f)["scale"], eps))
        return s(r(x) @ r(head["kernel"].astype(jnp.float32))) / scaling


def _sparse_sizes(a):
    return tuple(int(a[k]) for k in ("sparse_kernel", "sparse_stride", "sparse_block", "sparse_topk", "sparse_window",
                                     "sparse_init_blocks"))


def _row(trunk, a, ids, last, precision, drop=(), state_of=None, choices_of=None):
    """Logits [min(last, t), vocab] of the final positions of one unpadded row `ids` [t]; with `state_of` a
    lightning layer's index: that layer's state after the row's last token; with `choices_of` a sparse layer's
    index: that layer's chosen sets [t, G, blocks]; and no logits."""
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    multiplier = float(a.get("residual_multiplier", 1.0))
    shared = "bfloat16_stream" if precision == "bfloat16_state" else precision  # the other references' helpers know their own rows
    x = s(_embed(trunk["wte"]["embedding"], ids, precision=shared) * float(a.get("embedding_multiplier", 1.0)))
    for i, kind in enumerate(a["mixer_layers"]):
        p = trunk[f"h_{i}"]
        if kind == "lightning":
            x, final = _lightning(x, p["ln_1"], p["lightning"], heads=a["lightning_heads"], head_dim=a["lightning_head_dim"],
                                  eps=eps, theta=float(a.get("rope_theta", 10000.0)), multiplier=multiplier,
                                  qk_norm=bool(a.get("qk_norm")), gated=bool(a.get("lightning_output_gate")),
                                  precision=precision, drop=drop)
            if i == state_of:
                return final
        else:
            kwargs = dict(heads=a["n_head"], kv_heads=a.get("n_kv_head") or a["n_head"], eps=eps, multiplier=multiplier,
                          qk_norm=bool(a.get("qk_norm")), gated=bool(a.get("attn_output_gate")), sizes=_sparse_sizes(a),
                          precision=precision, drop=drop)
            if i == choices_of:
                return _sparse(x, p["ln_1"], p["attn"], choices=True, **kwargs)
            x = _sparse(x, p["ln_1"], p["attn"], **kwargs)
        y = _normed(x, p["ln_2"], eps=eps, precision=shared)
        x = s(x + multiplier * _gated_mlp(y, p["mlp"], precision=shared))
    return _scaled_head(x[-last:], trunk["ln_f"], trunk["lm_head"], eps=eps, scaling=float(a.get("logits_scaling", 1.0)),
                        precision=shared)


def layer_state(trunk, model_arch, ids, layer, precision="highest"):
    """float32 state [lightning_heads, lightning_head_dim, lightning_head_dim] of lightning layer `layer` after the
    last token of ONE unpadded row `ids` [t]: what a decode step's cache leaf of that layer must hold then."""
    if model_arch["mixer_layers"][layer] != "lightning":
        raise ValueError(f"layer {layer} is no lightning layer")
    return _row(trunk.get("transformer", trunk), model_arch, ids, 0, precision, state_of=layer)


def chosen_sets(trunk, model_arch, ids, layer, precision="highest"):
    """bool [t, G, blocks]: the blocks every query of ONE unpadded row `ids` [t] chooses in sparse layer `layer`
    (how often a choice flips between two precisions is read from two calls)."""
    if model_arch["mixer_layers"][layer] != "attention":
        raise ValueError(f"layer {layer} is no sparse attention layer")
    return _row(trunk.get("transformer", trunk), model_arch, ids, 0, precision, choices_of=layer)


def sparse_layer(attn, model_arch, x, precision="highest", choices=False):
    """ONE sparse attention mixer alone, on ONE unpadded row of its own input `x` [t, d_model] (what the block's
    norm would hand it): its output [t, d_model] float32 (no residual), or with `choices` its chosen sets bool
    [t, G, blocks]. `attn` is the layer's parameter subtree (the program's `h_<i>/attn`, any dtype)."""
    a = model_arch
    return _sparse(x.astype(jnp.float32), {"scale": jnp.ones((x.shape[-1],), jnp.float32)}, attn, heads=a["n_head"],
                   kv_heads=a.get("n_kv_head") or a["n_head"], eps=float(a.get("ln_eps", 1e-5)), multiplier=1.0,
                   qk_norm=bool(a.get("qk_norm")), gated=bool(a.get("attn_output_gate")), sizes=_sparse_sizes(a),
                   precision=precision, drop=(), choices=choices, mixer_only=True)


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest", drop=()):
    """float32 logits [b, last, vocab] of the final `last` positions of the
    padded batch; zeros where a row has no real token there.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype; the
    whole ``params`` passes too), `model_arch` the configuration's (the
    program's LMConfig keys), `attention_mask` CONCRETE (each row is cut to
    its real tokens on the host). `precision` names a row of PRECISIONS,
    `drop` pieces to leave out (module docstring)."""
    a = model_arch
    trunk = trunk.get("transformer", trunk)
    if (a.get("norm"), a.get("mlp"), a.get("attention"), a.get("activation"), a.get("pos_type"), a.get("rotary_layers")) != (
            "rmsnorm", "gated", "sparse", "silu", "rotary", "lightning") or a.get("tie_word_embeddings", True) \
            or a.get("parallel_residual", False) or a.get("ffn_layers") or not a.get("extra", {}).get("neox_rotary") \
            or set(a.get("mixer_layers", ())) - {"lightning", "attention"} or "lightning" not in a.get("mixer_layers", ()):
        raise ValueError("sala_decoder is the reference of the rmsnorm / gated silu decoder with lightning layers (rotary "
                         "inside, rotate-half pairs) and block-selected sparse attention layers without rotary, an untied head")
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
            logits = _row(trunk, a, ids[first:stop], want, precision, tuple(drop))
            out = jax.lax.dynamic_update_slice(out, logits, (last - (total - stop) - want, 0))
        rows.append(out)
    return jnp.stack(rows)

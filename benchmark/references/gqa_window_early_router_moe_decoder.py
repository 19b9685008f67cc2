"""Plain reference of the grouped-key, windowed decoder whose router stands
ahead of attention (SmallThinker, `model_name: smallthinker_*`): the forward
pass in straightforward float32 `jax.numpy`, matmuls at
`jax.default_matmul_precision("highest")`, no kernels, no cache, the masks
built from the two inequalities below, K and V REPEATED to the query heads in
the open (the plain form of grouped keys), the experts a plain loop over the
held ids. Written from the equations (ISSUE 44; the catalogued config.json and
its `described_as`; llama.cpp's graph of the family for where the router
reads), not from `trlx_tpu/models/`; it reads the program's parameter tree
only for the weights.

Token embedding, no position table; pre-norm blocks, sequential residual;
final RMSNorm; untied head, no bias. RMSNorm everywhere (scale only), no bias
in any projection. Block l, input x [T, d]:

    r    = x W_r                     [T, n_experts], a float32 product; x is the block's INPUT,
                                     before RMSNorm_1, ahead of attention
    ids  = the experts_per_token largest of r    over all n_experts, whatever is held here
    w    = softmax(r[ids])           over the chosen, float32; no bias, no scale
    a    = x + Attn_l(RMSNorm_1(x))
    h    = RMSNorm_2(a)
    y    = a + sum over e in (ids and held) of w_e W_down,e(relu(W_gate,e h) * W_up,e h)

    Attn  q = u W_q -> [T, H, hd];  k = u W_k, v = u W_v -> [T, H_kv, hd];  no qk-norm
          a "global" layer: no rotary at all (NoPE); key j admitted for query i iff j <= i
          a "local" layer:  q, k = RoPE(q, k), theta, all hd dims, rotate-half pairs (i, i + hd/2);
                            key j admitted iff 0 <= i - j < window
          scores_h = q_h . k_(h // g) / sqrt(hd), g = H / H_kv; float32 softmax; . v_(h // g);
          heads joined [T, H hd] W_o -> d

Departures from the published model, each on purpose:
  * only the routed experts `experts_held = [first, first + count)` exist:
    the sum runs over chosen AND held (one chip's share of an expert-parallel
    deployment); routing is over all n_experts all the same;
  * the vocabulary is the slice the configuration keeps;
  * every weight is drawn from the seed;
  * positions of a left-padded row count from its first real token (the
    program's convention for rollouts).
Attention goes through in blocks of `QUERY_BLOCK` queries: two rows of 6,144
positions at 28 heads are 8.4 GB of float32 scores in one piece, and the
reference runs beside a trainer that fills the chip. One sub-layer's weights
are cast up to float32 at a time, inside a jitted function. The embedding, the
norms and the head are the sparse-expert reference's own (`mla_moe_decoder`):
the same equations; the rotation is `gqa_window_moe_decoder`'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt_decoder import NEG, PRECISIONS, _f32, _rounding  # the same table of coarser reruns
from benchmark.references.gqa_window_moe_decoder import _rope_halves
from benchmark.references.mla_moe_decoder import _embed, _head, _normed, _rms_norm

__all__ = ["PRECISIONS", "forward"]

QUERY_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "rotary", "theta", "eps", "precision"))
def _attention(x, ln, p, attention_mask, positions, *, heads, kv_heads, window, rotary, theta, eps, precision):
    p, ln = _f32(p), _f32(ln)
    b, t, _ = x.shape
    r, ra, s = _rounding(precision)
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        u = s(_rms_norm(x, ln["scale"], eps))
        q, k, v = lin(u, "q_proj"), lin(u, "k_proj"), lin(u, "v_proj")
        hd = q.shape[-1] // heads
        q, k, v = q.reshape(b, t, heads, hd), k.reshape(b, t, kv_heads, hd), v.reshape(b, t, kv_heads, hd)
        if rotary:
            q, k = s(_rope_halves(q, positions, theta)), s(_rope_halves(k, positions, theta))
        group = heads // kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head h reads K/V head h // group
        block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
        j = jnp.arange(t)[None, :]

        def queries(args):
            q_block, i = args  # [b, block, H, hd], the queries' own indices [block]
            scores = jnp.einsum("bqhd,bkhd->bhqk", ra(q_block), ra(k)) / np.sqrt(hd)
            keep = j <= i[:, None]
            if window:
                keep = keep & (i[:, None] - j < window)
            keep = keep[None, None] & attention_mask[:, None, None, :].astype(bool)
            probs = s(jax.nn.softmax(scores + jnp.where(keep, 0.0, NEG), axis=-1))
            return s(jnp.einsum("bhqk,bkhd->bqhd", ra(probs), ra(v)))

        blocks = (jnp.moveaxis(q.reshape(b, t // block, block, heads, hd), 1, 0), jnp.arange(t).reshape(t // block, block))
        mixed = jnp.moveaxis(jax.lax.map(queries, blocks), 0, 1).reshape(b, t, heads * hd)
        return s(x + lin(mixed, "c_proj"))


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _route(x, router, *, k, precision):
    """(ids [b, t, k], weights [b, t, k]) from the block's input `x`: the k
    largest logits, a softmax over those k. The product is float32 whatever
    the stream's precision (the configuration states it so); the two int8
    controls feed it int8 like every other weight matmul."""
    r = _rounding(precision)[0] if precision.startswith("int8") else (lambda z: z)
    with jax.default_matmul_precision("highest"):
        logits = r(x) @ r(router.astype(jnp.float32))
    chosen, ids = jax.lax.top_k(logits, k)
    return ids, jax.nn.softmax(chosen, axis=-1)


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(h, gate, up, down, weight, *, precision):
    r, _, s = _rounding(precision)
    gate, up, down = gate.astype(jnp.float32), up.astype(jnp.float32), down.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        hidden = s(jnp.maximum(s(r(h) @ r(gate)), 0.0) * s(r(h) @ r(up)))
        return s(r(hidden) @ r(down)) * weight[..., None]


def _experts(h, ids, weights, p, a, precision):
    """sum over (chosen and held) of w_e Expert_e(h): one held expert at a time
    over every token, its weight zero where the token did not choose it."""
    first = a["experts_held"][0] if a.get("experts_held") else 0
    total = jnp.zeros_like(h)
    for j in range(p["experts_gate"].shape[0]):  # expert first + j is row j of the held tensors
        weight = jnp.sum(jnp.where(ids == first + j, weights, 0.0), axis=-1)
        total = total + _expert(h, p["experts_gate"][j], p["experts_up"][j], p["experts_down"][j], weight, precision=precision)
    return _rounding(precision)[2](total)


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest"):
    """float32 logits [b, last, vocab] of the final `last` positions.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype),
    `model_arch` the configuration's (the program's LMConfig keys).
    `precision` names a row of PRECISIONS: "highest" is the reference; the
    others rerun it coarser."""
    a = model_arch
    kinds = a.get("ffn_layers") or ["dense"] * a["n_layer"]
    if (a.get("norm"), a.get("mlp"), a.get("attention", "mha"), a.get("activation")) != ("rmsnorm", "gated", "mha", "relu") \
            or (a.get("router_scoring"), a.get("router_input")) != ("softmax", "block") or set(kinds) != {"experts"} \
            or a.get("n_shared_experts") or a.get("routed_scaling_factor", 1.0) != 1.0 \
            or a.get("tie_word_embeddings", True) or a.get("parallel_residual", False) or a.get("qk_norm") \
            or a.get("pos_type") != "rotary" or a.get("rotary_layers") != "local" \
            or not a.get("extra", {}).get("neox_rotary") or a.get("rotary_dim") or a.get("fused_qkv", True) \
            or a.get("qkv_bias", True) or a.get("out_bias", True):
        raise ValueError("gqa_window_early_router_moe_decoder is the reference of the rmsnorm / grouped-key decoder with "
                         "rotate-half rotary on its window layers only, every layer ReGLU experts under a softmax router "
                         "that reads the block's input, no shared expert, no biases, an untied head")
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=-1) - 1, 0)
    x = _embed(trunk["wte"]["embedding"], input_ids, precision=precision)
    for i, kind in enumerate(a.get("attention_layers") or ["global"] * a["n_layer"]):
        p, local = trunk[f"h_{i}"], kind == "local"
        ids, weights = _route(x, p["moe"]["router"], k=a["experts_per_token"], precision=precision)  # ahead of attention
        x = _attention(x, p["ln_1"], p["attn"], attention_mask, positions, heads=a["n_head"],
                       kv_heads=a.get("n_kv_head") or a["n_head"], window=int(a["window_size"]) if local else 0,
                       rotary=local, theta=float(a.get("rope_theta", 10000.0)), eps=eps, precision=precision)
        h = _normed(x, p["ln_2"], eps=eps, precision=precision)
        x = s(x + _experts(h, ids, weights, p["moe"], a, precision))
    return _head(x[:, -last:], trunk["ln_f"], trunk["lm_head"], eps=eps, precision=precision)

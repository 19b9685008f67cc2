"""Plain reference of the indexed latent-attention, sparse-expert decoder (GLM-5,
`model_type: glm_moe_dsa`: DeepSeek-V3's latent attention and expert layer
under DeepSeek Sparse Attention's indexer): the forward pass in
straightforward float32 `jax.numpy`, matmuls at
`jax.default_matmul_precision("highest")`, no kernels, no cache, no absorbed
form, no spans, no batching: ONE UNPADDED ROW AT A TIME, the index scores and
the top-k over the whole row (the queries in blocks only so that 8,192 tokens
fit), the chosen set applied as a mask (the topk-th largest score by a full sort), the
held experts a plain loop. Written from the equations of ISSUE 53 (DeepSeek-V2,
arXiv:2405.04434, for latent attention; DeepSeek-V3, arXiv:2412.19437, for the
router; the DeepSeek-V3.2-Exp report and its `inference/model.py` class
`Indexer` for the indexer; the catalogued keys of the family's config.json),
not from `trlx_tpu/models/`; it reads the program's parameter tree only for
the weights.

Trunk: r_0 = E[token]; each layer r <- r + Attn(RMSNorm_1(r)), r <- r + FFN(RMSNorm_2(r));
logits = W_head RMSNorm_f(r); no bias but the index key's LayerNorm's; H heads, H_I index heads of D_I.

    MLA      c_q = RMSNorm(W_qa x);  q_h = W_qb c_q -> [q^n (nope) | q^r (rope)]
             [c_kv | k^r] = W_kva x;  c_kv = RMSNorm(c_kv);  [k_h^n | v_h] = W_kvb c_kv
             RoPE on q_h^r and on the ONE shared k^r, interleaved pairs (2i, 2i + 1), base rope_theta, no scaling
             s_{t,h,j} = (q^n_{t,h} . k^n_{j,h} + q^r_{t,h} . k^r_j) / sqrt(nope + rope)
             o_{t,h} = sum_{j in S_t} softmax_{j in S_t}(s_{t,h,j}) v_{j,h};  out = W_o [o_1 .. o_H]
    indexer  q^I_{t,h} = W^I_q c_q,t (H_I x D_I), RoPE on its first `rope` channels (interleaved pairs)
             k^I_j = LayerNorm(W^I_k x_j) (weight and bias, eps 1e-6), RoPE on its first `rope` channels; one a token
             w_{t,h} = (W^I_w x_t)_h / sqrt(H_I) / sqrt(D_I)
             I_{t,j} = sum_h w_{t,h} ReLU(q^I_{t,h} . k^I_j),  j <= t
             S_t = the index_topk j of largest I_{t,j};  every j <= t where t + 1 <= index_topk;  of equal scores the earlier j
    FFN      a "dense" layer: W_down(silu(W_gate x') * W_up x');  an "experts" layer: benchmark/references/mla_moe_decoder.py's
             (sigmoid scores over all n_experts, the experts_per_token largest of score + bias, renormalised and scaled,
             the HELD experts summed, the shared expert)

Departures from the published model, each on purpose (the configuration's `assumed`):
  * no Hadamard rotation and no FP8 in the indexer (an orthogonal rotation of q^I and k^I alike changes no product);
  * no MTP block; only the routed experts `experts_held` and the attention heads the tree holds exist;
  * the vocabulary is the slice the configuration keeps; every weight is drawn from the seed;
  * a row is cut to its real tokens before anything is computed, so there is no padding; the logits land at the
    row's positions in the padded batch (rows are contiguous: padding on the left, as the rollout pads, or on the right).
One sub-layer's weights are cast up to float32 at a time, inside a jitted function, so the reference fits beside a
trainer that fills the chip; the head is computed at the `last` positions asked for, never at all of a row's.

`precision` names a row of PRECISIONS (the other references' table): in the coarser reruns `r` rounds what the
weight matmuls read, `ra` what attention's products and the index scores' read, `s` what an operation hands to the
next; every sum stays float32, and the router's product as benchmark/references/mla_moe_decoder.py has it.

`drop` names pieces to leave out, for the tests that show each piece is held by the comparison: "index_layernorm"
(k^I = W^I_k x), "index_rope" (neither q^I nor k^I rotated), "index_relu", "index_weights" (w = 1), "index_topk" (S_t
= the newest index_topk keys, whatever the scores say), "index_choice" (every j <= t: dense latent attention).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt_decoder import NEG, PRECISIONS, _f32, _layer_norm, _rounding
from benchmark.references.mla_moe_decoder import _embed, _expert_ffn, _gated_mlp, _head, _normed, _rms_norm, _rope

__all__ = ["PRECISIONS", "forward", "chosen_sets", "indexed_layer"]

QUERY_BLOCK = 256  # queries a step of the attention holds at once (memory only: every row is whole)
INDEX_EPS = 1e-6  # the index key's LayerNorm


def _sizes(a):
    return tuple(sorted({
        "heads": a["n_head"], "nope": a["qk_nope_head_dim"], "rope": a["qk_rope_head_dim"], "v": a["v_head_dim"],
        "rank": a["kv_lora_rank"], "index_heads": a["index_n_heads"], "index_dim": a["index_head_dim"],
        "topk": a["index_topk"], "eps": float(a.get("ln_eps", 1e-5)), "theta": float(a.get("rope_theta", 10000.0)),
    }.items()))


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "drop", "choices", "mixer_only"))
def _attention(x, ln, p, *, sizes, precision, drop, choices=False, mixer_only=False):
    """One indexed latent-attention layer on ONE unpadded row x [t, d]: x + out; with `mixer_only` out alone on x as
    the normed input; with `choices` the chosen sets bool [t, t] (row t: the keys query t attends to)."""
    a = dict(sizes)
    p, ln = _f32(p), _f32(ln)
    t = x.shape[0]
    h, dn, dr, dv, rank, hi, di, topk = (a[k] for k in ("heads", "nope", "rope", "v", "rank", "index_heads", "index_dim", "topk"))
    r, ra, s = _rounding(precision)
    inv_freq = 1.0 / a["theta"] ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    positions = jnp.arange(t)[None]
    rope = lambda z: _rope(z[None], positions, inv_freq, 1.0)[0]  # z [t, heads, dr]
    lin = lambda z, w: s(r(z) @ r(w["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = x if mixer_only else s(_rms_norm(x, ln["scale"], a["eps"]))
        c_q = s(_rms_norm(lin(y, p["q_a_proj"]), p["q_a_norm"]["scale"], a["eps"]))
        q = lin(c_q, p["q_b_proj"]).reshape(t, h, dn + dr)
        kv_a = lin(y, p["kv_a_proj"])
        c_kv = s(_rms_norm(kv_a[:, :rank], p["kv_a_norm"]["scale"], a["eps"]))
        kv = lin(c_kv, p["kv_b_proj"]).reshape(t, h, dn + dv)
        q_rope, k_rope = s(rope(q[..., dn:])), s(rope(kv_a[:, None, rank:]))[:, 0]  # [t, h, dr], [t, dr]: one key for all heads
        keys = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (t, h, dr))], axis=-1)
        queries = jnp.concatenate([q[..., :dn], q_rope], axis=-1)

        ix = p["indexer"]
        part_rope = (lambda z: z) if "index_rope" in drop else (lambda z: jnp.concatenate([rope(z[..., :dr]), z[..., dr:]], axis=-1))
        q_idx = s(part_rope(lin(c_q, ix["q_proj"]).reshape(t, hi, di)))
        k_idx = lin(y, ix["k_proj"])
        if "index_layernorm" not in drop:
            k_idx = s(_layer_norm(k_idx, ix["k_norm"], INDEX_EPS))
        k_idx = s(part_rope(k_idx[:, None, :]))[:, 0]
        w = lin(y, ix["w_proj"]) * (hi ** -0.5 * di ** -0.5)
        if "index_weights" in drop:
            w = jnp.ones_like(w)

        pad = -t % QUERY_BLOCK
        blocks = lambda z: jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1)).reshape((-1, QUERY_BLOCK) + z.shape[1:])
        at = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
        j = jnp.arange(t)

        def block(args):
            q_b, qi_b, w_b, at_b = args
            seen = j[None, :] <= at_b[:, None]  # [Q, t]
            per_head = jnp.einsum("qhd,kd->hqk", ra(qi_b), ra(k_idx))
            if "index_relu" not in drop:
                per_head = jax.nn.relu(per_head)
            score = jnp.einsum("hqk,qh->qk", per_head, w_b)
            if "index_topk" in drop:
                score = jnp.broadcast_to(j.astype(jnp.float32)[None, :], score.shape)  # the newest keys
            if "index_choice" in drop or t <= topk:
                chosen = seen
            else:
                # the topk largest by a full sort: the topk-th largest score, every key above it, and of the keys AT it
                # the earliest as far as topk goes (no scatter of indices: a TPU takes seconds over one)
                score = jnp.where(seen, score, -jnp.inf)
                kth = jnp.sort(score, axis=-1)[:, -topk][:, None]
                above, tied = score > kth, (score == kth) & seen
                room = topk - jnp.sum(above, axis=-1, keepdims=True)
                chosen = (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) & seen
            if choices:
                return chosen
            scores = jnp.einsum("qhd,khd->hqk", ra(q_b), ra(keys)) * (dn + dr) ** -0.5
            scores = scores + jnp.where(chosen, 0.0, NEG)[None]
            # softmax in two passes, the row maximum behind a barrier: fused with its subtraction the v5e compiler
            # makes one reduce-window over the whole [h, Q, t] array of them, which takes most of a minute a row
            weights = jnp.exp(scores - jax.lax.optimization_barrier(jnp.max(scores, axis=-1, keepdims=True)))
            probs = s(weights / jnp.sum(weights, axis=-1, keepdims=True))
            return s(jnp.einsum("hqk,khd->qhd", ra(probs), ra(kv[..., dn:]))).reshape(QUERY_BLOCK, h * dv)

        out = jax.lax.map(block, (blocks(queries), blocks(q_idx), blocks(w), at))
        out = out.reshape((t + pad,) + out.shape[2:])[:t]
        if choices:
            return out
        out = lin(out, p["c_proj"])
        return out if mixer_only else s(x + out)


def _row(trunk, a, ids, last, precision, drop=(), choices_of=None):
    """Logits [min(last, t), vocab] of the final positions of one unpadded row `ids` [t]; with `choices_of` a
    layer's index: that layer's chosen sets [t, t] and no logits."""
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    x = _embed(trunk["wte"]["embedding"], ids, precision=precision)
    kinds = a.get("ffn_layers") or ["dense"] * a["n_layer"]
    for i, kind in enumerate(kinds):
        p = trunk[f"h_{i}"]
        if i == choices_of:
            return _attention(x, p["ln_1"], p["attn"], sizes=_sizes(a), precision=precision, drop=drop, choices=True)
        x = _attention(x, p["ln_1"], p["attn"], sizes=_sizes(a), precision=precision, drop=drop)
        y = _normed(x, p["ln_2"], eps=eps, precision=precision)
        x = s(x + (_expert_ffn(y, p["moe"], a, precision) if kind == "experts" else _gated_mlp(y, p["mlp"], precision=precision)))
    return _head(x[-last:], trunk["ln_f"], trunk["lm_head"], eps=eps, precision=precision)


def chosen_sets(trunk, model_arch, ids, layer, precision="highest", drop=()):
    """bool [t, t]: the keys every query of ONE unpadded row `ids` [t] attends to in layer `layer` (how often a
    choice flips between two precisions is read from two calls)."""
    return _row(trunk.get("transformer", trunk), model_arch, ids, 0, precision, tuple(drop), choices_of=layer)


def indexed_layer(attn, model_arch, x, precision="highest", choices=False, drop=()):
    """ONE indexed latent-attention mixer alone, on ONE unpadded row of its own input `x` [t, d_model] (what the
    block's norm would hand it): its output [t, d_model] float32 (no residual), or with `choices` its chosen sets
    bool [t, t]. `attn` is the layer's parameter subtree (the program's `h_<i>/attn`, any dtype)."""
    return _attention(x.astype(jnp.float32), {"scale": jnp.ones((x.shape[-1],), jnp.float32)}, attn, sizes=_sizes(model_arch),
                      precision=precision, drop=tuple(drop), choices=choices, mixer_only=True)


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
    if (a.get("norm"), a.get("mlp"), a.get("attention"), a.get("activation"), a.get("pos_type")) != (
            "rmsnorm", "gated", "mla", "silu", "rotary") or a.get("tie_word_embeddings", True) \
            or a.get("parallel_residual", False) or a.get("rope_scaling") or not a.get("index_topk") or not a.get("q_lora_rank"):
        raise ValueError("dsa_mla_moe_decoder is the reference of the rmsnorm / gated silu / mla decoder with an indexer "
                         "(index_topk), a query bottleneck, rotary positions without scaling and an untied head")
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

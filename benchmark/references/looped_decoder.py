"""Plain reference of the looped decoder (Ouro, `model_type: ouro`): a stack
of N shared-weight blocks run R = `n_loops` times a token, in straightforward
float32 `jax.numpy`, matmuls at `jax.default_matmul_precision("highest")`, no
kernels, no cache, ONE UNPADDED ROW AT A TIME, the loops a plain `for`.
Written from the equations (ISSUE 37, the family's description; the inside of
the block is transformers' Llama block, which a tier-1 test holds both this
file and the program to), not from `trlx_tpu/models/`; it reads the program's
parameter tree only for the weights.

    x = E[ids]
    for r in 1..R:                                  the SAME parameters every r
      for l in 1..N:
        a = RMSNorm(h; g1_l);  q, k, v = a W_q, a W_k, a W_v -> [t, H, hd]
        q, k = RoPE(q, k): all hd dims, rotate-half pairs (i, i + hd/2), theta, positions 0..t-1
        o = softmax(q k^T / sqrt(hd) + causal) v . W_o                float32 softmax
        h = h + RMSNorm(o; g2_l)                                      the sandwich norm
        m = RMSNorm(h; g3_l);  f = (silu(m W_gate) * m W_up) W_down
        h = h + RMSNorm(f; g4_l)                                      the sandwich norm
      z_r = RMSNorm(h; g_f)          the ONE final norm; the loop's output AND loop r+1's input
      lambda_r = sigmoid(w_g . z_r + b_g)
    p_r = lambda_r prod_{j<r} (1 - lambda_j) for r < R;  p_R = prod_{j<R} (1 - lambda_j)
    logits = z_R W_head                             (threshold 1: every loop runs, the gate decides nothing)

Departures from the family's description, each on purpose:
  * the sandwich norms, the final norm at the end of every loop and the exit
    gate are the description's, not keys of the published config.json, and no
    publisher's code stands behind them here (transformers 4.57.6 has no
    `ouro` module): the configuration's file lists them under `assumed`;
  * PPO reads the last loop's logits only; the family's pretraining objective
    (an expectation over exits with an entropy term) is not RLHF's loss;
  * lambda_R is not computed: the last loop takes what is left;
  * positions of a left-padded row count from its first real token (the
    program's convention for rollouts): here a row is cut to its real tokens
    and its positions start at 0;
  * every weight is drawn from the seed.
`loops` overrides R (the control of the cell's logits check runs R - 1: one
loop fewer is another model and has to fail the check). One sub-layer's
weights are cast up to float32 at a time, inside a jitted function, so the
reference fits beside a trainer that fills the chip. The gated feed-forward,
the norms, the embedding and the head are the sparse-expert reference's own
(`mla_moe_decoder`), the rotary the grouped-key reference's: the same
equations.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt_decoder import NEG, PRECISIONS, _f32, _rounding  # the same table of coarser reruns
from benchmark.references.gqa_window_moe_decoder import _rope_halves
from benchmark.references.mla_moe_decoder import _embed, _gated_mlp, _head, _normed, _rms_norm

__all__ = ["PRECISIONS", "forward", "exit_distribution", "ppo_loss_gradients"]


@functools.partial(jax.jit, static_argnames=("heads", "theta", "eps", "sandwich", "precision"))
def _attention(x, ln, ln_out, p, *, heads, theta, eps, sandwich, precision):
    """x [1, t, d] -> x + (RMSNorm of) the attention branch."""
    p, ln = _f32(p), _f32(ln)
    b, t, _ = x.shape
    r, ra, s = _rounding(precision)
    lin = lambda z, name: s(r(z) @ r(p[name]["kernel"]))
    with jax.default_matmul_precision("highest"):
        y = s(_rms_norm(x, ln["scale"], eps))
        q, k, v = (lin(y, name).reshape(b, t, heads, -1) for name in ("q_proj", "k_proj", "v_proj"))
        hd = q.shape[-1]
        positions = jnp.arange(t)[None]
        q, k = s(_rope_halves(q, positions, theta)), s(_rope_halves(k, positions, theta))
        scores = jnp.einsum("bqhd,bkhd->bhqk", ra(q), ra(k)) / np.sqrt(hd)
        keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        probs = s(jax.nn.softmax(scores + jnp.where(keep, 0.0, NEG)[None, None], axis=-1))
        mixed = s(jnp.einsum("bhqk,bkhd->bqhd", ra(probs), ra(v))).reshape(b, t, heads * hd)
        out = lin(mixed, "c_proj")
        if sandwich:
            out = s(_rms_norm(out, _f32(ln_out)["scale"], eps))
        return s(x + out)


@functools.partial(jax.jit, static_argnames=("precision",))
def _gate(z, p, *, precision):
    """lambda [1, t] = sigmoid(w . z + b), float32 whatever the stream's precision."""
    p = _f32(p)
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid((z @ p["kernel"])[..., 0] + p["bias"][0])


def _row(trunk, a, ids, last, precision, loops=None, loop_blocks=None):
    """(logits [min(last, t), vocab] of the final positions, exit distribution
    [t, R] or None without a gate) of one unpadded row `ids` [t]. `loop_blocks`
    (a list of R trees): loop r reads its blocks from `loop_blocks[r]`, for the
    test that a shared block's gradient is the sum over its uses."""
    eps, s = float(a.get("ln_eps", 1e-5)), _rounding(precision)[2]
    sandwich, n_loops = bool(a.get("sandwich_norm")), int(loops or a.get("n_loops", 1))
    x = _embed(trunk["wte"]["embedding"], ids[None], precision=precision)
    lams = []
    for loop in range(n_loops):  # the same parameters every loop
        for i in range(a["n_layer"]):
            p = (loop_blocks[loop] if loop_blocks else trunk)[f"h_{i}"]
            x = _attention(x, p["ln_1"], p.get("ln_1_out"), p["attn"], heads=a["n_head"],
                           theta=float(a.get("rope_theta", 10000.0)), eps=eps, sandwich=sandwich, precision=precision)
            f = _gated_mlp(_normed(x, p["ln_2"], eps=eps, precision=precision), p["mlp"], precision=precision)
            x = s(x + (_normed(f, p["ln_2_out"], eps=eps, precision=precision) if sandwich else f))
        if n_loops == 1:
            break  # one pass: the final norm once, inside the head
        if loop < n_loops - 1:
            x = _normed(x, trunk["ln_f"], eps=eps, precision=precision)
            if a.get("exit_gate"):
                lams.append(_gate(x, trunk["exit_gate"], precision=precision)[0])
    exits = None
    if lams:
        lam = jnp.stack(lams, axis=-1)  # [t, R - 1]
        stay = jnp.cumprod(1.0 - lam, axis=-1)
        before = jnp.concatenate([jnp.ones_like(stay[:, :1]), stay[:, :-1]], axis=-1)
        exits = jnp.concatenate([lam * before, stay[:, -1:]], axis=-1)
    # `_head` applies the final norm: the last loop's, z_R
    return _head(x[0, -last:], trunk["ln_f"], trunk["lm_head"], eps=eps, precision=precision), exits


def _check(a):
    if (a.get("norm"), a.get("mlp"), a.get("attention", "mha"), a.get("activation"), a.get("pos_type")) != (
            "rmsnorm", "gated", "mha", "silu", "rotary") or a.get("tie_word_embeddings", True) \
            or a.get("parallel_residual", False) or a.get("fused_qkv", True) or a.get("qkv_bias", True) \
            or a.get("out_bias", True) or a.get("qk_norm") or a.get("attention_layers") or a.get("ffn_layers") \
            or a.get("mixer_layers") or a.get("rotary_dim") or a.get("n_kv_head") or a.get("head_width") \
            or not a.get("extra", {}).get("neox_rotary"):
        raise ValueError("looped_decoder is the reference of the rmsnorm / gated silu / full-attention decoder with "
                         "rotate-half rotary over the whole head, no biases, an untied head, run n_loops times a token")


def _rows(input_ids, attention_mask):
    """(row index, first, stop) of each row's real tokens, cut on the host."""
    mask = np.asarray(attention_mask).astype(bool)
    for j, real in enumerate(mask):
        where = np.flatnonzero(real)
        first, stop = (int(where[0]), int(where[-1]) + 1) if where.size else (0, 0)
        if not real[first:stop].all():
            raise ValueError("a row's real tokens must be contiguous")
        yield j, first, stop


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest", loops=None, loop_blocks=None):
    """float32 logits [b, last, vocab] of the final `last` positions of the
    padded batch; zeros where a row has no real token there.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype; the
    whole ``params`` passes too), `model_arch` the configuration's (the
    program's LMConfig keys), `attention_mask` CONCRETE. `precision` names a
    row of PRECISIONS; `loops` runs another number of loops than `n_loops`."""
    a = model_arch
    _check(a)
    trunk = trunk.get("transformer", trunk)
    total = np.asarray(attention_mask).shape[1]
    rows = []
    for j, first, stop in _rows(input_ids, attention_mask):
        want = max(0, stop - max(first, total - last))  # real positions inside the final `last`
        out = jnp.zeros((last, a["vocab_size"]), jnp.float32)
        if want:
            logits, _ = _row(trunk, a, input_ids[j, first:stop], want, precision, loops, loop_blocks)
            out = jax.lax.dynamic_update_slice(out, logits, (last - (total - stop) - want, 0))
        rows.append(out)
    return jnp.stack(rows)


def exit_distribution(trunk, model_arch, input_ids, attention_mask, precision="highest"):
    """float32 [b, t, R]: where the exit gate would leave the loop, at every
    position of the padded batch; zeros on padding."""
    a = model_arch
    _check(a)
    trunk = trunk.get("transformer", trunk)
    b, total = np.asarray(attention_mask).shape
    out = jnp.zeros((b, total, int(a["n_loops"])), jnp.float32)
    for j, first, stop in _rows(input_ids, attention_mask):
        if stop > first:
            out = out.at[j, first:stop].set(_row(trunk, a, input_ids[j, first:stop], 1, precision)[1])
    return out


def ppo_loss_gradients(trunk, model_arch, input_ids, attention_mask, prompt, old_logprobs, advantages, cliprange=0.2,
                       per_use=False):
    """(loss, gradients over `trunk`) of PPO's clipped policy loss on the
    response positions [prompt, t) of the padded batch, read from the LAST
    loop's logits: ratio = exp(log p(token) - old), loss = mean over the real
    response tokens of max(-A ratio, -A clip(ratio, 1 - c, 1 + c)). A shared
    block's gradient is the sum over its R uses: JAX's reverse pass over the
    plain loop gives exactly that; `per_use` returns instead the list of R
    gradients over the blocks, one a loop, each loop reading a copy of its own."""
    total = np.asarray(attention_mask).shape[1]
    response_mask = jnp.asarray(attention_mask)[:, prompt:].astype(jnp.float32)

    def loss_of(trunk, loop_blocks=None):
        logits = forward(trunk, model_arch, input_ids, attention_mask, total, loop_blocks=loop_blocks)
        logp = jax.nn.log_softmax(logits[:, prompt - 1:-1])
        new = jnp.take_along_axis(logp, input_ids[:, prompt:, None], axis=-1)[..., 0]
        ratio = jnp.exp(new - old_logprobs)
        loss = jnp.maximum(-advantages * ratio, -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange))
        return jnp.sum(loss * response_mask) / jnp.sum(response_mask)

    with jax.default_matmul_precision("highest"):
        if per_use:
            blocks = {k: v for k, v in trunk.items() if k.startswith("h_")}
            return jax.value_and_grad(lambda copies: loss_of(trunk, copies))([blocks] * int(model_arch["n_loops"]))
        return jax.value_and_grad(loss_of)(trunk)

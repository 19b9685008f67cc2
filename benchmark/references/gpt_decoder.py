"""Plain reference of the dense GPT decoder family (GPT-2, GPT-J, GPT-Neo,
GPT-NeoX): the forward pass in straightforward float32 `jax.numpy`, matmuls
at `jax.default_matmul_precision("highest")`, no kernels, no cache, no
batching tricks. Written from the published model descriptions (HF
`modeling_gptj.py`, `modeling_gpt_neo.py`), not from `trlx_tpu/models/lm.py`;
it reads the program's parameter tree only for the weights.

    GPT-J    rotary on the first `rotary_dim` dims of every head (interleaved
             pairs), one LayerNorm feeding attention and MLP in parallel,
             no projection biases, untied head with bias, 1/sqrt(head) scale.
    GPT-Neo  learned positions, sequential residual with two LayerNorms,
             no q/k/v bias, out-projection bias, UNSCALED attention, layers
             alternating global / local (a query sees itself and the
             window_size - 1 keys before it), head tied to the embedding.

Departures from the published code: positions of a left-padded row count
from its first real token (the program's convention for rollouts; HF leaves
that to the caller's position_ids). One block's weights are cast up to
float32 at a time, inside the jitted block, so the reference fits beside a
trainer that fills the chip.
"""

import functools
import math

import jax
import jax.numpy as jnp

NEG = -1e9


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _to_bf16(z):
    return z.astype(jnp.bfloat16).astype(jnp.float32)


def _keep_bf16(z):
    """The same rounding as an operation of its own, which no compiler pass
    may drop as excess precision between two fused element-wise operations."""
    return jax.lax.reduce_precision(z, exponent_bits=8, mantissa_bits=7)


def _to_int8(z):
    """Per-tensor symmetric int8, as an int8 matmul is fed: the largest
    magnitude lands on 127."""
    scale = jnp.maximum(jnp.max(jnp.abs(z)), 1e-30) / 127.0
    return jnp.clip(jnp.round(z / scale), -127.0, 127.0) * scale


def _identity(z):
    return z


# precision -> (r, ra, s): `r` rounds what a weight matmul reads, `ra` what
# attention's two matmuls read, `s` what an operation hands to the next one
# (the activations and the residual stream). Accumulation inside an operation
# (a matmul's sums, a LayerNorm's moments, the softmax over the scores) is
# float32 in every one of them.
#   highest          the reference.
#   bfloat16         one bf16 pass of the matrix unit and nothing else: the
#                    yardstick of the 8- and 24-layer cells (PR 22).
#   bfloat16_stream  what a configuration with `dtype: bfloat16` states:
#                    activations and residual stream are bf16 as well. The
#                    yardstick where depth makes the stream's rounding the
#                    larger part (28 layers, PR 25).
#   int8             the control of the logits check (benchmark/control.py),
#                    never a yardstick: `bfloat16_stream` with every matmul
#                    fed int8, scaled per tensor; `int8_dense` feeds only the
#                    weight matmuls so and leaves attention's two in bf16.
PRECISIONS = {
    "highest": (_identity, _identity, _identity),
    "bfloat16": (_to_bf16, _to_bf16, _identity),
    "bfloat16_stream": (_keep_bf16, _keep_bf16, _keep_bf16),
    "int8": (_to_int8, _to_int8, _keep_bf16),
    "int8_dense": (_to_int8, _keep_bf16, _keep_bf16),
}


def _rounding(precision):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    return PRECISIONS[precision]


def _dense(x, p, r, s):
    y = r(x) @ r(p["kernel"])
    return s(y + p["bias"] if "bias" in p else y)


def _act(x, name):
    if name == "gelu_new":
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))
    if name == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    if name == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(f"activation {name!r}")


def _rotary(x, positions, rotary_dim, neox):
    """x [b, t, h, hd]; rotate the first rotary_dim dims of every head."""
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    ang = positions[:, :, None].astype(jnp.float32) * inv_freq  # [b, t, rd/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    if neox:  # halves
        a, b = rot[..., : rotary_dim // 2], rot[..., rotary_dim // 2 :]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    else:  # GPT-J: interleaved pairs (0,1), (2,3), ...
        a, b = rot[..., 0::2], rot[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(rot.shape)
    return jnp.concatenate([out, rest], axis=-1)


def _mask_bias(attention_mask, window):
    """[b, 1, t, t]: key j visible to query i iff valid, j <= i, and inside the window."""
    t = attention_mask.shape[1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = j <= i
    if window > 0:
        keep = keep & (j > i - window)
    keep = keep[None, None] & attention_mask[:, None, None, :].astype(bool)
    return jnp.where(keep, 0.0, NEG)


@functools.partial(jax.jit, static_argnames=("arch", "window", "precision"))
def _block(x, p, attention_mask, positions, *, arch, window, precision):
    a = dict(arch)
    p = _f32(p)
    b, t, d = x.shape
    h = a["n_head"]
    hd = d // h
    r, ra, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        ln1 = s(_layer_norm(x, p["ln_1"], a["ln_eps"]))
        at = p["attn"]
        if "c_qkv" in at:
            q, k, v = jnp.split(_dense(ln1, at["c_qkv"], r, s), 3, axis=-1)
        else:
            q, k, v = (_dense(ln1, at[n], r, s) for n in ("q_proj", "k_proj", "v_proj"))
        q, k, v = (z.reshape(b, t, h, hd) for z in (q, k, v))
        if a["pos_type"] == "rotary":
            rd = a["rotary_dim"] or hd
            q, k = (s(_rotary(z, positions, rd, a["neox_rotary"])) for z in (q, k))
        scores = jnp.einsum("bqhd,bkhd->bhqk", ra(q), ra(k))
        if a["scale_attn"]:
            scores = scores / math.sqrt(hd)
        probs = s(jax.nn.softmax(scores + _mask_bias(attention_mask, window), axis=-1))
        mixed = s(jnp.einsum("bhqk,bkhd->bqhd", ra(probs), ra(v))).reshape(b, t, d)
        attn = _dense(mixed, at["c_proj"], r, s)

        def mlp(z):
            return _dense(s(_act(_dense(z, p["mlp"]["c_fc"], r, s), a["activation"])), p["mlp"]["c_proj"], r, s)

        if a["parallel_residual"]:
            mlp_in = s(_layer_norm(x, p["ln_2"], a["ln_eps"])) if a["use_parallel_ln"] else ln1
            return s(x + attn + mlp(mlp_in))
        x = s(x + attn)
        return s(x + mlp(s(_layer_norm(x, p["ln_2"], a["ln_eps"]))))


@functools.partial(jax.jit, static_argnames=("learned", "precision"))
def _embed(t, input_ids, positions, *, learned, precision):
    x = t["wte"]["embedding"][input_ids].astype(jnp.float32)
    if learned:
        x = x + t["wpe"]["embedding"][positions].astype(jnp.float32)
    return _rounding(precision)[2](x)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "precision"))
def _head(x, ln_f, head, *, eps, tied, precision):
    r, _, s = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        x = s(_layer_norm(x, _f32(ln_f), eps))
        if tied:
            return s(r(x) @ r(head["embedding"].astype(jnp.float32)).T)
        return _dense(x, _f32(head), r, s)


def arch_key(model_arch):
    """The hashable view of a configuration's `model_arch` the blocks need."""
    extra = model_arch.get("extra", {})
    return tuple(sorted({
        "n_head": model_arch["n_head"],
        "pos_type": model_arch.get("pos_type", "learned"),
        "rotary_dim": model_arch.get("rotary_dim", 0),
        "neox_rotary": bool(extra.get("neox_rotary", False)),
        "scale_attn": bool(model_arch.get("scale_attn", True)),
        "parallel_residual": bool(model_arch.get("parallel_residual", False)),
        "use_parallel_ln": bool(model_arch.get("use_parallel_ln", False)),
        "activation": model_arch.get("activation", "gelu_new"),
        "ln_eps": float(model_arch.get("ln_eps", 1e-5)),
    }.items()))


def forward(trunk, model_arch, input_ids, attention_mask, last, precision="highest"):
    """float32 logits [b, last, vocab] of the final `last` positions.

    `trunk` is the program's ``params["transformer"]`` subtree (any dtype).
    `precision` names a row of PRECISIONS: "highest" is the reference; the
    others rerun it coarser, which measures how far that arithmetic alone
    moves this architecture's logits."""
    key = arch_key(model_arch)
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=-1) - 1, 0)
    kinds = model_arch.get("attention_layers") or ["global"] * model_arch["n_layer"]
    x = _embed(trunk, input_ids, positions, learned=model_arch.get("pos_type", "learned") == "learned",
               precision=precision)
    for i, kind in enumerate(kinds):
        window = int(model_arch.get("window_size", 0)) if kind == "local" else 0
        x = _block(x, trunk[f"h_{i}"], attention_mask, positions, arch=key, window=window, precision=precision)
    tied = bool(model_arch.get("tie_word_embeddings", True))
    head = trunk["wte"] if tied else trunk["lm_head"]
    return _head(x[:, -last:], trunk["ln_f"], head, eps=float(model_arch.get("ln_eps", 1e-5)), tied=tied,
                 precision=precision)

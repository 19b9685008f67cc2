"""Operations and bytes of the decoder whose attention runs behind two causal
convolutions (CCA) and whose every layer routes one expert a token by an MLP
router (configurations whose file names `"flops": "cca_moe"`), with flops.py's
signatures and flops.py's meaning of "needs": no recomputation, no weight
gradients of frozen blocks, causal pairs only, activation gradients across
every layer. `arch` is the configuration file's `model_arch`.

What is counted is what is ACTIVE here, as in counts/mla_moe.py (whose
grouped-product count this module takes as it is): grouped attention at its
true head counts (4 query heads a K/V head), the second convolution as the two
products a token a head it is (the first, a multiply-add a tap a channel, is
under a thousandth and left out, like the norms), the router's four products
and the HELD experts' expected share of the one slot a token (8 of 16 held).
There is no dense layer and no shared expert; the head is the table.
"""

from benchmark.counts.mla_moe import expert_ffn_call, expert_params, held_share
from benchmark.flops import BF16, kept_pairs, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call",
           "least_seconds", "expert_ffn_call", "expert_params", "held_share", "decode_step_bytes", "cca_mix_call",
           "parameters"]

# The flash reader hands `flash_call` the head count it parses from a call's
# RESULT and no `arch`: the query heads for the forward and dq, the K/V heads
# for the grouped dk/dv kernel. The group of the one family that names this
# module is kept here (benchmark/tests checks it against its file).
GROUP = 4


def heads(arch):
    """(query heads, K/V heads, channels a head)."""
    return arch["n_head"], arch["n_kv_head"], arch["head_width"]


def conv_channels(arch):
    """Channels both convolutions run over: every query head and every key head."""
    h, g, hd = heads(arch)
    return (h + g) * hd


def projection_params(arch):
    """q, k, the two halves of v, out."""
    h, g, hd = heads(arch)
    return arch["d_model"] * (h + 2 * g) * hd + h * hd * arch["d_model"]


def conv_params(arch):
    """(depthwise taps and bias, a matrix a tap a head and its bias)."""
    h, g, hd = heads(arch)
    return (arch["cca_time0"] + 1) * conv_channels(arch), arch["cca_time1"] * (h + g) * hd * hd + conv_channels(arch)


def attention_params(arch):
    """Every parameter of the CCA sub-block's mixer: projections, both convolutions, a temperature a key head."""
    return projection_params(arch) + sum(conv_params(arch)) + arch["n_kv_head"]


def attention_matmul_params(arch):
    """Weights a token multiplies by in products: the projections and the second convolution's taps."""
    h, g, hd = heads(arch)
    return projection_params(arch) + arch["cca_time1"] * (h + g) * hd * hd


def router_matmul_params(arch):
    d, r = arch["d_model"], arch["router_hidden"]
    return d * r + 2 * r * r + r * arch["n_experts"]


def router_params(arch):
    """The MLP router as the tree holds it: three biases, the carry's vector,
    the norm's scale, and the balancing-bias buffer beside the four kernels."""
    return router_matmul_params(arch) + 5 * arch["router_hidden"] + arch["n_experts"]


def held(arch):
    return arch["experts_held"][1] if arch.get("experts_held") else arch["n_experts"]


def ffn_active_params(arch):
    """Weights one token multiplies by in a layer's feed-forward, in
    expectation over an even router: the router and its share of the held experts."""
    return router_matmul_params(arch) + arch["experts_per_token"] * held_share(arch) * expert_params(arch)


def parameters(arch):
    """{kind: parameters} of the tree the program builds (the experts HELD) and
    of the layer the two chips share (`layer_whole`: all n_experts)."""
    d = arch["d_model"]
    layer = attention_params(arch) + router_params(arch) + held(arch) * expert_params(arch) + 2 * d + 8 * d
    table = arch["vocab_size"] * d
    return {"attention": attention_params(arch), "router": router_params(arch), "expert": expert_params(arch),
            "layer": layer, "layer_whole": layer + (arch["n_experts"] - held(arch)) * expert_params(arch),
            "table": table, "trunk": arch["n_layer"] * layer + table + d}


def layer_windows(arch):
    return [0] * arch["n_layer"]


def attention_flops(arch, b, t):
    """Forward: scores and the value contraction, every query head, causal pairs only."""
    h, _, hd = heads(arch)
    return 2 * 2 * b * h * hd * kept_pairs(t)


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, n_layer = batch * seq, arch["n_layer"]
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    dense = 2 * n * (attention_matmul_params(arch) + ffn_active_params(arch))
    attn = attention_flops(arch, batch, seq)
    # forward, activation gradients (the scores' twice over), weight gradients of the trainable blocks only
    return n_layer * (dense + attn + dense + 2 * attn) + k * dense


def ppo_train_step_flops(arch, batch, prompt, response, unfrozen):
    d, v = arch["d_model"], arch["vocab_size"]
    head = 3 * 2 * batch * response * d * v  # response positions only
    value = 3 * mlp_head_flops(batch * response, d, 1)
    return trunk_train_flops(arch, batch, prompt + response, unfrozen) + head + value


def ilql_train_step_flops(arch, batch, seq, unfrozen, two_qs=True):
    raise NotImplementedError("no ILQL cell runs this configuration; count it with the cell that does")


def flash_call(kind, b, t, n_head, head_dim, window=0):
    """One flash kernel call of this family. `n_head` is the leading
    dimension of the call's result: query heads (forward, dq), or K/V heads
    (dk/dv), whose two contractions run over every query head of the group
    all the same. Tensors moved: q, o, do, dq at the query heads; k, v, dk, dv
    at the K/V heads, once a group."""
    q_heads = n_head * GROUP if kind == "bwd_dkv" else n_head
    ops = 2 * 2 * b * q_heads * head_dim * kept_pairs(t, window)
    at_q, at_kv = {"fwd": (2, 2), "bwd_dq": (3, 2), "bwd_dkv": (2, 4)}[kind]  # q o | q do dq | q do; k v (dk dv)
    moved = b * t * head_dim * (at_q * q_heads + at_kv * q_heads // GROUP) * BF16
    return ops, moved


def cca_mix_call(arch, rows, positions):
    """(operations, bytes) of one layer's pass, forward, over `rows` x
    `positions` tokens from the normed input to q, k and v as the attention
    core takes them (the program's scope `cca_mix`): the four projections
    into the latent (q, k, both halves of v), the second convolution's two
    products a head, and a multiply-add a tap a channel for the first; the
    q-k mean, the L2 norm and rotary are some ten operations a channel more and
    are left out. Moved once: the input, the three results, the weights; what
    lies between (u, a, c) could stay on the chip."""
    n, d = rows * positions, arch["d_model"]
    h, g, hd = heads(arch)
    ops = 2 * n * (d * (h + 2 * g) * hd + arch["cca_time1"] * (h + g) * hd * hd + arch["cca_time0"] * conv_channels(arch))
    moved = (n * (d + (h + 2 * g) * hd) + d * (h + 2 * g) * hd + sum(conv_params(arch))) * BF16
    return ops, moved


def state_bytes(arch, rows):
    """What the layers keep beside their slots, all layers: a window of
    cca_time0 + cca_time1 - 2 positions of the convolutions' input and one
    shifted value of half the value heads, a row."""
    _, g, hd = heads(arch)
    return arch["n_layer"] * rows * ((arch["cca_time0"] + arch["cca_time1"] - 2) * conv_channels(arch) + g // 2 * hd) * BF16


def decode_step_bytes(arch, rows, keys):
    """(bytes one decode step over `rows` rows must move, the keys' part):
    every weight once in bf16 (the table is the head and is read whole; a
    decode step is a small call of the expert layer, every held expert over
    every token, so every held expert's weights are read), `keys` cache slots
    of K and V a row in every layer (the mean the program's
    `rollout/kv_read_share` of the sequence length gives: the ranged read at
    the step's position), and the window and shifted value read and written.
    The value head and the logits themselves are left out (under 1%)."""
    _, g, hd = heads(arch)
    cache = int(arch["n_layer"] * rows * keys * 2 * g * hd * BF16)
    return parameters(arch)["trunk"] * BF16 + cache + 2 * state_bytes(arch, rows), cache

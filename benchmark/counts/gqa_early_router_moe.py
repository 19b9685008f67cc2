"""Operations and bytes of the grouped-key, windowed decoder whose every layer
is an expert layer routed ahead of attention (configurations whose file names
`"flops": "gqa_early_router_moe"`), with flops.py's signatures and flops.py's
meaning of "needs": no recomputation, no weight gradients of frozen blocks,
only the pairs the causal mask and a layer's window keep, activation
gradients across every layer. `arch` is the configuration file's `model_arch`.

What is counted is what is ACTIVE here, as in counts/mla_moe.py (whose
grouped-product count this module takes as it is): grouped attention at its
true head counts (7 query heads a K/V head), and in every layer the router's
product and the HELD experts' expected share of the token-slots (6 a token x
16 of 64 held). There is no dense layer and no shared expert. K and V are
projected, moved and cached once a GROUP of query heads, and counted so.
"""

from benchmark.counts.mla_moe import expert_ffn_call, expert_params, held_share
from benchmark.flops import BF16, kept_pairs, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call",
           "least_seconds", "expert_ffn_call", "expert_params", "held_share", "decode_step_bytes", "parameters"]

# The flash reader hands `flash_call` the head count it parses from a call's
# RESULT and no `arch`: the query heads for the forward and dq, the K/V heads
# for the grouped dk/dv kernel, which sums a group's query heads into one
# result. The group of the one family that names this module is kept here
# (benchmark/tests checks it against its file): query heads a K/V head.
GROUP = 7


def head_dim(arch):
    return arch.get("head_width") or arch["d_model"] // arch["n_head"]


def kv_heads(arch):
    return arch.get("n_kv_head") or arch["n_head"]


def attention_params(arch):
    """Weights grouped attention multiplies by: q, k, v, out."""
    d, hd, h = arch["d_model"], head_dim(arch), arch["n_head"]
    return d * h * hd + 2 * d * kv_heads(arch) * hd + h * hd * d


def router_params(arch):
    return arch["d_model"] * arch["n_experts"]


def held(arch):
    return arch["experts_held"][1] if arch.get("experts_held") else arch["n_experts"]


def ffn_active_params(arch):
    """Weights one token multiplies by in a layer's feed-forward, in
    expectation over an even router: the router and its share of the held experts."""
    return router_params(arch) + arch["experts_per_token"] * held_share(arch) * expert_params(arch)


def parameters(arch):
    """{kind: parameters} of the tree the program builds (the experts HELD) and
    of the layer the four chips share (`layer_whole`: all n_experts)."""
    d = arch["d_model"]
    layer = attention_params(arch) + router_params(arch) + held(arch) * expert_params(arch) + 2 * d
    table = arch["vocab_size"] * d
    return {"attention": attention_params(arch), "router": router_params(arch), "expert": expert_params(arch),
            "layer": layer, "layer_whole": layer + (arch["n_experts"] - held(arch)) * expert_params(arch),
            "table": table, "head": table, "trunk": arch["n_layer"] * layer + 2 * table + d}


def layer_windows(arch):
    kinds = arch.get("attention_layers") or ["global"] * arch["n_layer"]
    return [arch.get("window_size", 0) if k == "local" else 0 for k in kinds]


def attention_flops(arch, b, t, window=0):
    """Forward: scores and the value contraction, every query head, kept pairs only."""
    return 2 * 2 * b * arch["n_head"] * head_dim(arch) * kept_pairs(t, window)


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, n_layer = batch * seq, arch["n_layer"]
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    total = 0
    for i, window in enumerate(layer_windows(arch)):
        dense = 2 * n * (attention_params(arch) + ffn_active_params(arch))
        attn = attention_flops(arch, batch, seq, window)
        total += dense + attn  # forward
        total += dense + 2 * attn  # activation gradients
        if i >= n_layer - k:
            total += dense  # weight gradients, trainable blocks only
    return total


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


def decode_step_bytes(arch, rows, keys):
    """(bytes one decode step over `rows` rows must move, the keys' part):
    every weight once in bf16 but the embedding (untied: the lookup takes
    `rows` of its rows, the head reads all of its own; a decode step is a
    small call of the expert layer, every held expert over every token, so
    every held expert's weights are read), and `keys` cache slots of K and V
    a row in every layer. `keys` is the MEAN over the layers, as the program's
    `rollout/kv_read_share` of the sequence length gives it: a ring layer reads
    its window_size slots every step, a full-span layer the slots the ranged
    read takes at the step's position. The value head and the logits
    themselves are left out (under 1%)."""
    count = parameters(arch)
    key = 2 * kv_heads(arch) * head_dim(arch) * BF16  # one slot of K and V
    cache = int(arch["n_layer"] * rows * keys * key)
    weights = (count["trunk"] - count["table"]) * BF16 + rows * arch["d_model"] * BF16
    return weights + cache, cache

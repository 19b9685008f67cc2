"""Operations and bytes of the indexed latent-attention, sparse-expert decoder
(configurations whose file names `"flops": "dsa_mla_moe"`), with flops.py's
signatures and flops.py's meaning of "needs": no recomputation, no weight
gradients of frozen blocks, activation gradients across every layer, and of
attention only what the mathematics reads. `arch` is the configuration
file's `model_arch`.

An indexed layer is counted BY WHAT ITS QUERIES CHOOSE, whatever computes it:
`dsa_index_call` the products of every index head with the index key of every
causal pair, once a pass (the choice has no gradient, and neither has the
indexer: its projections are counted forward only); `dsa_attn_call` the two
contractions of latent attention (scores over nope + rope, values over v, at
the heads HELD here) over each query's CHOSEN pairs: min(t + 1, index_topk)
for the query at position t. A pass that computes every causal pair and masks
reads low against this need; a kernel that visits only chosen keys reads what
it gained. The ReLU, the head weights' sum, the k-th largest score and the
softmax are element-wise or comparisons and not counted. Rows are counted at
the full sequence length (what the hardware pays for under left padding is
more, what a shorter row needs is less).

An expert layer as counts/mla_moe.py counts it: the router, the shared expert
and the HELD experts' expected share of the token-slots.
"""

from benchmark.counts.mla_moe import expert_ffn_call, expert_params, ffn_active_params, held_share
from benchmark.flops import BF16, kept_pairs, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "logprob_head_call", "least_seconds",
           "expert_ffn_call", "dsa_index_call", "dsa_select_call", "dsa_attn_call", "decode_step_bytes", "parameters", "chosen_pairs",
           "held_share"]

F32 = 4  # bytes


def ffn_kinds(arch):
    return list(arch.get("ffn_layers") or ["dense"] * arch["n_layer"])


def attention_params(arch):
    """Weights latent attention multiplies by: q_a, q_b, kv_a, kv_b, out (the heads held here)."""
    d, h = arch["d_model"], arch["n_head"]
    nope, rope, v, rank, q_rank = (arch[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                                                       "q_lora_rank"))
    return d * q_rank + q_rank * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + v) + h * v * d


def indexer_params(arch):
    """Weights the indexer multiplies by: W^I_q from the query latent, W^I_k and W^I_w from the block's input."""
    heads, width = arch["index_n_heads"], arch["index_head_dim"]
    return arch["q_lora_rank"] * heads * width + arch["d_model"] * (width + heads)


def parameters(arch):
    """{kind: parameters} of the tree the program builds: one latent attention
    (its indexer and norms in it), the indexer alone, a dense and an expert
    feed-forward (the experts HELD), a block's two norms, the table, the head,
    the whole trunk."""
    model = arch["d_model"]
    indexer = indexer_params(arch) + 2 * arch["index_head_dim"]  # + the index key's LayerNorm
    attention = attention_params(arch) + arch["q_lora_rank"] + arch["kv_lora_rank"] + indexer  # + the two latents' norms
    dense = 3 * model * arch["d_ff"]
    held = arch["experts_held"][1] if arch.get("experts_held") else arch["n_experts"]
    experts = (held + arch.get("n_shared_experts", 0)) * expert_params(arch) + model * arch["n_experts"] + arch["n_experts"]
    table = arch["vocab_size"] * model
    trunk = 2 * table + model  # embedding, untied head, the final norm
    for ffn in ffn_kinds(arch):
        trunk += attention + (experts if ffn == "experts" else dense) + 2 * model
    return {"attention": attention, "indexer": indexer, "dense": dense, "experts": experts, "norms": 2 * model,
            "table": table, "head": table, "trunk": trunk}


def layer_windows(arch):
    """One entry an attention layer (what the flash reader averages over): no band anywhere."""
    return [0] * arch["n_layer"]


def chosen_pairs(arch, t):
    """Pairs the queries of one row of `t` tokens choose: min(i + 1, index_topk) for the query at position i."""
    k = min(t, arch["index_topk"])
    return k * (k + 1) // 2 + (t - k) * arch["index_topk"]


def dsa_index_call(arch, b, t):
    """(operations, bytes) of ONE layer's index scores over [b, t], forward: every index head's product with the
    index key of every causal pair. Bytes: q^I [b, t, H_I, D_I] and k^I [b, t, D_I] in bf16, w [b, t, H_I] in float32."""
    heads, width = arch["index_n_heads"], arch["index_head_dim"]
    return 2 * b * heads * width * kept_pairs(t), b * t * ((heads + 1) * width * BF16 + heads * F32)


def dsa_select_call(arch, b, t):
    """(operations, bytes) of ONE layer's choice over [b, t], forward: no product at all (comparisons and counts);
    every causal pair's float32 index score read once and its place in the choice written once, a byte."""
    return 0, b * kept_pairs(t) * (F32 + 1)


def dsa_attn_call(arch, b, t):
    """(operations, bytes) of ONE layer's attention over [b, t], forward: the scores (nope + rope wide) and the value
    contraction (v wide) of the heads held here over each query's CHOSEN pairs. Bytes: q and k [b, t, h, nope + rope],
    v and o [b, t, h, v] in bf16, each moved once."""
    h, qk, v = arch["n_head"], arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], arch["v_head_dim"]
    return 2 * b * h * (qk + v) * chosen_pairs(arch, t), b * t * h * 2 * (qk + v) * BF16


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, kinds = batch * seq, ffn_kinds(arch)
    n_layer = len(kinds)
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    index = 2 * n * indexer_params(arch) + dsa_index_call(arch, batch, seq)[0]  # forward only: no gradient reaches it
    attn = dsa_attn_call(arch, batch, seq)[0]
    total = 0
    for i, ffn in enumerate(kinds):
        dense = 2 * n * (attention_params(arch) + ffn_active_params(arch, ffn))
        total += dense + attn + index  # forward
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


def decode_step_bytes(arch, rows, keys):
    """(bytes one decode step over `rows` rows must move, the state's part: none):
    every weight once in bf16 but the embedding (untied: the lookup takes
    `rows` of its rows, the head reads all of its own), and in every layer a
    row's index keys (one of index_head_dim a slot of `max_position`; those
    past the query are masked) and `keys` chosen entries of the latent and the
    shared key (index_topk where the row has filled more: the program's
    `rollout/kv_read_share` of the sequence length). The value head and the
    logits themselves are left out (under 1%)."""
    count = parameters(arch)
    latent = (arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) * BF16
    index_keys = arch["max_position"] * arch["index_head_dim"] * BF16
    weights = (count["trunk"] - count["table"]) * BF16 + rows * arch["d_model"] * BF16
    return weights + int(arch["n_layer"] * rows * (keys * latent + index_keys)), 0

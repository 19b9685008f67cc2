"""Operations and bytes of the lightning / block-selected sparse attention
decoder (configurations whose file names `"flops": "sala"`), with flops.py's
signatures and flops.py's meaning of "needs": no recomputation, no weight
gradients of frozen blocks, activation gradients across every layer, and of
attention only what the mathematics reads. `arch` is the configuration
file's `model_arch`.

A lightning layer's pass over many tokens is counted IN ITS CHUNKED FORM at
C = `LIGHTNING_CHUNK` (the program's `models/lightning.py CHUNK`, 128),
products only, per chunk and head (D = `lightning_head_dim`):

    (Q K^T) * Lambda      the kept half of [C, C], D wide
    (...) V               the kept half of [C, C], D wide
    Diag(lambda^i) Q S    C x D x D
    K^T V into the state  C x D x D, and one multiply of [D, D] by the chunk's decay

The decay tables and their multiplies are element-wise and not counted, as a
softmax is not counted in attention. The token-by-token form a decode step
runs needs 2 D^2 multiply-adds a head a token; `decode_step_bytes` says why
nobody counts them: the step is bound by the bytes of the state it reads and
writes.

A sparse layer is counted BY WHAT ITS QUERIES CHOOSE, whatever computes it:
`sparse_select_call` the scores of every query over the compressed keys that
exist for it (the pooling, the sort and the compression's sums are not
products), `sparse_attn_call` the two contractions over the pairs of each
query with the filled slots of its CHOSEN blocks: the init blocks, the
window's, the top-k of the others (`chosen_blocks`: the rule of ISSUE 51,
step 5, restated here and held to the program's by
benchmark/tests/test_sala_counts.py). A pass that computes every causal pair
and masks reads low against this need; a kernel that visits only chosen
blocks reads what it gained. Rows are counted at the full sequence length
(what the hardware pays for under left padding is more, what a shorter row
needs is less).
"""

from benchmark.flops import BF16, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "logprob_head_call", "least_seconds",
           "lightning_scan_call", "sparse_select_call", "sparse_attn_call", "decode_step_bytes", "state_bytes",
           "parameters", "chosen_blocks", "chosen_pairs"]

F32 = 4  # bytes
LIGHTNING_CHUNK = 128  # trlx_tpu/models/lightning.py CHUNK (benchmark/tests/test_sala_counts.py holds the two together)


def mixers(arch):
    return list(arch.get("mixer_layers") or ["attention"] * arch["n_layer"])


def lightning_sizes(arch):
    """(H, D, inner = H D)."""
    h, d = arch["lightning_heads"], arch["lightning_head_dim"]
    return h, d, h * d


def sparse_sizes(arch):
    """(H, G, D): query heads, K/V heads, a head's width."""
    return arch["n_head"], arch.get("n_kv_head") or arch["n_head"], arch.get("head_width") or arch["d_model"] // arch["n_head"]


def lightning_matmul_params(arch):
    """Weights a lightning layer's projections multiply by: q, k, v, out, and the gate where it has one."""
    _, _, inner = lightning_sizes(arch)
    return (4 + bool(arch.get("lightning_output_gate"))) * arch["d_model"] * inner


def sparse_matmul_params(arch):
    """Weights a sparse layer's projections multiply by: q, k, v, out, and the gate where it has one."""
    h, g, d = sparse_sizes(arch)
    return arch["d_model"] * d * ((2 + bool(arch.get("attn_output_gate"))) * h + 2 * g)


def parameters(arch):
    """{kind: parameters} of the tree the program builds: one lightning mixer,
    one sparse attention, the feed-forward, a block's two norms, the table,
    the head, the whole trunk."""
    _, ld, inner = lightning_sizes(arch)
    _, _, d = sparse_sizes(arch)
    model = arch["d_model"]
    qk_norms = 2 if arch.get("qk_norm") else 0
    lightning = lightning_matmul_params(arch) + qk_norms * ld + inner  # + the output norm
    attention = sparse_matmul_params(arch) + qk_norms * d
    dense = 3 * model * arch["d_ff"]
    table = arch["vocab_size"] * model
    trunk = 2 * table + model  # embedding, untied head, the final norm
    for kind in mixers(arch):
        trunk += (lightning if kind == "lightning" else attention) + dense + 2 * model
    return {"lightning": lightning, "attention": attention, "dense": dense, "norms": 2 * model, "table": table,
            "head": table, "trunk": trunk}


def layer_windows(arch):
    """One entry an ATTENTION layer (what the flash reader averages over): no band anywhere."""
    return [0] * mixers(arch).count("attention")


def lightning_scan_call(arch, b, t):
    """(operations, bytes) of ONE lightning layer's pass over [b, t], forward, in the
    chunked form (module docstring). Bytes: q, k, v [b, t, H, D] in bf16 and o in
    float32, each moved once; nothing of the [C, C] tables or the chunk states,
    which a fused pass never writes."""
    h, d, _ = lightning_sizes(arch)
    c = min(LIGHTNING_CHUNK, t)
    chunks = -(-t // c)
    half = c * (c + 1) // 2
    macs = 2 * half * d + 2 * c * d * d + d * d
    return 2 * b * chunks * h * macs, b * t * h * d * (3 * BF16 + F32)


def chosen_blocks(arch, t):
    """Blocks the query at position `t` of its row chooses (step 5): the init
    blocks, the blocks that hold tokens t - window + 1 .. t, the top-k of the
    other blocks that start at or before t."""
    block, window, init, topk = (arch[k] for k in ("sparse_block", "sparse_window", "sparse_init_blocks", "sparse_topk"))
    started = t // block + 1
    first_in_window = max(t - window + 1, 0) // block
    forced = set(range(min(init, started))) | set(range(first_in_window, started))
    return len(forced) + min(topk, started - len(forced))


def chosen_pairs(arch, t):
    """Filled slots the query at position `t` reads: its chosen blocks whole, its own as far as `t`."""
    block = arch["sparse_block"]
    return chosen_blocks(arch, t) * block - (block - 1 - t % block)


def existing_compressed(arch, t):
    """Compressed keys that exist for the query at position `t` (step 2)."""
    return (t - arch["sparse_kernel"] + 1) // arch["sparse_stride"] + 1 if t >= arch["sparse_kernel"] - 1 else 0


def sparse_select_call(arch, b, t):
    """(operations, bytes) of ONE sparse layer's steps 2-5 over [b, t], forward: every
    query head's scores over the compressed keys that exist for it. Bytes: q
    [b, t, H, D] and the keys the compression reads [b, t, G, D] in bf16."""
    h, g, d = sparse_sizes(arch)
    scored = sum(existing_compressed(arch, i) for i in range(t))
    return 2 * b * h * d * scored, b * t * (h + g) * d * BF16


def sparse_attn_call(arch, b, t):
    """(operations, bytes) of ONE sparse layer's step 6 over [b, t], forward: the scores
    and the value contraction over each query's CHOSEN pairs. Bytes: q and o
    [b, t, H, D], k and v [b, t, G, D] in bf16, each moved once."""
    h, g, d = sparse_sizes(arch)
    pairs = sum(chosen_pairs(arch, i) for i in range(t))
    return 2 * 2 * b * h * d * pairs, b * t * 2 * (h + g) * d * BF16


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, kinds = batch * seq, mixers(arch)
    n_layer = len(kinds)
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    ffn = 3 * arch["d_model"] * arch["d_ff"]
    total = 0
    for i, kind in enumerate(kinds):
        if kind == "lightning":
            dense, mix = 2 * n * (lightning_matmul_params(arch) + ffn), lightning_scan_call(arch, batch, seq)[0]
        else:
            dense = 2 * n * (sparse_matmul_params(arch) + ffn)
            mix = sparse_select_call(arch, batch, seq)[0] + sparse_attn_call(arch, batch, seq)[0]
        total += dense + mix  # forward
        total += dense + 2 * mix  # activation gradients (the choice has none: counted as the forward's, an upper bound of 1%)
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


def state_bytes(arch, rows):
    """The lightning layers' cache at `rows` rows: a float32 state [H, D, D] a layer a row, nothing else."""
    h, d, _ = lightning_sizes(arch)
    return mixers(arch).count("lightning") * rows * h * d * d * F32


def decode_step_bytes(arch, rows, keys):
    """(bytes one decode step over `rows` rows must move, the state's part):
    every weight once in bf16 but the embedding (untied: the lookup takes
    `rows` of its rows, the head reads all of its own), the lightning layers'
    state read AND written, and in every sparse layer `keys` cache slots of K
    and of V a row (the slots of the blocks a step gathers: the program's
    `rollout/kv_read_share` of the sequence length) beside the compressed keys
    of a whole row (one every sparse_stride slots of `max_position`; those past
    the query are masked, 0.05% of the step either way). The value head and the
    logits themselves are left out (under 1%)."""
    count = parameters(arch)
    _, g, d = sparse_sizes(arch)
    state = 2 * state_bytes(arch, rows)
    weights = (count["trunk"] - count["table"]) * BF16 + rows * arch["d_model"] * BF16
    compressed = max(0, (arch["max_position"] - arch["sparse_kernel"]) // arch["sparse_stride"] + 1)
    slots = mixers(arch).count("attention") * rows * (2 * keys + compressed) * g * d * BF16
    return weights + state + int(slots), state

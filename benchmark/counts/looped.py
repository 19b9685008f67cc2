"""Operations and bytes of the looped decoder (configurations whose file
names `"flops": "looped"`): N shared-weight blocks run R = `n_loops` times a
token. flops.py's signatures and flops.py's meaning of "needs": no
recomputation, no weight gradients of frozen blocks, only the pairs the causal
mask keeps, activation gradients across every block APPLICATION. `arch` is the
configuration file's `model_arch`.

What the loops change in the count: every block is applied R times forward
and R times backward (R N applications a token, not N); a trained block's
weight gradient is the SUM over its R uses, each use one product of the
block's size, so the weight-gradient term is counted for k R applications;
the cache holds keys and values a (loop, layer) pair; and a decode step reads
the stack's weights once a LOOP (the stack does not stay on chip between
loops), the embedding's rows and the head once.
"""

from benchmark.flops import BF16, attention_flops, flash_call, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call",
           "least_seconds", "parameters", "cache_bytes_per_token", "decode_step_bytes", "weight_read_share"]

F32 = 4  # bytes


def loops(arch):
    return int(arch.get("n_loops", 1))


def head_dim(arch):
    return arch["d_model"] // arch["n_head"]


def block_matmul_params(arch):
    """Weights one block multiplies by: q, k, v, out (4 d^2) + gate, up, down (3 d d_ff)."""
    d = arch["d_model"]
    return 4 * d * d + 3 * d * arch["d_ff"]


def parameters(arch):
    """Parameters by part, as the program's tree holds them: the N blocks ONCE."""
    d, v = arch["d_model"], arch["vocab_size"]
    norms = (4 if arch.get("sandwich_norm") else 2) * d
    block = block_matmul_params(arch) + norms
    gate = d + 1 if arch.get("exit_gate") else 0
    parts = {"block": block, "stack": arch["n_layer"] * block, "table": v * d, "head": v * d, "ln_f": d, "gate": gate}
    parts["trunk"] = parts["stack"] + parts["table"] + parts["head"] + d + gate
    return parts


def layer_windows(arch):
    """Every layer attends over the full span (the family's layer_types are all full_attention)."""
    return [0] * arch["n_layer"]


def trunk_train_flops(arch, batch, seq, unfrozen):
    """Forward + backward of the R N block applications of one train step."""
    n, n_layer = batch * seq, arch["n_layer"]
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    dense = 2 * n * block_matmul_params(arch)
    attn = attention_flops(batch, seq, arch["n_head"], head_dim(arch))
    total = 0
    for _ in range(loops(arch)):
        for i in range(n_layer):
            total += dense + attn  # forward
            total += dense + 2 * attn  # activation gradients
            if i >= n_layer - k:
                total += dense  # weight gradients: one product a use of a trained block, summed over the loops
    return total


def ppo_train_step_flops(arch, batch, prompt, response, unfrozen):
    d, v = arch["d_model"], arch["vocab_size"]
    head = 3 * 2 * batch * response * d * v  # response positions only, the last loop's output only
    value = 3 * mlp_head_flops(batch * response, d, 1)
    return trunk_train_flops(arch, batch, prompt + response, unfrozen) + head + value


def ilql_train_step_flops(arch, batch, seq, unfrozen, two_qs=True):
    raise NotImplementedError("no ILQL cell runs this configuration; count it with the cell that does")


def cache_bytes_per_token(arch, int8=True):
    """Bytes the cache holds a token: K and V of every (loop, layer) pair, int8
    with a float32 scale a key a head, or bf16."""
    entries, h, hd = loops(arch) * arch["n_layer"], arch["n_head"], head_dim(arch)
    return entries * 2 * (h * hd + h * F32) if int8 else entries * 2 * h * hd * BF16


def decode_step_bytes(arch, rows, keys, int8=True):
    """(bytes one decode step over `rows` rows must move, the loops' part: the
    stack's weights read R times): the stack's bf16 weights once a loop, the
    head once (the embedding's `rows` rows, the value head and the logits
    themselves are left out: under 1%), `keys` cache slots a row of every
    (loop, layer) entry at the cache's bytes a token."""
    p = parameters(arch)
    stack = loops(arch) * p["stack"] * BF16
    total = stack + (p["head"] + p["ln_f"]) * BF16 + int(keys * rows * cache_bytes_per_token(arch, int8))
    return total, stack


def weight_read_share(arch, rows, keys):
    """The stack's weights, read once a loop, over all a decode step must move."""
    total, stack = decode_step_bytes(arch, rows, keys)
    return stack / total


"""Operations and bytes the algorithm needs, as functions of a call's shapes.

"Needs" means: recomputation (remat, the flash/fused-head backward's second
look at the scores) is not counted, a frozen layer's weight gradients are not
counted, causal attention counts the key/query pairs the mask keeps and a
local layer those inside its window. Activation gradients cross every layer,
because embeddings stay trainable under frozen blocks (models/heads.py
`trainable_mask`). One multiply-add is 2 operations. `arch` is a
configuration file's `model_arch` (the program's LMConfig keys).
"""

BF16 = 2  # bytes


def _ff(arch):
    return arch.get("d_ff") or 4 * arch["d_model"]


def layer_matmul_params(arch):
    """Weights one block multiplies by: q, k, v, out (4 d^2) + the MLP's two."""
    d = arch["d_model"]
    return 4 * d * d + 2 * d * _ff(arch)


def kept_pairs(t, window=0):
    """Query/key pairs a causal mask keeps over t positions (window > 0: each
    query sees itself and the window-1 keys before it)."""
    if window <= 0 or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def attention_flops(b, t, n_head, head_dim, window=0):
    """Forward: scores and the value contraction over the kept pairs."""
    return 2 * 2 * b * n_head * head_dim * kept_pairs(t, window)


def layer_windows(arch):
    kinds = arch.get("attention_layers") or ["global"] * arch["n_layer"]
    return [arch.get("window_size", 0) if k == "local" else 0 for k in kinds]


def mlp_head_flops(positions, d, out):
    """MLPHead d -> 2d -> out, forward."""
    return 2 * positions * (d * 2 * d + 2 * d * out)


def trunk_train_flops(arch, batch, seq, unfrozen):
    """Forward + backward of the blocks for one train step."""
    d, h, n_layer = arch["d_model"], arch["n_head"], arch["n_layer"]
    n = batch * seq
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    total = 0
    for i, window in enumerate(layer_windows(arch)):
        dense = 2 * n * layer_matmul_params(arch)
        attn = attention_flops(batch, seq, h, d // h, window)
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
    d, v = arch["d_model"], arch["vocab_size"]
    actions = batch * (seq - 1)
    n_q = 2 if two_qs else 1
    lm_head = 3 * 2 * actions * d * v  # AWAC term over every next-token position
    online_q = n_q * 3 * mlp_head_flops(actions, d, v)
    target_q = n_q * 2 * actions * d * 2 * d  # hidden layer; the output is one gathered column
    value = 3 * mlp_head_flops(batch * seq, d, 1)
    return trunk_train_flops(arch, batch, seq, unfrozen) + lm_head + online_q + target_q + value


# ---- kernels: (operations, bytes) of one call ---------------------------------

FLASH_TENSORS = {"fwd": 4, "bwd_dq": 5, "bwd_dkv": 6}  # q k v o | q k v do dq | q k v do dk dv


def flash_call(kind, b, t, n_head, head_dim, window=0):
    """One flash-attention kernel call over [b, t, n_head, head_dim] bf16.
    Each of the three kernels needs two contractions over the kept pairs
    (fwd: QK^T, PV; dq: dO V^T, dS K; dkv: P^T dO, dS^T Q)."""
    ops = attention_flops(b, t, n_head, head_dim, window)
    moved = FLASH_TENSORS[kind] * b * t * n_head * head_dim * BF16
    return ops, moved


def logprob_head_call(kind, n, d, v):
    """One fused log-prob head call over x [n, d], W [d, v] bf16: forward
    streams W once for the logits; dx and dw each need one contraction."""
    ops = 2 * n * d * v
    moved = (n * d + d * v) * BF16 + 3 * n * 4
    if kind != "fwd":
        moved += (n * d if kind == "bwd_dx" else d * v) * BF16
    return ops, moved


def least_seconds(ops, moved, peaks):
    """Roofline floor of a call and which bound applies."""
    compute, memory = ops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")

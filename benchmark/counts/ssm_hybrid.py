"""Operations and bytes of the hybrid state-space / attention decoder
(configurations whose file names `"flops": "ssm_hybrid"`), with flops.py's
signatures and flops.py's meaning of "needs": no recomputation, no weight
gradients of frozen blocks, only the pairs a causal mask keeps, activation
gradients across every layer. `arch` is the configuration file's `model_arch`.

A state-space layer's scan is counted IN ITS CHUNKED FORM at Q = `ssm_chunk`
(256 as published), the form a pass over many tokens runs, products only:

    C B^T            the kept half of each chunk's [Q, Q], once a B/C group (one group: once for all heads)
    (C B^T . decay) x   the kept half again, every head, P wide
    chunk states     every position's x (x) B into its chunk's end state, every head: P x N
    state to output  C on the state carried into the chunk, every head: P x N
    the recurrence   one multiply-add of [H, P, N] a chunk

The decays themselves (cumulative sums, exponentials, the mask's multiply) are
element-wise and not counted, as a softmax is not counted in attention. The
token-by-token form a decode step runs needs 3 H P N multiply-adds a token
(decay, add, read); `decode_step_bytes` says why nobody counts them: the step
is bound by the bytes of the state it reads and writes.

Grouped attention is counted at its true head counts without rotary (there is
none), in the layers `mixer_layers` names "attention"; the head is tied and
counted once forward and twice backward over the response positions, as every
PPO count here.
"""

from benchmark.flops import BF16, kept_pairs, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call",
           "least_seconds", "ssd_scan_call", "decode_step_bytes", "parameters"]

F32 = 4  # bytes
# Query heads a K/V head, for `flash_call` (the flash reader hands it a head
# count parsed from a call's result and no `arch`; benchmark/tests checks it
# against the configuration's file).
GROUP = 4


def head_dim(arch):
    return arch.get("head_width") or arch["d_model"] // arch["n_head"]


def mixers(arch):
    return list(arch.get("mixer_layers") or ["attention"] * arch["n_layer"])


def ssm_sizes(arch):
    """(H, P, N, inner = H P, convolution channels = inner + 2 N)."""
    h, p, n = arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"]
    return h, p, n, h * p, h * p + 2 * n


def attention_params(arch):
    """Weights grouped attention multiplies by: q, k, v, out."""
    d, hd, h = arch["d_model"], head_dim(arch), arch["n_head"]
    kv = arch.get("n_kv_head") or h
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def ssm_matmul_params(arch):
    """Weights a state-space layer's two projections multiply by: in (z | xBC | dt), out."""
    h, _, _, inner, width = ssm_sizes(arch)
    return arch["d_model"] * (inner + width + h) + inner * arch["d_model"]


def mlp_params(arch):
    return 3 * arch["d_model"] * (arch.get("d_ff") or 4 * arch["d_model"])


def parameters(arch):
    """{kind: parameters}: one state-space layer, one attention layer, the tied table, the whole trunk."""
    h, _, _, inner, width = ssm_sizes(arch)
    d = arch["d_model"]
    norms = 2 * d
    vectors = arch.get("ssm_conv", 4) * width + width + 3 * h + inner  # conv kernel and bias; dt_bias, A_log, D; the gated norm
    ssm = ssm_matmul_params(arch) + vectors + norms + mlp_params(arch)
    attention = attention_params(arch) + norms + mlp_params(arch)
    table = arch["vocab_size"] * d
    kinds = mixers(arch)
    total = kinds.count("mamba") * ssm + kinds.count("attention") * attention + table + d  # + the final norm
    return {"mamba": ssm, "attention": attention, "table": table, "trunk": total}


def layer_windows(arch):
    """One entry an ATTENTION layer (what the flash reader averages over): no window anywhere."""
    return [0] * mixers(arch).count("attention")


def attention_flops(arch, b, t):
    """Forward: scores and the value contraction, every query head, kept pairs only."""
    return 2 * 2 * b * arch["n_head"] * head_dim(arch) * kept_pairs(t)


def ssd_scan_call(arch, b, t):
    """(operations, bytes) of ONE state-space layer's scan over [b, t],
    forward, in the chunked form (module docstring). Bytes: x and y
    [b, t, H, P], B and C [b, t, N] in bf16 and the step [b, t, H] in float32,
    each moved once; nothing of the decay masks or the chunk states, which a
    fused scan never writes."""
    h, p, n, _, _ = ssm_sizes(arch)
    q = min(arch.get("ssm_chunk", 256), t)
    chunks = -(-t // q)
    half = q * (q + 1) // 2
    ops = 2 * b * chunks * (half * n + h * half * p + 2 * h * q * p * n + h * p * n)
    moved = b * t * ((2 * h * p + 2 * n) * BF16 + h * F32)
    return ops, moved


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, kinds = batch * seq, mixers(arch)
    n_layer = len(kinds)
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    total = 0
    for i, kind in enumerate(kinds):
        if kind == "mamba":
            dense, mix = 2 * n * (ssm_matmul_params(arch) + mlp_params(arch)), ssd_scan_call(arch, batch, seq)[0]
        else:
            dense, mix = 2 * n * (attention_params(arch) + mlp_params(arch)), attention_flops(arch, batch, seq)
        total += dense + mix  # forward
        total += dense + 2 * mix  # activation gradients
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


# The width of a head in this family. The program pads q, k and v with zeros
# to the kernels' 128, and the flash reader hands `flash_call` the width it
# parses from the padded call; the zeros are no work anyone needs.
HEAD = 64


def flash_call(kind, b, t, n_head, head_dim, window=0):
    """One flash kernel call of this family (counts/gqa_moe.py's, at this
    family's group and its true head width): `n_head` is the leading
    dimension of the call's result, `head_dim` the call's padded width."""
    head_dim = min(head_dim, HEAD)
    q_heads = n_head * GROUP if kind == "bwd_dkv" else n_head
    ops = 2 * 2 * b * q_heads * head_dim * kept_pairs(t, window)
    at_q, at_kv = {"fwd": (2, 2), "bwd_dq": (3, 2), "bwd_dkv": (2, 4)}[kind]
    moved = b * t * head_dim * (at_q * q_heads + at_kv * q_heads // GROUP) * BF16
    return ops, moved


def state_bytes(arch, rows):
    """The state-space layers' cache at `rows` rows: a float32 state [H, P, N] and the
    convolution's last K - 1 inputs in bf16, a layer a row."""
    h, p, n, _, width = ssm_sizes(arch)
    return mixers(arch).count("mamba") * rows * (h * p * n * F32 + (arch.get("ssm_conv", 4) - 1) * width * BF16)


def decode_step_bytes(arch, rows, keys):
    """(bytes one decode step over `rows` rows must move, the state's part):
    every weight once in bf16 (the tied table once: the head reads all of it,
    the lookup `rows` of its rows), the state-space layers' state and window
    read AND written, `keys` cache slots of K and V a row in every attention
    layer. The value head and the logits themselves are left out (under 1%)."""
    kv = 2 * (arch.get("n_kv_head") or arch["n_head"]) * head_dim(arch) * BF16
    state = 2 * state_bytes(arch, rows)
    total = parameters(arch)["trunk"] * BF16 + state + mixers(arch).count("attention") * rows * keys * kv
    return total, state

"""Operations and bytes of the latent-attention, sparse-expert decoder
(configurations whose file names `"flops": "mla_moe"`), with flops.py's
signatures and flops.py's meaning of "needs": no recomputation, no weight
gradients of frozen blocks, causal pairs only, activation gradients across
every layer. `arch` is the configuration file's `model_arch`.

What is counted is what is ACTIVE here: latent attention, a dense layer's
feed-forward, and in an expert layer the router, the shared expert and the
HELD experts' expected share of the token-slots (`experts_held` of
`n_experts`, each slot one expert's three matrices). The absent experts do
no work on this chip and are not counted. Attention's two contractions run
at their true widths (scores over nope + rope, values over v) whatever the
padded kernel call shows.
"""

from benchmark.flops import BF16, FLASH_TENSORS, kept_pairs, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call",
           "least_seconds", "expert_ffn_call"]

# The flash reader hands `flash_call` the PADDED head width it parses from the
# call (256) and no `arch`; the true widths of the one MLA family that names
# this module are kept here (benchmark/tests checks them against its file).
QK_WIDTH, V_WIDTH = 192, 128


def attention_params(arch):
    """Weights latent attention multiplies by: q_a, q_b, kv_a, kv_b, out."""
    d, h = arch["d_model"], arch["n_head"]
    nope, rope, v = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]
    return (d * arch["q_lora_rank"] + arch["q_lora_rank"] * h * (nope + rope) + d * (arch["kv_lora_rank"] + rope)
            + arch["kv_lora_rank"] * h * (nope + v) + h * v * d)


def expert_params(arch):
    """One expert (routed or shared): gate, up, down."""
    return 3 * arch["d_model"] * arch["expert_d_ff"]


def held_share(arch):
    held = arch["experts_held"][1] if arch.get("experts_held") else arch["n_experts"]
    return held / arch["n_experts"]


def ffn_active_params(arch, kind):
    """Weights one token multiplies by in a layer's feed-forward, in
    expectation over an even router."""
    if kind == "dense":
        return 3 * arch["d_model"] * arch["d_ff"]
    routed = arch["experts_per_token"] * held_share(arch) * expert_params(arch)
    return arch["d_model"] * arch["n_experts"] + arch.get("n_shared_experts", 0) * expert_params(arch) + routed


def layer_kinds(arch):
    return list(arch.get("ffn_layers") or ["dense"] * arch["n_layer"])


def attention_flops(arch, b, t):
    """Forward: scores over nope + rope, the value contraction over v, kept pairs only."""
    width = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"] + arch["v_head_dim"]
    return 2 * b * arch["n_head"] * width * kept_pairs(t)


def layer_windows(arch):
    return [0] * arch["n_layer"]


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, n_layer = batch * seq, arch["n_layer"]
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    total = 0
    for i, kind in enumerate(layer_kinds(arch)):
        dense = 2 * n * (attention_params(arch) + ffn_active_params(arch, kind))
        attn = attention_flops(arch, batch, seq)
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
    """One flash kernel call of this family: both contractions of every
    kernel (fwd QK^T, PV; dq dO V^T, dS K; dkv P^T dO, dS^T Q) are one over
    QK_WIDTH and one over V_WIDTH, and the tensors moved are q, k, dq, dk at
    QK_WIDTH and v, o, do, dv at V_WIDTH, whatever `head_dim` the padded
    call carries."""
    ops = 2 * b * n_head * (QK_WIDTH + V_WIDTH) * kept_pairs(t, window)
    wide = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 3}[kind]  # q k | q k dq | q k dk
    moved = b * t * n_head * (wide * QK_WIDTH + (FLASH_TENSORS[kind] - wide) * V_WIDTH) * BF16
    return ops, moved


def expert_ffn_call(rows, touched, d, f):
    """The held experts' three grouped products over `rows` token-slots that
    touched `touched` experts: (operations, bytes), each touched expert's
    weights read once, the slots' inputs read and outputs written once."""
    ops = 3 * 2 * rows * d * f
    moved = (touched * 3 * d * f + rows * (2 * d + 3 * f)) * BF16
    return ops, moved

"""Operations and bytes of the gated delta-rule / latent-attention /
sparse-expert decoder (configurations whose file names `"flops": "kda_moe"`),
with flops.py's signatures and flops.py's meaning of "needs": no
recomputation, no weight gradients of frozen blocks, only the pairs a causal
mask keeps, activation gradients across every layer. `arch` is the
configuration file's `model_arch`.

A kda layer's pass over many tokens is counted IN ITS CHUNKED FORM at C =
`KDA_CHUNK` (the program's `models/kda.py CHUNK`, 64), products only, per chunk and head (D = `kda_head_dim`):

    A, B             the kept half of [C, C] each, D wide (A without its diagonal)
    the solve        (I + beta A) [W | U] = [beta K exp(G) | beta V] by substitution: the kept half, 2 D wide
    U - W S          C x D x D
    O = Q S + B U    C x D x D and the kept half, D wide
    the state        K^T U into [D, D]: C x D x D, and one multiply of [D, D] by the chunk's decay

The decays themselves (cumulative sums, exponentials, the masks' multiplies)
are element-wise and not counted, as a softmax is not counted in attention;
nor is the program's explicit inverse of I + beta A, which the mathematics
does not need. The token-by-token form a decode step runs needs 4 D^2
multiply-adds a head a token; `decode_step_bytes` says why nobody counts them:
the step is bound by the bytes of the state it reads and writes.

Latent attention is counted at its true widths (scores over nope + rope, values
over v) whatever the padded kernel call shows, in the layers `mixer_layers`
names "attention"; an expert layer as counts/mla_moe.py counts it: the router,
the shared expert and the HELD experts' expected share of the token-slots.
"""

from benchmark.counts.mla_moe import (QK_WIDTH, V_WIDTH, expert_ffn_call, expert_params, ffn_active_params, flash_call,
                                      held_share)
from benchmark.flops import BF16, kept_pairs, least_seconds, logprob_head_call, mlp_head_flops

__all__ = ["ppo_train_step_flops", "ilql_train_step_flops", "layer_windows", "flash_call", "logprob_head_call",
           "least_seconds", "expert_ffn_call", "kda_scan_call", "decode_step_bytes", "state_bytes", "parameters",
           "QK_WIDTH", "V_WIDTH", "held_share"]

F32 = 4  # bytes
KDA_CHUNK = 64  # trlx_tpu/models/kda.py CHUNK (tests/test_kda_moe_counts.py holds the two together)


def mixers(arch):
    return list(arch.get("mixer_layers") or ["attention"] * arch["n_layer"])


def ffn_kinds(arch):
    return list(arch.get("ffn_layers") or ["dense"] * arch["n_layer"])


def kda_sizes(arch):
    """(H, D, inner = H D, convolution taps)."""
    h, d = arch["kda_heads"], arch["kda_head_dim"]
    return h, d, h * d, arch.get("kda_conv", 4)


def kda_matmul_params(arch):
    """Weights a kda layer's projections multiply by: q, k, v, out; the decay's and the gate's two steps through D; beta."""
    h, d, inner, _ = kda_sizes(arch)
    model = arch["d_model"]
    return 4 * model * inner + 2 * (model * d + d * inner) + model * h


def attention_params(arch):
    """Weights latent attention multiplies by: q (direct), kv_a, kv_b, out."""
    d, h = arch["d_model"], arch["n_head"]
    nope, rope, v, rank = (arch[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    return d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + v) + h * v * d


def parameters(arch):
    """{kind: parameters} of the tree the program builds: one kda mixer, one
    latent attention, a dense and an expert feed-forward (the experts HELD),
    a block's two norms, the table, the head, the whole trunk."""
    h, d, inner, taps = kda_sizes(arch)
    model = arch["d_model"]
    kda = kda_matmul_params(arch) + 3 * taps * inner + h + 2 * inner + d  # convolutions; A_log; dt_bias, b_g; the output norm
    attention = attention_params(arch) + arch["kv_lora_rank"]  # + the latent's norm
    dense = 3 * model * arch["d_ff"]
    held = arch["experts_held"][1] if arch.get("experts_held") else arch["n_experts"]
    experts = (held + arch.get("n_shared_experts", 0)) * expert_params(arch) + model * arch["n_experts"] + arch["n_experts"]
    table = arch["vocab_size"] * model
    trunk = 2 * table + model  # embedding, untied head, the final norm
    for mixer, ffn in zip(mixers(arch), ffn_kinds(arch)):
        trunk += (kda if mixer == "kda" else attention) + (experts if ffn == "experts" else dense) + 2 * model
    return {"kda": kda, "attention": attention, "dense": dense, "experts": experts, "norms": 2 * model, "table": table,
            "head": table, "trunk": trunk}


def layer_windows(arch):
    """One entry an ATTENTION layer (what the flash reader averages over): no window anywhere."""
    return [0] * mixers(arch).count("attention")


def attention_flops(arch, b, t):
    """Forward: scores over nope + rope, the value contraction over v, kept pairs only."""
    width = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"] + arch["v_head_dim"]
    return 2 * b * arch["n_head"] * width * kept_pairs(t)


def kda_scan_call(arch, b, t):
    """(operations, bytes) of ONE kda layer's pass over [b, t], forward, in the
    chunked form (module docstring). Bytes: q, k, v and o [b, t, H, D] in bf16,
    the log-decay [b, t, H, D] and beta [b, t, H] in float32, each moved once;
    nothing of A, B, the solve or the chunk states, which a fused pass never
    writes."""
    h, d, _, _ = kda_sizes(arch)
    c = min(KDA_CHUNK, t)
    chunks = -(-t // c)
    lower, half = c * (c - 1) // 2, c * (c + 1) // 2
    macs = d * (lower + half + 2 * lower + half) + 3 * c * d * d + d * d
    ops = 2 * b * chunks * h * macs
    moved = b * t * h * (d * (4 * BF16 + F32) + F32)
    return ops, moved


def trunk_train_flops(arch, batch, seq, unfrozen):
    n, kinds = batch * seq, mixers(arch)
    n_layer = len(kinds)
    k = n_layer if unfrozen <= 0 or unfrozen >= n_layer else unfrozen
    total = 0
    for i, (kind, ffn) in enumerate(zip(kinds, ffn_kinds(arch))):
        if kind == "kda":
            dense, mix = 2 * n * (kda_matmul_params(arch) + ffn_active_params(arch, ffn)), kda_scan_call(arch, batch, seq)[0]
        else:
            dense, mix = 2 * n * (attention_params(arch) + ffn_active_params(arch, ffn)), attention_flops(arch, batch, seq)
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


def state_bytes(arch, rows):
    """The kda layers' cache at `rows` rows: a float32 state [H, D, D] and the three
    convolutions' last K - 1 inputs in bf16, a layer a row."""
    h, d, inner, taps = kda_sizes(arch)
    return mixers(arch).count("kda") * rows * (h * d * d * F32 + (taps - 1) * 3 * inner * BF16)


def decode_step_bytes(arch, rows, keys):
    """(bytes one decode step over `rows` rows must move, the state's part):
    every weight once in bf16 but the embedding (untied: the lookup takes
    `rows` of its rows, the head reads all of its own), the kda layers' state
    and window read AND written, `keys` cache slots of the latent and the
    shared key a row in every latent layer. The value head and the logits
    themselves are left out (under 1%)."""
    count = parameters(arch)
    latent = (arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) * BF16
    state = 2 * state_bytes(arch, rows)
    weights = (count["trunk"] - count["table"]) * BF16 + rows * arch["d_model"] * BF16
    return weights + state + int(mixers(arch).count("attention") * rows * keys * latent), state

"""Unified TPU-native causal transformer LM (Flax).

One module covers the reference's supported families
(reference: README.md:6 — gpt2 / gpt-j / gpt-neo / gpt-neox):

- GPT-2:  learned positions, sequential residual, fused qkv, tied lm head
- GPT-J:  rotary (rotary_dim), parallel residual w/ single LN, untied head
- NeoX:   rotary (rotary_pct), parallel residual w/ two LNs, fused qkv

TPU-first design decisions (vs the reference's HF torch modules,
reference: trlx/model/nn/ppo_models.py:35-413):

- **Functional KV cache**: an explicit pytree argument `(k, v, mask)` per
  layer updated with `lax.dynamic_update_slice` — static shapes, donatable,
  shardable (heads on tp, batch on dp/fsdp). No mutable module state.
- **Partial-stack application** (`start_layer`/`stop_layer`): the hydra
  frozen-branch ref model (reference: trlx/model/nn/ppo_models.py:102-312's
  ModelBranch deepcopy) becomes "apply layers [k..N) + ln_f + head with a
  frozen param subset" — no module copy, just a second `apply` over a pytree
  subset (see trlx_tpu.models.heads.extract_branch_params).
- **bf16 compute / fp32 params**: matmuls hit the MXU in bfloat16; softmax and
  losses accumulate in fp32.
- **Static shapes everywhere**: padding + masks, no ragged tensors.
"""

import collections
import functools
import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.observability import numerics as obs_numerics
from trlx_tpu.ops.kv_read import attend, attend_cache, attend_latent, attend_latent_range, ranged_read
from trlx_tpu.parallel.mesh import partitioned
from trlx_tpu.parallel.schedule import hold_rows, use_weight
from trlx_tpu.utils import tree_size_bytes

Dtype = Any

ACTIVATIONS = {
    "gelu_new": lambda h: nn.gelu(h, approximate=True),
    "gelu": lambda h: nn.gelu(h, approximate=False),
    "relu": nn.relu,
    "silu": nn.silu,
}
# Keys of a `model_arch` dict that are not LMConfig's: the trainers read them
# from the dict (`eos_token_id`: trainer/ppo.py, trainer/ilql.py).
ARCH_KEYS_READ_ELSEWHERE = frozenset({"eos_token_id"})
# models/kda.py names the output of its chunked pass so; a remat'd block keeps what bears the name
KDA_SCAN_OUT = "kda_scan_out"
# models/sparse.py names the blocks its many-token pass chose so; a remat'd block keeps them likewise
SPARSE_CHOSEN = "sparse_chosen"
# models/sparse.py and models/indexer.py name the joined output of that pass so; a remat'd block keeps it likewise
SPAN_PASS_OUT = "span_pass_out"


@dataclass(frozen=True)
class LMConfig:
    """Architecture config (from-scratch capable, HF-checkpoint compatible)."""

    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 0  # 0 → 4*d_model
    max_position: int = 1024
    pos_type: str = "learned"  # "learned" | "rotary" | "none" (no position signal anywhere)
    rotary_dim: int = 0  # 0 w/ rotary → full head dim
    parallel_residual: bool = False  # gptj/neox style
    use_parallel_ln: bool = False  # neox: separate ln for mlp in parallel block
    fused_qkv: bool = True
    qkv_bias: bool = True
    out_bias: bool = True
    scale_attn: bool = True  # gpt-neo quirk: no 1/sqrt(head_dim) scaling
    # Per-layer attention pattern ("global" | "local"); empty → all global.
    # Local layers attend within a trailing window (gpt-neo's alternating
    # global/local stack).
    attention_layers: Tuple[str, ...] = ()
    window_size: int = 0
    tie_word_embeddings: bool = True
    # Deviation the token embedding is DRAWN with (initialisation only; a
    # checkpoint brings its own). 0 -> flax's default, 1/sqrt(d_model): rows so
    # small that after the first attention every position's hidden state is
    # the attention's running mean, not its token. That is harmless to a GPT
    # block and ruinous to a router over random weights, which then sends every
    # token to the same few experts (PERF.md, PR 26); 1.0 is torch's default.
    embed_init_std: float = 0.0
    # The dtype every weight is DRAWN in (initialisation only), cast to
    # param_dtype afterwards; "" -> param_dtype itself, as ever. A draw in
    # bfloat16 is biased: jax.random's normal has mean -0.012 deviations there
    # and the truncated normal of lecun_normal -0.018 (a coarse uniform under
    # the inverse error function), the same on every entry, so each kernel
    # carries a rank-one part along the all-ones direction that a 6,144-wide
    # sum amplifies 1.4 times a product: every hidden state shares that
    # direction, and a router over such weights sends 87% of the tokens to
    # one expert by the fourth expert layer (PERF.md §6, PR 30). "float32"
    # draws without the bias.
    draw_dtype: str = ""
    activation: str = "gelu_new"
    ln_eps: float = 1e-5
    embd_pdrop: float = 0.0  # dropout unused in RL fine-tuning; kept for parity
    # Learned prefix embeddings (soft-prompt tuning; capability counterpart of
    # the reference's SoftEmbedding, trlx/model/accelerate_ppo_softprompt_model.py:26-81).
    n_soft_tokens: int = 0
    # Attention kernel: "auto" routes long aligned sequences through the
    # pallas flash kernel (trlx_tpu/ops/flash_attention.py) and everything
    # else through XLA einsum; "flash"/"xla" force a path.
    attn_impl: str = "auto"
    # Sequence/context parallelism: >1 routes full-sequence attention through
    # the sp-axis ring (trlx_tpu/parallel/ring_attention.py). Set by the
    # trainer from the mesh; 0/1 disables.
    sp_size: int = 0
    # Sharded-mesh training: compute the token embedding as one_hot @ table
    # instead of a gather. A gather's backward is a scatter-add whose
    # activation-grad resharding the SPMD partitioner cannot express over a
    # (dp,fsdp)-batch → (tp,fsdp)-table layout (it falls back to full
    # rematerialization — full-tensor replication traffic per step on a
    # pod); matmul gradients shard cleanly (partial dW + psum/reduce-scatter
    # over the data axes). One-hot rows are exact (1.0·x bit-exact in bf16),
    # FLOP cost is <1% of a train step at 6B shapes. Set by the trainer when
    # the mesh is sharded; single-device keeps the cheaper gather. Decode
    # always gathers (no gradients).
    onehot_embed: bool = False
    # int8 KV cache (per-token-per-head absmax scales): decode attention is
    # HBM-bandwidth-bound on cache reads at scale — int8 halves that traffic
    # and halves cache memory (longer sequences / larger rollout chunks per
    # chip). Only cache READS see quantization error: decode steps always,
    # and prefill only when it takes the einsum-over-cache path (flash
    # prefill attends over the unquantized local block). On one device a
    # read never dequantizes: a key's scale multiplies its score after q.K
    # and its probability before probs.V, once a key (ops/kv_read.py
    # `attend_quantized`; a partitioned mesh keeps the dequantizing read).
    # Scoring/training passes have no cache and always run full precision.
    kv_cache_quant: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = False
    # remat granularity: "full" recomputes everything in the block (minimum
    # memory); "dots" saves matmul outputs with no batch dims (weight-matmul
    # results survive, attention scores recompute) — more memory, less
    # backward recompute. Only read when remat=True.
    remat_policy: str = "full"
    # What a block is made of, by kind. The defaults are the GPT block every
    # field above describes; a kind the program lacks is an error here, at
    # construction, never a quietly different model.
    norm: str = "layernorm"  # "layernorm" | "rmsnorm" (scale only, no mean)
    mlp: str = "dense"  # "dense": c_proj(act(c_fc x)) | "gated": down(act(gate x) * up x), no biases
    attention: str = "mha"  # "mha": per-head K and V in the cache | "mla": latent attention (below) | "cca": models/cca.py | "sparse": models/sparse.py
    # Grouped keys ("mha" only): K and V are projected to n_kv_head heads and
    # each serves n_head // n_kv_head query heads, in the cache and in every
    # read; 0 -> n_head (one K and V a query head).
    n_kv_head: int = 0
    # Width of one head where it is not d_model // n_head ("mha" with separate
    # projections only): q_proj is n_head * head_width wide, k_proj and v_proj
    # kv_heads * head_width, c_proj takes n_head * head_width back to d_model.
    head_width: int = 0
    # RMSNorm over each query head and each key head (one scale of head_dim for
    # all the query heads, one for the key heads), in float32, before rotary.
    qk_norm: bool = False
    # Which layers rotate q and k (pos_type "rotary"): "all", or "local": the
    # window layers of `attention_layers` only, a global layer has no position
    # signal at all; or "lightning": the "lightning" layers of `mixer_layers`
    # rotate (inside the linear layer, all lightning_head_dim channels) and the
    # attention layers have no position signal.
    rotary_layers: str = "all"
    # What a window layer keeps in the cache: "span": every slot like a global
    # layer, the window by the bias (gpt-neo); "ring": window_size slots,
    # position p written at slot p mod window_size. The ring is built for the
    # static generate path (one write offset a batch: a prefill at 0, then one
    # token a step); the engine, the paged pool, spec decode and sp refuse it.
    window_cache: str = "span"
    # Per-layer feed-forward kind ("dense" | "experts"); empty -> all dense.
    # An "experts" layer is trlx_tpu/models/moe.py: sigmoid router over all
    # n_experts, experts_per_token chosen, a shared expert, and the routed
    # experts THIS program holds.
    ffn_layers: Tuple[str, ...] = ()
    # Rotary base and its scaling ({"type": "yarn", "factor", "beta_fast",
    # "beta_slow", "original_max_position_embeddings", "mscale",
    # "mscale_all_dim"}; None = none). Scaling is built for "mla" only.
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    # Latent attention (MLA): queries through a q_lora_rank bottleneck, keys
    # and values through ONE kv_lora_rank latent a token plus ONE
    # qk_rope_head_dim rotary key shared by all heads; those two are the cache.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A learned indexer on every latent-attention layer (models/indexer.py,
    # DeepSeek Sparse Attention): index_n_heads query heads of index_head_dim
    # from the query latent against ONE index key a token (a LayerNorm'd
    # projection of the block's normed input, its first qk_rope_head_dim
    # channels rotated), ReLU'd, weighted a head and summed; a query attends to
    # the index_topk keys of largest score, the same for every attention head
    # (all of them where it has no more). The choice carries no gradient and
    # nothing here trains the indexer (models/heads.py `trainable_mask`). The
    # cache keeps the index keys as a third leaf; a decode step gathers the
    # chosen latent entries. 0 = none. Built for the static generate path,
    # scoring and the train step, with a query bottleneck (q_lora_rank) and
    # rotary positions without scaling; the engine, the paged pool, spec
    # decode, the sp ring, packed segments and a looped stack refuse it.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Expert layers: the router is n_experts wide whatever is held here.
    # experts_held = (first, count): the routed experts [first, first+count)
    # this program holds, one chip's share of an expert-parallel deployment;
    # what the absent experts would add is left out (no stand-in). Empty ->
    # all of them.
    n_experts: int = 0
    experts_per_token: int = 0
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: Tuple[int, ...] = ()
    # How the router scores: "sigmoid": sigmoid scores over all n_experts, the
    # choice by score + a correction bias (a buffer), the chosen renormalised
    # and scaled; "softmax": the experts_per_token largest of the raw logits,
    # a softmax over the chosen (= a softmax over all, renormalised over the
    # chosen), no bias parameter, no scale; "softmax_all": a softmax over all
    # n_experts, the choice by probability + a balancing bias (a buffer), the
    # weight the chosen probability itself, NOT renormalised (with one expert
    # a token a renormalised weight is the constant 1), no scale.
    router_scoring: str = "sigmoid"
    # What the router reads: "ffn": the feed-forward's normed input, after
    # attention; "block": the block's INPUT, before ln_1 and ahead of
    # attention, so a layer's expert choice is known before its attention
    # runs; the experts read ln_2(x + attn) as ever. Built for the sequential
    # residual over attention mixers.
    router_input: str = "ffn"
    # What the router is: "linear": one product x . W_g; "mlp": a
    # down-projection to router_hidden, an RMSNorm and two GeLU layers of that
    # width before the n_experts logits (models/moe.py `MLPRouter`), in
    # float32. `router_carry`: the down-projection's output takes the layer
    # below's (after ITS carry, before its norm) times a learned vector, so
    # the router's state crosses the depth of the stack: `Block` takes it in
    # and hands it on, through remat, a decode step and the frozen branch's
    # replay (`forward_branch`'s second input). Every layer is then an expert
    # layer.
    router_kind: str = "linear"
    router_hidden: int = 0
    router_carry: bool = False
    # Learned scale and bias on BOTH operands of each residual sum of a block
    # (sequential residual): x' = (x + b_x) * a_x + (f + b_f) * a_f, four
    # vectors of d_model a sum.
    residual_scaling: bool = False
    # attention "cca" (models/cca.py): queries and keys at n_head and
    # n_kv_head heads of head_width pass two causal convolutions over time,
    # cca_time0 wide a channel and cca_time1 wide a head, ahead of the scores;
    # the cache keeps the convolutions' window and the previous token's value
    # beside K and V. Built for the static generate path, scoring and the
    # train step; the engine, the paged pool, spec decode, the sp ring,
    # kv_cache_quant, decode_weight_quant, soft prompts, packed segments,
    # windows and a looped stack refuse it.
    cca_time0: int = 0
    cca_time1: int = 0
    # Per-layer mixer kind ("attention" | "mamba" | "kda"); empty -> all attention.
    # A "mamba" layer is trlx_tpu/models/ssm.py: the Mamba-2 state-space mixer
    # (ssm_heads x ssm_head_dim channels, one B/C group of ssm_state numbers, a
    # depthwise causal convolution ssm_conv wide, the scan in chunks of
    # ssm_chunk). Its cache is a fixed float32 state a row, no slot axis. Built
    # for the static generate path, scoring and the train step; the engine,
    # the paged pool, spec decode, the sp ring, kv_cache_quant,
    # decode_weight_quant and soft prompts refuse it.
    mixer_layers: Tuple[str, ...] = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # A "kda" layer of `mixer_layers` is trlx_tpu/models/kda.py: the gated
    # delta-rule mixer (Kimi Delta Attention): kda_heads heads whose keys and
    # values are kda_head_dim wide, a log-decay a key channel and a correction
    # strength a head, three depthwise causal convolutions kda_conv wide, the
    # pass over many tokens in chunks of kda.CHUNK. Its cache is a fixed
    # float32 state [kda_heads, kda_head_dim, kda_head_dim] a row, no slot
    # axis. It stands beside attention "mla" and beside expert layers; the
    # engine, the paged pool, spec decode, the sp ring, kv_cache_quant,
    # decode_weight_quant, soft prompts, packed segments and a looped stack
    # refuse it.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # A "lightning" layer of `mixer_layers` is trlx_tpu/models/lightning.py:
    # linear attention with ONE constant decay a head (a buffer), per-head q
    # and k of lightning_head_dim, `qk_norm` and rotary (`rotary_layers`
    # "lightning") inside it, no convolution, an RMSNorm over all of its output
    # channels and, with lightning_output_gate, a sigmoid gate of that width
    # from the block's normed input. Its cache is a fixed float32 state
    # [lightning_heads, lightning_head_dim, lightning_head_dim] a row. It
    # stands beside attention "mha" or "sparse" and dense feed-forwards; the
    # engine, the paged pool, spec decode, the sp ring, kv_cache_quant,
    # decode_weight_quant, soft prompts, packed segments and a looped stack
    # refuse it.
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_output_gate: bool = False
    # attention "sparse" (models/sparse.py): every query chooses the key blocks
    # it reads. Keys are compressed (the mean of sparse_kernel keys every
    # sparse_stride tokens), a query's softmax over the compressed keys, summed
    # over the heads of its K/V group and max-pooled onto blocks of
    # sparse_block tokens, ranks the blocks; it reads the first
    # sparse_init_blocks, the blocks of its trailing sparse_window tokens and
    # the sparse_topk best of the others. The cache keeps the compressed keys
    # beside K and V; a decode step gathers the chosen blocks. With
    # attn_output_gate the output takes a sigmoid gate n_head * head_dim wide
    # from the block's normed input. Built for the static generate path,
    # scoring and the train step, with grouped keys and no position signal in
    # the layer; the engine, the paged pool, spec decode, the sp ring,
    # kv_cache_quant, decode_weight_quant, soft prompts, packed segments,
    # windows and a looped stack refuse it.
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_block: int = 0
    sparse_topk: int = 0
    sparse_window: int = 0
    sparse_init_blocks: int = 0
    attn_output_gate: bool = False
    # The four scalars of the granite family: on the token embedding, on the
    # attention scores (0 -> 1/sqrt(head_dim), or 1 without scale_attn), on
    # both residual branches of a block, and dividing the logits (in the fused
    # log-prob head, the sampler and scoring alike).
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # A looped stack: the n_layer blocks run n_loops times a token with the
    # SAME parameters (the tree keeps h_0 .. h_{N-1} once), the final norm at
    # the end of EVERY loop, its output the next loop's input; the cache keeps
    # keys and values a (loop, layer) pair, entry loop * n_layer + layer
    # (`cache_entries`), and loop r's layer reads what loop r's layer wrote.
    # A pass with no cache runs ONE traced stack under a scan over the loops;
    # through the cache the loops are unrolled (each entry has its own leaves).
    # Built for the static generate path, scoring and the train step over
    # "mha" blocks with dense feed-forwards; the engine, the paged pool, spec
    # decode, the sp ring, decode_weight_quant and packed segments refuse it.
    n_loops: int = 1
    # An RMSNorm/LayerNorm on each residual branch's OUTPUT as well as on its
    # input: x + norm(attn(norm(x))), x + norm(mlp(norm(x))) (sequential
    # residual only).
    sandwich_norm: bool = False
    # The exit gate of a looped stack: lambda_r = sigmoid(w . z_r + b) on each
    # loop's output z_r, and the exit distribution p_r = lambda_r prod_{j<r}
    # (1 - lambda_j), the last loop taking what is left (`exit_probs` of a
    # pass with no cache). At exit_threshold 1 every loop runs for every row
    # and the gate decides nothing; a threshold under 1 (rows of one batch
    # leaving the loop at different depths) is not built.
    exit_gate: bool = False
    exit_threshold: float = 1.0
    extra: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        # Validate at construction, not first use: a typo'd policy on a
        # config where remat happens to be off must not silently no-op.
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} (expected 'full' or 'dots')"
            )
        for name, kinds in (("norm", ("layernorm", "rmsnorm")), ("mlp", ("dense", "gated")),
                            ("attention", ("mha", "mla", "cca", "sparse")), ("rotary_layers", ("all", "local", "lightning")),
                            ("window_cache", ("span", "ring")), ("pos_type", ("learned", "rotary", "none")),
                            ("router_scoring", ("sigmoid", "softmax", "softmax_all")), ("router_input", ("ffn", "block")),
                            ("router_kind", ("linear", "mlp"))):
            if getattr(self, name) not in kinds:
                raise ValueError(f"unknown {name} kind {getattr(self, name)!r} (expected one of {kinds})")
        if self.draw_dtype not in ("", "float32"):
            raise ValueError(f"unknown draw_dtype {self.draw_dtype!r} (expected '' or 'float32')")
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r} (expected one of {sorted(ACTIVATIONS)})")
        if self.ffn_layers and (len(self.ffn_layers) != self.n_layer
                                or set(self.ffn_layers) - {"dense", "experts"}):
            raise ValueError(f"ffn_layers must name 'dense' or 'experts' for each of {self.n_layer} layers: {self.ffn_layers!r}")
        if self.attention == "mla":
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
            if min(sizes) <= 0 or self.q_lora_rank < 0 or self.pos_type == "learned" or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "attention 'mla' needs pos_type 'rotary' (or 'none': the shared key kept and not rotated), its four "
                    f"sizes and q_lora_rank (0: queries projected directly), got {sizes} and {self.q_lora_rank}")
            if self.kv_cache_quant or self.attention_layers or self.n_soft_tokens:
                raise ValueError("attention 'mla' is not built with kv_cache_quant, windowed layers or soft prompts")
        elif self.rope_scaling:
            raise ValueError("rope_scaling is built for attention 'mla' only")
        index = (self.index_n_heads, self.index_head_dim, self.index_topk)
        if max(index):
            if (self.attention != "mla" or min(index) <= 0 or not self.q_lora_rank or self.pos_type != "rotary"
                    or self.rope_scaling or self.index_head_dim < self.qk_rope_head_dim or self.has_state):
                raise ValueError(
                    "index_n_heads, index_head_dim and index_topk (all three) describe an indexer on attention 'mla' with a "
                    "query bottleneck (q_lora_rank), pos_type 'rotary' without rope_scaling, index_head_dim at least "
                    f"qk_rope_head_dim and no state layer in the stack: got {index} on attention {self.attention!r}")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {self.rope_scaling.get('type')!r} is not built (only 'yarn')")
        if self.n_kv_head and self.n_kv_head != self.n_head:
            if self.n_kv_head < 0 or self.n_head % self.n_kv_head:
                raise ValueError(f"n_kv_head {self.n_kv_head} does not divide n_head {self.n_head}")
            if self.attention == "mla" or self.fused_qkv or self.sp_size > 1:
                raise ValueError("grouped keys (n_kv_head < n_head) are built for attention 'mha' and 'cca' with separate "
                                 "q/k/v projections (fused_qkv false), and not for the sp ring")
        if (self.qk_norm or self.head_width) and (self.attention == "mla" or self.fused_qkv):
            raise ValueError("qk_norm and head_width are built for attention 'mha' and 'sparse' (head_width: 'cca' too) with "
                             "separate q/k/v projections (fused_qkv false)")
        if self.rotary_layers == "lightning" and (self.pos_type != "rotary" or not self.has_lightning or self.attention == "mla"
                                                  or (self.rotary_dim or self.lightning_head_dim) != self.lightning_head_dim):
            raise ValueError("rotary_layers 'lightning' needs pos_type 'rotary', a 'lightning' layer in mixer_layers whose "
                             "whole head rotates (rotary_dim 0) and attention layers that rotate nothing")
        if self.rotary_layers == "local" and (self.pos_type != "rotary" or "local" not in self.attention_layers):
            raise ValueError("rotary_layers 'local' needs pos_type 'rotary' and a 'local' layer in attention_layers")
        if self.window_cache == "ring":
            if "local" not in self.attention_layers or self.window_size <= 0:
                raise ValueError("window_cache 'ring' needs a 'local' layer in attention_layers and a window_size")
            if self.n_soft_tokens or self.sp_size > 1:
                raise ValueError("window_cache 'ring' is not built with soft prompts or the sp ring")
        if "experts" in self.ffn_layers:
            first, count = self.held_experts
            if (self.mlp != "gated" or self.expert_d_ff <= 0 or not 0 < self.experts_per_token <= self.n_experts
                    or first < 0 or count <= 0 or first + count > self.n_experts):
                raise ValueError(
                    f"expert layers need mlp 'gated', expert_d_ff, 0 < experts_per_token <= n_experts "
                    f"and experts_held inside [0, n_experts): {self.experts_held!r} of {self.n_experts}")
            if self.router_scoring != "sigmoid" and self.routed_scaling_factor != 1.0:
                raise ValueError(f"router_scoring {self.router_scoring!r} (a softmax) takes no scale: "
                                 f"routed_scaling_factor must be 1, got {self.routed_scaling_factor}")
            if (self.router_kind == "mlp") != (self.router_hidden > 0) or (self.router_carry and self.router_kind != "mlp"):
                raise ValueError("router_kind 'mlp' needs router_hidden (and 'linear' takes none); router_carry needs "
                                 f"router_kind 'mlp': got {self.router_kind!r}, {self.router_hidden}, {self.router_carry}")
            if self.router_kind == "mlp" and self.router_input != "ffn":
                raise ValueError("router_kind 'mlp' is built for router_input 'ffn' (it reads the feed-forward's normed input)")
            if self.router_carry:
                unbuilt = [name for name, on in (
                    ("a 'dense' layer in ffn_layers (the state crosses every layer)", "dense" in self.ffn_layers),
                    ("parallel_residual", self.parallel_residual), ("a 'mamba' layer", self.has_ssm),
                    ("a 'kda' layer", self.has_kda), ("soft prompts", self.n_soft_tokens > 0)) if on]
                if unbuilt:
                    raise ValueError(f"router_carry (a router state carried across depth) is not built with {', '.join(unbuilt)}")
        elif (self.router_scoring != "sigmoid" or self.router_input != "ffn" or self.router_kind != "linear"
              or self.router_hidden or self.router_carry):
            raise ValueError("router_scoring, router_input, router_kind, router_hidden and router_carry describe expert "
                             "layers: ffn_layers names none")
        if self.residual_scaling and (self.parallel_residual or self.sandwich_norm or self.residual_multiplier != 1.0):
            raise ValueError("residual_scaling is built for the plain sequential residual: not with parallel_residual, "
                             "sandwich_norm or a residual_multiplier")
        if self.attention == "cca":
            if (min(self.cca_time0, self.cca_time1) < 1 or self.cca_time0 + self.cca_time1 < 3 or self.kv_heads % 2
                    or self.n_head % self.kv_heads or self.pos_type == "learned" or self.head_dim % 2):
                raise ValueError(
                    "attention 'cca' needs cca_time0 and cca_time1 (a window of at least one position between them), an "
                    "even n_kv_head that divides n_head (the values' heads split between the token and the one before) and "
                    f"pos_type 'rotary' or 'none': got {(self.cca_time0, self.cca_time1)}, {self.n_head} over {self.kv_heads}")
            unbuilt = [name for name, on in (
                ("fused_qkv", self.fused_qkv), ("qkv_bias or out_bias (it has no such bias)", self.qkv_bias or self.out_bias),
                ("qk_norm (its scores are L2-normalised by rule)", self.qk_norm), ("kv_cache_quant", self.kv_cache_quant),
                ("windowed attention_layers", "local" in self.attention_layers), ("soft prompts", self.n_soft_tokens > 0),
                ("the sp ring (sp_size > 1)", self.sp_size > 1), ("a 'mamba' layer", self.has_ssm),
                ("a 'kda' layer", self.has_kda), ("a looped stack (n_loops > 1)", self.n_loops > 1),
                ("an attention_multiplier or scale_attn false (its scale is 1/sqrt(head_dim) by rule)",
                 self.attention_multiplier != 0 or not self.scale_attn)) if on]
            if unbuilt:
                raise ValueError(f"attention 'cca' is not built with {', '.join(unbuilt)}")
        elif self.cca_time0 or self.cca_time1:
            raise ValueError("cca_time0 and cca_time1 describe attention 'cca'")
        if self.router_input == "block":
            unbuilt = [name for name, on in (
                ("parallel_residual", self.parallel_residual), ("sandwich_norm", self.sandwich_norm),
                ("a 'mamba' layer", self.has_ssm), ("a 'kda' layer", self.has_kda),
                ("a looped stack (n_loops > 1)", self.n_loops > 1)) if on]
            if unbuilt:
                raise ValueError(f"router_input 'block' (the router ahead of attention) is not built with {', '.join(unbuilt)}")

        if self.mixer_layers and (len(self.mixer_layers) != self.n_layer
                                  or set(self.mixer_layers) - {"attention", "mamba", "kda", "lightning"}):
            raise ValueError(f"mixer_layers must name 'attention', 'mamba', 'kda' or 'lightning' for each of {self.n_layer} "
                             f"layers: {self.mixer_layers!r}")
        if self.has_ssm:
            sizes = (self.ssm_heads, self.ssm_head_dim, self.ssm_state, self.ssm_conv - 1, self.ssm_chunk)
            if min(sizes) <= 0:
                raise ValueError(f"a 'mamba' layer needs ssm_heads, ssm_head_dim, ssm_state, ssm_conv >= 2 and ssm_chunk, got {sizes}")
            unbuilt = [name for name, on in (
                ("attention 'mla'", self.attention == "mla"), ("kv_cache_quant", self.kv_cache_quant),
                ("soft prompts", self.n_soft_tokens > 0), ("the sp ring (sp_size > 1)", self.sp_size > 1),
                ("windowed attention_layers", "local" in self.attention_layers),
                ("expert layers", "experts" in self.ffn_layers), ("parallel_residual", self.parallel_residual)) if on]
            if unbuilt:
                raise ValueError(f"a 'mamba' layer (mixer_layers) is not built with {', '.join(unbuilt)}")
        if self.has_kda:
            sizes = (self.kda_heads, self.kda_head_dim, self.kda_conv - 1)
            if min(sizes) <= 0:
                raise ValueError(f"a 'kda' layer needs kda_heads, kda_head_dim and kda_conv >= 2, got {sizes}")
            unbuilt = [name for name, on in (
                ("a 'mamba' layer", self.has_ssm), ("kv_cache_quant", self.kv_cache_quant),
                ("soft prompts", self.n_soft_tokens > 0), ("the sp ring (sp_size > 1)", self.sp_size > 1),
                ("windowed attention_layers", "local" in self.attention_layers),
                ("parallel_residual", self.parallel_residual)) if on]
            if unbuilt:
                raise ValueError(f"a 'kda' layer (mixer_layers) is not built with {', '.join(unbuilt)}")
        if self.has_lightning:
            if min(self.lightning_heads, self.lightning_head_dim) <= 0 or self.lightning_head_dim % 2:
                raise ValueError("a 'lightning' layer needs lightning_heads and an even lightning_head_dim, got "
                                 f"{(self.lightning_heads, self.lightning_head_dim)}")
            unbuilt = [name for name, on in (
                ("a 'mamba' layer", self.has_ssm), ("a 'kda' layer", self.has_kda),
                ("attention 'mla' or 'cca'", self.attention in ("mla", "cca")), ("kv_cache_quant", self.kv_cache_quant),
                ("soft prompts", self.n_soft_tokens > 0), ("the sp ring (sp_size > 1)", self.sp_size > 1),
                ("windowed attention_layers", "local" in self.attention_layers),
                ("expert layers", "experts" in self.ffn_layers), ("parallel_residual", self.parallel_residual),
                ("a looped stack (n_loops > 1)", self.n_loops > 1),
                ("rotary_layers 'all' (its rotary is rotary_layers 'lightning')",
                 self.pos_type == "rotary" and self.rotary_layers != "lightning")) if on]
            if unbuilt:
                raise ValueError(f"a 'lightning' layer (mixer_layers) is not built with {', '.join(unbuilt)}")
        elif self.lightning_heads or self.lightning_head_dim or self.lightning_output_gate:
            raise ValueError("lightning_heads, lightning_head_dim and lightning_output_gate describe 'lightning' layers: "
                             "mixer_layers names none")
        sparse = (self.sparse_kernel, self.sparse_stride, self.sparse_block, self.sparse_topk, self.sparse_window)
        if self.attention == "sparse":
            if (min(sparse) <= 0 or self.sparse_init_blocks < 0 or self.sparse_kernel % self.sparse_stride
                    or self.sparse_block % self.sparse_stride or self.sparse_window % self.sparse_block
                    or self.sparse_kernel > self.sparse_block):
                raise ValueError(
                    "attention 'sparse' needs sparse_kernel, sparse_stride, sparse_block, sparse_topk and sparse_window, the "
                    "stride dividing the kernel and the block, the block dividing the window and holding a kernel: got "
                    f"{sparse} and sparse_init_blocks {self.sparse_init_blocks}")
            unbuilt = [name for name, on in (
                ("fused_qkv", self.fused_qkv), ("qkv_bias or out_bias (it has no such bias)", self.qkv_bias or self.out_bias),
                ("kv_cache_quant", self.kv_cache_quant), ("windowed attention_layers", "local" in self.attention_layers),
                ("soft prompts", self.n_soft_tokens > 0), ("the sp ring (sp_size > 1)", self.sp_size > 1),
                ("a 'mamba' layer", self.has_ssm), ("a 'kda' layer", self.has_kda),
                ("a looped stack (n_loops > 1)", self.n_loops > 1), ("expert layers", "experts" in self.ffn_layers),
                ("rotary in the layer (pos_type 'rotary' with rotary_layers other than 'lightning') or learned positions",
                 self.pos_type == "learned" or (self.pos_type == "rotary" and self.rotary_layers != "lightning")),
                ("an attention_multiplier or scale_attn false (its scale is 1/sqrt(head_dim) by rule)",
                 self.attention_multiplier != 0 or not self.scale_attn)) if on]
            if unbuilt:
                raise ValueError(f"attention 'sparse' is not built with {', '.join(unbuilt)}")
        elif max(sparse) or self.sparse_init_blocks or self.attn_output_gate:
            raise ValueError("sparse_kernel, sparse_stride, sparse_block, sparse_topk, sparse_window, sparse_init_blocks and "
                             "attn_output_gate describe attention 'sparse'")
        if self.n_loops < 1:
            raise ValueError(f"n_loops must be at least 1, got {self.n_loops}")
        if self.n_loops > 1:
            unbuilt = [name for name, on in (
                ("attention 'mla'", self.attention == "mla"), ("expert layers", "experts" in self.ffn_layers),
                ("a 'mamba' layer", self.has_ssm), ("a 'kda' layer", self.has_kda),
                ("window_cache 'ring'", self.window_cache == "ring"),
                ("soft prompts", self.n_soft_tokens > 0), ("the sp ring (sp_size > 1)", self.sp_size > 1)) if on]
            if unbuilt:
                raise ValueError(f"a looped stack (n_loops > 1) is not built with {', '.join(unbuilt)}")
        if self.sandwich_norm and self.parallel_residual:
            raise ValueError("sandwich_norm is built for the sequential residual (parallel_residual false)")
        if self.exit_gate and self.n_loops == 1:
            raise ValueError("exit_gate needs a looped stack (n_loops > 1): with one loop there is nothing to leave")
        if self.exit_threshold != 1.0:
            if not self.exit_gate or not 0.0 < self.exit_threshold < 1.0:
                raise ValueError(f"exit_threshold {self.exit_threshold} needs exit_gate and lies in (0, 1]")
            raise NotImplementedError(
                f"exit_threshold {self.exit_threshold} < 1 (adaptive depth: rows of one batch leave the loop at "
                "different depths, so a step no longer costs every row the same) is not built; at 1 every loop runs")
        if self.logits_scaling != 1.0 and self.extra.get("lm_head_bias", False):
            raise ValueError("logits_scaling is not built with a head bias (extra.lm_head_bias)")
        if min(self.embedding_multiplier, self.residual_multiplier, self.logits_scaling) <= 0 or self.attention_multiplier < 0:
            raise ValueError("embedding_multiplier, residual_multiplier and logits_scaling are positive, attention_multiplier "
                             "is 0 (unset) or positive")

    @property
    def has_ssm(self) -> bool:
        """Whether any layer is a state-space ("mamba") mixer."""
        return "mamba" in self.mixer_layers

    @property
    def has_kda(self) -> bool:
        """Whether any layer is a gated delta-rule ("kda") mixer."""
        return "kda" in self.mixer_layers

    @property
    def has_lightning(self) -> bool:
        """Whether any layer is a constant-decay linear-attention ("lightning") mixer."""
        return "lightning" in self.mixer_layers

    @property
    def has_state(self) -> bool:
        """Whether any layer keeps a recurrent state in place of keys ("mamba", "kda" or "lightning")."""
        return self.has_ssm or self.has_kda or self.has_lightning

    @property
    def state_layer_name(self) -> str:
        """What a refusal calls the stack's recurrent layers: "state-space" ("mamba"), "kda" or "lightning"."""
        return "state-space" if self.has_ssm else "kda" if self.has_kda else "lightning"

    def mixer(self, layer: int) -> str:
        return self.mixer_layers[layer] if self.mixer_layers else "attention"

    @property
    def cache_entries(self) -> int:
        """Leaf groups of `init_cache`: one a (loop, layer) pair."""
        return self.n_loops * self.n_layer

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        if not self.experts_held:
            return 0, self.n_experts
        first, count = self.experts_held
        return int(first), int(count)

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_head

    @property
    def kv_heads(self) -> int:
        """K and V heads: n_kv_head, or n_head where keys are not grouped."""
        return self.n_kv_head or self.n_head

    @property
    def ff_dim(self) -> int:
        return self.d_ff if self.d_ff else 4 * self.d_model

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def params_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw):
        return replace(self, **kw)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]):
        """An unknown key is an error: a `model_arch` that names a mechanism
        the program lacks must not build some other model and run. Keys other
        modules read from the same dict themselves pass (`ARCH_KEYS_READ_ELSEWHERE`)."""
        unknown = sorted(set(d) - set(cls.__dataclass_fields__) - ARCH_KEYS_READ_ELSEWHERE)
        if unknown:
            raise ValueError(f"LMConfig: unknown architecture key(s) {unknown}")
        known = {k: v for k, v in d.items() if k in cls.__dataclass_fields__}
        for key in ("attention_layers", "ffn_layers", "experts_held", "mixer_layers"):
            if key in known:
                known[key] = tuple(known[key])
        return cls(**known)


# ---------------------------------------------------------------------------
# Rotary embeddings (GPT-J/NeoX)
# ---------------------------------------------------------------------------


def yarn_inv_freq(rotary_dim: int, base: float, scaling: Dict[str, Any]) -> np.ndarray:
    """YaRN-corrected rotary frequencies: dimensions that turn more than
    `beta_fast` times over the original context keep their frequency, those
    that turn less than `beta_slow` times are interpolated by `factor`, a
    linear ramp between."""
    exponent = np.arange(0, rotary_dim, 2) / rotary_dim
    extrapolated, interpolated = 1.0 / base**exponent, 1.0 / (scaling["factor"] * base**exponent)
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return rotary_dim * np.log(original / (turns * 2 * np.pi)) / (2 * np.log(base))

    low = max(int(np.floor(correction_dim(scaling["beta_fast"]))), 0)
    high = min(int(np.ceil(correction_dim(scaling["beta_slow"]))), rotary_dim - 1)
    ramp = np.clip((np.arange(rotary_dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def rotary_sincos(positions: jnp.ndarray, rotary_dim: int, base: float = 10000.0, inv_freq=None):
    """sin/cos tables for rotary positions. positions: [b, t] → [b, t, rd/2]."""
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (np.arange(0, rotary_dim, 2) / rotary_dim))
    freqs = positions[..., None].astype(jnp.float32) * inv_freq[None, None, :]
    return jnp.sin(freqs), jnp.cos(freqs)


@functools.lru_cache(maxsize=None)
def rotary_swap(width: int, rotary_dim: int, neox_style: bool) -> np.ndarray:
    """R as a [width, width] matrix of 0 and 1: (x @ R)[j] is the channel that
    channel j is paired with, x[j +- rotary_dim / 2] (NeoX: halves) or x[j ^ 1]
    (GPT-J: even/odd neighbours); a column past `rotary_dim` is empty."""
    j = np.arange(rotary_dim)
    swap = np.zeros((width, width), np.float32)
    swap[(j + rotary_dim // 2) % rotary_dim if neox_style else j ^ 1, j] = 1.0
    return swap


def rotary_tables(positions: jnp.ndarray, width: int, rotary_dim: int, base: float = 10000.0,
                  neox_style: bool = False, inv_freq=None, scale: float = 1.0):
    """(C, S) for `apply_rotary`, float32 [b, t, 1, width], made once a pass
    from positions [b, t]: over a head's whole width C is the pair's cos and S
    its sin with the pair's sign folded in (-sin on the member the formula
    subtracts for, +sin on the other); past `rotary_dim` C = 1 and S = 0.
    `scale` multiplies both (YaRN's table factor)."""
    sin, cos = rotary_sincos(positions, rotary_dim, base, inv_freq)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    if neox_style:
        c, s = jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)
    else:
        c, s = jnp.repeat(cos, 2, axis=-1), jnp.stack([-sin, sin], axis=-1).reshape(sin.shape[:-1] + (rotary_dim,))
    if rotary_dim < width:
        rest = ((0, 0), (0, 0), (0, width - rotary_dim))
        c, s = jnp.pad(c, rest, constant_values=1.0), jnp.pad(s, rest)
    return c[:, :, None, :], s[:, :, None, :]


# Elements of q (or k) from which the pair swap goes to the MXU. Over a train
# batch, a scoring chunk or a prefill (millions of elements) the product
# keeps the projection's output in the model's dtype and the rotation in one
# lane-dense fusion. A decode step's few rows are bound by the count of
# kernels, not by bytes: there the compiler folds the sliced members into
# the projection ahead and the cache write behind, and any whole-width swap
# (product, slices put together, a flip) cost Ouro's loop 1.3 ms a step
# (PERF.md section 6, PR 50).
ROTARY_MXU_MIN = 1 << 20


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rotate(x, c, s, rotary_dim, neox_style):
    """x * C + R(x) * S in float32, rounded once to x's dtype; R puts each
    pair's other member in a channel's place. Two statements of it, picked by
    the call's size, equal bit for bit."""
    f32, width = jnp.float32, x.shape[-1]
    if x.size >= ROTARY_MXU_MIN:
        # R as a product with a 0/1 matrix: exact (one term a column, float32
        # accumulation), and x is read in its own dtype, whole rows of lanes
        swap = jnp.asarray(rotary_swap(width, rotary_dim, neox_style), x.dtype)
        paired = jnp.einsum("...d,de->...e", x, swap, precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
        return (x.astype(f32) * c + paired * s).astype(x.dtype)
    # R by which member meets which: the pairs' first and second members, sliced
    rot = x[..., :rotary_dim].astype(f32)
    if neox_style:
        first, second = slice(0, rotary_dim // 2), slice(rotary_dim // 2, rotary_dim)
    else:
        first, second = slice(0, rotary_dim, 2), slice(1, rotary_dim, 2)
    x1, x2 = rot[..., first], rot[..., second]
    cos, sin = c[..., first], s[..., second]  # C is one value a pair; S holds -sin on the first member, +sin on the second
    members = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    out = jnp.concatenate(members, axis=-1) if neox_style else jnp.stack(members, axis=-1).reshape(rot.shape)
    return out.astype(x.dtype) if rotary_dim == width else jnp.concatenate([out.astype(x.dtype), x[..., rotary_dim:]], axis=-1)


def _rotate_fwd(x, c, s, rotary_dim, neox_style):
    return _rotate(x, c, s, rotary_dim, neox_style), (c, s)


def _rotate_bwd(rotary_dim, neox_style, tables, g):
    # the transpose of a rotation is the rotation back: the same pass over the
    # cotangent with S negated, float32 sums and one rounding as forward
    c, s = tables
    return _rotate(g, c, -s, rotary_dim, neox_style), jnp.zeros_like(c), jnp.zeros_like(s)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def apply_rotary(x: jnp.ndarray, tables, rotary_dim: int, neox_style: bool = False):
    """Apply rotary embedding to q or k.

    x: [b, t, n_head, head_dim]; tables: `rotary_tables` of the head's width.
    GPT-J interleaves even/odd pairs; NeoX rotates halves. Both supported —
    HF-checkpoint numerical fidelity requires matching the layout. Either is
    x * C + R(x) * S over the whole head: the layout picks R (`rotary_swap`)
    and the tables, the sums are float32 and the result is rounded once.
    """
    with jax.named_scope("rotary"):
        return _rotate(x, *tables, rotary_dim, neox_style)


def rotary_layout(cfg: "LMConfig"):
    """(width, rotary_dim, neox_style) of what a layer of `cfg` rotates: a
    head's leading `rotary_dim` channels, or latent attention's rope part,
    whole and in interleaved pairs as the published code rotates it."""
    if cfg.attention == "mla":
        return cfg.qk_rope_head_dim, cfg.qk_rope_head_dim, False
    if cfg.rotary_layers == "lightning":  # a lightning layer's whole head, the attention layers rotate nothing
        return cfg.lightning_head_dim, cfg.lightning_head_dim, bool(cfg.extra.get("neox_rotary", False))
    return cfg.head_dim, cfg.rotary_dim or cfg.head_dim, bool(cfg.extra.get("neox_rotary", False))


def rotate_heads(cfg: "LMConfig", x: jnp.ndarray, rope):
    """q, k or latent attention's rope part `x` [b, t, heads, width] rotated by
    the pass's tables in the layout `cfg` states."""
    _, rd, neox = rotary_layout(cfg)
    return apply_rotary(x, rope, rd, neox)


def rope_tables(cfg: "LMConfig", positions: jnp.ndarray):
    """The pass's rotary tables (every layer and every loop reads the same
    positions), None without rotary positions."""
    if cfg.pos_type != "rotary":
        return None
    width, rd, neox = rotary_layout(cfg)
    scaling, inv_freq, scale = cfg.rope_scaling, None, 1.0  # latent attention's alone (LMConfig refuses it elsewhere)
    if scaling:
        # YaRN: corrected frequencies, and the published code multiplies the
        # sin/cos tables by mscale(factor, mscale) / mscale(factor, mscale_all_dim)
        inv_freq = yarn_inv_freq(rd, cfg.rope_theta, scaling)
        scale = yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) / yarn_mscale(
            scaling["factor"], scaling.get("mscale_all_dim", 0) or 0)
    return rotary_tables(positions, width, rd, cfg.rope_theta, neox, inv_freq, scale)


# ---------------------------------------------------------------------------
# Modules
# ---------------------------------------------------------------------------


def ring_eligible(cfg: LMConfig, q_len: int, has_cache: bool, batch: Optional[int] = None) -> bool:
    """Sequence-parallel ring attention applies to full-sequence passes when
    the model was built for an sp>1 mesh and the (static) shapes divide the
    mesh: seq over sp, batch over (dp, fsdp), heads over tp. Decode steps
    (q_len==1, KV cache) and tiny init/tracing shapes stay local."""
    if cfg.sp_size <= 1 or has_cache or q_len % cfg.sp_size:
        return False
    from trlx_tpu.parallel.mesh import AXIS_DP, AXIS_FSDP, AXIS_TP, get_mesh

    mesh = get_mesh()
    data = int(mesh.shape[AXIS_DP]) * int(mesh.shape[AXIS_FSDP])
    if batch is not None and batch % data:
        return False
    return cfg.n_head % int(mesh.shape[AXIS_TP]) == 0


def flash_eligible(cfg: LMConfig, q_len: int, has_cache: bool, prefill_at_zero: bool = False) -> bool:
    """Static routing decision between the pallas flash kernel and XLA einsum.

    Flash applies to full-sequence (no-KV-cache) passes AND to generation
    prefill (cache present, q_len > 1, write offset 0): during prefill every
    cache slot beyond the prompt block is still invalid, so attention over
    just the local [q_len] block is exact — the kernel sees ordinary
    self-attention while K/V are written to the cache on the side. This keeps
    the hottest long-context path (a 768+-token prefill) off the einsum
    engine's materialized [b,1,P,T] bias. Single-token decode steps (q_len==1)
    stay on einsum. "auto" reserves flash for long aligned sequences where the
    O(T^2) bias materialization actually hurts.
    """
    if cfg.attn_impl not in ("auto", "flash", "xla"):
        raise ValueError(f"attn_impl must be auto|flash|xla, got {cfg.attn_impl!r}")
    if cfg.attn_impl == "xla":
        return False
    if has_cache and not (q_len > 1 and prefill_at_zero):
        return False
    if cfg.attention == "sparse":  # its queries choose their key blocks: no band the kernels could take
        return False
    if cfg.index_topk and q_len > cfg.index_topk:  # an indexed latent layer: past index_topk tokens a query chooses its keys
        return False
    if cfg.attn_impl == "auto":
        from trlx_tpu.ops.flash_attention import auto_flash_ok, one_device_tpu

        return one_device_tpu() and auto_flash_ok(q_len)
    return True


def layer_window(cfg: LMConfig, layer: int) -> int:
    """The trailing window of a layer's attention: `window_size` on a local
    layer (gpt-neo's alternating pattern), 0 on a global one."""
    return cfg.window_size if cfg.attention_layers and cfg.attention_layers[layer] == "local" else 0


def ring_slots(cfg: LMConfig, layer: int, max_len: int) -> int:
    """Slots of a layer's ring in a cache of `max_len` positions: the layer's
    window (the whole span where that is shorter) under window_cache "ring",
    0 for a layer that keeps the full span."""
    return min(layer_window(cfg, layer), max_len) if cfg.window_cache == "ring" else 0


def full_pass_takes_flash(cfg: LMConfig, q_len: int) -> bool:
    """Whether a full-sequence pass at `q_len` (a train step's) calls the
    flash kernels on the whole row: not the einsum path and not the ring
    path, whose calls are per chunk. What the two `flash/*` counters of a
    step record ask."""
    return not ring_eligible(cfg, q_len, False) and flash_eligible(cfg, q_len, has_cache=False)


def flash_kept_pair_share(cfg: LMConfig, q_len: int) -> Optional[float]:
    """Pairs the mask keeps over pairs the flash kernels' live chunks compute,
    mean over the layers' attention calls of one full-sequence pass at
    `q_len` (a train step's); None where that pass takes no flash kernel (or
    the ring path, whose calls are per chunk). Reads the routing the trunk
    reads (`ring_eligible`, `flash_eligible`, `layer_window`) and the sizes
    the call takes (`pick_block`). A host float from shapes: the counter
    `flash/kept_pair_share` of a trainer's step records. A description of the
    sizes, not a score: a wider chunk lowers it and is as fast or faster
    (PERF.md §6, PR 27)."""
    if not full_pass_takes_flash(cfg, q_len):
        return None
    from trlx_tpu.ops.flash_attention import kept_pair_share, pick_block

    blocks = pick_block(q_len)
    return sum(kept_pair_share(q_len, blocks, True, layer_window(cfg, i)) for i in range(cfg.n_layer)) / cfg.n_layer


def flash_pad_dead_chunk_share(cfg: LMConfig, attention_mask):
    """Of the key chunks the band keeps live in the flash forward of one
    full-sequence pass over `attention_mask` [b, T], the share the rows' own
    padding takes out (`ops/flash_attention.py pad_dead_chunks`: the kernels'
    rule through `pick_block`'s sizes), over the rows and the trunk's
    attention layers. One traced scalar from the batch's mask: the counter
    `flash/pad_dead_chunk_share` of a train step's stats, 0.0 where no row
    pads a whole chunk. None where that pass takes no flash kernel, as
    `flash_kept_pair_share`, and where a `major` piece is ONE chunk, which a
    block always keeps: the share is 0.0 by the rule, the trainer writes that
    into the step record itself (`trainer/base.py`) and the step's stats pull
    is spared a transfer (some 1 ms of host time a step, 0.6% of ILQL's)."""
    b, q_len = attention_mask.shape
    if not full_pass_takes_flash(cfg, q_len):
        return None
    from trlx_tpu.ops.flash_attention import key_range, pad_dead_chunks, pick_block

    blocks, dead, live = pick_block(q_len), 0, 0
    if blocks.major == blocks.chunk:
        return None
    first, end = key_range(attention_mask[:, None, :])
    windows = collections.Counter(layer_window(cfg, i) for i in range(cfg.n_layer) if cfg.mixer(i) == "attention")
    for window, layers in windows.items():
        row_dead, row_live = pad_dead_chunks(first, end, q_len, blocks, True, window)
        dead = dead + layers * jnp.sum(row_dead)
        live += layers * row_live * b
    return dead / jnp.float32(live)


def drawn_in(draw_dtype: str, init):
    """`init`, drawing in `draw_dtype` and casting to the parameter's dtype
    (`LMConfig.draw_dtype`); `init` itself where none is named."""
    if not draw_dtype:
        return init
    return lambda key, shape, dtype=jnp.float32: init(key, shape, jnp.dtype(draw_dtype)).astype(dtype)


class QDense(nn.Module):
    """`nn.Dense` drop-in whose weights can be OVERRIDDEN by an int8
    weight-only copy passed as the ``qw`` variable collection (decode-time
    W8A16: halves the per-step HBM traffic of the params reads that dominate
    autoregressive decoding). Without the collection this is exactly
    nn.Dense — same param names ("kernel"/"bias"), same init, same numerics;
    training and scoring never pass ``qw``. With it, XLA fuses the
    int8→compute-dtype convert into the matmul operand load (the same
    pattern as the int8 KV cache) and the per-output-channel scale applies
    after the contraction."""

    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    # the rows of the pass this product belongs to, where they are more than
    # x's own (the head over the response positions of a whole-sequence pass)
    pass_tokens: int = 0
    draw_dtype: str = ""  # LMConfig.draw_dtype

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            drawn_in(self.draw_dtype, nn.initializers.lecun_normal()),
            (x.shape[-1], self.features),
            self.param_dtype,
        )
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype)
            if self.use_bias
            else None
        )
        if self.has_variable("qw", "kernel_q"):
            kq = self.get_variable("qw", "kernel_q")
            scale = self.get_variable("qw", "scale")
            y = jnp.dot(x.astype(self.dtype), kq.astype(self.dtype)) * scale.astype(self.dtype)
        else:
            # on a partitioned mesh a pass over many tokens gathers the kernel
            # here, at its point of use (parallel/schedule.py)
            tokens = self.pass_tokens or math.prod(x.shape[:-1])
            kernel = use_weight(kernel.astype(self.dtype), self.path + ("kernel",), tokens)
            y = jnp.dot(x.astype(self.dtype), kernel)
        if bias is not None:
            y = y + bias.astype(self.dtype)
        return y


class HeadParams(nn.Module):
    """Declares the SAME parameters as QDense(name='lm_head') — identical
    names ('kernel'/'bias'), shapes, dtypes, and initializers — but returns
    the raw arrays instead of applying the projection. The fused-logprob
    head path (TransformerLM labels mode) streams the weight through the
    Pallas kernel itself; the param tree stays byte-compatible with the
    materializing path, so checkpoints and init are interchangeable."""

    features: int
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    draw_dtype: str = ""  # LMConfig.draw_dtype

    @nn.compact
    def __call__(self, in_features: int, tokens: int):
        kernel = self.param(
            "kernel",
            drawn_in(self.draw_dtype, nn.initializers.lecun_normal()),
            (in_features, self.features),
            self.param_dtype,
        )
        # `tokens`: the rows of the product the caller makes with the kernel
        kernel = use_weight(kernel, self.path + ("kernel",), tokens)
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype)
            if self.use_bias
            else None
        )
        return kernel, bias


QUANT_KERNEL_NAMES = ("c_qkv", "q_proj", "k_proj", "v_proj", "c_proj", "c_fc", "lm_head")


def quantize_weights(params, probe=None):
    """Build the ``qw`` variable collection: per-output-channel symmetric
    int8 of every trunk matmul kernel (+ untied lm_head), mirroring module
    paths so QDense finds its own leaves. Jit this (it is a cheap tree_map —
    ~10 ms at 2B) and rebuild whenever the policy params change (the trainer
    re-quantizes before each rollout phase). Embeddings, layernorms, and the
    RL heads stay full precision.

    ``probe`` (graftnum error probe, observability/numerics.py): a dict that
    accumulates per-kernel-class ``[max_abs_err, sum_sq_err, sum_sq_signal,
    count]`` from the int8 round trip. Callers on the hot path pass nothing
    — the default-None argument keeps the jitted trace identical."""

    def walk(node):
        out = {}
        for k, v in node.items():
            if not isinstance(v, dict):
                continue
            if k in QUANT_KERNEL_NAMES and "kernel" in v:
                w = v["kernel"].astype(jnp.float32)
                scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / 127.0, 1e-8)
                out[k] = {
                    "kernel_q": jnp.round(w / scale).astype(jnp.int8),
                    "scale": scale,
                }
                if probe is not None:
                    err = w - out[k]["kernel_q"].astype(jnp.float32) * scale
                    slot = probe.setdefault(k, [jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), 0])
                    slot[0] = jnp.maximum(slot[0], jnp.max(jnp.abs(err)))
                    slot[1] = slot[1] + jnp.sum(err * err)
                    slot[2] = slot[2] + jnp.sum(w * w)
                    slot[3] = slot[3] + int(w.size)
            else:
                sub = walk(v)
                if sub:
                    out[k] = sub
        return out

    return walk(params)


def write_cache(buf, upd, cache_index):
    """Write `upd` [b, q, ...] into a fixed cache buffer [b, T, ...] at
    `cache_index`. Scalar offset: one dynamic_update_slice covers the batch.
    Vector offset [b] (slot decode): every row writes at its own slot length
    — a vmap'd per-row update (lowers to scatter)."""
    upd = upd.astype(buf.dtype)
    zeros = (0,) * (buf.ndim - 2)
    if not isinstance(cache_index, (int, np.integer)) and jnp.ndim(cache_index) == 1:
        return jax.vmap(
            lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i,) + zeros)
        )(buf, upd, cache_index)
    return jax.lax.dynamic_update_slice(buf, upd, (0, cache_index) + zeros)


def write_ring(buf, upd, cache_index):
    """Write `upd` [b, q, ...] into a ring buffer [b, W, ...], position p at
    slot p mod W. One token (a decode step) goes to slot `cache_index` mod W;
    a block written at offset 0 (the prefill) leaves its last W positions."""
    slots, q_len = buf.shape[1], upd.shape[1]
    if q_len == 1:
        return write_cache(buf, upd, cache_index % slots)
    if q_len <= slots:
        return write_cache(buf, upd, 0)
    first = q_len - slots  # the oldest position kept
    return jnp.roll(upd[:, first:], first % slots, axis=1).astype(buf.dtype)


def ring_bias(cache_mask, cache_index, slots: int):
    """The additive bias [b, 1, 1, slots] of a decode step's read of a ring of
    `slots` slots, the step writing position `cache_index`: slot s holds the
    newest position p <= cache_index with p = s mod slots, which the window
    admits by construction; it is valid where that position exists (p >= 0)
    and `cache_mask` [b, T] marks it (left padding, a finished row)."""
    s = jnp.arange(slots, dtype=jnp.int32)
    pos = cache_index - (cache_index - s) % slots
    valid = (pos >= 0)[None, :] & jnp.take(cache_mask, jnp.maximum(pos, 0), axis=1).astype(bool)
    return jnp.where(valid, 0.0, -1e9).astype(jnp.float32)[:, None, None, :]


def flash_core(q, k, v, flash_mask, scale, dtype, window=0):
    """The flash kernels over q [b, t, h, hd] and k, v [b, t, kv_heads, hd],
    causal, under `flash_mask` [b, t].

    The kernels are tuned and tested at head widths that fill their 128 lanes;
    a narrower head (64: 13.8 ms on XLA, 5.3 ms here, forward and backward at
    [8, 1024], 32 over 8; PERF.md §6, PR 32) is padded with zeros, which add
    nothing to q.k, and the output's padded columns are dropped, as the latent
    path pads 192/128 to 256. The pad buys test coverage, not speed: unpadded
    at 64 the kernels read 5.2 ms in the same run, and no lowering or parity
    test holds them at that width (PERF.md §7, PR 32 e)."""
    from trlx_tpu.ops.flash_attention import flash_attention

    hd = q.shape[-1]
    pad = -hd % 128
    widen = (lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pad)))) if pad else (lambda a: a)
    with jax.named_scope("flash_attn"):  # what the call site costs: pad, relayouts, kernels
        out = flash_attention(widen(q), widen(k), widen(v), flash_mask, scale=scale, causal=True, window=window)
        return (out[..., :hd] if pad else out).astype(dtype)


class Attention(nn.Module):
    """Multi-head causal attention with functional KV cache.

    Layout: qkv projections are column-parallel over tp (see
    trlx_tpu/parallel/sharding.py), output projection row-parallel. Softmax in
    fp32. The cache is `(k, v)` of shape [b, cache_len, kv_heads, head_dim]
    (a window layer under window_cache "ring": cache_len = window_size slots)
    written at `cache_index` with dynamic_update_slice. When `flash_mask` is
    given (and attn_bias is None) the score/softmax/value contraction runs in
    the fused pallas kernel instead of einsum.
    """

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, attn_bias, rope, cache=None, cache_index=None,
                 flash_mask=None, window=0, use_ring=False, block_tables=None):
        cfg = self.cfg
        dtype = cfg.compute_dtype
        b, q_len, _ = x.shape
        hd = cfg.head_dim

        dense = lambda feats, name, use_bias: QDense(
            feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=use_bias, draw_dtype=cfg.draw_dtype, name=name
        )

        kvh = cfg.kv_heads
        if cfg.fused_qkv:
            qkv = dense(3 * cfg.d_model, "c_qkv", cfg.qkv_bias)(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = dense(cfg.n_head * hd, "q_proj", cfg.qkv_bias)(x)
            k = dense(kvh * hd, "k_proj", cfg.qkv_bias)(x)
            v = dense(kvh * hd, "v_proj", cfg.qkv_bias)(x)

        # Grouped keys: K and V keep their kvh heads from here to the cache and
        # through every read; a read serves n_head // kvh query heads from one
        # K/V head by a reshape of the QUERY heads (ops/kv_read.py `attend`,
        # ops/flash_attention.py), never by a repeated copy of K or V.
        q = q.reshape(b, q_len, cfg.n_head, hd)
        k = k.reshape(b, q_len, kvh, hd)
        v = v.reshape(b, q_len, kvh, hd)

        if cfg.qk_norm:
            q, k = qk_normed(cfg, q, k)

        if cfg.pos_type == "rotary" and (cfg.rotary_layers == "all" or window):
            q, k = rotate_heads(cfg, q, rope), rotate_heads(cfg, k, rope)

        new_cache = None
        read = None  # set → the einsum read covers a slice of the cache
        kv = (k, v)  # what the einsum read attends over otherwise: the block's own, or the whole cache
        if cache is not None:
            # WRITE. A per-row (vector) cache_index composes with q_len > 1
            # (the speculative verify window): the vmap'd write scatters a
            # [b, k, ...] update at each row's own frontier, and
            # make_attn_bias builds the per-row ragged causal bias. Rows whose
            # frontier would run past the buffer end get their start clamped
            # by dynamic_update_slice — callers must size the cache with a
            # k-1 scratch tail so live rows never clamp (see
            # RolloutEngine.cache_len).
            paged = block_tables is not None
            # A window layer under window_cache "ring" keeps window_size
            # slots. The trunk admits two calls and hands each its bias: one
            # token at one offset for the batch (a decode step: the bias over
            # the ring's slots), or a block at write offset 0 (the prefill:
            # the bias cut to the block). Told apart by q_len: under remat the
            # offset is a tracer here.
            ring = cfg.window_cache == "ring" and window > 0
            if paged:
                # Paged KV: the per-layer cache operand is ONE shared block
                # pool [n_blocks, block_size, h, d] and each row addresses it
                # through its own block table [b, blocks_per_slot]. The row's
                # VIRTUAL cache keeps every legacy [T] contract — write
                # offsets, cache_mask, bias, and positions are computed over
                # t_virt = blocks_per_slot * block_size exactly as over the
                # fixed buffer — only the physical placement is indirect, so
                # the write is one advanced-index scatter at (physical block,
                # in-block offset) and the read gathers the virtual view
                # back. q_len covers decode (1), spec verify windows
                # (spec_k), and suffix prefill (W - hit) uniformly.
                blk = int(cache[0].shape[1])
                t_virt = int(block_tables.shape[1]) * blk
                tbl = block_tables.astype(jnp.int32)
                base = (
                    cache_index.astype(jnp.int32)[:, None]
                    if not isinstance(cache_index, (int, np.integer)) and jnp.ndim(cache_index) == 1
                    else jnp.full((b, 1), cache_index, dtype=jnp.int32)
                )
                voff = base + jnp.arange(q_len, dtype=jnp.int32)[None, :]
                # Live rows never run past t_virt (the engine sizes the slot
                # table to cover the spec scratch tail); dead rows' clamped
                # writes collapse onto masked columns of their own table —
                # the engine parks freed rows on the reserved trash block.
                voff = jnp.minimum(voff, t_virt - 1)
                phys = jnp.take_along_axis(tbl, voff // blk, axis=1)
                off = voff % blk

                def cache_write(pool, upd):
                    return pool.at[phys, off].set(upd.astype(pool.dtype))

                def gather_virt(pool):
                    # The row's virtual cache: [b, t_virt, ...].
                    return pool[tbl].reshape((b, t_virt) + pool.shape[2:])

            else:

                def cache_write(buf, upd):
                    return (write_ring if ring else write_cache)(buf, upd, cache_index)

                def gather_virt(buf):
                    # Per-slot buffers ARE the virtual cache.
                    return buf

            if cfg.kv_cache_quant:
                (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
                new_cache = tuple(cache_write(c, u) for c, u in zip(cache, (kq, vq, ks, vs)))
            else:
                new_cache = tuple(cache_write(c, u) for c, u in zip(cache, (k, v)))

            # READ. Flash prefill attends over the LOCAL block only (cache
            # slots beyond the prompt are invalid until decode): k, v stay
            # the block's own. One traced write offset for the whole batch on
            # a fixed buffer (a decode step of the static generate path): the
            # ranged read covers only the slots the bias can admit and reads
            # new_cache itself (ops/kv_read.py). Everything else (a per-row
            # index, a block table, an unaligned prefill) attends over the
            # whole virtual cache with the cache-validity bias; an int8 one
            # as it is stored, its scales beside it (`attend_quantized`).
            # A ring layer: the prefill block likewise attends over itself;
            # a decode step reads all of the ring's slots, valid by the
            # position each holds (the trunk's `ring_bias`): one branch, no
            # switch.
            if flash_mask is None and not (ring and q_len > 1):
                if not (paged or ring):
                    read = ranged_read(int(cache[0].shape[1]), q_len, cache_index, window)
                if read is None:
                    kv = tuple(gather_virt(c) for c in new_cache)

        scale = cfg.attention_multiplier or (1.0 / np.sqrt(hd) if cfg.scale_attn else 1.0)
        with jax.named_scope("attn_window" if window else "attn_full"):
            if flash_mask is not None:
                if use_ring:
                    from trlx_tpu.parallel.ring_attention import ring_attention_sharded

                    out = ring_attention_sharded(
                        q, k, v, flash_mask, scale=scale, causal=True, window=window
                    ).astype(dtype)
                else:
                    out = flash_core(q, k, v, flash_mask, scale, dtype, window)
            elif read is not None:
                out = read(q, new_cache, attn_bias, scale, dtype)
            elif cache is not None:
                with jax.named_scope("kv_read"):  # the whole cache, where `ranged_read` gave none
                    out = attend_cache(q, kv, attn_bias, scale, dtype)
            else:
                out = attend_cache(q, kv, attn_bias, scale, dtype)
        out = out.reshape(b, q_len, cfg.n_head * hd)
        out = dense(cfg.d_model, "c_proj", cfg.out_bias)(out)
        return out, new_cache


# Rows whose per-head queries, keys and values the unabsorbed path holds at
# once (a train batch is at most this; a scoring pass over 32 rows is four groups).
MLA_ROW_GROUP = 8


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA), with its two read paths.

        c_q = RMSNorm(x W_qa);  q = c_q W_qb -> n_head x (nope | rope)     (q_lora_rank 0: q = x W_q, no bottleneck)
        [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_rope = RoPE(k_r), one for all heads
        [k_nope | v] = c_kv W_kvb -> n_head x (nope | v)
        scores = (q_nope . k_nope + RoPE(q_rope) . k_rope) * s, causal, softmax in float32
    With pos_type "none" RoPE is the identity: the "rope" part of q and the
    shared key are kept, as wide as ever, and carry no position.

    The cache is `(c_kv [b, T, kv_lora_rank], k_rope [b, T, qk_rope_head_dim])`:
    the latent after its norm and the rotated shared key, nothing per head.

    *Unabsorbed* (training, scoring, a prefill at write offset 0): k_nope and
    v are built from the block's own latents and attention runs over the
    block, through the flash kernel where the trunk chose it. The kernel
    takes one head width, so q and k (nope + rope wide) and v are padded with
    zeros to the next multiple of 128: zeros add nothing to q.k, the padded
    columns of the output are dropped, and the scale stays `s`; at 192/128
    that is the 256-wide shape the kernel is tuned for, in place of a second
    kernel of two widths.
    *Absorbed* (every other read of the cache: the decode step): with W_UK,
    W_UV the two halves of W_kvb, viewed not copied,
    q_lat = q_nope W_UK^T, scores = q_lat . c_kv + q_rope . k_rope,
    o = (softmax . c_kv) W_UV: the same function, contracted in another
    order, over the cache's 576 numbers a token (ops/kv_read.py).
    """

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, attn_bias, rope, cache=None, cache_index=None,
                 flash_mask=None, window=0, use_ring=False, block_tables=None, token_mask=None):
        cfg = self.cfg
        if use_ring or block_tables is not None or window:
            raise NotImplementedError("attention 'mla' is not built for the sp ring, paged caches or windows")
        dtype = cfg.compute_dtype
        b, q_len, _ = x.shape
        h, dn, dr, dv, rank = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
        dense = lambda feats, name: QDense(feats, dtype=dtype, param_dtype=cfg.params_dtype, use_bias=False,
                                           draw_dtype=cfg.draw_dtype, name=name)
        norm = lambda name: nn.RMSNorm(epsilon=cfg.ln_eps, dtype=dtype, param_dtype=cfg.params_dtype, name=name)

        # the queries' input and its width: the bottleneck's normed output, or x itself
        c_q, q_in = (norm("q_a_norm")(dense(cfg.q_lora_rank, "q_a_proj")(x)), cfg.q_lora_rank) if cfg.q_lora_rank else (x, cfg.d_model)
        kv_a = dense(rank + dr, "kv_a_proj")(x)
        c_kv = norm("kv_a_norm")(kv_a[..., :rank])
        scaling = cfg.rope_scaling
        # Interleaved pairs (0,1), (2,3), ... as the published code rotates them (`rotary_layout`).
        rotate = (lambda part, rope: rotate_heads(cfg, part, rope)) if rope is not None else (lambda part, rope: part)
        softmax_scale = (dn + dr) ** -0.5
        if scaling:
            # YaRN's attention temperature: the published code multiplies the
            # softmax scale by mscale(factor, mscale_all_dim)^2 (and the sin/cos
            # tables by a factor of their own: `rope_tables`).
            softmax_scale *= yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0) or 0) ** 2
        k_rope = rotate(kv_a[:, :, None, rank:], rope)[:, :, 0]
        params = lambda feats, name, fan_in: HeadParams(
            feats, param_dtype=cfg.params_dtype, use_bias=False, draw_dtype=cfg.draw_dtype, name=name)(fan_in, b * q_len)[0].astype(dtype)
        w_qb = params(h * (dn + dr), "q_b_proj" if cfg.q_lora_rank else "q_proj", q_in).reshape(q_in, h, dn + dr)
        w_kvb = params(h * (dn + dv), "kv_b_proj", rank).reshape(rank, h, dn + dv)

        def queries(c_q, rope):
            q = jnp.einsum("btc,chn->bthn", c_q, w_qb)
            return q[..., :dn], rotate(q[..., dn:], rope)

        new_cache = stats = None
        if cache is not None:
            new_cache = (write_cache(cache[0], c_kv, cache_index), write_cache(cache[1], k_rope, cache_index))
        # Before the first decode step every cache slot beyond the block is
        # invalid, so a prefill at (static) offset 0 attends over its own block.
        at_zero = isinstance(cache_index, (int, np.integer)) and int(cache_index) == 0
        # An indexed layer (`index_topk`, models/indexer.py): the index keys are
        # the cache's third leaf; a pass or a cache longer than index_topk
        # attends to the keys each query chose, a shorter one to every key, by
        # the paths below.
        # More than one token through a cache is the prefill at offset 0 (the
        # trunk refuses every other such pass of an indexed layer), though a
        # remat'd block sees its offset as a tracer.
        many, chooses = cache is None or q_len > 1, False
        if cfg.index_topk:
            from trlx_tpu.models import indexer

            with jax.named_scope("dsa_index"):
                q_idx, k_idx, w_idx = indexer.Indexer(cfg, name="indexer")(x, c_q, rope)
            if cache is not None:
                new_cache += (write_cache(cache[2], k_idx, cache_index),)
            chooses = (q_len if many else int(cache[0].shape[1])) > cfg.index_topk
        if chooses and many:
            q = jnp.concatenate(queries(c_q, rope), axis=-1)
            kv = jnp.einsum("btc,chn->bthn", c_kv, w_kvb)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :], (b, q_len, h, dr))], axis=-1)
            mask = token_mask if token_mask is not None else jnp.ones((b, q_len), jnp.int32)
            out, stats = indexer.indexed_attention(q, k, kv[..., dn:], q_idx, w_idx, k_idx, mask, cfg, softmax_scale, dtype)
            out, stats = out.reshape(b, q_len, h * dv), stats if cache is None else None
        elif chooses:
            q_nope, q_rope = queries(c_q, rope)
            with jax.named_scope("dsa_attn"):
                q_lat = jnp.einsum("bqhn,chn->bqhc", q_nope, w_kvb[..., :dn], preferred_element_type=jnp.float32)
            o_lat, stats = indexer.indexed_read(q_lat, q_rope, q_idx, w_idx, new_cache, token_mask, cfg, softmax_scale, dtype)
            with jax.named_scope("dsa_attn"):
                out = jnp.einsum("bqhc,chv->bqhv", o_lat, w_kvb[..., dn:]).reshape(b, q_len, h * dv)
        elif cache is None or at_zero:

            def unabsorbed(c_q, c_kv, k_rope, rope, mask_or_bias):
                """[rows, q_len, h * dv] from the rows' latents: everything per
                head (q, k, v, the padded copies) lives inside."""
                rows = c_q.shape[0]
                q = jnp.concatenate(queries(c_q, rope), axis=-1)
                kv = jnp.einsum("btc,chn->bthn", c_kv, w_kvb)
                k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :], (rows, q_len, h, dr))], axis=-1)
                v = kv[..., dn:]
                if flash_mask is None:
                    return attend(q, k, v, mask_or_bias[..., :q_len], softmax_scale, dtype).reshape(rows, q_len, h * dv)
                from trlx_tpu.ops.flash_attention import flash_attention

                wide = -(-(dn + dr) // 128) * 128
                pad = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, wide - a.shape[-1])))
                with jax.named_scope("flash_attn"):
                    out = flash_attention(pad(q), pad(k), pad(v), mask_or_bias, scale=softmax_scale, causal=True)
                    return out[..., :dv].astype(dtype).reshape(rows, q_len, h * dv)

            with jax.named_scope("mla_unabsorbed"):
                operands = (c_q, c_kv, k_rope, rope, flash_mask if flash_mask is not None else attn_bias)
                group = MLA_ROW_GROUP
                if b > group and b % group == 0:
                    # A scoring pass over a whole rollout chunk: per-head keys
                    # and values of 32 rows x 1024 tokens x 64 heads are 1 GB a
                    # tensor beside a resident train state. The rows are
                    # independent, so they go through a group at a time.
                    split = lambda a: a.reshape((b // group, group) + a.shape[1:])
                    out = jax.lax.map(lambda args: unabsorbed(*args), jax.tree_util.tree_map(split, operands))
                    out = out.reshape(b, q_len, h * dv)
                else:
                    out = unabsorbed(*operands)
        else:
            with jax.named_scope("mla_absorbed"):
                q_nope, q_rope = queries(c_q, rope)
                q_lat = jnp.einsum("bqhn,chn->bqhc", q_nope, w_kvb[..., :dn], preferred_element_type=jnp.float32)
                read = ranged_read(int(cache[0].shape[1]), q_len, cache_index,
                                   attend_range=attend_latent_range, slot_major=False)
                if read is not None:
                    o_lat = read((q_lat, q_rope), new_cache[:2], attn_bias, softmax_scale, dtype)
                else:
                    with jax.named_scope("kv_read"):
                        o_lat = attend_latent(q_lat, q_rope, *new_cache[:2], attn_bias, softmax_scale, dtype)
                out = jnp.einsum("bqhc,chv->bqhv", o_lat, w_kvb[..., dn:]).reshape(b, q_len, h * dv)
        return dense(cfg.d_model, "c_proj")(out), new_cache, stats


class MLP(nn.Module):
    """The feed-forward of a "dense" layer: c_proj(act(c_fc x)), or with
    `cfg.mlp == "gated"` down(act(gate x) * up x) without biases. `width`
    overrides the hidden width (an expert layer's shared expert)."""

    cfg: LMConfig
    width: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        act = ACTIVATIONS[cfg.activation]
        width = self.width or cfg.ff_dim
        dense = lambda feats, name, bias: QDense(
            feats, dtype=cfg.compute_dtype, param_dtype=cfg.params_dtype, use_bias=bias, draw_dtype=cfg.draw_dtype, name=name)
        if cfg.mlp == "gated":
            return dense(cfg.d_model, "down_proj", False)(
                act(dense(width, "gate_proj", False)(x)) * dense(width, "up_proj", False)(x))
        return dense(cfg.d_model, "c_proj", True)(act(dense(width, "c_fc", True)(x)))


def qk_normed(cfg: LMConfig, q, k):
    """`qk_norm`: RMSNorm over each query head and each key head (one scale of a head's width for all the query
    heads, `q_norm`, one for the key heads, `k_norm`), in float32, before rotary. Called from a mixer's
    `__call__`: the two norms are that module's children."""
    with jax.named_scope("qk_norm"):
        head_norm = lambda name: nn.RMSNorm(epsilon=cfg.ln_eps, dtype=jnp.float32, param_dtype=cfg.params_dtype, name=name)
        return head_norm("q_norm")(q).astype(cfg.compute_dtype), head_norm("k_norm")(k).astype(cfg.compute_dtype)


def make_norm(cfg: LMConfig, name: str, **kwargs):
    kind = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
    return kind(epsilon=cfg.ln_eps, dtype=cfg.compute_dtype, param_dtype=cfg.params_dtype, name=name, **kwargs)


class Block(nn.Module):
    """One transformer block; sequential (gpt2) or parallel (gptj/neox)
    residual; with `cfg.sandwich_norm` a second norm (`ln_1_out`, `ln_2_out`)
    on each branch's output; with `cfg.residual_scaling` a learned scale and
    bias on both operands of each sum. `ffn` is this layer's feed-forward kind ("dense" | "experts"),
    `mixer` its mixer kind ("attention" | "mamba": models/ssm.py | "kda":
    models/kda.py | "lightning": models/lightning.py; these keep a state for a
    cache and read `token_mask` [b, q_len], the real tokens of `x`, in place of
    a bias; attention "cca", models/cca.py, and "sparse", models/sparse.py, read
    it beside the bias, "sparse" the cache's occupancy in a decode step; an
    "experts" feed-forward reads it so that a padded position takes no routed
    expert, models/moe.py). `router_state` [b, q_len,
    router_hidden]: what the router of the block below handed on
    (`cfg.router_carry`). `rope`: the pass's rotary tables (`rope_tables`), None
    without rotary positions. Returns (x, cache, expert_counts, routing, sparse_stats): the tokens
    each held expert took in this block, None for a dense one; `routing` is
    None but under `router_carry` or `router_scoring` "softmax_all": {"state":
    this block's router state (None without the carry), "top_weight": the mean
    weight of a token's first choice}; `sparse_stats` is what a "sparse"
    attention layer counted of its own choice (models/sparse.py
    SparseAttention: four sums in a pass with no cache, two in a decode step),
    None for every other layer and in a prefill."""

    cfg: LMConfig
    ffn: str = "dense"
    mixer: str = "attention"

    @nn.compact
    def __call__(self, x, attn_bias, rope, cache=None, cache_index=None,
                 flash_mask=None, window=0, use_ring=False, block_tables=None, token_mask=None, router_state=None):
        cfg = self.cfg
        ln = lambda name: make_norm(cfg, name)
        counts = routing = sparse_stats = None
        # On a partitioned mesh a pass over many tokens keeps its rows where
        # the batch split put them, at both edges of the block (inside, so
        # that a remat'd backward holds them too), and every product gathers
        # its weight (parallel/schedule.py).
        x = hold_rows(x)

        def mix(h):
            nonlocal sparse_stats
            if self.mixer == "mamba":
                from trlx_tpu.models.ssm import SSMMixer

                return SSMMixer(cfg, name="mamba")(h, token_mask, cache)
            if self.mixer == "kda":
                from trlx_tpu.models.kda import KDAMixer

                return KDAMixer(cfg, name="kda")(h, token_mask, cache)
            if self.mixer == "lightning":
                from trlx_tpu.models.lightning import LightningMixer

                return LightningMixer(cfg, name="lightning")(h, token_mask, rope, cache)
            if cfg.attention == "sparse":
                from trlx_tpu.models.sparse import SparseAttention

                if window or use_ring or block_tables is not None:
                    raise NotImplementedError("attention 'sparse' is not built for windows, the sp ring or paged caches")
                out, new, sparse_stats = SparseAttention(cfg, name="attn")(h, cache, cache_index, token_mask)
                return out, new
            if cfg.attention == "cca":
                from trlx_tpu.models.cca import CCAttention

                if window or use_ring or block_tables is not None:
                    raise NotImplementedError("attention 'cca' is not built for windows, the sp ring or paged caches")
                return CCAttention(cfg, name="attn")(h, attn_bias, rope, cache, cache_index, flash_mask, token_mask)
            if cfg.attention == "mla":
                out, new, sparse_stats = LatentAttention(cfg, name="attn")(
                    h, attn_bias, rope, cache, cache_index, flash_mask, window, use_ring, block_tables, token_mask)
                return out, new
            return Attention(cfg, name="attn")(h, attn_bias, rope, cache, cache_index, flash_mask, window, use_ring, block_tables)

        moe = None
        if self.ffn == "experts":
            from trlx_tpu.models.moe import ExpertLayer

            moe = ExpertLayer(cfg, name="moe")
        # `router_input` "block": the expert choice is made from the block's
        # input, here, ahead of attention, and carried across it (through the
        # recomputation of a remat'd block, the frozen branch's replay and a
        # decode step alike: all of them run this function)
        routed = moe.routing(x) if moe is not None and cfg.router_input == "block" else None

        def feed_forward(h):
            nonlocal counts, routing, routed
            if moe is not None:
                if cfg.router_kind == "mlp":
                    routed, state = moe.routing_with_state(h, router_state if cfg.router_carry else None)
                    if cfg.router_carry or cfg.router_scoring == "softmax_all":
                        routing = {"state": state if cfg.router_carry else None,
                                   "top_weight": jnp.mean(jax.lax.stop_gradient(routed[1][:, 0]))}
                # a padded position takes no routed expert: `token_mask` where it is the pass's own [b, q_len] (a
                # sparse or an indexed layer's decode step is handed the cache's occupancy in its place)
                pads = token_mask if token_mask is not None and token_mask.shape == h.shape[:2] else None
                h, counts = moe(h, routed=routed, token_mask=pads)
                return h
            return MLP(cfg, name="mlp")(h)

        # both residual branches of a block take `residual_multiplier`
        branch = (lambda y: y) if cfg.residual_multiplier == 1.0 else (
            lambda y: y * jnp.asarray(cfg.residual_multiplier, y.dtype))

        def joined(name, skip, f):
            """`residual_scaling`: (skip + b_s) * a_s + (f + b_f) * a_f, four learned
            vectors a sum, drawn from the seed off their neutral values."""
            near_one = lambda key, shape, dtype=jnp.float32: 1.0 + 0.05 * jax.random.normal(key, shape, dtype)
            vec = lambda part, init: self.param(f"{name}_{part}", drawn_in(cfg.draw_dtype, init), (cfg.d_model,),
                                                cfg.params_dtype).astype(skip.dtype)
            small = nn.initializers.normal(0.02)
            with jax.named_scope("residual_scaling"):
                return ((skip + vec("skip_bias", small)) * vec("skip_scale", near_one)
                        + (f.astype(skip.dtype) + vec("branch_bias", small)) * vec("branch_scale", near_one))

        if cfg.parallel_residual:
            h = ln("ln_1")(x)
            attn_out, new_cache = mix(h)
            mlp_in = ln("ln_2")(x) if cfg.use_parallel_ln else h
            x = x + branch(attn_out) + branch(feed_forward(mlp_in))
        elif cfg.residual_scaling:
            attn_out, new_cache = mix(ln("ln_1")(x))
            x = joined("res_1", x, attn_out)
            x = joined("res_2", x, feed_forward(ln("ln_2")(x)))
        else:
            # sandwich_norm: a norm on each branch's output as well as on its input
            out_norm = (lambda name, y: ln(name)(y)) if cfg.sandwich_norm else (lambda name, y: y)
            attn_out, new_cache = mix(ln("ln_1")(x))
            x = x + branch(out_norm("ln_1_out", attn_out))
            x = x + branch(out_norm("ln_2_out", feed_forward(ln("ln_2")(x))))
        return hold_rows(x), new_cache, counts, routing, sparse_stats


def make_attn_bias(
    attn_mask_kv: jnp.ndarray,
    q_len: int,
    q_offset,
    window: int = 0,
    segment_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Build the additive attention bias [b, 1, q_len, kv_len].

    attn_mask_kv: [b, kv_len] validity of each key slot (handles left padding
    — the reference instead relies on HF mask plumbing plus position-id
    correction, reference: trlx/model/accelerate_ppo_model.py:110-112).
    Causality is by buffer index: key j visible to query i iff j <= q_offset+i;
    `window > 0` additionally requires j > q_offset+i−window (gpt-neo local
    attention layers).

    ``segment_ids`` [b, q_len] (packed train batches, full-sequence passes
    only — q_len == kv_len) additionally makes the bias block-diagonal: a
    key is visible only to queries of the SAME packed segment, so the
    sequences packed into one row cannot attend across each other.
    """
    kv_len = attn_mask_kv.shape[-1]
    if jnp.ndim(q_offset) == 1:
        # Per-row write offsets (slot decode): q_offset [b] gives every row
        # its own causal frontier, so one compiled program serves slots at
        # mixed sequence lengths. causal is [b, 1, q_len, kv_len].
        q_idx = q_offset[:, None, None] + jnp.arange(q_len)[None, :, None]
        k_idx = jnp.arange(kv_len)[None, None, :]
        causal = k_idx <= q_idx
        if window > 0:
            causal = causal & (k_idx > q_idx - window)
        causal = causal[:, None, :, :]
    else:
        q_idx = q_offset + jnp.arange(q_len)[:, None]
        k_idx = jnp.arange(kv_len)[None, :]
        causal = k_idx <= q_idx
        if window > 0:
            causal = causal & (k_idx > q_idx - window)
        causal = causal[None, None, :, :]
    valid = attn_mask_kv[:, None, None, :].astype(bool) & causal
    if segment_ids is not None:
        same_seg = segment_ids[:, None, None, :] == segment_ids[:, None, :, None]
        valid = valid & same_seg
    return jnp.where(valid, 0.0, -1e9).astype(jnp.float32)


class TransformerLM(nn.Module):
    """The trunk: embeddings + N blocks + final LN (+ optional untied head).

    `__call__` supports partial-stack application for the hydra ref branch:
    with `start_layer=k` and `inputs_embeds` = branch-point hidden states, it
    replays only blocks [k..N) + ln_f + head — the functional equivalent of the
    reference's ModelBranch (reference: trlx/model/nn/ppo_models.py:102-312).
    """

    cfg: LMConfig

    @nn.compact
    def __call__(
        self,
        input_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        position_ids: Optional[jnp.ndarray] = None,
        inputs_embeds: Optional[jnp.ndarray] = None,
        cache: Optional[Tuple] = None,
        cache_index=None,
        cache_mask: Optional[jnp.ndarray] = None,
        block_tables: Optional[jnp.ndarray] = None,
        start_layer: int = 0,
        stop_layer: Optional[int] = None,
        collect_hidden_at: Optional[int] = None,
        compute_logits: bool = True,
        logits_start: int = 0,
        prepend_soft: bool = True,
        labels: Optional[jnp.ndarray] = None,
        labels_mask: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        router_state: Optional[jnp.ndarray] = None,
    ):
        """Returns dict(logits, hidden, branch_hidden, cache).

        - Training/prefill: cache=None, attention over the q_len itself.
        - Decode: cache=(per-layer (k,v)), cache_mask [b, kv_len] marks valid
          key slots, cache_index = write offset (static-shape dynamic slice).
        - Paged decode: `block_tables` [b, blocks_per_slot] int32 switches the
          per-layer cache operand to ONE shared block pool
          ([n_blocks, block_size, h, d], see ``init_paged_cache``); cache_mask
          and cache_index then address the row's VIRTUAL cache of kv_len =
          blocks_per_slot * block_size — all position/bias semantics are
          unchanged, only physical placement is table-indirect.
        - `collect_hidden_at=k` also returns the hidden state entering block k
          (the hydra branch point, reference:
          trlx/model/nn/ppo_models.py:351-368's `forward_hydra` hidden pick).
        - `labels` [b, S] switches the head to the fused-logprob mode: instead
          of materializing [b, S, V] logits, the result dict carries fp32
          ``logprobs``/``lse``/``entropy`` [b, S] — label logprob, logsumexp,
          and entropy at positions logits_start..logits_start+S-1 — computed
          by the vocab-streaming Pallas kernel when eligible (see
          trlx_tpu.ops.fused_logprob; LMConfig.extra['fused_logprob'] ∈
          auto|force|off) and by the exact materializing log_softmax chain
          otherwise. ``labels_mask`` zeros masked rows on either path.
          ``logits`` is None in this mode: not existing is the point.
        - ``expert_counts`` [expert layers run, experts held] int32: how many
          tokens each held expert took in this call; None without expert layers.
        - ``exit_probs`` [b, q_len, n_loops] float32 (`exit_gate`, no cache,
          start_layer 0): the exit distribution over the loops.
        - `router_state` [b, q_len, router_hidden] (`router_carry`, with
          `start_layer` > 0): the router state block start_layer - 1 handed
          on, the replay's second input; `collect_hidden_at=k` returns it as
          ``branch_router_state`` beside ``branch_hidden``.
          ``router_top_weight``: the mean weight of a token's first expert
          choice over the expert layers run (`router_scoring` "softmax_all").
        - `segment_ids` [b, q_len] (packed train batches; full-sequence
          passes only) makes attention block-diagonal per packed segment —
          the einsum bias path is forced, since the flash/ring kernels'
          masks cannot express segments.
        """
        cfg = self.cfg
        stop_layer = cfg.n_layer if stop_layer is None else stop_layer
        assert segment_ids is None or cache is None, (
            "segment packing is a train-batch construct; decode caches are unpacked"
        )

        drawn = {"embedding_init": drawn_in(cfg.draw_dtype, nn.initializers.normal(cfg.embed_init_std))} if cfg.embed_init_std else {}
        wte = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.compute_dtype, param_dtype=cfg.params_dtype, name="wte", **drawn
        )

        def lookup(embed, ids):
            """`embed(ids)` (`nn.Embed.__call__`), with the table as this
            lookup uses it (parallel/schedule.py)."""
            (table,) = embed.promote_dtype(embed.embedding, dtype=embed.dtype, inexact=False)
            return jnp.take(use_weight(table, embed.path + ("embedding",), ids.size, lookup=True), ids, axis=0)

        with jax.named_scope("embed"):
            if inputs_embeds is None:
                if cfg.onehot_embed and cache is None:
                    # Training/scoring forward on a sharded mesh: one-hot matmul
                    # (see LMConfig.onehot_embed). Decode keeps the gather.
                    onehot = jax.nn.one_hot(input_ids, cfg.vocab_size, dtype=cfg.compute_dtype)
                    x = onehot @ use_weight(wte.embedding.astype(cfg.compute_dtype), wte.path + ("embedding",), input_ids.size)
                else:
                    x = lookup(wte, input_ids)
                if cfg.embedding_multiplier != 1.0:
                    x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
            else:
                x = inputs_embeds.astype(cfg.compute_dtype)

        b, q_len = x.shape[:2]
        if attention_mask is None:
            attention_mask = jnp.ones((b, q_len), dtype=jnp.int32)

        # Soft-prompt prefix: prepend learned embeddings ahead of the (left-
        # padded) sequence; outputs are sliced back so callers see the
        # original length. `prepend_soft=False` on single-token decode steps
        # (the prefix already sits in the KV cache from prefill).
        n_soft = cfg.n_soft_tokens if (cfg.n_soft_tokens > 0 and start_layer == 0) else 0
        if cfg.n_soft_tokens > 0 and start_layer == 0:
            soft = self.param(
                "soft_prompt",
                nn.initializers.normal(stddev=0.02),
                (cfg.n_soft_tokens, cfg.d_model),
                cfg.params_dtype,
            )
            if not prepend_soft:
                n_soft = 0
        if n_soft:
            x = jnp.concatenate(
                [jnp.broadcast_to(soft.astype(cfg.compute_dtype)[None], (b, n_soft, cfg.d_model)), x], axis=1
            )
            attention_mask = jnp.concatenate(
                [jnp.ones((b, n_soft), dtype=attention_mask.dtype), attention_mask], axis=1
            )
            if position_ids is not None:
                position_ids = jnp.concatenate(
                    [jnp.broadcast_to(jnp.arange(n_soft)[None], (b, n_soft)), position_ids + n_soft], axis=1
                )
            q_len = q_len + n_soft
        if position_ids is None:
            if cache is not None and cache_mask is not None:
                # Decode mode: derive absolute positions from the cache
                # occupancy mask (which already includes the query slots),
                # sliced at the write offset — NOT from the 1-token query mask.
                full_pos = jnp.maximum(jnp.cumsum(cache_mask, axis=-1) - 1, 0)
                if jnp.ndim(cache_index) == 1 and q_len == 1:
                    # Per-row write offsets (slot decode, q_len == 1): each
                    # row reads the position at its own offset.
                    position_ids = jnp.take_along_axis(
                        full_pos, cache_index.astype(jnp.int32)[:, None], axis=1
                    )
                elif jnp.ndim(cache_index) == 1:
                    # Per-row offsets with a multi-token query (speculative
                    # verify window): positions at offset..offset+q_len-1 per
                    # row, clamped so rows near the buffer tail gather in
                    # bounds (those rows' extra slots are masked anyway).
                    kv_len = full_pos.shape[-1]
                    ix = cache_index.astype(jnp.int32)[:, None] + jnp.arange(
                        q_len, dtype=jnp.int32
                    )[None, :]
                    position_ids = jnp.take_along_axis(
                        full_pos, jnp.minimum(ix, kv_len - 1), axis=1
                    )
                else:
                    position_ids = jax.lax.dynamic_slice_in_dim(full_pos, cache_index, q_len, axis=1)
            else:
                # Left-pad aware positions: cumsum over valid tokens
                # (reference: trlx/model/accelerate_ppo_model.py:110-112).
                position_ids = jnp.maximum(jnp.cumsum(attention_mask, axis=-1) - 1, 0)

        if start_layer == 0 and cfg.pos_type == "learned":
            wpe = nn.Embed(
                cfg.max_position, cfg.d_model, dtype=cfg.compute_dtype, param_dtype=cfg.params_dtype, name="wpe"
            )
            with jax.named_scope("embed"):
                x = x + lookup(wpe, position_ids)
        if start_layer == 0:
            # graftnum probe tap (observability/numerics.py): identity unless
            # the NaN-provenance bisector's EAGER re-forward is live — inside
            # a trace (the permanent hot-path state) this is one global load
            # returning x, so the compiled program is tap-free.
            x = obs_numerics.probe_tap("embed", x)

        # every layer and every loop of the pass rotates by the same positions:
        # one pair of tables, an operand of every block (None without rotary)
        rope = rope_tables(cfg, position_ids)
        use_ring = ring_eligible(cfg, q_len, cache is not None, b)
        # Prefill at a STATIC zero write offset may use flash over the local
        # block (see flash_eligible); decode steps pass a traced cache_index.
        prefill_at_zero = (
            cache is not None
            and isinstance(cache_index, (int, np.integer))
            and int(cache_index) == 0
        )
        use_flash = use_ring or flash_eligible(cfg, q_len, cache is not None, prefill_at_zero)
        ring_cache = cfg.window_cache == "ring" and cache is not None
        if ring_cache and (block_tables is not None or jnp.ndim(cache_index) != 0 or (q_len > 1 and not prefill_at_zero)):
            raise NotImplementedError(
                "a ring cache takes a prefill at write offset 0 or one token a step at one offset for the "
                "whole batch (the static generate path): no block table, per-row offset or verify window")
        if cfg.has_state and (segment_ids is not None or (cache is not None and (
                block_tables is not None or jnp.ndim(cache_index) != 0 or (q_len > 1 and not prefill_at_zero)))):
            raise NotImplementedError(
                f"a {cfg.state_layer_name} layer takes a pass with no cache, a prefill at write "
                "offset 0 or one token a step for the whole batch (the static generate path): no block table, "
                "per-row offset, verify window or packed segments")
        if cfg.attention == "cca" and (segment_ids is not None or (cache is not None and (
                block_tables is not None or jnp.ndim(cache_index) != 0 or (q_len > 1 and not prefill_at_zero)))):
            raise NotImplementedError(
                "attention 'cca' takes a pass with no cache, a prefill at write offset 0 or one token a step for the "
                "whole batch (the static generate path): its convolutions' window and shifted value are one a row, so "
                "no block table, per-row offset, verify window or packed segments")
        if cfg.attention == "sparse" and (segment_ids is not None or (cache is not None and (
                block_tables is not None or jnp.ndim(cache_index) != 0 or (q_len > 1 and not prefill_at_zero)))):
            raise NotImplementedError(
                "attention 'sparse' takes a pass with no cache, a prefill at write offset 0 or one token a step for the "
                "whole batch (the static generate path): its compressed keys lie in each row's own grid from the row's "
                "first slot, so no block table, per-row offset, verify window or packed segments")
        if cfg.index_topk and (segment_ids is not None or (cache is not None and (
                block_tables is not None or jnp.ndim(cache_index) != 0 or (q_len > 1 and not prefill_at_zero)))):
            raise NotImplementedError(
                "an indexed latent layer (index_topk) takes a pass with no cache, a prefill at write offset 0 or one token a "
                "step for the whole batch (the static generate path): its queries choose their keys by slot under one "
                "causal edge, so no block table, per-row offset, verify window or packed segments")
        # an indexed latent layer past index_topk tokens: its queries mask by the tokens' own mask and their choice
        chooses = bool(cfg.index_topk) and q_len > cfg.index_topk
        if cfg.router_carry and (start_layer > 0) != (router_state is not None):
            raise ValueError("router_carry: a pass from start_layer > 0 (the frozen branch's replay) takes the router "
                             "state of the block below as `router_state`, a pass from the first block takes none")
        if segment_ids is not None:
            # Packed segments need a block-diagonal mask; the flash/ring
            # kernels' (causal × key-validity) masks cannot express that.
            use_ring = use_flash = False
        if use_flash:
            attn_bias = local_bias = None
            flash_mask = attention_mask.astype(jnp.float32)
        elif cfg.attention == "sparse" or chooses:  # its layers mask by the tokens' own mask and their choice: no bias is read
            attn_bias = local_bias = flash_mask = None
            kv_mask = cache_mask if cache_mask is not None else attention_mask
        else:
            flash_mask = None
            if cache is not None:
                kv_mask = cache_mask if cache_mask is not None else attention_mask
                bias_mask, bias_offset = kv_mask, cache_index
            else:
                bias_mask, bias_offset = attention_mask, 0
            attn_bias = make_attn_bias(bias_mask, q_len, bias_offset, segment_ids=segment_ids)
            local_bias = None
            if ring_cache and q_len == 1:
                # a decode step over ring caches: the window layers' bias is
                # over their ring's slots
                (slots,) = {int(cache[i][0].shape[1]) for i in range(cfg.n_layer) if layer_window(cfg, i)}
                local_bias = ring_bias(kv_mask, cache_index, slots)
            elif any(t == "local" for t in cfg.attention_layers):
                local_bias = make_attn_bias(
                    bias_mask, q_len, bias_offset, window=cfg.window_size, segment_ids=segment_ids
                )
                if ring_cache:  # the prefill: a ring layer attends over its own block
                    local_bias = local_bias[..., :q_len]

        block_cls = Block
        if cfg.remat:
            # window/use_ring are Python control-flow values inside the block
            # (`if use_ring:`) — they must stay STATIC under remat tracing or
            # TracerBoolConversionError fires on the flash/ring paths.
            # Argnums count self as 0: x=1 ... window=7, use_ring=8.
            policy = None
            if cfg.remat_policy == "dots":  # validated in LMConfig.__post_init__
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            # On a partitioned mesh the recomputed block gathers its weights
            # again (ZeRO-3): left to common-subexpression elimination, the
            # forward's gathers of every layer stay alive for the backward,
            # 11 GB at GPT-J-6B (PERF.md §6, PR 29).
            # With state-space layers likewise, on one chip too: merged with
            # the forward, the recomputation keeps every layer's projections
            # and scan products alive, 34.7 GB for a 16 GB chip at 40 layers
            # (PERF.md §6, PR 32); a gated delta-rule layer is held to the same,
            # and keeps the output of its chunked pass: the pass is recomputed
            # in its row groups' own backward (models/kda.py), so the block's
            # recomputation need not run it a third time.
            # A looped stack likewise: 48 applications of 12 blocks kept every
            # gate and up projection alive, 19.5 GB (PERF.md §6, PR 37).
            # A block-selected sparse layer keeps the blocks its queries chose (a
            # few MB of bool): the choice carries no gradient, so the block's
            # recomputation need not make it again (models/sparse.py).
            # An indexed latent layer keeps its queries' chosen keys likewise (models/indexer.py), and its
            # long passes are held apart from the forward too: merged, the 8,192-token train step of five blocks
            # at d 6144 kept the forward's projections alive and wanted 9.5 GB of temporaries (PERF.md §6, PR 53).
            # Both keep the joined output of their many-token pass too, ONE array a layer in the model's dtype
            # ([b, T, H, D]: 101 MB at MiniCPM-SALA's 12,288 tokens, 67 MB at GLM-5's 8,192): it is all the block's
            # recomputation wants of the spans' forward loops, which then fall out of it. A chunk's float32 scores
            # are still made again in the chunk's own backward pass, where they are read (models/sparse.py
            # `over_spans`): the pass runs forward twice a step, not three times (PERF.md §6, PR 54).
            span_pass = cfg.attention == "sparse" or chooses
            named = [name for name, held in ((KDA_SCAN_OUT, cfg.has_kda), (SPARSE_CHOSEN, span_pass), (SPAN_PASS_OUT, span_pass)) if held]
            if named:
                kept = jax.checkpoint_policies.save_only_these_names(*named)
                policy = kept if policy is None else jax.checkpoint_policies.save_from_both_policies(policy, kept)
            block_cls = nn.remat(
                Block, prevent_cse=partitioned() or cfg.has_state or cfg.n_loops > 1 or chooses, static_argnums=(7, 8), policy=policy
            )

        looped = cfg.n_loops > 1
        if looped and (segment_ids is not None or block_tables is not None or stop_layer != cfg.n_layer or (
                cache is not None and (jnp.ndim(cache_index) != 0 or (q_len > 1 and not prefill_at_zero)))):
            raise NotImplementedError(
                "a looped stack (n_loops > 1) takes a pass with no cache, a prefill at write offset 0 or one token a "
                "step for the whole batch (the static generate path): no block table, per-row offset, verify "
                "window, packed segments or stop_layer")

        branch_hidden = branch_router_state = None
        new_cache = [] if cache is not None else None
        expert_counts, top_weights, sparse_stats = [], [], []
        if cfg.router_carry and router_state is None:  # nothing lies below the first block
            router_state = jnp.zeros((b, q_len, cfg.router_hidden), jnp.float32)
        # All blocks are *defined* every call so the param structure is
        # identical regardless of start/stop — only [start, stop) execute. A
        # looped stack calls the same N modules n_loops times (the same
        # parameters; a trained block's gradient is the sum over its uses).
        def stack_modules(parent):
            """(blocks, final norm, exit gate) as children of `parent`: this
            module, or its clone inside the scan over the loops."""
            blocks = [block_cls(cfg, cfg.ffn_layers[i] if cfg.ffn_layers else "dense", cfg.mixer(i), name=f"h_{i}",
                                parent=parent) for i in range(cfg.n_layer)]
            gate = nn.Dense(1, dtype=jnp.float32, param_dtype=cfg.params_dtype, name="exit_gate", parent=parent,
                            kernel_init=drawn_in(cfg.draw_dtype, nn.initializers.normal(0.02))) if cfg.exit_gate else None
            return blocks, make_norm(cfg, "ln_f", parent=parent), gate

        def one_pass(blocks, x, loop, first):
            """Blocks [first, stop_layer) once; `loop` is an int where the pass
            is unrolled (it names the cache entries and the taps), None in the scan."""
            nonlocal branch_hidden, branch_router_state, router_state
            for i, block in enumerate(blocks):
                if i < first or i >= stop_layer:
                    continue
                if collect_hidden_at is not None and i == collect_hidden_at and loop == 0:
                    branch_hidden, branch_router_state = x, router_state
                layer_cache = cache[loop * cfg.n_layer + i] if cache is not None else None
                window = layer_window(cfg, i)
                layer_bias = local_bias if window else attn_bias
                # a layer that keeps a state reads the tokens' mask itself, in place of a bias; an expert layer
                # reads it to leave the pads unrouted (models/moe.py)
                token_mask = (attention_mask,) if (cfg.mixer(i) != "attention" or cfg.attention in ("cca", "sparse")
                                                   or cfg.index_topk or block.ffn == "experts") else ()
                if (cfg.attention == "sparse" or cfg.index_topk) and cfg.mixer(i) == "attention" and cache is not None and q_len == 1:
                    token_mask = (kv_mask,)  # a decode step: the cache's occupancy tells each row's first slot
                if cfg.router_carry:  # the block's last operand, after a token mask or None in its place
                    token_mask = (token_mask or (None,)) + (router_state,)
                x, layer_new_cache, layer_counts, routing, layer_sparse = block(
                    x, layer_bias, rope, layer_cache, cache_index,
                    flash_mask, window, use_ring, block_tables, *token_mask,
                )
                if layer_sparse is not None:
                    sparse_stats.append(jnp.stack(layer_sparse))
                if routing is not None:
                    router_state = routing["state"]
                    top_weights.append(routing["top_weight"])
                if loop is not None:
                    x = obs_numerics.probe_tap(f"block_{i}" if loop == 0 else f"loop_{loop}_block_{i}", x)
                if cache is not None:
                    new_cache.append(layer_new_cache)
                if layer_counts is not None:
                    expert_counts.append(layer_counts)
            return x

        def loop_end(ln_f, gate, x):
            """The ONE final norm, at the end of every loop: its output is the
            loop's output and the next loop's input; the gate reads it."""
            with jax.named_scope("loop_norm"):
                x = ln_f(x)
            if not gated:
                return x, None
            with jax.named_scope("exit_gate"):
                return x, gate(x)[..., 0]

        blocks, ln_f, gate = stack_modules(self)
        # the exit gate reads each loop's output but the last; a pass through
        # the cache (a decode step) and the branch replay leave it out
        gated = cfg.exit_gate and cache is None and start_layer == 0
        # Loops unrolled in Python: all of them through the cache, where every
        # (loop, layer) pair has leaves of its own (and while the parameters
        # are made). A pass with no cache (train step, scoring, the replay)
        # runs ONE traced stack under a scan over the loops, the parameters
        # broadcast to every iteration; `start_layer` (the hydra replay) and
        # `collect_hidden_at` cut into the FIRST loop only, which is then
        # unrolled before the scan: every later loop depends on the replayed
        # blocks and runs whole.
        if not looped or cache is not None or self.is_initializing():
            unrolled = cfg.n_loops
        else:
            unrolled = int(start_layer > 0 or collect_hidden_at is not None)
        readings = []  # the gate's logit of each loop's output, [b, t, loops] a piece
        for loop in range(unrolled):
            x = one_pass(blocks, x, loop, start_layer if loop == 0 else 0)
            if looped:
                x, reading = loop_end(ln_f, gate, x)
                readings.append(reading[..., None] if gated else None)
        if unrolled < cfg.n_loops:

            def body(clone, x, _):
                blocks, ln_f, gate = stack_modules(clone)
                return loop_end(ln_f, gate, one_pass(blocks, x, None, 0))

            x, scanned = nn.scan(body, variable_broadcast="params", split_rngs={"params": False},
                                 length=cfg.n_loops - unrolled)(self, x, None)
            readings.append(jnp.moveaxis(scanned, 0, -1) if gated else None)
        # [b, t, n_loops - 1]: the last loop's reading decides nothing
        gate_logits = jnp.concatenate(readings, axis=-1)[..., :-1] if gated else None

        if not looped:
            x = ln_f(x)
        x = obs_numerics.probe_tap("ln_f", x)
        if collect_hidden_at is not None and collect_hidden_at == cfg.n_layer:
            branch_hidden = x

        exit_probs = None
        if gate_logits is not None:
            # p_r = lambda_r prod_{j<r} (1 - lambda_j); the last loop takes what is left
            with jax.named_scope("exit_gate"):
                lam = jax.nn.sigmoid(gate_logits)  # [b, t, n_loops - 1]
                stay = jnp.cumprod(1.0 - lam, axis=-1)
                before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]], axis=-1)
                exit_probs = jnp.concatenate([lam * before, stay[..., -1:]], axis=-1)  # [b, t, n_loops]

        if n_soft:
            # Drop the soft-prefix positions: callers see the original length.
            # (Hydra branch replay is incompatible with soft prompts — the
            # branch would need the prefix context; soft-prompt training uses
            # a full frozen ref copy instead.)
            x = x[:, n_soft:]
            if branch_hidden is not None:
                branch_hidden = branch_hidden[:, n_soft:]

        logits = None
        logprobs = lse = entropy = None
        # logits / logits_scaling, in every head alike: the head is linear and
        # has no bias where a family scales, so its input is divided instead
        unscaled = (lambda h: h) if cfg.logits_scaling == 1.0 else (
            lambda h: h * jnp.asarray(1.0 / cfg.logits_scaling, h.dtype))
        # The head's weight moves as the trunk's do: by the tokens of the pass
        # (the rows `hold_rows` kept in place), not the positions it evaluates.
        with jax.named_scope("lm_head"):
            if labels is not None:
                # Fused head mode: the [b, S, V] logits are never materialized —
                # the vocab projection streams through the Pallas kernel (or the
                # exact log_softmax chain when ineligible). The label length S
                # selects how many head positions are evaluated: callers that
                # previously computed logits[:, :-1] simply pass S = len-1 labels.
                from trlx_tpu.ops.fused_logprob import routed_logprob

                S = labels.shape[1]
                x_head = x[:, logits_start:] if logits_start else x
                x_head = unscaled(x_head[:, :S])
                if cfg.tie_word_embeddings:
                    w_head, b_head, tied = use_weight(wte.embedding, wte.path + ("embedding",), b * q_len), None, True
                else:
                    w_head, b_head = HeadParams(
                        cfg.vocab_size,
                        param_dtype=cfg.params_dtype,
                        use_bias=cfg.extra.get("lm_head_bias", False),
                        draw_dtype=cfg.draw_dtype,
                        name="lm_head",
                    )(x_head.shape[-1], b * q_len)
                    tied = False
                logprobs, lse, entropy = routed_logprob(
                    x_head,
                    w_head,
                    labels,
                    b_head,
                    tied=tied,
                    mode=cfg.extra.get("fused_logprob", "auto"),
                    mask=labels_mask,
                )
            elif compute_logits:
                # RL losses/scoring only need logits from the first response
                # position on — slicing before the head skips ~P/T of the
                # vocab-projection FLOPs and the fp32 logit memory.
                x_head = unscaled(x[:, logits_start:] if logits_start else x)
                if cfg.tie_word_embeddings:
                    # `wte.attend(x_head)`, with the table as this product uses it
                    query, table = wte.promote_dtype(x_head, wte.embedding, dtype=wte.dtype)
                    logits = jnp.dot(query, use_weight(table, wte.path + ("embedding",), b * q_len).T)
                else:
                    logits = QDense(
                        cfg.vocab_size,
                        dtype=cfg.compute_dtype,
                        param_dtype=cfg.params_dtype,
                        use_bias=cfg.extra.get("lm_head_bias", False),
                        pass_tokens=b * q_len,
                        draw_dtype=cfg.draw_dtype,
                        name="lm_head",
                    )(x_head)

        return {
            "logits": logits,
            "hidden": x,
            "branch_hidden": branch_hidden,
            "cache": tuple(new_cache) if new_cache is not None else None,
            # [expert layers run, experts held]: tokens each held expert took
            # in this call (models/moe.py); None for a model without them.
            "expert_counts": jnp.stack(expert_counts) if expert_counts else None,
            # `router_carry`: the router state entering block `collect_hidden_at`, float32
            "branch_router_state": branch_router_state,
            "router_top_weight": jnp.mean(jnp.stack(top_weights)) if top_weights else None,
            # attention "sparse", summed over its layers, float32. A pass with no cache: [4], (kept pairs,
            # causal pairs, chosen blocks, query-groups). A decode step: [2], (the share of its filled
            # slots a step's softmax saw, summed over rows and K/V heads; their count); an indexed latent
            # layer's decode step (`index_topk`) counts the same two of the slots it gathered, summed over rows
            "sparse_sums": sum(sparse_stats) if sparse_stats and cache is None and not cfg.index_topk else None,
            "sparse_read": sum(sparse_stats) if sparse_stats and cache is not None else None,
            # an indexed latent layer where it chooses, a pass with no cache: [2], (chosen pairs, causal pairs)
            "dsa_sums": sum(sparse_stats) if sparse_stats and cache is None and cfg.index_topk else None,
            # [b, t, n_loops] float32: where the exit gate would leave the loop
            # (a pass with no cache over a gated looped stack); None otherwise.
            "exit_probs": exit_probs,
            "logprobs": logprobs,
            "lse": lse,
            "entropy": entropy,
        }


def quantize_kv(x: jnp.ndarray, probe=None, probe_class: str = "kv"):
    """[b, t, h, d] → (int8 values, [b, t, h] fp32 absmax scales).

    ``probe`` (graftnum error probe): accumulates the int8 round-trip error
    under ``probe_class`` in the same ``[max_abs_err, sum_sq_err,
    sum_sq_signal, count]`` layout as ``quantize_weights``. The decode hot
    path passes nothing — default-None keeps the traced program identical."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    if probe is not None:
        err = xf - q.astype(jnp.float32) * scale[..., None]
        slot = probe.setdefault(
            probe_class, [jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), 0]
        )
        slot[0] = jnp.maximum(slot[0], jnp.max(jnp.abs(err)))
        slot[1] = slot[1] + jnp.sum(err * err)
        slot[2] = slot[2] + jnp.sum(xf * xf)
        slot[3] = slot[3] + int(xf.size)
    return q, scale


def init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None):
    """Allocate an empty KV cache pytree, as the attention kind and the
    layer's kind keep it: "mha": per-layer (k, v) [b, T, kv_heads, hd], or
    (k_i8, v_i8, k_scale, v_scale) with kv_cache_quant (scales [b, T,
    kv_heads]), T = `max_len`, but min(window_size, max_len) on a window layer
    under window_cache "ring"; "mla": per-layer (c_kv [b, T, kv_lora_rank],
    k_rope [b, T, qk_rope_head_dim]), shared by all heads, and with an indexer
    (`index_topk`) its keys k_idx [b, T, index_head_dim]; a "mamba" layer
    (models/ssm.py): (conv [b, ssm_conv - 1, channels], state [b, ssm_heads,
    ssm_head_dim, ssm_state] float32), no slot axis whatever `max_len`; a
    "kda" layer (models/kda.py) likewise: (conv [b, kda_conv - 1, 3 x channels],
    state [b, kda_heads, kda_head_dim, kda_head_dim] float32), beside the
    latent leaves of the "mla" layers of the same stack; "cca" (models/cca.py):
    (k, v) [b, T, kv_heads, hd] and, with no slot axis, (window [b, cca_time0 +
    cca_time1 - 2, (n_head + kv_heads) hd], shifted [b, 1, kv_heads / 2 hd]); a
    "lightning" layer (models/lightning.py): (state [b, lightning_heads,
    lightning_head_dim, lightning_head_dim] float32,), nothing else; "sparse"
    (models/sparse.py): (k, v) [b, T, kv_heads, hd] and the compressed keys
    [b, (T - sparse_kernel) // sparse_stride + 1, kv_heads, hd] in each row's own grid. A
    looped stack (n_loops > 1) keeps n_loops * n_layer groups, entry
    loop * n_layer + layer: loop r's layer reads what loop r's layer wrote."""
    if cfg.kv_cache_quant:
        assert dtype is None, "kv_cache_quant caches are int8; dtype not honored"
    dtype = dtype or cfg.compute_dtype

    def layer(i):
        if cfg.mixer(i) != "attention":
            from trlx_tpu.models import kda, lightning, ssm

            shapes = {"mamba": ssm, "kda": kda, "lightning": lightning}[cfg.mixer(i)].cache_shapes(cfg, batch)
            return tuple(jnp.zeros(shape, leaf_dtype) for shape, leaf_dtype in shapes)
        if cfg.attention == "sparse":
            from trlx_tpu.models import sparse

            return tuple(jnp.zeros(shape, leaf_dtype) for shape, leaf_dtype in sparse.cache_shapes(cfg, batch, max_len))
        if cfg.attention == "mla":
            widths = (cfg.kv_lora_rank, cfg.qk_rope_head_dim) + ((cfg.index_head_dim,) if cfg.index_topk else ())
            return tuple(jnp.zeros((batch, max_len, width), dtype) for width in widths)
        if cfg.attention == "cca":
            from trlx_tpu.models import cca

            return tuple(jnp.zeros(shape, leaf_dtype) for shape, leaf_dtype in cca.cache_shapes(cfg, batch, max_len))
        sshape = (batch, ring_slots(cfg, i, max_len) or max_len, cfg.kv_heads)
        shape = sshape + (cfg.head_dim,)
        if cfg.kv_cache_quant:
            return (
                jnp.zeros(shape, dtype=jnp.int8),
                jnp.zeros(shape, dtype=jnp.int8),
                jnp.ones(sshape, dtype=jnp.float32),
                jnp.ones(sshape, dtype=jnp.float32),
            )
        return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)

    # a looped stack: one leaf group a (loop, layer) pair, loop-major
    return tuple(layer(entry % cfg.n_layer) for entry in range(cfg.cache_entries))


def init_paged_cache(cfg: LMConfig, n_blocks: int, block_size: int, dtype=None):
    """Allocate the shared paged KV pool: per-layer (k, v) pools
    [n_blocks, block_size, n_head, hd], or (k_i8, v_i8, k_scale, v_scale)
    with kv_cache_quant — the paged twin of ``init_cache``. Zero/one init
    matters: freed blocks are never scrubbed, and the trash block (index 0,
    reserved by the engine pool) absorbs dead rows' clamped writes — masked
    reads weight stale content by an exact softmax zero, which only stays
    zero if the content (values AND scales) is finite."""
    if cfg.attention != "mha" or cfg.window_cache != "span" or cfg.has_state or cfg.n_loops > 1:
        raise NotImplementedError(
            f"the paged pool is not built for attention {cfg.attention!r}, window_cache {cfg.window_cache!r}, a "
            "state-space, lightning or kda layer (a state has no slots to page) or a looped stack (one table a layer, where a "
            "looped stack keeps keys a (loop, layer) pair)")
    shape = (n_blocks, block_size, cfg.kv_heads, cfg.head_dim)
    if cfg.kv_cache_quant:
        assert dtype is None, "kv_cache_quant caches are int8; dtype not honored"
        sshape = (n_blocks, block_size, cfg.kv_heads)
        return tuple(
            (
                jnp.zeros(shape, dtype=jnp.int8),
                jnp.zeros(shape, dtype=jnp.int8),
                jnp.ones(sshape, dtype=jnp.float32),
                jnp.ones(sshape, dtype=jnp.float32),
            )
            for _ in range(cfg.n_layer)
        )
    dtype = dtype or cfg.compute_dtype
    zero = lambda: jnp.zeros(shape, dtype=dtype)
    return tuple((zero(), zero()) for _ in range(cfg.n_layer))


def cache_partition_spec(cfg: LMConfig, leaf_ndim: int, layer: int = 0):
    """PartitionSpec of one leaf of `init_cache`'s pytree, in entry `layer`
    (a looped stack: loop * n_layer + layer, the layer's spec in every loop):
    batch over the data axes, heads over tp (grouped keys: the kv_heads; a
    ring layer's leaves have the same axes, fewer slots). An "mla" cache has
    no head axis: one latent a token serves every head, so it is whole on
    every tp shard. A "mamba", "kda" or "lightning" layer: the state's rows over
    the data axes and its heads over tp, the convolution's window whole on every
    tp shard. A "sparse" layer's compressed keys have the axes of its keys."""
    from jax.sharding import PartitionSpec

    from trlx_tpu.parallel.mesh import AXIS_TP, DATA_AXES

    if cfg.mixer(layer % cfg.n_layer) != "attention":
        return PartitionSpec(DATA_AXES, AXIS_TP, None, None) if leaf_ndim == 4 else PartitionSpec(DATA_AXES, None, None)
    if cfg.attention == "mla":
        return PartitionSpec(DATA_AXES, None, None)
    if cfg.attention == "cca" and leaf_ndim == 3:  # the window and the shifted value: whole on every tp shard
        return PartitionSpec(DATA_AXES, None, None)
    # 4-D leaves are k/v ([b, T, h, d]); 3-D leaves are the int8 cache's
    # per-slot scales ([b, T, h]).
    return PartitionSpec(DATA_AXES, None, AXIS_TP, None) if leaf_ndim == 4 else PartitionSpec(DATA_AXES, None, AXIS_TP)


def cache_bytes(cfg: LMConfig, batch: int, max_len: int) -> int:
    """Bytes of the cache `init_cache(cfg, batch, max_len)` allocates, all
    layers, from its own shapes: the counter `rollout/cache_bytes` at the
    generate program's batch and length."""
    return tree_size_bytes(jax.eval_shape(lambda: init_cache(cfg, batch, max_len)))


def ring_cache_bytes(cfg: LMConfig, batch: int, max_len: int) -> int:
    """The part of `cache_bytes` that the window layers' rings hold
    (window_cache "ring"), from `init_cache`'s own shapes: over
    `rollout/cache_bytes` it is the counter `rollout/ring_cache_share`."""
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    return tree_size_bytes([cache[i] for i in range(cfg.n_layer) if ring_slots(cfg, i, max_len)])


def state_bytes(cfg: LMConfig, batch: int) -> int:
    """The part of `cache_bytes` that does not grow with the length: what the
    layers with a recurrent state hold ("mamba", "kda": state and convolution
    window; "lightning": the state) and what "cca" layers keep beside their slots (`cca_state_bytes`).
    The counter `rollout/state_bytes`, from `init_cache`'s own shapes."""
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, 1))
    return tree_size_bytes([cache[i] for i in range(cfg.n_layer) if cfg.mixer(i) != "attention"]) + cca_state_bytes(cfg, batch)


def cca_state_bytes(cfg: LMConfig, batch: int) -> int:
    """The part of `cache_bytes` that "cca" layers hold beside keys and values
    (the convolutions' window and the shifted value, whatever the length): the
    counter `rollout/cca_state_bytes`, from `init_cache`'s own shapes."""
    if cfg.attention != "cca":
        return 0
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, 1))
    return tree_size_bytes([cache[i][2:] for i in range(cfg.n_layer) if cfg.mixer(i) == "attention"])


def index_key_bytes(cfg: LMConfig, batch: int, max_len: int) -> int:
    """The part of `cache_bytes` that the indexers' keys take (`index_topk`): the
    counter `rollout/index_key_bytes`, from `init_cache`'s own shapes."""
    if not cfg.index_topk:
        return 0
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    return tree_size_bytes([cache[i][2] for i in range(cfg.n_layer) if cfg.mixer(i) == "attention"])


def compressed_key_bytes(cfg: LMConfig, batch: int, max_len: int) -> int:
    """The part of `cache_bytes` that "sparse" layers' compressed keys take: the
    counter `rollout/compressed_key_bytes`, from `init_cache`'s own shapes."""
    if cfg.attention != "sparse":
        return 0
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    return tree_size_bytes([cache[i][2] for i in range(cfg.n_layer) if cfg.mixer(i) == "attention"])


def cache_bytes_per_token(cfg: LMConfig) -> int:
    """Bytes the cache holds a token, all layers (a looped stack: all
    n_loops * n_layer entries): the counter
    `rollout/cache_bytes_per_token`, from `init_cache`'s own shapes at one
    row of one token. A ring layer counts its ring once: one slot, like a
    full-span layer's, though past window_size tokens it grows no further
    (`rollout/cache_bytes` is the whole allocation). A state-space layer
    holds nothing a token: its state is `rollout/state_bytes_per_row`. A
    "sparse" layer's keys and values count; its compressed keys (one every
    sparse_stride tokens) are `rollout/compressed_key_bytes`. An indexed
    latent layer's index keys count: one a token, like the latent."""
    return cache_bytes(cfg, 1, 1) - state_bytes(cfg, 1) - compressed_key_bytes(cfg, 1, 1)


def decode_step_bytes(cfg: LMConfig, batch: int, keys_read: float, weight_bytes: int,
                      stack_bytes: int = 0, cache_len: int = 0) -> Tuple[int, int]:
    """(bytes a decode step of the static generate path must move, the
    state's part of them), from shapes: the weights read once
    (`weight_bytes`), the state-space layers' state and window read AND
    written, and `keys_read` cache slots of every attention layer's K and V
    (what the ranged read takes at that step). A looped stack reads its
    blocks' weights (`stack_bytes` of `weight_bytes`) once a LOOP: the stack
    does not stay on chip between loops; its keys are a (loop, layer) pair's,
    which `cache_bytes_per_token` counts. An indexed latent layer
    (`index_topk`) reads `keys_read` chosen latent entries and the index key
    of every one of the cache's `cache_len` slots. The counters
    `rollout/step_bytes_needed`, `ssm/state_rw_share` (a "kda" stack:
    `kda/state_rw_share`) and `loops/weight_read_share`."""
    state = 2 * state_bytes(cfg, batch)
    index_keys = index_key_bytes(cfg, 1, 1)
    keys = int(batch * (keys_read * (cache_bytes_per_token(cfg) - index_keys) + cache_len * index_keys))
    return weight_bytes + (cfg.n_loops - 1) * stack_bytes + state + keys, state

"""HF checkpoint → trlx_tpu param pytree conversion, STREAMED per tensor.

The reference builds models with AutoModelForCausalLM.from_pretrained
(reference: trlx/model/nn/ppo_models.py:322-325) — the full torch module in
host RAM (~80 GB/host for NeoX-20B fp32, twice that while both module and
converted copies are alive), which it papers over with DeepSpeed's zero3_init
(reference: trlx/model/nn/ilql_models.py:39-45). Here HF is only a WEIGHT
SOURCE and the load is TPU-native streaming:

- the conversion layout is a SPEC tree (one thunk per target leaf), so
  materialization is per-tensor;
- safetensors checkpoints (single-file or index.json-sharded) are read
  lazily and torch-free (`safe_open(framework="np")` handles fp16/bf16);
- each converted tensor is cast to its target dtype and `device_put`
  against its partition spec IMMEDIATELY — peak host memory is O(largest
  tensor), not O(model). On a pod every host streams the same file and
  contributes its addressable shards (jax.make_array_from_callback).

Legacy pytorch_model.bin checkpoints fall back to the full torch load.
Supported families match the reference's (reference: README.md:6): gpt2,
gpt-j, gpt-neo, gpt-neox; granitemoehybrid without experts (state-space
and attention layers, models/ssm.py); and kimi_linear (gated delta-rule and
unrotated latent-attention layers, sparse experts: models/kda.py, models/moe.py). `ouro` (the looped family) maps its
config only: its weights are not imported (`load_hf_trunk` raises); so does `smallthinker` (grouped keys, windows beside
NoPE layers, a softmax router ahead of attention over ReGLU experts) and `zaya` (attention behind two causal convolutions,
one expert a token by an MLP router with a carried state, learned residual scaling) and `minicpm_sala` (constant-decay
linear-attention layers beside block-selected sparse attention, the MiniCPM scale constants) and `glm_moe_dsa` (latent
attention under a learned indexer, models/indexer.py, sigmoid-routed experts behind leading dense layers). With no checkpoint (or `model_arch` given) params
initialize from scratch — the randomwalks path
(reference: examples/randomwalks.py:99-101).
"""

import json
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.models.lm import LMConfig


def build_lm_config(config) -> LMConfig:
    """Resolve an LMConfig from model_arch overrides or an HF config."""
    mc = config.model
    base: Dict[str, Any] = dict(
        dtype=mc.dtype,
        param_dtype=mc.param_dtype,
        remat=mc.remat,
        remat_policy=getattr(mc, "remat_policy", "full"),
        kv_cache_quant=getattr(mc, "kv_cache_quant", False),
    )
    if mc.model_arch:
        return LMConfig.from_dict({**base, **mc.model_arch})
    if not mc.model_path:
        raise ValueError("Either model.model_path or model.model_arch must be set")
    from transformers import AutoConfig

    hf = AutoConfig.from_pretrained(mc.model_path)
    return lm_config_from_hf(hf, **base)


def lm_config_from_hf(hf, **overrides) -> LMConfig:
    t = getattr(hf, "model_type", None)
    if t is None and str(getattr(hf, "model_name", "")).startswith("smallthinker"):
        t = "smallthinker"  # the family's published config.json carries `model_name`
    if t == "gpt2":
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=hf.n_layer,
            n_head=hf.n_head,
            d_model=hf.n_embd,
            max_position=hf.n_positions,
            pos_type="learned",
            parallel_residual=False,
            fused_qkv=True,
            qkv_bias=True,
            tie_word_embeddings=True,
            activation="gelu_new",
            ln_eps=hf.layer_norm_epsilon,
        )
    elif t == "gptj":
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=hf.n_layer,
            n_head=hf.n_head,
            d_model=hf.n_embd,
            max_position=hf.n_positions,
            pos_type="rotary",
            rotary_dim=hf.rotary_dim or (hf.n_embd // hf.n_head),
            parallel_residual=True,
            use_parallel_ln=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=False,
            tie_word_embeddings=False,
            activation="gelu_new",
            ln_eps=hf.layer_norm_epsilon,
            extra={"lm_head_bias": True},
        )
    elif t == "gpt_neo":
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=hf.num_layers,
            n_head=hf.num_heads,
            d_model=hf.hidden_size,
            d_ff=hf.intermediate_size or 0,
            max_position=hf.max_position_embeddings,
            pos_type="learned",
            parallel_residual=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=True,
            scale_attn=False,  # gpt-neo attention is unscaled
            attention_layers=tuple(hf.attention_layers),
            window_size=hf.window_size,
            tie_word_embeddings=True,
            activation=hf.activation_function,
            ln_eps=hf.layer_norm_epsilon,
        )
    elif t == "gpt_neox":
        head_dim = hf.hidden_size // hf.num_attention_heads
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=hf.num_hidden_layers,
            n_head=hf.num_attention_heads,
            d_model=hf.hidden_size,
            d_ff=hf.intermediate_size,
            max_position=hf.max_position_embeddings,
            pos_type="rotary",
            rotary_dim=int(hf.rotary_pct * head_dim),
            parallel_residual=getattr(hf, "use_parallel_residual", True),
            use_parallel_ln=True,
            fused_qkv=True,
            qkv_bias=True,
            tie_word_embeddings=False,
            activation="gelu",
            ln_eps=hf.layer_norm_eps,
            extra={"neox_rotary": True},
        )
    elif t == "granitemoehybrid":
        # The dense members of the family (granite-4.0-h-micro): state-space
        # (Mamba-2) and grouped-key attention layers, no position signal, the
        # four multipliers. What the program lacks raises here.
        kinds = list(hf.layers_block_type)
        unbuilt = [name for name, on in (
            ("num_local_experts > 0 (the family's expert members)", getattr(hf, "num_local_experts", 0) > 0),
            ("position_embedding_type other than 'nope'", getattr(hf, "position_embedding_type", "nope") not in ("nope", None)),
            ("mamba_n_groups other than 1", hf.mamba_n_groups != 1),
            ("mamba_expand * hidden_size != mamba_n_heads * mamba_d_head",
             int(hf.mamba_expand * hf.hidden_size) != hf.mamba_n_heads * hf.mamba_d_head),
            ("mamba_proj_bias / attention_bias", hf.mamba_proj_bias or hf.attention_bias),
            ("mamba_conv_bias false", not hf.mamba_conv_bias),
            ("hidden_act other than silu", hf.hidden_act != "silu")) if on]
        if unbuilt:
            raise ValueError(f"granitemoehybrid: not built: {'; '.join(unbuilt)}")
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=hf.num_hidden_layers,
            n_head=hf.num_attention_heads,
            n_kv_head=hf.num_key_value_heads,
            head_width=hf.hidden_size // hf.num_attention_heads,
            d_model=hf.hidden_size,
            d_ff=hf.shared_intermediate_size,
            max_position=hf.max_position_embeddings,
            pos_type="none",
            norm="rmsnorm",
            mlp="gated",
            activation="silu",
            ln_eps=hf.rms_norm_eps,
            parallel_residual=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=False,
            tie_word_embeddings=hf.tie_word_embeddings,
            mixer_layers=tuple("mamba" if k == "mamba" else "attention" for k in kinds),
            ssm_heads=hf.mamba_n_heads,
            ssm_head_dim=hf.mamba_d_head,
            ssm_state=hf.mamba_d_state,
            ssm_conv=hf.mamba_d_conv,
            ssm_chunk=hf.mamba_chunk_size,
            embedding_multiplier=float(hf.embedding_multiplier),
            attention_multiplier=float(hf.attention_multiplier),
            residual_multiplier=float(hf.residual_multiplier),
            logits_scaling=float(hf.logits_scaling),
        )
    elif t == "kimi_linear":
        # Kimi-Linear: gated delta-rule (KDA) layers beside latent attention
        # with directly projected queries and (mla_use_nope) no rotation, a
        # sigmoid router with a correction bias over one group, one shared
        # expert. `linear_attn_config` names its layers 1-indexed. What the
        # program lacks raises here.
        given = hf.to_dict() if hasattr(hf, "to_dict") else dict(vars(hf))
        linear = dict(given["linear_attn_config"])
        n_layer = hf.num_hidden_layers
        kda_layers, full_layers = set(linear["kda_layers"]), set(linear["full_attn_layers"])
        unbuilt = [name for name, on in (
            ("linear_attn_config that does not name each of layers 1..num_hidden_layers once",
             kda_layers & full_layers or (kda_layers | full_layers) != set(range(1, n_layer + 1))),
            ("moe_router_activation_func other than sigmoid", given.get("moe_router_activation_func") != "sigmoid"),
            ("moe_renormalize false", not given.get("moe_renormalize")),
            ("num_expert_group / topk_group other than 1", (given.get("num_expert_group", 1), given.get("topk_group", 1)) != (1, 1)),
            ("moe_layer_freq other than 1", given.get("moe_layer_freq", 1) != 1),
            ("num_nextn_predict_layers > 0", given.get("num_nextn_predict_layers", 0) > 0),
            ("rope_scaling", given.get("rope_scaling") is not None),
            ("hidden_act other than silu", hf.hidden_act != "silu")) if on]
        if unbuilt:
            raise ValueError(f"kimi_linear: not built: {'; '.join(unbuilt)}")
        dense_first = int(given.get("first_k_dense_replace", 0))
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=n_layer,
            n_head=hf.num_attention_heads,
            d_model=hf.hidden_size,
            d_ff=hf.intermediate_size,
            max_position=int(given.get("model_max_length") or given.get("max_position_embeddings") or 2048),
            pos_type="none" if given.get("mla_use_nope") else "rotary",
            rope_theta=float(given.get("rope_theta", 10000.0)),
            norm="rmsnorm",
            mlp="gated",
            attention="mla",
            activation="silu",
            ln_eps=hf.rms_norm_eps,
            parallel_residual=False,
            tie_word_embeddings=bool(given.get("tie_word_embeddings", False)),
            mixer_layers=tuple("kda" if i + 1 in kda_layers else "attention" for i in range(n_layer)),
            ffn_layers=tuple("dense" if i < dense_first else "experts" for i in range(n_layer)),
            kda_heads=linear["num_heads"],
            kda_head_dim=linear["head_dim"],
            kda_conv=linear["short_conv_kernel_size"],
            q_lora_rank=int(given.get("q_lora_rank") or 0),
            kv_lora_rank=hf.kv_lora_rank,
            qk_nope_head_dim=hf.qk_nope_head_dim,
            qk_rope_head_dim=hf.qk_rope_head_dim,
            v_head_dim=hf.v_head_dim,
            n_experts=given["num_experts"],
            experts_per_token=given["num_experts_per_token"],
            expert_d_ff=hf.moe_intermediate_size,
            n_shared_experts=given.get("num_shared_experts", 0),
            routed_scaling_factor=float(given.get("routed_scaling_factor", 1.0)),
        )
    elif t == "ouro":
        # The looped family (Ouro-1.4B / 2.6B): a Llama-shaped block (rotary
        # over the whole head in the rotate-half layout, RMSNorm, gated SiLU,
        # no bias, untied head) run `total_ut_steps` times a token. The
        # sandwich norms, the final norm at the end of every loop and the exit
        # gate are the family's description, not keys of its config.json. A key
        # this branch does not know is an error, as in LMConfig.from_dict.
        given = hf.to_dict() if hasattr(hf, "to_dict") else dict(vars(hf))
        generic = set()
        if hasattr(hf, "to_dict"):
            from transformers import PretrainedConfig

            generic = set(PretrainedConfig().to_dict())
        unknown = sorted(set(given) - OURO_KEYS - generic)
        if unknown:
            raise ValueError(f"ouro: unknown config key(s) {unknown}")
        head_dim = given.get("head_dim") or hf.hidden_size // hf.num_attention_heads
        unbuilt = [name for name, on in (
            ("layer_types other than full_attention", set(given.get("layer_types") or ["full_attention"]) != {"full_attention"}),
            ("use_sliding_window / sliding_window", bool(given.get("use_sliding_window")) or given.get("sliding_window") is not None),
            ("rope_scaling", given.get("rope_scaling") is not None),
            ("hidden_act other than silu", hf.hidden_act != "silu"),
            ("layer_types of another length than num_hidden_layers",
             given.get("layer_types") is not None and len(given["layer_types"]) != hf.num_hidden_layers)) if on]
        if unbuilt:
            raise ValueError(f"ouro: not built: {'; '.join(unbuilt)}")
        d = dict(
            vocab_size=hf.vocab_size,
            n_layer=hf.num_hidden_layers,
            n_head=hf.num_attention_heads,
            n_kv_head=0 if hf.num_key_value_heads == hf.num_attention_heads else hf.num_key_value_heads,
            head_width=0 if head_dim * hf.num_attention_heads == hf.hidden_size else head_dim,
            d_model=hf.hidden_size,
            d_ff=hf.intermediate_size,
            max_position=hf.max_position_embeddings,
            pos_type="rotary",
            rope_theta=float(hf.rope_theta),
            norm="rmsnorm",
            mlp="gated",
            activation="silu",
            ln_eps=hf.rms_norm_eps,
            parallel_residual=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=False,
            tie_word_embeddings=hf.tie_word_embeddings,
            n_loops=int(hf.total_ut_steps),
            sandwich_norm=True,
            exit_gate=int(hf.total_ut_steps) > 1,
            exit_threshold=float(given.get("early_exit_threshold", 1.0)),
            extra={"neox_rotary": True},
        )
    elif t == "smallthinker":
        # SmallThinker (21B-A3B / 4B-A0.6B): grouped keys, RMSNorm, no bias;
        # `sliding_window_layout` marks the window layers and `rope_layout` the
        # rotated ones, and the program builds the stacks in which the two
        # agree (a window layer rotates, a full-span layer has no position
        # signal); every layer an expert layer of ReGLU experts, no shared one;
        # the router a softmax over the chosen logits, reading the block's
        # input ahead of attention (`moe_enable_early_router`, true where the
        # key is absent: the family's description). What the program lacks
        # raises here, by name.
        given = hf.to_dict() if hasattr(hf, "to_dict") else dict(vars(hf))
        n_layer = given["num_hidden_layers"]
        windows, rotated = list(given["sliding_window_layout"]), list(given["rope_layout"])
        unbuilt = [name for name, on in (
            ("rope_layout and sliding_window_layout that disagree", [bool(x) for x in windows] != [bool(x) for x in rotated]),
            ("layouts of another length than num_hidden_layers", len(windows) != n_layer or len(rotated) != n_layer),
            ("a dense layer (moe_layer_layout)", not all(given.get("moe_layer_layout") or [1])),
            ("moe_primary_router_apply_softmax false", not given.get("moe_primary_router_apply_softmax")),
            ("norm_topk_prob false", not given.get("norm_topk_prob")),
            ("rope_scaling", given.get("rope_scaling") is not None)) if on]
        if unbuilt:
            raise ValueError(f"smallthinker: not built: {'; '.join(unbuilt)}")
        local = any(windows)
        d = dict(
            vocab_size=given["vocab_size"],
            n_layer=n_layer,
            n_head=given["num_attention_heads"],
            n_kv_head=given["num_key_value_heads"],
            head_width=given["head_dim"],
            d_model=given["hidden_size"],
            max_position=given["max_position_embeddings"],
            pos_type="rotary" if local else "none",
            rotary_layers="local" if local else "all",
            rope_theta=float(given["rope_theta"]),
            extra={"neox_rotary": True},
            attention_layers=tuple("local" if w else "global" for w in windows) if local else (),
            window_size=int(given["sliding_window_size"]) if local else 0,
            norm="rmsnorm",
            mlp="gated",
            activation="relu",
            ln_eps=given["rms_norm_eps"],
            parallel_residual=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=False,
            tie_word_embeddings=bool(given.get("tie_word_embeddings", False)),
            ffn_layers=("experts",) * n_layer,
            n_experts=given["moe_num_primary_experts"],
            experts_per_token=given["moe_num_active_primary_experts"],
            expert_d_ff=given["moe_ffn_hidden_size"],
            router_scoring="softmax",
            router_input="block" if given.get("moe_enable_early_router", True) else "ffn",
        )
    elif t == "zaya":
        # ZAYA1 (8B-A0.8B): every layer a CCA sub-block (grouped attention at
        # `head_dim` behind two causal convolutions `cca_time0` and `cca_time1`
        # wide, rotary on `partial_rotary_factor` of a head) and an expert
        # sub-block (one expert a token by an MLP router `router_hidden_size`
        # wide that carries its state across depth), learned residual scaling,
        # tied head. What config.json does not say (the convolutions' biases,
        # the value shift, the router's depth, the form of the scaling) is the
        # program's reading of the family's papers (models/cca.py, models/moe.py
        # `MLPRouter`). What the program lacks raises here, by name.
        given = hf.to_dict() if hasattr(hf, "to_dict") else dict(vars(hf))
        n_layer, kinds = given["num_hidden_layers"], list(given.get("layer_types") or [])
        rope = (given.get("rope_parameters") or {}).get("hybrid") or {}
        factor = float(rope.get("partial_rotary_factor", given.get("partial_rotary_factor", 1.0)))
        unbuilt = [name for name, on in (
            ("a layer type other than 'hybrid' (a sliding window every fourth layer is the 74B sibling's)", set(kinds) - {"hybrid"}),
            ("layer_types of another length than num_hidden_layers", kinds and len(kinds) != n_layer),
            ("sliding_window", given.get("sliding_window") is not None),
            ("attention_bias", given.get("attention_bias")), ("lm_head_bias", given.get("lm_head_bias")),
            (f"hidden_act {given.get('hidden_act')!r}", given.get("hidden_act") != "silu"),
            (f"rope_type {rope.get('rope_type', 'default')!r}", rope.get("rope_type", "default") != "default"),
            ("a zero-compute 'skip' expert (a router output beyond num_experts)", given.get("zaya_use_mod"))) if on]
        if unbuilt:
            raise ValueError(f"zaya: not built: {'; '.join(unbuilt)}")
        d = dict(
            vocab_size=given["vocab_size"],
            n_layer=n_layer,
            n_head=given["num_attention_heads"],
            n_kv_head=given["num_key_value_heads"],
            head_width=given["head_dim"],
            d_model=given["hidden_size"],
            max_position=given["max_position_embeddings"],
            pos_type="rotary",
            rotary_dim=int(given["head_dim"] * factor),
            rope_theta=float(rope.get("rope_theta", given.get("rope_theta", 10000.0))),
            extra={"neox_rotary": True},
            attention="cca",
            cca_time0=given["cca_time0"],
            cca_time1=given["cca_time1"],
            norm="rmsnorm",
            mlp="gated",
            activation="silu",
            ln_eps=given["rms_norm_eps"],
            parallel_residual=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=False,
            tie_word_embeddings=bool(given.get("tie_word_embeddings", True)),
            ffn_layers=("experts",) * n_layer,
            n_experts=given["num_experts"],
            experts_per_token=given["num_experts_per_tok"],
            expert_d_ff=given["moe_intermediate_size"],
            router_scoring="softmax_all",
            router_kind="mlp",
            router_hidden=given["router_hidden_size"],
            router_carry=True,
            residual_scaling=True,
        )
    elif t == "minicpm_sala":
        # MiniCPM-SALA (9B): `mixer_types` names each layer "lightning-attn"
        # (models/lightning.py) or "minicpm4" (InfLLM-V2's block-selected
        # sparse attention, models/sparse.py), rotary inside the lightning
        # layers only, qk-norm in both, an output gate in both, and the MiniCPM
        # family's three scale constants. What config.json does not say (the
        # six constants of MiniCPM4's `sparse_config`, the decay's slopes, the
        # width of the gates and norms) is the program's reading of the
        # family's papers (models/sparse.py, models/lightning.py): overrides
        # name them. `dense_len` (a short sequence attended densely by the
        # published inference code) is not taken. What the program lacks
        # raises here, by name.
        given = hf.to_dict() if hasattr(hf, "to_dict") else dict(vars(hf))
        n_layer, kinds = given["num_hidden_layers"], list(given["mixer_types"])
        unbuilt = [name for name, on in (
            ("a mixer type other than 'minicpm4' and 'lightning-attn'", set(kinds) - {"minicpm4", "lightning-attn"}),
            ("mixer_types of another length than num_hidden_layers", len(kinds) != n_layer),
            ("attention_bias", given.get("attention_bias")), ("attn_use_rope", given.get("attn_use_rope")),
            ("lightning layers without rotary (lightning_use_rope false)", not given.get("lightning_use_rope", True)),
            ("lightning layers without their output norm (use_output_norm false)", not given.get("use_output_norm", True)),
            ("grouped lightning keys (lightning_nkv != lightning_nh)", given.get("lightning_nkv") != given.get("lightning_nh")),
            (f"lightning_scale {given.get('lightning_scale')!r}", given.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)"),
            (f"hidden_act {given.get('hidden_act')!r}", given.get("hidden_act") != "silu"),
            ("rope_scaling", given.get("rope_scaling"))) if on]
        if unbuilt:
            raise ValueError(f"minicpm_sala: not built: {'; '.join(unbuilt)}")
        d = dict(
            vocab_size=given["vocab_size"],
            n_layer=n_layer,
            n_head=given["num_attention_heads"],
            n_kv_head=given["num_key_value_heads"],
            head_width=given["head_dim"],
            d_model=given["hidden_size"],
            d_ff=given["intermediate_size"],
            max_position=given["max_position_embeddings"],
            pos_type="rotary",
            rotary_layers="lightning",
            rope_theta=float(given.get("rope_theta", 10000.0)),
            extra={"neox_rotary": True},
            attention="sparse",
            # MiniCPM4's published sparse_config (InfLLM-V2), not keys of this family's config.json
            sparse_kernel=32, sparse_stride=16, sparse_block=64, sparse_topk=64, sparse_window=2048, sparse_init_blocks=1,
            attn_output_gate=bool(given.get("attn_use_output_gate", False)),
            mixer_layers=tuple("lightning" if kind == "lightning-attn" else "attention" for kind in kinds),
            lightning_heads=given["lightning_nh"],
            lightning_head_dim=given["lightning_head_dim"],
            lightning_output_gate=bool(given.get("use_output_gate", False)),
            qk_norm=bool(given.get("qk_norm", False)),
            norm="rmsnorm",
            mlp="gated",
            activation="silu",
            ln_eps=given["rms_norm_eps"],
            parallel_residual=False,
            fused_qkv=False,
            qkv_bias=False,
            out_bias=False,
            tie_word_embeddings=bool(given.get("tie_word_embeddings", False)),
            embedding_multiplier=float(given.get("scale_emb", 1.0)),
            # scale_depth / sqrt(the PUBLISHED depth), whatever depth an override runs
            residual_multiplier=float(given.get("scale_depth", 1.0)) / float(np.sqrt(n_layer)),
            logits_scaling=float(given["hidden_size"]) / float(given.get("dim_model_base", given["hidden_size"])),
        )
    elif t == "glm_moe_dsa":
        # GLM-5: DeepSeek-V3's latent attention (a query bottleneck, one shared
        # rotary key, interleaved pairs, no rope scaling) and expert layer (a
        # sigmoid router with a correction bias over one group, one shared
        # expert, first_k_dense_replace leading dense layers) under DeepSeek
        # Sparse Attention's indexer (models/indexer.py): every size of it is a
        # key of config.json. The MTP block (num_nextn_predict_layers) is not
        # built and not taken: the trunk's own head gives every log-prob PPO
        # reads. What the program lacks raises here, by name.
        given = hf.to_dict() if hasattr(hf, "to_dict") else dict(vars(hf))
        rope = dict(given.get("rope_parameters") or {})
        n_layer = given["num_hidden_layers"]
        unbuilt = [name for name, on in (
            (f"scoring_func {given.get('scoring_func')!r}", given.get("scoring_func") != "sigmoid"),
            (f"topk_method {given.get('topk_method')!r}", given.get("topk_method") != "noaux_tc"),
            ("norm_topk_prob false", not given.get("norm_topk_prob")),
            ("n_group / topk_group other than 1", (given.get("n_group", 1), given.get("topk_group", 1)) != (1, 1)),
            ("moe_layer_freq other than 1", given.get("moe_layer_freq", 1) != 1),
            ("attention_bias", given.get("attention_bias")),
            (f"rope_type {rope.get('rope_type')!r}", rope.get("rope_type", "default") != "default"),
            ("rope_interleave / indexer_rope_interleave false (rotate-half pairs)",
             not given.get("rope_interleave", True) or not given.get("indexer_rope_interleave", True)),
            ("grouped keys (num_key_value_heads != num_attention_heads)",
             given.get("num_key_value_heads", given["num_attention_heads"]) != given["num_attention_heads"]),
            ("no query bottleneck (q_lora_rank null)", not given.get("q_lora_rank")),
            (f"hidden_act {given.get('hidden_act')!r}", given.get("hidden_act") != "silu")) if on]
        if unbuilt:
            raise ValueError(f"glm_moe_dsa: not built: {'; '.join(unbuilt)}")
        dense_first = int(given.get("first_k_dense_replace", 0))
        d = dict(
            vocab_size=given["vocab_size"],
            n_layer=n_layer,
            n_head=given["num_attention_heads"],
            d_model=given["hidden_size"],
            d_ff=given["intermediate_size"],
            max_position=given["max_position_embeddings"],
            pos_type="rotary",
            rope_theta=float(rope.get("rope_theta", given.get("rope_theta", 10000.0))),
            norm="rmsnorm",
            mlp="gated",
            attention="mla",
            activation="silu",
            ln_eps=given["rms_norm_eps"],
            parallel_residual=False,
            tie_word_embeddings=bool(given.get("tie_word_embeddings", False)),
            ffn_layers=tuple("dense" if i < dense_first else "experts" for i in range(n_layer)),
            q_lora_rank=given["q_lora_rank"],
            kv_lora_rank=given["kv_lora_rank"],
            qk_nope_head_dim=given["qk_nope_head_dim"],
            qk_rope_head_dim=given["qk_rope_head_dim"],
            v_head_dim=given["v_head_dim"],
            index_n_heads=given["index_n_heads"],
            index_head_dim=given["index_head_dim"],
            index_topk=given["index_topk"],
            n_experts=given["n_routed_experts"],
            experts_per_token=given["num_experts_per_tok"],
            expert_d_ff=given["moe_intermediate_size"],
            n_shared_experts=given.get("n_shared_experts", 0),
            routed_scaling_factor=float(given.get("routed_scaling_factor", 1.0)),
        )
    else:
        raise ValueError(f"unsupported HF model_type for conversion: {t}")
    d.update(overrides)
    return LMConfig.from_dict(d)


# The keys of the looped family's published config.json (`model_type: ouro`).
OURO_KEYS = frozenset({
    "model_type", "head_dim", "hidden_act", "hidden_size", "intermediate_size", "layer_types", "max_position_embeddings",
    "max_window_layers", "num_attention_heads", "num_hidden_layers", "num_key_value_heads", "rms_norm_eps", "rope_scaling",
    "rope_theta", "sliding_window", "tie_word_embeddings", "total_ut_steps", "early_exit_threshold", "use_sliding_window",
    "vocab_size", "attention_dropout", "initializer_range", "use_cache", "torch_dtype", "architectures", "auto_map",
    "bos_token_id", "eos_token_id", "pad_token_id", "transformers_version",
})


def load_or_init_params(model, config, rng) -> Dict[str, Any]:
    """Initialize params; when a checkpoint is available, splice converted HF
    trunk weights over the fresh init (heads stay fresh, like the reference's
    newly-initialized value/Q heads, reference: trlx/model/nn/ppo_models.py:333).

    Pod-scale discipline end to end: with a checkpoint AND a multi-device
    mesh, the fresh init is jitted with sharded out_shardings (params are
    BORN distributed — no host copy of the full tree ever exists) and the
    trunk then streams over it tensor-by-tensor via make_stream_put. Peak
    per-host memory is O(model/n_devices) for the resident shards plus
    O(largest tensor) for the stream — never O(model)."""
    from trlx_tpu.parallel.mesh import peek_mesh

    cfg = model.cfg
    dummy = jnp.zeros((1, 2), dtype=jnp.int32)
    mesh = peek_mesh()
    multi_device = mesh is not None and int(np.prod(list(mesh.shape.values()))) > 1

    def init_fn(r):
        return model.init(r, dummy, jnp.ones_like(dummy))["params"]

    if multi_device:
        abstract = jax.eval_shape(init_fn, rng)
        shardings = _tree_shardings(mesh, abstract)
        params = jax.jit(init_fn, out_shardings=shardings)(rng)
    else:
        # Jitted even single-device: one compiled program instead of hundreds
        # of eagerly-dispatched initializer ops (~2x faster cold, and the
        # program lands in the persistent compile cache for warm starts).
        params = jax.jit(init_fn)(rng)
    mc = config.model
    if mc.model_path and not mc.model_arch:
        put = make_stream_put(params["transformer"])
        trunk = load_hf_trunk(mc.model_path, cfg, put=put)
        params = {**params, "transformer": trunk}
    return params


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _tree_shardings(mesh, abstract_tree):
    """NamedShardings for an abstract (eval_shape) param tree via the shared
    lm partition rules + sanitize (works on ShapeDtypeStructs: only .shape
    and .ndim are consulted)."""
    from trlx_tpu.parallel.sharding import (
        lm_partition_rules,
        match_partition_rules,
        sanitize_specs,
        specs_to_shardings,
    )

    specs = sanitize_specs(
        mesh, abstract_tree, match_partition_rules(lm_partition_rules(), abstract_tree)
    )
    return specs_to_shardings(mesh, specs)


def make_stream_put(init_trunk) -> Callable[[str, np.ndarray], Any]:
    """Per-tensor placement hook for the streamed load.

    Casts each converted tensor to the dtype of the matching init leaf (the
    flax module's param_dtype), then — when a process-global mesh exists —
    builds the GLOBAL sharded array for that leaf's partition spec via
    make_array_from_callback: every host reads the full tensor from disk and
    contributes its addressable shards, so nothing larger than one tensor is
    ever resident per host. Sharding specs come from the shared lm partition
    rules (match_partition_rules + sanitize_specs — one source of truth with
    shard_pytree)."""
    from trlx_tpu.parallel.mesh import peek_mesh

    flat, _ = jax.tree_util.tree_flatten_with_path(init_trunk)
    dtypes = {_path_str(p): l.dtype for p, l in flat}
    mesh = peek_mesh()
    shardings_by_path: Dict[str, Any] = {}
    if mesh is not None and int(np.prod(list(mesh.shape.values()))) > 1:
        sh = _tree_shardings(mesh, init_trunk)
        flat_sh, _ = jax.tree_util.tree_flatten_with_path(
            sh, is_leaf=lambda x: hasattr(x, "spec")
        )
        shardings_by_path = {_path_str(p): s for p, s in flat_sh}

    def put(path: str, arr: np.ndarray):
        target = dtypes.get(path)
        if target is not None and arr.dtype != target:
            arr = np.asarray(arr).astype(target)
        sharding = shardings_by_path.get(path)
        if sharding is None:
            return jnp.asarray(arr)
        return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])

    return put


class LazySafetensors:
    """Per-tensor lazy mapping over a safetensors checkpoint directory —
    single-file (model.safetensors) or sharded
    (model-0000X-of-0000N.safetensors + model.safetensors.index.json).
    Torch-free: safe_open(framework="np") yields numpy views with fp16 and
    (ml_dtypes) bf16 preserved. One tensor is materialized per lookup."""

    def __init__(self, model_path: str):
        index = os.path.join(model_path, "model.safetensors.index.json")
        single = os.path.join(model_path, "model.safetensors")
        self._key2file: Dict[str, str] = {}
        self._handles: Dict[str, Any] = {}
        if os.path.isfile(index):
            with open(index) as f:
                weight_map = json.load(f)["weight_map"]
            self._key2file = {
                k: os.path.join(model_path, v) for k, v in weight_map.items()
            }
        elif os.path.isfile(single):
            from safetensors import safe_open

            with safe_open(single, framework="np") as sf:
                self._key2file = {k: single for k in sf.keys()}
        else:
            raise FileNotFoundError(
                f"no safetensors checkpoint under {model_path!r}"
            )

    def _handle(self, file: str):
        if file not in self._handles:
            from safetensors import safe_open

            self._handles[file] = safe_open(file, framework="np")
        return self._handles[file]

    def __getitem__(self, key: str) -> np.ndarray:
        return self._handle(self._key2file[key]).get_tensor(key)

    def __contains__(self, key) -> bool:
        return key in self._key2file

    def __iter__(self):
        return iter(self._key2file)

    def keys(self):
        return self._key2file.keys()


def load_hf_trunk(model_path: str, cfg: LMConfig, put=None) -> Dict[str, Any]:
    """Convert an HF checkpoint's transformer trunk to our Flax layout.

    Streams per tensor from safetensors when present (`put` is applied to
    each converted tensor immediately — dtype cast + sharded device
    placement); falls back to a full torch load for legacy
    pytorch_model.bin checkpoints."""
    if cfg.router_scoring == "softmax" or cfg.router_input == "block":  # expert layers only: LMConfig holds them to that
        raise NotImplementedError(
            "importing a smallthinker checkpoint's weights is not built: the names of the family's tensors could not be "
            "read without the network, and a guessed mapping would load another model; `model_arch` (weights from "
            "the seed) is the path that runs")
    if cfg.attention == "cca" or cfg.router_kind == "mlp":
        raise NotImplementedError(
            "importing a zaya checkpoint's weights is not built: the names of the family's tensors could not be read "
            "without the network, and a guessed mapping would load another model; `model_arch` (weights from the seed) is "
            "the path that runs")
    if cfg.has_lightning or cfg.attention == "sparse":
        raise NotImplementedError(
            "importing a minicpm_sala checkpoint's weights is not built: the names of the family's tensors could not be "
            "read without the network, and a guessed mapping would load another model; `model_arch` (weights from the "
            "seed) is the path that runs")
    if cfg.index_topk:
        raise NotImplementedError(
            "importing a glm_moe_dsa checkpoint's weights is not built: the names of the family's tensors (the indexer's "
            "among them) could not be read without the network, and a guessed mapping would load another model; "
            "`model_arch` (weights from the seed) is the path that runs")
    if cfg.n_loops > 1:
        raise NotImplementedError(
            "importing a looped checkpoint's weights is not built: the names of the family's tensors (the two "
            "sandwich norms a block, the exit gate) could not be read without the network, and a guessed mapping "
            "would load another model; `model_arch` (weights from the seed) is the path that runs")
    try:
        sd: Any = LazySafetensors(model_path)
    except (FileNotFoundError, NotADirectoryError):
        import torch  # host-only legacy fallback

        from transformers import AutoModelForCausalLM

        hf_model = AutoModelForCausalLM.from_pretrained(model_path, torch_dtype=torch.float32)
        sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
        del hf_model
    t = _detect_family(sd)
    if t == "unknown":
        raise ValueError(
            f"cannot detect supported family from state dict ({list(sd)[:3]}...)"
        )
    return materialize_spec(trunk_spec(t, cfg), sd, put=put)


def _detect_family(sd) -> str:
    if any(".mamba.in_proj." in k for k in sd):
        return "granitemoehybrid"
    if any(".self_attn.f_a_proj." in k for k in sd):
        return "kimi_linear"
    if any(k.startswith("transformer.h.") and ".attn.c_attn." in k for k in sd):
        return "gpt2"
    if any(".attn.attention.q_proj." in k for k in sd):
        return "gpt_neo"
    if any(".attn.q_proj." in k for k in sd):
        return "gptj"
    if any("gpt_neox.layers." in k for k in sd):
        return "gpt_neox"
    return "unknown"


# --------------------------------------------------------------------------
# Conversion specs: trees of per-leaf thunks `fn(sd) -> np.ndarray`, so a
# lazy state dict materializes ONE source tensor per target leaf. The eager
# convert_* functions below are materializations of these specs.


def _id(key):
    def f(sd):
        return np.asarray(sd[key])

    return f


def _t(key):
    def f(sd):
        return np.asarray(sd[key]).T

    return f


def _ln_spec(prefix):
    return {"scale": _id(f"{prefix}.weight"), "bias": _id(f"{prefix}.bias")}


def materialize_spec(spec: Dict[str, Any], sd, put: Optional[Callable] = None) -> Dict[str, Any]:
    """Evaluate a spec tree against a (possibly lazy) state dict, applying
    `put(path, arr)` to each tensor as soon as it is converted."""

    def mat(path, thunk):
        arr = thunk(sd)
        return put(_path_str(path), arr) if put is not None else arr

    return jax.tree_util.tree_map_with_path(mat, spec)


def trunk_spec(family: str, cfg: LMConfig) -> Dict[str, Any]:
    if family == "gpt2":
        return _spec_gpt2(cfg)
    if family == "gptj":
        return _spec_gptj(cfg)
    if family == "gpt_neo":
        return _spec_gpt_neo(cfg)
    if family == "gpt_neox":
        return _spec_neox(cfg)
    if family == "granitemoehybrid":
        return _spec_granite_hybrid(cfg)
    if family == "kimi_linear":
        return _spec_kimi_linear(cfg)
    raise ValueError(f"unsupported family: {family}")


def _spec_gpt2(cfg: LMConfig) -> Dict[str, Any]:
    """GPT-2: HF Conv1D weights are already [in, out] — direct copy."""
    p: Dict[str, Any] = {
        "wte": {"embedding": _id("transformer.wte.weight")},
        "wpe": {"embedding": _id("transformer.wpe.weight")},
        "ln_f": _ln_spec("transformer.ln_f"),
    }
    if not cfg.tie_word_embeddings:
        # Canonical gpt2 ties; an untied checkpoint (e.g. our own export of
        # an untied from-scratch arch) carries a real head.
        p["lm_head"] = {"kernel": _t("lm_head.weight")}
    for i in range(cfg.n_layer):
        h = f"transformer.h.{i}"
        p[f"h_{i}"] = {
            "ln_1": _ln_spec(f"{h}.ln_1"),
            "ln_2": _ln_spec(f"{h}.ln_2"),
            "attn": {
                "c_qkv": {"kernel": _id(f"{h}.attn.c_attn.weight"), "bias": _id(f"{h}.attn.c_attn.bias")},
                "c_proj": {"kernel": _id(f"{h}.attn.c_proj.weight"), "bias": _id(f"{h}.attn.c_proj.bias")},
            },
            "mlp": {
                "c_fc": {"kernel": _id(f"{h}.mlp.c_fc.weight"), "bias": _id(f"{h}.mlp.c_fc.bias")},
                "c_proj": {"kernel": _id(f"{h}.mlp.c_proj.weight"), "bias": _id(f"{h}.mlp.c_proj.bias")},
            },
        }
    return p


def _spec_gptj(cfg: LMConfig) -> Dict[str, Any]:
    """GPT-J: nn.Linear weights are [out, in] — transpose to Flax [in, out]."""
    p: Dict[str, Any] = {
        "wte": {"embedding": _id("transformer.wte.weight")},
        "ln_f": _ln_spec("transformer.ln_f"),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = {"kernel": _t("lm_head.weight")}
        if cfg.extra.get("lm_head_bias", False):
            p["lm_head"]["bias"] = _id("lm_head.bias")
    for i in range(cfg.n_layer):
        h = f"transformer.h.{i}"
        p[f"h_{i}"] = {
            "ln_1": _ln_spec(f"{h}.ln_1"),
            "attn": {
                "q_proj": {"kernel": _t(f"{h}.attn.q_proj.weight")},
                "k_proj": {"kernel": _t(f"{h}.attn.k_proj.weight")},
                "v_proj": {"kernel": _t(f"{h}.attn.v_proj.weight")},
                "c_proj": {"kernel": _t(f"{h}.attn.out_proj.weight")},
            },
            "mlp": {
                "c_fc": {"kernel": _t(f"{h}.mlp.fc_in.weight"), "bias": _id(f"{h}.mlp.fc_in.bias")},
                "c_proj": {"kernel": _t(f"{h}.mlp.fc_out.weight"), "bias": _id(f"{h}.mlp.fc_out.bias")},
            },
        }
    return p


def _spec_gpt_neo(cfg: LMConfig) -> Dict[str, Any]:
    """GPT-Neo: gpt2-style trunk but nn.Linear projections ([out, in] →
    transpose), biasless q/k/v, tied head."""
    p: Dict[str, Any] = {
        "wte": {"embedding": _id("transformer.wte.weight")},
        "wpe": {"embedding": _id("transformer.wpe.weight")},
        "ln_f": _ln_spec("transformer.ln_f"),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = {"kernel": _t("lm_head.weight")}
    for i in range(cfg.n_layer):
        h = f"transformer.h.{i}"
        a = f"{h}.attn.attention"
        p[f"h_{i}"] = {
            "ln_1": _ln_spec(f"{h}.ln_1"),
            "ln_2": _ln_spec(f"{h}.ln_2"),
            "attn": {
                "q_proj": {"kernel": _t(f"{a}.q_proj.weight")},
                "k_proj": {"kernel": _t(f"{a}.k_proj.weight")},
                "v_proj": {"kernel": _t(f"{a}.v_proj.weight")},
                "c_proj": {"kernel": _t(f"{a}.out_proj.weight"), "bias": _id(f"{a}.out_proj.bias")},
            },
            "mlp": {
                "c_fc": {"kernel": _t(f"{h}.mlp.c_fc.weight"), "bias": _id(f"{h}.mlp.c_fc.bias")},
                "c_proj": {"kernel": _t(f"{h}.mlp.c_proj.weight"), "bias": _id(f"{h}.mlp.c_proj.bias")},
            },
        }
    return p


def _spec_neox(cfg: LMConfig) -> Dict[str, Any]:
    """GPT-NeoX: fused query_key_value is laid out [n_head, 3, head_dim] on
    the output dim — permute into our q|k|v block layout."""
    nh, hd, d = cfg.n_head, cfg.head_dim, cfg.d_model

    def qkv_w(key):
        def f(sd):  # [3d, d] torch → [d, 3d] ours (q|k|v)
            w = np.asarray(sd[key]).reshape(nh, 3, hd, d)  # heads-major interleave
            w = np.concatenate([w[:, j] for j in range(3)], axis=0)  # [3*nh, hd, d]
            return w.reshape(3 * d, d).T

        return f

    def qkv_b(key):
        def f(sd):
            b = np.asarray(sd[key]).reshape(nh, 3, hd)
            return np.concatenate([b[:, j] for j in range(3)], axis=0).reshape(3 * d)

        return f

    p: Dict[str, Any] = {
        "wte": {"embedding": _id("gpt_neox.embed_in.weight")},
        "ln_f": _ln_spec("gpt_neox.final_layer_norm"),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = {"kernel": _t("embed_out.weight")}
    for i in range(cfg.n_layer):
        h = f"gpt_neox.layers.{i}"
        p[f"h_{i}"] = {
            "ln_1": _ln_spec(f"{h}.input_layernorm"),
            "ln_2": _ln_spec(f"{h}.post_attention_layernorm"),
            "attn": {
                "c_qkv": {
                    "kernel": qkv_w(f"{h}.attention.query_key_value.weight"),
                    "bias": qkv_b(f"{h}.attention.query_key_value.bias"),
                },
                "c_proj": {
                    "kernel": _t(f"{h}.attention.dense.weight"),
                    "bias": _id(f"{h}.attention.dense.bias"),
                },
            },
            "mlp": {
                "c_fc": {
                    "kernel": _t(f"{h}.mlp.dense_h_to_4h.weight"),
                    "bias": _id(f"{h}.mlp.dense_h_to_4h.bias"),
                },
                "c_proj": {
                    "kernel": _t(f"{h}.mlp.dense_4h_to_h.weight"),
                    "bias": _id(f"{h}.mlp.dense_4h_to_h.bias"),
                },
            },
        }
    return p


def _spec_granite_hybrid(cfg: LMConfig) -> Dict[str, Any]:
    """granitemoehybrid without experts: nn.Linear weights transposed; the
    depthwise Conv1d's [channels, 1, K] to [K, channels]; the gated MLP's
    fused `input_linear` [2 f, d] split into gate (first f rows) and up."""
    f = cfg.ff_dim

    def half(key, first):
        def thunk(sd):
            w = np.asarray(sd[key])
            return (w[:f] if first else w[f:]).T

        return thunk

    def depthwise(key):
        return lambda sd: np.asarray(sd[key])[:, 0, :].T

    scale = lambda key: {"scale": _id(key)}
    p: Dict[str, Any] = {"wte": {"embedding": _id("model.embed_tokens.weight")}, "ln_f": scale("model.norm.weight")}
    if not cfg.tie_word_embeddings:
        p["lm_head"] = {"kernel": _t("lm_head.weight")}
    for i in range(cfg.n_layer):
        h = f"model.layers.{i}"
        block = {
            "ln_1": scale(f"{h}.input_layernorm.weight"),
            "ln_2": scale(f"{h}.post_attention_layernorm.weight"),
            "mlp": {
                "gate_proj": {"kernel": half(f"{h}.shared_mlp.input_linear.weight", True)},
                "up_proj": {"kernel": half(f"{h}.shared_mlp.input_linear.weight", False)},
                "down_proj": {"kernel": _t(f"{h}.shared_mlp.output_linear.weight")},
            },
        }
        if cfg.mixer(i) == "mamba":
            m = f"{h}.mamba"
            block["mamba"] = {
                "in_proj": {"kernel": _t(f"{m}.in_proj.weight")},
                "out_proj": {"kernel": _t(f"{m}.out_proj.weight")},
                "conv_kernel": depthwise(f"{m}.conv1d.weight"),
                "conv_bias": _id(f"{m}.conv1d.bias"),
                "dt_bias": _id(f"{m}.dt_bias"),
                "A_log": _id(f"{m}.A_log"),
                "D": _id(f"{m}.D"),
                "norm_scale": _id(f"{m}.norm.weight"),
            }
        else:
            a = f"{h}.self_attn"
            block["attn"] = {
                "q_proj": {"kernel": _t(f"{a}.q_proj.weight")},
                "k_proj": {"kernel": _t(f"{a}.k_proj.weight")},
                "v_proj": {"kernel": _t(f"{a}.v_proj.weight")},
                "c_proj": {"kernel": _t(f"{a}.o_proj.weight")},
            }
        p[f"h_{i}"] = block
    return p


def _spec_kimi_linear(cfg: LMConfig) -> Dict[str, Any]:
    """kimi_linear, by the tensor names of the family's `modeling_kimi.py` as
    this file's writer knew them (no network here to read them again: a
    checkpoint that names a tensor otherwise fails with a KeyError on that
    name, never loads another model): nn.Linear weights transposed; a
    depthwise Conv1d's [channels, 1, K] to [K, channels]; `A_log`
    [1, 1, heads, 1] to [heads]; the gate's bias zeros where the checkpoint
    has none; the router `gate.weight` [experts, d] transposed, its bias as it
    is; the HELD experts `[first, first + count)` of `experts.{e}.w1 | w3 | w2`
    (gate | up | down) stacked."""
    if cfg.q_lora_rank:
        raise NotImplementedError("kimi_linear import is built for directly projected queries (q_lora_rank null)")
    first, held = cfg.held_experts
    inner = cfg.kda_heads * cfg.kda_head_dim
    scale = lambda key: {"scale": _id(key)}
    depthwise = lambda key: (lambda sd: np.asarray(sd[key])[:, 0, :].T)
    stacked = lambda h, w: (lambda sd: np.stack([np.asarray(sd[f"{h}.experts.{first + e}.{w}.weight"]).T for e in range(held)]))
    gated = lambda h: {name: {"kernel": _t(f"{h}.{name}.weight")} for name in ("gate_proj", "up_proj", "down_proj")}
    p: Dict[str, Any] = {"wte": {"embedding": _id("model.embed_tokens.weight")}, "ln_f": scale("model.norm.weight")}
    if not cfg.tie_word_embeddings:
        p["lm_head"] = {"kernel": _t("lm_head.weight")}
    for i in range(cfg.n_layer):
        h = f"model.layers.{i}"
        a = f"{h}.self_attn"
        block = {"ln_1": scale(f"{h}.input_layernorm.weight"), "ln_2": scale(f"{h}.post_attention_layernorm.weight")}
        if cfg.mixer(i) == "kda":
            bias_key = f"{a}.g_b_proj.bias"
            block["kda"] = {
                **{name: {"kernel": _t(f"{a}.{name}.weight")} for name in (
                    "q_proj", "k_proj", "v_proj", "f_a_proj", "f_b_proj", "b_proj", "g_a_proj", "o_proj")},
                "g_b_proj": {"kernel": _t(f"{a}.g_b_proj.weight"),
                             "bias": lambda sd, key=bias_key: np.asarray(sd[key]) if key in sd else np.zeros((inner,), np.float32)},
                **{name: depthwise(f"{a}.{name}1d.weight") for name in ("q_conv", "k_conv", "v_conv")},
                "A_log": lambda sd, key=f"{a}.A_log": np.asarray(sd[key]).reshape(-1),
                "dt_bias": _id(f"{a}.dt_bias"),
                "o_norm": _id(f"{a}.o_norm.weight"),
            }
        else:
            block["attn"] = {
                "q_proj": {"kernel": _t(f"{a}.q_proj.weight")},
                "kv_a_proj": {"kernel": _t(f"{a}.kv_a_proj_with_mqa.weight")},
                "kv_a_norm": scale(f"{a}.kv_a_layernorm.weight"),
                "kv_b_proj": {"kernel": _t(f"{a}.kv_b_proj.weight")},
                "c_proj": {"kernel": _t(f"{a}.o_proj.weight")},
            }
        if cfg.ffn_layers and cfg.ffn_layers[i] == "experts":
            m = f"{h}.block_sparse_moe"
            block["moe"] = {
                "router": _t(f"{m}.gate.weight"),
                "e_score_correction_bias": _id(f"{m}.gate.e_score_correction_bias"),
                "experts_gate": stacked(m, "w1"), "experts_up": stacked(m, "w3"), "experts_down": stacked(m, "w2"),
            }
            if cfg.n_shared_experts:
                block["moe"]["shared"] = gated(f"{m}.shared_experts")
        else:
            block["mlp"] = gated(f"{h}.mlp")
        p[f"h_{i}"] = block
    return p


# Eager converters (tests and tooling): materializations of the specs above.


def convert_gpt2(sd: Dict[str, np.ndarray], cfg: LMConfig) -> Dict[str, Any]:
    return materialize_spec(_spec_gpt2(cfg), sd)


def convert_gptj(sd: Dict[str, np.ndarray], cfg: LMConfig) -> Dict[str, Any]:
    return materialize_spec(_spec_gptj(cfg), sd)


def convert_gpt_neo(sd: Dict[str, np.ndarray], cfg: LMConfig) -> Dict[str, Any]:
    return materialize_spec(_spec_gpt_neo(cfg), sd)


def convert_neox(sd: Dict[str, np.ndarray], cfg: LMConfig) -> Dict[str, Any]:
    return materialize_spec(_spec_neox(cfg), sd)

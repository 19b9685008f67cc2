"""trlx_tpu param pytree → HF checkpoint export.

The inverse of hf_import: after RLHF training, the tuned policy trunk is
written back as an ordinary HuggingFace checkpoint (config.json + weights
via save_pretrained), loadable by `AutoModelForCausalLM.from_pretrained`
or re-imported by trlx_tpu itself. The reference has no export at all —
its checkpoints are Accelerate/DeepSpeed state dirs
(reference: trlx/model/accelerate_base_model.py:126-128) that users must
unwrap by hand; here the handoff to the HF serving/eval ecosystem is one
call.

RL heads (value / Q / V) have no HF counterpart and are exported alongside
as `trlx_tpu_heads.npz` so a resumed fine-tune or an RM built on the policy
can restore them.

Families mirror hf_import: gpt2, gptj, gpt_neo, gpt_neox.
"""

import os
from typing import Any, Dict, Optional

import numpy as np

from trlx_tpu.models.lm import LMConfig


def infer_family(cfg: LMConfig) -> str:
    """Canonical family from the architecture flags (the same axes
    hf_import's per-family tables set)."""
    if cfg.pos_type == "rotary":
        return "gpt_neox" if cfg.fused_qkv else "gptj"
    return "gpt2" if cfg.fused_qkv else "gpt_neo"


def validate_exportable(cfg: LMConfig, family: str):
    """Fail LOUDLY when the LMConfig's semantics can't be represented by the
    target HF family — a silent mismatch would export a checkpoint that
    computes different logits than the trained model."""
    if cfg.has_state or cfg.pos_type == "none" or cfg.n_loops > 1 or cfg.sandwich_norm or (
            cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling, cfg.attention_multiplier) != (1.0, 1.0, 1.0, 0.0):
        raise ValueError(
            "export to an HF checkpoint is not built for a state-space, lightning or kda layer (mixer_layers 'mamba', 'kda', "
            "'lightning': the minicpm_sala family's tensor names are not known here), pos_type "
            "'none', a looped stack (n_loops > 1), sandwich norms "
            f"or the embedding / residual / attention / logits multipliers: HF {family} has none of them, and "
            "the checkpoint would compute another model (models/hf_import.py reads granitemoehybrid and kimi_linear; nothing writes them)")
    if cfg.index_topk:
        raise ValueError(
            "export to an HF checkpoint is not built for the glm_moe_dsa family (an indexer on latent attention, index_topk): "
            f"HF {family} has no such layer, and the family's tensor names are not known here")
    if cfg.router_scoring == "softmax" or cfg.router_input == "block":  # expert layers only: LMConfig holds them to that
        raise ValueError(
            "export to an HF checkpoint is not built for the smallthinker family (a softmax router over the chosen logits, "
            f"router_input 'block'): HF {family} has no such layer, and the family's tensor names are not known here")
    if cfg.attention == "cca" or cfg.router_kind == "mlp" or cfg.router_carry or cfg.residual_scaling:
        raise ValueError(
            "export to an HF checkpoint is not built for the zaya family (attention 'cca', an MLP router with a carried "
            f"state, residual scaling): HF {family} has no such layer, and the family's tensor names are not known here")
    problems = []
    if family == "gpt_neo":
        if cfg.scale_attn:
            problems.append("HF gpt_neo attention is UNSCALED: requires scale_attn=False")
    elif not cfg.scale_attn:
        problems.append(f"HF {family} scales attention by 1/sqrt(head_dim): requires scale_attn=True")
    # Residual structure is fixed per family — except gpt_neox, whose HF
    # config carries use_parallel_residual itself (both styles exportable).
    if family != "gpt_neox":
        wants_parallel = family == "gptj"
        if cfg.parallel_residual != wants_parallel:
            problems.append(
                f"HF {family} uses {'parallel' if wants_parallel else 'sequential'} "
                f"residuals: requires parallel_residual={wants_parallel}"
            )
    # Attention-projection biases are fixed per family; a trained bias the
    # family can't carry would silently vanish from the checkpoint.
    want_qkv_bias = family in ("gpt2", "gpt_neox")
    want_out_bias = family != "gptj"
    if cfg.qkv_bias != want_qkv_bias:
        problems.append(f"HF {family} q/k/v projections: requires qkv_bias={want_qkv_bias}")
    if cfg.out_bias != want_out_bias:
        problems.append(f"HF {family} attention out projection: requires out_bias={want_out_bias}")
    # Local-attention layer patterns exist only in gpt_neo.
    if family != "gpt_neo" and any(t == "local" for t in cfg.attention_layers):
        problems.append(f"HF {family} has no local-attention layers: requires all-global attention_layers")
    if family == "gptj":
        if cfg.extra.get("neox_rotary"):
            problems.append("HF gptj uses interleaved rotary: drop extra.neox_rotary")
        if cfg.use_parallel_ln:
            problems.append("HF gptj has a single shared pre-LN: requires use_parallel_ln=False")
    if family == "gpt_neox" and not cfg.extra.get("neox_rotary"):
        problems.append("HF gpt_neox uses half-rotation rotary: requires extra.neox_rotary=True")
    if problems:
        raise ValueError(
            f"LMConfig not exportable as {family}: " + "; ".join(problems)
        )


def _np(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float32)


def _ln(p) -> Dict[str, np.ndarray]:
    return {"weight": _np(p["scale"]), "bias": _np(p["bias"])}


def export_state_dict(params: Dict[str, Any], cfg: LMConfig, family: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Transformer trunk pytree → flat HF state dict (numpy fp32)."""
    family = family or infer_family(cfg)
    validate_exportable(cfg, family)
    t = params["transformer"] if "transformer" in params else params
    if family == "gpt2":
        return _export_gpt2(t, cfg)
    if family == "gptj":
        return _export_gptj(t, cfg)
    if family == "gpt_neo":
        return _export_gpt_neo(t, cfg)
    if family == "gpt_neox":
        return _export_neox(t, cfg)
    raise ValueError(f"unsupported export family: {family}")


def _put_ln(sd, prefix, p):
    for k, v in _ln(p).items():
        sd[f"{prefix}.{k}"] = v


def _head_weight(t, cfg) -> np.ndarray:
    """The LM head as HF's [vocab, d] weight — the tied embedding or the
    trained untied Dense (dropping the untied head would silently export
    wrong logits)."""
    if cfg.tie_word_embeddings:
        return _np(t["wte"]["embedding"])
    return _np(t["lm_head"]["kernel"]).T


def _head_bias(t, cfg) -> np.ndarray:
    """HF GPTJ's lm_head always has a bias; ours only when
    extra.lm_head_bias — export zeros otherwise (numerically identical)."""
    if not cfg.tie_word_embeddings and "bias" in t.get("lm_head", {}):
        return _np(t["lm_head"]["bias"])
    return np.zeros((cfg.vocab_size,), np.float32)


def _export_gpt2(t, cfg) -> Dict[str, np.ndarray]:
    """Inverse of hf_import.convert_gpt2 (Conv1D keeps [in, out])."""
    sd: Dict[str, np.ndarray] = {
        "transformer.wte.weight": _np(t["wte"]["embedding"]),
        "transformer.wpe.weight": _np(t["wpe"]["embedding"]),
    }
    _put_ln(sd, "transformer.ln_f", t["ln_f"])
    for i in range(cfg.n_layer):
        h, o = f"transformer.h.{i}", t[f"h_{i}"]
        _put_ln(sd, f"{h}.ln_1", o["ln_1"])
        _put_ln(sd, f"{h}.ln_2", o["ln_2"])
        sd[f"{h}.attn.c_attn.weight"] = _np(o["attn"]["c_qkv"]["kernel"])
        sd[f"{h}.attn.c_attn.bias"] = _np(o["attn"]["c_qkv"]["bias"])
        sd[f"{h}.attn.c_proj.weight"] = _np(o["attn"]["c_proj"]["kernel"])
        sd[f"{h}.attn.c_proj.bias"] = _np(o["attn"]["c_proj"]["bias"])
        sd[f"{h}.mlp.c_fc.weight"] = _np(o["mlp"]["c_fc"]["kernel"])
        sd[f"{h}.mlp.c_fc.bias"] = _np(o["mlp"]["c_fc"]["bias"])
        sd[f"{h}.mlp.c_proj.weight"] = _np(o["mlp"]["c_proj"]["kernel"])
        sd[f"{h}.mlp.c_proj.bias"] = _np(o["mlp"]["c_proj"]["bias"])
    sd["lm_head.weight"] = _head_weight(t, cfg)
    return sd


def _export_gptj(t, cfg) -> Dict[str, np.ndarray]:
    """Inverse of hf_import.convert_gptj (nn.Linear wants [out, in])."""
    sd: Dict[str, np.ndarray] = {
        "transformer.wte.weight": _np(t["wte"]["embedding"]),
        "lm_head.weight": _head_weight(t, cfg),
        "lm_head.bias": _head_bias(t, cfg),
    }
    _put_ln(sd, "transformer.ln_f", t["ln_f"])
    for i in range(cfg.n_layer):
        h, o = f"transformer.h.{i}", t[f"h_{i}"]
        _put_ln(sd, f"{h}.ln_1", o["ln_1"])
        sd[f"{h}.attn.q_proj.weight"] = _np(o["attn"]["q_proj"]["kernel"]).T
        sd[f"{h}.attn.k_proj.weight"] = _np(o["attn"]["k_proj"]["kernel"]).T
        sd[f"{h}.attn.v_proj.weight"] = _np(o["attn"]["v_proj"]["kernel"]).T
        sd[f"{h}.attn.out_proj.weight"] = _np(o["attn"]["c_proj"]["kernel"]).T
        sd[f"{h}.mlp.fc_in.weight"] = _np(o["mlp"]["c_fc"]["kernel"]).T
        sd[f"{h}.mlp.fc_in.bias"] = _np(o["mlp"]["c_fc"]["bias"])
        sd[f"{h}.mlp.fc_out.weight"] = _np(o["mlp"]["c_proj"]["kernel"]).T
        sd[f"{h}.mlp.fc_out.bias"] = _np(o["mlp"]["c_proj"]["bias"])
    return sd


def _export_gpt_neo(t, cfg) -> Dict[str, np.ndarray]:
    """Inverse of hf_import.convert_gpt_neo."""
    sd: Dict[str, np.ndarray] = {
        "transformer.wte.weight": _np(t["wte"]["embedding"]),
        "transformer.wpe.weight": _np(t["wpe"]["embedding"]),
    }
    _put_ln(sd, "transformer.ln_f", t["ln_f"])
    for i in range(cfg.n_layer):
        h, o = f"transformer.h.{i}", t[f"h_{i}"]
        a = f"{h}.attn.attention"
        _put_ln(sd, f"{h}.ln_1", o["ln_1"])
        _put_ln(sd, f"{h}.ln_2", o["ln_2"])
        sd[f"{a}.q_proj.weight"] = _np(o["attn"]["q_proj"]["kernel"]).T
        sd[f"{a}.k_proj.weight"] = _np(o["attn"]["k_proj"]["kernel"]).T
        sd[f"{a}.v_proj.weight"] = _np(o["attn"]["v_proj"]["kernel"]).T
        sd[f"{a}.out_proj.weight"] = _np(o["attn"]["c_proj"]["kernel"]).T
        sd[f"{a}.out_proj.bias"] = _np(o["attn"]["c_proj"]["bias"])
        sd[f"{h}.mlp.c_fc.weight"] = _np(o["mlp"]["c_fc"]["kernel"]).T
        sd[f"{h}.mlp.c_fc.bias"] = _np(o["mlp"]["c_fc"]["bias"])
        sd[f"{h}.mlp.c_proj.weight"] = _np(o["mlp"]["c_proj"]["kernel"]).T
        sd[f"{h}.mlp.c_proj.bias"] = _np(o["mlp"]["c_proj"]["bias"])
    sd["lm_head.weight"] = _head_weight(t, cfg)
    return sd


def _export_neox(t, cfg) -> Dict[str, np.ndarray]:
    """Inverse of hf_import.convert_neox (re-interleave q|k|v blocks into the
    heads-major [nh, 3, hd] fused layout)."""
    nh, hd, d = cfg.n_head, cfg.head_dim, cfg.d_model

    def qkv_w_inv(w):  # ours [d, 3d] → torch [3d, d] heads-major interleave
        w = w.T.reshape(3, nh, hd, d)  # q|k|v blocks
        w = np.stack([w[j] for j in range(3)], axis=1)  # [nh, 3, hd, d]
        return w.reshape(3 * d, d)

    def qkv_b_inv(b):
        b = b.reshape(3, nh, hd)
        return np.stack([b[j] for j in range(3)], axis=1).reshape(3 * d)

    sd: Dict[str, np.ndarray] = {
        "gpt_neox.embed_in.weight": _np(t["wte"]["embedding"]),
        "embed_out.weight": _head_weight(t, cfg),
    }
    _put_ln(sd, "gpt_neox.final_layer_norm", t["ln_f"])
    for i in range(cfg.n_layer):
        h, o = f"gpt_neox.layers.{i}", t[f"h_{i}"]
        _put_ln(sd, f"{h}.input_layernorm", o["ln_1"])
        _put_ln(sd, f"{h}.post_attention_layernorm", o["ln_2"])
        sd[f"{h}.attention.query_key_value.weight"] = qkv_w_inv(_np(o["attn"]["c_qkv"]["kernel"]))
        sd[f"{h}.attention.query_key_value.bias"] = qkv_b_inv(_np(o["attn"]["c_qkv"]["bias"]))
        sd[f"{h}.attention.dense.weight"] = _np(o["attn"]["c_proj"]["kernel"]).T
        sd[f"{h}.attention.dense.bias"] = _np(o["attn"]["c_proj"]["bias"])
        sd[f"{h}.mlp.dense_h_to_4h.weight"] = _np(o["mlp"]["c_fc"]["kernel"]).T
        sd[f"{h}.mlp.dense_h_to_4h.bias"] = _np(o["mlp"]["c_fc"]["bias"])
        sd[f"{h}.mlp.dense_4h_to_h.weight"] = _np(o["mlp"]["c_proj"]["kernel"]).T
        sd[f"{h}.mlp.dense_4h_to_h.bias"] = _np(o["mlp"]["c_proj"]["bias"])
    return sd


def build_hf_config(cfg: LMConfig, family: Optional[str] = None):
    """LMConfig → the matching transformers config object (offline)."""
    family = family or infer_family(cfg)
    validate_exportable(cfg, family)
    # n_inner/intermediate_size: only set when it differs from the 4*d
    # default (None keeps canonical configs byte-identical).
    n_inner = cfg.d_ff if (cfg.d_ff and cfg.d_ff != 4 * cfg.d_model) else None
    if family == "gpt2":
        from transformers import GPT2Config

        return GPT2Config(
            vocab_size=cfg.vocab_size,
            n_positions=cfg.max_position,
            n_embd=cfg.d_model,
            n_layer=cfg.n_layer,
            n_head=cfg.n_head,
            n_inner=n_inner,
            activation_function=cfg.activation,
            layer_norm_epsilon=cfg.ln_eps,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
    if family == "gptj":
        from transformers import GPTJConfig

        return GPTJConfig(
            vocab_size=cfg.vocab_size,
            n_positions=cfg.max_position,
            n_embd=cfg.d_model,
            n_layer=cfg.n_layer,
            n_head=cfg.n_head,
            n_inner=n_inner,
            rotary_dim=cfg.rotary_dim or cfg.head_dim,
            activation_function=cfg.activation,
            layer_norm_epsilon=cfg.ln_eps,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
    if family == "gpt_neo":
        from transformers import GPTNeoConfig

        layers = list(cfg.attention_layers) or ["global"] * cfg.n_layer
        return GPTNeoConfig(
            vocab_size=cfg.vocab_size,
            max_position_embeddings=cfg.max_position,
            hidden_size=cfg.d_model,
            num_layers=cfg.n_layer,
            num_heads=cfg.n_head,
            intermediate_size=cfg.ff_dim,
            window_size=cfg.window_size or 256,
            attention_types=[[layers, 1]],
            activation_function=cfg.activation,
            layer_norm_epsilon=cfg.ln_eps,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
    if family == "gpt_neox":
        from transformers import GPTNeoXConfig

        return GPTNeoXConfig(
            vocab_size=cfg.vocab_size,
            max_position_embeddings=cfg.max_position,
            hidden_size=cfg.d_model,
            num_hidden_layers=cfg.n_layer,
            num_attention_heads=cfg.n_head,
            intermediate_size=cfg.ff_dim,
            rotary_pct=(cfg.rotary_dim or cfg.head_dim) / cfg.head_dim,
            use_parallel_residual=cfg.parallel_residual,
            hidden_act=cfg.activation,
            layer_norm_eps=cfg.ln_eps,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
    raise ValueError(f"unsupported export family: {family}")


_HF_CLASSES = {
    "gpt2": "GPT2LMHeadModel",
    "gptj": "GPTJForCausalLM",
    "gpt_neo": "GPTNeoForCausalLM",
    "gpt_neox": "GPTNeoXForCausalLM",
}


def export_hf(
    params: Dict[str, Any],
    cfg: LMConfig,
    out_dir: str,
    family: Optional[str] = None,
    head_params: Optional[Dict[str, Any]] = None,
):
    """Write an HF checkpoint directory from a trained param pytree.

    `params` is a model pytree with a "transformer" subtree (the head
    wrappers' layout) or a bare trunk. `head_params` (e.g. {"v_head": ...})
    is saved alongside as trlx_tpu_heads.npz — HF has no slot for RL heads.
    Returns out_dir. Round-trip guaranteed against hf_import (tested per
    family in tests/test_hf_export.py).
    """
    import torch
    import transformers

    family = family or infer_family(cfg)
    hf_config = build_hf_config(cfg, family)
    model_cls = getattr(transformers, _HF_CLASSES[family])
    model = model_cls(hf_config)

    # A tuned soft prompt has no HF representation — carry it in the heads
    # sidecar instead of silently dropping the training's entire effect.
    trunk = params["transformer"] if "transformer" in params else params
    if "soft_prompt" in trunk:
        head_params = dict(head_params or {})
        head_params["soft_prompt"] = trunk["soft_prompt"]

    # copy=True: jax-backed numpy views are read-only, which torch rejects
    sd = {
        k: torch.from_numpy(np.array(v, copy=True))
        for k, v in export_state_dict(params, cfg, family).items()
    }
    missing, unexpected = model.load_state_dict(sd, strict=False)

    # Only attention-mask / rotary buffers may be absent from the export;
    # anything else means the export map drifted from the family.
    def _is_buffer(k: str) -> bool:
        return any(
            s in k
            for s in (
                ".attn.bias",
                ".attn.masked_bias",
                ".attention.bias",
                ".attention.masked_bias",
                "rotary_emb",
                "inv_freq",
            )
        )

    real_missing = [k for k in missing if not _is_buffer(k)]
    if unexpected:
        raise ValueError(f"export produced unexpected keys: {unexpected[:5]}")
    if real_missing:
        raise ValueError(f"export left keys uninitialized: {real_missing[:5]}")

    os.makedirs(out_dir, exist_ok=True)
    model.save_pretrained(out_dir, safe_serialization=True)
    if head_params:
        flat = {}

        def flatten(prefix, tree):
            if isinstance(tree, dict):
                for k, v in tree.items():
                    flatten(f"{prefix}/{k}" if prefix else k, v)
            else:
                flat[prefix] = np.asarray(tree, dtype=np.float32)

        flatten("", head_params)
        np.savez(os.path.join(out_dir, "trlx_tpu_heads.npz"), **flat)
    return out_dir

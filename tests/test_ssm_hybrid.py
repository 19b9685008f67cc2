"""The hybrid state-space / attention family (models/ssm.py; models/lm.py
`mixer_layers`, `pos_type: none`, the four multipliers, the state leaves of
`init_cache`) against the plain reference
`benchmark/references/ssm_hybrid_decoder.py` and against the publisher's own
code (`transformers`' GraniteMoeHybridForCausalLM): seeded random weights,
tiny sizes, float32, CPU. The kinds are the granite-4.0-h-micro
configuration's rehearsal kinds (ISSUE 32).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import ssm_hybrid_decoder as reference
from trlx_tpu.models import ssm
from trlx_tpu.models.lm import (LMConfig, TransformerLM, cache_bytes, cache_bytes_per_token, cache_partition_spec,
                                decode_step_bytes, flash_eligible, init_cache, init_paged_cache, state_bytes)

# mamba, attention, mamba, mamba, mamba; 8 state-space heads of 16 with a
# state of 16, chunks of 8; 4 query heads over 2 K/V heads of 16.
ARCH = dict(
    vocab_size=512, n_layer=5, n_head=4, n_kv_head=2, head_width=16, d_model=64, d_ff=128, max_position=128,
    eos_token_id=0, pos_type="none", norm="rmsnorm", mlp="gated", attention="mha", activation="silu", ln_eps=1e-5,
    parallel_residual=False, tie_word_embeddings=True, fused_qkv=False, qkv_bias=False, out_bias=False,
    mixer_layers=["mamba", "attention", "mamba", "mamba", "mamba"], ssm_heads=8, ssm_head_dim=16, ssm_state=16,
    ssm_conv=4, ssm_chunk=8, embedding_multiplier=12.0, attention_multiplier=0.0625, residual_multiplier=0.22,
    logits_scaling=8.0, embed_init_std=0.02, draw_dtype="float32",
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 3, 29  # not a multiple of the chunk
PADS = (0, 5, 11)  # left padding of each row


def _model(seed=0, t=T, **over):
    cfg = LMConfig.from_dict({**ARCH, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, t), 2, cfg.vocab_size)
    mask = jnp.stack([(jnp.arange(t) >= pad).astype(jnp.int32) for pad in PADS])
    params = model.init(jax.random.PRNGKey(seed), ids, mask)["params"]
    # every vector matters: the norm scales, D and the convolution's bias start at constants
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [x + 0.05 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)])
    return cfg, model, params, ids * mask, mask


# ---- (a) the program against the reference, rows left-padded into one batch ----------------------


@pytest.mark.parametrize("attn_impl", ["xla", "flash"], ids=["einsum", "flash kernels, interpreted, heads padded to 128"])
def test_logits_match_the_reference_and_a_padded_row_equals_its_unpadded_self(attn_impl):
    cfg, model, params, ids, mask = _model(attn_impl=attn_impl)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids, mask)["logits"]
        want = reference.forward(params, ARCH, ids, mask, T)
        assert float(jnp.abs(want).max()) > 0.05
        for row, pad in enumerate(PADS):
            np.testing.assert_allclose(got[row, pad:], want[row, pad:], atol=2e-6, rtol=1e-4)
            alone = model.apply({"params": params}, ids[row:row + 1, pad:], mask[row:row + 1, pad:])["logits"]
            np.testing.assert_allclose(got[row, pad:], alone[0], atol=2e-6, rtol=1e-4)
    # and each multiplier does something: without it the logits differ
    for key in ("embedding_multiplier", "attention_multiplier", "residual_multiplier", "logits_scaling"):
        other = TransformerLM(cfg.replace(**{key: 1.0})).apply({"params": params}, ids, mask)["logits"]
        assert float(jnp.abs(other - got).max()) > 1e-3, key


# ---- (b) chunked against recurrent ------------------------------------------------------------


@pytest.mark.parametrize("t, chunk, H, P, dtype", [
    (29, 8, 4, 8, "float32"), (5, 8, 4, 8, "float32"), (32, 8, 4, 8, "float32"), (24, 256, 4, 8, "float32"),
    (29, 8, 4, 64, "float32"), (29, 8, 3, 64, "float32"), (29, 8, 2, 128, "float32"), (300, 256, 2, 64, "float32"),
    (29, 8, 4, 64, "bfloat16"), (300, 256, 2, 64, "bfloat16"),
], ids=["not a multiple of the chunk", "shorter than one chunk", "four chunks", "one short chunk",
        "heads 64 wide: half a lane tile", "an odd number of heads 64 wide", "heads a whole lane tile wide",
        "chunks of 256, the second padded", "bf16 operands", "bf16 operands, chunks of 256"])
def test_the_chunked_scan_is_the_token_recurrence(t, chunk, H, P, dtype):
    """`ssd_chunked` against `ssd_step` run over the tokens, in float32:
    y, the last state, and the gradient of a scalar of both with respect to x,
    dt, B and C. With operands in bf16 (x arrives in it; each product rounds
    its operands to 8 bits once) the float32 recurrence is met to a relative
    rms of 1%. Row 1 is left-padded (dt 0): it equals its unpadded self."""
    N, b, pad = 16, 2, 3
    keys = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(keys[0], (b, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, t, H)) - 2.0)
    dt = dt.at[1, :pad].set(0.0)  # padding: the state stays as it is
    Bm, Cm = jax.random.normal(keys[2], (b, t, N)), jax.random.normal(keys[3], (b, t, N))
    wy, wl = jax.random.normal(keys[4], (b, t, H, P)), jax.random.normal(keys[5], (b, H, P, N))
    a = -jnp.arange(1.0, H + 1)
    if dtype == "bfloat16":  # what the mixer hands over, and what the recurrence then reads
        x, Bm, Cm = (v.astype(jnp.bfloat16) for v in (x, Bm, Cm))

    def chunked(x, dt, Bm, Cm):
        return ssm.ssd_chunked(x, dt, a, Bm, Cm, chunk, jnp.dtype(dtype))

    def recurrent(x, dt, Bm, Cm):  # `ssd_step`, one token after another
        def token(state, ops):
            y, state = ssm.ssd_step(state, ops[0], ops[1], a, ops[2], ops[3])
            return state, y

        state, ys = jax.lax.scan(token, jnp.zeros((b, H, P, N)), tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, Bm, Cm)))
        return jnp.moveaxis(ys, 0, 1), state

    scalar = lambda scan: lambda *ops: sum(jnp.sum(out * w) for out, w in zip(scan(*ops), (wy, wl)))
    with jax.default_matmul_precision("highest"):
        (y, last), (want_y, want_last) = jax.jit(chunked)(x, dt, Bm, Cm), jax.jit(recurrent)(x, dt, Bm, Cm)
        alone_y, alone_last = jax.jit(chunked)(x[1:, pad:], dt[1:, pad:], Bm[1:, pad:], Cm[1:, pad:])
        grads = jax.jit(jax.grad(scalar(chunked), argnums=(0, 1, 2, 3)))(x, dt, Bm, Cm)
        want_grads = jax.jit(jax.grad(scalar(recurrent), argnums=(0, 1, 2, 3)))(x, dt, Bm, Cm)
    assert y.dtype == last.dtype == jnp.float32 and all(g.dtype == w.dtype for g, w in zip(grads, want_grads))

    def close(got, want, name, gradient=False):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        if dtype == "bfloat16":
            rel = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
            assert rel < (2e-2 if gradient else 1e-2), (name, rel)
        elif gradient:
            np.testing.assert_allclose(got, want, atol=2e-4 * float(np.abs(want).max()) + 1e-9, rtol=2e-3, err_msg=name)
        else:
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4, err_msg=name)

    close(y, want_y, "y")
    close(last, want_last, "last state")
    close(y[1, pad:], alone_y[0], "the padded row's y")
    close(last[1], alone_last[0], "the padded row's state")
    for g, w, name in zip(grads, want_grads, ("x", "dt", "B", "C")):
        close(g, w, "gradient of " + name, gradient=True)


def test_a_scoring_pass_over_many_rows_goes_through_row_groups(monkeypatch):
    cfg, model, params, ids, mask = _model()
    whole = model.apply({"params": params}, ids, mask)["logits"]
    monkeypatch.setattr(ssm, "SCAN_TOKENS", T)  # one row a group
    grouped = model.apply({"params": params}, ids, mask)["logits"]
    np.testing.assert_allclose(grouped, whole, atol=1e-6, rtol=1e-5)


# ---- (c) prefill + decode through the state against the full forward ---------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["", "remat"])
@pytest.mark.parametrize("prompt", [13, 2], ids=["prompt 13", "prompt shorter than the convolution"])
def test_prefill_then_decode_through_the_state_matches_the_full_forward(prompt, remat):
    """Prompts of unequal length, left-padded into one batch (a row of the
    second case has NO real token in its prompt: its state must still be
    zero when its first token arrives)."""
    cfg, model, params, ids, mask = _model(remat=remat)
    with jax.default_matmul_precision("highest"):
        full = model.apply({"params": params}, ids, mask)["logits"]
        cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
        out = model.apply({"params": params}, ids[:, :prompt], mask[:, :prompt], cache=init_cache(cfg, B, T),
                          cache_index=0, cache_mask=cache_mask)
        real = np.asarray(mask[:, :prompt]).astype(bool)
        np.testing.assert_allclose(np.asarray(out["logits"])[real], np.asarray(full[:, :prompt])[real], atol=2e-6, rtol=1e-4)
        cache = out["cache"]
        step = jax.jit(lambda cache, tok, m, index, cm: model.apply(
            {"params": params}, tok, m, cache=cache, cache_index=index, cache_mask=cm))
        for t in range(prompt, T):
            cache_mask = cache_mask.at[:, t].set(mask[:, t])
            out = step(cache, ids[:, t:t + 1], mask[:, t:t + 1], t, cache_mask)
            cache = out["cache"]
            live = np.asarray(mask[:, t]).astype(bool)
            np.testing.assert_allclose(np.asarray(out["logits"][:, 0])[live], np.asarray(full[:, t])[live], atol=3e-6, rtol=1e-4)
    shapes = [tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(cache[0])]
    assert shapes == [(B, 3, 8 * 16 + 2 * 16), (B, 8, 16, 16)] and cache[0][1].dtype == jnp.float32


def test_the_state_the_decode_steps_leave_is_the_reference_s_and_a_bf16_state_is_farther():
    """The state check's two sides (benchmark/state_parity.py): the cache's state leaf of every state-space layer after
    prefill + decode against `layer_state`, the reference's recurrence on the unpadded row; and, with the first
    layer's heads set slow as the check sets them, the reference's own bf16-state rerun farther from it than the
    bf16 stream alone."""
    from benchmark.state_parity import SLOW_STEP, slow_heads

    cfg, model, params, ids, mask = _model()
    params = slow_heads(params, 0)
    np.testing.assert_allclose(jax.nn.softplus(params["h_0"]["mamba"]["dt_bias"]), SLOW_STEP, rtol=1e-5)
    prompt = 13
    with jax.default_matmul_precision("highest"):
        cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
        cache = model.apply({"params": params}, ids[:, :prompt], mask[:, :prompt], cache=init_cache(cfg, B, T),
                            cache_index=0, cache_mask=cache_mask)["cache"]
        step = jax.jit(lambda cache, tok, m, index, cm: model.apply(
            {"params": params}, tok, m, cache=cache, cache_index=index, cache_mask=cm)["cache"])
        for t in range(prompt, T):
            cache_mask = cache_mask.at[:, t].set(mask[:, t])
            cache = step(cache, ids[:, t:t + 1], mask[:, t:t + 1], t, cache_mask)
        for layer, kind in enumerate(ARCH["mixer_layers"]):
            if kind != "mamba":
                with pytest.raises(ValueError, match="no state-space layer"):
                    reference.layer_state(params, ARCH, ids[0], layer)
                continue
            for row, pad in enumerate(PADS):
                want = reference.layer_state(params, ARCH, ids[row, pad:], layer)
                assert float(jnp.abs(want).max()) > 1e-3
                np.testing.assert_allclose(cache[layer][1][row], want, atol=2e-6, rtol=1e-4)
        want = reference.layer_state(params, ARCH, ids[0], 0)
        far = {name: float(jnp.sqrt(jnp.sum((reference.layer_state(params, ARCH, ids[0], 0, precision=name) - want) ** 2)))
               for name in ("bfloat16_stream", "bfloat16_state")}
    assert 0 < far["bfloat16_stream"] < far["bfloat16_state"], far


def test_generate_runs_the_static_path_and_matches_a_teacher_forced_forward():
    from trlx_tpu.ops.generate import generate
    from trlx_tpu.ops.sampling import GenerateConfig

    cfg, model, params, ids, mask = _model()
    prompt, new = 12, 9
    gcfg = GenerateConfig(max_new_tokens=new, min_new_tokens=new, do_sample=False, eos_token_id=None, pad_token_id=0)

    def stats(tok, s):
        return {"logprob": jnp.take_along_axis(jax.nn.log_softmax(s["last_logits"]), tok[:, None].astype(jnp.int32), axis=-1)[:, 0]}

    tokens, out_mask, got = jax.jit(lambda p, i, m: generate(
        {"params": p}, i, m, jax.random.PRNGKey(0), model=model, gcfg=gcfg, step_stats_fn=stats))(
            params, ids[:, :prompt], mask[:, :prompt])
    logp = jax.nn.log_softmax(model.apply({"params": params}, tokens, out_mask)["logits"])
    want = jnp.take_along_axis(logp[:, prompt - 1:-1], tokens[:, prompt:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(got["logprob"], want, atol=2e-5, rtol=1e-4)  # PPO's ratio at the first step: decode against chunked


# ---- (d) gradients ------------------------------------------------------------------------------


def test_gradients_of_ppo_s_loss_match_the_reference():
    """Every parameter of the unfrozen top blocks (state-space: A_log, dt_bias,
    D, the convolution, both projections, the gated norm) and the embedding,
    whose gradient crosses the frozen state-space layers below them; under
    remat, as the train step runs."""
    from trlx_tpu.models.heads import trainable_mask

    cfg, model, params, ids, mask = _model(remat=True)
    prompt = 12
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    old = -6.0 + 0.1 * jax.random.normal(keys[0], (B, T - prompt))
    advantages = jax.random.normal(keys[1], (B, T - prompt))
    response_mask = mask[:, prompt:].astype(jnp.float32)

    def ppo_loss(logits):
        logp = jax.nn.log_softmax(logits[:, prompt - 1:-1])
        new = jnp.take_along_axis(logp, ids[:, prompt:, None], axis=-1)[..., 0]
        ratio = jnp.exp(new - old)
        loss = jnp.maximum(-advantages * ratio, -advantages * jnp.clip(ratio, 0.8, 1.2))
        return jnp.sum(loss * response_mask) / jnp.sum(response_mask)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: ppo_loss(model.apply({"params": p}, ids, mask)["logits"]))(params)
        want = jax.grad(lambda p: ppo_loss(reference.forward(p, ARCH, ids, mask, T)))(params)
    trains = trainable_mask({"transformer": params}, cfg, 2)["transformer"]
    checked = set()
    for (path, g), w, train in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want),
                                   jax.tree_util.tree_leaves(trains)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale + 1e-9, rtol=2e-3, err_msg=name)
        if train:
            checked.add(name.split("']['")[-1].rstrip("']") if "mamba" in name or "wte" in name else "")
    assert {"A_log", "D", "dt_bias", "conv_kernel", "conv_bias", "norm_scale", "kernel", "embedding"} <= checked


# ---- (e) against the publisher's code -----------------------------------------------------------


def test_program_reference_and_transformers_agree_on_the_publisher_s_tensors():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from trlx_tpu.models.hf_import import _detect_family, lm_config_from_hf, materialize_spec, trunk_spec

    published = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                                            "granite-4.0-h-micro.json")))["published"]
    tiny = dict(published, hidden_size=64, intermediate_size=128, shared_intermediate_size=128, num_hidden_layers=5,
                layer_types=["mamba", "attention", "mamba", "mamba", "mamba"], num_attention_heads=4,
                num_key_value_heads=2, vocab_size=512, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=8, max_position_embeddings=128, attention_multiplier=0.0625)
    # the multipliers, position_embedding_type "nope" and num_local_experts 0 are the published ones
    assert (tiny["embedding_multiplier"], tiny["residual_multiplier"], tiny["logits_scaling"],
            tiny["position_embedding_type"], tiny["num_local_experts"]) == (12, 0.22, 8, "nope", 0)
    hf_config = transformers.GraniteMoeHybridConfig(**{k: v for k, v in tiny.items() if k != "model_type"})
    torch.manual_seed(0)
    hf = transformers.GraniteMoeHybridForCausalLM(hf_config).eval()
    with torch.no_grad():  # every vector matters: they start at constants
        for name, p in hf.named_parameters():
            if p.ndim == 1:
                p.add_(0.05 * torch.randn_like(p))
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    assert _detect_family(sd) == "granitemoehybrid"
    cfg = lm_config_from_hf(hf_config, dtype="float32", param_dtype="float32", attn_impl="xla")
    assert cfg.mixer_layers == tuple(ARCH["mixer_layers"]) and cfg.pos_type == "none" and cfg.has_ssm
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk) == (8, 16, 16, 4, 8)
    params = jax.tree_util.tree_map(jnp.asarray, materialize_spec(trunk_spec("granitemoehybrid", cfg), sd))
    model = TransformerLM(cfg)
    ids = np.random.default_rng(0).integers(2, 512, size=(B, T))
    mask = np.stack([(np.arange(T) >= pad).astype(np.int64) for pad in PADS])  # an attention_mask that left-pads
    init = model.init(jax.random.PRNGKey(0), jnp.asarray(ids, jnp.int32), jnp.asarray(mask, jnp.int32))["params"]
    assert jax.tree_util.tree_map(lambda a: a.shape, init) == jax.tree_util.tree_map(lambda a: a.shape, params)
    with torch.no_grad():
        theirs = hf(input_ids=torch.tensor(ids), attention_mask=torch.tensor(mask)).logits.numpy()
    arch = {**ARCH, "mixer_layers": list(cfg.mixer_layers)}
    with jax.default_matmul_precision("highest"):
        ours = model.apply({"params": params}, jnp.asarray(ids * mask, jnp.int32), jnp.asarray(mask, jnp.int32))["logits"]
        plain = reference.forward(params, arch, jnp.asarray(ids, jnp.int32), mask, T)
    assert np.abs(theirs).max() > 0.05
    for row, pad in enumerate(PADS):
        np.testing.assert_allclose(ours[row, pad:], theirs[row, pad:], atol=5e-6, rtol=2e-4)
        np.testing.assert_allclose(plain[row, pad:], theirs[row, pad:], atol=5e-6, rtol=2e-4)
    # the family's expert members are not built
    with pytest.raises(ValueError, match="num_local_experts"):
        lm_config_from_hf(transformers.GraniteMoeHybridConfig(**{**{k: v for k, v in tiny.items() if k != "model_type"},
                                                               "num_local_experts": 4, "num_experts_per_tok": 2}))


def test_export_refuses_a_state_space_layer():
    from trlx_tpu.models.hf_export import export_state_dict

    cfg, model, params, _, _ = _model()
    with pytest.raises(ValueError, match="state-space"):
        export_state_dict(params, cfg)


# ---- (f) every refusal --------------------------------------------------------------------------


@pytest.mark.parametrize("bad, message", [
    ({"attention": "mla", "q_lora_rank": 8, "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 8, "pos_type": "rotary", "n_kv_head": 0, "head_width": 0}, "attention 'mla'"),
    ({"kv_cache_quant": True}, "kv_cache_quant"),
    ({"n_soft_tokens": 4}, "soft prompts"),
    ({"sp_size": 2, "n_kv_head": 0}, "sp ring"),
    ({"attention_layers": ["global", "local", "global", "global", "global"], "window_size": 8}, "windowed"),
    ({"ffn_layers": ["dense", "experts", "dense", "dense", "dense"], "n_experts": 4, "experts_per_token": 2,
      "expert_d_ff": 32}, "expert layers"),
    ({"parallel_residual": True}, "parallel_residual"),
    ({"ssm_state": 0}, "needs ssm_heads"),
    ({"mixer_layers": ["mamba", "attention"]}, "mixer_layers must name"),
    ({"mixer_layers": ["mamba", "attention", "rwkv", "mamba", "mamba"]}, "mixer_layers must name"),
    ({"pos_type": "alibi"}, "unknown pos_type"),
    ({"logits_scaling": 0.0}, "positive"),
    ({"extra": {"lm_head_bias": True}, "tie_word_embeddings": False}, "head bias"),
    ({"ssm_groups": 2}, "unknown architecture key"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **F32, **bad})


@pytest.mark.parametrize("options", [{}, {"paged_kv": True}, {"spec_decode": "ngram", "spec_k": 4}],
                         ids=["engine", "paged pool", "spec decode"])
def test_the_engine_the_paged_pool_and_spec_decode_refuse_a_state_space_layer(options):
    from trlx_tpu.engine.rollout_engine import RolloutEngine
    from trlx_tpu.ops.sampling import GenerateConfig

    cfg, model, params, _, _ = _model()
    with pytest.raises(NotImplementedError, match="state-space"):
        RolloutEngine(model, GenerateConfig(max_new_tokens=4), n_slots=2, prompt_width=8, **options)
    with pytest.raises(NotImplementedError, match="state-space"):
        init_paged_cache(cfg, 4, 8)


def test_the_trunk_refuses_calls_the_static_generate_path_does_not_make():
    cfg, model, params, ids, mask = _model()
    cache = init_cache(cfg, B, T)
    cases = {
        "a per-row offset": dict(input_ids=ids[:, :1], attention_mask=mask[:, :1], cache=cache,
                                 cache_index=jnp.zeros((B,), jnp.int32), cache_mask=mask),
        "a verify window": dict(input_ids=ids[:, :4], attention_mask=mask[:, :4], cache=cache, cache_index=8, cache_mask=mask),
        "packed segments": dict(input_ids=ids, attention_mask=mask, segment_ids=jnp.zeros((B, T), jnp.int32)),
    }
    for name, call in cases.items():
        with pytest.raises(NotImplementedError, match="state-space"):
            model.apply({"params": params}, **call)


def test_decode_weight_quant_refuses_a_state_space_layer(tmp_path):
    from trlx_tpu.trainer.api import default_config, get_model

    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 2
    config.model.decode_weight_quant = True
    config.train.checkpoint_dir = str(tmp_path)
    config.train.seq_length = 16
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 8, "do_sample": True}
    with pytest.raises(ValueError, match="state-space"):
        get_model(config.model.model_type)(config, reward_fn=lambda rows: [0.0] * len(rows), metric_fn=None, logit_mask=None)


# ---- (g) the cache's shapes and the counters ----------------------------------------------------


def test_cache_shapes_and_counters_by_hand():
    cfg = LMConfig.from_dict({**ARCH, "dtype": "bfloat16"})
    rows, span = 6, 40
    cache = init_cache(cfg, rows, span)
    state, conv = rows * 8 * 16 * 16 * 4, rows * 3 * (8 * 16 + 32) * 2  # a float32 state, a bf16 window
    keys = rows * span * 2 * 2 * 16 * 2  # K and V, 2 heads of 16, bf16
    assert [c[1].dtype for i, c in enumerate(cache) if cfg.mixer(i) == "mamba"] == [jnp.float32] * 4
    assert [tuple(c[0].shape) for c in cache] == [(rows, 3, 160), (rows, span, 2, 16)] + [(rows, 3, 160)] * 3
    assert state_bytes(cfg, rows) == 4 * (state + conv)
    assert cache_bytes(cfg, rows, span) == 4 * (state + conv) + keys
    assert cache_bytes_per_token(cfg) == 2 * 2 * 16 * 2  # the attention layer's alone: a state holds nothing a token
    # the chunked scan's float32 results are positions-minor: a chunk fills the 128 lanes of a tile or it does not
    assert [ssm.lane_fill(cfg.replace(ssm_chunk=q), 1024) for q in (8, 128, 192, 256)] == [0.0625, 1.0, 0.75, 1.0]
    assert ssm.lane_fill(cfg.replace(ssm_chunk=256), 128) == 1.0 and ssm.lane_fill(cfg.replace(ssm_chunk=256), 40) == 0.3125
    needed, rw = decode_step_bytes(cfg, rows, keys_read=10, weight_bytes=1000)
    assert rw == 2 * 4 * (state + conv) and needed == 1000 + rw + 10 * rows * 2 * 2 * 16 * 2
    # on a mesh: the state's rows over the data axes and its heads over tp, the window whole on every tp shard
    from jax.sharding import PartitionSpec

    from trlx_tpu.parallel.mesh import AXIS_TP, DATA_AXES
    assert cache_partition_spec(cfg, 4, layer=0) == PartitionSpec(DATA_AXES, AXIS_TP, None, None)
    assert cache_partition_spec(cfg, 3, layer=0) == PartitionSpec(DATA_AXES, None, None)
    assert cache_partition_spec(cfg, 4, layer=1) == PartitionSpec(DATA_AXES, None, AXIS_TP, None)
    # the kernels where a TPU is; here, a CPU, the einsum whatever the width
    assert not flash_eligible(cfg.replace(head_width=64), 1024, has_cache=False)
    assert flash_eligible(cfg.replace(head_width=64, attn_impl="flash"), 1024, has_cache=False)


def test_partition_rules_name_every_new_parameter():
    from trlx_tpu.parallel.sharding import lm_partition_rules, match_partition_rules

    cfg, model, params, _, _ = _model()
    specs = match_partition_rules(lm_partition_rules()[:-1], params)  # without the catch-all
    named = {jax.tree_util.keystr(path): spec for path, spec in jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))}
    mamba = {k: v for k, v in named.items() if "mamba" in k and "h_0" in k}
    assert len(mamba) == 8
    from trlx_tpu.parallel.mesh import AXIS_FSDP, AXIS_TP
    assert mamba["['h_0']['mamba']['in_proj']['kernel']"] == jax.sharding.PartitionSpec(AXIS_FSDP, AXIS_TP)
    assert mamba["['h_0']['mamba']['out_proj']['kernel']"] == jax.sharding.PartitionSpec(AXIS_TP, AXIS_FSDP)
    assert all(spec == jax.sharding.PartitionSpec() for k, spec in mamba.items() if "proj" not in k)


def test_the_published_initialisers():
    cfg, model, _, ids, mask = _model()
    params = model.init(jax.random.PRNGKey(3), ids, mask)["params"]["h_0"]["mamba"]
    np.testing.assert_allclose(params["A_log"], np.log(np.arange(1, 9)), rtol=1e-6)
    np.testing.assert_allclose(params["D"], 1.0)
    step = jax.nn.softplus(params["dt_bias"])
    assert 0.001 <= float(step.min()) and float(step.max()) <= 0.1
    assert float(jnp.abs(params["conv_kernel"]).max()) <= 0.5


# ---- (h) the compiler, device-free: tests/test_tpu_lowering.py (one file describes the topology) ----


# ---- the normal path ----------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` with the rehearsal `model_arch` of the configuration's
    file: the same entry point, orchestrator, trainer, static generate path
    and cache pytree as every other cell, and the new counters in the phase
    records."""
    import trlx_tpu
    from trlx_tpu.trainer.api import default_config

    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "granite-4.0-h-micro.json")))
    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(spec["rehearsal_arch"])
    config.model.num_layers_unfrozen = 2
    config.train.seq_length, config.train.batch_size, config.train.total_steps = 32, 8, 4  # dp 8 over the test devices
    config.train.epochs, config.train.eval_interval, config.train.checkpoint_interval = 100, 10**9, 0
    config.train.checkpoint_dir, config.train.log_interval = str(tmp_path), 1
    config.method.num_rollouts, config.method.chunk_size, config.method.ppo_epochs = 8, 8, 2
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 24, "min_new_tokens": 24, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(2, 512, size=rng.integers(4, 9)))) for _ in range(8)]
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 512 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    assert trainer.fused_rollout and trainer.model.cfg.has_ssm and trainer.model.branch_layer == 3
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = [r for r in records if "step_time" in r]
    assert steps and all(np.isfinite(r["loss"]) for r in steps)
    assert abs(steps[0]["mean_ratio"] - 1.0) < 1e-3  # the recurrent decode path's log-probs against the chunked forward's
    phases = [r for r in records if "rollout/state_bytes" in r]
    itemsize = jnp.dtype(trainer.model.cfg.dtype).itemsize
    row = 4 * (8 * 16 * 16 * 4 + 3 * 160 * itemsize)
    assert phases and all(p["rollout/state_bytes"] == 8 * row and p["rollout/state_bytes_per_row"] == row for p in phases)
    assert all(p["rollout/cache_bytes"] == 8 * row + 8 * 32 * 2 * 2 * 16 * itemsize for p in phases)
    assert all(0 < p["ssm/state_rw_share"] < 1 and p["rollout/step_bytes_needed"] > 2 * 8 * row for p in phases)
    assert all(p["ssm/chunks_per_pass"] == 4 and 0 <= p["ssm/pad_share"] < 0.2 for p in phases if "ssm/pad_share" in p)
    # chunks of 8 positions, held positions-minor: 8 of a tile's 128 lanes
    assert all(p["ssm/lane_fill"] == 8 / 128 for p in phases if "ssm/pad_share" in p)
    assert any("ssm/pad_share" in p for p in phases)

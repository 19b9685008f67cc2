"""The looped family (models/lm.py `n_loops`, `sandwich_norm`, `exit_gate`; a
cache entry a (loop, layer) pair; models/heads.py `branch_replay_params`)
against the plain reference `benchmark/references/looped_decoder.py`, and the
inside of the block against transformers' `LlamaForCausalLM`: seeded random
weights, the `rehearsal_arch` size of benchmark/configs/ouro-2.6b-l12.json
(d 64, 3 blocks, 4 loops), float32, CPU (ISSUE 37).
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import looped_decoder as reference
from trlx_tpu.models.heads import LMWithValueHead, branch_replay_params, extract_branch_params, trainable_mask
from trlx_tpu.models.lm import (LMConfig, TransformerLM, cache_bytes, cache_bytes_per_token, cache_partition_spec,
                                decode_step_bytes, init_cache, init_paged_cache)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(HERE, "..", "benchmark", "configs")
ARCH = json.load(open(os.path.join(CONFIGS, "ouro-2.6b-l12.json")))["rehearsal_arch"]
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 3, 21
PADS = (0, 5, 11)  # left padding of each row
# 1e-4 on logits of size 1-4: float32 sums of 64 to 128 terms in another order
# (the program's batched einsum over padded rows against the reference's one
# unpadded row), 12 block applications deep, each behind a norm that divides by
# a root mean square; a wrong mask, norm, rotary or loop moves them by 0.1 or more.
TOL = 1e-4


def _model(seed=0, t=T, **over):
    cfg = LMConfig.from_dict({**ARCH, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, t), 2, cfg.vocab_size)
    mask = jnp.stack([(jnp.arange(t) >= pad).astype(jnp.int32) for pad in PADS])
    params = model.init(jax.random.PRNGKey(seed), ids, mask)["params"]
    # every vector matters: the norm scales and the gate's bias start at constants
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [x + 0.05 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)])
    return cfg, model, params, ids * mask, mask


# ---- (a) the program against the reference ------------------------------------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat, as the train step runs"])
def test_train_path_logits_match_the_reference(remat):
    cfg, model, params, ids, mask = _model(remat=remat)
    assert (cfg.n_loops, cfg.n_layer, cfg.sandwich_norm, cfg.exit_gate) == (4, 3, True, True)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids, mask)["logits"]
        want = reference.forward(params, ARCH, ids, mask, T)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got * mask[:, :, None], want, atol=TOL, rtol=0)
    # the loops are the model: one loop fewer is another function of the same weights
    fewer = reference.forward(params, ARCH, ids, mask, T, loops=3)
    assert float(jnp.abs(fewer - want).max()) > 0.1


def test_a_pass_with_no_cache_scans_the_loops_and_a_pass_through_the_cache_unrolls_them():
    """One traced stack under a `scan` over the loops where no cache is carried
    (train step, scoring, check (a)'s forward): the program is a loop's size,
    not four loops'. The replay and `collect_hidden_at` cut into the first
    loop, which is then unrolled before a scan of the other three. Through the
    cache every (loop, layer) pair has leaves of its own and the loops unroll."""
    import re

    cfg, model, params, ids, mask = _model()
    dots = lambda text: text.count("dot_general")
    whole = str(jax.make_jaxpr(lambda p: model.apply({"params": p}, ids, mask)["logits"])(params))
    assert whole.count("scan[") == 1 and re.findall(r"length=(\d+)", whole) == ["4"]
    one_loop = TransformerLM(cfg.replace(n_loops=1, exit_gate=False))
    once = str(jax.make_jaxpr(lambda p: one_loop.apply({"params": p}, ids, mask)["logits"])(
        {k: v for k, v in params.items() if k != "exit_gate"}))
    assert dots(whole) == dots(once) + 1  # the stack once, and the gate's product
    hidden = jnp.zeros((B, T, cfg.d_model))
    replay = str(jax.make_jaxpr(lambda p: model.apply({"params": p}, inputs_embeds=hidden, attention_mask=mask, start_layer=2)["logits"])(params))
    assert re.findall(r"length=(\d+)", replay) == ["3"] and dots(once) < dots(replay) < 2 * dots(once)
    collected = str(jax.make_jaxpr(lambda p: model.apply({"params": p}, ids, mask, collect_hidden_at=2)["branch_hidden"])(params))
    assert re.findall(r"length=(\d+)", collected) == ["3"]
    cache = init_cache(cfg, B, T)
    prefill = str(jax.make_jaxpr(lambda p, c: model.apply({"params": p}, ids, mask, cache=c, cache_index=0, cache_mask=mask)["logits"])(params, cache))
    assert "scan[" not in prefill and dots(prefill) >= 4 * (dots(once) - 1)


def test_the_exit_distribution_matches_the_reference():
    cfg, model, params, ids, mask = _model()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids, mask)["exit_probs"]
        want = reference.exit_distribution(params, ARCH, ids, mask)
    assert got.shape == (B, T, 4) and got.dtype == jnp.float32
    np.testing.assert_allclose(got * mask[:, :, None], want, atol=1e-5, rtol=0)  # probabilities: sigmoids of the same sums
    np.testing.assert_allclose(jnp.sum(got, axis=-1), 1.0, atol=1e-6)
    assert 0.01 < float(got.min()) and float(got.max()) < 0.9  # no loop takes everything: the gate's weights were moved
    # the first loop unrolled before the scan (`collect_hidden_at` cuts into it): the same distribution
    with jax.default_matmul_precision("highest"):
        cut = model.apply({"params": params}, ids, mask, collect_hidden_at=1)
    np.testing.assert_allclose(cut["exit_probs"], got, atol=1e-6, rtol=0)
    assert cut["branch_hidden"].shape == (B, T, cfg.d_model)
    # a decode step and the branch replay leave the gate out
    cache = init_cache(cfg, B, T)
    assert model.apply({"params": params}, ids, mask, cache=cache, cache_index=0, cache_mask=mask)["exit_probs"] is None


# ---- (b) gradients: a shared block's is the sum over its four uses ------------------------------


def test_ppo_loss_gradient_of_a_shared_block_is_the_reference_s_sum_over_four_uses():
    cfg, model, params, ids, mask = _model(remat=True)
    prompt = 8
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
    loss, want = reference.ppo_loss_gradients(params, ARCH, ids, mask, prompt, old, advantages)
    _, uses = reference.ppo_loss_gradients(params, ARCH, ids, mask, prompt, old, advantages, per_use=True)
    assert np.isfinite(float(loss)) and len(uses) == 4
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *uses)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        if "exit_gate" in name:
            assert scale == 0.0 and float(jnp.abs(g).max()) == 0.0  # at threshold 1 the gate decides nothing
            continue
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale + 1e-9, rtol=2e-3, err_msg=name)
    for i in range(cfg.n_layer):
        block = f"h_{i}"
        for (path, g), s in zip(jax.tree_util.tree_leaves_with_path(got[block]), jax.tree_util.tree_leaves(summed[block])):
            scale = float(jnp.abs(s).max())
            np.testing.assert_allclose(g, s, atol=2e-4 * scale, rtol=2e-3, err_msg=block + jax.tree_util.keystr(path))
        # no single use is the whole: the sum matters
        kernel = lambda tree: tree[block]["mlp"]["down_proj"]["kernel"]
        assert all(float(jnp.abs(kernel(u) - kernel(summed)).max()) > 0.1 * float(jnp.abs(kernel(summed)).max()) for u in uses)


# ---- (c) prefill, then decode through the (loop, layer) cache -----------------------------------


@pytest.mark.parametrize("quant, tolerance", [(False, 1e-5), (True, 0.08)],
                         ids=["float32 cache", "int8 cache, at its own tolerance"])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(quant, tolerance):
    """`tolerance` bounds the relative RMS distance of a step's logits from the
    full forward's (the float32 cache also to 1e-4 in every logit). The int8
    cache's own: a key or value is rounded to 1/254 of its head's largest
    entry, once, and read by every later step of its own (loop, layer) entry
    through 12 block applications; at these widths (heads 16 wide) the
    readings are 0.023-0.040 a step, twice that is the limit, and a read of
    another loop's entry gives 0.49 (below)."""
    cfg, model, params, ids, mask = _model(kv_cache_quant=quant, remat=True)
    prompt = 13
    assert cfg.cache_entries == 12
    cache = init_cache(cfg, B, T)
    assert len(cache) == 12 and len(cache[0]) == (4 if quant else 2)
    with jax.default_matmul_precision("highest"):
        full = model.apply({"params": params}, ids, mask)["logits"]
        cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
        out = model.apply({"params": params}, ids[:, :prompt], mask[:, :prompt], cache=cache, cache_index=0, cache_mask=cache_mask)
        rel = lambda got, want: float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want**2)))
        assert rel(out["logits"][:, -1], full[:, prompt - 1]) <= tolerance
        step = jax.jit(lambda cache, index, cache_mask, token: model.apply(
            {"params": params}, token, jnp.ones((B, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask))
        cache, worst, worst_logit = out["cache"], 0.0, 0.0
        for i in range(prompt, T):
            cache_mask = cache_mask.at[:, i].set(1)
            out = step(cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
            cache = out["cache"]
            worst = max(worst, rel(out["logits"][:, 0], full[:, i]))
            worst_logit = max(worst_logit, float(jnp.abs(out["logits"][:, 0] - full[:, i]).max()))
        assert worst <= tolerance, worst
        assert worst > 1e-3 if quant else worst_logit <= 1e-4  # the int8 cache was read; the float32 one to 1e-4 a logit
        # loop r's layer reads what loop r's layer wrote: with loops 1 and 2 of the cache swapped, it does not
        swapped = tuple(cache[(e + 3) % 6] if e < 6 else cache[e] for e in range(12))
        out = step(swapped, jnp.int32(T - 1), cache_mask, ids[:, T - 1:])
        assert rel(out["logits"][:, 0], full[:, T - 1]) > 0.3


def test_cache_shapes_and_counters_count_a_loop_layer_pair():
    cfg = LMConfig.from_dict({**ARCH, "dtype": "bfloat16", "kv_cache_quant": True})
    rows, span = 6, 40
    cache = init_cache(cfg, rows, span)
    assert [tuple(leaf.shape) for leaf in cache[0]] == [(rows, span, 4, 16)] * 2 + [(rows, span, 4)] * 2
    assert len(cache) == 4 * 3
    a_token = 4 * 3 * 2 * (4 * 16 + 4 * 4)  # R N entries, K and V, int8 values and a float32 scale a head
    assert cache_bytes_per_token(cfg) == a_token and cache_bytes(cfg, rows, span) == rows * span * a_token
    assert cache_bytes_per_token(cfg.replace(kv_cache_quant=False)) == 4 * 3 * 2 * 4 * 16 * 2
    assert cache_bytes_per_token(cfg.replace(n_loops=1, exit_gate=False)) == a_token // 4
    # a decode step: the stack's weights once a LOOP, the rest once, the keys of every entry
    needed, state = decode_step_bytes(cfg, rows, keys_read=10, weight_bytes=1000, stack_bytes=600)
    assert state == 0 and needed == 1000 + 3 * 600 + 10 * rows * a_token
    from jax.sharding import PartitionSpec

    from trlx_tpu.parallel.mesh import AXIS_TP, DATA_AXES
    assert cache_partition_spec(cfg, 4, layer=11) == PartitionSpec(DATA_AXES, None, AXIS_TP, None)
    # the published widths, as the issue counts them: 202,752 bytes a token
    big = LMConfig.from_dict({**json.load(open(os.path.join(CONFIGS, "ouro-2.6b-l12.json")))["model_arch"],
                              "dtype": "bfloat16", "kv_cache_quant": True})
    assert cache_bytes_per_token(big) == 202_752


# ---- (d) the frozen reference policy replays from the FIRST loop --------------------------------


def _trainer(tmp_path, **method):
    from trlx_tpu.trainer.api import default_config, get_model

    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 1
    config.model.dtype = config.model.param_dtype = "float32"
    config.train.checkpoint_dir = str(tmp_path)
    config.train.seq_length, config.train.batch_size = 24, 8
    config.method.num_rollouts, config.method.chunk_size = 8, 8
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 16, "min_new_tokens": 16, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    for key, value in method.items():
        setattr(config.method, key, value)
    return get_model(config.model.model_type)(config, reward_fn=lambda rows: [0.0] * len(rows), metric_fn=None, logit_mask=None)


@pytest.mark.parametrize("fused", [False, True], ids=["unfused scoring", "fused rollout: the carried branch_hidden is loop 1's"])
def test_the_reference_policy_is_the_initial_one_after_the_top_blocks_have_moved(tmp_path, fused):
    """After an optimizer step has moved the trained blocks, the final norm and
    the head, the scoring path's reference log-probs are those of an untouched
    copy of the initial parameters run whole: the replay starts at (loop 1,
    block N - k), where the hidden state depends on frozen weights only (and on
    the embedding, which trains and is shared with the reference in every
    one-pass model of this repo too: it is left unmoved here). A replay begun
    in the LAST loop scores a reference that drifts with the policy."""
    trainer = _trainer(tmp_path)
    model, cfg = trainer.model, trainer.model.cfg
    assert model.branch_layer == 2 and trainer.fused_rollout and cfg.n_loops == 4
    initial = jax.tree_util.tree_map(jnp.copy, trainer.state.params)
    trains = trainable_mask(initial, cfg, 1)

    def moved(path, leaf, train):
        keys = [str(getattr(k, "key", k)) for k in path]
        if not train or "wte" in keys or "v_head" in keys:
            return leaf
        return leaf + 0.1 * jax.random.normal(jax.random.PRNGKey(len(keys) + leaf.size % 97), leaf.shape, leaf.dtype)

    trainer.state = trainer.state.replace(params=jax.tree_util.tree_map_with_path(moved, initial, trains))
    drift = float(jnp.abs(trainer.state.params["transformer"]["h_2"]["mlp"]["down_proj"]["kernel"]
                          - initial["transformer"]["h_2"]["mlp"]["down_proj"]["kernel"]).max())
    assert drift > 0.1 and bool(jnp.all(trainer.state.params["transformer"]["h_1"]["mlp"]["down_proj"]["kernel"]
                                        == initial["transformer"]["h_1"]["mlp"]["down_proj"]["kernel"]))
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, 512, size=(8, 8)).astype(np.int32)
    prompt_mask = np.ones((8, 8), np.int32)
    prompt_mask[3, :3] = 0
    scores = np.zeros((8,), np.float32)
    if fused:
        tokens, mask, stats, prefill = trainer.rollout_generate_fused(prompts * prompt_mask, prompt_mask)
        lp, _, _, kl = trainer.rollout_score_fused(tokens, mask, scores, (stats, prefill))
    else:
        tokens, mask = trainer.rollout_generate(prompts * prompt_mask, prompt_mask)[:2]
        lp, _, _, kl = trainer.rollout_score(tokens, mask, scores)
    got = np.asarray(lp - kl)  # kl = (logp - ref logp) on the response tokens: all are real
    P = 8

    def logprobs(logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32)[:, P - 1:-1])
        return np.asarray(jnp.take_along_axis(logp, tokens[:, P:, None], axis=-1)[..., 0])

    want = logprobs(model.apply({"params": initial}, tokens, mask)["logits"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)  # log-probs near -6: float32, another order of the same sums
    assert float(np.abs(np.asarray(lp) - want).max()) > 0.05  # the policy itself has moved
    # the replay begun in the last loop: three whole loops of the MOVED policy, then the frozen top blocks
    new = trainer.state.params["transformer"]
    one_loop = dict(n_loops=1, exit_gate=False)
    z3 = TransformerLM(cfg.replace(n_loops=3, exit_gate=False)).apply({"params": new}, tokens, mask)["hidden"]
    frozen_top = {**new, **trainer.state.extras["transformer"]}
    late = TransformerLM(cfg.replace(**one_loop)).apply({"params": frozen_top}, inputs_embeds=z3, attention_mask=mask)["logits"]
    margin = float(np.abs(logprobs(late) - want).max())
    assert margin > 0.05, margin  # 250 times the tolerance above: the drift the KL penalty would not have seen


def test_branch_replay_params_adds_the_live_bottom_blocks_for_a_looped_stack_only():
    cfg, _, params, _, _ = _model()
    tree = {"transformer": params}
    branch = extract_branch_params(tree, cfg, 1)
    assert sorted(branch["transformer"]) == ["h_1", "h_2", "lm_head", "ln_f"]
    replay = branch_replay_params(tree, branch, cfg, 1)
    assert sorted(replay["transformer"]) == ["h_0", "h_1", "h_2", "lm_head", "ln_f"]
    assert replay["transformer"]["h_0"] is params["h_0"] and replay["transformer"]["h_1"] is branch["transformer"]["h_1"]
    assert branch_replay_params(tree, branch, cfg.replace(n_loops=1, exit_gate=False), 1) is branch


def test_trainable_mask_freezes_the_gate_and_the_bottom_blocks():
    cfg, _, params, _, _ = _model()
    tree = {"transformer": params, "v_head": {"layers_0": {"kernel": jnp.zeros((2, 2))}}}
    mask = trainable_mask(tree, cfg, 1)
    flat = {jax.tree_util.keystr(p): v for p, v in jax.tree_util.tree_leaves_with_path(mask)}
    assert not any(v for k, v in flat.items() if "exit_gate" in k) and sum("exit_gate" in k for k in flat) == 2
    assert not any(v for k, v in flat.items() if "'h_0'" in k or "'h_1'" in k)
    assert all(v for k, v in flat.items() if "'h_2'" in k or "ln_f" in k or "wte" in k or "lm_head" in k or "v_head" in k)
    assert sum("ln_1_out" in k or "ln_2_out" in k for k in flat) == 6  # two sandwich norms a block


# ---- (e) what is not built raises ---------------------------------------------------------------


@pytest.mark.parametrize("bad, error, message", [
    ({"exit_threshold": 0.5}, NotImplementedError, "adaptive depth"),
    ({"exit_threshold": 1.5}, ValueError, "exit_threshold"),
    ({"sp_size": 2}, ValueError, "sp ring"),
    ({"n_loops": 0, "exit_gate": False}, ValueError, "at least 1"),
    ({"n_loops": 1}, ValueError, "exit_gate needs"),
    ({"parallel_residual": True}, ValueError, "sandwich_norm"),
    ({"attention_layers": ["global", "local", "local"], "window_size": 4, "window_cache": "ring"}, ValueError, "ring"),
    ({"n_soft_tokens": 2}, ValueError, "soft prompts"),
    ({"ut_steps": 4}, ValueError, "unknown architecture key"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_lmconfig_refuses_what_is_not_built(bad, error, message):
    with pytest.raises(error, match=message):
        LMConfig.from_dict({**ARCH, **F32, **bad})


@pytest.mark.parametrize("options", [{}, {"paged_kv": True}, {"spec_decode": "ngram", "spec_k": 4}],
                         ids=["engine", "paged pool", "spec decode"])
def test_the_engine_the_paged_pool_and_spec_decode_refuse_a_looped_stack(options):
    from trlx_tpu.engine.rollout_engine import RolloutEngine
    from trlx_tpu.ops.sampling import GenerateConfig

    cfg, model, _, _, _ = _model()
    with pytest.raises(NotImplementedError, match="looped stack"):
        RolloutEngine(model, GenerateConfig(max_new_tokens=4), n_slots=2, prompt_width=8, **options)
    with pytest.raises(NotImplementedError, match="looped stack"):
        init_paged_cache(cfg, 4, 8)


def test_the_trunk_refuses_calls_the_static_generate_path_does_not_make():
    cfg, model, params, ids, mask = _model()
    cache = init_cache(cfg, B, T)
    cases = {
        "a per-row offset": dict(input_ids=ids[:, :1], attention_mask=mask[:, :1], cache=cache,
                                 cache_index=jnp.zeros((B,), jnp.int32), cache_mask=mask),
        "a verify window": dict(input_ids=ids[:, :4], attention_mask=mask[:, :4], cache=cache, cache_index=8, cache_mask=mask),
        "packed segments": dict(input_ids=ids, attention_mask=mask, segment_ids=jnp.zeros((B, T), jnp.int32)),
        "stop_layer": dict(input_ids=ids, attention_mask=mask, stop_layer=2),
    }
    for name, call in cases.items():
        with pytest.raises(NotImplementedError, match="looped stack"):
            model.apply({"params": params}, **call)


@pytest.mark.parametrize("section, key, error", [("model", "decode_weight_quant", ValueError),
                                                 ("method", "pack_train_batch", NotImplementedError)],
                         ids=["W8 decode weights", "packed segments"])
def test_the_trainer_refuses_w8_and_packed_segments_for_a_looped_stack(tmp_path, section, key, error):
    from trlx_tpu.trainer.api import default_config, get_model

    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 1
    setattr(getattr(config, section), key, True)
    config.train.checkpoint_dir = str(tmp_path)
    config.train.seq_length = 16
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 8, "do_sample": True}
    with pytest.raises(error, match="looped stack"):
        get_model(config.model.model_type)(config, reward_fn=lambda rows: [0.0] * len(rows), metric_fn=None, logit_mask=None)


def test_import_maps_the_published_config_and_refuses_the_rest():
    from trlx_tpu.models.hf_export import validate_exportable
    from trlx_tpu.models.hf_import import lm_config_from_hf, load_hf_trunk

    published = json.load(open(os.path.join(CONFIGS, "ouro-2.6b-l12.json")))["published"]
    cfg = lm_config_from_hf(types.SimpleNamespace(**published))
    assert (cfg.n_loops, cfg.sandwich_norm, cfg.exit_gate, cfg.exit_threshold) == (4, True, True, 1.0)
    assert (cfg.n_layer, cfg.n_head, cfg.head_dim, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (48, 16, 128, 2048, 5632, 49152)
    assert (cfg.pos_type, cfg.rope_theta, cfg.ln_eps, cfg.norm, cfg.mlp, cfg.tie_word_embeddings, cfg.kv_heads) == (
        "rotary", 1e6, 1e-6, "rmsnorm", "gated", False, 16)
    assert cfg.extra["neox_rotary"] and not (cfg.fused_qkv or cfg.qkv_bias or cfg.out_bias)
    with pytest.raises(ValueError, match="unknown config key"):
        lm_config_from_hf(types.SimpleNamespace(**published, ut_gate_bias=True))
    with pytest.raises(NotImplementedError, match="adaptive depth"):
        lm_config_from_hf(types.SimpleNamespace(**{**published, "early_exit_threshold": 0.9}))
    with pytest.raises(ValueError, match="layer_types other than full_attention"):
        lm_config_from_hf(types.SimpleNamespace(**{**published, "layer_types": ["sliding_attention"] * 48}))
    with pytest.raises(NotImplementedError, match="looped checkpoint"):
        load_hf_trunk("/nonexistent", cfg)
    with pytest.raises(ValueError, match="looped stack"):
        validate_exportable(cfg, "gptj")


# ---- (f) the inside of the block against transformers' Llama ------------------------------------


def test_one_loop_without_sandwich_or_gate_is_transformers_llama():
    """With one loop and neither sandwich norm nor gate the program and the
    reference are transformers' LlamaForCausalLM with the same weights: the
    rotary's rotate-half layout and theta, the gated MLP, the norm's epsilon."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    plain = {**ARCH, "n_loops": 1, "sandwich_norm": False, "exit_gate": False}
    cfg, model, params, ids, mask = _model(**{k: plain[k] for k in ("n_loops", "sandwich_norm", "exit_gate")})
    hf = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, hidden_act="silu", max_position_embeddings=128, rms_norm_eps=1e-6,
        rope_theta=1e6, tie_word_embeddings=False, attention_bias=False, mlp_bias=False)).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    sd = {"model.embed_tokens.weight": t(params["wte"]["embedding"]), "model.norm.weight": t(params["ln_f"]["scale"]),
          "lm_head.weight": t(params["lm_head"]["kernel"].T)}
    for i in range(3):
        p, pre = params[f"h_{i}"], f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = t(p["ln_1"]["scale"])
        sd[pre + "post_attention_layernorm.weight"] = t(p["ln_2"]["scale"])
        for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"), ("c_proj", "o_proj")):
            sd[pre + f"self_attn.{theirs}.weight"] = t(p["attn"][ours]["kernel"].T)
        for name in ("gate_proj", "up_proj", "down_proj"):
            sd[pre + f"mlp.{name}.weight"] = t(p["mlp"][name]["kernel"].T)
    hf.load_state_dict(sd, strict=True)
    row = 0  # the unpadded row: transformers counts positions as the program does only without padding
    with torch.no_grad():
        theirs = hf(input_ids=torch.tensor(np.asarray(ids[row:row + 1]), dtype=torch.long)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        ours = model.apply({"params": params}, ids, mask)["logits"]
        ref = reference.forward(params, plain, ids, mask, T)
    np.testing.assert_allclose(ours[row:row + 1], theirs, atol=TOL, rtol=0)
    np.testing.assert_allclose(ref[row:row + 1], theirs, atol=TOL, rtol=0)
    np.testing.assert_allclose(ours * mask[:, :, None], ref, atol=TOL, rtol=0)  # the padded rows too


# ---- (g) what stands: the six benchmark configurations' trees -----------------------------------


PARENT_TREES = json.load(open(os.path.join(HERE, "data", "rehearsal_param_trees_pr36.json")))


@pytest.mark.parametrize("name", sorted(PARENT_TREES))
def test_with_the_new_fields_at_their_defaults_a_configuration_s_tree_is_the_parent_s(name):
    """Leaf for leaf what commit d7f3da0 (PR 36) builds from the same
    `rehearsal_arch` and key: path, shape, dtype and the draw itself (the sum
    of magnitudes of each leaf; recorded there by tests/data's file)."""
    spec = json.load(open(os.path.join(CONFIGS, f"{name}.json")))
    cfg = LMConfig.from_dict(spec["rehearsal_arch"])
    assert (cfg.n_loops, cfg.sandwich_norm, cfg.exit_gate, cfg.exit_threshold, cfg.cache_entries) == (1, False, False, 1.0, cfg.n_layer)
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = TransformerLM(cfg).init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    got = {jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    want = PARENT_TREES[name]
    assert sorted(got) == sorted(want)
    for path, (shape, dtype, magnitude) in want.items():
        leaf = got[path]
        assert (list(leaf.shape), str(leaf.dtype)) == (shape, dtype), path
        np.testing.assert_allclose(float(jnp.sum(jnp.abs(leaf.astype(jnp.float32)))), magnitude, rtol=1e-6, err_msg=path)
    assert len(init_cache(cfg, 2, 8)) == cfg.n_layer


# ---- (h) the normal path ------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` with the rehearsal `model_arch` of the configuration's
    file: the same entry point, orchestrator, trainer, static generate path
    and cache pytree as every other cell, and the new counters in the records."""
    import trlx_tpu
    from trlx_tpu.trainer.api import default_config

    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 2
    config.model.kv_cache_quant = True
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
    cfg = trainer.model.cfg
    assert trainer.fused_rollout and cfg.n_loops == 4 and trainer.model.branch_layer == 1
    # the gate and the bottom block did not move; a trained block did
    gate = trainer.state.params["transformer"]["exit_gate"]
    assert float(jnp.abs(gate["bias"]).max()) == 0.0
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = [r for r in records if "step_time" in r]
    assert len(steps) == 4 and all(np.isfinite(r["loss"]) for r in steps)
    assert abs(steps[0]["mean_ratio"] - 1.0) < 0.02  # the int8 (loop, layer) cache's log-probs against the train forward's
    assert all(1.0 < r["policy/expected_exit_loop"] < 4.0 for r in steps)
    phases = [r for r in records if "loops/n_loops" in r]
    a_token = 4 * 3 * 2 * (4 * 16 + 4 * 4)
    assert phases and all(p["loops/n_loops"] == 4 and p["loops/block_applications"] == 12 for p in phases)
    assert all(p["rollout/cache_bytes_per_token"] == a_token and p["rollout/cache_bytes"] == 8 * 32 * a_token for p in phases)
    assert all(0 < p["loops/weight_read_share"] < 1 and p["rollout/step_bytes_needed"] > 0 for p in phases)
    assert not any("ssm/state_rw_share" in p for p in phases)

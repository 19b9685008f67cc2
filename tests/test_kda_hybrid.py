"""The gated delta-rule / latent-attention / sparse-expert family (models/kda.py;
models/lm.py `mixer_layers: kda`, attention "mla" with directly projected
queries and `pos_type: none`, the state leaves of `init_cache` beside latent
leaves and expert layers) against the plain reference
`benchmark/references/kda_mla_moe_decoder.py`: seeded random weights, tiny
sizes, float32, CPU. The kinds are the kimi-linear-48b-ep32-l13
configuration's rehearsal kinds (ISSUE 39).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import kda_mla_moe_decoder as reference
from trlx_tpu.models import kda, moe
from trlx_tpu.models.lm import (LMConfig, TransformerLM, cache_bytes, cache_bytes_per_token, cache_partition_spec,
                                decode_step_bytes, init_cache, init_paged_cache, state_bytes)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(HERE, "..", "benchmark", "configs")
# kda, kda, kda, latent, kda; dense then experts. 4 delta-rule heads of 16;
# 4 latent-attention heads of 16 + 8 and 16 over a latent of 16; 16 experts, 2 a token, [4, 8) held here.
ARCH = dict(
    vocab_size=512, n_layer=5, n_head=4, d_model=64, d_ff=128, max_position=128, eos_token_id=0, pos_type="none",
    norm="rmsnorm", mlp="gated", attention="mla", activation="silu", ln_eps=1e-5, parallel_residual=False,
    tie_word_embeddings=False, mixer_layers=["kda", "kda", "kda", "attention", "kda"],
    ffn_layers=["dense", "experts", "experts", "experts", "experts"], kda_heads=4, kda_head_dim=16, kda_conv=4,
    q_lora_rank=0, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_experts=16, experts_per_token=2, expert_d_ff=32, n_shared_experts=1, routed_scaling_factor=2.446, experts_held=[4, 4],
    embed_init_std=1.0, draw_dtype="float32",
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 3, 77  # two chunks of kda.CHUNK, the second short
PADS = (0, 5, 11)  # left padding of each row


def _model(seed=0, t=T, arch=ARCH, **over):
    cfg = LMConfig.from_dict({**arch, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, t), 2, cfg.vocab_size)
    mask = jnp.stack([(jnp.arange(t) >= pad).astype(jnp.int32) for pad in PADS])
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids, mask)["params"]
    # every vector matters: the norm scales and the gate's bias start at constants
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [x + 0.05 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)])
    return cfg, model, params, ids * mask, mask


def _forward(model, params, ids, mask):
    return jax.jit(lambda p, i, m: model.apply({"params": p}, i, m)["logits"])(params, ids, mask)


# ---- (a) chunked = the step folded over tokens --------------------------------------------------


def _operands(t, decay, b=2, H=3, D=8, pad=0, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k, v = (jax.random.normal(key, (b, t, H, D)) for key in keys[:3])
    g = -decay * jax.random.uniform(keys[3], (b, t, H, D), minval=0.5, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, H)))
    real = (jnp.arange(t) >= pad).astype(jnp.float32)[None, :, None]
    return unit(q) * D ** -0.5 * real[..., None], unit(k) * real[..., None], v * real[..., None], g * real[..., None], beta * real


def _folded(q, k, v, g, beta):
    def token(state, inputs):
        o, state = kda.kda_step(state, *inputs)
        return state, o

    first = lambda x: jnp.moveaxis(x, 1, 0)
    b, _, H, D = q.shape
    last, o = jax.lax.scan(token, jnp.zeros((b, H, D, D)), tuple(first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


@pytest.mark.parametrize("t, chunk, decay, pad", [
    (70, 32, 0.3, 0), (64, 64, 0.3, 0), (29, 64, 0.3, 0), (45, 32, 0.3, 11), (130, 64, 10.0, 0)],
    ids=["two chunks and a short third", "one whole chunk of four sub-blocks", "shorter than a chunk", "left padding",
         "log-decay of -10 a token over whole chunks"])
def test_the_chunked_form_is_the_step_folded_over_the_tokens(t, chunk, decay, pad):
    """Outputs, the final state and every gradient; at the fast end (exp(-640) over
    a chunk: the factorised form's exp(+640) is inf in float32) all of them finite."""
    ops = _operands(t, decay, pad=pad)
    if decay == 10.0:
        ops = ops[:3] + (jnp.full_like(ops[3], -10.0),) + ops[4:]
    with jax.default_matmul_precision("highest"):
        chunked = jax.jit(lambda *a: kda.kda_chunked(*a, chunk, jnp.float32))
        (o, last), (o_want, last_want) = chunked(*ops), jax.jit(_folded)(*ops)
        assert float(jnp.abs(o_want).max()) > 1e-2
        np.testing.assert_allclose(o, o_want, atol=2e-6, rtol=1e-4)
        np.testing.assert_allclose(last, last_want, atol=2e-6, rtol=1e-4)
        assert last.dtype == jnp.float32
        loss = lambda f: (lambda *a: jnp.sum(f(*a)[0] ** 2) + jnp.sum(f(*a)[1] ** 2))
        got = jax.jit(jax.grad(loss(lambda *a: kda.kda_chunked(*a, chunk, jnp.float32)), argnums=(0, 1, 2, 3, 4)))(*ops)
        want = jax.jit(jax.grad(loss(_folded), argnums=(0, 1, 2, 3, 4)))(*ops)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, w, atol=2e-5 * max(1.0, float(jnp.abs(w).max())), rtol=1e-3, err_msg=name)
    if pad:  # a padded position leaves the state as it is: the row equals its unpadded self
        alone = kda.kda_chunked(*(x[:, pad:] for x in ops), chunk, jnp.float32)
        np.testing.assert_allclose(o[:, pad:], alone[0], atol=2e-6, rtol=1e-4)
        np.testing.assert_allclose(last, alone[1], atol=2e-6, rtol=1e-4)


def test_bf16_operands_keep_the_state_and_the_sums_float32():
    ops = _operands(70, 0.3)
    o, last = kda.kda_chunked(*(x.astype(jnp.bfloat16) if i < 3 else x for i, x in enumerate(ops)), 32, jnp.bfloat16)
    want, last_want = _folded(*ops)
    assert o.dtype == last.dtype == jnp.float32
    assert 1e-5 < float(jnp.abs(o - want).max()) < 0.05 and float(jnp.abs(last - last_want).max()) < 0.05


def _substituted_inverse(m, sub):
    """The parent's `unit_lower_inverse` (PR 39), kept as the reference of the hand-written backward:
    row-by-row substitution in the diagonal blocks, then block elimination, differentiated step by step."""
    blocks, hi = m.shape[-1] // sub, jax.lax.Precision.HIGHEST
    diag = jnp.stack([m[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub] for i in range(blocks)], axis=-3)
    eye = jnp.eye(sub, dtype=m.dtype)
    rows = []
    for r in range(sub):
        row = jnp.broadcast_to(eye[r], diag.shape[:-2] + (sub,))
        if r:
            row = row - jnp.einsum("...s,...sc->...c", diag[..., r, :r], jnp.stack(rows, axis=-2), precision=hi)
        rows.append(row)
    diag_inv = jnp.stack(rows, axis=-2)
    inv = diag_inv[..., 0, :, :]
    for i in range(1, blocks):
        below, own = m[..., i * sub:(i + 1) * sub, :i * sub], diag_inv[..., i, :, :]
        corner = -jnp.matmul(own, jnp.matmul(below, inv, precision=hi), precision=hi)
        top = jnp.concatenate([inv, jnp.zeros(inv.shape[:-1] + (sub,), m.dtype)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([corner, own], axis=-1)], axis=-2)
    return inv


@pytest.mark.parametrize("size, sub", [(16, 16), (32, 16), (48, 16), (64, 16), (8, 8), (64, 32)],
                         ids=["one block of 16", "two", "three", "a chunk of four", "a chunk shorter than a sub-block", "two of 32"])
def test_the_unit_lower_inverse(size, sub):
    """Against float64, exactly lower triangular, and the hand-written backward (two products)
    against autodiff through the parent's substitution, on the strictly lower part (the only
    part of `m` either reads)."""
    m = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(size + sub), (2, 3, size, size)), -1)  # beta k . k: within (-1, 1)
    weight = jax.random.normal(jax.random.PRNGKey(1), m.shape)
    with jax.default_matmul_precision("highest"):
        inv = jax.jit(lambda m: kda.unit_lower_inverse(m, sub))(m)
        np.testing.assert_allclose(inv @ (jnp.eye(size) + m), jnp.broadcast_to(jnp.eye(size), m.shape), atol=1e-4)
        got = jax.jit(jax.grad(lambda m: jnp.sum(kda.unit_lower_inverse(m, sub) * weight)))(m)
        want = jax.jit(jax.grad(lambda m: jnp.sum(_substituted_inverse(m, sub) * weight)))(m)
    want64 = np.linalg.inv(np.eye(size) + np.asarray(m, np.float64))
    assert float(np.abs(inv - want64).max()) < 1e-5 * float(np.abs(want64).max())
    assert float(jnp.abs(jnp.triu(inv, 1)).max()) == 0.0
    assert float(jnp.abs(jnp.triu(got)).max()) == 0.0  # nothing reads the rest of m: its gradient is 0, not garbage
    assert float(jnp.abs(jnp.tril(got - want, -1)).max()) < 1e-5 * float(jnp.abs(want).max())


def test_the_unit_lower_inverse_on_keys_that_share_their_direction():
    """A model that repeats a token hands a chunk keys that are nearly one vector: I + tril(K K^T, -1)
    is then all ones under its diagonal and a series form of the inverse (the Neumann product
    (I - N)(I + N^2)(I + N^4)..., exact in exact arithmetic) is wrong by 1e11 of max|T| in float32
    where substitution and block elimination read 3e-7 (ISSUE 40)."""
    rng = np.random.default_rng(0)
    base, noise = rng.standard_normal((2, 4, 1, 128)), rng.standard_normal((2, 4, 64, 128))
    k = 0.98 * base + 0.02 * noise
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    m = np.tril(k @ np.swapaxes(k, -1, -2), -1)  # beta = 1
    want = np.linalg.inv(np.eye(64) + m)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda m: kda.unit_lower_inverse(m, 16))(jnp.asarray(m, jnp.float32))
    assert float(np.abs(got - want).max()) < 1e-5 * float(np.abs(want).max())
    series, power = np.eye(64, dtype=np.float32) - m.astype(np.float32), m.astype(np.float32)
    for _ in range(5):  # (I - N)(I + N^2)(I + N^4)...(I + N^32): N^64 = 0
        power = power @ power
        series = series @ (np.eye(64, dtype=np.float32) + power)
    assert float(np.abs(series - want).max()) > 1e3 * float(np.abs(want).max())  # what this case is here to refuse


# ---- (b) the whole trunk against the reference, rows left-padded into one batch ----------------


def test_logits_match_the_reference_and_a_padded_row_equals_its_unpadded_self():
    cfg, model, params, ids, mask = _model()
    with jax.default_matmul_precision("highest"):
        got = _forward(model, params, ids, mask)
        want = reference.forward(params, ARCH, ids, mask, T)
        assert float(jnp.abs(want).max()) > 0.5
        for row, pad in enumerate(PADS):
            np.testing.assert_allclose(got[row, pad:], want[row, pad:], atol=3e-5, rtol=1e-4)
        alone = _forward(model, params, ids[2:, PADS[2]:], mask[2:, PADS[2]:])
        np.testing.assert_allclose(got[2, PADS[2]:], alone[0], atol=3e-5, rtol=1e-4)
    # rotating the 8-wide parts gives another model: mla_use_nope is not a no-op
    rotated = _forward(TransformerLM(cfg.replace(pos_type="rotary")), params, ids, mask)
    assert float(jnp.abs(rotated - got).max()) > 1e-3


def test_a_scoring_pass_over_many_rows_goes_through_row_groups(monkeypatch):
    cfg, model, params, ids, mask = _model()
    whole = _forward(model, params, ids, mask)
    monkeypatch.setattr(kda, "SCAN_TOKENS", T)  # one row a group
    grouped = _forward(model, params, ids, mask)
    np.testing.assert_allclose(grouped, whole, atol=1e-5, rtol=1e-5)


# ---- (c) prefill + decode through both kinds of leaf against the full forward -------------------

ALL_LATENT = {**ARCH, "n_layer": 2, "mixer_layers": ["attention", "attention"], "ffn_layers": ["dense", "experts"]}


def _decode(cfg, model, params, ids, mask, prompt, full=None):
    """The cache after a prefill of `prompt` positions and one token a step to T; with `full`, each step's logits held to it."""
    cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    out = jax.jit(lambda p, i, m, cm: model.apply({"params": p}, i, m, cache=init_cache(cfg, B, T), cache_index=0, cache_mask=cm))(
        params, ids[:, :prompt], mask[:, :prompt], cache_mask)
    if full is not None:
        real = np.asarray(mask[:, :prompt]).astype(bool)
        np.testing.assert_allclose(np.asarray(out["logits"])[real], np.asarray(full[:, :prompt])[real], atol=3e-5, rtol=1e-4)
    cache = out["cache"]
    step = jax.jit(lambda cache, tok, m, index, cm: model.apply(
        {"params": params}, tok, m, cache=cache, cache_index=index, cache_mask=cm))
    for t in range(prompt, T):
        cache_mask = cache_mask.at[:, t].set(mask[:, t])
        out = step(cache, ids[:, t:t + 1], mask[:, t:t + 1], t, cache_mask)
        cache = out["cache"]
        if full is not None:
            live = np.asarray(mask[:, t]).astype(bool)
            np.testing.assert_allclose(np.asarray(out["logits"][:, 0])[live], np.asarray(full[:, t])[live], atol=3e-5, rtol=1e-4)
    return cache


@pytest.mark.parametrize("arch, prompt, remat", [(ARCH, 13, False), (ARCH, 2, True), (ALL_LATENT, 13, False)],
                         ids=["prompt 13", "prompt shorter than the convolution, remat", "latent layers only: absorbed = unabsorbed"])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(arch, prompt, remat):
    """Prompts of unequal length, left-padded into one batch (a row of the second
    case has NO real token in its prompt: its state must still be zero when its
    first token arrives). A decode step reads the latent cache ABSORBED with the
    shared key as projected; the full forward is the unabsorbed pass."""
    cfg, model, params, ids, mask = _model(arch=arch, remat=remat)
    with jax.default_matmul_precision("highest"):
        cache = _decode(cfg, model, params, ids, mask, prompt, full=_forward(model, params, ids, mask))
    if arch is ARCH:
        shapes = [[tuple(leaf.shape) for leaf in layer] for layer in cache]
        assert shapes == [[(B, 3, 192), (B, 4, 16, 16)]] * 3 + [[(B, T, 16), (B, T, 8)]] + [[(B, 3, 192), (B, 4, 16, 16)]]
        assert all(cache[i][1].dtype == jnp.float32 for i in (0, 1, 2, 4))


def test_the_state_the_decode_steps_leave_is_the_reference_s_and_a_bf16_state_is_farther():
    """The state check's two sides (benchmark/kda_state_parity.py): the cache's state leaf of every kda layer after
    prefill + decode against `layer_state`, the reference's recurrence on the unpadded row, float32 by dtype and by
    value; and, with the first layer's channels set slow as the check sets them, the reference's own bf16-state rerun
    farther from it than the bf16 stream alone."""
    from benchmark.kda_state_parity import SLOW_STEP, slow_channels

    cfg, model, params, ids, mask = _model()
    params = slow_channels(params, 0)
    np.testing.assert_allclose(jax.nn.softplus(params["h_0"]["kda"]["dt_bias"]), SLOW_STEP, rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        cache = _decode(cfg, model, params, ids, mask, 13)
        for layer, kind in enumerate(ARCH["mixer_layers"]):
            if kind != "kda":
                with pytest.raises(ValueError, match="no kda layer"):
                    reference.layer_state(params, ARCH, ids[0], layer)
                continue
            for row, pad in enumerate(PADS):
                want = reference.layer_state(params, ARCH, ids[row, pad:], layer)
                assert float(jnp.abs(want).max()) > 1e-2 and cache[layer][1].dtype == jnp.float32
                np.testing.assert_allclose(cache[layer][1][row], want, atol=2e-6, rtol=1e-4)
        want = reference.layer_state(params, ARCH, ids[0], 0)
        far = {name: float(jnp.sqrt(jnp.sum((reference.layer_state(params, ARCH, ids[0], 0, precision=name) - want) ** 2)))
               for name in ("bfloat16_stream", "bfloat16_state")}
        # a state kept in bf16 (rounded after every token) fails the value check above
        rounded = reference.layer_state(params, ARCH, ids[0], 0, precision="bfloat16_state")
    assert 0 < far["bfloat16_stream"] < far["bfloat16_state"], far
    assert float(jnp.abs(rounded - want).max()) > 50 * 2e-6


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
    logp = jax.nn.log_softmax(_forward(model, params, tokens, out_mask))
    want = jnp.take_along_axis(logp[:, prompt - 1:-1], tokens[:, prompt:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(got["logprob"], want, atol=5e-5, rtol=1e-4)  # PPO's ratio at the first step: decode against chunked
    assert got["experts_touched_per_step"].shape == ()


# ---- (d) gradients ------------------------------------------------------------------------------


@pytest.mark.parametrize("scan_tokens", [kda.SCAN_TOKENS, T], ids=["one call", "a row a group, the block keeping the pass's output"])
def test_gradients_of_ppo_s_loss_match_the_reference(monkeypatch, scan_tokens):
    """value_and_grad: every parameter of the unfrozen top blocks (kda: A_log, dt_bias, the convolutions, every
    projection, the gate's bias, the output norm; latent attention; the experts, router and shared expert) and the
    embedding, whose gradient crosses the frozen layers below them; under remat, as the train step runs: in row
    groups each recomputed in its own backward pass, the remat'd block keeping `KDA_SCAN_OUT`."""
    from trlx_tpu.models.heads import trainable_mask

    monkeypatch.setattr(kda, "SCAN_TOKENS", scan_tokens)
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
        value, got = jax.jit(jax.value_and_grad(lambda p: ppo_loss(model.apply({"params": p}, ids, mask)["logits"])))(params)
        value_want, want = jax.value_and_grad(lambda p: ppo_loss(reference.forward(p, ARCH, ids, mask, T)))(params)
    np.testing.assert_allclose(value, value_want, rtol=1e-4)
    trains = trainable_mask({"transformer": params}, cfg, 3)["transformer"]  # latent, kda
    checked = set()
    for (path, g), w, train in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want),
                                   jax.tree_util.tree_leaves(trains)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        if "e_score_correction_bias" in name:
            assert scale == 0 and float(jnp.abs(g).max()) == 0  # a buffer: it chooses, no gradient reaches it
            continue
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-9, rtol=3e-3, err_msg=name)
        if train:
            checked.update(part for part in name.replace("']", "").split("['") if part)
    assert {"kda", "attn", "moe", "A_log", "dt_bias", "q_conv", "k_conv", "v_conv", "o_norm", "f_a_proj", "f_b_proj", "b_proj",
            "g_a_proj", "g_b_proj", "bias", "o_proj", "q_proj", "kv_a_proj", "kv_b_proj", "router", "experts_gate", "shared",
            "embedding"} <= checked


# ---- (e) the shares add up ----------------------------------------------------------------------


def test_the_shares_add_up():
    """16 experts as 4 shares of 4: the routed parts summed and the shared expert counted once equal the uncut
    reference layer (this trunk's expert layer: 2.446, one group, the bias over all 16)."""
    one = {**ARCH, "experts_held": [0, 16]}
    cfg, model, params, ids, mask = _model(arch=one)
    whole = params["h_1"]["moe"]
    y = reference._normed(jax.random.normal(jax.random.PRNGKey(7), (B, T, 64)), params["h_1"]["ln_2"], eps=1e-5, precision="highest")
    want = reference._expert_ffn(y, whole, one, "highest")

    def share(first, count, n_shared):
        part = {**whole, **{f"experts_{m}": whole[f"experts_{m}"][first:first + count] for m in ("gate", "up", "down")}}
        layer = moe.ExpertLayer(cfg.replace(experts_held=(first, count), n_shared_experts=n_shared))
        out, counts = layer.apply({"params": part}, y)
        assert counts.shape == (count,)
        return out, counts

    shared_once = share(0, 4, 1)[0] - share(0, 4, 0)[0]
    parts = [share(first, 4, 0) for first in (0, 4, 8, 12)]
    assert int(sum(counts.sum() for _, counts in parts)) == B * T * 2  # every slot lands in exactly one share
    np.testing.assert_allclose(sum(out for out, _ in parts) + shared_once, want, atol=2e-5, rtol=1e-4)
    counted_per_share = sum(share(first, 4, 1)[0] for first in (0, 4, 8, 12))
    assert float(jnp.abs(counted_per_share - want).max()) > 1e-2


# ---- (f) every refusal, by its message ----------------------------------------------------------


@pytest.mark.parametrize("bad, message", [
    ({"kv_cache_quant": True}, "kv_cache_quant"),
    ({"n_soft_tokens": 4}, "soft prompts"),
    ({"sp_size": 2}, "sp ring"),
    ({"n_loops": 2}, "a 'kda' layer"),
    ({"parallel_residual": True}, "parallel_residual"),
    ({"mixer_layers": ["kda", "mamba", "kda", "attention", "kda"], "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 16},
     "a 'mamba' layer"),
    ({"kda_heads": 0}, "needs kda_heads"),
    ({"mixer_layers": ["kda", "attention"]}, "mixer_layers must name"),
    ({"mixer_layers": ["kda", "kda", "gdn", "attention", "kda"]}, "mixer_layers must name"),
    ({"pos_type": "learned"}, "attention 'mla' needs"),
    ({"kv_lora_rank": 0}, "attention 'mla' needs"),
    ({"q_lora_rank": -1}, "attention 'mla' needs"),
    ({"kda_groups": 2}, "unknown architecture key"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **F32, **bad})


@pytest.mark.parametrize("options", [{}, {"paged_kv": True}, {"spec_decode": "ngram", "spec_k": 4}],
                         ids=["engine", "paged pool", "spec decode"])
def test_the_engine_the_paged_pool_and_spec_decode_refuse_a_kda_layer(options):
    from trlx_tpu.engine.rollout_engine import RolloutEngine
    from trlx_tpu.ops.sampling import GenerateConfig

    cfg = LMConfig.from_dict({**ARCH, **F32})
    with pytest.raises(NotImplementedError, match="not built for a kda layer"):
        RolloutEngine(TransformerLM(cfg), GenerateConfig(max_new_tokens=4), n_slots=2, prompt_width=8, **options)
    with pytest.raises(NotImplementedError, match="kda layer"):
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
        with pytest.raises(NotImplementedError, match="a kda layer takes"):
            model.apply({"params": params}, **call)


@pytest.mark.parametrize("knob, where, error, message", [
    ("decode_weight_quant", "model", ValueError, "kda layers"),
    ("pack_train_batch", "method", NotImplementedError, "a kda layer"),
], ids=["W8", "packed segments"])
def test_the_trainer_refuses_a_kda_layer(tmp_path, knob, where, error, message):
    from trlx_tpu.trainer.api import default_config, get_model

    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 2
    setattr(getattr(config, where), knob, True)
    config.train.checkpoint_dir = str(tmp_path)
    config.train.seq_length = 16
    config.method.gen_kwargs = {"prompt_length": 8, "max_new_tokens": 8, "do_sample": True}
    with pytest.raises(error, match=message):
        get_model(config.model.model_type)(config, reward_fn=lambda rows: [0.0] * len(rows), metric_fn=None, logit_mask=None)


def test_export_refuses_a_kda_layer():
    from trlx_tpu.models.hf_export import export_state_dict

    cfg, model, params, _, _ = _model()
    with pytest.raises(ValueError, match="kda layer"):
        export_state_dict(params, cfg)


# ---- (g) the publisher's names ------------------------------------------------------------------


class _Published:
    """A config object with `kimi_linear`'s published keys (the catalog row's), at tiny sizes."""

    def __init__(self, **over):
        catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
        spec = json.load(open(os.path.join(CONFIGS, "kimi-linear-48b-ep32-l13.json")))["published"]
        if os.path.isfile(catalog):
            (row,) = [r for r in map(json.loads, open(catalog)) if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
            assert row["config"] == spec
        self.__dict__.update({**spec, **over})

    def to_dict(self):
        return dict(self.__dict__)


def test_the_published_config_maps_to_lmconfig_and_a_seeded_state_dict_to_the_tree():
    from trlx_tpu.models import hf_import

    cfg = hf_import.lm_config_from_hf(_Published())
    assert (cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.n_head, cfg.vocab_size) == (27, 2304, 9216, 32, 163840)
    assert [i + 1 for i, kind in enumerate(cfg.mixer_layers) if kind == "attention"] == [4, 8, 12, 16, 20, 24, 27]
    assert cfg.mixer_layers.count("kda") == 20 and cfg.ffn_layers == ("dense",) + ("experts",) * 26
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.attention, cfg.pos_type, cfg.q_lora_rank) == (32, 128, 4, "mla", "none", 0)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_d_ff, cfg.n_shared_experts, cfg.routed_scaling_factor) == (256, 8, 1024, 1, 2.446)
    assert not cfg.tie_word_embeddings and cfg.held_experts == (0, 256) and cfg.head_dim == 72  # which sizes nothing
    for bad, message in (({"moe_router_activation_func": "softmax"}, "sigmoid"), ({"num_expert_group": 8}, "num_expert_group"),
                         ({"moe_renormalize": False}, "moe_renormalize"), ({"num_nextn_predict_layers": 1}, "num_nextn")):
        with pytest.raises(ValueError, match=message):
            hf_import.lm_config_from_hf(_Published(**bad))

    # a tiny tree written out under the publisher's names and read back: leaf for leaf, logits for logits
    cfg, model, params, ids, mask = _model()
    t = lambda w: np.asarray(w).T
    sd = {"model.embed_tokens.weight": np.asarray(params["wte"]["embedding"]), "model.norm.weight": np.asarray(params["ln_f"]["scale"]),
          "lm_head.weight": t(params["lm_head"]["kernel"])}
    for i in range(cfg.n_layer):
        p, h = params[f"h_{i}"], f"model.layers.{i}"
        sd[f"{h}.input_layernorm.weight"], sd[f"{h}.post_attention_layernorm.weight"] = p["ln_1"]["scale"], p["ln_2"]["scale"]
        if cfg.mixer(i) == "kda":
            for name in ("q_proj", "k_proj", "v_proj", "f_a_proj", "f_b_proj", "b_proj", "g_a_proj", "g_b_proj", "o_proj"):
                sd[f"{h}.self_attn.{name}.weight"] = t(p["kda"][name]["kernel"])
            sd[f"{h}.self_attn.g_b_proj.bias"] = np.asarray(p["kda"]["g_b_proj"]["bias"])
            for name in ("q_conv", "k_conv", "v_conv"):
                sd[f"{h}.self_attn.{name}1d.weight"] = t(p["kda"][name])[:, None, :]
            sd[f"{h}.self_attn.A_log"] = np.asarray(p["kda"]["A_log"]).reshape(1, 1, -1, 1)
            sd[f"{h}.self_attn.dt_bias"], sd[f"{h}.self_attn.o_norm.weight"] = p["kda"]["dt_bias"], p["kda"]["o_norm"]
        else:
            for ours, theirs in (("q_proj", "q_proj"), ("kv_a_proj", "kv_a_proj_with_mqa"), ("kv_b_proj", "kv_b_proj"), ("c_proj", "o_proj")):
                sd[f"{h}.self_attn.{theirs}.weight"] = t(p["attn"][ours]["kernel"])
            sd[f"{h}.self_attn.kv_a_layernorm.weight"] = p["attn"]["kv_a_norm"]["scale"]
        gated = lambda tree, prefix: sd.update({f"{prefix}.{n}.weight": t(tree[n]["kernel"]) for n in ("gate_proj", "up_proj", "down_proj")})
        if cfg.ffn_layers[i] == "experts":
            m = f"{h}.block_sparse_moe"
            sd[f"{m}.gate.weight"], sd[f"{m}.gate.e_score_correction_bias"] = t(p["moe"]["router"]), p["moe"]["e_score_correction_bias"]
            for e in range(4):  # experts 4..7 are the held ones; the others' tensors are never read
                for ours, theirs in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
                    sd[f"{m}.experts.{4 + e}.{theirs}.weight"] = t(p["moe"][f"experts_{ours}"][e])
            gated(p["moe"]["shared"], f"{m}.shared_experts")
        else:
            gated(p["mlp"], f"{h}.mlp")
    assert hf_import._detect_family(sd) == "kimi_linear"
    read = hf_import.materialize_spec(hf_import.trunk_spec("kimi_linear", cfg), sd)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(read), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(got, want, err_msg=jax.tree_util.keystr(path))
    assert jax.tree_util.tree_structure(read) == jax.tree_util.tree_structure(params)
    del sd["model.layers.0.self_attn.g_b_proj.bias"]  # a checkpoint whose gate has no bias: zeros
    again = hf_import.materialize_spec(hf_import.trunk_spec("kimi_linear", cfg), sd)
    assert float(np.abs(again["h_0"]["kda"]["g_b_proj"]["bias"]).max()) == 0.0


# ---- (h) the cache's shapes, the counters, the rules, the initialisers ---------------------------


def test_cache_shapes_and_counters_by_hand():
    cfg = LMConfig.from_dict({**ARCH, "dtype": "bfloat16"})
    rows, span = 6, 40
    cache = init_cache(cfg, rows, span)
    state, conv = rows * 4 * 16 * 16 * 4, rows * 3 * (3 * 64) * 2  # a float32 state, a bf16 window over q | k | v
    latent = rows * span * (16 + 8) * 2  # the latent and the shared key, bf16
    assert [c[1].dtype for i, c in enumerate(cache) if cfg.mixer(i) == "kda"] == [jnp.float32] * 4
    assert [tuple(c[0].shape) for c in cache] == [(rows, 3, 192)] * 3 + [(rows, span, 16)] + [(rows, 3, 192)]
    assert state_bytes(cfg, rows) == 4 * (state + conv)
    assert cache_bytes(cfg, rows, span) == 4 * (state + conv) + latent
    assert cache_bytes_per_token(cfg) == (16 + 8) * 2  # the latent layer's alone: a state holds nothing a token
    needed, rw = decode_step_bytes(cfg, rows, keys_read=10, weight_bytes=1000)
    assert rw == 2 * 4 * (state + conv) and needed == 1000 + rw + 10 * rows * (16 + 8) * 2
    assert cfg.has_kda and cfg.has_state and not cfg.has_ssm
    # the triangular solve: a chunk's and a sub-block's positions, and the share of a 128-lane tile a row of the substitution fills
    assert [kda.chunk_sizes(1024), kda.chunk_sizes(45), kda.chunk_sizes(29, 32), kda.chunk_sizes(8)] == [(64, 16), (48, 16), (32, 16), (16, 16)]
    assert kda.solve_lane_fill(1024) == kda.solve_lane_fill(8) == 16 / 128
    from jax.sharding import PartitionSpec

    from trlx_tpu.parallel.mesh import AXIS_TP, DATA_AXES
    assert cache_partition_spec(cfg, 4, layer=0) == PartitionSpec(DATA_AXES, AXIS_TP, None, None)
    assert cache_partition_spec(cfg, 3, layer=0) == PartitionSpec(DATA_AXES, None, None)
    assert cache_partition_spec(cfg, 3, layer=3) == PartitionSpec(DATA_AXES, None, None)  # a latent leaf: no head axis


def test_partition_rules_name_every_new_parameter():
    from trlx_tpu.parallel.mesh import AXIS_FSDP, AXIS_TP
    from trlx_tpu.parallel.sharding import lm_partition_rules, match_partition_rules

    cfg, model, params, _, _ = _model()
    specs = match_partition_rules(lm_partition_rules()[:-1], params)  # without the catch-all
    named = {jax.tree_util.keystr(path): spec for path, spec in jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))}
    mixer = {k: v for k, v in named.items() if "'kda'" in k and "h_0" in k}
    P = jax.sharding.PartitionSpec
    assert len(mixer) == 16
    assert mixer["['h_0']['kda']['q_proj']['kernel']"] == P(AXIS_FSDP, AXIS_TP)
    assert mixer["['h_0']['kda']['o_proj']['kernel']"] == P(AXIS_TP, AXIS_FSDP)
    assert mixer["['h_0']['kda']['f_b_proj']['kernel']"] == P(None, AXIS_TP) and mixer["['h_0']['kda']['b_proj']['kernel']"] == P(AXIS_FSDP, None)
    assert all(spec == P() for k, spec in mixer.items() if "proj" not in k)
    assert named["['h_3']['attn']['q_proj']['kernel']"] == P(AXIS_FSDP, AXIS_TP)  # the direct query projection


def test_the_published_initialisers():
    cfg, model, _, ids, mask = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(3), ids, mask)["params"]["h_0"]["kda"]
    a = np.exp(params["A_log"])
    assert 1.0 <= a.min() and a.max() < 16.0 and a.std() > 0
    step = jax.nn.softplus(params["dt_bias"])
    assert params["dt_bias"].shape == (64,) and 0.001 <= float(step.min()) and float(step.max()) <= 0.1
    assert all(float(jnp.abs(params[name]).max()) <= 0.5 for name in ("q_conv", "k_conv", "v_conv"))
    np.testing.assert_allclose(params["o_norm"], 1.0)
    np.testing.assert_allclose(params["g_b_proj"]["bias"], 0.0)


# ---- (i) what stands: the seven benchmark configurations' trees ----------------------------------

PARENT_TREES = json.load(open(os.path.join(HERE, "data", "rehearsal_param_trees_pr38.json")))


@pytest.mark.parametrize("name", sorted(PARENT_TREES))
def test_with_the_new_fields_at_their_defaults_a_configuration_s_tree_is_the_parent_s(name):
    """Leaf for leaf what commit 435054d (PR 38) builds from the same `rehearsal_arch` and key: path, shape, dtype
    and the draw itself (the sum of magnitudes of each leaf; recorded there into tests/data's file)."""
    spec = json.load(open(os.path.join(CONFIGS, f"{name}.json")))
    cfg = LMConfig.from_dict(spec["rehearsal_arch"])
    assert not cfg.has_kda and (cfg.kda_heads, cfg.kda_head_dim) == (0, 0)
    ids = jnp.zeros((1, 2), jnp.int32)
    tree = jax.jit(TransformerLM(cfg).init)(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    got = {jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    want = PARENT_TREES[name]
    assert sorted(got) == sorted(want)
    for path, (shape, dtype, magnitude) in want.items():
        leaf = got[path]
        assert (list(leaf.shape), str(leaf.dtype)) == (shape, dtype), path
        np.testing.assert_allclose(float(jnp.sum(jnp.abs(leaf.astype(jnp.float32)))), magnitude, rtol=1e-6, err_msg=path)
    assert len(init_cache(cfg, 2, 8)) == cfg.cache_entries


# ---- (j) the normal path ------------------------------------------------------------------------


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` with the rehearsal `model_arch` of the configuration's file: the same entry point,
    orchestrator, trainer, static generate path and cache pytree as every other cell, the frozen branch replaying
    blocks of three kinds, and the new counters in the phase records."""
    import trlx_tpu
    from trlx_tpu.trainer.api import default_config

    spec = json.load(open(os.path.join(CONFIGS, "kimi-linear-48b-ep32-l13.json")))
    config = default_config("ppo")
    config.model.model_path, config.model.tokenizer_path = "", ""
    config.model.model_arch = dict(spec["rehearsal_arch"])
    config.model.num_layers_unfrozen = 3  # kda, latent, kda
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
    assert trainer.fused_rollout and cfg.has_kda and trainer.model.branch_layer == 2
    assert sorted(trainer.state.extras["transformer"]) == ["h_2", "h_3", "h_4", "lm_head", "ln_f"]
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = [r for r in records if "step_time" in r]
    assert steps and all(np.isfinite(r["loss"]) for r in steps)
    # the recurrent, absorbed decode path's log-probs against the chunked, unabsorbed forward's: bf16 here, 192 tokens,
    # a row's 32 positions one chunk (0.012 off; the cell's rehearsal limit is 0.05)
    assert abs(steps[0]["mean_ratio"] - 1.0) < 0.03
    assert all(0 < r["moe/held_slot_share"] < 1 for r in steps)
    phases = [r for r in records if "rollout/state_bytes" in r]
    itemsize = jnp.dtype(cfg.dtype).itemsize
    row = 4 * (4 * 16 * 16 * 4 + 3 * 192 * itemsize)
    assert phases and all(p["rollout/state_bytes"] == 8 * row and p["rollout/state_bytes_per_row"] == row for p in phases)
    assert all(p["rollout/cache_bytes_per_token"] == 24 * itemsize for p in phases)  # the one latent layer's
    assert all(p["rollout/cache_bytes"] == 8 * row + 8 * 32 * 24 * itemsize for p in phases)  # the whole allocation
    assert all(0 < p["kda/state_rw_share"] < 1 and p["rollout/step_bytes_needed"] > 2 * 8 * row for p in phases)
    assert not any("ssm/state_rw_share" in p or "ssm/pad_share" in p for p in phases)
    assert any(p.get("kda/chunks_per_pass") == 1 for p in records)  # 32 positions, under one chunk of 64
    assert all(p["kda/solve_lane_fill"] == 0.125 for p in records if "kda/chunks_per_pass" in p)  # a row of a 16-wide sub-block

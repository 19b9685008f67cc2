"""The latent-attention, sparse-expert block family (models/lm.py
`LatentAttention`, models/moe.py) against the plain reference
`benchmark/references/mla_moe_decoder.py`: seeded random weights, tiny sizes,
float32, CPU. The letters are ISSUE 26's.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import mla_moe_decoder as reference
from trlx_tpu.models import moe
from trlx_tpu.models.heads import LMWithValueHead, extract_branch_params, trainable_mask
from trlx_tpu.models.lm import LMConfig, TransformerLM, cache_bytes_per_token, init_cache
from trlx_tpu.ops.generate import generate
from trlx_tpu.ops.sampling import GenerateConfig

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs")

YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 4096, "mscale": 1, "mscale_all_dim": 1}
# One dense layer, then two expert layers; 16 experts of which [4, 8) are held, 2 a token.
ARCH = dict(
    vocab_size=96, n_layer=3, n_head=4, d_model=32, d_ff=64, max_position=256, eos_token_id=0, pos_type="rotary",
    norm="rmsnorm", mlp="gated", attention="mla", activation="silu", tie_word_embeddings=False,
    ffn_layers=["dense", "experts", "experts"], rope_theta=50000, rope_scaling=YARN,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    n_experts=16, experts_per_token=2, expert_d_ff=16, n_shared_experts=1, routed_scaling_factor=2.827,
    experts_held=[4, 4],
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 2, 24


def _model(arch=ARCH, seed=0):
    cfg = LMConfig.from_dict({**arch, **F32})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :5].set(0)  # row 1 is left-padded
    params = model.init(jax.random.PRNGKey(seed), ids, mask)["params"]
    return cfg, model, params, ids * mask, mask


@pytest.mark.parametrize("row_group", [8, 1], ids=["rows at once", "a row group at a time"])
def test_logits_match_the_reference_padded_and_unpadded_rows(monkeypatch, row_group):
    """(a) also with the unabsorbed path's rows going through in groups
    (`MLA_ROW_GROUP`: a scoring pass over a whole rollout chunk)."""
    import trlx_tpu.models.lm as lm

    monkeypatch.setattr(lm, "MLA_ROW_GROUP", row_group)
    monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0 if row_group == 1 else 2048)  # and the large call's grouped path
    cfg, model, params, ids, mask = _model()
    got = model.apply({"params": params}, ids, mask)["logits"]
    want = reference.forward(params, ARCH, ids, mask, T)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=1e-4)  # no padding
    np.testing.assert_allclose(got[1, 5:], want[1, 5:], atol=2e-5, rtol=1e-4)  # its real positions


@pytest.mark.parametrize("small_call_slots", [2048, 0], ids=["small call: experts over tokens", "large call: sorted, grouped products"])
def test_gradients_of_an_unfrozen_expert_block_match_the_reference(monkeypatch, small_call_slots):
    """(b) the gradient of a mean target log-prob, every trainable leaf of the
    top block, through either way the held experts' part is computed."""
    monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", small_call_slots)
    cfg, model, params, ids, mask = _model()
    labels = jnp.roll(ids, -1, axis=1)

    predicts = mask[:, :-1] * mask[:, 1:]  # a real token from a real position: a pad's own output is nobody's input
    # (no key, no label in a PPO batch) and takes no routed expert since PR 53, which the reference does not know

    def target(logits):
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), labels[:, :-1, None], axis=-1)[..., 0]
        return jnp.sum(lp * predicts) / jnp.sum(predicts)

    top = "h_2"
    program = jax.grad(lambda p: target(model.apply({"params": {**params, top: p}}, ids, mask)["logits"]))(params[top])
    plain = jax.grad(lambda p: target(reference.forward({**params, top: p}, ARCH, ids, mask, T)))(params[top])
    train = trainable_mask({"transformer": params}, cfg, 1)["transformer"][top]
    leaves = jax.tree_util.tree_leaves_with_path(program)
    assert len(leaves) == 17
    for (path, got), want, trains in zip(leaves, jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(train)):
        name = jax.tree_util.keystr(path)
        if not trains:  # the correction bias: a buffer, no gradient moves it
            assert moe.BIAS_NAME in name and not np.any(got)
            continue
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-4, err_msg=name)


def _decode_through_cache(model, params, ids, mask, prefill):
    """Prefill `prefill` tokens, then one step a token, teacher-forced:
    ([b, T - prefill, V] logits, the expert counts of every step)."""
    cfg = model.cfg
    cache_mask = jnp.concatenate([mask[:, :prefill], jnp.zeros((B, T - prefill), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prefill], mask[:, :prefill], cache=init_cache(cfg, B, T),
                      cache_index=0, cache_mask=cache_mask)
    step = jax.jit(lambda cache, index, cache_mask, token: model.apply(
        {"params": params}, token, jnp.ones((B, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask))
    cache, logits, counts = out["cache"], [out["logits"][:, -1]], []
    for i in range(prefill, T):
        cache_mask = cache_mask.at[:, i].set(1)
        out = step(cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
        cache = out["cache"]
        logits.append(out["logits"][:, 0])
        counts.append(out["expert_counts"])
    return jnp.stack(logits[:-1], axis=1), jnp.stack(logits[1:], axis=1), counts


def test_prefill_then_absorbed_decode_matches_the_full_forward():
    """(c) logits and the chosen experts, through the latent cache."""
    cfg, model, params, ids, mask = _model()
    prefill = 9
    want = reference.forward(params, ARCH, ids, mask, T)
    last_of_prefill, decoded, counts = _decode_through_cache(model, params, ids, mask, prefill)
    np.testing.assert_allclose(last_of_prefill[:, 0], want[:, prefill - 1], atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(decoded, want[:, prefill:], atol=2e-5, rtol=1e-4)
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(init_cache(cfg, B, T))] == [(B, T, 16), (B, T, 8)] * 3
    assert cache_bytes_per_token(cfg) == (16 + 8) * 4 * 3  # float32 here
    # The chosen experts: what each step's router picked is what the full forward's router picks there.
    assert model.apply({"params": params}, ids, mask, stop_layer=1)["expert_counts"] is None  # the dense layer
    per_position = _held_counts_per_position(params, ids, mask)
    for offset, step_counts in enumerate(counts):
        np.testing.assert_array_equal(step_counts, per_position[:, :, prefill + offset].sum(axis=1))


def _held_counts_per_position(params, ids, mask):
    """[expert layers, b, T, held]: whether each position chose each held
    expert, from the reference's own router on the full forward."""
    a = ARCH
    first, held = a["experts_held"]
    x = reference._embed(params["wte"]["embedding"], ids, precision="highest")
    positions = jnp.maximum(jnp.cumsum(mask, axis=-1) - 1, 0)
    key, out = reference.arch_key(a), []
    for i, kind in enumerate(a["ffn_layers"]):
        p = params[f"h_{i}"]
        x = reference._attention(x, p["ln_1"], p["attn"], mask, positions, arch=key, precision="highest")
        y = reference._normed(x, p["ln_2"], eps=1e-5, precision="highest")
        if kind == "experts":
            chosen, _ = reference._route(y, p["moe"]["router"], p["moe"]["e_score_correction_bias"], k=2,
                                         scaling=2.827, precision="highest")
            out.append((chosen[..., None] == first + jnp.arange(held)).sum(axis=2))
            x = x + reference._expert_ffn(y, p["moe"], a, "highest")
        else:
            x = x + reference._gated_mlp(y, p["mlp"], precision="highest")
    return np.asarray(jnp.stack(out))


@pytest.mark.parametrize("capacity", ["small call", "worst-case buffer", "sized buffer", "twice the even share",
                                      "overflow: dense path", "token chunks"])
def test_the_shares_add_up(monkeypatch, capacity):
    """(d) 16 experts as 4 shares of 4: the routed parts summed and the shared
    expert counted once equal the uncut reference layer, on every path of
    `held_experts_ffn`."""
    if capacity != "small call":
        monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0)  # 96 token-slots: the grouped path of a large call
    if capacity == "sized buffer":
        # 96 slots, some 24 of them held in a share: a buffer of 64 rows holds them, under the `cond`
        monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: 64)
    elif capacity == "token chunks":
        monkeypatch.setattr(moe, "NARROW_PASS_TOKENS", 12), monkeypatch.setattr(moe, "WIDE_PASS_TOKENS", 12)  # 48 tokens: four passes
    elif capacity == "twice the even share":
        monkeypatch.setattr(moe, "ROW_TILE", 8)  # `slot_capacity`'s own rule: 2 x 24 slots a share = 48 rows of 96
    elif capacity == "overflow: dense path":
        monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: 8)
    one = {**ARCH, "n_layer": 2, "ffn_layers": ["dense", "experts"], "experts_held": [0, 16]}
    cfg, model, params, ids, mask = _model(one)
    whole = params["h_1"]["moe"]
    y = reference._normed(jax.random.normal(jax.random.PRNGKey(7), (B, T, 32)), params["h_1"]["ln_2"], eps=1e-5,
                          precision="highest")
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
    # a held range off by one, or the shared expert in every share, does not add up
    off_by_one = sum(share(first, 4, 0)[0] for first in (1, 5, 9)) + share(13, 3, 0)[0] + shared_once
    assert float(jnp.abs(off_by_one - want).max()) > 1e-2
    counted_per_share = sum(share(first, 4, 1)[0] for first in (0, 4, 8, 12))
    assert float(jnp.abs(counted_per_share - want).max()) > 1e-2


GPT_DICTS = {
    "gptj": dict(vocab_size=64, n_layer=2, n_head=2, d_model=32, d_ff=0, max_position=64, eos_token_id=0, pos_type="rotary",
                 rotary_dim=8, parallel_residual=True, use_parallel_ln=False, fused_qkv=False, qkv_bias=False,
                 out_bias=False, scale_attn=True, tie_word_embeddings=False, activation="gelu_new", ln_eps=1e-5,
                 extra={"lm_head_bias": True}),
    "gptneo": dict(vocab_size=64, n_layer=2, n_head=2, d_model=32, max_position=64, eos_token_id=0, pos_type="learned",
                   fused_qkv=False, qkv_bias=False, scale_attn=False, attention_layers=["global", "local"], window_size=8),
}


@pytest.mark.parametrize("family", sorted(GPT_DICTS))
def test_gpt_dicts_still_load(family):
    """(e)"""
    cfg = LMConfig.from_dict(GPT_DICTS[family])
    assert (cfg.norm, cfg.mlp, cfg.attention, cfg.ffn_layers) == ("layernorm", "dense", "mha", ())


def test_benchmark_configurations_load():
    """(e) every `model_arch` and `rehearsal_arch` the benchmark has."""
    import glob
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = sorted(glob.glob(os.path.join(root, "benchmark", "configs", "*.json")))
    assert len(files) >= 4
    for path in files:
        spec = json.load(open(path))
        for key in ("model_arch", "rehearsal_arch"):
            LMConfig.from_dict(spec[key])


@pytest.mark.parametrize("bad, message", [
    ({"sliding_window": 128}, "unknown architecture key"),
    ({"num_experts": 8}, "unknown architecture key"),
    ({"attention": "gqa"}, "unknown attention kind"),
    ({"norm": "layer_norm"}, "unknown norm kind"),
    ({"activation": "swiglu"}, "unknown activation"),
    ({"ffn_layers": ["dense", "experts"]}, "ffn_layers"),
    ({"experts_held": [12, 8]}, "experts_held"),
    ({"kv_cache_quant": True}, "kv_cache_quant"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling type"),
])
def test_from_dict_raises_on_what_the_program_lacks(bad, message):
    """(e) a `model_arch` that names a mechanism the program lacks fails at construction."""
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **bad})


def test_mask_and_branch_on_the_mixed_stack():
    """(f)"""
    cfg = LMConfig.from_dict({**ARCH, **F32})
    model = LMWithValueHead(cfg, branch_layer=2)
    ids = jnp.ones((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    mask = trainable_mask(params, cfg, 1)
    flat = {jax.tree_util.keystr(p): v for p, v in jax.tree_util.tree_leaves_with_path(mask)}
    trains = lambda fragment: {v for k, v in flat.items() if fragment in k}
    assert trains("'h_0'") == {False} and trains("'h_1'") == {False}  # a dense block and an expert block, frozen
    assert trains("'h_2']['attn'") == {True} and trains("'h_2']['moe']['experts_") == {True}
    assert trains("'h_2']['moe']['router'") == {True} and trains("'h_2']['moe']['shared'") == {True}
    assert trains(moe.BIAS_NAME) == {False}  # a buffer in every block, unfrozen or not
    assert trains("'wte'") == trains("'ln_f'") == trains("'lm_head'") == trains("'v_head'") == {True}
    everything = trainable_mask(params, cfg, 0)
    assert {v for k, v in jax.tree_util.tree_leaves_with_path(everything) if moe.BIAS_NAME not in jax.tree_util.keystr(k)} == {True}
    branch = extract_branch_params(params, cfg, 2)["transformer"]
    assert sorted(branch) == ["h_2", "lm_head", "ln_f"]
    assert branch["h_2"]["moe"]["experts_gate"].shape == (4, 32, 16)
    assert branch["h_2"]["moe"]["experts_gate"] is not params["transformer"]["h_2"]["moe"]["experts_gate"]
    # the replay of the branch from the hidden state entering it gives the policy's own logits
    out = model.apply({"params": params}, ids, jnp.ones_like(ids), collect_branch_hidden=True)
    replay = model.apply({"params": {"transformer": branch}}, out["branch_hidden"], jnp.ones_like(ids),
                         method="forward_branch")
    np.testing.assert_allclose(replay, out["logits"], atol=1e-5)


def test_counters_by_hand():
    """(g) three tokens, two choices each, experts [4, 8) of 16 held."""
    ids = jnp.array([[4, 9], [5, 4], [15, 0]], jnp.int32)
    counts = moe.held_counts(ids, 4, 4)
    np.testing.assert_array_equal(counts, [2, 1, 0, 0])
    share, load = moe.expert_load_stats(jnp.stack([counts, jnp.array([1, 1, 1, 1])]), n_tokens=3, k=2)
    assert float(share) == pytest.approx((3 + 4) / (2 * 3 * 2))  # held slots over slots, both layers
    assert float(load) == pytest.approx(2 / (7 / 8))  # the fullest expert over the mean of the held ones
    # the router by hand: sigmoid scores, the bias moves the choice and not the weight
    x = jnp.eye(2, dtype=jnp.float32)
    router = jnp.array([[2.0, 0.0, -2.0], [0.0, 0.0, 0.0]])
    chosen, weights = moe.route(x, router, jnp.array([0.0, 0.0, 5.0]), 2, 3.0)
    # expert 2 wins by its bias alone; token 1's three scores tie, the lower index takes the second place
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), axis=-1), [[0, 2], [0, 2]])
    sig = 1 / (1 + np.exp(-np.array([2.0, -2.0])))
    order = np.argsort(np.asarray(chosen[0]))
    np.testing.assert_allclose(np.asarray(weights[0])[order], sig / sig.sum() * 3.0, rtol=1e-6)
    np.testing.assert_allclose(weights[1], [1.5, 1.5], rtol=1e-6)
    assert 32 * 8 <= moe.SMALL_CALL_SLOTS < 4096 * 8  # a decode step is a small call, a train step is not
    # twice the even share in whole tiles of 512 rows; the worst case is 32768
    assert moe.slot_capacity(4096, 8, 8, 384) == 1536  # Kimi's train step: 2 x 682.7 slots
    assert moe.slot_capacity(4096, 8, 8, 128) == 4096  # K-EXAONE's: 2 x 2048
    assert moe.slot_capacity(4096, 8, 8, 32) == 12288 == moe.SLOTS_PER_TOKEN * 4096  # never more than three rows a token
    assert moe.slot_capacity(48, 2, 4, 16) == 96  # nor than the worst case, where that is no larger


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _primitives(jaxpr):
    return (eqn.primitive.name for eqn in _equations(jaxpr))


def test_a_decode_step_runs_every_held_expert_whatever_the_routing():
    """A small call's work does not follow the routing: no branch on the
    counts (a rollout's seconds followed the experts a seed made popular,
    PERF.md PR 26), and an expert no token chose adds exactly nothing."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 8), jnp.float32)
    gate, up = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8, 16), jnp.float32)
    down = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 8), jnp.float32)
    ids = jnp.array([[4, 9], [9, 4], [0, 1], [6, 4], [4, 6], [1, 0]], jnp.int32)  # of the held [4, 8): 5 and 7 untouched
    weights = jax.random.uniform(jax.random.PRNGKey(3), (6, 2), jnp.float32)
    call = lambda x: moe.held_experts_ffn(x, ids, weights, 4, 16, gate, up, down, jax.nn.silu)
    assert not {"cond", "sort", "scatter", "scatter-add", "ragged_dot_general"} & set(_primitives(jax.make_jaxpr(call)(x).jaxpr))
    y, counts = call(x)
    np.testing.assert_array_equal(counts, [4, 0, 2, 0])
    want = sum(jnp.where((ids == e)[..., None], weights[..., None], 0.0).sum(1)
               * ((jax.nn.silu(x @ gate[e - 4]) * (x @ up[e - 4])) @ down[e - 4]) for e in (4, 6))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


def _routing(case, n=64, k=2, n_experts=16, first=4, held=4):
    """ids [n, k] int32 of a large call, the held experts `[first, first + held)`."""
    if case == "no token chooses a held expert":
        return jnp.tile(jnp.array([[0, 15]], jnp.int32), (n, 1))
    if case == "every token chooses the same one":
        return jnp.tile(jnp.array([[first + 2, 0]], jnp.int32), (n, 1))
    if case == "every token chooses two held ones":
        return jnp.tile(jnp.array([[first + 3, first]], jnp.int32), (n, 1))
    _, ids = jax.lax.top_k(jax.random.uniform(jax.random.PRNGKey(int(case.split()[-1])), (n, n_experts)), k)
    return ids.astype(jnp.int32)


def _sorted_placement(ids, first, held, capacity):
    """The placement the stable `argsort` of PR 26 gave: (slot, live)."""
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)[:capacity]
    return order, key[order] < held


@pytest.mark.parametrize("capacity", [24, 32, 128], ids=lambda rows: f"{rows} rows")
@pytest.mark.parametrize("case", ["seed 0", "seed 1", "seed 2", "no token chooses a held expert",
                                  "every token chooses the same one", "every token chooses two held ones"])
def test_the_counted_placement_is_the_stable_sort_row_for_row(case, capacity):
    """`place_slots` puts every held slot where the stable sort by expert put
    it, so the grouped products see the same groups in the same order; rows
    past the held slots are dead; a buffer too small holds the first rows."""
    ids = _routing(case)
    counts = moe.held_counts(ids, 4, 4)
    slot, live, by_token = moe.place_slots(ids, counts, 4, capacity)
    assert by_token is None  # 12 to 64 rows a choice: the sum back is the product, and nothing is computed for the gather
    want_slot, want_live = _sorted_placement(ids, 4, 4, capacity)
    placed = min(int(counts.sum()), capacity)
    assert slot.shape == live.shape == (capacity,) and slot.dtype == jnp.int32
    np.testing.assert_array_equal(slot[:placed], want_slot[:placed])
    if int(counts.sum()) <= capacity:
        np.testing.assert_array_equal(live, want_live)
        assert int(live.sum()) == placed and not np.any(slot[placed:])  # dead rows name slot 0
    # a live row's expert, read back from its slot, is its group's
    expert = ids.reshape(-1)[slot[:placed]] - 4
    np.testing.assert_array_equal(expert, np.repeat(np.arange(4), np.asarray(counts))[:placed])


FORMS = {"the 0/1 product": 10 ** 9, "a gather of each token's rows": 1}  # `GATHER_ROWS_PER_CHOICE` that makes every call take the form


@pytest.mark.parametrize("held_of", ["more held than a token chooses", "fewer held than a token chooses"])
@pytest.mark.parametrize("capacity", [24, 32, 128], ids=lambda rows: f"{rows} rows")
@pytest.mark.parametrize("case", ["seed 0", "seed 1", "seed 2", "no token chooses a held expert",
                                  "every token chooses the same one", "every token chooses two held ones"])
def test_the_tokens_side_of_the_placement_is_its_inverse_row_for_row(monkeypatch, case, capacity, held_of):
    """`rows_of` is `place_slots`' own (slot, live) read from the tokens'
    side: a held choice names the live row that holds its slot, every live row
    is named by exactly one choice, and a choice that is not held (or whose
    row lies past a buffer too small) names row 0 under a False mark. A column
    a choice where the chip holds as many experts as a token chooses, a column
    a held expert where it holds fewer."""
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", FORMS["a gather of each token's rows"])
    ids = _routing(case)
    first, held = (4, 4) if held_of == "more held than a token chooses" else (4, 1)
    k = ids.shape[-1]
    counts = moe.held_counts(ids, first, held)
    slot, live, (rows_of, held_choice) = moe.place_slots(ids, counts, first, capacity)
    assert rows_of.shape == held_choice.shape == (64, min(k, held)) and rows_of.dtype == jnp.int32 and held_choice.dtype == jnp.bool_
    slot, live, rows_of, held_choice = (np.asarray(a) for a in (slot, live, rows_of, held_choice))
    placed = min(int(counts.sum()), capacity)
    assert int(held_choice.sum()) == placed and not rows_of[~held_choice].any()
    named = np.zeros(capacity, np.int32)
    for token, column in zip(*np.nonzero(held_choice)):
        row = rows_of[token, column]
        assert live[row] and slot[row] // k == token
        if held >= k:
            assert slot[row] == token * k + column  # the column is the choice
        else:
            assert np.asarray(ids)[token, slot[row] % k] == first + column  # the column is the held expert
        named[row] += 1
    np.testing.assert_array_equal(named[:placed], 1)
    assert not named[placed:].any()
    # and the (slot, live) beside it is what the product's placement is
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", FORMS["the 0/1 product"])
    want_slot, want_live, none = moe.place_slots(ids, counts, first, capacity)
    assert none is None
    np.testing.assert_array_equal(slot, want_slot)
    np.testing.assert_array_equal(live, want_live)


@pytest.mark.parametrize("cell, n, k, held, n_experts, capacity, gather", [
    ("smallthinker-ep4.ppo-4096x2048", 4096, 6, 16, 64, 12288, True),
    ("zaya1-ep2.ppo-4096x2048", 4096, 1, 8, 16, 4096, True),
    ("kexaone-l5.ppo-128x896", 4096, 8, 8, 128, 4096, False),
    ("kimilinear-l13.ppo-128x896", 4096, 8, 8, 256, 2048, False),
    ("kimik2.5-l5.ppo-128x896", 4096, 8, 8, 384, 1536, False),
])
def test_the_shapes_pick_the_form_of_the_sum_back(cell, n, k, held, n_experts, capacity, gather):
    """The rule at the five expert cells' own call shapes (PERF.md section 6,
    PR 45: both forms' times on the chip): the gather where the buffer has
    2,048 and 4,096 rows a choice, the product at 512, 256 and 192; and the
    counter `moe/sum_rows_per_token` says which, for 4,096 tokens and for a
    train batch of three times as many alike (three passes under the product,
    ONE under the gather: `pass_tokens`)."""
    assert moe.slot_capacity(n, k, held, n_experts) == capacity
    assert moe.sums_by_gather(capacity, min(k, held)) is gather
    assert moe.sum_rows_per_token(n, k, held, n_experts) == moe.sum_rows_per_token(3 * n, k, held, n_experts) == (min(k, held) if gather else capacity)
    ids = jax.ShapeDtypeStruct((n, k), jnp.int32), jax.ShapeDtypeStruct((held,), jnp.int32)
    by_token = jax.eval_shape(lambda ids, counts: moe.place_slots(ids, counts, 0, capacity), *ids)[2]
    assert (by_token is not None) is gather
    if gather:
        assert [a.shape for a in by_token] == [(n, min(k, held))] * 2


def test_sum_rows_per_token_by_hand(monkeypatch):
    """`moe/sum_rows_per_token`: what the sum back reads for one token. A
    small call has no buffer and sums one result a held expert; a large call
    the whole buffer of a pass under the product, a row a choice under the
    gather, whichever `sums_by_gather` says."""
    assert moe.sum_rows_per_token(32, 8, 8, 384) == 8 and moe.sum_rows_per_token(256, 8, 4, 16) == 4  # small calls
    assert moe.sum_rows_per_token(4096, 8, 8, 384) == 1536 and moe.sum_rows_per_token(32768, 8, 8, 384) == 1536  # the product, a pass's buffer
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", 192)
    assert moe.sum_rows_per_token(4096, 8, 8, 384) == 8 and moe.sum_rows_per_token(4096, 8, 4, 192) == 4  # 1,536 rows over 8, and over 4 held
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", 193)
    assert moe.sum_rows_per_token(4096, 8, 8, 384) == 1536
    # and the train step's record carries it beside `moe/first_buffer_share` (the PPO test below reads it)


def _expert_call(seed=0, n=64, d=8, f=16, held=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (n, d), jnp.float32)
    weights = jax.random.uniform(keys[1], (n, 2), jnp.float32, 0.2, 1.0)
    gate, up = jax.random.normal(keys[2], (2, held, d, f), jnp.float32) * 0.5
    down = jax.random.normal(keys[3], (held, f, d), jnp.float32) * 0.5
    return x, weights, gate, up, down


RUNGS = {  # rows of the slot buffer; the held slots of `_routing("seed 0")`: 33
    "the buffer holds the slots with room": 40,
    "held slots exactly the buffer's rows": 33,
    "one more than the buffer's rows: every held expert over every token": 32,
    "far past the buffer": 8,
    "the worst case: no cond": 128,
    "twice the even share in tiles of 8 rows": None,  # `slot_capacity`'s own rule: 64 rows
}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("rung", RUNGS)
def test_each_rung_matches_every_held_expert_over_every_token(monkeypatch, rung, form):
    """The large call is exact for any routing, whichever path the held slots
    select and whichever form sums the rows back: forward and the gradient of
    every input a training run moves (tokens, slot weights, the three stacks)
    equal `dense_held_ffn`'s in float32; and the grouped path is taken exactly
    where the buffer holds the slots."""
    monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0)
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", FORMS[form])
    if RUNGS[rung] is None:
        monkeypatch.setattr(moe, "ROW_TILE", 8)
        assert moe.slot_capacity(64, 2, 4, 16) == 64
    else:
        monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: RUNGS[rung])
    ids = _routing("seed 0")
    assert int(moe.held_counts(ids, 4, 4).sum()) == 33
    args = _expert_call()
    large = lambda x, w, gate, up, down: moe.held_experts_ffn(x, ids, w, 4, 16, gate, up, down, jax.nn.silu)[0]
    dense = lambda x, w, gate, up, down: moe.dense_held_ffn(x, ids, w, 4, gate, up, down, jax.nn.silu)
    np.testing.assert_allclose(large(*args), dense(*args), atol=1e-5, rtol=1e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    got = jax.grad(loss(large), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(dense), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("x", "weights", "gate", "up", "down"), got, want):
        assert float(jnp.abs(w).max()) > 1e-3, name
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-4, err_msg=name)

    # which path ran: each stands in for itself with a constant
    monkeypatch.setattr(moe, "grouped_held_ffn", lambda x, ids, weights, counts, first, capacity, *rest: jnp.full_like(x, capacity))
    monkeypatch.setattr(moe, "dense_held_ffn", lambda x, *rest: jnp.full_like(x, -1.0))
    taken = float(moe.held_experts_ffn(args[0], ids, args[1], 4, 16, *args[2:], jax.nn.silu)[0][0, 0])
    rows = moe.slot_capacity(64, 2, 4, 16)
    assert taken == (rows if 33 <= rows else -1.0)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("rung", ["the buffer holds the slots with room", "the worst case: no cond"])
def test_what_a_grouped_product_leaves_in_dead_rows_reaches_nothing(monkeypatch, rung, form):
    """A grouped product leaves the rows outside its groups unwritten, in its
    result and in its lhs-gradient (on the chip: whatever was there, PERF.md
    PR 26). Non-finite values planted in both reach neither the result nor a
    gradient: every operand of a product and its last result pass the mask."""
    plain = jax.lax.ragged_dot

    @jax.custom_vjp
    def planted(lhs, rhs, group_sizes):
        dead = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(group_sizes)
        return jnp.where(dead, jnp.nan, plain(lhs, rhs, group_sizes))

    def planted_fwd(lhs, rhs, group_sizes):
        return planted(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def planted_bwd(res, g):
        lhs, rhs, group_sizes = res
        dead = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(group_sizes)
        # the transposes read the rows of the groups only, and leave the dead rows of the lhs-gradient unwritten
        d_lhs, d_rhs = jax.vjp(lambda l, r: plain(l, r, group_sizes), lhs, rhs)[1](jnp.where(dead, 0, g))
        return jnp.where(dead, jnp.inf, d_lhs), d_rhs, None

    planted.defvjp(planted_fwd, planted_bwd)
    monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0)
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", FORMS[form])
    monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: RUNGS[rung])
    ids = _routing("seed 0")
    args = _expert_call()
    large = lambda x, w, gate, up, down: moe.held_experts_ffn(x, ids, w, 4, 16, gate, up, down, jax.nn.silu)[0]
    loss = lambda *a: jnp.sum(jnp.sin(large(*a)))
    want_y, want_g = large(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(jax.lax, "ragged_dot", planted)
    got_y, got_g = large(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    assert bool(jnp.all(jnp.isfinite(got_y)))
    np.testing.assert_allclose(got_y, want_y, atol=1e-6)
    for name, g, w in zip(("x", "weights", "gate", "up", "down"), got_g, want_g):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, w, atol=1e-6, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("form", FORMS)
def test_put_rows_is_the_transpose_of_take_rows(form):
    """The two maps between tokens and buffer rows, by hand, in both forms of
    the sum back: a dead row takes nothing and gives nothing, a token two rows
    name gets their sum, and each is the other's gradient. Under the gather a
    dead row need not be finite: it is selected, not multiplied by zero."""
    x = jnp.arange(12, dtype=jnp.float32).reshape(4, 3) + 1
    token, live = jnp.array([2, 0, 2, 0, 0], jnp.int32), jnp.array([True, True, True, False, False])
    # the same placement from the tokens' side: token 0 is named by row 1, token 2 by rows 0 and 2, a column a choice
    rows_of = jnp.array([[0, 1], [0, 0], [0, 2], [0, 0]], jnp.int32)
    held_choice = jnp.array([[False, True], [False, False], [True, True], [False, False]])
    placed = (token, live, (rows_of, held_choice) if form == "a gather of each token's rows" else None)
    rows = moe.take_rows(4, x, placed)
    np.testing.assert_array_equal(rows, [x[2], x[0], x[2], [0, 0, 0], [0, 0, 0]])
    v = jnp.arange(15, dtype=jnp.float32).reshape(5, 3) * 1.25 + 0.1
    back = moe.put_rows(4, v, placed)
    np.testing.assert_array_equal(back, [v[1], [0, 0, 0], v[0] + v[2], [0, 0, 0]])
    np.testing.assert_array_equal(back, jnp.zeros_like(x).at[token].add(jnp.where(live[:, None], v, 0)))
    np.testing.assert_array_equal(jax.grad(lambda x: jnp.sum(moe.take_rows(4, x, placed) * v))(x), back)
    np.testing.assert_array_equal(jax.grad(lambda v: jnp.sum(moe.put_rows(4, v, placed) * x))(v), rows)
    planted = v.at[3:].set(jnp.array([[jnp.nan, jnp.inf, -jnp.inf]] * 2))
    if placed[2] is None:
        assert not bool(jnp.all(jnp.isfinite(moe.put_rows(4, planted, placed))))  # 0 x NaN: the product needs its dead rows finite
    else:
        np.testing.assert_array_equal(moe.put_rows(4, planted, placed), back)
        np.testing.assert_array_equal(jax.grad(lambda x: jnp.sum(moe.take_rows(4, x, placed) * planted))(x), back)
    # bf16 rows: the sum is in float32 and rounded once, in either form
    tiny = jnp.array([[256.0], [1.0], [1.0], [0.0], [0.0]], jnp.bfloat16)
    by_token = None if placed[2] is None else (jnp.array([[0, 1, 2]], jnp.int32), jnp.array([[True, True, True]]))
    assert float(moe.put_rows(1, tiny, (jnp.zeros(5, jnp.int32), live, by_token))[0, 0]) == 258.0  # 256 + 1 + 1 in bf16 steps would stay 256


def test_first_buffer_share_by_hand(monkeypatch):
    """`moe/first_buffer_share`: the expert layers of a step whose held slots
    the first buffer holds, over the expert layers."""
    counts = jnp.array([[700, 300, 300, 200], [800, 300, 300, 137], [0, 0, 0, 0], [1536, 1, 0, 0]], jnp.int32)
    # 4,096 tokens, 8 a token, 4 held of 192: the first buffer is 2 x 682.7 slots in whole tiles = 1,536 rows
    assert moe.slot_capacity(4096, 8, 4, 192) == 1536
    assert float(moe.first_buffer_share(counts, 4096, 8, 192)) == 0.5  # 1500 fits, 1537 does not, 0 fits, 1537 does not
    assert float(moe.first_buffer_share(counts[:1], 4096, 8, 192)) == 1.0
    assert float(moe.first_buffer_share(counts[1:2], 4096, 8, 192)) == 0.0
    # a call in token chunks is held to its chunks' buffers together; a small call has no buffer to overflow
    assert float(moe.first_buffer_share(counts, 8192, 8, 192)) == 1.0
    assert float(moe.first_buffer_share(counts // 16, 128, 8, 192)) == 1.0
    # and the train step's record carries it beside the two counters of PR 26 (the PPO test below reads it)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("call", ["large call", "large call in token chunks", "small call"])
def test_no_call_sorts_and_the_small_call_neither_scatters_nor_groups(monkeypatch, call, form):
    """The large call places its slots by counting and sums them back by a
    product or a gather: its jaxpr, forward and backward, holds no `sort` and
    no `scatter-add` of rows in either form (the pair `take_rows` / `put_rows`
    is its own transpose; autodiff of a gather of rows would be one: the one
    scatter-add there is, and was, adds the slot weights' gradients, scalars,
    into a vector); the small call's holds no `sort`, no scatter and no
    grouped product, as before."""
    monkeypatch.setattr(moe, "GATHER_ROWS_PER_CHOICE", FORMS[form])
    n = {"large call": 2048, "large call in token chunks": 8192, "small call": 128}[call]
    args = (jax.ShapeDtypeStruct((n, 8), jnp.float32), jax.ShapeDtypeStruct((n, 8), jnp.int32), jax.ShapeDtypeStruct((n, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 8, 16), jnp.float32), jax.ShapeDtypeStruct((8, 8, 16), jnp.float32), jax.ShapeDtypeStruct((8, 16, 8), jnp.float32))
    fn = lambda x, ids, w, gate, up, down: jnp.sum(moe.held_experts_ffn(x, ids, w, 0, 384, gate, up, down, jax.nn.silu)[0])
    jaxpr = jax.make_jaxpr(jax.value_and_grad(fn, argnums=(0, 2, 3, 4, 5)))(*args).jaxpr
    names = set(_primitives(jaxpr))
    assert "sort" not in names
    assert all(len(eqn.invars[0].aval.shape) == 1 for eqn in _equations(jaxpr) if eqn.primitive.name == "scatter-add")
    if call == "small call":
        assert not {"scatter", "scatter-add", "ragged_dot_general", "cond"} & names
    else:
        assert {"ragged_dot_general", "cond", "cumsum", "scatter", "gather"} <= names  # the one scatter is `place_slots`' own `.at[row].set`


def test_generate_carries_experts_touched_and_stays_one_loop():
    """(g) `rollout/experts_touched`'s source, against the counts of single steps."""
    cfg, model, params, ids, mask = _model()
    gcfg = GenerateConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
    fn = lambda p, i, m: generate(p, i, m, jax.random.PRNGKey(2), model=model, gcfg=gcfg,
                                  step_stats_fn=lambda tok, s: {"token": tok.astype(jnp.float32)})
    tokens, out_mask, stats = jax.jit(fn)({"params": params}, ids[:, :9], mask[:, :9])
    per_position = _held_counts_per_position(params, tokens, out_mask)
    touched = [(per_position[:, :, 9 + step].sum(axis=1) > 0).sum(axis=-1).mean() for step in range(6)]
    assert float(stats["experts_touched_per_step"]) == pytest.approx(float(np.mean(touched)), abs=1e-6)
    jaxpr = jax.make_jaxpr(fn)({"params": params}, ids[:, :9], mask[:, :9]).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count("while") == 1  # ops/generate.py stays one loop


def test_partition_rules_name_every_new_parameter():
    """No parameter of the new block family falls to the replicating fallback,
    and on an fsdp x tp mesh none of the large ones stays whole."""
    from jax.sharding import PartitionSpec

    from trlx_tpu.parallel import make_mesh
    from trlx_tpu.parallel.sharding import lm_partition_rules, match_partition_rules, sanitize_specs

    cfg, model, params, ids, mask = _model()
    rules = lm_partition_rules()
    assert rules[-1][0] == ".*"
    specs = match_partition_rules(rules[:-1] + [(".*", "fallback")], {"transformer": params})
    assert "fallback" not in jax.tree_util.tree_leaves(specs, is_leaf=lambda s: isinstance(s, (str, PartitionSpec)))
    mesh = make_mesh((2, 2, 2, 1))
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # sanitize_specs warns where it falls back to replication
        specs = sanitize_specs(mesh, params, match_partition_rules(rules, params))
    for path, spec in jax.tree_util.tree_leaves_with_path(specs, is_leaf=lambda s: isinstance(s, PartitionSpec)):
        name = jax.tree_util.keystr(path)
        if any(part in name for part in ("kernel", "experts_", "router", "embedding")):
            assert any(axis is not None for axis in spec), name


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """(h) `trlx_tpu.train` -> orchestrator -> ops/generate.py -> make_experience -> learn():
    the fresh-step PPO ratio compares the absorbed decode path (the sampler's
    own log-probs, fused rollout stats) with the unabsorbed train forward."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))
    import trlx_tpu
    from randomwalks import base_config

    config = base_config("ppo", ARCH["vocab_size"], 16)
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 1
    config.train.batch_size, config.train.total_steps, config.train.epochs = 8, 4, 4  # dp 8 over the test devices
    config.train.eval_interval, config.train.log_interval = 100, 1
    config.train.checkpoint_dir = str(tmp_path)
    config.method.num_rollouts = config.method.chunk_size = 8
    config.method.ppo_epochs = 2
    config.method.gen_kwargs = {"prompt_length": 4, "max_new_tokens": 12, "min_new_tokens": 12, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, ARCH["vocab_size"], size=int(n)).tolist() for n in rng.integers(2, 5, size=8)]
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 96 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    assert trainer.fused_rollout and trainer.model.cfg.attention == "mla"
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == [1, 2, 3, 4]
    for first in (1, 3):  # the first step of each iteration: the policy has not moved since it sampled
        assert abs(steps[first]["mean_ratio"] - 1.0) < 1e-3, steps[first]["mean_ratio"]
    for r in steps.values():
        assert np.isfinite(r["loss"]) if "loss" in r else True
        assert 0.0 < r["moe/held_slot_share"] < 1.0 and r["moe/max_expert_load"] >= 1.0
        assert r["moe/first_buffer_share"] == 1.0  # a tiny model's train step is a small call: no buffer to overflow
        assert r["moe/sum_rows_per_token"] == ARCH["experts_held"][1]  # and sums one result a held expert
    phases = [r for r in records if "time/window_wall_s" in r]
    assert phases and all(0.0 <= p["rollout/experts_touched"] <= 4.0 for p in phases)
    assert all(p["rollout/cache_bytes_per_token"] == (16 + 8) * 4 * 3 for p in phases)


# ---- the length of a pass (PR 48) ----------------------------------------------------------------------

EXPERT_CONFIGS = {  # configuration: (wide buffer, {tokens of a call the cell makes: its passes})
    "smallthinker-21b-ep4": (True, {4096: 1, 12288: 1, 65536: 4, 98304: 6}),  # a row, the train batch, the prefill, the scoring pass
    "zaya1-8b-ep2-l8": (True, {4096: 1, 12288: 1, 65536: 4, 98304: 6}),
    "kimi-k2.5-ep48-l5": (False, {4096: 1, 8192: 2, 32768: 8}),
    "k-exaone-236b-ep16-l5": (False, {4096: 1, 8192: 2, 32768: 8}),
    "kimi-linear-48b-ep32-l13": (False, {4096: 1, 8192: 2, 32768: 8}),
}


def _expert_shapes(name):
    arch = json.load(open(os.path.join(CONFIGS, name + ".json")))["model_arch"]
    return arch["experts_per_token"], arch["experts_held"][1], arch["n_experts"]


@pytest.mark.parametrize("name", EXPERT_CONFIGS)
def test_pass_tokens_at_the_expert_configurations_own_calls(name):
    """The length of a pass from the call's shapes, at the five expert
    configurations' published counts: where the sum back is the gather (the
    buffer is wide) a pass is the longest divisor not above 16,384 tokens, so
    the 12,288-token train batch is ONE grouped call; where it is the 0/1
    product the passes are the 4,096 tokens they were before PR 48, at every
    length those cells call with. The counters follow the same rule."""
    k, held, n_experts = _expert_shapes(name)
    wide, calls = EXPERT_CONFIGS[name]
    assert moe.sums_by_gather(moe.slot_capacity(moe.NARROW_PASS_TOKENS, k, held, n_experts), min(k, held)) is wide
    for n, passes in calls.items():
        tokens = moe.pass_tokens(n, k, held, n_experts)
        assert (n // tokens, n % tokens) == (passes, 0) and tokens <= (moe.WIDE_PASS_TOKENS if wide else moe.NARROW_PASS_TOKENS)
        share = held / n_experts  # an even router
        assert moe.rows_per_held_expert(share, n, k, held, n_experts) == pytest.approx(tokens * k / n_experts)
        capacity = moe.slot_capacity(tokens, k, held, n_experts)
        assert moe.sum_rows_per_token(n, k, held, n_experts) == (min(k, held) if wide else capacity)
        # a call's held slots are held to its passes' buffers together: twice the even share fits, one slot more does not
        fits = jnp.full((1, held), passes * capacity // held, jnp.int32)
        assert float(moe.first_buffer_share(fits, n, k, n_experts)) == 1.0
        assert float(moe.first_buffer_share(fits.at[0, 0].add(passes * capacity % held + 1), n, k, n_experts)) == 0.0


@pytest.mark.parametrize("name, n, want", [
    ("smallthinker-21b-ep4", 4096 * 17, 512 * 17),  # eight passes of 8,704, not one of 69,632
    ("smallthinker-21b-ep4", 20480, 10240),
    ("smallthinker-21b-ep4", 16384 + 2, 8193),
    ("smallthinker-21b-ep4", 16411, None),  # a prime: its longest divisor is 1
    ("smallthinker-21b-ep4", 2 * 16411, None),  # 16,411 is over the cap, and a pass of 2 tokens is a small call
    ("smallthinker-21b-ep4", 17 * 1021, 1021),  # seventeen passes: however many the longest divisor makes
    ("kimi-k2.5-ep48-l5", 6000, 3000),
    ("kimi-k2.5-ep48-l5", 4099, None),  # a prime just over a pass
    ("kimi-k2.5-ep48-l5", 7 * 1001, 1001),  # the parent ran it in one pass of 7,007
    ("kimi-k2.5-ep48-l5", 17 * 257, 257),  # 257 x 8 slots is a large call still
    ("kimi-k2.5-ep48-l5", 17 * 251, None),  # 251 x 8 is a small call's
    ("kimi-k2.5-ep48-l5", 3000, 3000),  # under a pass: itself, whatever its divisors
    ("kimi-k2.5-ep48-l5", 1031, 1031),
])
def test_a_pass_is_a_divisor_or_the_call_is_refused(name, n, want):
    """Never one pass of everything: a length over the cap goes in passes of
    its longest divisor not above it, however many that makes, and one whose
    longest is a small call's (`SMALL_CALL_SLOTS`; a prime's is 1) is refused
    by name (before PR 48 a length 4,096 did not divide went through in ONE
    pass, whatever its size)."""
    shapes = _expert_shapes(name)
    if want is None:
        with pytest.raises(ValueError, match=f"{n} tokens has no divisor"):
            moe.pass_tokens(n, *shapes)
        x = jax.ShapeDtypeStruct((n, 8), jnp.float32)
        stacks = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((shapes[1], 8, 4), (shapes[1], 8, 4), (shapes[1], 4, 8))]
        routed = jax.ShapeDtypeStruct((n, shapes[0]), jnp.int32), jax.ShapeDtypeStruct((n, shapes[0]), jnp.float32)
        with pytest.raises(ValueError, match="no divisor"):  # and the layer's own call refuses with it
            jax.eval_shape(lambda x, ids, w, *s: moe.held_experts_ffn(x, ids, w, 0, shapes[2], *s, jax.nn.relu), x, *routed, *stacks)
    else:
        assert moe.pass_tokens(n, *shapes) == want

"""Continuous-batching rollout engine (trlx_tpu/engine).

Unit tier: the width-grouped admission queue, the model's vector
``cache_index`` path (per-slot scatter writes + per-row causal frontier), and
the engine's straggler accounting. Parity tier (the acceptance criterion):
greedy slot decode is token-for-token identical to whole-batch
``make_generate_fn`` decode — mixed bucket widths, mixed response lengths,
slot refill mid-run, ONE compiled decode program. Integration tier (still
fast, CPU): a full PPO run with ``method.rollout_engine`` on trains and tears
down cleanly, and the reward_hang / slow_step fault drills hold through the
engine path (the PR 5 drill, re-run against the new generation machinery).
"""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import trlx_tpu  # noqa: E402
from randomwalks import base_config, generate_random_walks  # noqa: E402
from trlx_tpu.engine import Episode, RolloutEngine  # noqa: E402
from trlx_tpu.models import LMConfig, LMWithValueHead  # noqa: E402
from trlx_tpu.ops.generate import make_generate_fn  # noqa: E402
from trlx_tpu.ops.sampling import GenerateConfig  # noqa: E402
from trlx_tpu.pipeline.prompt_pipeline import PromptSlotQueue  # noqa: E402


# ------------------------------------------------------------ admission queue


def test_prompt_slot_queue_groups_by_width_fifo():
    q = PromptSlotQueue()
    q.push_rows(np.arange(8).reshape(2, 4), np.ones((2, 4), np.int32))
    q.push_rows(np.arange(18).reshape(3, 6), np.ones((3, 6), np.int32))
    assert len(q) == 5
    # fullest width first
    width, ids, msk = q.pop_group(2)
    assert width == 6 and ids.shape == (2, 6)
    np.testing.assert_array_equal(ids[0], np.arange(6))  # FIFO within width
    # widths tie at 1 vs 2 → width-4 group still drains
    width, ids, _ = q.pop_group(10)
    assert width in (4, 6)
    assert len(q) + ids.shape[0] == 3
    while q.pop_group(10) is not None:
        pass
    assert len(q) == 0 and q.pop_group(1) is None


# ------------------------------------------------------- vector cache_index


def _tiny_model(**overrides):
    cfg = LMConfig(
        vocab_size=23, n_layer=2, n_head=2, d_model=32, max_position=64,
        dtype="float32", **overrides,
    )
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (3, 6), 2, cfg.vocab_size)
    ids = ids.at[0, :2].set(0)
    mask = jnp.ones((3, 6), jnp.int32).at[0, :2].set(0)
    params = {"params": model.init(rng, ids, mask)["params"]}
    return model, params, ids, mask


@pytest.mark.parametrize("quant", [False, True])
def test_vector_cache_index_matches_scalar_per_row(quant):
    """One decode step with a [b] vector cache_index at DIFFERENT per-row
    offsets must equal running each row alone through the scalar path — the
    scatter write, position derivation, and per-row causal frontier all have
    to agree."""
    from trlx_tpu.models.lm import init_cache

    model, params, ids, mask = _tiny_model(kv_cache_quant=quant)
    B, P = ids.shape
    T = P + 4
    # Stagger the rows: row b's sequence ends b positions early, so each row
    # appends its next token at a DIFFERENT offset P - b.
    row_mask = np.array(mask)
    for b in range(B):
        row_mask[b, P - b :] = 0
    grid_mask = jnp.asarray(row_mask)
    cache = init_cache(model.cfg, B, T)
    pre = model.apply(
        params, ids, grid_mask, cache=cache, cache_index=0,
        cache_mask=jnp.zeros((B, T), jnp.int32).at[:, :P].set(grid_mask),
    )
    vec = jnp.asarray([P - b for b in range(B)], jnp.int32)
    tok = jnp.asarray([[5], [7], [9]], jnp.int32)
    step_mask = jnp.ones((B, 1), jnp.int32)

    def cache_mask_for(off):
        cm = np.zeros((B, T), np.int32)
        cm[:, :P] = row_mask
        for b in range(B):
            cm[b, int(off[b])] = 1
        return jnp.asarray(cm)

    out_vec = model.apply(
        params, tok, step_mask, cache=pre["cache"], cache_index=vec,
        cache_mask=cache_mask_for(np.asarray(vec)),
    )
    # Scalar reference: run each row on its own with its scalar offset.
    for b in range(B):
        cache_b = init_cache(model.cfg, 1, T)
        pre_b = model.apply(
            params, ids[b : b + 1], grid_mask[b : b + 1], cache=cache_b,
            cache_index=0,
            cache_mask=jnp.zeros((1, T), jnp.int32).at[:, :P].set(grid_mask[b : b + 1]),
        )
        cm = np.zeros((1, T), np.int32)
        cm[0, :P] = row_mask[b]
        cm[0, int(vec[b])] = 1
        out_b = model.apply(
            params, tok[b : b + 1], step_mask[b : b + 1], cache=pre_b["cache"],
            cache_index=int(vec[b]), cache_mask=jnp.asarray(cm),
        )
        np.testing.assert_allclose(
            np.asarray(out_vec["logits"][b]), np.asarray(out_b["logits"][0]),
            rtol=1e-5, atol=1e-5,
        )
    # and the scatter landed where the scalar path would have put it
    leaf_vec = out_vec["cache"][0][0]
    leaf_pre = pre["cache"][0][0]
    for b in range(B):
        w = int(vec[b])
        assert not np.allclose(
            np.asarray(leaf_vec[b, w]), np.asarray(leaf_pre[b, w])
        ), f"row {b}: no KV written at its offset {w}"
        # untouched past the write offset
        np.testing.assert_array_equal(
            np.asarray(leaf_vec[b, w + 1 :]), np.asarray(leaf_pre[b, w + 1 :])
        )


@pytest.mark.parametrize("quant", [False, True])
def test_vector_cache_index_multi_token_window_matches_sequential(quant):
    """Spec-verify substrate: a K-token query with a [b] vector cache_index at
    STAGGERED per-row offsets must equal feeding the same K tokens one at a
    time through the single-token vector path — logits at every window
    position and every KV write bit-for-bit."""
    from trlx_tpu.models.lm import init_cache

    model, params, ids, mask = _tiny_model(kv_cache_quant="int8" if quant else None)
    B, P = ids.shape
    K = 3
    T = P + K + 2
    row_mask = np.array(mask)
    for b in range(B):
        row_mask[b, P - b :] = 0
    grid_mask = jnp.asarray(row_mask)

    def prefilled():
        cache = init_cache(model.cfg, B, T)
        return model.apply(
            params, ids, grid_mask, cache=cache, cache_index=0,
            cache_mask=jnp.zeros((B, T), jnp.int32).at[:, :P].set(grid_mask),
        )["cache"]

    wp = np.array([P - b for b in range(B)], np.int64)
    window = np.array([[5, 7, 9], [9, 5, 7], [7, 9, 5]], np.int32)

    def cm_for(extent):
        cm = np.zeros((B, T), np.int32)
        cm[:, :P] = row_mask
        for b in range(B):
            cm[b, int(wp[b]) : int(wp[b]) + int(extent[b])] = 1
        return jnp.asarray(cm)

    # one K-wide dispatch: cache_mask covers the whole window up front, as the
    # engine's verify program does before it knows the accepted length
    out_w = model.apply(
        params, jnp.asarray(window), jnp.ones((B, K), jnp.int32),
        cache=prefilled(), cache_index=jnp.asarray(wp, jnp.int32),
        cache_mask=cm_for(np.full(B, K)),
    )
    # sequential reference: same tokens one at a time through the proven path
    cache = prefilled()
    seq_logits = []
    for j in range(K):
        out_j = model.apply(
            params, jnp.asarray(window[:, j : j + 1]), jnp.ones((B, 1), jnp.int32),
            cache=cache, cache_index=jnp.asarray(wp + j, jnp.int32),
            cache_mask=cm_for(np.full(B, j + 1)),
        )
        cache = out_j["cache"]
        seq_logits.append(np.asarray(out_j["logits"][:, 0]))

    for j in range(K):
        np.testing.assert_allclose(
            np.asarray(out_w["logits"][:, j]), seq_logits[j], rtol=1e-5, atol=1e-5
        )
    # Layer-1 KVs carry reduction-order noise (3-query vs 1-query einsum), and
    # int8 codes may flip one ulp when a scale wobbles — tolerance, not equal.
    for leaf_w, leaf_s in zip(jax.tree.leaves(out_w["cache"]), jax.tree.leaves(cache)):
        lw, ls = np.asarray(leaf_w), np.asarray(leaf_s)
        if np.issubdtype(lw.dtype, np.integer):
            assert np.abs(lw.astype(np.int32) - ls.astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(lw, ls, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- greedy parity


def _mixed_prompts(vocab=23, seed=3):
    """Unique prompts at two bucket widths, one row left-padded."""
    rng = np.random.default_rng(seed)
    w6 = rng.integers(2, vocab, size=(3, 6)).astype(np.int32)
    m6 = np.ones((3, 6), np.int32)
    w6[0, :2] = 0
    m6[0, :2] = 0
    w4 = rng.integers(2, vocab, size=(3, 4)).astype(np.int32)
    m4 = np.ones((3, 4), np.int32)
    return (w6, m6), (w4, m4)


def _reference_episodes(model, params, gcfg, groups):
    """Whole-batch greedy decode per width group → prompt-keyed episodes."""
    ref = {}
    for ids, msk in groups:
        gen = make_generate_fn(model, gcfg)
        toks, m = gen(params, jnp.asarray(ids), jnp.asarray(msk), jax.random.PRNGKey(1))
        toks, m = np.asarray(toks), np.asarray(m)
        P = ids.shape[1]
        for b in range(ids.shape[0]):
            key = (tuple(ids[b].tolist()), tuple(msk[b].tolist()))
            ref[key] = (toks[b, P:], m[b, P:])
    return ref


@pytest.mark.parametrize("quant", [False, True])
def test_engine_greedy_parity_token_for_token(quant):
    """THE acceptance test: per-slot decode == whole-batch decode, token for
    token and mask bit for mask bit, across mixed bucket widths and natural
    mixed response lengths — with fewer slots than prompts, so refill
    mid-run is exercised, and with exactly ONE compiled decode program."""
    model, params, _, _ = _tiny_model(kv_cache_quant=quant)
    (w6, m6), (w4, m4) = _mixed_prompts()
    # Pick an eos the greedy decode emits at DIFFERENT depths across rows, so
    # response lengths are naturally mixed (per-row first occurrence decides
    # where each row stops once it becomes the eos).
    free = GenerateConfig(max_new_tokens=8, do_sample=False, eos_token_id=None, pad_token_id=0)
    first_at = {}
    for ids, msk in [(w6, m6), (w4, m4)]:
        toks, _ = make_generate_fn(model, free)(
            params, jnp.asarray(ids), jnp.asarray(msk), jax.random.PRNGKey(1)
        )
        for row in np.asarray(toks)[:, ids.shape[1] :]:
            seen = {}
            for step, t in enumerate(row.tolist()):
                seen.setdefault(int(t), step)
            for t, step in seen.items():
                first_at.setdefault(t, set()).add(step)
    eos = max(first_at, key=lambda t: len(first_at[t]))
    assert len(first_at[eos]) >= 2, "tiny model emitted no repeat token — reseed"
    gcfg = GenerateConfig(max_new_tokens=8, do_sample=False, eos_token_id=eos, pad_token_id=0)
    ref = _reference_episodes(model, params, gcfg, [(w6, m6), (w4, m4)])

    engine = RolloutEngine(
        model, gcfg, n_slots=4, prompt_width=6,
        prefill_batch=2, steps_per_sync=3, rng=jax.random.PRNGKey(2),
    )
    engine.update_weights(params, version=7)
    engine.submit(w6, m6)
    engine.submit(w4, m4)
    assert engine.pending == 6

    episodes = []
    for _ in range(200):
        episodes.extend(engine.step())
        if engine.idle:
            break
    assert len(episodes) == 6
    assert engine.num_decode_traces == 1, "decode retraced: slot lengths leaked into shapes"

    for ep in episodes:
        assert isinstance(ep, Episode) and ep.weight_version == 7
        key = (tuple(ep.prompt_ids.tolist()), tuple(ep.prompt_mask.tolist()))
        rtoks, rmask = ref[key]
        np.testing.assert_array_equal(ep.response_ids, rtoks)
        np.testing.assert_array_equal(ep.response_mask, rmask)
        assert ep.decode_steps == int(rmask.sum())

    # mixed lengths actually happened (otherwise this test proves nothing)
    lens = sorted(ep.decode_steps for ep in episodes)
    assert lens[0] < lens[-1]

    stats = engine.stats(reset=False)
    assert 0.0 < stats["engine/slot_occupancy"] <= 1.0
    assert stats["engine/refills"] == 6
    assert stats["engine/completed"] == 6
    assert stats["engine/gen_tokens"] == sum(lens)
    assert stats["engine/decode_tokens_per_s"] > 0
    # stats window resets on read
    engine.stats(reset=True)
    assert engine.stats(reset=False)["engine/completed"] == 0
    engine.shutdown()
    assert engine.idle


def test_engine_straggler_accounting_under_early_exit():
    """Satellite: per-episode decode_steps must SUM to the engine's generated
    tokens, and the chunked-path helper's per-episode view must reconcile
    with its whole-batch step count (max row) — the straggler gap both paths
    report."""
    from trlx_tpu.trainer.base import JaxBaseTrainer

    # chunked helper on an early-exited mask: rows used 2/4/1 of a 6 budget
    mask_h = np.zeros((3, 5 + 6), np.int32)
    mask_h[:, :5] = 1
    mask_h[0, 5:7] = 1
    mask_h[1, 5:9] = 1
    mask_h[2, 5:6] = 1
    ds = JaxBaseTrainer.rollout_decode_stats(mask_h, 5)
    assert ds["episode_steps"].tolist() == [2, 4, 1]
    assert int(ds["episode_steps"].sum()) == ds["gen_tokens"] == 7
    assert ds["decode_steps"] == 4  # whole batch PAID the longest row
    assert ds["decode_step_budget"] == 6

    # engine side: same identity from the slot lengths
    model, params, _, _ = _tiny_model()
    gcfg = GenerateConfig(max_new_tokens=6, do_sample=False, eos_token_id=None, pad_token_id=0)
    engine = RolloutEngine(model, gcfg, n_slots=2, prompt_width=4, prefill_batch=2)
    engine.update_weights(params)
    rng = np.random.default_rng(0)
    engine.submit(rng.integers(2, 23, size=(4, 4)).astype(np.int32), np.ones((4, 4), np.int32))
    eps = []
    while not engine.idle:
        eps.extend(engine.step())
    assert sum(e.decode_steps for e in eps) == engine.stats()["engine/gen_tokens"]
    engine.shutdown()


# ----------------------------------------------- in-flight weight updates


def _drain(engine):
    episodes = []
    for _ in range(200):
        episodes.extend(engine.step())
        if engine.idle:
            break
    return episodes


def test_mid_decode_update_splits_episodes_at_the_sync_boundary():
    """THE in-flight acceptance test: update_weights between sync points —
    slots mid-decode, no drain, no abort — is adopted at the NEXT
    steps_per_sync boundary, and every harvested Episode carries the exact
    per-token split. steps_per_sync=3 and max_new_tokens=6 with no eos pin
    the arithmetic: one step() generates exactly 3 tokens, so a push after
    the first step must split every episode [(v1, 3), (v2, 3)]. Pushing the
    SAME params under a new version number also proves the swap itself is
    token-neutral: the decode output is unchanged vs an uninterrupted run."""
    model, params, _, _ = _tiny_model()
    gcfg = GenerateConfig(max_new_tokens=6, do_sample=False, eos_token_id=None, pad_token_id=0)
    prompts = np.random.default_rng(5).integers(2, 23, size=(2, 4)).astype(np.int32)
    pmask = np.ones((2, 4), np.int32)

    ref_engine = RolloutEngine(
        model, gcfg, n_slots=2, prompt_width=4, prefill_batch=2,
        steps_per_sync=3, rng=jax.random.PRNGKey(2),
    )
    ref_engine.update_weights(params, version=1)
    ref_engine.submit(prompts, pmask)
    ref = {tuple(e.prompt_ids.tolist()): e for e in _drain(ref_engine)}
    ref_engine.shutdown()

    engine = RolloutEngine(
        model, gcfg, n_slots=2, prompt_width=4, prefill_batch=2,
        steps_per_sync=3, rng=jax.random.PRNGKey(2),
    )
    engine.update_weights(params, version=1)
    engine.submit(prompts, pmask)
    eps = engine.step()
    assert eps == []  # 3 of 6 tokens decoded: nothing finished yet
    # slots are mid-decode RIGHT NOW — push without draining or aborting
    engine.update_weights(params, version=2)
    states = engine.slot_states()
    assert [s["n_gen"] for s in states] == [3, 3]  # positions from the sync
    episodes = _drain(engine)
    assert len(episodes) == 2
    for ep in episodes:
        assert ep.version_spans == [(1, 3), (2, 3)]
        assert ep.weight_version == 2  # tagged with the LAST version
        assert ep.decode_steps == 6
        r = ref[tuple(ep.prompt_ids.tolist())]
        np.testing.assert_array_equal(ep.response_ids, r.response_ids)
        np.testing.assert_array_equal(ep.response_mask, r.response_mask)
    stats = engine.stats(reset=False)
    assert stats["engine/weight_switches"] == 1
    assert stats["engine/switches_coalesced"] == 0
    engine.shutdown()


def test_push_storm_coalesces_to_latest_and_same_version_is_a_noop():
    """version_switch_storm contract: N pushes between two sync points adopt
    ONCE, at the latest version — the queue never forms. And re-pushing the
    version the engine already holds records no switch at all (the
    phase-boundary handoff path stays span-free and byte-identical)."""
    model, params, _, _ = _tiny_model()
    gcfg = GenerateConfig(max_new_tokens=6, do_sample=False, eos_token_id=None, pad_token_id=0)
    engine = RolloutEngine(
        model, gcfg, n_slots=2, prompt_width=4, prefill_batch=2,
        steps_per_sync=3, rng=jax.random.PRNGKey(2),
    )
    engine.update_weights(params, version=1)
    engine.submit(np.full((2, 4), 3, np.int32), np.ones((2, 4), np.int32))
    engine.step()
    # the storm: three pushes before the next sync boundary
    engine.update_weights(params, version=2)
    engine.update_weights(params, version=3)
    engine.update_weights(params, version=4)
    episodes = _drain(engine)
    assert all(ep.version_spans == [(1, 3), (4, 3)] for ep in episodes)
    stats = engine.stats(reset=False)
    assert stats["engine/weight_switches"] == 1  # one adoption, not three
    assert stats["engine/switches_coalesced"] == 2  # v2 and v3 never ran

    # same-version re-push mid-decode: staged, adopted, but NO switch
    engine.submit(np.full((2, 4), 5, np.int32), np.ones((2, 4), np.int32))
    engine.step()
    engine.update_weights(params, version=4)
    episodes = _drain(engine)
    assert all(ep.version_spans == [(4, 6)] for ep in episodes)
    assert engine.stats(reset=False)["engine/weight_switches"] == 1
    engine.shutdown()


def test_schedule_fingerprint_is_deterministic_and_order_sensitive():
    """The slot-schedule crc: identical configs + identical submissions make
    identical fingerprints (the multi-host lockstep invariant
    verify_engine_schedule checks by allgather), and a reordered admission
    stream makes a DIFFERENT one (so a desynced host cannot collide)."""
    model, params, _, _ = _tiny_model()
    (w6, m6), (w4, m4) = _mixed_prompts()
    gcfg = GenerateConfig(max_new_tokens=4, do_sample=False, eos_token_id=None, pad_token_id=0)

    def run(order):
        engine = RolloutEngine(
            model, gcfg, n_slots=2, prompt_width=6, prefill_batch=2,
            steps_per_sync=2, rng=jax.random.PRNGKey(2),
        )
        engine.update_weights(params, version=1)
        for ids, msk in order:
            engine.submit(ids, msk)
        _drain(engine)
        crc = engine.schedule_fingerprint()
        engine.shutdown()
        return crc

    a = run([(w6, m6), (w4, m4)])
    b = run([(w6, m6), (w4, m4)])
    c = run([(w4, m4), (w6, m6)])
    assert a == b
    assert a != c
    assert 0 <= a <= 0xFFFFFFFF


@pytest.mark.parametrize("kv_quant", [False, True])
def test_engine_int8_decode_parity(kv_quant):
    """Satellite: the engine decodes with the int8 weight copies (the qw
    collection riding in the update_weights variables) token-for-token
    identically to whole-batch make_generate_fn decode with the SAME
    variables — the engine adds no numeric skew on top of W8A16 itself."""
    from trlx_tpu.models.lm import quantize_weights

    model, params, _, _ = _tiny_model(kv_cache_quant=kv_quant)
    variables = {"params": params["params"], "qw": quantize_weights(params["params"])}
    (w6, m6), (w4, m4) = _mixed_prompts()
    gcfg = GenerateConfig(max_new_tokens=6, do_sample=False, eos_token_id=None, pad_token_id=0)
    ref = _reference_episodes(model, variables, gcfg, [(w6, m6), (w4, m4)])

    engine = RolloutEngine(
        model, gcfg, n_slots=3, prompt_width=6, prefill_batch=3,
        steps_per_sync=2, rng=jax.random.PRNGKey(2),
    )
    engine.update_weights(variables, version=1)
    engine.submit(w6, m6)
    engine.submit(w4, m4)
    episodes = _drain(engine)
    assert len(episodes) == 6
    for ep in episodes:
        key = (tuple(ep.prompt_ids.tolist()), tuple(ep.prompt_mask.tolist()))
        rtoks, rmask = ref[key]
        np.testing.assert_array_equal(ep.response_ids, rtoks)
        np.testing.assert_array_equal(ep.response_mask, rmask)
    engine.shutdown()


def test_engine_requires_weight_handoff_and_bounds_prompt_width():
    model, params, _, _ = _tiny_model()
    gcfg = GenerateConfig(max_new_tokens=4, do_sample=False, pad_token_id=0)
    engine = RolloutEngine(model, gcfg, n_slots=2, prompt_width=4)
    with pytest.raises(RuntimeError, match="update_weights"):
        engine.step()
    with pytest.raises(ValueError, match="prompt width"):
        engine.submit(np.ones((1, 9), np.int32), np.ones((1, 9), np.int32))
    engine.update_weights(params)
    assert engine.step() == []  # empty queue: a no-op, not an error
    engine.shutdown()


def test_sanitizer_catches_unlocked_engine_dispatch(monkeypatch):
    """TRLX_TPU_SANITIZE=dispatch acceptance: an intentionally unlocked
    decode dispatch from a trlx-* worker thread raises DispatchLockViolation
    naming the program, while the engine's own locked dispatches still run."""
    from trlx_tpu.utils import sanitize

    monkeypatch.setenv(sanitize.ENV_VAR, "dispatch")
    try:
        lock = sanitize.make_dispatch_lock()
        assert isinstance(lock, sanitize.SanitizedDispatchLock)
        model, params, _, _ = _tiny_model()
        gcfg = GenerateConfig(max_new_tokens=3, do_sample=False, pad_token_id=0)
        engine = RolloutEngine(
            model, gcfg, n_slots=2, prompt_width=4, dispatch_lock=lock
        )
        engine.update_weights(params)
        engine.submit(np.ones((1, 4), np.int32), np.ones((1, 4), np.int32))
        assert engine.step() is not None  # locked path works under the sanitizer

        errors = []

        def rogue():
            try:
                # the PR 5 bug, replayed on purpose: dispatch without the lock
                engine._decode(engine._variables, engine._state)
            except sanitize.DispatchLockViolation as e:
                errors.append(e)

        t = threading.Thread(target=rogue, name="trlx-rogue-dispatcher")
        t.start()
        t.join()
        assert len(errors) == 1 and "engine/decode" in str(errors[0])
        engine.shutdown()
    finally:
        monkeypatch.delenv(sanitize.ENV_VAR, raising=False)
        sanitize.refresh()


def test_sanitizer_catches_donated_weight_handoff(monkeypatch):
    """TRLX_TPU_SANITIZE=donation acceptance: handing the engine a tree that
    was donated to a jitted program fails at update_weights with the donation
    site, instead of a deleted-array error mid-decode."""
    from trlx_tpu.utils import sanitize

    monkeypatch.setenv(sanitize.ENV_VAR, "donation")
    try:
        sanitize.refresh()
        model, params, _, _ = _tiny_model()
        gcfg = GenerateConfig(max_new_tokens=3, do_sample=False, pad_token_id=0)
        engine = RolloutEngine(model, gcfg, n_slots=2, prompt_width=4)
        sanitize.mark_donated(params, "train_step(state) [drill]")
        with pytest.raises(sanitize.DonatedBufferRead, match="train_step"):
            engine.update_weights(params)
        engine.shutdown()
    finally:
        monkeypatch.delenv(sanitize.ENV_VAR, raising=False)
        sanitize.refresh()
        sanitize.clear_donated()


# ------------------------------------------------------------ e2e acceptance


@pytest.fixture(scope="module")
def task():
    return generate_random_walks(n_nodes=15, max_length=8, n_walks=60, seed=1000)


def _run_ppo(task, ckpt_dir, **method_overrides):
    _, logit_mask, metric_fn, reward_fn = task
    config = base_config("ppo", 15, 8)
    config.train.total_steps = 8
    config.train.epochs = 4
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.checkpoint_dir = str(ckpt_dir)
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    for k, v in method_overrides.items():
        setattr(config.method, k, v)
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[1]],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    with open(os.path.join(str(ckpt_dir), "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    return model, records


def test_ppo_with_rollout_engine_trains_and_tears_down(task, tmp_path, monkeypatch):
    # Fully-armed sanitizer: the engine e2e doubles as the dispatch-lock,
    # donation, AND race (lockset) acceptance run — the engine migrates
    # between the producer thread (per-phase) and the main thread (teardown),
    # so every update_weights/shutdown handoff must keep the tracker clean.
    from trlx_tpu.utils import sanitize

    monkeypatch.setenv(sanitize.ENV_VAR, "dispatch,donation,race")
    try:
        model, records = _run_ppo(
            task, tmp_path / "eng", rollout_engine=True, engine_slots=8,
            prefill_batch=4, engine_steps_per_sync=4,
        )
    finally:
        monkeypatch.delenv(sanitize.ENV_VAR, raising=False)
        sanitize.refresh()
        sanitize.clear_donated()
        sanitize.clear_races()
    losses = [r["loss"] for r in records if "loss" in r]
    assert len(losses) == 8 and all(np.isfinite(losses))
    # engine gauges flowed to the tracker
    occ = [r["engine/slot_occupancy"] for r in records if "engine/slot_occupancy" in r]
    assert occ and all(0.0 < o <= 1.0 for o in occ)
    assert any("engine/refills" in r for r in records)
    assert any("exp_decode_steps_per_episode" in r for r in records)
    # learn()'s finally tore the engine down; no threads leaked
    assert model._rollout_engine is None
    assert not any(t.name.startswith("trlx-") for t in threading.enumerate())


def test_rollout_engine_config_validation(task):
    """The engine+decode_weight_quant guard is LIFTED (the unfused scoring
    delta is bounded by test_engine_int8_decode_parity): construction
    succeeds and both the engine and the int8 copies are armed. Without the
    engine, int8 decode still demands the fused-stats path."""
    from trlx_tpu.trainer.ppo import PPOTrainer

    _, logit_mask, _, _ = task
    config = base_config("ppo", 15, 8)
    config.train.batch_size = 16
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    config.method.rollout_engine = True
    config.model.decode_weight_quant = True
    trainer = PPOTrainer(config, logit_mask=logit_mask)
    assert trainer.rollout_engine_enabled and trainer._qw is not None
    # the engine's versioned handoff payload carries the int8 copies too
    assert "qw" in trainer.rollout_engine_variables()
    trainer._shutdown_experience_pipeline()

    config = base_config("ppo", 15, 8)
    config.train.batch_size = 16
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    config.model.decode_weight_quant = True
    config.method.fused_rollout_stats = False  # no fused path, no engine
    with pytest.raises(ValueError, match="decode_weight_quant"):
        PPOTrainer(config, logit_mask=logit_mask)


# ---------------------------------------------------------------- fault drill


def test_reward_hang_through_engine_path_drains_cleanly(task, tmp_path, monkeypatch):
    """TRLX_TPU_FAULTS=reward_hang against _make_experience_engine: the hang
    watchdog fires, the error surfaces from make_experience, and nothing
    leaks — then with retries restored the SAME injected hang is absorbed
    and the store fills completely (mirror of the PR 5 drill)."""
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo import PPOTrainer

    monkeypatch.setenv("TRLX_TPU_FAULTS", "reward_hang@1")
    _, logit_mask, metric_fn, reward_fn = task
    config = base_config("ppo", 15, 8)
    config.train.checkpoint_dir = str(tmp_path / "ck")
    config.train.batch_size = 16
    config.train.reward_fn_timeout = 0.2
    config.train.reward_fn_retries = 0
    config.train.reward_fn_backoff = 0.0
    config.method.num_rollouts = 32
    config.method.chunk_size = 16
    config.method.rollout_engine = True
    config.method.engine_slots = 8
    trainer = PPOTrainer(config, reward_fn=reward_fn, metric_fn=metric_fn, logit_mask=logit_mask)
    assert trainer.rollout_engine_enabled

    pipeline = PromptPipeline([[1]] * 32, tokenizer=None, max_prompt_length=1)
    orch = PPOOrchestrator(trainer, pipeline, reward_fn, chunk_size=16)
    with pytest.raises(TimeoutError, match="still running"):
        orch.make_experience(num_rollouts=32)
    assert not any(t.name.startswith("trlx-") for t in threading.enumerate())

    # with retries restored the SAME injected hang is absorbed
    monkeypatch.setenv("TRLX_TPU_FAULTS", "reward_hang@3")
    from trlx_tpu.resilience import FaultPlan

    trainer.fault_plan = FaultPlan.from_env_or_config("")
    trainer.config.train.reward_fn_retries = 2
    store = PPORolloutStorage(pad_token_id=trainer.pad_token_id, record_staleness=True)
    orch.make_experience(num_rollouts=32, store=store, staleness=1)
    assert len(store) == 32
    assert all(f.fired for f in trainer.fault_plan.faults)
    g = store._buffer.gather(np.arange(32))
    assert np.all(g["staleness"] == 1.0)
    # the engine drained: nothing queued, nothing live, ready for next phase
    assert trainer.rollout_engine().idle
    trainer._shutdown_experience_pipeline()
    assert trainer._rollout_engine is None


def test_slow_step_with_engine_completes_and_captures(task, tmp_path, monkeypatch):
    """TRLX_TPU_FAULTS=slow_step through a full engine-path run: the anomaly
    detector's CPU drill must not interact badly with the engine (the stall
    sits between train dispatch and the log sync) — the run completes and
    shutdown is clean."""
    monkeypatch.setenv("TRLX_TPU_FAULTS", "slow_step@4")
    monkeypatch.setenv("TRLX_TPU_SLOW_STEP_SECONDS", "0.2")
    model, records = _run_ppo(task, tmp_path / "slow", rollout_engine=True, engine_slots=8)
    losses = [r["loss"] for r in records if "loss" in r]
    assert len(losses) == 8
    assert model._rollout_engine is None
    assert not any(t.name.startswith("trlx-") for t in threading.enumerate())


# --------------------------------------------------------- paged KV cache


def _paged_prompts():
    """7 width-6 rows, two of which duplicate row 0 exactly (ids AND mask)
    — the prefix-cache hit candidates at kv_block_size=4."""
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, 23, size=(7, 6)).astype(np.int32)
    pmask = np.ones((7, 6), np.int32)
    prompts[1, :2] = 0
    pmask[1, :2] = 0
    prompts[5] = prompts[0]
    prompts[6] = prompts[0]
    return prompts, pmask


def _paged_pair(quant, *, spec="", paged_kwargs=None):
    """A (fixed, paged) engine pair over the same tiny model/weights."""
    cfg = LMConfig(
        vocab_size=23, n_layer=2, n_head=2, d_model=32, max_position=96,
        dtype="float32", kv_cache_quant=quant,
    )
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (3, 6), 2, cfg.vocab_size)
    params = {"params": model.init(rng, ids, jnp.ones((3, 6), jnp.int32))["params"]}
    gcfg = GenerateConfig(
        max_new_tokens=7, do_sample=False, pad_token_id=0, eos_token_id=1
    )
    kw = dict(n_slots=3, prompt_width=6, prefill_batch=2, steps_per_sync=3)
    if spec:
        kw.update(spec_decode=spec, spec_k=3)
    # fresh rng arrays per engine: decode donates the slot state, and the
    # key rides in it — a shared array would be deleted under the 2nd engine
    fixed = RolloutEngine(model, gcfg, **kw, rng=jax.random.PRNGKey(2))
    paged = RolloutEngine(
        model, gcfg, **kw, rng=jax.random.PRNGKey(2), paged_kv=True,
        **(paged_kwargs or {"kv_block_size": 4}),
    )
    for e in (fixed, paged):
        e.update_weights(params, version=1)
    return fixed, paged


@pytest.mark.parametrize("quant", [False, True])
def test_paged_engine_token_parity_with_prefix_hits(quant):
    """The tentpole acceptance: the paged engine with prefix caching ON is
    token-for-token identical to the fixed-slot engine on a mixed workload
    with duplicate prompts, int8 KV on and off — and actually HITS (the dup
    rows skip their shared prefix's prefill), with a clean pool at the end."""
    prompts, pmask = _paged_prompts()
    fixed, paged = _paged_pair(quant)
    for e in (fixed, paged):
        e.submit(prompts, pmask)
    ref = {tuple(x.prompt_ids.tolist()): x for x in _drain(fixed)}
    got = {tuple(x.prompt_ids.tolist()): x for x in _drain(paged)}
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_array_equal(ref[k].response_ids, got[k].response_ids)
        np.testing.assert_array_equal(ref[k].response_mask, got[k].response_mask)
    assert paged.num_decode_traces == 1, "paged decode retraced"
    st = paged.stats()
    assert st["engine/prefix_hits_total"] >= 1
    assert st["engine/prefill_tokens_saved_total"] >= 4
    assert 0.0 <= st["engine/pool_frag_frac"] <= 1.0
    paged.pool.leak_audit(expect_idle=True)
    fixed.shutdown()
    paged.shutdown()


def test_paged_engine_spec_decode_parity():
    """Satellite: paged_kv composes with spec_decode — the verify windows
    write through the block table (scratch tail in the slot's last block)
    and greedy output stays token-for-token equal to the non-paged spec
    engine."""
    prompts, pmask = _paged_prompts()
    fixed, paged = _paged_pair(False, spec="ngram")
    for e in (fixed, paged):
        e.submit(prompts, pmask)
    ref = {tuple(x.prompt_ids.tolist()): x for x in _drain(fixed)}
    got = {tuple(x.prompt_ids.tolist()): x for x in _drain(paged)}
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_array_equal(ref[k].response_ids, got[k].response_ids)
    assert paged.num_verify_traces == 1, "paged verify retraced"
    # the scratch tail rounded INTO the last block: kv_len covers cache_len
    assert paged.kv_len >= paged.cache_len
    assert paged.stats()["engine/prefix_hits_total"] >= 1
    paged.pool.leak_audit(expect_idle=True)
    fixed.shutdown()
    paged.shutdown()


def test_paged_engine_undersized_pool_requeues_and_drains():
    """A pool too small for all slots at once (2 spans for 3 slots) must
    requeue pool-bound admissions and still drain the whole workload —
    transactional admission, no deadlock, no leak."""
    prompts, pmask = _paged_prompts()
    _, paged = _paged_pair(
        False, paged_kwargs={"kv_block_size": 4, "kv_pool_blocks": 1 + 2 * 4}
    )
    paged.submit(prompts, pmask)
    eps = _drain(paged)
    assert len(eps) == 7
    paged.pool.leak_audit(expect_idle=True)
    paged.shutdown()


def test_paged_engine_abort_releases_all_blocks():
    """Satellite: abort() mid-decode releases every pinned/private block
    (leak_audit inside abort raises otherwise) and repoints the device
    tables at the trash block."""
    prompts, pmask = _paged_prompts()
    _, paged = _paged_pair(False)
    paged.submit(prompts, pmask)
    paged.step()  # slots mid-decode: blocks pinned and referenced
    assert paged.pool.used_blocks() > 0
    paged.abort()
    assert paged.pool.used_blocks() == 0
    assert not np.asarray(jax.device_get(paged._state["block_tables"])).any()
    paged.shutdown()


def test_paged_kv_off_leaves_engine_byte_identical():
    """The default-off contract: an engine with paged_kv=False is the SAME
    engine as one built before the paged knobs existed — no block tables in
    the slot state, no pool, kv_len == cache_len, and a bit-identical
    decode jaxpr (the gather-indirection must vanish at trace time, not
    just at runtime)."""
    cfg = LMConfig(
        vocab_size=23, n_layer=2, n_head=2, d_model=32, max_position=96,
        dtype="float32",
    )
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (3, 6), 2, cfg.vocab_size)
    params = {"params": model.init(rng, ids, jnp.ones((3, 6), jnp.int32))["params"]}
    gcfg = GenerateConfig(max_new_tokens=7, do_sample=False, pad_token_id=0, eos_token_id=1)
    kw = dict(n_slots=3, prompt_width=6, prefill_batch=2, steps_per_sync=3)
    default = RolloutEngine(model, gcfg, **kw, rng=jax.random.PRNGKey(2))
    off = RolloutEngine(model, gcfg, **kw, rng=jax.random.PRNGKey(2),
                        paged_kv=False, kv_block_size=4,
                        kv_pool_blocks=99)  # knobs present but off
    assert off.pool is None and off.kv_len == off.cache_len
    for e in (default, off):
        e.update_weights(params, version=1)
        e._adopt_staged()  # weights are staged until the next step() top
        e._ensure_state()
    assert "block_tables" not in off._state
    assert jax.tree_util.tree_structure(default._state) == jax.tree_util.tree_structure(off._state)
    j_default = jax.make_jaxpr(default._decode_fn)(default._variables, default._state)
    j_off = jax.make_jaxpr(off._decode_fn)(off._variables, off._state)
    # identical programs modulo the memory addresses of callables embedded
    # in eqn params (two engine instances -> two bound-method objects)
    import re

    strip = lambda s: re.sub(r"0x[0-9a-f]+", "0x", str(s))  # noqa: E731
    assert strip(j_default) == strip(j_off)
    default.shutdown()
    off.shutdown()

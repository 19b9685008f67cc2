"""CPU smoke of the decode hot path: minutes, no TPU, CI-safe.

Probes covering exactly what BENCH_r05 showed CPU CI was blind to:

1. rollout — a tiny bucketed rollout: PromptPipeline with bucket widths
   feeding make_generate_fn, asserting the compiled-program count stays
   <= n_buckets (the trace-count hook) and the decode metrics helper returns
   sane numbers.

2. overlap — a tiny bucketed PPO run with the rollout/train pipeline on
   (method.max_staleness=1): the phase windows in metrics.jsonl must carry
   time/overlap_fraction, the stored samples must carry the staleness
   column, and the producer/score-worker threads must be joined by the time
   train() returns.

3. fused_loss — the streaming logprob head: static tile legality at the
   FULL bench head shape (N=6656, d=4096, V=50400), interpret-mode parity
   vs the materialized log_softmax chain at the flagship head/vocab layout
   (d=4096, V=50400, N scaled down), gradient parity at a reduced width,
   and a tiny PPO train run with method.pack_train_batch=true whose
   metrics must carry train_tokens_per_s / train_batch_fill.

4. decode_engine — the continuous-batching rollout engine (trlx_tpu/engine)
   on a mixed-response-length CPU workload where every static chunk carries
   one full-budget straggler: slot decode must match the whole-batch decode
   token for token, keep slot occupancy > 85%, and deliver HIGHER decode
   tokens/s than the static-batch path (the straggler steps the slot refill
   reclaims). Both rates land in BENCH_SMOKE.json.

5. paged_kv — the paged KV cache + prefix caching path (trlx_tpu/engine,
   RUNBOOK §20) on a mixed-length workload whose prompts all open with the
   same 64-token template: the paged engine must match the fixed-slot
   engine token for token (int8 KV on and off), run >= 1.5x the slot count
   in the SAME cache bytes (pool blocks x block size <= fixed slots x
   cache_len), and skip the template's prefill on every admission after
   the first (prefix hits + tokens-saved land in BENCH_SMOKE.json).

6. fleet_elastic — elastic N-worker fleet transport throughput
   (trlx_tpu/fleet, RUNBOOK §18): threaded workers with a fixed synthetic
   produce cost drive the real lease ledger + per-worker stream indexes +
   exactly-once intake at 1 worker then 2. Intake must stay exactly-once
   (every unit chosen once, zero duplicates, no reclaims) and the 2-worker
   run must beat the 1-worker rate by > 1.3x — the claim/append/consume
   transports must overlap workers, not serialize them. Episodes/s for
   both fleet sizes land in BENCH_SMOKE.json.

Writes BENCH_SMOKE.json and prints one JSON summary line; exits 1 on any
failure. Wall time ~1-2 min on a laptop CPU.
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "BENCH_SMOKE.json")


def rollout_probe():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models import LMConfig, LMWithValueHead
    from trlx_tpu.ops.generate import make_generate_fn
    from trlx_tpu.ops.sampling import GenerateConfig
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
    from trlx_tpu.trainer.base import JaxBaseTrainer

    cfg = LMConfig(vocab_size=29, n_layer=1, n_head=2, d_model=16, max_position=32, dtype="float32")
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    ids0 = jnp.ones((2, 4), jnp.int32)
    params = {"params": model.init(rng, ids0, jnp.ones_like(ids0))["params"]}
    gcfg = GenerateConfig(max_new_tokens=4, do_sample=False, eos_token_id=None, pad_token_id=0)
    gen = make_generate_fn(model, gcfg)

    prng = np.random.default_rng(1)
    prompts = [list(prng.integers(2, 28, size=n)) for n in (2, 3, 5, 7, 8, 4, 6, 3)]
    pipe = PromptPipeline(prompts, tokenizer=None, max_prompt_length=8, bucket_widths=(4, 8))
    loader = pipe.create_loader(batch_size=2, shuffle=True, drop_last=False, seed=2)

    gen_tokens = 0
    t0 = time.time()
    for i, batch in enumerate(loader):
        toks, mask = gen(
            params,
            jnp.asarray(batch["input_ids"]),
            jnp.asarray(batch["attention_mask"]),
            jax.random.PRNGKey(i),
        )
        P = batch["input_ids"].shape[1]
        stats = JaxBaseTrainer.rollout_decode_stats(np.asarray(mask), P)
        assert 0 < stats["decode_steps"] <= stats["decode_step_budget"]
        gen_tokens += stats["gen_tokens"]
    gen_s = time.time() - t0

    n_buckets = len(pipe.bucket_widths)
    assert gen.num_traces <= n_buckets, (
        f"bucketing leak: {gen.num_traces} generate traces for {n_buckets} "
        f"buckets (shapes: {gen.traced_shapes})"
    )
    return {
        "buckets": list(pipe.bucket_widths),
        "generate_traces": gen.num_traces,
        "gen_tokens": gen_tokens,
        "tokens_per_s": round(gen_tokens / max(gen_s, 1e-9), 1),
        "seconds": round(gen_s, 2),
    }


def overlap_probe():
    import tempfile
    import threading

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import trlx_tpu
    from randomwalks import base_config, generate_random_walks

    _, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=15, max_length=8, n_walks=60, seed=1000
    )
    config = base_config("ppo", 15, 8)
    config.train.total_steps = 16
    config.train.epochs = 8
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.method.num_rollouts = 32
    config.method.chunk_size = 16
    config.method.max_staleness = 1
    config.method.gen_kwargs["prompt_buckets"] = [1]
    d = tempfile.mkdtemp(prefix="overlap_smoke_")
    config.train.checkpoint_dir = d
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    t0 = time.time()
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[1]],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    wall_s = time.time() - t0

    with open(os.path.join(d, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    fractions = [r["time/overlap_fraction"] for r in records if "time/overlap_fraction" in r]
    assert fractions, "no phase windows reached metrics.jsonl"
    stale = [r["staleness/mean"] for r in records if "staleness/mean" in r]
    assert stale and stale[-1] == 1.0, f"staleness stats missing/wrong: {stale}"
    # the producer joined cleanly: no pipeline thread outlives train()
    leaked = [t.name for t in threading.enumerate() if t.name.startswith("trlx-")]
    assert not leaked, f"pipeline threads leaked: {leaked}"
    assert model._rollout_producer is None
    return {
        "steps": model.iter_count,
        "overlap_fraction_max": round(max(fractions), 3),
        "windows": len(fractions),
        "staleness_last": stale[-1],
        "seconds": round(wall_s, 2),
    }


def fused_loss_probe():
    import tempfile

    import numpy as np
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.fused_logprob import fused_logprob, naive_logprob
    from trlx_tpu.ops.tiling import check_layout, fused_logprob_block_layout

    # Static legality at the REAL bench head shape: 8 rows x T=832 states
    # flattened (N=6656), GPT-J head d=4096 over the ragged 50400 vocab.
    N, D, V = 8 * 832, 4096, 50400
    for tied, bias in ((True, False), (False, False), (False, True)):
        check_layout(fused_logprob_block_layout(N, D, V, 128, 512, tied, bias))

    # Interpret-mode parity at the flagship head/vocab layout, N scaled
    # down (one 128-row block; the 99-tile vocab stream incl. the masked
    # 224-wide tail is the coverage that matters).
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, D)), jnp.float32) * 0.2
    w = jnp.asarray(rng.normal(size=(D, V)), jnp.float32) * 0.05
    y = jnp.asarray(rng.integers(0, V, size=(2, 8)), jnp.int32)
    t0 = time.time()
    lp, lse, ent = jax.jit(
        lambda x, w: fused_logprob(x, w, y, tied=False, interpret=True)
    )(x, w)
    kernel_s = time.time() - t0
    lp_n, lse_n, ent_n = naive_logprob(x, w, y, tied=False)
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in ((lp, lp_n), (lse, lse_n), (ent, ent_n))
    )
    assert err < 1e-4, f"fused-logprob parity failed: maxerr={err}"

    # Gradient parity through the custom VJP at a reduced width (full-D
    # backward in interpret mode is minutes of CPU for no extra coverage).
    Dg, Vg = 256, 1000
    xg = jnp.asarray(rng.normal(size=(2, 8, Dg)), jnp.float32) * 0.2
    wg = jnp.asarray(rng.normal(size=(Dg, Vg)), jnp.float32) * 0.1
    yg = jnp.asarray(rng.integers(0, Vg, size=(2, 8)), jnp.int32)

    def scal(fn):
        return lambda x, w: sum(
            jnp.sum(o) for o in fn(x, w, yg, tied=False)
        )

    gk = jax.grad(scal(lambda *a, **k: fused_logprob(*a, interpret=True, **k)), argnums=(0, 1))(xg, wg)
    gn = jax.grad(scal(naive_logprob), argnums=(0, 1))(xg, wg)
    gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(gk, gn))
    assert gerr < 1e-4, f"fused-logprob grad parity failed: maxerr={gerr}"

    # Tiny packed PPO train step end-to-end (pack_train_batch routes the
    # loader through pack_ppo_batch and the segment-aware loss).
    sys.path.insert(0, os.path.join(REPO, "examples"))
    import trlx_tpu
    from randomwalks import base_config, generate_random_walks

    _, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=15, max_length=8, n_walks=60, seed=1000
    )
    config = base_config("ppo", 15, 8)
    # must cross at least one rollout boundary: phase windows (and the
    # train_tokens_per_s / fill stats) flush there
    config.train.total_steps = 8
    config.train.epochs = 4
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    config.method.pack_train_batch = True
    d = tempfile.mkdtemp(prefix="packed_smoke_")
    config.train.checkpoint_dir = d
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    t0 = time.time()
    model = trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
        metric_fn=metric_fn, config=config, logit_mask=logit_mask,
    )
    packed_s = time.time() - t0
    assert model.iter_count >= 8
    with open(os.path.join(d, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    toks = [r["train_tokens_per_s"] for r in records if "train_tokens_per_s" in r]
    fill = [r["train_batch_fill"] for r in records if "train_batch_fill" in r]
    assert toks and toks[-1] > 0, f"train_tokens_per_s missing: {toks}"
    assert fill and 0 < fill[-1] <= 1, f"train_batch_fill missing/bad: {fill}"
    return {
        "head_shape": [N, D, V],
        "maxerr": err,
        "grad_maxerr": gerr,
        "kernel_seconds": round(kernel_s, 2),
        "packed_steps": model.iter_count,
        "packed_fill": round(fill[-1], 3),
        "tokens_per_s": round(toks[-1], 1),
        "packed_seconds": round(packed_s, 2),
    }


def decode_engine_probe():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel import mesh as mesh_mod

    # The earlier probes (overlap/fused-loss train runs) leave the
    # process-global mesh installed; the engine pins its decode state to
    # that mesh, which would shard 8 slots one-per-fake-device and turn
    # every decode step into cross-device traffic. This probe measures the
    # single-host engine, so it runs mesh-free and restores the global.
    prev_mesh = mesh_mod.peek_mesh()
    mesh_mod.set_mesh(None)
    try:
        return _decode_engine_probe_meshless()
    finally:
        mesh_mod.set_mesh(prev_mesh)


def _decode_engine_probe_meshless():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from trlx_tpu.engine import RolloutEngine
    from trlx_tpu.models import LMConfig, LMWithValueHead
    from trlx_tpu.ops.generate import make_generate_fn
    from trlx_tpu.ops.sampling import (
        GenerateConfig,
        make_bigram_mask_processor,
        process_logits_default,
    )

    # Forced-chain decode (the bigram-mask trick from tests/test_generate):
    # greedy can only emit (last_token + 1) % V, so a prompt ending at token
    # t runs for EXACTLY eos - t steps — response lengths are engineered,
    # not sampled, and both paths must agree token for token.
    V, R, W = 64, 16, 4
    eos, pad = V - 1, 0
    cfg = LMConfig(vocab_size=V, n_layer=4, n_head=2, d_model=256, max_position=64, dtype="float32")
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    params = {"params": model.init(rng, jnp.ones((2, W), jnp.int32), jnp.ones((2, W), jnp.int32))["params"]}
    gcfg = GenerateConfig(max_new_tokens=R, do_sample=False, eos_token_id=eos, pad_token_id=pad)
    forbidden = np.ones((V, V), dtype=bool)
    for i in range(V):
        forbidden[i, (i + 1) % V] = False
    bigram = make_bigram_mask_processor(jnp.asarray(forbidden))

    def proc(logits, state):
        return process_logits_default(bigram(logits, state), gcfg, state["step"])

    # 5 chunks of 8: each chunk = 1 straggler (full 16-step budget) + 7
    # short rows (5 steps) — the static while_loop pays 16 steps per chunk,
    # the engine refills the short rows' slots and pays ~mean steps.
    prng = np.random.default_rng(2)
    chunks = []
    for c in range(5):
        ids = prng.integers(1, 40, size=(8, W)).astype(np.int32)
        ids[0, -1] = eos - R  # straggler: 16 steps
        ids[1:, -1] = eos - 5  # short: 5 steps
        chunks.append((ids, np.ones((8, W), np.int32)))
    total_tokens = 5 * (R + 7 * 5)

    # Static-batch reference: whole-batch decode per chunk (warm chunk 0
    # first so both paths time EXECUTION, not compilation).
    gen = make_generate_fn(model, gcfg, processor=proc)
    ref = {}
    gen(params, jnp.asarray(chunks[0][0]), jnp.asarray(chunks[0][1]), jax.random.PRNGKey(1))
    t0 = time.time()
    for i, (ids, msk) in enumerate(chunks):
        toks, m = gen(params, jnp.asarray(ids), jnp.asarray(msk), jax.random.PRNGKey(i))
        toks, m = np.asarray(toks), np.asarray(m)
        for b in range(ids.shape[0]):
            ref[tuple(ids[b].tolist())] = (toks[b, W:], m[b, W:])
    static_s = time.time() - t0
    static_rate = total_tokens / max(static_s, 1e-9)

    engine = RolloutEngine(
        model, gcfg, n_slots=8, prompt_width=W, processor=proc,
        prefill_batch=1, steps_per_sync=1, rng=jax.random.PRNGKey(3),
    )
    engine.update_weights(params, version=0)
    # warm the compiled prefill/decode programs off the clock
    engine.submit(chunks[0][0][:1], chunks[0][1][:1])
    while not engine.idle:
        engine.step()
    engine.stats(reset=True)

    # Stragglers first: a 16-step row admitted near the end of the queue
    # would drain with mostly-empty slots and depress occupancy for no
    # reason the engine controls — admission order is the host's call.
    all_ids = np.concatenate([c[0] for c in chunks])
    all_msk = np.concatenate([c[1] for c in chunks])
    order = np.argsort(all_ids[:, -1], kind="stable")  # eos-R rows sort first
    engine.submit(all_ids[order], all_msk[order])
    episodes = []
    t0 = time.time()
    while not engine.idle:
        episodes.extend(engine.step())
    engine_s = time.time() - t0
    engine_rate = total_tokens / max(engine_s, 1e-9)
    stats = engine.stats(reset=False)
    engine.shutdown()

    assert len(episodes) == 40
    for ep in episodes:
        rtoks, rmask = ref[tuple(ep.prompt_ids.tolist())]
        assert np.array_equal(ep.response_ids, rtoks), "engine/static token mismatch"
        assert np.array_equal(ep.response_mask, rmask), "engine/static mask mismatch"
    assert engine.num_decode_traces == 1, f"decode retraced: {engine.num_decode_traces}"
    occ = stats["engine/slot_occupancy"]
    assert occ > 0.85, f"slot occupancy {occ:.3f} <= 0.85"
    assert stats["engine/gen_tokens"] == total_tokens
    assert engine_rate > static_rate, (
        f"engine decode {engine_rate:.1f} tok/s did not beat static batch "
        f"{static_rate:.1f} tok/s on the mixed-length workload"
    )
    return {
        "episodes": len(episodes),
        "slot_occupancy": round(occ, 3),
        "refills": stats["engine/refills"],
        "decode_tokens_per_s": round(engine_rate, 1),
        "static_decode_tokens_per_s": round(static_rate, 1),
        "speedup": round(engine_rate / max(static_rate, 1e-9), 2),
        "seconds": round(engine_s + static_s, 2),
    }


def spec_decode_probe():
    import numpy as np  # noqa: F401

    from trlx_tpu.parallel import mesh as mesh_mod

    # Meshless for the same reason as decode_engine_probe: the engine pins
    # its slot state to the process-global mesh left by earlier probes.
    prev_mesh = mesh_mod.peek_mesh()
    mesh_mod.set_mesh(None)
    try:
        return _spec_decode_probe_meshless()
    finally:
        mesh_mod.set_mesh(prev_mesh)


def _spec_decode_probe_meshless():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from trlx_tpu.engine import NgramDrafter, RolloutEngine
    from trlx_tpu.models import LMConfig, LMWithValueHead
    from trlx_tpu.ops.sampling import (
        GenerateConfig,
        make_bigram_mask_processor,
        process_logits_default,
    )

    # Perfect-draft case (ISSUE 19 acceptance): the forced-bigram chain makes
    # greedy decode emit exactly (t+1) % V, and the drafter is seeded with
    # THAT transition — every in-budget draft position matches the model, so
    # the verify path's ceiling is measured: ~spec_k fewer dispatches for the
    # same token stream. The non-spec engine on the same workload is the
    # baseline; both must agree with each other token for token. Short rows
    # run 24 tokens = exactly 3 draft windows (eos lands on a window edge),
    # so the perfect drafter's accept rate is exactly 1.0. The model is kept
    # tiny on purpose: CPU decode is FLOP-bound, so speculation's win here is
    # dispatch-overhead amortization — the gauge the probe gates on is the
    # engine's own decode rate (tokens over decode wall), where the 8x
    # dispatch reduction shows as >= 2x even before accelerator memory
    # bandwidth enters the picture.
    V, R, W = 64, 48, 4
    K = 8
    eos, pad = V - 1, 0
    cfg = LMConfig(vocab_size=V, n_layer=2, n_head=2, d_model=64, max_position=64, dtype="float32")
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    params = {"params": model.init(rng, jnp.ones((2, W), jnp.int32), jnp.ones((2, W), jnp.int32))["params"]}
    gcfg = GenerateConfig(max_new_tokens=R, do_sample=False, eos_token_id=eos, pad_token_id=pad)
    forbidden = np.ones((V, V), dtype=bool)
    for i in range(V):
        forbidden[i, (i + 1) % V] = False
    bigram = make_bigram_mask_processor(jnp.asarray(forbidden))

    def proc(logits, state):
        return process_logits_default(bigram(logits, state), gcfg, state["step"])

    # Mixed lengths like decode_engine_probe, scaled so decode dominates
    # prefill: 2 chunks of 8, one straggler (48 steps) + 7 short rows (24
    # steps) per chunk.
    prng = np.random.default_rng(2)
    chunks = []
    for c in range(2):
        ids = prng.integers(1, 40, size=(8, W)).astype(np.int32)
        ids[0, -1] = eos - R
        ids[1:, -1] = eos - 24
        chunks.append((ids, np.ones((8, W), np.int32)))
    total_tokens = 2 * (R + 7 * 24)
    all_ids = np.concatenate([c[0] for c in chunks])
    all_msk = np.concatenate([c[1] for c in chunks])
    order = np.argsort(all_ids[:, -1], kind="stable")

    def run(spec):
        kw = {}
        if spec:
            kw = dict(
                spec_decode="ngram",
                spec_k=K,
                drafter=NgramDrafter(pad, transition=lambda t: (t + 1) % V),
            )
        engine = RolloutEngine(
            model, gcfg, n_slots=8, prompt_width=W, processor=proc,
            prefill_batch=1, steps_per_sync=1, rng=jax.random.PRNGKey(3), **kw,
        )
        engine.update_weights(params, version=0)
        # warm the compiled programs off the clock
        engine.submit(chunks[0][0][:1], chunks[0][1][:1])
        while not engine.idle:
            engine.step()
        # two timed passes, best decode wall kept — jitter in the host loop
        # must not decide a regression gate
        best = None
        for _ in range(2):
            engine.stats(reset=True)
            engine.submit(all_ids[order], all_msk[order])
            episodes = []
            t0 = time.time()
            while not engine.idle:
                episodes.extend(engine.step())
            wall = time.time() - t0
            stats = engine.stats(reset=False)
            if best is None or stats["engine/decode_wall_s"] < best[1]["engine/decode_wall_s"]:
                best = (episodes, stats, wall)
        traces = engine.num_verify_traces if spec else engine.num_decode_traces
        engine.shutdown()
        return best + (traces,)

    base_eps, base_stats, base_s, base_traces = run(spec=False)
    spec_eps, spec_stats, spec_s, spec_traces = run(spec=True)
    base_rate = base_stats["engine/decode_tokens_per_s"]
    spec_rate = spec_stats["engine/decode_tokens_per_s"]

    assert len(base_eps) == len(spec_eps) == 16
    ref = {tuple(e.prompt_ids.tolist()): e for e in base_eps}
    for ep in spec_eps:
        r = ref[tuple(ep.prompt_ids.tolist())]
        assert np.array_equal(ep.response_ids, r.response_ids), "spec/non-spec token mismatch"
        assert np.array_equal(ep.response_mask, r.response_mask), "spec/non-spec mask mismatch"
    assert base_traces == 1 and spec_traces == 1, "decode/verify retraced"
    assert spec_stats["engine/decode_tokens"] == total_tokens
    # the whole point: far fewer device round-trips for the same tokens
    assert spec_stats["engine/decode_dispatches"] < base_stats["engine/decode_dispatches"]
    accept = spec_stats["engine/spec_accept_rate"]
    assert accept == 1.0, f"perfect-draft accept rate {accept:.3f} != 1.0"
    speedup = spec_rate / max(base_rate, 1e-9)
    assert speedup >= 2.0, (
        f"speculative decode {spec_rate:.1f} tok/s is only {speedup:.2f}x the "
        f"non-spec engine {base_rate:.1f} tok/s on the perfect-draft workload"
    )
    return {
        "episodes": len(spec_eps),
        "spec_k": K,
        "accept_rate": round(accept, 3),
        "decode_dispatches": spec_stats["engine/decode_dispatches"],
        "decode_tokens": spec_stats["engine/decode_tokens"],
        "nonspec_decode_dispatches": base_stats["engine/decode_dispatches"],
        "decode_tokens_per_s": round(spec_rate, 1),
        "nonspec_decode_tokens_per_s": round(base_rate, 1),
        "speedup_vs_nonspec": round(speedup, 2),
        "wall_speedup": round(base_s / max(spec_s, 1e-9), 2),
        "seconds": round(base_s + spec_s, 2),
    }


def paged_kv_probe():
    from trlx_tpu.parallel import mesh as mesh_mod

    # Meshless for the same reason as decode_engine_probe: the engine pins
    # its slot state to the process-global mesh left by earlier probes.
    prev_mesh = mesh_mod.peek_mesh()
    mesh_mod.set_mesh(None)
    try:
        return _paged_kv_probe_meshless()
    finally:
        mesh_mod.set_mesh(prev_mesh)


def _paged_kv_probe_meshless():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from trlx_tpu.engine import RolloutEngine
    from trlx_tpu.models import LMConfig, LMWithValueHead
    from trlx_tpu.ops.sampling import (
        GenerateConfig,
        make_bigram_mask_processor,
        process_logits_default,
    )

    # Paged KV + prefix caching (ISSUE 20): a mixed-length workload where
    # every prompt opens with the SAME 64-token template (the RLHF shape:
    # one system/task preamble, per-episode suffix). Three claims, each
    # gated here:
    #   1. parity — the paged engine with prefix caching ON returns
    #      token-for-token the fixed-slot engine's episodes (quant on/off);
    #   2. capacity — the paged pool runs MORE concurrent slots in the SAME
    #      cache bytes: S_paged >= 1.5 x S_fixed with
    #      n_blocks*block_size <= S_fixed*cache_len (same per-token layout,
    #      so token-slots ARE bytes);
    #   3. prefix savings — template blocks prefill ONCE per weight version;
    #      every later admission pins them and dispatches a suffix-only
    #      prefill (64 of 72 prompt tokens skipped per hit).
    # Forced-bigram chain (as in decode_engine_probe) engineers response
    # lengths: one straggler (16 steps) per wave, short rows run 5.
    V, R, W, TPL, BS = 64, 16, 72, 64, 16
    eos, pad = V - 1, 0
    S_FIXED, S_PAGED = 4, 6
    cache_len = W + R  # 88 -> 6 blocks of 16 per slot (kv_len 96)
    POOL_BLOCKS = (S_FIXED * cache_len) // BS  # 22: byte-parity with fixed
    gcfg = GenerateConfig(max_new_tokens=R, do_sample=False, eos_token_id=eos, pad_token_id=pad)
    forbidden = np.ones((V, V), dtype=bool)
    for i in range(V):
        forbidden[i, (i + 1) % V] = False
    bigram = make_bigram_mask_processor(jnp.asarray(forbidden))

    def proc(logits, state):
        return process_logits_default(bigram(logits, state), gcfg, state["step"])

    # 12 rows = 2 waves of 6: shared template, unique 8-token suffixes, the
    # suffix's last token engineering the response length.
    prng = np.random.default_rng(5)
    template = prng.integers(1, 40, size=TPL).astype(np.int32)
    ids = np.tile(template, (12, 1))
    suffix = prng.integers(1, 40, size=(12, W - TPL)).astype(np.int32)
    suffix[:, -1] = eos - 5  # short rows: 5 steps
    suffix[0, -1] = eos - R  # wave stragglers: full 16-step budget
    suffix[6, -1] = eos - R
    ids = np.concatenate([ids, suffix], axis=1)
    msk = np.ones_like(ids)

    def run(quant, paged):
        cfg = LMConfig(
            vocab_size=V, n_layer=2, n_head=2, d_model=64, max_position=128,
            dtype="float32", kv_cache_quant=quant,
        )
        model = LMWithValueHead(cfg)
        params = {"params": model.init(
            jax.random.PRNGKey(0), jnp.ones((2, W), jnp.int32), jnp.ones((2, W), jnp.int32)
        )["params"]}
        kw = dict(paged_kv=True, kv_block_size=BS, kv_pool_blocks=POOL_BLOCKS) if paged else {}
        engine = RolloutEngine(
            model, gcfg, n_slots=S_PAGED if paged else S_FIXED, prompt_width=W,
            processor=proc, prefill_batch=1, steps_per_sync=1,
            rng=jax.random.PRNGKey(3), **kw,
        )
        engine.update_weights(params, version=0)
        # warm the compiled programs off the clock (full-width prefill, the
        # suffix-only prefill shape, and decode)
        engine.submit(ids[:2], msk[:2])
        while not engine.idle:
            engine.step()
        engine.stats(reset=True)
        # pool hit counters are lifetime totals by contract — diff across
        # the timed window so the warm-up's hit does not inflate the claim
        base = {k: v for k, v in engine.stats(reset=False).items()
                if k.endswith("_total")} if paged else {}
        episodes, peak = [], 0
        t0 = time.time()
        engine.submit(ids, msk)
        while not engine.idle:
            episodes.extend(engine.step())
            if paged:
                peak = max(peak, engine.pool.used_blocks())
        wall = time.time() - t0
        stats = engine.stats(reset=False)
        for k, v in base.items():
            stats[k] = stats[k] - v
        if paged:
            engine.abort()  # leak_audit: every pool block accounted for
        engine.shutdown()
        return episodes, stats, peak, wall

    result = {
        "slots_fixed": S_FIXED,
        "slots_paged": S_PAGED,
        "slot_capacity_ratio": round(S_PAGED / S_FIXED, 2),
        "cache_tokens_fixed": S_FIXED * cache_len,
        "cache_tokens_paged": POOL_BLOCKS * BS,
        "block_size": BS,
        "pool_blocks": POOL_BLOCKS,
        "template_tokens": TPL,
    }
    # claim 2 is pure arithmetic — pin it before paying for any run
    assert POOL_BLOCKS * BS <= S_FIXED * cache_len
    assert S_PAGED >= 1.5 * S_FIXED
    t_all = time.time()
    for quant in (False, True):
        fixed_eps, _, _, _ = run(quant, paged=False)
        paged_eps, stats, peak, wall = run(quant, paged=True)
        assert len(fixed_eps) == len(paged_eps) == 12
        ref = {tuple(e.prompt_ids.tolist()): e for e in fixed_eps}
        for ep in paged_eps:
            r = ref[tuple(ep.prompt_ids.tolist())]
            assert np.array_equal(ep.response_ids, r.response_ids), (
                f"paged/fixed token mismatch (quant={quant})"
            )
            assert np.array_equal(ep.response_mask, r.response_mask), (
                f"paged/fixed mask mismatch (quant={quant})"
            )
        # claim 3: the warm-up registered the template at this weight
        # version, so ALL 12 timed admissions hit and skip TPL tokens of
        # prefill each (prefill_batch=1 admits one row per call — even on a
        # cold registry the second admission would see the first's entry).
        hits = stats["engine/prefix_hits_total"]
        saved = stats["engine/prefill_tokens_saved_total"]
        assert hits >= 12, f"prefix hits {hits} < 12 (quant={quant})"
        assert saved >= 12 * TPL, f"prefill tokens saved {saved} < {12 * TPL}"
        assert peak <= POOL_BLOCKS - 1, f"pool peak {peak} blocks overflows"
        frag = stats["engine/pool_frag_frac"]
        assert 0.0 <= frag <= 1.0
        key = "int8" if quant else "fp"
        result[key] = {
            "prefix_hits": int(hits),
            "prefill_tokens_saved": int(saved),
            "prefill_token_reduction": round(saved / float(12 * W), 3),
            "peak_pool_blocks": int(peak),
            "evictions": int(stats["engine/pool_evictions_total"]),
            "decode_tokens_per_s": round(stats["engine/decode_tokens_per_s"], 1),
            "wall_s": round(wall, 2),
        }
    # headline fields for the trajectory fold: worst case over quant modes
    result["prefix_hits_total"] = min(result["fp"]["prefix_hits"], result["int8"]["prefix_hits"])
    result["prefill_token_reduction"] = min(
        result["fp"]["prefill_token_reduction"], result["int8"]["prefill_token_reduction"]
    )
    result["seconds"] = round(time.time() - t_all, 2)
    return result


def fleet_elastic_probe():
    """Elastic fleet transport throughput: episode batches/s through the
    REAL lease ledger + per-worker stream indexes + exactly-once intake
    (trlx_tpu/fleet, RUNBOOK §18), at 1 worker vs 2. Workers are threads
    with a fixed synthetic produce cost standing in for generation — no
    model, no mesh — so the number isolates what the probe is for: the
    claim/append/consume transports must let N workers overlap, not
    serialize them. Intake must stay exactly-once either way."""
    import tempfile
    import threading

    import numpy as np

    from trlx_tpu.fleet import (
        ElasticStreamReader,
        EpisodeStreamWriter,
        FleetPaths,
        LeaseLedger,
        WorkerRegistry,
    )

    UNITS, S, BATCH = 24, 4, 16
    PRODUCE_S = 0.02  # modeled per-unit generation cost (dominates transport)
    cols = {
        "query_tensors": np.ones((BATCH, 8), np.int32),
        "query_mask": np.ones((BATCH, 8), np.int32),
        "response_tensors": np.ones((BATCH, 8), np.int32),
        "response_mask": np.ones((BATCH, 8), np.int32),
    }

    def run_fleet(n_workers: int, root: str) -> float:
        paths = FleetPaths(root=root).ensure_elastic()
        ledger = LeaseLedger(paths.leases_dir, ttl=60.0)
        registry = WorkerRegistry(paths.workers_dir)
        cursor = {"consumed": 0}
        lock = threading.Lock()

        def worker(wid: int):
            registry.register(wid)
            writer = EpisodeStreamWriter(paths, worker=wid)
            while True:
                with lock:
                    consumed = cursor["consumed"]
                if consumed >= UNITS:
                    return
                lease = None
                for unit in range(consumed, min(UNITS, consumed + S + 1)):
                    got = ledger.try_claim(unit, wid)
                    if got is not None:
                        lease = got
                        break
                if lease is None:
                    time.sleep(0.002)
                    continue
                time.sleep(PRODUCE_S)
                writer.append(cols, weight_version=0, unit=lease.unit)
                ledger.complete(lease)

        reader = ElasticStreamReader(paths)
        threads = [
            threading.Thread(target=worker, args=(k,), name=f"smoke-fleet-w{k}", daemon=True)
            for k in range(n_workers)
        ]
        t0 = time.time()
        for t in threads:
            t.start()
        for unit in range(UNITS):
            rec = reader.wait(unit, timeout=30.0, retries=1, backoff=0.1)
            loaded = reader.load(rec)
            assert int(next(iter(loaded.values())).shape[0]) == BATCH
            with lock:
                cursor["consumed"] = unit + 1
        wall = time.time() - t0
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads), "fleet worker thread leaked"
        # Exactly-once: every unit chosen once, zero duplicates (nothing
        # died, so the O_EXCL ledger must have prevented every double claim).
        assert sorted(reader.chosen()) == list(range(UNITS))
        assert reader.duplicates() == 0, f"{reader.duplicates()} duplicate records"
        assert ledger.reclaimed_units() == []
        assert sorted(registry.active()) == list(range(n_workers))
        return wall

    with tempfile.TemporaryDirectory() as tmp:
        wall_1 = run_fleet(1, os.path.join(tmp, "fleet1"))
        wall_2 = run_fleet(2, os.path.join(tmp, "fleet2"))
    rate_1 = UNITS / max(wall_1, 1e-9)
    rate_2 = UNITS / max(wall_2, 1e-9)
    speedup = rate_2 / max(rate_1, 1e-9)
    # 2 workers over a 20ms produce cost should approach 2x; 1.3x is the
    # "transports do not serialize the fleet" floor with CI noise headroom.
    assert speedup > 1.3, (
        f"2-worker elastic fleet {rate_2:.1f} units/s is not ahead of "
        f"1-worker {rate_1:.1f} units/s (speedup {speedup:.2f})"
    )
    return {
        "units": UNITS,
        "episodes_per_batch": BATCH,
        "units_per_s_1worker": round(rate_1, 1),
        "units_per_s_2workers": round(rate_2, 1),
        "episodes_per_s_1worker": round(rate_1 * BATCH, 1),
        "episodes_per_s_2workers": round(rate_2 * BATCH, 1),
        "speedup": round(speedup, 2),
        "seconds": round(wall_1 + wall_2, 2),
    }


def main():
    from trlx_tpu.observability.graftscope import RunManifest

    t0 = time.time()
    # Same crash contract as bench.py: a killed smoke run leaves a
    # line-atomic journal saying which probe it died in.
    manifest = RunManifest(
        os.path.join(REPO, "BENCH_SMOKE_MANIFEST.jsonl"), cmd=" ".join(sys.argv)
    )
    result = {}
    for name, probe in (
        ("rollout", rollout_probe),
        ("overlap", overlap_probe),
        ("fused_loss", fused_loss_probe),
        ("decode_engine", decode_engine_probe),
        ("spec_decode", spec_decode_probe),
        ("paged_kv", paged_kv_probe),
        ("fleet_elastic", fleet_elastic_probe),
    ):
        manifest.heartbeat("probe", candidate=name)
        result[name] = probe()
        manifest.partial(result)
    # An engine speedup number is only meaningful NEXT TO the occupancy it
    # was measured at (a low-occupancy run can "beat" a static batch that
    # padding starved) — the recorded artifact must keep the pair together.
    eng = result["decode_engine"]
    assert {"speedup", "slot_occupancy"} <= set(eng), (
        f"decode_engine record must pair speedup with slot_occupancy: {eng}"
    )
    # Same pairing rule for speculation: a speedup without the accept rate
    # and the dispatch/token split it was achieved at is unreadable.
    spec = result["spec_decode"]
    assert {"speedup_vs_nonspec", "accept_rate", "decode_dispatches", "decode_tokens"} <= set(spec), (
        f"spec_decode record must pair speedup with accept rate + dispatch split: {spec}"
    )
    # A slot-capacity ratio is only meaningful next to the byte budget it
    # was achieved in and the prefix savings that funded it.
    paged = result["paged_kv"]
    assert {"slot_capacity_ratio", "cache_tokens_fixed", "cache_tokens_paged",
            "prefix_hits_total", "prefill_token_reduction"} <= set(paged), (
        f"paged_kv record must pair capacity ratio with bytes + prefix savings: {paged}"
    )
    result["wall_s"] = round(time.time() - t0, 1)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"smoke": "ok", **result}))
    manifest.finish(rc=0)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 — CI needs the one-line verdict
        print(json.dumps({"smoke": "FAIL", "error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)

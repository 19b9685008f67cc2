"""Benchmark: PPO iteration throughput + MFU on real hardware.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Measures the full PPO cadence — compiled rollout generation (prefill +
while_loop decode), fused rollout scoring, and ppo_epochs donated train
steps — and reports, alongside samples/s/chip:

- per-phase wall time (generate / score / train),
- modeled TFLOP/s and %-of-peak (MFU) for the train step and for the whole
  iteration, against the detected chip's peak bf16 FLOP/s,
- the honest model identity (a GPT-J-family architecture auto-sized to the
  chip's HBM — "gptj-l28-d4096" IS 6B; smaller chips bench a smaller
  truthfully-named proxy),
- the PIPELINED orchestrator path (PPOOrchestrator.make_experience, where
  the next chunk's generation is dispatched before the current chunk's host
  scoring) measured against the same phases run serialized, as
  "overlap_gain_pct" — the design claim, measured rather than asserted,
- an fp32-master measured point (the production master-weights dtype) on a
  smaller HBM-fitting size, alongside the flagship bf16 throughput entry.

The default preset is "auto": the largest HBM-fitting entry from SIZES at
seq 1024 (768-token prefill + 256-token decode), which routes scoring and
training attention through the pallas flash kernel. The HEADLINE value is
the PRODUCTION cadence (pipelined + fused-scoring rollouts + measured train
phase); the serialized-unfused phase loop is kept as
`ablation_serialized_unfused_samples_per_sec_per_chip`. `decode_hbm_util_pct`
states the "decode at its bandwidth floor" claim as a falsifiable percentage.

vs_baseline: the reference publishes no numbers and no Accelerate-GPU
baseline can run here (BASELINE.md), so the TPU-vs-GPU gate stays open; the
ratio reported is the MEASURED CPU head-to-head against the reference's own
training loop (bench_reference.py → HEADTOHEAD.json), scope-labeled.
"""

import json
import os
import re
import sys
import time

import numpy as np

# (name, n_layer, d_model, n_head, vocab, prompt, new_tokens, train_batch,
#  unfrozen, rollout_chunk)
# Auto sizes run with bf16 params (master + moments) — throughput benching,
# named honestly in the metric. A 16GB v5e fits the 2.0B entry; fp32-master
# production recipes shard over fsdp instead (ppo_gptj_config.yml).
# rollout_chunk > train_batch amortizes the bandwidth/latency-bound decode
# over more samples (the real orchestrator's chunk_size/batch_size split):
# measured on a v5e at 2.0B, chunk 32 over batch 8 is +57% samples/s.
# (name, L, d, heads, vocab, P, R, B, unfrozen, chunk[, w8]) — the optional
# 11th field turns on W8A16 decode for that entry (BENCH_W8 env still wins).
# The W8 2.0B entry (chunk 32 — the int8 copies cost ~+2.3 GB so chunk 48
# doesn't fit with them) measured 2.715 production samples/s/chip vs 2.647
# for chunk-48 full-precision (r4); the non-W8 entry right after it is the
# SAME-SIZE fallback if the marginal fit ever tips over, so an OOM degrades
# the quantization, not the model size.
SIZES = [
    ("gptj-l28-d4096-6.1B-bf16", 28, 4096, 16, 50400, 768, 256, 8, 2, 16),
    ("gptj-l16-d4096-3.7B-bf16", 16, 4096, 16, 50400, 768, 256, 8, 2, 16),
    ("gptj-l8-d4096-2.0B-w8-bf16", 8, 4096, 16, 50400, 768, 256, 8, 2, 32, 1),
    ("gptj-l8-d4096-2.0B-bf16", 8, 4096, 16, 50400, 768, 256, 8, 2, 48),
    ("gptj-l4-d4096-1.2B-bf16", 4, 4096, 16, 50400, 768, 256, 8, 2, 32),
    ("gptj-l4-d2048-0.4B-bf16", 4, 2048, 16, 50400, 768, 256, 8, 2, 32),
    ("gptj-l2-d512-tiny", 2, 512, 8, 1024, 256, 128, 4, 1, 8),
]
# fp32-master measured points (production master-weights dtype; the big
# recipes shard fp32 masters over fsdp on a pod — single-chip benches the
# largest fp32 size that fits). Largest-fitting entry runs as a SECONDARY
# measurement alongside the flagship bf16 number.
FP32_SIZES = [
    ("gptj-l6-d2048-0.5B-fp32", 6, 2048, 16, 50400, 768, 256, 8, 2, 32),
    ("gptj-l4-d2048-0.4B-fp32", 4, 2048, 16, 50400, 768, 256, 8, 2, 32),
    ("gptj-l2-d1024-0.1B-fp32", 2, 1024, 16, 50400, 768, 256, 8, 1, 16),
]
# Legacy fixed presets (BENCH_PRESET env) — the r1 shapes, kept comparable.
# ILQL bench sizes: the reference's ILQL cadence is short-sequence offline
# batches (seq 64, configs/ilql_config.yml:8) and the method trains ALL
# layers + 4 vocab-wide Q heads (2 online + 2 target) — different memory
# shape than PPO (full-trunk Adam moments + [B,T,vocab] Q tensors in the
# loss), so the candidate list is its own. (name, L, d, heads, vocab, P, R,
# B, unfrozen(-1=all), C unused)
ILQL_SIZES = [
    # d4096 with every layer unfrozen is not in the list: at r4 its compile
    # failed twice at that size, ~6 min of bench budget before the fallback.
    # Batch 128 is the reference's own ilql_config batch size and measured
    # +47% over b32 here (358 vs 243 samples/s/chip, 61.9% vs 42.0% MFU —
    # short seq-64 rows need the batch dim for arithmetic intensity).
    ("ilql-l4-d2048-0.4B-bf16", 4, 2048, 16, 50400, 16, 48, 128, -1, 32),
    # SAME-SIZE fallback at b32 (the b128 loss holds ~4x larger [B,T,vocab]
    # Q tensors): an OOM degrades the batch, not the model size.
    ("ilql-l4-d2048-0.4B-b32-bf16", 4, 2048, 16, 50400, 16, 48, 32, -1, 32),
    ("ilql-l2-d512-tiny", 2, 512, 8, 1024, 16, 48, 16, -1, 16),
]

PRESETS = {
    "tiny": ("gptj-l2-d256", 2, 256, 8, 1024, 16, 32, 16, 1, 16),
    "small": ("gptj-l8-d1024", 8, 1024, 16, 50400, 16, 32, 16, 4, 16),
    "medium": ("gptj-l16-d2048", 16, 2048, 16, 50400, 16, 32, 8, 8, 8),
    "long": ("gptj-l8-d1024", 8, 1024, 16, 50400, 768, 256, 4, 4, 4),
}

def detect_chip():
    """(peak bf16 TFLOP/s, HBM GB/s, device_kind) of this process's chip,
    from the one table in trlx_tpu/observability/devicemon.py. A
    device_kind that is not in the table is an error there: a line with no
    MFU is not a result."""
    import jax

    from trlx_tpu.observability.devicemon import chip_peaks

    kind = jax.devices()[0].device_kind
    tflops, gbps = chip_peaks(kind)
    return tflops, gbps, kind


# Allocator-specific phrases only: a bare 'alloc'/'memory'/'hbm' net would
# classify unrelated runtime errors ('invalid memory access', layout/allocation
# asserts) as OOM and silently fall back to a smaller size.
_OOM_PHRASES = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "failed to allocate",
    "allocation failed",
)
# "oom" only as a whole word (plus the oom_kill/oomkilled variants) — a bare
# substring would match unrelated text ("zoom", "bloomfilter", file paths)
# and wrongly trigger the size fallback.
_OOM_WORD = re.compile(r"\boom(?:_?kill(?:ed|er)?)?\b")


def is_oom(e: Exception) -> bool:
    """Allocator-failure detection for the auto-size fallback. The classified
    error is logged to stderr so a misclassification is visible in the bench
    transcript rather than silently becoming a smaller model size."""
    msg = str(e).lower()
    hit = any(s in msg for s in _OOM_PHRASES) or bool(_OOM_WORD.search(msg))
    if hit:
        print(f"[bench] classified as OOM ({type(e).__name__}): {str(e)[:500]}", file=sys.stderr)
    return hit


def fits_hbm(L, d, vocab, unfrozen, hbm, param_bytes=2):
    """Rough static-memory model: master params + Adam moments on trainable
    params (top `unfrozen` blocks + embeddings + heads) + frozen ref branch
    copy, all at `param_bytes` per element, with a 1.6x activation/workspace
    margin. Conservative on purpose — the auto-sizer also try/excepts OOM."""
    block = 12 * d * d
    emb = 2 * vocab * d  # wte + untied lm_head
    params = L * block + emb
    trainable = unfrozen * block + emb + 3 * 2 * d * d  # + value head approx
    branch = unfrozen * block + emb  # frozen ref branch copy (hydra extras)
    bytes_needed = (params + trainable * 2 + branch) * param_bytes
    return bytes_needed * 1.6 < hbm


def lm_flops(L, d, vocab, n_tokens, kv_avg, logits_tokens, value_head=False):
    """Modeled fwd matmul FLOPs: per LAYER 12·d² MACs/token in blocks
    (qkv+proj+mlp) + 2·kv·d MACs/token attention; plus d·vocab MACs per
    logits token and (value_head) 4·d² MACs/token; ×2 FLOP/MAC."""
    per_tok = L * (12 * d * d + 2 * kv_avg * d)
    if value_head:
        per_tok += 4 * d * d  # MLPHead d -> 2d -> 1
    return 2.0 * (n_tokens * per_tok + logits_tokens * d * vocab)


OOM_EXIT_CODE = 77

# Crash-proof run forensics (utils/manifest.RunManifest): the manifest is opened
# at the top of main() and every heartbeat / child rc / partial result is one
# line-atomic append, so a `timeout -k`-killed bench (the BENCH_r04/r05
# shapes) still leaves a parseable journal bench_trajectory.py can turn into
# a reason string. Module-global so the __main__ crash handler can close it.
_MANIFEST = None


def _run_child(args):
    """Start one JAX-owning child of this JAX-free parent and wait for it.
    On a TPU host the chip belongs to one process at a time, so the parent
    never imports jax and runs its children one after another."""
    import subprocess

    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        capture_output=True,
        text=True,
    )


def main():
    global _MANIFEST
    from trlx_tpu.utils.manifest import MANIFEST_FILENAME, RunManifest

    manifest = _MANIFEST = RunManifest(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), MANIFEST_FILENAME),
        cmd=" ".join(sys.argv),
    )

    # The first child reports the device it got (platform, kind, count, HBM
    # bytes from memory_stats()). A measurement path that finds no TPU
    # fails: a CPU run is never what a missing chip turns into.
    probe = _run_child(["--probe"])
    manifest.child("probe", probe.returncode, probe.stderr)
    if probe.returncode != 0:
        sys.stderr.write(probe.stderr[-4000:])
        manifest.finish(rc=1, reason="device probe failed")
        raise RuntimeError(f"bench: device probe failed (rc={probe.returncode})")
    device = json.loads(probe.stdout.strip().splitlines()[-1])
    manifest.heartbeat("device", **device)
    if device["platform"] != "tpu":
        manifest.finish(rc=1, reason=f"no TPU (platform {device['platform']})")
        raise RuntimeError(
            f"bench: JAX reports platform {device['platform']!r}, not a TPU — "
            "bench.py measures on the chip only"
        )
    hbm = device["hbm_bytes"]

    preset = os.environ.get("BENCH_PRESET", "auto")
    fp32_point = os.environ.get("BENCH_FP32_POINT", "1") == "1"
    if preset != "auto":
        candidates = [PRESETS[preset]]
        fp32_candidates = []
    else:
        candidates = [
            s for s in SIZES if fits_hbm(s[1], s[2], s[4], s[8], hbm)
        ] or [SIZES[-1]]
        fp32_candidates = [
            s
            for s in FP32_SIZES
            if fits_hbm(s[1], s[2], s[4], s[8], hbm, param_bytes=4)
        ] or [FP32_SIZES[-1]]

    # Non-OOM candidate failures (a child rc!=0) are RECORDED here and the
    # size ladder continues — published beside the flagship number as
    # `failed_candidates`, never silently.
    failed_candidates = []

    def try_one(cand, **kwargs):
        """One size candidate in its own process: an OOM'd attempt's device
        memory is only reliably reclaimed when its process dies."""
        proc = _run_child(["--one", json.dumps({"cand": cand, "kwargs": kwargs})])
        manifest.child(cand[0], proc.returncode, proc.stderr)
        if proc.returncode == OOM_EXIT_CODE or proc.returncode < 0:
            # OOM exit, or the runtime hard-aborted the child (SIGABRT from a
            # native allocator failure never reaches the Python handler) —
            # either way this size doesn't fit; keep the attempt debuggable.
            sys.stderr.write(proc.stderr[-1500:])
            return None
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-4000:])
            failed_candidates.append(
                {"candidate": cand[0], "rc": proc.returncode, "tail": proc.stderr[-2000:]}
            )
            print(
                f"bench: {cand[0]} failed (rc={proc.returncode}); recorded, trying next size",
                file=sys.stderr,
            )
            return None
        if proc.stderr.strip():
            sys.stderr.write(proc.stderr[-1500:])
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def first_fitting(cands, **kwargs):
        for cand in cands:
            # Journal BEFORE launching: a hard kill mid-candidate leaves
            # this heartbeat as the manifest's "died during X" evidence.
            manifest.heartbeat(
                "size_ladder", candidate=cand[0], mode=kwargs.get("mode", "ppo")
            )
            result = try_one(cand, **kwargs)
            if result is not None:
                return result
            print(f"bench: {cand[0]} did not complete, trying next size", file=sys.stderr)
        return None

    bench_t0 = time.time()
    # Parse up front: a malformed value must fail BEFORE the flagship run,
    # not crash the bench after it (losing the very JSON line this guard
    # protects).
    try:
        optional_deadline = float(os.environ.get("BENCH_OPTIONAL_DEADLINE", "900"))
    except ValueError:
        print("bench: invalid BENCH_OPTIONAL_DEADLINE; using 900s", file=sys.stderr)
        optional_deadline = 900.0

    def _optional_budget_left(label):
        """The flagship number must never be lost to a driver-side timeout
        because optional points pushed the total past the budget: once
        elapsed exceeds BENCH_OPTIONAL_DEADLINE seconds (e.g. the flagship
        needed slow OOM fallbacks), skip remaining optional points with a
        note instead of gambling the whole JSON line."""
        elapsed = time.time() - bench_t0
        if elapsed > optional_deadline:
            print(
                f"bench: skipping {label} — {elapsed:.0f}s elapsed exceeds "
                f"BENCH_OPTIONAL_DEADLINE={optional_deadline:.0f}s",
                file=sys.stderr,
            )
            return False
        return True

    result = first_fitting(candidates)
    if result is None:
        detail = "; ".join(
            f"{f['candidate']} rc={f['rc']}" for f in failed_candidates
        )
        msg = "no bench size fit the device" + (
            f" (non-OOM failures: {detail})" if detail else ""
        )
        manifest.finish(rc=1, reason=msg)
        raise RuntimeError(msg)
    # The flagship number exists from here on: journal it immediately so a
    # kill during the OPTIONAL points (fp32/ILQL) cannot lose it.
    manifest.partial(
        {k: result.get(k) for k in ("metric", "value", "unit", "size") if k in result}
    )
    if failed_candidates:
        # Published alongside the flagship number: which larger sizes failed
        # for non-OOM reasons, with the stderr tail for triage.
        result["failed_candidates"] = failed_candidates

    def _required(label, point):
        """The fp32 and ILQL points are part of the run: one that produced
        no result fails it, instead of a line that quietly lacks the field."""
        if point is None:
            manifest.finish(rc=1, reason=f"{label} produced no result")
            raise RuntimeError(
                f"bench: {label} produced no result "
                f"(failures: {[f['candidate'] for f in failed_candidates]})"
            )
        return point

    if fp32_candidates and fp32_point and _optional_budget_left("fp32 point"):
        manifest.heartbeat("fp32_point")
        fp32 = _required(
            "fp32 point", first_fitting(fp32_candidates, iters=2, orchestrator=False)
        )
        result["fp32_master_point"] = {
            k: fp32[k]
            for k in (
                "metric",
                "value",
                "unit",
                "phase_seconds_per_iter",
                "train_mfu_pct",
                "iter_mfu_pct",
            )
            if k in fp32
        }

    # ILQL measured point (the reference ships two methods; both get a perf
    # story). Heads add ~4x(2d*V) params over the PPO config, so the fitting
    # size may be smaller — the same OOM-fallback machinery sizes it.
    if os.environ.get("BENCH_ILQL_POINT", "1") == "1" and _optional_budget_left("ILQL point"):
        manifest.heartbeat("ilql_point")
        ilql_candidates = ILQL_SIZES if preset == "auto" else [ILQL_SIZES[-1]]
        result["ilql_point"] = _required(
            "ILQL point", first_fitting(ilql_candidates, mode="ilql", iters=2)
        )

    # The first MEASURED baseline ratio: bench_reference.py runs the
    # reference's OWN trlx.train head-to-head against trlx_tpu on CPU
    # (identical dataset + protocol, the reference's own metric) and records
    # HEADTOHEAD.json. Scope-labeled — a same-hardware implementation ratio,
    # NOT the v4-32 ≥2x gate (which needs hardware this environment lacks).
    h2h_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "HEADTOHEAD.json")
    if os.path.exists(h2h_path):
        # Assemble in a temp dict so a malformed file leaves `result`
        # untouched (vs_baseline really does stay null on any failure).
        try:
            with open(h2h_path) as f:
                h2h = json.load(f)
            if "reference" in h2h:  # legacy single-task layout
                h2h = {"ilql": h2h}
            # The headline metric is a PPO throughput number, so the primary
            # `vs_baseline` carries the PPO ratio (same method); both methods
            # are exposed symmetrically under vs_baseline_{ppo,ilql}_* keys.
            fields = {}
            if "ppo" in h2h:
                ppo = h2h["ppo"]
                fields["vs_baseline"] = ppo["vs_baseline_samples_per_s"]
                fields["vs_baseline_scope"] = (
                    "CPU head-to-head vs the reference's own training loop "
                    "(randomwalks PPO, identical dataset/protocol/metric — "
                    "HEADTOHEAD.json; cold-compile included). Warm-cache: "
                    f"{ppo.get('vs_baseline_warm_cache')}, full-step steady-state: "
                    f"{ppo.get('vs_baseline_steady_state')}. Not the v4-32 gate."
                )
                fields["vs_baseline_ppo"] = ppo["vs_baseline_samples_per_s"]
                fields["vs_baseline_ppo_warm_cache"] = ppo.get("vs_baseline_warm_cache")
                fields["vs_baseline_ppo_steady_state"] = ppo.get("vs_baseline_steady_state")
                fields["vs_baseline_ppo_steady_cycle"] = ppo.get("vs_baseline_steady_cycle")
            if "ilql" in h2h:
                ilql = h2h["ilql"]
                fields["vs_baseline_ilql"] = ilql["vs_baseline_samples_per_s"]
                fields["vs_baseline_ilql_warm_cache"] = ilql.get("vs_baseline_warm_cache")
                fields["vs_baseline_ilql_steady_state"] = ilql.get("vs_baseline_steady_state")
                fields["vs_baseline_final_optimality"] = {
                    "reference": ilql["reference"]["final_optimality"],
                    "ours": ilql["ours"]["final_optimality"],
                }
                if "vs_baseline" not in fields:
                    # ILQL-only (or legacy single-task) file: a measured ratio
                    # on disk must not surface as null — fall back with an
                    # explicit cross-method scope label.
                    fields["vs_baseline"] = ilql["vs_baseline_samples_per_s"]
                    fields["vs_baseline_scope"] = (
                        "CPU head-to-head vs the reference's own training loop "
                        "(randomwalks ILQL — no PPO section in HEADTOHEAD.json; "
                        "note the headline metric is a PPO throughput). "
                        f"Warm-cache: {ilql.get('vs_baseline_warm_cache')}, "
                        f"steady-state: {ilql.get('vs_baseline_steady_state')}. "
                        "Not the v4-32 gate."
                    )
            result.update(fields)
        except (KeyError, ValueError, TypeError) as e:
            print(f"bench: HEADTOHEAD.json unreadable ({e}); vs_baseline stays null", file=sys.stderr)
    print(json.dumps(result))
    manifest.finish(rc=0, metric=result.get("metric"), value=result.get("value"))


def device_sync(tree):
    """Phase barrier: dispatch is asynchronous, so every timed phase ends
    by waiting for its result."""
    import jax

    jax.block_until_ready(tree)


def run_one(cand, iters=None, orchestrator=True, mode="ppo"):
    import jax

    if mode == "ilql":
        return run_one_ilql(cand, iters=iters)

    name, n_layer, d_model, n_head, vocab, P, R, B, unfrozen, C = cand[:10]
    cand_w8 = bool(cand[10]) if len(cand) > 10 else False
    # Tuning knobs (experimentation; the shipped SIZES carry the defaults).
    B = int(os.environ.get("BENCH_BATCH", B))
    C = int(os.environ.get("BENCH_CHUNK", C))
    P = int(os.environ.get("BENCH_PROMPT", P))
    R = int(os.environ.get("BENCH_DECODE", R))
    remat_env = os.environ.get("BENCH_REMAT")
    from trlx_tpu.data import PPORLBatch
    from trlx_tpu.trainer.api import default_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    n_dev = jax.device_count()
    B = ((B + n_dev - 1) // n_dev) * n_dev
    C = max(((C + B - 1) // B) * B, B)  # chunk = whole train batches
    T = P + R

    config = default_config("ppo")
    config.model.model_path = ""
    config.model.tokenizer_path = ""
    config.model.num_layers_unfrozen = unfrozen
    config.model.model_arch = {
        "vocab_size": vocab,
        "n_layer": n_layer,
        "n_head": n_head,
        "d_model": d_model,
        "max_position": max(2048, T),
        "eos_token_id": 0,
        "pos_type": "rotary",
        "rotary_dim": 64 if d_model // n_head >= 64 else d_model // n_head,
        "parallel_residual": True,
        "fused_qkv": False,
        "qkv_bias": False,
        "out_bias": False,
        "tie_word_embeddings": False,
        "extra": {"lm_head_bias": True},
    }
    config.model.remat = d_model >= 4096 if remat_env is None else remat_env == "1"
    config.model.remat_policy = os.environ.get("BENCH_REMAT_POLICY", "full")
    # int8 decode KV cache ON by default for the bench: decode is HBM-bound
    # on cache reads, int8 halves that traffic (+6% samples/s at 2.0B) and
    # frees HBM for a larger rollout chunk. Learning-quality verified: PPO
    # randomwalks reaches 1.0 optimality with it; training re-forwards are
    # always full precision, and under fused rollout stats the stored
    # behavior logprobs are the quantized sampler's own (≤0.008 from the fp
    # recompute — tests/test_fused_rollout.py).
    config.model.kv_cache_quant = os.environ.get("BENCH_KV_QUANT", "1") == "1"
    # W8A16 decode (int8 trunk kernels for sampling only): measured −18..21%
    # decode time (BASELINE.md). Per-entry default via the SIZES w8 field —
    # the flagship 2.0B entry runs W8 at chunk 32 (2.715 vs 2.647 production
    # samples/s/chip measured r4; chunk 48 + the ~+2.3 GB int8 copies don't
    # fit). BENCH_W8 env overrides either way.
    w8_env = os.environ.get("BENCH_W8")
    config.model.decode_weight_quant = (w8_env == "1") if w8_env is not None else cand_w8
    if name.endswith("-bf16"):
        # Throughput benching at the largest HBM-fitting size: bf16 master
        # params + moments (named honestly in the metric). Production fp32-
        # master recipes shard over fsdp instead.
        config.model.param_dtype = "bfloat16"
    config.train.batch_size = B
    config.train.seq_length = T
    config.train.mesh = [-1, 1, 1, 1]
    config.method.gen_kwargs = {
        "prompt_length": P,
        "max_new_tokens": R,
        "min_new_tokens": R,  # fixed-length decode: measure the full loop
        "do_sample": True,
        "top_k": 0,
        "top_p": 1.0,
    }
    config.method.chunk_size = C
    config.method.num_rollouts = C
    config.method.ppo_epochs = 4

    trainer = PPOTrainer(config)
    rng = np.random.default_rng(0)
    prompt_ids = rng.integers(2, vocab, size=(C, P)).astype(np.int32)
    prompt_mask = np.ones((C, P), dtype=np.int32)

    sync = device_sync

    def phase_generate():
        tokens, mask = trainer.rollout_generate(prompt_ids, prompt_mask)
        sync(tokens)
        return tokens, mask

    def phase_score(tokens, mask):
        scores = rng.normal(size=(C,)).astype(np.float32)
        out = trainer.rollout_score(tokens, mask, scores)
        sync(out[0])
        return out

    def phase_train(tokens, mask, logprobs, values, rewards, warmup=False):
        """The chunk trains as C/B donated sub-batches × ppo_epochs steps —
        the orchestrator's chunk_size/batch_size split. Warmup compiles with
        just the first sub-batch (all sub-batches share one program)."""
        tk, mk, lp, v, r = (np.asarray(x) for x in (tokens, mask, logprobs, values, rewards))
        for s in range(0, B if warmup else C, B):
            sl = slice(s, s + B)
            batch = trainer.put_batch(
                PPORLBatch(
                    query_tensors=tk[sl, :P],
                    response_tensors=tk[sl, P:],
                    logprobs=lp[sl],
                    values=v[sl],
                    rewards=r[sl],
                    response_mask=mk[sl, P:],
                    query_mask=mk[sl, :P],
                )
            )
            for _ in range(config.method.ppo_epochs):
                trainer.state, stats = trainer.train_step(trainer.state, batch)
        sync(trainer.state.params)

    # Warmup / compile all three programs once.
    tokens, mask = phase_generate()
    logprobs, values, rewards, _ = phase_score(tokens, mask)
    phase_train(tokens, mask, logprobs, values, rewards, warmup=True)

    iters = iters if iters is not None else int(os.environ.get("BENCH_ITERS", "3"))
    t_gen = t_score = t_train = 0.0
    t0 = time.time()
    for _ in range(iters):
        t = time.time()
        tokens, mask = phase_generate()
        t_gen += time.time() - t
        t = time.time()
        logprobs, values, rewards, _ = phase_score(tokens, mask)
        t_score += time.time() - t
        t = time.time()
        phase_train(tokens, mask, logprobs, values, rewards)
        t_train += time.time() - t
    elapsed = time.time() - t0

    n_chips = jax.device_count()
    samples = iters * C
    sps_per_chip = samples / elapsed / n_chips

    # ---- modeled FLOPs (see lm_flops) -------------------------------------
    L, d, V = n_layer, d_model, vocab
    resp = T - P + 1  # logits region [P-1, T)
    kv_train = T / 2  # causal average
    fwd_train = lm_flops(L, d, V, B * T, kv_train, B * resp, value_head=True)
    # bwd = activation-grad pass over everything + weight-grad pass over the
    # trainable fraction (stop_gradient skips frozen weight grads).
    f_train = (unfrozen * 12 * d * d + 2 * V * d) / (L * 12 * d * d + 2 * V * d)
    train_step = fwd_train * (2.0 + f_train)
    train_flops = config.method.ppo_epochs * (C // B) * train_step
    # scoring: policy fwd + frozen branch replay over `unfrozen` layers
    score_flops = lm_flops(L, d, V, C * T, kv_train, C * resp, value_head=True) + lm_flops(
        unfrozen, d, V, C * T, kv_train, C * resp
    )
    # generation: prefill + R single-token decode steps (kv grows P..T)
    gen_flops = lm_flops(L, d, V, C * P, P / 2, C) + lm_flops(
        L, d, V, C * R, (P + T) / 2, C * R
    )
    iter_flops = gen_flops + score_flops + train_flops

    peak, bw_gbps, kind = detect_chip()
    train_tflops = train_flops * iters / max(t_train, 1e-9) / n_chips / 1e12
    iter_tflops = iter_flops * iters / max(elapsed, 1e-9) / n_chips / 1e12

    out = {
        "metric": f"ppo_samples_per_sec_per_chip[{name},seq{T},prefill{P}+decode{R},chunk{C},b{B}]",
        "value": round(sps_per_chip, 3),
        # No measured Accelerate-GPU reference exists in this environment
        # (BASELINE.md) — null, not a fabricated ratio.
        "vs_baseline": None,
        "unit": "samples/s/chip",
        "device_kind": kind,
        "n_chips": n_chips,
        "phase_seconds_per_iter": {
            "generate": round(t_gen / iters, 3),
            "score": round(t_score / iters, 3),
            "train": round(t_train / iters, 3),
        },
        "train_tflops_per_chip": round(train_tflops, 2),
        "iter_tflops_per_chip": round(iter_tflops, 2),
        "peak_bf16_tflops": peak,
        "train_mfu_pct": round(100 * train_tflops / peak, 2),
        "iter_mfu_pct": round(100 * iter_tflops / peak, 2),
    }

    # ---- decode HBM utilization (the falsifiable form of "decode runs at
    # its bandwidth floor"): modeled bytes the decode loop must move —
    # weights re-read every step + growing KV-cache reads/writes — over the
    # measured decode seconds. Decode time = generate phase minus a modeled
    # prefill (prefill FLOPs at the measured TRAIN MFU — both are
    # large-batch matmul phases). 100% ≈ the roofline; the gap is the
    # remaining W8/int8-KV headroom.
    if t_gen > 0:
        w8 = bool(config.model.decode_weight_quant)
        wb = 1.0 if w8 else 2.0  # int8 trunk kernels vs bf16
        kvb = 1.0 if config.model.kv_cache_quant else 2.0
        # per-step weight reads: trunk matmuls + lm_head (batch C shares one
        # read); wte is a C-row gather — negligible.
        step_weight_bytes = (L * 12 * d * d + V * d) * wb
        # KV reads grow P→T over the R steps (keys+values), one write/step.
        kv_bytes = C * L * 2 * d * kvb * (R * (P + T) / 2 + R)
        decode_bytes = R * step_weight_bytes + kv_bytes
        prefill_flops = lm_flops(L, d, V, C * P, P / 2, C)
        mfu = max(train_tflops / peak, 1e-3)
        t_prefill = prefill_flops / (peak * 1e12 * mfu)
        t_decode = max(t_gen / iters - t_prefill, 1e-6)
        out["decode_hbm_util_pct"] = round(
            100.0 * decode_bytes / t_decode / (bw_gbps * 1e9), 1
        )
        out["decode_hbm_model"] = {
            "peak_hbm_gbps": bw_gbps,
            "decode_seconds_modeled": round(t_decode, 3),
            "prefill_seconds_modeled": round(t_prefill, 3),
            "weight_bytes_per_step_gb": round(step_weight_bytes / 1e9, 3),
            "kv_bytes_total_gb": round(kv_bytes / 1e9, 3),
        }
    if orchestrator and os.environ.get("BENCH_ORCH", "1") == "1":
        orch_out = bench_orchestrator(trainer, C, P, vocab)
        out["orchestrator"] = orch_out
        # THE HEADLINE IS THE PRODUCTION PATH: full-cadence throughput with
        # rollouts going through the REAL pipelined (+fused) orchestrator —
        # chunk rollout time from the orchestrator measurement + the measured
        # train phase. The serialized-phase loop measured above (unfused
        # scorer, full sync between phases) is kept as the ablation field.
        rollout_s = C / max(orch_out["samples_per_sec_per_chip"] * n_chips, 1e-9)
        production = C / (rollout_s + t_train / iters) / n_chips
        out["ablation_serialized_unfused_samples_per_sec_per_chip"] = out["value"]
        out["value"] = round(production, 3)
        out["metric"] = out["metric"].replace(
            "ppo_samples_per_sec_per_chip", "ppo_production_samples_per_sec_per_chip"
        )
        # iteration MFU at the production cadence. With fused stats the
        # scoring pass is a ref-branch replay only — model THAT flop count,
        # not the unfused full re-forward, so the MFU is not inflated by a
        # faster wall clock against phantom FLOPs.
        if orch_out.get("fused_rollout_stats"):
            prod_score_flops = lm_flops(unfrozen, d, V, C * T, kv_train, C * resp)
        else:
            prod_score_flops = score_flops
        prod_flops = gen_flops + prod_score_flops + train_flops
        prod_iter_tflops = prod_flops / max(rollout_s + t_train / iters, 1e-9) / n_chips / 1e12
        out["production_iter_mfu_pct"] = round(100 * prod_iter_tflops / peak, 2)
    return out


def bench_orchestrator(trainer, C, P, vocab):
    """Measure the PIPELINED rollout path (PPOOrchestrator.make_experience:
    the next chunk's generation is dispatched before the current chunk's
    decode + host reward_fn + scoring) against the SAME work run serialized
    (full device sync between every phase). The delta is the overlap the
    orchestrator design buys; reported as overlap_gain_pct.

    The host reward here is a real (cheap) numpy pass over the decoded token
    rows; BENCH_HOST_MS adds emulated heavier host scoring (e.g. a sentiment
    model) per chunk to probe how the gain scales with host cost."""
    import jax

    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline

    host_ms = float(os.environ.get("BENCH_HOST_MS", "0"))
    rng = np.random.default_rng(7)

    def reward_fn(rows):
        if host_ms:
            time.sleep(host_ms / 1e3)
        return [float(np.mean(np.asarray(r, np.float32)) / vocab) for r in rows]

    prompts = [list(map(int, rng.integers(2, vocab, size=P))) for _ in range(C)]
    pipeline = PromptPipeline(prompts, None, max_prompt_length=P)
    orch = PPOOrchestrator(trainer, pipeline, reward_fn, chunk_size=C)
    n_chunks = int(os.environ.get("BENCH_ORCH_CHUNKS", "3"))
    rows_per_chunk = C // jax.process_count()
    sync = device_sync

    # Warmup: one pipelined pass compiles generate+score for this shape.
    trainer.store.clear_history()
    orch.make_experience(rows_per_chunk)

    trainer.store.clear_history()
    t0 = time.time()
    orch.make_experience(n_chunks * rows_per_chunk)
    t_pipelined = time.time() - t0

    def serialized_pass(fused: bool) -> float:
        """The same chunks with hard syncs between every phase (the
        reference's serial structure, reference:
        trlx/orchestrator/ppo_orchestrator.py:58-110); `fused` picks the
        in-decode-stats scorer vs the full policy re-forward."""
        trainer.store.clear_history()
        t0 = time.time()
        for _ in range(n_chunks):
            # Same prompt pipeline as every other pass — the comparison must
            # time identical work, not different prompt sets.
            tokens, mask, p_len, aux = orch._generate_next_chunk(fused=fused)
            sync(tokens)
            tokens_h, mask_h = trainer.to_local_host((tokens, mask))
            scores = np.asarray(reward_fn(trainer.decode(tokens_h, mask_h)), np.float32)
            if aux is not None:
                outs = trainer.rollout_score_fused(tokens, mask, scores, aux)
            else:
                outs = trainer.rollout_score(tokens, mask, scores)
            sync(outs[0])
            logprobs, values, rewards, _ = trainer.to_local_host(outs)
            trainer.store.push_batch(
                {
                    "query_tensors": tokens_h[:, :p_len],
                    "query_mask": mask_h[:, :p_len],
                    "response_tensors": tokens_h[:, p_len:],
                    "response_mask": mask_h[:, p_len:],
                    "logprobs": logprobs,
                    "values": values,
                    "rewards": rewards,
                }
            )
        trainer.store.clear_history()
        return time.time() - t0

    fused_on = bool(getattr(trainer, "fused_rollout", False))
    # serialized with the SAME scorer the pipelined path used → isolates the
    # overlap gain; serialized unfused → isolates the fused-scoring gain.
    t_serial = serialized_pass(fused=fused_on)
    t_serial_unfused = serialized_pass(fused=False) if fused_on else t_serial

    samples = n_chunks * C
    # All *_gain_pct fields are THROUGHPUT (rate) gains: rate_a/rate_b − 1.
    out = {
        "samples_per_sec_per_chip": round(samples / t_pipelined / jax.device_count(), 3),
        "serialized_samples_per_sec_per_chip": round(samples / t_serial / jax.device_count(), 3),
        "overlap_gain_pct": round(100.0 * (t_serial / max(t_pipelined, 1e-9) - 1.0), 2),
        "fused_rollout_stats": fused_on,
        "host_ms_emulated_per_chunk": host_ms,
        "n_chunks": n_chunks,
    }
    if fused_on:
        out["serialized_unfused_samples_per_sec_per_chip"] = round(
            samples / t_serial_unfused / jax.device_count(), 3
        )
        out["fused_scoring_gain_pct"] = round(
            100.0 * (t_serial_unfused / max(t_serial, 1e-9) - 1.0), 2
        )
    return out


def run_one_ilql(cand, iters=None):
    """ILQL full-cadence bench (the reference's second method had no perf
    story until now — capability: trlx/model/accelerate_ilql_model.py:50-156,
    trlx/model/nn/ilql_models.py:162-251):

    - train samples/s/chip + modeled MFU over the jitted ILQL step (trunk +
      double vocab-wide Q heads + target heads + V head + AWAC logits) at
      the reference cadence incl. the jitted Polyak target sync every
      `steps_for_target_q_sync` steps,
    - advantage-steered decode tokens/s/chip (target-Q steering
      `pi_beta + beta*(Q−V)`, top-k, in-loop stat collection).

    Dataset is synthetic full-length token rows (compute, not learning, is
    under measurement; learning gates live in tests/test_e2e.py)."""
    import jax

    from trlx_tpu.orchestrator.offline_orchestrator import OfflineOrchestrator
    from trlx_tpu.trainer.api import default_config
    from trlx_tpu.trainer.ilql import ILQLTrainer

    name, n_layer, d_model, n_head, vocab, P, R, B, unfrozen, C = cand[:10]
    # ILQL-specific knobs (the BENCH_PROMPT/BENCH_DECODE PPO knobs don't
    # apply — ILQL's cadence is short-sequence offline, ILQL_SIZES).
    B = int(os.environ.get("BENCH_ILQL_BATCH", B))
    n_dev = jax.device_count()
    B = ((B + n_dev - 1) // n_dev) * n_dev
    T = P + R

    config = default_config("ilql")
    config.model.model_path = ""
    config.model.tokenizer_path = ""
    config.model.num_layers_unfrozen = -1  # reference ILQL default: all train
    config.model.model_arch = {
        "vocab_size": vocab,
        "n_layer": n_layer,
        "n_head": n_head,
        "d_model": d_model,
        "max_position": max(2048, T),
        "eos_token_id": 0,
        "pos_type": "rotary",
        "rotary_dim": 64 if d_model // n_head >= 64 else d_model // n_head,
        "parallel_residual": True,
        "fused_qkv": False,
        "qkv_bias": False,
        "out_bias": False,
        "tie_word_embeddings": False,
        "extra": {"lm_head_bias": True},
    }
    config.model.remat = d_model >= 4096 if os.environ.get("BENCH_REMAT") is None else os.environ.get("BENCH_REMAT") == "1"
    config.model.kv_cache_quant = os.environ.get("BENCH_KV_QUANT", "1") == "1"
    if name.endswith("-bf16"):
        config.model.param_dtype = "bfloat16"
    config.train.batch_size = B
    config.train.seq_length = T
    config.train.mesh = [-1, 1, 1, 1]
    config.method.gen_kwargs = {
        "prompt_length": P,
        "max_new_tokens": R,
        "min_new_tokens": R,
        "top_k": 20,
    }
    trainer = ILQLTrainer(config)

    rng = np.random.default_rng(0)
    samples = [rng.integers(2, vocab, size=(T,)).astype(np.int32) for _ in range(2 * B)]
    rewards = rng.normal(size=(2 * B,)).astype(np.float32).tolist()
    OfflineOrchestrator(trainer).make_experience(samples, rewards)
    batch = next(iter(trainer.store.create_loader(B, shuffle=True)))
    device_batch = trainer.put_batch(batch)

    sync_every = max(int(config.method.steps_for_target_q_sync), 1)

    def train_steps(n):
        for _ in range(n):
            trainer.state, stats = trainer.train_step(trainer.state, device_batch)
            trainer.iter_count += 1
            trainer.post_backward_callback(stats)  # Polyak sync at cadence
        device_sync(trainer.state.params)

    train_steps(1)  # compile
    steps = (iters if iters is not None else int(os.environ.get("BENCH_ITERS", "3"))) * max(
        4, sync_every
    )
    t0 = time.time()
    train_steps(steps)
    t_train = time.time() - t0

    prompt_ids = rng.integers(2, vocab, size=(B, P)).astype(np.int32)
    pmask = np.ones((B, P), dtype=np.int32)
    tokens, _ = trainer.rollout_generate(prompt_ids, pmask)  # compile
    device_sync(tokens)
    dec_iters = 2
    t0 = time.time()
    for _ in range(dec_iters):
        tokens, _ = trainer.rollout_generate(prompt_ids, pmask)
        device_sync(tokens)
    t_dec = (time.time() - t0) / dec_iters

    # Plain-sampling ablation: the same model/params/shapes WITHOUT advantage
    # steering (no Q/V carry, no per-step head evals, default logit chain) —
    # the measured price of ILQL's steered decode vs vanilla sampling.
    from trlx_tpu.ops.generate import make_generate_fn as _mk_gen

    plain_fn = _mk_gen(trainer.model, trainer.gen_cfg)
    swapped = {"params": {**trainer.state.params, **trainer.state.extras}}
    batch_io = trainer.put_batch({"i": prompt_ids, "m": pmask})
    ptok, _ = plain_fn(swapped, batch_io["i"], batch_io["m"], trainer.next_rng())  # compile
    device_sync(ptok)
    t0 = time.time()
    for _ in range(dec_iters):
        ptok, _ = plain_fn(swapped, batch_io["i"], batch_io["m"], trainer.next_rng())
        device_sync(ptok)
    t_plain = (time.time() - t0) / dec_iters

    n_chips = jax.device_count()
    sps_per_chip = steps * B / t_train / n_chips
    decode_tps_per_chip = B * R / t_dec / n_chips

    # ---- modeled FLOPs. Per-token head MACs (d→2d→vocab MLP): online Q
    # heads train (fwd+bwd ≈ 3x fwd), target heads are fwd-only, V head
    # trains; trunk is fully trainable here (num_layers_unfrozen = -1).
    L, d, V = n_layer, d_model, vocab
    mac_q = 2 * d * d + 2 * d * V
    mac_v = 2 * d * d + 2 * d
    trunk_fwd = lm_flops(L, d, V, B * T, T / 2, B * T)
    # trunk fwd+bwd ≈ 3x fwd; heads: 2 online Q at 3x, 2 target Q at 1x
    # (fwd only, no grads), V head at 3x — all per token, x2 FLOP/MAC.
    step_flops = 3.0 * trunk_fwd + 2.0 * B * T * (3 * 2 * mac_q + 1 * 2 * mac_q + 3 * mac_v)
    train_tflops = step_flops * steps / max(t_train, 1e-9) / n_chips / 1e12

    peak, bw_gbps, kind = detect_chip()
    out = {
        "metric": f"ilql_train_samples_per_sec_per_chip[{name},seq{T},b{B}]",
        "value": round(sps_per_chip, 3),
        "unit": "samples/s/chip",
        "device_kind": kind,
        "ilql_decode_tokens_per_s_per_chip": round(decode_tps_per_chip, 1),
        "decode_seconds_per_batch": round(t_dec, 3),
        "train_seconds_per_step": round(t_train / steps, 4),
        "target_q_sync_every": sync_every,
        "ilql_train_tflops_per_chip": round(train_tflops, 2),
        "ilql_train_mfu_pct": round(100 * train_tflops / peak, 2),
    }

    out["plain_decode_tokens_per_s_per_chip"] = round(B * R / t_plain / n_chips, 1)
    out["steering_overhead_pct"] = round(100.0 * (t_dec - t_plain) / max(t_plain, 1e-9), 1)

    # ---- decode HBM roofline (same honesty the PPO point gets): modeled
    # bytes the steered decode must move per batch — trunk + lm_head weights
    # re-read every step, the two (target) Q heads + V head the steering
    # evaluates per step, and the growing KV cache — over the measured decode
    # seconds net of a modeled prefill (prefill FLOPs at the measured train
    # MFU, the same large-batch-matmul proxy the PPO model uses).
    if t_dec > 0:
        # trunk/head param bytes follow param_dtype (ILQL has no W8 path)
        pb = 2.0 if config.model.param_dtype == "bfloat16" else 4.0
        kvb = 1.0 if config.model.kv_cache_quant else 2.0
        head_bytes = 2 * (d * 2 * d + 2 * d * V) + (d * 2 * d + 2 * d)
        step_weight_bytes = (L * 12 * d * d + V * d + head_bytes) * pb
        kv_bytes = B * L * 2 * d * kvb * (R * (P + T) / 2 + R)
        decode_bytes = R * step_weight_bytes + kv_bytes
        prefill_flops = lm_flops(L, d, V, B * P, P / 2, B)
        mfu = max(train_tflops / peak, 1e-3)
        t_prefill = prefill_flops / (peak * 1e12 * mfu)
        t_decode = max(t_dec - t_prefill, 1e-6)
        out["decode_hbm_util_pct"] = round(100.0 * decode_bytes / t_decode / (bw_gbps * 1e9), 1)
        out["decode_hbm_model"] = {
            "peak_hbm_gbps": bw_gbps,
            "decode_seconds_modeled": round(t_decode, 3),
            "prefill_seconds_modeled": round(t_prefill, 3),
            "weight_bytes_per_step_gb": round(step_weight_bytes / 1e9, 3),
            "head_bytes_per_step_gb": round(head_bytes * pb / 1e9, 3),
            "kv_bytes_total_gb": round(kv_bytes / 1e9, 3),
        }
    return out


def _main_probe():
    """Child entry: say which device this process got, as one JSON line."""
    import jax

    dev = jax.devices()[0]
    print(
        json.dumps(
            {
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "n_devices": jax.device_count(),
                "hbm_bytes": int((dev.memory_stats() or {}).get("bytes_limit", 0)),
            }
        )
    )


def _main_one(payload: str):
    """Child entry: run ONE size candidate, print its JSON; exit
    OOM_EXIT_CODE on allocator failure so the parent tries the next size
    with a clean device."""
    from trlx_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    spec = json.loads(payload)
    try:
        result = run_one(tuple(spec["cand"]), **spec["kwargs"])
    except Exception as e:
        if is_oom(e):
            sys.exit(OOM_EXIT_CODE)
        raise
    print(json.dumps(result))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--probe":
        _main_probe()
        sys.exit(0)
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        _main_one(sys.argv[2])
        sys.exit(0)
    try:
        sys.exit(main())
    except BaseException as e:
        # SystemExit(0) falls through finish() above; anything else gets a
        # forensic end record (finish() is idempotent, so a reason already
        # journaled — e.g. "no bench size fit" — stands). A SIGKILL never
        # reaches here, which is exactly what the heartbeat trail is for.
        if _MANIFEST is not None and not isinstance(e, SystemExit):
            _MANIFEST.finish(rc=1, reason=f"{type(e).__name__}: {str(e)[:300]}")
        raise

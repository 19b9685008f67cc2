"""chip_smoke.py — the quickest proof that the PPO main path starts on the chip.

    python chip_smoke.py                # one TPU chip (the default)
    python chip_smoke.py --devices 4    # all four chips of one host, mesh [4,1,1,1]
    python chip_smoke.py --rehearsal    # CPU, tiny shapes, interpret-mode kernels

One process, no network, seeded random weights, no tokenizer. It

1. prints what JAX sees (version, platform, device_kind, device count) and
   exits 2 at once unless the platform is ``tpu`` and the count is the one
   asked for. The CPU rehearsal exists only behind ``--rehearsal``, says
   ``"rehearsal": true`` in its summary, and is never what a missing chip
   turns into;
2. compiles (``.lower().compile()``, ``interpret=False``), runs and compares
   with its einsum / ``naive_logprob`` reference every Pallas entry point in
   ``trlx_tpu/ops/`` at the GPT-J-6B shapes, and prints one line per kernel:
   compiled, max abs error against the tolerance, the route the model layer
   takes for that shape, and the kernel's and the reference's time per call;
   then times a decode step's einsum read of an int8 cache inside a loop,
   whole cache beside the ranged read, at the benchmark's two rollout shapes,
   each with the bytes it must move and their share of the chip's memory rate
   (the baseline a decode kernel would have to beat: ops/kv_read.py);
3. runs PPO through ``trlx_tpu.train(reward_fn=..., prompts=<token ids>,
   config=...)`` — default orchestrator, static rollout, fused rollout
   stats — at GPT-J-6B's published widths (d4096, 16 heads x 256, V50400,
   rotary 64, parallel residual, untied head with bias), 8 of 28 layers,
   bf16 params, 768-token prompts + 256 new tokens, chunk 32, batch 8,
   ppo_epochs 4, 2 layers unfrozen, int8 KV + W8 decode: two rollouts and
   the 32 train steps that follow them;
4. fails, naming the phase, unless every logged loss and reward is finite,
   no step was skipped, a trained parameter changed and a frozen one did
   not, the second iteration compiled nothing, and the kernels traced into
   the real train / prefill / decode programs are the routes step 2 printed.

The last stdout line is ``{"ok": true, "device": {...}}``; the line before
it is the JSON summary. Seconds in the summary are set-up information (how
long a cold or warm start takes), not metrics: the benchmark measures.
No phase is wrapped so that its failure is logged and the run goes on.
"""

import argparse
import contextlib
import functools
import json
import math
import os
import shutil
import sys
import time

import numpy as np

# GPT-J-6B's published widths (EleutherAI/gpt-j-6b's config.json), depth cut
# to 8 of 28 layers so that the state fits one 16 GB chip: the benchmark's
# `benchmark/configs/gptj-6b-l8.json`, 2.0B parameters, int8 KV + W8; REHEARSAL is the same program small
# enough for interpret-mode kernels on a CPU.
FLAGSHIP = dict(
    d_model=4096, n_head=16, vocab=50400, n_layer=8, rotary_dim=64,
    prompt=768, new_tokens=256, chunk=32, batch=8, ppo_epochs=4, unfrozen=2,
    n_prompts=64,
)
REHEARSAL = dict(
    d_model=256, n_head=2, vocab=640, n_layer=2, rotary_dim=32,
    prompt=24, new_tokens=8, chunk=8, batch=4, ppo_epochs=2, unfrozen=1,
    n_prompts=16,
)
# The decode read's second timed shape (rows, slots, head width; 16 heads as
# above): the `gptneo1.3b.ppo-256x256` cell's int8 cache.
READ_SHAPE_NARROW_HEADS = (64, 512, 128)
SEED = 0
# Kernel-vs-reference bound, set beforehand from the dtype: both sides read
# the same bf16 operands and accumulate in fp32, so what separates them is
# bf16 rounding of intermediates (probabilities, the output cast) — a few
# eps of the largest reference value; gradients chain two such products.
BF16_EPS = 2.0 ** -8
FWD_TOL = 4 * BF16_EPS
BWD_TOL = 16 * BF16_EPS
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--devices", type=int, default=1,
                   help="chips to drive from this one process (mesh [N,1,1,1])")
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU run at a tiny size with interpret-mode kernels")
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chip_smoke_out"),
        help="directory for logs and the summary")
    return p.parse_args(argv)


def place_process(args):
    """Decide, before JAX starts, which devices this process may see.

    make_mesh takes every visible device and refuses a smaller mesh, so a
    one-chip run on a four-chip host is only possible by hiding the other
    chips from the process. Values already in the environment win."""
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={args.devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    elif args.devices == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


@contextlib.contextmanager
def phase(name, seconds):
    t0 = time.time()
    print(f"[chip_smoke] phase {name} ...", flush=True)
    try:
        yield
    except BaseException:
        print(f"[chip_smoke] FAILED in phase: {name}", file=sys.stderr, flush=True)
        raise
    seconds[name] = round(time.time() - t0, 1)
    print(f"[chip_smoke] phase {name} ok ({seconds[name]} s)", flush=True)


class CompileLog:
    """Every backend compile request of the process (jax.monitoring): wall
    time it ended, program name, seconds; plus persistent-cache hits."""

    def __init__(self):
        self.events = []
        self.cache_hits = 0

    def install(self):
        import jax

        def on_duration(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.events.append((time.time(), kw.get("fun_name", "?"), float(duration)))

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def seconds(self):
        return round(sum(e[2] for e in self.events), 1)

    def between(self, t0, t1):
        return [(name, round(d, 2)) for t, name, d in self.events if t0 < t <= t1]


# --------------------------------------------------------------------------
# Kernel phase
# --------------------------------------------------------------------------


def _time_us(fn, *args, iters=5):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e6)


def _max_err(got, want, where=None):
    import jax
    import jax.numpy as jnp

    got = [g.astype(jnp.float32) for g in jax.tree_util.tree_leaves(got)]
    want = [w.astype(jnp.float32) for w in jax.tree_util.tree_leaves(want)]
    err = scale = 0.0
    for g, w in zip(got, want):
        if not bool(jnp.isfinite(g).all()):
            return float("inf"), 1.0
        d = jnp.abs(g - w)
        if where is not None and where.shape == d.shape[: where.ndim]:
            m = where.reshape(where.shape + (1,) * (d.ndim - where.ndim))
            d, w = jnp.where(m, d, 0.0), jnp.where(m, w, 0.0)
        err = max(err, float(jnp.max(d)))
        scale = max(scale, float(jnp.max(jnp.abs(w))))
    return err, max(scale, 1e-6)


def _check_kernel(name, kernel_fn, ref_fn, args, tol, route, where=None, timed=True):
    """compile → run → compare; returns the verdict dict and prints it.
    Times per call are taken on the chip only (`timed`): an interpret-mode
    time on a CPU says nothing about the kernel."""
    import jax

    t0 = time.time()
    compiled = jax.jit(kernel_fn).lower(*args).compile()
    compile_s = round(time.time() - t0, 1)
    got = compiled(*args)
    ref_jit = jax.jit(ref_fn)
    err, scale = _max_err(got, ref_jit(*args), where)
    verdict = {
        "kernel": name, "compiled": True, "compile_s": compile_s,
        "max_abs_err": float(f"{err:.3g}"), "tol": float(f"{tol * scale:.3g}"),
        "within_tol": bool(err <= tol * scale), "route": route,
    }
    info = "not timed (rehearsal)"
    if timed:
        verdict["kernel_us"] = _time_us(compiled, *args)
        verdict["reference_us"] = _time_us(ref_jit, *args)
        info = f"kernel {verdict['kernel_us']} us, reference {verdict['reference_us']} us"
    print(
        f"[kernel] {name}: compiled=yes ({compile_s} s) max_abs_err={err:.3g} "
        f"(tol {tol * scale:.3g}) route={route} info: {info}",
        flush=True,
    )
    if not verdict["within_tol"]:
        raise SmokeFailure(f"kernel {name}: max abs error {err:.3g} > {tol * scale:.3g}")
    return verdict


def _masks(rng, batch, length):
    """Key validity with a left-padded row (0) and a fully masked row (1)."""
    valid = np.ones((batch, length), dtype=bool)
    valid[0, : length // 5] = False
    valid[1, :] = False
    if batch > 2:
        valid[2, : int(rng.integers(1, length // 2))] = False
    return valid


def _time_ranged_read(q, cache, bias, T, scale, steps=64):
    """What a decode kernel would have to beat: XLA's read inside a loop that
    carries the int8 cache, as the generate program's does (alone, the same
    read compiles to another program and takes two to three times as long).
    Each step writes the frontier's slot and reads: the whole cache, as before
    PR 24, beside the program's own read (ops/kv_read.py) with the frontier in
    the middle of the cache. Beside each time: the bytes that read must move
    (the int8 K and V and their scales over its keys) and the share of the
    chip's memory rate they amount to at that time (the step's write and
    softmax are in the time and not in the bytes)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.observability.devicemon import chip_peaks
    from trlx_tpu.ops.kv_read import attend_range, kv_read_bucket, kv_read_ranges, ranged_read

    def loop(ranged):
        def body(i, carry):
            q, cache = carry
            index = T // 2 + i % 8  # traced, like the generate loop's write offset
            cache = tuple(jax.lax.dynamic_update_slice_in_dim(a, a[:, :1], index, axis=1) for a in cache)
            if ranged:
                out = ranged_read(T, 1, index)(q, cache, bias, scale, jnp.bfloat16)
            else:
                out = attend_range(q, cache, bias, 0, T, scale, jnp.bfloat16)
            # the next query depends on this read: nothing of it can be hoisted
            return q + out * jnp.bfloat16(1e-3), cache

        return jax.jit(lambda q, cache: jax.lax.fori_loop(0, steps, body, (q, cache))[0])

    per_step = lambda ranged: round(_time_us(loop(ranged), q[:, None], cache, iters=3) / steps)
    bias = bias[:, None, None, :]
    lo, hi = kv_read_ranges(T)[(T // 2) // kv_read_bucket(T)]
    hbm_bytes_per_us = chip_peaks(jax.devices()[0].device_kind)[1] * 1e3
    key_bytes = sum(a.nbytes for a in cache) // T  # one slot of every row: K, V, both scales
    verdict = {
        "kernel": f"xla read in a loop, int8 cache [{','.join(map(str, cache[0].shape))}]",
        "compiled": True, "within_tol": True, "route": "xla einsum (ranged read)",
        "whole_cache_us": per_step(False), "range": [lo, hi], "range_us": per_step(True),
    }
    for name, keys in (("whole_cache", T), ("range", hi - lo)):
        verdict[f"{name}_bytes"] = keys * key_bytes
        verdict[f"{name}_hbm_share_pct"] = round(100 * keys * key_bytes / hbm_bytes_per_us / verdict[f"{name}_us"], 1)
    print(
        f"[kernel] {verdict['kernel']}: route={verdict['route']} info: a step reading the whole cache "
        f"{verdict['whole_cache_us']} us for {verdict['whole_cache_bytes'] / 1e6:.1f} MB "
        f"({verdict['whole_cache_hbm_share_pct']}% of {hbm_bytes_per_us / 1e3:.0f} GB/s), the ranged read at frontier "
        f"{T // 2} ([{lo},{hi})) {verdict['range_us']} us for {verdict['range_bytes'] / 1e6:.1f} MB "
        f"({verdict['range_hbm_share_pct']}%)",
        flush=True,
    )
    return verdict


def kernel_phase(size, interpret):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.lm import LMConfig, flash_eligible, make_attn_bias, quantize_kv
    from trlx_tpu.ops import fused_logprob as fl
    from trlx_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(SEED)
    h, d = size["n_head"], size["d_model"] // size["n_head"]
    D, V, B, C = size["d_model"], size["vocab"], size["batch"], size["chunk"]
    T = size["prompt"] + size["new_tokens"]
    scale = 1.0 / math.sqrt(d)
    bf16 = jnp.bfloat16
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), bf16)
    check = functools.partial(_check_kernel, timed=not interpret)
    verdicts = []

    # ---- flash attention, forward and both backward kernels --------------
    q, k, v, w_out = (normal(B, T, h, d) for _ in range(4))
    valid = _masks(rng, B, T)
    kv_mask = jnp.asarray(valid, jnp.float32)
    # Positions whose output the model uses: valid queries of rows that have
    # any key. Pad positions and fully masked rows are compared for
    # finiteness only (the kernel skips key blocks the einsum weighs).
    where = jnp.asarray(valid)

    def flash_fwd(q, k, v):
        return flash_attention(q, k, v, kv_mask, scale=scale, causal=True, interpret=interpret)

    def einsum_fwd(q, k, v):
        bias = make_attn_bias(kv_mask, T, 0)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        p = jax.nn.softmax(s * scale + bias, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

    def grads_of(fwd):
        def loss(q, k, v):
            o = fwd(q, k, v).astype(jnp.float32)
            return jnp.sum(jnp.where(where[:, :, None, None], o * w_out.astype(jnp.float32), 0.0))

        return jax.grad(loss, argnums=(0, 1, 2))

    lm_cfg = LMConfig(vocab_size=V, n_layer=size["n_layer"], n_head=h, d_model=D)
    flash_route = (
        "pallas flash" if flash_eligible(lm_cfg, T, False) else "xla einsum (rule: flash_eligible)"
    )
    shape = f"q/k/v [{B},{T},{h},{d}] bf16 causal"
    verdicts.append(check(
        f"flash_attention fwd {shape}", flash_fwd, einsum_fwd, (q, k, v), FWD_TOL, flash_route, where))
    verdicts.append(check(
        f"flash_attention bwd {shape}", grads_of(flash_fwd), grads_of(einsum_fwd), (q, k, v),
        BWD_TOL, flash_route))
    del q, k, v, w_out

    # ---- fused log-prob head, forward and both backward kernels ----------
    N = B * (size["new_tokens"] + 1)
    x = normal(N, D)
    w = jnp.asarray(rng.normal(size=(D, V)) / math.sqrt(D), bf16)
    b = jnp.asarray(rng.normal(size=(V,)) * 0.1, bf16)
    y = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(3, N)), jnp.float32)

    def fused(x, w, b):
        return fl.fused_logprob(x, w, y, b, tied=False, interpret=interpret)

    def naive(x, w, b):
        return fl.naive_logprob(x, w, y, b, tied=False)

    def head_grads(fwd):
        def loss(x, w, b):
            return sum(jnp.sum(o * gi) for o, gi in zip(fwd(x, w, b), g))

        return jax.grad(loss, argnums=(0, 1, 2))

    fused_route = (
        "pallas fused_logprob"
        if fl.fused_logprob_eligible(D, V) and fl.fused_logprob_supported(N, D, V, False, True, bf16)
        else "xla log_softmax (rule: fused_logprob_eligible)"
    )
    shape = f"N={N} D={D} V={V} bias"
    verdicts.append(check(
        f"fused_logprob fwd {shape}", fused, naive, (x, w, b), FWD_TOL, fused_route))
    verdicts.append(check(
        f"fused_logprob bwd {shape}", head_grads(fused), head_grads(naive), (x, w, b),
        BWD_TOL, fused_route))
    del x, w, b, g

    # ---- a decode step's read: XLA's einsum, whole cache and ranged ------
    # at the benchmark's two rollout shapes: this size's rows (GPT-J's heads,
    # 256 wide), then GPT-Neo's (64 rows of 512 slots, heads 128 wide)
    for rows, slots, width in [] if interpret else [(C, T, d), READ_SHAPE_NARROW_HEADS]:
        (kq, ks), (vq, vs) = (quantize_kv(normal(rows, slots, h, width)) for _ in range(2))
        bias = jnp.asarray(np.where(_masks(rng, rows, slots), 0.0, -1e9), jnp.float32)
        verdicts.append(_time_ranged_read(
            normal(rows, h, width), (kq, vq, ks, vs), bias, slots, 1.0 / math.sqrt(width)))
    return verdicts


# --------------------------------------------------------------------------
# PPO phase
# --------------------------------------------------------------------------


def ppo_config(size, devices, out_dir, rehearsal):
    from trlx_tpu.trainer.api import default_config

    P, R = size["prompt"], size["new_tokens"]
    config = default_config("ppo")
    config.model.model_path = ""
    config.model.tokenizer_path = ""
    config.model.num_layers_unfrozen = size["unfrozen"]
    config.model.model_arch = {
        "vocab_size": size["vocab"], "n_layer": size["n_layer"], "n_head": size["n_head"],
        "d_model": size["d_model"], "max_position": max(2048, P + R), "eos_token_id": 0,
        "pos_type": "rotary", "rotary_dim": size["rotary_dim"], "parallel_residual": True,
        "fused_qkv": False, "qkv_bias": False, "out_bias": False,
        "tie_word_embeddings": False, "extra": {"lm_head_bias": True},
    }
    config.model.param_dtype = "bfloat16"
    config.model.remat = not rehearsal
    config.model.kv_cache_quant = True
    config.model.decode_weight_quant = True
    config.train.seed = SEED
    config.train.batch_size = size["batch"]
    config.train.seq_length = P + R
    config.train.mesh = [devices, 1, 1, 1]
    config.train.epochs = 2
    config.train.total_steps = 10**6
    config.train.log_interval = 1
    config.train.eval_interval = 10**6
    # no checkpoint: the full state is 5.7 GB in files of over a gigabyte,
    # which a checking machine with a file-size limit refuses (EFBIG)
    config.train.checkpoint_interval = 0
    config.train.checkpoint_dir = os.path.join(out_dir, "ppo")
    config.method.gen_kwargs = {
        "prompt_length": P, "max_new_tokens": R, "min_new_tokens": R,
        "do_sample": True, "top_k": 0, "top_p": 1.0,
    }
    config.method.chunk_size = size["chunk"]
    config.method.num_rollouts = size["chunk"]
    config.method.ppo_epochs = size["ppo_epochs"]
    return config


@contextlib.contextmanager
def record_pallas_calls(record):
    """Note, at trace time, every Pallas kernel the real programs contain:
    {(ops module, kernel function): {leading operand shapes}}."""
    from jax.experimental import pallas as pl

    original = pl.pallas_call

    def recording(kernel, *a, **kw):
        inner = original(kernel, *a, **kw)
        fn = getattr(kernel, "func", kernel)
        key = f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__name__}"

        def call(*operands):
            shapes = tuple(tuple(o.shape) for o in operands if hasattr(o, "shape"))
            record.setdefault(key, set()).add(max(shapes, key=math.prod))
            return inner(*operands)

        return call

    pl.pallas_call = recording
    try:
        yield
    finally:
        pl.pallas_call = original


def ppo_phase(size, devices, out_dir, rehearsal, compiles):
    import trlx_tpu
    from trlx_tpu.utils.logging import read_jsonl

    rng = np.random.default_rng(SEED)
    P, V = size["prompt"], size["vocab"]
    # Token-id prompts of uneven length: every chunk has left-padded rows.
    prompts = [
        rng.integers(2, V, size=int(rng.integers(P // 2, P + 1))).tolist()
        for _ in range(size["n_prompts"])
    ]
    reward_calls = []

    def reward_fn(rows):
        reward_calls.append(time.time())
        return [float(np.mean(np.asarray(r, np.float32)) / V) for r in rows]

    config = ppo_config(size, devices, out_dir, rehearsal)
    traced = {}
    with record_pallas_calls(traced):
        trainer = trlx_tpu.train(
            reward_fn=reward_fn, prompts=prompts, eval_prompts=prompts[: size["batch"]],
            config=config,
        )

    steps_per_iter = size["ppo_epochs"] * (size["chunk"] // size["batch"])
    records = [r for r in read_jsonl(os.path.join(config.train.checkpoint_dir, "metrics.jsonl"))
               if "step" in r and "t" in r and "table" not in r and "histogram" not in r]
    by_step = {int(r["step"]): r for r in records if any(k.startswith("loss") for k in r)}
    if sorted(by_step) != list(range(1, 2 * steps_per_iter + 1)):
        raise SmokeFailure(f"expected logged steps 1..{2 * steps_per_iter}, got {sorted(by_step)}")
    watched = {}
    for r in records:
        for key, val in r.items():
            timing = key.endswith("_s") or key.startswith("time/")
            if not timing and any(s in key for s in ("loss", "kl", "score", "reward")):
                watched.setdefault(key, []).append(val)
    if "loss" not in watched or "rollout_mean_score" not in watched:
        raise SmokeFailure(f"no loss/reward keys in metrics.jsonl: {sorted(watched)}")
    bad = {k: v for k, v in watched.items()
           if not all(isinstance(x, (int, float)) and math.isfinite(x) for x in v)}
    if bad:
        raise SmokeFailure(f"non-finite values logged: {bad}")
    if trainer.skipped_steps != 0:
        raise SmokeFailure(f"skipped_steps = {trainer.skipped_steps}")

    # Second iteration = after step 16 was logged, up to step 32's log: the
    # decode-weight refresh, rollout 2, its scoring, and 16 train steps.
    second = compiles.between(by_step[steps_per_iter]["t"], by_step[2 * steps_per_iter]["t"])
    if second:
        raise SmokeFailure(f"second iteration compiled: {second}")
    if len(reward_calls) < 2:
        raise SmokeFailure(f"reward_fn ran {len(reward_calls)} times; expected two rollouts")

    changed = parameter_check(trainer, size)
    return trainer, {
        "logged_steps": len(by_step),
        "skipped_steps": int(trainer.skipped_steps),
        "reward_fn_calls": len(reward_calls),
        "loss": [by_step[1]["loss"], by_step[2 * steps_per_iter]["loss"]],
        "watched_keys": sorted(watched),
        "second_iteration_compiles": len(second),
        "parameters": changed,
        "traced_kernels": {k: sorted(v) for k, v in sorted(traced.items())},
    }


def parameter_check(trainer, size):
    """A trained parameter moved and a frozen one did not, read off leaves
    whose initial value is a constant of the initializer: LayerNorm scale
    (ones) and bias (zeros) of the bottom (frozen) and top (trained) block."""
    import jax

    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    }

    def ln(block, leaf):
        (name,) = [n for n in flat if f"h_{block}/ln_1/{leaf}" in n]
        return np.asarray(jax.device_get(flat[name]), np.float32)

    top = size["n_layer"] - 1
    frozen_same = bool((ln(0, "scale") == 1).all() and (ln(0, "bias") == 0).all())
    trained_moved = float(np.abs(ln(top, "bias")).max())
    if not frozen_same:
        raise SmokeFailure("frozen block h_0's LayerNorm left its initial value")
    if not trained_moved > 0:
        raise SmokeFailure(f"trained block h_{top}'s LayerNorm bias never moved")
    return {"frozen_h_0_ln_unchanged": frozen_same, f"trained_h_{top}_ln_bias_max_abs": trained_moved}


def route_check(verdicts, traced, size):
    """The kernels inside the real programs are the routes the kernel phase
    printed: flash in the train step (its backward kernels, at the train
    batch) and in prefill (forward at the prompt length over the chunk),
    the fused log-prob head in the loss."""
    P, T = size["prompt"], size["prompt"] + size["new_tokens"]
    routed = lambda name: any(v["kernel"].startswith(name) and v["route"].startswith("pallas")
                              for v in verdicts)
    has = lambda key, pred=lambda s: True: any(pred(s) for s in traced.get(key, ()))
    found = {
        "flash in train step": has("flash_attention._bwd_dq_kernel", lambda s: s[1] == T),
        "flash in prefill": has("flash_attention._fwd_kernel", lambda s: s[1] == P),
        "fused log-prob in loss": has("fused_logprob._bwd_dx_kernel"),
    }
    want = {
        "flash in train step": routed("flash_attention"),
        "flash in prefill": routed("flash_attention"),
        "fused log-prob in loss": routed("fused_logprob"),
    }
    if found != want:
        raise SmokeFailure(f"routes in the real programs {found} != routes printed {want}")
    return found


def spread_check(trainer, devices):
    """Batch rows and bytes are spread over the devices, not sitting on one."""
    import jax

    rows = trainer.config.train.batch_size
    probe = trainer.put_batch({"x": np.zeros((rows, 8), np.float32)})["x"]
    shards = [(str(s.device), tuple(s.data.shape)) for s in probe.addressable_shards]
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    per_device = [
        {"device": str(d), "bytes_in_use": int(s.get("bytes_in_use", 0)),
         "peak_bytes_in_use": int(s.get("peak_bytes_in_use", 0))}
        for d, s in zip(jax.local_devices(), stats)
    ]
    if len({dev for dev, _ in shards}) != devices or any(s[0] != rows // devices for _, s in shards):
        raise SmokeFailure(f"batch rows not spread over {devices} devices: {shards}")
    in_use = [p["bytes_in_use"] for p in per_device]
    if any(stats) and min(in_use) < 0.5 * max(in_use):
        raise SmokeFailure(f"device memory sits unevenly: {per_device}")
    return {"batch_shards": shards, "per_device": per_device}


def main(argv=None):
    args = parse_args(argv)
    place_process(args)
    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"jax {jax.__version__} jaxlib {jaxlib.__version__} python {sys.version.split()[0]}")
    print(f"platform={dev.platform} device_kind={dev.device_kind} device_count={device['count']}",
          flush=True)
    if not args.rehearsal and dev.platform != "tpu":
        print("chip_smoke: no TPU — JAX reports platform "
              f"{dev.platform!r}; --rehearsal is the CPU run", file=sys.stderr)
        return 2
    if device["count"] != args.devices:
        print(f"chip_smoke: asked for {args.devices} device(s), JAX shows {device['count']}",
              file=sys.stderr)
        return 2

    from trlx_tpu.native import native_available
    from trlx_tpu.parallel.mesh import make_mesh, set_mesh
    from trlx_tpu.utils.compile_cache import ENV_VAR, setup_compile_cache

    cache_dir = setup_compile_cache()
    compiles = CompileLog()
    compiles.install()
    size = REHEARSAL if args.rehearsal else FLAGSHIP
    # a fresh run directory: metrics.jsonl is appended to, and the checks
    # below read it
    shutil.rmtree(os.path.join(args.out, "ppo"), ignore_errors=True)
    os.makedirs(args.out, exist_ok=True)
    print(f"compile cache: {cache_dir} ({ENV_VAR} {'set' if os.environ.get(ENV_VAR) else 'unset'}), "
          f"entries at start: {len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}")
    print(f"native collate available: {native_available()}", flush=True)
    # The mesh the trainer will build, set first so the routes the kernel
    # phase prints are the ones this mesh gets.
    set_mesh(make_mesh([args.devices, 1, 1, 1]))

    seconds = {}
    with phase("kernels", seconds):
        verdicts = kernel_phase(size, interpret=args.rehearsal)
    kernel_compile_s = compiles.seconds()
    with phase("ppo", seconds):
        trainer, ppo = ppo_phase(size, args.devices, args.out, args.rehearsal, compiles)
    with phase("routes", seconds):
        routes = route_check(verdicts, ppo["traced_kernels"], size)
    with phase("spread", seconds):
        spread = spread_check(trainer, args.devices)

    summary = {
        "ok": True, "rehearsal": bool(args.rehearsal), "device": device,
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "model": {k: size[k] for k in ("d_model", "n_head", "vocab", "n_layer", "prompt",
                                       "new_tokens", "chunk", "batch", "ppo_epochs", "unfrozen")},
        "mesh": [args.devices, 1, 1, 1],
        "cache_dir": cache_dir,
        "cache_entries": len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
        "compile": {"requests": len(compiles.events), "persistent_cache_hits": compiles.cache_hits,
                    "seconds": compiles.seconds(), "kernel_phase_seconds": kernel_compile_s},
        "native_available": bool(native_available()),
        "kernels": verdicts, "ppo": ppo, "routes": routes, "spread": spread,
        "phase_seconds_setup_info": seconds,
    }
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

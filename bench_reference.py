"""Head-to-head: the reference's OWN training loop vs trlx_tpu, CPU, identical data.

Ends three rounds of `vs_baseline: null`. Two acceptance tasks, BOTH methods:

- ILQL (`--method ilql`): the reference's randomwalks example exactly as it
  ships (reference: examples/randomwalks.py:87-109, trlx/trlx.py:61-93)
  through the real Accelerate CPU path, vs trlx_tpu's ILQL on the IDENTICAL
  dataset (same walks/rewards/graph, seed 1000), judged by the reference's
  own optimality metric.
- PPO (`--method ppo`): the reference's flagship method (AcceleratePPOModel +
  hydra frozen branch, reference: trlx/model/accelerate_ppo_model.py) on a
  synthetic char task — reward = fraction of 'a' characters in the response —
  with BOTH sides starting from the IDENTICAL saved init checkpoint and a
  local char-level tokenizer (no network), matched protocol.

Scope: CPU smoke (the reference's torch loop cannot run on a TPU, and the
v4-32 ≥2x gate needs hardware that is not here). Both sides run on the same
CPU: torch eager for the reference, XLA-CPU for trlx_tpu.
JAX compile time is INCLUDED in trlx_tpu's cold wallclock (warm-cache pass
reported separately).

The reference is never edited: import-time stubs for deps absent from this
image (wandb, deepspeed, torchtyping), no-op'd Accelerator tracker methods,
and a `use_cache=False` patch on ModelBranch.forward (transformers>=4.38
removed tuple `presents` from GPT2Block outputs; cache collection has no
effect on logits) — the same shim technique as tests/test_reference_parity.py.
Everything the reference executes is its shipped code.

Usage:
  python bench_reference.py                 # both methods -> HEADTOHEAD.json
  python bench_reference.py --method ilql   # one method only
  python bench_reference.py --side ref ...  # (internal) one side subprocess

bench.py picks up HEADTOHEAD.json to fill `vs_baseline` in the bench JSON.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
REFERENCE_ROOT = "/root/reference"
RESULT_PATH = os.path.join(REPO, "HEADTOHEAD.json")

THRESHOLDS = {"ilql": [0.5, 0.7, 0.8, 0.9], "ppo": [0.05, 0.1, 0.2, 0.3]}
TRAJECTORY_KEY = {"ilql": "optimality", "ppo": "reward"}

# PPO char task: both sides start from the IDENTICAL saved init checkpoint.
# d144/L4 keeps per-step work comparable to the ILQL task's (d144 reference
# example model) — large enough that neither stack is dominated by per-call
# dispatch overhead on this single core.
PPO_CHARS = "abcdefghijklmnopqrstuvwxyz0123456789 .,!?"
PPO_PROTOCOL = dict(
    n_layer=4, d_model=144, n_head=4, vocab=42, seq_length=32,
    batch_size=64, total_steps=300, num_rollouts=128, chunk_size=64,
    ppo_epochs=4, lr_init=1e-3, lr_target=1e-4, init_kl_coef=0.05,
    eval_interval=25, num_layers_unfrozen=2, response_tokens=24,
)


def _ppo_reward_fn(texts):
    r = PPO_PROTOCOL["response_tokens"]
    return [sum(c == "a" for c in t) / float(r) for t in texts]


def _ppo_prompts():
    import numpy as np

    rng = np.random.default_rng(0)
    return ["".join(rng.choice(list("bcdefgh"), size=6)) for _ in range(64)]


def _parse_ours_metrics(ckpt_dir, key, t0):
    """Shared trlx_tpu-side accounting from the tracker's metrics.jsonl:
    (trajectory of `key`, eval seconds, per-step times, phase sums). Eval cost
    counts generate + reward + metric time — the same components the reference
    side's timed evaluate() wrapper excludes from train_s. Phases mirror the
    reference-side wrappers: rollout total / generate-blocked / host reward /
    device scoring / store push, plus optimizer-step and batch-transfer sums."""
    trajectory, eval_components, eval_wall, step_times = [], 0.0, 0.0, []
    makeexp_starts, eval_calls = [], []
    phases = {"rollout": 0.0, "generate": 0.0, "reward": 0.0, "score": 0.0,
              "push": 0.0, "train_steps": 0.0, "data": 0.0, "save": 0.0}
    with open(os.path.join(ckpt_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if key in rec:
                trajectory.append({"t": round(rec["t"] - t0, 2), "value": round(rec[key], 4)})
            eval_components += (
                rec.get("generate_time", 0.0)
                + rec.get("reward_time", 0.0)
                + rec.get("metric_time", 0.0)
            )
            if "eval_wall_time" in rec:
                eval_wall += rec["eval_wall_time"]
                # "t" is the log stamp right after eval finished
                eval_calls.append((rec["t"] - rec["eval_wall_time"], rec["eval_wall_time"]))
            if "step_time" in rec:
                step_times.append(rec["step_time"])
                phases["train_steps"] += rec["step_time"]
                phases["data"] += rec.get("data_time", 0.0)
            if "exp_time" in rec:
                makeexp_starts.append(rec["t"] - rec["exp_time"])
            phases["rollout"] += rec.get("exp_time", 0.0)
            phases["generate"] += rec.get("exp_gen_s", 0.0)
            phases["reward"] += rec.get("exp_reward_s", 0.0)
            phases["score"] += rec.get("exp_score_s", 0.0)
            phases["push"] += rec.get("exp_push_s", 0.0)
            phases["save"] += rec.get("save_time", 0.0)
    # eval_wall_time (whole-call wall, matching the reference side's timed
    # evaluate() wrapper) supersedes the legacy component sum when present.
    eval_s = eval_wall if eval_wall > 0 else eval_components
    phases = {k: round(v, 2) for k, v in phases.items()}
    return trajectory, eval_s, step_times, phases, makeexp_starts, eval_calls


def build_ppo_assets(assets_dir):
    """Identical starting point for both sides: a tiny GPT-2 checkpoint
    (fixed torch seed) + a char-level byte-BPE tokenizer, saved as ordinary
    HF files. The reference loads them with from_pretrained; trlx_tpu streams
    the same safetensors through models/hf_import — so the two frameworks
    train the SAME initial weights."""
    import json as _json

    import torch
    import transformers
    from transformers.models.gpt2.tokenization_gpt2 import bytes_to_unicode

    p = PPO_PROTOCOL
    if os.path.exists(os.path.join(assets_dir, "model.safetensors")):
        return assets_dir
    os.makedirs(assets_dir, exist_ok=True)
    cfg = transformers.GPT2Config(
        n_layer=p["n_layer"], n_embd=p["d_model"], n_head=p["n_head"],
        vocab_size=p["vocab"], n_positions=128,
        bos_token_id=p["vocab"] - 1, eos_token_id=p["vocab"] - 1,
    )
    torch.manual_seed(7)
    transformers.GPT2LMHeadModel(cfg).save_pretrained(assets_dir, safe_serialization=True)
    b2u = bytes_to_unicode()
    vocab = {}
    for ch in PPO_CHARS:
        rep = "".join(b2u[b] for b in ch.encode("utf-8"))
        vocab.setdefault(rep, len(vocab))
    vocab["<|endoftext|>"] = len(vocab)
    assert len(vocab) == p["vocab"], len(vocab)
    with open(os.path.join(assets_dir, "vocab.json"), "w") as f:
        _json.dump(vocab, f)
    with open(os.path.join(assets_dir, "merges.txt"), "w") as f:
        f.write("#version: 0.2\n")
    return assets_dir


# ---------------------------------------------------------------------------
# Reference side


def _install_reference_stubs():
    """Import-time stubs for modules the reference imports but this image
    lacks. Mirrors tests/test_reference_parity.py:58-99; here they stay
    installed for the process lifetime (this subprocess runs nothing else)."""
    import importlib.machinery
    import types

    for name in ("deepspeed", "wandb", "torchtyping"):
        if name in sys.modules:
            continue
        m = types.ModuleType(name)
        m.__spec__ = importlib.machinery.ModuleSpec(name, None)
        sys.modules[name] = m
    ds = sys.modules["deepspeed"]
    ds.comm = types.SimpleNamespace(get_rank=lambda: 0)
    ds.zero = types.SimpleNamespace()

    wb = sys.modules["wandb"]

    class _Blob:
        def __init__(self, *a, **k):
            pass

    wb.Histogram = _Blob
    wb.Table = _Blob

    class _TensorType:
        def __class_getitem__(cls, item):
            return cls

    sys.modules["torchtyping"].TensorType = _TensorType


def _instrument_reference():
    """Harness-side shims (the reference itself is untouched): no-op'd
    Accelerator trackers with a log recorder, AdamW step timestamps
    (full-step steady-state = median inter-step delta, robust to eval-step
    outliers and the same definition as trlx_tpu's per-step step_time), and
    a timed evaluate() wrapper so eval cost is excluded from train_s.
    Returns (logged, eval_seconds, step_stamps). Call after
    _install_reference_stubs()."""
    import torch
    from accelerate import Accelerator

    from trlx.model.accelerate_base_model import AccelerateRLModel

    logged = []
    Accelerator.init_trackers = lambda self, *a, **k: None
    Accelerator.log = lambda self, stats, **k: logged.append((time.time(), dict(stats)))

    step_stamps = []
    orig_opt_step = torch.optim.AdamW.step

    def timed_opt_step(self, *a, **k):
        r = orig_opt_step(self, *a, **k)
        step_stamps.append(time.time())
        return r

    torch.optim.AdamW.step = timed_opt_step

    eval_seconds = [0.0]
    eval_calls = []  # (start, duration) — lets cycle timing subtract evals
    orig_evaluate = AccelerateRLModel.evaluate

    def timed_evaluate(self):
        t = time.time()
        out = orig_evaluate(self)
        eval_calls.append((t, time.time() - t))
        eval_seconds[0] += time.time() - t
        return out

    AccelerateRLModel.evaluate = timed_evaluate
    return logged, eval_seconds, step_stamps, eval_calls


def _cycle_sps(makeexp_starts, eval_calls, samples_per_cycle):
    """Steady-state throughput of the FULL recurring PPO cycle
    (rollout + its train steps + logging), from consecutive make_experience
    start stamps with any eval wall falling inside a cycle subtracted.
    The per-step steady state ignores the rollout phase entirely; this metric
    measures everything that recurs — one-time costs (imports, init, compile)
    fall out because they precede the first stamp or inflate only one cycle
    (the median discards it)."""
    import numpy as np

    if len(makeexp_starts) < 3:
        return None
    starts = list(makeexp_starts)
    cycles = []
    for a, b in zip(starts[:-1], starts[1:]):
        dur = b - a
        dur -= sum(d for (t, d) in eval_calls if a <= t < b)
        cycles.append(dur)
    return round(samples_per_cycle / float(np.median(cycles)), 1)


def _side_result(impl, steps, batch, wall, eval_s, trajectory, final_key, step_seconds,
                 phases=None, cycle_sps=None):
    """Shared result assembly — both sides, both methods, measured under the
    same rules (train_s = wall − eval cost; steady-state = batch / median
    full-step seconds). `phases` carries the matched per-phase attribution:
    rollout total, generate, host reward, device/score forwards — with
    `train_other` derived as train_s − rollout (optimizer steps + data +
    logging) so both sides decompose identically."""
    import numpy as np

    train_s = wall - eval_s
    steady = batch / float(np.median(step_seconds)) if len(step_seconds) else None
    out = {
        "impl": impl,
        "steps": int(steps),
        "batch_size": int(batch),
        "wallclock_s": round(wall, 2),
        "eval_s": round(eval_s, 2),
        "train_s": round(train_s, 2),
        "samples_per_s": round(steps * batch / train_s, 2),
        "steady_state_samples_per_s": round(steady, 1) if steady else None,
        "steady_state_cycle_samples_per_s": cycle_sps,
        final_key: (trajectory[-1]["value"] if trajectory else None),
        "trajectory": trajectory,
    }
    if phases:
        phases = dict(phases)
        if "rollout" in phases:
            phases["score"] = round(
                phases.get("score", max(phases["rollout"] - phases.get("generate", 0.0)
                                        - phases.get("reward", 0.0), 0.0)), 2)
            phases["train_other"] = round(train_s - phases["rollout"], 2)
        out["phase_seconds"] = phases
    return out


def run_reference_side(dataset_path: str, workdir: str) -> dict:
    """Run the reference's ILQL randomwalks example end-to-end via its real
    trlx.train → AccelerateILQLModel → Accelerate CPU path, and save the
    generated dataset for the trlx_tpu side."""
    _install_reference_stubs()
    sys.path.insert(0, REFERENCE_ROOT)

    import importlib.util

    import numpy as np
    import torch

    # The reference's own dataset generator (networkx graph, torch walks).
    spec = importlib.util.spec_from_file_location(
        "ref_randomwalks", os.path.join(REFERENCE_ROOT, "examples", "randomwalks.py")
    )
    ref_rw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref_rw)

    walks, logit_mask, metric_fn = ref_rw.generate_random_walks(seed=1000)
    eval_prompts = torch.arange(1, logit_mask.shape[0]).view(-1, 1)
    lengths = metric_fn(walks)["lengths"]

    # Extract the metric closure's constants so the trlx_tpu side can apply
    # the IDENTICAL optimality formula (best_lengths is not returned).
    free = dict(zip(metric_fn.__code__.co_freevars, (c.cell_contents for c in metric_fn.__closure__)))
    best_lengths = free["best_lengths"].numpy()
    worstlen = int(free["worstlen"])

    np.savez(
        dataset_path,
        walks=np.array([w.numpy() for w in walks], dtype=object),
        rewards=lengths.numpy(),
        logit_mask=logit_mask.numpy(),
        best_lengths=best_lengths,
        worstlen=worstlen,
    )

    logged, eval_seconds, step_stamps, _eval_calls = _instrument_reference()

    # --- the reference example's own __main__, verbatim semantics ---------
    import trlx
    from trlx.data.configs import TRLConfig
    from transformers import GPT2Config

    config = TRLConfig.load_yaml(os.path.join(REFERENCE_ROOT, "configs", "ilql_config.yml"))
    config.train.gen_size = 10
    config.train.epochs = 100
    config.train.learning_rate_init = 1e-3
    config.method.alpha = 0.1
    config.model.tokenizer_path = ""
    config.model.model_path = GPT2Config(n_layer=2, n_embd=144, vocab_size=logit_mask.shape[0])
    config.train.checkpoint_dir = os.path.join(workdir, "ref_ckpts")

    os.chdir(workdir)
    t0 = time.time()
    model = trlx.train(
        dataset=(walks, lengths),
        eval_prompts=eval_prompts,
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    wall = time.time() - t0

    trajectory = [
        {"t": round(t - t0, 2), "value": round(float(torch.as_tensor(s["metrics/optimality"]).mean()), 4)}
        for (t, s) in logged
        if "metrics/optimality" in s
    ]
    return _side_result(
        "reference (trlx v0.2.0, torch eager, Accelerate CPU)",
        model.iter_count, config.train.batch_size, wall, eval_seconds[0],
        trajectory, "final_optimality", np.diff(step_stamps),
    )


# ---------------------------------------------------------------------------
# trlx_tpu side


def run_ours_side(dataset_path: str, workdir: str) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")  # a CPU head-to-head, wherever it is started
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        # Persistent compile cache: the "warm" pass quantifies how much of the
        # cold wallclock is one-time XLA compilation (any long-lived deployment
        # runs warm; the cold number stays the headline).
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import numpy as np

    sys.path.insert(0, REPO)
    from examples.randomwalks import base_config
    import trlx_tpu

    data = np.load(dataset_path, allow_pickle=True)
    walks = [w.astype(np.int32) for w in data["walks"]]
    rewards = data["rewards"].astype(np.float32)
    logit_mask = data["logit_mask"].astype(bool)
    best_lengths = data["best_lengths"].astype(np.float32)
    worstlen = int(data["worstlen"])
    n_nodes = logit_mask.shape[0]

    def metric_fn(samples):
        """The REFERENCE's optimality formula (reference:
        examples/randomwalks.py:62-81) on this side's eval samples, with its
        exact best_lengths; modulo indexing covers fixed-shape eval batches
        that wrap past the 20 unique prompts."""
        lengths = []
        for s in samples:
            s = np.asarray(s).reshape(-1)
            hits = np.nonzero(s == 0)[0]
            if s[-1] == 0 and len(hits):
                lengths.append(-(int(hits[0]) + 1))
            else:
                lengths.append(-100)
        lengths = np.asarray(lengths, np.float32)
        bound = np.where(lengths == -100, worstlen, np.abs(lengths))
        denom = worstlen - best_lengths[np.arange(len(lengths)) % len(best_lengths)]
        opt = (worstlen - bound) / np.maximum(denom, 1e-9)
        return {"lengths": lengths, "optimality": opt}

    config = base_config("ilql", n_nodes, worstlen)
    # Matched protocol: the reference example's effective hyperparameters
    # (reference: configs/ilql_config.yml + examples/randomwalks.py:92-96) so
    # both sides see the same batch size, step count, LR schedule, and ILQL
    # method constants — the comparison is implementation vs implementation.
    config.train.batch_size = 128
    # The reference's DataLoader keeps the last partial batch (8 steps/epoch
    # from 1000 walks); this side's fixed-shape loader drops it (7). 115
    # epochs × 7 = 805, capped at total_steps — both sides run exactly 800
    # optimizer steps at batch 128.
    config.train.epochs = 115
    config.train.total_steps = 800
    config.train.eval_interval = 16
    config.train.learning_rate_init = 1e-3
    config.train.learning_rate_target = 1e-4
    config.method.alpha = 0.1
    config.method.steps_for_target_q_sync = 1
    config.method.betas = [16]
    config.train.checkpoint_dir = os.path.join(workdir, "ours_ckpts")
    eval_prompts = [[i] for i in range(1, n_nodes)]

    t0 = time.time()
    model = trlx_tpu.train(
        dataset=(walks, rewards),
        eval_prompts=eval_prompts,
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    wall = time.time() - t0

    trajectory, eval_s, step_times, phases, _, _ = _parse_ours_metrics(
        config.train.checkpoint_dir, "metrics/optimality", t0
    )
    return _side_result(
        "trlx_tpu (JAX/XLA CPU, jit train step)",
        model.iter_count, config.train.batch_size, wall, eval_s,
        trajectory, "final_optimality", step_times, phases,
    )


# ---------------------------------------------------------------------------
# PPO sides


def run_reference_side_ppo(assets_dir: str, workdir: str) -> dict:
    """The reference's flagship PPO (hydra frozen branch, adaptive KL,
    alternating rollout/optimize) through its real trlx.train, on the char
    task, from the shared init checkpoint."""
    _install_reference_stubs()
    sys.path.insert(0, REFERENCE_ROOT)

    import torch

    build_ppo_assets(assets_dir)
    logged, eval_seconds, step_stamps, eval_calls = _instrument_reference()

    # Matched phase attribution (harness-side wrappers; the reference code is
    # untouched): rollout = make_experience total, generate = model.generate
    # inside make_experience only (evaluate() also calls generate — that time
    # belongs to eval_s), reward = orchestrator.score.
    from trlx.model.accelerate_base_model import AccelerateRLModel
    from trlx.orchestrator.ppo_orchestrator import PPOOrchestrator as RefPPOOrch

    ph = {"rollout": 0.0, "generate": 0.0, "reward": 0.0, "in_makeexp": False}
    makeexp_stamps = []

    def _timed(orig, key, flag_only_inside=False):
        def wrapper(self, *a, **k):
            if flag_only_inside and not ph["in_makeexp"]:
                return orig(self, *a, **k)
            t = time.time()
            out = orig(self, *a, **k)
            ph[key] += time.time() - t
            return out
        return wrapper

    orig_makeexp = RefPPOOrch.make_experience

    def timed_makeexp(self, *a, **k):
        ph["in_makeexp"] = True
        t = time.time()
        makeexp_stamps.append(t)
        out = orig_makeexp(self, *a, **k)
        ph["rollout"] += time.time() - t
        ph["in_makeexp"] = False
        return out

    RefPPOOrch.make_experience = timed_makeexp
    AccelerateRLModel.generate = _timed(AccelerateRLModel.generate, "generate", True)
    RefPPOOrch.score = _timed(RefPPOOrch.score, "reward", True)

    from trlx.model.nn.ppo_models import ModelBranch

    orig_mb = ModelBranch.forward

    def mb_no_cache(self, *a, **k):
        # transformers>=4.38 removed tuple `presents` from GPT2Block outputs
        # (the reference indexes outputs[1] when use_cache). Cache collection
        # has no effect on the frozen branch's logits — force it off.
        k["use_cache"] = False
        return orig_mb(self, *a, **k)

    ModelBranch.forward = mb_no_cache

    import numpy as np
    import trlx
    from trlx.data.configs import TRLConfig

    p = PPO_PROTOCOL
    prompts = _ppo_prompts()
    config = TRLConfig.load_yaml(os.path.join(REFERENCE_ROOT, "configs", "ppo_config.yml"))
    config.model.model_path = assets_dir
    config.model.tokenizer_path = assets_dir
    config.model.num_layers_unfrozen = p["num_layers_unfrozen"]
    config.train.seq_length = p["seq_length"]
    config.train.batch_size = p["batch_size"]
    config.train.total_steps = p["total_steps"]
    config.train.epochs = 10**6
    config.train.eval_interval = p["eval_interval"]
    config.train.checkpoint_interval = 10**9
    config.train.checkpoint_dir = os.path.join(workdir, "ref_ckpts")
    config.train.learning_rate_init = p["lr_init"]
    config.train.learning_rate_target = p["lr_target"]
    config.method.init_kl_coef = p["init_kl_coef"]
    config.method.num_rollouts = p["num_rollouts"]
    config.method.chunk_size = p["chunk_size"]
    # Prompts tokenize to exactly 6 char-tokens and HF max_length counts
    # prompt+response, so 6+24 pins the response at response_tokens — the
    # same 24 tokens the trlx_tpu side decodes (matched protocol, matched
    # reward denominator).
    ref_total_len = 6 + p["response_tokens"]
    config.method.gen_kwargs = {
        "max_length": ref_total_len,
        "min_length": ref_total_len,
        "top_k": 0.0,
        "top_p": 1.0,
        "do_sample": True,
    }

    os.chdir(workdir)
    t0 = time.time()
    model = trlx.train(
        reward_fn=_ppo_reward_fn,
        prompts=prompts,
        eval_prompts=prompts[: p["batch_size"] // 2],
        config=config,
    )
    wall = time.time() - t0

    trajectory = [
        {"t": round(t - t0, 2), "value": round(float(torch.as_tensor(s["mean_reward"])), 4)}
        for (t, s) in logged
        if "mean_reward" in s
    ]
    return _side_result(
        "reference (trlx v0.2.0, torch eager, Accelerate CPU, hydra PPO)",
        model.iter_count, p["batch_size"], wall, eval_seconds[0],
        trajectory, "final_reward", np.diff(step_stamps),
        {k: round(v, 2) for k, v in ph.items() if k != "in_makeexp"},
        _cycle_sps(makeexp_stamps, eval_calls, p["ppo_epochs"] * p["num_rollouts"]),
    )


def run_ours_side_ppo(assets_dir: str, workdir: str) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import numpy as np

    sys.path.insert(0, REPO)
    import trlx_tpu
    from trlx_tpu.data.configs import TRLConfig

    p = PPO_PROTOCOL
    prompts = _ppo_prompts()
    ckpt_dir = os.path.join(workdir, "ours_ckpts")
    config = TRLConfig.from_dict(
        {
            "model": {
                "model_path": assets_dir,
                "tokenizer_path": assets_dir,
                "model_type": "ppo",
                "num_layers_unfrozen": p["num_layers_unfrozen"],
                "dtype": "float32",
                "param_dtype": "float32",
            },
            "train": {
                "seq_length": p["seq_length"],
                "epochs": 10**6,
                "total_steps": p["total_steps"],
                "batch_size": p["batch_size"],
                "lr_ramp_steps": 10,
                "lr_decay_steps": p["total_steps"],
                "weight_decay": 1.0e-6,
                "learning_rate_init": p["lr_init"],
                "learning_rate_target": p["lr_target"],
                "opt_betas": [0.9, 0.95],
                "checkpoint_interval": 10**9,
                "eval_interval": p["eval_interval"],
                "orchestrator": "PPOOrchestrator",
                "mesh": [-1, 1, 1, 1],
                "seed": 1000,
                "checkpoint_dir": ckpt_dir,
            },
            "method": {
                "name": "ppoconfig",
                "num_rollouts": p["num_rollouts"],
                "chunk_size": p["chunk_size"],
                "ppo_epochs": p["ppo_epochs"],
                "init_kl_coef": p["init_kl_coef"],
                "target": 6,
                "horizon": 10000,
                "gamma": 1.0,
                "lam": 0.95,
                "cliprange": 0.2,
                "cliprange_value": 0.2,
                "vf_coef": 1.0,
                "gen_kwargs": {
                    "prompt_length": p["seq_length"] - p["response_tokens"],
                    "max_new_tokens": p["response_tokens"],
                    "min_new_tokens": p["response_tokens"],
                    "top_k": 0,
                    "top_p": 1.0,
                    "do_sample": True,
                    "temperature": 1.0,
                },
            },
        }
    )

    if os.environ.get("TRLX_TPU_TIMELINE"):
        # Diagnostic mode: stderr stamps around the coarse startup stages so
        # wall-clock gaps in this side are attributable without a profiler.
        from trlx_tpu.trainer.ppo import PPOTrainer
        from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator as _O

        _t = time.time()

        def _stamp(name):
            print(f"[timeline] +{time.time() - _t:7.2f}s {name}", file=sys.stderr, flush=True)

        for cls, meth in ((PPOTrainer, "__init__"), (_O, "make_experience"),
                          (PPOTrainer, "learn"), (PPOTrainer, "evaluate")):
            orig = getattr(cls, meth)

            def wrap(o=orig, m=meth):
                def inner(self, *a, **k):
                    _stamp(f"{m} enter")
                    r = o(self, *a, **k)
                    _stamp(f"{m} exit")
                    return r
                return inner

            setattr(cls, meth, wrap())

    t0 = time.time()
    model = trlx_tpu.train(
        reward_fn=_ppo_reward_fn,
        prompts=prompts,
        eval_prompts=prompts[: p["batch_size"] // 2],
        config=config,
    )
    wall = time.time() - t0

    trajectory, eval_s, step_times, phases, makeexp_starts, eval_calls = _parse_ours_metrics(
        ckpt_dir, "mean_reward", t0
    )
    return _side_result(
        "trlx_tpu (JAX/XLA CPU, jit train step, hydra PPO)",
        model.iter_count, p["batch_size"], wall, eval_s,
        trajectory, "final_reward", step_times, phases,
        _cycle_sps(makeexp_starts, eval_calls, p["ppo_epochs"] * p["num_rollouts"]),
    )


# ---------------------------------------------------------------------------
# Orchestrator


def time_to(trajectory, thr):
    for p in trajectory:
        if p["value"] >= thr:
            return p["t"]
    return None


_SIDE_FNS = {
    ("ref", "ilql"): run_reference_side,
    ("ours", "ilql"): run_ours_side,
    ("ref", "ppo"): run_reference_side_ppo,
    ("ours", "ppo"): run_ours_side_ppo,
}

_TASK_META = {
    "ilql": {
        "task": "randomwalks ILQL (reference: examples/randomwalks.py, seed 1000)",
        "final_key": "final_optimality",
    },
    "ppo": {
        "task": "char-task PPO, reward = frac('a') in response (hydra frozen branch, "
                "identical init checkpoint both sides)",
        "final_key": "final_reward",
    },
}

_SCOPE = (
    "cpu-smoke: both sides on this container's single CPU core, identical "
    "dataset/init, matched protocol (batch/steps/LR/method constants), and the "
    "same metric applied to both; NOT the v4-32 gate"
)


def run_method(method: str, reps: int = 1) -> dict:
    workdir = tempfile.mkdtemp(prefix=f"headtohead_{method}_")
    # For ILQL the shared artifact is the dataset the reference side
    # generates; for PPO it is the init checkpoint + tokenizer dir.
    shared = os.path.join(workdir, "dataset.npz" if method == "ilql" else "assets")
    key = TRAJECTORY_KEY[method]
    final_key = _TASK_META[method]["final_key"]

    # This machine's single core drifts ±10% on the minutes scale (measured:
    # identical step microbenches spread 204-319 ms across runs). One rep
    # cannot resolve a 10-15% ratio; with reps > 1 each (ref, ours, warm)
    # triple runs back-to-back per rep and each label's MEDIAN-throughput rep
    # is reported, so a slow patch of machine hits whole reps, not one side.
    runs = {label: [] for label in ("ref", "ours", "ours_warm")}
    for rep in range(reps):
        for side, label in (("ref", "ref"), ("ours", "ours"), ("ours", "ours_warm")):
            out = os.path.join(workdir, f"{label}_{rep}.json")
            env = dict(os.environ)
            env.pop("JAX_PLATFORMS", None)  # each side pins its own platform
            if side == "ours":
                env["JAX_PLATFORMS"] = "cpu"
                env["TRLX_TPU_NO_PROGRESS"] = "1"
                # cold uses THIS rep's fresh cache dir (populating it); the
                # warm pass reuses the same rep's now-populated cache
                env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, f"xla_cache_{rep}")
            rundir = os.path.join(workdir, f"{label}_{rep}")
            os.makedirs(rundir, exist_ok=True)
            print(f"[bench_reference] running {method}/{label} (rep {rep + 1}/{reps}) ...", flush=True)
            t = time.time()
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--side", side, "--method", method,
                 "--dataset", shared, "--workdir", rundir, "--out", out],
                env=env, check=True, cwd=REPO,
            )
            with open(out) as f:
                runs[label].append(json.load(f))
            print(f"[bench_reference] {method}/{label} done in {time.time()-t:.1f}s: "
                  f"{runs[label][-1]['samples_per_s']} samples/s, "
                  f"final {key} {runs[label][-1][final_key]}", flush=True)

    def median_rep(rs):
        """The rep whose samples_per_s is the median — one self-consistent
        run's full record (trajectory, phases, steady-states together)."""
        ranked = sorted(rs, key=lambda r: r["samples_per_s"])
        return ranked[len(ranked) // 2]

    def paired_ratio(metric):
        """Median over reps of the PER-REP ours/ref ratio. Pairing within a
        rep (runs minutes apart) is what actually cancels machine drift —
        independent per-label medians can select different speed regimes."""
        import statistics

        vals = []
        for o, r in zip(runs["ours"], runs["ref"]):
            if o.get(metric) and r.get(metric):
                vals.append(o[metric] / r[metric])
        return round(statistics.median(vals), 3) if vals else None

    def paired_ratio_warm(metric):
        import statistics

        vals = []
        for w, r in zip(runs["ours_warm"], runs["ref"]):
            if w.get(metric) and r.get(metric):
                vals.append(w[metric] / r[metric])
        return round(statistics.median(vals), 3) if vals else None

    sides = {label: median_rep(rs) for label, rs in runs.items()}
    if reps > 1:
        for label in sides:
            sides[label]["rep_samples_per_s"] = [r["samples_per_s"] for r in runs[label]]
    ref, ours, warm = sides["ref"], sides["ours"], sides["ours_warm"]
    t2o = {}
    for thr in THRESHOLDS[method]:
        tr, to = time_to(ref["trajectory"], thr), time_to(ours["trajectory"], thr)
        tw = time_to(warm["trajectory"], thr)
        t2o[str(thr)] = {
            "ref_s": tr,
            "ours_s": to,
            "ours_warm_s": tw,
            "speedup": round(tr / to, 2) if (tr and to) else None,
        }
    return {
        "task": _TASK_META[method]["task"],
        "scope": _SCOPE,
        "reference": ref,
        "ours": ours,
        "ours_warm_cache": warm,
        # All ratios are medians of PER-REP pairings (see paired_ratio).
        "vs_baseline_samples_per_s": paired_ratio("samples_per_s"),
        "vs_baseline_warm_cache": paired_ratio_warm("samples_per_s"),
        "vs_baseline_steady_state": paired_ratio("steady_state_samples_per_s"),
        # Full recurring cycle (rollout + train + logging; one-time costs
        # excluded) — the production-cadence steady state. The per-step
        # steady state above ignores the rollout phase, where the two
        # implementations differ most.
        "vs_baseline_steady_cycle": paired_ratio("steady_state_cycle_samples_per_s"),
        "vs_baseline_steady_cycle_warm": paired_ratio_warm("steady_state_cycle_samples_per_s"),
        "per_rep_ratios": {
            "cold": [
                round(o["samples_per_s"] / r["samples_per_s"], 3)
                for o, r in zip(runs["ours"], runs["ref"])
            ],
            "steady_cycle": [
                round(o["steady_state_cycle_samples_per_s"] / r["steady_state_cycle_samples_per_s"], 3)
                for o, r in zip(runs["ours"], runs["ref"])
                if o.get("steady_state_cycle_samples_per_s") and r.get("steady_state_cycle_samples_per_s")
            ],
        },
        f"time_to_{key}": t2o,
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--side", choices=["ref", "ours"])
    parser.add_argument("--method", choices=["ilql", "ppo", "both"], default="both")
    parser.add_argument("--reps", type=int, default=3,
                        help="repetitions per side; the median-throughput rep is "
                             "reported (this machine's core drifts ±10%%)")
    parser.add_argument("--dataset", default=None)
    parser.add_argument("--workdir", default=None)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    if args.side:
        if args.method == "both":
            parser.error("--side requires an explicit --method (ilql or ppo)")
        result = _SIDE_FNS[(args.side, args.method)](args.dataset, args.workdir)
        with open(args.out, "w") as f:
            json.dump(result, f)
        return

    # Merge into the existing HEADTOHEAD.json so the two methods can be
    # (re)run independently; migrate the legacy single-task layout.
    existing = {}
    if os.path.exists(RESULT_PATH):
        with open(RESULT_PATH) as f:
            existing = json.load(f)
        if "reference" in existing:
            existing = {"ilql": existing}

    methods = ["ilql", "ppo"] if args.method == "both" else [args.method]
    for method in methods:
        existing[method] = run_method(method, reps=args.reps)
    existing["recorded_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(RESULT_PATH, "w") as f:
        json.dump(existing, f, indent=1)

    summary = {"metric": "headtohead_cpu_speedup_vs_reference", "unit": "x reference samples/s (CPU)"}
    for method in ("ilql", "ppo"):
        if method in existing:
            r = existing[method]
            summary[f"{method}_cold"] = r["vs_baseline_samples_per_s"]
            summary[f"{method}_warm_cache"] = r["vs_baseline_warm_cache"]
            summary[f"{method}_steady_state"] = r["vs_baseline_steady_state"]
            if r.get("vs_baseline_steady_cycle") is not None:
                summary[f"{method}_steady_cycle"] = r["vs_baseline_steady_cycle"]
    print(json.dumps(summary))


if __name__ == "__main__":
    main()

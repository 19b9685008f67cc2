"""End-to-end smokes: a few PPO and ILQL steps on the randomwalks task
(the reference's de-facto integration suite is examples/, SURVEY.md §4 —
here it's in CI)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import trlx_tpu  # noqa: E402
from randomwalks import base_config, generate_random_walks  # noqa: E402

pytestmark = pytest.mark.slow  # excluded from `make test-fast` (see conftest)


@pytest.fixture(scope="module")
def task():
    return generate_random_walks(n_nodes=15, max_length=8, n_walks=60, seed=1000)


def shrink(config):
    config.train.total_steps = 6
    config.train.epochs = 2
    config.train.batch_size = 16
    config.train.eval_interval = 4
    config.method.num_rollouts = 16 if hasattr(config.method, "num_rollouts") else None
    if hasattr(config.method, "chunk_size"):
        config.method.chunk_size = 16
    return config


def test_ppo_e2e_smoke(task, tmp_path):
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.model.kv_cache_quant = True  # int8 decode cache path in CI
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.iter_count >= 6
    assert len(model.store) > 0
    # the int8 cache's reads scale a key's score and its probability, once each (ops/kv_read.py)
    assert model._last_exp_stats["rollout/kv_scale_mults_per_key"] == 2.0


def test_ppo_e2e_bucketed_prompts(task, tmp_path):
    """Mixed prompt lengths with prompt_buckets: rollouts generate at
    per-bucket widths, the store and train step stay at the single global
    prompt_length (the orchestrator re-pads queries before the push), and
    training completes. The trace-count proof lives in test_bucketing; this
    is the full train-loop integration."""
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.method.gen_kwargs["prompt_length"] = 3
    config.method.gen_kwargs["max_new_tokens"] = 5
    config.method.gen_kwargs["prompt_buckets"] = [1, 3]
    rng = np.random.default_rng(7)
    # walk prefixes of mixed lengths 1..3 (nodes stay in-vocab; the bigram
    # mask only constrains GENERATED steps)
    prompts = [list(rng.integers(1, 15, size=rng.integers(1, 4))) for _ in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.prompt_buckets == (1, 3)
    assert model.iter_count >= 6
    assert len(model.store) > 0
    # stored queries were re-padded to the GLOBAL prompt width
    el = model.store[0]
    assert el.query_tensor.shape[0] == model.prompt_length == 3
    assert el.response_tensor.shape[0] == model.response_length == 5


def test_ilql_e2e_smoke(task, tmp_path):
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ilql", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    lengths = metric_fn(walks)["lengths"]
    model = trlx_tpu.train(
        dataset=(walks, lengths),
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.iter_count >= 6


def test_checkpoint_save_load(task, tmp_path):
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.total_steps = 2
    config.train.checkpoint_dir = str(tmp_path / "ck")
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]], metric_fn=metric_fn,
        config=config, logit_mask=logit_mask,
    )
    import jax

    step_before = int(jax.device_get(model.state.step))
    model.load()
    assert int(jax.device_get(model.state.step)) == step_before


def test_ppo_fully_unfrozen_uses_ref_copy(task, tmp_path):
    """num_layers_unfrozen >= n_layer means no shared trunk: the trainer must
    fall back to a full frozen ref copy (a layer-0 branch replay would
    re-apply position embeddings — regression test)."""
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.total_steps = 2
    config.model.num_layers_unfrozen = config.model.model_arch["n_layer"]
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.model.branch_layer == -1
    assert model.iter_count >= 2


def test_preemption_checkpoints_and_stops(task, tmp_path):
    """SIGTERM mid-training must save a resumable checkpoint at the next step
    boundary and stop cleanly (the reference has no preemption handling)."""
    import os
    import signal

    from trlx_tpu.trainer.ppo import PPOTrainer

    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.epochs = 100
    config.train.total_steps = 50  # would run long; preemption cuts it short
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    fired = {"done": False}
    orig = PPOTrainer.post_backward_callback

    def fire_once(self, stats=None):
        orig(self, stats)
        if not fired["done"] and self.iter_count >= 2:
            fired["done"] = True
            os.kill(os.getpid(), signal.SIGTERM)

    PPOTrainer.post_backward_callback = fire_once
    try:
        model = trlx_tpu.train(
            reward_fn=reward_fn,
            prompts=prompts,
            eval_prompts=[[i] for i in range(1, 15)],
            metric_fn=metric_fn,
            config=config,
            logit_mask=logit_mask,
        )
    finally:
        PPOTrainer.post_backward_callback = orig

    assert fired["done"]
    assert model.iter_count < 50  # stopped at the preemption boundary
    with open(os.path.join(str(tmp_path), "latest.txt")) as f:
        assert f.read().strip()
    model.load()  # the checkpoint restores


def test_ppo_e2e_on_sharded_mesh(task, tmp_path):
    """Whole PPO path (generate → score → train) on a dp=2,tp=2,sp=2 mesh of
    virtual CPU devices — the multi-chip semantics the reference cannot test
    at all (SURVEY.md §4)."""
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.mesh = (2, 1, 2, 2)
    config.train.total_steps = 4
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.iter_count >= 4
    assert model.model.cfg.sp_size == 2  # ring attention was actually on


def test_ilql_e2e_on_sharded_mesh(task, tmp_path):
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ilql", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.mesh = (1, 2, 2, 2)
    config.train.total_steps = 3
    rewards = [float(reward_fn([w])[0]) for w in walks]
    model = trlx_tpu.train(
        dataset=(walks, rewards),
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.iter_count >= 3


def test_resume_from_checkpoint_continues_training(task, tmp_path):
    """train.resume_from_checkpoint restores the full state and continues
    counting from the saved step — true resume, which the reference's
    save-only checkpoints cannot do."""
    import jax

    walks, logit_mask, metric_fn, reward_fn = task
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    def run(total_steps, resume):
        config = shrink(base_config("ppo", 15, 8))
        config.train.total_steps = total_steps
        config.train.checkpoint_dir = str(tmp_path / "ck")
        config.train.resume_from_checkpoint = resume
        return trlx_tpu.train(
            reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
            metric_fn=metric_fn, config=config, logit_mask=logit_mask,
        )

    first = run(total_steps=2, resume=False)
    assert int(jax.device_get(first.state.step)) == 2

    second = run(total_steps=5, resume=True)
    # picked up at step 2 and trained only the remaining 3 steps
    assert int(jax.device_get(second.state.step)) == 5
    assert second.iter_count == 5


def test_resume_restores_host_state(task, tmp_path):
    """The adaptive KL coefficient and the sampling RNG are host-side Python
    state; a true resume must restore them too."""
    walks, logit_mask, metric_fn, reward_fn = task
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    def run(total_steps, resume):
        config = shrink(base_config("ppo", 15, 8))
        config.train.total_steps = total_steps
        config.train.checkpoint_dir = str(tmp_path / "ck")
        config.train.resume_from_checkpoint = resume
        return trlx_tpu.train(
            reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
            metric_fn=metric_fn, config=config, logit_mask=logit_mask,
        )

    first = run(total_steps=2, resume=False)
    first.kl_ctl.value = 0.0123  # pretend the controller adapted
    first.save()

    second = run(total_steps=4, resume=True)
    # restored at construction time, then possibly adapted during the 2
    # resumed steps — but never reset to init_kl_coef (0.05 in this config)
    assert second.kl_ctl.value != first.config.method.init_kl_coef
    assert second.kl_ctl.value == pytest.approx(0.0123, rel=0.2)


def test_ppo_learns_randomwalks(tmp_path):
    """Learning-QUALITY gate (not just a smoke): PPO on randomwalks must
    reach ≥0.8 eval optimality — a zero-learning regression passes the
    smoke tests above but fails here. Reference metric:
    trlx/examples/randomwalks.py:62-81; measured headroom: optimality
    reaches ~0.95 by step 48 on CPU with the example config."""
    n_nodes, max_length = 21, 10
    walks, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=n_nodes, max_length=max_length
    )
    config = base_config("ppo", n_nodes, max_length)
    config.train.total_steps = 48
    config.train.eval_interval = 16
    config.train.checkpoint_interval = 10**6
    config.train.checkpoint_dir = str(tmp_path)
    # batch must divide the 8-virtual-device dp mesh (conftest)
    config.train.batch_size = 48
    config.method.num_rollouts = 96
    config.method.chunk_size = 48

    history = []
    n_eval_prompts = n_nodes - 1  # 20 prompts at batch 50: one wrapped batch

    def recording_metric(samples):
        # eval must hand the metric exactly the valid rows — the loader's
        # static-shape wrap-around duplicates must have been dropped
        assert len(samples) == n_eval_prompts
        m = metric_fn(samples)
        history.append(float(np.mean(m["optimality"])))
        return m

    prompts = [[int(np.random.default_rng(i).integers(1, n_nodes))] for i in range(200)]
    trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, n_nodes)],
        metric_fn=recording_metric,
        config=config,
        logit_mask=logit_mask,
    )
    assert history, "evaluate() never ran"
    assert max(history) >= 0.8, f"PPO failed to learn: optimality history {history}"


def test_ilql_learns_randomwalks(tmp_path):
    """ILQL on the offline randomwalks dataset must beat the random-walk
    baseline (~0.55 optimality) by a clear margin."""
    n_nodes, max_length = 21, 10
    walks, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=n_nodes, max_length=max_length
    )
    config = base_config("ilql", n_nodes, max_length)
    config.train.total_steps = 100
    config.train.eval_interval = 25
    config.train.checkpoint_interval = 10**6
    config.train.checkpoint_dir = str(tmp_path)
    # batch must divide the 8-virtual-device dp mesh (conftest)
    config.train.batch_size = 48

    history = []

    def recording_metric(samples):
        m = metric_fn(samples)
        history.append(float(np.mean(m["optimality"])))
        return m

    lengths = metric_fn(walks)["lengths"]
    trlx_tpu.train(
        dataset=(walks, lengths),
        eval_prompts=[[i] for i in range(1, n_nodes)],
        metric_fn=recording_metric,
        config=config,
        logit_mask=logit_mask,
    )
    assert history, "evaluate() never ran"
    assert max(history) >= 0.70, f"ILQL failed to learn: optimality history {history}"


def test_ppo_with_on_device_reward_model(task, tmp_path):
    """PPO driven by an ON-DEVICE reward model (no host reward_fn at all):
    rollout scoring and eval rewards come from the RM inside the fused
    sharded programs — the pod-scale RM path (BASELINE.json eval config 5)."""
    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.total_steps = 2
    config.model.reward_model_arch = dict(config.model.model_arch)
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.has_reward_model and model.reward_fn is None
    assert model.iter_count >= 2
    assert len(model.store) > 0
    stats = model.evaluate()
    assert "mean_reward" in stats  # RM-sourced eval rewards
    # the RM scores exactly one scalar per sequence
    import jax

    batch, n_valid = next(iter(model.eval_dataloader.iter_with_valid()))
    tokens, mask = model.rollout_generate(batch["input_ids"], batch["attention_mask"])
    scores = np.asarray(jax.device_get(model.rm_eval_scores(tokens, mask)))
    assert scores.shape == (batch["input_ids"].shape[0],)
    assert np.isfinite(scores).all()


def test_profile_dir_captures_trace(task, tmp_path):
    """train.profile_dir: steps [2,5) of the learn loop are traced with
    jax.profiler (the TPU-native upgrade over the reference's wall-clock
    timers, SURVEY.md §5) — trace artifacts must land on disk."""
    import os

    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.total_steps = 6
    config.train.profile_dir = str(tmp_path / "trace")
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
        metric_fn=metric_fn, config=config, logit_mask=logit_mask,
    )
    trace_files = []
    for root, _, files in os.walk(tmp_path / "trace"):
        trace_files.extend(files)
    assert trace_files, "profiler produced no trace artifacts"


def test_log_interval_skips_stat_reads(task, tmp_path):
    """train.log_interval > 1 logs (and syncs stats) only every Nth step —
    the reference reads this field but never defines it
    (trlx/model/__init__.py:137)."""
    import json

    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.train.total_steps = 4
    config.train.log_interval = 2
    config.train.eval_interval = 100  # no eval logs in the window
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
        metric_fn=metric_fn, config=config, logit_mask=logit_mask,
    )
    assert model.iter_count >= 4
    with open(tmp_path / "metrics.jsonl") as f:
        recs = [json.loads(line) for line in f]
    # train-step stat lines carry "loss"; rollout/eval lines don't
    logged_train_steps = [r["step"] for r in recs if "loss" in r]
    assert logged_train_steps, "nothing logged at all"
    assert set(logged_train_steps) <= {2, 4}, logged_train_steps


def test_offline_orchestrator_degenerate_samples(task):
    """Prompt-only / over-truncated samples must not crash experience
    building (empty action rows are padded no-ops in the storage)."""
    from trlx_tpu.orchestrator.offline_orchestrator import OfflineOrchestrator
    from trlx_tpu.trainer.ilql import ILQLTrainer

    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ilql", 15, 8))
    config.train.total_steps = 1
    model = ILQLTrainer(config, metric_fn=metric_fn, logit_mask=logit_mask)
    orch = OfflineOrchestrator(model)
    samples = [np.asarray([3]), np.asarray(walks[0]), np.asarray(walks[1])]
    orch.make_experience(samples, [0.5, 1.0, -1.0])
    assert len(model.store) == 3


def test_kl_controller_trajectory_invariant_to_log_interval(task, tmp_path):
    """The adaptive KL controller buffers every step's mean_kl and applies
    per-step updates in order, so its final coefficient is IDENTICAL for
    log_interval 1 and 4 on the same seeds/data (it used to react only to
    every Nth step's KL with a rescaled step count)."""

    def run(log_interval, ckpt_dir):
        walks, logit_mask, metric_fn, reward_fn = task
        config = shrink(base_config("ppo", 15, 8))
        config.train.checkpoint_dir = str(ckpt_dir)
        config.train.total_steps = 5
        config.train.log_interval = log_interval
        config.train.eval_interval = 100
        assert config.method.target is not None  # adaptive controller in play
        prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
        model = trlx_tpu.train(
            reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
            metric_fn=metric_fn, config=config, logit_mask=logit_mask,
        )
        model._flush_kl_updates()
        return model.kl_ctl.value

    v1 = run(1, tmp_path / "a")
    v4 = run(4, tmp_path / "b")
    assert v1 != pytest.approx(0.05), "controller never moved — test is vacuous"
    assert v4 == pytest.approx(v1, rel=1e-6)


def test_ppo_e2e_packed_train_batch(task, tmp_path):
    """method.pack_train_batch=True: episodes pack into dense bucketed rows
    (block-diagonal attention, segment-gated GAE) and the whole train loop
    completes, logging the packed-throughput metrics."""
    import json

    walks, logit_mask, metric_fn, reward_fn = task
    config = shrink(base_config("ppo", 15, 8))
    config.train.checkpoint_dir = str(tmp_path)
    config.method.pack_train_batch = True
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    model = trlx_tpu.train(
        reward_fn=reward_fn,
        prompts=prompts,
        eval_prompts=[[i] for i in range(1, 15)],
        metric_fn=metric_fn,
        config=config,
        logit_mask=logit_mask,
    )
    assert model.iter_count >= 6
    assert len(model.store) > 0
    # packed rows shard over the data axes like any train batch
    from trlx_tpu.data import PackedPPOBatch

    batch = next(iter(model.train_dataloader))
    assert isinstance(batch, PackedPPOBatch)
    assert batch.input_ids.shape[0] % model._pack_rows_multiple == 0
    # satellite metrics: tokens/s + fill fraction land in metrics.jsonl
    with open(tmp_path / "metrics.jsonl") as f:
        recs = [json.loads(line) for line in f]
    assert any("train_tokens_per_s" in r for r in recs)
    fills = [r["train_batch_fill"] for r in recs if "train_batch_fill" in r]
    assert fills and all(0 < v <= 1 for v in fills)


def test_ppo_packed_losses_match_unpacked(task, tmp_path):
    """Same seed, same rollouts: the packed train step must reproduce the
    unpacked losses (layout is a pure re-indexing of the same loss sum —
    only float reassociation differs). With packing OFF the loader still
    yields the plain PPORLBatch, i.e. the default path is untouched."""
    import json

    walks, logit_mask, metric_fn, reward_fn = task

    def run(packed, sub):
        config = shrink(base_config("ppo", 15, 8))
        config.train.checkpoint_dir = str(tmp_path / sub)
        config.train.total_steps = 2
        config.method.pack_train_batch = packed
        prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
        model = trlx_tpu.train(
            reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
            metric_fn=metric_fn, config=config, logit_mask=logit_mask,
        )
        with open(tmp_path / sub / "metrics.jsonl") as f:
            recs = [json.loads(line) for line in f]
        return model, {r["step"]: r for r in recs if "loss" in r}

    model_u, logs_u = run(False, "unpacked")
    model_p, logs_p = run(True, "packed")

    from trlx_tpu.data import PackedPPOBatch, PPORLBatch

    assert isinstance(next(iter(model_u.train_dataloader)), PPORLBatch)
    assert isinstance(next(iter(model_p.train_dataloader)), PackedPPOBatch)

    # step 1 trains on identical params + identical experience — packed vs
    # unpacked is the same loss up to reassociation
    assert 1 in logs_u and 1 in logs_p
    assert logs_u[1]["loss"] == pytest.approx(logs_p[1]["loss"], rel=5e-3, abs=1e-5)
    assert logs_u[1]["mean_kl"] == pytest.approx(logs_p[1]["mean_kl"], rel=5e-3, abs=1e-6)

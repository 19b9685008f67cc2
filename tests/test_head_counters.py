"""The `head/*` counters (ops/fused_logprob.count_head_calls): what the fused
log-prob head chose at each call site of a tiny trainer's programs, once a
compiled program in the tracker's scalars."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import trlx_tpu  # noqa: E402
from randomwalks import base_config, generate_random_walks  # noqa: E402
from trlx_tpu.ops.fused_logprob import count_head_calls, fused_logprob, head_tiles, take_head_call_scalars  # noqa: E402

N_NODES, MAX_LENGTH, D_MODEL = 15, 8, 144


@pytest.fixture(scope="module")
def task():
    return generate_random_walks(n_nodes=N_NODES, max_length=MAX_LENGTH, n_walks=60, seed=1000)


def _config(method, tmp_path, batch):
    config = base_config(method, N_NODES, MAX_LENGTH)
    config.model.model_arch["extra"] = {"fused_logprob": "force"}  # the kernel, interpreted on the CPU
    config.train.total_steps = 6
    config.train.epochs = 6
    config.train.batch_size = batch
    config.train.eval_interval = 100
    config.train.checkpoint_interval = 0
    config.train.checkpoint_dir = str(tmp_path)
    return config


def _head_scalars(tmp_path):
    """{key: [values, one a record that holds it]} over the run's records."""
    seen = {}
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        for line in f:
            for k, v in json.loads(line).items():
                if k.startswith("head/"):
                    seen.setdefault(k, []).append(v)
    return seen


def _expected(program, site, rows, d, x_itemsize, has_bias):
    tiles = head_tiles(rows, d, N_NODES, x_itemsize, 4, has_bias)
    padded = tiles.padded(rows)
    return {
        f"head/{program}/{site}/weight_passes": [float(padded // tiles.fwd[0])],
        f"head/{program}/{site}/row_tile": [float(tiles.fwd[0])],
        f"head/{program}/{site}/vocab_tile": [float(tiles.fwd[1])],
        f"head/{program}/{site}/padded_rows": [float(padded)],
    }


def test_ppo_train_step_and_scoring_log_their_weight_passes_once(task, tmp_path):
    _, logit_mask, metric_fn, reward_fn = task
    config = _config("ppo", tmp_path, batch=24)
    config.method.num_rollouts = 72
    config.method.chunk_size = 72
    config.method.ppo_epochs = 1  # three steps an iteration: the run crosses a rollout boundary
    prompts = [[int(np.random.default_rng(i).integers(1, N_NODES))] for i in range(72)]
    trlx_tpu.train(reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]], metric_fn=metric_fn,
                   config=config, logit_mask=logit_mask)
    response = MAX_LENGTH - 1
    want = {**_expected("train", "lm_head", 24 * response, D_MODEL, 4, False),
            **_expected("score", "lm_head", 72 * response, D_MODEL, 4, False)}
    # 168 rows pad to 256 at the floor: two passes; 504 take 256-row tiles (512 is more rows than the call has)
    assert want["head/train/lm_head/weight_passes"] == [2.0] and want["head/score/lm_head/row_tile"] == [256.0]
    assert _head_scalars(tmp_path) == want  # each key in one record: once a compiled program


def test_ilql_train_step_logs_the_lm_head_and_each_q_head(task, tmp_path):
    walks, logit_mask, metric_fn, reward_fn = task
    config = _config("ilql", tmp_path, batch=40)
    rewards = reward_fn(walks)
    trlx_tpu.train(dataset=(walks, rewards), eval_prompts=[[1]], metric_fn=metric_fn, config=config,
                   logit_mask=logit_mask)
    seen = _head_scalars(tmp_path)
    sites = {k.split("/")[2] for k in seen}
    assert sites == {"lm_head", "q1_head", "q2_head"} and {k.split("/")[1] for k in seen} == {"train"}
    for key, values in seen.items():
        assert len(values) == 1, key
    for site in sites:
        padded, tile = seen[f"head/train/{site}/padded_rows"][0], seen[f"head/train/{site}/row_tile"][0]
        assert seen[f"head/train/{site}/weight_passes"] == [padded / tile]
    assert seen["head/train/lm_head/padded_rows"] == [float(-(-40 * (MAX_LENGTH - 1) // 128) * 128)]


def test_counters_fill_only_while_armed_and_empty_on_take():
    import jax.numpy as jnp

    x, w, y = jnp.ones((500, 64)), jnp.ones((64, 200)), jnp.zeros((500,), jnp.int32)
    fused_logprob(x, w, y, interpret=True)  # unarmed: nothing to fill, nothing raised
    tally = {}
    with count_head_calls(tally):
        fused_logprob(x, w, y, interpret=True, site="q1_head")
    assert tally == {"q1_head": {"padded_rows": 512, "row_tile": 256, "vocab_tile": 200, "weight_passes": 2}}
    scalars = take_head_call_scalars(tally, "train")
    assert scalars["head/train/q1_head/weight_passes"] == 2.0 and len(scalars) == 4
    assert tally == {} and take_head_call_scalars(tally, "train") == {}

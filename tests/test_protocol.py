"""BaseRL protocol surface: sample()/act() honor their arguments
(reference protocol: trlx/model/__init__.py:49-71) and the wandb.watch
equivalent (`train.watch_interval`) emits per-group grad norms + parameter
histograms."""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

from randomwalks import base_config, generate_random_walks  # noqa: E402


def _tiny_trainer(tmp_path, **cfg_overrides):
    from trlx_tpu.trainer.ppo import PPOTrainer

    config = base_config("ppo", 15, 8)
    config.train.checkpoint_dir = str(tmp_path)
    config.train.batch_size = 8
    config.method.chunk_size = 8
    config.method.num_rollouts = 8
    config.model.num_layers_unfrozen = 1
    for k, v in cfg_overrides.items():
        section, key = k.split(".")
        setattr(getattr(config, section), key, v)
    return PPOTrainer(config)


def test_sample_honors_n_samples_and_length(tmp_path):
    trainer = _tiny_trainer(tmp_path)
    P, R = trainer.prompt_length, trainer.response_length
    rng = np.random.default_rng(0)
    prompts = {
        "input_ids": rng.integers(1, 15, size=(4, P)).astype(np.int32),
        "attention_mask": np.ones((4, P), np.int32),
    }
    # n_samples > batch: tiled
    out = trainer.sample(prompts, length=None, n_samples=6)
    assert np.asarray(out).shape[0] == 6
    # n_samples < batch: truncated
    out = trainer.sample(prompts, length=None, n_samples=2)
    assert np.asarray(out).shape[0] == 2
    # length clips the response region (never exceeds compiled R)
    out = trainer.sample(prompts, length=3, n_samples=4)
    assert np.asarray(out).shape[1] == P + min(3, R)
    out = trainer.sample(prompts, length=10 * R, n_samples=4)
    assert np.asarray(out).shape[1] == P + R


def test_act_returns_tokens_and_mask(tmp_path):
    # act() keeps the orchestrator's contract: batches arrive mesh-divisible
    # (8 = the conftest virtual-device data axes), unlike sample() which pads.
    trainer = _tiny_trainer(tmp_path)
    P = trainer.prompt_length
    rng = np.random.default_rng(1)
    data = {
        "input_ids": rng.integers(1, 15, size=(8, P)).astype(np.int32),
        "attention_mask": np.ones((8, P), np.int32),
    }
    tokens, mask = trainer.act(data)
    assert np.asarray(tokens).shape == np.asarray(mask).shape
    assert np.asarray(tokens).shape == (8, P + trainer.response_length)


def test_watch_interval_logs_grad_norms_and_histograms(tmp_path):
    """watch_interval=1: every logged step carries per-group
    watch/grad_norm/* scalars, and param histograms land in metrics.jsonl."""
    import trlx_tpu

    walks, logit_mask, metric_fn, reward_fn = generate_random_walks(15, 8, 60, seed=1000)
    config = base_config("ppo", 15, 8)
    config.train.checkpoint_dir = str(tmp_path)
    config.train.batch_size = 16
    config.train.total_steps = 3
    config.train.eval_interval = 100
    config.train.watch_interval = 1
    config.model.num_layers_unfrozen = 1
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=[[1]],
        metric_fn=metric_fn, config=config, logit_mask=logit_mask,
    )

    grad_groups, hist_names = set(), set()
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            grad_groups.update(k for k in rec if k.startswith("watch/grad_norm/"))
            if "histogram" in rec and rec["histogram"].startswith("watch/params/"):
                hist_names.add(rec["histogram"])
    assert grad_groups, "no per-group grad norms logged"
    assert hist_names, "no parameter histograms logged"


# A local HF checkpoint directory with a tokenizer of its own: a tiny GPT-2
# (fixed torch seed) and a character-level byte-BPE vocabulary with no merges,
# saved as the ordinary HF files.
_CHARS = "abcdefghijklmnopqrstuvwxyz0123456789 .,!?"
_N_LAYER, _D_MODEL, _N_HEAD, _VOCAB = 4, 144, 4, 42
_RESPONSE_TOKENS = 24


def _ppo_reward_fn(texts):
    return [sum(c == "a" for c in t) / float(_RESPONSE_TOKENS) for t in texts]


def _ppo_prompts():
    rng = np.random.default_rng(0)
    return ["".join(rng.choice(list("bcdefgh"), size=6)) for _ in range(64)]


def build_ppo_assets(assets_dir):
    import torch
    import transformers
    from transformers.models.gpt2.tokenization_gpt2 import bytes_to_unicode

    os.makedirs(assets_dir, exist_ok=True)
    cfg = transformers.GPT2Config(
        n_layer=_N_LAYER, n_embd=_D_MODEL, n_head=_N_HEAD,
        vocab_size=_VOCAB, n_positions=128,
        bos_token_id=_VOCAB - 1, eos_token_id=_VOCAB - 1,
    )
    torch.manual_seed(7)
    transformers.GPT2LMHeadModel(cfg).save_pretrained(assets_dir, safe_serialization=True)
    b2u = bytes_to_unicode()
    vocab = {}
    for ch in _CHARS:
        vocab.setdefault("".join(b2u[b] for b in ch.encode("utf-8")), len(vocab))
    vocab["<|endoftext|>"] = len(vocab)
    assert len(vocab) == _VOCAB, len(vocab)
    with open(os.path.join(assets_dir, "vocab.json"), "w") as f:
        json.dump(vocab, f)
    with open(os.path.join(assets_dir, "merges.txt"), "w") as f:
        f.write("#version: 0.2\n")
    return assets_dir


def test_local_hf_checkpoint_and_char_tokenizer_load_into_a_hydra_ppo_trainer(tmp_path):
    """A local HF checkpoint directory with its own character-level tokenizer
    goes through `model.model_path` / `tokenizer_path` and the streamed
    importer into a working PPOTrainer: the tokenizer round-trips the task
    alphabet one id a character, the hydra branch is engaged, and
    `rollout_generate` returns prompt + response columns."""
    assets = str(tmp_path / "assets")
    build_ppo_assets(assets)

    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(assets, use_fast=False)
    text = "abc 123!"
    ids = tok(text).input_ids
    assert tok.decode(ids) == text
    assert len(ids) == len(text)  # strictly char-level: no merges

    prompts = _ppo_prompts()
    assert len(prompts) == 64 and all(len(p) == 6 for p in prompts)
    assert _ppo_reward_fn(["a" * 24, "b" * 24]) == [1.0, 0.0]

    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.trainer.ppo import PPOTrainer

    config = TRLConfig.from_dict(
        {
            "model": {"model_path": assets, "tokenizer_path": assets, "model_type": "ppo",
                      "num_layers_unfrozen": 2, "dtype": "float32",
                      "param_dtype": "float32"},
            "train": {"seq_length": 32, "epochs": 1, "total_steps": 1,
                      "batch_size": 8, "lr_ramp_steps": 1, "lr_decay_steps": 10,
                      "weight_decay": 0.0, "learning_rate_init": 1e-3,
                      "learning_rate_target": 1e-4, "checkpoint_dir": str(tmp_path / "ck"),
                      "mesh": [-1, 1, 1, 1], "seed": 0},
            "method": {"name": "ppoconfig", "num_rollouts": 8, "chunk_size": 8,
                       "gen_kwargs": {"prompt_length": 8, "max_new_tokens": 4, "do_sample": True}},
        }
    )
    trainer = PPOTrainer(config)
    assert trainer.model.branch_layer >= 0  # hydra engaged
    enc = tok(prompts[:8], padding=False)

    ids8 = np.full((8, 8), tok.eos_token_id, dtype=np.int32)
    mask8 = np.zeros((8, 8), dtype=np.int32)
    for i, row in enumerate(enc.input_ids):
        ids8[i, -len(row):] = row
        mask8[i, -len(row):] = 1
    tokens, _ = trainer.rollout_generate(ids8, mask8)
    assert np.asarray(tokens).shape == (8, 12)

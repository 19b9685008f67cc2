"""Config system + registries (reference has no such tests; ours cover the
YAML → dataclass path the whole framework hangs off)."""

import os

import pytest

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.method_configs import get_method, PPOConfig, ILQLConfig

CONFIG_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "trlx_tpu", "configs")


def test_load_default_ppo_yaml():
    cfg = TRLConfig.load_yaml(os.path.join(CONFIG_DIR, "ppo_config.yml"))
    assert cfg.method.name == "ppoconfig"
    assert cfg.method.ppo_epochs == 4
    assert cfg.train.mesh == (-1, 1, 1, 1)
    assert cfg.model.num_layers_unfrozen == 2
    d = cfg.to_dict()
    assert "cliprange" in d and "seq_length" in d


def test_load_default_ilql_yaml():
    cfg = TRLConfig.load_yaml(os.path.join(CONFIG_DIR, "ilql_config.yml"))
    assert cfg.method.name == "ilqlconfig"
    assert cfg.method.two_qs is True
    assert cfg.method.betas == [16]


def test_method_registry():
    assert get_method("ppoconfig") is PPOConfig
    assert get_method("ILQLConfig") is ILQLConfig
    with pytest.raises(Exception):
        get_method("nonexistent")


def test_trainer_registry_names():
    import trlx_tpu.trainer.api  # populates registries
    from trlx_tpu.trainer import get_model

    # reference-compatible names resolve (reference: configs/*.yml model_type)
    # + the BASELINE north-star's backend names
    from trlx_tpu.trainer.ilql import ILQLTrainer
    from trlx_tpu.trainer.ppo import PPOTrainer

    assert get_model("TPUJaxPPOModel") is PPOTrainer
    assert get_model("TPUJaxILQLModel") is ILQLTrainer
    assert get_model("AcceleratePPOModel") is get_model("ppo")
    assert get_model("ILQLModel") is get_model("ilql")


def test_orchestrator_registry():
    import trlx_tpu.trainer.api  # noqa: F401
    from trlx_tpu.orchestrator import get_orchestrator

    assert get_orchestrator("PPOOrchestrator") is not None
    assert get_orchestrator("OfflineOrchestrator") is not None


def test_all_shipped_configs_load():
    import glob

    paths = sorted(glob.glob(os.path.join(CONFIG_DIR, "*.yml")))
    assert len(paths) >= 5
    for path in paths:
        cfg = TRLConfig.load_yaml(path)
        assert cfg.train.batch_size > 0, path


def test_sentiment_score_shapes():
    from trlx_tpu.utils import sentiment_score

    top1 = [{"label": "POSITIVE", "score": 0.9}, {"label": "NEGATIVE", "score": 0.8}]
    assert sentiment_score(top1) == [0.9, pytest.approx(0.2)]
    all_scores = [[{"label": "NEGATIVE", "score": 0.3}, {"label": "POSITIVE", "score": 0.7}]]
    assert sentiment_score(all_scores) == [pytest.approx(0.7)]


def test_indivisible_batch_and_chunk_fail_at_construction(tmp_path):
    """Batch/chunk sizes that cannot shard over the mesh's data axes must
    fail at trainer construction with a clear message, not as a cryptic
    sharding error at the first put_batch."""
    import os
    import sys

    import pytest

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))
    from randomwalks import base_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    config = base_config("ppo", 15, 8)
    config.train.checkpoint_dir = str(tmp_path)
    config.train.mesh = [8, 1, 1, 1]
    config.train.batch_size = 12  # 12 % 8 != 0
    config.method.chunk_size = 16  # valid, so the error is the BATCH check's
    with pytest.raises(ValueError, match="train.batch_size"):
        PPOTrainer(config)

    config.train.batch_size = 16
    config.method.chunk_size = 20  # 20 % 8 != 0
    with pytest.raises(ValueError, match="chunk_size"):
        PPOTrainer(config)


def test_r4_train_config_fields_round_trip():
    """watch_interval survives dict round-trips and carries its documented
    default (off)."""
    from trlx_tpu.data.configs import TRLConfig

    cfg = TRLConfig.from_dict(
        {
            "model": {"model_path": "", "tokenizer_path": "", "model_type": "ppo"},
            "train": {
                "total_steps": 1, "seq_length": 8, "epochs": 1, "batch_size": 2,
                "lr_ramp_steps": 1, "lr_decay_steps": 1, "weight_decay": 0.0,
                "learning_rate_init": 1e-3, "learning_rate_target": 1e-4,
                "watch_interval": 7,
            },
            "method": {"name": "ppoconfig"},
        }
    )
    assert cfg.train.watch_interval == 7
    default = TRLConfig.from_dict(
        {
            "model": {"model_path": "", "tokenizer_path": "", "model_type": "ppo"},
            "train": {
                "total_steps": 1, "seq_length": 8, "epochs": 1, "batch_size": 2,
                "lr_ramp_steps": 1, "lr_decay_steps": 1, "weight_decay": 0.0,
                "learning_rate_init": 1e-3, "learning_rate_target": 1e-4,
            },
            "method": {"name": "ppoconfig"},
        }
    )
    assert default.train.watch_interval == 0

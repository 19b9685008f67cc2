"""`train()` dispatch: reward_fn → online PPO, dataset → offline ILQL
(reference: trlx/trlx.py:13-93)."""

import os
from typing import Callable, List, Optional, Tuple

from trlx_tpu.data.configs import TRLConfig

# Importing these modules populates the registries (the reference does the
# same via package imports, reference: trlx/model/__init__.py:17-36).
import trlx_tpu.trainer.ppo  # noqa: F401
import trlx_tpu.trainer.ppo_softprompt  # noqa: F401
import trlx_tpu.trainer.ilql  # noqa: F401
import trlx_tpu.orchestrator.ppo_orchestrator  # noqa: F401
import trlx_tpu.orchestrator.offline_orchestrator  # noqa: F401
import trlx_tpu.pipeline.prompt_pipeline  # noqa: F401

from trlx_tpu.orchestrator import get_orchestrator
from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
from trlx_tpu.trainer import get_model
from trlx_tpu.utils.startup import mark_imported

_CONFIG_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def default_config(name: str) -> TRLConfig:
    return TRLConfig.load_yaml(os.path.join(_CONFIG_DIR, f"{name}_config.yml"))


def train(
    model_path: Optional[str] = None,
    reward_fn: Optional[Callable] = None,
    dataset: Optional[Tuple[List[str], List[float]]] = None,
    prompts: Optional[List] = None,
    eval_prompts: Optional[List] = None,
    metric_fn: Optional[Callable] = None,
    config: Optional[TRLConfig] = None,
    split_token: Optional[str] = None,
    logit_mask=None,
    backend: str = "tpu",
):
    # `backend` exists for drop-in compatibility with the
    # `trlx.train(..., backend='tpu')` call shape; this framework IS the
    # tpu backend.
    if backend not in ("tpu", "jax"):
        raise ValueError(f"trlx_tpu only implements the tpu/jax backend, got {backend!r}")
    has_rm = config is not None and config.model.has_reward_model
    if reward_fn is not None and has_rm:
        raise ValueError(
            "Both reward_fn and an on-device reward model "
            "(model.reward_model_path/reward_model_arch) are set — rollouts "
            "would optimize the RM while eval reports reward_fn. Pick one "
            "reward source."
        )
    if reward_fn is not None or has_rm:
        # ---------------- online PPO (reference: trlx/trlx.py:38-59).
        # Dispatch extends the reference's: an ON-DEVICE reward model in the
        # config selects PPO too (scores computed inside rollout scoring —
        # no host reward_fn needed).
        if config is None:
            config = default_config("ppo")
        if model_path:
            config.model.model_path = model_path

        model = get_model(config.model.model_type)(
            config, reward_fn=reward_fn, metric_fn=metric_fn, logit_mask=logit_mask
        )

        batch_size = config.train.batch_size
        if prompts is None:
            assert model.tokenizer is not None, "default prompts need a tokenizer"
            prompts = [model.tokenizer.bos_token] * batch_size

        # prompt_buckets (method.gen_kwargs) flows trainer → pipeline: the
        # rollout loader then yields bucket-uniform batches, padded only to
        # the bucket width, and the trainer keys compiled generate/score
        # programs per bucket. The eval pipeline stays single-width.
        pipeline = PromptPipeline(
            prompts,
            model.tokenizer,
            max_prompt_length=model.prompt_length,
            bucket_widths=getattr(model, "prompt_buckets", None),
        )
        orch = get_orchestrator(config.train.orchestrator)(
            model, pipeline, reward_fn=reward_fn, metric_fn=metric_fn, chunk_size=config.method.chunk_size
        )
        fleet_role = getattr(model, "fleet_role", None)
        if fleet_role is None:
            orch.make_experience(config.method.num_rollouts)
        elif fleet_role != "rollout":
            # Fleet learner/colocated: iteration 0's experience arrives
            # through the episode stream (trlx_tpu/fleet), after the v0
            # weight broadcast that lets a worker's staleness gate open.
            model._fleet_bootstrap()
        # Fleet rollout role: no pre-learn fill — the worker loop below
        # produces on demand, gated by the learner's cursor.

        eval_pipeline = PromptPipeline(
            eval_prompts if eval_prompts is not None else prompts,
            model.tokenizer,
            max_prompt_length=model.prompt_length,
        )
        model.add_eval_pipeline(eval_pipeline)

    elif dataset is not None:
        # ---------------- offline ILQL (reference: trlx/trlx.py:61-87)
        samples, rewards = dataset
        if config is None:
            config = default_config("ilql")
        if model_path:
            config.model.model_path = model_path

        if len(samples) != len(rewards):
            raise ValueError(f"Number of samples {len(samples)} should match the number of rewards {len(rewards)}")

        model = get_model(config.model.model_type)(
            config, metric_fn=metric_fn, logit_mask=logit_mask
        )
        orch = get_orchestrator(config.train.orchestrator)(model, split_token=split_token)
        orch.make_experience(samples, rewards)

        eval_pipeline = PromptPipeline(
            eval_prompts if eval_prompts is not None else ([model.tokenizer.bos_token] * config.train.batch_size if model.tokenizer else [[0]] * config.train.batch_size),
            model.tokenizer,
            max_prompt_length=model.prompt_length,
        )
        model.add_eval_pipeline(eval_pipeline)

    else:
        raise ValueError("Either reward_fn or dataset must be given (reference: trlx/trlx.py:89-90)")

    if getattr(model, "fleet_role", None) == "rollout":
        # Disaggregated rollout job: run the persistent worker loop INSTEAD
        # of learn() — generate under the staleness gate, stream episodes,
        # follow the versioned weight broadcast, exit on the coordinated
        # abort marker (trlx_tpu/fleet/runner.py).
        from trlx_tpu.fleet import run_rollout_worker

        run_rollout_worker(model, orch)
        return model

    model.learn()
    return model


mark_imported()  # `setup/import_s` ends here: everything a run imports before it builds a trainer

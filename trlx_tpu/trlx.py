"""User-facing `train()` dispatch (reference: trlx/trlx.py:13-93).

The dispatch contract is identical to the reference: reward_fn → online PPO,
dataset → offline ILQL.
"""

from typing import Callable, List, Optional, Tuple

from trlx_tpu.data.configs import TRLConfig


def train(
    model_path: Optional[str] = None,
    reward_fn: Optional[Callable] = None,
    dataset: Optional[Tuple[List[str], List[float]]] = None,
    prompts: Optional[List[str]] = None,
    eval_prompts: Optional[List[str]] = None,
    metric_fn: Optional[Callable] = None,
    config: Optional[TRLConfig] = None,
    split_token: Optional[str] = None,
    logit_mask: Optional[List[List[bool]]] = None,
    backend: str = "tpu",
):
    """Dispatch to online PPO (reward_fn) or offline ILQL (dataset)
    (reference: trlx/trlx.py:13-93). `backend` accepts "tpu"/"jax" for
    drop-in compatibility with `trlx.train(..., backend='tpu')`."""
    # Imported here, not at module scope: `import trlx_tpu` stays jax-free
    # (trlx_tpu/__init__.py), and trainer modules register themselves at
    # import time.
    from trlx_tpu.trainer.api import train as _train

    return _train(
        model_path=model_path,
        reward_fn=reward_fn,
        dataset=dataset,
        prompts=prompts,
        eval_prompts=eval_prompts,
        metric_fn=metric_fn,
        config=config,
        split_token=split_token,
        logit_mask=logit_mask,
        backend=backend,
    )

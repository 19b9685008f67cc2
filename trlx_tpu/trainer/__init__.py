"""Trainer layer: the RL training loops.

Mirrors the reference's model layer (reference: trlx/model/__init__.py) —
"trainer" here because in functional JAX the nn module and the training logic
are distinct objects.
"""

from abc import abstractmethod
from typing import Any, Callable, Dict, Iterable

# Registry (reference: trlx/model/__init__.py:14-36)
_MODELS: Dict[str, type] = {}


def register_model(name=None):
    """Decorator registering a trainer class by (lowercased) name."""

    def register_class(cls, registered_name):
        _MODELS[registered_name.lower()] = cls
        return cls

    if isinstance(name, str):
        return lambda cls: register_class(cls, name)
    if name is None:
        return lambda cls: register_class(cls, cls.__name__)
    cls = name
    return register_class(cls, cls.__name__)


# alias with the clearer name
register_trainer = register_model


def get_model(name: str) -> type:
    name = name.lower()
    if name in _MODELS:
        return _MODELS[name]
    raise Exception(f"Error: Trying to access a model that has not been registered: {name}")


get_trainer = get_model


class BaseRLTrainer:
    """Abstract RL trainer (reference: trlx/model/__init__.py:39-140)."""

    def __init__(self, config, train_mode: bool = True):
        self.store = None
        self.config = config
        self.train_mode = train_mode

    def push_to_store(self, data: Iterable[Any]):
        """(reference: trlx/model/__init__.py:46-47)"""
        self.store.push(data)

    @abstractmethod
    def act(self, data) -> Any:
        """Rollout a batch (reference: trlx/model/__init__.py:49-55)."""

    @abstractmethod
    def sample(self, prompts, length: int, n_samples: int) -> Any:
        """Sample continuations (reference: trlx/model/__init__.py:57-71)."""

    @abstractmethod
    def learn(self, log_fn: Callable = None, save_fn: Callable = None, eval_fn: Callable = None):
        """Train on stored experience (reference: trlx/model/__init__.py:73-92)."""

    @abstractmethod
    def save(self, directory=None):
        ...

    @abstractmethod
    def load(self, directory=None):
        ...

    def intervals(self, steps: int) -> Dict[str, bool]:
        """Which per-step side effects fire
        (reference: trlx/model/__init__.py:131-140 — which reads a
        log_interval field its TrainConfig never defines; here the field
        exists and works). ``checkpoint_interval: 0`` schedules no save."""
        ci = self.config.train.checkpoint_interval
        return {
            "do_checkpoint": ci > 0 and steps % ci == 0,
            "do_eval": steps % self.config.train.eval_interval == 0,
            "do_log": steps % self.config.train.log_interval == 0,
        }

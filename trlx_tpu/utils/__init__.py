"""General utilities (reference: trlx/utils/__init__.py).

Host-side helpers (timing, batching, filesystem) plus small JAX helpers. The
math ops that run on device live in :mod:`trlx_tpu.ops`.
"""

import math
import os
import time
from typing import Any, Iterable, List

import numpy as np

# jax is imported inside the three pytree helpers below, not here: the
# package must import without the accelerator stack (utils/compile_cache.py
# is asked where the cache is by processes that never start JAX).


def flatten(L: Iterable[Iterable[Any]]) -> List[Any]:
    """Flatten a list of lists (reference: trlx/utils/__init__.py:12-16)."""
    return [x for sublist in L for x in sublist]


def chunk(L: Iterable[Any], chunk_size: int) -> List[List[Any]]:
    """Chunk a list into sublists of chunk_size
    (reference: trlx/utils/__init__.py:19-23)."""
    out = []
    for i in range(0, len(L), chunk_size):
        out.append(L[i : i + chunk_size])
    return out


def safe_mkdir(path: str):
    """mkdir -p (reference: trlx/utils/__init__.py:38-44)."""
    os.makedirs(path, exist_ok=True)


def significant(x: float, ndigits: int = 2) -> float:
    """Round to a number of significant digits (for log readability)."""
    if not isinstance(x, (int, float)) or x == 0 or not math.isfinite(x):
        return x
    return round(x, ndigits - int(math.floor(math.log10(abs(x)))) - 1)


class Clock:
    """Wall-clock timer with samples/sec accounting
    (reference: trlx/utils/__init__.py:50-88).

    On TPU, callers must ``block_until_ready`` (or read a device value) before
    ``tick`` if they want to time device work — JAX dispatch is async.
    """

    def __init__(self):
        self.start = time.time()
        self.total_time = 0.0
        self.total_samples = 0

    def tick(self, samples: int = 0) -> float:
        """Returns time (s) since last tick; optionally accumulates samples."""
        end = time.time()
        delta = end - self.start
        self.start = end
        if samples != 0:
            self.total_time += delta
            self.total_samples += samples
        return delta

    def get_stat(self, n_samp: int = 1000, reset: bool = False) -> float:
        """Seconds per n_samp samples (reference: trlx/utils/__init__.py:74-84)."""
        sec_per_samp = self.total_time / max(self.total_samples, 1)
        if reset:
            self.total_time = 0.0
            self.total_samples = 0
        return sec_per_samp * n_samp


def tree_size_bytes(tree) -> int:
    """Total bytes of all arrays in a pytree (for memory telemetry)."""
    import jax

    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "size") and hasattr(x, "dtype")
    )


def tree_param_count(tree) -> int:
    """Total number of elements in a pytree of arrays."""
    import jax

    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "shape"))


def to_host(tree):
    """Device→host transfer of a pytree (numpy)."""
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


def sentiment_score(sentiment_outputs):
    """Positive-class probabilities from HF sentiment-pipeline outputs
    (capability counterpart of the reference's sentiment_score util,
    reference: trlx/utils/__init__.py:109-116). Accepts either
    top-1 dicts ({label, score}) or per-class score lists."""
    scores = []
    for out in sentiment_outputs:
        if isinstance(out, list):  # pipeline(..., return_all_scores=True)
            by_label = {str(x["label"]).upper(): float(x["score"]) for x in out}
            pos = by_label.get("POSITIVE", by_label.get("LABEL_1", 0.0))
        else:
            label = str(out.get("label", "")).upper()
            pos = float(out["score"]) if label in ("POSITIVE", "LABEL_1") else 1.0 - float(out["score"])
        scores.append(pos)
    return scores

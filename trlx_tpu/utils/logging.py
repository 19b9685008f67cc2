"""Experiment tracking: wandb when available, JSONL + stdout otherwise.

The reference is wandb-centric through Accelerate
(reference: trlx/model/accelerate_base_model.py:31,66-79,244). This container
has no wandb and no egress, so the tracker degrades gracefully: rank-0 writes
`<checkpoint_dir>/metrics.jsonl` and prints compact lines. Setting
`TRLX_TPU_DISABLE_TRACKER` disables tracking entirely — the explicit
counterpart of the reference's generic `debug` env switch
(reference: trlx/model/accelerate_base_model.py:72-79). The old generic
`debug` name is still honored with a deprecation warning for one release.
"""

import importlib.util
import os
import sys
import time
import warnings
from typing import Any, Dict, Optional

from trlx_tpu.parallel.mesh import is_main_process
from trlx_tpu.utils import jsonl
from trlx_tpu.utils.startup import deferred_import

# wandb is looked for here and imported by the first enabled Tracker
# (`_load_wandb`): a process that builds no tracker never pays its import.
try:
    _HAS_WANDB = importlib.util.find_spec("wandb") is not None
except (ImportError, ValueError):  # ValueError: a stub in sys.modules with no __spec__
    _HAS_WANDB = False
wandb = None

# Canonical implementation lives in utils/jsonl (shared with spans/lineage);
# re-exported here because read_jsonl grew up in this module and external
# callers import it from here.
from trlx_tpu.utils.jsonl import read_jsonl  # noqa: F401


def _load_wandb():
    """The wandb module at a tracker's first use, None where it is absent
    or its import fails (the JSONL fallback then serves alone)."""
    global wandb, _HAS_WANDB
    if _HAS_WANDB and wandb is None:
        try:
            wandb = deferred_import("wandb")
        except Exception:
            _HAS_WANDB = False
    return wandb if _HAS_WANDB else None


def _tracker_disabled() -> bool:
    if "TRLX_TPU_DISABLE_TRACKER" in os.environ:
        return os.environ["TRLX_TPU_DISABLE_TRACKER"] not in ("", "0")
    if "debug" in os.environ:
        warnings.warn(
            "the generic `debug` env var for disabling the tracker is deprecated; "
            "set TRLX_TPU_DISABLE_TRACKER=1 instead",
            DeprecationWarning,
            stacklevel=3,
        )
        return True
    return False


class Tracker:
    def __init__(
        self,
        project_name: str,
        config: Optional[Dict[str, Any]] = None,
        run_name: Optional[str] = None,
        entity_name: Optional[str] = None,
        log_dir: str = "ckpts",
    ):
        self.enabled = is_main_process() and not _tracker_disabled()
        self._wandb = None
        self._file = None
        self._stringified_keys = set()  # warned-once registry (log())
        if not self.enabled:
            return
        if _load_wandb() is not None:
            self._wandb = wandb.init(
                project=project_name, name=run_name, entity=entity_name, config=config
            )
        os.makedirs(log_dir, exist_ok=True)
        # Line-atomic append contract shared with spans/lineage — see
        # utils/jsonl for the tear-tolerance story.
        self._file = jsonl.open_line_atomic(os.path.join(log_dir, "metrics.jsonl"))
        if config:
            self._write_record({"_config": {k: str(v) for k, v in config.items()}})

    def _write_record(self, record: Dict[str, Any]):
        jsonl.write_record(self._file, record)

    def log(self, stats: Dict[str, Any], step: Optional[int] = None):
        if not self.enabled:
            return
        scalars = {}
        for k, v in stats.items():
            try:
                scalars[k] = float(v)
            except (TypeError, ValueError):
                # Stringified, not dropped — but say so ONCE per key: a
                # non-numeric value under a metric name is usually a caller
                # bug (an array that needed a reduction, a dict that leaked)
                # and silently storing "'[1 2 3]'" hides it from every
                # downstream plot.
                if k not in self._stringified_keys:
                    self._stringified_keys.add(k)
                    warnings.warn(
                        f"Tracker.log: value for {k!r} is not a scalar "
                        f"({type(v).__name__}) — logged as its str(); reduce "
                        "it to a float before logging to make it plottable",
                        stacklevel=2,
                    )
                scalars[k] = str(v)
        if self._wandb is not None:
            self._wandb.log(scalars, step=step)
        self._write_record({"step": step, "t": round(time.time(), 3), **scalars})

    def log_table(self, name: str, columns, rows, step: Optional[int] = None):
        """Sample tables (≈ wandb.Table at
        reference: trlx/model/accelerate_base_model.py:186-197)."""
        if not self.enabled:
            return
        if self._wandb is not None:
            self._wandb.log({name: wandb.Table(columns=list(columns), data=list(rows))}, step=step)
        preview = rows[:4]
        print(f"[{name}] step={step}", file=sys.stderr)
        for row in preview:
            cells = " | ".join(str(c)[:60] for c in row)
            print(f"  {cells}", file=sys.stderr)
        self._write_record({"table": name, "step": step, "columns": list(columns), "rows": [[str(c) for c in r] for r in rows[:32]]})

    def log_histogram(self, name: str, values, step: Optional[int] = None):
        """Distribution logging (≈ wandb.Histogram of qs/vs/adv during ILQL
        decode, reference: trlx/model/nn/ilql_models.py:238-249). Fallback
        records summary statistics to the JSONL."""
        if not self.enabled:
            return
        import numpy as np

        values = np.asarray(values, dtype=np.float32).reshape(-1)
        if values.size == 0:
            return
        if self._wandb is not None:
            self._wandb.log({name: wandb.Histogram(values)}, step=step)
        self._write_record(
            {
                "histogram": name,
                "step": step,
                "count": int(values.size),
                "mean": float(values.mean()),
                "std": float(values.std()),
                "min": float(values.min()),
                "p5": float(np.percentile(values, 5)),
                "p50": float(np.median(values)),
                "p95": float(np.percentile(values, 95)),
                "max": float(values.max()),
            }
        )

    def finish(self):
        if self._wandb is not None:
            self._wandb.finish()
        if self._file is not None:
            self._file.close()

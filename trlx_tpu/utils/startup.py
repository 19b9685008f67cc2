"""What start-up imports, and when.

The package imports, and a trainer constructs, only what the path to the
first train step uses. An optional dependency loads where it is first called,
through `deferred_import`, under a `setup/import` span: a run that does use it
pays the same seconds as an eager import would, under a name (spans.jsonl when
armed, the profiler's timeline under a session); a run that does not, pays
nothing. What decides is what the run calls: no option, no variable, no
background thread.

Two counters go into every step record (`startup_counters`):

- ``setup/import_s``: wall seconds from the first line of
  ``trlx_tpu/__init__.py`` to the end of ``trlx_tpu.trainer.api``'s import,
  taken once (`mark_imported`). A process that never imports
  ``trlx_tpu.trainer.api`` has no such reading and its records leave the key
  out.
- ``setup/deferred_loaded``: how many of `DEFERRED` are in ``sys.modules``
  when the record is written. 0 says no deferred import has run, so none
  landed inside a timed window.
"""

import importlib
import sys
import time

import trlx_tpu
from trlx_tpu.observability.spans import trace_span

# Third-party packages the path to the first train step never calls, each
# with the site that loads it: trainer/base.py `_build_tokenizer`,
# trainer/base.py `_checkpointer`, utils/logging.py `Tracker.__init__`.
DEFERRED = ("transformers", "orbax.checkpoint", "wandb")

_IMPORT_S = None


def deferred_import(module: str):
    """`importlib.import_module(module)` under a ``setup/import`` span."""
    with trace_span("setup/import", module=module):
        return importlib.import_module(module)


def mark_imported():
    """The last line of ``trlx_tpu/trainer/api.py``: the first call fixes
    ``setup/import_s``."""
    global _IMPORT_S
    if _IMPORT_S is None:
        _IMPORT_S = time.time() - trlx_tpu.IMPORT_T0


def startup_counters() -> dict:
    counters = {"setup/deferred_loaded": float(sum(m in sys.modules for m in DEFERRED))}
    if _IMPORT_S is not None:
        counters["setup/import_s"] = _IMPORT_S
    return counters

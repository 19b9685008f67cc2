"""A configuration's own count of operations and bytes, one module each.

`configs/<c>.json` may name `"flops": "<name>"`; the harness then hands the
readers `counts/<name>.py` in place of `benchmark/flops.py` (the dense GPT
block: 4 d^2 + 2 d d_ff a layer). Such a module exposes what the readers call,
with flops.py's signatures: `ppo_train_step_flops`, `ilql_train_step_flops`,
`layer_windows`, `flash_call`, `logprob_head_call`, `least_seconds`. It may
import flops.py for what it shares with it. A later PR adds a module here
with its configuration; it edits none.
"""

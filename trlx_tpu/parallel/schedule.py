"""On a partitioned mesh, which operand of a dense product travels: the
weight or the rows.

The partition rules (`sharding.lm_partition_rules`) store every kernel with
its `d_model` dimension split over `fsdp`, and the batch is split over
`(dp, fsdp)`. A product `x @ w` then has both operands split over the same
axis on different dimensions, and one of them has to move:

- **the weight travels** (ZeRO-3): it is held, at its point of use, to its
  rule's spec with the `fsdp` axis dropped. That is an all-gather in the
  forward pass and, by transposition, a reduce-scatter of its gradient in
  the backward pass; inside a remat'd block the backward gathers again. The
  gather depends on no activation, so it can run ahead of the product. The
  rows stay where the batch split put them (`hold_rows`, at a block's edges).
- **the rows travel**: nothing is stated, the kernel keeps its shard and the
  partitioner moves activations (what every pass did before this module).

One algorithm, two sizes of call. Moving the rows of a product costs about
`tokens * (d_in + d_out)` elements, moving the weight `d_in * d_out`; so
`weights_travel` is true where the call's tokens times the weight's two
widths exceed the weight's size. At GPT-J's widths the break-even is 2,048
tokens (a 4096 x 4096 projection) to 3,277 (4096 x 16384): a train step of
8,192 tokens, a prefill of 24,576 and a scoring pass of 32,768 gather; a
decode step of 32 tokens, an engine step and a speculative verify window
keep the shards (gathering 12 GB of weights for 32 tokens would be a
hundred times the step). The rule reads the call's shapes and the process
mesh, never a model's name or an option (PERF.md §3, §6 PR 29).

What a pass did is counted at trace time into the dict the caller arms
(`count_weight_gathers`): weight bytes by (parameter path, gathered or
kept), whence the counter `parallel/weight_gather_share`.
"""

import math
import re
import threading
from contextlib import contextmanager
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from trlx_tpu.parallel.mesh import AXIS_DP, AXIS_FSDP, AXIS_SP, partitioned, peek_mesh
from trlx_tpu.parallel.sharding import batch_sharding, lm_partition_rules, sanitize_specs

_armed = threading.local()  # .tally: the dict of the program being traced in this thread


def weights_travel(tokens: int, weight_shape: Sequence[int]) -> bool:
    """The size rule: does a product of `tokens` rows with a weight of
    `weight_shape` (`[..., d_in, d_out]`) move the weight rather than the
    rows. False without an `fsdp` axis to gather over."""
    mesh = peek_mesh()
    if mesh is None or int(mesh.shape[AXIS_FSDP]) == 1:
        return False
    d_in, d_out = weight_shape[-2:]  # a stack of experts: by one expert's widths
    return tokens * (d_in + d_out) > d_in * d_out


def _without_fsdp(spec: P) -> P:
    dims = []
    for d in spec:
        names = tuple(n for n in (d if isinstance(d, tuple) else (d,)) if n is not None and n != AXIS_FSDP)
        dims.append(None if not names else names[0] if len(names) == 1 else names)
    return P(*dims)


def use_spec(path: str, shape: Tuple[int, ...]) -> Tuple[P, P]:
    """(stored, at use) specs of the parameter at `path`: its partition rule
    as `shard_pytree` places it on the process mesh, and the same with the
    `fsdp` axis dropped (`tp` and `sp` stay as the rule says)."""
    mesh = peek_mesh()
    spec = next((s for pattern, s in lm_partition_rules() if re.search(pattern, path)), P())  # as match_partition_rules
    stored = sanitize_specs(mesh, jax.ShapeDtypeStruct(shape, "float32"), spec)
    return stored, _without_fsdp(stored)


def use_weight(w, path: Sequence[str], tokens: int, lookup: bool = False):
    """`w` as a product over `tokens` rows uses it: gathered over `fsdp`
    where `weights_travel` says so, else as it is. `path` is the parameter's
    path in the tree (a module's `self.path` and the leaf's name). Returns
    its argument without a partitioned mesh. `lookup`: `w` is a table read
    by index, not multiplied: its backward is a scatter-add, and asked for
    the table's shards the partitioner moves the rows to them (an
    all-to-all); so a table's gradient is left whole on every chip (an
    all-reduce of a table, 8 MB at GPT-Neo's positions)."""
    if not partitioned():
        return w
    path = "/".join(path)
    stored, at_use = use_spec(path, w.shape)
    if stored == at_use:
        return w  # the rule never split it over fsdp: nothing to gather
    travels = weights_travel(tokens, w.shape)
    tally = getattr(_armed, "tally", None)
    if tally is not None:
        tally[(path, travels)] = w.size * w.dtype.itemsize
    if not travels:
        return w
    mesh = peek_mesh()
    if lookup:
        return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, at_use))
    return _gathered(w, NamedSharding(mesh, stored), NamedSharding(mesh, at_use))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gathered(w, stored: NamedSharding, at_use: NamedSharding):
    """`w` held to `at_use`; its cotangent held to `stored`, so that the
    chips' partial weight gradients are reduce-scattered into the shards the
    optimizer updates (the plain constraint's transpose asks for the whole
    gradient on every chip: an all-reduce of twice the traffic)."""
    return jax.lax.with_sharding_constraint(w, at_use)


_gathered.defvjp(
    lambda w, stored, at_use: (jax.lax.with_sharding_constraint(w, at_use), None),
    lambda stored, at_use, _, ct: (jax.lax.with_sharding_constraint(ct, stored),),
)


def gathering_dot_general(path: Sequence[str]):
    """`lax.dot_general` whose rhs is a kernel at `path`, for a flax module
    that takes its product as an argument (`nn.Dense(dot_general=...)`)."""

    def dot_general(x, kernel, dimension_numbers, precision=None, preferred_element_type=None):
        kernel = use_weight(kernel, path, math.prod(x.shape[:-1]))
        return jax.lax.dot_general(x, kernel, dimension_numbers, precision=precision,
                                   preferred_element_type=preferred_element_type)

    return dot_general


def hold_rows(x):
    """`x` `[b, T, ...]` (the residual stream at a block's edge) held to the
    batch split `P((dp, fsdp), ...)`, the sequence over `sp` where the mesh
    has one, in the passes whose weights travel: where the narrowest product
    of a block, `d_model x d_model`, gathers. Returns its argument without a
    partitioned mesh, or where the batch does not divide the data axes."""
    if not partitioned():
        return x
    mesh = peek_mesh()
    d_model = x.shape[-1]
    data = int(mesh.shape[AXIS_DP]) * int(mesh.shape[AXIS_FSDP])
    if x.shape[0] % data or not weights_travel(x.shape[0] * x.shape[1], (d_model, d_model)):
        return x
    sp = int(mesh.shape[AXIS_SP])
    over_sp = sp > 1 and x.shape[1] % sp == 0
    return jax.lax.with_sharding_constraint(x, batch_sharding(mesh, x.ndim - 1, seq_axis=1 if over_sp else None))


@contextmanager
def count_weight_gathers(tally: Dict):
    """While a program is traced inside, `use_weight` records into `tally`
    the weight bytes of each product by `(path, gathered)`. A call that
    traces nothing (the program is compiled) leaves `tally` as it was."""
    prior = getattr(_armed, "tally", None)
    _armed.tally = tally
    try:
        yield tally
    finally:
        _armed.tally = prior


def weight_gather_share(tally: Dict) -> Optional[float]:
    """Weight bytes of the products that took the gather over those of all
    the products whose kernel is split over `fsdp`; None where none was
    counted (no partitioned mesh, or nothing traced yet)."""
    total = sum(tally.values())
    if not total:
        return None
    return sum(n for (_, gathered), n in tally.items() if gathered) / total

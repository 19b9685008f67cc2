"""Device mesh construction and multi-host bootstrap.

The reference's distributed backend is NCCL hidden behind Accelerate
(reference: trlx/model/accelerate_base_model.py:31-36 — Accelerator() process
group init + torch.distributed.barrier). The TPU-native design replaces all of
it with one object: a `jax.sharding.Mesh` over four named axes

    dp    — pure data parallel (params replicated, batch sharded)
    fsdp  — data parallel with param/optimizer sharding (≡ ZeRO-3; the
            equivalent of the reference's DeepSpeed zero_stage 2/3,
            reference: configs/deepspeed_configs/default_configs.yml:2-9)
    tp    — tensor (Megatron-style) parallel over hidden/vocab dims
    sp    — sequence/context parallel (ring attention over the seq dim)

Collectives (psum/all_gather/reduce_scatter/ppermute) are emitted by XLA from
sharding annotations — there is no hand-written NCCL analogue. Axis ORDER
matters for ICI locality: the innermost (fastest-varying) mesh dims should map
to physically adjacent chips, so tp (latency-bound, every-layer collectives)
is placed innermost.
"""

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from trlx_tpu.utils import sanitize

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SP = "sp"
MESH_AXES = (AXIS_DP, AXIS_FSDP, AXIS_TP, AXIS_SP)
# Axes over which the *batch* dimension is sharded (fsdp is a flavor of data
# parallelism: same batch sharding, plus param sharding).
DATA_AXES = (AXIS_DP, AXIS_FSDP)

_GLOBAL_MESH: Optional[Mesh] = None


def init_distributed(coordinator_address: Optional[str] = None, num_processes: Optional[int] = None, process_id: Optional[int] = None):
    """Multi-host bootstrap over DCN.

    The analogue of Accelerate's process-group init + barrier
    (reference: trlx/model/accelerate_base_model.py:31-36). On a TPU pod,
    call with no args — jax auto-detects the coordinator from TPU metadata.
    On single-host CPU/dev environments with no multi-host signal this is a
    no-op. Safe to call twice (already-initialized is tolerated); genuine
    config errors propagate.
    """
    multi_host_signal = (
        coordinator_address is not None
        or num_processes is not None
        or "JAX_COORDINATOR_ADDRESS" in os.environ
        or os.environ.get("TPU_WORKER_HOSTNAMES", "localhost") not in ("", "localhost")
    )
    if not multi_host_signal:
        return  # single host dev environment
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        if "already" not in str(e).lower():
            raise


def resolve_mesh_shape(shape: Sequence[int], n_devices: Optional[int] = None) -> Tuple[int, ...]:
    """Resolve a mesh shape with at most one -1 ("fill remaining devices").

    e.g. (-1, 1, 1, 1) on 8 devices → (8, 1, 1, 1).
    """
    n_devices = n_devices if n_devices is not None else jax.device_count()
    shape = tuple(int(s) for s in shape)
    if shape.count(-1) > 1:
        raise ValueError(f"mesh shape can have at most one -1, got {shape}")
    fixed = int(np.prod([s for s in shape if s != -1]))
    if -1 in shape:
        if n_devices % fixed != 0:
            raise ValueError(f"{n_devices} devices not divisible by fixed mesh product {fixed}")
        shape = tuple(n_devices // fixed if s == -1 else s for s in shape)
    if int(np.prod(shape)) != n_devices:
        raise ValueError(f"mesh {shape} needs {int(np.prod(shape))} devices, have {n_devices}")
    return shape


def make_mesh(shape: Sequence[int] = (-1, 1, 1, 1), devices=None) -> Mesh:
    """Build the 4-axis (dp, fsdp, tp, sp) device mesh.

    ``devices`` defaults to all addressable+remote devices;
    `mesh_utils.create_device_mesh` places them so the tp axis rides
    ICI-adjacent chips. A shape it cannot place on the physical topology is
    an error — a row-major reshape would run, on links the axis order was
    chosen to avoid.
    """
    from jax.experimental import mesh_utils

    if devices is None:
        devices = jax.devices()
    shape = resolve_mesh_shape(shape, len(devices))
    return Mesh(mesh_utils.create_device_mesh(shape, devices=devices), MESH_AXES)


def set_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def peek_mesh() -> Optional[Mesh]:
    """The process-global mesh if one was created, else None (no side
    effects — unlike get_mesh, which creates a default mesh)."""
    return _GLOBAL_MESH


def partitioned() -> bool:
    """A process mesh of more than one device: the one rule by which the
    routes in ops/ that cannot be partitioned stand back (the Pallas kernels,
    `flash_attention.one_device_tpu`; the ranged cache read,
    `kv_read.ranged_read`). It reads the process-global mesh, not the arrays
    a call is given (ROADMAP: routing reads a module global)."""
    return _GLOBAL_MESH is not None and _GLOBAL_MESH.size > 1


def get_mesh(shape: Sequence[int] = (-1, 1, 1, 1)) -> Mesh:
    """Return the process-global mesh, creating it on first use."""
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = make_mesh(shape)
    return _GLOBAL_MESH


def to_local_host(tree, mesh: Optional[Mesh] = None, batch_axes=DATA_AXES):
    """Global (possibly multi-host sharded) device arrays → THIS process's
    batch rows as host numpy.

    The device→host inverse of the put_batch direction
    (host_local_array_to_global_array): each process gets back exactly the
    rows it fed in, so rollout decode/score/store stay process-local and the
    whole path is process-count-agnostic. A plain np.asarray on a multi-host
    global array would throw on non-addressable shards. Single-process (and
    for host numpy passed through): a plain np.asarray.
    """
    # Sanitizer checkpoint: pulling a donated buffer to host is the classic
    # use-after-donate read — fail here with the donation site, not with
    # jax's anonymous "Array has been deleted" downstream.
    sanitize.check_host_read(tree, "to_local_host")

    def pull(x):
        if jax.process_count() == 1 or not isinstance(x, jax.Array):
            return np.asarray(x)
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec

        spec = PartitionSpec(batch_axes, *([None] * (x.ndim - 1)))
        m = mesh if mesh is not None else get_mesh()
        return np.asarray(
            multihost_utils.global_array_to_host_local_array(x, m, spec)  # graftlint: disable=GL004 -- pull() only runs inside the collective_guard("to_local_host") tree_map below
        )

    if jax.process_count() > 1:
        # Reading a global array blocks until every host's shards exist — a
        # dead peer would hang this forever; the guard converts that into a
        # deadline'd CollectiveTimeout abort (resilience/distributed.py).
        from trlx_tpu.resilience.distributed import collective_guard

        with collective_guard("to_local_host"):
            return jax.tree_util.tree_map(pull, tree)
    return jax.tree_util.tree_map(pull, tree)


def allgather_host(tree):
    """Each process's host-local numpy rows → the full global rows on every
    process, concatenated along axis 0 in process order.

    The counterpart of the reference's eval-time accelerator.gather
    (reference: trlx/model/accelerate_base_model.py:149-158). Single-process:
    identity (np.asarray).
    """
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(np.asarray, tree)
    from jax.experimental import multihost_utils

    from trlx_tpu.resilience.distributed import collective_guard

    # Guarded: an allgather with a dead/wedged peer never completes — abort
    # with CollectiveTimeout after train.collective_deadline instead.
    with collective_guard("allgather_host"):
        return jax.tree_util.tree_map(
            lambda x: np.asarray(multihost_utils.process_allgather(np.asarray(x), tiled=True)),
            tree,
        )


def barrier(name: str = "trlx_tpu_barrier"):
    """Cross-host barrier ≈ the reference's torch.distributed.barrier
    (reference: trlx/model/accelerate_base_model.py:33-34). A tiny psum forces
    all hosts/devices to synchronize. Guarded by the collective deadline —
    a barrier whose peer died aborts with CollectiveTimeout, not a hang."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        from trlx_tpu.resilience.distributed import collective_guard

        with collective_guard(f"barrier:{name}"):
            multihost_utils.sync_global_devices(name)


def broadcast_host(value):
    """Rank-0's host value → every process (the guarded counterpart of a bare
    ``multihost_utils.broadcast_one_to_all``). Used for process-agreed
    decisions (e.g. "does a checkpoint exist?") that every host must answer
    identically before entering a collective code path. Single-process:
    identity."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    from trlx_tpu.resilience.distributed import collective_guard

    # Guarded: a broadcast with a dead coordinator never completes — abort
    # with CollectiveTimeout after train.collective_deadline instead.
    with collective_guard("broadcast_host"):
        return multihost_utils.broadcast_one_to_all(value)


def is_main_process() -> bool:
    """Rank-0 check for logging/checkpoint side effects
    (≈ accelerator.is_main_process, reference: trlx/model/accelerate_base_model.py:66)."""
    return jax.process_index() == 0

"""Every Pallas entry point in ops/ lowers — and compiles — for a TPU, on CPU.

The static tile rule (tests/test_tiling.py) is only the first of Mosaic's
checks. Twice a kernel passed it, passed its interpret-mode parity tests and
could not run on the chip: a block mapping the lowering rejected, then a
batched matrix-vector `dot_general` Mosaic cannot express (hidden, three PRs
deep, by a probe that caught the exception and routed to einsum). Neither
needs a chip to find:

- `jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))` runs the
  jaxpr → Mosaic lowering for a TPU target in seconds on a CPU;
- `jax.experimental.topologies` describes a v5e host to the installed
  libtpu, and `.lower(...).compile()` against it runs the REST of the
  compiler — Mosaic's layout passes and the scoped-VMEM limit — with no
  device attached.

Shapes are the GPT-J-6B ones chip_smoke.py runs (flagship train batch,
logprob head). Run this before spending chip time on a kernel. The last test
holds the other end: a kernel in ops/ that no benchmark cell expects is a
kernel no program runs.
"""

import ast
import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from trlx_tpu.ops.flash_attention import flash_attention
from trlx_tpu.ops.fused_logprob import fused_logprob

B, T, H, D = 8, 1024, 16, 256  # train batch, seq, heads x head_dim
N, DM, V = 2056, 4096, 50400  # logprob head: 8 x 257 rows, d_model, vocab
SCALE = 1.0 / 16.0


def _flash_fwd(q, k, v, m):
    return flash_attention(q, k, v, m, scale=SCALE, causal=True, interpret=False)


def _flash(q, k, v, m):
    """value_and_grad: the forward kernel and both backward kernels."""
    loss = lambda q, k, v: _flash_fwd(q, k, v, m).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_windowed(q, k, v, m):
    """GPT-Neo's calls: 128-wide heads, unscaled, a local layer's window
    (the loop's lower bound and the edge chunks below the mask-free run)."""
    loss = lambda q, k, v: flash_attention(
        q, k, v, m, scale=1.0, causal=True, window=256, interpret=False
    ).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_grouped(q, k, v, m):
    """Grouped keys (64 query heads over 8 K/V heads of 128) under a 128-key
    window: K and V by the index map, dk/dv summed over a group in scratch."""
    loss = lambda q, k, v: flash_attention(
        q, k, v, m, scale=128**-0.5, causal=True, window=128, interpret=False
    ).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_ring_chunk(q, k, v, m, offset):
    """A sequence too long to be resident (major pieces, the state carried in
    scratch) called the way the ring path calls it: a traced offset (the
    loop bounds are traced scalars), lse returned and differentiated."""
    def loss(q, k, v):
        o, lse = flash_attention(q, k, v, m, scale=SCALE, causal=True, offset=offset, return_lse=True, interpret=False)
        return o.astype(jnp.float32).sum() + lse.sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _fused(x, w, y, b):
    """value_and_grad: the forward kernel and both backward kernels."""
    loss = lambda x, w, b: sum(
        o.sum() for o in fused_logprob(x, w, y, b, tied=False, interpret=False)
    )
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)


def _cases(s):
    """(name, fn, abstract args) with `s(shape, dtype)` building each arg."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    qkv = s((B, T, H, D), bf16)
    head = (s((N, DM), bf16), s((DM, V), bf16), s((N,), i32), s((V,), bf16))
    return [
        ("flash fwd+bwd", _flash, (qkv, qkv, qkv, s((B, T), f32))),
        ("flash fwd+bwd windowed d128", _flash_windowed, (s((16, 512, H, 128), bf16),) * 3 + (s((16, 512), f32),)),
        ("flash fwd+bwd grouped keys windowed d128", _flash_grouped,
         (s((4, 1024, 64, 128), bf16), s((4, 1024, 8, 128), bf16), s((4, 1024, 8, 128), bf16), s((4, 1024), f32))),
        ("flash fwd+bwd major pieces, traced offset", _flash_ring_chunk,
         (s((1, 8192, H, D), bf16),) * 3 + (s((1, 8192), f32), s((), f32))),
        ("fused_logprob fwd+bwd", _fused, head),
    ]


CASE_NAMES = [name for name, _, _ in _cases(jax.ShapeDtypeStruct)]


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_lowers_for_tpu(name):
    """jaxpr → Mosaic for a TPU target. Catches the block-mapping class of
    failure and the dot_general-Mosaic-cannot-express class."""
    (fn, args), = [(f, a) for n, f, a in _cases(jax.ShapeDtypeStruct) if n == name]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def v5e_sharding():
    """A one-device sharding on a described (not attached) v5e host."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu to describe a TPU with
        pytest.skip(f"no TPU topology description available here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_compiles_for_v5e(name, v5e_sharding):
    """The whole compiler, device-free: Mosaic's layout passes and the
    scoped-VMEM limit run at compile(), which lowering alone never reaches."""
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    (fn, args), = [(f, a) for n, f, a in _cases(s) if n == name]
    jax.jit(fn).lower(*args).compile()


def test_mosaic_kernels_refuse_a_multi_device_jit(v5e_sharding):
    """Why every model-layer gate requires a one-device mesh
    (flash_attention.one_device_tpu): jax will not partition a Mosaic call."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    sh = NamedSharding(Mesh(np.array(topo.devices), ("dp",)), PartitionSpec("dp"))
    qkv = jax.ShapeDtypeStruct((B, 256, H, D), jnp.bfloat16, sharding=sh)
    mask = jax.ShapeDtypeStruct((B, 256), jnp.float32, sharding=sh)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_flash_fwd).lower(qkv, qkv, qkv, mask)


def test_one_device_rule(monkeypatch):
    """On a TPU backend the gates open on a one-device mesh and close on a
    larger one; off TPU they are closed."""
    from trlx_tpu.ops import flash_attention as fa
    from trlx_tpu.ops.fused_logprob import fused_logprob_eligible
    from trlx_tpu.parallel import mesh as mesh_mod

    assert not fa.one_device_tpu()  # the CPU backend of this test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert fa.one_device_tpu() and fused_logprob_eligible(DM, V)
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", mesh_mod.make_mesh([1, 1, 1, 1], jax.devices()[:1]))
    assert fa.one_device_tpu()
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", mesh_mod.make_mesh([4, 1, 1, 1], jax.devices()[:4]))
    assert not fa.one_device_tpu() and not fused_logprob_eligible(DM, V)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def _pallas_calls_in_ops():
    """{"<module>.<kernel function>": [(line, has a name=), ...]} of every
    `pallas_call` under trlx_tpu/ops/, read from the source: the key is the
    one the benchmark records at trace time (benchmark/harness.py
    `record_pallas_calls`) and a cell's `expect_kernels` lists."""
    calls = {}
    for path in sorted(glob.glob(os.path.join(REPO, "trlx_tpu", "ops", "*.py"))):
        module = os.path.basename(path)[:-3]
        with open(path) as f:
            tree = ast.parse(f.read())
        functions = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}
        assigned = {n.targets[0].id: n.value for n in ast.walk(tree)
                    if isinstance(n, ast.Assign) and isinstance(n.targets[0], ast.Name)}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call"):
                continue
            kernel = node.args[0]
            if isinstance(kernel, ast.Name) and kernel.id not in functions:
                kernel = assigned.get(kernel.id, kernel)  # `kernel = functools.partial(_fwd_kernel, ...)`
            if isinstance(kernel, ast.Call) and getattr(kernel.func, "attr", "") == "partial":
                kernel = kernel.args[0]
            # a kernel this cannot name is a case of its own, and fails below
            fn = kernel.id if isinstance(kernel, ast.Name) and kernel.id in functions else f"<line {node.lineno}>"
            named = any(kw.arg == "name" for kw in node.keywords)
            calls.setdefault(f"{module}.{fn}", []).append((node.lineno, named))
    return calls


@pytest.mark.parametrize("kernel", sorted(_pallas_calls_in_ops()))
def test_every_pallas_kernel_in_ops_is_expected_by_a_cell(kernel):
    """A kernel stays in ops/ while a benchmark cell runs it. Every
    `pallas_call` carries a `name=` (how a device trace finds it), and its
    kernel is in `expect_kernels` of at least one cell, so the chip run of
    that cell fails when the route to it closes. A kernel that loses its
    measurement goes, with its gates; it does not stay switched off."""
    expected = set()
    for path in glob.glob(os.path.join(REPO, "benchmark", "workloads", "*.json")):
        with open(path) as f:
            expected |= set(json.load(f)["expect_kernels"])
    unnamed = [line for line, named in _pallas_calls_in_ops()[kernel] if not named]
    assert not unnamed, f"{kernel}: pallas_call without name= at line(s) {unnamed}"
    assert kernel in expected, f"{kernel} is in no cell's expect_kernels: {sorted(expected)}"

"""Test env: force CPU JAX with 8 virtual devices BEFORE any jax backend init.

The reference has no distributed tests at all (SURVEY.md §4); here mesh
semantics are tested single-host via --xla_force_host_platform_device_count.
"""

import os

# Tests run on the CPU, wherever they are started: a chip belongs to one
# process at a time and is spent on chip_smoke.py and the benchmark, not on
# a suite that spawns subprocesses. The env var covers children; the
# jax.config.update below covers an interpreter whose platform list was
# already configured before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    # Two-tier suite (the reference's whole suite is one 46-LoC file and runs
    # per-push, reference: .github/workflows/build.yml:33-41; this repo's suite
    # outgrew a per-commit budget, so the fast tier is the per-commit signal):
    #   make test-fast  → -m "not slow"  (< ~3 min CPU)
    #   make test       → everything     (nightly / pre-release)
    config.addinivalue_line(
        "markers",
        "slow: learning-gate / e2e / multihost / pallas-kernel tests; excluded by `make test-fast`",
    )
    config.addinivalue_line(
        "markers",
        "network: needs internet + HF checkpoint downloads; skipped unless TRLX_TPU_NETWORK=1 (see RUNBOOK.md)",
    )


import pytest


@pytest.fixture(autouse=True)
def _no_leftover_process_mesh():
    """`parallel/mesh.py` keeps ONE process-global mesh, and what a program
    traces to depends on it (a mesh of more than one device keeps the full
    cache read, ops/kv_read.py; the kernel gates close). A test that builds
    a trainer or a mesh leaves it behind for whichever test its xdist worker
    runs next, so each test starts and ends with none."""
    from trlx_tpu.parallel import mesh

    mesh._GLOBAL_MESH = None
    yield
    mesh._GLOBAL_MESH = None

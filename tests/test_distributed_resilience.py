"""2-process distributed fault drills (trlx_tpu/resilience/distributed.py).

The single-process resilience suite (tests/test_resilience.py) proves the
mechanisms in isolation; these drills prove the COORDINATED behavior only a
fleet exhibits, with real jax.distributed processes on CPU:

- drill A (``host_hang``): one host wedges mid-step → the healthy host's
  ``collective_guard`` deadline fires inside the next fingerprint allgather
  and aborts with exit code EXIT_COLLECTIVE_TIMEOUT and a CollectiveTimeout
  diagnostic naming the hung host.
- drill B (preemption): SIGTERM lands on ONE host → the save-and-exit flag
  is process-agreed, both hosts write the SAME checkpoint step, latest.txt
  flips only after both committed — and a 2-process resume continues to
  completion with host-identical state (the per-step desync guard is the
  witness) and finite losses.
- drill C (``host_desync``): one host's local replica of a replicated param
  is silently perturbed → the fingerprint check catches it within one check
  period and EVERY host raises the identical HostDesync naming host 1.

Skipped gracefully (same patterns as tests/test_multihost.py) when the
environment can't run two coordinated jax.distributed processes. Run via
``make test-multihost`` — slow-marked, excluded from the fast tier.
"""

import os
import socket
import subprocess
import sys

import pytest

from trlx_tpu.resilience.distributed import EXIT_COLLECTIVE_TIMEOUT

pytestmark = pytest.mark.slow  # excluded from `make test-fast` (see conftest)

_DRILL_WORKER = r"""
import json, os, sys
import numpy as np

mode = sys.argv[1]            # "hang" | "preempt" | "desync"
pid = int(sys.argv[2])
port = sys.argv[3]
ckpt = sys.argv[4]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TRLX_TPU_NO_PROGRESS"] = "1"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid,
    local_device_ids=[0, 1],
)
assert jax.process_count() == 2

sys.path.insert(0, os.path.join(os.environ["TRLX_REPO"], "examples"))
import trlx_tpu
from randomwalks import base_config, generate_random_walks
from trlx_tpu.resilience import distributed as dist_res

walks, logit_mask, metric_fn, reward_fn = generate_random_walks(
    n_nodes=15, max_length=8, n_walks=60, seed=1000
)

per = 8  # per-process rows

def make_config(total_steps, resume=False):
    config = base_config("ppo", 15, 8)
    config.train.total_steps = total_steps
    config.train.epochs = 100
    config.train.batch_size = per
    config.train.eval_interval = 10**6
    # log_interval huge on purpose: the buffered resilience scalars never
    # flush mid-drill, so the first cross-host BLOCKING op after an injected
    # hang is the GUARDED fingerprint allgather, not an unguarded stats sync.
    config.train.log_interval = 10**6
    config.train.checkpoint_interval = 10**6
    config.train.checkpoint_dir = ckpt
    config.train.mesh = [4, 1, 1, 1]
    config.train.resume_from_checkpoint = resume
    config.method.num_rollouts = per
    config.method.chunk_size = per
    config.method.ppo_epochs = 2
    # distributed resilience knobs under drill
    config.train.heartbeat_interval = 0.2
    # Generous deadline: it must cover first-call compilation of any program
    # launched INSIDE a guarded collective on a loaded CI core, while still
    # converting a real hang into an abort within the test budget.
    config.train.collective_deadline = 30.0
    config.train.desync_check_interval = 2 if mode == "desync" else 1
    config.train.preempt_check_interval = 1
    return config

prompts = [[(i % 14) + 1] for i in range(8 * pid, 8 * (pid + 1))]
eval_prompts = [[1], [2]]

def run(total_steps, resume=False):
    return trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=eval_prompts,
        metric_fn=metric_fn, config=make_config(total_steps, resume),
        logit_mask=logit_mask,
    )

if mode == "hang":
    # Faults come from each process's own env (set by the test harness):
    # proc 1 carries host_hang@2 and wedges after step 2; proc 0 blocks in
    # the step-2 fingerprint allgather and must be aborted by the guard
    # (exit 117) — this print is only reachable if detection FAILED.
    run(total_steps=10)
    print(f"hang proc {pid} FINISHED WITHOUT ABORT")

elif mode == "preempt":
    # Proc 1 carries sigterm@2: SIGTERM on one host only. The agreement
    # allgather (preempt_check_interval=1) flips both hosts, both enter the
    # collective save at step 2, latest.txt lands only after both committed.
    model = run(total_steps=10)
    assert model.iter_count == 2, model.iter_count
    with open(os.path.join(ckpt, "latest.txt")) as f:
        assert f.read().strip() == "state_2"
    states = [e for e in os.listdir(ckpt) if e.startswith("state_") and
              os.path.isdir(os.path.join(ckpt, e))]
    assert states == ["state_2"], states  # ONE coordinated checkpoint
    print(f"preempt proc {pid} SAVED state_2")

    # Resume on both hosts and run to completion. The per-step desync guard
    # (desync_check_interval=1) is the witness that the restored state is
    # host-identical at EVERY step — any divergence raises HostDesync.
    os.environ.pop("TRLX_TPU_FAULTS", None)
    model2 = run(total_steps=4, resume=True)
    assert model2._resumed, "did not resume from the coordinated checkpoint"
    assert model2.iter_count == 4, model2.iter_count
    dist_res.verify_fingerprints(
        dist_res.host_fingerprint(
            model2.iter_count, model2.state.params, rng=model2.rng
        )
    )
    if pid == 0:
        from trlx_tpu.utils.logging import read_jsonl
        losses = [r["loss"] for r in read_jsonl(os.path.join(ckpt, "metrics.jsonl"))
                  if "loss" in r]
        assert losses and all(np.isfinite(losses)), losses
    print(f"preempt proc {pid} OK")

elif mode == "desync":
    # Proc 1 carries host_desync@1: its local replica of a replicated param
    # leaf is perturbed after step 1. The step-2 fingerprint check must
    # catch it — on BOTH hosts, with the identical error naming host 1.
    try:
        run(total_steps=10)
    except dist_res.HostDesync as e:
        assert "host 1" in str(e), str(e)
        assert "param replica crc32" in str(e), str(e)
        print(f"desync proc {pid} OK")
    else:
        print(f"desync proc {pid} GUARD MISSED THE DIVERGENCE")
"""


def _launch(tmp_path, mode, faults_by_pid):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "drill_worker.py"
    script.write_text(_DRILL_WORKER)
    ckpt = str(tmp_path / f"ckpt_{mode}")
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("TRLX_TPU_FAULTS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo
        env["TRLX_REPO"] = repo
        if pid in faults_by_pid:
            env["TRLX_TPU_FAULTS"] = faults_by_pid[pid]
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), mode, str(pid), str(port), ckpt],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
        )
    return procs, ckpt


def _communicate(procs, timeout, skip_on_timeout=True):
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out.decode(errors="replace"))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        if skip_on_timeout:
            pytest.skip("2-process drill did not complete in this environment")
        raise
    return outs


def _skip_if_distributed_unavailable(proc, out):
    if proc.returncode != 0 and (
        ("initialize" in out and "failed" in out.lower())
        # jaxlib builds without cross-process CPU collectives raise this from
        # the very first sync_global_devices — nothing distributed can run.
        or "Multiprocess computations aren't implemented" in out
    ):
        pytest.skip(f"jax.distributed unavailable here: {out[-400:]}")


def test_drill_host_hang_aborts_with_collective_timeout(tmp_path):
    """Drill A: host 1 wedges after step 2 → host 0's guarded fingerprint
    allgather hits the deadline → CollectiveTimeout diagnostic naming the
    hung host + hard abort with the dedicated exit code."""
    procs, _ = _launch(tmp_path, "hang", {1: "host_hang@2"})
    try:
        out0, _ = procs[0].communicate(timeout=900)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("2-process drill did not complete in this environment")
    finally:
        procs[1].kill()  # intentionally hung for TRLX_TPU_HANG_SECONDS
        procs[1].communicate()
    out0 = out0.decode(errors="replace")
    _skip_if_distributed_unavailable(procs[0], out0)
    assert procs[0].returncode == EXIT_COLLECTIVE_TIMEOUT, (
        f"expected exit {EXIT_COLLECTIVE_TIMEOUT}, got {procs[0].returncode}:\n{out0[-4000:]}"
    )
    assert "CollectiveTimeout" in out0
    assert "collective_deadline" in out0
    assert "slowest host: host 1" in out0  # heartbeat stall report named it
    assert "FINISHED WITHOUT ABORT" not in out0


def test_drill_preemption_coordinated_save_and_resume(tmp_path):
    """Drill B: SIGTERM on host 1 only → both hosts agree, write ONE
    checkpoint at the identical step, and a 2-process resume runs to
    completion with host-identical state and finite losses."""
    procs, _ = _launch(tmp_path, "preempt", {1: "sigterm@2"})
    outs = _communicate(procs, timeout=900)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        _skip_if_distributed_unavailable(p, out)
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"preempt proc {pid} SAVED state_2" in out
        assert f"preempt proc {pid} OK" in out


def test_drill_host_desync_caught_by_fingerprint_guard(tmp_path):
    """Drill C: host 1's replica silently perturbed after step 1 → the
    step-2 fingerprint check raises the identical HostDesync (naming host 1
    and the mismatched component) on BOTH hosts."""
    procs, _ = _launch(tmp_path, "desync", {1: "host_desync@1"})
    outs = _communicate(procs, timeout=900)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        _skip_if_distributed_unavailable(p, out)
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"desync proc {pid} OK" in out
        assert "GUARD MISSED THE DIVERGENCE" not in out

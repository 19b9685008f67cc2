"""2-process graftfleet drills (trlx_tpu/observability/fleet.py).

tests/test_fleet.py proves the federation pieces in isolation; these drills
prove the CROSS-HOST story with real jax.distributed processes on CPU:

- drill A (``slow_host``): host 1 stalls 2s at steps 2 and 4 with graftfleet
  + the metrics endpoint armed → ONE merged Chrome trace with a lane per
  host and a stated clock-alignment bound, a per-collective skew table whose
  worst-host column names the injected laggard, live ``trlx_tpu_fleet_*``
  gauges (per-host labeled) in a /metrics scrape taken DURING the run, and a
  /healthz ``fleet`` block carrying both hosts' heartbeats.
- drill B (``host_hang``): host 1 wedges → host 0's collective_guard abort
  (exit EXIT_COLLECTIVE_TIMEOUT) leaves a fleet incident bundle under
  ``incidents/<step>/`` containing BOTH hosts' span tails — the aborting
  host collects its wedged peer's file from the shared checkpoint dir.

When ``TRLX_TPU_DRILL_ARTIFACTS`` is set (the CI job does), the merged
fleet trace, the report's Fleet section, and the live scrapes are copied
there for upload. Skipped gracefully (same patterns as
tests/test_distributed_resilience.py) when the environment can't run two
coordinated jax.distributed processes. Run via ``make fleet-drill`` (which
also arms TRLX_TPU_SANITIZE=dispatch,donation,race) or ``make
test-multihost`` — slow-marked, excluded from the fast tier.
"""

import json
import os
import shutil
import socket
import subprocess
import sys

import pytest

from trlx_tpu.resilience.distributed import EXIT_COLLECTIVE_TIMEOUT

pytestmark = pytest.mark.slow  # excluded from `make test-fast` (see conftest)

_DRILL_WORKER = r"""
import json, os, sys, threading, time
import urllib.request

mode = sys.argv[1]  # "slow" | "hang" | "engine" | "engine_spec" | "engine_kill"
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

walks, logit_mask, metric_fn, reward_fn = generate_random_walks(
    n_nodes=15, max_length=8, n_walks=60, seed=1000
)

per = 8  # per-process rows

def make_config(total_steps):
    config = base_config("ppo", 15, 8)
    config.train.total_steps = total_steps
    config.train.epochs = 100
    config.train.batch_size = per
    config.train.eval_interval = 10**6
    config.train.checkpoint_interval = 10**6
    config.train.checkpoint_dir = ckpt
    config.train.mesh = [4, 1, 1, 1]
    config.method.num_rollouts = per
    config.method.chunk_size = per
    config.method.ppo_epochs = 2
    config.train.graftfleet = True  # config-consistent across hosts
    config.train.heartbeat_interval = 0.2
    # Generous deadline: it must cover first-call compilation of any program
    # launched INSIDE a guarded collective on a loaded CI core, while still
    # converting drill B's real hang into an abort within the test budget.
    config.train.collective_deadline = 30.0
    config.train.desync_check_interval = 1  # a guarded allgather every step
    if mode == "slow":
        # Per-step log boundaries feed the fleet window rollup + exporter;
        # a resync mid-run exercises the periodic clock re-estimate.
        config.train.log_interval = 1
        config.train.fleet_resync_interval = 2
    else:
        # Buffered scalars must never flush mid-drill: the first cross-host
        # BLOCKING op after the injected hang has to be the GUARDED
        # fingerprint allgather, not an unguarded stats sync.
        config.train.log_interval = 10**6
    return config

if mode in ("engine", "engine_spec", "engine_kill"):
    # Multi-process ENGINE contract (engine/rollout_engine.py): every host
    # submits the SAME global prompt set — identical slot schedules by
    # construction, verified per phase by the slot-schedule crc.
    prompts = [[(i % 14) + 1] for i in range(8)]
else:
    prompts = [[(i % 14) + 1] for i in range(8 * pid, 8 * (pid + 1))]
eval_prompts = [[1], [2]]

scrapes_stop = threading.Event()

def scrape_loop():
    # Live-endpoint witness: poll the exporter DURING the run and keep the
    # freshest scrape that already carries fleet gauges / the fleet block.
    mport = int(os.environ.get("TRLX_TPU_METRICS_PORT", "0"))
    while not scrapes_stop.is_set():
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=2
            ) as r:
                body = r.read().decode()
            if "trlx_tpu_fleet_hosts" in body:
                with open(os.path.join(ckpt, "scrape_metrics.txt"), "w") as f:
                    f.write(body)
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/healthz", timeout=2
            ) as r:
                payload = json.loads(r.read().decode())
            if "fleet" in payload:
                with open(os.path.join(ckpt, "scrape_healthz.json"), "w") as f:
                    json.dump(payload, f)
        except Exception:
            pass  # exporter not up yet / mid-teardown
        scrapes_stop.wait(0.3)

if mode == "slow":
    scraper = None
    if pid == 0:
        os.makedirs(ckpt, exist_ok=True)
        scraper = threading.Thread(target=scrape_loop, daemon=True)
        scraper.start()
    try:
        trlx_tpu.train(
            reward_fn=reward_fn, prompts=prompts, eval_prompts=eval_prompts,
            metric_fn=metric_fn, config=make_config(6), logit_mask=logit_mask,
        )
    finally:
        scrapes_stop.set()
        if scraper is not None:
            scraper.join(timeout=5)
    print(f"fleet slow proc {pid} DONE")

elif mode == "hang":
    # Proc 1 carries host_hang@2 (from its env) and wedges after step 2;
    # proc 0 blocks in the step-3 fingerprint allgather, the guard aborts it
    # (exit 117) and its _fire path writes the FLEET incident bundle — this
    # print is only reachable if detection FAILED.
    trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=eval_prompts,
        metric_fn=metric_fn, config=make_config(10), logit_mask=logit_mask,
    )
    print(f"fleet hang proc {pid} FINISHED WITHOUT ABORT")

elif mode in ("engine", "engine_spec", "engine_kill"):
    # 2-process continuous-batching engine run: replicated slot state
    # (_globalize), identical schedules cross-checked per phase by
    # verify_engine_schedule under the engine/schedule_verify guard.
    # - clean leg: completes → proves the per-phase crc check passes when
    #   schedules really match;
    # - engine_spec: same clean leg with SPECULATION armed — each verify
    #   dispatch folds its accepted-token total into the schedule crc, so
    #   the per-phase check also proves the two hosts accepted identical
    #   draft prefixes on every dispatch;
    # - TRLX_TPU_ENGINE_SCHEDULE_SKEW on proc 1: the phase-end check raises
    #   HostDesync NAMING host 1 on every host — desync by name, not hang;
    # - engine_kill: proc 1 carries mid_decode_host_kill@2 and dies abruptly
    #   between decode syncs with slots live; proc 0 blocks on the dead peer
    #   at its next guarded cross-host sync and aborts exit-117 with an
    #   incident bundle carrying its slot states — this FINISHED print is
    #   only reachable on proc 0 if detection FAILED.
    config = make_config(10 if mode == "engine_kill" else 3)
    config.method.rollout_engine = True
    config.method.engine_steps_per_sync = 2
    if mode == "engine_spec":
        config.method.spec_decode = "ngram"
        config.method.spec_k = 3
    if mode in ("engine_spec", "engine_kill"):
        # Paged KV armed: these drills double as POOL LEAK drills. Trainer
        # teardown runs engine.abort(), whose BlockPool.leak_audit raises a
        # named RuntimeError on any lost/double-freed block — so the DONE
        # marker below is unreachable if the fleet path leaks pool blocks,
        # and the pool's table rows fold into the same slot-schedule crc
        # the per-phase check verifies across hosts.
        config.method.paged_kv = True
        config.method.kv_block_size = 4
    trlx_tpu.train(
        reward_fn=reward_fn, prompts=prompts, eval_prompts=eval_prompts,
        metric_fn=metric_fn, config=config, logit_mask=logit_mask,
    )
    print(f"fleet {mode} proc {pid} FINISHED WITHOUT ABORT"
          if mode == "engine_kill" else f"fleet {mode} proc {pid} DONE")
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(tmp_path, mode, faults_by_pid, metrics_port=0, env_by_pid=None):
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "fleet_drill_worker.py"
    script.write_text(_DRILL_WORKER)
    ckpt = str(tmp_path / f"ckpt_fleet_{mode}")
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("TRLX_TPU_FAULTS", None)
        env.pop("TRLX_TPU_ENGINE_SCHEDULE_SKEW", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo
        env["TRLX_REPO"] = repo
        if metrics_port:
            # Same knob on EVERY process (the multi-host gauge rollup is a
            # collective); only process 0 actually binds the exporter.
            env["TRLX_TPU_METRICS_PORT"] = str(metrics_port)
        if pid in faults_by_pid:
            env["TRLX_TPU_FAULTS"] = faults_by_pid[pid]
        env.update((env_by_pid or {}).get(pid, {}))
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), mode, str(pid), str(port), ckpt],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
        )
    return procs, ckpt


def _skip_if_distributed_unavailable(proc, out):
    if proc.returncode != 0 and (
        ("initialize" in out and "failed" in out.lower())
        or "Multiprocess computations aren't implemented" in out
    ):
        pytest.skip(f"jax.distributed unavailable here: {out[-400:]}")


def _export_artifacts(ckpt, extra=()):
    """Copy the drill's fleet artifacts where CI uploads them (no-op when
    TRLX_TPU_DRILL_ARTIFACTS is unset)."""
    dest = os.environ.get("TRLX_TPU_DRILL_ARTIFACTS")
    if not dest:
        return
    os.makedirs(dest, exist_ok=True)
    from trlx_tpu.observability.report import _fleet_section
    from trlx_tpu.observability.spans import read_fleet_spans

    merged = read_fleet_spans(ckpt)
    with open(os.path.join(dest, "fleet_trace.json"), "w") as f:
        json.dump({"traceEvents": merged["traceEvents"]}, f)
    with open(os.path.join(dest, "fleet_report.md"), "w") as f:
        f.write("\n".join(_fleet_section(ckpt)))
    for name in extra:
        src = os.path.join(ckpt, name)
        if os.path.exists(src):
            if os.path.isdir(src):
                shutil.copytree(src, os.path.join(dest, name), dirs_exist_ok=True)
            else:
                shutil.copy(src, os.path.join(dest, name))


def _communicate(procs):
    """Collect both drill processes' merged output, skipping (not failing)
    when the environment can't finish a 2-process run in the budget."""
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out.decode(errors="replace"))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("2-process drill did not complete in this environment")
    return outs


def test_fleet_drill_slow_host_attribution_and_live_gauges(tmp_path):
    """Drill A: host 1 stalls at steps 2 and 4 → merged trace, skew table
    naming host 1, live fleet gauges, and the /healthz fleet block."""
    from trlx_tpu.observability import fleet as obs_fleet
    from trlx_tpu.observability.export import sanitize_metric_name
    from trlx_tpu.observability.report import _fleet_section
    from trlx_tpu.observability.spans import read_fleet_spans

    metrics_port = _free_port()
    procs, ckpt = _launch(
        tmp_path, "slow", {1: "slow_host@2,slow_host@4"}, metrics_port=metrics_port
    )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out.decode(errors="replace"))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("2-process drill did not complete in this environment")
    try:
        for pid, (p, out) in enumerate(zip(procs, outs)):
            _skip_if_distributed_unavailable(p, out)
            assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
            assert f"fleet slow proc {pid} DONE" in out

        # ONE merged Chrome trace: a process lane per host, clocks aligned
        # into host 0's frame under a STATED error bound.
        merged = read_fleet_spans(ckpt)
        assert merged["hosts"] == [0, 1]
        assert merged["clock"] is not None
        assert 0.0 < merged["alignment_error_s"] < 5.0
        lanes = {
            e["pid"]: e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert set(lanes) == {0, 1}
        assert "clock offset" in lanes[1]
        # Both hosts contributed real spans (the guards' collective/* boxes).
        for host in (0, 1):
            assert any(
                e.get("ph") == "X" and e.get("pid") == host
                for e in merged["traceEvents"]
            ), f"host {host} has no spans in the merged trace"

        # Per-collective skew table: the worst-host column names the
        # injected laggard, and the 2s stall dominates the max column.
        rows = obs_fleet.collective_skew_table(ckpt)
        assert rows, "no collective arrival records federated"
        worst_rows = [r for r in rows if r["worst_host"] is not None]
        assert worst_rows, f"no site attributed a straggler: {rows}"
        assert all(r["worst_host"] == 1 for r in worst_rows), worst_rows
        assert max(r["max_ms"] for r in worst_rows) > 1000.0  # the 2s sleeps

        # Live /metrics scrape taken DURING the run: fleet gauges with
        # per-host labels/keys, via the real exporter.
        with open(os.path.join(ckpt, "scrape_metrics.txt")) as f:
            scrape = f.read()
        assert sanitize_metric_name("trlx_tpu_fleet/hosts") + " 2.0" in scrape
        assert sanitize_metric_name("trlx_tpu_fleet/collective_skew_ms") + "_bucket" in scrape
        assert 'site="' in scrape  # per-site histogram labels
        assert sanitize_metric_name("trlx_tpu_fleet/host1_worst_arrivals_total") in scrape
        # per_host rollup rows: every host's own value, labeled by key path.
        assert sanitize_metric_name("trlx_tpu_fleet/host0/") in scrape
        assert sanitize_metric_name("trlx_tpu_fleet/host1/") in scrape

        # /healthz fleet block: both hosts' heartbeats + straggler verdict.
        with open(os.path.join(ckpt, "scrape_healthz.json")) as f:
            healthz = json.load(f)
        fleet_block = healthz["fleet"]
        assert fleet_block["hosts"] == 2
        assert {"0", "1"} <= set(fleet_block["heartbeats"])
        assert fleet_block["straggler"]["state"] in ("ok", "warn", "crit")
        assert len(fleet_block["clock"]["offsets_s"]) == 2

        # The report's Fleet section renders the same story.
        section = "\n".join(_fleet_section(ckpt))
        assert "clock-alignment error" in section
        assert "host 1" in section
    finally:
        _export_artifacts(ckpt, extra=("scrape_metrics.txt", "scrape_healthz.json"))


def test_fleet_drill_hang_leaves_cross_host_incident_bundle(tmp_path):
    """Drill B: host 1 wedges after step 2 → host 0's guard abort writes a
    fleet incident bundle holding BOTH hosts' span tails."""
    procs, ckpt = _launch(tmp_path, "hang", {1: "host_hang@2"})
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
    try:
        assert procs[0].returncode == EXIT_COLLECTIVE_TIMEOUT, (
            f"expected exit {EXIT_COLLECTIVE_TIMEOUT}, got {procs[0].returncode}:\n{out0[-4000:]}"
        )
        assert "FINISHED WITHOUT ABORT" not in out0

        incidents = os.path.join(ckpt, "incidents")
        fleet_bundles = [
            d
            for d in (os.listdir(incidents) if os.path.isdir(incidents) else [])
            if os.path.exists(os.path.join(incidents, d, "fleet_incident.json"))
        ]
        assert fleet_bundles, f"no fleet incident bundle under {incidents}"
        bundle = os.path.join(incidents, fleet_bundles[0])
        with open(os.path.join(bundle, "fleet_incident.json")) as f:
            manifest = json.load(f)
        assert manifest["reason"] == "collective_timeout"
        assert manifest["collected_by"] == 0  # the healthy host collected
        assert set(manifest["hosts"]) >= {0, 1}
        # BOTH hosts' span tails: the wedged peer's file came off the shared
        # checkpoint dir.
        for host in (0, 1):
            tail = os.path.join(bundle, f"host{host}", "spans_tail.jsonl")
            assert os.path.exists(tail), f"missing {tail}"
            assert os.path.getsize(tail) > 0
            with open(os.path.join(bundle, f"host{host}", "heartbeat.json")) as f:
                json.load(f)  # well-formed forensics payload
    finally:
        _export_artifacts(ckpt, extra=("incidents",))


# --------------------------------------- multi-host engine drills (PR 17)


def test_fleet_drill_engine_two_process_clean(tmp_path):
    """Drill C (clean leg): the continuous-batching engine runs at
    process_count()==2 — replicated slot state, identical per-host
    admission/harvest schedules — and the per-phase slot-schedule crc check
    passes on every phase. Both procs finish cleanly, no incident bundle."""
    procs, ckpt = _launch(tmp_path, "engine", {})
    outs = _communicate(procs)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        _skip_if_distributed_unavailable(p, out)
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"fleet engine proc {pid} DONE" in out
    # A clean run must not leave collective-timeout forensics behind.
    incidents = os.path.join(ckpt, "incidents")
    bundles = [
        d
        for d in (os.listdir(incidents) if os.path.isdir(incidents) else [])
        if os.path.exists(os.path.join(incidents, d, "fleet_incident.json"))
    ]
    assert not bundles, f"clean engine drill left incident bundles: {bundles}"


def test_fleet_drill_engine_spec_two_process_clean(tmp_path):
    """Drill C (speculative leg, ISSUE 19): the engine runs at
    process_count()==2 WITH spec_decode armed. The host-side drafter makes
    identical proposals on every host (same prompt set, same accepted
    stream), every verify dispatch folds its accepted-token total into the
    slot-schedule crc, and the per-phase crc check stays clean — speculation
    does not desync the slot managers. The leg also arms method.paged_kv:
    every admission's block-table row folds into the same crc (identical
    allocators on identical streams), and teardown's pool leak_audit makes
    the DONE marker unreachable if spec verify windows leaked pool blocks."""
    procs, ckpt = _launch(tmp_path, "engine_spec", {})
    outs = _communicate(procs)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        _skip_if_distributed_unavailable(p, out)
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"fleet engine_spec proc {pid} DONE" in out
    incidents = os.path.join(ckpt, "incidents")
    bundles = [
        d
        for d in (os.listdir(incidents) if os.path.isdir(incidents) else [])
        if os.path.exists(os.path.join(incidents, d, "fleet_incident.json"))
    ]
    assert not bundles, f"clean engine_spec drill left incident bundles: {bundles}"


def test_fleet_drill_engine_schedule_skew_is_named_desync(tmp_path):
    """Drill C (skew leg): host 1 reports a skewed slot-schedule crc
    (TRLX_TPU_ENGINE_SCHEDULE_SKEW — the injection signature of a desynced
    slot manager) → the phase-end check raises the identical HostDesync
    NAMING host 1 on BOTH hosts. Desync by name, never a hung collective."""
    procs, _ = _launch(
        tmp_path,
        "engine",
        {},
        env_by_pid={1: {"TRLX_TPU_ENGINE_SCHEDULE_SKEW": "1"}},
    )
    outs = _communicate(procs)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        _skip_if_distributed_unavailable(p, out)
        assert p.returncode != 0, (
            f"proc {pid} should have aborted on HostDesync:\n{out[-4000:]}"
        )
        assert f"fleet engine proc {pid} DONE" not in out
        # The coordinated abort names the skewed host and the component.
        assert "engine slot-schedule check failed" in out, out[-4000:]
        assert "host 1" in out
        assert "slot schedule crc32" in out


def test_fleet_drill_mid_decode_host_kill_exit117_with_slot_states(tmp_path):
    """Drill D: host 1 dies abruptly (os._exit) between decode syncs with
    slots mid-decode → host 0 hits its guarded cross-host engine sync, the
    collective_guard converts the dead peer into exit 117, and the fleet
    incident bundle names the wedged engine collective AND carries host 0's
    per-slot states at abort time. Runs with method.paged_kv armed: the
    kill lands with pool blocks pinned mid-decode, and the survivor's
    teardown must not trip the pool leak audit on its way to the bundle."""
    procs, ckpt = _launch(tmp_path, "engine_kill", {1: "mid_decode_host_kill@2"})
    try:
        out0, _ = procs[0].communicate(timeout=900)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("2-process drill did not complete in this environment")
    finally:
        procs[1].kill()  # no-op when the fault already os._exit(1)'d it
        procs[1].communicate()
    out0 = out0.decode(errors="replace")
    _skip_if_distributed_unavailable(procs[0], out0)
    try:
        assert procs[1].returncode == 1, (
            f"proc 1 should have died via mid_decode_host_kill, "
            f"got {procs[1].returncode}"
        )
        assert procs[0].returncode == EXIT_COLLECTIVE_TIMEOUT, (
            f"expected exit {EXIT_COLLECTIVE_TIMEOUT}, "
            f"got {procs[0].returncode}:\n{out0[-4000:]}"
        )
        assert "FINISHED WITHOUT ABORT" not in out0

        incidents = os.path.join(ckpt, "incidents")
        fleet_bundles = [
            d
            for d in (os.listdir(incidents) if os.path.isdir(incidents) else [])
            if os.path.exists(os.path.join(incidents, d, "fleet_incident.json"))
        ]
        assert fleet_bundles, f"no fleet incident bundle under {incidents}"
        bundle = os.path.join(incidents, fleet_bundles[0])
        with open(os.path.join(bundle, "fleet_incident.json")) as f:
            manifest = json.load(f)
        assert manifest["reason"] == "collective_timeout"
        assert manifest["collected_by"] == 0  # the surviving host collected
        detail = manifest["detail"]
        # The wedged collective is one of the engine's guarded syncs — both
        # carry the engine's slot states in their forensics detail.
        assert detail["collective"] in (
            "engine/schedule_verify",
            "engine/decode_sync",
        ), detail
        assert "slot_states" in detail, detail
        assert isinstance(detail["slot_states"], list)
        for slot in detail["slot_states"]:
            assert "slot" in slot and "n_gen" in slot and "version" in slot, slot
        # The survivor's own span tail made it into the bundle.
        tail = os.path.join(bundle, "host0", "spans_tail.jsonl")
        assert os.path.exists(tail) and os.path.getsize(tail) > 0
    finally:
        _export_artifacts(ckpt, extra=("incidents",))

"""CPU smoke of the observability layer: minutes, no TPU, CI-safe.

One probe run — a short overlapped PPO randomwalks run (max_staleness=1)
with every observability surface armed:

- span tracing (train.trace_spans): spans.jsonl must hold valid Chrome
  trace events with the producer / score-worker / main threads on distinct
  lanes and producer/train wall-clock overlap actually visible;
- device telemetry (train.device_telemetry, TRLX_TPU_PEAK_TFLOPS pinned so
  CPU gets an MFU %): metrics.jsonl must carry obs/train_mfu_pct and the
  kernel-routing gauges, and programs.json must register the train step;
- anomaly capture (train.anomaly_factor + the TRLX_TPU_FAULTS=slow_step
  drill): an incident bundle with thread stacks must land;
- training health (train.health_monitor + the reward_drift drill): the
  reward-drift detector must walk OK→WARN→CRIT, escalate a
  health_reward_drift incident bundle, and leave lineage.jsonl behind;
- live exporter (train.metrics_port): /metrics must serve the health/*
  gauges in Prometheus text format and /healthz must report degraded
  WHILE the run is alive (scraped from a background thread);
- reporting: trlx_tpu.observability.report must render every section from
  the run's artifacts and export the chrome://tracing JSON.

Two follow-up probes ride along: ``graftscope_probe`` (PR 12 — ledger
conservation, slot timeline, crash-proof manifest) and ``numerics_probe``
(PR 15 — an armed graftnum run under the ``nan_layer@2`` drill whose
incident bundle names the injected layer, with ``num/*`` gauges on the
live scrape and a rendered Numerics report section; writes
OBS_NUMERICS.json).

Writes OBS_SMOKE.json + OBS_REPORT.md + OBS_METRICS.prom (the last live
scrape) and prints one JSON summary line; exits 1 on any failure. Wall
time ~2 min on a laptop CPU.
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "OBS_SMOKE.json")
REPORT_OUT = os.path.join(REPO, "OBS_REPORT.md")
METRICS_OUT = os.path.join(REPO, "OBS_METRICS.prom")


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Scraper:
    """Background poller proving the endpoint is LIVE during the run: keeps
    the last successful /metrics text and the worst /healthz status seen."""

    def __init__(self, port):
        import threading

        self.port = port
        self.metrics_text = ""
        self.worst_status = None
        self.scrapes = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="trlx-obs-scraper", daemon=True
        )
        self._thread.start()

    def _run(self):
        import urllib.request

        rank = {"ok": 0, "degraded": 1, "critical": 2}
        while not self._stop.is_set():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/metrics", timeout=1
                ) as r:
                    self.metrics_text = r.read().decode()
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/healthz", timeout=1
                ) as r:
                    status = json.loads(r.read().decode()).get("status")
                self.scrapes += 1
                if rank.get(status, -1) > rank.get(self.worst_status, -1):
                    self.worst_status = status
            except OSError:
                pass  # exporter not up yet / torn down
            self._stop.wait(0.05)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def observability_probe():
    import tempfile
    import threading

    import numpy as np

    # slow_step drills the anomaly detector; reward_drift (from reward call
    # 2 on — call 1 seeds the warmup baseline) drills the health monitor.
    os.environ["TRLX_TPU_FAULTS"] = "slow_step@6,reward_drift@2"
    os.environ["TRLX_TPU_SLOW_STEP_SECONDS"] = "1.5"
    os.environ["TRLX_TPU_PEAK_TFLOPS"] = "0.01"

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import trlx_tpu
    from randomwalks import base_config, generate_random_walks
    from trlx_tpu.observability import report, spans

    _, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=15, max_length=8, n_walks=60, seed=1000
    )
    config = base_config("ppo", 15, 8)
    config.train.total_steps = 8
    config.train.epochs = 4
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.trace_spans = True
    config.train.device_telemetry = True
    config.train.anomaly_factor = 3.0
    # Health monitor: chunk_size=8 gives 2 reward calls per store, so the
    # drift walk is obs1 clean baseline (warmup=1) → obs2 drifted WARN
    # (warn_streak=1) → obs3 drifted CRIT (crit_streak=2), all in the first
    # few seconds — the exporter then serves CRIT for the rest of the run.
    config.train.health_monitor = True
    config.train.health_warmup = 1
    config.train.health_warn_streak = 1
    config.train.health_crit_streak = 2
    port = _free_port()
    config.train.metrics_port = port
    config.method.num_rollouts = 16
    config.method.chunk_size = 8
    config.method.max_staleness = 1
    d = tempfile.mkdtemp(prefix="obs_smoke_")
    config.train.checkpoint_dir = d
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    scraper = _Scraper(port)
    t0 = time.time()
    try:
        model = trlx_tpu.train(
            reward_fn=reward_fn,
            prompts=prompts,
            eval_prompts=[[1]],
            metric_fn=metric_fn,
            config=config,
            logit_mask=logit_mask,
        )
    finally:
        wall_s = time.time() - t0
        scraper.stop()
    assert model.iter_count >= 8
    leaked = [t.name for t in threading.enumerate() if t.name.startswith("trlx-")]
    assert not leaked, f"pipeline threads leaked: {leaked}"

    # --- spans: distinct lanes, visible producer/train overlap ------------
    events = spans.read_spans(os.path.join(d, spans.SPANS_FILENAME))
    assert events and {e["ph"] for e in events} <= {"X", "i", "M"}, "bad trace events"
    lanes = {e["args"]["name"]: e["tid"] for e in events if e["ph"] == "M"}
    for thread in ("MainThread", "trlx-rollout-producer", "trlx-score-worker"):
        assert thread in lanes, f"missing span lane: {thread} (have {sorted(lanes)})"
    xs = [e for e in events if e["ph"] == "X"]
    producer = [e for e in xs if e["name"] == "rollout/produce"]
    train = [e for e in xs if e["name"] == "train/step"]
    assert producer and train, "producer/train spans missing"

    def overlap_us(a, b):
        return min(a["ts"] + a["dur"], b["ts"] + b["dur"]) - max(a["ts"], b["ts"])

    overlap_s = max(
        (overlap_us(p, t) for p in producer for t in train), default=0
    ) / 1e6
    assert overlap_s > 0, "no producer/train overlap visible in spans"

    # --- telemetry: MFU + routing gauges + program registry ---------------
    with open(os.path.join(d, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    mfu = [r["obs/train_mfu_pct"] for r in records if "obs/train_mfu_pct" in r]
    assert mfu and all(m > 0 for m in mfu), f"MFU gauges missing/zero: {mfu}"
    routed = [r for r in records if "obs/fused_logprob_active" in r]
    assert routed, "kernel-routing gauges missing"
    with open(os.path.join(d, "programs.json")) as f:
        programs = json.load(f)
    assert "train/step" in programs and programs["train/step"]["dispatches"] >= 8

    # --- anomaly + health escalation: both drills produced bundles --------
    incidents_dir = os.path.join(d, "incidents")
    bundles = sorted(os.listdir(incidents_dir)) if os.path.isdir(incidents_dir) else []
    reasons = {}
    for b in bundles:
        with open(os.path.join(incidents_dir, b, "incident.json")) as f:
            reasons[json.load(f)["reason"]] = b
    assert "slow_step" in reasons, f"slow_step drill produced no bundle: {reasons}"
    assert "health_reward_drift" in reasons, (
        f"reward_drift CRIT did not escalate into an incident: {reasons}"
    )
    with open(os.path.join(incidents_dir, reasons["slow_step"], "threads.txt")) as f:
        assert "trlx-" in f.read(), "pipeline threads absent from stack dump"

    # --- health: detector walked to CRIT, lineage landed ------------------
    drift_states = [
        r["health/reward_drift_state"]
        for r in records
        if "health/reward_drift_state" in r
    ]
    assert drift_states and max(drift_states) == 2, (
        f"reward_drift detector never reached CRIT: {drift_states}"
    )
    changes = [
        r["health/state_changes_total"]
        for r in records
        if "health/state_changes_total" in r
    ]
    assert changes and changes[-1] >= 2, f"state-change counter: {changes}"
    with open(os.path.join(d, "lineage.jsonl")) as f:
        lineage = [json.loads(line) for line in f]
    assert lineage and all("weight_version" in r and "staleness" in r for r in lineage)

    # --- live exporter: scraped DURING the run ----------------------------
    assert scraper.scrapes > 0, "never scraped the live /metrics endpoint"
    prom = scraper.metrics_text
    assert "# TYPE trlx_tpu_health_reward_drift_state gauge" in prom, prom[:2000]
    assert "# TYPE trlx_tpu_health_state_changes_total counter" in prom
    assert scraper.worst_status in ("degraded", "critical"), scraper.worst_status
    with open(METRICS_OUT, "w") as f:
        f.write(prom)

    # --- report: renders every section + exports the trace ----------------
    trace_out = os.path.join(d, "trace.json")
    assert report.main([d, "-o", REPORT_OUT, "--trace-out", trace_out]) == 0
    with open(REPORT_OUT) as f:
        md = f.read()
    for heading in (
        "## Span lanes",
        "## MFU / FLOP throughput",
        "## Training health",
        "## Incidents",
    ):
        assert heading in md, f"report section missing: {heading}"
    assert "slow_step" in md and "health_reward_drift" in md

    return {
        "steps": model.iter_count,
        "span_events": len(events),
        "lanes": sorted(lanes),
        "producer_train_overlap_s": round(overlap_s, 2),
        "mfu_windows": len(mfu),
        "mfu_last_pct": round(mfu[-1], 3),
        "incidents": reasons,
        "health_worst_status": scraper.worst_status,
        "live_scrapes": scraper.scrapes,
        "lineage_rows": len(lineage),
        "report_bytes": len(md),
        "seconds": round(wall_s, 2),
    }


def graftscope_probe():
    """PR 12 smoke: an armed overlapped+engine run must produce the
    conservation ledger, a bubble fraction, slot-timeline rows, and /metrics
    histograms — and a SIGKILLed bench child must still leave a RunManifest
    that bench_trajectory turns into a reason instead of ``no_data``."""
    import signal
    import subprocess
    import tempfile
    import threading

    import numpy as np

    # The first probe's drills must not pollute this run's timings.
    os.environ.pop("TRLX_TPU_FAULTS", None)
    os.environ.pop("TRLX_TPU_SLOW_STEP_SECONDS", None)
    os.environ["TRLX_TPU_PEAK_TFLOPS"] = "0.01"

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import trlx_tpu
    from randomwalks import base_config, generate_random_walks
    from trlx_tpu.observability import spans
    from trlx_tpu.observability.graftscope import RunManifest

    _, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=15, max_length=8, n_walks=60, seed=1000
    )
    config = base_config("ppo", 15, 8)
    config.train.total_steps = 8
    config.train.epochs = 4
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.graftscope = True  # implies spans + device telemetry
    port = _free_port()
    config.train.metrics_port = port
    config.method.num_rollouts = 16
    config.method.chunk_size = 8
    config.method.max_staleness = 1
    config.method.rollout_engine = True
    config.method.engine_slots = 4
    config.method.prefill_batch = 2
    d = tempfile.mkdtemp(prefix="obs_smoke_gs_")
    config.train.checkpoint_dir = d
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    scraper = _Scraper(port)
    t0 = time.time()
    try:
        model = trlx_tpu.train(
            reward_fn=reward_fn,
            prompts=prompts,
            eval_prompts=[[1]],
            metric_fn=metric_fn,
            config=config,
            logit_mask=logit_mask,
        )
    finally:
        wall_s = time.time() - t0
        scraper.stop()
    assert model.iter_count >= 8
    leaked = [t.name for t in threading.enumerate() if t.name.startswith("trlx-")]
    assert not leaked, f"threads leaked (graftscope drain?): {leaked}"

    # --- conservation ledger in metrics.jsonl -----------------------------
    with open(os.path.join(d, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    windows = [r for r in records if "obs/ledger_wall_s" in r]
    assert windows, "no ledger windows in metrics.jsonl"
    for r in windows:
        wall = r["obs/ledger_wall_s"]
        err = abs(
            r["obs/ledger_device_busy_s"]
            + r["obs/ledger_host_s"]
            + r["obs/ledger_bubble_s"]
            - wall
        ) / max(wall, 1e-9)
        assert err <= 0.05, f"ledger conservation violated: {err:.4f} in {r}"
        assert 0.0 <= r["obs/bubble_fraction"] <= 1.0
    assert any(r["obs/ledger_device_busy_s"] > 0 for r in windows), (
        "fence drain attributed zero device time across every window"
    )

    # --- slot timeline in spans.jsonl + snapshot rollups ------------------
    events = spans.read_spans(os.path.join(d, spans.SPANS_FILENAME))
    admits = [e for e in events if e.get("name") == "engine/slot/admit"]
    harvests = [e for e in events if e.get("name") == "engine/slot/harvest"]
    assert admits and harvests, (
        f"slot timeline missing: {len(admits)} admits, {len(harvests)} harvests"
    )
    gs_path = os.path.join(d, "graftscope.json")
    with open(gs_path) as f:
        snap = json.load(f)
    assert snap["windows"], "graftscope.json has no windows"
    assert snap["slots"] and all(row["episodes"] > 0 for row in snap["slots"]), (
        f"slot occupancy rows missing/empty: {snap.get('slots')}"
    )
    with open(os.path.join(REPO, "OBS_GRAFTSCOPE.json"), "w") as f:
        json.dump(snap, f, indent=1)

    # --- /metrics histograms (lane gaps at minimum) -----------------------
    prom = scraper.metrics_text
    assert "trlx_tpu_obs_lane_gap_s_bucket" in prom, prom[:2000]
    assert "trlx_tpu_obs_bubble_fraction" in prom

    # --- forced-kill bench child → valid manifest with a reason -----------
    mdir = tempfile.mkdtemp(prefix="obs_smoke_manifest_")
    mpath = os.path.join(mdir, "BENCH_MANIFEST_r99.jsonl")
    child_src = (
        "import os, sys, time\n"
        "sys.path.insert(0, %r)\n"
        "from trlx_tpu.observability.graftscope import RunManifest\n"
        "m = RunManifest(%r, cmd='bench.py (smoke drill)')\n"
        "m.heartbeat('size_ladder', candidate='gptj-l8-d4096-2.0B-w8-bf16')\n"
        "m.child('gptj-l8-d4096-2.0B-w8-bf16', 1, 'ValueError: mosaic lowering failed')\n"
        "m.heartbeat('size_ladder', candidate='gptj-l6-d2048-0.4B-w8-bf16')\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n"
    ) % (REPO, mpath)
    proc = subprocess.Popen(
        [sys.executable, "-c", child_src],
        stdout=subprocess.PIPE,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.stdout.readline().strip() == "ready"
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)
    summary = RunManifest.read(mpath)
    assert summary["valid"] and not summary["complete"], summary
    assert "killed mid-flight during size_ladder" in summary["reason"], summary
    assert "rc=1" in summary["reason"], summary

    # --- bench_trajectory ingests the manifest reason ---------------------
    import bench_trajectory

    art = os.path.join(mdir, "BENCH_r99.json")
    with open(art, "w") as f:
        json.dump({"n": 99, "cmd": "timeout -k 10 900 python bench.py", "rc": 124, "tail": ""}, f)
    traj = bench_trajectory.build_trajectory(
        [art], smoke_path=os.path.join(mdir, "missing.json"),
        manifest_path=os.path.join(mdir, "missing.jsonl"),
    )
    entry = traj["runs"][0]
    assert entry.get("no_data") and entry.get("manifest"), entry
    assert entry["reason"] == summary["reason"], (entry["reason"], summary["reason"])

    return {
        "steps": model.iter_count,
        "ledger_windows": len(windows),
        "worst_conservation_error": round(
            max(
                abs(
                    r["obs/ledger_device_busy_s"]
                    + r["obs/ledger_host_s"]
                    + r["obs/ledger_bubble_s"]
                    - r["obs/ledger_wall_s"]
                )
                / max(r["obs/ledger_wall_s"], 1e-9)
                for r in windows
            ),
            6,
        ),
        "bubble_fraction_last": round(windows[-1]["obs/bubble_fraction"], 4),
        "slot_spans": len(slot_spans),
        "slot_admits": len(admits),
        "snapshot_slots": len(snap["slots"]),
        "killed_manifest_reason": summary["reason"],
        "seconds": round(wall_s, 2),
    }


def numerics_probe():
    """PR 15 smoke: an armed overlapped graftnum run under the nan_layer
    drill must stream num/* gauges to the LIVE /metrics endpoint, attach a
    numerics.json to the guard-skip incident bundle that names the injected
    layer as first-NaN (plus the nonfinite grad leaves by path), and render
    the report's Numerics section. Writes OBS_NUMERICS.json."""
    import tempfile
    import threading

    import numpy as np

    # nan_layer@2: step 2's batch is NaN-poisoned (guard trips for real) AND
    # the bisector's injection target block_2 is latched — so the model needs
    # n_layer > 2 for the clamp min(2, n_layer-1) to name a distinct layer.
    os.environ["TRLX_TPU_FAULTS"] = "nan_layer@2"
    os.environ.pop("TRLX_TPU_SLOW_STEP_SECONDS", None)
    os.environ["TRLX_TPU_PEAK_TFLOPS"] = "0.01"

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import trlx_tpu
    from randomwalks import base_config, generate_random_walks
    from trlx_tpu.observability import report

    _, logit_mask, metric_fn, reward_fn = generate_random_walks(
        n_nodes=15, max_length=8, n_walks=60, seed=1000
    )
    config = base_config("ppo", 15, 8)
    config.model.model_arch["n_layer"] = 4
    config.train.total_steps = 8
    config.train.epochs = 4
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.graftnum = True
    port = _free_port()
    config.train.metrics_port = port
    config.method.num_rollouts = 16
    config.method.chunk_size = 8
    config.method.max_staleness = 1
    d = tempfile.mkdtemp(prefix="obs_smoke_num_")
    config.train.checkpoint_dir = d
    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]

    scraper = _Scraper(port)
    t0 = time.time()
    try:
        model = trlx_tpu.train(
            reward_fn=reward_fn,
            prompts=prompts,
            eval_prompts=[[1]],
            metric_fn=metric_fn,
            config=config,
            logit_mask=logit_mask,
        )
    finally:
        wall_s = time.time() - t0
        scraper.stop()
        os.environ.pop("TRLX_TPU_FAULTS", None)
    assert model.iter_count >= 8
    assert model.skipped_steps >= 1, "nan_layer drill never tripped the guard"
    leaked = [t.name for t in threading.enumerate() if t.name.startswith("trlx-")]
    assert not leaked, f"pipeline threads leaked: {leaked}"

    # --- num/* telemetry in metrics.jsonl ---------------------------------
    with open(os.path.join(d, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    gnorm = [r["num/grad_global_norm"] for r in records if "num/grad_global_norm" in r]
    assert gnorm, "no num/grad_global_norm records"
    subtree_keys = sorted(
        {k for r in records for k in r if k.startswith("num/update_ratio/")}
    )
    assert subtree_keys, "no per-subtree update-ratio gauges"

    # --- num/* gauges on the LIVE /metrics scrape -------------------------
    assert scraper.scrapes > 0, "never scraped the live /metrics endpoint"
    prom = scraper.metrics_text
    assert "trlx_tpu_num_grad_global_norm" in prom, prom[:2000]
    assert "trlx_tpu_num_update_ratio_" in prom

    # --- incident bundle: numerics.json names the injected layer ----------
    incidents_dir = os.path.join(d, "incidents")
    payload = None
    for b in sorted(os.listdir(incidents_dir) if os.path.isdir(incidents_dir) else []):
        p = os.path.join(incidents_dir, b, "numerics.json")
        if os.path.exists(p):
            with open(p) as f:
                payload = json.load(f)
            break
    assert payload is not None, "no numerics.json in any incident bundle"
    census = payload["grad_census"]
    assert census["total_nonfinite_leaves"] > 0, census
    bisect = payload["forward_bisect"]
    assert bisect["first_nonfinite"] == "block_2", bisect
    assert bisect["injected"] == "block_2", bisect

    # --- report renders the Numerics section ------------------------------
    md = report.build_report(d)
    assert "## Numerics (graftnum)" in md, "Numerics section missing from report"
    assert "block_2" in md and "nonfinite grad leaves" in md

    out = {
        "steps": model.iter_count,
        "skipped_steps": model.skipped_steps,
        "grad_norm_records": len(gnorm),
        "subtree_gauges": len(subtree_keys),
        "first_nonfinite": bisect["first_nonfinite"],
        "nonfinite_grad_leaves": census["total_nonfinite_leaves"],
        "leaf_paths": [e["path"] for e in census["nonfinite_leaves"][:4]],
        "live_scrapes": scraper.scrapes,
        "seconds": round(wall_s, 2),
    }
    with open(os.path.join(REPO, "OBS_NUMERICS.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


def main():
    t0 = time.time()
    result = {"observability": observability_probe()}
    result["graftscope"] = graftscope_probe()
    result["numerics"] = numerics_probe()
    result["wall_s"] = round(time.time() - t0, 1)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"smoke": "ok", **result}))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 — CI needs the one-line verdict
        print(json.dumps({"smoke": "FAIL", "error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)

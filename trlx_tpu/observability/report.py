"""Performance report generator: metrics.jsonl + spans.jsonl -> markdown.

    python -m trlx_tpu.observability.report <checkpoint_dir> [-o report.md]
                                            [--trace-out trace.json]

Merges everything the observability layer wrote during a run into one
readable document: per-window phase breakdown, MFU trend from compiled-cost
FLOPs, staleness distribution, kernel-routing table, span-lane accounting
(with the measured producer/train overlap), the stalls the flight recorder
caught (stalls.jsonl) and the incident index.
``--trace-out`` additionally emits a ``{"traceEvents": [...]}`` wrapper of
spans.jsonl for chrome://tracing (Perfetto loads the raw JSONL directly).

Multi-host: each host appends to the SAME spans.jsonl (line-atomic, lanes
keyed by pid) and rank 0 writes metrics.jsonl, so the report needs no
gather at read time. For LIVE multi-host window stats,
``rollup_window_stats`` aggregates each host's scalar window over the
existing ``allgather_host`` path — the trainer calls it at the window
boundary so metrics.jsonl carries fleet-mean/max gauges, not just rank 0's.
"""

import argparse
import bisect
import gzip
import json
import os
import re
import warnings
from collections import defaultdict

import numpy as np

__all__ = ["build_report", "rollup_window_stats", "main"]


# ------------------------------------------------------------------ rollup


def rollup_window_stats(stats: dict, per_host: bool = False) -> dict:
    """Aggregate one window's scalar stats across hosts.

    Returns ``{key/hostmean, key/hostmax}`` for every float-valued key, via
    ``allgather_host`` — so it MUST be called collectively (every host, same
    window boundary). Identity-shaped at process_count()==1: the mean/max of
    one host is itself (tests exercise this path; pods get the real gather).

    ``per_host=True`` (graftfleet armed — must be config-consistent, the
    flag changes nothing about the gather itself) additionally emits every
    host's own value as ``fleet/host{k}/<key>`` plus ``key/hostmin`` /
    ``key/hostspread`` fleet-level views, all from the SAME gathered matrix
    — no extra collective."""
    import jax

    keys = sorted(k for k, v in stats.items() if isinstance(v, (int, float)))
    if not keys:
        return {}
    row = np.asarray([float(stats[k]) for k in keys], dtype=np.float64)
    if jax.process_count() == 1:
        gathered = row[None, :]
    else:
        from trlx_tpu.parallel.mesh import allgather_host

        gathered = np.asarray(allgather_host(row[None, :])).reshape(-1, len(keys))
    out = {}
    for j, key in enumerate(keys):
        out[f"{key}/hostmean"] = float(gathered[:, j].mean())
        out[f"{key}/hostmax"] = float(gathered[:, j].max())
        if per_host:
            out[f"{key}/hostmin"] = float(gathered[:, j].min())
            out[f"{key}/hostspread"] = float(gathered[:, j].max() - gathered[:, j].min())
            for host in range(gathered.shape[0]):
                out[f"fleet/host{host}/{key}"] = float(gathered[host, j])
    return out


# ----------------------------------------------------------------- loading


def _load_jsonl(path):
    from trlx_tpu.utils.jsonl import read_jsonl

    if not os.path.exists(path):
        return []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # torn tails are routine post-kill
        return read_jsonl(path)


def _scalar_records(metrics):
    return [r for r in metrics if "step" in r and "table" not in r and "histogram" not in r]


def _fmt(value, digits=3):
    if value is None:
        return "-"
    if isinstance(value, float):
        return f"{value:.{digits}f}"
    return str(value)


def _trend(values, width: int = 24) -> str:
    """Coarse text sparkline — enough to see an MFU ramp or collapse."""
    if not values:
        return ""
    marks = " .:-=+*#"
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    if len(values) > width:
        # Mean-pool to `width` buckets.
        idx = np.array_split(np.asarray(values, dtype=np.float64), width)
        values = [float(chunk.mean()) for chunk in idx if chunk.size]
    return "".join(marks[int((v - lo) / span * (len(marks) - 1))] for v in values)


# ----------------------------------------------------------------- spans


def _lane_summary(spans):
    """Per-(pid, tid) lane accounting + cross-lane overlap of X-events."""
    names = {}
    lanes = defaultdict(lambda: {"events": 0, "busy_us": 0, "top": defaultdict(int)})
    for event in spans:
        key = (event.get("pid", 0), event.get("tid", 0))
        if event.get("ph") == "M" and event.get("name") == "thread_name":
            names[key] = event.get("args", {}).get("name", "?")
        elif event.get("ph") == "X":
            lane = lanes[key]
            lane["events"] += 1
            lane["busy_us"] += int(event.get("dur", 0))
            lane["top"][event.get("name", "?")] += int(event.get("dur", 0))
    rows = []
    for key, lane in sorted(lanes.items()):
        top = max(lane["top"].items(), key=lambda kv: kv[1])[0] if lane["top"] else "-"
        rows.append(
            {
                "pid": key[0],
                "tid": key[1],
                "thread": names.get(key, "?"),
                "events": lane["events"],
                "busy_s": lane["busy_us"] / 1e6,
                "top_span": top,
            }
        )
    return rows


def _overlap_seconds(spans, lane_a_substr: str, lane_b_substr: str):
    """Wall seconds where an X-span on a thread named like A overlaps one on
    a thread named like B — the picture-level form of overlap_fraction."""
    names = {}
    for event in spans:
        if event.get("ph") == "M" and event.get("name") == "thread_name":
            names[(event.get("pid", 0), event.get("tid", 0))] = event.get("args", {}).get("name", "")

    def intervals(substr):
        out = []
        for event in spans:
            if event.get("ph") != "X":
                continue
            lane = names.get((event.get("pid", 0), event.get("tid", 0)), "")
            if substr in lane:
                t0 = event.get("ts", 0)
                out.append((t0, t0 + event.get("dur", 0)))
        out.sort()
        return out

    a, b = intervals(lane_a_substr), intervals(lane_b_substr)
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if lo < hi:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total / 1e6


# ----------------------------------------------------------------- fleet


def _stalled_in(record):
    """Where the stalled thread was: of the spans on the lane of the record's
    own span (`train/step`, `rollout/generate`), the one with the most
    self-seconds (its duration less its children's) inside the record."""
    events = [e for e in record.get("spans", []) if e.get("ph") == "X"]
    own = "train/step" if record.get("kind") == "train_step" else "rollout/generate"
    lanes = {e["tid"] for e in events if e["name"] == own}
    events = [e for e in events if e["tid"] in lanes]
    self_us = {e["args"]["id"]: e["dur"] for e in events}
    for e in events:
        if e["args"]["parent"] in self_us:
            self_us[e["args"]["parent"]] -= e["dur"]
    if not self_us:
        return "?"
    span_id = max(self_us, key=self_us.get)
    name = next(e["name"] for e in events if e["args"]["id"] == span_id)
    return f"{name} ({_fmt(self_us[span_id] * 1e-6, 3)}s self)"


def _stalls_section(checkpoint_dir):
    """Stalls: one row a line of stalls.jsonl (observability/anomaly.py). How
    to read a row is RUNBOOK section 8's."""
    records = _load_jsonl(os.path.join(checkpoint_dir, "stalls.jsonl"))
    lines = ["## Stalls", ""]
    if not records:
        return lines + ["None: no logged step or rollout passed 1.5x its rolling median.", ""]
    lines.append("| kind | step | seconds (p50) | side | wait excess s | largest tick gap s | involuntary switches | stalled in |")
    lines.append("|---|---|---|---|---|---|---|---|")
    for r in records:
        excess, wait = r.get("excess_s", 0.0), r.get("wait_excess_s", 0.0)
        side = "wait" if excess > 0 and wait >= 0.5 * excess else "host"
        lines.append(
            f"| {r.get('kind', '?')} | {r.get('step', '?')} | {_fmt(r.get('seconds'), 3)} ({_fmt(r.get('p50'), 3)}) "
            f"| {side} | {_fmt(wait, 3)} | {_fmt(r.get('proc/tick_gap_max_s'), 3)} | {_fmt(r.get('proc/nivcsw'), 0)} "
            f"| {_stalled_in(r)} |"
        )
    lines += [
        "",
        "A tick gap near the excess: the host (or the interpreter) stopped. Side `wait`, no gap, a quiet process: the device",
        "or its runtime. Side `host` with involuntary switches up: the process was kept off the CPU. Each line of",
        "`stalls.jsonl` holds every counter and the spans of the interval (Chrome trace events: Perfetto opens them).",
        "",
    ]
    return lines


def _fleet_section(checkpoint_dir):
    """Render graftfleet's federation artifacts: the merged multi-host
    timeline summary (with the stated clock-alignment bound), the
    per-collective skew table naming the worst-arrival host per site, and
    the per-host heartbeat summary."""
    from trlx_tpu.observability import fleet as obs_fleet
    from trlx_tpu.observability.spans import read_fleet_spans
    from trlx_tpu.resilience.distributed import read_heartbeats

    lines = ["## Fleet (graftfleet)", ""]
    merged = read_fleet_spans(checkpoint_dir)
    arrivals = obs_fleet.read_collective_arrivals(checkpoint_dir)
    if merged["clock"] is None and not arrivals:
        lines.append("No fleet artifacts (train.graftfleet off — set it or TRLX_TPU_GRAFTFLEET=1).")
        lines.append("")
        return lines
    clock = merged["clock"] or {}
    offsets = clock.get("offsets_s", [])
    lines.append(
        f"- merged trace: {len(merged['traceEvents'])} events across host lane(s) "
        f"{merged['hosts']} · clock-alignment error ≤ {merged['alignment_error_s'] * 1e3:.3f}ms "
        f"(estimate uncertainty + drift, fleet_clock.jsonl step {clock.get('step', '?')})"
    )
    if offsets:
        lines.append(
            "- clock offsets vs host 0: "
            + " · ".join(f"host{k} {v * 1e3:+.3f}ms" for k, v in enumerate(offsets))
        )
    lines.append("")
    rows = obs_fleet.collective_skew_table(checkpoint_dir)
    if rows:
        lines.append("### Per-collective skew")
        lines.append("")
        lines.append("| site | occurrences | p50_ms | p95_ms | max_ms | worst host | worst share |")
        lines.append("|---|---|---|---|---|---|---|")
        for row in rows:
            worst = "-" if row["worst_host"] is None else f"host {row['worst_host']}"
            lines.append(
                f"| {row['site']} | {row['count']} | {_fmt(row['p50_ms'], 1)} "
                f"| {_fmt(row['p95_ms'], 1)} | {_fmt(row['max_ms'], 1)} "
                f"| {worst} | {_fmt(row['worst_share'], 2)} |"
            )
        lines.append("")
    beats = read_heartbeats(os.path.join(checkpoint_dir, "heartbeats"))
    if beats:
        lines.append("### Per-host heartbeat summary")
        lines.append("")
        lines.append("| host | last step | phase | progress_t | written_t |")
        lines.append("|---|---|---|---|---|")
        for host, rec in sorted(beats.items()):
            lines.append(
                f"| {host} | {rec.get('step')} | {rec.get('phase')} "
                f"| {_fmt(rec.get('progress_t'), 1)} | {_fmt(rec.get('written_t'), 1)} |"
            )
        lines.append("")
    incident = os.path.join(checkpoint_dir, "incidents")
    fleet_bundles = []
    if os.path.isdir(incident):
        for name in sorted(os.listdir(incident)):
            if os.path.exists(os.path.join(incident, name, "fleet_incident.json")):
                fleet_bundles.append(name)
    if fleet_bundles:
        lines.append(
            "- fleet incident bundles: "
            + " · ".join(f"`incidents/{name}/host<k>/`" for name in fleet_bundles)
        )
        lines.append("")
    return lines


def _numerics_section(checkpoint_dir, scalars):
    """Render graftnum's numerics artifacts: the global grad-norm trend,
    the per-subtree grad/update-ratio table, quantization-error gauges per
    kernel class, and the NaN-provenance verdict of every incident bundle
    that carries a numerics.json."""
    from trlx_tpu.observability import numerics as obs_numerics

    lines = ["## Numerics (graftnum)", ""]
    num_keys = sorted({k for r in scalars for k in r if k.startswith("num/")})
    incidents_dir = os.path.join(checkpoint_dir, "incidents")
    numerics_bundles = []
    if os.path.isdir(incidents_dir):
        for name in sorted(os.listdir(incidents_dir)):
            path = os.path.join(incidents_dir, name, obs_numerics.NUMERICS_FILENAME)
            try:
                with open(path) as f:
                    numerics_bundles.append((name, json.load(f)))
            except (OSError, ValueError):
                continue
    if not num_keys and not numerics_bundles:
        lines.append("No numerics records (train.graftnum off — set it or TRLX_TPU_GRAFTNUM=1).")
        lines.append("")
        return lines
    gnorm = [float(r["num/grad_global_norm"]) for r in scalars if "num/grad_global_norm" in r]
    # NaN records are real data here (the guard-tripped step logs a NaN
    # norm) but poison min/max and the sparkline — count them, trend the rest.
    gnorm_bad = sum(1 for v in gnorm if not np.isfinite(v))
    gnorm_ok = [v for v in gnorm if np.isfinite(v)]
    if gnorm:
        line = f"- global grad norm: {len(gnorm)} records"
        if gnorm_ok:
            line += (
                f" · last finite {_fmt(gnorm_ok[-1])} · max {_fmt(max(gnorm_ok))}"
                f" · trend `{_trend(gnorm_ok)}`"
            )
        if gnorm_bad:
            line += f" · {gnorm_bad} NONFINITE record(s)"
        lines.append(line)
        lines.append("")
    subtrees = sorted(
        {k[len("num/grad_norm/"):] for k in num_keys if k.startswith("num/grad_norm/")}
    )
    if subtrees:
        lines.append("| subtree | grad_norm (last) | param_norm (last) | update_ratio (last) | ratio trend |")
        lines.append("|---|---|---|---|---|")
        for sub in subtrees:
            ratios = [
                float(r[f"num/update_ratio/{sub}"])
                for r in scalars
                if f"num/update_ratio/{sub}" in r
                and np.isfinite(float(r[f"num/update_ratio/{sub}"]))
            ]
            last = {
                col: next(
                    (r[f"num/{col}/{sub}"] for r in reversed(scalars) if f"num/{col}/{sub}" in r),
                    None,
                )
                for col in ("grad_norm", "param_norm", "update_ratio")
            }
            lines.append(
                f"| {sub} | {_fmt(last['grad_norm'], 4)} | {_fmt(last['param_norm'], 2)} "
                f"| {_fmt(last['update_ratio'], 6)} | `{_trend(ratios)}` |"
            )
        lines.append("")
    classes = sorted(
        {k[len("num/quant_err_rms/"):] for k in num_keys if k.startswith("num/quant_err_rms/")}
    )
    if classes:
        version = next(
            (r["num/quant_weight_version"] for r in reversed(scalars) if "num/quant_weight_version" in r),
            None,
        )
        lines.append(
            f"### Quantization error (last handoff, weight version {_fmt(version, 0)})"
        )
        lines.append("")
        lines.append("| kernel class | max_abs_err | rms_err | snr_db |")
        lines.append("|---|---|---|---|")
        for cls in classes:
            row = {
                col: next(
                    (r[f"num/{col}/{cls}"] for r in reversed(scalars) if f"num/{col}/{cls}" in r),
                    None,
                )
                for col in ("quant_err_max", "quant_err_rms", "quant_snr_db")
            }
            lines.append(
                f"| {cls} | {_fmt(row['quant_err_max'], 6)} "
                f"| {_fmt(row['quant_err_rms'], 6)} | {_fmt(row['quant_snr_db'], 1)} |"
            )
        lines.append("")
    if numerics_bundles:
        lines.append("### NaN provenance")
        lines.append("")
        for name, payload in numerics_bundles:
            census = payload.get("grad_census", {}) or {}
            bisect = payload.get("forward_bisect", {}) or {}
            leaves = census.get("nonfinite_leaves", []) or []
            first = bisect.get("first_nonfinite")
            verdict = f"first nonfinite at `{first}`" if first else "forward clean"
            if bisect.get("injected"):
                verdict += f" (drill injection: {bisect['injected']})"
            head = " · ".join(leaf.get("path", "?") for leaf in leaves[:3])
            lines.append(
                f"- `incidents/{name}/numerics.json`: "
                f"{census.get('total_nonfinite_leaves', 0)} nonfinite grad leaves"
                + (f" ({head}{' …' if len(leaves) > 3 else ''})" if leaves else "")
                + f" · {verdict}"
            )
        lines.append("")
    return lines


# ----------------------------------------------------------------- report


def _device_scopes_section(checkpoint_dir, xplane):
    """Device time by scope: the profiler's trace (`xplane`, an `.xplane.pb`,
    gzipped or not) joined with the run's `device_scopes.json` by
    `<program>/<instruction>`, as benchmark/readers/scope_time.py joins them.
    A loop, conditional or call (an event that contains the next one) is left
    out: its body's events are counted."""
    from jax.profiler import ProfileData

    from trlx_tpu.observability.device_scopes import SCOPES_FILENAME

    lines = ["## Device time by scope", ""]
    try:
        with open(os.path.join(checkpoint_dir, SCOPES_FILENAME)) as f:
            tables = json.load(f)["programs"]
    except (OSError, ValueError):
        return lines + [f"No `{SCOPES_FILENAME}`: written at the first iteration boundary after a profiler session "
                        "closed (`train.profile_dir`, RUNBOOK §8).", ""]
    scopes = {}
    for table in tables:
        for name, entry in table["ops"].items():
            scopes.setdefault((table["module"], name), entry)
    with (gzip.open if xplane.endswith(".gz") else open)(xplane, "rb") as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    seconds = defaultdict(lambda: defaultdict(float))  # program -> (innermost scope, pass) -> device seconds
    for plane in planes:
        by_name = {line.name: line for line in plane.lines}
        if "XLA Ops" not in by_name or "XLA Modules" not in by_name:
            continue
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, re.sub(r"\(\d+\)$", "", e.name))
                         for e in by_name["XLA Modules"].events)
        starts = [m[0] for m in modules]
        events = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name) for e in by_name["XLA Ops"].events
                         if e.duration_ns > 0), key=lambda t: (t[0], -t[1]))
        for k, (s, e, text) in enumerate(events):
            if k + 1 < len(events) and events[k + 1][0] < e:
                continue
            i = bisect.bisect_right(starts, s) - 1
            program = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
            chain, which = scopes.get((program, text.split(" = ", 1)[0].lstrip("%")), ("", "-"))
            seconds[program][(chain.rsplit("/", 1)[-1] or "(no scope)", which)] += (e - s) / 1e9
    if not seconds:
        return lines + [f"`{xplane}` holds no device plane with `XLA Ops`.", ""]
    lines += ["| program | scope | pass | device ms | share of the program's busy time |", "|---|---|---|---|---|"]
    for program, rows in sorted(seconds.items(), key=lambda kv: -sum(kv[1].values())):
        busy = sum(rows.values())
        for (scope, which), s in sorted(rows.items(), key=lambda kv: -kv[1])[:12]:
            lines.append(f"| {program} | {scope} | {which} | {_fmt(1e3 * s, 3)} | {_fmt(100 * s / busy, 1)}% |")
    return lines + ["", "Milliseconds are summed over the device planes of the trace; a scope is the innermost "
                    "`device_scopes.SCOPES` name on the operation's `op_name` path.", ""]


def build_report(checkpoint_dir: str) -> str:
    checkpoint_dir = os.path.abspath(checkpoint_dir)
    metrics = _load_jsonl(os.path.join(checkpoint_dir, "metrics.jsonl"))
    # Fleet-aware span load: merges spans.host<k>.jsonl lanes (clock-aligned,
    # host-prefixed tids) when graftfleet ran; falls back to the plain
    # spans.jsonl events unchanged otherwise.
    from trlx_tpu.observability.spans import read_fleet_spans

    spans = read_fleet_spans(checkpoint_dir)["traceEvents"]
    scalars = _scalar_records(metrics)
    lines = [f"# Performance report — `{checkpoint_dir}`", ""]

    # --- run summary ------------------------------------------------------
    steps = [r["step"] for r in scalars if isinstance(r.get("step"), (int, float))]
    hosts = sorted({e.get("pid", 0) for e in spans}) if spans else []
    lines += ["## Run summary", ""]
    lines.append(f"- scalar records: {len(scalars)}" + (f" (steps {int(min(steps))}..{int(max(steps))})" if steps else ""))
    lines.append(f"- span events: {len(spans)}" + (f" across host pid(s) {hosts}" if hosts else ""))
    times = [r["t"] for r in scalars if isinstance(r.get("t"), (int, float))]
    if len(times) >= 2:
        lines.append(f"- metrics wall span: {times[-1] - times[0]:.1f}s")
    lines.append("")

    # --- phase breakdown per window --------------------------------------
    windows = [r for r in scalars if "time/window_wall_s" in r]
    lines += ["## Phase breakdown (per window)", ""]
    if windows:
        lines.append("| step | rollout_s | score_s | train_s | wall_s | overlap | tokens/s |")
        lines.append("|---|---|---|---|---|---|---|")
        for r in windows[-12:]:
            lines.append(
                "| {} | {} | {} | {} | {} | {} | {} |".format(
                    _fmt(r.get("step"), 0),
                    _fmt(r.get("time/rollout_s")),
                    _fmt(r.get("time/score_s")),
                    _fmt(r.get("time/train_s")),
                    _fmt(r.get("time/window_wall_s")),
                    _fmt(r.get("time/overlap_fraction"), 2),
                    _fmt(r.get("train_tokens_per_s"), 0),
                )
            )
        if len(windows) > 12:
            lines.append(f"\n(last 12 of {len(windows)} windows)")
    else:
        lines.append("No phase windows recorded (serial single-batch run, or PhaseTimer off).")
    lines.append("")

    # --- MFU trend --------------------------------------------------------
    lines += ["## MFU / FLOP throughput (compiled-cost derived)", ""]
    mfu = [(r.get("step"), r["obs/train_mfu_pct"]) for r in scalars if "obs/train_mfu_pct" in r]
    tfl = [r["obs/train_tflops_per_chip"] for r in scalars if "obs/train_tflops_per_chip" in r]
    if mfu:
        values = [v for _, v in mfu]
        lines.append(
            f"- train MFU: last {_fmt(values[-1], 2)}% · mean {_fmt(float(np.mean(values)), 2)}% "
            f"· max {_fmt(max(values), 2)}% over {len(values)} windows"
        )
        lines.append(f"- trend: `{_trend(values)}`")
    elif tfl:
        lines.append(
            f"- train TFLOP/s per chip: last {_fmt(tfl[-1], 2)} · mean {_fmt(float(np.mean(tfl)), 2)} "
            "(peak FLOP/s unknown — set TRLX_TPU_PEAK_TFLOPS for an MFU %)"
        )
    else:
        lines.append("No compiled-cost gauges recorded (train.device_telemetry off).")
    lines.append("")

    # --- staleness --------------------------------------------------------
    lines += ["## Staleness", ""]
    stale = [r for r in scalars if "staleness/mean" in r]
    hists = [r for r in metrics if r.get("histogram") == "staleness"]
    if stale:
        means = [r["staleness/mean"] for r in stale]
        maxes = [r.get("staleness/max", 0.0) for r in stale]
        lines.append(
            f"- per-batch staleness: mean {_fmt(float(np.mean(means)), 3)} · "
            f"max {_fmt(float(np.max(maxes)), 1)} over {len(stale)} batches"
        )
    if hists:
        last = hists[-1]
        lines.append(
            "- last histogram: " + " · ".join(
                f"{k} {_fmt(last.get(k))}" for k in ("p5", "p50", "p95", "max") if k in last
            )
        )
    if not stale and not hists:
        lines.append("No staleness records (serial on-policy run).")
    lines.append("")

    # --- kernel routing ---------------------------------------------------
    lines += ["## Kernel routing", ""]
    routed = [r for r in scalars if "obs/fused_logprob_active" in r]
    if routed:
        last = routed[-1]
        lines.append("| gauge | value |")
        lines.append("|---|---|")
        for key in sorted(k for k in last if k.startswith("obs/") and ("active" in k or "fallback" in k)):
            lines.append(f"| {key} | {_fmt(last[key], 0)} |")
        fallbacks = [k for k in last if k.endswith("_fallback") and last[k]]
        if fallbacks:
            lines.append("")
            lines.append(f"**WARNING: silent kernel fallback active: {fallbacks}** — see RUNBOOK.md §8.")
    else:
        lines.append("No routing gauges recorded.")
    programs_path = os.path.join(checkpoint_dir, "programs.json")
    if os.path.exists(programs_path):
        try:
            with open(programs_path) as f:
                programs = json.load(f)
        except (OSError, ValueError):
            programs = {}
        if programs:
            lines += ["", "### Monitored programs", "", "| program | phase | dispatches | GFLOPs | temp MiB |", "|---|---|---|---|---|"]
            for name, prog in sorted(programs.items()):
                variants = prog.get("variants", [])
                flops = max((v.get("flops") or 0.0 for v in variants), default=0.0)
                temp = max((v.get("temp_size_in_bytes") or 0 for v in variants), default=0)
                lines.append(
                    f"| {name} | {prog.get('phase')} | {prog.get('dispatches')} "
                    f"| {_fmt(flops / 1e9, 2)} | {_fmt(temp / 2**20, 1)} |"
                )
    lines.append("")

    # --- span lanes -------------------------------------------------------
    lines += ["## Span lanes", ""]
    if spans:
        lanes = _lane_summary(spans)
        lines.append("| pid | thread | events | busy_s | top span |")
        lines.append("|---|---|---|---|---|")
        for lane in lanes:
            lines.append(
                f"| {lane['pid']} | {lane['thread']} | {lane['events']} "
                f"| {_fmt(lane['busy_s'], 2)} | {lane['top_span']} |"
            )
        overlap = _overlap_seconds(spans, "trlx-rollout-producer", "MainThread")
        if overlap > 0:
            lines.append("")
            lines.append(f"- producer/train overlap: {_fmt(overlap, 2)}s of wall where both lanes were busy")
        lines.append("")
        lines.append("Load the raw lanes in Perfetto (https://ui.perfetto.dev): open `spans.jsonl` directly,")
        lines.append("or `--trace-out trace.json` for chrome://tracing.")
    else:
        lines.append("No spans recorded (train.trace_spans off — set it or TRLX_TPU_SPANS=1).")
    lines.append("")

    # --- graftfleet: cross-host federation --------------------------------
    lines += _fleet_section(checkpoint_dir)

    # --- graftnum: numerics observatory -----------------------------------
    lines += _numerics_section(checkpoint_dir, scalars)

    # --- training health --------------------------------------------------
    incidents_dir = os.path.join(checkpoint_dir, "incidents")
    bundles = sorted(os.listdir(incidents_dir)) if os.path.isdir(incidents_dir) else []
    lines += ["## Training health", ""]
    state_names = {0: "OK", 1: "WARN", 2: "CRIT"}
    state_keys = sorted(
        {k for r in scalars for k in r if k.startswith("health/") and k.endswith("_state")}
    )
    if state_keys:
        lines.append("| detector | last | worst | records | trend (0=OK 1=WARN 2=CRIT) |")
        lines.append("|---|---|---|---|---|")
        for key in state_keys:
            series = [float(r[key]) for r in scalars if key in r]
            detector = key[len("health/") : -len("_state")]
            lines.append(
                "| {} | {} | {} | {} | `{}` |".format(
                    detector,
                    state_names.get(int(series[-1]), "?"),
                    state_names.get(int(max(series)), "?"),
                    len(series),
                    _trend(series),
                )
            )
        changes = [r["health/state_changes_total"] for r in scalars if "health/state_changes_total" in r]
        if changes:
            lines.append("")
            lines.append(f"- state transitions: {int(changes[-1])} total")
        # Cross-links: incident bundles this monitor escalated (reason
        # health_<detector>) — the full bundle table is in ## Incidents.
        health_bundles = []
        for name in bundles:
            try:
                with open(os.path.join(incidents_dir, name, "incident.json")) as f:
                    manifest = json.load(f)
            except (OSError, ValueError):
                continue
            if str(manifest.get("reason", "")).startswith("health_"):
                health_bundles.append((name, manifest.get("reason")))
        if health_bundles:
            lines.append(
                "- escalated incidents: "
                + " · ".join(f"{reason} -> `incidents/{name}/`" for name, reason in health_bundles)
            )
        hists = [r for r in metrics if r.get("histogram") == "health/lineage_staleness"]
        if hists:
            last = hists[-1]
            lines.append(
                "- lineage staleness (last window): " + " · ".join(
                    f"{k} {_fmt(last.get(k))}"
                    for k in ("count", "p5", "p50", "p95", "max")
                    if k in last
                )
            )
        lineage_path = os.path.join(checkpoint_dir, "lineage.jsonl")
        if os.path.exists(lineage_path):
            records = _load_jsonl(lineage_path)
            if records:
                stale_vals = [r.get("staleness", 0.0) for r in records]
                lines.append(
                    f"- lineage records: {len(records)} chunks · staleness mean "
                    f"{_fmt(float(np.mean(stale_vals)))} max {_fmt(float(np.max(stale_vals)), 1)} "
                    "(`lineage.jsonl`)"
                )
    else:
        lines.append("No health records (train.health_monitor off — set it or TRLX_TPU_HEALTH=1).")
    lines.append("")

    # --- stalls -----------------------------------------------------------
    lines += _stalls_section(checkpoint_dir)

    # --- incidents --------------------------------------------------------
    lines += ["## Incidents", ""]
    if bundles:
        lines.append("| step | reason | sections | bundle |")
        lines.append("|---|---|---|---|")
        for name in bundles:
            manifest_path = os.path.join(incidents_dir, name, "incident.json")
            reason, sections = "?", "?"
            try:
                with open(manifest_path) as f:
                    manifest = json.load(f)
                reason = manifest.get("reason", "?")
                sections = ",".join(k for k, v in manifest.get("sections", {}).items() if v == "ok")
            except (OSError, ValueError):
                pass
            lines.append(f"| {name} | {reason} | {sections} | `incidents/{name}/` |")
    else:
        lines.append("None.")
    lines.append("")
    return "\n".join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m trlx_tpu.observability.report",
        description="Render a markdown performance report from a run's checkpoint dir.",
    )
    parser.add_argument("checkpoint_dir", help="directory holding metrics.jsonl / spans.jsonl")
    parser.add_argument("-o", "--out", default=None, help="write the report here (default: stdout)")
    parser.add_argument(
        "--trace-out",
        default=None,
        help="also write spans.jsonl as a {'traceEvents': [...]} JSON for chrome://tracing",
    )
    parser.add_argument(
        "--xplane",
        default=None,
        help="a profiler trace (.xplane.pb[.gz]) of this run: adds 'Device time by scope' from device_scopes.json",
    )
    args = parser.parse_args(argv)

    report = build_report(args.checkpoint_dir)
    if args.xplane:
        report += "\n" + "\n".join(_device_scopes_section(os.path.abspath(args.checkpoint_dir), args.xplane))
    if args.out:
        with open(args.out, "w") as f:
            f.write(report)
        print(f"wrote {args.out}")
    else:
        print(report)

    if args.trace_out:
        # Fleet-aware: merges spans.host<k>.jsonl into clock-aligned per-host
        # lanes when graftfleet ran; identical to the plain spans.jsonl dump
        # otherwise.
        from trlx_tpu.observability.spans import read_fleet_spans

        spans = read_fleet_spans(os.path.abspath(args.checkpoint_dir))["traceEvents"]
        with open(args.trace_out, "w") as f:
            json.dump({"traceEvents": spans}, f)
        print(f"wrote {args.trace_out} ({len(spans)} events)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

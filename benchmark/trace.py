"""From the profiler's `.xplane.pb` to numbers: the one reduction every PR uses.

What a v5e trace looks like (read by hand, PR 22; the names are data in
`trace_patterns.json`): the plane `/device:TPU:0` has a line `XLA Modules`
with one event per execution of a jitted program, named
`jit_<function>(<fingerprint>)`, and a line `XLA Ops` with one event per
executed HLO operation, named by the operation's whole HLO text
(`%fusion.79 = bf16[8,1024,4096]{...} fusion(...)`); a loop is one event that
contains its body's events. A Pallas kernel is a
`custom-call(..., custom_call_target="tpu_custom_call")` under the named
scope it was traced in (`%attn.41`, `%transformer.3`): the kernels carry no
name of their own, so they are told apart by their result and operand types.
The plane `/host:CPU` has a line `python` with JAX's own host events
(`PjitFunction(train_step)`, `np.asarray(jax.Array)`) and any
`jax.profiler.TraceAnnotation` that begins inside the traced window.

Busy time is the union of the operation intervals of a device plane; a
program's device time is the duration of its module events; an operation
belongs to the program whose event contains its start. Idle gaps are the
complement of the busy union, each labelled with the innermost host event
covering its middle and the programs before and after it.
"""

import bisect
import re
import statistics
from collections import defaultdict

MIN_GAP_NS = 1000  # shorter pauses between two operations are the device's own, not the host's
LAYOUT = re.compile(r"\{[^{}]*\}")
OP_TEXT = re.compile(r"^%(?P<base>[^\s=]+?)(?:\.\d+)? = (?P<result>.*?) (?P<opcode>[\w\-]+)\(")
CALLS = re.compile(r", calls=%[\w\.\-]+")  # what a fusion calls: a v5e writes a reduce-scatter so, after the operand list
TEXT_KEPT = 1200


def describe_op(text, mosaic_pattern):
    """(label, is_mosaic) of an operation's HLO text: `<name without its
    number> <opcode> -> <result types>`, which groups the same operation of
    every layer under one label."""
    mosaic = bool(re.search(mosaic_pattern, text))
    m = OP_TEXT.match(text)
    if not m:
        return text[:80], mosaic
    result = LAYOUT.sub("", m.group("result"))
    kind = "mosaic-kernel" if mosaic else m.group("opcode")
    return f"{m.group('base')} {kind} -> {result[:90]}", mosaic


def kept_text(text):
    """The part of an operation's HLO text a row keeps for the metric files'
    patterns: its first `TEXT_KEPT` characters without layouts and, where a
    long operand list pushed it beyond them, what the operation calls."""
    text = LAYOUT.sub("", text)
    called = CALLS.search(text)
    return text[:TEXT_KEPT] + (called.group(0) if called and called.end() > TEXT_KEPT else "")


def _events(plane, line_pattern):
    for line in plane.lines:
        if re.search(line_pattern, line.name):
            for e in line.events:
                if e.duration_ns > 0:
                    yield e


def _union(intervals):
    """Merged, sorted [start, end] list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_planes(planes, patterns, window_ns=None):
    """The reduction over already-loaded planes (see `reduce_file`)."""
    planes = list(planes)
    devices = [p for p in planes if re.search(patterns["device_plane"], p.name)]
    hosts = [p for p in planes if re.search(patterns["host_plane"], p.name)]
    name_re = re.compile(patterns["program_name"])
    n = max(len(devices), 1)
    programs = defaultdict(list)  # program -> [seconds per execution]
    ops = {}  # (program, op name) -> {seconds, calls, text, mosaic, container}
    busy_ns, first_chip, first_modules = 0.0, [], []
    for plane in devices:
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, name_re.match(e.name).group("name"))
            for e in _events(plane, patterns["modules_line"])
        )
        starts = [m[0] for m in modules]
        for s, e, name in modules:
            programs[name].append((e - s) / 1e9)
        events = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in _events(plane, patterns["ops_line"])), key=lambda t: (t[0], -t[1]))
        for k, (s, e, text) in enumerate(events):
            i = bisect.bisect_right(starts, s) - 1
            program = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
            short = text.split(" = ", 1)[0].lstrip("%")
            row = ops.get((program, short))
            if row is None:
                label, mosaic = describe_op(text, patterns["mosaic_op"])
                row = ops[(program, short)] = {"seconds": 0.0, "calls": 0, "text": kept_text(text), "label": label,
                                               "mosaic": mosaic, "container": False}
            row["seconds"] += (e - s) / 1e9 / n
            row["calls"] += 1
            if k + 1 < len(events) and events[k + 1][0] < e:
                row["container"] = True  # a loop or a call: its body's events lie inside it
        merged = _union((s, e) for s, e, _ in events)
        busy_ns += sum(e - s for s, e in merged)
        if not first_chip:
            first_chip, first_modules = merged, modules

    host_events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for plane in hosts for line in plane.lines if re.search(patterns["host_label_line"], line.name)
        for e in line.events if e.duration_ns > 0
    )
    if window_ns is None:
        window_ns = (first_chip[-1][1] - first_chip[0][0]) if first_chip else 0.0
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(first_chip, first_chip[1:])
                   if s1 - e0 >= MIN_GAP_NS), reverse=True)
    module_starts = [m[0] for m in first_modules]
    by_label, longest = defaultdict(float), []
    for length, e0, s1 in gaps:
        mid = (e0 + s1) / 2
        covering = [h for h in host_events if h[0] <= mid < h[1]]
        host = min(covering, key=lambda h: h[1] - h[0])[2] if covering else "unattributed"
        i = bisect.bisect_right(module_starts, e0) - 1  # the program whose operation ended at e0
        before = first_modules[i][2] if i >= 0 else "start"
        after = first_modules[i + 1][2] if i + 1 < len(first_modules) else "end"
        inside = i >= 0 and first_modules[i][1] >= s1
        label = f"{host} [inside {before}]" if inside else f"{host} [{before} -> {after}]"
        by_label[label] += length / 1e9
        if len(longest) < 10:
            longest.append([label, length / 1e9])
    by_op = defaultdict(float)
    for (program, _), row in ops.items():
        by_op[f"{program}/{row['label']}" + (" (contains its body)" if row["container"] else "")] += row["seconds"]
    return {
        "n_devices": len(devices),
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "programs": {
            k: {"count": len(v), "total_s": sum(v) / n, "median_s": statistics.median(v)}
            for k, v in programs.items()
        },
        "ops": {f"{p}/{o}": row for (p, o), row in ops.items()},
        "device_ops": [[k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": longest,
        "idle_by_label": dict(sorted(by_label.items(), key=lambda kv: -kv[1])[:20]),
    }


def reduce_file(path, patterns, window_ns=None):
    """Reduce one `.xplane.pb`. `window_ns` is the traced window on the host's
    clock where the caller took it; without it, first to last device event."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, patterns, window_ns)


def program_rows(reduction, pattern):
    return [row for name, row in reduction["programs"].items() if re.search(pattern, name)]


def median_execution_seconds(reduction, pattern):
    """Median device time of one execution of the matching program that took
    most time in all; None where the trace shows no such program."""
    rows = program_rows(reduction, pattern)
    return max(rows, key=lambda r: r["total_s"])["median_s"] if rows else None


def op_rows(reduction, spec):
    """Rows of `reduction["ops"]` a metric's file selects: inside programs
    matching spec["programs"] (default: all), Pallas kernels only where
    spec["mosaic"], HLO text (layouts stripped) matching spec["select"] and
    not spec["exclude"]. Loops are left out: their bodies are counted."""
    rows = []
    for name, row in reduction["ops"].items():
        if row["container"] or not re.search(spec.get("programs", ""), name.split("/", 1)[0]):
            continue
        if spec.get("mosaic") and not row["mosaic"]:
            continue
        if re.search(spec.get("select", ""), row["text"]) and not (
                spec.get("exclude") and re.search(spec["exclude"], row["text"])):
            rows.append(row)
    return rows

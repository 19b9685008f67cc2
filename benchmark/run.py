"""Run one cell of the benchmark: one process, one cell, one JSON line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It hides every chip but the cell's before JAX starts, fails (exit 2, no
result line) without a TPU or with another number of chips, builds the model
on the device from --seed, keeps the compile cache where
`trlx_tpu/utils/compile_cache.py` puts it (`JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`), runs the cell through the program's normal path
(benchmark/harness.py), checks the outputs, and prints the contract's line
last. With --trace 0 the metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics and the breakdown. Everything else it knows
(phase times, the window, routes, compile log) goes on earlier lines and
into `benchmark_out/<cell>/summary.json`.

`--rehearsal` runs the same control flow at the configuration's tiny
rehearsal widths on the CPU: it prints `platform: cpu`, no device metric, no
result line, and exits 3.
"""

T0 = __import__("time").time()  # process start, as near as Python can see it

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None, help="default: BENCHMARK.json run_seconds")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true", help="CPU, tiny widths, control flow only")
    p.add_argument("--keep-trace", default="", help="copy the traced run's .xplane.pb (gzipped) here")
    return p.parse_args(argv)


def say(kind, payload):
    print(f"[bench] {kind} {json.dumps(payload, default=str)}", flush=True)


def main(argv=None):
    args = parse_args(argv)
    from benchmark import harness
    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config_spec = manifest.config(cell["config"])
    if args.rehearsal:
        cell = harness.merged(cell, cell.get("rehearsal"))
    seconds = args.seconds if args.seconds is not None else float(manifest.doc["run_seconds"])
    harness.place_process(cell["chips"], args.rehearsal)

    import jax

    harness.mark("jax_imported")
    dev = jax.devices()[0]
    harness.mark("device_ready")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    say("device", {**device, "jax": jax.__version__})
    if not args.rehearsal and dev.platform != "tpu":
        print(f"benchmark: no TPU (platform {dev.platform!r}); --rehearsal is the CPU run", file=sys.stderr)
        return 2
    if device["count"] != cell["chips"]:
        print(f"benchmark: cell wants {cell['chips']} chip(s), JAX shows {device['count']}", file=sys.stderr)
        return 2

    cache_dir = harness.setup_cache()
    compiles = harness.CompileLog().install()
    out_dir = os.path.join(ROOT, "benchmark_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)  # metrics.jsonl is appended to, and read below
    os.makedirs(out_dir)
    tracer = harness.Tracer(os.path.join(out_dir, "trace")) if args.trace else None
    config, arch = harness.build_config(cell, config_spec, args.seed, out_dir, args.rehearsal)
    harness.mark("program_imported")
    say("cell", {"name": cell["name"], "seed": args.seed, "seconds": seconds, "trace": args.trace,
                 "cache_dir": cache_dir, "rehearsal": args.rehearsal})

    kernels = {}
    with harness.record_pallas_calls(kernels):
        trainer, window, traced = harness.RUNNERS[cell["method"]](cell, config, arch, args.seed, seconds, tracer)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    # `peak_bytes_in_use` leaves out what lives inside a running program (the
    # rollout's KV cache, the train step's activations): the runtime reserves
    # that apart. So the peak is taken as the larger of the live buffers'
    # own peak and the live buffers after the window plus the largest
    # reservation for program temporaries; read before the checks add to
    # either (PERF.md section 6, PR 22).
    peak_bytes = max(
        max(int(s.get("peak_bytes_in_use", 0)), int(s.get("bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))
        for s in stats)
    say("memory_stats", stats[0])
    say("window", {k: window[k] for k in ("t0", "t1", "seconds", "iterations", "samples", "tokens", "samples_per_s",
                                          "tokens_per_s", "iteration_seconds") if k in window})
    say("timeline_s_since_start", {k: round(v - T0, 2) for k, v in sorted(
        {**harness.MARKS, "window_start": window["t0"], "window_end": window["t1"]}.items(), key=lambda kv: kv[1])})

    logits = harness.check_logits(trainer, manifest.reference(config_spec["reference"]), arch, cell, args.seed)
    checks, detail, counts = harness.verdict(cell, trainer, window, compiles, kernels, logits)
    say("checks", checks)
    say("detail", detail)

    chips = cell["chips"]
    setup = compiles.summary(window["t0"])
    values = {
        "samples_per_s_chip": window["samples_per_s"] / chips,
        "tokens_per_s_chip": window["tokens_per_s"] / chips,
        "peak_hbm_gb": peak_bytes / 1e9,
        "setup_s": window["t0"] - T0,
    }
    summary = {
        "cell": cell["name"], "seed": args.seed, "device": device, "checks": checks, "detail": detail,
        "window": {k: v for k, v in window.items() if k not in ("steps", "all_steps")},
        "step_time_median_s": harness.median([r["step_time"] for r in window["steps"] if "step_time" in r]),
        "compile_setup": setup, "compile_all": compiles.summary(time.time()),
        "end_to_end": values, "seconds_after_window": None,
    }

    if args.trace:
        from benchmark import trace

        reduction = None
        xplane = tracer.xplane()
        if xplane is not None:
            patterns = json.load(open(manifest.path("trace_patterns.json")))
            window_ns = (tracer.t_stop - tracer.t_start) * 1e9
            reduction = trace.reduce_file(xplane, patterns, window_ns)
            if args.keep_trace:
                import gzip

                os.makedirs(args.keep_trace, exist_ok=True)
                with open(xplane, "rb") as src, gzip.open(
                        os.path.join(args.keep_trace, f"{cell['name']}.xplane.pb.gz"), "wb") as dst:
                    shutil.copyfileobj(src, dst)
            shutil.rmtree(tracer.directory, ignore_errors=True)  # keep only the reduction
        tp = cell["traffic_params"]
        ctx = {
            "cell": cell, "arch": arch, "reduction": reduction, "traced": traced, "window": window,
            "compile": setup, "flops": manifest.counts(config_spec.get("flops")), "trace": trace, "chips": chips,
            "peaks": None if args.rehearsal else manifest.peaks(device["kind"]),
            "shapes": {
                "batch": config.train.batch_size, "seq": config.train.seq_length,
                "prompt": tp.get("prompt_length", {}).get("max", 0), "response": tp.get("new_tokens", 0),
                "unfrozen": config.model.num_layers_unfrozen, "two_qs": getattr(config.method, "two_qs", False),
                "method": cell["method"],
            },
        }
        metrics = {}
        for m in manifest.metrics_for(cell["name"], "per_layer"):
            spec = manifest.layer_metric(m["name"])
            value = manifest.reader(spec["reader"])(ctx, spec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduction is not None:
            summary["trace"] = {k: v for k, v in reduction.items() if k != "ops"}
            summary["trace"]["top_ops"] = sorted(
                ([k, v["seconds"], v["calls"]] for k, v in reduction["ops"].items()), key=lambda r: -r[1])[:60]
    else:
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in manifest.metrics_for(cell["name"], "end_to_end")
        }
    summary["metrics"] = metrics
    summary["seconds_after_window"] = time.time() - window["t1"]
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    say("summary", {k: summary[k] for k in ("step_time_median_s", "compile_setup", "compile_all",
                                            "end_to_end", "seconds_after_window")})

    if args.rehearsal:
        say("rehearsal", {"platform": device["platform"], "checks": checks, "metrics_named": sorted(metrics)})
        print("platform: cpu (rehearsal: control flow only, no device metric, no result)", flush=True)
        return 3
    device["memory_peak_bytes"] = peak_bytes
    line = {"correct": all(checks.values()), **counts, "metrics": metrics, "device": device}
    if args.trace and reduction is not None:
        device["busy_s"], device["window_s"] = reduction["busy_s"], reduction["window_s"]
        line["breakdown"] = {"device_ops": reduction["device_ops"],
                             "idle_gaps": [[k, v] for k, v in reduction["idle_by_label"].items() if v >= 1e-4][:10]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

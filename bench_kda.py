"""Microbench of the gated delta-rule pass (`models/kda.py kda_chunked`) on the
chip: one layer at Kimi-Linear's published widths, the train batch of the cell
`kimilinear-l13.ppo-128x896` ([8, 1024], 32 heads of 128, bf16 operands,
chunks of 64), in the row groups `KDAMixer.__call__` runs it in (`lax.map`
over groups of `SCAN_TOKENS`, each recomputed in its own backward pass).

    chiprun --timeout 900 -- python bench_kda.py                 # forward and value_and_grad, ms a call
    chiprun --timeout 900 -- python bench_kda.py --ops 25        # and the 25 longest operations of each
    chiprun --timeout 1200 -- bash -c "python bench_kda.py --repo .scratch/parent --label parent && python bench_kda.py"

One process. Each program runs `--reps` times under the profiler and its
device time is the median of its executions on the trace's `XLA Modules` line
(`benchmark/trace.py`, the benchmark's own reduction); `--ops N` adds the N
operations (no loops: their bodies are counted) that took longest, ms a call.
One JSON line a program on stdout and in `chiprun_out/bench_kda.jsonl`.
`--repo DIR` measures the `trlx_tpu` of another checkout (a `git archive` of
the parent) with the same script, so before and after come from one call.
Exits 2 without a TPU: a CPU time is not a device time (PERF.md). Not a metric
of the benchmark, run by no cell; PERF.md section 6 (PR 40) keeps its table.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=None, help="measure the trlx_tpu of this checkout instead")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--ops", type=int, default=0, help="print this many of each program's longest operations")
    ap.add_argument("--label", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.repo:
        sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, HERE)  # benchmark/trace.py: always this checkout's reduction

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("bench_kda.py: no TPU backend; a pass's time comes only from the chip", file=sys.stderr)
        return 2
    from benchmark import trace
    from trlx_tpu.models import kda

    patterns = json.load(open(os.path.join(HERE, "benchmark", "trace_patterns.json")))
    label = args.label or (os.path.basename(os.path.abspath(args.repo)) if args.repo else "tree")
    b, T, H, D = args.rows, args.tokens, args.heads, args.width
    dtype = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + kda.NORM_EPS)
    q, k, v, weight = (jax.random.normal(key, (b, T, H, D), jnp.float32) for key in keys[:4])
    q, k, v = (unit(q) * D ** -0.5).astype(dtype), unit(k).astype(dtype), v.astype(dtype)
    g = -jax.random.uniform(keys[4], (b, T, H, D), jnp.float32, 0.01, 0.3)  # a log-decay a key channel, <= 0
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, T, H), jnp.float32))

    def forward(q, k, v, g, beta):
        """`KDAMixer.__call__`'s pass over a batch with no cache: row groups under `lax.map`."""
        group = max(1, kda.SCAN_TOKENS // T)
        if not (b > group and b % group == 0):
            return kda.kda_chunked(q, k, v, g, beta, kda.CHUNK, dtype)[0]
        split = lambda t: t.reshape((b // group, group) + t.shape[1:])
        o, _ = jax.lax.map(jax.checkpoint(lambda ops: kda.kda_chunked(*ops, kda.CHUNK, dtype)),
                           tuple(split(t) for t in (q, k, v, g, beta)))
        return o.reshape((b,) + o.shape[2:])

    def gradient(q, k, v, g, beta):
        return jax.value_and_grad(lambda *ops: jnp.sum(forward(*ops) * weight), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/bench_kda.jsonl", "a")
    for fn in (forward, gradient):
        step = jax.jit(fn)
        jax.block_until_ready(step(q, k, v, g, beta))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(args.reps):
                    res = step(q, k, v, g, beta)
                jax.block_until_ready(res)
            (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
            reduction = trace.reduce_file(path, patterns)
        (program,) = trace.program_rows(reduction, f"^jit_{fn.__name__}$")
        rows = sorted((r for name, r in reduction["ops"].items() if not r["container"] and name.startswith(f"jit_{fn.__name__}/")),
                      key=lambda r: -r["seconds"])
        record = {"label": label, "program": fn.__name__, "bTHD": [b, T, H, D], "reps": program["count"],
                  "ms": round(program["median_s"] * 1e3, 4), "operations": len(rows),
                  "finite": bool(jnp.isfinite(jax.tree_util.tree_leaves(res)[0]).all())}
        if args.ops:
            record["ops"] = [[round(r["seconds"] / program["count"] * 1e3, 4), r["calls"] // program["count"], r["label"]]
                             for r in rows[:args.ops]]
        line = json.dumps(record)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

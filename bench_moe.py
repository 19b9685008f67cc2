"""Microbench of the expert layer's grouped product (`jax.lax.ragged_dot`, as
`models/moe.py grouped_held_ffn` calls it) on the chip, at the train-step
shapes of the two cells whose slot buffer is wide:

    smallthinker-ep4.ppo-4096x2048   12,288 tokens, 6 of 64 a token, 16 held: [rows, 2560] x [16, 2560, 768]
    zaya1-ep2.ppo-4096x2048          12,288 tokens, 1 of 16 a token, 8 held:  [rows, 2048] x [8, 2048, 2048]

each product (`up`: d -> f, `down`: f -> d where f is not d) forward, its lhs-gradient and its
rhs-gradient (autodiff's own), in three forms:

    one       ONE call over the train batch's rows (PR 48: `pass_tokens`)
    three     `lax.map` over three passes of a third of the rows each, the weight gradient summed over the passes
    aligned   ONE call whose every group starts on a `ROW_TILE`-row tile (a group's rows padded up to whole tiles)

    chiprun --timeout 900 -- python bench_moe.py
    chiprun --timeout 900 -- python bench_moe.py --cell zaya --held-share 0.49

One process. Each program runs `--reps` times under the profiler and its
device time is the median of its executions on the trace's `XLA Modules` line
(`benchmark/trace.py`, the benchmark's own reduction). Beside each time the
line gives what the two accounts of a grouped product's time would have it
follow: `live_rows`, and `tile_visits`, the (group, 512-row tile) pairs the
groups touch (about `live / 512 + groups - 1`: each group ends in a partial
tile). The groups' sizes are drawn from `--seed` around the held share the
chip read (`moe/held_slot_share`: 0.27 and 0.5). One JSON line a program on
stdout and in `chiprun_out/bench_moe.jsonl`. Exits 2 without a TPU: a CPU
time is not a device time (PERF.md). Not a metric of the benchmark, run by no
cell; PERF.md section 6 (PR 48) keeps its table.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = {  # tokens of the train batch, choices a token, experts, held, d_model, expert_d_ff, the held share of the slots the chip read
    "smallthinker": dict(tokens=12288, k=6, n_experts=64, held=16, d=2560, f=768, held_share=0.27),
    "zaya": dict(tokens=12288, k=1, n_experts=16, held=8, d=2048, f=2048, held_share=0.5),
}
PASSES = 3


def group_sizes(rng, live: int, groups: int):
    """[groups] sizes that sum to `live`, each 0.5 to 1.5 of the mean."""
    share = rng.uniform(0.5, 1.5, groups)
    sizes = (share / share.sum() * live).astype(int)
    sizes[0] += live - sizes.sum()
    return sizes


def tile_visits(sizes, tile: int) -> int:
    """(group, tile) pairs that contiguous groups of `sizes` rows touch."""
    visits, start = 0, 0
    for size in sizes:
        if size:
            visits += (start + size - 1) // tile - start // tile + 1
        start += size
    return int(visits)


def build_programs(cell: str, c: dict, forms: dict, seed: int) -> dict:
    """{name: (jitted step, operands, record)}: each product of the cell, each
    form of `forms` (form: (group sizes [passes, groups], rows of a pass's
    buffer)), forward and both gradients."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.moe import ROW_TILE

    def steps_over(counts):
        def passes(xs, w):
            if counts.shape[0] == 1:
                return jax.lax.ragged_dot(xs[0], w, counts[0])[None]
            return jax.lax.map(lambda a: jax.lax.ragged_dot(a[0], w, a[1]), (xs, counts))

        return {
            "forward": lambda xs, w, dy: passes(xs, w),
            "lhs_gradient": lambda xs, w, dy: jax.vjp(lambda xs: passes(xs, w), xs)[1](dy)[0],
            "rhs_gradient": lambda xs, w, dy: jax.vjp(lambda w: passes(xs, w), w)[1](dy)[0],
        }

    dtype = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    programs = {}
    products = {"up": (c["d"], c["f"]), "down": (c["f"], c["d"])}
    if c["d"] == c["f"]:
        del products["down"]  # the same program as `up`: XLA shares the executable and the trace names it once
    for product, (d_in, d_out) in products.items():
        w = (jax.random.normal(keys[0], (c["held"], d_in, d_out), jnp.float32) * d_in ** -0.5).astype(dtype)
        for form, (sizes, rows) in forms.items():
            xs = jax.random.normal(keys[1], (sizes.shape[0], rows, d_in), dtype)
            dy = jax.random.normal(keys[2], (sizes.shape[0], rows, d_out), dtype)
            for kind, step in steps_over(jnp.asarray(sizes, jnp.int32)).items():
                step.__name__ = f"{cell}_{product}_{form}_{kind}"
                programs[step.__name__] = (jax.jit(step), (xs, w, dy), dict(
                    cell=cell, product=product, form=form, kind=kind, passes=int(sizes.shape[0]), groups=c["held"],
                    buffer_rows=int(rows), live_rows=int(sizes.sum()), tile_visits=sum(tile_visits(s, ROW_TILE) for s in sizes),
                    gflop=round(2 * int(sizes.sum()) * d_in * d_out / 1e9, 3), shape=[d_in, d_out]))
    return programs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(CELLS), action="append", help="default: both")
    ap.add_argument("--held-share", type=float, default=None, help="held slots over slots; default: what the chip read in the cell")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, HERE)

    import jax
    import numpy as np

    if jax.default_backend() != "tpu":
        print("bench_moe.py: no TPU backend; a product's time comes only from the chip", file=sys.stderr)
        return 2
    from benchmark import trace
    from trlx_tpu.models.moe import ROW_TILE, slot_capacity

    patterns = json.load(open(os.path.join(HERE, "benchmark", "trace_patterns.json")))
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/bench_moe.jsonl", "a")
    whole_tiles = lambda n: -(-n // ROW_TILE) * ROW_TILE

    for cell in args.cell or sorted(CELLS):
        c = CELLS[cell]
        rng = np.random.default_rng(args.seed)
        share = args.held_share or c["held_share"]
        third = [group_sizes(rng, int(share * c["tokens"] * c["k"] / PASSES), c["held"]) for _ in range(PASSES)]
        # form: (the passes' group sizes [passes, groups], rows of a pass's buffer)
        forms = {
            "one": (np.sum(third, axis=0)[None], slot_capacity(c["tokens"], c["k"], c["held"], c["n_experts"])),
            "three": (np.stack(third), slot_capacity(c["tokens"] // PASSES, c["k"], c["held"], c["n_experts"])),
        }
        padded = whole_tiles(forms["one"][0])  # every group whole tiles: the next starts on one
        forms["aligned"] = (padded, max(forms["one"][1], int(padded.sum())))
        programs = build_programs(cell, c, forms, args.seed)
        for step, operands, _ in programs.values():
            jax.block_until_ready(step(*operands))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for step, operands, _ in programs.values():
                    for _ in range(args.reps):
                        res = step(*operands)
                    jax.block_until_ready(res)
            (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
            reduction = trace.reduce_file(path, patterns)
        for name, (_, _, record) in programs.items():
            (program,) = trace.program_rows(reduction, f"^jit_{name}$")
            ms = program["median_s"] * 1e3
            record.update(reps=program["count"], ms=round(ms, 4), us_per_visit=round(ms * 1e3 / record["tile_visits"], 3),
                          live_tflops=round(record["gflop"] / ms, 2), held_share=share, seed=args.seed)
            line = json.dumps(record)
            print(line, flush=True)
            out.write(line + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

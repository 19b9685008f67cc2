"""Microbench of the three flash-attention kernels on the chip, at the shapes
the benchmark's one-chip cells call them with.

    chiprun --timeout 1500 -- python bench_flash.py            # pick_block's sizes
    chiprun --timeout 1500 -- python bench_flash.py --sweep    # and a grid of sizes around them

One process. Each (shape, sizes) point runs forward + backward a few times
under the profiler and reads the kernels' own device time from the trace by
the calls' names (`flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`): milliseconds
a call, one JSON line a point on stdout and in
`chiprun_out/bench_flash.jsonl`. `--repo DIR` measures the `trlx_tpu` of
another checkout (a `git archive` of the parent) with the same script, so
before and after come from one call. Exits 2 without a TPU: a CPU time is not
a kernel time (PERF.md). Not a metric of the benchmark; PERF.md §6 keeps the
table `pick_block`'s rule was read from.
"""

import argparse
import glob
import inspect
import json
import os
import sys
import tempfile

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")

# name: batch, positions, heads, head_dim, scale, window, padding, offset
SHAPES = {
    "gptj_train": (8, 1024, 16, 256, 1 / 16, 0, "left", None),
    "gptneo_global": (16, 512, 16, 128, 1.0, 0, "left", None),
    "gptneo_local": (16, 512, 16, 128, 1.0, 256, "left", None),
    "ilql": (8, 256, 16, 128, 1.0, 0, "right", None),
    "kimi_train": (4, 1024, 64, 256, 0.1147, 0, "left", None),
    "gptj_prefill768": (8, 768, 16, 256, 1 / 16, 0, "left", None),
    # in no cell: a sequence too long to be resident (major pieces, the state in
    # scratch), and the ring path's call (a traced offset, lse returned; the
    # visiting chunk one chunk in the past: every pair live)
    "long8192": (2, 8192, 16, 256, 1 / 16, 0, "left", None),
    "ring_chunk": (8, 1024, 16, 256, 1 / 16, 0, "left", -1024.0),
}
SWEEP = ((128, 128), (256, 128), (256, 256), (512, 128), (512, 256), (512, 512))  # (block, chunk)


def kernel_ms(trace_dir):
    """{kernel: mean device ms a call} from the one trace under trace_dir."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spent = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                # an instruction is named after its call: %jvp_flash_fwd_.1, %transpose_jvp_flash_bwd_dq__.1
                called = e.name.split(" = ", 1)[0]
                for k in KERNELS:
                    if k in called:
                        spent.setdefault(k, []).append(e.duration_ns / 1e6)
    return {k: sum(v) / len(v) for k, v in spent.items()}


def max_error(step, q, k, v, do, mask, off, scale, window):
    """Largest absolute difference of (o, dq, dk, dv) on the chip against dense
    attention in f32, over the first row's first head (heads are independent,
    so its gradients under the whole loss are its gradients alone)."""
    import jax
    import jax.numpy as jnp

    T = q.shape[1]
    one = lambda x: x[:1, :, :1].astype(jnp.float32)
    qi, ki = jnp.arange(T)[:, None], jnp.arange(T)[None, :] + (0 if off is None else int(off))
    keep = (ki <= qi) & (mask[0] > 0.5)[None, :]
    if window:
        keep = keep & (ki > qi - window)

    def dense(q, k, v):
        s = jnp.where(keep, jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale, -1e9)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision="highest")

    want, vjp = jax.vjp(dense, one(q), one(k), one(v))
    got = step(q, k, v, off)
    pairs = zip((got[0], *got[1]), (want, *vjp(one(do))))
    seen = keep.any(-1)[None, :, None, None]  # a query with no key to see is a meaningless mix on both sides
    return [float(jnp.max(jnp.abs(jnp.where(seen, one(g) - w, 0.0)))) for g, w in pairs]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=None, help="measure the trlx_tpu of this checkout instead")
    ap.add_argument("--sweep", action="store_true", help="a grid of sizes, not only pick_block's")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--sizes", default=None, help="explicit sizes instead: 256x1024x256,512x1024x512")
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--label", default=None)
    args = ap.parse_args()
    if args.repo:
        sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("bench_flash.py: no TPU backend; a kernel's time comes only from the chip", file=sys.stderr)
        return 2
    from trlx_tpu.ops import flash_attention as fa

    label = args.label or (os.path.basename(os.path.abspath(args.repo)) if args.repo else "tree")
    sized = "blocks" in inspect.signature(fa.flash_attention).parameters  # this PR's API; else block_q/block_k
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/bench_flash.jsonl", "a")
    rng = np.random.default_rng(0)
    for name in args.shapes.split(","):
        b, T, h, d, scale, window, padding, offset = SHAPES[name]
        q, k, v, do = (jnp.asarray(rng.standard_normal((b, T, h, d)), jnp.bfloat16) for _ in range(4))
        lengths = rng.integers(T // 2, T + 1, b)
        pos = np.arange(T)[None, :]
        valid = pos >= (T - lengths)[:, None] if padding == "left" else pos < lengths[:, None]
        mask = jnp.asarray(valid, jnp.float32)
        do = do * mask[:, :, None, None].astype(do.dtype)  # as every loss does: nothing flows from a padded query
        off = None if offset is None else jnp.float32(offset)
        if sized:
            picked = tuple(fa.pick_block(*(T, d)[: len(inspect.signature(fa.pick_block).parameters)]))
            points = [picked] + [
                (blk, T, ch) for blk, ch in SWEEP if args.sweep and T % blk == 0 and (blk, T, ch) != picked
            ]
        else:
            picked = (fa.pick_block(T),) * 2
            points = [picked] + [(blk, blk) for blk in (512, 256, 128) if args.sweep and T % blk == 0 and (blk, blk) != picked]
        if args.sizes:
            points = [tuple(int(n) for n in p.split("x")) for p in args.sizes.split(",")]
        for sizes in points:
            kw = {"blocks": sizes} if sized else {"block_q": sizes[0], "block_k": sizes[1]}

            def step(q, k, v, off):
                def call(q, k, v):
                    if off is None:
                        return fa.flash_attention(q, k, v, mask, scale=scale, window=window, **kw)
                    return fa.flash_attention(q, k, v, mask, scale=scale, window=window, offset=off, return_lse=True, **kw)[0]

                o, vjp = jax.vjp(call, q, k, v)
                return o, vjp(do)

            record = {"label": label, "shape": name, "bTHD": [b, T, h, d], "window": window, "sizes": list(sizes),
                      "picked": sizes == picked}
            try:
                fn = jax.jit(step)
                jax.block_until_ready(fn(q, k, v, off))
                with tempfile.TemporaryDirectory() as tmp:
                    with jax.profiler.trace(tmp):
                        for _ in range(args.reps):
                            res = fn(q, k, v, off)
                        jax.block_until_ready(res)
                    ms = kernel_ms(tmp)
                record.update({k: round(ms[k], 4) for k in KERNELS}, total=round(sum(ms[k] for k in KERNELS), 4))
                record["max_error_o_dq_dk_dv"] = [round(e, 5) for e in max_error(fn, q, k, v, do, mask, off, scale, window)]
                if sized and offset is None:
                    record["kept_pair_share"] = round(fa.kept_pair_share(T, fa.FlashBlocks(*sizes), True, window), 4)
            except Exception as e:  # noqa: BLE001 — a size that does not compile is a row of the table
                record["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            line = json.dumps(record)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The control a looped stack invites: the reference run with one loop fewer.

    python3 benchmark/loop_control.py --workload <cell> [--seeds 6] [--first-seed N] [--rehearsal]

benchmark/control.py puts the reference in the program's place one PRECISION
down; this puts it there one LOOP down: the same weights, the same sample
(`harness.logits_sample`: two rows of the cell's sequence length, last 64
positions), the plain float32 reference with `n_loops - 1` loops against the
same reference with all of them. A program that left a loop out (or ran one
twice) would be that far from the reference, so the reading has to fail check
(a)'s rule (`harness.logits_pass`: the cell's multiple of the yardstick,
under its ceiling) on every seed. One process, one chip, no trainer, no
program reading, no timed window; weights drawn per seed with the program's
initializer, as control.py `--reference-only` draws them. Exit 0 when every
seed's control fails the rule, 1 when one passes it, 2 without the chip.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=6)
    p.add_argument("--first-seed", type=int, default=2147483700)
    p.add_argument("--rehearsal", action="store_true", help="CPU, tiny widths")
    args = p.parse_args(argv)
    from benchmark import harness
    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config_spec = manifest.config(cell["config"])
    if args.rehearsal:
        cell = harness.merged(cell, cell.get("rehearsal"))
    harness.place_process(1, args.rehearsal)

    import jax

    device = jax.devices()[0]
    if not args.rehearsal and device.platform != "tpu":
        print(f"loop_control: no TPU (platform {device.platform!r}); --rehearsal is the CPU run", file=sys.stderr)
        return 2
    import jax.numpy as jnp

    from trlx_tpu.models.heads import LMWithValueHead
    from trlx_tpu.models.hf_import import build_lm_config

    harness.setup_cache()
    out_dir = os.path.join(ROOT, "benchmark_out", cell["name"] + ".loop_control")
    config, arch = harness.build_config(cell, config_spec, args.first_seed, out_dir, args.rehearsal)
    loops = int(arch.get("n_loops", 1))
    if loops < 2:
        raise SystemExit(f"{cell['config']} runs its stack once a token: there is no loop to leave out")
    reference = manifest.reference(config_spec["reference"])
    model = LMWithValueHead(build_lm_config(config))
    dummy = jnp.zeros((1, 2), jnp.int32)
    draw = jax.jit(lambda rng: model.init(rng, dummy, jnp.ones_like(dummy))["params"]["transformer"])
    seq, last = int(config.train.seq_length), min(64, int(config.train.seq_length) // 2)

    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        trunk = draw(jax.random.PRNGKey(seed))
        ids, mask = (jnp.asarray(a) for a in harness.logits_sample(arch, seq, seed))
        _, rel, r = harness.reference_distances(reference, trunk, arch, cell, ids, mask, last)
        r.update(seed=seed, loops=[loops - 1, loops],
                 fewer_loops_rel_rms=rel(reference.forward(trunk, arch, ids, mask, last, loops=loops - 1)),
                 limit=min(r["tol_rel_rms"], r["tol_vs_bf16_reference"] * r["bf16_reference_rel_rms"]))
        r["control_passes"] = bool(harness.logits_pass(r, r["fewer_loops_rel_rms"]))
        rows.append(r)
        print("[loop_control] " + json.dumps(r), flush=True)
        del trunk
    verdict = {
        "cell": cell["name"], "seeds": len(rows), "device": [device.platform, device.device_kind], "loops": [loops - 1, loops],
        "yardstick": rows[0]["yardstick"],
        "control_rel_rms_min": min(r["fewer_loops_rel_rms"] for r in rows),
        "control_rel_rms_max": max(r["fewer_loops_rel_rms"] for r in rows),
        "control_vs_yardstick_min": min(r["fewer_loops_rel_rms"] / r["bf16_reference_rel_rms"] for r in rows),
        "limit_min": min(r["limit"] for r in rows), "limit_max": max(r["limit"] for r in rows),
        "every_control_fails": not any(r["control_passes"] for r in rows),
    }
    print("[loop_control] verdict " + json.dumps(verdict), flush=True)
    if args.rehearsal:
        print("platform: cpu (rehearsal: control flow only)", flush=True)
        return 3
    return 0 if verdict["every_control_fails"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""The control of the logits check, and the readings its limits are set from.

    python3 benchmark/control.py --workload <cell> [--seeds 12] [--first-seed N] [--reference-only] [--rehearsal]

One process, no timed window. For each seed it draws the weights anew with
the program's initializer (the model's `init`) and reads, on the cell's own
sample (`harness.logits_sample`: two rows of the cell's sequence length, last
64 positions), distances from the plain float32 reference
(benchmark/references/): the program's (`rel_rms`), the yardstick's (the
reference's own rerun at the precision the cell's limit is a multiple of,
`bf16_reference_rel_rms`) and the controls'. The control is the reference
put in the program's place one precision down: the cell's `bfloat16_stream`
arithmetic with every matmul fed int8 scaled per tensor (`int8`), the only
8-bit matmul a v5e has. `int8_dense`, which leaves attention's two matmuls in
bf16, is read beside it to say what the check cannot see; it sets no limit.
The limits stand only if every seed's program passes them and every seed's
control fails them; the last line says so, with the largest sound reading
and the smallest control reading. PERF.md section 2 quotes them.

Default: the cell's trainer is built once, on the cell's chips and mesh (the
program's own placement), and the program is read too. `--reference-only`
builds no trainer and reads no program: the same weights (the same module,
the same keys; jax.random does not depend on the sharding) and the same
sample on ONE chip, for the reference's side of seeds whose program side a
run on the cell's chips already logged; a model of four chips' size then
fits beside nothing but the reference. Exit 0 when the limits separate (or,
reference only, when every control fails them), 1 when not, 2 without the chips.
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL, BESIDE_IT = "int8", "int8_dense"


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2147483700)
    p.add_argument("--reference-only", action="store_true", help="one chip, no trainer, no program reading")
    p.add_argument("--rehearsal", action="store_true", help="CPU, tiny widths")
    args = p.parse_args(argv)
    from benchmark import harness
    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config_spec = manifest.config(cell["config"])
    if args.rehearsal:
        cell = harness.merged(cell, cell.get("rehearsal"))
    chips = 1 if args.reference_only else cell["chips"]
    harness.place_process(chips, args.rehearsal)

    import jax

    devices = jax.devices()
    if (not args.rehearsal and devices[0].platform != "tpu") or len(devices) != chips:
        print(f"control: wants {chips} TPU chip(s), JAX shows {len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    import jax.numpy as jnp

    harness.setup_cache()
    out_dir = os.path.join(ROOT, "benchmark_out", cell["name"] + ".control")
    config, arch = harness.build_config(cell, config_spec, args.first_seed, out_dir, args.rehearsal)
    reference = manifest.reference(config_spec["reference"])
    controls = (CONTROL, BESIDE_IT)
    dummy = jnp.zeros((1, 2), jnp.int32)
    if args.reference_only:
        from trlx_tpu.models.heads import LMWithValueHead
        from trlx_tpu.models.hf_import import build_lm_config

        # a parameter's key follows from its path, and every trainer's module holds the trunk as `transformer`
        model = LMWithValueHead(build_lm_config(config))
        draw = jax.jit(lambda rng: model.init(rng, dummy, jnp.ones_like(dummy))["params"]["transformer"])
        seq, last = int(config.train.seq_length), min(64, int(config.train.seq_length) // 2)

        def read(trunk, seed):
            ids, mask = (jnp.asarray(a) for a in harness.logits_sample(arch, seq, seed))
            trunk = harness.sample_weights({"transformer": trunk}, arch)["transformer"]
            return harness.reference_distances(reference, trunk, arch, cell, ids, mask, last, controls)[2]
    else:
        from trlx_tpu.trainer.api import get_model

        reward = {"reward_fn": lambda rows: [0.0] * len(rows)} if cell["method"] == "ppo" else {}
        trainer = get_model(config.model.model_type)(config, metric_fn=None, logit_mask=None, **reward)
        # placed as the trainer placed its own state; one program for every seed
        draw = jax.jit(lambda rng: trainer.model.init(rng, dummy, jnp.ones_like(dummy))["params"],
                       out_shardings=trainer.state_shardings.params)

        def read(params, seed):
            shim = types.SimpleNamespace(config=config, model=trainer.model, state=types.SimpleNamespace(params=params))
            return harness.check_logits(shim, reference, arch, cell, seed, controls=controls)

    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        weights = draw(jax.random.PRNGKey(seed))
        r = read(weights, seed)
        r.update(seed=seed, control_passes=bool(harness.logits_pass(r, r["controls"][CONTROL])),
                 limit=min(r["tol_rel_rms"], r["tol_vs_bf16_reference"] * r["bf16_reference_rel_rms"]))
        if "rel_rms" in r:
            r["program_passes"] = bool(harness.logits_pass(r))
        rows.append(r)
        print("[control] " + json.dumps(r), flush=True)
        del weights
    ratio = lambda r, value: value / r["bf16_reference_rel_rms"]
    verdict = {
        "cell": cell["name"], "seeds": len(rows), "device": [devices[0].platform, devices[0].device_kind, len(devices)],
        "yardstick": rows[0]["yardstick"], "control": CONTROL,
        "control_rel_rms_min": min(r["controls"][CONTROL] for r in rows),
        "control_vs_yardstick_min": min(ratio(r, r["controls"][CONTROL]) for r in rows),
        "beside_it": BESIDE_IT, "beside_it_vs_yardstick": [min(ratio(r, r["controls"][BESIDE_IT]) for r in rows),
                                                           max(ratio(r, r["controls"][BESIDE_IT]) for r in rows)],
        "limit_min": min(r["limit"] for r in rows), "limit_max": max(r["limit"] for r in rows),
        "separates": all(r.get("program_passes", True) and not r["control_passes"] for r in rows),
    }
    if not args.reference_only:
        verdict["program_rel_rms_max"] = max(r["rel_rms"] for r in rows)
        verdict["program_vs_yardstick_max"] = max(ratio(r, r["rel_rms"]) for r in rows)
    print("[control] verdict " + json.dumps(verdict), flush=True)
    return 0 if verdict["separates"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Prefill, then decoding through the cache, against the plain reference's
full forward, at the configuration's published widths.

    python3 benchmark/decode_parity.py --workload <cell> [--seed N] [--prompt 128] [--steps 64] [--rehearsal]

Check (a) of a run (`harness.check_logits`) is a full forward: it never
touches the cache or the decode step's read. This script does: one process,
no trainer, no timed window. The configuration's trunk is drawn from the
seed on the device (the program's initializer, the dtypes the configuration
states), `--prompt` tokens are prefilled into `init_cache` (row 1
left-padded by a third), `--steps` tokens are then fed one a step,
teacher-forced, through the same jitted `apply` the generate loop makes
(scalar traced `cache_index`, so the ranged read engages), and every step's
logits are compared with the reference's logits at the same position of ONE
full forward over prompt + steps (float32, `highest`, no cache). Read beside
it: the program's own full forward (the path check (a) reads) and the
reference's coarser rerun at the cell's yardstick, so the decode path's
distance has both its neighbours. The rule is check (a)'s own
(`harness.logits_pass`: the cell's multiple of the yardstick, under its
ceiling): the decode path is held to the limit the train path is held to,
because PPO's ratio compares the two. Exit 0 when prefill and every decode
step pass, 1 when not, 2 without a TPU.
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
    p.add_argument("--seed", type=int, default=2147483777)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--rehearsal", action="store_true", help="CPU, tiny widths")
    args = p.parse_args(argv)
    from benchmark import harness
    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config_spec = manifest.config(cell["config"])
    harness.place_process(1, args.rehearsal)

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    if not args.rehearsal and device.platform != "tpu":
        print(f"decode_parity: no TPU (platform {device.platform!r}); --rehearsal is the CPU run", file=sys.stderr)
        return 2
    from trlx_tpu.models.hf_import import build_lm_config
    from trlx_tpu.models.lm import TransformerLM, init_cache

    harness.setup_cache()
    config, arch = harness.build_config(cell, config_spec, args.seed, os.path.join(ROOT, "benchmark_out", "decode_parity"),
                                        args.rehearsal)
    reference = manifest.reference(config_spec["reference"])
    prompt, steps = (8, 6) if args.rehearsal else (args.prompt, args.steps)
    total, batch = prompt + steps, 2
    cfg = build_lm_config(config)
    model = TransformerLM(cfg)
    dummy = jnp.zeros((1, 2), jnp.int32)
    params = jax.jit(lambda rng: model.init(rng, dummy, jnp.ones_like(dummy))["params"])(jax.random.PRNGKey(args.seed))
    ids, mask = (jnp.asarray(a) for a in harness.logits_sample(arch, total, args.seed))
    pad = int((1 - np.asarray(mask)[1]).sum())
    if pad >= prompt:
        raise SystemExit("the left padding of row 1 would cover the whole prompt")

    cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((batch, steps), jnp.int32)], axis=1)
    prefill = jax.jit(lambda params, ids, mask, cache_mask: model.apply(
        {"params": params}, ids, mask, cache=init_cache(cfg, batch, total), cache_index=0, cache_mask=cache_mask))
    step = jax.jit(lambda params, cache, index, cache_mask, token: model.apply(
        {"params": params}, token, jnp.ones((batch, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask))
    out = prefill(params, ids[:, :prompt], mask[:, :prompt], cache_mask)
    cache, rows = out["cache"], [out["logits"][:, -1].astype(jnp.float32)]
    for i in range(prompt, total):
        cache_mask = cache_mask.at[:, i].set(1)
        out = step(params, cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
        cache = out["cache"]
        rows.append(out["logits"][:, 0].astype(jnp.float32))
    decoded = jnp.stack(rows, axis=1)  # positions prompt - 1 .. total - 1
    last = steps + 1
    full = jax.jit(lambda params, ids, mask: model.apply({"params": params}, ids, mask)["logits"][:, -last:].astype(
        jnp.float32))(params, ids, mask)
    want, rel, result = harness.reference_distances(reference, params, arch, cell, ids, mask, last)
    per_step = [float(jnp.sqrt(jnp.mean((decoded[:, j] - want[:, j]) ** 2) / jnp.mean(want[:, j] ** 2))) for j in range(last)]
    result.update(
        cell=cell["name"], seed=args.seed, device=[device.platform, device.device_kind], prompt=prompt, steps=steps,
        left_pad_row_1=pad, cache_leaves=[list(leaf.shape) for leaf in jax.tree_util.tree_leaves(cache)[:2]],
        prefill_last_rel_rms=per_step[0], decode_rel_rms=rel(decoded), decode_step_rel_rms_max=max(per_step[1:]),
        decode_vs_own_full_forward_rel_rms=float(jnp.sqrt(jnp.mean((decoded - full) ** 2) / jnp.mean(full**2))),
        full_forward_rel_rms=rel(full), finite=bool(jnp.isfinite(decoded).all()),
    )
    result["limit"] = min(result["tol_rel_rms"], result["tol_vs_bf16_reference"] * result["bf16_reference_rel_rms"])
    result["passes"] = bool(result["finite"] and harness.logits_pass(result, result["decode_rel_rms"])
                            and harness.logits_pass(result, result["prefill_last_rel_rms"]))
    print("[decode_parity] " + json.dumps(result), flush=True)
    if args.rehearsal:
        print("platform: cpu (rehearsal: control flow only)", flush=True)
        return 3
    return 0 if result["passes"] else 1


if __name__ == "__main__":
    sys.exit(main())

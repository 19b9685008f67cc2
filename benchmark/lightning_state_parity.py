"""The state check of a lightning (constant-decay linear attention) layer: the
state a rollout's decode steps leave, against the plain reference's
recurrence, at the configuration's published widths. The sibling of
`state_parity.py` ("mamba") and `kda_state_parity.py` ("kda"), which name
their layers' subtrees and are not this PR's to edit.

    python3 benchmark/lightning_state_parity.py --workload <cell> [--seed N] [--rehearsal]

Check (a) of a run (`harness.check_logits`) is the chunked forward; its
`mean_ratio` check reads an importance ratio whose expectation is 1 whatever
the decode path computes. Neither holds a lightning layer's state to the
float32 its configuration states: the bf16 stream's own rounding hides a bf16
state in the logits (PERF.md section 7, PR 32). This script reads the state
itself, where nothing hides it: one process, no trainer, no timed window. The
configuration's trunk is drawn from the seed on the device (the program's
initializer), the cell's longest prompt is prefilled into `init_cache` (two
rows, row 1 left-padded by a third of the prompt and seven: a length that
neither the stride nor the block of the sparse layer divides), the cell's
`new_tokens` tokens are then fed one a step, teacher-forced, through the same
jitted `apply` the generate loop makes, and after the last step

  * the FIRST lightning layer's state leaf is compared with the reference's
    (`layer_state`: float32, `highest`, the recurrence token by token on the
    unpadded row): their relative RMS distance over the leaf, `state_rel_rms`,
    held to the cell's `tolerances.decode_state_rel_rms`. The decay is a
    constant a head: head 32 forgets a token in some 256 steps (lambda =
    exp(-2^-8)), head 1 in two; a float32 state averages its inputs' bf16
    roundings over the tokens it remembers, a state rounded to bf16 after every
    update walks away by a rounding a token, the further the slower the head;
  * the decode path's logits at the final 64 positions (through the three
    states AND the sparse layer's gathered blocks) are compared with the
    reference's full forward, under check (a)'s own rule
    (`harness.logits_pass`), as `decode_parity.py` reads a shorter span.

Read beside them, on the same weights and tokens: the reference's own state
under `bfloat16_stream` (the cell's yardstick) and under `bfloat16_state` (the
control: the state rounded to bf16 after every token). The limit stands if the
program passes and the control fails; the last line says so. Exit 0 then, 1
when not (a narrowed state among the causes), 2 without a TPU.
`harness.verdict` does not call this: wiring a state check in as a condition
of `correct` is an edit to `harness.py` and `run.py`, a `benchmark` PR's
(ROADMAP.md A0, PERF.md section 7).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL = "bfloat16_state"


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2147483777)
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
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    if not args.rehearsal and device.platform != "tpu":
        print(f"lightning_state_parity: no TPU (platform {device.platform!r}); --rehearsal is the CPU run", file=sys.stderr)
        return 2
    from trlx_tpu.models.hf_import import build_lm_config
    from trlx_tpu.models.lm import TransformerLM, init_cache

    harness.setup_cache()
    config, arch = harness.build_config(cell, config_spec, args.seed, os.path.join(ROOT, "benchmark_out", "lightning_state_parity"),
                                        args.rehearsal)
    reference = manifest.reference(config_spec["reference"])
    tp = cell["traffic_params"]
    prompt, steps, batch = int(tp["prompt_length"]["max"]), int(tp["new_tokens"]), 2
    total, last = prompt + steps, min(64, steps)
    cfg = build_lm_config(config)
    layer = list(cfg.mixer_layers).index("lightning")
    model = TransformerLM(cfg)
    dummy = jnp.zeros((1, 2), jnp.int32)
    params = jax.jit(lambda rng: model.init(rng, dummy, jnp.ones_like(dummy))["params"])(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng([args.seed, 5])
    ids = rng.integers(2, arch["vocab_size"], size=(batch, total)).astype(np.int32)
    mask, pad = np.ones((batch, total), np.int32), prompt // 3 + 7
    mask[1, :pad], ids[1, :pad] = 0, 0
    first = (0, pad)  # each row's first real token
    ids, mask = jnp.asarray(ids), jnp.asarray(mask)

    cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((batch, steps), jnp.int32)], axis=1)
    prefill = jax.jit(lambda params, ids, mask, cache_mask: model.apply(
        {"params": params}, ids, mask, cache=init_cache(cfg, batch, total), cache_index=0, cache_mask=cache_mask))
    step = jax.jit(lambda params, cache, index, cache_mask, token: model.apply(
        {"params": params}, token, jnp.ones((batch, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask))
    out = prefill(params, ids[:, :prompt], mask[:, :prompt], cache_mask)
    cache, rows = out["cache"], []
    for i in range(prompt, total):
        cache_mask = cache_mask.at[:, i].set(1)
        out = step(params, cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
        cache = out["cache"]
        if i >= total - last:
            rows.append(out["logits"][:, 0].astype(jnp.float32))
    decoded = jnp.stack(rows, axis=1)  # the final `last` positions, each read from a decode step
    leaf = cache[layer][0]
    got = np.asarray(leaf.astype(jnp.float32))

    state = lambda precision: np.stack([np.asarray(reference.layer_state(
        params, arch, ids[r, first[r]:], layer, precision=precision)) for r in range(batch)])
    want = state("highest")
    rel_state = lambda x: float(np.sqrt(((x - want) ** 2).sum() / (want**2).sum()))
    of_reference = {name: rel_state(state(name)) for name in ("bfloat16_stream", CONTROL)}
    _, rel, result = harness.reference_distances(reference, params, arch, cell, ids, mask, last)
    limit = cell["tolerances"]["decode_state_rel_rms"]
    result.update(
        cell=cell["name"], seed=args.seed, device=[device.platform, device.device_kind], prompt=prompt, steps=steps,
        left_pad_row_1=pad, layer=layer, state_leaf=[list(leaf.shape), str(leaf.dtype)],
        state_rel_rms=rel_state(got), reference_state_rel_rms=of_reference, tol_decode_state_rel_rms=limit,
        decode_rel_rms=rel(decoded), finite=bool(jnp.isfinite(decoded).all() and np.isfinite(got).all()),
    )
    result["logits_limit"] = min(result["tol_rel_rms"], result["tol_vs_bf16_reference"] * result["bf16_reference_rel_rms"])
    result["passes"] = bool(result["finite"] and result["state_rel_rms"] <= limit
                            and harness.logits_pass(result, result["decode_rel_rms"]))
    result["control_passes"] = bool(of_reference[CONTROL] <= limit)
    print("[lightning_state_parity] " + json.dumps(result), flush=True)
    if args.rehearsal:
        print("platform: cpu (rehearsal: control flow only)", flush=True)
        return 3
    return 0 if result["passes"] and not result["control_passes"] else 1


if __name__ == "__main__":
    sys.exit(main())

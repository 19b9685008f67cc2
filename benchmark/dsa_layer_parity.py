"""The layer check of an indexed latent-attention layer (`index_topk`,
trlx_tpu/models/indexer.py): ONE layer's output, chosen keys and read count,
through the pass with no cache, a prefill and decode steps at the
configuration's published widths, against the plain reference's
`indexed_layer`.

    python3 benchmark/dsa_layer_parity.py --workload <cell> [--seed N] [--rehearsal]

Why a layer alone: on seeded weights one attention layer's output is a small
part of the residual stream, so a run's logits check, its `mean_ratio` and
`decode_parity.py` see little of which keys it read (PERF.md section 6, PR 51's
finding, again in PR 53). Here nothing stands in front of it. One process, no
trainer, no timed window. The layer's weights are drawn from the seed on the
device by the program's initializer; its input is two seeded rows of
unit-variance tokens in the model's dtype (what the block's norm hands it),
row 1 left-padded by a third of the prompt and seven (128 does not divide it).
The program runs, as the PPO path does:

  * the pass with no cache over the whole rows (the train step's and scoring's);
  * the prefill of the cell's longest prompt into the layer's three cache leaves;
  * decode steps, teacher-forced, one token a step for both rows, the cell's
    `new_tokens`: every one past index_topk filled slots, so every step chooses.

Read, each against the reference in float32 at `highest` on the same weights
and inputs, each row unpadded and alone:

  * `out_rel_rms`: relative RMS distance of the layer's output, for the pass
    with no cache, the prefill, and the decode steps;
  * `choice_differ_share`: of the keys the decode steps chose (`indexer.choose_slots`
    over what the layer's indexer gave the token and the cache the step left:
    the step's own choice, made again), the share that are not among the
    reference's for that query (beside it the share of steps whose set differs
    in any key: nearly all of them at 2,048 keys a query, where a few scores
    always nearly tie); the no-cache pass's own sum (`kept_pair_share`)
    beside the reference's sets';
  * `keys_read_share`: the steps' own count of the entries they gathered over
    the slots filled (the rollout's `rollout/dsa_keys_read_share`), beside the
    rule's (`counts/dsa_mla_moe.py`: min(t + 1, index_topk) / (t + 1)).

Beside them the reference's own rerun under the cell's yardstick
(`bfloat16_stream`): how far rounding alone moves the output and flips a
choice (two index scores nearly tie often on seeded weights). Limits: the
cell's `tolerances.dsa_layer` (`out_rel_rms`, `choice_differ_share`,
`keys_read_share_abs`). Exit 0 when every reading is inside, 1 when not, 2
without a TPU, 3 in a rehearsal. `harness.verdict` does not call this: its
conditions are five, fixed in `harness.py`; a sixth is a `benchmark` PR's
(ROADMAP.md A0, PERF.md section 7).
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
        print(f"dsa_layer_parity: no TPU (platform {device.platform!r}); --rehearsal is the CPU run", file=sys.stderr)
        return 2
    from trlx_tpu.models import indexer
    from trlx_tpu.models.hf_import import build_lm_config
    from trlx_tpu.models.lm import LatentAttention, init_cache, make_attn_bias, rope_tables

    harness.setup_cache()
    config, arch = harness.build_config(cell, config_spec, args.seed, os.path.join(ROOT, "benchmark_out", "dsa_layer_parity"),
                                        args.rehearsal)
    reference, counts = manifest.reference(config_spec["reference"]), manifest.counts(config_spec["flops"])
    tp = cell["traffic_params"]
    prompt, steps, batch = int(tp["prompt_length"]["max"]), int(tp["new_tokens"]), 2
    total = prompt + steps
    cfg = build_lm_config(config)
    layer = LatentAttention(cfg)
    rng = np.random.default_rng([args.seed, 7])
    x = jnp.asarray(rng.standard_normal((batch, total, cfg.d_model), dtype=np.float32)).astype(cfg.compute_dtype)
    pad = prompt // 3 + 7
    first = np.array([0, pad])  # each row's first real token
    mask = np.ones((batch, total), np.int32)
    mask[1, :pad] = 0
    mask = jnp.asarray(mask)
    x = x * mask[..., None].astype(x.dtype)
    positions = jnp.maximum(jnp.cumsum(mask, axis=-1) - 1, 0)
    rope = lambda lo, n: rope_tables(cfg, jax.lax.dynamic_slice_in_dim(positions, lo, n, axis=1))
    params = jax.jit(lambda key: layer.init(key, x[:, :8], make_attn_bias(mask[:, :8], 8, 0), rope(0, 8))["params"])(jax.random.PRNGKey(args.seed))
    run = lambda *a, **k: layer.apply({"params": params}, *a, **k)

    # the program: the pass with no cache, the prefill, the decode steps
    whole, _, sums = jax.jit(lambda x, mask: run(x, None, rope(0, total), token_mask=mask))(x, mask)
    (cache,) = init_cache(cfg.replace(n_layer=1, ffn_layers=("dense",)), batch, total)
    filled, cache, _ = jax.jit(lambda x, cache, mask: run(x, None, rope(0, prompt), cache, 0, token_mask=mask))(
        x[:, :prompt], cache, mask[:, :prompt])

    def step(carry, i):
        cache, occupancy = carry
        occupancy = jax.lax.dynamic_update_slice(occupancy, jnp.ones((batch, 1), jnp.int32), (0, i))
        token = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=1)
        (out, cache, (share, count)), seen = run(token, None, rope(i, 1), cache, i, token_mask=occupancy, mutable=["intermediates"],
                                                 capture_intermediates=lambda module, _: isinstance(module, indexer.Indexer))
        (q_idx, _, w), = seen["intermediates"]["indexer"]["__call__"]  # what the layer's indexer gave this token
        slots, taken = indexer.choose_slots(q_idx, w, cache[2], occupancy, cfg.index_topk)  # the step's own choice, made again
        return (cache, occupancy), (out[:, 0], slots, taken, share / count)

    occupancy = jnp.concatenate([mask[:, :prompt], jnp.zeros((batch, steps), jnp.int32)], axis=1)
    (cache, _), (decoded, slots, taken, read) = jax.jit(lambda cache, occupancy: jax.lax.scan(
        step, (cache, occupancy), jnp.arange(prompt, total, dtype=jnp.int32)))(cache, occupancy)
    decoded, slots, taken = np.asarray(decoded.astype(jnp.float32)), np.asarray(slots), np.asarray(taken)  # [steps, b, ...]
    whole, filled = np.asarray(whole.astype(jnp.float32)), np.asarray(filled.astype(jnp.float32))

    # the reference: each row unpadded and alone, float32 at highest, and its own rerun under the cell's yardstick
    yardstick = cell["tolerances"].get("logits_yardstick", "bfloat16")
    row = lambda r, **k: np.asarray(reference.indexed_layer(params, arch, x[r, first[r]:], **k))
    want = [row(r) for r in range(batch)]
    want_sets = [row(r, choices=True) for r in range(batch)]  # [t_r, t_r]
    rerun = [row(r, precision=yardstick) for r in range(batch)]
    rerun_sets = [row(r, precision=yardstick, choices=True) for r in range(batch)]

    def rel(got, lo, hi):
        """Relative RMS distance over slots [lo, hi) of both rows; `got(r)` a row's outputs by its own positions."""
        num = den = 0.0
        for r in range(batch):
            a, b = max(lo - first[r], 0), hi - first[r]
            num, den = num + float(((got(r)[a:b] - want[r][a:b]) ** 2).sum()), den + float((want[r][a:b] ** 2).sum())
        return (num / den) ** 0.5

    spans = {"no_cache": (0, total), "prefill": (0, prompt), "decode": (prompt, total)}
    by_pass = {"no_cache": lambda r: whole[r, first[r]:], "prefill": lambda r: filled[r, first[r]:]}
    from_steps = lambda r: np.concatenate([np.zeros((prompt - first[r], cfg.d_model), np.float32), decoded[:, r]])
    out_rel = {name: rel(by_pass.get(name, from_steps), lo, hi) for name, (lo, hi) in spans.items()}
    rerun_rel = {name: rel(lambda r: rerun[r], lo, hi) for name, (lo, hi) in spans.items()}

    def program_sets(r):
        """bool [steps, t_r]: the keys row r's decode steps gathered, by the row's own positions."""
        sets = np.zeros((steps, total - first[r]), bool)
        at = np.broadcast_to(np.arange(steps)[:, None], slots[:, r].shape)
        keep = taken[:, r] & (slots[:, r] >= first[r])
        sets[at[keep], slots[:, r][keep] - first[r]] = True
        return sets

    def differ(sets_of):
        """(of the keys the decode steps chose, the share that are not the reference's; the share of (step, row)
        choices that differ in any key) over the decode steps of both rows."""
        wrong = swapped = chosen = n = 0
        for r in range(batch):
            ref = want_sets[r][prompt - first[r]:]
            off = (sets_of(r) != ref).sum(-1)
            wrong, swapped, chosen, n = wrong + int((off > 0).sum()), swapped + off.sum() / 2.0, chosen + int(ref.sum()), n + off.size
        return float(swapped / chosen), wrong / n

    choice = dict(zip(("program", "program_steps_that_differ", "yardstick", "yardstick_steps_that_differ"),
                      differ(program_sets) + differ(lambda r: rerun_sets[r][prompt - first[r]:])))
    kept, causal = (float(s) for s in sums)
    ref_kept = sum(float(s.sum()) for s in want_sets)
    ref_causal = sum(float(s.shape[0] * (s.shape[0] + 1) // 2) for s in want_sets)
    at = np.arange(prompt, total)[:, None] - first[None, :]  # [steps, b]
    by_rule = float(np.mean(np.minimum(at + 1, cfg.index_topk) / (at + 1.0)))
    assert counts.chosen_pairs(arch, total) == sum(min(t + 1, cfg.index_topk) for t in range(total))
    limits = cell["tolerances"]["dsa_layer"]
    result = dict(
        cell=cell["name"], seed=args.seed, device=[device.platform, device.device_kind], prompt=prompt, steps=steps,
        left_pad_row_1=pad, yardstick=yardstick, out_rel_rms=out_rel, yardstick_out_rel_rms=rerun_rel,
        choice_differ_share=choice,
        no_cache_pass={"kept_pair_share": kept / causal, "reference_kept_pair_share": ref_kept / ref_causal},
        keys_read_share=float(jnp.mean(read)), keys_read_share_by_rule=by_rule,
        finite=bool(np.isfinite(decoded).all() and np.isfinite(whole).all()), limits=limits,
    )
    result["passes"] = bool(
        result["finite"] and max(out_rel.values()) <= limits["out_rel_rms"]
        and choice["program"] <= limits["choice_differ_share"]
        and abs(result["keys_read_share"] - by_rule) <= limits["keys_read_share_abs"])
    print("[dsa_layer_parity] " + json.dumps(result), flush=True)
    if args.rehearsal:
        print("platform: cpu (rehearsal: control flow only)", flush=True)
        return 3
    return 0 if result["passes"] else 1


if __name__ == "__main__":
    sys.exit(main())

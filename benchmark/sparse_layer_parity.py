"""The layer check of block-selected sparse attention (attention "sparse"):
ONE sparse layer's output, chosen sets, compressed keys and read count, through
a prefill and decode steps at the configuration's published widths, against
the plain reference's `sparse_layer`.

    python3 benchmark/sparse_layer_parity.py --workload <cell> [--seed N] [--rehearsal]

Why a layer alone: on seeded weights the sparse layer's output is a
five-hundredth of the residual stream, so a run's logits check, its
`mean_ratio` and `decode_parity.py` compare the other layers and pass whatever
this one reads (PERF.md section 6, PR 51). Here nothing stands in front of it.
One process, no trainer, no timed window. The layer's weights are drawn from
the seed on the device by the program's initializer; its input is two seeded
rows of unit-variance tokens in the model's dtype (what the block's norm hands
it), row 1 left-padded by a third of the prompt and seven (neither the stride
nor the block divides it). The program runs, as the PPO path does:

  * the pass with no cache over the whole rows (the train step's and scoring's);
  * the prefill of the cell's longest prompt into `init_cache`'s three leaves;
  * decode steps, teacher-forced, one token a step for both rows, TWICE the
    cell's `new_tokens`: the cell's own span never leaves the window of
    `sparse_window` tokens, so there a compressed key completed after the
    prefill ranks no block; the second span is where it does.

Read, each against the reference in float32 at `highest` on the same weights
and inputs, each row unpadded and alone:

  * `out_rel_rms`: relative RMS distance of the layer's output, for the pass
    with no cache, the prefill, and the decode steps of each span;
  * `choice_differ_share`: of the decode steps' (row, K/V head) choices (the
    step's own, sown by the module), the share whose chosen set is not the
    reference's, with the blocks swapped in the mean; the no-cache pass's own
    sums (`kept_pair_share`, `chosen_blocks_mean`) beside the reference's;
  * `compressed_rel_rms`: the cache's compressed-key leaf after the last step
    against the means of the cache's own keys (numpy), for the entries the
    prefill completed and for those the decode steps completed;
  * `keys_read_share`: the steps' own count of the slots their softmax saw over
    the slots filled (the rollout's `rollout/sparse_keys_read_share`), beside
    the rule's (`counts/sala.py chosen_pairs`).

Beside them the reference's own rerun under the cell's yardstick
(`bfloat16_stream`): how far rounding alone moves the output and flips a
choice (two block scores nearly tie often on seeded weights). Limits: the
cell's `tolerances.sparse_layer` (`out_rel_rms`, `choice_differ_share`,
`compressed_rel_rms`, `keys_read_share_abs`). Exit 0 when every reading is
inside, 1 when not, 2 without a TPU, 3 in a rehearsal. `harness.verdict`
does not call this: its conditions are five, fixed in `harness.py`; a sixth is
a `benchmark` PR's (ROADMAP.md A0, PERF.md section 7).
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
        print(f"sparse_layer_parity: no TPU (platform {device.platform!r}); --rehearsal is the CPU run", file=sys.stderr)
        return 2
    from trlx_tpu.models import sparse
    from trlx_tpu.models.hf_import import build_lm_config

    harness.setup_cache()
    config, arch = harness.build_config(cell, config_spec, args.seed, os.path.join(ROOT, "benchmark_out", "sparse_layer_parity"),
                                        args.rehearsal)
    reference, counts = manifest.reference(config_spec["reference"]), manifest.counts(config_spec["flops"])
    tp = cell["traffic_params"]
    prompt, span, batch = int(tp["prompt_length"]["max"]), int(tp["new_tokens"]), 2
    steps, total = 2 * span, prompt + 2 * span
    cfg = build_lm_config(config)
    block, G = cfg.sparse_block, cfg.kv_heads
    layer = sparse.SparseAttention(cfg)
    rng = np.random.default_rng([args.seed, 7])
    x = jnp.asarray(rng.standard_normal((batch, total, cfg.d_model), dtype=np.float32)).astype(cfg.compute_dtype)
    pad = prompt // 3 + 7
    first = np.array([0, pad])  # each row's first real token
    mask = np.ones((batch, total), np.int32)
    mask[1, :pad] = 0
    mask = jnp.asarray(mask)
    x = x * mask[..., None].astype(x.dtype)
    params = jax.jit(lambda key: layer.init(key, x[:, :8], token_mask=mask[:, :8])["params"])(jax.random.PRNGKey(args.seed))
    run = lambda *a, **k: layer.apply({"params": params}, *a, **k)

    # the program: the pass with no cache, the prefill, the decode steps
    whole, _, sums = jax.jit(lambda x, mask: run(x, token_mask=mask))(x, mask)
    cache = tuple(jnp.zeros(shape, dtype) for shape, dtype in sparse.cache_shapes(cfg, batch, total))
    filled, cache, _ = jax.jit(lambda x, cache, mask: run(x, cache, 0, mask))(x[:, :prompt], cache, mask[:, :prompt])

    def step(carry, i):
        cache, occupancy = carry
        occupancy = jax.lax.dynamic_update_slice(occupancy, jnp.ones((batch, 1), jnp.int32), (0, i))
        token = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=1)
        (out, cache, (share, count)), sown = run(token, cache, i, occupancy, mutable=["intermediates"])
        return (cache, occupancy), (out[:, 0], sown["intermediates"]["chosen"][0], share / count)

    occupancy = jnp.concatenate([mask[:, :prompt], jnp.zeros((batch, steps), jnp.int32)], axis=1)
    (cache, _), (decoded, chose, read) = jax.jit(lambda cache, occupancy: jax.lax.scan(
        step, (cache, occupancy), jnp.arange(prompt, total, dtype=jnp.int32)))(cache, occupancy)
    decoded, chose = np.asarray(decoded.astype(jnp.float32)), np.asarray(chose)  # [steps, b, d], [steps, b, G, n_blocks]
    whole, filled = np.asarray(whole.astype(jnp.float32)), np.asarray(filled.astype(jnp.float32))

    # the reference: each row unpadded and alone, float32 at highest, and its own rerun under the cell's yardstick
    yardstick = cell["tolerances"].get("logits_yardstick", "bfloat16")
    row = lambda r, **k: np.asarray(reference.sparse_layer(params, arch, x[r, first[r]:], **k))
    want = [row(r) for r in range(batch)]
    want_sets = [row(r, choices=True) for r in range(batch)]  # [t_r, G, blocks_r]
    rerun = [row(r, precision=yardstick) for r in range(batch)]
    rerun_sets = [row(r, precision=yardstick, choices=True) for r in range(batch)]

    def rel(got, lo, hi):
        """Relative RMS distance over slots [lo, hi) of both rows; `got(r)` a row's outputs by its own positions."""
        num = den = 0.0
        for r in range(batch):
            a, b = max(lo - first[r], 0), hi - first[r]
            num, den = num + float(((got(r)[a:b] - want[r][a:b]) ** 2).sum()), den + float((want[r][a:b] ** 2).sum())
        return (num / den) ** 0.5

    spans = {"no_cache": (0, total), "prefill": (0, prompt), "decode_cell_span": (prompt, prompt + span),
             "decode_past_the_window": (prompt + span, total)}
    by_pass = {"no_cache": lambda r: whole[r, first[r]:], "prefill": lambda r: filled[r, first[r]:]}
    from_steps = lambda r: np.concatenate([np.zeros((prompt - first[r], cfg.d_model), np.float32), decoded[:, r]])
    out_rel = {name: rel(by_pass.get(name, from_steps), lo, hi) for name, (lo, hi) in spans.items()}
    rerun_rel = {name: rel(lambda r: rerun[r], lo, hi) for name, (lo, hi) in spans.items()}

    def differ(sets_of, lo, hi):
        """(share of (step, row, K/V head) choices that are not the reference's, blocks swapped in the mean) over
        the decode steps at slots [lo, hi); `sets_of(r, t)` the sets of row r's queries at its positions t."""
        wrong = swapped = n = 0
        for r in range(batch):
            t = np.arange(lo, hi) - first[r]
            got, ref = sets_of(r, t), want_sets[r][t]
            off = (got[..., :ref.shape[-1]] != ref).sum(-1) + got[..., ref.shape[-1]:].sum(-1)  # [steps, G]
            wrong, swapped, n = wrong + int((off > 0).sum()), swapped + off.sum() / 2.0, n + off.size
        return wrong / n, float(swapped / n)

    program_sets = lambda r, t: chose[t + first[r] - prompt, r]
    yard_sets = lambda r, t: rerun_sets[r][t]
    choice = {name: dict(zip(("program", "program_blocks_swapped", "yardstick", "yardstick_blocks_swapped"),
                             differ(program_sets, *spans[name]) + differ(yard_sets, *spans[name])))
              for name in ("decode_cell_span", "decode_past_the_window")}

    # the no-cache pass's own sums beside the reference's sets
    kept, causal, blocks_sum, groups = (float(s) for s in sums)
    ref_kept = ref_causal = ref_blocks = ref_groups = 0.0
    for r in range(batch):
        t = np.arange(want_sets[r].shape[0])
        n = want_sets[r].sum(-1)  # [t_r, G]
        ref_kept += float((n * block - (block - 1 - t % block)[:, None]).sum())
        ref_causal, ref_blocks, ref_groups = ref_causal + G * float((t + 1).sum()), ref_blocks + float(n.sum()), ref_groups + n.size

    # the compressed-key leaf against the means of the cache's own keys
    keys, leaf = np.asarray(cache[0].astype(jnp.float32)), np.asarray(cache[2].astype(jnp.float32))
    compressed = {}
    for name, (lo, hi) in (("completed_by_the_prefill", (0, prompt)), ("completed_by_decode_steps", (prompt, total))):
        num = den = 0.0
        for r in range(batch):
            own = keys[r, first[r]:]
            j = np.arange(sparse.compressed_slots(cfg, own.shape[0]))
            last = cfg.sparse_stride * j + cfg.sparse_kernel - 1 + first[r]  # the slot whose token completes entry j
            j = j[(last >= lo) & (last < hi)]
            means = np.mean([own[cfg.sparse_stride * j + i] for i in range(cfg.sparse_kernel)], axis=0)
            num, den = num + float(((leaf[r, j] - means) ** 2).sum()), den + float((means**2).sum())
        compressed[name] = (num / den) ** 0.5

    at = np.arange(prompt, total)[:, None] - first[None, :]  # [steps, b]
    by_rule = float(np.mean([[counts.chosen_pairs(arch, int(t)) / (t + 1.0) for t in row_t] for row_t in at]))
    limits = cell["tolerances"]["sparse_layer"]
    result = dict(
        cell=cell["name"], seed=args.seed, device=[device.platform, device.device_kind], prompt=prompt, steps=steps,
        left_pad_row_1=pad, yardstick=yardstick, out_rel_rms=out_rel, yardstick_out_rel_rms=rerun_rel,
        choice_differ_share=choice,
        no_cache_pass={"kept_pair_share": kept / causal, "chosen_blocks_mean": blocks_sum / groups,
                       "reference_kept_pair_share": ref_kept / ref_causal, "reference_chosen_blocks_mean": ref_blocks / ref_groups},
        compressed_rel_rms=compressed, keys_read_share=float(jnp.mean(read)), keys_read_share_by_rule=by_rule,
        finite=bool(np.isfinite(decoded).all() and np.isfinite(whole).all()), limits=limits,
    )
    result["passes"] = bool(
        result["finite"] and max(out_rel.values()) <= limits["out_rel_rms"]
        and max(c["program"] for c in choice.values()) <= limits["choice_differ_share"]
        and max(compressed.values()) <= limits["compressed_rel_rms"]
        and abs(result["keys_read_share"] - by_rule) <= limits["keys_read_share_abs"])
    print("[sparse_layer_parity] " + json.dumps(result), flush=True)
    if args.rehearsal:
        print("platform: cpu (rehearsal: control flow only)", flush=True)
        return 3
    return 0 if result["passes"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""A decode step against the memory system's floor: the bytes one step must
move (the configuration's count, `decode_step_bytes(arch, rows, keys)`: the
weights once, a state-space layer's state read and written, the keys the
ranged read takes) over the chip's peak bytes/s, over the generate program's
seconds a decode step. The seconds are the program's own: the phase records'
spec["seconds_key"] (the generate-blocked wall of an iteration, spans
`rollout/generate`) over spec["steps_key"] (the decode steps it ran), medians
over the window's iterations; so a cell whose traced run holds no rollout
(`traced_cycle: train_steps`) reports it too. The prefill is inside those
seconds (one pass over the prompts before 896 steps: 1% here, so the share
reads that much low). `rows` is the rollout chunk; `keys` the cache slots a
step reads a row in an attention layer, the mean over the rollout: the
program's `rollout/kv_read_share` of the cell's sequence length. Nothing on a
run without the chip's peaks (a rehearsal), where the program logs neither key
or where the configuration's count has no `decode_step_bytes`."""

import statistics


def read(ctx, spec):
    f, peaks, s, phases = ctx["flops"], ctx["peaks"], ctx["shapes"], ctx["window"]["phases"]
    median = lambda key: statistics.median([p[key] for p in phases if p.get(key)] or [0])
    seconds, steps = median(spec["seconds_key"]), median(spec["steps_key"])
    if not seconds or not steps or not peaks or not hasattr(f, "decode_step_bytes"):
        return None
    keys = (median("rollout/kv_read_share") or 1.0) * s["seq"]
    needed, _ = f.decode_step_bytes(ctx["arch"], ctx["cell"]["recipe"]["method"]["chunk_size"], keys)
    return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / (seconds / steps)

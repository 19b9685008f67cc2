"""One run of one cell through the program's normal path.

A PPO cell makes the calls `trlx_tpu/trainer/api.py:train` makes and no
others (trainer class from `get_model`, `PromptPipeline`, the orchestrator
from `get_orchestrator`, `make_experience`, `add_eval_pipeline`, `learn()`);
an ILQL cell likewise. The harness owns the inputs (benchmark/traffic.py),
the clock, the profiler and the checks; it ends `learn()` from outside by
lowering `trainer.total_steps`, the one stop the program offers.
"""

import contextlib
import json
import math
import os
import shutil
import statistics
import threading
import time

import numpy as np

from benchmark import traffic as traffic_gen

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ILQL_WARMUP_STEPS = 8  # the train step, the Polyak sync and a steady queue
ILQL_TRACED_STEPS = 30
# PPO: iterations kept out of the window. The first holds the compiles and cache
# reads; the second still pays 0.15-0.4 s once on the host (its rollout is the
# first made from inside learn(); PERF.md section 6, PR 22), which a busier
# host stretches, so it is set-up too.
PPO_WARMUP_ITERATIONS = 2
PPO_TRACE_FROM_CALL = 4  # trace reward call 4 -> 5: one whole steady cycle
# What a traced PPO run traces is the cell file's `traced_cycle`. "whole" (a cell
# that names none): reward call 4 to 5. "train_steps": the last train steps of
# iteration 3, eight at most, and `learn()` ends with them. The second is for a
# cell whose decode loop alone leaves millions of events: on four chips at 28
# layers the profiler took 91 s to hand a whole cycle over (some 35 us an event)
# and the run passed the 360 s a run may take (PERF.md section 6, PR 25).
PPO_TRACED_TRAIN_STEPS = 8


class BenchFailure(RuntimeError):
    pass


MARKS = {}  # name -> wall time; run.py prints them as seconds since process start


def mark(name):
    MARKS[name] = time.time()


def place_process(chips, rehearsal):
    """Decide, before JAX starts, which devices this process may see (copied
    from chip_smoke.py): `make_mesh` takes every visible device, so a one-chip
    cell on a four-chip host hides the other three from itself."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs under /tmp otherwise
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={chips}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    elif chips == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def setup_cache():
    """Where the program's own rule puts the persistent compile cache
    (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`), with no cap on
    its size. Under a cap JAX evicts the least recently used entries, and the
    programs of the four-chip 28-layer cell are larger than the 192 MiB the
    chip tool's machine sets (`JAX_COMPILATION_CACHE_MAX_SIZE`): each run
    evicted what the one before had written, no run ever hit, and every run
    compiled for 300 s (PERF.md section 6, PR 25). Only a cell's first run in a
    checkout may compile."""
    import jax

    from trlx_tpu.utils.compile_cache import setup_compile_cache

    directory = setup_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    return directory


class CompileLog:
    """Every backend compile request of the process (jax.monitoring): wall
    time it ended, program name, seconds; plus persistent-cache hits. Copied
    from chip_smoke.py."""

    def __init__(self):
        self.events = []
        self.cache_hits = []

    def install(self):
        import jax

        def on_duration(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.events.append((time.time(), kw.get("fun_name", "?"), float(duration)))

        def on_event(event, **kw):
            if event == CACHE_HIT_EVENT:
                self.cache_hits.append(time.time())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def between(self, t0, t1):
        return [(name, round(d, 3)) for t, name, d in self.events if t0 < t <= t1]

    def summary(self, until):
        before = [e for e in self.events if e[0] <= until]
        return {
            "seconds": sum(e[2] for e in before),
            "requests": len(before),
            "cache_hits": sum(1 for t in self.cache_hits if t <= until),
        }


@contextlib.contextmanager
def record_pallas_calls(record):
    """Note, at trace time, every Pallas kernel the real programs contain:
    {"<ops module>.<kernel function>": {largest operand shape}}. Copied from
    chip_smoke.py. Tracing happens on warm-cache runs too, so this sees the
    routes whether or not anything compiles."""
    from jax.experimental import pallas as pl

    original = pl.pallas_call

    def recording(kernel, *a, **kw):
        inner = original(kernel, *a, **kw)
        fn = getattr(kernel, "func", kernel)
        key = f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__name__}"

        def call(*operands):
            shapes = tuple(tuple(o.shape) for o in operands if hasattr(o, "shape"))
            record.setdefault(key, set()).add(max(shapes, key=math.prod))
            return inner(*operands)

        return call

    pl.pallas_call = recording
    try:
        yield
    finally:
        pl.pallas_call = original


def annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def merged(base, override):
    out = dict(base)
    for k, v in (override or {}).items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def cell_mesh(cell):
    return list(cell.get("mesh") or [cell["chips"], 1, 1, 1])


def build_config(cell, config_spec, seed, out_dir, rehearsal):
    """The TRLConfig of a cell: the method's default yml, then the
    configuration's architecture and dtypes, then the cell's recipe. The mesh
    (`dp, fsdp, tp, sp`) is the cell file's `mesh`; a cell that names none
    is data-parallel over its chips."""
    from trlx_tpu.trainer.api import default_config

    arch = dict(config_spec["rehearsal_arch"] if rehearsal else config_spec["model_arch"])
    recipe, tp = cell["recipe"], cell["traffic_params"]
    config = default_config(cell["method"])
    config.model.model_path = ""
    config.model.tokenizer_path = ""
    config.model.model_arch = arch
    for section in ("model", "train", "method"):
        values = dict(config_spec.get("serving", {})) if section == "model" else {}
        values.update(recipe.get(section, {}))
        for key, value in values.items():
            setattr(getattr(config, section), key, value)
    if rehearsal:
        config.model.remat = False  # interpret-mode kernels and remat add minutes on a CPU
    config.train.seed = seed
    config.train.mesh = cell_mesh(cell)
    config.train.epochs = 10**6
    config.train.total_steps = 10**9
    config.train.log_interval = 1
    config.train.eval_interval = 10**9
    config.train.checkpoint_interval = 0  # schedules no save (PERF.md, PR 21 item 8)
    config.train.checkpoint_dir = os.path.join(out_dir, "run")
    if cell["method"] == "ppo":
        prompt, new = tp["prompt_length"]["max"], tp["new_tokens"]
        config.train.seq_length = prompt + new
        config.method.gen_kwargs = {
            "prompt_length": prompt, "max_new_tokens": new, "min_new_tokens": new,
            "do_sample": True, "top_k": 0, "top_p": 1.0,
        }
    else:
        config.train.seq_length = tp["row_length"]["max"]
        config.method.gen_kwargs = dict(tp["eval_gen_kwargs"])
    return config, arch


def step_records(path):
    """{step: record} of the program's per-step records (those with a
    step_time) and the list of its phase-window records, from metrics.jsonl."""
    steps, phases = {}, []
    if not os.path.exists(path):
        return steps, phases
    with open(path) as f:
        for line in f:
            try:
                r = json.loads(line)
            except ValueError:
                continue  # a torn last line while the program is writing
            if not isinstance(r, dict) or "t" not in r or "step" not in r:
                continue
            if "step_time" in r:
                steps[int(r["step"])] = r
            elif "time/window_wall_s" in r:
                phases.append(r)
    return steps, phases


class Tracer:
    """Starts and stops the profiler once and remembers the host's window."""

    def __init__(self, directory):
        self.directory = directory
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python frames are most of a trace's bytes
        options.host_tracer_level = 2
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.t_start = time.time()

    def stop(self):
        import jax

        self.t_stop = time.time()
        jax.profiler.stop_trace()
        mark("trace_stopped")

    @property
    def active(self):
        return self.t_start is not None and self.t_stop is None

    def xplane(self):
        for base, _, files in os.walk(self.directory):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        return None


def iteration_seconds(steps, steps_per_iter, first, last):
    """Seconds of iterations first+1 .. last, each from the last train step of
    the iteration before it to its own last train step (the program's own
    clock, key `t`); None while one of those records is missing."""
    ends = [steps.get(k * steps_per_iter, {}).get("t") for k in range(first, last + 1)]
    if None in ends:
        return None
    return [b - a for a, b in zip(ends, ends[1:])]


class IterationStop:
    """PPO: called from the harness's reward_fn, which the program calls once
    per rollout chunk (here: once per iteration, after generation and before
    scoring). Counts the iteration's non-pad tokens, decides on which whole
    iteration `learn()` ends, and drives the profiler in a traced run."""

    def __init__(self, metrics_path, steps_per_iter, seconds, tracer, traced_cycle="whole"):
        self.metrics_path, self.spi, self.seconds, self.tracer = metrics_path, steps_per_iter, seconds, tracer
        self.traced_cycle = traced_cycle
        self.trainer = None
        self.calls = []  # (wall time, non-pad tokens, rows) per rollout
        self.last_iteration = None
        self.traced_calls = None
        self.step_trace = None  # "train_steps": the thread that starts the profiler

    def on_rollout(self, rows):
        if self.last_iteration is not None and len(self.calls) >= self.last_iteration:
            return  # learn()'s closing evaluate(), not a rollout
        self.calls.append((time.time(), int(sum(len(r) for r in rows)), len(rows)))
        i = len(self.calls)  # this rollout feeds iteration i
        if i <= PPO_WARMUP_ITERATIONS or self.last_iteration is not None:
            return
        if self.tracer is not None and self.traced_cycle == "train_steps":
            # This rollout feeds the first measured iteration: trace its last
            # train steps and end with them. The profiler starts when the first
            # of those is dispatched, never inside this call, whose seconds the
            # program books on the phase record before the iteration.
            self._end_after(i)
            first = i * self.spi - min(PPO_TRACED_TRAIN_STEPS, self.spi - 1) + 1
            self.step_trace = TraceFromStep(self.trainer, self.tracer, first)
            self.step_trace.start()
            return
        if self.tracer is not None:
            if i == PPO_TRACE_FROM_CALL:
                self.tracer.start()
            elif i == PPO_TRACE_FROM_CALL + 1:
                self.tracer.stop()
                self.traced_calls = (PPO_TRACE_FROM_CALL, i)
                self._end_after(i)
            return
        steps, _ = step_records(self.metrics_path)
        durations = iteration_seconds(steps, self.spi, PPO_WARMUP_ITERATIONS, i - 1)
        if not durations:
            return  # no whole measured iteration yet
        # as many whole iterations as fit, two at least; this one makes len(durations) + 1
        if len(durations) + 1 >= max(2, int(self.seconds / statistics.median(durations))):
            self._end_after(i)

    def _end_after(self, iteration):
        self.last_iteration = iteration
        self.trainer.total_steps = iteration * self.spi


class TraceFromStep(threading.Thread):
    """PPO, `traced_cycle: train_steps`: starts the profiler from beside
    `learn()` when the program has dispatched train step `first_step`
    (`trainer.iter_count`), as StepStop does for ILQL. `run_ppo` stops it when
    `learn()` has returned."""

    def __init__(self, trainer, tracer, first_step):
        super().__init__(name="bench-trace-from-step", daemon=True)
        self.trainer, self.tracer, self.first_step = trainer, tracer, first_step
        self.done = threading.Event()
        self.error = None

    def run(self):
        try:
            while self.trainer.iter_count < self.first_step and not self.done.is_set():
                time.sleep(0.005)
            if not self.done.is_set():
                self.tracer.start()
        except Exception as e:  # surfaced by the main thread after learn()
            self.error = e

    def check(self):
        if self.error is not None:
            raise self.error
        if self.tracer.t_start is None:
            raise BenchFailure(f"learn() ended before train step {self.first_step}: nothing was traced")


class StepStop(threading.Thread):
    """ILQL: `learn()` calls nothing of the harness, so one sleeping thread
    watches `trainer.iter_count` and lowers `total_steps` when the clock has
    run out (traced: after the traced steps). The window itself is cut from
    the program's step records, not from this thread's clock."""

    def __init__(self, trainer, seconds, tracer):
        super().__init__(name="bench-step-stop", daemon=True)
        self.trainer, self.seconds, self.tracer = trainer, seconds, tracer
        self.done = threading.Event()
        self.traced_steps = None
        self.error = None

    def _wait_for_step(self, step, poll):
        while self.trainer.iter_count < step and not self.done.is_set():
            time.sleep(poll)
        return self.trainer.iter_count

    def run(self):
        try:
            self._wait_for_step(ILQL_WARMUP_STEPS, 0.02)
            if self.tracer is None:
                self.done.wait(self.seconds)
            else:
                first = self._wait_for_step(ILQL_WARMUP_STEPS + 10, 0.005)
                self.tracer.start()
                last = self._wait_for_step(first + ILQL_TRACED_STEPS, 0.005)
                self.tracer.stop()
                self.traced_steps = (first, last)
            self.trainer.total_steps = self.trainer.iter_count + 1
        except Exception as e:  # surfaced by the main thread after learn()
            self.error = e
            self.trainer.total_steps = 0


def run_ppo(cell, config, arch, seed, seconds, tracer):
    from trlx_tpu.trainer.api import PromptPipeline, get_model, get_orchestrator

    tp = cell["traffic_params"]
    vocab = arch["vocab_size"]
    prompts = traffic_gen.ppo_prompts(tp, vocab, seed)
    steps_per_iter = config.method.ppo_epochs * (config.method.num_rollouts // config.train.batch_size)
    stop = IterationStop(os.path.join(config.train.checkpoint_dir, "metrics.jsonl"),
                         steps_per_iter, seconds, tracer, cell.get("traced_cycle", "whole"))

    def reward_fn(rows):
        with annotate("bench/reward_fn"):
            stop.on_rollout(rows)
            return traffic_gen.ppo_reward(rows, vocab)

    with annotate("bench/build"):
        trainer = get_model(config.model.model_type)(config, reward_fn=reward_fn, metric_fn=None, logit_mask=None)
        stop.trainer = trainer
        mark("trainer_built")
        pipeline = PromptPipeline(
            prompts, trainer.tokenizer, max_prompt_length=trainer.prompt_length,
            bucket_widths=getattr(trainer, "prompt_buckets", None),
        )
        orch = get_orchestrator(config.train.orchestrator)(
            trainer, pipeline, reward_fn=reward_fn, metric_fn=None, chunk_size=config.method.chunk_size
        )
    with annotate("bench/make_experience"):
        orch.make_experience(config.method.num_rollouts)
    mark("first_experience_made")
    # evaluation stays outside the window and is kept to nothing: no prompts
    trainer.add_eval_pipeline(PromptPipeline([], trainer.tokenizer, max_prompt_length=trainer.prompt_length))
    try:
        with annotate("bench/learn"):
            trainer.learn()
    finally:
        if stop.step_trace is not None:
            stop.step_trace.done.set()
            stop.step_trace.join()
    mark("learn_returned")
    if tracer is not None and tracer.active:
        tracer.stop()
    if stop.step_trace is not None:
        stop.step_trace.check()

    steps, phases = step_records(stop.metrics_path)
    warm = PPO_WARMUP_ITERATIONS
    iters = max(steps) // steps_per_iter if steps else 0
    durations = iteration_seconds(steps, steps_per_iter, warm, iters)
    if not durations:
        raise BenchFailure(f"{iters} whole iterations logged; need the {warm} of warm-up and one more")
    t0, t1 = steps[warm * steps_per_iter]["t"], steps[iters * steps_per_iter]["t"]
    measured = stop.calls[warm:iters]  # the rollouts that fed iterations warm+1 .. iters
    first_steps = [steps[k * steps_per_iter + 1] for k in range(iters)]
    window = {
        "t0": t0, "t1": t1, "seconds": t1 - t0, "iterations": iters - warm, "iteration_seconds": durations,
        "samples": sum(c[2] for c in measured), "tokens": sum(c[1] for c in measured),
        # Every iteration does the same work (same prompts, fixed new tokens),
        # so the rate is the median over the iterations of work over seconds:
        # one iteration that the host held up does not move it.
        "samples_per_s": statistics.median(c[2] / d for c, d in zip(measured, durations)),
        "tokens_per_s": statistics.median(c[1] / d for c, d in zip(measured, durations)),
        "steps": [r for s, r in sorted(steps.items()) if s > warm * steps_per_iter],
        "all_steps": steps, "steps_per_iter": steps_per_iter,
        "fresh_ratio": [r.get("mean_ratio") for r in first_steps],
        # train(k) + rollout(k+1) windows the profiler never touched: in either
        # traced cycle it starts inside the third
        "phases": [p for p in phases[1:] if tracer is None or p["step"] < (PPO_TRACE_FROM_CALL - 1) * steps_per_iter],
    }
    traced = None
    if tracer is not None and stop.traced_calls:
        traced = {"iterations": 1, "generated_tokens": config.method.num_rollouts * tp["new_tokens"],
                  "train_steps": steps_per_iter}
    elif tracer is not None and stop.step_trace is not None:  # no rollout, no scoring in it
        traced = {"iterations": 1, "generated_tokens": 0,
                  "train_steps": stop.last_iteration * steps_per_iter - stop.step_trace.first_step + 1}
    return trainer, window, traced


def run_ilql(cell, config, arch, seed, seconds, tracer):
    from trlx_tpu.trainer.api import PromptPipeline, get_model, get_orchestrator

    samples, rewards = traffic_gen.ilql_dataset(cell["traffic_params"], arch["vocab_size"], seed)
    with annotate("bench/build"):
        trainer = get_model(config.model.model_type)(config, metric_fn=None, logit_mask=None)
        orch = get_orchestrator(config.train.orchestrator)(trainer, split_token=None)
    mark("trainer_built")
    with annotate("bench/make_experience"):
        orch.make_experience(samples, rewards)
    mark("first_experience_made")
    trainer.add_eval_pipeline(PromptPipeline([], trainer.tokenizer, max_prompt_length=trainer.prompt_length))
    stop = StepStop(trainer, seconds, tracer)
    stop.start()
    try:
        with annotate("bench/learn"):
            trainer.learn()
    finally:
        stop.done.set()
        stop.join()
    mark("learn_returned")
    if stop.error is not None:
        raise stop.error
    if tracer is not None and tracer.active:
        tracer.stop()

    steps, _ = step_records(os.path.join(config.train.checkpoint_dir, "metrics.jsonl"))
    last = max(steps) if steps else 0
    if last <= ILQL_WARMUP_STEPS + 1 or any(s not in steps for s in range(ILQL_WARMUP_STEPS, last + 1)):
        raise BenchFailure(f"{last} steps logged; need more than the {ILQL_WARMUP_STEPS} of warm-up")
    # Rows per step, from the program's own store in the loader's own order
    # (a fresh loader of the same store repeats the order learn() saw).
    loader, per_step = trainer.store.create_loader(config.train.batch_size, shuffle=True), []
    while len(per_step) < last:
        per_step.extend(int(np.asarray(b.attention_mask).sum()) for b in loader)
    t0, t1 = steps[ILQL_WARMUP_STEPS]["t"], steps[last]["t"]
    n = last - ILQL_WARMUP_STEPS
    window = {
        "t0": t0, "t1": t1, "seconds": t1 - t0, "iterations": n,
        "samples": n * config.train.batch_size, "tokens": sum(per_step[ILQL_WARMUP_STEPS:last]),
        "samples_per_s": n * config.train.batch_size / (t1 - t0),
        "tokens_per_s": sum(per_step[ILQL_WARMUP_STEPS:last]) / (t1 - t0),
        "steps": [r for s, r in sorted(steps.items()) if s > ILQL_WARMUP_STEPS],
        "all_steps": steps, "steps_per_iter": 1, "fresh_ratio": [], "phases": [],
    }
    traced = None
    if tracer is not None and stop.traced_steps:
        traced = {"iterations": stop.traced_steps[1] - stop.traced_steps[0], "generated_tokens": 0,
                  "train_steps": stop.traced_steps[1] - stop.traced_steps[0]}
    return trainer, window, traced


RUNNERS = {"ppo": run_ppo, "ilql": run_ilql}


def logits_sample(arch, seq, seed):
    """Two rows of `seq` tokens from the seed, one full and one left-padded
    by a third: the sample of check (a)."""
    rng = np.random.default_rng([seed, 3])
    ids = rng.integers(2, arch["vocab_size"], size=(2, seq)).astype(np.int32)
    mask = np.ones((2, seq), np.int32)
    mask[1, : seq // 3] = 0
    ids[1, : seq // 3] = 0
    return ids, mask


def sample_weights(params, arch):
    """The weights check (a) is read on: the run's own, but for GPT-Neo's q."""
    import jax
    import jax.numpy as jnp

    if arch.get("scale_attn", True):
        return params
    # GPT-Neo's attention is unscaled. A trained checkpoint keeps q.k
    # moderate; lecun-normal weights give scores of deviation
    # sqrt(head_dim), a near-one-hot softmax whose winner bf16 rounding
    # flips: on the chip the bf16 rerun of the plain reference alone moved
    # the logits by 75% at 512 positions (PERF.md, PR 22). So the sample
    # weights shrink q by 1/sqrt(head_dim), on both sides alike; the
    # training run itself keeps the weights as drawn.
    shrink = (arch["d_model"] // arch["n_head"]) ** -0.5

    def tame(path, leaf):
        keys = [str(getattr(k, "key", k)) for k in path]
        return leaf * jnp.asarray(shrink, leaf.dtype) if keys[-2:] == ["q_proj", "kernel"] else leaf

    return jax.tree_util.tree_map_with_path(tame, params)


def reference_distances(reference, trunk, arch, cell, ids, mask, last, controls=()):
    """The plain float32 reference's logits on the sample, a function that
    gives any logits' relative RMS distance from them, and the distances of
    the reference's own coarser reruns: the cell's yardstick (its
    `tolerances.logits_yardstick`, a precision of the reference; absent: the
    one-pass `bfloat16` rerun of PR 22) and whatever `controls` names."""
    import jax.numpy as jnp

    want = reference.forward(trunk, arch, ids, mask, last)
    rel = lambda x: float(jnp.sqrt(jnp.mean((x - want) ** 2) / jnp.mean(want**2)))
    rerun = lambda precision: rel(reference.forward(trunk, arch, ids, mask, last, precision=precision))
    tol = cell["tolerances"]
    yardstick = tol.get("logits_yardstick", "bfloat16")
    result = {"yardstick": yardstick, "bf16_reference_rel_rms": rerun(yardstick),
              "ref_rms": float(jnp.sqrt(jnp.mean(want**2))),
              "tol_rel_rms": tol["logits_rel_rms"], "tol_vs_bf16_reference": tol["logits_vs_bf16_reference"]}
    if controls:
        result["controls"] = {name: rerun(name) for name in controls}
    return want, rel, result


def check_logits(trainer, reference, arch, cell, seed, last=64, controls=()):
    """(a): the program's policy forward against the plain reference on a
    seeded sample: two rows of the cell's sequence length, last `last`
    positions. `controls` names precisions of the reference to put in the
    program's place as well (benchmark/control.py)."""
    import jax
    import jax.numpy as jnp

    seq = int(trainer.config.train.seq_length)
    last = min(last, seq // 2)
    ids, mask = (jnp.asarray(a) for a in logits_sample(arch, seq, seed))
    params = sample_weights(trainer.state.params, arch)

    @jax.jit
    def program_logits(params, ids, mask):
        out = trainer.model.apply({"params": params}, ids, mask)
        return out["logits"][:, -last:].astype(jnp.float32)

    got = program_logits(params, ids, mask)
    want, rel, result = reference_distances(reference, params["transformer"], arch, cell, ids, mask, last, controls)
    result.update(rel_rms=rel(got), max_abs=float(jnp.max(jnp.abs(got - want))), finite=bool(jnp.isfinite(got).all()))
    return result


def logits_pass(logits, rel_rms=None):
    """(a)'s rule: finite, and no farther from the float32 reference than the
    cell's ceiling and its multiple of the reference's own rerun at the
    cell's yardstick."""
    rel_rms = logits["rel_rms"] if rel_rms is None else rel_rms
    return logits.get("finite", True) and rel_rms <= min(
        logits["tol_rel_rms"], logits["tol_vs_bf16_reference"] * logits["bf16_reference_rel_rms"])


def verdict(cell, trainer, window, compiles, kernels, logits):
    """The five conditions of `correct`, each with what it saw."""
    losses = [[v for k, v in r.items() if "loss" in k] for r in window["all_steps"].values()]
    is_finite = lambda row: bool(row) and all(isinstance(x, (int, float)) and math.isfinite(x) for x in row)
    finite = all(is_finite(row) for row in losses)
    in_window = compiles.between(window["t0"], window["t1"])
    ratio_tol = cell["tolerances"].get("mean_ratio")
    ratios = window["fresh_ratio"]
    checks = {
        "logits": logits_pass(logits),
        "losses_finite": finite and int(trainer.skipped_steps) == 0,
        "fresh_ratio": ratio_tol is None or all(r is not None and abs(r - 1.0) <= ratio_tol for r in ratios),
        "kernels": sorted(kernels) == sorted(cell["expect_kernels"]),
        "no_compile_in_window": not in_window,
    }
    detail = {
        "logits": logits, "skipped_steps": int(trainer.skipped_steps), "fresh_ratio": ratios,
        "kernels_traced": {k: sorted(v) for k, v in sorted(kernels.items())},
        "kernels_expected": sorted(cell["expect_kernels"]), "compiles_in_window": in_window,
    }
    nonfinite = sum(1 for row in losses if not is_finite(row))
    return checks, detail, {"attempted": len(losses), "failed": max(nonfinite, int(trainer.skipped_steps))}


def median(values):
    return statistics.median(values) if values else None

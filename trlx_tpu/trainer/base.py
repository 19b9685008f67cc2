"""Base JAX trainer: mesh, optimizer, train loop, eval, checkpointing.

The TPU-native counterpart of AccelerateRLModel
(reference: trlx/model/accelerate_base_model.py:22-276). Everything the
reference delegates to Accelerate/DeepSpeed is explicit here:

- device placement / ZeRO     → `shard_pytree` over the (dp, fsdp, tp, sp) mesh
- accelerator.backward allreduce → emitted by XLA from batch/param shardings
- accelerator.save_state      → Orbax (async, sharded, WITH true resume —
                                 the reference's save has no resume logic,
                                 reference: trlx/model/__init__.py:101-129)
- wandb trackers              → utils.logging.Tracker
"""

import os
import signal
import sys
import threading
import time
import warnings
from abc import abstractmethod
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

# transformers and orbax.checkpoint are not imported here: together they
# were two thirds of this module's import (PERF.md section 6, PR 42), and a
# run built from `model_arch` that schedules no save calls neither. Each
# loads where it is first called (`_build_tokenizer`, `_checkpointer`) under
# a `setup/import` span (utils/startup.py): a run that names a tokenizer, a
# `checkpoint_interval > 0` or a checkpoint to resume pays them in __init__
# as before, under that name; any other pays orbax's at an explicit `save()`
# or a preemption save.
from flax import struct

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.heads import trainable_mask
from trlx_tpu.models.lm import flash_kept_pair_share
from trlx_tpu import observability as obs
from trlx_tpu.observability import device_scopes
from trlx_tpu.observability import fleet as obs_fleet
from trlx_tpu.observability import numerics as obs_numerics
from trlx_tpu.observability import spans as obs_spans
from trlx_tpu.observability.spans import trace_span
from trlx_tpu.ops.fused_logprob import count_head_calls, take_head_call_scalars
from trlx_tpu.parallel import make_mesh, set_mesh, shard_pytree
from trlx_tpu.parallel.mesh import DATA_AXES, barrier, init_distributed, is_main_process
from trlx_tpu.parallel.schedule import count_weight_gathers, weight_gather_share
from trlx_tpu.resilience import (
    CheckpointError,
    DivergenceWatchdog,
    FaultPlan,
    TrainingDiverged,
)
from trlx_tpu.pipeline.overlap import PrefetchIterator, SerialFeed
from trlx_tpu.resilience import checkpoint as ckpt_util
from trlx_tpu.resilience import distributed as dist_res
from trlx_tpu.resilience.faults import poison_nan
from trlx_tpu.trainer import BaseRLTrainer
from trlx_tpu.utils import Clock
from trlx_tpu.utils import sanitize
from trlx_tpu.utils.compile_cache import setup_compile_cache
from trlx_tpu.utils.logging import Tracker
from trlx_tpu.utils.startup import deferred_import, startup_counters


class TrainState(struct.PyTreeNode):
    """Donatable training state: params + optimizer state + frozen extras
    (ref-branch params for PPO, target-Q params for ILQL). `bad_steps`
    counts CONSECUTIVE updates skipped by the on-device non-finite guard
    (trlx_tpu/resilience/guard.py) — on-device so the guard costs no host
    sync, in the state so it survives checkpoints. Default None keeps
    hand-built abstract states (tests/test_scale_compile.py) valid."""

    step: jnp.ndarray
    params: Any
    opt_state: Any
    extras: Any = None
    bad_steps: Any = None


def lr_schedule(train_cfg):
    """Warmup + cosine decay (reference: trlx/model/accelerate_base_model.py:93)."""
    init, target = float(train_cfg.learning_rate_init), float(train_cfg.learning_rate_target)
    decay_steps = max(train_cfg.lr_decay_steps, 1)
    cosine = optax.cosine_decay_schedule(init, decay_steps, alpha=target / max(init, 1e-12))
    if train_cfg.lr_ramp_steps > 0:
        warmup = optax.linear_schedule(0.0, init, train_cfg.lr_ramp_steps)
        return optax.join_schedules([warmup, cosine], [train_cfg.lr_ramp_steps])
    return cosine


def build_optimizer(train_cfg, opt_mask):
    """(optimizer, schedule) from explicit ingredients — module-level so AOT
    validation (tests/test_scale_compile.py) can build the production
    optimizer against abstract params. multi_transform (not optax.masked):
    masked would pass frozen params' raw gradients through untouched;
    multi_transform routes them to set_to_zero, which both freezes them and
    allocates no Adam moments for them."""
    schedule = lr_schedule(train_cfg)
    inner = optax.chain(
        optax.clip_by_global_norm(train_cfg.grad_clip),
        optax.adamw(
            schedule,
            b1=train_cfg.opt_betas[0],
            b2=train_cfg.opt_betas[1],
            weight_decay=train_cfg.weight_decay,
        ),
    )
    labels = jax.tree_util.tree_map(lambda t: "train" if t else "freeze", opt_mask)
    return optax.multi_transform({"train": inner, "freeze": optax.set_to_zero()}, labels), schedule


class JaxBaseTrainer(BaseRLTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, train_mode=True)

        # Persistent XLA compile cache: restarts/resumes skip the one-time
        # compilation cost. Where it lives is utils/compile_cache.py's rule.
        setup_compile_cache()

        init_distributed()
        self.mesh = make_mesh(config.train.mesh, devices=kwargs.pop("mesh_devices", None))
        set_mesh(self.mesh)

        # Distributed resilience (trlx_tpu/resilience/distributed.py) is
        # armed BEFORE the first barrier so even the init collectives are
        # deadline-guarded: a host that dies during bootstrap aborts the
        # fleet with a CollectiveTimeout diagnostic instead of wedging it.
        self.heartbeat = None
        if config.train.heartbeat_interval > 0:
            self.heartbeat = dist_res.Heartbeat(
                os.path.join(os.path.abspath(config.train.checkpoint_dir), "heartbeats"),
                config.train.heartbeat_interval,
            ).start()
        dist_res.configure(
            deadline=config.train.collective_deadline,
            heartbeat=self.heartbeat,
            step_provider=lambda: getattr(self, "iter_count", 0),
        )

        barrier()  # ≈ reference's init barrier (trlx/model/accelerate_base_model.py:33-34)

        # Fail misconfigured batch/mesh combinations HERE — before the
        # expensive model build / checkpoint restore — with a clear message
        # instead of a cryptic sharding error at the first put_batch. Sizes
        # are rows per PROCESS (the reference's per-rank semantics); the
        # assembled global batch must shard evenly over the data axes.
        self._validate_data_sharding(config.train.batch_size, "train.batch_size")
        chunk = getattr(config.method, "chunk_size", None)
        if chunk is not None:
            self._validate_data_sharding(chunk, "method.chunk_size (rollout chunk)")

        self.rng = jax.random.PRNGKey(config.train.seed)
        # next_rng is consumed from the main thread (eval) AND, with the
        # pipelined rollout producer on, from the producer thread — the
        # split-and-advance must be atomic.
        self._rng_lock = threading.Lock()
        # put_batch sharding cache: specs depend only on array rank (batch
        # dim over DATA_AXES, rest replicated) and the mesh is fixed for the
        # trainer's lifetime.
        self._sharding_cache = {}
        # Device-dispatch serialization for the staleness>0 rollout producer:
        # two threads launching COLLECTIVE-bearing programs concurrently can
        # enqueue them in different orders on different local devices, and
        # XLA's rendezvous then deadlocks (observed on the 8-device CPU mesh:
        # half the devices enter run A's all-reduce, half run B's). Holding
        # this lock across the dispatch call (not the execution — dispatch is
        # async) keeps every device queue in one global program order.
        # Uncontended acquire is ~100ns; the serial path never contends.
        # (A plain RLock unless TRLX_TPU_SANITIZE=dispatch arms the
        # ownership-asserting variant — utils/sanitize.py.)
        self._dispatch_lock = sanitize.make_dispatch_lock()
        # ---- observability, the span file (trlx_tpu/observability/): armed
        # before the tokenizer, the model build and a resume, so that what
        # they pay (`setup/import`, `ckpt/load`) lands in this run's file.
        # Env flags override config so a drill can be bolted onto any run
        # command; everything defaults OFF and the instrumentation stays off
        # the hot dispatch path.
        ckpt_dir = os.path.abspath(config.train.checkpoint_dir)
        # graftfleet (cross-host federation) owns the span filename when
        # armed: each host writes spans.host<k>.jsonl so read_fleet_spans can
        # merge per-host lanes. Arming it implies span tracing (the merged
        # trace and the incident span tails are its artifacts).
        fleet_on = config.train.graftfleet or obs.env_flag("TRLX_TPU_GRAFTFLEET")
        if (
            config.train.trace_spans
            or fleet_on
            or obs.env_flag("TRLX_TPU_SPANS")
        ):
            obs_spans.configure(
                os.path.join(
                    ckpt_dir,
                    obs_spans.host_spans_filename(jax.process_index())
                    if fleet_on
                    else obs_spans.SPANS_FILENAME,
                ),
                process_index=jax.process_index(),
            )
        else:
            # Trainer construction owns the process-global tracer: a prior
            # trainer in this process (tests build several) must not keep
            # appending this run's thread spans to its old file.
            obs_spans.shutdown()
        self.tokenizer = self._build_tokenizer(config.model.tokenizer_path)

        # Subclass builds the Flax module + initial host params.
        self.model, init_params = self.get_arch(self.config)

        self.opt_mask = self.build_trainable_mask(init_params)
        self.optimizer = self._build_optimizer()

        state = self.init_state(init_params)
        self.state, self.state_shardings = shard_pytree(state, self.mesh)

        # ---- resilience state (trlx_tpu/resilience/): must exist before
        # _maybe_resume — load() finalizes pending saves and restores the
        # resilience host state.
        self.fault_plan = FaultPlan.from_env_or_config(config.train.fault_plan)
        # The checkpointer exists from its first use on (`_checkpointer`). A
        # run that schedules saves builds it here, so that orbax's import is
        # paid in set-up and never inside a save, least of all a preemption
        # save's grace window; a resume builds it in `load()`, below.
        self._ckptr = None
        if config.train.checkpoint_interval > 0:
            self._checkpointer()
        self._pending_save = None  # at most one async save in flight
        self._save_count = 0
        self._lr_scale = 1.0  # watchdog LR decay multiplier (compounds)
        self._rollbacks = 0
        self.skipped_steps = 0  # total guard-skipped updates (host count)
        self._res_pending = []  # buffered per-step device scalars (no sync)
        self._host_t0 = None  # where time/step_host_ms counts from (learn loop)
        self._step_wait_s = 0.0  # device waits inside a step, outside its stats read
        # `flash/kept_pair_share` of every step record: a host float from the
        # train step's shapes, None where its attention takes no flash kernel
        self._flash_kept_share = flash_kept_pair_share(self.model.cfg, config.train.seq_length)
        # `parallel/weight_gather_share`: what the traced train step and
        # generate program did with each kernel split over fsdp, filled while
        # they are traced (parallel/schedule.py); empty without such a mesh
        self._weight_gathers = {"train": {}, "generate": {}}
        # `head/<program>/<site>/weight_passes` and the tiles beside it: what
        # the fused log-prob head chose at each call site of the train step
        # and the scoring program, filled while they are traced
        # (ops/fused_logprob.py) and logged once a compiled program
        self._head_calls = {"train": {}, "score": {}}
        # Parallel host-side batch refs for the graftnum nonfinite census:
        # populated ONLY when incident capture is armed (None placeholders
        # otherwise), so default runs keep zero extra references alive.
        self._res_batch_refs = []
        self.last_restore_fallback = False  # load() fell past latest.txt
        self.watchdog = (
            DivergenceWatchdog(
                config.train.watchdog_threshold,
                patience=config.train.watchdog_patience,
                ema_alpha=config.train.watchdog_ema_alpha,
                warmup=config.train.watchdog_warmup,
            )
            if config.train.watchdog_threshold > 0
            else None
        )

        # Resume BEFORE any rollout: PPO's initial experience must come from
        # the restored policy, not the fresh init (stale behavior logprobs
        # would mis-clip the whole first epoch's importance ratios).
        self._resumed = False
        if config.train.resume_from_checkpoint:
            self._maybe_resume()

        run_name = config.model.model_path or "from-scratch"
        self.tracker = Tracker(
            project_name=config.train.project_name,
            config=config.to_dict(),
            run_name=run_name,
            entity_name=config.train.entity_name,
            log_dir=config.train.checkpoint_dir,
        )

        # ---- observability, the rest: device telemetry, anomaly capture,
        # health, fleet, numerics, the metrics endpoint (same rule for flags).
        device_scopes.configure(ckpt_dir)
        obs_spans.install_compile_listener()
        self._devicemon = None
        if (
            config.train.device_telemetry
            or obs.env_flag("TRLX_TPU_DEVICE_TELEMETRY")
        ):
            self._devicemon = obs.DeviceMonitor(
                programs_path=(
                    os.path.join(ckpt_dir, "programs.json") if is_main_process() else None
                )
            )
        anomaly_factor = float(
            os.environ.get("TRLX_TPU_ANOMALY_FACTOR", "") or config.train.anomaly_factor
        )
        # The flight recorder (observability/anomaly.py), on in every run: one
        # detector over the step records' step_time at STALL_FACTOR, with
        # train.anomaly_factor as the same median's second threshold (the
        # bundle's); the ticker and the step's window of process counters and
        # tick gaps; stalls.jsonl, which appears with the first stall.
        self._anomaly = obs.AnomalyDetector(
            obs.anomaly.STALL_FACTOR, window=config.train.anomaly_window, bundle_factor=anomaly_factor
        )
        self._ticker = obs.anomaly.Ticker()
        self._step_proc = obs.anomaly.ProcWindow(self._ticker, "step")
        self._stall_log = obs.anomaly.StallLog(ckpt_dir, process_index=jax.process_index())
        self._pending_stall = None  # (breach, (record, interval)) found at the log boundary, written after train/step
        self._incidents = None
        if anomaly_factor > 0:
            self._incidents = self._build_incident_capture(ckpt_dir)
        # Training-health monitor (trlx_tpu/observability/health.py):
        # streaming drift/collapse/sentinel detectors over the stats this
        # trainer already logs. A CRIT transition escalates through the same
        # emergency hook as the collective-timeout path, so arming health
        # also arms IncidentCapture even at anomaly_factor 0.
        self._health = None
        if config.train.health_monitor or obs.env_flag("TRLX_TPU_HEALTH"):
            if self._incidents is None:
                self._incidents = self._build_incident_capture(ckpt_dir)
            self._health = obs.HealthMonitor(
                warmup=config.train.health_warmup,
                warn_streak=config.train.health_warn_streak,
                crit_streak=config.train.health_crit_streak,
                lineage_path=(
                    os.path.join(ckpt_dir, "lineage.jsonl") if is_main_process() else None
                ),
            )
        # graftfleet monitor: records guarded-collective arrivals (via the
        # collective_guard exit hook), estimates the cross-host clock
        # alignment, and (process 0) rolls the fleet gauges / healthz block
        # at log boundaries. Construction-owned like the span tracer; the
        # startup clock_sync is collective, so the knob must be
        # config-consistent across hosts.
        self._fleet = None
        if fleet_on:
            self._fleet = obs_fleet.configure(
                ckpt_dir,
                process_index=jax.process_index(),
                process_count=jax.process_count(),
                resync_interval=config.train.fleet_resync_interval,
            )
            self._fleet.clock_sync(step=0)
            if self._health is not None:
                self._health.register_detector(self._fleet.straggler)
        else:
            obs_fleet.shutdown()
        # graftnum (streaming numerics observatory, trlx_tpu/observability/
        # numerics.py): per-subtree grad/update telemetry folded into the
        # jitted step at BUILD time, NaN-provenance census + bisect on guard
        # trips, and quantization-error gauges at weight handoffs. Arming it
        # implies IncidentCapture (the provenance artifact lives in the
        # guard_skip bundle). Construction-owned like the span tracer.
        self._graftnum = None
        if obs_numerics.armed(config.train):
            self._graftnum = obs_numerics.configure()
            if self._incidents is None:
                self._incidents = self._build_incident_capture(ckpt_dir)
            if self._health is not None:
                for det in self._graftnum.detectors:
                    self._health.register_detector(det)
            else:
                # No health monitor: CRIT transitions still escalate through
                # the shared emergency-capture hook (same health_<name>
                # incident reason, so the report cross-links either way).
                for det in self._graftnum.detectors:
                    det.on_crit = obs_numerics.escalate
        else:
            obs_numerics.shutdown()
        # Live /metrics + /healthz endpoint (trlx_tpu/observability/
        # export.py): process 0 only, armed by the port knob. The port is
        # recorded on EVERY process — multi-host gauge rollup needs all
        # hosts to enter the allgather (see _export_metrics).
        self._metrics_port = int(
            os.environ.get("TRLX_TPU_METRICS_PORT", "") or config.train.metrics_port
        )
        self._metrics_exporter = None
        if self._metrics_port > 0 and is_main_process():
            from trlx_tpu.observability.export import MetricsExporter

            # port_file: where a scraper finds the ACTUAL port when the
            # requested one was busy and the exporter rebound ephemerally.
            self._metrics_exporter = MetricsExporter(
                self._metrics_port,
                port_file=os.path.join(ckpt_dir, "metrics_port"),
            )

        self.reward_fn = kwargs.pop("reward_fn", None)
        self.metric_fn = kwargs.pop("metric_fn", None)
        self.logit_mask = kwargs.pop("logit_mask", None)
        self.orch = None
        self.iter_count = 0

    # ------------------------------------------------------------------ setup

    def _validate_data_sharding(self, rows_per_process: int, name: str):
        """Per-process row counts globalize to rows × process_count and shard
        over the SAME data axes put_batch uses (DATA_AXES) — validate against
        exactly that product so the check cannot drift from the sharding."""
        data = int(np.prod([self.mesh.shape[a] for a in DATA_AXES]))
        global_rows = rows_per_process * jax.process_count()
        if global_rows % data:
            raise ValueError(
                f"{name}={rows_per_process} × {jax.process_count()} "
                f"process(es) = {global_rows} global rows, which does not "
                f"divide the mesh's data axes {DATA_AXES}={data} — pick a "
                "size that shards evenly"
            )

    def _build_tokenizer(self, tokenizer_path: str):
        if not tokenizer_path:
            return None
        tokenizer = deferred_import("transformers").AutoTokenizer.from_pretrained(tokenizer_path)
        # pad = eos, left padding (reference:
        # trlx/model/accelerate_base_model.py:42-45); padding itself is done
        # by our fixed-shape pipeline, but the ids matter.
        tokenizer.pad_token = tokenizer.eos_token
        tokenizer.padding_side = "left"
        return tokenizer

    def _checkpointer(self):
        """The orbax checkpointer, built (and orbax imported) at the first
        call: in `__init__` where the run schedules saves, else at the first
        `save()` or restore."""
        if self._ckptr is None:
            self._ckptr = deferred_import("orbax.checkpoint").StandardCheckpointer()
        return self._ckptr

    def _lr_schedule(self):
        return lr_schedule(self.config.train)

    def _build_optimizer(self):
        """AdamW + cosine schedule + global-norm clip
        (reference: trlx/model/accelerate_base_model.py:81-91), with frozen
        layers excluded via optax.masked — the functional requires_grad_
        (reference: trlx/model/accelerate_base_model.py:49-64). Masked params
        get NO optimizer moments: layer freezing is also a ZeRO-style memory
        saving here."""
        optimizer, self.schedule = build_optimizer(self._scaled_train_cfg(), self.opt_mask)
        return optimizer

    def _scaled_train_cfg(self):
        """Train config with the watchdog's LR decay folded into the
        schedule endpoints (identity when no rollback has fired). getattr:
        the first build in __init__ runs before the resilience state does."""
        scale = getattr(self, "_lr_scale", 1.0)
        if scale == 1.0:
            return self.config.train
        from dataclasses import replace

        t = self.config.train
        return replace(
            t,
            learning_rate_init=t.learning_rate_init * scale,
            learning_rate_target=t.learning_rate_target * scale,
        )

    def _rebuild_for_lr_scale(self):
        """Rebuild optimizer/schedule (and the jitted train step, once it
        exists) after `_lr_scale` changed. The optimizer STATE layout is
        unchanged — only hyperparameters differ — so the live/restored
        opt_state remains valid. Recompile cost is paid per rollback event,
        never on the hot path."""
        self.optimizer = self._build_optimizer()
        if getattr(self, "train_step", None) is not None:
            self.train_step = self._wrap_monitored("train/step", self.build_train_step())

    def _wrap_monitored(self, name: str, fn, phase: str = "train"):
        """Route a jitted fn through the device-telemetry monitor (when
        telemetry is on; call sites stay unconditional). getattr:
        subclass __init__ code may build programs before the base bootstrap
        has armed the monitor. Every registered jitted program funnels
        through here, so this is also where the dispatch sanitizer hooks in
        (identity unless TRLX_TPU_SANITIZE=dispatch), and where a program
        dispatched under an open profiler session notes itself for the
        scope table (observability/device_scopes.py), telemetry on or off."""
        fn = sanitize.wrap_dispatch(name, device_scopes.wrap(fn), getattr(self, "_dispatch_lock", None))
        monitor = getattr(self, "_devicemon", None)
        if monitor is None:
            return fn
        return monitor.wrap(name, fn, phase=phase)

    def _build_incident_capture(self, ckpt_dir: str):
        """Arm the incident machinery + the emergency hook (the collective-
        timeout abort path and the health monitor's CRIT escalation both run
        on threads with no trainer reference in scope)."""
        incidents = obs.IncidentCapture(
            ckpt_dir,
            monitor=self._devicemon,
            metrics_path=os.path.join(ckpt_dir, "metrics.jsonl"),
            max_incidents=self.config.train.max_incidents,
            profiling_active=lambda: getattr(self, "_profiling", False),
        )
        obs.anomaly.register_emergency(
            incidents, lambda: getattr(self, "iter_count", 0)
        )
        return incidents

    def _export_metrics(self, stats_host: dict):
        """Push the freshest log-boundary scalars (health gauges included) to
        the live /metrics endpoint. Multi-host: the scalars are rolled up
        over the existing allgather_host path FIRST — the port knob is
        config-consistent, so every process enters the collective and
        process 0 serves fleet /hostmean //hostmax views, not its own
        shard's numbers."""
        if self._metrics_port <= 0:
            return
        gauges = dict(stats_host)
        if jax.process_count() > 1:
            from trlx_tpu.observability.report import rollup_window_stats

            # per_host only when graftfleet armed: the per-host labeled rows
            # multiply the gauge count by process_count, and fleet triage is
            # what wants them. The flag is config-consistent across hosts, so
            # the gather shape stays aligned.
            gauges.update(
                rollup_window_stats(gauges, per_host=self._fleet is not None)
            )
        if self._metrics_exporter is not None:
            health = getattr(self, "_health", None)
            self._metrics_exporter.update(
                gauges,
                step=self.iter_count,
                health=health.healthz() if health is not None else None,
            )

    def _flush_device_telemetry(self, phase_seconds: dict) -> dict:
        """Window-boundary telemetry flush: drain the monitor's per-phase
        FLOP accumulators into MFU/throughput gauges and sample the
        kernel-routing + device-memory gauges. Returns {} when telemetry is
        off — callers merge unconditionally."""
        monitor = getattr(self, "_devicemon", None)
        if monitor is None:
            return {}
        out = monitor.window(phase_seconds)
        out.update(monitor.kernel_routing_gauges())
        out.update(monitor.device_memory_gauges())
        return out

    def build_trainable_mask(self, init_params):
        """Default layer-freezing mask (num_layers_unfrozen); subclasses
        override for other parameter-efficiency schemes (soft prompts)."""
        return trainable_mask(init_params, self.model.cfg, self.config.model.num_layers_unfrozen)

    def detach_frozen(self, params):
        """stop_gradient on frozen leaves inside the loss: XLA then drops the
        frozen blocks' weight-gradient matmuls entirely (≈half the backward
        FLOPs per frozen layer). Activation gradients still flow through, so
        trainable embeddings below frozen blocks keep learning. The optimizer
        masking (build_trainable_mask) stays as the semantic source of truth;
        this is the compute-side twin."""
        return jax.tree_util.tree_map(
            lambda p, t: p if t else jax.lax.stop_gradient(p), params, self.opt_mask
        )

    def init_state(self, init_params) -> TrainState:
        """Build the initial TrainState (subclasses add extras)."""
        return TrainState(
            step=jnp.zeros((), dtype=jnp.int32),
            params=init_params,
            opt_state=self.optimizer.init(init_params),
            extras=self.make_extras(init_params),
            bad_steps=jnp.zeros((), dtype=jnp.int32),
        )

    def make_extras(self, init_params):
        return None

    def _maybe_resume(self):
        """Restore the latest checkpoint if one exists. The existence check
        is process-AGREED (main process decides, broadcast to all) so the
        collective orbax restore is entered by every host or by none."""
        latest = os.path.join(
            os.path.abspath(self.config.train.checkpoint_dir), "latest.txt"
        )
        exists = os.path.exists(latest)
        if jax.process_count() > 1:
            # GL004: the broadcast blocks on every peer — the guarded mesh
            # helper turns a dead peer into a CollectiveTimeout abort.
            from trlx_tpu.parallel.mesh import broadcast_host

            exists = bool(broadcast_host(np.asarray(exists)))
        if not exists:
            return
        self.load()
        self._resumed = True
        if is_main_process():
            print(f"[trlx_tpu] resumed from step {int(jax.device_get(self.state.step))}")

    # -------------------------------------------------------------- tokenize

    def tokenize(self, texts):
        """BOS + text, truncated to seq_length keeping the TRAILING tokens.

        Truncation convention, unified framework-wide: PROMPTS keep the most
        recent (trailing) context — the same keep_last rule as PromptPipeline
        and the left-padding discipline. Offline ILQL SAMPLES are the one
        deliberate exception (tokenize_ilql keeps leading tokens, so
        action/state indices stay aligned from the sequence start).
        (reference: trlx/model/accelerate_base_model.py:93-103, minus its
        nonexistent-config-field bug)."""
        assert self.tokenizer is not None, "tokenize() requires a tokenizer"
        out = []
        for text in texts:
            ids = self.tokenizer(text, add_special_tokens=False)["input_ids"]
            if self.tokenizer.bos_token_id is not None:
                ids = [self.tokenizer.bos_token_id] + ids
            out.append(ids[-self.config.train.seq_length :])
        return out

    def to_local_host(self, tree):
        """Global device arrays → this process's batch rows as host numpy
        (see parallel.mesh.to_local_host)."""
        from trlx_tpu.parallel.mesh import to_local_host

        return to_local_host(tree, mesh=self.mesh)

    def decode(self, tokens, mask=None):
        """Device tokens → host text (or trimmed token arrays w/o tokenizer).

        Multi-host: each process decodes ITS OWN batch rows (the device→host
        pull goes through addressable shards only — np.asarray on a global
        array would throw on a pod)."""
        tokens = self.to_local_host(tokens)
        if self.tokenizer is not None:
            return self.tokenizer.batch_decode(tokens, skip_special_tokens=True)
        if mask is None:
            return [t for t in tokens]
        mask = self.to_local_host(mask)
        return [t[m.astype(bool)] for t, m in zip(tokens, mask)]

    @staticmethod
    def rollout_decode_stats(mask_h, prompt_length: int):
        """Decode-loop observability for one rollout chunk, from the HOST
        mask: generated-token count (mask-valid response positions) and the
        number of decode steps the while_loop actually executed — the highest
        response position any row was still live at, which is what the
        early-exit decode pays for (vs the max_new_tokens budget)."""
        resp = np.asarray(mask_h)[:, prompt_length:]
        return {
            "gen_tokens": int(resp.sum()),
            "decode_steps": int(resp.any(axis=0).sum()),
            "decode_step_budget": int(resp.shape[1]),
            # Per-EPISODE decode steps (response masks are contiguous from
            # position 0, so the row sum IS each row's step count). The
            # whole-batch decode_steps above is what the static batch PAID —
            # max over rows; the per-episode view is what each row USED, and
            # the gap between their means is the straggler overhead the
            # continuous-batching engine removes.
            "episode_steps": resp.sum(axis=1).astype(np.int64),
        }

    def next_rng(self):
        with getattr(self, "_rng_lock", None) or threading.Lock():
            self.rng, sub = jax.random.split(self.rng)
            return sub

    def chunk_rng(self, chunk: int):
        """Sampling key for absolute prompt chunk ``chunk`` — a pure function
        of (train.seed, chunk), independent of this process's ``next_rng``
        consumption history. Rollout generation keys off the schedule
        position, not the call count, so every elastic worker (or a resumed
        learner) sampling chunk c draws exactly the serial run's tokens."""
        return jax.random.fold_in(jax.random.PRNGKey(self.config.train.seed), int(chunk))

    def put_batch(self, tree):
        """Host batch → device, batch dim sharded over (dp, fsdp).

        Multi-host: each process feeds its local shard
        (the WORLD_SIZE batch-scaling semantics of the reference,
        reference: trlx/trlx.py:47, live here).

        Shardings are cached per array rank: the spec is fully determined by
        ndim (batch dim over DATA_AXES, every other dim replicated) and the
        mesh is fixed, so rebuilding a NamedSharding per leaf per step was
        pure allocation overhead on the hot path."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        cache = getattr(self, "_sharding_cache", None)
        if cache is None:
            cache = self._sharding_cache = {}
        multihost = jax.process_count() > 1

        def put(x):
            x = np.asarray(x)
            entry = cache.get(x.ndim)
            if entry is None:
                spec = P(DATA_AXES, *([None] * (x.ndim - 1)))
                entry = cache[x.ndim] = (spec, NamedSharding(self.mesh, spec))
            spec, sharding = entry
            if multihost:
                from jax.experimental import multihost_utils

                return multihost_utils.host_local_array_to_global_array(x, self.mesh, spec)
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(put, tree)

    def finalize_lm_config(self, lm_cfg):
        """Inject mesh-derived settings the architecture needs statically:
        sp>1 turns on ring-attention sequence parallelism; any sharded mesh
        switches the training-path embedding to the one-hot matmul whose
        gradients the SPMD partitioner shards without falling back to full
        rematerialization (LMConfig.onehot_embed)."""
        from trlx_tpu.parallel.mesh import AXIS_SP

        sp = int(self.mesh.shape[AXIS_SP])
        if sp > 1:
            lm_cfg = lm_cfg.replace(sp_size=sp)
        if int(self.mesh.size) > 1:
            lm_cfg = lm_cfg.replace(onehot_embed=True)
        return lm_cfg

    # ------------------------------------------------------------- abstracts

    @abstractmethod
    def get_arch(self, config: TRLConfig):
        """Return (flax_module, host_param_pytree)."""

    @abstractmethod
    def build_train_step(self) -> Callable:
        """Return jitted train_step(state, batch, *extra) -> (state, stats)."""

    def post_backward_callback(self, stats=None):
        """Called after EVERY optimizer step with the step's stats dict.
        The values are un-fetched device scalars — implementations must not
        force a sync on the hot path (buffer, then read at a log boundary)."""

    def post_epoch_callback(self):
        pass

    def progress_line(self, stats_host: dict):
        """Rank-0 live progress line on stderr at each logged step — the
        counterpart of the reference's tqdm bar with stats description
        (reference: trlx/model/accelerate_base_model.py:210-248). A plain
        carriage-return-rewritten line: no tqdm dependency, degrades to one
        line per log step when stderr is a file."""
        if not is_main_process() or os.environ.get("TRLX_TPU_NO_PROGRESS"):
            return
        # Fold in the last rollout-phase window (exp/s, time/* split) so the
        # line shows the full iteration economics, not just the train step.
        merged = dict(getattr(self, "_last_phase_stats", None) or {})
        merged.update(stats_host)
        parts = [f"step {self.iter_count}/{self.total_steps}"]
        for key, label in (
            ("loss", "loss"),
            ("mean_reward", "reward"),
            ("mean_kl", "kl"),
            ("metrics/optimality", "optimality"),
            ("samples_per_sec", "samples/s"),
            ("exp_per_sec", "exp/s"),
            ("train_tokens_per_s", "tok/s"),
            ("train_batch_fill", "fill"),
        ):
            if key in merged:
                parts.append(f"{label}={merged[key]:.4g}")
        if all(f"time/{p}_s" in merged for p in ("rollout", "score", "train")):
            parts.append(
                "phases r/s/t={:.1f}/{:.1f}/{:.1f}s ov={:.0%}".format(
                    merged["time/rollout_s"],
                    merged["time/score_s"],
                    merged["time/train_s"],
                    merged.get("time/overlap_fraction", 0.0),
                )
            )
        fl = getattr(self, "_fleet", None)
        if fl is not None and jax.process_count() > 1:
            # Fleet readout: host count + the last window's worst aligned
            # collective skew (graftfleet's straggler signal at a glance).
            parts.append(
                f"hosts={jax.process_count()} skew={fl.last_skew_ms:.0f}ms"
            )
        # \x1b[K clears to end-of-line so a previous longer line (e.g. one
        # with eval-only keys) leaves no remnants after the rewrite.
        print("  ".join(parts) + "\x1b[K", end="\r", file=sys.stderr, flush=True)
        self._progress_open = True

    def log_param_watch(self, limit_per_leaf: int = 4096):
        """`wandb.watch`-equivalent parameter distributions (the reference's
        softprompt example watches the model, reference:
        examples/ppo_softprompt_sentiments.py:38-39), shaped for XLA: per
        top-level param group, a strided ON-DEVICE subsample (≤limit_per_leaf
        elements per leaf) is the only host transfer — full params never
        leave HBM. The grad-side counterpart is the per-group
        `watch/grad_norm/*` scalars the train step emits when
        `train.watch_interval` is set.

        Pod runs skip the histograms (slicing non-addressable shards to host
        is not free of collectives); the grad-norm scalars still flow."""
        if not self.tracker.enabled or jax.process_count() > 1:
            return
        for group, sub in self.state.params.items():
            pieces = []
            for leaf in jax.tree_util.tree_leaves(sub):
                if not hasattr(leaf, "dtype") or not jnp.issubdtype(leaf.dtype, jnp.floating):
                    continue
                flat = leaf.reshape(-1)
                stride = max(1, flat.shape[0] // limit_per_leaf)
                pieces.append(flat[::stride][:limit_per_leaf].astype(jnp.float32))
            if pieces:
                sample = np.asarray(jax.device_get(jnp.concatenate(pieces)))
                self.tracker.log_histogram(f"watch/params/{group}", sample, step=self.iter_count)

    def end_progress(self):
        """Terminate an open \\r-rewritten progress line so subsequent output
        (eval tables, tracebacks) doesn't print over its remnants."""
        if getattr(self, "_progress_open", False):
            print(file=sys.stderr, flush=True)
            self._progress_open = False

    @abstractmethod
    def prepare_learning(self):
        """Build train/eval loaders; set n_updates_per_batch, total_steps."""

    # ------------------------------------------------------------------ eval

    def add_eval_pipeline(self, eval_pipeline):
        self.eval_pipeline = eval_pipeline

    def _gather_valid_rows(self, tree, n_valid: int):
        """One eval batch of per-row arrays → host rows over exactly the
        valid rows, from ALL processes.

        Each process pulls its own rows, drops the loader's wrap-around
        duplicates ([n_valid:]), then arrays (token grids, scores — not
        strings, which can't ride collectives) are all-gathered so every
        process returns the full global rows (reference's eval gather:
        trlx/model/accelerate_base_model.py:149-158). n_valid is per-process:
        each process's loader wraps independently."""
        tree = self.to_local_host(tree)
        tree = jax.tree_util.tree_map(lambda x: x[:n_valid], tree)
        if jax.process_count() == 1:
            return tree
        from trlx_tpu.parallel.mesh import allgather_host

        # Pad row counts to a common size before the fixed-shape gather,
        # then trim each process's segment by its gathered valid count.
        nv = allgather_host(np.asarray([n_valid], dtype=np.int32)).reshape(-1)
        B = int(nv.max())

        def g(x):
            pad = [(0, B - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
            xg = allgather_host(np.pad(x, pad)).reshape((len(nv), B) + x.shape[1:])
            return np.concatenate([xg[p, : nv[p]] for p in range(len(nv))])

        return jax.tree_util.tree_map(g, tree)

    def evaluate(self):
        """Sample eval prompts, score/metric, log a table
        (reference: trlx/model/accelerate_base_model.py:134-201). Statistics
        run over exactly the valid eval rows: the loader's static-shape
        wrap-around duplicates are dropped before means/tables. With an
        on-device reward model (and no host reward_fn), eval rewards come
        from the RM."""
        self.end_progress()
        eval_t0 = time.time()
        stats = {}
        all_texts = []
        rm_scores = []
        use_rm = self.reward_fn is None and getattr(self, "has_reward_model", False)
        if jax.process_count() > 1:
            # The loop below runs collectives per batch — if per-process eval
            # pipelines held different row counts, processes would iterate
            # different batch counts and deadlock in the gather. Fail loudly
            # up front instead.
            from trlx_tpu.parallel.mesh import allgather_host

            counts = allgather_host(
                np.asarray([len(self.eval_dataloader)], dtype=np.int32)
            ).reshape(-1)
            if len(set(int(c) for c in counts)) != 1:
                raise RuntimeError(
                    f"eval dataloader length differs across processes: {counts.tolist()} "
                    "— every host must hold the same number of eval batches"
                )
        clock = Clock()
        for batch, n_valid in self.eval_dataloader.iter_with_valid():
            tokens, mask = self.rollout_generate(batch["input_ids"], batch["attention_mask"])
            if use_rm:
                rm_scores.append(
                    self._gather_valid_rows(self.rm_eval_scores(tokens, mask), n_valid)
                )
            t, m = self._gather_valid_rows((tokens, mask), n_valid)
            all_texts.extend(self.decode(t, m))
        stats["generate_time"] = clock.tick()

        if not is_main_process():
            return stats

        columns = ["sample"]
        rows = [[t] for t in all_texts]
        rewards = None
        if use_rm:
            rewards = np.concatenate(rm_scores).astype(np.float32)
        elif self.reward_fn is not None:
            t0 = time.time()
            rewards = np.asarray(self.reward_fn(all_texts), dtype=np.float32)
            # own key — metric_fn below logs "metric_time" and must not
            # clobber (or be clobbered by) the reward timing
            stats["reward_time"] = time.time() - t0
        if rewards is not None:
            stats["mean_reward"] = float(np.mean(rewards))
            columns.append("reward")
            for row, r in zip(rows, rewards):
                row.append(float(r))
        if self.metric_fn is not None:
            t0 = time.time()
            metrics = self.metric_fn(all_texts)
            stats["metric_time"] = time.time() - t0
            for k, v in metrics.items():
                v = np.asarray(v)
                stats[f"metrics/{k}"] = float(np.mean(v))
                if v.ndim > 0 and len(v) == len(rows):
                    columns.append(k)
                    for row, item in zip(rows, v):
                        row.append(float(item))
        self.tracker.log_table("samples", columns, rows, step=self.iter_count)
        # Total wall spent in eval — the component timers above (generate/
        # reward/metric) undercount by the table/stat assembly; benchmarks
        # excluding eval cost should use this, matching a wall-clock wrapper
        # around the whole call (how the reference side is measured).
        stats["eval_wall_time"] = time.time() - eval_t0
        return stats

    # ----------------------------------------------------------------- learn

    def learn(self):
        """The training loop
        (reference: trlx/model/accelerate_base_model.py:203-256): epochs ×
        store batches × n_updates_per_batch jitted steps, with checkpoint/eval
        intervals and the PPO rollout/optimize alternation via
        post_epoch_callback."""
        self.prepare_learning()
        # True resume (the reference's checkpoints were save-only,
        # reference: trlx/model/__init__.py:101-129): the state was restored
        # in __init__ (before the first rollout); continue counting from it.
        self.iter_count = int(jax.device_get(self.state.step)) if self._resumed else 0
        if self.iter_count >= self.total_steps:
            return self.evaluate()  # nothing left to train

        # jax.profiler trace of a few steady-state steps (reference has
        # wall-clock timers only, SURVEY.md §5; XLA traces are the TPU-native
        # upgrade). The window is anchored to steps-since-learn-start, not the
        # absolute iter_count — a resumed run (iter_count restored > 2) still
        # profiles its own steps [2, 5): past this process's compilation,
        # short enough to inspect.
        profile_dir = self.config.train.profile_dir
        self._profiling = False
        learn_start = self.iter_count
        # Device-telemetry window anchor for trainers without a phase timer:
        # the first MFU window must span from HERE (covering every dispatch
        # whose FLOPs the monitor accumulated), not just the last step.
        self._telemetry_t0 = time.time()
        obs.anomaly.time_collections(True)
        self._ticker.start()
        self._open_step_window()
        obs_spans.set_iteration(0)

        def profiler_tick():
            if not profile_dir or not is_main_process():
                return
            local_step = self.iter_count - learn_start
            if local_step == 2 and not self._profiling:
                jax.profiler.start_trace(profile_dir)
                self._profiling = True
            elif self._profiling and local_step >= 5:
                jax.profiler.stop_trace()
                self._profiling = False

        # Preemption/failure handling the reference lacks entirely ("crash =
        # job death", SURVEY.md §5): SIGTERM (TPU preemption notice, k8s
        # eviction) requests a checkpoint at the next safe boundary, so a
        # resumable state lands before the VM disappears. Multi-host safe:
        # the local SIGTERM flag is only acted on after PROCESS AGREEMENT
        # (an any-reduce at each batch boundary, see _preemption_agreed) so
        # every host enters the collective orbax save together — an
        # unsynchronized per-process flag would deadlock a pod.
        self._preempted = False

        def on_sigterm(signum, frame):
            self._preempted = True

        old_handler = None
        handler_installed = False
        try:
            old_handler = signal.signal(signal.SIGTERM, on_sigterm)
            handler_installed = True
        except ValueError:  # not in main thread
            pass

        try:
            return self._learn_loop(profiler_tick)
        finally:
            # Pipeline machinery first: a live prefetch thread or rollout
            # producer must be stopped/joined before the checkpoint drain —
            # an early return (preemption, total_steps mid-epoch) leaves
            # them running otherwise.
            self._close_batch_feed()
            self._shutdown_experience_pipeline()
            self.end_progress()
            self._ticker.stop()
            obs.anomaly.time_collections(False)
            self._stall_log.close()
            if self._profiling:  # before the flush: a closed session's scope table is written by it
                jax.profiler.stop_trace()
                self._profiling = False
            obs_spans.flush()
            # An async interval save may still be in flight — its sidecars
            # (manifest, latest.txt) only land at finalize, so the exit path
            # must drain it or the checkpoint is invisible to resume.
            self._finalize_pending_save()
            if self._devicemon is not None:
                # Final registry persist: dispatches since the last window
                # boundary must still show in programs.json for the report.
                self._devicemon.flush()
            if self._fleet is not None:
                # Closes the arrival-record file (no thread to join); the
                # fleet artifacts stay on disk for read_fleet_spans and the
                # report's Fleet section.
                obs_fleet.shutdown()
                self._fleet = None
            if self._graftnum is not None:
                # No thread to join — clears the process-global instance and
                # any latched bisector injection so a later trainer in this
                # process starts clean.
                obs_numerics.shutdown()
                self._graftnum = None
            if self.heartbeat is not None:
                # Join the writer thread (a leaked trlx-heartbeat would fail
                # the drills' thread-cleanliness assertions); stop() flushes
                # one final record so post-mortem readers see the exit state.
                self.heartbeat.stop()
            if self._metrics_exporter is not None:
                # Exporter last: it only serves snapshots, so scrapers get
                # the final gauge state right up to teardown.
                self._metrics_exporter.close()
                self._metrics_exporter = None
            if handler_installed:
                # old_handler may be None (disposition installed outside
                # Python) — restore to default in that case rather than
                # leaking our handler.
                signal.signal(signal.SIGTERM, old_handler if old_handler is not None else signal.SIG_DFL)

    def _save_at_end(self):
        """The checkpoint `learn` leaves behind; `checkpoint_interval: 0`
        turns it off along with the periodic ones."""
        if self.config.train.checkpoint_interval > 0:
            self.save()

    def _save_on_preemption(self):
        self.save()
        self.tracker.log({"preempted_at_step": self.iter_count}, step=self.iter_count)

    def _preemption_agreed(self) -> bool:
        """True when ANY process has a pending SIGTERM.

        Multi-host: an any-reduce over the per-process flags — every host
        returns the same answer, so the collective checkpoint save is
        entered by all or by none (a TPU pod's preemption notice doesn't hit
        every VM at the same instant). Single-process: the local flag."""
        from trlx_tpu.parallel.mesh import allgather_host

        return bool(
            np.any(allgather_host(np.asarray([self._preempted], dtype=np.int32)))
        )

    # ------------------------------------------------------ pipelined batches

    def _prepare_batch(self, batch):
        """Host batch → (device_batch, host_extras). Host-only extras (the
        per-sample staleness column from the pipelined producer) are split
        off BEFORE put_batch so they never ride to device or change the
        jitted step's input pytree."""
        host_extras = None
        if getattr(batch, "extras", None) is not None:
            from dataclasses import replace

            host_extras = batch.extras
            batch = replace(batch, extras=None)
        return self.put_batch(batch), host_extras

    def _train_batch_feed(self):
        """One epoch's batch feed, yielding (device_batch, host_extras).

        Serial by default (put_batch inline, today's exact schedule). When
        the subclass enables the pipeline (PPO's overlap knobs), batches are
        staged through a PrefetchIterator so the host→device transfer for
        batch k+1 overlaps train_step(k). Multi-host note: put_batch's
        host_local_array_to_global_array is collective-free, so running it
        on the prefetch thread cannot interleave with main-thread
        collectives."""
        depth = 0
        if getattr(self, "overlap_rollouts", False):
            depth = max(0, int(getattr(self.config.method, "prefetch_depth", 0) or 0))
        if depth > 0:
            feed = PrefetchIterator(self.train_dataloader, self._prepare_batch, depth=depth)
        else:
            feed = SerialFeed(self.train_dataloader, self._prepare_batch)
        self._active_feed = feed
        return feed

    def _close_batch_feed(self):
        feed = getattr(self, "_active_feed", None)
        if feed is not None:
            self._active_feed = None
            feed.close()

    def _shutdown_experience_pipeline(self):
        """Stop background experience machinery (rollout producer, score
        worker) — no-op here; subclasses that arm them override."""

    def _learn_loop(self, profiler_tick):
        timer = getattr(self, "_phase_timer", None)
        for epoch in range(self.config.train.epochs):
            feed = self._train_batch_feed()
            while True:
                if timer is None:
                    # No rollout phases (ILQL): an iteration is one batch, its
                    # data wait and its step(s).
                    obs_spans.set_iteration(self.iter_count + 1)
                # put_batch already ran (inline via SerialFeed, or ahead of
                # time on the prefetch thread): this pop measures the
                # residual host→device blocking the train step pays.
                with trace_span("train/data_wait") as wait:
                    item = next(feed, None)
                if item is None:
                    break
                device_batch, host_extras = item
                if self._host_t0 is None:
                    self._host_t0 = wait.start_s  # first batch, or the first after a rollout
                self._data_s = getattr(self, "_data_s", 0.0) + wait.seconds
                self._last_batch_extras = host_extras
                # SIGTERM may land during the (long) rollout phase that
                # rebuilt this dataloader — checkpoint before spending a
                # further step on a doomed VM. Checked once per BATCH (not
                # per step): the agreement collective stays off the hot
                # step loop.
                if self._preemption_agreed():
                    self._save_on_preemption()
                    return None
                self._phase_exclude_s = 0.0  # eval/save wall inside the window
                stop = None
                with trace_span("train/batch") as batch_span:
                    for _ in range(self.n_updates_per_batch):
                        profiler_tick()
                        stop = self._train_one_step(device_batch)
                        if stop is not None:
                            break
                if stop == "preempted":
                    self._save_on_preemption()
                    return None
                if stop == "finished":
                    self._save_at_end()
                    return self.evaluate()
                if timer is not None:
                    train_dt = max(0.0, batch_span.seconds - self._phase_exclude_s)
                    timer.add("train", train_dt)
            self._close_batch_feed()
            self.post_epoch_callback()
            self._open_step_window()

        self._save_at_end()
        return self.evaluate()

    def _open_step_window(self):
        """The next step is the first of an iteration (at learn()'s start, after
        a rollout): its host window, and its window of process counters, start
        where its batch is asked for."""
        self._host_t0, self._step_wait_s = None, 0.0
        self._step_proc.open()

    def _train_one_step(self, device_batch):
        """One jitted update on ``device_batch`` and what follows it on the
        host. Returns None to go on, "preempted" or "finished" to stop; the
        caller saves and evaluates outside the step's spans."""
        with trace_span("train/step", step=self.iter_count + 1) as step_span:
            step_batch = device_batch
            if self.fault_plan and self.fault_plan.fire(
                "nan_grad", self.iter_count + 1
            ):
                # Injected numeric blow-up: NaN-poison the float
                # leaves of THIS step's batch (fault drill for the
                # on-device non-finite guard).
                step_batch = poison_nan(device_batch)
            if self.fault_plan and self.fault_plan.fire(
                "nan_layer", self.iter_count + 1
            ):
                # NaN-provenance drill: same batch poison (the guard
                # genuinely trips) PLUS a latched tap injection so the
                # graftnum bisector's re-forward must name that layer
                # as first-NaN. One @N gives both the step tick and
                # the target block (clamped to the model's depth).
                step_batch = poison_nan(device_batch)
                n_layer = int(self.model.cfg.n_layer)
                tap = f"block_{min(self.iter_count + 1, n_layer - 1)}"
                obs_numerics.latch_injection(tap)
            with trace_span("train/dispatch"), self._dispatch_lock, count_weight_gathers(
                self._weight_gathers["train"]
            ), count_head_calls(self._head_calls["train"]):
                prev_state = self.state
                self.state, stats = self.train_step(self.state, step_batch)
            # Donation handoff: train_step donates the old state
            # (donate_argnums=(0,)); record it so a stale host read
            # raises with this site named (no-op unless
            # TRLX_TPU_SANITIZE=donation).
            sanitize.mark_donated(prev_state, "train_step(state) [learn loop]")
            del prev_state
            self.iter_count += 1
            if self.heartbeat is not None:
                # Progress stamp (cheap attribute stores; the
                # heartbeat thread does the file I/O) — a host whose
                # stamp freezes here is the one the CollectiveTimeout
                # diagnostic will name.
                self.heartbeat.beat(step=self.iter_count, phase="train")
            self._fire_host_faults()

            # Every step gets the DEVICE stats dict (async, no sync):
            # subclasses buffer what they need (the adaptive KL
            # controller queues each step's mean_kl scalar and applies
            # the per-step updates at its next flush, so log_interval
            # no longer blinds or rescales the controller).
            self.post_backward_callback(stats)

            # Buffer this step's resilience scalars (un-fetched
            # device values — the same zero-sync discipline as the
            # KL buffer); flushed at log boundaries below.
            if self.watchdog is not None or "resilience/bad_steps" in stats:
                self._res_pending.append(
                    (
                        stats.get("loss"),
                        stats.get("resilience/nonfinite"),
                        stats.get("resilience/bad_steps"),
                    )
                )
                # Batch ref for the guard-skip census (popped in
                # lockstep by _flush_resilience). Kept ONLY when a
                # trip could produce an incident bundle — None
                # placeholders otherwise, so default runs pin no
                # extra device memory.
                self._res_batch_refs.append(
                    step_batch if self._incidents is not None else None
                )
                if len(self._res_pending) >= max(self.config.train.log_interval, 8):
                    self._flush_resilience()

            if self.fault_plan and self.fault_plan.fire("sigterm", self.iter_count):
                # Synthetic preemption notice (fault drill for the
                # SIGTERM save/resume path) — delivered for real so
                # the actual signal handler runs.
                os.kill(os.getpid(), signal.SIGTERM)

            intervals = self.intervals(self.iter_count)
            if intervals["do_checkpoint"]:
                # Interval saves follow train.async_checkpointing:
                # async dispatches the orbax write and returns — the
                # save overlaps training and only blocks at the next
                # save/exit (_finalize_pending_save).
                self.save(block=not self.config.train.async_checkpointing)
            if intervals["do_log"] or intervals["do_eval"]:
                self._log_step(stats, step_span, intervals)
        self._write_pending_stall()

        # Independent of the log cadence (a nested check would
        # silently thin the histograms to lcm(log, watch)).
        wi = self.config.train.watch_interval
        if wi and self.iter_count % wi == 0:
            self.log_param_watch()

        # Cross-host consistency guard: every N steps, compare
        # [step, replicated-param crc, rng crc] fingerprints and
        # raise HostDesync naming the diverged host — keyed on
        # iter_count so every host enters the collective at the
        # identical step.
        di = self.config.train.desync_check_interval
        if di and self.iter_count % di == 0:
            self._check_desync()

        # graftfleet clock resync: two tiny guarded allgathers
        # every train.fleet_resync_interval steps — collective,
        # keyed on iter_count so every host enters at the
        # identical step.
        if self._fleet is not None:
            self._fleet.maybe_resync(self.iter_count)

        # Mid-batch reaction is single-process by default: a
        # per-step agreement collective would tax the hot loop,
        # and a local-only save would deadlock a pod — pods
        # react at the next batch boundary, or every
        # train.preempt_check_interval steps when set (tighter
        # preemption windows at one tiny allgather per N steps).
        if jax.process_count() == 1 and self._preempted:
            return "preempted"
        pi = self.config.train.preempt_check_interval
        if (
            pi
            and jax.process_count() > 1
            and self.iter_count % pi == 0
            and self._preemption_agreed()
        ):
            return "preempted"

        if self.iter_count >= self.total_steps:
            return "finished"
        return None

    def _log_step(self, stats, step_span, intervals):
        """The log boundary of one step: the blocking stats read, then the
        record (health, export, tracker, progress line) and the evaluation."""
        # Reading stats forces a device sync — the price of
        # logging (per-step by default, as in the reference's
        # accelerator.log, reference:
        # trlx/model/accelerate_base_model.py:244). With
        # log_interval > 1 the device queue stays full
        # between logs. The wait for the step is a span of its own
        # (the resilience flush reads the same step's scalars, so armed it
        # pays the wait); pulling the scalars, one transfer each, is host
        # work the device idles through.
        with trace_span("train/stats_read") as read:
            with trace_span("train/stats_wait") as wait:
                self._flush_resilience()
                jax.block_until_ready(stats)
            stats_host = {k: float(v) for k, v in stats.items()}
        with trace_span("train/log"):
            self._write_step_record(stats_host, step_span, read, wait, intervals)
        if getattr(self, "_phase_timer", None) is None:
            obs_spans.flush()  # no rollout boundary (ILQL): the log boundary is the iteration's

    def _write_step_record(self, stats_host, step_span, read, wait, intervals):
        # step_time BEFORE any evaluate(): the stats read synced the
        # step; folding eval seconds in would make the logged throughput
        # wrong by orders of magnitude on eval steps. From the step's span
        # start (dispatch) to the end of the read, both already on record.
        stats_host["step_time"] = read.end_s - step_span.start_s
        # Host wall from the end of the previous stats read to the end of this
        # one (the first step after a rollout: since its batch was asked for)
        # less the waits for the device (this read's, and what a callback
        # added to _step_wait_s), evaluation left out: the log, the loop's
        # tail, the data wait, the dispatch and the pull of the stats. With
        # log_interval 1 the device idles through nearly all of it.
        waited = wait.seconds + self._step_wait_s
        stats_host["time/step_host_ms"] = max(0.0, read.end_s - self._host_t0 - waited) * 1e3
        self._host_t0, self._step_wait_s = read.end_s, 0.0
        stats_host["obs/compiles"] = obs_spans.take_compiles()
        stats_host.update(startup_counters())
        if self._flash_kept_share is not None:
            stats_host["flash/kept_pair_share"] = self._flash_kept_share
            # the step's own stats carry it where padding can take a chunk out
            # (models/lm.py flash_pad_dead_chunk_share); 0.0 by the rule elsewhere
            stats_host.setdefault("flash/pad_dead_chunk_share", 0.0)
        if "moe/held_slot_share" in stats_host:
            from trlx_tpu.models.moe import pass_tokens, rows_per_held_expert, sum_rows_per_token

            cfg, tokens = self.model.cfg, self.config.train.batch_size * self.config.train.seq_length
            shapes = (tokens, cfg.experts_per_token, cfg.held_experts[1], cfg.n_experts)
            stats_host["moe/passes"] = tokens // pass_tokens(*shapes)
            stats_host["moe/rows_per_held_expert"] = rows_per_held_expert(stats_host["moe/held_slot_share"], *shapes)
            stats_host["moe/sum_rows_per_token"] = sum_rows_per_token(*shapes)
        gather_share = weight_gather_share(self._weight_gathers["train"])
        if gather_share is not None:
            stats_host["parallel/weight_gather_share"] = gather_share
        stats_host.update(take_head_call_scalars(self._head_calls["train"], "train"))
        self._observe_step(stats_host, step_span, read, waited)
        if self._devicemon is not None and getattr(self, "_phase_timer", None) is None:
            # Trainers without a phase timer (ILQL) flush the
            # device telemetry here; PPO flushes at its
            # rollout-window boundary (_log_phase_window)
            # where the true per-phase seconds live.
            now = read.end_s
            since = now - self._telemetry_t0
            self._telemetry_t0 = now
            stats_host.update(
                self._flush_device_telemetry(
                    {"train": since, "wall": since}
                )
            )
        stats_host["samples_per_sec"] = (
            self.config.train.batch_size / max(stats_host["step_time"], 1e-9)
        )
        # Cumulative host→device batch-transfer seconds since
        # the last log (phase attribution: the "data" phase).
        stats_host["data_time"] = getattr(self, "_data_s", 0.0)
        self._data_s = 0.0
        if intervals["do_eval"]:
            stats_host.update(self.evaluate())
            # Eval wall must not count as train-phase time in
            # the overlap window (single-host reads it back;
            # non-main pod hosts return a reduced stats dict).
            self._phase_exclude_s += stats_host.get("eval_wall_time", 0.0)
            self._host_t0 += stats_host.get("eval_wall_time", 0.0)
        extras = getattr(self, "_last_batch_extras", None)
        if extras:
            # Host-side batch metadata (e.g. the staleness
            # column from the pipelined producer): log-boundary
            # stats only, never device traffic.
            for k, v in extras.items():
                v = np.asarray(v)
                stats_host[f"{k}/mean"] = float(v.mean())
                stats_host[f"{k}/max"] = float(v.max())
        if self._graftnum is not None:
            # Numerics feed BEFORE the health gauges merge:
            # the grad-spike / update-ratio detectors judge
            # this record's num/* scalars, so their
            # health/*_state gauges below reflect THIS step.
            self._graftnum.observe_train(stats_host)
        if self._health is not None:
            # Health feed: judge the synced per-step stats,
            # then ride the health/* gauges along in the same
            # record. The entropy_collapse drill latches here
            # (stats-only — training never sees it).
            if self.fault_plan and self.fault_plan.fire(
                "entropy_collapse", self.iter_count
            ):
                self._health.inject_entropy_collapse()
            kl_ctl = getattr(self, "kl_ctl", None)
            self._health.observe_train(
                stats_host,
                self.iter_count,
                kl_coef=getattr(kl_ctl, "value", None),
                kl_target=getattr(kl_ctl, "target", None),
                kl_init_coef=getattr(
                    self.config.method, "init_kl_coef", None
                ),
            )
            stats_host.update(self._health.gauges())
            self._health.maybe_log_lineage(
                self.tracker, self.iter_count
            )
        if self._graftnum is not None:
            # Quant-error gauges from the latest weight
            # handoff; detector states ride along only when
            # no health monitor already emits them.
            stats_host.update(
                self._graftnum.gauges(
                    include_states=self._health is None
                )
            )
        self._export_metrics(stats_host)
        if self._fleet is not None:
            # Fleet window rollup AFTER _export_metrics'
            # collective gather: the fleet/* keys exist only
            # on process 0, and mismatched key sets across
            # hosts would misalign the rollup's allgather.
            stats_host.update(
                self._fleet.on_log_boundary(
                    self.iter_count,
                    exporter=self._metrics_exporter,
                )
            )
        self.tracker.log(stats_host, step=self.iter_count)
        self.progress_line(stats_host)

    def _observe_step(self, stats_host, step_span, read, waited):
        """The flight recorder's part of a step record: `stall/*` from the
        detector, `proc/*` over the step's host window (the previous stats
        read to this one), and, on a breach, the stall record that
        `_write_pending_stall` writes once `train/step` has ended."""
        proc = self._step_proc.close()
        breach = self._anomaly.observe(stats_host["step_time"], waited)
        stats_host["stall/excess_s"] = breach.excess_s if breach else 0.0
        stats_host["stall/wait_excess_s"] = breach.wait_excess_s if breach else 0.0
        stats_host["proc/nivcsw"] = proc["nivcsw"]
        stats_host["proc/cpu_s"] = proc["cpu_s"]
        stats_host["proc/tick_gap_max_s"] = proc["tick_gap_max_s"]
        if not breach:
            return
        self._pending_stall = breach, obs.anomaly.stall_record(
            "train_step",
            breach,
            proc,
            step=self.iter_count,
            iter=obs_spans.iteration(),
            t0=step_span.start_s,
            t1=read.end_s,
            seconds=stats_host["step_time"],
            wait_s=waited,
            host_ms=stats_host["time/step_host_ms"],
            compiles=stats_host["obs/compiles"],
        )

    def _write_pending_stall(self):
        """After `train/step` has ended (its record is in the spans' ring by
        then; PPO: after the boundary's spans, for a rollout): the line in
        stalls.jsonl where the step passed STALL_FACTOR, the incident bundle
        where it passed train.anomaly_factor."""
        if self._pending_stall is None:
            return
        (breach, (record, window)), self._pending_stall = self._pending_stall, None
        bundle = (
            breach.bundle and self._incidents is not None and self._incidents.captured < self._incidents.max_incidents
        )
        if breach.excess_s > 0:
            record = self._stall_log.write(record, window, keep=bundle) or record
        if bundle:
            self._incidents.capture(
                record["step"],
                "slow_step",
                detail={"step_time": record["seconds"], "p50": breach.p50, "factor": self._anomaly.bundle_factor},
                stall=record,
            )

    # ------------------------------------------------------------ checkpoint

    def host_state_dict(self) -> dict:
        """Host-side Python state that a true resume must also restore
        (subclasses extend — PPO adds the adaptive KL coefficient)."""
        self._flush_resilience(allow_rollback=False)  # counters up to date
        return {
            "rng": [int(x) for x in np.asarray(jax.device_get(self.rng)).reshape(-1)],
            "resilience": {
                "skipped_steps": int(self.skipped_steps),
                "rollbacks": int(self._rollbacks),
                "lr_scale": float(self._lr_scale),
            },
        }

    def load_host_state(self, d: dict):
        """Called during __init__-time resume — subclass state that doesn't
        exist yet is re-applied from self.loaded_host_state afterwards."""
        self.loaded_host_state = d
        if "rng" in d:
            self.rng = jnp.asarray(np.asarray(d["rng"], dtype=np.uint32))
        res = d.get("resilience", {})
        if res:
            self.skipped_steps = int(res.get("skipped_steps", self.skipped_steps))
            # Monotone merges, NOT plain overwrites: a watchdog rollback
            # restores an OLDER checkpoint whose host state predates the
            # rollback itself — taking its (lower) rollback count or (higher)
            # lr_scale verbatim would reset the safety budget and un-decay
            # the LR, making a divergence loop unbounded.
            self._rollbacks = max(self._rollbacks, int(res.get("rollbacks", 0)))
            scale = min(self._lr_scale, float(res.get("lr_scale", 1.0)))
            if scale != self._lr_scale:
                self._lr_scale = scale
                self._rebuild_for_lr_scale()

    # ------------------------------------------------------------ resilience

    def _flush_resilience(self, allow_rollback: bool = True):
        """Drain the buffered per-step resilience scalars in ONE host sync.

        Per buffered step: count skipped (non-finite) updates, abort after
        ``train.max_bad_steps`` CONSECUTIVE skips, and feed the loss to the
        divergence watchdog — which may trigger a checkpoint rollback
        (suppressed with ``allow_rollback=False`` when called from inside
        save/host_state_dict, where a rollback would recurse)."""
        if not self._res_pending:
            return
        pending, self._res_pending = self._res_pending, []
        batch_refs, self._res_batch_refs = self._res_batch_refs, []
        if len(batch_refs) < len(pending):
            # Refs are best-effort (a subclass step that bypasses the learn
            # loop appends none) — pad rather than misalign the zip.
            batch_refs = batch_refs + [None] * (len(pending) - len(batch_refs))
        max_bad = self.config.train.max_bad_steps
        skips_before = self.skipped_steps
        offending_batch = None
        for (loss, nonfinite, bad), batch in zip(jax.device_get(pending), batch_refs):
            if nonfinite is not None and float(nonfinite) > 0:
                self.skipped_steps += 1
                if offending_batch is None:
                    # First tripped step in the window: the batch the NaN
                    # census re-derives gradients from.
                    offending_batch = batch
            if bad is not None and max_bad > 0 and int(bad) >= max_bad:
                raise TrainingDiverged(
                    f"{int(bad)} consecutive non-finite train steps (>= "
                    f"train.max_bad_steps={max_bad}) around step "
                    f"{self.iter_count} — persistent numeric blow-up, not a "
                    "one-off bad batch. Lower the learning rate, tighten "
                    "train.grad_clip, or inspect the data; raise "
                    "train.max_bad_steps only if skips are expected."
                )
            if (
                allow_rollback
                and self.watchdog is not None
                and loss is not None
                and self.watchdog.observe(float(loss))
            ):
                # Remaining observations predate the rollback — drop them.
                self._rollback()
                return
        if self.skipped_steps != skips_before:
            obs_spans.instant(
                "guard_skip", step=self.iter_count, skipped=int(self.skipped_steps)
            )
            incidents = getattr(self, "_incidents", None)
            if incidents is not None:
                bundle_dir = incidents.capture(
                    self.iter_count,
                    "guard_skip",
                    detail={"skipped_steps": int(self.skipped_steps)},
                )
                if bundle_dir and offending_batch is not None:
                    self._capture_numerics(bundle_dir, offending_batch)
            if getattr(self, "tracker", None) is not None:
                self.tracker.log(
                    {"resilience/skipped_steps": float(self.skipped_steps)},
                    step=self.iter_count,
                )

    def _capture_numerics(self, bundle_dir: str, batch):
        """NaN-provenance artifact for a guard-skip incident bundle
        (trlx_tpu/observability/numerics.py). Two parts, both incident-path
        only — the hot step is never touched:

        - grad census: the jitted step donated its gradient tree, so
          re-derive it EAGERLY from the stored loss_fn on the offending
          microbatch and name every nonfinite leaf by param path. Runs
          whenever the trainer exposes ``_numerics_loss_fn`` — i.e. even
          with graftnum disarmed, a nonfinite_guard trip still gets leaf
          provenance in its bundle.
        - forward bisect (graftnum armed only): re-run the forward with the
          probe taps live and record the FIRST layer producing NaN/Inf —
          consuming any fault-drill injection latched by ``nan_layer@N``."""
        payload = {"step": int(self.iter_count), "reason": "guard_skip"}
        loss_fn = getattr(self, "_numerics_loss_fn", None)
        if loss_fn is not None:
            try:
                with self._dispatch_lock:
                    grads = jax.grad(lambda p: loss_fn(p, batch)[0])(
                        self.state.params
                    )
                payload["grad_census"] = obs_numerics.nonfinite_census(grads)
            except Exception as e:  # incident path must never kill training
                payload["grad_census"] = {"error": repr(e)}
        if obs_numerics.enabled() and hasattr(self, "_numerics_forward"):
            with self._dispatch_lock:
                payload["forward_bisect"] = obs_numerics.bisect_forward(
                    lambda: self._numerics_forward(batch),
                    inject=obs_numerics.consume_injection(),
                )
        obs_numerics.write_incident(bundle_dir, payload)

    def _fire_host_faults(self):
        """Per-PROCESS fault drills (trlx_tpu/resilience/faults.py): each
        worker reads its OWN ``TRLX_TPU_FAULTS`` env, so a 2-process drill
        can slow/diverge/hang/kill one host and exercise the detection
        machinery (collective_guard, desync guard, heartbeats) on the rest."""
        if not self.fault_plan:
            return
        step = self.iter_count
        if self.fault_plan.fire("slow_step", step):
            # Synthetic straggler STEP (vs. slow_host's straggler HOST): the
            # stall sits between this step's dispatch and its log-boundary
            # stats sync, so the measured step_time inflates past the
            # anomaly detector's rolling-p50 gate — the CPU drill for the
            # incident-capture path (step N must be a logged step).
            time.sleep(float(os.environ.get("TRLX_TPU_SLOW_STEP_SECONDS", "1")))
        if self.fault_plan.fire("slow_host", step):
            # Straggler, not a death: long enough to dominate a stall
            # report, short enough (vs. a sane deadline) not to abort.
            time.sleep(float(os.environ.get("TRLX_TPU_SLOW_SECONDS", "2")))
        if self.fault_plan.fire("host_desync", step):
            # Silent state divergence on THIS host only: perturb the local
            # replicas of one replicated param leaf — no collective, the
            # other hosts keep the original values — for the fingerprint
            # guard to catch within one check period.
            self.state = self.state.replace(
                params=dist_res.perturb_local_replicas(self.state.params)
            )
        if self.fault_plan.fire("host_hang", step):
            # Alive-but-wedged: the daemon heartbeat thread keeps writing
            # (written_t advances) while the progress stamp freezes — the
            # exact signature stall_report uses to name this host when the
            # peers' collective_guard deadlines fire.
            if self.heartbeat is not None:
                self.heartbeat.beat(step=step, phase="fault:host_hang")
            time.sleep(float(os.environ.get("TRLX_TPU_HANG_SECONDS", "3600")))
        if self.fault_plan.fire("host_kill", step):
            # Hard death: no cleanup, no final heartbeat — peers see the
            # heartbeat file age out and their next collective deadline.
            os._exit(1)

    def _check_desync(self):
        """Cross-host consistency guard: allgather and compare each host's
        [step counter, replicated-param crc32, rng crc32] fingerprint.
        Every host sees the identical gathered matrix, so a mismatch raises
        the identical HostDesync (naming the diverged host) everywhere — a
        coordinated abort, never a one-sided hang."""
        if jax.process_count() == 1:
            return
        fingerprint = dist_res.host_fingerprint(
            self.iter_count, self.state.params, rng=self.rng
        )
        fleet = getattr(self, "_fleet", None)
        if fleet is not None:
            # Cache BEFORE the verify: on a desync abort the bundle must
            # show the fingerprint this host brought to the comparison.
            fleet.note_fingerprint(self.iter_count, fingerprint)
        try:
            dist_res.verify_fingerprints(fingerprint)
        except dist_res.HostDesync as e:
            if fleet is not None:
                fleet.incident_bundle(
                    self.iter_count, "host_desync", detail=str(e)
                )
            raise
        if fleet is not None:
            fleet.note_desync(self.iter_count, ok=True)

    def _rollback(self):
        """Divergence watchdog response: restore the last intact checkpoint,
        decay the LR, and resume — aborting after ``train.max_rollbacks``."""
        self._rollbacks += 1
        # Capture BEFORE the restore mutates state (and before the
        # max_rollbacks abort below): the bundle's thread stacks / memory
        # show the run AT the divergence, which is what post-mortems need.
        obs_spans.instant("watchdog_rollback", step=self.iter_count)
        incidents = getattr(self, "_incidents", None)
        if incidents is not None:
            incidents.capture(
                self.iter_count,
                "watchdog_rollback",
                detail={"rollbacks": int(self._rollbacks)},
            )
        t = self.config.train
        if self._rollbacks > t.max_rollbacks:
            raise TrainingDiverged(
                f"divergence watchdog fired after {t.max_rollbacks} rollback(s) "
                "already spent — training is not recovering. Lower the "
                "learning rate / tighten train.grad_clip, or raise "
                "train.max_rollbacks if the loss spikes are believed transient."
            )
        self.end_progress()
        if is_main_process():
            print(
                f"[trlx_tpu.resilience] divergence watchdog fired at step "
                f"{self.iter_count} — rolling back "
                f"({self._rollbacks}/{t.max_rollbacks})",
                file=sys.stderr,
                flush=True,
            )
        try:
            self.load()
        except CheckpointError as e:
            raise TrainingDiverged(
                f"divergence watchdog fired at step {self.iter_count} but no "
                f"restorable checkpoint exists to roll back to: {e}"
            ) from e
        if t.watchdog_lr_decay < 1.0:
            self._lr_scale *= t.watchdog_lr_decay
            self._rebuild_for_lr_scale()
        self.watchdog.reset()
        self._res_pending = []
        self._res_batch_refs = []
        self.iter_count = int(jax.device_get(self.state.step))
        if getattr(self, "tracker", None) is not None:
            self.tracker.log(
                {
                    "resilience/rollback_to_step": float(self.iter_count),
                    "resilience/rollbacks": float(self._rollbacks),
                    "resilience/lr_scale": float(self._lr_scale),
                },
                step=self.iter_count,
            )

    def save(self, directory: Optional[str] = None, block: bool = True):
        """Orbax sharded checkpoint of the FULL TrainState (params, optimizer
        moments, step, extras) plus host-side state (RNG, KL controller) — a
        true resume point, unlike the reference's save-only
        accelerator.save_state
        (reference: trlx/model/accelerate_base_model.py:126-128).

        ``block=False`` honors train.async_checkpointing: the orbax write is
        dispatched and training continues; the sidecars (host state,
        manifest, latest.txt) land at `_finalize_pending_save` — i.e. at the
        next save, rollback, load, or learn-loop exit. Crash-consistent by
        construction: latest.txt is only repointed AFTER the data is fully
        committed, so a crash mid-async-save leaves the previous checkpoint
        as the resume point."""
        # Covers exactly the wall the train loop PAID: through finalize when
        # blocking, dispatch-only when async (the deferred commit then shows
        # up as its own ckpt/finalize span).
        with trace_span("ckpt/save", blocking=bool(block)) as span:
            directory = os.path.abspath(directory or self.config.train.checkpoint_dir)
            self._finalize_pending_save()  # at most one save in flight
            name = f"state_{int(jax.device_get(self.state.step))}"
            span.args["ckpt"] = name
            self._save_count += 1
            self._pending_save = {
                "directory": directory,
                "name": name,
                "t0": time.time(),
                "save_index": self._save_count,
                # Captured NOW — by finalize time the host state (RNG, KL
                # coefficient) may have advanced past this checkpoint's step.
                "host_state": self.host_state_dict(),
            }
            self._checkpointer().save(os.path.join(directory, name), self.state, force=True)
            if block:
                self._finalize_pending_save()

    def _finalize_pending_save(self):
        """Drain the in-flight async save: wait for the orbax commit, then
        atomically write host state + manifest + latest.txt (in that order —
        the pointer flips last), apply the retention policy, and fire any
        ckpt_corrupt fault."""
        pending, self._pending_save = self._pending_save, None
        if pending is None:
            return None
        with trace_span("ckpt/finalize", ckpt=pending["name"]):
            directory, name = pending["directory"], pending["name"]
            self._checkpointer().wait_until_finished()
            if jax.process_count() > 1:
                # All-hosts-committed barrier: every host's shards are on disk
                # before rank 0 writes the sidecars and flips latest.txt — the
                # pointer must never lead a straggler host's data, or a
                # preemption save could advertise a checkpoint missing shards.
                barrier(f"ckpt_commit_{name}")
            if getattr(self, "tracker", None) is not None:
                self.tracker.log(
                    {"save_time": time.time() - pending["t0"]}, step=self.iter_count
                )
            if is_main_process():
                step = ckpt_util.checkpoint_step(name)
                ckpt_util.atomic_write_json(
                    os.path.join(directory, f"{name}.host.json"), pending["host_state"]
                )
                ckpt_util.write_manifest(directory, name, step if step is not None else 0)
                # basename, not abspath: checkpoint dirs get synced/remounted
                # between the preempted VM and its replacement. Written LAST and
                # atomically — a crash anywhere above leaves the old pointer.
                ckpt_util.atomic_write_text(os.path.join(directory, "latest.txt"), name)
                if self.fault_plan and self.fault_plan.fire(
                    "ckpt_corrupt", pending["save_index"]
                ):
                    rel = ckpt_util.corrupt_checkpoint(directory, name)
                    print(
                        f"[trlx_tpu.resilience] injected checkpoint corruption: "
                        f"truncated {name}/{rel}",
                        file=sys.stderr,
                    )
                ckpt_util.gc_checkpoints(
                    directory, self.config.train.keep_checkpoints, protect=(name,)
                )
            if jax.process_count() > 1:
                # Visibility barrier: no host returns (and, on a preemption
                # save, exits) until rank 0's pointer flip is durable — every
                # host's view of "the save is done" includes latest.txt.
                barrier(f"ckpt_visible_{name}")
        return name

    def save_pretrained(self, out_dir: str, family: Optional[str] = None):
        """Export the trained policy trunk as an ordinary HuggingFace
        checkpoint (+ RL heads in trlx_tpu_heads.npz) — the handoff to the
        HF serving/eval ecosystem the reference leaves to manual
        Accelerate-state unwrapping
        (reference: trlx/model/accelerate_base_model.py:126-128).

        Pod-safe: on multi-host meshes each param leaf is replicated through
        a one-leaf jitted identity (every host participates in the SPMD
        all-gather over ICI/DCN), materialized to host memory, and only
        rank 0 accumulates the full tree and writes the HF directory — other
        hosts hold at most one leaf at a time. Returns out_dir on rank 0,
        None elsewhere; all hosts leave together (barrier)."""
        from trlx_tpu.models.hf_export import export_hf

        if jax.process_count() == 1:
            params = jax.device_get(self.state.params)
        else:
            params = self._gather_params_to_main()

        result = None
        if params is not None:  # rank 0 (or single host)
            heads = {k: v for k, v in params.items() if k != "transformer"}
            result = export_hf(
                params, self.model.cfg, out_dir, family=family, head_params=heads
            )
        barrier()  # non-writing hosts wait for the export to land
        return result

    def _gather_params_to_main(self):
        """Replicate each param leaf across the mesh and pull it to host on
        rank 0. Leaf-at-a-time keeps device overhead to one replicated leaf
        and non-main host memory O(largest tensor) — the export-side mirror
        of the streamed safetensors import (models/hf_import.py)."""
        from jax.sharding import NamedSharding, PartitionSpec

        replicate = jax.jit(lambda x: x, out_shardings=NamedSharding(self.mesh, PartitionSpec()))
        main = is_main_process()

        def pull(leaf):
            rep = replicate(leaf)
            # A replicated multihost array is NOT fully addressable from one
            # process — read the local shard (which holds the full value).
            host = np.asarray(rep.addressable_data(0)) if main else None
            del rep  # free the replicated device copy before the next leaf
            return host

        tree = jax.tree_util.tree_map(pull, self.state.params)
        return tree if main else None

    def load(self, directory: Optional[str] = None):
        """Restore a TrainState + host state saved by `save` (resume support
        the reference lacks).

        Hardened: candidates are tried newest-first starting from the
        latest.txt pointer; each is manifest-verified (truncated / corrupted
        files fail BEFORE the orbax restore) and a failed restore falls back
        to the previous intact checkpoint. Raises CheckpointError with the
        full attempt log when nothing is restorable — instead of the raw
        FileNotFoundError / orbax traceback a missing or half-written
        checkpoint used to produce."""
        import json

        with trace_span("ckpt/load") as span:
            self._finalize_pending_save()  # a pending async save IS the latest
            directory = os.path.abspath(directory or self.config.train.checkpoint_dir)
            latest_path = os.path.join(directory, "latest.txt")
            latest = None
            if os.path.exists(latest_path):
                with open(latest_path) as f:
                    latest = f.read().strip() or None

            # Candidate order: the latest pointer first, then every other
            # state_* directory newest-step-first.
            candidates = []
            if latest is not None:
                candidates.append(latest)
            for name in ckpt_util.list_checkpoints(directory):
                if name != os.path.basename(candidates[0] if candidates else ""):
                    candidates.append(name)
            if not candidates:
                raise CheckpointError(
                    f"no checkpoint found in {directory}: "
                    + ("latest.txt is empty" if os.path.exists(latest_path) else "latest.txt is missing")
                    + " and no state_* directories exist — nothing to resume from "
                    "(set train.resume_from_checkpoint=False to start fresh, or "
                    "point train.checkpoint_dir at the directory that holds the run)"
                )

            attempts = []
            for i, cand in enumerate(candidates):
                name = os.path.basename(cand)
                # Older checkpoints stored an absolute path; fall back to its
                # basename under the current directory when it moved.
                path = (
                    cand
                    if os.path.isabs(cand) and os.path.exists(cand)
                    else os.path.join(directory, name)
                )
                # In-use marker: another process GC-ing this directory (e.g. a
                # concurrent run finalizing its own save) must not delete a
                # candidate out from under the verify/restore below.
                with ckpt_util.mark_in_use(os.path.dirname(path), name):
                    if not os.path.isdir(path):
                        ok, reason = False, "checkpoint directory missing"
                    else:
                        ok, reason = ckpt_util.verify_checkpoint(os.path.dirname(path), name)
                    if jax.process_count() > 1:
                        # Cross-host agreement BEFORE the collective restore:
                        # the orbax restore must be entered by every host or by
                        # none, and a checkpoint torn on ONE host's view of the
                        # filesystem fails the candidate for ALL — otherwise
                        # the fleet deadlocks split across two candidates.
                        from trlx_tpu.parallel.mesh import allgather_host

                        oks = allgather_host(np.asarray([ok], dtype=np.int32)).reshape(-1)
                        if not oks.all():
                            bad = [int(p) for p in np.flatnonzero(oks == 0)]
                            attempts.append(
                                f"{name}: failed verification on host(s) {bad}"
                                + (f" (local: {reason})" if not ok else "")
                            )
                            continue
                    elif not ok:
                        attempts.append(f"{name}: {reason}")
                        continue
                    try:
                        self.state = self._checkpointer().restore(path, self.state)
                    except Exception as e:  # noqa: BLE001 — fall back to older checkpoint
                        attempts.append(f"{name}: orbax restore failed ({type(e).__name__}: {e})")
                        continue
                    self.last_restore_fallback = i > 0
                    if i > 0 and is_main_process():
                        print(
                            f"[trlx_tpu.resilience] latest checkpoint unusable "
                            f"({'; '.join(attempts)}) — fell back to {name}",
                            file=sys.stderr,
                        )
                    host_file = f"{path}.host.json"
                    if os.path.exists(host_file):
                        with open(host_file) as f:
                            self.load_host_state(json.load(f))
                    span.args.update(ckpt=name, fallback=bool(i > 0))
                    return self.state

            raise CheckpointError(
                f"no restorable checkpoint in {directory} — every candidate "
                f"failed verification or restore: {'; '.join(attempts)}. "
                "If the data is gone, set train.resume_from_checkpoint=False to "
                "start fresh."
            )

    # ------------------------------------------------------- BaseRL protocol

    def act(self, data):
        tokens, mask = self.rollout_generate(data["input_ids"], data["attention_mask"])
        return tokens, mask

    def sample(self, prompts, length: int = None, n_samples: int = None):
        """Sample continuations (reference protocol:
        trlx/model/__init__.py:57-71). `n_samples` rows are produced by tiling
        or truncating the prompt batch; `length` clips the response region to
        at most the compiled response length (XLA shapes are static, so a
        request longer than `method.gen_kwargs` max tokens is clipped — with a
        one-time warning — not recompiled). Note each NOVEL padded batch shape
        (after rounding up to the mesh data axes) compiles a fresh generate
        program; reuse batch sizes to stay on the cached executable."""
        ids = np.asarray(prompts["input_ids"])
        mask = np.asarray(prompts["attention_mask"])
        n = n_samples if n_samples is not None else ids.shape[0]
        data = int(np.prod([self.mesh.shape[a] for a in DATA_AXES]))
        gen_rows = -(-n // data) * data
        reps = -(-gen_rows // ids.shape[0])
        ids = np.tile(ids, (reps, 1))[:gen_rows]
        mask = np.tile(mask, (reps, 1))[:gen_rows]
        tokens, out_mask = self.rollout_generate(ids, mask)
        tokens = np.asarray(tokens)[:n]
        if length is not None:
            P = ids.shape[1]
            compiled = tokens.shape[1] - P
            if int(length) > compiled and not getattr(self, "_warned_sample_clip", False):
                self._warned_sample_clip = True
                warnings.warn(
                    f"sample(length={int(length)}) exceeds the compiled response "
                    f"length {compiled}; output is clipped to {compiled} new tokens "
                    "(raise method.gen_kwargs max tokens to generate more)",
                    stacklevel=2,
                )
            end = P + min(int(length), compiled)
            tokens = tokens[:, :end]
        return tokens

"""Top-level config system: YAML → nested dataclasses.

Mirrors the reference's three-section config (model/train/method —
reference: trlx/data/configs.py:126-140) and flattening ``to_dict``
(reference: trlx/data/configs.py:142-149), with TPU-first extensions:

- ``ModelConfig`` carries compute/param dtypes, remat policy, and a
  from-scratch architecture dict (so toy models need no checkpoint).
- ``TrainConfig`` carries the mesh shape (dp/fsdp/tp/sp axis sizes) — the
  explicit replacement for the Accelerate/DeepSpeed runtime the reference
  delegates to (reference: trlx/model/accelerate_base_model.py:31).
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import yaml

from trlx_tpu.data.method_configs import MethodConfig, get_method


@dataclass
class ModelConfig:
    """Model architecture + loading (reference: trlx/data/configs.py:24-44).

    :param model_path: HF checkpoint name/path, or "" for from-scratch.
    :param tokenizer_path: tokenizer name/path; "" → tensor-prompt mode
        (no tokenizer, like examples/randomwalks.py in the reference).
    :param model_type: registered trainer name (e.g. "ppo", "ilql").
    :param num_layers_unfrozen: how many top transformer blocks train; the
        rest are frozen via optax update masking (the functional analogue of
        reference trlx/model/accelerate_base_model.py:49-64's requires_grad_).
    :param model_arch: from-scratch architecture overrides (n_layer, n_head,
        d_model, vocab_size, ...) — see trlx_tpu.models.lm.LMConfig.
    :param dtype: compute dtype ("bfloat16" on TPU; MXU-native).
    :param param_dtype: parameter storage dtype ("float32" master params).
    :param remat: rematerialize transformer blocks (trade FLOPs for HBM).
    :param reward_model_path / reward_model_arch: an ON-DEVICE learned reward
        model (LM + scalar head, scored at the last valid token) sharded with
        the same partition rules as the policy and evaluated inside the fused
        rollout-scoring program. Replaces the host `reward_fn` boundary — the
        only way to express a pod-scale RM (e.g. BASELINE.json's NeoX-20B PPO
        w/ learned RM; the reference can only call host Python on decoded
        text, reference: trlx/orchestrator/ppo_orchestrator.py:73).
    """

    model_path: str
    tokenizer_path: str = ""
    model_type: str = "ppo"
    num_layers_unfrozen: int = -1
    model_arch: Dict[str, Any] = field(default_factory=dict)
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = False
    # "full" | "dots" — see LMConfig.remat_policy.
    remat_policy: str = "full"
    # int8 decode KV cache (halves cache HBM traffic + memory; see
    # LMConfig.kv_cache_quant). Off by default.
    kv_cache_quant: bool = False
    # int8 weight-only decode (W8A16): rollout sampling reads int8 trunk
    # kernels (re-quantized from the live policy before each rollout phase);
    # training/scoring stay full precision. Off by default.
    decode_weight_quant: bool = False
    reward_model_path: str = ""
    reward_model_arch: Dict[str, Any] = field(default_factory=dict)

    @property
    def has_reward_model(self) -> bool:
        return bool(self.reward_model_path or self.reward_model_arch)

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return cls(**config)


@dataclass
class TrainConfig:
    """Training loop + runtime config (reference: trlx/data/configs.py:47-123).

    Reference fields kept 1:1 (total_steps..seed); TPU-native additions:

    :param mesh: axis sizes (dp, fsdp, tp, sp). -1 on one axis = "fill with
        remaining devices". Replaces WORLD_SIZE/accelerate config.
    :param seq_length: max total tokens (prompt + generation). STATIC under
        XLA: prompts are left-padded to ``seq_length - gen_length``.
    :param loss_dtype: dtype losses/logits softmax run in (fp32 for stability).
    """

    total_steps: int
    seq_length: int
    epochs: int
    batch_size: int

    lr_ramp_steps: int
    lr_decay_steps: int
    weight_decay: float
    learning_rate_init: float
    learning_rate_target: float
    opt_betas: Tuple[float, float] = (0.9, 0.95)

    checkpoint_interval: int = 1000
    eval_interval: int = 100
    # Read stats/log every N steps. Reading a jitted step's stats forces a
    # host⇄device sync; >1 keeps the device queue full between logs (the
    # reference reads a log_interval that its config never defines,
    # reference: trlx/model/__init__.py:137).
    log_interval: int = 1

    pipeline: str = "PromptPipeline"
    orchestrator: str = "PPOOrchestrator"

    project_name: str = "trlx_tpu"
    entity_name: Optional[str] = None
    checkpoint_dir: str = "ckpts"
    seed: int = 1000

    # --- TPU-native additions ---
    mesh: Tuple[int, int, int, int] = (-1, 1, 1, 1)  # (dp, fsdp, tp, sp)
    loss_dtype: str = "float32"
    grad_clip: float = 1.0
    resume_from_checkpoint: bool = False
    async_checkpointing: bool = True
    profile_dir: Optional[str] = None  # jax.profiler trace output, if set
    # wandb.watch-equivalent: every N steps log per-group parameter
    # histograms + per-group grad norms (0 = off). The reference's softprompt
    # example watches the model (reference:
    # examples/ppo_softprompt_sentiments.py:38-39).
    watch_interval: int = 0

    # --- resilience (trlx_tpu/resilience/) ---
    # On-device non-finite guard: the jitted train step skips the parameter
    # update (params/opt_state pass through unchanged) when grads or loss go
    # NaN/inf, and counts consecutive skips in TrainState.bad_steps.
    nonfinite_guard: bool = True
    # Abort with TrainingDiverged after this many CONSECUTIVE skipped steps
    # (persistent numeric blow-up, not a one-off bad batch). 0 disables.
    max_bad_steps: int = 8
    # Retention: keep only the N newest state_* checkpoints (the one
    # latest.txt points at is always kept). 0 = keep everything.
    keep_checkpoints: int = 0
    # Divergence watchdog: roll back to the last intact checkpoint when the
    # per-step loss exceeds ema + threshold*max(|ema|,1) for `patience`
    # consecutive observations. threshold 0 = watchdog off.
    watchdog_threshold: float = 0.0
    watchdog_patience: int = 4
    watchdog_ema_alpha: float = 0.9
    watchdog_warmup: int = 5
    # Multiply the learning rate by this on every rollback (1.0 = no decay).
    watchdog_lr_decay: float = 0.5
    # Abort with TrainingDiverged after this many watchdog rollbacks.
    max_rollbacks: int = 2
    # Host reward_fn hardening (PPO orchestrator): hang timeout in seconds
    # (0 = none), bounded retries, exponential backoff base.
    reward_fn_timeout: float = 0.0
    reward_fn_retries: int = 2
    reward_fn_backoff: float = 0.5
    # Fault-injection plan, e.g. "nan_grad@3,reward_exc@2,ckpt_corrupt@1,
    # sigterm@5" (see trlx_tpu/resilience/faults.py). The TRLX_TPU_FAULTS
    # env var overrides this field. Empty = no faults.
    fault_plan: str = ""

    # --- distributed resilience (trlx_tpu/resilience/distributed.py) ---
    # Write <checkpoint_dir>/heartbeats/host_<idx>.json every N seconds
    # (last step, phase, progress timestamp) — the data the CollectiveTimeout
    # diagnostic uses to name the slowest host. 0 = off.
    heartbeat_interval: float = 0.0
    # Abort (exit code 117, CollectiveTimeout diagnostic) when any blocking
    # host collective (allgather_host / to_local_host / barrier) outlives
    # this many seconds — a dead or wedged peer must fail the fleet fast,
    # not deadlock it. Set comfortably above the slowest legitimate
    # collective (first-call compilation included). 0 = no deadline.
    collective_deadline: float = 0.0
    # Cross-host consistency guard: every N train steps, allgather+compare a
    # [step, replicated-param crc32, rng crc32] fingerprint and raise
    # HostDesync naming the diverged host. 0 = off.
    desync_check_interval: int = 0
    # Also check the SIGTERM save-and-exit agreement every N train steps
    # (0 = batch boundaries only). Step-boundary observation tightens the
    # window between a preemption notice and the coordinated save at the
    # cost of one tiny allgather per N steps.
    preempt_check_interval: int = 0

    # --- disaggregated fleet (trlx_tpu/fleet/) ---
    # All knobs are inert unless method.fleet_disaggregate is set (and
    # validated to be so at trainer construction — see
    # trlx_tpu/fleet/topology.py). Each role runs as its OWN single-controller
    # job; the two jobs couple only through the shared fleet directory.
    #
    # Which role this process plays: "rollout" | "learner" | "" (= colocated:
    # both roles run serially in one process through the same stream/broadcast
    # transports — the bitwise-parity mode). The TRLX_TPU_FLEET_ROLE env var
    # overrides this field, so one config file serves both jobs of a drill.
    fleet_role: str = ""
    # Shared coupling directory holding the episode stream, the weight
    # broadcasts, the per-role heartbeats, and the abort record. "" defaults
    # to <checkpoint_dir>/fleet — fine colocated; disaggregated jobs with
    # per-role checkpoint_dirs must point BOTH at one shared path.
    fleet_dir: str = ""
    # Per-episode-batch stream read: seconds to wait for the next streamed
    # batch before one retry cycle (0 = 60s), bounded retries (0 = 2), and
    # the exponential backoff base between them (0 = 0.5s) — the
    # resilience/retry.py semantics, applied to the stream.
    fleet_episode_timeout: float = 0.0
    fleet_stream_retries: int = 0
    fleet_stream_backoff: float = 0.0
    # Declare the rollout role DEAD when its fleet heartbeat file goes
    # unwritten this long, and STALLED when the file is fresh but its
    # progress timestamp is older than this (0 = max(10x heartbeat_interval,
    # 10s)). Drives the learner's degraded-drain state machine.
    fleet_heartbeat_timeout: float = 0.0
    # Rollout-side deadline (collective_guard semantics, exit 117 on expiry)
    # on waiting for a weight broadcast the staleness gate requires
    # (0 = train.collective_deadline, else 60s).
    fleet_broadcast_deadline: float = 0.0
    # Elastic fleet (method.fleet_elastic): seconds a claimed work-unit
    # lease stays valid without a renewal before any peer may reclaim the
    # unit (0 = max(6x heartbeat_interval, 3s)). Renewals ride the
    # producer's progress heartbeat; drills shrink this to ~1s so a
    # reclaim fits the test budget.
    fleet_lease_ttl: float = 0.0

    # --- observability (trlx_tpu/observability/) ---
    # The span FILE: host-side spans from the train loop, the pipeline
    # threads, checkpointing, and the collective guards also land as Chrome
    # trace events in <checkpoint_dir>/spans.jsonl (one lane per thread per
    # host; open in Perfetto). TRLX_TPU_SPANS=1 overrides to on. The spans
    # themselves (profiler annotations, time/* keys) need no switch.
    trace_spans: bool = False
    # Compiled-cost telemetry: capture cost_analysis()/memory_analysis() at
    # each monitored program's first dispatch and derive per-window
    # obs/train_mfu_pct + kernel-routing/device-memory gauges in
    # metrics.jsonl. One synchronous AOT compile per program at first
    # dispatch (absorbed by the persistent compile cache,
    # utils/compile_cache.py).
    # TRLX_TPU_DEVICE_TELEMETRY=1 overrides to on.
    device_telemetry: bool = False
    # Anomaly capture: a step slower than anomaly_factor × rolling-p50 step
    # time (or a watchdog/guard event) writes a one-shot incident bundle —
    # thread stacks, device-memory snapshot, metrics tail, profiler trace —
    # under <checkpoint_dir>/incidents/<step>/. 0 disables the step-time
    # bundle (resilience-event capture still requires a factor > 0 to arm
    # the capture machinery). TRLX_TPU_ANOMALY_FACTOR overrides. The detector
    # itself runs in every run at 1.5x for stalls.jsonl (observability/
    # anomaly.py STALL_FACTOR); this factor is the same median's second
    # threshold.
    anomaly_factor: float = 0.0
    # Trailing window (observations) for the detector's rolling p50, and the
    # per-run cap on captured incident bundles.
    anomaly_window: int = 64
    max_incidents: int = 4
    # Training-health monitor (trlx_tpu/observability/health.py): streaming
    # detectors — reward drift vs a warmup baseline, KL-controller health,
    # entropy collapse, value explained variance, degenerate-rollout
    # sentinels — each with OK/WARN/CRIT hysteresis, health/* gauges in
    # metrics.jsonl, per-chunk lineage records in lineage.jsonl, and CRIT
    # escalation into the incident bundles. TRLX_TPU_HEALTH=1 overrides.
    health_monitor: bool = False
    # Observations the baseline-relative detectors (reward drift, entropy,
    # KL, explained variance) absorb before judging.
    health_warmup: int = 5
    # Hysteresis: consecutive bad observations before OK->WARN, consecutive
    # severity-2 observations before ->CRIT; de-escalation costs
    # health_warn_streak clean observations PER level.
    health_warn_streak: int = 2
    health_crit_streak: int = 4
    # Live exporter (trlx_tpu/observability/export.py): process 0 serves
    # Prometheus-text /metrics and JSON /healthz on this port while the run
    # is alive (0 = off). TRLX_TPU_METRICS_PORT overrides.
    metrics_port: int = 0
    # graftfleet (trlx_tpu/observability/fleet.py): cross-host trace
    # federation (per-host spans.host<k>.jsonl + a barrier-based clock-offset
    # estimator so read_fleet_spans merges one aligned Chrome trace),
    # collective straggler attribution (per-site arrival records ->
    # fleet/collective_skew_ms_* gauges + the FleetStragglerDetector), the
    # /healthz fleet block, and the HostDesync/CollectiveTimeout fleet
    # incident bundles. Implies span tracing while armed; single-process
    # arming degrades to a one-host fleet. Must be config-consistent across
    # hosts (the per-host metric rollup is collective).
    # TRLX_TPU_GRAFTFLEET=1 overrides.
    graftfleet: bool = False
    # Re-estimate the cross-host clock offsets every N train steps (two tiny
    # guarded allgathers per resync; the drift bound between resyncs is part
    # of the trace's stated alignment error). 0 = startup-only estimate.
    fleet_resync_interval: int = 0
    # graftnum (trlx_tpu/observability/numerics.py): streaming numerics
    # observatory — per-subtree grad/param-norm + update-ratio reductions
    # compiled into the train step (num/* gauges), NaN provenance on guard
    # trips (non-finite grad census + first-NaN layer bisection into the
    # incident bundle's numerics.json), int8 quantization-error gauges at
    # each weight-version handoff, and the grad-spike / update-ratio health
    # detectors. Disarmed hooks are one dict load — the serial path stays
    # byte-identical. TRLX_TPU_GRAFTNUM=1 overrides.
    graftnum: bool = False

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        cfg = dict(config)
        if "opt_betas" in cfg:
            cfg["opt_betas"] = tuple(cfg["opt_betas"])
        if "mesh" in cfg:
            cfg["mesh"] = tuple(cfg["mesh"])
        return cls(**cfg)


@dataclass
class TRLConfig:
    """Aggregate config (reference: trlx/data/configs.py:112-149)."""

    model: ModelConfig
    train: TrainConfig
    method: MethodConfig

    @classmethod
    def load_yaml(cls, yml_fp: str):
        """Load config from YAML (reference: trlx/data/configs.py:126-140)."""
        with open(yml_fp, mode="r") as file:
            config = yaml.safe_load(file)
        return cls.from_dict(config)

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return cls(
            model=ModelConfig.from_dict(config["model"]),
            train=TrainConfig.from_dict(config["train"]),
            method=get_method(config["method"]["name"]).from_dict(config["method"]),
        )

    def to_dict(self) -> Dict[str, Any]:
        """Flatten for logging (reference: trlx/data/configs.py:142-149)."""
        data = self.model.__dict__.copy()
        data.update(self.train.__dict__)
        data.update(self.method.__dict__)
        return data

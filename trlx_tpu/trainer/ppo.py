"""PPO trainer: KL controllers, fused rollout-scoring and train-step programs.

TPU redesign of AcceleratePPOModel
(reference: trlx/model/accelerate_ppo_model.py:12-184). The whole PPO update
— GAE, whitening, policy forward, clipped losses, grad, optimizer, LR
schedule — is ONE pjit'd program with donated state; rollout scoring (policy
forward + hydra ref logits + KL-penalty rewards) is another. The KL
controller stays host-side Python, exactly as stateful-scalar logic should.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.data import PackedPPOBatch, PPORLBatch
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.fleet import FleetDegradedExit, validate_fleet_config
from trlx_tpu.models import kda, sparse
from trlx_tpu.models.heads import LMWithValueHead, branch_replay_params, extract_branch_params
from trlx_tpu.models.lm import flash_pad_dead_chunk_share
from trlx_tpu.models.ssm import lane_fill
from trlx_tpu.ops.fused_logprob import count_head_calls, fused_logprob_eligible, take_head_call_scalars
from trlx_tpu.ops.generate import make_generate_fn
from trlx_tpu.ops.modeling import logprobs_from_logits
from trlx_tpu.ops.rl_losses import kl_penalty_rewards, ppo_loss
from trlx_tpu.observability import anomaly as obs_anomaly
from trlx_tpu.observability import numerics as obs_numerics
from trlx_tpu.observability import spans as obs_spans
from trlx_tpu.observability.spans import trace_span
from trlx_tpu.ops.sampling import GenerateConfig
from trlx_tpu.parallel.mesh import DATA_AXES
from trlx_tpu.parallel.schedule import count_weight_gathers
from trlx_tpu.pipeline.overlap import PhaseTimer, RolloutProducer
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage
from trlx_tpu.resilience.guard import guarded_update
from trlx_tpu.trainer import register_model
from trlx_tpu.trainer.base import JaxBaseTrainer


def resolve_fused_head(cfg) -> bool:
    """Static decision: route the LM-head logprob passes through the fused
    streaming kernel (trlx_tpu/ops/fused_logprob.py) instead of the
    materialize-logits + log_softmax chain. "force" always adopts (the
    router still falls back to the exact naive path per-shape); "auto"
    adopts only where the kernel is structurally eligible — on CPU/default
    configs this is False, keeping every default code path verbatim
    pre-fusion. The decision changes which tensors EXIST in the jitted
    programs, so it is made at build time, never in-trace."""
    mode = cfg.extra.get("fused_logprob", "auto")
    if mode == "force":
        return True
    return mode == "auto" and fused_logprob_eligible(cfg.d_model, cfg.vocab_size)


class AdaptiveKLController:
    """Proportional KL-coefficient controller
    (reference: trlx/model/accelerate_ppo_model.py:12-22)."""

    def __init__(self, init_kl_coef: float, target: float, horizon: int):
        self.value = init_kl_coef
        self.target = target
        self.horizon = horizon

    def update(self, current: float, n_steps: int):
        proportional_error = np.clip(current / self.target - 1, -0.2, 0.2)
        mult = 1 + proportional_error * n_steps / self.horizon
        self.value *= mult


class FixedKLController:
    """(reference: trlx/model/accelerate_ppo_model.py:25-32)"""

    def __init__(self, kl_coef: float):
        self.value = kl_coef

    def update(self, current: float, n_steps: int):
        pass


@register_model("ppo")
@register_model("AcceleratePPOModel")  # reference-compatible registry name
@register_model("TPUJaxPPOModel")  # the BASELINE north-star's name
@register_model("PPOTrainer")
class PPOTrainer(JaxBaseTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        m = config.method

        # Disaggregated rollout/learner fleet (trlx_tpu/fleet), validated at
        # CONSTRUCTION: stray fleet knobs (fleet_disaggregate off but
        # train.fleet_* set), a bad role, a multi-controller world, or a
        # fleet+rollout_overlap combination all fail HERE with a config
        # ValueError — never as a mid-run raise. None = fleet off.
        self.fleet_role = validate_fleet_config(config)

        # Pipelined rollout/train overlap (trlx_tpu/pipeline/overlap.py).
        # overlap_rollouts turns the machinery on: background reward scoring,
        # device batch prefetch, and the double-buffered rollout producer.
        # max_staleness > 0 additionally lets the producer generate off a
        # boundary param snapshot while training runs — bounded off-policy.
        # In fleet mode max_staleness instead bounds the CROSS-JOB episode
        # stream (trlx_tpu/fleet/runner.py) and the in-process machinery
        # stays off.
        self.max_staleness = max(0, int(getattr(m, "max_staleness", 0) or 0))
        self.overlap_rollouts = (
            bool(getattr(m, "rollout_overlap", False)) or self.max_staleness > 0
        ) and self.fleet_role is None
        # Packed train batches (pipeline.ppo_pipeline.pack_ppo_batch) +
        # train-throughput metering for the phase window (satellite of the
        # fused-logprob head work; see make_ppo_train_step).
        self._pack_train_batch = bool(getattr(m, "pack_train_batch", False))
        if self._pack_train_batch and (self.model.cfg.n_loops > 1 or self.model.cfg.has_kda or self.model.cfg.has_lightning
                                       or self.model.cfg.attention == "sparse" or self.model.cfg.index_topk):
            raise NotImplementedError(
                "method.pack_train_batch (packed segments) is not built for a looped stack (n_loops > 1), a kda layer or a "
                "lightning layer (a state crosses a segment's edge), attention 'sparse' (its block grid starts at a "
                "row's first token) or an indexed latent layer (index_topk: its queries choose among a row's keys)")
        # put_batch shards the leading dim over DATA_AXES — packed row-count
        # buckets must round up to a multiple of that axis product.
        self._pack_rows_multiple = int(np.prod([self.mesh.shape[a] for a in DATA_AXES]))
        self._window_tokens = []
        self._window_fill = []
        self._window_pad = []  # state-space layers: padding positions of each train batch (`ssm/pad_share`)
        # Multi-host overlap (max_staleness > 0 at process_count() > 1) used
        # to raise here: two threads dispatching device programs concurrently
        # cannot GUARANTEE the same collective launch order on every host —
        # the classic multi-controller deadlock. The guard is lifted, not the
        # hazard: every host-side decision that shapes dispatch order (chunk
        # schedule, producer handoff boundary, engine slot admission) is
        # deterministic given the config and the device-synced values that
        # are identical on every host, the shared dispatch lock serializes
        # launches within a host, and the phase-boundary fingerprint checks
        # (verify_fingerprints; verify_engine_schedule for the engine's
        # slot-manager crc) convert any residual divergence into a HostDesync
        # naming the offending host. The hang case is bounded too: decode
        # syncs run under collective_guard(train.collective_deadline), so a
        # desynced collective aborts with exit 117 + an incident bundle
        # instead of stalling the pod forever.
        self._phase_timer = PhaseTimer()
        self._rollout_producer = None
        self._last_exp_stats = None
        # The flight recorder's rollout side (observability/anomaly.py): the
        # orchestrator opens and closes the window of process counters and tick
        # gaps at the rollout's edges (first `rollout/generate` to last
        # `rollout/push`) and leaves its reading here; the detector holds
        # each phase record's `time/generate_s` against STALL_FACTOR.
        self._rollout_proc = obs_anomaly.ProcWindow(self._ticker, "rollout")
        self._rollout_obs = None
        self._rollout_anomaly = obs_anomaly.AnomalyDetector(
            obs_anomaly.STALL_FACTOR, window=config.train.anomaly_window, min_samples=2
        )
        # Fleet learner/colocated feed (built by _fleet_bootstrap) and the
        # degraded-exit latch (set when the feed raises FleetDegradedExit).
        self._fleet_feed = None
        self._fleet_stopped = False

        # record_staleness is decided ONCE here so iteration 0's store (the
        # pre-learn fill) and every producer-built store share one column
        # layout — and therefore one batch pytree and one train-step trace.
        # Fleet stores always carry the column: realized staleness is
        # stamped at consume time (trlx_tpu/fleet/runner.py).
        self.store = PPORolloutStorage(
            self.pad_token_id,
            record_staleness=self.overlap_rollouts or self.fleet_role is not None,
        )

        if m.target is not None:
            self.kl_ctl = AdaptiveKLController(m.init_kl_coef, m.target, m.horizon)
        else:
            self.kl_ctl = FixedKLController(m.init_kl_coef)
        # Per-step mean_kl device scalars queued by post_backward_callback;
        # flushed (fetched + applied in order) at log boundaries and before
        # any consumer of kl_ctl.value — see _flush_kl_updates.
        self._kl_pending = []
        # Resume happened in the base __init__, before kl_ctl existed —
        # re-apply the buffered host state now that it does.
        resumed = getattr(self, "loaded_host_state", None)
        if resumed:
            self.load_host_state(resumed)

        # Static decode shapes: prompt length + new tokens == seq_length.
        gen_kwargs = dict(m.gen_kwargs)
        self.prompt_length = int(gen_kwargs.pop("prompt_length", 0)) or max(
            config.train.seq_length - int(gen_kwargs.get("max_new_tokens", config.train.seq_length // 2)),
            1,
        )
        # Prompt-length bucketing (method.gen_kwargs["prompt_buckets"]): the
        # prompt pipeline pads each prompt to the smallest listed width that
        # fits instead of always to prompt_length. Rollout generation/scoring
        # then compile once per bucket (jit keys on the prompt width) while
        # the stored experience — and therefore the train step — stays at the
        # single prompt_length width (the orchestrator re-pads queries before
        # the store push). None = off, single-width behavior.
        from trlx_tpu.pipeline.prompt_pipeline import normalize_buckets

        self.prompt_buckets = normalize_buckets(
            gen_kwargs.pop("prompt_buckets", None), self.prompt_length
        )
        self.gen_cfg = GenerateConfig.from_gen_kwargs(
            gen_kwargs,
            prompt_len=self.prompt_length,
            pad_token_id=self.pad_token_id,
            eos_token_id=self.eos_token_id,
        )
        self.response_length = self.gen_cfg.max_new_tokens

        # Optional bigram logit mask constrains generation (tensor-prompt
        # tasks like randomwalks; the reference only supports this in ILQL
        # decode, reference: trlx/model/nn/ilql_models.py:211-212).
        processor = None
        if self.logit_mask is not None:
            from trlx_tpu.ops.sampling import make_bigram_mask_processor, process_logits_default

            bigram = make_bigram_mask_processor(self.logit_mask)
            gcfg = self.gen_cfg

            def processor(logits, state):
                return process_logits_default(bigram(logits, state), gcfg, state["step"])

        # The continuous-batching engine reuses the exact same processor
        # chain (its per-slot state passes step as a [n_slots, 1] column,
        # which broadcasts identically against [n_slots, vocab] logits).
        self._gen_processor = processor
        self._generate_fn = make_generate_fn(
            self.model,
            self.gen_cfg,
            processor,
            monitor=getattr(self, "_devicemon", None),
            monitor_name="rollout/generate",
        )
        # Rollout scoring compiles per prompt width: prompt_length is a
        # STATIC argument (it sets slice boundaries inside the program), so
        # bucketed rollouts key a dict of jitted score fns by P — at most one
        # per bucket, resolved from the incoming batch width in rollout_score*.
        self._score_fns = {}
        self._score_fused_fns = {}
        self._score_rm_fns = {}

        # W8A16 decode: int8 copies of the trunk matmul kernels ride along as
        # the 'qw' variable collection; QDense reads them instead of the bf16
        # masters, halving decode's dominant HBM term. Re-quantized from the
        # LIVE policy before every rollout phase (post_epoch_callback) so the
        # sampler never lags the optimizer.
        self._qw = None
        if getattr(config.model, "decode_weight_quant", False):
            from trlx_tpu.models.lm import quantize_weights

            lm_cfg = self.model.cfg
            if (lm_cfg.attention != "mha" or lm_cfg.mlp != "dense" or "experts" in lm_cfg.ffn_layers or lm_cfg.has_state
                    or lm_cfg.n_loops > 1):
                raise ValueError(
                    "model.decode_weight_quant covers the GPT block's kernels only "
                    "(models/lm.py QUANT_KERNEL_NAMES): it is not built for attention "
                    f"{lm_cfg.attention!r}, mlp {lm_cfg.mlp!r}, expert layers, state-space layers, lightning layers, kda layers or a "
                    "looped stack (n_loops > 1)"
                )

            self._quantize_fn = self._wrap_monitored(
                "rollout/quantize", jax.jit(quantize_weights), phase="rollout"
            )
            # GL001: __init__ predates any producer thread, but the warm-up
            # quantize is still a jitted dispatch — lock it so the invariant
            # holds unconditionally rather than by thread-lifecycle argument.
            with self._dispatch_lock:
                self._qw = self._quantize_fn(self.state.params)

        # Fused rollout statistics: the decode loop ALREADY computes every
        # policy quantity rollout scoring needs — raw logits of each sampled
        # token, the value head, and (hydra models) the branch-point hidden
        # states. Collecting them in-loop makes the post-generation scoring
        # pass a ref-branch replay ONLY: the full policy re-forward (most of
        # the score phase's FLOPs) disappears. Engaged when a hydra branch
        # exists and rollouts are scored by a host reward_fn (the on-device
        # RM path keeps the fully-fused RM program instead).
        #
        # With kv_cache_quant the stored logprobs/values are the int8-cache
        # decode loop's own — i.e. the TRUE behavior policy that sampled the
        # tokens, rather than a full-precision re-approximation of it.
        # Measured delta vs the fp recompute: |Δlogprob| ≤ ~0.008 (mean
        # 0.0025) on the randomwalks model — noise against cliprange 0.2;
        # the fused+int8 learning gate reaches ≥0.86 optimality
        # (tests/test_fused_rollout.py). Training re-forwards always run
        # full precision.
        self.fused_rollout = bool(
            getattr(m, "fused_rollout_stats", True)
            and self.model.branch_layer >= 0
            and not config.model.has_reward_model
        )
        # The rollout engine scores through the unfused re-forward BY DESIGN
        # (episodes stream out per slot; there is no fused in-loop stats
        # collection), so int8 decode + engine recomputes behavior logprobs
        # at full precision. That delta is the same magnitude already
        # measured and accepted for the int8 KV cache (|Δlogprob| ≤ ~0.008,
        # noise against cliprange 0.2) and is pinned by the engine+int8
        # parity test in tests/test_engine.py — so the engine path is
        # exempted from the fused-stats requirement below.
        if (
            self._qw is not None
            and not self.fused_rollout
            and not getattr(m, "rollout_engine", False)
        ):
            raise ValueError(
                "model.decode_weight_quant requires the fused rollout-stats "
                "path (a hydra model with a host reward_fn and "
                "method.fused_rollout_stats on): fused stats store the "
                "QUANTIZED sampler's own logprobs, keeping PPO on-policy by "
                "construction. Unfused scoring would recompute behavior "
                "logprobs at full precision against int8-sampled tokens — a "
                "silent off-policy bias. Disable decode_weight_quant, enable "
                "the fused path, or use method.rollout_engine (whose unfused "
                "scoring delta is bounded by the engine+int8 parity test)."
            )
        if self.fused_rollout:
            # a router state carried across depth: the replay's second input, collected beside the first
            branch_keys = ("branch_hidden",) + (("branch_router_state",) if self.model.cfg.router_carry else ())

            def rollout_stats_fn(tok, s):
                lp = jax.nn.log_softmax(s["last_logits"], axis=-1)  # fp32 raw
                return {
                    "logprob": jnp.take_along_axis(
                        lp, tok[:, None].astype(jnp.int32), axis=-1
                    )[:, 0],
                    "value": s["carry"]["values"],
                    **{key: s["carry"][key] for key in branch_keys},
                }

            self._generate_fused_fn = make_generate_fn(
                self.model,
                self.gen_cfg,
                processor,
                carry_keys=("values",) + branch_keys,
                step_stats_fn=rollout_stats_fn,
                apply_kwargs={"collect_branch_hidden": True},
                prefill_collect=branch_keys,
                monitor=getattr(self, "_devicemon", None),
                monitor_name="rollout/generate_fused",
            )

        # Continuous-batching rollout engine (trlx_tpu/engine): slot-based
        # decode behind the RolloutEngine boundary — finished sequences free
        # their slot immediately and queued prompts are prefilled into them,
        # so mixed response lengths stop paying the whole-chunk straggler
        # cost. Off by default; the chunked path above stays byte-identical.
        # Multi-host engine: the slot manager's admissions ARE
        # data-dependent, but every input to those decisions (finished
        # flags, n_gen, the prompt queue order) is a device-synced value
        # identical on every host — so identical code makes identical
        # choices and every host dispatches the same program sequence.
        # That claim is ENFORCED, not assumed: each admission and harvest
        # rolls into the engine's slot-schedule crc
        # (RolloutEngine._roll_schedule), allgathered and compared at
        # every phase boundary (resilience.distributed.
        # verify_engine_schedule) so a divergent host is named in a
        # HostDesync instead of deadlocking a collective; the decode sync
        # itself runs under collective_guard(collective_deadline) as the
        # exit-117 backstop. Soft prompts replay through the per-slot
        # prefill (the learned prefix lands in rows [0, n_soft) of every
        # admitted slot's cache) and has_reward_model scores harvested
        # chunks through rollout_score_rm — both engine-compatible since
        # the spec-decode PR, parity-tested in tests/test_spec_decode.py.
        self.rollout_engine_enabled = bool(getattr(m, "rollout_engine", False))
        self._rollout_engine = None
        if getattr(m, "paged_kv", False) and not self.rollout_engine_enabled:
            raise ValueError(
                "method.paged_kv requires method.rollout_engine: the paged "
                "block pool and prefix cache live in the slot engine's "
                "admission/harvest lifecycle; the chunked rollout path has "
                "no slot reuse to page."
            )

        # On-device learned reward model: a second LM + scalar head, sharded
        # with the SAME partition rules as the policy and scored inside the
        # fused rollout program — the pod-scale path a host reward_fn cannot
        # take (BASELINE.json eval config 5: NeoX-20B PPO w/ learned RM).
        self.rm_model = None
        self.rm_params = None
        if config.model.has_reward_model:
            self.rm_model, rm_host_params = self._build_reward_model(config)
            from trlx_tpu.parallel import shard_pytree

            self.rm_params, _ = shard_pytree(rm_host_params, self.mesh)
            self._rm_eval_fn = self._wrap_monitored(
                "eval/rm_scores", jax.jit(self._rm_scores), phase="score"
            )

        self.train_step = self._wrap_monitored("train/step", self.build_train_step())

    # ----------------------------------------------------------------- setup

    @property
    def pad_token_id(self) -> int:
        if self.tokenizer is not None and self.tokenizer.pad_token_id is not None:
            return int(self.tokenizer.pad_token_id)
        return 0

    @property
    def eos_token_id(self):
        if self.tokenizer is not None:
            return self.tokenizer.eos_token_id
        return self.config.model.model_arch.get("eos_token_id")

    def get_arch(self, config: TRLConfig):
        """Build LMWithValueHead (+ hydra branch point) — the counterpart of
        GPTHydraHeadWithValueModel (reference: trlx/model/nn/ppo_models.py:315-346)."""
        from trlx_tpu.models.hf_import import build_lm_config, load_or_init_params

        lm_cfg = self.finalize_lm_config(build_lm_config(config))
        k = config.model.num_layers_unfrozen
        # k >= n_layer means nothing is shared with the ref model — same as
        # fully unfrozen: keep a complete frozen param copy instead of a
        # branch (a branch at layer 0 would re-apply position embeddings).
        branch_layer = lm_cfg.n_layer - k if 0 < k < lm_cfg.n_layer else -1
        model = LMWithValueHead(lm_cfg, branch_layer=branch_layer)
        params = load_or_init_params(model, config, self.rng)
        return model, params

    def _build_reward_model(self, config: TRLConfig):
        """Build the on-device RM: LMWithValueHead with no hydra branch; the
        value head at the LAST VALID token is the scalar reward. Loads HF
        trunk weights from reward_model_path or initializes from
        reward_model_arch (from-scratch / tests)."""
        import copy

        from trlx_tpu.models.hf_import import build_lm_config, load_or_init_params

        rm_config = copy.deepcopy(config)
        rm_config.model.model_path = config.model.reward_model_path
        rm_config.model.model_arch = dict(config.model.reward_model_arch)
        rm_cfg = self.finalize_lm_config(build_lm_config(rm_config))
        rm = LMWithValueHead(rm_cfg, branch_layer=-1)
        params = load_or_init_params(rm, rm_config, self.next_rng())
        return rm, params

    @property
    def has_reward_model(self) -> bool:
        return self.rm_params is not None

    def _rm_scores(self, rm_params, tokens, mask):
        """Scalar reward per sequence: RM value head at the last valid token
        (sequence-classifier convention). Logit projection skipped — the RM's
        vocab head is never needed."""
        out = self.rm_model.apply(
            {"params": rm_params}, tokens, mask, compute_logits=False
        )
        vals = out["values"].astype(jnp.float32)  # [b, T]
        B, T = tokens.shape
        last_ix = T - 1 - jnp.argmax(mask[:, ::-1].astype(jnp.int32), axis=-1)
        # An all-padding row would index T-1 (argmax of all-zeros is 0) and
        # read a reward from an arbitrary position — zero its score instead.
        has_valid = (jnp.sum(mask, axis=-1) > 0).astype(jnp.float32)
        return vals[jnp.arange(B), last_ix] * has_valid

    def _rollout_score_rm_impl(self, params, extras, rm_params, tokens, mask, kl_coef, *, prompt_length: int):
        scores = self._rm_scores(rm_params, tokens, mask)
        lp, values, rewards, kl = self._rollout_score_impl(
            params, extras, tokens, mask, scores, kl_coef, prompt_length=prompt_length
        )
        return lp, values, rewards, kl, scores

    def rollout_score_rm(self, tokens, mask, snapshot=None):
        """Fused rollout scoring with the ON-DEVICE reward model: policy
        logprobs + values + hydra ref KL + RM scores in one program — no
        decode, no host boundary. rm_params stay live in every mode: the RM
        is not part of the TrainState, so it is never donated."""
        params = self.state.params if snapshot is None else snapshot["params"]
        extras = self.state.extras if snapshot is None else snapshot["extras"]
        with self._dispatch_lock:
            return self._score_rm_fn_for(self._batch_prompt_length(tokens))(
                params,
                extras,
                self.rm_params,
                tokens,
                mask,
                jnp.asarray(self.kl_ctl.value, dtype=jnp.float32),
            )

    def rm_eval_scores(self, tokens, mask):
        """RM scores for eval generations (device arrays in/out)."""
        with self._dispatch_lock:
            return self._rm_eval_fn(self.rm_params, tokens, mask)

    def make_extras(self, init_params):
        """The frozen ref branch = initial top-k blocks + head
        (functional hydra; reference deep-copies modules instead at
        trlx/model/nn/ppo_models.py:336-346). Fully-unfrozen models keep a
        complete frozen param copy (the reference's separate ref model path,
        reference: trlx/orchestrator/ppo_orchestrator.py:38-39)."""
        if self.model.branch_layer >= 0:
            return extract_branch_params(init_params, self.model.cfg, self.model.branch_layer)
        return jax.tree_util.tree_map(jnp.copy, init_params)

    # --------------------------------------------------------------- rollout

    def _rollout_snapshot(self):
        """Deep device copy of everything rollouts read from the TrainState:
        policy params, the frozen ref branch (extras), and re-quantized int8
        decode weights. Needed at max_staleness > 0 ONLY — the jitted train
        step donates the whole TrainState, so a producer thread reading the
        live state mid-train would touch deleted buffers. Taken on the MAIN
        thread at iteration boundaries (prepare_learning / post_epoch), when
        no train step is in flight."""
        with self._dispatch_lock:
            snap = {
                "params": jax.tree_util.tree_map(jnp.copy, self.state.params),
                "extras": (
                    None
                    if self.state.extras is None
                    else jax.tree_util.tree_map(jnp.copy, self.state.extras)
                ),
                # Weight-version tag for the lineage records: the train
                # iteration these params were copied at. Pure host metadata —
                # nothing device-side reads it.
                "version": int(self.iter_count),
            }
            if self._qw is not None:
                snap["qw"] = self._quantize_fn(snap["params"])
            if obs_numerics.enabled():
                obs_numerics.record_weight_quant(snap["params"], version=snap["version"])
            return snap

    def _decode_variables(self, snapshot=None):
        """Variable collections for the decode programs: live params (plus
        the int8 weight copies when W8A16 decode is on), or the producer's
        boundary snapshot of both."""
        if snapshot is not None:
            v = {"params": snapshot["params"]}
            if snapshot.get("qw") is not None:
                v["qw"] = snapshot["qw"]
            return v
        v = {"params": self.state.params}
        if self._qw is not None:
            v["qw"] = self._qw
        return v

    def rollout_engine(self):
        """The lazily-built continuous-batching engine (method.rollout_engine
        on). ONE engine per trainer: it owns the slot KV cache and keeps it
        across experience phases; weights are handed over per phase via
        update_weights (see orchestrator._make_experience_engine)."""
        if self._rollout_engine is None:
            from trlx_tpu.engine import RolloutEngine

            m = self.config.method
            n_slots = int(getattr(m, "engine_slots", 0) or 0) or int(m.chunk_size)
            self._rollout_engine = RolloutEngine(
                self.model,
                self.gen_cfg,
                n_slots=n_slots,
                prompt_width=self.prompt_length,
                processor=self._gen_processor,
                prefill_batch=int(getattr(m, "prefill_batch", 4) or 4),
                steps_per_sync=int(getattr(m, "engine_steps_per_sync", 8) or 8),
                spec_decode=str(getattr(m, "spec_decode", "") or ""),
                spec_k=int(getattr(m, "spec_k", 0) or 0),
                paged_kv=bool(getattr(m, "paged_kv", False)),
                kv_block_size=int(getattr(m, "kv_block_size", 128) or 128),
                kv_pool_blocks=int(getattr(m, "kv_pool_blocks", 0) or 0),
                dispatch_lock=self._dispatch_lock,
                monitor=getattr(self, "_devicemon", None),
                rng=self.next_rng(),
                # Multi-host decode syncs abort (exit 117 + incident bundle
                # with per-slot states) instead of hanging when a peer dies
                # mid-phase — same deadline the train-step guard uses. 0 =
                # unset: the guard stays disarmed (None), never a 0s timer.
                collective_deadline=(
                    float(self.config.train.collective_deadline)
                    if getattr(self.config.train, "collective_deadline", 0.0)
                    else None
                ),
            )
        return self._rollout_engine

    def rollout_engine_variables(self, snapshot=None):
        """The engine's versioned weight handoff payload: the same decode
        variable collections the chunked path resolves per call — but taken
        ONCE per phase boundary, so the engine never reads donated state."""
        return self._decode_variables(snapshot)

    def _refresh_decode_weights(self):
        """Re-quantize the int8 decode kernels from the LIVE policy — called
        before every rollout phase so the sampler never lags the optimizer."""
        if self._qw is not None:
            with self._dispatch_lock:
                self._qw = self._quantize_fn(self.state.params)
            if obs_numerics.enabled():
                obs_numerics.record_weight_quant(
                    self.state.params, version=int(self.iter_count)
                )

    def _batch_prompt_length(self, tokens) -> int:
        """The prompt width of a rollout batch: total width minus the (fixed)
        response length. With bucketing this varies per batch; without, it is
        always self.prompt_length."""
        return int(tokens.shape[1]) - self.response_length

    def _score_fn_for(self, P: int):
        fn = self._score_fns.get(P)
        if fn is None:
            fn = self._wrap_monitored(
                f"rollout/score[P={P}]",
                jax.jit(partial(self._rollout_score_impl, prompt_length=P)),
                phase="score",
            )
            self._score_fns[P] = fn
        return fn

    def _score_fused_fn_for(self, P: int):
        fn = self._score_fused_fns.get(P)
        if fn is None:
            fn = self._wrap_monitored(
                f"rollout/score_fused[P={P}]",
                jax.jit(partial(self._rollout_score_fused_impl, prompt_length=P)),
                phase="score",
            )
            self._score_fused_fns[P] = fn
        return fn

    def _score_rm_fn_for(self, P: int):
        fn = self._score_rm_fns.get(P)
        if fn is None:
            fn = self._wrap_monitored(
                f"rollout/score_rm[P={P}]",
                jax.jit(partial(self._rollout_score_rm_impl, prompt_length=P)),
                phase="score",
            )
            self._score_rm_fns[P] = fn
        return fn

    def rollout_generate(self, input_ids, attention_mask, snapshot=None, rng=None):
        batch = self.put_batch({"i": input_ids, "m": attention_mask})
        if rng is None:
            rng = self.next_rng()
        # _dispatch_lock: generation runs on the producer thread at
        # max_staleness > 0 while the main thread dispatches train steps —
        # see JaxBaseTrainer.__init__ for the rendezvous hazard.
        with self._dispatch_lock, count_weight_gathers(self._weight_gathers["generate"]):
            return self._generate_fn(
                self._decode_variables(snapshot), batch["i"], batch["m"], rng
            )

    def rollout_generate_fused(self, input_ids, attention_mask, snapshot=None, rng=None):
        """Generation that also emits the rollout statistics (sampled-token
        logprobs, values, branch hiddens) collected inside the decode loop.
        Returns (tokens, mask, stats, prefill_extras) — feed the last two to
        rollout_score_fused."""
        batch = self.put_batch({"i": input_ids, "m": attention_mask})
        if rng is None:
            rng = self.next_rng()
        with self._dispatch_lock, count_weight_gathers(self._weight_gathers["generate"]):
            return self._generate_fused_fn(
                self._decode_variables(snapshot), batch["i"], batch["m"], rng
            )

    def _rollout_score_fused_impl(self, extras, tokens, mask, scores, kl_coef, logprob, value, bh_steps, bh_prefill,
                                  rs_steps=None, rs_prefill=None, *, prompt_length: int):
        """Scoring with decode-collected stats: ONLY the frozen ref branch
        replays (for KL); the policy's logprobs/values come from the decode
        loop that produced the tokens (identical parameters, so they ARE the
        behavior policy's quantities — same justification as the unfused
        re-forward, minus its recompute).

        The branch-hidden sequence is assembled as [prefill positions 0..P)
        ; per-step entries 1.. (positions P..T-1) ; one zero pad at T-1] —
        position T-1 is never read (it is no query's key under causality
        once the last logits row is dropped), the pad only keeps the ring/
        flash sequence shapes identical to the unfused path."""
        P = prompt_length
        joined = lambda prefill, steps: jnp.concatenate([prefill, steps[:, 1:], jnp.zeros_like(steps[:, :1])], axis=1)
        bh = joined(bh_prefill, bh_steps)  # [b, T, d]
        # `router_carry`: the router state entering the branch point, assembled the same way
        second = {} if rs_steps is None else {"router_state": joined(rs_prefill, rs_steps)}
        if resolve_fused_head(self.model.cfg):
            # Streaming head: the ref branch's [b, R, V] logits never land in
            # HBM — forward_branch returns the label logprobs directly.
            rlp = self.model.apply(
                {"params": extras}, bh, mask, method="forward_branch",
                logits_start=P - 1, labels=tokens[:, P:], labels_mask=mask[:, P:], **second,
            )
        else:
            ref_logits = self.model.apply(
                {"params": extras}, bh, mask, method="forward_branch", logits_start=P - 1, **second
            ).astype(jnp.float32)
            rlp = logprobs_from_logits(ref_logits[:, :-1], tokens[:, P:])
        rmask = mask[:, P:]
        rewards, kl = kl_penalty_rewards(logprob, rlp, rmask, scores, kl_coef)
        return logprob, value, rewards, kl

    def rollout_score_fused(self, tokens, mask, scores, gen_aux, snapshot=None):
        stats, prefill_extras = gen_aux
        params = self.state.params if snapshot is None else snapshot["params"]
        extras = self.state.extras if snapshot is None else snapshot["extras"]
        scores = self.put_batch(np.asarray(scores, dtype=np.float32))
        with self._dispatch_lock, count_head_calls(self._head_calls["score"]):
            return self._score_fused_fn_for(self._batch_prompt_length(tokens))(
                # a looped stack's replay also runs the live bottom blocks (loops 2..R)
                branch_replay_params(params, extras, self.model.cfg, self.model.branch_layer),
                tokens,
                mask,
                scores,
                jnp.asarray(self.kl_ctl.value, dtype=jnp.float32),
                stats["logprob"],
                stats["value"],
                stats["branch_hidden"],
                prefill_extras["branch_hidden"],
                *((stats["branch_router_state"], prefill_extras["branch_router_state"]) if self.model.cfg.router_carry else ()),
            )

    def _branch_router_state(self, out):
        """`forward_branch`'s second input from the policy's pass `out`, as keywords: the router state
        entering the branch point where the model carries one across depth (`router_carry`), else nothing."""
        return {"router_state": out["branch_router_state"]} if self.model.cfg.router_carry else {}

    def _rollout_score_impl(self, params, extras, tokens, mask, scores, kl_coef, *, prompt_length: int):
        P = prompt_length
        if self.model.branch_layer >= 0:
            extras = branch_replay_params(params, extras, self.model.cfg, self.model.branch_layer)
        # Response region, state-before-token convention [P-1, P+R-1)
        # (reference: trlx/orchestrator/ppo_orchestrator.py:94-98).
        if resolve_fused_head(self.model.cfg):
            # Fused head on BOTH passes: policy apply and ref replay return
            # label logprobs straight from the streaming kernel — neither
            # [b, R, V] logits buffer exists.
            rlabels, rlmask = tokens[:, P:], mask[:, P:]
            out = self.model.apply(
                {"params": params}, tokens, mask, collect_branch_hidden=True,
                logits_start=P - 1, labels=rlabels, labels_mask=rlmask,
            )
            lp = out["logprobs"]
            if self.model.branch_layer >= 0:
                rlp = self.model.apply(
                    {"params": extras}, out["branch_hidden"], mask,
                    method="forward_branch", logits_start=P - 1,
                    labels=rlabels, labels_mask=rlmask, **self._branch_router_state(out),
                )
            else:
                rlp = self.model.apply(
                    {"params": extras}, tokens, mask, logits_start=P - 1,
                    labels=rlabels, labels_mask=rlmask,
                )["logprobs"]
        else:
            # logits_start=P-1: the vocab projection + fp32 softmax run only
            # over the response region [P-1, T) — the prompt's logits are
            # never needed.
            out = self.model.apply(
                {"params": params}, tokens, mask, collect_branch_hidden=True, logits_start=P - 1
            )
            logits = out["logits"].astype(jnp.float32)
            if self.model.branch_layer >= 0:
                ref_logits = self.model.apply(
                    {"params": extras}, out["branch_hidden"], mask,
                    method="forward_branch", logits_start=P - 1, **self._branch_router_state(out),
                ).astype(jnp.float32)
            else:
                ref_logits = self.model.apply(
                    {"params": extras}, tokens, mask, logits_start=P - 1
                )["logits"].astype(jnp.float32)

            lp = logprobs_from_logits(logits[:, :-1], tokens[:, P:])
            rlp = logprobs_from_logits(ref_logits[:, :-1], tokens[:, P:])
        values = out["values"].astype(jnp.float32)[:, P - 1 : -1]
        rmask = mask[:, P:]
        rewards, kl = kl_penalty_rewards(lp, rlp, rmask, scores, kl_coef)
        return lp, values, rewards, kl

    def rollout_score(self, tokens, mask, scores, snapshot=None):
        params = self.state.params if snapshot is None else snapshot["params"]
        extras = self.state.extras if snapshot is None else snapshot["extras"]
        scores = self.put_batch(np.asarray(scores, dtype=np.float32))
        with self._dispatch_lock, count_head_calls(self._head_calls["score"]):
            return self._score_fn_for(self._batch_prompt_length(tokens))(
                params,
                extras,
                tokens,
                mask,
                scores,
                jnp.asarray(self.kl_ctl.value, dtype=jnp.float32),
            )

    # ------------------------------------------------------------ train step

    def build_train_step(self):
        # The same loss the jitted step compiles in, reachable OUTSIDE the
        # donated program: the graftnum NaN census re-derives the gradient
        # tree from it on the incident path (base._capture_numerics).
        self._numerics_loss_fn = make_ppo_loss_fn(
            self.model, self.config, self.prompt_length, self.detach_frozen
        )
        return make_ppo_train_step(
            self.model,
            self.optimizer,
            self.config,
            self.prompt_length,
            self.schedule,
            self.detach_frozen,
        )

    def _numerics_forward(self, batch):
        """Eval-only EAGER forward over the offending microbatch for the
        graftnum first-NaN bisector — eager so the probe taps in
        models/lm.py actually observe concrete activations (a jitted call
        would trace straight through them). Outputs are discarded; only
        the taps' per-layer finite-ness matters."""
        if isinstance(batch, PackedPPOBatch):
            self.model.apply(
                {"params": self.state.params},
                batch.input_ids,
                batch.attention_mask,
                position_ids=batch.position_ids,
                segment_ids=batch.segment_ids,
            )
            return
        all_ids = jnp.concatenate([batch.query_tensors, batch.response_tensors], axis=1)
        all_mask = jnp.concatenate([batch.query_mask, batch.response_mask], axis=1)
        self.model.apply(
            {"params": self.state.params},
            all_ids,
            all_mask,
            logits_start=self.prompt_length - 1,
        )

    def load_host_state(self, d: dict):
        super().load_host_state(d)
        if "kl_coef" in d and hasattr(self, "kl_ctl"):
            import math

            v = float(d["kl_coef"])
            # A checkpoint written by an older build could carry a poisoned
            # coefficient — restoring NaN would NaN every KL-penalty reward.
            if math.isfinite(v):
                self.kl_ctl.value = v

    # ------------------------------------------------------------- callbacks

    def post_backward_callback(self, stats=None):
        """Queue this step's policy-vs-rollout mean_kl for the adaptive
        controller (reference: trlx/model/accelerate_ppo_model.py:163-165).

        The value arrives as an un-fetched device scalar — appending costs
        nothing on the hot path. The controller applies the buffered per-step
        updates in order at the next flush, so its trajectory is EXACTLY the
        per-step (log_interval == 1) trajectory regardless of logging cadence
        (tests/test_e2e.py::test_kl_controller_trajectory_invariant_to_log_interval).
        kl_ctl.value is only ever consumed at a rollout or checkpoint, and
        both flush first."""
        if isinstance(self.kl_ctl, FixedKLController):
            return  # no-op controller: don't buy device syncs for nothing
        if stats and "mean_kl" in stats:
            self._kl_pending.append(stats["mean_kl"])
            # Keep the buffer (and the retained device scalars) bounded.
            if len(self._kl_pending) >= max(self.config.train.log_interval, 8):
                # The pull waits for the step just dispatched: device wait,
                # not host work, so time/step_host_ms leaves it out.
                with trace_span("train/kl_flush") as flush:
                    self._flush_kl_updates()
                self._step_wait_s += flush.seconds

    def _flush_kl_updates(self):
        if not self._kl_pending:
            return
        import math

        pending, self._kl_pending = self._kl_pending, []
        for v in jax.device_get(pending):
            v = float(v)
            if not math.isfinite(v):
                # A guard-skipped (non-finite) step's stats are garbage by
                # construction — feeding its NaN mean_kl to the controller
                # would poison kl_ctl.value and, through the KL-penalty
                # rewards, every subsequent rollout (and the saved host
                # state). Skip it; the step's update was skipped too.
                continue
            self.kl_ctl.update(v, self.config.train.batch_size)

    def host_state_dict(self) -> dict:
        self._flush_kl_updates()
        d = super().host_state_dict()
        d["kl_coef"] = float(self.kl_ctl.value)
        return d

    def post_epoch_callback(self):
        """Alternate back to rollout
        (reference: trlx/model/accelerate_ppo_model.py:157-161). Each piece
        of the boundary is a span of its own (short ones: a profiler that
        starts inside the rollout still sees the next one begin)."""
        obs_spans.set_iteration(obs_spans.iteration() + 1)  # rollout n+1 and the training on it
        with trace_span("boundary/kl_flush"):
            self._flush_kl_updates()  # rollout rewards consume kl_ctl.value
        with trace_span("boundary/refresh_weights"):
            self._refresh_decode_weights()  # sampler follows the updated policy
        if self._fleet_feed is not None:
            # Disaggregated/colocated fleet: publish the post-train weights
            # (versioned broadcast), then consume the next stream batch.
            # A FleetDegradedExit is the coordinated abort: checkpoint the
            # rollback point FIRST (with the degraded /healthz state still
            # exported), then unwind — learn() treats it as a clean stop.
            try:
                self._fleet_feed.consume_done()
                self.store = self._fleet_feed.next_store()
            except FleetDegradedExit:
                self._fleet_stopped = True
                self.save()
                raise
        elif self._rollout_producer is None:
            # Serial schedule: generate the next iteration's experience
            # inline, into the (cleared) long-lived store.
            with trace_span("boundary/store_clear"):
                self.store.clear_history()
            self.orch.make_experience(self.config.method.num_rollouts, self.iter_count)
        else:
            # Pipelined schedule: release the producer (one iteration fully
            # consumed, decode weights refreshed above — the staleness-0
            # producer reads the LIVE state while this thread blocks in
            # next_store) and swap in its double buffer. At staleness > 0 the
            # boundary snapshot travels with the release so the producer
            # never touches donated buffers.
            snapshot = self._rollout_snapshot() if self.max_staleness > 0 else None
            self._rollout_producer.consume_done(snapshot=snapshot)
            self.store = self._rollout_producer.next_store()
        with trace_span("boundary/loader"):
            self.train_dataloader = self.store.create_loader(
                self.config.train.batch_size,
                shuffle=True,
                pack=self._pack_train_batch,
                rows_multiple=self._pack_rows_multiple,
            )
        with trace_span("boundary/phase_log"):
            self._log_phase_window()
        self._write_pending_stall()  # a stalled rollout's line, outside every span of the boundary
        obs_spans.flush()

    def _prepare_batch(self, batch):
        """Also meter the train phase's token throughput: count the tokens
        the step will PROCESS (padded row area — the quantity the hardware
        pays for) and, when packing, the batch's fill fraction. Appended
        per-batch (list append: safe from the prefetch thread), reduced at
        the next phase window."""
        if isinstance(batch, PackedPPOBatch):
            tokens = int(np.prod(batch.input_ids.shape))
            if batch.extras and "pack_fill" in batch.extras:
                self._window_fill.append(float(batch.extras["pack_fill"]))
        else:
            tokens = batch.query_tensors.shape[0] * (
                batch.query_tensors.shape[1] + batch.response_tensors.shape[1]
            )
            if self.model.cfg.has_ssm:
                # the host batch, before it is put: no transfer. The scan
                # walks every position, padding too.
                pad = sum(int(np.sum(np.asarray(t) == self.pad_token_id)) for t in (batch.query_tensors, batch.response_tensors))
                self._window_pad.append(pad / tokens)
        # The same device batch feeds every PPO inner epoch.
        self._window_tokens.append(tokens * max(1, getattr(self, "n_updates_per_batch", 1)))
        return super()._prepare_batch(batch)

    def _log_phase_window(self):
        """Flush the phase timer at the rollout boundary: one window spans
        train(iter n) + rollout/score(iter n+1) — the span the pipeline
        overlaps — and feeds time/* + overlap_fraction to the tracker and
        the progress line."""
        stats = self._phase_timer.window()
        # The spans' accumulators, drained on the same cadence: self-seconds
        # by span name since the last window, and what this thread spent
        # inside its top-level spans (the rest of the wall is unspanned).
        acc = obs_spans.drain()
        self_s = acc["self_s"]
        stats["time/generate_s"] = sum(
            v for k, v in self_s.items() if k.startswith("rollout/generate") or k == "rollout/pull"
        )
        stats["time/score_device_s"] = self_s.get("rollout/score_device", 0.0)
        stats["time/push_s"] = self_s.get("rollout/push", 0.0)
        stats["time/boundary_s"] = sum(v for k, v in self_s.items() if k.startswith("boundary/"))
        stats["time/unspanned_s"] = max(0.0, stats["time/window_wall_s"] - acc["top_s"])
        self._observe_rollout(stats, self_s.get("rollout/pull", 0.0))
        window_tokens, self._window_tokens = self._window_tokens, []
        window_fill, self._window_fill = self._window_fill, []
        train_s = stats.get("time/train_s", 0.0)
        if window_tokens and train_s > 0:
            stats["train_tokens_per_s"] = float(sum(window_tokens)) / train_s
        if window_fill:
            stats["train_batch_fill"] = float(np.mean(window_fill))
        window_pad, self._window_pad = self._window_pad, []
        if window_pad:
            lm_cfg = self.model.cfg
            stats["ssm/pad_share"] = float(np.mean(window_pad))
            stats["ssm/chunks_per_pass"] = float(-(-int(self.config.train.seq_length) // lm_cfg.ssm_chunk))
            stats["ssm/lane_fill"] = lane_fill(lm_cfg, int(self.config.train.seq_length))
        if self.model.cfg.has_kda and window_tokens:
            # chunks of the delta-rule pass over one row of a train batch
            stats["kda/chunks_per_pass"] = float(-(-int(self.config.train.seq_length) // kda.CHUNK))
            stats["kda/solve_lane_fill"] = kda.solve_lane_fill(int(self.config.train.seq_length))
        if self._last_exp_stats:
            stats.update(self._last_exp_stats)
        stats.update(take_head_call_scalars(self._head_calls["score"], "score"))
        # Device telemetry flushes on the SAME cadence as the phase window —
        # its per-phase FLOP accumulators divide by exactly these seconds, so
        # obs/train_mfu_pct is the window's true utilization, not a smoothed
        # proxy.
        stats.update(
            self._flush_device_telemetry(
                {
                    "train": stats.get("time/train_s", 0.0),
                    "rollout": stats.get("time/rollout_s", 0.0),
                    "score": stats.get("time/score_s", 0.0),
                    "wall": stats.get("time/window_wall_s", 0.0),
                }
            )
        )
        health = getattr(self, "_health", None)
        if health is not None:
            # The window record carries the freshest health states too, so
            # the per-window view (the one the report's tables read) shows
            # detector state at rollout boundaries, not just per-step.
            stats.update(health.gauges())
        if jax.process_count() > 1 and self._devicemon is not None:
            from trlx_tpu.observability.report import rollup_window_stats

            stats.update(rollup_window_stats(stats))
        self._last_phase_stats = stats
        self.tracker.log(stats, step=self.iter_count)
        # The phase-window gauges (overlap fraction, MFU)
        # belong on /metrics too — the per-step export at the log boundary
        # only ever sees train-step stats. Already rolled up above, so no
        # second collective here (the exporter lives on process 0 only).
        if self._metrics_exporter is not None:
            self._metrics_exporter.update(stats, step=self.iter_count)

    def _observe_rollout(self, stats, pull_s):
        """The flight recorder's part of a phase record: `stall/rollout_excess_s`
        and `proc/rollout_*` over the rollout this window holds, and on a
        breach the line `_write_pending_stall` writes once the boundary's
        spans have ended. The detector serves generation on the main thread
        (the path every cell runs): a producer's, an engine's or a fleet's
        rollout gets the counters and no verdict, and a rollout that compiled
        neither seeds nor trips."""
        proc, self._rollout_obs = self._rollout_obs, None
        stats["stall/rollout_excess_s"] = 0.0
        for key in ("nivcsw", "cpu_s", "tick_gap_max_s"):
            stats[f"proc/rollout_{key}"] = proc[key] if proc else 0.0
        on_main = self._rollout_producer is None and self._fleet_feed is None and not self.rollout_engine_enabled
        if not proc or not on_main or proc["compiles"] > 0:
            return
        breach = self._rollout_anomaly.observe(stats["time/generate_s"], pull_s)
        if not breach:
            return
        stats["stall/rollout_excess_s"] = breach.excess_s
        self._pending_stall = breach, obs_anomaly.stall_record(
            "rollout",
            breach,
            proc,
            step=self.iter_count,
            iter=obs_spans.iteration(),
            t0=proc["t0_ns"] * 1e-9,
            t1=proc["t1_ns"] * 1e-9,
            seconds=stats["time/generate_s"],
            wait_s=pull_s,
            host_ms=max(0.0, stats["time/generate_s"] - pull_s) * 1e3,
            compiles=proc["compiles"],
        )

    def learn(self):
        """Fleet-aware learn: a FleetDegradedExit unwinding out of the loop
        is a CLEAN stop, not a crash — the feed drained the in-flight
        episodes, post_epoch_callback saved the rollback checkpoint, and
        the base finally-teardown (which runs before this except) shut the
        feed down with the coordinated abort marker."""
        try:
            return super().learn()
        except FleetDegradedExit as e:
            print(f"[fleet] learner stopped cleanly: {e}", flush=True)
            return None

    def _fleet_bootstrap(self):
        """Learner/colocated fleet roles: iteration 0's store arrives
        through the episode stream — trainer/api.py calls this in place of
        the direct ``make_experience`` fill. Publishes the v0 weights first
        so a disaggregated worker's staleness gate can open."""
        from trlx_tpu.fleet import FleetLearnerFeed

        if getattr(self, "_resumed", False):
            # The feed tags weight versions with iter_count; a resumed
            # learner must publish its RESTORED step, not 0 (learn() derives
            # the same value later).
            self.iter_count = int(jax.device_get(self.state.step))
        self._fleet_feed = FleetLearnerFeed(self, getattr(self, "orch", None))
        self.store = self._fleet_feed.bootstrap()

    def prepare_learning(self):
        """(reference: trlx/model/accelerate_ppo_model.py:167-184)"""
        self.eval_dataloader = self.eval_pipeline.create_loader(self.config.train.batch_size)
        self.train_dataloader = self.store.create_loader(
            self.config.train.batch_size,
            shuffle=True,
            pack=self._pack_train_batch,
            rows_multiple=self._pack_rows_multiple,
        )
        self.n_updates_per_batch = self.config.method.ppo_epochs
        self.total_steps = min(
            self.config.train.epochs * self.n_updates_per_batch * len(self.train_dataloader),
            self.config.train.total_steps,
        )
        orch = getattr(self, "orch", None)
        if self.overlap_rollouts and orch is not None and self._rollout_producer is None:
            num_rollouts = self.config.method.num_rollouts

            def produce(store, index, snapshot, staleness, stop):
                orch.make_experience(
                    num_rollouts,
                    self.iter_count,
                    store=store,
                    snapshot=snapshot,
                    staleness=staleness,
                    stop=stop,
                )

            def new_store():
                return PPORolloutStorage(self.pad_token_id, record_staleness=True)

            # At staleness 0 the producer starts parked (its first store is
            # gated on the first consume_done) and needs no snapshot — it
            # reads live state only while the main thread waits. At
            # staleness >= 1 it starts generating iteration 1's experience
            # immediately, off the same pre-training params that built
            # iteration 0's store.
            self._rollout_producer = RolloutProducer(
                produce, new_store, max_staleness=self.max_staleness
            ).start(snapshot=self._rollout_snapshot() if self.max_staleness > 0 else None)

    def _shutdown_experience_pipeline(self):
        """learn()'s finally: stop the producer before the run tears down
        (also on the preemption/early-return paths)."""
        feed = self._fleet_feed
        if feed is not None:
            self._fleet_feed = None
            # Preemption must NOT write the abort marker: this learner will
            # resume into the same fleet_dir and the worker (alive the whole
            # time) keeps serving it. Every other exit coordinates shutdown.
            if getattr(self, "_preempted", False):
                reason = "preempted"
            elif self._fleet_stopped:
                reason = "degraded"
            else:
                reason = "complete"
            feed.shutdown(reason=reason)
        producer = self._rollout_producer
        if producer is not None:
            self._rollout_producer = None
            producer.shutdown()
        engine = self._rollout_engine
        if engine is not None:
            # Synchronous (the engine owns no threads): drop queued prompts,
            # in-flight slots, the device state, and the weight reference.
            self._rollout_engine = None
            engine.shutdown()


def make_ppo_loss_fn(model, config, prompt_length, detach_frozen):
    """The PPO loss as a standalone ``loss_fn(params, batch) -> (loss,
    stats)`` — the single ingredient both the jitted train step and the
    graftnum incident path share: when the non-finite guard trips, the
    gradient tree was consumed inside the donated step, so the NaN census
    re-derives it from THIS function on the offending microbatch (eager,
    no donation — incident path only, never the hot loop)."""
    m = config.method
    P = prompt_length
    use_fused = resolve_fused_head(model.cfg)
    packed = bool(getattr(m, "pack_train_batch", False))
    loss_kwargs = dict(
        gamma=m.gamma,
        lam=m.lam,
        cliprange=m.cliprange,
        cliprange_value=m.cliprange_value,
        vf_coef=m.vf_coef,
    )

    def with_trunk_stats(result, out, n_tokens, exit_mask=None, attention_mask=None):
        """A model with expert layers: the step's routing counters beside the
        loss's own stats (`moe/held_slot_share`, `moe/max_expert_load`,
        `moe/first_buffer_share`). A gated looped stack: the loop the exit
        gate would leave at, mean over the response positions `exit_mask`
        (`policy/expected_exit_loop`, sum over r of r p_r, no gradient). A
        pass through the flash kernels: the live key chunks the batch's own
        padding `attention_mask` takes out (`flash/pad_dead_chunk_share`).
        Attention "sparse": `sparse/kept_pair_share`, `sparse/chosen_blocks_mean`,
        `sparse/computed_pair_share`; an indexed latent layer (`index_topk`):
        `dsa/kept_pair_share`."""
        share = None if attention_mask is None else flash_pad_dead_chunk_share(model.cfg, attention_mask)
        if share is not None:
            loss, stats = result
            result = loss, {**stats, "flash/pad_dead_chunk_share": share}
        if out["exit_probs"] is not None and exit_mask is not None:
            p = jax.lax.stop_gradient(out["exit_probs"])[:, P - 1 : -1]
            loops = jnp.sum(p * jnp.arange(1, p.shape[-1] + 1, dtype=p.dtype), axis=-1)
            weight = exit_mask.astype(p.dtype)
            loss, stats = result
            result = loss, {**stats, "policy/expected_exit_loop": jnp.sum(loops * weight) / jnp.maximum(jnp.sum(weight), 1.0)}
        if out.get("dsa_sums") is not None:
            # an indexed latent layer: of the causal pairs, those the queries chose, from the choice the pass made
            kept, causal = jax.lax.stop_gradient(out["dsa_sums"])
            loss, stats = result
            result = loss, {**stats, "dsa/kept_pair_share": kept / jnp.maximum(causal, 1.0)}
        if out.get("sparse_sums") is not None:
            # attention "sparse": of the causal pairs, those in blocks the queries chose, and the blocks a
            # query's group chose, both from the choice the pass made (models/sparse.py), no gradient
            kept, causal, blocks, queries = jax.lax.stop_gradient(out["sparse_sums"])
            # and the pairs the many-token pass computed for them, a fact of its shapes (1.0: none above the diagonal)
            cfg = model.cfg
            layers = sum(cfg.mixer(i) == "attention" for i in range(cfg.n_layer))
            computed = layers * sparse.computed_pairs(cfg, *attention_mask.shape, cfg.n_head)
            loss, stats = result
            result = loss, {**stats, "sparse/kept_pair_share": kept / jnp.maximum(causal, 1.0),
                            "sparse/chosen_blocks_mean": blocks / jnp.maximum(queries, 1.0),
                            "sparse/computed_pair_share": computed / jnp.maximum(causal, 1.0)}
        if out["expert_counts"] is None:
            return result
        from trlx_tpu.models.moe import expert_load_stats, first_buffer_share

        k = model.cfg.experts_per_token
        share, load = expert_load_stats(out["expert_counts"], n_tokens, k)
        fit = first_buffer_share(out["expert_counts"], n_tokens, k, model.cfg.n_experts)
        loss, stats = result
        if out["router_top_weight"] is not None:  # one expert a token: whether the policy's router has collapsed
            stats = {**stats, "moe/top1_weight_mean": out["router_top_weight"]}
        return loss, {**stats, "moe/held_slot_share": share, "moe/max_expert_load": load, "moe/first_buffer_share": fit}

    def dense_loss_fn(params, batch: PPORLBatch):
        params = detach_frozen(params)
        all_ids = jnp.concatenate([batch.query_tensors, batch.response_tensors], axis=1)
        all_mask = jnp.concatenate([batch.query_mask, batch.response_mask], axis=1)
        out = model.apply({"params": params}, all_ids, all_mask, logits_start=P - 1)
        logits = out["logits"].astype(jnp.float32)
        lp = logprobs_from_logits(logits[:, :-1], all_ids[:, P:])
        vpred = out["values"].astype(jnp.float32)[:, P - 1 : -1]
        return with_trunk_stats(ppo_loss(
            lp, vpred, batch.logprobs, batch.values, batch.rewards,
            batch.response_mask, **loss_kwargs,
        ), out, all_ids.size, batch.response_mask, all_mask)

    def fused_loss_fn(params, batch: PPORLBatch):
        # Same update, fused head: the policy's per-label logprobs come out
        # of the streaming kernel (with its custom VJP), so no [b, R, V]
        # fp32 logits buffer is live anywhere in the step — forward or
        # backward.
        params = detach_frozen(params)
        all_ids = jnp.concatenate([batch.query_tensors, batch.response_tensors], axis=1)
        all_mask = jnp.concatenate([batch.query_mask, batch.response_mask], axis=1)
        out = model.apply(
            {"params": params}, all_ids, all_mask, logits_start=P - 1,
            labels=all_ids[:, P:], labels_mask=batch.response_mask,
        )
        vpred = out["values"].astype(jnp.float32)[:, P - 1 : -1]
        return with_trunk_stats(ppo_loss(
            out["logprobs"], vpred, batch.logprobs, batch.values, batch.rewards,
            batch.response_mask, **loss_kwargs,
        ), out, all_ids.size, batch.response_mask, all_mask)

    def packed_loss_fn(params, batch: PackedPPOBatch):
        # Packed layout: episodes live as segments inside dense rows
        # (pipeline.ppo_pipeline.pack_ppo_batch). segment_ids drive the
        # block-diagonal attention and the GAE reset; loss_mask marks the
        # response state positions; per-sequence stats normalize by the
        # TRUE episode count (== train batch_size, drop_last guarantees).
        params = detach_frozen(params)
        out = model.apply(
            {"params": params}, batch.input_ids, batch.attention_mask,
            position_ids=batch.position_ids, segment_ids=batch.segment_ids,
            labels=batch.labels, labels_mask=batch.loss_mask,
        )
        vpred = out["values"].astype(jnp.float32)
        return with_trunk_stats(ppo_loss(
            out["logprobs"], vpred, batch.old_logprobs, batch.old_values,
            batch.rewards, batch.loss_mask,
            segment_ids=batch.segment_ids, n_seqs=config.train.batch_size,
            **loss_kwargs,
        ), out, batch.input_ids.size)

    if packed:
        return packed_loss_fn
    if use_fused:
        return fused_loss_fn
    return dense_loss_fn


def make_ppo_train_step(model, optimizer, config, prompt_length, schedule, detach_frozen):
    """The jitted PPO update program, built from its explicit ingredients.

    Factored out of PPOTrainer.build_train_step so AOT validation
    (tests/test_scale_compile.py) can lower + compile the REAL production
    step at 6B shapes from abstract arrays — without ever allocating the
    parameters. The trainer method delegates here; there is exactly one
    definition of the PPO update."""
    loss_fn = make_ppo_loss_fn(model, config, prompt_length, detach_frozen)
    # graftnum gate, resolved at BUILD time: a disarmed program compiles to
    # the identical pre-graftnum jaxpr (byte-identical loss contract).
    graftnum = obs_numerics.armed(config.train)

    def train_step(state, batch: PPORLBatch):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params, batch)
        stats = dict(stats)
        with jax.named_scope("optimizer"):
            if config.train.nonfinite_guard:
                # Abstract states built before the bad_steps field existed
                # (tests/test_scale_compile.py hand-constructs them) default it
                # to None — materialize the counter in-trace.
                bad0 = state.bad_steps
                if bad0 is None:
                    bad0 = jnp.zeros((), dtype=jnp.int32)
                params, opt_state, bad, finite = guarded_update(
                    optimizer, grads, loss, state.params, state.opt_state, bad0
                )
                stats["resilience/nonfinite"] = 1.0 - finite.astype(jnp.float32)
                stats["resilience/bad_steps"] = bad.astype(jnp.float32)
            else:
                updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
                bad = state.bad_steps
            stats["grad_norm"] = optax.global_norm(grads)
        if config.train.watch_interval:
            # per-group grad norms for the wandb.watch-equivalent; device
            # scalars, fetched only at log boundaries with the rest
            for group, sub in grads.items():
                stats[f"watch/grad_norm/{group}"] = optax.global_norm(sub)
        if graftnum:
            # graftnum per-subtree reductions (device scalars, fetched only
            # at log boundaries): grad/param norms + the REALIZED update
            # ratio — zero on guard-skipped steps, which is itself signal.
            stats.update(obs_numerics.train_step_stats(grads, state.params, params))
        stats["learning_rate"] = schedule(state.step)
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state, bad_steps=bad
        )
        return new_state, stats

    return jax.jit(train_step, donate_argnums=(0,))

"""ILQL trainer: offline Q-learning with advantage-steered decoding.

TPU redesign of AccelerateILQLModel
(reference: trlx/model/accelerate_ilql_model.py:13-181) +
CausalLMWithValueHeads' target-head machinery
(reference: trlx/model/nn/ilql_models.py:31-160):

- target Q heads are a frozen param subtree in TrainState.extras; Polyak sync
  is a jitted tree blend — no GatheredParameters/rank-0 dance, sharding-safe
  by construction (vs reference: trlx/model/nn/ilql_models.py:148-158);
- the whole loss (double-Q TD + expectile V + CQL + AWAC) is one pjit'd step;
- eval decoding runs the compiled while_loop sampler with the ILQL advantage
  processor instead of the reference's per-token Python loop
  (reference: trlx/model/nn/ilql_models.py:162-251).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.data import ILQLBatch
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.heads import LMWithILQLHeads
from trlx_tpu.models.lm import flash_pad_dead_chunk_share
from trlx_tpu.observability import numerics as obs_numerics
from trlx_tpu.observability.spans import trace_span
from trlx_tpu.ops.fused_logprob import fused_logprob_eligible, routed_logprob
from trlx_tpu.ops.generate import make_generate_fn
from trlx_tpu.ops.ilql_loss import action_tokens, ilql_loss, ilql_loss_terms
from trlx_tpu.ops.modeling import topk_mask
from trlx_tpu.ops.sampling import NEG_INF, GenerateConfig
from trlx_tpu.resilience.guard import guarded_update
from trlx_tpu.trainer import register_model
from trlx_tpu.trainer.base import JaxBaseTrainer
from trlx_tpu.utils import sanitize


@register_model("ilql")
@register_model("ILQLModel")
@register_model("AccelerateILQLModel")
@register_model("TPUJaxILQLModel")  # the BASELINE north-star's name
class ILQLTrainer(JaxBaseTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        m = config.method

        gen_kwargs = dict(m.gen_kwargs)
        self.beta = float(gen_kwargs.pop("beta", m.betas[0] if m.betas else 1.0))
        self.decode_top_k = int(gen_kwargs.pop("top_k", 20))
        self.decode_temperature = float(gen_kwargs.pop("temperature", 1.0))
        self.prompt_length = int(gen_kwargs.pop("prompt_length", 0)) or max(
            config.train.seq_length - int(gen_kwargs.get("max_new_tokens", config.train.seq_length // 2)),
            1,
        )
        if "max_new_tokens" not in gen_kwargs and "max_length" not in gen_kwargs:
            gen_kwargs["max_length"] = config.train.seq_length
        self.gen_cfg = GenerateConfig.from_gen_kwargs(
            gen_kwargs,
            prompt_len=self.prompt_length,
            pad_token_id=self.pad_token_id,
            eos_token_id=self.eos_token_id,
        )

        self._generate_fn = make_generate_fn(
            self.model,
            self.gen_cfg,
            processor=self._make_ilql_processor(),
            carry_keys=("qs", "vs"),
            step_stats_fn=self._decode_step_stats,
            monitor=getattr(self, "_devicemon", None),
            monitor_name="rollout/generate",
        )
        self.train_step = self._wrap_monitored("train/step", self.build_train_step())
        self._sync_fn = self._wrap_monitored(
            "train/polyak_sync", jax.jit(self._polyak_sync, donate_argnums=(1,))
        )

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
        from trlx_tpu.models.hf_import import build_lm_config, load_or_init_params

        lm_cfg = self.finalize_lm_config(build_lm_config(config))
        model = LMWithILQLHeads(lm_cfg, two_qs=config.method.two_qs)
        params = load_or_init_params(model, config, self.rng)
        return model, params

    def make_extras(self, init_params):
        """Frozen target-Q heads start as copies of the online heads
        (reference: trlx/model/nn/ilql_models.py:79-87)."""
        extras = {"q1_head": jax.tree_util.tree_map(jnp.copy, init_params["q1_head"])}
        if self.config.method.two_qs:
            extras["q2_head"] = jax.tree_util.tree_map(jnp.copy, init_params["q2_head"])
        return extras

    # ------------------------------------------------------------ generation

    def _make_ilql_processor(self):
        """Advantage-steered decode chain
        (reference: trlx/model/nn/ilql_models.py:203-221). Q/V come from the
        generate loop's carry (heads evaluated in the same forward pass);
        qs carry holds the TARGET heads because rollout_generate swaps them
        into the param tree."""
        beta, top_k, temperature = self.beta, self.decode_top_k, self.decode_temperature
        logit_mask = jnp.asarray(self.logit_mask) if self.logit_mask is not None else None

        def processor(logits, state):
            logits = logits.astype(jnp.float32)
            if logit_mask is not None:
                forbidden = logit_mask[state["last_token"]]
                logits = jnp.where(forbidden, NEG_INF, logits)
            qs = state["carry"]["qs"]
            vs = state["carry"]["vs"]
            q = jnp.minimum(qs[0], qs[1]) if len(qs) > 1 else qs[0]
            adv = q.astype(jnp.float32) - vs.astype(jnp.float32)[..., None]
            pi_beta = jax.nn.log_softmax(logits, axis=-1)
            pi_top = jnp.maximum(topk_mask(pi_beta + beta * adv, top_k), NEG_INF)
            return pi_top / temperature

        return processor

    @staticmethod
    def _decode_step_stats(tok, state):
        """Per-step Q(s, tok) / V(s) straight from the generate carry — the
        SAME target-head values that steered the sample, collected inside the
        decode while_loop so stats cost no extra forward pass
        (the reference gathers these inside its Python decode loop,
        reference: trlx/model/nn/ilql_models.py:238-249)."""
        qs = state["carry"]["qs"]
        vs = state["carry"]["vs"]
        q = jnp.minimum(qs[0], qs[1]) if len(qs) > 1 else qs[0]
        q_tok = jnp.take_along_axis(q.astype(jnp.float32), tok[:, None].astype(jnp.int32), axis=-1)[:, 0]
        return {"q": q_tok, "v": vs.astype(jnp.float32)}

    def rollout_generate(self, input_ids, attention_mask):
        batch = self.put_batch({"i": input_ids, "m": attention_mask})
        # Swap TARGET Q heads into the applied params: decode steers by the
        # target network (reference: trlx/model/nn/ilql_models.py:203-206).
        params = {**self.state.params, **self.state.extras}
        # GL001: eval decode can run while a producer thread is mid-dispatch
        # (the overlap pipeline is PPO-only today, but the dispatch-lock
        # discipline is trainer-wide — uncontended acquire is ~100ns).
        with self._dispatch_lock:
            tokens, mask, dstats = self._generate_fn(
                {"params": params}, batch["i"], batch["m"], self.next_rng()
            )
        if self.tracker.enabled:
            # Tracker gating (rank-0, not disabled) replaces the reference's
            # silent `"debug" in os.environ` switch
            # (reference: trlx/model/accelerate_base_model.py:72-79) — stat
            # collection follows the same explicit knob as every other log.
            self._log_decode_stats(dstats, mask)
        return tokens, mask

    def _log_decode_stats(self, dstats, mask):
        """Q/V/advantage distributions over the decoded tokens, read from the
        in-loop stat buffers (process-local rows; stats compute is part of
        the SPMD generate program, so this is pod-safe)."""
        P = self.prompt_length
        q, v, rmask = self.to_local_host((dstats["q"], dstats["v"], mask[:, P:]))
        valid = rmask.astype(bool)
        from trlx_tpu.parallel.mesh import is_main_process

        if not is_main_process():
            return
        self.tracker.log_histogram("decode/qs", q[valid], step=self.iter_count)
        self.tracker.log_histogram("decode/vs", v[valid], step=self.iter_count)
        self.tracker.log_histogram("decode/adv", (q - v)[valid], step=self.iter_count)

    # ------------------------------------------------------------ train step

    def build_train_step(self):
        m = self.config.method
        model = self.model
        optimizer = self.optimizer
        schedule = self.schedule
        cfg = model.cfg
        fused_mode = cfg.extra.get("fused_logprob", "auto")
        # Static branch: the fused path changes which tensors exist in the
        # step (no [b, T, V] logits, no [b, A, V] online Q), so the decision
        # is made at build time. "auto" adopts it only where the kernel is
        # actually eligible (TPU, aligned d_model, big vocab); CPU/default
        # keeps the pre-fusion loss verbatim.
        use_fused = fused_mode == "force" or (
            fused_mode == "auto" and fused_logprob_eligible(cfg.d_model, cfg.vocab_size)
        )
        compute_dtype = cfg.compute_dtype

        def mlp_hidden(head, x):
            # MLPHead.layers_0 + relu over raw param arrays (byte-matching
            # nn.Dense(dtype=compute_dtype): inputs/kernel/bias cast, then
            # x @ k + b).
            k0 = head["layers_0"]["kernel"].astype(compute_dtype)
            b0 = head["layers_0"]["bias"].astype(compute_dtype)
            return jax.nn.relu(jnp.dot(x.astype(compute_dtype), k0) + b0)

        def gathered_head_logit(head, x, actions):
            # Target heads only ever feed TD targets at the dataset action —
            # a [D2]-column gather of layers_1 beats projecting all V logits.
            h = mlp_hidden(head, x).astype(jnp.float32)
            k1 = head["layers_1"]["kernel"].astype(jnp.float32)  # [D2, V]
            b1 = head["layers_1"]["bias"].astype(jnp.float32)
            w = jnp.take(k1.T, actions, axis=0)  # [b, A, D2]
            return jnp.sum(h * w, axis=-1) + b1[actions]

        def fused_loss_fn(params, extras, batch: ILQLBatch):
            params = self.detach_frozen(params)
            labels = batch.input_ids[:, 1:]
            attn1 = batch.attention_mask[:, 1:]
            out = model.apply(
                {"params": params},
                batch.input_ids,
                batch.attention_mask,
                states_ixs=batch.states_ixs,
                actions_ixs=batch.actions_ixs,
                labels=labels,
                labels_mask=attn1,
                compute_q_heads=False,
            )
            # AWAC straight from the fused LM head (out["logprobs"] is fp32,
            # zeroed at masked rows).
            attn = attn1.astype(jnp.float32)
            loss_awac = jnp.sum(-out["logprobs"] * attn) / jnp.maximum(jnp.sum(attn), 1.0)

            hs_actions = jnp.take_along_axis(out["hidden"], batch.actions_ixs[..., None], axis=1)
            actions = action_tokens(batch.input_ids, batch.actions_ixs)
            head_names = ["q1_head"] + (["q2_head"] if m.two_qs else [])
            with jax.named_scope("lm_head"):  # the Q heads: vocabulary-wide, as the LM head is
                Qs, cql_nlls = [], []
                for name in head_names:
                    head = params[name]
                    lp, lse, _ = routed_logprob(
                        mlp_hidden(head, hs_actions).astype(jnp.float32),
                        head["layers_1"]["kernel"],
                        actions,
                        head["layers_1"]["bias"],
                        tied=False,
                        mode=fused_mode,
                        site=name,
                    )
                    # gathered Q at the action = label logit = logprob + logsumexp
                    Qs.append(lp + lse)
                    cql_nlls.append(-lp)
                targetQs = [gathered_head_logit(extras[name], hs_actions, actions) for name in head_names]
            return ilql_loss_terms(
                Qs,
                targetQs,
                cql_nlls,
                out["vs"],
                batch.rewards,
                batch.dones,
                loss_awac,
                gamma=m.gamma,
                tau=m.tau,
                cql_scale=m.cql_scale,
                awac_scale=m.awac_scale,
            )

        def dense_loss_fn(params, extras, batch: ILQLBatch):
            params = self.detach_frozen(params)
            out = model.apply(
                {"params": params},
                batch.input_ids,
                batch.attention_mask,
                states_ixs=batch.states_ixs,
                actions_ixs=batch.actions_ixs,
            )
            hs_actions = jnp.take_along_axis(out["hidden"], batch.actions_ixs[..., None], axis=1)
            target_qs = model.apply({"params": extras}, hs_actions, method="compute_qs")
            return ilql_loss(
                out["logits"].astype(jnp.float32),
                out["qs"],
                target_qs,
                out["vs"],
                batch.input_ids,
                batch.attention_mask,
                batch.actions_ixs,
                batch.rewards,
                batch.dones,
                gamma=m.gamma,
                tau=m.tau,
                cql_scale=m.cql_scale,
                awac_scale=m.awac_scale,
            )

        head_loss_fn = fused_loss_fn if use_fused else dense_loss_fn

        def loss_fn(params, extras, batch: ILQLBatch):
            """The loss and its stats, with the live key chunks the batch's own
            (right) padding takes out of a pass through the flash kernels."""
            loss, stats = head_loss_fn(params, extras, batch)
            share = flash_pad_dead_chunk_share(model.cfg, batch.attention_mask)
            return loss, (stats if share is None else {**stats, "flash/pad_dead_chunk_share": share})

        # Incident-path handle for the graftnum NaN census: the same loss,
        # reachable eagerly (the jitted step donates its inputs). Closes over
        # the LIVE extras at call time, matching what the step just consumed.
        self._numerics_loss_fn = lambda params, batch: loss_fn(
            params, self.state.extras, batch
        )
        # Arming is resolved when the step is BUILT: a disarmed trainer
        # compiles a jaxpr with no numerics reductions, so the serial path
        # stays byte-identical (same contract as spans).
        graftnum = obs_numerics.armed(self.config.train)

        def train_step(state, batch: ILQLBatch):
            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params, state.extras, batch)
            stats = dict(stats)
            with jax.named_scope("optimizer"):
                if self.config.train.nonfinite_guard:
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
            if self.config.train.watch_interval:
                for group, sub in grads.items():
                    stats[f"watch/grad_norm/{group}"] = optax.global_norm(sub)
            if graftnum:
                stats.update(
                    obs_numerics.train_step_stats(grads, state.params, params)
                )
            stats["learning_rate"] = schedule(state.step)
            return state.replace(
                step=state.step + 1, params=params, opt_state=opt_state, bad_steps=bad
            ), stats

        return jax.jit(train_step, donate_argnums=(0,))

    def _numerics_forward(self, batch):
        """Eval-only EAGER forward for the graftnum first-NaN bisector —
        eager so the probe taps in models/lm.py see concrete activations.
        Outputs are discarded; only per-layer finite-ness matters."""
        self.model.apply(
            {"params": self.state.params},
            batch.input_ids,
            batch.attention_mask,
            states_ixs=batch.states_ixs,
            actions_ixs=batch.actions_ixs,
        )

    # ------------------------------------------------------------- callbacks

    def _polyak_sync(self, params, extras, alpha: float):
        """target ← α·online + (1−α)·target
        (reference: trlx/model/nn/ilql_models.py:131-146)."""
        online = {k: params[k] for k in extras}
        return jax.tree_util.tree_map(lambda q, t: alpha * q + (1 - alpha) * t, online, extras)

    def post_backward_callback(self, stats=None):
        """(reference: trlx/model/accelerate_ilql_model.py:46-48)"""
        if self.iter_count % self.config.method.steps_for_target_q_sync == 0:
            # GL001: polyak sync is a jitted dispatch like any other — it must
            # enqueue under the lock so it cannot interleave with a concurrent
            # generate/train dispatch from another thread.
            with trace_span("train/polyak_sync"), self._dispatch_lock:
                prev_extras = self.state.extras
                new_extras = self._sync_fn(self.state.params, self.state.extras, self.config.method.alpha)
            # _sync_fn donates the old target heads (donate_argnums=(1,)).
            sanitize.mark_donated(prev_extras, "_sync_fn(extras) [polyak sync]")
            self.state = self.state.replace(extras=new_extras)

    def post_epoch_callback(self):
        pass

    def prepare_learning(self):
        """(reference: trlx/model/accelerate_ilql_model.py:158-181)"""
        self.eval_dataloader = self.eval_pipeline.create_loader(self.config.train.batch_size)
        self.train_dataloader = self.store.create_loader(self.config.train.batch_size, shuffle=True)
        self.n_updates_per_batch = 1
        self.total_steps = min(
            self.config.train.epochs * len(self.train_dataloader),
            self.config.train.total_steps,
        )

    # -------------------------------------------------------------- tokenize

    def tokenize_ilql(self, texts):
        """BOS + text + EOS (reference: trlx/model/accelerate_ilql_model.py:34-44)."""
        out = []
        for text in texts:
            if not isinstance(text, str):
                out.append(np.asarray(text).reshape(-1))
                continue
            ids = self.tokenizer(text, add_special_tokens=False)["input_ids"]
            if self.tokenizer.bos_token_id is not None:
                ids = [self.tokenizer.bos_token_id] + ids
            if self.tokenizer.eos_token_id is not None:
                ids = ids + [self.tokenizer.eos_token_id]
            out.append(np.asarray(ids[: self.config.train.seq_length], dtype=np.int32))
        return out

"""PPO experience generation: the rollout hot loop.

Redesign of the reference's PPOOrchestrator
(reference: trlx/orchestrator/ppo_orchestrator.py:14-130) around the TPU/host
boundary:

- `trainer.rollout_generate` — ONE jitted program (prefill + while_loop
  decode) per batch shape;
- host: detokenize + user `reward_fn` (arbitrary Python over text — the
  unavoidable host boundary, reference:
  trlx/orchestrator/ppo_orchestrator.py:70-73);
- `trainer.rollout_score` — ONE jitted program computing policy logprobs,
  values, hydra ref logprobs, and per-token KL-penalty rewards (fusing the
  reference's separate forward / forward_hydra / reward arithmetic,
  reference: trlx/orchestrator/ppo_orchestrator.py:79-104).

JAX async dispatch overlaps the next generate with host scoring when the
loader can prefetch (device work is enqueued, not awaited, until arrays are
read) — the reference serializes these phases.
"""

import os
import time
from collections import deque

import jax
import numpy as np

from trlx_tpu.observability.spans import trace_span
from trlx_tpu.models.lm import (cache_bytes, cache_bytes_per_token, cca_state_bytes, compressed_key_bytes, decode_step_bytes,
                                index_key_bytes, layer_window, ring_cache_bytes, ring_slots, state_bytes)
from trlx_tpu.ops.kv_read import kv_keys_read, kv_scale_mults_per_key
from trlx_tpu.parallel.schedule import weight_gather_share
from trlx_tpu.orchestrator import Orchestrator, register_orchestrator
from trlx_tpu.pipeline.overlap import ScoreWorker
from trlx_tpu.resilience.faults import FaultInjected
from trlx_tpu.resilience.retry import call_with_retries
from trlx_tpu.utils import Clock, tree_size_bytes


@register_orchestrator
class PPOOrchestrator(Orchestrator):
    def __init__(self, model, pipeline, reward_fn, metric_fn=None, chunk_size: int = 512):
        super().__init__(pipeline, model)
        self.chunk_size = chunk_size
        self.pipeline_loader = self.pipeline.create_loader(self.chunk_size, shuffle=True)
        self.pipeline_iterator = iter(self.pipeline_loader)
        # Absolute position in the deterministic prompt-chunk schedule
        # (create_loader's fixed seed makes the shuffled chunk sequence a
        # pure function of this counter) — what seek_chunks() navigates by.
        self._chunks_consumed = 0
        self._reward_calls = 0

        # Inject callbacks into the trainer (reference:
        # trlx/orchestrator/ppo_orchestrator.py:41-43).
        self.rl_model.orch = self
        self.rl_model.reward_fn = reward_fn
        self.rl_model.metric_fn = metric_fn

    def score(self, texts):
        """User reward on decoded samples
        (reference: trlx/orchestrator/ppo_orchestrator.py:45-49).

        Hardened: reward_fn is arbitrary user Python, usually crossing a
        network/subprocess boundary — a transient exception or hang costs a
        bounded retry (train.reward_fn_retries / _backoff / _timeout), not
        the run. Fault kinds reward_exc / reward_hang inject both failure
        modes, keyed on the reward-call number."""
        t = self.rl_model.config.train
        self._reward_calls += 1
        call_index = self._reward_calls
        fault_plan = getattr(self.rl_model, "fault_plan", None)

        def call():
            if fault_plan is not None:
                if fault_plan.fire("reward_exc", call_index):
                    raise FaultInjected(f"injected reward_fn exception (call {call_index})")
                if fault_plan.fire("reward_hang", call_index):
                    # Sleep well past the timeout so the hang watchdog, not
                    # luck, decides the outcome.
                    time.sleep(max(t.reward_fn_timeout, 0.1) * 3)
                if fault_plan.fire("reward_drift", call_index):
                    # Latch the health monitor's observed-reward offset from
                    # this call INDEX on — training rewards stay untouched,
                    # only the drift detector's view shifts (the stats-only
                    # drill contract, trlx_tpu/resilience/faults.py). Keyed
                    # by index, not wall clock: earlier calls' observations
                    # may still be in flight on another thread and must stay
                    # clean to seed the baseline.
                    monitor = getattr(self.rl_model, "_health", None)
                    if monitor is not None:
                        monitor.inject_reward_drift(from_call=call_index)
            return self.rl_model.reward_fn(texts)

        return call_with_retries(
            call,
            retries=t.reward_fn_retries,
            backoff=t.reward_fn_backoff,
            timeout=t.reward_fn_timeout,
            description="reward_fn",
        )

    def _next_prompt_batch(self):
        """Pull the next prompt chunk (epoch wrap included) and advance the
        absolute chunk counter — the ONLY way prompts leave the loader, so
        ``_chunks_consumed`` is always the true schedule position."""
        try:
            batch = next(self.pipeline_iterator)
        except StopIteration:
            self.pipeline_iterator = iter(self.pipeline_loader)
            batch = next(self.pipeline_iterator)
        self._chunks_consumed += 1
        return batch

    def chunks_per_unit(self, num_rollouts: int) -> int:
        """Prompt chunks one experience phase consumes — the elastic
        fleet's work-unit width (unit u owns chunks [u*w, (u+1)*w))."""
        return max(1, -(-int(num_rollouts) // max(1, int(self.chunk_size))))

    def seek_chunks(self, target: int):
        """Deterministically position the prompt stream at absolute chunk
        ``target``. The loader's shuffle rng is seeded (pipeline.create_
        loader default seed), so the chunk sequence is identical on every
        worker; seeking backward rebuilds the loader (fresh rng → same
        sequence from 0) and both directions skip forward by discarding
        chunks. This is what lets ANY elastic worker produce work unit u's
        exact prompt shard — the reclaim path's correctness (and the
        N-worker staleness-0 bitwise-parity proof) rests on it. Assumes the
        loader's constant-chunk schedule (drop_last, the fleet default)."""
        target = int(target)
        if target < self._chunks_consumed:
            self.pipeline_loader = self.pipeline.create_loader(self.chunk_size, shuffle=True)
            self.pipeline_iterator = iter(self.pipeline_loader)
            self._chunks_consumed = 0
        while self._chunks_consumed < target:
            self._next_prompt_batch()

    def _generate_next_chunk(self, fused=None, snapshot=None):
        """`fused=None` follows the trainer's fused_rollout setting; False
        forces the plain generate+recompute path (benchmark baselines).
        `snapshot` routes generation through a boundary param snapshot
        instead of the live (donated) TrainState — the staleness>0 producer."""
        # The sampling key is derived from the ABSOLUTE chunk index, never
        # from this process's rng-consumption history: chunk c's episodes are
        # a pure function of (weights, train.seed, c), so an elastic worker
        # reproducing a reclaimed unit — or N workers splitting the schedule
        # — samples exactly what the serial schedule would have.
        rng = self.rl_model.chunk_rng(self._chunks_consumed)
        batch = self._next_prompt_batch()
        P = batch["input_ids"].shape[1]
        if fused is None:
            fused = getattr(self.rl_model, "fused_rollout", False)
        # Dispatched, not awaited: jax queues the compiled prefill+decode
        # program and returns immediately. With fused rollout stats the same
        # program also emits the policy logprobs/values/branch-hiddens the
        # scorer needs (aux), so scoring is a ref-branch replay only.
        if fused:
            tokens, mask, stats, prefill = self.rl_model.rollout_generate_fused(
                batch["input_ids"], batch["attention_mask"], snapshot=snapshot, rng=rng
            )
            return tokens, mask, P, (stats, prefill)
        tokens, mask = self.rl_model.rollout_generate(
            batch["input_ids"], batch["attention_mask"], snapshot=snapshot, rng=rng
        )
        return tokens, mask, P, None

    def make_experience(
        self,
        num_rollouts: int = 1024,
        iter_count: int = 0,
        store=None,
        snapshot=None,
        staleness: int = 0,
        stop=None,
        weight_poll=None,
    ):
        """Fill a rollout store with `num_rollouts` rollout rows
        (reference: trlx/orchestrator/ppo_orchestrator.py:50-130).

        PIPELINED at three depths:

        1. Always: the next chunk's generation is dispatched to the device
           BEFORE the current chunk crosses the host boundary (decode +
           reward_fn), so the TPU decodes chunk i+1 while the host scores
           chunk i — JAX async dispatch, no threads.
        2. ``rl_model.overlap_rollouts``: host scoring moves onto a single
           FIFO ScoreWorker thread, so the MAIN thread keeps dispatching /
           pulling device chunks while the worker runs decode + reward_fn —
           the rollout/overlap idea of the pipeline-RLHF line of work
           (PAPERS.md). FIFO preserves the serial path's reward-call order
           and store push order exactly.
        3. The RolloutProducer calls this with an explicit ``store`` (a fresh
           double buffer), a boundary param ``snapshot`` (staleness>0: the
           live TrainState is donated mid-train), the store's ``staleness``
           for the per-sample column, and a ``stop`` poll so shutdown drains
           between chunks.

        Rows are pushed as whole chunks into the native column store
        (trlx_tpu/native/collate.cpp) — no per-sample Python objects."""
        rl = self.rl_model
        if getattr(rl, "rollout_engine_enabled", False):
            # Continuous-batching path (method.rollout_engine): the slot
            # engine streams finished episodes; everything downstream of
            # generation (reward → device scoring → store push) is shared.
            return self._make_experience_engine(
                num_rollouts=num_rollouts,
                iter_count=iter_count,
                store=store,
                snapshot=snapshot,
                staleness=staleness,
                stop=stop,
                weight_poll=weight_poll,
            )
        # ``weight_poll`` (in-flight weight updates) is an engine-path
        # contract: the chunked whole-batch path has no sync boundary to
        # adopt at mid-phase, so a poller is silently unused here and the
        # phase keeps its boundary snapshot — same behavior as PR 16.
        store = store if store is not None else rl.store
        record_staleness = bool(getattr(store, "record_staleness", False))
        timer = getattr(rl, "_phase_timer", None)
        use_worker = bool(getattr(rl, "overlap_rollouts", False)) and not getattr(
            rl, "has_reward_model", False
        )

        monitor = getattr(rl, "_health", None)
        # Lineage: the weights these rollouts come from. A boundary snapshot
        # carries the train iteration it was copied at; the serial /
        # staleness-0 paths read the LIVE state, whose version is iter_count.
        weight_version = iter_count
        if isinstance(snapshot, dict):
            weight_version = int(snapshot.get("version", iter_count))

        def note_chunk(tokens_h, mask_h, P, scores, reward_call=None):
            # Health feed for one scored chunk: reward-drift observation,
            # degenerate-sample sentinels, lineage record. Runs on whichever
            # thread finishes the chunk (the make_experience thread) — the
            # monitor serializes internally. reward_call keys the drift
            # drill's offset to this chunk's reward-call index.
            if monitor is not None:
                monitor.observe_chunk(
                    tokens_h,
                    mask_h,
                    P,
                    scores=scores,
                    weight_version=weight_version,
                    staleness=staleness,
                    step=iter_count,
                    reward_call=reward_call,
                )

        n_collected = 0
        clock = Clock()
        # Per-phase accounting (head-to-head attribution): generate-blocked,
        # host decode+reward, device scoring, store push. With pipelining the
        # generate time that host work hides does NOT show up in gen_s — it
        # reports residual blocking, which is the honest pipelined cost.
        gen_s = reward_s = score_s = push_s = 0.0
        gen_tokens = 0
        decode_steps = []
        episode_steps = []
        step_budget = 0
        # Keys the decode steps' attention read, and what full-cache reads
        # would have touched (ops/kv_read.py): from shapes and step counts.
        lm_cfg = rl.model.cfg
        n_soft = lm_cfg.n_soft_tokens
        # the layers that keep keys: a state-space or kda layer reads none
        # (a looped stack: every (loop, layer) entry of the cache is read a step)
        key_layers = [i for i in range(lm_cfg.n_layer) if lm_cfg.mixer(i) == "attention"] * lm_cfg.n_loops
        layer_windows = [layer_window(lm_cfg, i) for i in key_layers]
        cache_alloc = gen_rows = gen_len = 0  # bytes of the cache the generate program allocated (the last chunk's), its rows and slots
        kv_keys = np.zeros(2, dtype=np.int64)
        sparse_read = []  # attention "sparse" or an indexed latent layer: the decode steps' own count of the slots they read, one reading a chunk
        experts_touched = []  # a model with expert layers: one reading a chunk
        # Final-chunk stats for logging; placeholders are never logged (the
        # aborted path returns before the tracker call).
        last_scores = np.zeros((1,), dtype=np.float32)
        last_kl = np.zeros((1, 1), dtype=np.float32)

        def push_rows(tokens_h, mask_h, P, logprobs, values, rewards):
            # Store holds process-local rows; put_batch re-shards them on the
            # way back to the device at train time.
            nonlocal push_s
            with trace_span("rollout/push", rows=int(tokens_h.shape[0])) as span:
                # With prompt bucketing the chunks arrive at per-bucket widths P,
                # but the rollout store fixes its query width on the FIRST push
                # and the train step compiles at the single full prompt_length —
                # so the query region is re-left-padded to the trainer's global
                # width here, on the host, before storage. Pad rows are mask-0:
                # the training forward sees exactly the tokens generation saw.
                q_ids, q_mask = tokens_h[:, :P], mask_h[:, :P]
                P_full = int(getattr(rl, "prompt_length", P))
                if P < P_full:
                    pad_id = int(getattr(rl, "pad_token_id", 0))
                    pad = np.full((q_ids.shape[0], P_full - P), pad_id, dtype=np.asarray(q_ids).dtype)
                    q_ids = np.concatenate([pad, q_ids], axis=1)
                    q_mask = np.concatenate([np.zeros_like(pad), np.asarray(q_mask)], axis=1)
                rows = {
                    "query_tensors": q_ids,
                    "query_mask": q_mask,
                    "response_tensors": tokens_h[:, P:],
                    "response_mask": mask_h[:, P:],
                    "logprobs": logprobs,
                    "values": values,
                    "rewards": rewards,
                }
                if record_staleness:
                    rows["staleness"] = np.full((q_ids.shape[0], 1), float(staleness), dtype=np.float32)
                store.push_batch(rows)
            push_s += span.seconds

        def finish_chunk(ctx, scored):
            # Device scoring + pulls + store push for one scored chunk. Runs
            # on the make_experience thread ONLY — all device dispatch stays
            # on one thread, so program order is deterministic.
            nonlocal score_s, last_scores, last_kl
            scores, reward_call = scored
            with trace_span("rollout/score_device", step=iter_count) as span:
                if ctx["gen_aux"] is not None:
                    logprobs, values, rewards, kl = rl.rollout_score_fused(
                        ctx["tokens"], ctx["mask"], scores, ctx["gen_aux"], snapshot=snapshot
                    )
                else:
                    logprobs, values, rewards, kl = rl.rollout_score(
                        ctx["tokens"], ctx["mask"], scores, snapshot=snapshot
                    )
                logprobs, values, rewards, kl = rl.to_local_host((logprobs, values, rewards, kl))
            score_s += span.seconds
            push_rows(ctx["tokens_h"], ctx["mask_h"], ctx["P"], logprobs, values, rewards)
            note_chunk(ctx["tokens_h"], ctx["mask_h"], ctx["P"], scores, reward_call)
            last_scores, last_kl = np.asarray(scores), kl

        def host_score(args):
            # Host boundary: decode → user reward_fn. Process-LOCAL on every
            # host: these are this process's rows only, reward_fn scores
            # them, and rollout_score's put_batch reassembles the global
            # scores array — so a multi-host pod never materializes
            # non-addressable shards on any single host (the reference's
            # per-rank reward_fn semantics, reference:
            # trlx/orchestrator/ppo_orchestrator.py:73). Runs on the
            # ScoreWorker thread when overlap is on (self.score's retry/
            # timeout wrapper nests fine there — its watchdog is its own
            # daemon thread), inline otherwise.
            tokens_h, mask_h = args
            # Lands on whichever thread runs the scoring (the ScoreWorker's
            # lane when overlap is on, the main lane otherwise) — exactly the
            # attribution the trace viewer should show.
            nonlocal reward_s
            with trace_span("rollout/decode", step=iter_count) as decode:
                texts_or_tokens = rl.decode(tokens_h, mask_h)
            with trace_span("rollout/reward_fn", step=iter_count) as reward:
                scores = np.asarray(self.score(texts_or_tokens), dtype=np.float32)
            if worker is None:
                reward_s += decode.seconds + reward.seconds  # the worker times its own busy_s
            # The call index this chunk was scored under (scoring runs
            # sequentially on one thread, so the counter is stable here) —
            # finish_chunk hands it to the health monitor's lineage feed.
            return scores, self._reward_calls

        worker = None
        inflight = None
        depth = 0
        if use_worker:
            depth = max(1, int(getattr(rl.config.method, "score_queue_depth", 2) or 2))
            worker = ScoreWorker(host_score, depth=depth)
            inflight = deque()

        # The rollout's window of process counters and tick gaps (observability/
        # anomaly.py): first `rollout/generate` to last `rollout/push`.
        proc = getattr(rl, "_rollout_proc", None)
        if proc is not None:
            proc.open()
        with trace_span("rollout/generate", step=iter_count, dispatch=True) as span:
            with trace_span("rollout/generate_dispatch"):
                pending = self._generate_next_chunk(snapshot=snapshot)
        gen_s += span.seconds
        heartbeat = getattr(rl, "heartbeat", None)
        aborted = False
        try:
            while True:
                if stop is not None and stop():
                    # Producer shutdown mid-phase: abandon the partial store
                    # (the producer drops it) without waiting out the queue.
                    aborted = True
                    return
                if heartbeat is not None:
                    # Rollout progress stamp: without it, a long experience
                    # phase looks identical to a wedged host in the stall
                    # report — the phase tag tells the CollectiveTimeout
                    # diagnostic this host was generating, not stuck.
                    heartbeat.beat(step=iter_count, phase="rollout")
                tokens, mask, P, gen_aux = pending
                # Rows THIS process will store (num_rollouts is per-process,
                # the reference's per-rank semantics). Static shape — no
                # device sync.
                n_proc = jax.process_count()
                if int(tokens.shape[0]) % n_proc != 0 or int(tokens.shape[0]) < n_proc:
                    raise ValueError(
                        f"rollout chunk of {int(tokens.shape[0])} rows does not divide "
                        f"evenly over {n_proc} processes — pick a chunk_size that is a "
                        "positive multiple of the process count"
                    )
                chunk_rows = int(tokens.shape[0]) // n_proc
                need_more = n_collected + chunk_rows < num_rollouts
                # Generate-BLOCKED wall: the next chunk's dispatch and this
                # chunk's grid pull, each a child span.
                with trace_span("rollout/generate", step=iter_count) as span:
                    if need_more:
                        with trace_span("rollout/generate_dispatch"):
                            pending = self._generate_next_chunk(snapshot=snapshot)
                    # ONE device→host pull of the generation grids per chunk —
                    # both reward paths and the store push reuse these host rows.
                    with trace_span("rollout/pull"):
                        tokens_h, mask_h = rl.to_local_host((tokens, mask))
                gen_s += span.seconds
                ds = rl.rollout_decode_stats(mask_h, P)
                gen_tokens += ds["gen_tokens"]
                decode_steps.append(ds["decode_steps"])
                cache_len = mask_h.shape[1] + n_soft
                if lm_cfg.attention == "sparse":
                    from trlx_tpu.models import sparse

                    # a decode step gathers a fixed count of slots (the static shape of ops/kv_read.py
                    # attend_selected's gather), whatever the ranged read would have taken: from shapes, as
                    # every cell's; what the step's softmax saw of them is the loop's own counter, below
                    gathered = sparse.gathered_blocks(lm_cfg, -(-cache_len // lm_cfg.sparse_block)) * lm_cfg.sparse_block
                    kv_keys += np.array([gathered, cache_len]) * ds["decode_steps"] * len(key_layers)
                elif lm_cfg.index_topk and cache_len > lm_cfg.index_topk:
                    # an indexed latent layer's step gathers index_topk entries (models/indexer.py indexed_read)
                    kv_keys += np.array([lm_cfg.index_topk, cache_len]) * ds["decode_steps"] * len(key_layers)
                else:
                    kv_keys += np.array(kv_keys_read(
                        cache_len, P + n_soft, ds["decode_steps"], layer_windows,
                        [ring_slots(lm_cfg, i, cache_len) for i in key_layers],
                    ))
                cache_alloc = cache_bytes(lm_cfg, mask_h.shape[0], cache_len)
                gen_rows, gen_len = mask_h.shape[0], cache_len
                episode_steps.extend(int(v) for v in ds["episode_steps"])
                step_budget = ds["decode_step_budget"]
                if gen_aux is not None and "experts_touched_per_step" in gen_aux[0]:
                    # The loop's own counter (ops/generate.py), read after
                    # the rollout's grids: the program has finished.
                    experts_touched.append(float(gen_aux[0]["experts_touched_per_step"]))
                if gen_aux is not None and "sparse_keys_read_share" in gen_aux[0]:
                    sparse_read.append(float(gen_aux[0]["sparse_keys_read_share"]))

                if getattr(rl, "has_reward_model", False):
                    # On-device learned RM: the whole scoring pass (policy
                    # logprobs/values, hydra ref KL, RM scores) is ONE fused
                    # sharded program — no decode, no host reward boundary
                    # (and so nothing for a score worker to overlap).
                    with trace_span("rollout/score_rm", step=iter_count) as span:
                        logprobs, values, rewards, kl, scores = rl.rollout_score_rm(
                            tokens, mask, snapshot=snapshot
                        )
                        scores = rl.to_local_host(scores)
                        logprobs, values, rewards, kl = rl.to_local_host(
                            (logprobs, values, rewards, kl)
                        )
                    score_s += span.seconds
                    push_rows(tokens_h, mask_h, P, logprobs, values, rewards)
                    note_chunk(tokens_h, mask_h, P, scores)
                    last_scores, last_kl = np.asarray(scores), kl
                elif worker is not None:
                    # Hand decode+reward to the worker; keep the device busy.
                    # Drain completed scores eagerly (FIFO pairs results with
                    # the inflight contexts) and block only when the queue of
                    # decoded-but-unscored chunks hits its depth bound.
                    worker.submit((tokens_h, mask_h))
                    inflight.append(
                        {
                            "tokens": tokens,
                            "mask": mask,
                            "P": P,
                            "gen_aux": gen_aux,
                            "tokens_h": tokens_h,
                            "mask_h": mask_h,
                        }
                    )
                    while inflight and (len(inflight) > depth or worker.ready()):
                        finish_chunk(inflight.popleft(), worker.result())
                else:
                    scores = host_score((tokens_h, mask_h))
                    # Device: score rollouts. Fused: ref-branch replay only,
                    # the policy stats rode along with generation. Unfused:
                    # full policy forward + ref logits + KL in one program.
                    finish_chunk(
                        {
                            "tokens": tokens,
                            "mask": mask,
                            "P": P,
                            "gen_aux": gen_aux,
                            "tokens_h": tokens_h,
                            "mask_h": mask_h,
                        },
                        scores,
                    )
                n_collected += chunk_rows
                if not need_more:
                    break
            if worker is not None:
                while inflight:
                    if stop is not None and stop():
                        aborted = True
                        return
                    finish_chunk(inflight.popleft(), worker.result())
        finally:
            if worker is not None:
                worker.close()
                # Host decode+reward wall, measured on the worker. Joined, so
                # the read is race-free.
                reward_s += worker.busy_s
            if proc is not None and not aborted:
                rl._rollout_obs = proc.close()
            if timer is not None and not aborted:
                timer.add("rollout", gen_s + score_s + push_s)
                timer.add("score", reward_s)

        # The rollout's own counters and its record: host work after the last
        # push, with the device idle (three `eval_shape` of the cache: 36 ms a
        # cycle at 24 layers; PERF.md section 6, PR 35).
        with trace_span("rollout/stats"):
            exp_time = clock.tick()
            # Process-local statistics of the final chunk (logging only).
            stats = {
                "exp_time": exp_time,
                "exp_gen_s": gen_s,
                "exp_reward_s": reward_s,
                "exp_score_s": score_s,
                "exp_push_s": push_s,
                # Decode-loop observability: generated tokens per second of
                # generate-BLOCKED wall time (pipelining hides device time
                # behind host work, so this is a lower bound on the device
                # rate), and the per-chunk while_loop steps actually executed
                # vs the max_new_tokens budget (early-exit savings).
                "exp_decode_tokens_per_s": gen_tokens / max(gen_s, 1e-9),
                "exp_decode_steps": float(np.mean(decode_steps)),
                # Dispatch/token split (same keys as the engine path): the
                # static-batch loop advances every row one token per step, so
                # dispatches = total while-loop steps and tokens = the unpadded
                # generated-token count.
                "exp_decode_dispatches": float(np.sum(decode_steps)),
                "exp_decode_tokens": float(gen_tokens),
                "exp_decode_step_budget": float(step_budget),
                # Per-EPISODE decode steps vs the per-chunk max above: their gap
                # is the straggler overhead the static batch pays (see
                # rollout_decode_stats; the engine path logs the same key).
                "exp_decode_steps_per_episode": (
                    float(np.mean(episode_steps)) if episode_steps else 0.0
                ),
                "rollout_mean_score": float(np.mean(last_scores)),
                "rollout_mean_kl": float(np.mean(np.asarray(last_kl).sum(-1))),
                "exp_per_sec": num_rollouts / max(exp_time, 1e-9),
            }
            if record_staleness:
                stats["exp_staleness"] = float(staleness)
            # Surfaced by progress_line at the next log boundary.
            rl._last_exp_stats = {
                "exp_per_sec": stats["exp_per_sec"],
                "rollout/decode_steps": stats["exp_decode_dispatches"],
                "rollout/kv_read_share": float(kv_keys[0] / kv_keys[1]) if kv_keys[1] else 1.0,
                "rollout/cache_bytes_per_token": float(cache_bytes_per_token(lm_cfg)),
                "rollout/cache_bytes": float(cache_alloc),
            }
            if lm_cfg.kv_cache_quant:
                # an int8 cache: a read applies a key's scale once a key, not once an element (a mesh: once an element)
                rl._last_exp_stats["rollout/kv_scale_mults_per_key"] = kv_scale_mults_per_key(
                    lm_cfg.n_head, lm_cfg.kv_heads, lm_cfg.head_dim)
            if lm_cfg.window_cache == "ring" and cache_alloc:
                rl._last_exp_stats["rollout/ring_cache_share"] = ring_cache_bytes(lm_cfg, gen_rows, gen_len) / cache_alloc
            if lm_cfg.attention == "cca" and cache_alloc:
                # what the layers keep beside their slots: the convolutions' window and the shifted value
                rl._last_exp_stats["rollout/cca_state_bytes"] = float(cca_state_bytes(lm_cfg, gen_rows))
                # the cache over what keys and values 2 x d_model wide a token a layer would take
                rl._last_exp_stats["rollout/cca_cache_share"] = cache_alloc / (
                    gen_rows * gen_len * lm_cfg.n_layer * 2 * lm_cfg.d_model * lm_cfg.compute_dtype.itemsize)
            if lm_cfg.attention == "sparse" and cache_alloc:
                rl._last_exp_stats["rollout/compressed_key_bytes"] = float(compressed_key_bytes(lm_cfg, gen_rows, gen_len))
                if sparse_read:
                    rl._last_exp_stats["rollout/sparse_keys_read_share"] = float(np.mean(sparse_read))
            if lm_cfg.index_topk and cache_alloc:
                rl._last_exp_stats["rollout/index_key_bytes"] = float(index_key_bytes(lm_cfg, gen_rows, gen_len))
                if sparse_read:
                    rl._last_exp_stats["rollout/dsa_keys_read_share"] = float(np.mean(sparse_read))
            if experts_touched:
                rl._last_exp_stats["rollout/experts_touched"] = float(np.mean(experts_touched))
            if (lm_cfg.has_state or lm_cfg.n_loops > 1 or lm_cfg.index_topk) and cache_alloc:
                # What a decode step must move, from shapes: the weights once (a
                # looped stack's blocks once a loop), the state read and written,
                # the keys the ranged read took (the mean over the rollout's steps).
                steps = max(1, int(np.sum(decode_steps)))
                keys_a_step = kv_keys[0] / steps / max(1, len(key_layers))
                trunk = rl.state.params["transformer"]
                stack = tree_size_bytes({k: v for k, v in trunk.items() if k.startswith("h_")})
                # an untied table is looked up (a row a token), not read (a tied one is the head);
                # a decode step leaves the exit gate out
                read_once = tree_size_bytes({k: v for k, v in trunk.items()
                                             if k != "exit_gate" and (k != "wte" or lm_cfg.tie_word_embeddings)})
                needed, state_rw = decode_step_bytes(lm_cfg, gen_rows, keys_a_step, read_once, stack, cache_len=gen_len)
                rl._last_exp_stats["rollout/step_bytes_needed"] = float(needed)
                if lm_cfg.has_state:
                    rl._last_exp_stats.update({
                        "rollout/state_bytes": float(state_bytes(lm_cfg, gen_rows)),
                        "rollout/state_bytes_per_row": float(state_bytes(lm_cfg, 1)),
                        ("ssm" if lm_cfg.has_ssm else lm_cfg.state_layer_name) + "/state_rw_share": float(state_rw / needed),
                    })
                if lm_cfg.n_loops > 1:
                    rl._last_exp_stats.update({
                        "loops/n_loops": float(lm_cfg.n_loops),
                        "loops/block_applications": float(lm_cfg.cache_entries),
                        "loops/weight_read_share": float(lm_cfg.n_loops * stack / needed),
                    })
            gather_share = weight_gather_share(rl._weight_gathers["generate"])
            if gather_share is not None:
                # the generate program on a partitioned mesh: the prefill gathers
                # its weights, the decode loop keeps the shards
                rl._last_exp_stats["parallel/weight_gather_share"] = gather_share
            rl.tracker.log(stats, step=iter_count)

    def _make_experience_engine(
        self,
        num_rollouts: int,
        iter_count: int,
        store=None,
        snapshot=None,
        staleness: int = 0,
        stop=None,
        weight_poll=None,
    ):
        """Continuous-batching experience generation (method.rollout_engine).

        The slot engine replaces chunk-wise generate: all ``num_rollouts``
        prompts are submitted up front, the engine streams finished episodes
        back in COMPLETION order (short responses free their slot early and a
        queued prompt refills it), and episodes are re-assembled into
        chunk_size batches at the trainer's full prompt width for the SAME
        downstream pipeline as the chunked path — host decode + reward_fn
        (optionally on the ScoreWorker thread), unfused device scoring, store
        push, health feed. The phase drains fully before returning: no episode
        crosses a phase boundary, so every stored row's lineage is this
        phase's weight handoffs (explicit `update_weights`, never the live
        donated TrainState).

        ``weight_poll`` (optional zero-arg callable → None or
        ``(variables, version)``) is checked once per engine sync: a
        non-None result is pushed into the RUNNING engine mid-phase —
        in-flight weight updates, PipelineRL-style. No drain, no abort:
        the engine stages the push and swaps at its next sync boundary,
        and harvested episodes carry per-token ``version_spans``. Returns
        ``{"version_spans": [[version, n_tokens], ...]}`` (the phase
        aggregate) on success, None on abort."""
        rl = self.rl_model
        store = store if store is not None else rl.store
        record_staleness = bool(getattr(store, "record_staleness", False))
        timer = getattr(rl, "_phase_timer", None)
        has_rm = bool(getattr(rl, "has_reward_model", False))
        # On-device RM scoring has no host reward boundary — nothing for a
        # score worker thread to overlap (same rule as the chunked path).
        use_worker = bool(getattr(rl, "overlap_rollouts", False)) and not has_rm
        monitor = getattr(rl, "_health", None)
        heartbeat = getattr(rl, "heartbeat", None)
        weight_version = iter_count
        if isinstance(snapshot, dict):
            weight_version = int(snapshot.get("version", iter_count))

        # Versioned weight handoff: re-resolve (and re-quantize, when the KV
        # path is int8) the decode variables once per phase. The engine holds
        # its own reference — training may donate the TrainState underneath.
        engine = rl.rollout_engine()
        engine.update_weights(rl.rollout_engine_variables(snapshot), version=weight_version)

        P_full = int(rl.prompt_length)
        R = int(rl.response_length)
        pad_id = int(getattr(rl, "pad_token_id", 0))
        chunk = max(1, min(int(self.chunk_size), int(num_rollouts)))

        # Submit EXACTLY num_rollouts prompts — the engine's queue empties as
        # the phase drains, so the next phase starts from a clean engine.
        submitted = 0
        while submitted < num_rollouts:
            batch = self._next_prompt_batch()
            ids = np.asarray(batch["input_ids"])
            msk = np.asarray(batch["attention_mask"])
            take = min(int(ids.shape[0]), num_rollouts - submitted)
            engine.submit(ids[:take], msk[:take])
            submitted += take

        n_collected = 0
        clock = Clock()
        gen_s = reward_s = score_s = push_s = 0.0
        episode_steps = []
        span_agg = {}  # version -> total tokens, the phase-level lineage
        fault_plan = getattr(rl, "fault_plan", None)
        sync_tick = 0
        last_scores = np.zeros((1,), dtype=np.float32)
        last_kl = np.zeros((1, 1), dtype=np.float32)

        def push_rows(tokens_h, mask_h, logprobs, values, rewards):
            # Episodes are assembled at P_full already — no re-padding.
            nonlocal push_s
            with trace_span("rollout/push", rows=int(tokens_h.shape[0])) as span:
                rows = {
                    "query_tensors": tokens_h[:, :P_full],
                    "query_mask": mask_h[:, :P_full],
                    "response_tensors": tokens_h[:, P_full:],
                    "response_mask": mask_h[:, P_full:],
                    "logprobs": logprobs,
                    "values": values,
                    "rewards": rewards,
                }
                if record_staleness:
                    rows["staleness"] = np.full(
                        (tokens_h.shape[0], 1), float(staleness), dtype=np.float32
                    )
                store.push_batch(rows)
            push_s += span.seconds

        def finish_chunk(ctx, scored):
            # Device scoring + pulls + store push; make_experience thread
            # only, so device program order stays deterministic. The engine
            # path always scores UNFUSED (full policy forward): sampled-token
            # stats never rode along with slot decode.
            nonlocal score_s, last_scores, last_kl
            with trace_span("rollout/score_device", step=iter_count) as span:
                if has_rm:
                    # On-device learned RM over the harvested chunk: policy
                    # logprobs/values, hydra ref KL, and RM scores in ONE
                    # sharded program — the same rollout_score_rm the chunked
                    # path runs, fed assembled engine episodes. ``scored`` is
                    # None on this branch (host_score never ran).
                    reward_call = None
                    logprobs, values, rewards, kl, scores = rl.rollout_score_rm(
                        ctx["tokens"], ctx["mask"], snapshot=snapshot
                    )
                    scores = rl.to_local_host(scores)
                else:
                    scores, reward_call = scored
                    logprobs, values, rewards, kl = rl.rollout_score(
                        ctx["tokens"], ctx["mask"], scores, snapshot=snapshot
                    )
                logprobs, values, rewards, kl = rl.to_local_host((logprobs, values, rewards, kl))
            score_s += span.seconds
            push_rows(ctx["tokens_h"], ctx["mask_h"], logprobs, values, rewards)
            if monitor is not None:
                monitor.observe_chunk(
                    ctx["tokens_h"],
                    ctx["mask_h"],
                    P_full,
                    scores=scores,
                    weight_version=weight_version,
                    staleness=staleness,
                    step=iter_count,
                    reward_call=reward_call,
                    version_spans=ctx.get("version_spans"),
                )
            last_scores, last_kl = np.asarray(scores), kl

        def host_score(args):
            # Same host boundary as the chunked path (see make_experience's
            # host_score for the multi-host rationale).
            tokens_h, mask_h = args
            with trace_span("rollout/decode", step=iter_count):
                texts_or_tokens = rl.decode(tokens_h, mask_h)
            with trace_span("rollout/reward_fn", step=iter_count):
                scores = np.asarray(self.score(texts_or_tokens), dtype=np.float32)
            return scores, self._reward_calls

        def assemble(eps):
            # Episodes arrive at their bucket widths; left-pad the prompt
            # region to the trainer's global width (pad rows mask-0, same
            # rule as the chunked push_rows) so ONE score program shape
            # serves every chunk.
            n = len(eps)
            tokens_h = np.full((n, P_full + R), pad_id, dtype=np.int32)
            mask_h = np.zeros((n, P_full + R), dtype=np.int32)
            chunk_spans = {}
            for i, e in enumerate(eps):
                w = int(e.prompt_ids.shape[0])
                tokens_h[i, P_full - w : P_full] = e.prompt_ids
                mask_h[i, P_full - w : P_full] = e.prompt_mask
                tokens_h[i, P_full:] = e.response_ids
                mask_h[i, P_full:] = e.response_mask
                episode_steps.append(int(e.decode_steps))
                # Per-token weight-version provenance: aggregate the
                # episode spans into a chunk histogram (and the phase one)
                # for the lineage/stream records.
                for v, k in e.version_spans or ((e.weight_version, e.decode_steps),):
                    chunk_spans[v] = chunk_spans.get(v, 0) + int(k)
                    span_agg[v] = span_agg.get(v, 0) + int(k)
            dev = rl.put_batch({"tokens": tokens_h, "mask": mask_h})
            return {
                "tokens": dev["tokens"],
                "mask": dev["mask"],
                "tokens_h": tokens_h,
                "mask_h": mask_h,
                "version_spans": sorted(
                    ([v, k] for v, k in chunk_spans.items()),
                    key=lambda s: (s[0] is None, s[0]),
                ),
            }

        worker = None
        inflight = None
        depth = 0
        if use_worker:
            depth = max(1, int(getattr(rl.config.method, "score_queue_depth", 2) or 2))
            worker = ScoreWorker(host_score, depth=depth)
            inflight = deque()

        finished_buf = []
        proc = getattr(rl, "_rollout_proc", None)  # as in make_experience
        if proc is not None:
            proc.open()
        aborted = False
        ok = False
        try:
            while n_collected < num_rollouts:
                if stop is not None and stop():
                    aborted = True
                    engine.abort()
                    return
                if heartbeat is not None:
                    heartbeat.beat(step=iter_count, phase="rollout")
                if weight_poll is not None:
                    pushed = weight_poll()
                    if pushed is not None:
                        # In-flight update: staged now, adopted at the top
                        # of engine.step() — the sync boundary. Live slots
                        # keep decoding; episodes split into version spans.
                        new_vars, new_version = pushed
                        engine.update_weights(new_vars, version=new_version)
                sync_tick += 1
                if fault_plan is not None and fault_plan.fire(
                    "mid_decode_host_kill", sync_tick
                ):
                    # Abrupt mid-phase death with slots live: no cleanup, no
                    # final heartbeat — the surviving hosts' decode-sync
                    # collective guard must turn this into exit 117 + an
                    # incident bundle naming this host and their slot states.
                    os._exit(1)
                with trace_span("rollout/generate", step=iter_count, engine=True) as span:
                    eps = engine.step()
                gen_s += span.seconds
                finished_buf.extend(eps)
                if not eps and engine.idle and n_collected + len(finished_buf) < num_rollouts:
                    raise RuntimeError(
                        "rollout engine went idle before the phase collected "
                        f"{num_rollouts} episodes (have {n_collected + len(finished_buf)})"
                    )
                # Flush full chunks — plus the final partial chunk once every
                # submitted prompt has come back.
                while len(finished_buf) >= chunk or (
                    finished_buf and n_collected + len(finished_buf) == num_rollouts
                ):
                    take = min(chunk, len(finished_buf))
                    batch_eps, finished_buf = finished_buf[:take], finished_buf[take:]
                    ctx = assemble(batch_eps)
                    if worker is not None:
                        worker.submit((ctx["tokens_h"], ctx["mask_h"]))
                        inflight.append(ctx)
                        while inflight and (len(inflight) > depth or worker.ready()):
                            finish_chunk(inflight.popleft(), worker.result())
                    elif has_rm:
                        finish_chunk(ctx, None)
                    else:
                        t = time.time()
                        scored = host_score((ctx["tokens_h"], ctx["mask_h"]))
                        reward_s += time.time() - t
                        finish_chunk(ctx, scored)
                    n_collected += take
            if worker is not None:
                while inflight:
                    if stop is not None and stop():
                        aborted = True
                        engine.abort()
                        return
                    finish_chunk(inflight.popleft(), worker.result())
            ok = True
        finally:
            if not ok:
                # Error or stop mid-phase: drop queued prompts and in-flight
                # slots so the NEXT phase's episode count starts from zero —
                # a leftover slot would otherwise leak a stale-weights
                # episode into it.
                engine.abort()
            if worker is not None:
                worker.close()
                reward_s += worker.busy_s
            if proc is not None and ok:
                rl._rollout_obs = proc.close()
            if timer is not None and not aborted:
                timer.add("rollout", gen_s + score_s + push_s)
                timer.add("score", reward_s)
        if aborted:
            return

        if jax.process_count() > 1:
            # Multi-process engine phase: every host must have made the SAME
            # admission/harvest decisions (the decode program is collective).
            # A desynced slot schedule is caught here by host name at the
            # phase boundary — not as a hung collective next phase. The
            # outer guard adds the engine's slot states to the incident
            # bundle when a PEER never arrives (mid_decode_host_kill: on
            # meshes whose decode has no cross-host comm, this allgather is
            # where survivors first block on the dead host).
            from trlx_tpu.resilience import distributed as dist_res

            with dist_res.collective_guard(
                "engine/schedule_verify",
                detail=lambda: {"slot_states": engine.slot_states()},
            ):
                dist_res.verify_engine_schedule(
                    engine.schedule_fingerprint(), phase=iter_count
                )

        eng = engine.stats(reset=True)
        exp_time = clock.tick()
        stats = {
            "exp_time": exp_time,
            "exp_gen_s": gen_s,
            "exp_reward_s": reward_s,
            "exp_score_s": score_s,
            "exp_push_s": push_s,
            # Engine-BLOCKED rate (admission + decode dispatch + harvest per
            # step() call); the engine's own engine/decode_tokens_per_s gauge
            # below isolates the pure jitted-decode rate.
            "exp_decode_tokens_per_s": float(eng.get("engine/gen_tokens", 0.0))
            / max(gen_s, 1e-9),
            "exp_decode_steps": float(eng.get("engine/decode_steps", 0.0)),
            # Dispatch/token split: with speculative decode a dispatch
            # advances up to spec_k tokens per slot, so "steps" stops being
            # one number — dispatches counts compiled decode/verify calls,
            # tokens counts ACCEPTED tokens (the two coincide up to
            # steps_per_sync batching on the non-spec path).
            "exp_decode_dispatches": float(eng.get("engine/decode_dispatches", 0.0)),
            "exp_decode_tokens": float(eng.get("engine/decode_tokens", 0.0)),
            "exp_decode_step_budget": float(R),
            # Same key as the chunked path: per-episode steps. Here the gap
            # to decode_step_budget is RECLAIMED by slot refill rather than
            # paid as straggler idle time.
            "exp_decode_steps_per_episode": (
                float(np.mean(episode_steps)) if episode_steps else 0.0
            ),
            "rollout_mean_score": float(np.mean(last_scores)),
            "rollout_mean_kl": float(np.mean(np.asarray(last_kl).sum(-1))),
            "exp_per_sec": num_rollouts / max(exp_time, 1e-9),
        }
        stats.update(eng)
        if record_staleness:
            stats["exp_staleness"] = float(staleness)
        rl._last_exp_stats = {"exp_per_sec": stats["exp_per_sec"]}
        rl.tracker.log(stats, step=iter_count)
        return {
            "version_spans": sorted(
                ([v, k] for v, k in span_agg.items()),
                key=lambda s: (s[0] is None, s[0]),
            )
        }

"""Slot-based continuous-batching rollout engine.

Static-batch decode (ops/generate.py) pays for the SLOWEST sequence in every
chunk: all rows step together until the last one finishes, so mixed response
lengths leave most of the batch idle — the straggler cost the serving-style
continuous-batching loop (PipelineRL, arxiv 2509.19128) removes. This module
is that loop for the rollout side of PPO:

- A fixed pool of ``n_slots`` decode slots shares ONE KV cache pytree
  ([n_slots, cache_len, ...], int8 when kv_cache_quant) and ONE compiled
  ``decode_step`` program. Per-slot lengths are pure data: every slot carries
  its own write offset (``write_pos``) and cache-validity row, the model's
  vector ``cache_index`` path scatters each slot's KV at its own offset, and
  the attention bias already handles ragged cache lengths per row.
- A host-side slot manager admits prompts from a width-grouped queue
  (pipeline.PromptSlotQueue — PR 4's bucketing becomes slot admission) into
  free slots via a batched, jitted prefill (one compiled program per
  (group size, bucket width)), and harvests finished slots every
  ``steps_per_sync`` decode steps.
- Weights are handed over EXPLICITLY and versioned (``update_weights``) via
  the trainer's snapshot/re-quantize path — the engine never reads live
  (donated) train state. The dispatch lock is held exactly at the engine's
  own dispatch sites.

Parity contract: with greedy sampling the engine's per-slot decode is
token-for-token identical to whole-batch ``generate`` (same write-mask-
before-apply ordering, same position derivation, EOS written with its mask
bit set, post-finish positions pad/mask-0). Sampled decode draws from a
single per-step key shared across slots — statistically equivalent but not
bitwise equal to the chunked path, which is why the trainer only routes
PPO's default sampled rollouts through the engine when asked
(``method.rollout_engine``).

Multi-process contract: every controller runs this SAME host-side loop over
the SAME prompt set (submit the full global set on every host — never a
per-process slice) so all hosts make identical admission/harvest/refill
decisions and dispatch identical programs. Slot state and prefill inputs are
lifted to fully-replicated global arrays (``_globalize``); the decision
stream is fingerprinted (``schedule_fingerprint``) and cross-checked per
phase by ``resilience.distributed.verify_engine_schedule`` so a desynced
slot manager is named, not hung; the per-sync ``collective_guard`` turns a
dead peer mid-decode into exit-117 + an incident bundle.
"""

import time
import zlib
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.engine.paged_pool import BlockPool, PoolExhausted
from trlx_tpu.models.lm import init_cache, init_paged_cache
from trlx_tpu.observability import numerics as obs_numerics
from trlx_tpu.observability.spans import trace_span
from trlx_tpu.ops.sampling import GenerateConfig, process_logits_default
from trlx_tpu.pipeline.prompt_pipeline import PromptSlotQueue
from trlx_tpu.utils import sanitize


@dataclass
class Episode:
    """One finished rollout episode, as host arrays.

    ``prompt_ids``/``prompt_mask`` are the bucket-width left-padded rows as
    submitted; ``response_ids``/``response_mask`` are right-padded to the
    max_new_tokens budget with EXACTLY the whole-batch ``generate``
    convention (EOS token mask-1, post-finish positions pad/mask-0).
    ``decode_steps`` is the per-episode decode step count — free from the
    slot length, no mask arithmetic needed.

    ``version_spans`` is the per-token weight-version provenance,
    ``[(version, n_tokens), ...]`` in generation order, summing to
    ``decode_steps``. A single-span episode (no in-flight push while the
    slot was live) keeps ``weight_version == version_spans[0][0]``; a
    mid-decode switch (PipelineRL-style in-flight update) splits the
    episode at the sync boundary where the swap landed, and
    ``weight_version`` reports the LAST span's version (the weights that
    finished the episode)."""

    prompt_ids: np.ndarray
    prompt_mask: np.ndarray
    response_ids: np.ndarray
    response_mask: np.ndarray
    decode_steps: int
    weight_version: Optional[int] = None
    version_spans: Optional[list] = None


class RolloutEngine:
    """Continuous-batching decode over a fixed slot pool.

    Protocol (the orchestrator is the first client):

        engine.update_weights(variables, version=it)   # explicit handoff
        engine.submit(prompt_ids, prompt_mask)         # any bucket width
        while collecting:
            episodes = engine.step()                   # admit → decode → harvest

    ``step()`` runs ``steps_per_sync`` decode steps per device round-trip
    (amortizing the host sync), refills finished slots from the queue
    (batched prefill once ≥ ``prefill_batch`` slots are free — or
    unconditionally when nothing is live, so admission can never deadlock),
    and returns finished episodes in completion order.
    """

    def __init__(
        self,
        model,
        gen_cfg: GenerateConfig,
        *,
        n_slots: int,
        prompt_width: int,
        processor: Optional[Callable] = None,
        prefill_batch: int = 4,
        steps_per_sync: int = 8,
        spec_decode: str = "",
        spec_k: int = 0,
        drafter=None,
        paged_kv: bool = False,
        kv_block_size: int = 128,
        kv_pool_blocks: int = 0,
        dispatch_lock=None,
        monitor=None,
        rng=None,
        collective_deadline=None,
    ):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if model.cfg.window_cache == "ring":
            raise NotImplementedError(
                "the rollout engine (per-slot write offsets, suffix prefill, spec verify windows) is not built "
                "for window_cache 'ring': a ring takes one write offset for the whole batch (ops/generate.py)")
        if model.cfg.has_state:
            raise NotImplementedError(
                "the rollout engine (and with it the paged pool and spec decode) is not built for a "
                f"{model.cfg.state_layer_name} layer: a slot's state is not carried through "
                "admission, a block table has nothing to page and a rejected draft needs a snapshot of the state to "
                "roll back to")
        if model.cfg.attention == "cca":
            raise NotImplementedError(
                "the rollout engine (and with it the paged pool and spec decode) is not built for attention 'cca': a "
                "slot's convolution window and shifted value are not carried through admission or suffix prefill, a "
                "block table pages slots and they have none, and a rejected draft needs a snapshot of both to roll back to")
        if model.cfg.attention == "sparse":
            raise NotImplementedError(
                "the rollout engine (and with it the paged pool and spec decode) is not built for attention 'sparse': its "
                "compressed keys lie in each row's own grid from the row's first slot and a decode step completes one every "
                "sparse_stride tokens, which admission, suffix prefill and a block table do not carry, and a rejected draft "
                "needs a snapshot of them to roll back to")
        if model.cfg.index_topk:
            raise NotImplementedError(
                "the rollout engine (and with it the paged pool and spec decode) is not built for an indexed latent layer "
                "(index_topk): its queries choose their keys by slot under one causal edge for the whole batch, which per-slot "
                "offsets, suffix prefill and a block table do not give, and a rejected draft's index keys would have to be rolled back")
        if model.cfg.n_loops > 1:
            raise NotImplementedError(
                "the rollout engine (and with it the paged pool and spec decode) is not built for a looped stack "
                "(n_loops > 1): its slots, block tables and verify windows address one cache entry a layer, where a "
                "looped stack keeps keys a (loop, layer) pair")
        self.model = model
        self.gcfg = gen_cfg
        self.processor = processor
        self.n_slots = int(n_slots)
        self.prompt_width = int(prompt_width)
        # Soft-prompt prefix: admission prefills replay the learned prefix
        # through the model (prepend_soft default) into each slot's cache
        # rows [0, n_soft); decode/verify then run with prepend_soft=False
        # against the absolute write offset — the ops/generate.py split,
        # per slot.
        self.n_soft = int(model.cfg.n_soft_tokens)
        spec = (spec_decode or "").lower()
        if spec == "off":
            spec = ""
        if spec not in ("", "ngram", "model"):
            raise ValueError(f"unknown spec_decode mode: {spec_decode!r}")
        self.spec_decode = spec
        self.spec_k = int(spec_k) if spec_k else (4 if spec else 0)
        if spec and self.spec_k < 2:
            raise ValueError(
                f"spec_k must be >= 2 when spec_decode is armed, got {self.spec_k}"
            )
        self.cache_len = self.n_soft + self.prompt_width + int(gen_cfg.max_new_tokens)
        if spec:
            # Scratch tail: the verify window scatters spec_k tokens at the
            # live frontier; the last budgeted token can sit at position
            # cache_len-1, so spec_k-1 scratch columns keep the per-row
            # dynamic_update_slice from clamping a live row's window back
            # onto valid (mask-1) entries. Scratch positions never get a
            # mask bit, so they are never attended.
            self.cache_len += self.spec_k - 1
        self.paged = bool(paged_kv)
        if self.paged:
            # Paged KV (ROADMAP item 3): the slot cache becomes ONE shared
            # physical block pool plus per-slot block tables. Each slot keeps
            # a VIRTUAL cache of kv_len = ceil(cache_len / block) * block
            # columns — every legacy offset/mask/bias contract unchanged —
            # and the pool size decouples memory from n_slots x max-width.
            if self.n_soft:
                raise ValueError(
                    "paged_kv does not compose with soft prompts yet: the "
                    "learned prefix would alias every slot's block 0 content "
                    "(disable method.paged_kv or n_soft_tokens)"
                )
            self.block_size = int(kv_block_size)
            if self.block_size < 1:
                raise ValueError(f"kv_block_size must be >= 1, got {kv_block_size}")
            self.blocks_per_slot = -(-self.cache_len // self.block_size)
            self.kv_len = self.blocks_per_slot * self.block_size
            # Default pool: full commitment for every slot (+ trash block 0)
            # — same worst-case capacity as the fixed layout, so default-on
            # sizing can never be a regression; savings come from setting
            # kv_pool_blocks below it once prefix sharing is in play.
            self.n_blocks = int(kv_pool_blocks) or (
                1 + self.n_slots * self.blocks_per_slot
            )
            self.pool = BlockPool(
                self.n_blocks, self.block_size, self.blocks_per_slot, self.n_slots
            )
        else:
            self.kv_len = self.cache_len
            self.pool = None
        self.prefill_batch = max(1, int(prefill_batch))
        self.steps_per_sync = max(1, int(steps_per_sync))
        self._lock = dispatch_lock
        self.queue = PromptSlotQueue()
        self._slot_meta = [None] * self.n_slots  # per-occupied-slot host facts
        self._free = list(range(self.n_slots))
        self._variables = None
        self.weight_version = None
        # In-flight weight staging (PipelineRL, arxiv 2509.19128): pushes
        # that arrive while slots are mid-decode are STAGED here and adopted
        # at the top of the next step() — the engine_steps_per_sync boundary
        # — never mid-scan. One staging cell, not a queue: a push storm
        # coalesces to the latest version (``switches_coalesced`` counts the
        # versions that were superseded before any decode step saw them).
        self._staged = None
        self._staged_lock = sanitize.make_lock("engine.staged_weights")
        # Host copy of per-slot n_gen from the LAST device sync — the token
        # position a mid-decode version switch lands at for each live slot.
        self._n_gen_host = None
        self._state = None
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        # Slot-schedule fingerprint: a rolling crc over every host-side slot
        # decision (admission order, group widths, refill slot choices,
        # harvest order). In a multi-process run every host must make the
        # SAME decisions from the same data — a desynced schedule would hang
        # in the collective decode; this crc lets resilience.distributed
        # catch it by host name instead (ISSUE 17 / PR 2 fingerprint guards
        # extended to the slot manager).
        self._schedule_crc = 0
        # Optional collective-guard deadline for multi-process decode syncs:
        # when armed (process_count() > 1 and a deadline configured), the
        # device_get after each decode dispatch runs under a watchdog so a
        # dead peer host surfaces as exit-117 + incident bundle instead of a
        # silent hang (mid_decode_host_kill drill).
        self._collective_deadline = collective_deadline

        # Trace counters bump INSIDE the traced bodies (the make_generate_fn
        # idiom), so they count novel shapes only: decode must stay at 1 for
        # the life of the engine — that is the one-compiled-program contract.
        self._traces = {"decode": 0, "prefill": 0, "verify": 0}
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._prefill = jax.jit(
            self._prefill_paged_fn if self.paged else self._prefill_fn,
            donate_argnums=(1,),
        )
        # Identity unless TRLX_TPU_SANITIZE=dispatch armed the lock we were
        # handed — then every engine dispatch asserts lock ownership.
        self._decode = sanitize.wrap_dispatch("engine/decode", self._decode, dispatch_lock)
        self._prefill = sanitize.wrap_dispatch("engine/prefill", self._prefill, dispatch_lock)
        if monitor is not None:
            self._decode = monitor.wrap(
                "engine/decode_step", self._decode, phase="rollout"
            )
        if spec:
            from trlx_tpu.engine.drafters import make_drafter

            self.drafter = (
                drafter
                if drafter is not None
                else make_drafter(spec, gen_cfg.pad_token_id)
            )
            # Host frontier token per slot (the drafter's chaining basis) —
            # refreshed at admit and after every verify sync.
            self._spec_last_tok = np.zeros((self.n_slots,), dtype=np.int64)
            self._verify = jax.jit(self._verify_fn, donate_argnums=(1,))
            self._verify = sanitize.wrap_dispatch(
                "engine/verify", self._verify, dispatch_lock
            )
            if monitor is not None:
                self._verify = monitor.wrap(
                    "engine/verify_step", self._verify, phase="rollout"
                )
        else:
            self.drafter = None
            self._spec_last_tok = None
            self._verify = None
        self._reset_counters()

    # ------------------------------------------------------------- host side

    def _reset_counters(self):
        self._decode_calls = 0
        self._decode_steps = 0
        self._slot_steps = 0
        self._live_row_steps = 0
        self._gen_tokens = 0
        self._refills = 0
        self._prefill_calls = 0
        self._completed = 0
        self._decode_wall = 0.0
        self._prefill_wall = 0.0
        self._weight_switches = 0
        self._switches_coalesced = 0
        self._spec_proposed = 0
        self._spec_accepted = 0

    def _dispatch(self):
        return self._lock if self._lock is not None else nullcontext()

    @property
    def num_decode_traces(self) -> int:
        return self._traces["decode"]

    @property
    def num_prefill_traces(self) -> int:
        return self._traces["prefill"]

    @property
    def num_verify_traces(self) -> int:
        return self._traces["verify"]

    @property
    def live_slots(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def idle(self) -> bool:
        """Nothing queued and nothing in flight."""
        return self.live_slots == 0 and len(self.queue) == 0

    @property
    def pending(self) -> int:
        """Episodes still owed: queued + in-flight."""
        return self.live_slots + len(self.queue)

    def update_weights(self, variables, version=None):
        """Explicit versioned weight handoff: ``variables`` is the decode
        variable dict (params [+ int8 qw]) from the trainer's snapshot /
        re-quantize path — a stable copy, never the live donated state.

        Callable at ANY time, including between sync points while slots are
        mid-decode — no drain, no abort. The new version is STAGED on the
        host and adopted at the top of the next ``step()`` (the
        ``engine_steps_per_sync`` boundary), under the dispatch lock with
        everything else the step does. Live slots record the token position
        of the switch, so harvested Episodes carry per-token
        ``version_spans``. Pushing again before adoption replaces the staged
        version (coalesce-to-latest — a push storm never queues)."""
        # Sanitizer checkpoint: handing the engine a donated tree (e.g. the
        # trainer's pre-train_step state instead of the snapshot) fails HERE
        # with the donation site, not mid-decode with a deleted-array error.
        sanitize.check_host_read(variables, "engine.update_weights")
        if obs_numerics.enabled():
            # graftnum quant-error probe at the handoff boundary: eager
            # round-trip over the handed-off params (+ an embedding-derived
            # KV proxy) — refreshes the num/quant_err_* gauges per version,
            # never touches the compiled decode programs.
            obs_numerics.record_weight_handoff(variables, version=version)
        with self._staged_lock:
            sanitize.race_access(self, "staged_weights", write=True)
            if self._staged is not None and self._staged[1] != version:
                # A staged version no decode step ever saw is superseded:
                # coalesce, don't queue (version_switch_storm contract).
                self._switches_coalesced += 1
            self._staged = (variables, version)

    def _adopt_staged(self):
        """Swap in the staged weights at the sync boundary (top of step(),
        before admission and the next decode dispatch). Every live slot
        whose version actually changes records the switch position — the
        tokens it has generated so far — so harvest can split its episode
        into per-token version spans."""
        with self._staged_lock:
            sanitize.race_access(self, "staged_weights", write=True)
            staged, self._staged = self._staged, None
        if staged is None:
            return
        variables, version = staged
        # The engine migrates threads at phase boundaries (producer thread in
        # overlap mode, main thread serial / at teardown); each migration is
        # ordered by the producer join or the phase handoff, and always
        # passes through a fresh handoff first — reset the lockset history
        # at the boundary. (Adoption runs on the step() thread, which is the
        # only thread that ever touches slot_state.)
        sanitize.race_forget(self)
        sanitize.race_access(self, "slot_state", write=True)
        if (
            self._variables is not None
            and version != self.weight_version
            and self.live_slots > 0
        ):
            # Mid-decode switch: stamp the per-slot token position. n_gen
            # from the last device sync IS the sync-boundary position — the
            # swap lands before any further decode step.
            for i in range(self.n_slots):
                meta = self._slot_meta[i]
                if meta is None:
                    continue
                pos = (
                    int(self._n_gen_host[i]) if self._n_gen_host is not None else 0
                )
                meta.setdefault("switches", []).append((pos, version))
            self._weight_switches += 1
        if self.paged and version != self.weight_version:
            # Prefix blocks hold KV computed under the OUTGOING weights:
            # sharing them into a new-version slot would mix versions inside
            # one episode's prompt. Warm cache entries free now; pinned ones
            # (live slots mid-decode over them — the in-flight contract lets
            # those finish on recorded version spans) just unregister and
            # free at harvest. Shared templates re-prefill ONCE per version.
            self.pool.flush_registry()
        self._variables = variables
        self.weight_version = version

    def submit(self, input_ids, attention_mask) -> int:
        """Queue left-padded prompts ([n, width] or [width]) for decode."""
        ids = np.asarray(input_ids, dtype=np.int32)
        msk = np.asarray(attention_mask, dtype=np.int32)
        if ids.ndim == 1:
            ids, msk = ids[None], msk[None]
        if ids.shape[1] > self.prompt_width:
            raise ValueError(
                f"prompt width {ids.shape[1]} exceeds the engine's "
                f"prompt_width {self.prompt_width}"
            )
        return self.queue.push_rows(ids, msk)

    def step(self):
        """One sync quantum: admit queued prompts into free slots, advance
        every live slot ``steps_per_sync`` tokens in the single compiled
        decode program, harvest finished slots. Returns list[Episode].

        The top of step() IS the sync boundary: a staged in-flight weight
        push is adopted here, before admission and the decode dispatch."""
        self._adopt_staged()
        if self._variables is None:
            raise RuntimeError(
                "RolloutEngine.update_weights() must be called before step()"
            )
        self._ensure_state()
        sanitize.race_access(self, "slot_state", write=True)
        self._admit()
        n_live = self.live_slots
        if n_live == 0:
            return []
        if self.spec_decode:
            finished, n_gen = self._step_verify(n_live)
        else:
            finished, n_gen = self._step_decode(n_live)

        episodes = []
        done = [
            i
            for i in range(self.n_slots)
            if self._slot_meta[i] is not None and bool(finished[i])
        ]
        if done:
            # Harvest order is a slot-manager decision — fold it into the
            # schedule fingerprint so a desynced harvest on one host is
            # caught by name, not by a hung collective.
            self._roll_schedule("harvest", *done)
            toks = np.asarray(jax.device_get(self._state["tokens"]), dtype=np.int32)
            R = int(self.gcfg.max_new_tokens)
            for i in done:
                meta, self._slot_meta[i] = self._slot_meta[i], None
                steps = int(n_gen[i])
                rmask = np.zeros((R,), dtype=np.int32)
                rmask[:steps] = 1
                spans = self._build_spans(meta, steps)
                episodes.append(
                    Episode(
                        prompt_ids=meta["prompt_ids"],
                        prompt_mask=meta["prompt_mask"],
                        response_ids=toks[i],
                        response_mask=rmask,
                        decode_steps=steps,
                        weight_version=spans[-1][0],
                        version_spans=spans,
                    )
                )
                self._free.append(i)
                if self.paged:
                    # Release the slot's span: pinned shared blocks unref,
                    # registered prompt blocks park in the warm cache,
                    # everything else returns to the free list.
                    self.pool.release(i)
            if self.paged:
                # Repoint the harvested rows' DEVICE tables at the trash
                # block BEFORE any freed block can be re-issued: the dead
                # rows keep issuing clamped writes inside the compiled
                # decode program, and those must land on the trash block,
                # not on a block the next admission now owns.
                idx = self._globalize(np.asarray(done, dtype=np.int32))
                self._state = dict(
                    self._state,
                    block_tables=self._state["block_tables"].at[idx].set(0),
                )
            self._completed += len(done)
        return episodes

    def _step_decode(self, n_live):
        """One non-speculative sync quantum: ``steps_per_sync`` single-token
        decode steps in the one compiled program. Returns the host
        (finished, n_gen) arrays for harvest."""
        t0 = time.time()
        with trace_span("engine/decode", slots=n_live, steps=self.steps_per_sync):
            with self._sync_guard():
                with self._dispatch():
                    prev_state = self._state
                    self._state, live_steps = self._decode(
                        self._variables, self._state
                    )
                # _decode donates the slot state (donate_argnums=(1,)).
                sanitize.mark_donated(prev_state, "engine._decode(state) [step]")
                del prev_state
                # device_get sits OUTSIDE the dispatch lock (blocking on the
                # program under the lock would serialize overlap's train
                # dispatch against decode completion) but INSIDE the sync
                # guard: in a multi-process run this is where a dead peer
                # host turns into an indefinite collective wait.
                finished, n_gen, live_steps = jax.device_get(
                    (self._state["finished"], self._state["n_gen"], live_steps)
                )
        self._n_gen_host = np.asarray(n_gen)
        self._decode_wall += time.time() - t0
        self._decode_calls += 1
        self._decode_steps += self.steps_per_sync
        self._slot_steps += self.steps_per_sync * self.n_slots
        self._live_row_steps += int(live_steps)
        self._gen_tokens += int(live_steps)
        return finished, n_gen

    def _step_verify(self, n_live):
        """One speculative sync quantum: draft spec_k-1 tokens per slot on
        the host, run ONE batched verify dispatch over every slot's window,
        adopt each slot's longest accepted prefix. Dispatch accounting is
        split: ``_decode_calls`` counts dispatches, ``_gen_tokens`` counts
        ACCEPTED tokens only — the number every consumer of decode progress
        (version_spans, occupancy, tokens/s) sees."""
        K = self.spec_k
        drafts = self._propose_drafts()
        t0 = time.time()
        with trace_span("engine/verify", slots=n_live, k=K):
            with self._sync_guard():
                with self._dispatch():
                    prev_state = self._state
                    self._state, accepted, window = self._verify(
                        self._variables, self._state, self._globalize(drafts)
                    )
                # _verify donates the slot state (donate_argnums=(1,)).
                sanitize.mark_donated(prev_state, "engine._verify(state) [step]")
                del prev_state
                finished, n_gen, accepted, window = jax.device_get(
                    (
                        self._state["finished"],
                        self._state["n_gen"],
                        accepted,
                        window,
                    )
                )
        self._n_gen_host = np.asarray(n_gen)
        acc = np.asarray(accepted, dtype=np.int64)
        acc_total = int(acc.sum())
        self._decode_wall += time.time() - t0
        self._decode_calls += 1
        self._decode_steps += K
        self._slot_steps += K * self.n_slots
        self._live_row_steps += acc_total
        self._gen_tokens += acc_total
        self._spec_proposed += K * n_live
        self._spec_accepted += acc_total
        # The accepted-token total is a pure function of replicated state —
        # fold it into the schedule fingerprint so a cross-host numerics
        # divergence is caught by name (the crc guard) before it desyncs
        # the admission schedule.
        self._roll_schedule("verify", acc_total)
        self._observe_accepted(acc, np.asarray(window))
        return finished, n_gen

    def _propose_drafts(self):
        """Host-side drafting: the [S, K] verify windows. Column 0 is a
        placeholder — the verify program puts the model's OWN next token
        there (forced accept, so every live slot advances >= 1 token per
        dispatch and a cold drafter degrades to the non-spec rate, never
        below it). Columns 1..K-1 are the drafter's chain from each slot's
        frontier token, shifted by one: the drafter's first prediction is
        its guess for column 0, so its continuations land at the positions
        they would occupy if that guess is what the model actually emits."""
        K = self.spec_k
        pad = int(self.gcfg.pad_token_id)
        drafts = np.full((self.n_slots, K), pad, dtype=np.int32)
        for i in range(self.n_slots):
            meta = self._slot_meta[i]
            if meta is None:
                continue
            chain = self.drafter.propose(i, int(self._spec_last_tok[i]), K)
            drafts[i, 1:] = np.asarray(chain[1:], dtype=np.int32)
        return drafts

    def _observe_accepted(self, acc, window):
        """Fold each slot's ACCEPTED tokens back into the drafter (rejected
        drafts are exactly what the big model disagreed with — never learn
        from them) and advance the host frontier tokens."""
        for i in range(self.n_slots):
            meta = self._slot_meta[i]
            if meta is None:
                continue
            a = int(acc[i])
            if a <= 0:
                continue
            toks = [int(self._spec_last_tok[i])] + [int(t) for t in window[i, :a]]
            self.drafter.observe(i, toks)
            self._spec_last_tok[i] = toks[-1]

    @staticmethod
    def _build_spans(meta, steps):
        """Per-token weight-version spans for one harvested slot:
        ``[(version, n_tokens), ...]`` summing to ``steps``. Walks the
        recorded ``(pos, version)`` switches in push order, clamping each
        switch position into [0, steps], dropping zero-length segments and
        merging adjacent equal versions."""
        spans = []
        cur_v = meta["version"]
        cur_start = 0
        for pos, v in meta.get("switches", ()):
            pos = max(0, min(int(pos), int(steps)))
            if v == cur_v:
                continue
            if pos > cur_start:
                spans.append((cur_v, pos - cur_start))
                cur_start = pos
            cur_v = v
        if steps > cur_start or not spans:
            spans.append((cur_v, int(steps) - cur_start))
        return spans

    def _roll_schedule(self, tag, *vals):
        """Fold one slot-manager decision into the rolling schedule crc."""
        payload = (tag + ":" + ",".join(str(int(v)) for v in vals)).encode()
        self._schedule_crc = zlib.crc32(payload, self._schedule_crc)

    def schedule_fingerprint(self) -> int:
        """Rolling crc32 over every admission/harvest decision this engine
        has made — identical across hosts iff the slot schedules matched.
        Verified cross-host by resilience.distributed.verify_engine_schedule
        at engine phase boundaries."""
        return self._schedule_crc

    def slot_states(self) -> list:
        """Host-side forensic summary of the in-flight slots — what a
        mid-decode incident bundle records about the work that was live
        when a peer host died."""
        out = []
        for i in range(self.n_slots):
            meta = self._slot_meta[i]
            if meta is None:
                continue
            out.append(
                {
                    "slot": i,
                    "width": int(meta.get("width", len(meta["prompt_ids"]))),
                    "version": meta["version"],
                    "n_gen": (
                        int(self._n_gen_host[i])
                        if self._n_gen_host is not None
                        else 0
                    ),
                    "switches": [
                        [int(p), v] for p, v in meta.get("switches", ())
                    ],
                }
            )
        return out

    def _sync_guard(self):
        """Collective-guard context for the decode sync, armed only in
        multi-process runs with a configured deadline — single-host stays
        on the zero-overhead nullcontext path."""
        if self._collective_deadline is None or jax.process_count() <= 1:
            return nullcontext()
        from trlx_tpu.resilience import distributed as dist_res

        return dist_res.collective_guard(
            "engine/decode_sync",
            deadline=self._collective_deadline,
            detail=lambda: {"slot_states": self.slot_states()},
        )

    def _admit(self) -> int:
        """Refill free slots from the queue. Prefill is BATCHED: while any
        slot is still live, admission waits until ≥ prefill_batch slots are
        free (or the whole queue fits in fewer) so each prefill dispatch
        carries a full same-width group; with no live slots it admits
        unconditionally — an empty pool must never wait on itself."""
        if self.paged:
            return self._admit_paged()
        admitted = 0
        while self._free and len(self.queue):
            want = min(self.prefill_batch, len(self.queue))
            if len(self._free) < want and self.live_slots > 0:
                break
            group = self.queue.pop_group(min(len(self._free), self.prefill_batch))
            if group is None:
                break
            width, ids, msk = group
            slots = np.asarray(
                [self._free.pop() for _ in range(ids.shape[0])], dtype=np.int32
            )
            # Admission is a slot-manager decision (which slots, what width,
            # what group size) — fold it into the schedule fingerprint.
            self._roll_schedule("admit", int(width), int(ids.shape[0]), *slots)
            t0 = time.time()
            with trace_span("engine/prefill", n=int(ids.shape[0]), width=int(width)):
                with self._dispatch():
                    prev_state = self._state
                    self._state = self._prefill(
                        self._variables,
                        self._state,
                        # _globalize: local jnp arrays in one process,
                        # replicated global arrays when the mesh spans
                        # processes (every host admits the SAME group — the
                        # identical-prompt-set contract).
                        self._globalize(ids),
                        self._globalize(msk),
                        self._globalize(slots),
                    )
                # _prefill donates the slot state (donate_argnums=(1,)).
                sanitize.mark_donated(prev_state, "engine._prefill(state) [admit]")
                del prev_state
            self._prefill_wall += time.time() - t0
            for row, slot in enumerate(slots):
                self._slot_meta[int(slot)] = {
                    "prompt_ids": ids[row],
                    "prompt_mask": msk[row],
                    "version": self.weight_version,
                }
                if self.spec_decode:
                    j = int(slot)
                    # Frontier = the last real prompt token (rows are
                    # left-padded, so that is the final column); the drafter
                    # table reseeds from the new occupant's prompt so a
                    # refilled slot never inherits the previous episode's
                    # statistics.
                    self._spec_last_tok[j] = int(ids[row, -1])
                    self.drafter.reset_slot(j, ids[row][msk[row] > 0].tolist())
            self._prefill_calls += 1
            self._refills += int(ids.shape[0])
            admitted += int(ids.shape[0])
        return admitted

    def _admit_paged(self) -> int:
        """Paged admission: same batching policy as ``_admit``, plus the
        block-pool gate and prefix caching.

        Each popped row is admitted transactionally against the pool
        (worst-case span committed up front: prefix-hit blocks pinned,
        private blocks allocated). The first row the pool cannot serve stops
        the group — it and the rest re-queue (back of their width bucket;
        deterministic on every host) and wait for a harvest to free blocks.
        Admitted rows then prefill in (width, hit-length) subgroups — one
        compiled suffix-prefill program per (rows, suffix width) shape — and
        register their freshly written full-prompt blocks for the NEXT
        admission to share."""
        admitted = 0
        while self._free and len(self.queue):
            want = min(self.prefill_batch, len(self.queue))
            if len(self._free) < want and self.live_slots > 0:
                break
            group = self.queue.pop_group(min(len(self._free), self.prefill_batch))
            if group is None:
                break
            width, ids, msk = group
            n = int(ids.shape[0])
            rows = []  # (slot, row index, table row, hit tokens)
            for r in range(n):
                slot = self._free[-1]
                try:
                    tbl_row, hit = self.pool.admit(
                        slot, self.weight_version, ids[r], msk[r]
                    )
                except PoolExhausted:
                    break
                self._free.pop()
                rows.append((slot, r, tbl_row, hit))
            if len(rows) < n:
                # Pool-bound, not slot-bound: requeue the tail and stop
                # admitting until a harvest releases blocks. A single-row
                # admission against an idle pool always succeeds (init
                # validates n_blocks - 1 >= blocks_per_slot), so this can
                # only happen with live slots to wait on.
                rest = [r for r in range(len(rows), n)]
                self.queue.push_rows(ids[rest], msk[rest])
            if not rows:
                break
            slots_admitted = [s for s, _, _, _ in rows]
            self._roll_schedule("admit", int(width), len(rows), *slots_admitted)
            for slot, _, tbl_row, hit in rows:
                # The table row and hit length are pool decisions — fold them
                # into the schedule crc so a divergent allocator on one host
                # is caught by name, not by silently different attention.
                self._roll_schedule("pool", slot, hit, *tbl_row)
            by_hit = {}
            for slot, r, tbl_row, hit in rows:
                by_hit.setdefault(hit, []).append((slot, r, tbl_row))
            for hit, sub in by_hit.items():
                slots = np.asarray([s for s, _, _ in sub], dtype=np.int32)
                rr = [r for _, r, _ in sub]
                tables = np.stack([t for _, _, t in sub]).astype(np.int32)
                sub_ids = ids[rr]
                sub_msk = msk[rr]
                t0 = time.time()
                with trace_span(
                    "engine/prefill", n=len(sub), width=int(width), hit=int(hit)
                ):
                    with self._dispatch():
                        prev_state = self._state
                        self._state = self._prefill(
                            self._variables,
                            self._state,
                            self._globalize(sub_ids[:, hit:]),
                            self._globalize(sub_msk),
                            self._globalize(slots),
                            self._globalize(tables),
                        )
                    # _prefill donates the slot state (donate_argnums=(1,)).
                    sanitize.mark_donated(
                        prev_state, "engine._prefill(state) [admit_paged]"
                    )
                    del prev_state
                self._prefill_wall += time.time() - t0
                for row, slot in enumerate(slots):
                    j = int(slot)
                    r = rr[row]
                    # The prefill dispatch above wrote this row's prompt
                    # blocks (device program order makes them visible to any
                    # later dispatch) — register the full-prompt ones so the
                    # next admission with the same (version, content) shares
                    # instead of re-prefilling.
                    self.pool.register_prefix(
                        j, self.weight_version, ids[r], msk[r]
                    )
                    self._slot_meta[j] = {
                        "prompt_ids": ids[r],
                        "prompt_mask": msk[r],
                        "version": self.weight_version,
                        "prefix_hit": int(hit),
                    }
                    if self.spec_decode:
                        self._spec_last_tok[j] = int(ids[r, -1])
                        self.drafter.reset_slot(j, ids[r][msk[r] > 0].tolist())
                self._prefill_calls += 1
            self._refills += len(rows)
            admitted += len(rows)
            if len(rows) < n:
                break
        return admitted

    def stats(self, reset: bool = True) -> dict:
        """Window gauges: slot occupancy (live-slot decode steps over total
        slot-steps paid), refill counters, and the engine-side decode rate."""
        out = {
            "engine/slot_occupancy": self._live_row_steps / max(1, self._slot_steps),
            "engine/decode_steps": self._decode_steps,
            "engine/decode_calls": self._decode_calls,
            "engine/decode_dispatches": self._decode_calls,
            "engine/decode_tokens": self._gen_tokens,
            "engine/gen_tokens": self._gen_tokens,
            "engine/refills": self._refills,
            "engine/prefill_batches": self._prefill_calls,
            "engine/completed": self._completed,
            "engine/queue_depth": len(self.queue),
            "engine/free_slots": len(self._free),
            "engine/decode_wall_s": self._decode_wall,
            "engine/prefill_wall_s": self._prefill_wall,
            "engine/decode_tokens_per_s": self._gen_tokens
            / max(self._decode_wall, 1e-9),
            "engine/weight_switches": self._weight_switches,
            "engine/switches_coalesced": self._switches_coalesced,
        }
        if self.spec_decode:
            out["engine/spec_proposed"] = self._spec_proposed
            out["engine/spec_accepted"] = self._spec_accepted
            out["engine/spec_accept_rate"] = self._spec_accepted / max(
                1, self._spec_proposed
            )
        if self.paged:
            # Pool gauges (cumulative counters are lifetime totals — the
            # bench/triage consumers diff them, matching the *_total names).
            out["engine/pool_blocks"] = self.n_blocks
            out["engine/pool_used_blocks"] = self.pool.used_blocks()
            out["engine/pool_cached_blocks"] = self.pool.cached_blocks()
            out["engine/pool_free_blocks"] = len(self.pool.free)
            out["engine/pool_frag_frac"] = self._pool_frag()
            out["engine/pool_evictions_total"] = self.pool.evictions
            out["engine/prefix_hits_total"] = self.pool.hits_total
            out["engine/prefill_tokens_saved_total"] = self.pool.tokens_saved_total
        if reset:
            self._reset_counters()
        return out

    def _pool_frag(self) -> float:
        """Internal fragmentation of the referenced pool span: 1 − (tokens
        actually resident) / (referenced blocks × block_size). Worst-case
        commitment makes this the price of never preempting — the gauge is
        what says whether a smaller kv_pool_blocks would still fit."""
        used = self.pool.used_blocks()
        if used == 0:
            return 0.0
        toks = 0
        shared = set()
        for i in range(self.n_slots):
            meta = self._slot_meta[i]
            if meta is None:
                continue
            width = int(meta.get("width", len(meta["prompt_ids"])))
            n_gen = int(self._n_gen_host[i]) if self._n_gen_host is not None else 0
            # The slot's private resident tokens (its shared prefix tokens
            # are counted once, below, over the distinct shared blocks).
            toks += min(width + n_gen, self.kv_len) - int(meta.get("prefix_hit", 0))
            shared.update(self.pool.shared_blocks(i))
        toks += len(shared) * self.block_size
        return max(0.0, 1.0 - toks / float(used * self.block_size))

    def abort(self):
        """Drop queued prompts and in-flight slots (phase abort on a stop
        request). Device buffers are kept for the next phase; all slots are
        deactivated so a subsequent decode has no live rows. With paged_kv,
        every in-flight slot's pinned/private blocks are released (the warm
        prefix cache survives — an abort is not a version change) and the
        pool's leak audit runs: a block the bookkeeping lost raises HERE,
        named, instead of surfacing later as slow pool exhaustion."""
        self.queue.clear()
        if self.paged:
            for i in range(self.n_slots):
                if self._slot_meta[i] is not None:
                    self.pool.release(i)
            self.pool.leak_audit(expect_idle=True)
        self._slot_meta = [None] * self.n_slots
        self._free = list(range(self.n_slots))
        self._slot_free_t = [None] * self.n_slots
        if self._state is not None:
            extra = {}
            if self.paged:
                # Dead rows park on the trash block, same as at harvest.
                extra["block_tables"] = self._globalize(
                    jnp.zeros(
                        (self.n_slots, self.blocks_per_slot), dtype=jnp.int32
                    )
                )
            self._state = dict(
                self._state,
                active=self._globalize(jnp.zeros((self.n_slots,), dtype=bool)),
                **extra,
            )

    def shutdown(self):
        """Release everything: queue, slot bookkeeping, device state, and the
        weight reference (learn()'s finally — mirrors the producer teardown).
        The engine owns no threads, so shutdown is synchronous and
        idempotent."""
        # Teardown runs on main AFTER the producer join ordered every
        # producer-side access before us — drop the stale lockset records.
        sanitize.race_forget(self)
        self.abort()
        with self._staged_lock:
            self._staged = None
        self._state = None
        self._variables = None
        self._n_gen_host = None

    # ----------------------------------------------------------- device side

    def _ensure_state(self):
        if self._state is not None:
            return
        cfg = self.model.cfg
        S, T, R = self.n_slots, self.kv_len, int(self.gcfg.max_new_tokens)
        if self.paged:
            # One shared physical pool; the per-slot layout pin does not
            # apply (there is no slot axis to shard) — pool placement is
            # left to XLA, and _globalize replicates it in multi-process
            # runs exactly like the fixed cache.
            cache = init_paged_cache(cfg, self.n_blocks, self.block_size)
        else:
            cache = self._pin_cache(init_cache(cfg, S, T))
        state = {
            "cache": cache,
            "cache_mask": jnp.zeros((S, T), dtype=jnp.int32),
            "write_pos": jnp.zeros((S,), dtype=jnp.int32),
            "n_gen": jnp.zeros((S,), dtype=jnp.int32),
            "tokens": jnp.full((S, R), self.gcfg.pad_token_id, dtype=jnp.int32),
            "active": jnp.zeros((S,), dtype=bool),
            "finished": jnp.zeros((S,), dtype=bool),
            "last_token": jnp.zeros((S,), dtype=jnp.int32),
            "last_logits": jnp.zeros((S, cfg.vocab_size), dtype=jnp.float32),
            "last_hidden": jnp.zeros((S, cfg.d_model), dtype=cfg.compute_dtype),
            "rng": self._rng,
        }
        if self.paged:
            # Trash-initialized tables: every slot's virtual blocks point at
            # the reserved block 0 until admission assigns a real span.
            state["block_tables"] = jnp.zeros(
                (S, self.blocks_per_slot), dtype=jnp.int32
            )
        if self.spec_decode:
            # Deferred rejection-sampling residual: the draft token the LAST
            # verify window rejected at its break position (-1 = none). The
            # next window's forced position 0 masks it out, which samples
            # the exact residual distribution — see _verify_fn.
            state["spec_resid"] = jnp.full((S,), -1, dtype=jnp.int32)
        self._state = self._globalize(state)

    def _globalize(self, tree):
        """Make a host/process-local pytree a valid input for the engine's
        jitted programs under the CURRENT mesh.

        Single process: identity up to ``jnp.asarray`` — byte-identical to
        the pre-multi-host path. Multi-process: the trainer's variables are
        GLOBAL (multi-process) arrays, and jit refuses to mix them with
        process-local inputs — so every host materialises its leaf (every
        host computes the SAME value; the identical-schedule contract makes
        that true for slot state and prefill groups alike) and lifts it to a
        fully-REPLICATED global array via ``make_array_from_callback``.
        Replication trades cache memory (each host holds the whole slot
        cache) for the simplest possible availability story: any surviving
        host owns a complete copy, and the slot manager needs no cross-host
        index math. RNG keys ride through ``np.asarray`` (legacy uint32
        keys)."""
        if jax.process_count() <= 1:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        from trlx_tpu.parallel import mesh as mesh_mod

        mesh = mesh_mod.peek_mesh()
        if mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        spec = NamedSharding(mesh, PSpec())

        def lift(x):
            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, spec, lambda idx, h=host: h[idx]
            )

        return jax.tree_util.tree_map(lift, tree)

    def _pin_cache(self, cache):
        # Same layout pin as ops/generate.py: slots over the data axes, heads
        # over tp — skipped when the shapes don't divide the mesh. In a
        # multi-process world the pin is skipped outright: _globalize
        # replicates the cache instead (see its docstring for the tradeoff),
        # and an eager with_sharding_constraint on process-local leaves would
        # not build a global array anyway.
        from trlx_tpu.parallel import mesh as mesh_mod

        if jax.process_count() > 1:
            return cache
        mesh = mesh_mod.peek_mesh()
        if mesh is None:
            return cache
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        cfg = self.model.cfg
        data = int(mesh.shape[mesh_mod.AXIS_DP]) * int(mesh.shape[mesh_mod.AXIS_FSDP])
        tp = int(mesh.shape[mesh_mod.AXIS_TP])
        if self.n_slots % data == 0 and cfg.n_head % tp == 0:
            spec4 = NamedSharding(
                mesh, PSpec(mesh_mod.DATA_AXES, None, mesh_mod.AXIS_TP, None)
            )
            spec3 = NamedSharding(mesh, PSpec(mesh_mod.DATA_AXES, None, mesh_mod.AXIS_TP))
            cache = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, spec4 if x.ndim == 4 else spec3
                ),
                cache,
            )
        elif mesh.size > 1:
            import warnings

            warnings.warn(
                f"engine KV cache left to XLA propagation: n_slots "
                f"{self.n_slots} or n_head {cfg.n_head} does not divide the "
                f"mesh (data={data}, tp={tp})"
            )
        return cache

    def _prefill_fn(self, variables, state, prompt_ids, prompt_mask, slot_ids):
        """Batched prefill of a same-width prompt group into its slots.

        Runs the group through a MINI cache at bucket width (flash-eligible:
        static zero write offset), then scatters the per-layer KV leaves into
        the big slot cache at [slot_ids, :width] and resets every per-slot
        column for the admitted rows. Compiled once per (group size, width);
        ``state`` is donated."""
        self._traces["prefill"] += 1  # traced-body bump: novel shapes only
        cfg = self.model.cfg
        j, Pb = prompt_ids.shape
        T = self.cache_len
        R = int(self.gcfg.max_new_tokens)
        n_soft = self.n_soft
        Ps = Pb + n_soft  # cache rows the prefill occupies (soft prefix first)
        pm = prompt_mask.astype(jnp.int32)
        # With soft prompts the model prepends the learned prefix itself
        # (prepend_soft default): the mini cache carries n_soft extra rows
        # and the cache mask marks them valid; outputs come back sliced to
        # the prompt length, so logits_start stays Pb-1. n_soft == 0 reduces
        # every expression here to the original prefill, same jaxpr.
        soft_pm = (
            jnp.concatenate([jnp.ones((j, n_soft), dtype=pm.dtype), pm], axis=1)
            if n_soft
            else pm
        )
        out = self.model.apply(
            variables,
            input_ids=prompt_ids,
            attention_mask=pm,
            cache=init_cache(cfg, j, Ps),
            cache_index=0,
            cache_mask=soft_pm,
            logits_start=Pb - 1,
        )
        new_cache = tuple(
            tuple(
                big.at[slot_ids, :Ps].set(mini.astype(big.dtype))
                for big, mini in zip(big_layer, mini_layer)
            )
            for big_layer, mini_layer in zip(state["cache"], out["cache"])
        )
        row_mask = (
            jnp.zeros((j, T), dtype=state["cache_mask"].dtype).at[:, :Ps].set(soft_pm)
        )
        s = dict(state)
        s["cache"] = new_cache
        s["cache_mask"] = state["cache_mask"].at[slot_ids].set(row_mask)
        s["write_pos"] = state["write_pos"].at[slot_ids].set(Ps)
        s["n_gen"] = state["n_gen"].at[slot_ids].set(0)
        s["active"] = state["active"].at[slot_ids].set(True)
        s["finished"] = state["finished"].at[slot_ids].set(False)
        s["tokens"] = (
            state["tokens"]
            .at[slot_ids]
            .set(jnp.full((j, R), self.gcfg.pad_token_id, dtype=state["tokens"].dtype))
        )
        s["last_logits"] = (
            state["last_logits"].at[slot_ids].set(out["logits"][:, -1].astype(jnp.float32))
        )
        s["last_hidden"] = (
            state["last_hidden"]
            .at[slot_ids]
            .set(out["hidden"][:, -1].astype(state["last_hidden"].dtype))
        )
        s["last_token"] = (
            state["last_token"].at[slot_ids].set(prompt_ids[:, -1].astype(jnp.int32))
        )
        if "spec_resid" in state:  # static: spec-armed engines only
            s["spec_resid"] = state["spec_resid"].at[slot_ids].set(-1)
        return s

    def _prefill_paged_fn(self, variables, state, suffix_ids, prompt_mask, slot_ids, tables):
        """Paged prefill of a same-(width, hit) prompt group into its slots.

        ``suffix_ids`` is the prompt MINUS the prefix-cache hit: the first H
        virtual positions of each row are already resident in shared pool
        blocks (pinned by the allocator before dispatch), so only the suffix
        runs through the model. Unlike ``_prefill_fn`` there is no mini
        cache + scatter: KV writes go straight through the slot's block
        table into the shared pool (the model's paged cache_write), which is
        exactly what makes a later admit able to alias this slot's prefix
        blocks without a copy. The vector ``cache_index`` (= H per row)
        routes the suffix to virtual positions [H, W); positions derive from
        the cumsum of the full-row mask, so suffix tokens see the same
        rotary/ALiBi phases as a full prefill — prefix-cached KV is bitwise
        identical to full-prefill KV because per-token projections don't mix
        across positions. Compiled once per (group size, width, hit).
        ``state`` is donated."""
        self._traces["prefill"] += 1  # traced-body bump: novel shapes only
        j, Ws = suffix_ids.shape
        W = prompt_mask.shape[1]
        H = W - Ws  # static hit length: part of the trace shape key
        T = self.kv_len
        R = int(self.gcfg.max_new_tokens)
        pm = prompt_mask.astype(jnp.int32)
        row_mask = jnp.zeros((j, T), dtype=state["cache_mask"].dtype).at[:, :W].set(pm)
        out = self.model.apply(
            variables,
            input_ids=suffix_ids,
            attention_mask=pm[:, H:],
            cache=state["cache"],
            cache_index=jnp.full((j,), H, dtype=jnp.int32),
            cache_mask=row_mask,
            block_tables=tables,
            logits_start=Ws - 1,
            prepend_soft=False,
        )
        s = dict(state)
        s["cache"] = out["cache"]
        s["cache_mask"] = state["cache_mask"].at[slot_ids].set(row_mask)
        s["block_tables"] = state["block_tables"].at[slot_ids].set(tables)
        s["write_pos"] = state["write_pos"].at[slot_ids].set(W)
        s["n_gen"] = state["n_gen"].at[slot_ids].set(0)
        s["active"] = state["active"].at[slot_ids].set(True)
        s["finished"] = state["finished"].at[slot_ids].set(False)
        s["tokens"] = (
            state["tokens"]
            .at[slot_ids]
            .set(jnp.full((j, R), self.gcfg.pad_token_id, dtype=state["tokens"].dtype))
        )
        s["last_logits"] = (
            state["last_logits"].at[slot_ids].set(out["logits"][:, -1].astype(jnp.float32))
        )
        s["last_hidden"] = (
            state["last_hidden"]
            .at[slot_ids]
            .set(out["hidden"][:, -1].astype(state["last_hidden"].dtype))
        )
        # Rows are left-padded, so the suffix's last column IS the prompt's
        # real last token (H < W is guaranteed by the allocator's hit cap).
        s["last_token"] = (
            state["last_token"].at[slot_ids].set(suffix_ids[:, -1].astype(jnp.int32))
        )
        if "spec_resid" in state:  # static: spec-armed engines only
            s["spec_resid"] = state["spec_resid"].at[slot_ids].set(-1)
        return s

    def _decode_fn(self, variables, state):
        """``steps_per_sync`` decode steps for ALL slots in one program.

        Mirrors ops/generate.py's loop invariants per live slot: the new
        token's cache-mask bit is written BEFORE model.apply (the token
        attends to itself), EOS is written with mask-1, finished/free slots
        write nothing visible (their buffer writes are value-preserving and
        their clamped cache write lands on a mask-0 position). Returns the
        new state and the number of live-slot steps executed (the occupancy
        numerator). ``state`` is donated."""
        self._traces["decode"] += 1  # traced-body bump: must stay at 1
        gcfg = self.gcfg
        S, T = self.n_slots, self.kv_len  # T: virtual cache width (== cache_len unpaged)
        R = int(gcfg.max_new_tokens)
        pad = jnp.asarray(gcfg.pad_token_id, dtype=jnp.int32)

        def write_col(grid, vals, ixs):
            # Per-row scatter of one value at each row's own column.
            return jax.vmap(
                lambda row, v, i: jax.lax.dynamic_update_slice(row, v[None], (i,))
            )(grid, vals, ixs)

        def one_step(carry, _):
            s, live_steps = carry
            live = s["active"] & ~s["finished"]
            step_col = s["n_gen"][:, None]  # [S, 1]: per-slot decode step
            if self.processor is not None:
                logits = self.processor(
                    s["last_logits"],
                    {
                        "last_token": s["last_token"],
                        "hidden": s["last_hidden"],
                        "step": step_col,
                        "carry": {},
                    },
                )
            else:
                logits = process_logits_default(s["last_logits"], gcfg, step_col)
            rng, sub = jax.random.split(s["rng"])
            if gcfg.do_sample:
                tok = jax.random.categorical(sub, logits, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            tok = jnp.where(live, tok.astype(jnp.int32), pad)

            # Token buffer write at (slot, n_gen), value-preserving for
            # non-live slots (a clamped index must not clobber real tokens).
            w_ix = jnp.minimum(s["n_gen"], R - 1)
            cur_tok = jnp.take_along_axis(s["tokens"], w_ix[:, None], axis=1)[:, 0]
            tokens = write_col(s["tokens"], jnp.where(live, tok, cur_tok), w_ix)

            # Cache-mask bit at each live slot's write offset — BEFORE apply.
            c_ix = jnp.minimum(s["write_pos"], T - 1)
            cur_bit = jnp.take_along_axis(s["cache_mask"], c_ix[:, None], axis=1)[:, 0]
            bit = jnp.where(live, jnp.ones_like(cur_bit), cur_bit)
            cache_mask = write_col(s["cache_mask"], bit, c_ix)

            if gcfg.eos_token_id is not None:
                hit_eos = tok == gcfg.eos_token_id
            else:
                hit_eos = jnp.zeros_like(live)
            finished = s["finished"] | (live & (hit_eos | (s["n_gen"] + 1 >= R)))

            out = self.model.apply(
                variables,
                input_ids=tok[:, None],
                attention_mask=jnp.ones((S, 1), dtype=jnp.int32),
                cache=s["cache"],
                cache_index=c_ix,  # [S] vector: per-slot write offsets
                cache_mask=cache_mask,
                prepend_soft=False,
                # Paged: the block tables ride the scan carry unchanged —
                # table edits happen host-side at admit/harvest boundaries
                # only. The kwarg is omitted entirely when off so the
                # non-paged jaxpr stays byte-identical.
                **({"block_tables": s["block_tables"]} if self.paged else {}),
            )
            live_i = live.astype(jnp.int32)
            new_s = {
                "cache": out["cache"],
                "cache_mask": cache_mask,
                "write_pos": s["write_pos"] + live_i,
                "n_gen": s["n_gen"] + live_i,
                "tokens": tokens,
                "active": s["active"],
                "finished": finished,
                "last_token": jnp.where(live, tok, s["last_token"]),
                "last_logits": out["logits"][:, 0].astype(jnp.float32),
                "last_hidden": out["hidden"][:, 0].astype(s["last_hidden"].dtype),
                "rng": rng,
            }
            if self.paged:
                new_s["block_tables"] = s["block_tables"]
            return (new_s, live_steps + live_i.sum()), None

        (state, live_steps), _ = jax.lax.scan(
            one_step,
            (state, jnp.zeros((), dtype=jnp.int32)),
            None,
            length=self.steps_per_sync,
        )
        return state, live_steps

    def _verify_fn(self, variables, state, drafts):
        """ONE batched speculative verify step for ALL slots.

        The window per slot is [model's own next token, draft 1..K-1]: the
        frontier logits from the previous sync select position 0 on device
        (greedy argmax or the rejection-sampling residual draw), so every
        live slot is guaranteed >= 1 accepted token per dispatch. The big
        model runs ONCE over all windows (q_len = K, vector cache_index —
        the multi-token per-row KV path in models/lm.py), then the longest
        accepted prefix per slot is adopted:

        - greedy: position j accepts iff the draft equals argmax of the
          processed logits after position j-1 — token-for-token equal to
          sequential decode by construction;
        - do_sample: standard rejection sampling against a point-mass
          drafter: accept draft d with probability p(d). On the FIRST
          rejection the rejected token is stored in ``spec_resid`` and the
          residual distribution norm(p - p(d)·δ_d) is drawn at the NEXT
          window's position 0 by masking d there — exact, because that
          position's processed frontier logits equal this position's target.

        Rollback of rejected suffixes is pure mask arithmetic: cache values
        only matter where a ``cache_mask`` bit is 1, every future bit-set is
        paired with a same-dispatch value write (the next window rewrites
        [wp', wp'+K) ⊇ the stale tail), so un-setting nothing and only
        committing bits for the accepted prefix IS the rollback — the cache
        stays bit-consistent with the accepted stream. ``state`` is donated;
        returns (new_state, accepted [S] int32, window [S, K] int32)."""
        self._traces["verify"] += 1  # traced-body bump: must stay at 1
        gcfg = self.gcfg
        S, T, K = self.n_slots, self.kv_len, self.spec_k
        R = int(gcfg.max_new_tokens)
        pad = jnp.asarray(gcfg.pad_token_id, dtype=jnp.int32)
        live = state["active"] & ~state["finished"]
        n_gen = state["n_gen"]
        wp = state["write_pos"]
        keys = jax.random.split(state["rng"], K + 1)
        rng = keys[0]

        def proc(raw_logits, last_token, hidden, step_col):
            # Same processor contract as _decode_fn: stateless per position,
            # fresh empty carry.
            if self.processor is not None:
                return self.processor(
                    raw_logits,
                    {
                        "last_token": last_token,
                        "hidden": hidden,
                        "step": step_col,
                        "carry": {},
                    },
                )
            return process_logits_default(raw_logits, gcfg, step_col)

        if gcfg.eos_token_id is not None:
            is_eos = lambda t: t == gcfg.eos_token_id  # noqa: E731
        else:
            is_eos = lambda t: jnp.zeros(t.shape, dtype=bool)  # noqa: E731

        # ---- forced position 0: the model's own next token.
        logits0 = proc(
            state["last_logits"], state["last_token"], state["last_hidden"], n_gen[:, None]
        )
        if gcfg.do_sample:
            resid = state["spec_resid"]
            vocab = jnp.arange(logits0.shape[-1], dtype=jnp.int32)[None, :]
            logits0 = jnp.where(vocab == resid[:, None], -1e9, logits0)
            tok0 = jax.random.categorical(keys[1], logits0, axis=-1)
        else:
            tok0 = jnp.argmax(logits0, axis=-1)
        tok0 = jnp.where(live, tok0.astype(jnp.int32), pad)
        window = jnp.concatenate([tok0[:, None], drafts[:, 1:]], axis=1)
        window = jnp.where(live[:, None], window, pad)

        # ---- whole window masked BEFORE apply (each query attends to itself
        # and its in-window predecessors; the per-row causal bias hides the
        # future positions).
        pos = jnp.arange(T, dtype=jnp.int32)[None, :]
        in_window = (pos >= wp[:, None]) & (pos < (wp + K)[:, None]) & live[:, None]
        mask_apply = jnp.maximum(
            state["cache_mask"], in_window.astype(state["cache_mask"].dtype)
        )
        # Live rows never clamp (wp + K <= T by the scratch tail); dead rows'
        # clamped writes land on their own mask-0 positions.
        c_ix = jnp.minimum(wp, T - K)
        out = self.model.apply(
            variables,
            input_ids=window,
            attention_mask=jnp.ones((S, K), dtype=jnp.int32),
            cache=state["cache"],
            cache_index=c_ix,  # [S] vector: per-slot ragged frontiers
            cache_mask=mask_apply,
            prepend_soft=False,
            # Paged: verify windows write through the block table like any
            # other cache write; the scratch tail lives in the slot's LAST
            # block (kv_len rounds cache_len up, never down), so wp + K <= T
            # still holds for live rows.
            **({"block_tables": state["block_tables"]} if self.paged else {}),
        )
        L = out["logits"].astype(jnp.float32)  # [S, K, V]

        # ---- longest-accepted-prefix chain (static python loop, K is a
        # shape constant). acc_prev gates each position on its predecessor,
        # so the chain breaks at the first rejection; EOS acceptance stops
        # further accepts; the response budget clips the window tail.
        accepted = live.astype(jnp.int32)
        stop = live & is_eos(window[:, 0])
        resid_new = jnp.full((S,), -1, dtype=jnp.int32)
        acc_prev = live
        for j in range(1, K):
            lj = proc(
                L[:, j - 1], window[:, j - 1], out["hidden"][:, j - 1], (n_gen + j)[:, None]
            )
            in_budget = (n_gen + j) < R
            alive = acc_prev & ~stop & in_budget
            if gcfg.do_sample:
                p = jax.nn.softmax(lj, axis=-1)
                p_d = jnp.take_along_axis(p, window[:, j][:, None], axis=-1)[:, 0]
                u = jax.random.uniform(keys[j + 1], (S,))
                match = u < p_d
                resid_new = jnp.where(alive & ~match, window[:, j], resid_new)
            else:
                match = window[:, j] == jnp.argmax(lj, axis=-1).astype(jnp.int32)
            acc_j = alive & match
            accepted = accepted + acc_j.astype(jnp.int32)
            stop = stop | (acc_j & is_eos(window[:, j]))
            acc_prev = acc_j

        # ---- commit the accepted prefix.
        a = jnp.where(live, accepted, 0)
        n_gen2 = n_gen + a
        wp2 = wp + a
        keep = (pos >= wp[:, None]) & (pos < wp2[:, None]) & live[:, None]
        cache_mask2 = jnp.maximum(
            state["cache_mask"], keep.astype(state["cache_mask"].dtype)
        )

        rpos = jnp.arange(R, dtype=jnp.int32)[None, :]
        sel = jnp.clip(rpos - n_gen[:, None], 0, K - 1)
        vals = jnp.take_along_axis(window, sel, axis=1)
        put = (rpos >= n_gen[:, None]) & (rpos < n_gen2[:, None]) & live[:, None]
        tokens2 = jnp.where(put, vals, state["tokens"])

        finished2 = state["finished"] | (live & (stop | (n_gen2 >= R)))
        ix = jnp.maximum(a - 1, 0)[:, None]  # a >= 1 for live rows
        last_tok = jnp.take_along_axis(window, ix, axis=1)[:, 0]
        last_logits = jnp.take_along_axis(L, ix[..., None], axis=1)[:, 0]
        last_hidden = jnp.take_along_axis(out["hidden"], ix[..., None], axis=1)[:, 0]

        new_state = dict(
            state,
            cache=out["cache"],
            cache_mask=cache_mask2,
            write_pos=wp2,
            n_gen=n_gen2,
            tokens=tokens2,
            finished=finished2,
            last_token=jnp.where(live, last_tok, state["last_token"]),
            last_logits=jnp.where(live[:, None], last_logits, state["last_logits"]),
            last_hidden=jnp.where(
                live[:, None],
                last_hidden.astype(state["last_hidden"].dtype),
                state["last_hidden"],
            ),
            rng=rng,
        )
        if gcfg.do_sample:
            new_state["spec_resid"] = jnp.where(live, resid_new, state["spec_resid"])
        return new_state, a, window

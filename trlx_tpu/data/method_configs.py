"""Per-method hyperparameter dataclasses + registry.

Mirrors the reference's method registry (reference:
trlx/data/method_configs.py:6-39) with the same method names and fields, plus
TPU-specific knobs documented inline.
"""

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# Registry of method configs, keyed by lowercased name
# (reference: trlx/data/method_configs.py:6).
_METHODS: Dict[str, type] = {}


def register_method(name=None):
    """Decorator registering a method config class by (lowercased) name
    (reference: trlx/data/method_configs.py:9-28)."""

    def register_class(cls, registered_name):
        _METHODS[registered_name.lower()] = cls
        return cls

    if isinstance(name, str):
        return lambda cls: register_class(cls, name)
    if name is None:
        return lambda cls: register_class(cls, cls.__name__)
    # bare @register_method usage
    cls = name
    return register_class(cls, cls.__name__)


def get_method(name: str) -> type:
    """Return a registered method config class
    (reference: trlx/data/method_configs.py:31-39)."""
    name = name.lower()
    if name in _METHODS:
        return _METHODS[name]
    raise Exception(f"Error: Trying to access a method that has not been registered: {name}")


@dataclass
@register_method
class MethodConfig:
    """Base method config (reference: trlx/data/method_configs.py:42-55)."""

    name: str = "MethodConfig"

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return cls(**config)


@dataclass
@register_method
class PPOConfig(MethodConfig):
    """PPO hyperparameters (reference: trlx/data/method_configs.py:58-110).

    TPU additions: ``gen_kwargs`` lengths are STATIC shapes compiled into the
    decode loop; ``num_rollouts``/``chunk_size`` should be multiples of the
    data-axis size so rollout batches shard evenly over the mesh.
    """

    name: str = "ppoconfig"
    ppo_epochs: int = 4
    num_rollouts: int = 128
    chunk_size: int = 128
    init_kl_coef: float = 0.2
    target: Optional[float] = 6.0
    horizon: int = 10000
    gamma: float = 1.0
    lam: float = 0.95
    cliprange: float = 0.2
    cliprange_value: float = 0.2
    vf_coef: float = 1.0
    gen_kwargs: dict = field(default_factory=dict)
    # TPU addition: collect rollout statistics (sampled-token logprobs,
    # values, branch-point hiddens) INSIDE the decode loop, so rollout
    # scoring skips the full policy re-forward and only replays the frozen
    # ref branch. Engages when the hydra branch exists (num_layers_unfrozen
    # in (0, n_layer)) and no on-device RM is configured.
    fused_rollout_stats: bool = True
    # Pipelined experience (trlx_tpu/pipeline/overlap.py). All four knobs
    # default to the serial schedule — no threads, no double-buffering —
    # unless rollout_overlap is set or max_staleness > 0.
    #
    # max_staleness: how many training iterations ahead the background
    # rollout producer may run. 0 keeps today's fully-on-policy schedule
    # (production of iteration n starts only after n-1 is fully trained on,
    # so results are bitwise-identical to serial); S >= 1 lets generation of
    # iteration n overlap training of iterations n-S..n-1 off a boundary
    # param snapshot, with per-sample staleness recorded in the store.
    max_staleness: int = 0
    # rollout_overlap: turn the pipeline machinery on at max_staleness=0 —
    # background reward scoring + producer thread + device batch prefetch,
    # without relaxing the on-policy schedule.
    rollout_overlap: bool = False
    # score_queue_depth: max rollout chunks queued decoded-but-unscored for
    # the background reward worker (backpressure bound on host memory).
    score_queue_depth: int = 2
    # prefetch_depth: how many train batches the epoch loop's PrefetchIterator
    # stages on device ahead of the running train step (when the pipeline is
    # enabled).
    prefetch_depth: int = 1
    # pack_train_batch: pack the variable-length episodes of each train batch
    # into dense rows (pipeline.ppo_pipeline.pack_ppo_batch) — fewer padded
    # positions through the train forward/backward, so short-response
    # workloads stop paying full [batch, P+R] compute. Row counts are
    # bucketed (B/4, B/2, 3B/4, B) to bound retraces. Off (the default)
    # keeps the unpacked per-episode-row layout byte-identical to before.
    pack_train_batch: bool = False
    # Continuous-batching rollout engine (trlx_tpu/engine). All four knobs
    # default to the static-batch chunked rollout path, byte-identical to
    # before.
    #
    # rollout_engine: route experience generation through the slot-based
    # engine — finished sequences free their slot immediately and a queued
    # prompt is prefilled into it, so mixed response lengths stop paying the
    # whole-chunk straggler cost. Runs multi-host (every controller makes
    # the same slot decisions, verified per phase by the slot-schedule crc)
    # and with decode_weight_quant (unfused-scoring delta bounded by the
    # engine+int8 parity test); requires no soft prompts — see PPOTrainer's
    # validation.
    rollout_engine: bool = False
    # engine_slots: size of the engine's fixed slot pool (the compiled decode
    # program's batch dimension). 0 = auto: chunk_size.
    engine_slots: int = 0
    # prefill_batch: slot admission batches prompt prefills — while slots are
    # live, admission waits until this many slots are free, then prefills one
    # same-width group in a single compiled call.
    prefill_batch: int = 4
    # engine_steps_per_sync: decode steps the engine runs per host
    # round-trip. Larger values amortize dispatch/sync overhead; finished
    # slots sit idle for at most this many steps before harvest+refill (the
    # occupancy cost of the amortization).
    engine_steps_per_sync: int = 8
    # spec_decode: per-slot speculative decoding inside the rollout engine.
    # "" / "off" (default) keeps the one-token-per-dispatch decode program
    # byte-identical; "ngram" arms the host-side per-slot bigram drafter
    # (engine/drafters.py) — each sync proposes spec_k tokens per slot and
    # ONE jitted batched verify program scores every slot's draft window at
    # once, accepting the longest matching prefix (greedy) or via standard
    # rejection sampling (do_sample). Requires rollout_engine. "model"
    # (drafter-model hook) is reserved and raises NotImplementedError.
    spec_decode: str = ""
    # spec_k: draft window width per verify dispatch (position 0 is the
    # model's own next token, so k-1 drafted tokens ride along and every
    # live slot advances >= 1 token per dispatch). 0 = auto (4 when
    # spec_decode is armed). Values >= 2 required when armed.
    spec_k: int = 0
    # paged_kv: paged KV cache + prefix caching inside the rollout engine
    # (ROADMAP item 3). The fixed per-slot [n_slots, T] cache becomes ONE
    # shared physical block pool [n_blocks, block_size, h, d] plus per-slot
    # block tables; prompt prefixes whose block-aligned content already sits
    # in the pool (same weight version) are SHARED — admission pins the
    # resident blocks and prefills only the suffix, so identical prompt
    # templates prefill once per weight version instead of once per slot.
    # Composes with kv_cache_quant (int8 pool + per-block scales) and
    # spec_decode (verify windows write through the table; the spec_k-1
    # scratch tail lives in each slot's last block). Requires rollout_engine
    # and no soft prompts. Off (default) keeps the engine byte-identical.
    paged_kv: bool = False
    # kv_block_size: tokens per physical KV block; any size >= 1 works.
    # 128 is one lane tile of the bias row and of the scales.
    kv_block_size: int = 128
    # kv_pool_blocks: physical blocks in the shared pool (incl. the reserved
    # trash block 0). 0 = auto: 1 + engine_slots * ceil(cache_len /
    # kv_block_size) — full worst-case commitment, never a capacity
    # regression. Set BELOW auto to serve more slots than the same bytes
    # could hold fixed-slot (prefix sharing covers the difference); admission
    # is transactional, so an oversubscribed pool requeues instead of
    # deadlocking. See RUNBOOK §20 for the sizing math.
    kv_pool_blocks: int = 0
    # Disaggregated rollout/learner fleet (trlx_tpu/fleet): dedicated
    # rollout and learner JOBS (each its own single-controller JAX world)
    # coupled by a versioned weight broadcast and a bounded-staleness
    # episode stream over train.fleet_dir — the LlamaRL/PipelineRL shape.
    # max_staleness is the coupling knob: the rollout worker may run at most
    # that many stream batches ahead of the learner's consume cursor, and
    # must hold a weight version no older than the gate allows (staleness 0
    # degenerates to the exact serial synchronous schedule — bitwise parity,
    # tests/test_fleet_disagg.py). The per-process role comes from
    # train.fleet_role / TRLX_TPU_FLEET_ROLE; unset = colocated (both roles
    # in one process through the same transports). Off (default) keeps every
    # existing path byte-identical.
    fleet_disaggregate: bool = False
    # fleet_inflight_weights: let the fleet rollout worker adopt broadcast
    # weights MID-PHASE — the engine loop polls weights_latest.json between
    # decode syncs and stages the new version into RolloutEngine.
    # update_weights (adopted at the next engine_steps_per_sync boundary; no
    # drain, no abort). Episodes then carry per-token version_spans and the
    # learner gates staleness at token granularity (fleet/
    # mixed_version_tokens). Requires rollout_engine on the rollout side;
    # silently inert on the chunked path. Off (default) keeps the PR 16
    # phase-boundary adoption byte-identical.
    fleet_inflight_weights: bool = False
    # fleet_elastic: N-worker elastic fleet. Work is partitioned into
    # prompt-shard WORK UNITS (unit u = train iteration u's deterministic
    # prompt chunks); rollout workers claim units through the atomic lease
    # ledger (<fleet_dir>/leases, O_EXCL generation files with
    # heartbeat-renewed expiry), each streams into its OWN index
    # (stream.w<k>.jsonl), and the learner's intake dedupes by
    # (work_unit, episode_key) so a reclaimed unit's double-production is
    # consumed exactly once. Workers may join mid-run (register, adopt the
    # latest broadcast, start claiming) and leave cleanly (deregister); a
    # dead worker's leases expire and peers reclaim them. Requires
    # fleet_disaggregate. Off (default) keeps the single-worker PR 16/17
    # stream layout byte-identical.
    fleet_elastic: bool = False


@dataclass
@register_method
class ILQLConfig(MethodConfig):
    """ILQL hyperparameters (reference: trlx/data/method_configs.py:113-145)."""

    name: str = "ilqlconfig"
    tau: float = 0.7
    gamma: float = 0.99
    cql_scale: float = 0.1
    awac_scale: float = 1.0
    alpha: float = 0.005
    steps_for_target_q_sync: int = 5
    betas: List[float] = field(default_factory=lambda: [4.0])
    two_qs: bool = True
    # TPU addition: decode shapes/params must be static; the reference builds
    # them ad hoc in prepare_learning (trlx/model/accelerate_ilql_model.py:158-181).
    gen_kwargs: dict = field(default_factory=dict)


@dataclass
@register_method
class PPOSoftpromptConfig(PPOConfig):
    """Soft-prompt PPO: learned prefix embeddings, frozen LM
    (reference: trlx/data/method_configs.py:148-153)."""

    name: str = "pposoftpromptconfig"
    n_soft_tokens: int = 8
    initialize_from_vocab: bool = True

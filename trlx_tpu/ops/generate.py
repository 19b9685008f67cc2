"""Jitted autoregressive decode: prefill + `lax.while_loop` token loop.

Replaces both HF `.generate` under no_grad
(reference: trlx/model/accelerate_base_model.py:105-116) and ILQL's Python
per-token loop (reference: trlx/model/nn/ilql_models.py:162-251) with ONE
compiled XLA program per (batch, prompt_len, max_new_tokens) shape:

- prompts are LEFT-padded to a static length (the reference's left-padding
  discipline, reference: trlx/model/accelerate_base_model.py:42-45), so the
  last prompt position is always the sampling position;
- the KV cache is a donated, sharded pytree (heads on tp, batch on dp/fsdp);
- the while_loop exits early when every sequence has finished — on TPU this
  is the difference between paying for max_new_tokens and paying for the
  actual longest sample;
- logit processing (HF chain or ILQL advantage steering) is a pure function
  fused into the step.
"""

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.models.lm import cache_partition_spec, init_cache
from trlx_tpu.observability import device_scopes
from trlx_tpu.ops.sampling import GenerateConfig, process_logits_default


def generate(
    variables,
    prompt_ids: jnp.ndarray,
    prompt_mask: jnp.ndarray,
    rng: jax.Array,
    *,
    model,
    gcfg: GenerateConfig,
    processor: Optional[Callable] = None,
    carry_keys: Tuple[str, ...] = (),
    step_stats_fn: Optional[Callable] = None,
    apply_kwargs: Optional[dict] = None,
    prefill_collect: Tuple[str, ...] = (),
) -> Tuple[jnp.ndarray, ...]:
    """Decode `gcfg.max_new_tokens` tokens after left-padded prompts.

    prompt_ids/prompt_mask: [b, P] (left-padded). Returns (tokens, mask) of
    shape [b, P + max_new_tokens]; generated positions after a sequence
    finishes hold pad_token_id with mask 0.

    `carry_keys` names model-output entries (e.g. "qs", "vs" for ILQL) whose
    last-position values are carried through the loop and handed to the
    processor under state["carry"] — this is how advantage-steered decoding
    reads the Q/V heads each step.

    `step_stats_fn(tok, state) -> {name: [b, ...] float}` (optional) reduces
    the in-loop state to per-step values — scalars (e.g. Q(s, tok), V(s), the
    sampled token's raw logprob) or vectors (e.g. the branch-point hidden
    state) — collected into [b, max_new_tokens, ...] buffers and returned as
    a third output (a model with expert layers adds the scalar
    ``experts_touched_per_step`` to it: held experts with at least one token,
    mean over decode steps and expert layers). This makes decode-side rollout statistics FREE: no extra
    forward pass after generation (validity = the returned mask's response
    region). Scalar stats are stored fp32; vector stats keep their dtype.
    When set, the return is (tokens, mask, stats).

    `apply_kwargs` merges extra kwargs into every model.apply (prefill and
    steps) — e.g. collect_branch_hidden=True. `prefill_collect` names prefill
    output entries returned verbatim as a final `prefill_extras` dict (e.g.
    the prompt region's branch-point hiddens for the fused PPO rollout
    scorer); when non-empty the return is (tokens, mask, stats,
    prefill_extras)."""
    if prefill_collect and step_stats_fn is None:
        raise ValueError(
            "prefill_collect requires step_stats_fn — the 4-tuple "
            "(tokens, mask, stats, prefill_extras) return is the only "
            "supported shape for prefill collection"
        )
    cfg = model.cfg
    B, P = prompt_ids.shape
    N = gcfg.max_new_tokens
    n_soft = cfg.n_soft_tokens
    T = P + N
    eos = gcfg.eos_token_id

    tokens = jnp.concatenate(
        [prompt_ids, jnp.full((B, N), gcfg.pad_token_id, dtype=prompt_ids.dtype)], axis=1
    )
    mask = jnp.concatenate([prompt_mask.astype(jnp.int32), jnp.zeros((B, N), dtype=jnp.int32)], axis=1)

    def with_soft(m):
        """Cache-space mask: soft-prompt slots (always valid) + token slots."""
        if n_soft == 0:
            return m
        return jnp.concatenate([jnp.ones((B, n_soft), dtype=m.dtype), m], axis=1)

    cache = init_cache(cfg, B, T + n_soft)
    # Pin the decode KV cache's layout: batch over the data axes, heads over
    # tp — at 6B+ scale the cache dominates decode memory and XLA's
    # propagation must not replicate it. Skipped when the shapes don't
    # divide the mesh (tiny test models) or no mesh was ever created. NOTE:
    # the mesh is read at trace time; make_generate_fn asserts at every call
    # that the process mesh still matches, so a set_mesh() after tracing
    # fails loudly instead of silently misplacing the cache.
    from trlx_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.peek_mesh()
    if mesh is not None:
        from jax.sharding import NamedSharding

        data = int(mesh.shape[mesh_mod.AXIS_DP]) * int(mesh.shape[mesh_mod.AXIS_FSDP])
        tp = int(mesh.shape[mesh_mod.AXIS_TP])
        if (B % data == 0 and cfg.kv_heads % tp == 0 and (not cfg.has_ssm or cfg.ssm_heads % tp == 0)
                and (not cfg.has_kda or cfg.kda_heads % tp == 0)
                and (not cfg.has_lightning or cfg.lightning_heads % tp == 0)):
            cache = tuple(
                jax.tree_util.tree_map(
                    lambda x, i=i: jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, cache_partition_spec(cfg, x.ndim, i))
                    ),
                    layer_cache,
                )
                for i, layer_cache in enumerate(cache)
            )
        elif mesh.size > 1:
            import warnings

            warnings.warn(
                f"decode KV cache left to XLA propagation: batch {B} or "
                f"{cfg.kv_heads} cache heads do not divide the mesh "
                f"(data={data}, tp={tp}) — at large scale this can "
                "replicate the cache per device"
            )
    extra = apply_kwargs or {}
    # prefill and the decode loop are one program; the scopes tell them apart
    # in a device trace.
    with jax.named_scope("prefill"):
        out = model.apply(
            variables,
            input_ids=prompt_ids,
            attention_mask=prompt_mask,
            cache=cache,
            cache_index=0,
            cache_mask=with_soft(mask),
            **extra,
        )
    prefill_extras = {k: out[k] for k in prefill_collect}

    def last_pos(tree):
        return jax.tree_util.tree_map(lambda x: x[:, -1], tree)

    state = {
        "tokens": tokens,
        "mask": mask,
        "cache": out["cache"],
        "finished": jnp.zeros((B,), dtype=bool),
        "rng": rng,
        "step": jnp.array(0, dtype=jnp.int32),
        "last_logits": out["logits"][:, -1].astype(jnp.float32),
        "last_hidden": out["hidden"][:, -1],
        "carry": {k: last_pos(out[k]) for k in carry_keys},
    }
    counts_experts = out.get("expert_counts") is not None
    if counts_experts:
        # Held experts a decode step gave at least one token, mean over the
        # expert layers, summed over the steps: carried here and read once a
        # rollout (`rollout/experts_touched`), no sync a step.
        state["experts_touched"] = jnp.zeros((), jnp.float32)
    if cfg.attention == "sparse" or (cfg.index_topk and T > cfg.index_topk):
        # The share of their filled slots the sparse layers' decode steps read,
        # summed over rows, K/V heads, layers and steps, and its count (the
        # steps' own `sparse_read`, models/sparse.py): carried like the experts'.
        # An indexed latent layer's steps likewise (models/indexer.py).
        state["sparse_read"] = jnp.zeros((2,), jnp.float32)
    if step_stats_fn is not None:
        # eval_shape: discover the stat names/shapes without executing the fn.
        probe = jax.eval_shape(
            step_stats_fn, jax.ShapeDtypeStruct((B,), tokens.dtype), state
        )
        state["stats"] = {
            k: jnp.zeros(
                (B, N) + tuple(v.shape[1:]),
                dtype=jnp.float32 if v.ndim == 1 else v.dtype,
            )
            for k, v in probe.items()
        }

    def cond(s):
        return (s["step"] < N) & ~jnp.all(s["finished"])

    def body(s):
        step = s["step"]
        last_token = jax.lax.dynamic_slice_in_dim(s["tokens"], P - 1 + step, 1, axis=1)[:, 0]
        with jax.named_scope("sample"):
            if processor is not None:
                logits = processor(
                    s["last_logits"],
                    {"last_token": last_token, "hidden": s["last_hidden"], "step": step, "carry": s["carry"]},
                )
            else:
                logits = process_logits_default(s["last_logits"], gcfg, step)

            rng, sub = jax.random.split(s["rng"])
            if gcfg.do_sample:
                tok = jax.random.categorical(sub, logits, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            tok = tok.astype(s["tokens"].dtype)

        was_finished = s["finished"]
        tok = jnp.where(was_finished, gcfg.pad_token_id, tok)
        finished = was_finished | (tok == eos) if eos is not None else was_finished

        write_pos = P + step
        tokens = jax.lax.dynamic_update_slice(s["tokens"], tok[:, None], (0, write_pos))
        mask_bit = (~was_finished).astype(jnp.int32)
        mask = jax.lax.dynamic_update_slice(s["mask"], mask_bit[:, None], (0, write_pos))

        step_out = model.apply(
            variables,
            input_ids=tok[:, None],
            attention_mask=jnp.ones((B, 1), dtype=jnp.int32),
            cache=s["cache"],
            cache_index=write_pos + n_soft,
            cache_mask=with_soft(mask),
            prepend_soft=False,
            **extra,
        )
        new_s = {
            "tokens": tokens,
            "mask": mask,
            "cache": step_out["cache"],
            "finished": finished,
            "rng": rng,
            "step": step + 1,
            "last_logits": step_out["logits"][:, 0].astype(jnp.float32),
            "last_hidden": step_out["hidden"][:, 0],
            "carry": {k: last_pos(step_out[k]) for k in carry_keys},
        }
        if counts_experts:
            touched = jnp.mean(jnp.sum(step_out["expert_counts"] > 0, axis=-1).astype(jnp.float32))
            new_s["experts_touched"] = s["experts_touched"] + touched
        if "sparse_read" in s:
            new_s["sparse_read"] = s["sparse_read"] + step_out["sparse_read"]
        if step_stats_fn is not None:
            # Stats read the PRE-step state: Q/V at the position that
            # produced `tok` (state-before-token, matching rollout scoring).
            # Rows already finished record EXACT ZEROS — the pad_sequence
            # convention the RL losses assume for post-EOS positions (and
            # zeroed branch-hiddens are safe: post-finish positions are
            # mask-0, so they are never attention keys).
            sv = step_stats_fn(tok, s)
            live = ~was_finished

            def _masked(v, dt):
                return (v * live.reshape((-1,) + (1,) * (v.ndim - 1)).astype(v.dtype)).astype(dt)

            new_s["stats"] = {
                k: jax.lax.dynamic_update_slice(
                    s["stats"][k],
                    _masked(sv[k], s["stats"][k].dtype)[:, None],
                    (0, step) + (0,) * (s["stats"][k].ndim - 2),
                )
                for k in s["stats"]
            }
        return new_s

    with jax.named_scope("decode_loop"):
        final = jax.lax.while_loop(cond, body, state)
    if step_stats_fn is not None and counts_experts:
        final["stats"]["experts_touched_per_step"] = final["experts_touched"] / jnp.maximum(final["step"], 1)
    if step_stats_fn is not None and "sparse_read" in final:
        final["stats"]["sparse_keys_read_share"] = final["sparse_read"][0] / jnp.maximum(final["sparse_read"][1], 1.0)
    if step_stats_fn is not None and prefill_collect:
        return final["tokens"], final["mask"], final["stats"], prefill_extras
    if step_stats_fn is not None:
        return final["tokens"], final["mask"], final["stats"]
    return final["tokens"], final["mask"]


def make_generate_fn(model, gcfg: GenerateConfig, processor: Optional[Callable] = None, carry_keys: Tuple[str, ...] = (), step_stats_fn: Optional[Callable] = None, apply_kwargs: Optional[dict] = None, prefill_collect: Tuple[str, ...] = (), monitor=None, monitor_name: str = "rollout/generate"):
    """Build a jitted generate fn of (variables, prompt_ids, prompt_mask, rng).

    Call once per (model, gcfg, processor) and reuse — each distinct
    (batch, prompt_len) shape compiles once, then is cached. The KV-cache
    sharding constraint reads the process-global mesh at trace time, so the
    built fn is bound to the mesh active at build time: calling it after a
    set_mesh() swap raises instead of silently tracing/running with a stale
    cache placement.

    ``monitor`` (an observability.DeviceMonitor) wraps the INNER jitted fn —
    the monitor must see the post-bucketing padded shapes, not the caller's
    raw prompts, for its compiled-cost capture to hit the executables that
    actually run. The trace-count hook is unaffected: the monitor's one-time
    ``lower()`` shares the jit tracing cache, so ``num_traces`` still counts
    only novel shapes.
    """
    from trlx_tpu.parallel import mesh as mesh_mod

    built_mesh = mesh_mod.peek_mesh()
    fn = partial(
        generate,
        model=model,
        gcfg=gcfg,
        processor=processor,
        carry_keys=carry_keys,
        step_stats_fn=step_stats_fn,
        apply_kwargs=apply_kwargs,
        prefill_collect=prefill_collect,
    )

    # Trace-count hook: the counter bumps INSIDE the traced body, so it
    # increments exactly once per novel (batch, prompt_len) shape — a cached
    # executable replays without re-tracing. This is how the bucketing tests
    # (and operators reading metrics) verify that prompt bucketing bounds the
    # number of compiled generate programs to the number of buckets.
    _traces = {"n": 0, "shapes": []}

    def traced(variables, prompt_ids, prompt_mask, rng):
        _traces["n"] += 1
        _traces["shapes"].append(tuple(prompt_ids.shape))
        return fn(variables, prompt_ids, prompt_mask, rng)

    jitted = device_scopes.wrap(jax.jit(traced))
    if monitor is not None:
        jitted = monitor.wrap(monitor_name, jitted, phase="rollout")

    def call(variables, prompt_ids, prompt_mask, rng):
        current = mesh_mod.peek_mesh()
        if current is not built_mesh:
            raise RuntimeError(
                "generate fn was built under a different process mesh than is "
                "now active (set_mesh() after make_generate_fn). Rebuild the "
                "generate fn for the new mesh — the traced KV-cache sharding "
                "would otherwise be stale."
            )
        out = jitted(variables, prompt_ids, prompt_mask, rng)
        call.num_traces = _traces["n"]
        call.traced_shapes = tuple(_traces["shapes"])
        return out

    call.num_traces = 0
    call.traced_shapes = ()
    return call

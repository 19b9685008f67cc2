"""RL heads and head-carrying model wrappers.

TPU-native redesign of the reference's head models
(reference: trlx/model/nn/ppo_models.py:29-413, trlx/model/nn/ilql_models.py:31-160).

The hydra trick — a frozen ref model sharing the lower trunk with the policy
(reference: trlx/model/nn/ppo_models.py:315-368) — is functional here: the
policy and the ref "branch" are the SAME module; the branch is just a second
`apply` over blocks [k..N) with a frozen pytree subset captured at init
(`extract_branch_params`). No module deepcopy, no separate nn graph; under
pjit both applies fuse into one XLA program.
"""

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.lm import LMConfig, TransformerLM, drawn_in
from trlx_tpu.parallel.schedule import gathering_dot_general


class MLPHead(nn.Module):
    """2-layer head: Dense(2*d) → ReLU → Dense(out)
    (reference: trlx/model/nn/ppo_models.py:29-32 make_head)."""

    out_features: int
    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        # layers_0's kernel is split over fsdp like a trunk kernel and is used
        # like one (parallel/schedule.py); layers_1's never is
        # nn.Dense's own default initializer, drawn as LMConfig.draw_dtype says
        kernel_init = drawn_in(self.cfg.draw_dtype, nn.initializers.lecun_normal())
        h = nn.Dense(
            self.cfg.d_model * 2, dtype=self.cfg.compute_dtype, param_dtype=self.cfg.params_dtype, name="layers_0",
            dot_general=gathering_dot_general(self.path + ("layers_0", "kernel")), kernel_init=kernel_init,
        )(x)
        h = nn.relu(h)
        # Head output in fp32: value/Q targets are small-magnitude scalars and
        # bf16 rounding hurts GAE/TD numerics.
        return nn.Dense(
            self.out_features, dtype=jnp.float32, param_dtype=self.cfg.params_dtype, name="layers_1", kernel_init=kernel_init
        )(h)


class LMWithValueHead(nn.Module):
    """Policy LM + scalar value head (+ hydra frozen branch support).

    Equivalent of GPTHydraHeadWithValueModel / GPTHeadWithValueModel
    (reference: trlx/model/nn/ppo_models.py:35-99,315-413). ``branch_layer`` is
    the block index where the frozen ref branch starts
    (= n_layer - num_layers_unfrozen); -1 disables branch collection (fully
    unfrozen → a separate full ref model is needed, as in the reference's
    orchestrator fallback, reference: trlx/orchestrator/ppo_orchestrator.py:38-39).
    """

    cfg: LMConfig
    branch_layer: int = -1

    def setup(self):
        assert not (self.cfg.n_soft_tokens > 0 and self.branch_layer >= 0), (
            "soft-prompt models use a full frozen ref copy, not the hydra branch"
        )
        self.transformer = TransformerLM(self.cfg)
        self.v_head = MLPHead(1, self.cfg)

    def __call__(
        self,
        input_ids=None,
        attention_mask=None,
        position_ids=None,
        inputs_embeds=None,
        cache=None,
        cache_index=None,
        cache_mask=None,
        block_tables=None,
        collect_branch_hidden: bool = False,
        prepend_soft: bool = True,
        logits_start: int = 0,
        compute_logits: bool = True,
        labels=None,
        labels_mask=None,
        segment_ids=None,
    ):
        out = self.transformer(
            input_ids=input_ids,
            attention_mask=attention_mask,
            position_ids=position_ids,
            inputs_embeds=inputs_embeds,
            cache=cache,
            cache_index=cache_index,
            cache_mask=cache_mask,
            block_tables=block_tables,
            collect_hidden_at=self.branch_layer if (collect_branch_hidden and self.branch_layer >= 0) else None,
            prepend_soft=prepend_soft,
            logits_start=logits_start,
            compute_logits=compute_logits,
            labels=labels,
            labels_mask=labels_mask,
            segment_ids=segment_ids,
        )
        values = self.v_head(out["hidden"])[..., 0]
        return {
            "logits": out["logits"],
            "values": values,
            "hidden": out["hidden"],
            "branch_hidden": out["branch_hidden"],
            "cache": out["cache"],
            "expert_counts": out["expert_counts"],
            "branch_router_state": out["branch_router_state"],
            "router_top_weight": out["router_top_weight"],
            "sparse_sums": out["sparse_sums"],
            "sparse_read": out["sparse_read"],
            "dsa_sums": out["dsa_sums"],
            "exit_probs": out["exit_probs"],
            "logprobs": out["logprobs"],
            "lse": out["lse"],
            "entropy": out["entropy"],
        }

    def forward_branch(self, branch_hidden, attention_mask=None, position_ids=None, logits_start: int = 0,
                       labels=None, labels_mask=None, segment_ids=None, router_state=None):
        """Replay blocks [branch_layer..N) + ln_f + lm head from the
        branch-point hidden states (a looped stack: from the FIRST loop's
        block branch_layer through every later loop whole, over
        `branch_replay_params`; `cfg.router_carry`: and from `router_state`,
        the router state block branch_layer - 1 handed on, which the policy's
        pass returns as ``branch_router_state``). Called via
        ``model.apply({'params': ref_branch_params}, ..., method='forward_branch')``
        — the functional `forward_hydra`
        (reference: trlx/model/nn/ppo_models.py:351-368). With ``labels``
        the replay returns fp32 label logprobs [b, S] straight from the
        fused head (the ref branch's [b, S, V] logits never materialize);
        without, it returns logits as before."""
        out = self.transformer(
            inputs_embeds=branch_hidden,
            attention_mask=attention_mask,
            position_ids=position_ids,
            start_layer=self.branch_layer,
            logits_start=logits_start,
            labels=labels,
            labels_mask=labels_mask,
            segment_ids=segment_ids,
            router_state=router_state,
        )
        if labels is not None:
            return out["logprobs"]
        return out["logits"]


class LMWithILQLHeads(nn.Module):
    """LM + vocab-wide Q head(s) + scalar V head for ILQL
    (reference: trlx/model/nn/ilql_models.py:31-129).

    Target Q heads are NOT modules here: the trainer holds a frozen pytree
    copy of the q-head params and evaluates them via ``compute_qs`` with the
    target subtree swapped in — Polyak sync becomes a pure tree_map blend
    (vs the reference's GatheredParameters/rank-0 dance,
    reference: trlx/model/nn/ilql_models.py:131-160).
    """

    cfg: LMConfig
    two_qs: bool = True

    def setup(self):
        self.transformer = TransformerLM(self.cfg)
        self.v_head = MLPHead(1, self.cfg)
        self.q1_head = MLPHead(self.cfg.vocab_size, self.cfg)
        if self.two_qs:
            self.q2_head = MLPHead(self.cfg.vocab_size, self.cfg)

    def __call__(
        self,
        input_ids=None,
        attention_mask=None,
        position_ids=None,
        states_ixs=None,
        actions_ixs=None,
        cache=None,
        cache_index=None,
        cache_mask=None,
        prepend_soft: bool = True,
        labels=None,
        labels_mask=None,
        compute_q_heads: bool = True,
    ):
        """Returns dict(logits, qs, vs, hidden, cache, logprobs).

        With states_ixs/actions_ixs [b, n]: Q heads run only on action hidden
        states, V head on state hidden states (reference:
        trlx/model/nn/ilql_models.py:99-118). Without: all positions.

        ``labels`` switches the LM head to the fused-logprob mode (logits
        stays None, ``logprobs`` [b, S] comes back instead — the AWAC term
        without a [b, T, V] buffer). ``compute_q_heads=False`` skips the
        vocab-wide online Q projection (qs = None): the fused trainer path
        evaluates the Q heads itself through the streaming kernel, so the
        [b, A, V] tensors never materialize either.
        """
        out = self.transformer(
            input_ids=input_ids,
            attention_mask=attention_mask,
            position_ids=position_ids,
            cache=cache,
            cache_index=cache_index,
            cache_mask=cache_mask,
            prepend_soft=prepend_soft,
            labels=labels,
            labels_mask=labels_mask,
        )
        hs = out["hidden"]
        if actions_ixs is not None:
            hs_actions = jnp.take_along_axis(hs, actions_ixs[..., None], axis=1)
        else:
            hs_actions = hs
        if states_ixs is not None:
            hs_states = jnp.take_along_axis(hs, states_ixs[..., None], axis=1)
        else:
            hs_states = hs

        qs = self.compute_qs(hs_actions) if compute_q_heads else None
        vs = self.v_head(hs_states)[..., 0]
        return {
            "logits": out["logits"],
            "qs": qs,
            "vs": vs,
            "hidden": hs,
            "cache": out["cache"],
            "logprobs": out["logprobs"],
        }

    def compute_qs(self, hidden) -> Tuple[jnp.ndarray, ...]:
        """Q head application; also the target-Q entry point (apply with the
        target params subtree swapped into 'q1_head'/'q2_head')."""
        with jax.named_scope("lm_head"):
            qs = (self.q1_head(hidden),)
            if self.two_qs:
                qs = qs + (self.q2_head(hidden),)
        return qs


# ---------------------------------------------------------------------------
# Param-pytree surgery (the functional hydra / freezing machinery)
# ---------------------------------------------------------------------------


def extract_branch_params(params: dict, cfg: LMConfig, branch_layer: int) -> dict:
    """Copy the frozen-branch param subset: blocks [branch_layer..N), ln_f,
    and the LM head (wte when tied). This pytree is the entire "ref model" —
    the counterpart of ModelBranch's deepcopy of top-k blocks
    (reference: trlx/model/nn/ppo_models.py:109-129)."""
    t = params["transformer"]
    branch = {}
    for i in range(branch_layer, cfg.n_layer):
        branch[f"h_{i}"] = t[f"h_{i}"]
    branch["ln_f"] = t["ln_f"]
    if cfg.tie_word_embeddings:
        branch["wte"] = t["wte"]
    else:
        branch["lm_head"] = t["lm_head"]
    # Real copies, not aliases: the frozen branch must not share buffers with
    # the trainable params (donation would see the same buffer twice, and the
    # "frozen" semantics require an immutable snapshot).
    return jax.tree_util.tree_map(jnp.copy, {"transformer": branch})


def branch_replay_params(params: dict, branch: dict, cfg: LMConfig, branch_layer: int) -> dict:
    """The tree `forward_branch` replays over. One pass through the stack:
    `branch` itself, the frozen copies. A looped stack shares its weights
    between the loops, so the trained top blocks act in loop 1 and feed every
    later loop: the hidden state entering block `branch_layer` in the LAST
    loop already depends on trained weights, and a replay from there would
    score a reference that drifts with the policy. The replay starts at (loop
    1, block branch_layer) and runs everything after it, so it needs the
    bottom blocks too, in loops 2..R: the live ones, which are frozen and so
    identical to the initial ones (no second copy is kept)."""
    if cfg.n_loops == 1:
        return branch
    live = params["transformer"]
    return {"transformer": {**{f"h_{i}": live[f"h_{i}"] for i in range(branch_layer)}, **branch["transformer"]}}


def trainable_mask(params: dict, cfg: LMConfig, num_layers_unfrozen: int) -> dict:
    """Boolean pytree: True where the param trains.

    The functional analogue of requires_grad_(False) layer freezing
    (reference: trlx/model/accelerate_base_model.py:49-64): with
    num_layers_unfrozen = k > 0 the bottom N-k blocks are frozen. Embeddings
    and ln_f stay trainable, exactly like the reference (which freezes only
    entries of `hidden_layers`). k <= 0 → everything trains. Blocks are
    found by name (`h_<i>`), whatever kind each is; an expert layer's router
    correction bias is a buffer and never trains (models/moe.py). A latent
    layer's indexer (models/indexer.py) never trains: its choice carries no
    gradient and the PPO loss has no term for it, so AdamW's decay alone would
    shrink it. A looped
    stack's exit gate never trains either: at exit_threshold 1 it decides
    nothing and has no gradient, and AdamW's decay alone would shrink it.
    """
    from trlx_tpu.models.moe import BIAS_NAME

    k = num_layers_unfrozen if num_layers_unfrozen > 0 else cfg.n_layer
    frozen_blocks = {f"h_{i}" for i in range(cfg.n_layer - k)}

    def mask(path, _leaf):
        keys = [str(getattr(k, "key", k)) for k in path]
        if keys[-1] == BIAS_NAME or "exit_gate" in keys or "indexer" in keys:
            return False
        if "transformer" in keys and any(fb in keys for fb in frozen_blocks):
            return False
        return True

    return jax.tree_util.tree_map_with_path(mask, params)

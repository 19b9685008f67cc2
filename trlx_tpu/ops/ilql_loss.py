"""ILQL losses: double-Q TD + expectile-V + CQL + AWAC.

Pure-function redesign of the reference's in-trainer loss
(reference: trlx/model/accelerate_ilql_model.py:50-156). Operates on
fixed-shape padded batches; the reference's implicit masking conventions
(dones zero-padded ⇒ terminal_mask kills padded entries; AWAC masked by
attention) carry over exactly.

Split into two layers so the fused-logprob head can feed it without ever
materializing [b, A, V] Q tensors or [b, T, V] logits:

- ``ilql_loss_terms`` — the actual objective, over per-action GATHERED
  quantities: online Q at the dataset action (= the label LOGIT, which the
  fused kernel reconstructs as logprob + logsumexp), target Q at the action,
  and the CQL NLL (= −label logprob, straight from the kernel). The AWAC
  term arrives as a precomputed scalar for the same reason.
- ``ilql_loss`` — the legacy dense entry point (takes full [b, A, V] /
  [b, T, V] tensors, gathers, and delegates). Kept byte-identical to the
  pre-split behavior; CPU tests and the non-fused trainer path use it.
"""

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.modeling import logprobs_from_logits


def action_tokens(input_ids: jnp.ndarray, actions_ixs: jnp.ndarray) -> jnp.ndarray:
    """Action token = the token following each action position
    (reference: trlx/model/accelerate_ilql_model.py:66). [b, T], [b, A] → [b, A]."""
    return jnp.take_along_axis(input_ids[:, 1:], actions_ixs, axis=1)


@jax.named_scope("loss")
def ilql_loss_terms(
    Qs: Sequence[jnp.ndarray],        # each [b, A] fp32: online Q at dataset action
    targetQs: Sequence[jnp.ndarray],  # each [b, A] fp32: target Q at dataset action
    cql_nlls: Sequence[jnp.ndarray],  # each [b, A] fp32: −log softmax(q)[action]
    vs: jnp.ndarray,                  # [b, A+1] (V head at states)
    rewards: jnp.ndarray,             # [b, A]
    dones: jnp.ndarray,               # [b, A+1] (1 while alive, 0 at terminal & padding)
    loss_awac: jnp.ndarray,           # scalar fp32: mean NLL over attended tokens
    *,
    gamma: float,
    tau: float,
    cql_scale: float,
    awac_scale: float,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The ILQL objective over already-gathered per-action values.

    ``targetQs`` entries are stop-gradiented here (callers may pass live
    arrays). Everything else is consumed as-is — in particular the fused
    head path hands in Q = logprob + logsumexp and cql_nll = −logprob with
    no [·, ·, V] tensor ever built.
    """
    targetQs = [jax.lax.stop_gradient(q) for q in targetQs]
    targetQ = jnp.minimum(*targetQs) if len(targetQs) > 1 else targetQs[0]

    dones = dones.astype(jnp.float32)
    terminal_mask = dones[:, :-1]  # [b, A]
    n_nonterminal = jnp.maximum(jnp.sum(terminal_mask), 1.0)

    vs = vs.astype(jnp.float32)
    V = vs[:, :-1]
    Vnext = jax.lax.stop_gradient(vs[:, 1:]) * dones[:, 1:]
    Q_target_value = rewards.astype(jnp.float32) + gamma * Vnext

    loss_q = sum(
        jnp.sum(jnp.square(Q - Q_target_value) * terminal_mask) / n_nonterminal for Q in Qs
    )

    # expectile regression of V toward targetQ
    # (reference: trlx/model/accelerate_ilql_model.py:99-105)
    diff = targetQ - V
    weight = jnp.where(diff >= 0, tau, 1.0 - tau)
    loss_v = jnp.sum(weight * jnp.square(diff) * terminal_mask) / n_nonterminal

    # CQL: push Q mass toward dataset actions via cross-entropy
    # (reference: trlx/model/accelerate_ilql_model.py:107-133)
    loss_cql = sum(jnp.sum(nll * terminal_mask) / n_nonterminal for nll in cql_nlls)

    loss = loss_q + loss_v + cql_scale * loss_cql + awac_scale * loss_awac
    stats = {
        "losses/loss": loss,
        "losses/loss_q": loss_q,
        "losses/loss_v": loss_v,
        "losses/loss_cql": loss_cql,
        "losses/loss_awac": loss_awac,
    }
    return loss, stats


@jax.named_scope("loss")
def ilql_loss(
    logits: jnp.ndarray,       # [b, T, V]
    qs: Tuple[jnp.ndarray, ...],        # each [b, A, V] (online heads)
    target_qs: Tuple[jnp.ndarray, ...], # each [b, A, V] (frozen target heads)
    vs: jnp.ndarray,           # [b, A+1] (V head at states)
    input_ids: jnp.ndarray,    # [b, T]
    attention_mask: jnp.ndarray,  # [b, T]
    actions_ixs: jnp.ndarray,  # [b, A] int (padded with 0)
    rewards: jnp.ndarray,      # [b, A]
    dones: jnp.ndarray,        # [b, A+1] (1 while alive, 0 at terminal & padding)
    *,
    gamma: float,
    tau: float,
    cql_scale: float,
    awac_scale: float,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    actions = action_tokens(input_ids, actions_ixs)  # [b, A]

    def gather_a(q):
        return jnp.take_along_axis(q.astype(jnp.float32), actions[..., None], axis=-1)[..., 0]

    Qs = [gather_a(q) for q in qs]
    targetQs = [gather_a(q) for q in target_qs]
    cql_nlls = [-logprobs_from_logits(q, actions) for q in qs]

    # AWAC: supervised LM loss over the whole sequence
    # (reference: trlx/model/accelerate_ilql_model.py:135-142)
    attn = attention_mask.astype(jnp.float32)
    nll = -logprobs_from_logits(logits[:, :-1], input_ids[:, 1:])
    loss_awac = jnp.sum(nll * attn[:, 1:]) / jnp.maximum(jnp.sum(attn[:, 1:]), 1.0)

    return ilql_loss_terms(
        Qs,
        targetQs,
        cql_nlls,
        vs,
        rewards,
        dones,
        loss_awac,
        gamma=gamma,
        tau=tau,
        cql_scale=cql_scale,
        awac_scale=awac_scale,
    )

"""RL loss functions: GAE + PPO clipped objectives (ILQL in ops/ilql_loss.py).

TPU re-design of the reference's in-loss Python GAE loop
(reference: trlx/model/accelerate_ppo_model.py:83-97) as a `lax.scan` over the
time axis, and the clipped pg/vf losses (reference:
trlx/model/accelerate_ppo_model.py:122-147) as masked fixed-shape ops. All in
fp32.

Two deliberate deviations from reference quirks (do-not-reproduce list,
SURVEY.md §7):

1. Consistent value indexing: the reference's rollout stores V at positions
   [P-1, P+R-1) (trlx/orchestrator/ppo_orchestrator.py:94-96) but its loss
   reads vpred at positions [P, P+R) (trlx/model/accelerate_ppo_model.py:120)
   — off by one. Here BOTH use the state-before-token convention [P-1, P+R-1).
2. Terminal score lands on the last *valid* token, not the last column
   (trlx/orchestrator/ppo_orchestrator.py:101-104 adds the score at column
   R-1, which is masked out of the loss for early-terminated sequences).
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.modeling import masked_mean, masked_whiten


def gae_advantages(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    mask: jnp.ndarray,
    gamma: float,
    lam: float,
    segment_ids: jnp.ndarray = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generalized advantage estimation over the response region.

    rewards/values/mask: [b, R] right-padded. Returns (advantages, returns),
    both zeroed at padded positions. The reversed recurrence
    A_t = delta_t + gamma*lam*A_{t+1} runs as a `lax.scan` over reversed time
    — one compiled pass instead of the reference's per-step Python loop.

    ``segment_ids`` (optional, [b, R] int, 0 = pad): with packed rows holding
    several independent episodes per row, both the bootstrap V(s_{t+1}) and
    the scan carry must stop at segment boundaries — each packed episode gets
    exactly the recurrence it would get unpacked. Without it (the default)
    the function is unchanged: one episode per row, boundary handled by the
    zero-padded tail.
    """
    mask = mask.astype(jnp.float32)
    r = rewards.astype(jnp.float32) * mask
    v = values.astype(jnp.float32) * mask
    next_v = jnp.concatenate([v[:, 1:], jnp.zeros_like(v[:, :1])], axis=1)
    if segment_ids is not None:
        # cont[t] = 1 iff t+1 is a valid token of the SAME episode; kills the
        # bootstrap and the lam-carry across packed-episode boundaries.
        same = (segment_ids[:, 1:] == segment_ids[:, :-1]) & (mask[:, 1:] > 0)
        cont = jnp.concatenate(
            [same.astype(jnp.float32), jnp.zeros_like(mask[:, :1])], axis=1
        )
        next_v = next_v * cont
    deltas = r + gamma * next_v - v  # zero at padded tail ⇒ clean boundary

    if segment_ids is None:

        def step(carry, delta_t):
            adv_t = delta_t + gamma * lam * carry
            return adv_t, adv_t

        _, advs_rev = jax.lax.scan(step, jnp.zeros_like(deltas[:, 0]), deltas.T[::-1])
    else:

        def step(carry, xs):
            delta_t, cont_t = xs
            adv_t = delta_t + gamma * lam * carry * cont_t
            return adv_t, adv_t

        _, advs_rev = jax.lax.scan(
            step, jnp.zeros_like(deltas[:, 0]), (deltas.T[::-1], cont.T[::-1])
        )
    advantages = advs_rev[::-1].T * mask
    returns = (advantages + v) * mask
    return advantages, returns


@jax.named_scope("loss")
def ppo_loss(
    logprobs: jnp.ndarray,
    vpred: jnp.ndarray,
    old_logprobs: jnp.ndarray,
    old_values: jnp.ndarray,
    rewards: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    gamma: float,
    lam: float,
    cliprange: float,
    cliprange_value: float,
    vf_coef: float,
    segment_ids: jnp.ndarray = None,
    n_seqs: int = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Clipped PPO objective over the response region
    (reference: trlx/model/accelerate_ppo_model.py:76-155).

    All args [b, R] fp32 (right-padded, mask marks valid response tokens).
    GAE + whitening happen inside so the whole update is one fused program.
    Returns (loss, stats); stats["mean_kl"] is the policy-vs-rollout
    sum-over-tokens KL the adaptive controller consumes (the same quantity the
    reference records at trlx/model/accelerate_ppo_model.py:134-136).

    Packed batches: pass ``segment_ids`` ([b, R] int, 0 = pad — forwarded to
    GAE so the recurrence resets at episode boundaries) and ``n_seqs`` (static
    int: the number of ORIGINAL episodes packed into the batch). The
    token-level reductions (masked_mean over valid tokens) are already
    layout-invariant; only the per-sequence means (mean_kl, mean_return) need
    n_seqs — row count no longer equals episode count. Defaults keep the
    unpacked path byte-identical.
    """
    mask = mask.astype(jnp.float32)
    advantages, returns = gae_advantages(
        rewards, old_values, mask, gamma, lam, segment_ids=segment_ids
    )
    advantages = jax.lax.stop_gradient(masked_whiten(advantages, mask))
    returns = jax.lax.stop_gradient(returns)

    vpred = vpred.astype(jnp.float32)
    vpredclipped = jnp.clip(vpred, old_values - cliprange_value, old_values + cliprange_value)
    vf_losses1 = jnp.square(vpred - returns)
    vf_losses2 = jnp.square(vpredclipped - returns)
    vf_loss = 0.5 * masked_mean(jnp.maximum(vf_losses1, vf_losses2), mask)
    vf_clipfrac = masked_mean((vf_losses2 > vf_losses1).astype(jnp.float32), mask)

    log_ratio = (logprobs - old_logprobs) * mask
    ratio = jnp.exp(log_ratio)
    pg_losses = -advantages * ratio
    pg_losses2 = -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange)
    pg_loss = masked_mean(jnp.maximum(pg_losses, pg_losses2), mask)
    pg_clipfrac = masked_mean((pg_losses2 > pg_losses).astype(jnp.float32), mask)

    loss = pg_loss + vf_coef * vf_loss
    if n_seqs is None:
        mean_kl = jnp.mean(jnp.sum(log_ratio, axis=-1))
        mean_return = jnp.mean(jnp.sum(rewards * mask, axis=-1))
    else:
        # Packed: per-episode sums still add up across rows, but rows != episodes,
        # so normalize by the true episode count instead of jnp.mean's row count.
        mean_kl = jnp.sum(log_ratio) / n_seqs
        mean_return = jnp.sum(rewards * mask) / n_seqs
    # Health diagnostics (trlx_tpu/observability/health.py) — reductions
    # only, the objective above is untouched: a Monte-Carlo entropy estimate
    # over the sampled tokens (E[-log pi(a|s)] under the policy's own
    # samples), and the value head's explained variance over the (stopped)
    # GAE returns — negative EV means the critic is worse than predicting
    # the mean return.
    ret_mean = masked_mean(returns, mask)
    ret_var = masked_mean(jnp.square(returns - ret_mean), mask)
    err_var = masked_mean(jnp.square(returns - vpred), mask)
    stats = {
        "loss": loss,
        "pg_loss": pg_loss,
        "vf_loss": vf_loss,
        "pg_clipfrac": pg_clipfrac,
        "vf_clipfrac": vf_clipfrac,
        "mean_kl": mean_kl,
        "mean_ratio": masked_mean(ratio, mask),
        "mean_return": mean_return,
        "mean_advantage": masked_mean(advantages, mask),
        "mean_entropy": masked_mean(-logprobs, mask),
        "explained_variance": 1.0 - err_var / (ret_var + 1e-8),
    }
    return loss, stats


def kl_penalty_rewards(
    logprobs: jnp.ndarray,
    ref_logprobs: jnp.ndarray,
    response_mask: jnp.ndarray,
    scores: jnp.ndarray,
    kl_coef: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token reward = −kl_coef·(logp − ref_logp), with the scalar score
    added at the last VALID response token
    (reference: trlx/orchestrator/ppo_orchestrator.py:101-104; see module
    docstring for the masked-terminal fix).

    Returns (rewards [b, R], kl [b, R]).
    """
    mask = response_mask.astype(jnp.float32)
    kl = (logprobs - ref_logprobs) * mask
    non_score = -kl_coef * kl
    lengths = jnp.sum(mask, axis=-1).astype(jnp.int32)
    last_ix = jnp.maximum(lengths - 1, 0)
    terminal = jax.nn.one_hot(last_ix, logprobs.shape[-1], dtype=jnp.float32) * mask
    rewards = non_score + terminal * scores[:, None]
    return rewards, kl

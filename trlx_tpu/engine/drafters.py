"""Host-side draft-token proposers for the speculative verify path.

The engine's spec-decode loop (rollout_engine._step_verify) is
drafter-agnostic: anything with ``reset_slot`` / ``observe`` / ``propose``
can feed the batched verify program. The contract is deliberately host-side
and per-slot — drafting costs O(n_slots * spec_k) dict lookups per sync,
which hides entirely under the verify dispatch, and a slot's table dies
with its episode so continuous-batching refills never leak another prompt's
statistics into a fresh slot.

``NgramDrafter`` is the first real drafter: a per-slot bigram table seeded
from the admitted prompt and updated online from the ACCEPTED token stream
(never from rejected drafts — those are exactly the tokens the big model
disagreed with). A seeded ``transition`` function overrides the learned
table for workloads whose next-token map is known a priori — a
forced-bigram probe needs it for the perfect-draft case, since that
workload's chained pairs never repeat within an episode and an online
table would score zero accepts.

The drafter-MODEL hook (a small LM proposing k tokens on device) is
reserved: ``make_drafter("model", ...)`` raises NotImplementedError with
the integration point spelled out, so the config surface is stable before
the model lands.
"""

from typing import Callable, Optional, Sequence

__all__ = ["NgramDrafter", "make_drafter"]


class NgramDrafter:
    """Per-slot bigram (order-1 n-gram) draft proposer.

    propose(slot, last_token, k) chains k predictions through the slot's
    table: each miss falls back to ``pad_token_id`` — a deliberate
    "worthless draft" that the verify program will reject at its position,
    costing nothing beyond the already-dispatched window. A cold table
    therefore degrades to exactly the non-speculative rate (the verify
    window's position 0 is the model's own token, not a draft).
    """

    def __init__(
        self,
        pad_token_id: int,
        transition: Optional[Callable[[int], int]] = None,
    ):
        self.pad_token_id = int(pad_token_id)
        self.transition = transition
        self._tables = {}  # slot -> {prev_token: next_token} (last-seen wins)

    def reset_slot(self, slot: int, prompt_tokens: Sequence[int]) -> None:
        """A slot was (re)admitted: drop the previous occupant's table and
        seed from the new prompt's bigrams."""
        table = {}
        toks = [int(t) for t in prompt_tokens]
        for prev, nxt in zip(toks, toks[1:]):
            table[prev] = nxt
        self._tables[int(slot)] = table

    def observe(self, slot: int, tokens: Sequence[int]) -> None:
        """Fold newly ACCEPTED tokens (including the transition from the
        previous frontier token — callers prepend it) into the slot table."""
        table = self._tables.setdefault(int(slot), {})
        toks = [int(t) for t in tokens]
        for prev, nxt in zip(toks, toks[1:]):
            table[prev] = nxt

    def propose(self, slot: int, last_token: int, k: int) -> list:
        """k draft tokens continuing ``last_token``, chained through the
        table (or the seeded transition fn)."""
        out = []
        cur = int(last_token)
        if self.transition is not None:
            for _ in range(k):
                cur = int(self.transition(cur))
                out.append(cur)
            return out
        table = self._tables.get(int(slot), {})
        for _ in range(k):
            cur = table.get(cur, self.pad_token_id)
            out.append(cur)
        return out


def make_drafter(kind: str, pad_token_id: int):
    """Drafter factory for ``method.spec_decode`` values.

    "ngram" -> NgramDrafter (learned per-slot bigram table). "model" is the
    reserved drafter-model hook: a small on-device LM proposing the window
    in one call — plumb it by returning an object with the same
    reset_slot/observe/propose surface whose propose() reads a host
    snapshot of the draft model's greedy chain.
    """
    if kind == "ngram":
        return NgramDrafter(pad_token_id)
    if kind == "model":
        raise NotImplementedError(
            "spec_decode='model' (drafter-model hook) is reserved: implement "
            "a propose() backed by a small LM and register it here"
        )
    raise ValueError(f"unknown spec_decode drafter kind: {kind!r}")

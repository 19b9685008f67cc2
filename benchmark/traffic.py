"""The one traffic generator: a cell's `traffic` parameters + a seed → inputs.

Lengths are a fixed quantile grid of the stated distribution (so every seed
has the same histogram and the same total of non-pad tokens), placed by a
seeded shuffle (`"placement": "seeded"`) or by one fixed shuffle
(`"placement": "fixed"`, where the rows a window reaches must not depend on
the seed). Token contents and rewards always come from the seed. Ids 0 and 1
are never drawn: 0 is the pad and end-of-sequence id of a tokenizer-less run.
"""

import math
from statistics import NormalDist

import numpy as np


def length_grid(spec, n):
    """n lengths at the quantiles (i + 0.5) / n of `spec`, ascending."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["distribution"]
    if kind == "uniform":
        x = spec["min"] + (spec["max"] - spec["min"]) * u
    elif kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(q)) for q in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif kind == "fixed":
        x = np.full(n, spec["max"], dtype=np.float64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def placed_lengths(spec, n, seed):
    lengths = length_grid(spec, n)
    placement = spec.get("placement", "seeded")
    if placement not in ("seeded", "fixed"):
        raise ValueError(f"unknown placement {placement!r}")
    order = np.random.default_rng(seed if placement == "seeded" else 0).permutation(n)
    return lengths[order]


def token_rows(lengths, vocab_size, seed):
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(2, vocab_size, size=int(n)).astype(np.int32) for n in lengths]


def ppo_prompts(traffic, vocab_size, seed):
    """The prompt pool of a PPO cell: one chunk's worth of token-id rows, so
    every iteration sees the whole pool and the same number of tokens."""
    return token_rows(placed_lengths(traffic["prompt_length"], traffic["n_prompts"], seed), vocab_size, seed)


def ppo_reward(rows, vocab_size):
    """A cheap numpy pass over the rows (as chip_smoke.py's): the mean token
    id, scaled to [0, 1]. Any finite number serves; the cost is what counts."""
    return [float(np.mean(np.asarray(r, np.float32)) / vocab_size) for r in rows]


def ilql_dataset(traffic, vocab_size, seed):
    """(samples, rewards): reward-labelled token-id rows of an ILQL cell."""
    rows = token_rows(placed_lengths(traffic["row_length"], traffic["n_rows"], seed), vocab_size, seed)
    rewards = np.random.default_rng([seed, 2]).normal(size=len(rows)).astype(np.float32)
    return rows, [float(r) for r in rewards]

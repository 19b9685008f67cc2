"""The sparse expert feed-forward layer, as ONE chip of an expert-parallel
deployment holds it.

    sigma = sigmoid(x . W_g)                       over ALL n_experts, product in float32
    chosen = the experts_per_token largest of sigma + b     (b: the correction bias, a buffer)
    w_e = sigma_e / sum_chosen sigma * routed_scaling_factor
    y = sum over (chosen and held) of w_e Expert_e(x) + Shared(x)

That is `LMConfig.router_scoring` "sigmoid". "softmax" is another family's
rule (`route`): the experts_per_token largest of the raw logits x . W_g, then
a softmax over the chosen, no bias buffer and no scale; "softmax_all" a third
(`choose`): a softmax over all experts, the choice by probability + a
balancing bias, the weight the chosen probability itself. The logits are one
product (`router_kind` "linear") or an MLP behind a down-projection whose
output the layer above carries on (`MLPRouter`, `router_carry`). What the router reads
is a kind too (`LMConfig.router_input`): the feed-forward's input, or the
block's own input ahead of attention (`ExpertLayer.routing`, called by
`models/lm.py Block` before its mixer).

The layer is told which routed experts it holds (`LMConfig.experts_held`,
`[first, first + count)`), routes over all of them at the published width,
and computes its own experts' part of the result. What the absent experts
would add is left out; nothing here stands in for the other chips or their
tokens, and on one chip the layer runs without its exchange. No token is
dropped: there is no capacity limit. A padded position (`token_mask`, the
pass's own, which `models/lm.py Block` hands over) takes no routed expert and
is in no count: pads are one token and would all choose the same experts. The balance of a trained router is the
bias `b`, which no gradient moves (`models/heads.py` `trainable_mask`); no
balance term joins a loss.

How the held experts' part is computed (`held_experts_ffn`), by the size of
the call, both exact for any routing:

A small call (a decode step: at most `SMALL_CALL_SLOTS` token-slots) runs EVERY
held expert over every token of the call, weighted by zero where a token did
not choose it: at 32 tokens the arithmetic is nothing and the step is the held
experts' weights read once, whatever the routing (0.98 ms a layer of 8 held
experts on the chip, 88% of the weight-read floor; PERF.md, PR 26). It does
not skip an expert that no token of the call chose: on one chip's 32 tokens
about half of the held experts go untouched a step and a `lax.cond` an expert
would save their weights' read, but a rollout's seconds then follow the
routing (which experts the seed made popular, how peaked the policy has
become: 1% of an iteration between seeds), and in the deployment this layer
is a share of, a chip's experts take the tokens of all the chips that share
the layer and none goes untouched.

A large call goes through in passes whose length follows the call's shapes
(`pass_tokens`): the longest divisor of its tokens not above `WIDE_PASS_TOKENS`
where the slot buffer is wide (the sum back onto the tokens is the gather,
below), not above `NARROW_PASS_TOKENS` where it is narrow. Every pass reads
every held expert's weights again and ends every group in a partial row tile
that the grouped product pays whole (its time follows the tiles its groups
touch, not its rows), and passes in a loop sum the weight gradients in the
loop's carry: a train batch goes in ONE pass where it can. Where the sum back
is the 0/1 product its work grows with the SQUARE of a pass (2 x buffer rows
FLOPs a token a column), which eats what longer grouped products give back:
those calls keep 4,096 tokens a pass. The longer cap bounds the buffers of a
scoring pass over a whole rollout chunk (the readings: PERF.md section 6,
PR 48; `bench_moe.py`). A pass's (token, choice) slots that chose a held
expert are placed in a buffer, one group an expert, and go through three
grouped products (`jax.lax.ragged_dot`).
Where a slot goes is counted, not sorted (`place_slots`): a token chooses an
expert at most once, so a slot's row is its expert's offset plus the tokens
before its own that chose the same expert, which is the row a stable sort by
expert gives it. The rows are gathered from the tokens (`take_rows`) and summed
back onto them (`put_rows`), never by XLA's scatter-add, which cost more than
the three products and did not fall with the rows (PERF.md, PR 31). The sum
has two forms and the call's shapes say which runs (`sums_by_gather`): ONE
product with the placement's 0/1 matrix `[tokens, rows]`, whose work grows with
the buffer's rows, or a gather of each token's own rows (`place_slots` hands
out the inverse placement, the row of each of a token's choices), whose
traffic grows with the choices a token has, held or not. The product where
the buffer is narrow (1,536 rows of 7,168 for 4,096 tokens of 8 choices: a
token holds 0.17 of its choices and the gather reads all 8; 0.47 ms on the
chip against 1.47), the gather where it is wide (12,288 rows of 2,560 for
4,096 tokens of 6 choices: the product is 258 GFLOP a call where the three
grouped products it serves need 72; 1.34 ms against 0.38; PERF.md, PR 45).

The buffer is sized to the layer's share of the experts, from the call's
shapes alone (`slot_capacity`): twice the even share of the held experts,
`2 n k held / n_experts` slots in whole tiles of `ROW_TILE` rows (1,536 rows
where 8 of 384 experts are held and 4,096 tokens choose 8 each, 4,096 where 8
of 128 are), never more than `SLOTS_PER_TOKEN` rows a token (three: where 16
of 64 experts are held and a token chooses 6, twice the even share is 12,288
rows for 4,096 tokens, and at two rows a token, a third above even, a router
over random weights passed the buffer in one layer of eight on the chip and the
step's time followed the routing; PERF.md, PR 44). A call whose held
slots pass it takes `dense_held_ffn`, every held expert over every token in
token chunks, recomputed in the backward pass: a `lax.cond` on the call's held
slots, exact for any routing. That path is for safety, not a ladder for speed:
a step's time must not follow the routing from step to step, and at twice the
even share the chip never left the buffer (`moe/first_buffer_share`, the share
of a step's calls it served: 1.0). A second buffer of two rows a token between
the two was built and taken out: a branch nobody took, it set the train step's
temporaries (0.63 GB more on the chip at 8 of 384, PERF.md, PR 31).
"""

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.lm import ACTIVATIONS, MLP, LMConfig, drawn_in
from trlx_tpu.parallel.schedule import use_weight

BIAS_NAME = "e_score_correction_bias"
SMALL_CALL_SLOTS = 2048  # token-slots (tokens x experts_per_token) up to which a call is "small": a decode step
SLOTS_PER_TOKEN = 3  # the most rows a token the slot buffer of a large call takes
ROW_TILE = 512  # a slot buffer is whole row tiles of the grouped product
GATHER_ROWS_PER_CHOICE = 640  # rows of slot buffer a choice of a token from which the sum back onto the tokens is a gather: on the chip the two forms cross at 510-610
NARROW_PASS_TOKENS = 4096  # the most tokens a pass where the sum back onto the tokens is the 0/1 product, whose work grows with the square of a pass
WIDE_PASS_TOKENS = 16384  # and where it is the gather: bounds the buffers of a scoring pass over a whole rollout chunk


def choose(logits, bias, k: int, scaling: float, scoring: str):
    """(ids [n, k] int32, weights [n, k] float32) from the router's float32
    `logits` [n, n_experts]. `scoring` "sigmoid": the rule of the module
    docstring. "softmax": the k largest of the raw logits and a softmax over
    those k, which is a softmax over all n_experts renormalised over the
    chosen; `bias` is None and `scaling` 1. "softmax_all": a softmax over all
    n_experts, the k largest of probability + `bias` (a buffer no gradient
    moves), the weights the chosen probabilities as they are: not
    renormalised, so that with k = 1 the router still has a gradient
    (`LMConfig.router_scoring`)."""
    if scoring == "softmax":
        chosen, ids = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(chosen, axis=-1)
    elif scoring == "softmax_all":
        probs = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        weights = jnp.take_along_axis(probs, ids, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
        weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scaling
    return ids.astype(jnp.int32), weights


def route(x, router, bias, k: int, scaling: float, scoring: str = "sigmoid"):
    """`choose` over the linear router's logits of tokens `x` [n, d]: the
    router's product and everything after it in float32, as published."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        return choose(logits, bias, k, scaling, scoring)


class MLPRouter(nn.Module):
    """The router of `LMConfig.router_kind` "mlp", all of it in float32:

        s = h W_d + b_d                      [n, router_hidden]
        s = s + gamma * s_below              `router_carry`: the layer below's s, after ITS carry and before its norm
        z = RMSNorm(s)
        logits = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3          [n, n_experts]

    Returns (logits, s): s is the state the layer above carries on. The first
    layer is handed zeros (nothing lies below it), so its gamma moves nothing."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, h, below):
        cfg, f32 = self.cfg, jnp.float32
        lecun = nn.initializers.lecun_normal()
        # The two kernels behind a GeLU are DRAWN with zero column sums (initialisation only): a GeLU's output has
        # a positive mean, the same on every token, which a plain draw turns into logit offsets every token shares
        # (deviation 0.37 beside the tokens' own 0.52), and a router over random weights then sends 40-60% of the
        # tokens to two experts (`moe_max_expert_load` 7-10 on the chip, PERF.md section 6, PR 47). A trained
        # router's balancing bias evens that out; here the draw does (1.3-2.0).
        centred = lambda key, shape, dtype=f32: (lambda w: w - jnp.mean(w, axis=0, keepdims=True))(lecun(key, shape, dtype))
        dense = lambda feats, name, bias=True, init=lecun: nn.Dense(
            feats, use_bias=bias, dtype=f32, param_dtype=cfg.params_dtype, precision=jax.lax.Precision.HIGHEST, name=name,
            kernel_init=drawn_in(cfg.draw_dtype, init), bias_init=nn.initializers.normal(0.02))
        s = dense(cfg.router_hidden, "down")(h.astype(f32))
        if cfg.router_carry:
            # drawn from the seed at the state's own size: a zero one would let a program that dropped the carry pass
            gamma = self.param("carry_scale", nn.initializers.normal(0.5), (cfg.router_hidden,), cfg.params_dtype)
            if below is not None:  # None: the layer alone, nothing below it
                s = s + gamma.astype(f32) * below.astype(f32)
        z = nn.RMSNorm(epsilon=cfg.ln_eps, dtype=f32, param_dtype=cfg.params_dtype, name="norm")(s)
        gelu = lambda a: nn.gelu(a, approximate=False)
        hidden = gelu(dense(cfg.router_hidden, "hidden_1", init=centred)(gelu(dense(cfg.router_hidden, "hidden_0")(z))))
        return dense(cfg.n_experts, "out", False, centred)(hidden), s


def slot_capacity(n_tokens: int, k: int, held: int, n_experts: int) -> int:
    """Rows of the slot buffer of a large call over `n_tokens` tokens, from
    shapes only: twice the even share of the held experts (`n_tokens * k *
    held / n_experts` slots) in whole row tiles; never more than
    `SLOTS_PER_TOKEN` rows a token, nor than the worst case, every token
    choosing as many held experts as it can."""
    even_twice = -(-2 * n_tokens * k * held // n_experts)
    return min(-(-even_twice // ROW_TILE) * ROW_TILE, SLOTS_PER_TOKEN * n_tokens, n_tokens * min(k, held))


def pass_tokens(n_tokens: int, k: int, held: int, n_experts: int) -> int:
    """Tokens of ONE pass of a large call over `n_tokens` tokens, from shapes
    only: the longest divisor of `n_tokens` not above `WIDE_PASS_TOKENS` where
    the slot buffer is wide (what `sums_by_gather` says of a
    `NARROW_PASS_TOKENS` pass's buffer: the sum back is the gather, whose
    traffic does not grow with a pass), not above `NARROW_PASS_TOKENS` where
    the sum is the 0/1 product. A divisor, so that every pass is the same
    program, however many passes that makes; a length whose longest one is a
    small call's (a prime's is 1) is refused: one pass of everything has no
    bound, and a small call runs every held expert over every token."""
    wide = sums_by_gather(slot_capacity(NARROW_PASS_TOKENS, k, held, n_experts), min(k, held))
    cap = WIDE_PASS_TOKENS if wide else NARROW_PASS_TOKENS
    if n_tokens <= cap:
        return n_tokens
    passes = next(p for p in range(-(-n_tokens // cap), n_tokens + 1) if n_tokens % p == 0)
    if n_tokens // passes * k <= SMALL_CALL_SLOTS:
        raise ValueError(
            f"an expert layer's call of {n_tokens} tokens has no divisor between {SMALL_CALL_SLOTS // k + 1} and {cap} tokens "
            f"to take as a pass ({k} of {n_experts} experts a token, {held} held): give the batch x length a factor in that range"
        )
    return n_tokens // passes


def held_counts(ids, first: int, held: int):
    """[held] int32: the tokens each held expert was chosen by."""
    local = ids.reshape(-1) - first
    return jnp.sum(local[:, None] == jnp.arange(held, dtype=ids.dtype)[None, :], axis=0, dtype=jnp.int32)


DENSE_CHUNK = 1024


def experts_over_tokens(x, ids, weights, first: int, gate, up, down, act):
    """sum over the held experts of w_e Expert_e(x), each expert over EVERY
    token with weight zero where the token did not choose it."""
    total = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)[:, None].astype(x.dtype)
        total = total + jnp.dot(act(jnp.dot(x, gate[e])) * jnp.dot(x, up[e]), down[e]) * w_e
    return total


def dense_held_ffn(x, ids, weights, first: int, gate, up, down, act):
    """`experts_over_tokens` in chunks of `DENSE_CHUNK` tokens, each
    recomputed in the backward pass, so that what a differentiated `cond`
    keeps of this branch (and fills with zeros when the other one runs) is
    its inputs and not one [tokens, d] product an expert."""
    n, d = x.shape
    chunk = min(DENSE_CHUNK, n)
    pad = -n % chunk
    split = lambda a: jnp.pad(a, ((0, pad), (0, 0))).reshape((n + pad) // chunk, chunk, a.shape[-1])
    one_chunk = jax.checkpoint(lambda _, args: (None, experts_over_tokens(*args, first, gate, up, down, act)))
    return jax.lax.scan(one_chunk, None, (split(x), split(ids), split(weights)))[1].reshape(n + pad, d)[:n]


def sums_by_gather(capacity: int, choices: int) -> bool:
    """Which form `put_rows` takes over a buffer of `capacity` rows where a
    token has `choices` rows at most, from those two alone: the 0/1 product
    does 2 x capacity FLOPs a token a column, the gather moves 2 x choices
    bytes, so the gather wins from some number of buffer rows a choice on
    (`GATHER_ROWS_PER_CHOICE`). On the chip, bf16, 4,096 tokens, ms a call,
    product / gather: 1.34 / 0.38 at 12,288 rows of 2,560 and 6 choices
    (2,048 rows a choice), 1.06 / 1.27 at 4,096 of 6,144 and 8 (512), 0.20 /
    0.40 at 2,048 of 2,304 and 8 (256), 0.47 / 1.47 at 1,536 of 7,168 and 8
    (192): the product runs at 1.04e-14 s a token a row a column at all four,
    the gather at 5.3-6.3e-12 s a token a choice a column, and they cross at
    510-610 rows a choice (PERF.md, PR 45)."""
    return capacity >= GATHER_ROWS_PER_CHOICE * choices


def place_slots(ids, counts, first: int, capacity: int):
    """(slot [capacity] int32, live [capacity] bool, by_token): the (token,
    choice) slot `token * k + choice` that each row of a slot buffer holds:
    the held experts in order and an expert's tokens in theirs, which is where
    a stable sort of the slots by expert puts them. By counting: a token
    chooses an expert at most once, so a slot's row is its expert's offset
    (the counts before it) plus the tokens before its own that chose the same
    expert. Rows past the held slots are not live and name slot 0.

    `by_token` is the same placement read from the tokens' side, for the sum
    back as a gather, and None where the shapes say the sum is the product
    (`sums_by_gather`): (rows_of [n, s] int32, held_choice [n, s] bool) with
    s = min(k, held), the buffer row of each of a token's choices (of each
    held expert where fewer are held than a token chooses), row 0 and False
    where the choice is not held or its row lies past the buffer."""
    n, k = ids.shape
    held = counts.shape[0]
    hit = ids.T[None] == first + jnp.arange(held, dtype=ids.dtype)[:, None, None]  # [held, k, n]: tokens along the lanes
    chose = jnp.any(hit, axis=1)
    rank = jnp.cumsum(chose, axis=1, dtype=jnp.int32) - chose
    row = jnp.where(chose, (jnp.cumsum(counts) - counts)[:, None] + rank, capacity)
    slot = jnp.arange(n, dtype=jnp.int32)[None] * k + jnp.argmax(hit, axis=1).astype(jnp.int32)
    slot = jnp.zeros(capacity, jnp.int32).at[row.reshape(-1)].set(slot.reshape(-1), mode="drop")
    by_token = None
    if sums_by_gather(capacity, min(k, held)):
        # a (choice, token) pair is hit by one held expert at most: the least over the experts is its row, or `capacity`
        rows = row if held < k else jnp.min(jnp.where(hit, row[:, None], capacity), axis=0)
        in_buffer = rows < capacity
        by_token = (jnp.where(in_buffer, rows, 0).T, in_buffer.T)
    return slot, jnp.arange(capacity) < jnp.sum(counts), by_token


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def take_rows(n: int, x, placed):
    """[rows, d]: row r is `x[token[r]]` of `x` [n, d], zero where not live;
    `placed` is (token [rows], live [rows], by_token), `place_slots`' own."""
    token, live, _ = placed
    return jnp.where(live[:, None], x[token], 0)


def sum_rows_product(n: int, v, token, live):
    """`put_rows` as ONE product with the 0/1 matrix `[n, rows]` of the
    placement: exact (a row is taken whole, the sum is in float32), at the
    MXU's rate, 2 x rows FLOPs a token a column. A dead row has to be finite:
    0 x NaN is NaN here too."""
    takes = (token[None, :] == jnp.arange(n, dtype=token.dtype)[:, None]) & live[None, :]
    return jnp.dot(takes.astype(v.dtype), v, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(v.dtype)


def sum_rows_gather(v, rows_of, held_choice):
    """`put_rows` as a gather: each token reads the row of each of its
    choices and adds the held ones in float32, one choice at a time into one
    `[n, d]` sum, so that no `[n, s, d]` float32 array is kept (ONE gather
    `v[rows_of]` and a reduction took 0.92 ms and 294 MB of temporaries on
    the chip where this takes 0.38 and 84; PERF.md, PR 45). The same sum of
    the same rows as the product's, in the order of a token's choices. A
    `where` selects: what a row no token names holds reaches nothing."""
    total = jnp.zeros((rows_of.shape[0], v.shape[1]), jnp.float32)
    for choice in range(rows_of.shape[1]):
        total = total + jnp.where(held_choice[:, choice, None], v[rows_of[:, choice]], 0).astype(jnp.float32)
    return total.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def put_rows(n: int, v, placed):
    """[n, d]: the transpose of `take_rows`, token t the sum of the live rows
    of `v` [rows, d] that name it, by the tokens' side of the placement where
    `place_slots` handed one out (`sum_rows_gather`) and as the 0/1 product
    where it did not (`sum_rows_product`). Neither is XLA's scatter-add, which
    does not fall with the rows: on the chip 3.21 ms at 1,536 rows of 7,168
    where the product takes 0.49, 1.63 at 4,096 rows of 6,144 where it takes
    1.17 (PERF.md, PR 31), 2.87 at 12,288 rows of 2,560 where the product
    takes 1.34 and the gather 0.38 (PERF.md, PR 45)."""
    token, live, by_token = placed
    return sum_rows_product(n, v, token, live) if by_token is None else sum_rows_gather(v, *by_token)


# each is the other's transpose, in whichever form the placement says: autodiff of a gather would be a scatter-add
take_rows.defvjp(lambda n, x, placed: (take_rows(n, x, placed), placed), lambda n, placed, g: (put_rows(n, g, placed), None))
put_rows.defvjp(lambda n, v, placed: (put_rows(n, v, placed), placed), lambda n, placed, g: (take_rows(n, g, placed), None))


def grouped_held_ffn(x, ids, weights, counts, first: int, capacity: int, gate, up, down, act):
    """The held experts' part through grouped products over a buffer of
    `capacity` rows, which has to hold every held slot: gathered by
    `place_slots`, one group an expert, summed back onto the tokens."""
    n = x.shape[0]
    slot, live, by_token = place_slots(ids, counts, first, capacity)
    placed = (slot // ids.shape[-1], live, by_token)
    w = jnp.where(live, weights.reshape(-1)[slot], 0.0).astype(x.dtype)

    # A grouped product writes only the rows of its groups: what it leaves in
    # the others is not defined (on the chip: whatever was there), in its
    # result and in the lhs-gradient its transpose computes alike; 0 x NaN is
    # NaN, and the first train steps on the chip were all non-finite. So every
    # operand a grouped product reads, and the last result, pass the mask:
    # its forward keeps dead rows out of a sum, its backward zeroes what the
    # transposed product left in them.
    alive = lambda a: jnp.where(live[:, None], a, 0)
    xs = alive(take_rows(n, x, placed))
    with jax.named_scope("moe_grouped_ffn"):
        hidden = alive(act(jax.lax.ragged_dot(xs, gate, counts)) * jax.lax.ragged_dot(xs, up, counts))
        ys = alive(jax.lax.ragged_dot(hidden, down, counts))
    return put_rows(n, ys * w[:, None], placed)


def held_experts_ffn(x, ids, weights, first: int, n_experts: int, gate, up, down, act):
    """(y [n, d], counts [held]): sum over the held experts a token chose of
    w_e Expert_e(x); `gate`/`up` [held, d, f], `down` [held, f, d], the held
    experts `[first, first + held)` of `n_experts`."""
    n, k, held = x.shape[0], ids.shape[-1], gate.shape[0]
    if n * k <= SMALL_CALL_SLOTS:
        return experts_over_tokens(x, ids, weights, first, gate, up, down, act), held_counts(ids, first, held)
    tokens = pass_tokens(n, k, held, n_experts)
    if tokens < n:
        split = lambda a: a.reshape((n // tokens, tokens) + a.shape[1:])
        y, counts = jax.lax.map(lambda args: held_experts_ffn(*args, first, n_experts, gate, up, down, act),
                                (split(x), split(ids), split(weights)))
        return y.reshape(x.shape), jnp.sum(counts, axis=0)
    counts = held_counts(ids, first, held)
    capacity = slot_capacity(n, k, held, n_experts)
    grouped_ffn = lambda: grouped_held_ffn(x, ids, weights, counts, first, capacity, gate, up, down, act)
    if capacity >= n * min(k, held):
        return grouped_ffn(), counts
    y = jax.lax.cond(jnp.sum(counts) <= capacity, grouped_ffn,
                     jax.checkpoint(lambda: dense_held_ffn(x, ids, weights, first, gate, up, down, act)))
    return y, counts


class ExpertLayer(nn.Module):
    """The feed-forward of an "experts" layer. `__call__` returns (y, counts
    [held]). The router reads the feed-forward's input `h`, or `router_input`
    where the block hands one (`LMConfig.router_input` "block": the block's
    own input). A block that routes ahead of its attention calls `routing`
    first and hands the result back as `routed`."""

    cfg: LMConfig

    def setup(self):
        cfg = self.cfg
        d, f = cfg.d_model, cfg.expert_d_ff
        held = cfg.held_experts[1]
        if cfg.router_kind == "mlp":
            self.router = MLPRouter(cfg)
        else:
            self.router = self.param("router", drawn_in(cfg.draw_dtype, nn.initializers.lecun_normal()), (d, cfg.n_experts), cfg.params_dtype)
        # Drawn from the seed, small: a trained router's bias is not zero, and
        # a zero one would let a program that forgot it pass every comparison;
        # but the bias exists to even the load out, and a random one of the
        # scores' own size (deviation 0.1 against the sigmoid's 0.2) skews it:
        # the chip read a fullest expert at 6-15 times the mean (PERF.md, PR 26).
        # A softmax router over the chosen (`router_scoring`) has no such
        # buffer; one over all experts has a balancing bias on probabilities,
        # which at 16 experts are a sixteenth each: drawn that much smaller.
        self.bias = None
        if cfg.router_scoring != "softmax":
            std = 0.01 if cfg.router_scoring == "sigmoid" else 0.005
            self.bias = self.param(BIAS_NAME, nn.initializers.normal(stddev=std), (cfg.n_experts,), jnp.float32)
        stacked = drawn_in(cfg.draw_dtype, nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,)))
        self.experts_gate = self.param("experts_gate", stacked, (held, d, f), cfg.params_dtype)
        self.experts_up = self.param("experts_up", stacked, (held, d, f), cfg.params_dtype)
        self.experts_down = self.param("experts_down", stacked, (held, f, d), cfg.params_dtype)
        if cfg.n_shared_experts:
            self.shared = MLP(cfg, width=cfg.n_shared_experts * f)

    def _route(self, flat):
        """(ids, weights) [n, k] of the tokens `flat` [n, d]."""
        cfg = self.cfg
        # on a partitioned mesh a call of many tokens gathers the router and the
        # stacks at their use, as a dense layer gathers its kernels (parallel/schedule.py)
        router = use_weight(self.router, self.path + ("router",), flat.shape[0])
        return route(flat, router, self.bias, cfg.experts_per_token, cfg.routed_scaling_factor, cfg.router_scoring)

    def _flat(self, x):
        return x.reshape(-1, x.shape[-1]).astype(self.cfg.compute_dtype)

    def routing(self, x):
        """(ids, weights) [b * t, k] of the tokens `x` [b, t, d]."""
        return self._route(self._flat(x))

    def routing_with_state(self, h, below):
        """((ids, weights) [b * t, k], state [b, t, router_hidden] float32)
        of the tokens `h` [b, t, d] under `router_kind` "mlp"; `below` is the
        state the layer below handed on (`router_carry`; None without it)."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            logits, state = self.router(self._flat(h), None if below is None else below.reshape(-1, below.shape[-1]))
            routed = choose(logits, self.bias, cfg.experts_per_token, cfg.routed_scaling_factor, cfg.router_scoring)
        return routed, state.reshape(h.shape[:-1] + state.shape[-1:])

    def __call__(self, h, router_input=None, routed=None, token_mask=None):
        cfg = self.cfg
        dtype = cfg.compute_dtype
        b, t, d = h.shape
        flat = self._flat(h)
        if routed is None and cfg.router_kind == "mlp":
            routed, _ = self.routing_with_state(h, None)
        if routed is None:  # traced in the order it always was: the router, the stacks, the tokens
            routed = self._route(flat if router_input is None else self._flat(router_input))
        if token_mask is not None:
            # `token_mask` [b, t]: a padded position takes no routed expert (its ids name none) and is in no
            # count. Pads are all one token, so they all choose the same experts: a row's 1,792 on one held expert
            # passed the slot buffer and sent the call down `dense_held_ffn` (PERF.md section 6, PR 53)
            ids, weights = routed
            routed = jnp.where(token_mask.reshape(-1, 1) > 0, ids, cfg.n_experts), weights
        at_use = lambda w, name: use_weight(w.astype(dtype), self.path + (name,), b * t)
        gate, up, down = (at_use(getattr(self, name), name) for name in ("experts_gate", "experts_up", "experts_down"))
        with jax.named_scope("moe_experts"):
            y, counts = held_experts_ffn(flat, *routed, cfg.held_experts[0], cfg.n_experts, gate, up, down,
                                         ACTIVATIONS[cfg.activation])
        y = y.reshape(b, t, d)
        if cfg.n_shared_experts:
            with jax.named_scope("moe_shared"):
                y = y + self.shared(h)
        return y, counts


def expert_load_stats(counts, n_tokens: int, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(held_slot_share, max_expert_load) of `counts` [expert layers, held]
    from a call of `n_tokens` tokens: the token-slots that chose a held expert
    over all token-slots (held / n_experts where routing is even), and the
    fullest held expert's tokens over the mean of the held experts'."""
    counts = counts.astype(jnp.float32)
    share = jnp.sum(counts) / (counts.shape[0] * n_tokens * k)
    return share, jnp.max(counts) / jnp.maximum(jnp.mean(counts), 1e-9)


def rows_per_held_expert(held_slot_share: float, n_tokens: int, k: int, held: int, n_experts: int) -> float:
    """Mean rows a held expert takes in ONE grouped call of a pass over
    `n_tokens` tokens (a call a `pass_tokens` pass), from the pass's own
    `moe/held_slot_share`: the counter `moe/rows_per_held_expert` of a step
    record. What the grouped products' arithmetic stands against the read of
    an expert's weights with."""
    return held_slot_share * pass_tokens(n_tokens, k, held, n_experts) * k / held


def sum_rows_per_token(n_tokens: int, k: int, held: int, n_experts: int) -> int:
    """Rows the sum back onto the tokens reads for ONE token in a pass over
    `n_tokens` tokens, from shapes only: the counter `moe/sum_rows_per_token`
    of a step record, which says what form of `put_rows` ran. The whole slot
    buffer of a `pass_tokens` pass under the 0/1 product, a row a choice under
    the gather (`sums_by_gather`); a small call has no buffer and sums one
    result a held expert."""
    if n_tokens * k <= SMALL_CALL_SLOTS:
        return held
    capacity, choices = slot_capacity(pass_tokens(n_tokens, k, held, n_experts), k, held, n_experts), min(k, held)
    return choices if sums_by_gather(capacity, choices) else capacity


def first_buffer_share(counts, n_tokens: int, k: int, n_experts: int):
    """The share of the expert layers' calls (`counts` [expert layers, held],
    each layer one call of `n_tokens` tokens) whose held slots fit the slot
    buffer of `held_experts_ffn`: 1.0 says the buffer sized from the shapes
    served every call. A small call has no buffer to overflow and counts as
    served; a call in several passes is held to its passes' buffers together."""
    held = counts.shape[-1]
    if n_tokens * k <= SMALL_CALL_SLOTS:
        rows = n_tokens * min(k, held)
    else:
        tokens = pass_tokens(n_tokens, k, held, n_experts)
        rows = n_tokens // tokens * slot_capacity(tokens, k, held, n_experts)
    return jnp.mean((jnp.sum(counts, axis=-1) <= rows).astype(jnp.float32))

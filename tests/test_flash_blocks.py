"""The flash kernels' numerics in tier-1 (interpret mode, tiny shapes): the
loop over live chunks, the band's mask, the additive key bias, the carry
over `major` pieces, the chunks a row's padding takes out — against a dense
reference and, to the bit, against the same kernels with the valid key range
forced whole; the liveness rule and the range rule against a brute-force
reading of the dense mask; the counters `flash/kept_pair_share` and
`flash/pad_dead_chunk_share` against counts by hand; and the kernel names
the benchmark's recorder compares with a cell's `expect_kernels`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops import flash_attention as fa
from trlx_tpu.ops.flash_attention import (
    FlashBlocks,
    flash_attention,
    kept_pair_share,
    live_chunks,
    mask_band,
    pad_dead_chunks,
    pick_block,
    valid_block,
    valid_chunks,
)

B, T, H, D = 2, 384, 2, 32
CHUNK = 128

WINDOWS = {"global": 0, "window<chunk": 40, "window=chunk": CHUNK, "window>chunks": 300}
PADDINGS = {
    "no-padding": lambda m: m,
    "left-inside-a-chunk": lambda m: m.at[0, :17].set(0),
    "left-whole-chunks": lambda m: m.at[0, :200].set(0),  # one whole chunk and part of the next
    "right": lambda m: m.at[0, 300:].set(0),
    # pads that cover whole chunks: the loop's bounds leave the band's
    "left-one-chunk": lambda m: m.at[0, :128].set(0),
    "left-two-chunks-and-a-half": lambda m: m.at[0, :320].set(0),
    "right-one-chunk-and-a-half": lambda m: m.at[0, 192:].set(0),
    "left-and-right": lambda m: m.at[0, :130].set(0).at[0, 250:].set(0).at[1, :128].set(0),
    "one-row-all-padding": lambda m: m.at[0, :].set(0),
}
WHOLE_CHUNK_PADDINGS = [p for p in PADDINGS if p not in ("no-padding", "left-inside-a-chunk", "right")]
OFFSETS = {"offset0": None, "traced-offset": 128.0}  # the second: a ring chunk's displacement, with return_lse
BLOCKS = {
    "block=chunk": FlashBlocks(128, T, CHUNK),
    "block>chunk": FlashBlocks(T, T, CHUNK),
    "one-chunk": FlashBlocks(T, T, T),
    "major-pieces": FlashBlocks(128, 128, CHUNK),  # three pieces: the state is carried in scratch
}
PIECES_OF_CHUNKS = FlashBlocks(128, 192, 64)  # two pieces of three chunks: a pad takes chunks out of a piece


def dense_mask(t, window, offset=0, causal=True):
    qi, ki = np.arange(t)[:, None], np.arange(t)[None, :] + offset
    m = ki <= qi if causal else np.ones((t, t), bool)
    return m & (ki > qi - window) if window else m


def ref_attn(q, k, v, kvmask, scale, window, offset, causal=True):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    m = jnp.asarray(dense_mask(q.shape[1], window, offset, causal))[None, None] & kvmask[:, None, None, :].astype(bool)
    s = jnp.where(m, jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale, -1e9)
    lse = jax.nn.logsumexp(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v), lse


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32) for _ in range(3))


def whole_range(kmask):
    """`key_range` forced whole: the kernels' bounds are the band's alone."""
    b, t = kmask.shape[0], kmask.shape[-1]
    return jnp.stack([jnp.zeros((b,), jnp.int32), jnp.full((b,), t, jnp.int32)])


def check_against_reference(monkeypatch, qkv, kvmask, *, window, blocks, off=None, causal=True, to_the_bit=False):
    """Forward and the three gradients against the dense reference at every
    row with a key to see; finite at EVERY position; and, `to_the_bit`, equal
    to the same kernels with the valid key range forced whole."""
    t = qkv[0].shape[1]
    scale = 0.25 if window == 0 else D**-0.5
    # Rows with no key to see (padding queries, or every key of a displaced
    # chunk in their future) are left out: both sides emit a meaningless mix
    # there, normalized differently, and every loss masks them.
    seen = (dense_mask(t, window, int(off or 0), causal)[None] & np.asarray(kvmask, bool)[:, None, :]).any(-1)
    rows = jnp.asarray(seen, jnp.float32)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, kvmask, scale=scale, causal=causal, window=window, blocks=blocks, return_lse=True,
            offset=None if off is None else jnp.float32(off),
        )

    def run(fn):
        """(o, lse) and (dq, dk, dv) of the test's loss, in one pass forward and one back."""
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            use_lse = 0.0 if off is None else 1.0  # the ring path differentiates through lse
            total = jnp.sum(jnp.sin(o) * rows[:, :, None, None]) + use_lse * jnp.sum(jnp.where(rows[:, None, :] > 0, lse, 0.0))
            return total, (o, lse)

        (_, outs), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(*qkv)
        return dict(zip(("o", "lse", "dq", "dk", "dv"), (*outs, *grads)))

    got = run(flash)
    want = run(lambda q, k, v: ref_attn(q, k, v, kvmask, scale, window, int(off or 0), causal))
    at = {"o": rows[:, :, None, None], "lse": rows[:, None, :]}
    for name, atol in (("o", 2e-5), ("lse", 2e-5), ("dq", 5e-5), ("dk", 5e-5), ("dv", 5e-5)):
        np.testing.assert_allclose(np.asarray((got[name] - want[name]) * at.get(name, 1.0)), 0.0, atol=atol, err_msg=name)
        assert np.isfinite(np.asarray(got[name])).all(), name
    if to_the_bit:
        monkeypatch.setattr(fa, "key_range", whole_range)
        whole = run(flash)
        for name in got:
            keep = np.broadcast_to(np.asarray(at.get(name, 1.0)) > 0, got[name].shape)
            np.testing.assert_array_equal(np.asarray(got[name])[keep], np.asarray(whole[name])[keep], err_msg=name)


@pytest.mark.parametrize("blocks", BLOCKS)
@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("padding", PADDINGS)
@pytest.mark.parametrize("window", WINDOWS)
def test_forward_dq_dk_dv_match_reference(monkeypatch, qkv, window, padding, offset, blocks):
    kvmask = PADDINGS[padding](jnp.ones((B, T), jnp.int32))
    check_against_reference(
        monkeypatch, qkv, kvmask, window=WINDOWS[window], blocks=BLOCKS[blocks], off=OFFSETS[offset],
        to_the_bit=padding in WHOLE_CHUNK_PADDINGS,
    )


VARIANTS = {  # beside the main grid: what else decides the kernels' rectangles
    "grouped": dict(kv_heads=1, window=300),  # two query heads over one K/V head: dk, dv summed over the group
    "non-causal": dict(causal=False, window=0),  # a padding query sees the valid keys behind it
    "non-causal-displaced": dict(causal=False, window=40, off=128.0),
}


@pytest.mark.parametrize("blocks", ["block=chunk", "block>chunk", "pieces-of-chunks"])
@pytest.mark.parametrize("padding", WHOLE_CHUNK_PADDINGS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_whole_chunk_padding_in_the_other_rectangles(monkeypatch, qkv, variant, padding, blocks):
    """Grouped keys, `causal=False` (alone and displaced with a window: a
    band with a lower edge only), and pieces of several chunks, under the
    pads that take whole chunks out."""
    kw = dict(VARIANTS[variant])
    q, k, v = qkv
    kv_heads = kw.pop("kv_heads", H)
    kvmask = PADDINGS[padding](jnp.ones((B, T), jnp.int32))
    check_against_reference(
        monkeypatch, (q, k[:, :, :kv_heads], v[:, :, :kv_heads]), kvmask,
        blocks=PIECES_OF_CHUNKS if blocks == "pieces-of-chunks" else BLOCKS[blocks], to_the_bit=True, **kw,
    )


@pytest.mark.parametrize("keys_own_block", [False, True], ids=["fwd-dq", "dkv"])
@pytest.mark.parametrize("offset", [0, 128, -256, 37])
@pytest.mark.parametrize("window", [0, 1, 40, 128, 300])
@pytest.mark.parametrize("causal", [True, False])
def test_live_chunks_equal_brute_force(causal, window, offset, keys_own_block):
    """[lo, hi) is exactly the chunks holding a kept pair (any) — per block,
    for both orientations, over sizes with block <, = and > chunk."""
    t = 512
    mask = dense_mask(t, window, offset, causal)  # [query, key]
    if keys_own_block:
        mask = mask.T
    band = mask_band(offset, causal, window, keys_own_block)
    for block, chunk in ((128, 128), (256, 128), (128, 256), (512, 64)):
        for start in range(0, t, block):
            lo, hi = live_chunks(start, band, block=block, chunk=chunk, count=t // chunk)
            tiles = mask[start:start + block].reshape(block, t // chunk, chunk)
            live = [j for j in range(t // chunk) if tiles[:, j].any()]
            assert live == list(range(lo, hi)) or (not live and lo == hi), (block, chunk, start)


VALID_RANGES = [(0, 512), (130, 512), (256, 512), (300, 512), (511, 512), (0, 200), (0, 256), (0, 1), (140, 390), (128, 384)]


@pytest.mark.parametrize("keys_own_block", [False, True], ids=["fwd-dq", "dkv"])
@pytest.mark.parametrize("offset", [0, 128, -256, 37])
@pytest.mark.parametrize("window", [0, 1, 40, 128, 300])
@pytest.mark.parametrize("causal", [True, False])
def test_valid_range_bounds_equal_brute_force(causal, window, offset, keys_own_block):
    """The band's bounds narrowed by a row's valid key range [first, end),
    per block and per `major` piece: no rectangle with a kept pair on a valid
    key is outside them. Forward / dq: they are exactly the band's chunks that
    hold a valid key, or ONE chunk where there is none and the band had some
    (no new row without a log-sum-exp). dk/dv: the band's, or empty where the
    key block holds no valid key."""
    t = 512
    mask = dense_mask(t, window, offset, causal)  # [query, key]
    band = mask_band(offset, causal, window, keys_own_block)
    for first, end in VALID_RANGES:
        valid = (np.arange(t) >= first) & (np.arange(t) < end)
        kept = (mask & valid[None, :]).T if keys_own_block else mask & valid[None, :]  # [block side, chunk side]
        for block, chunk, major in ((128, 128, 512), (256, 128, 512), (128, 256, 512), (512, 64, 512), (128, 64, 256), (128, 128, 128)):
            for piece in range(0, t, major):
                for start in range(0, t, block):
                    lo, hi = live_chunks(start - piece, band, block=block, chunk=chunk, count=major // chunk)
                    if keys_own_block:
                        bounds = valid_block((lo, hi), start, first, end, block=block)
                        assert bounds == ((lo, hi) if valid[start:start + block].any() else (lo, lo))
                    else:
                        bounds = valid_chunks((lo, hi), first - piece, end - piece, chunk=chunk)
                        holds_valid = [j for j in range(lo, hi) if valid[piece + j * chunk:piece + (j + 1) * chunk].any()]
                        assert list(range(*bounds)) == holds_valid or (not holds_valid and bounds[1] - bounds[0] == (hi > lo))
                    assert lo <= bounds[0] <= bounds[1] <= hi
                    tiles = kept[start:start + block, piece:piece + major].reshape(block, major // chunk, chunk)
                    needed = [j for j in range(major // chunk) if tiles[:, j].any()]
                    assert all(bounds[0] <= j < bounds[1] for j in needed), (first, end, block, chunk, piece, start)


CELL_CALLS = {  # the train step's attention calls of the five one-chip cells: positions, a layer's windows, the share
    "gptj6b-l8.ppo-768x256": (1024, (0,), 0.6673),
    "gptj6b-l8.ppo-128x896": (1024, (0,), 0.6673),
    "gptneo1.3b.ppo-256x256": (512, (0, 256), 0.4382),
    "gptneo1.3b.ilql-256": (256, (0, 256), 0.5020),
    "kimik2.5-l5.ppo-128x896": (1024, (0,), 0.6673),
    # 6,144 positions in three major pieces; a window of 4,096 cuts 11% of a window layer's pairs
    "smallthinker-ep4.ppo-4096x2048": (6144, (0, 4096, 4096, 4096), 0.8976),
}


@pytest.mark.parametrize("cell", CELL_CALLS)
def test_kept_pair_share_is_kept_over_computed_by_hand(cell):
    t, windows, mean_share = CELL_CALLS[cell]
    blocks = pick_block(t)
    shares = []
    for window in windows:
        mask = dense_mask(t, window)
        computed = 0
        for start in range(0, t, blocks.block):
            tiles = mask[start:start + blocks.block].reshape(blocks.block, t // blocks.chunk, blocks.chunk)
            computed += sum(blocks.block * blocks.chunk for j in range(t // blocks.chunk) if tiles[:, j].any())
        shares.append(kept_pair_share(t, blocks, True, window))
        assert shares[-1] == pytest.approx(mask.sum() / computed, rel=1e-12)
    assert sum(shares) / len(shares) == pytest.approx(mean_share, abs=5e-5)  # what `flash/kept_pair_share` logs there


def test_trainer_counter_is_the_mean_over_the_layers_calls(monkeypatch):
    """`flash/kept_pair_share` of a step record: lm.flash_kept_pair_share, the
    mean over the layers of one full-sequence pass; absent where that pass
    takes no flash kernel."""
    from trlx_tpu.models import LMConfig
    from trlx_tpu.models.lm import flash_kept_pair_share

    neo = dict(vocab_size=64, n_layer=4, n_head=2, d_model=256, attention_layers=("global", "local") * 2, window_size=256)
    assert flash_kept_pair_share(LMConfig(**neo, attn_impl="xla"), 512) is None
    assert flash_kept_pair_share(LMConfig(**neo, attn_impl="auto"), 512) is None  # off TPU the auto gate is closed
    share = flash_kept_pair_share(LMConfig(**neo, attn_impl="flash"), 512)
    blocks = pick_block(512)
    assert share == pytest.approx((kept_pair_share(512, blocks, True, 0) + kept_pair_share(512, blocks, True, 256)) / 2)
    gptj = LMConfig(vocab_size=64, n_layer=2, n_head=2, d_model=512, attn_impl="flash")
    assert flash_kept_pair_share(gptj, 1024) == pytest.approx(0.6673, abs=5e-5)


def test_pad_dead_chunk_share_is_the_enumeration_over_rows_and_layers():
    """`flash/pad_dead_chunk_share` of a train step's stats
    (lm.flash_pad_dead_chunk_share): on a batch shaped like
    smallthinker-ep4.ppo-4096x2048's (T 6,144, a 4,096-key window in three
    layers of four, left pads of 1,984 and 64) the live chunks of the forward
    that hold no valid key, less the one a block keeps, over the live chunks —
    counted here from the dense mask; 0.0 where every pad is shorter than a
    chunk; absent where the pass takes no flash kernel."""
    from trlx_tpu.models import LMConfig
    from trlx_tpu.models.lm import flash_pad_dead_chunk_share

    t, pads, window = 6144, (1984, 64), 4096
    cell = dict(vocab_size=64, n_layer=8, n_head=2, d_model=256, attention_layers=("local", "local", "local", "global") * 2, window_size=window)
    mask = jnp.asarray(np.arange(t)[None, :] >= np.asarray(pads)[:, None], jnp.int32)
    block, major, chunk = pick_block(t)
    dead = live = 0
    for layer_window in (window, window, window, 0) * 2:
        dense = dense_mask(t, layer_window)
        for pad in pads:
            for start in range(0, t, block):
                for piece in range(0, t, major):
                    tiles = dense[start:start + block, piece:piece + major].reshape(block, major // chunk, chunk).any((0, 2))
                    holds_valid = tiles & (piece + (np.arange(major // chunk) + 1) * chunk > pad)
                    live += tiles.sum()
                    dead += tiles.sum() - max(holds_valid.sum(), min(tiles.sum(), 1))
    assert (dead, live) == (2 * 30 + 6 * 24, 2 * (2 * 78 + 6 * 72))  # by hand: rows of 3 and 0 whole chunks of padding
    share = flash_pad_dead_chunk_share(LMConfig(**cell, attn_impl="flash"), mask)
    assert float(share) == pytest.approx(dead / live, rel=1e-6) and float(share) == pytest.approx(0.17347, abs=5e-6)
    assert pad_dead_chunks(1984, t, t, pick_block(t), True, 0) == (30, 78)  # python ints in, python ints out
    short = jnp.asarray(np.arange(t)[None, :] >= np.asarray((511, 64))[:, None], jnp.int32)
    assert float(flash_pad_dead_chunk_share(LMConfig(**cell, attn_impl="flash"), short)) == 0.0
    right = jnp.asarray(np.arange(1024)[None, :] < np.asarray((400, 1024))[:, None], jnp.int32)  # ILQL's side
    gptj = LMConfig(vocab_size=64, n_layer=2, n_head=2, d_model=512, attn_impl="flash")
    assert float(flash_pad_dead_chunk_share(gptj, right)) == pytest.approx(1 / 6)  # of 3 live chunks a row, the last block's second
    # one chunk a piece (T 512: GPT-Neo's cells): 0.0 by the rule, no scalar in the stats, the trainer writes it
    assert flash_pad_dead_chunk_share(LMConfig(**cell, attn_impl="flash"), mask[:, :512]) is None
    assert flash_pad_dead_chunk_share(LMConfig(**cell, attn_impl="xla"), mask) is None
    assert flash_pad_dead_chunk_share(LMConfig(**cell, attn_impl="auto"), mask) is None  # off TPU the auto gate is closed


@pytest.mark.parametrize("attn_impl", ["flash", "xla"])
def test_ppo_step_stats_carry_the_counter_where_the_pass_takes_a_flash_kernel(attn_impl):
    """The PPO loss's stats (what a step record is written from): one scalar
    `flash/pad_dead_chunk_share` from the batch's own masks where the train
    pass takes a flash kernel, no key where it does not."""
    from trlx_tpu.data import PPORLBatch
    from trlx_tpu.models import LMConfig
    from trlx_tpu.models.heads import LMWithValueHead
    from trlx_tpu.trainer.api import default_config
    from trlx_tpu.trainer.ppo import make_ppo_loss_fn

    b, prompt, response = 2, 256, 128  # 384 positions: three chunks of 128
    cfg = LMConfig(vocab_size=64, n_layer=2, n_head=2, d_model=32, max_position=512, dtype="float32", attn_impl=attn_impl)
    model = LMWithValueHead(cfg, branch_layer=1)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 64, (b, prompt + response)), jnp.int32)
    query_mask = jnp.ones((b, prompt), jnp.int32).at[0, :130].set(0)  # row 0: one whole chunk of left padding
    params = model.init(jax.random.PRNGKey(0), ids[:, :2], jnp.ones((b, 2), jnp.int32))["params"]
    floats = lambda: jnp.asarray(rng.standard_normal((b, response)), jnp.float32)
    batch = PPORLBatch(query_tensors=ids[:, :prompt], query_mask=query_mask, response_tensors=ids[:, prompt:],
                       response_mask=jnp.ones((b, response), jnp.int32), logprobs=floats(), values=floats(), rewards=floats())
    loss, stats = make_ppo_loss_fn(model, default_config("ppo"), prompt, lambda p: p)(params, batch)
    assert np.isfinite(float(loss))
    if attn_impl == "xla":
        assert "flash/pad_dead_chunk_share" not in stats
    else:  # a row's three blocks keep 1 + 2 + 3 chunks live; the pad takes chunk 0 from the second and third
        assert float(stats["flash/pad_dead_chunk_share"]) == pytest.approx(2 / 12)


def test_pick_block_reads_only_the_calls_length():
    for t in (1024, 768, 512, 256, 4096, 8192, 640, 2560):
        blocks = pick_block(t)
        assert t % blocks.block == 0 and t % blocks.major == 0 and blocks.major % blocks.chunk == 0
        assert blocks.chunk % 128 == 0 and blocks.block == blocks.chunk and blocks.major <= 2048
    assert pick_block(1024) == FlashBlocks(512, 1024, 512)  # every cell shape: the sequence is resident
    assert pick_block(512) == FlashBlocks(512, 512, 512) and pick_block(768) == FlashBlocks(256, 768, 256)
    assert pick_block(8192) == FlashBlocks(512, 2048, 512)  # too long for VMEM: major pieces, the same loop
    assert pick_block(6144) == pick_block(4096) == FlashBlocks(512, 2048, 512)  # a 6,144-token train row, a 4,096-token prefill
    assert pick_block(48) == FlashBlocks(48, 48, 48)  # a length no chunk divides: one whole-length chunk
    assert pick_block(300) == FlashBlocks(300, 300, 300)


def test_recorder_sees_exactly_the_three_kernel_names(qkv):
    """benchmark/harness.py compares the recorded names with a cell's
    `expect_kernels` as a set: the static arguments (`functools.partial`)
    must stay hidden from it, and the three Python names must not change."""
    from benchmark.harness import record_pallas_calls

    record = {}
    kvmask = jnp.ones((B, T), jnp.int32)
    with record_pallas_calls(record):
        for window in (0, 40):
            jax.grad(lambda q, k, v: flash_attention(q, k, v, kvmask, scale=0.25, window=window).sum(), (0, 1, 2))(*qkv)
    assert set(record) == {"flash_attention._fwd_kernel", "flash_attention._bwd_dq_kernel", "flash_attention._bwd_dkv_kernel"}
    assert all(shapes == {(B * H, T, D)} for shapes in record.values())

"""The flash kernels' numerics in tier-1 (interpret mode, tiny shapes): the
loop over live chunks, the band's mask, the additive key bias, the carry
over `major` pieces — against a dense reference; the
liveness rule against a brute-force reading of the dense mask; the counter
`flash/kept_pair_share` against pairs counted by hand; and the kernel names
the benchmark's recorder compares with a cell's `expect_kernels`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops.flash_attention import (
    FlashBlocks,
    flash_attention,
    kept_pair_share,
    live_chunks,
    mask_band,
    pick_block,
)

B, T, H, D = 2, 384, 2, 32
CHUNK = 128

WINDOWS = {"global": 0, "window<chunk": 40, "window=chunk": CHUNK, "window>chunks": 300}
PADDINGS = {
    "no-padding": lambda m: m,
    "left-inside-a-chunk": lambda m: m.at[0, :17].set(0),
    "left-whole-chunks": lambda m: m.at[0, :200].set(0),  # one whole chunk and part of the next
    "right": lambda m: m.at[0, 300:].set(0),
}
OFFSETS = {"offset0": None, "traced-offset": 128.0}  # the second: a ring chunk's displacement, with return_lse
BLOCKS = {
    "block=chunk": FlashBlocks(128, T, CHUNK),
    "block>chunk": FlashBlocks(T, T, CHUNK),
    "one-chunk": FlashBlocks(T, T, T),
    "major-pieces": FlashBlocks(128, 128, CHUNK),  # three pieces: the state is carried in scratch
}


def dense_mask(t, window, offset=0, causal=True):
    qi, ki = np.arange(t)[:, None], np.arange(t)[None, :] + offset
    m = ki <= qi if causal else np.ones((t, t), bool)
    return m & (ki > qi - window) if window else m


def ref_attn(q, k, v, kvmask, scale, window, offset):
    m = jnp.asarray(dense_mask(q.shape[1], window, offset))[None, None] & kvmask[:, None, None, :].astype(bool)
    s = jnp.where(m, jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale, -1e9)
    lse = jax.nn.logsumexp(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v), lse


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32) for _ in range(3))


@pytest.mark.parametrize("blocks", BLOCKS)
@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("padding", PADDINGS)
@pytest.mark.parametrize("window", WINDOWS)
def test_forward_dq_dk_dv_match_reference(qkv, window, padding, offset, blocks):
    window, blocks, off = WINDOWS[window], BLOCKS[blocks], OFFSETS[offset]
    kvmask = PADDINGS[padding](jnp.ones((B, T), jnp.int32))
    scale = 0.25 if window == 0 else D**-0.5
    # Rows with no key to see (padding queries, or every key of a displaced
    # chunk in their future) are left out: both sides emit a meaningless mix
    # there, normalized differently, and every loss masks them.
    seen = (dense_mask(T, window, int(off or 0))[None] & np.asarray(kvmask, bool)[:, None, :]).any(-1)
    rows = jnp.asarray(seen, jnp.float32)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, kvmask, scale=scale, window=window, blocks=blocks, return_lse=True,
            offset=None if off is None else jnp.float32(off),
        )

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            use_lse = 0.0 if off is None else 1.0  # the ring path differentiates through lse
            return jnp.sum(jnp.sin(o) * rows[:, :, None, None]) + use_lse * jnp.sum(jnp.where(rows[:, None, :] > 0, lse, 0.0))

        return f

    ref = lambda q, k, v: ref_attn(q, k, v, kvmask, scale, window, int(off or 0))
    (o, lse), (ro, rlse) = flash(*qkv), ref(*qkv)
    np.testing.assert_allclose(np.asarray((o - ro) * rows[:, :, None, None]), 0.0, atol=2e-5)
    np.testing.assert_allclose(np.asarray((lse - rlse) * rows[:, None, :]), 0.0, atol=2e-5)
    for name, got, want in zip(("dq", "dk", "dv"), jax.grad(loss(flash), (0, 1, 2))(*qkv), jax.grad(loss(ref), (0, 1, 2))(*qkv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, err_msg=name)


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

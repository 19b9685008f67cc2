"""Tier-1 (fast, CPU) static tile-legality tests for the Pallas kernels.

The Mosaic last-two-dims (8, 128)-or-full rule only bites at lowering time
for a TPU — exactly how the old decode-attention kernel's (1, 1, d)
blocks survived CPU CI and then crashed BENCH_r05 mid-bench. These tests
run the rule statically at the REAL bench shapes (B=32, h=16, d=256,
T=832), so an illegal block mapping in ops/ fails the fast tier without
any TPU. The rule is the first of Mosaic's checks, not the last:
tests/test_tpu_lowering.py lowers and compiles the same kernels for a TPU
target."""

import pytest

from trlx_tpu.ops.tiling import (
    BlockLayout,
    TileError,
    block_tile_issues,
    check_layout,
    decode_block_layout,
    flash_block_layout,
    is_tile_legal,
)

# The flagship bench decode shape (gptj-l8-d4096-2.0B: chunk 32 rows/host,
# 16 heads x 256 head_dim, prompt 768 + 64 decoded = 832 cache slots).
BENCH_B, BENCH_H, BENCH_D, BENCH_T = 32, 16, 256, 832


def test_rule_basics():
    # full blocks are always legal, any size
    assert not block_tile_issues((3, 5), (3, 5))
    # divisible blocks are legal
    assert not block_tile_issues((8, 128), (64, 832))
    assert not block_tile_issues((16, 256), (32, 16, 256)[1:])
    # sublane violation
    assert block_tile_issues((1, 128), (64, 832))
    # lane violation
    assert block_tile_issues((8, 100), (64, 832))
    # block larger than array can never map
    assert block_tile_issues((16, 128), (8, 832))
    # rank mismatch is flagged, not crashed on
    assert block_tile_issues((8, 128), (4, 64, 832))
    # rank-0/1 blocks are out of scope for the last-two-dims rule
    assert not block_tile_issues((7,), (9,))


def test_old_decode_specs_are_rejected():
    """The exact block shapes of the pre-rewrite kernel at the BENCH_r05
    crash shape — the validator must reject every one of them."""
    old = [
        BlockLayout("q", (1, 1, BENCH_D), (BENCH_B, BENCH_H, BENCH_D)),
        BlockLayout("k_cache", (1, BENCH_T, 1, BENCH_D), (BENCH_B, BENCH_T, BENCH_H, BENCH_D)),
        BlockLayout("k_scale", (1, BENCH_T, 1), (BENCH_B, BENCH_T, BENCH_H)),
        BlockLayout("bias", (1, BENCH_T), (BENCH_B, BENCH_T)),
    ]
    assert not is_tile_legal(old)
    # and each operand individually carries a violation the error names
    for lay in old:
        issues = block_tile_issues(lay.block_shape, lay.array_shape, lay.name)
        assert issues, f"{lay.name} should be illegal"
        assert lay.name in issues[0]
    with pytest.raises(TileError):
        check_layout(old)


@pytest.mark.parametrize("quant", (True, False))
def test_new_decode_specs_are_legal_at_bench_shape(quant):
    layouts = decode_block_layout(BENCH_B, BENCH_T, BENCH_H, BENCH_D, quant)
    check_layout(layouts)  # raises on violation
    # the cache streams in lane-dense [bt, n_head * head_dim] blocks and the
    # q/out rows carry every head
    by_name = {l.name: l for l in layouts}
    assert by_name["k_cache"].block_shape == (1, 128, BENCH_H * BENCH_D)
    assert by_name["q"].block_shape == (1, 1, BENCH_H * BENCH_D)
    assert by_name["out"].block_shape == (1, 1, BENCH_H * BENCH_D)


@pytest.mark.parametrize(
    "T", (64, 100, 128, 200, 832, 833, 4096)
)
def test_decode_specs_legal_for_ragged_cache_lengths(T):
    """The masked tail removed the cache-length alignment restriction: the
    layout must stay tile-legal for ANY cache length, aligned or not."""
    check_layout(decode_block_layout(BENCH_B, T, BENCH_H, BENCH_D, True))
    check_layout(decode_block_layout(BENCH_B, T, BENCH_H, BENCH_D, False))


def test_decode_specs_legal_for_test_model_shapes():
    """Tiny shapes (CPU test models) are legal too — full blocks everywhere."""
    check_layout(decode_block_layout(2, 17, 2, 16, True))


@pytest.mark.parametrize(
    "BH, T, D",
    [
        (128, 1024, 256),  # gptj6b-l8.ppo-768x256 and gptj6b-l8.ppo-128x896: the train step
        (512, 768, 256),  # the 768-token prefill
        (256, 512, 128),  # gptneo1.3b.ppo-256x256, global and local layers
        (128, 256, 128),  # gptneo1.3b.ilql-256
        (256, 1024, 256),  # kimik2.5-l5.ppo-128x896, heads padded to 256
        (32, 8192, 256),  # too long to be resident: major pieces
    ],
)
def test_flash_specs_legal_at_bench_shape(BH, T, D):
    from trlx_tpu.ops.flash_attention import pick_block

    blocks = pick_block(T)
    check_layout(flash_block_layout(BH, T, D, blocks))
    # what the kernels slice inside a step: 128-row chunks of the resident side
    assert blocks.chunk % 128 == 0 and blocks.block % 128 == 0 and blocks.major % blocks.chunk == 0


def test_routing_probe_refuses_illegal_layout(monkeypatch):
    """decode_attn_supported answers False (with a warning, once) when the
    static layout check fails — a stated CPU-side rule the model layer's
    einsum route keys off. (What passes the rule must lower on a TPU
    backend: tests/test_tpu_lowering.py.)"""
    import warnings

    from trlx_tpu.ops import decode_attention as da
    from trlx_tpu.ops import tiling

    def bad_layout(B, T, h, d, quant, block_t=None):
        return [BlockLayout("q", (1, 1, d), (B, h, d))]

    da._PROBE_CACHE.clear()
    monkeypatch.setattr(tiling, "decode_block_layout", bad_layout)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert not da.decode_attn_supported(4, 64, 4, 128, True)
        assert any("falling back to the einsum" in str(x.message) for x in w)
    # cached: the next call must not warn again
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert not da.decode_attn_supported(4, 64, 4, 128, True)
        assert not w
    da._PROBE_CACHE.clear()


# ---------------------------------------------------------------------------
# Fused logprob head kernel (ops/fused_logprob.py)
# ---------------------------------------------------------------------------

from trlx_tpu.ops.tiling import fused_logprob_block_layout

# The flagship bench HEAD shape: gptj-l8-d4096-2.0B trains with 8 rows of
# T=832 per step (N = 8*832 = 6656 flattened states), d_model 4096, and the
# GPT-J vocab of 50400 (NOT 512-divisible: the last bv=512 vocab tile is a
# partial 224-wide block, masked in-kernel).
HEAD_N, HEAD_D, HEAD_V = 8 * BENCH_T, 4096, 50400


@pytest.mark.parametrize("tied,has_bias", [(True, False), (False, False), (False, True)])
def test_fused_logprob_layout_legal_at_bench_head_shape(tied, has_bias):
    layouts = fused_logprob_block_layout(
        HEAD_N, HEAD_D, HEAD_V, 128, 512, tied, has_bias
    )
    check_layout(layouts)  # raises TileError on violation
    # the weight streams in vocab tiles — it must never be the full [V, D]
    w = next(l for l in layouts if l.name == "w")
    assert w.block_shape != w.array_shape


def test_fused_logprob_layout_rejects_unaligned_vocab_tile():
    # bv=100: lane dim neither 128-divisible nor the full V — Mosaic would
    # reject this at lowering; the static check must catch it on CPU.
    with pytest.raises(TileError):
        check_layout(
            fused_logprob_block_layout(HEAD_N, HEAD_D, HEAD_V, 128, 100, False, False)
        )
    # bn=4: sublane dim of the hidden block violates the 8-row rule.
    with pytest.raises(TileError):
        check_layout(
            fused_logprob_block_layout(HEAD_N, HEAD_D, HEAD_V, 4, 512, True, False)
        )


def test_fused_probe_refuses_illegal_layout(monkeypatch):
    """fused_logprob_supported answers False (with a warning, once) when the
    static layout check fails — the stated rule the model's head routing
    keys off."""
    import warnings

    from trlx_tpu.ops import fused_logprob as fl
    from trlx_tpu.ops import tiling

    def bad_layout(N, D, V, bn, bv, tied, has_bias):
        return [BlockLayout("x", (4, D), (N, D))]

    fl._PROBE_CACHE.clear()
    monkeypatch.setattr(tiling, "fused_logprob_block_layout", bad_layout)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert not fl.fused_logprob_supported(256, 128, 1024, False, False)
        assert any("falling back to the log_softmax" in str(x.message) for x in w)
    # cached: the next call must not warn again
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert not fl.fused_logprob_supported(256, 128, 1024, False, False)
        assert not w
    fl._PROBE_CACHE.clear()


def test_fused_logprob_eligibility_is_static():
    from trlx_tpu.ops.fused_logprob import BLOCK_V, fused_logprob_eligible

    import jax

    on_tpu = jax.default_backend() == "tpu"
    # the flagship head qualifies wherever a TPU is attached
    assert fused_logprob_eligible(HEAD_D, HEAD_V) == on_tpu
    # sub-block vocabs and unaligned d_model never qualify
    assert not fused_logprob_eligible(HEAD_D, BLOCK_V - 1)
    assert not fused_logprob_eligible(HEAD_D + 1, HEAD_V)

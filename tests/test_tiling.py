"""Tier-1 (fast, CPU) static tile-legality tests for the Pallas kernels.

The Mosaic last-two-dims (8, 128)-or-full rule only bites at lowering time
for a TPU — exactly how a kernel's (1, 1, d) blocks once survived CPU CI and
then crashed a bench run mid-way. These tests run the rule statically at the
REAL bench shapes, so an illegal block mapping in ops/ fails the fast tier
without any TPU. The rule is the first of Mosaic's checks, not the last:
tests/test_tpu_lowering.py lowers and compiles the same kernels for a TPU
target."""

import pytest

from trlx_tpu.ops.tiling import (
    BlockLayout,
    TileError,
    block_tile_issues,
    check_layout,
    flash_block_layout,
)

BENCH_T = 832  # prompt 768 + 64 new tokens: the sequence of the flagship head shape below


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


@pytest.mark.parametrize("lowers", [False, True], ids=["does-not-lower", "lowers"])
def test_fused_probe_on_a_tpu_backend_must_lower(monkeypatch, lowers):
    """No quiet fallback: on a TPU backend a shape that passes the tile rule
    is lowered, forward and backward, before the verdict is given. A failure
    raises KernelLoweringError naming the kernel and the shape, and caches
    nothing (the next call raises again); a success is cached and not probed
    twice. The backend here is the CPU under the name "tpu": a Mosaic call
    cannot lower for it, which is the failure."""
    import jax

    from trlx_tpu.ops import fused_logprob as fl
    from trlx_tpu.ops import tiling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fl, "_PROBE_CACHE", {})
    probed = []
    if lowers:
        monkeypatch.setattr(tiling, "require_lowering", lambda kernel, shape, fn, *args: probed.append((kernel, shape, len(args))))
        assert fl.fused_logprob_supported(256, 128, 1024, False, True) is True
        assert fl.fused_logprob_supported(256, 128, 1024, False, True) is True
        assert probed == [("fused-logprob", "[N=256, D=128, V=1024, tied=False, bias=True]", 4)]
        assert list(fl._PROBE_CACHE.values()) == [True]
    else:
        for _ in range(2):
            with pytest.raises(tiling.KernelLoweringError, match=r"fused-logprob kernel is eligible for shape \[N=256, D=128, V=1024"):
                fl.fused_logprob_supported(256, 128, 1024, False, False)
        assert not fl._PROBE_CACHE


def test_fused_logprob_eligibility_is_static():
    from trlx_tpu.ops.fused_logprob import BLOCK_V, fused_logprob_eligible

    import jax

    on_tpu = jax.default_backend() == "tpu"
    # the flagship head qualifies wherever a TPU is attached
    assert fused_logprob_eligible(HEAD_D, HEAD_V) == on_tpu
    # sub-block vocabs and unaligned d_model never qualify
    assert not fused_logprob_eligible(HEAD_D, BLOCK_V - 1)
    assert not fused_logprob_eligible(HEAD_D + 1, HEAD_V)


# The seven one-chip cells' head calls (benchmark/configs, PERF.md §4):
# (rows of a train step, rows of the scoring chunk, D, V, rows' itemsize, tied, bias).
CELL_HEADS = {
    "gptj6b-l8.ppo-768x256": (8 * 256, 32 * 256, 4096, 50400, 2, False, True),
    "gptj6b-l8.ppo-128x896": (8 * 896, 32 * 896, 4096, 50400, 2, False, True),
    "gptneo1.3b.ppo-256x256": (16 * 256, 64 * 256, 2048, 50257, 2, True, False),
    "gptneo1.3b.ilql-256 LM head": (8 * 255, None, 2048, 50257, 2, True, False),
    "gptneo1.3b.ilql-256 Q heads": (8 * 255, None, 4096, 50257, 4, False, True),
    "kimik2.5-l5.ppo-128x896": (4 * 896, 32 * 896, 7168, 20480, 2, False, False),
    "kexaone-l5.ppo-128x896": (4 * 896, 32 * 896, 6144, 19200, 2, False, False),
    "granite4hmicro.ppo-128x896": (8 * 896, 32 * 896, 2048, 100352, 2, True, False),
}
_CELL_CALLS = [(cell, which) for cell, head in CELL_HEADS.items() for which in ("train", "scoring") if head[which == "scoring"]]


@pytest.mark.parametrize("cell,which", _CELL_CALLS, ids=[f"{c}-{w}" for c, w in _CELL_CALLS])
def test_fused_logprob_tiles_legal_and_inside_their_vmem_limit_at_every_cell(cell, which):
    """At each cell's real head shape the rule's tiles are tile-legal in all
    three kernels, in both orientations an untied weight can be handed over
    in (as stored, or transposed where the chip holds it vocabulary-major),
    above the 128-row floor, inside the rule's budget, and each kernel's
    scoped-VMEM request covers its estimate and stays under a v5e's 128 MiB."""
    from trlx_tpu.ops import fused_logprob as fl
    from trlx_tpu.ops.tiling import fused_logprob_vmem_bytes

    train, scoring, D, V, x_itemsize, tied, bias = CELL_HEADS[cell]
    N = scoring if which == "scoring" else train
    tiles = fl.head_tiles(N, D, V, x_itemsize, 2, bias)
    Np = tiles.padded(N)
    assert Np - N < 128 and tiles.weight_passes(N) == Np // tiles.fwd[0]
    for kind, (bn, bv) in zip(("fwd", "dx", "dw"), tiles):
        for orientation in {tied, True}:
            check_layout(fused_logprob_block_layout(Np, D, V, bn, bv, orientation, bias))
        assert bn > fl.ROW_TILE_FLOOR and Np % bn == 0
        need = fused_logprob_vmem_bytes(kind, D, bn, bv, x_itemsize, 2, bias)
        limit = fl._compiler_params(False, need)["compiler_params"].vmem_limit_bytes
        assert need <= fl.VMEM_BUDGET and need <= limit <= 100 * 2**20
    assert tiles.fwd[0] == 512  # every cell's forward streams its weight once per 512 rows

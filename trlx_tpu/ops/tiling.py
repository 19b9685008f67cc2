"""Static Mosaic tile-legality validator for Pallas BlockSpecs.

The Mosaic TPU lowering requires that the LAST TWO dimensions of every
BlockSpec block shape are divisible by (8, 128) — or equal the respective
dimensions of the overall array (a "full" block needs no tiling). Violations
only surface at lowering time for a TPU, as a mid-run ValueError: exactly
how an early decode-attention kernel's per-head `(1, 1, d)` q block killed a
flagship bench run.

This module makes the rule checkable on CPU, without lowering anything:
kernel modules describe their real block layouts (`decode_block_layout`,
`flash_block_layout`) and tier-1 tests assert legality at the real bench
shapes. The routing gates run `layout_issues` first, so a tile-illegal shape
is refused (and routed to einsum) by a stated rule; what passes the rule
must then lower (`require_lowering`) — the tile rule is only the first of
Mosaic's checks, and a kernel that passes it and still cannot lower is a
defect to surface, not a route to take quietly.
"""

from typing import NamedTuple, Optional, Sequence, Tuple

# The divisibility floor Mosaic enforces on the last two block dims (the
# fp32 register tile). Per-dtype minimum tiles — bf16 (16, 128), int8
# (32, 128) — affect layout efficiency, not lowering legality, so the
# validator enforces (8, 128) and leaves dtype padding to the compiler.
SUBLANE = 8
LANE = 128


class BlockLayout(NamedTuple):
    """One operand's (block shape, array shape) pair, as handed to
    pl.BlockSpec / pl.pallas_call."""

    name: str
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]


class TileError(ValueError):
    """A BlockSpec violates the Mosaic last-two-dims (8, 128)-or-full rule."""


def block_tile_issues(
    block_shape: Sequence[int],
    array_shape: Sequence[int],
    name: str = "operand",
) -> list:
    """All (8, 128)-or-full violations for one block spec, as strings.

    Mirrors Mosaic's actual check: for arrays of rank >= 2, block dim -1
    must be divisible by 128 or equal array dim -1, and block dim -2 must be
    divisible by 8 or equal array dim -2. Rank-0/1 blocks are unconstrained
    here (Mosaic handles them separately). Also flags blocks larger than the
    array and rank mismatches, which can never map."""
    issues = []
    if len(block_shape) != len(array_shape):
        return [
            f"{name}: block rank {len(block_shape)} != array rank "
            f"{len(array_shape)} (block {tuple(block_shape)} vs array "
            f"{tuple(array_shape)})"
        ]
    for b, a in zip(block_shape, array_shape):
        if b > a:
            issues.append(
                f"{name}: block dim {b} exceeds array dim {a} "
                f"(block {tuple(block_shape)} vs array {tuple(array_shape)})"
            )
    if len(block_shape) < 2:
        return issues
    checks = ((-2, SUBLANE), (-1, LANE))
    for axis, tile in checks:
        b, a = block_shape[axis], array_shape[axis]
        if b % tile != 0 and b != a:
            issues.append(
                f"{name}: block dim {axis} is {b} — must be divisible by "
                f"{tile} or equal the array dim {a} (block "
                f"{tuple(block_shape)} vs array {tuple(array_shape)}); "
                "the Mosaic TPU lowering rejects this spec"
            )
    return issues


def layout_issues(layouts: Sequence[BlockLayout]) -> list:
    """Every (8, 128)-or-full violation across a kernel's specs."""
    issues = []
    for lay in layouts:
        issues.extend(block_tile_issues(lay.block_shape, lay.array_shape, lay.name))
    return issues


def check_layout(layouts: Sequence[BlockLayout]) -> None:
    """Raise TileError listing every violation across a kernel's specs."""
    issues = layout_issues(layouts)
    if issues:
        raise TileError("; ".join(issues))


def is_tile_legal(layouts: Sequence[BlockLayout]) -> bool:
    return not layout_issues(layouts)


def routing_verdict(cache: dict, key, kernel: str, shape: str, layouts, fallback: str, lower) -> bool:
    """Cached routing verdict for one call-site shape of a kernel whose
    static eligibility rule already passed.

    The CPU-runnable tile check over the kernel's real block layouts may
    refuse the shape — a stated rule, warned once, answered False, and the
    caller takes `fallback`. A shape that passes must, on a TPU backend,
    lower: `lower()` returns `(fn, *abstract_args)` for `require_lowering`,
    which raises naming the kernel and the shape. `cache` is the kernel
    module's probe cache (devicemon's routing gauges read it)."""
    import warnings

    import jax

    hit = cache.get(key)
    if hit is not None:
        return hit
    issues = layout_issues(layouts)
    if issues:
        warnings.warn(
            f"{kernel} kernel refused for shape {shape} by the static tile "
            f"check — falling back to the {fallback} path "
            f"({'; '.join(issues)[:300]})"
        )
    elif jax.default_backend() == "tpu":
        require_lowering(kernel, shape, *lower())
    cache[key] = not issues
    return cache[key]


class KernelLoweringError(RuntimeError):
    """A kernel that its static rules call eligible does not lower."""


def require_lowering(kernel: str, shape: str, fn, *abstract_args) -> None:
    """Lower `fn` for the current (TPU) backend at abstract operands; a
    failure raises KernelLoweringError naming the kernel and the shape.
    Routing never reads the outcome: an eligible shape either lowers or the
    run stops here with the Mosaic diagnostic attached, instead of printing
    einsum numbers under the kernel's name."""
    import jax

    try:
        jax.jit(fn).lower(*abstract_args)
    except Exception as e:
        raise KernelLoweringError(
            f"{kernel} kernel is eligible for shape {shape} but does not "
            f"lower on the {jax.default_backend()} backend "
            f"({type(e).__name__}: {str(e)[:500]})"
        ) from e


# ---------------------------------------------------------------------------
# Layout descriptions of the in-tree kernels (one source of truth: the
# kernel wrappers build their pallas specs FROM these, so the validator can
# never drift from what actually lowers).
# ---------------------------------------------------------------------------


def decode_block_layout(
    B: int, T: int, h: int, d: int, quant: bool, block_t: Optional[int] = None
) -> list:
    """The flash-decode kernel's block layouts at a given shape (see
    trlx_tpu.ops.decode_attention: grid (batch, T-blocks), the cache viewed
    as [B, T, h*d] and streamed in lane-dense (bt, h*d) blocks, q/out as
    [B, 1, h*d] rows, scales in their natural [B, T, h] cache layout, bias
    as [B, 1, T])."""
    from trlx_tpu.ops.decode_attention import pick_t_block

    bt = pick_t_block(T) if block_t is None else block_t
    hd = h * d
    layouts = [
        BlockLayout("q", (1, 1, hd), (B, 1, hd)),
        BlockLayout("k_cache", (1, bt, hd), (B, T, hd)),
        BlockLayout("v_cache", (1, bt, hd), (B, T, hd)),
        BlockLayout("bias", (1, 1, bt), (B, 1, T)),
        BlockLayout("out", (1, 1, hd), (B, 1, hd)),
    ]
    if quant:
        layouts[3:3] = [
            BlockLayout("k_scale", (1, bt, h), (B, T, h)),
            BlockLayout("v_scale", (1, bt, h), (B, T, h)),
        ]
    return layouts


def slot_decode_layout(
    n_slots: int, T: int, h: int, d: int, quant: bool, block_t: Optional[int] = None
) -> list:
    """Block layouts of the slot-based continuous-batching decode step
    (trlx_tpu.engine): identical to ``decode_block_layout`` with the batch
    axis reinterpreted as the fixed slot axis. This is the one-compiled-
    program contract — the kernel's masked tail block plus the per-slot bias
    row already handle RAGGED cache lengths, so slots at mixed sequence
    lengths share one decode program; only (n_slots, T, h, d, quant) are
    shape keys, per-slot lengths are data."""
    return decode_block_layout(n_slots, T, h, d, quant, block_t=block_t)


def spec_verify_layout(
    n_slots: int,
    T: int,
    h: int,
    d: int,
    spec_k: int,
    quant: bool,
    block_t: Optional[int] = None,
) -> list:
    """Block layouts of the speculative multi-token verify step
    (trlx_tpu.engine spec decode): every slot runs the big model over a
    [spec_k]-token draft window at its own ragged frontier, so q/out grow a
    window axis next to the slot axis while the cache-resident operands stay
    the slot-decode buffers. The cache T axis carries the spec_k-1 scratch
    tail (see RolloutEngine.cache_len) — callers pass the POST-tail T so the
    legality verdict matches the buffers that actually lower. The flash
    decode kernel stays single-token; this layout is what the einsum verify
    path would hand a future multi-token kernel, and the legality probe in
    decode_attention.spec_verify_supported consumes it today so GL006 and
    the kernel gate share one source of truth."""
    from trlx_tpu.ops.decode_attention import pick_t_block

    bt = pick_t_block(T) if block_t is None else block_t
    layouts = [
        BlockLayout("q", (1, spec_k, h, d), (n_slots, spec_k, h, d)),
        BlockLayout("k_cache", (1, bt, h, d), (n_slots, T, h, d)),
        BlockLayout("v_cache", (1, bt, h, d), (n_slots, T, h, d)),
        BlockLayout("bias", (1, spec_k, bt), (n_slots, spec_k, T)),
        BlockLayout("out", (1, spec_k, h, d), (n_slots, spec_k, h, d)),
    ]
    if quant:
        layouts[3:3] = [
            BlockLayout("k_scale", (1, h, bt), (n_slots, h, T)),
            BlockLayout("v_scale", (1, h, bt), (n_slots, h, T)),
        ]
    return layouts


def paged_decode_layout(
    n_slots: int,
    n_blocks: int,
    block_size: int,
    blocks_per_slot: int,
    h: int,
    d: int,
    quant: bool,
) -> list:
    """Block layouts of the block-table-indirect paged decode step
    (trlx_tpu.ops.decode_attention.paged_decode_attention): the KV cache is
    ONE shared pool ``[n_blocks, block_size, h, d]`` — viewed, like the
    fixed cache in ``decode_block_layout``, as ``[n_blocks, block_size,
    h*d]`` — and each slot walks its own ``blocks_per_slot`` virtual blocks
    through a per-slot block table, so the grid is (slot, virtual-block) and
    the K/V/scale BlockSpec index maps read the scalar-prefetched table —
    ``(table[s, it], 0, 0)`` — to fetch each slot's physical block. Pool and
    scale blocks are full in their last two dims (tile-legal by
    construction); the bias row covers the slot's VIRTUAL address space
    ``[n_slots, 1, blocks_per_slot * block_size]`` in block_size-wide tiles
    — the one operand whose lane dim is a strict tile, so kernel legality
    requires ``block_size % 128 == 0`` (or a single-block table). The
    routing gate (decode_attention.paged_decode_supported) consumes this
    SAME description, so GL006 provenance and the kernel gate share one
    source of truth."""
    t_virt = blocks_per_slot * block_size
    hd = h * d
    layouts = [
        BlockLayout("q", (1, 1, hd), (n_slots, 1, hd)),
        BlockLayout("k_pool", (1, block_size, hd), (n_blocks, block_size, hd)),
        BlockLayout("v_pool", (1, block_size, hd), (n_blocks, block_size, hd)),
        BlockLayout("bias", (1, 1, block_size), (n_slots, 1, t_virt)),
        BlockLayout("out", (1, 1, hd), (n_slots, 1, hd)),
    ]
    if quant:
        layouts[3:3] = [
            BlockLayout("k_scale", (1, block_size, h), (n_blocks, block_size, h)),
            BlockLayout("v_scale", (1, block_size, h), (n_blocks, block_size, h)),
        ]
    return layouts


def flash_block_layout(BH: int, T: int, D: int, blocks) -> list:
    """The flash-attention kernels' block layouts (see
    trlx_tpu.ops.flash_attention `_fwd` and `_flash_lse_bwd`; `blocks` is its
    FlashBlocks: block, major, chunk). A grid step owns a `block` of rows of
    one side (q, o, dq in the forward and dq; k, v, dk, dv in dk/dv) and
    holds `major` rows of the other side resident; both sides are [BH, T, D],
    so the two shapes cover the three kernels. The block side's row vectors
    (lse, delta, the key bias of dk/dv) are [BH, 1, T] in `block`-wide tiles;
    the resident side's (the key bias of the forward and dq; lse and delta of
    dk/dv) are [BH, T / chunk, 1, chunk], so a loop step's row is an index on
    an untiled dimension and the last two block dims are always full."""
    block, major, chunk = blocks
    return [
        BlockLayout("block side", (1, block, D), (BH, T, D)),
        BlockLayout("resident side", (1, major, D), (BH, T, D)),
        BlockLayout("block rows", (1, 1, block), (BH, 1, T)),
        BlockLayout("resident rows", (1, major // chunk, 1, chunk), (BH, T // chunk, 1, chunk)),
    ]


def fused_logprob_block_layout(
    N: int, D: int, V: int, bn: int, bv: int, tied: bool, has_bias: bool
) -> list:
    """The fused vocab-projection/logprob kernel's forward block layouts (see
    trlx_tpu.ops.fused_logprob: grid (N-blocks, V-blocks), the hidden block
    carries the full [D] model axis, the weight streams in bv-wide vocab
    tiles, labels/outputs are [N, 1] columns whose width-1 last dim equals
    the array dim — legal without lane tiling). `tied` flips the weight
    between the untied lm_head kernel [D, V] and the embedding table [V, D].
    The V axis may be ragged (GPT-2/J vocabs are not 128-divisible): the
    bv-divisible tail block is partial and masked in-kernel, exactly like
    the flash-decode T tail."""
    w = BlockLayout("w", (bv, D), (V, D)) if tied else BlockLayout("w", (D, bv), (D, V))
    layouts = [
        BlockLayout("x", (bn, D), (N, D)),
        w,
        BlockLayout("labels", (bn, 1), (N, 1)),
        BlockLayout("logprob", (bn, 1), (N, 1)),
        BlockLayout("lse", (bn, 1), (N, 1)),
        BlockLayout("entropy", (bn, 1), (N, 1)),
    ]
    if has_bias:
        layouts.insert(2, BlockLayout("bias", (1, bv), (1, V)))
    return layouts

"""Static Mosaic tile-legality validator for Pallas BlockSpecs.

The Mosaic TPU lowering requires that the LAST TWO dimensions of every
BlockSpec block shape are divisible by (8, 128) — or equal the respective
dimensions of the overall array (a "full" block needs no tiling). Violations
only surface at lowering time for a TPU, as a mid-run ValueError (a per-head
`(1, 1, d)` query block once killed a flagship bench run that way).

This module makes the rule checkable on CPU, without lowering anything:
kernel modules describe their real block layouts (`flash_block_layout`,
`fused_logprob_block_layout`) and tier-1 tests assert legality at the real
bench shapes. A routing gate runs `layout_issues` first, so a tile-illegal
shape is refused (and takes the XLA path) by a stated rule; what passes the
rule must then lower (`require_lowering`) — the tile rule is only the first
of Mosaic's checks, and a kernel that passes it and still cannot lower is a
defect to surface, not a route to take quietly.
"""

from typing import NamedTuple, Sequence, Tuple

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
    bv-divisible tail block is partial and masked in-kernel."""
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


def fused_logprob_vmem_bytes(
    kind: str, D: int, bn: int, bv: int, x_itemsize: int, w_itemsize: int, has_bias: bool
) -> int:
    """VMEM one grid step of the fused log-prob kernel `kind` ("fwd", "dx",
    "dw") holds at row tile `bn` and vocabulary tile `bv`: every pipelined
    block twice (the pipeline's double buffer), the scratch accumulators
    once, and the compiler's temporaries: the [bn, bv] float32 score tile and
    its elementwise companions (iota, mask, p, p·s, the cotangent), the weight
    tile cast to the rows' dtype where they differ, the product's float32
    result. An [n, 1] column occupies [n, 128] lanes and a [1, bv] bias 8
    sublanes. `head_tiles` picks tiles against this and the kernels ask the
    compiler for it as their scoped-VMEM limit, so it errs high."""
    column = bn * LANE * 4
    x_block, w_block = bn * D * x_itemsize, D * bv * w_itemsize
    bias = SUBLANE * bv * 4 if has_bias else 0
    score = bn * bv * 4
    cast = D * bv * x_itemsize if x_itemsize != w_itemsize else 0
    if kind == "fwd":  # x, w, bias, labels in; three columns out; m, l, r, label score
        piped = x_block + w_block + bias + 4 * column
        scratch = 4 * column
        temps = 6 * score + cast
    elif kind == "dx":  # + five more columns in, the dx block out; the tail-masked weight, its iota
        piped = 2 * x_block + w_block + bias + 6 * column
        scratch = bn * D * 4
        temps = 8 * score + cast + D * bv * (w_itemsize + 4) + bn * D * 4
    elif kind == "dw":  # the dW tile (and db) out
        piped = x_block + 2 * (w_block + bias) + 6 * column
        scratch = D * bv * 4 + bias
        temps = 8 * score + cast + D * bv * 4
    else:
        raise ValueError(f"unknown fused log-prob kernel {kind!r}")
    return 2 * piped + scratch + temps

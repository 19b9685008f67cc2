"""Device time under the program's own scope names.

The program names its work where it happens, with ``jax.named_scope``; the
compiler writes the name stack into every instruction's
``metadata={op_name="jit(train_step)/transformer/h_3/attn/flash_attn/..."}``.
A profiler trace names a device event by its instruction (``%fusion.79``) and
``jax.profiler.ProfileData`` hands out no metadata, so a reader of the trace
cannot see the scope. This module makes the missing table, *compiled
instruction -> scope*, for exactly the programs that ran while somebody was
tracing:

- ``SCOPES`` is the vocabulary: every ``jax.named_scope("<literal>")`` under
  ``trlx_tpu/`` is one of these names and each name has a site
  (tests/test_device_scopes.py walks the source).
- ``wrap(fn)`` is the proxy every jitted program of the trainers is
  dispatched through (``trainer/base.py _wrap_monitored``,
  ``ops/generate.py make_generate_fn``). A dispatch asks
  ``TraceAnnotation.is_enabled()``, which is true exactly while a profiler
  session is open, whoever opened it (``train.profile_dir``, an incident
  capture, a benchmark harness). Only then it notes ``(fn, abstract
  arguments)`` once a signature (the shapes of its arguments: ``note``),
  before the call (donated inputs are still alive), and calls through. No ``lower()``, no text, no file on the loop's
  thread; with no session ever open: one Python frame and one static call.
- ``tables()`` does the rest on demand, memoised: ``fn.lower(*avals)
  .compile().as_text()`` of each noted program, served from JAX's in-memory
  caches because the abstract arguments repeat what the call's arguments
  were (a sharding only where the array was committed, the weak type kept),
  and ``scope_table`` over the text.
- ``flush()`` rides ``spans.flush()``: once a session has been seen AND is
  closed again it writes ``<checkpoint_dir>/device_scopes.json`` (process 0),
  so the capture never lands inside anybody's traced window. ``report.py
  --xplane`` joins that file with the trace ("Device time by scope").

A table tells what the *executable* holds. One read from a persistent compile
cache was compiled from the source that first produced its key, and JAX
leaves names out of the key: after a change of scopes, a traced run wants a
fresh cache directory (RUNBOOK section 8).
"""

import json
import os
import re
import threading

import jax
from jax.profiler import TraceAnnotation

__all__ = ["SCOPES", "SCOPES_FILENAME", "scope_table", "wrap", "note", "tables", "configure", "flush", "write"]

SCOPES_FILENAME = "device_scopes.json"

SCOPES = (
    "prefill",  # generate: the forward over the prompts that fills the cache
    "decode_loop",  # generate: the while loop of decode steps, all of it
    "sample",  # a decode step's logits processor and the draw of the next token
    "embed",  # the token (and learned position) table lookup; backward: the scatter into the table
    "attn_window",  # an attention layer's score/softmax/value part, window layer
    "attn_full",  # the same, full-span layer
    "qk_norm",  # the RMSNorm over each query and key head
    "rotary",  # the rotary signal on q and k (models/lm.py apply_rotary): the pair swap and the float32 multiply-adds; inside `cca_mix` and `mla_*` too
    "flash_attn",  # a flash_attention call site: pad, [b,T,h,d] <-> [bh,T,d] relayouts, kernels, un-pad
    "kv_read",  # a read of the KV cache: the ranged switch, its branches, the int8 scale work
    "mla_absorbed",  # latent attention's read of the latent cache (decode)
    "mla_unabsorbed",  # latent attention over a block's own latents (train, score, prefill)
    "cca_mix",  # attention "cca": projections, both causal convolutions, the q-k mean, the value shift, L2 norm and rotary
    "residual_scaling",  # a block's residual sums under learned scales and biases on both operands
    "moe_router",  # expert layer: router scores (a product, or the MLP router and its carried state), top-k, slot placement
    "moe_experts",  # expert layer: everything the held experts do to their tokens
    "moe_grouped_ffn",  # the grouped products of the held experts
    "moe_shared",  # the shared expert
    "ssm_in",  # state-space mixer: input projection
    "ssm_conv",  # its causal convolution
    "ssm_scan",  # its chunked scan (or one recurrence step)
    "ssm_gate",  # its gated norm
    "ssm_out",  # its output projection
    "kda_in",  # gated delta-rule mixer: the q/k/v, decay, strength and gate projections
    "kda_conv",  # its three causal convolutions and the L2 norm of q and k
    "kda_scan",  # its chunked pass (or one recurrence step)
    "kda_gate",  # its gated per-head norm
    "kda_out",  # its output projection
    "lightning_in",  # lightning mixer: the q/k/v and gate projections, the padding's mask on v
    "lightning_scan",  # its chunked pass (or one recurrence step)
    "lightning_gate",  # its output norm over all channels and the sigmoid gate
    "lightning_out",  # its output projection
    "sparse_select",  # attention "sparse": compressed keys, the scores over them, the pooled block scores and the choice
    "sparse_attn",  # attention "sparse": attention over the chosen blocks (many tokens: the masked pass; a decode step: the gather)
    "dsa_index",  # an indexed latent layer (models/indexer.py): the index queries, keys and weights, the index scores
    "dsa_select",  # its choice: the k-th largest score and the mask (many tokens), the top-k and the gather's slots (a decode step)
    "dsa_attn",  # latent attention over the chosen keys (many tokens: the masked unabsorbed pass; a decode step: the gathered absorbed read)
    "loop_norm",  # a looped stack: the final norm at the end of every loop, the next loop's input
    "exit_gate",  # a looped stack: the exit gate on each loop's output and the exit distribution
    "lm_head",  # the vocabulary head in every form: fused log-probs, dense logits, ILQL's Q heads
    "loss",  # the RL loss terms and GAE inside the train step
    "optimizer",  # optax update, gradient norm and clip, the non-finite guard's select
)
_NAMES = frozenset(SCOPES)

# Programs noted per process; a run dispatches a dozen.
MAX_NOTED = 64

_LOCK = threading.Lock()
_NOTED = {}  # (id(fn), the arguments' shapes) -> [fn, args avals, kwargs avals, table or None]
_STATE = {"dir": None, "seen": False}


# ------------------------------------------------------------------ the table


def _scope_path(op_name, memo):
    """(chain of SCOPES names on the path, outermost first, joined by "/";
    pass) of one ``op_name``. A name-stack component is a scope's name, bare
    or wrapped by the transforms it went through (``jvp(loss)``,
    ``transpose(jvp(ssm_scan))``); ``jit(f)`` is a function, not a scope."""
    got = memo.get(op_name)
    if got is None:
        chain = []
        for part in op_name.split(";", 1)[0].split("/"):  # a merged instruction lists its members': the first
            while part.endswith(")"):
                head, _, rest = part.partition("(")
                if head in ("jit", "pjit") or not rest:
                    break
                part = rest[:-1]
            if part in _NAMES and (not chain or chain[-1] != part):
                chain.append(part)
        if "rematted_computation" in op_name:
            which = "recompute"
        elif "transpose(" in op_name:
            which = "bwd"
        else:
            which = "fwd"
        got = memo[op_name] = ["/".join(chain), which]
    return got


_CALLED = re.compile(r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def scope_table(hlo_text):
    """``{"module": "jit_train_step", "ops": {"fusion.79": ["ssm_scan", "bwd"],
    ...}}`` from a compiled module's text (``compiled.as_text()``).

    Key: the instruction's name without its ``%``, as a trace event's name
    starts. Value: the SCOPES names on the instruction's ``op_name`` path,
    outermost first and joined by "/" (``"decode_loop/kv_read"``; the last is
    the innermost; ``""`` where there is none), and its pass, read off the
    name stack JAX writes itself: ``recompute`` under a
    ``rematted_computation``, else ``bwd`` under a ``transpose(``, else
    ``fwd``. A fusion carries the ``op_name`` the compiler gave it: its root's
    (multi-output: its first named member's); the instructions inside fused
    computations are left out, no device event names them. A ``while``,
    ``conditional`` or ``call`` gets its own scope like any other, **and hands
    it down**: an instruction in a computation it calls whose own path holds
    no scope (the compiler re-creates some with a bare ``op_name``, as the
    grouped products' ``ragged-dot-none`` kernels, or with none, as the waits
    for a prefetched weight inside a loop) takes the caller's, at any depth.
    One that no container covers and whose ``op_name`` holds no name stack at
    all (the grouped products of a call that goes in ONE pass sit in the entry
    computation; so does a relayout copy of a weight, named after its
    parameter) takes the scope of what it reads, else of what reads it: the
    nearest operand with a scope, looking through instructions without
    metadata (a tuple's element, a copy's two halves, a bitcast), then the
    nearest user the same way. Operands first: a weight gradient's one user
    is the optimizer, its operands are the expert layer's own. It keeps its
    own pass. An instruction of the entry computation without ``op_name``
    (a copy the compiler added) has no entry: a reader counts it as
    unattributed."""
    module, ops, memo, fused, computation = "", {}, {}, False, ""
    inside, caller, bare = {}, {}, []  # instruction -> its computation; computation -> who calls it; no scope of their own
    reads, read_by, stackless = {}, {}, []  # instruction -> its operands; -> its users; op_name without a name stack
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line[len("HloModule "):].split(",", 1)[0].strip()
            continue
        if line.endswith("{") and " -> " in line and " = " not in line:  # a computation's header
            computation = line.split(None, 2)[1 if line.startswith("ENTRY") else 0].lstrip("%")
            fused = computation.startswith("fused_computation")
            continue
        eq = line.find(" = ")
        if fused or eq < 0:
            continue
        name = line[:eq].split()[-1].lstrip("%")
        for one, several in _CALLED.findall(line, eq):
            for called in (one,) if one else several.replace("%", "").split(", "):
                caller.setdefault(called, name)
        inside[name] = computation
        reads[name] = operands = _operands(line, eq + 3)
        for operand in operands:
            read_by.setdefault(operand, []).append(name)
        at = line.find('op_name="', eq)
        if at >= 0:
            op_name = line[at + 9: line.find('"', at + 9)]
            ops[name] = _scope_path(op_name, memo)
            if "/" not in op_name and operands:  # not a parameter, which is named after its argument
                stackless.append(name)
        if at < 0 or not ops[name][0]:
            bare.append(name)
    for name in bare:
        above = caller.get(inside[name])
        while above is not None and not (above in ops and ops[above][0]):
            above = caller.get(inside[above])
        if above is not None:
            ops[name] = [ops[above][0], ops[name][1] if name in ops else ops[above][1]]
    for _ in range(2):  # the second round for one between two of its kind: the grouped products' metadata call
        for name, chain in [(name, _nearest_scope(name, reads, ops) or _nearest_scope(name, read_by, ops))
                            for name in stackless if not ops[name][0]]:  # a round's are all found before any is written
            if chain:
                ops[name] = [chain, ops[name][1]]
    return {"module": module, "ops": ops}


_OPCODE = re.compile(r" [a-z][\w\-]*\(")
_OPERAND = re.compile(r"%?([A-Za-z_][\w.\-]*)\s*(?:,|$)")


def _operands(line, start):
    """Names of an instruction's operands, in order: what stands between the
    parentheses after the opcode, each operand's last word (a long-form text
    puts its shape before it), with or without ``%``."""
    opcode = _OPCODE.search(line, start)
    if opcode is None:
        return ()
    depth, first = 0, opcode.end()
    for paren in re.finditer(r"[()]", line[first - 1:]):
        depth += 1 if paren.group() == "(" else -1
        if not depth:
            inner = re.sub(r"/\*.*?\*/", "", line[first: first - 1 + paren.start()])
            return tuple(_OPERAND.findall(inner))
    return ()


def _nearest_scope(name, edges, ops, reach=4):
    """The scope chain of the nearest instruction along `edges` that has one,
    breadth first and in the text's order, looking through instructions with
    no entry (no metadata), at most `reach` of them deep."""
    layer, seen = [name], {name}
    for _ in range(reach):
        layer = list(dict.fromkeys(n for of in layer for n in edges.get(of, ()) if n not in seen))
        seen.update(layer)
        for n in layer:
            if n in ops and ops[n][0]:
                return ops[n][0]
        layer = [n for n in layer if n not in ops]
    return ""


# ---------------------------------------------------------------- the capture


def _abstract(leaf):
    """What ``lower`` needs of one argument leaf to find the executable the
    call ran: shape, dtype and weak type, and the sharding only of an array
    that was committed to one (an uncommitted array's placement is the
    compiler's to choose, and naming it makes another program)."""
    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=leaf.sharding if leaf.committed else None,
                                    weak_type=leaf.weak_type)
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):  # numpy
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf


def note(fn, args, kwargs):
    """Called while a profiler session is open: remember ``fn`` and the abstract
    form of this call's arguments, once a signature. A traced
    window must read what it would without this, so a dispatch pays for the
    shapes of its ARGUMENTS only (an array's own, a pytree's kind: the train
    state has hundreds of leaves and never changes shape); the leaves are
    walked when that is new. Two calls that differ only inside a pytree
    argument are one signature here: the first one's."""
    _STATE["seen"] = True
    key = (id(fn), tuple([(x.shape, x.dtype) if hasattr(x, "shape") else type(x) for x in (*args, *kwargs.values())]))
    if key in _NOTED or len(_NOTED) >= MAX_NOTED:
        return
    a, k = jax.tree_util.tree_map(_abstract, (args, kwargs))
    with _LOCK:
        _NOTED.setdefault(key, [fn, a, k, None])


class _Noting:
    """A jitted program, callable as it was; notes itself while traced."""

    __slots__ = ("_fn",)

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *args, **kwargs):
        if TraceAnnotation.is_enabled():
            note(self._fn, args, kwargs)
        return self._fn(*args, **kwargs)

    def __getattr__(self, item):  # .lower, ._cache_size, a closure's counters
        return getattr(self._fn, item)


def wrap(fn):
    return _Noting(fn)


def tables():
    """One ``scope_table`` per noted program, in the order they were noted.
    Built on first demand and kept (a compiled GPT-J train step's text is
    3.5 MB, half a second to print; its table 2,010 entries). Two noted programs may share a module name (one function
    at two shapes); a reader takes the first entry an instruction has."""
    with _LOCK:
        noted = list(_NOTED.values())
    out = []
    for entry in noted:
        if entry[3] is None:
            fn, args, kwargs, _ = entry
            entry[3] = scope_table(fn.lower(*args, **kwargs).compile().as_text())
        out.append(entry[3])
    return out


def configure(directory):
    """A trainer's start: where ``flush`` writes, and a clean slate (the
    programs of a trainer before this one in the process are not this run's)."""
    with _LOCK:
        _NOTED.clear()
    _STATE.update(dir=directory, seen=False)


def write(directory):
    """``<directory>/device_scopes.json``: ``{"programs": tables()}``, whole
    and atomically, from process 0."""
    if jax.process_index() != 0:
        return
    from trlx_tpu.resilience.checkpoint import atomic_write_text

    os.makedirs(directory, exist_ok=True)
    atomic_write_text(os.path.join(directory, SCOPES_FILENAME), json.dumps({"programs": tables()}))


def flush(closing=False):
    """From ``spans.flush()`` (an iteration boundary) and, ``closing``, from
    ``spans.shutdown()``: write the file if a session has been seen and is
    closed again, or the run is closing. Never kills the run it observes."""
    if not _STATE["seen"] or _STATE["dir"] is None or (TraceAnnotation.is_enabled() and not closing):
        return
    _STATE["seen"] = False
    try:
        write(_STATE["dir"])
    except Exception as e:  # noqa: BLE001 — a boundary that must keep running
        import warnings

        warnings.warn(f"device_scopes.json not written: {type(e).__name__}: {e}", stacklevel=2)

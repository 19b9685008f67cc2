"""The graftlint rules (GL001–GL007): repo conventions as machine checks.

Each rule encodes an invariant this codebase already paid for at runtime:

- GL001 is the PR 5 lesson — two threads dispatching collective-bearing
  jitted programs concurrently interleave per-device enqueue order and
  deadlock XLA's cross-program rendezvous, so every dispatch of a registered
  wrapper must be lexically under ``_dispatch_lock``.
- GL002/GL003 guard jit semantics (donated buffers die at dispatch; host
  side effects inside traced bodies run at trace time only).
- GL004 is the resilience contract: a raw host collective with a dead peer
  hangs forever — ``collective_guard`` turns that into a deadline'd abort.
- GL005 enforces the serial-path-byte-identical knob convention plus "every
  knob you read must be declared" (typo'd getattr fallbacks silently
  disable features).
- GL006 is the PR 3 lesson: Mosaic tile legality has one source of truth
  (ops/tiling.py layout factories); ad-hoc ``pl.BlockSpec`` shapes drift.
- GL007 is the PR 9 lesson: metric keys that do not survive
  ``sanitize_metric_name`` (or that collide after it) corrupt the
  Prometheus export.

Everything here is stdlib ``ast`` over source text — no imports of the
checked modules, no jax.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.core import Finding, Module

# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a pure Name/Attribute chain, else None."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


def last_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def own_statements(fn: ast.AST) -> Iterator[ast.stmt]:
    """Statements of ``fn`` in source order, descending into compound
    statements but NOT into nested function/class scopes."""

    def walk(body: Sequence[ast.stmt]) -> Iterator[ast.stmt]:
        for stmt in body:
            yield stmt
            if isinstance(
                stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue
            for field in ("body", "orelse", "finalbody"):
                yield from walk(getattr(stmt, field, []) or [])
            for handler in getattr(stmt, "handlers", []) or []:
                yield from walk(handler.body)

    yield from walk(fn.body)


def walk_no_nested_scopes(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk that does not descend into nested function/class defs."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        cur = stack.pop()
        yield cur
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(cur))


_STMT_BODY_FIELDS = {"body", "orelse", "finalbody", "handlers"}


def stmt_header_nodes(stmt: ast.stmt) -> Iterator[ast.AST]:
    """The expression children of ``stmt`` excluding nested statement blocks
    (those are visited as their own statements by :func:`own_statements`), so
    each expression is processed exactly once in source order."""
    for field, value in ast.iter_fields(stmt):
        if field in _STMT_BODY_FIELDS:
            continue
        nodes = value if isinstance(value, list) else [value]
        for n in nodes:
            if isinstance(n, ast.AST):
                yield n
                yield from walk_no_nested_scopes(n)


# --------------------------------------------------------------------------
# GL001 — dispatch-lock
# --------------------------------------------------------------------------

#: Registered jitted-program wrapper names. Calling any of these dispatches a
#: compiled (usually collective-bearing) program, so the call site must be
#: lexically inside a dispatch-lock context (PR 5: interleaved per-device
#: enqueue order deadlocks XLA's cross-program rendezvous).
DISPATCH_WRAPPERS = {
    "train_step",          # trainer/{ppo,ilql}.py build_train_step products
    "_generate_fn",        # rollout decode (ops/generate.make_generate_fn)
    "_generate_fused_fn",  # fused rollout decode+score
    "_rm_eval_fn",         # on-mesh RM eval scoring
    "_quantize_fn",        # int8 decode-weight requantization
    "_sync_fn",            # ILQL polyak target sync
    "_decode",             # engine decode_step program
    "_prefill",            # engine batched prefill program
}

#: Builders returning a jitted program that is immediately called:
#: ``self._score_fn_for(T)(args...)`` — the *outer* call dispatches.
DISPATCH_BUILDERS = {"_score_fn_for", "_score_fused_fn_for", "_score_rm_fn_for"}

#: Functions documented as only ever running with the dispatch lock already
#: held by their caller (none today; ROADMAP item 1 will grow this).
LOCK_HOLDING_FUNCS: Set[str] = set()


def _is_lock_withitem(item: ast.withitem) -> bool:
    e = item.context_expr
    if last_attr(e) == "_dispatch_lock":
        return True
    if isinstance(e, ast.Call) and last_attr(e.func) in {"_dispatch", "dispatch_lock"}:
        return True
    return False


def _under_dispatch_lock(module: Module, node: ast.AST) -> bool:
    for anc in module.ancestors(node):
        if isinstance(anc, ast.With) and any(
            _is_lock_withitem(i) for i in anc.items
        ):
            return True
        if (
            isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef))
            and anc.name in LOCK_HOLDING_FUNCS
        ):
            return True
    return False


def check_gl001(module: Module) -> Iterator[Finding]:
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        name = None
        func = node.func
        if last_attr(func) in DISPATCH_WRAPPERS:
            name = last_attr(func)
        elif isinstance(func, ast.Call) and last_attr(func.func) in DISPATCH_BUILDERS:
            name = f"{last_attr(func.func)}(...)"
        if name is None:
            continue
        if not _under_dispatch_lock(module, node):
            yield module.finding(
                "GL001",
                node,
                f"jitted program {name!r} dispatched outside a _dispatch_lock "
                "context (concurrent dispatch interleaves device queues and "
                "deadlocks XLA collectives — hold the lock or register the "
                "enclosing function as lock-holding)",
            )


# --------------------------------------------------------------------------
# GL002 — use-after-donate
# --------------------------------------------------------------------------

#: wrapper name → donated positional-argument indices, for wrappers whose
#: jax.jit(..., donate_argnums=...) definition lives in another module.
KNOWN_DONATING = {
    "train_step": (0,),
    "_sync_fn": (1,),
    "_decode": (1,),
    "_prefill": (1,),
}

_INT_TUPLE = (ast.Tuple, ast.List)


def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, int):
                return (kw.value.value,)
            if isinstance(kw.value, _INT_TUPLE):
                out = []
                for el in kw.value.elts:
                    if isinstance(el, ast.Constant) and isinstance(el.value, int):
                        out.append(el.value)
                return tuple(out)
    return None


def _discover_donating(module: Module) -> Dict[str, Tuple[int, ...]]:
    """Map assigned wrapper names to donated positions by scanning
    ``<target> = ...jax.jit(fn, donate_argnums=...)...`` assignments."""
    found = dict(KNOWN_DONATING)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = last_attr(node.targets[0])
        if target is None:
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Call) and last_attr(sub.func) == "jit":
                pos = _donate_positions(sub)
                if pos:
                    found[target] = pos
    return found


def _expr_key(node: ast.AST) -> Optional[str]:
    """A stable key for simple Name / self-attribute chains only."""
    d = dotted(node)
    return d


def check_gl002(module: Module) -> Iterator[Finding]:
    donating = _discover_donating(module)
    fns = [
        n
        for n in ast.walk(module.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for fn in fns:
        donated: Dict[str, Tuple[str, int]] = {}  # key → (wrapper, line)
        for stmt in own_statements(fn):
            # 1) reads of already-donated keys (args of the donating call
            #    itself were processed in the *previous* statement pass).
            if donated:
                for sub in stmt_header_nodes(stmt):
                    if not isinstance(sub, (ast.Name, ast.Attribute)):
                        continue
                    if not isinstance(getattr(sub, "ctx", None), ast.Load):
                        continue
                    key = _expr_key(sub)
                    if key in donated:
                        wrapper, line = donated[key]
                        yield module.finding(
                            "GL002",
                            sub,
                            f"{key!r} read after being donated to "
                            f"{wrapper!r} (line {line}); donated buffers are "
                            "deleted at dispatch — rebind the result or copy "
                            "before dispatch",
                        )
                        del donated[key]  # one finding per donation
            # 2) new donations in this statement.
            for sub in stmt_header_nodes(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                wrapper = None
                if last_attr(sub.func) in donating:
                    wrapper = last_attr(sub.func)
                elif (
                    isinstance(sub.func, ast.Call)
                    and last_attr(sub.func.func) in donating
                ):
                    wrapper = last_attr(sub.func.func)
                if wrapper is None:
                    continue
                for pos in donating.get(wrapper, ()):
                    if pos < len(sub.args):
                        key = _expr_key(sub.args[pos])
                        if key is not None:
                            donated[key] = (wrapper, sub.lineno)
            # 3) rebinds kill the donation record (covers the canonical
            #    ``self.state, stats = self.train_step(self.state, ...)``).
            kills: List[str] = []
            targets: List[ast.AST] = []
            if isinstance(stmt, ast.Assign):
                targets = list(stmt.targets)
            elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
                targets = [stmt.target]
            elif isinstance(stmt, ast.For):
                targets = [stmt.target]
            elif isinstance(stmt, ast.With):
                targets = [i.optional_vars for i in stmt.items if i.optional_vars]
            for t in targets:
                for el in ast.walk(t):
                    key = _expr_key(el)
                    if key is not None:
                        kills.append(key)
            for sub in stmt_header_nodes(stmt):
                if isinstance(sub, ast.NamedExpr):
                    key = _expr_key(sub.target)
                    if key is not None:
                        kills.append(key)
            for key in kills:
                for dkey in list(donated):
                    if dkey == key or dkey.startswith(key + "."):
                        del donated[dkey]


# --------------------------------------------------------------------------
# GL003 — trace purity
# --------------------------------------------------------------------------

#: tracing entry point (by trailing attribute) → positional indices of the
#: traced callables it receives.
_TRACING_ENTRIES = {
    "jit": (0,),
    "pallas_call": (0,),
    "scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "checkpoint": (0,),
    "remat": (0,),
}

_HOST_BUILTINS = {"print", "open", "input", "breakpoint"}
_HOST_MODULE_PREFIXES = (
    ("time",),
    ("logging",),
    ("random",),
    ("np", "random"),
    ("numpy", "random"),
)


def _banned_host_call(call: ast.Call) -> Optional[str]:
    func = call.func
    if isinstance(func, ast.Name) and func.id in _HOST_BUILTINS:
        return f"{func.id}()"
    d = dotted(func)
    if d is not None:
        parts = tuple(d.split("."))
        for prefix in _HOST_MODULE_PREFIXES:
            if parts[: len(prefix)] == prefix and len(parts) > len(prefix):
                return d
        if "tracker" in (p.lower() for p in parts[:-1]):
            return d  # Tracker emission from a traced body
    if isinstance(func, ast.Attribute) and func.attr == "item" and not call.args:
        return ".item()"
    return None


def _resolve_traced_bodies(module: Module) -> List[Tuple[ast.AST, str]]:
    """(traced function/lambda node, how it got traced) pairs."""
    by_name: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    out: List[Tuple[ast.AST, str]] = []
    seen: Set[int] = set()
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        entry = last_attr(node.func)
        if entry not in _TRACING_ENTRIES:
            continue
        for pos in _TRACING_ENTRIES[entry]:
            if pos >= len(node.args):
                continue
            arg = node.args[pos]
            # unwrap functools.partial(fn, ...)
            if isinstance(arg, ast.Call) and last_attr(arg.func) == "partial" and arg.args:
                arg = arg.args[0]
            if isinstance(arg, ast.Lambda):
                if id(arg) not in seen:
                    seen.add(id(arg))
                    out.append((arg, entry))
                continue
            name = last_attr(arg)
            for fn in by_name.get(name or "", []):
                if id(fn) not in seen:
                    seen.add(id(fn))
                    out.append((fn, entry))
    return out


def check_gl003(module: Module) -> Iterator[Finding]:
    for body, entry in _resolve_traced_bodies(module):
        for sub in ast.walk(body):
            if not isinstance(sub, ast.Call):
                continue
            banned = _banned_host_call(sub)
            if banned is not None:
                name = getattr(body, "name", "<lambda>")
                yield module.finding(
                    "GL003",
                    sub,
                    f"host side effect {banned!r} inside {entry}-traced body "
                    f"{name!r}: it runs at trace time only (once per novel "
                    "shape), never per step — hoist it to the host caller",
                )


# --------------------------------------------------------------------------
# GL004 — collective-guard
# --------------------------------------------------------------------------

#: raw host-side collectives: these block until every process participates,
#: so a dead peer hangs them forever unless a collective_guard deadline wraps
#: the call. (host_local_array_to_global_array is collective-free: exempt.)
RAW_COLLECTIVES = {
    "broadcast_one_to_all",
    "process_allgather",
    "sync_global_devices",
    "global_array_to_host_local_array",
}

#: the guard implementation itself may touch collectives freely.
GUARD_HOME = "resilience/distributed.py"


def _under_collective_guard(module: Module, node: ast.AST) -> bool:
    for anc in module.ancestors(node):
        if isinstance(anc, ast.With):
            for item in anc.items:
                e = item.context_expr
                if isinstance(e, ast.Call) and last_attr(e.func) == "collective_guard":
                    return True
    return False


def check_gl004(module: Module) -> Iterator[Finding]:
    if module.relpath.endswith(GUARD_HOME):
        return
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        name = last_attr(node.func)
        if name not in RAW_COLLECTIVES:
            continue
        if not _under_collective_guard(module, node):
            yield module.finding(
                "GL004",
                node,
                f"bare host collective {name!r}: a dead peer hangs this "
                "forever — wrap it in collective_guard(...) (or use the "
                "guarded helpers in parallel/mesh.py)",
            )


# --------------------------------------------------------------------------
# GL005 — knob defaults
# --------------------------------------------------------------------------

#: Fields that predate the off-by-default convention (baseline hyperparams
#: and deliberately-on safety defaults). Any NEW field with a truthy default
#: must either go here with a reviewed reason or default to off/0/False so
#: the serial path stays byte-identical when the knob is absent from a
#: config file.
BASELINE_TRUTHY_FIELDS = frozenset(
    {
        # ModelConfig
        "model_type", "num_layers_unfrozen", "dtype", "param_dtype",
        "remat_policy",
        # TrainConfig baseline hyperparams / deliberately-on safety nets
        "opt_betas", "checkpoint_interval", "eval_interval", "log_interval",
        "pipeline", "orchestrator", "project_name", "checkpoint_dir", "seed",
        "mesh", "loss_dtype", "grad_clip", "async_checkpointing",
        "nonfinite_guard", "max_bad_steps", "watchdog_patience",
        "watchdog_ema_alpha", "watchdog_warmup", "watchdog_lr_decay",
        "max_rollbacks", "reward_fn_retries", "reward_fn_backoff",
        "anomaly_window", "max_incidents", "health_warmup",
        "health_warn_streak", "health_crit_streak",
        # method configs: PPO/ILQL/softprompt hyperparameters
        "name", "ppo_epochs", "num_rollouts", "chunk_size", "init_kl_coef",
        "target", "horizon", "gamma", "lam", "cliprange", "cliprange_value",
        "vf_coef", "fused_rollout_stats", "score_queue_depth",
        "prefetch_depth", "prefill_batch", "engine_steps_per_sync",
        "tau", "cql_scale", "awac_scale", "alpha", "steps_for_target_q_sync",
        "betas", "two_qs", "n_soft_tokens", "initialize_from_vocab",
        # kv_block_size is a PARAMETER of the paged-KV feature, not its
        # toggle: it is only read when paged_kv (default False) is on, so
        # the serial path stays byte-identical with it truthy.
        "kv_block_size",
    }
)

_CONFIG_FILES = ("data/configs.py", "data/method_configs.py")

#: attributes that are API of the config objects, not knobs.
_CONFIG_API = {"to_dict", "from_dict", "replace", "__dict__", "name"}


def _is_off_default(node: Optional[ast.AST]) -> Optional[bool]:
    """True if the default keeps the feature off; None if undecidable."""
    if node is None:
        return None  # required field
    if isinstance(node, ast.Constant):
        return not bool(node.value)
    if isinstance(node, (ast.Tuple, ast.List, ast.Dict, ast.Set)):
        return not bool(getattr(node, "elts", None) or getattr(node, "keys", None))
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = node.operand
        if isinstance(inner, ast.Constant):
            return not bool(inner.value)
    if isinstance(node, ast.Call) and last_attr(node.func) == "field":
        for kw in node.keywords:
            if kw.arg == "default":
                return _is_off_default(kw.value)
            if kw.arg == "default_factory":
                if isinstance(kw.value, ast.Name) and kw.value.id in {
                    "dict", "list", "tuple", "set",
                }:
                    return True
                return None
    return None


def _config_fields_of(tree: ast.AST) -> Dict[str, List[Tuple[str, ast.AnnAssign]]]:
    """class name → [(field name, AnnAssign node)] for *Config dataclasses."""
    out: Dict[str, List[Tuple[str, ast.AnnAssign]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        decorated = any(last_attr(d) == "register_method" for d in node.decorator_list)
        if not (node.name.endswith("Config") or decorated):
            continue
        fields = []
        for st in node.body:
            if isinstance(st, ast.AnnAssign) and isinstance(st.target, ast.Name):
                fields.append((st.target.id, st))
        out[node.name] = fields
    return out


class _ConfigRegistry:
    """Declared train/method field names, anchored at the real repo files so
    fixture trees still validate reads against the live schema."""

    def __init__(self) -> None:
        self.train: Set[str] = set()
        self.method: Set[str] = set()
        here = os.path.dirname(os.path.abspath(__file__))
        data_dir = os.path.join(os.path.dirname(here), "data")
        for fname in ("configs.py", "method_configs.py"):
            path = os.path.join(data_dir, fname)
            if not os.path.exists(path):
                continue
            with open(path, "r", encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read())
                except SyntaxError:
                    continue
            self.add_tree(tree, fname)

    def add_tree(self, tree: ast.AST, fname: str) -> None:
        for cls, fields in _config_fields_of(tree).items():
            names = {n for n, _ in fields}
            if cls == "TrainConfig":
                self.train |= names
            elif fname.endswith("method_configs.py") or cls.startswith(
                ("PPO", "ILQL", "Method")
            ):
                self.method |= names


_REGISTRY: Optional[_ConfigRegistry] = None


def _registry() -> _ConfigRegistry:
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = _ConfigRegistry()
    return _REGISTRY


def _method_train_aliases(fn: ast.AST) -> Dict[str, str]:
    """local name → 'method'|'train' for ``m = <...>.method`` style aliases."""
    aliases: Dict[str, str] = {}
    for stmt in own_statements(fn):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            t = stmt.targets[0]
            if isinstance(t, ast.Name) and isinstance(stmt.value, ast.Attribute):
                if stmt.value.attr in {"method", "train"}:
                    aliases[t.id] = stmt.value.attr
    return aliases


def check_gl005(module: Module) -> Iterator[Finding]:
    registry = _registry()
    is_config_file = any(module.relpath.endswith(s) for s in _CONFIG_FILES)
    if is_config_file:
        # definition-site check: new knobs must default to off/0/False.
        registry.add_tree(module.tree, module.relpath)
        for cls, fields in _config_fields_of(module.tree).items():
            for fname, st in fields:
                off = _is_off_default(st.value)
                if off is False and fname not in BASELINE_TRUTHY_FIELDS:
                    yield module.finding(
                        "GL005",
                        st,
                        f"{cls}.{fname} defaults ON: feature knobs must "
                        "default to off/0/False so the serial path stays "
                        "byte-identical (add to BASELINE_TRUTHY_FIELDS only "
                        "with a reviewed reason)",
                    )
        return

    declared = {"method": registry.method, "train": registry.train}
    alias_by_fn = {
        id(fn): _method_train_aliases(fn)
        for fn in ast.walk(module.tree)
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
    }

    def enclosing_aliases(node: ast.AST) -> Dict[str, str]:
        for anc in module.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return alias_by_fn.get(id(anc), {})
        return {}

    for node in ast.walk(module.tree):
        # direct reads: <...>.method.X / <...>.train.X
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Attribute):
            kind = node.value.attr
            if kind in declared and node.attr not in _CONFIG_API:
                if node.attr not in declared[kind]:
                    yield module.finding(
                        "GL005",
                        node,
                        f"config read '.{kind}.{node.attr}' has no declared "
                        f"field in the {kind} config schema (undeclared "
                        "knobs read via getattr fallbacks silently disable "
                        "features)",
                    )
        # getattr(<alias-or-.method>, "X", default)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "getattr"
            and len(node.args) >= 2
        ):
            obj, attr_node = node.args[0], node.args[1]
            attr = const_str(attr_node)
            if attr is None or attr in _CONFIG_API:
                continue
            kind = None
            if isinstance(obj, ast.Attribute) and obj.attr in declared:
                kind = obj.attr
            elif isinstance(obj, ast.Name):
                kind = enclosing_aliases(node).get(obj.id)
            if kind is not None and attr not in declared[kind]:
                yield module.finding(
                    "GL005",
                    node,
                    f"getattr read of undeclared {kind} knob {attr!r}: "
                    "declare it in the config schema (with an off default) "
                    "instead of a silent fallback",
                )


# --------------------------------------------------------------------------
# GL006 — tiling provenance
# --------------------------------------------------------------------------

TILING_HOME = "ops/tiling.py"
TILING_FACTORIES = {
    "flash_block_layout",
    "fused_logprob_block_layout",
    "check_layout",
    "block_tile_issues",
}


def _references_tiling(module: Module) -> bool:
    for node in ast.walk(module.tree):
        if isinstance(node, ast.ImportFrom) and node.module and "tiling" in node.module:
            if any(a.name in TILING_FACTORIES for a in node.names):
                return True
        if last_attr(node) in TILING_FACTORIES and isinstance(
            node, (ast.Name, ast.Attribute)
        ):
            return True
    return False


def check_gl006(module: Module) -> Iterator[Finding]:
    rel = module.relpath
    if "ops/" not in rel or rel.endswith(TILING_HOME):
        return
    has_provenance = _references_tiling(module)
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call) and last_attr(node.func) == "BlockSpec":
            if not has_provenance:
                yield module.finding(
                    "GL006",
                    node,
                    "pl.BlockSpec built in ops/ without referencing an "
                    "ops/tiling.py layout factory (decode/flash/fused "
                    "layouts are the single source of tile legality — "
                    "derive or validate shapes through them; PR 3's Mosaic "
                    "tile-rule crash is the failure mode)",
                )


# --------------------------------------------------------------------------
# GL007 — metric-name conformance (global: collisions are cross-file)
# --------------------------------------------------------------------------

_ILLEGAL = re.compile(r"[^a-zA-Z0-9_:]")
#: the repo's namespacing characters, which sanitize_metric_name folds to _.
_CANONICAL = re.compile(r"[/.\-]")


def _sanitize(name: str) -> str:
    """Mirror observability/export.sanitize_metric_name with stdlib re only
    (tests assert parity so the two cannot drift)."""
    out = _ILLEGAL.sub("_", name)
    if not out or not (out[0].isalpha() or out[0] in "_:"):
        out = "_" + out
    return out


def _canonical(name: str) -> str:
    out = _CANONICAL.sub("_", name)
    if not out or not (out[0].isalpha() or out[0] in "_:"):
        out = "_" + out
    return out


def _metric_key_sites(module: Module) -> Iterator[Tuple[str, ast.AST]]:
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call):
            attr = last_attr(node.func)
            if attr in {"log_histogram", "log_table"} and node.args:
                key = const_str(node.args[0])
                if key is not None:
                    yield key, node.args[0]
            if attr == "log" and node.args and isinstance(node.args[0], ast.Dict):
                for k in node.args[0].keys:
                    key = const_str(k) if k is not None else None
                    if key is not None:
                        yield key, k
        # namespaced literal keys anywhere a dict is built or stored into:
        # these flow into stats/gauge dicts that reach the Tracker/exporter.
        if isinstance(node, ast.Dict):
            for k in node.keys:
                key = const_str(k) if k is not None else None
                if key is not None and "/" in key:
                    yield key, k
        if isinstance(node, ast.Subscript) and isinstance(
            getattr(node, "ctx", None), ast.Store
        ):
            key = const_str(node.slice)
            if key is not None and "/" in key:
                yield key, node


def check_gl007(modules: Sequence[Module]) -> Iterator[Finding]:
    by_sanitized: Dict[str, Dict[str, Tuple[Module, ast.AST]]] = {}
    for module in modules:
        for key, node in _metric_key_sites(module):
            san = _sanitize(key)
            if san != _canonical(key):
                yield module.finding(
                    "GL007",
                    node,
                    f"metric key {key!r} does not survive "
                    f"sanitize_metric_name cleanly (becomes {san!r}): use "
                    "only [a-zA-Z0-9_:] plus '/' namespacing",
                )
                continue
            by_sanitized.setdefault(san, {}).setdefault(key, (module, node))
    for san, variants in sorted(by_sanitized.items()):
        if len(variants) > 1:
            keys = sorted(variants)
            for key in keys:
                module, node = variants[key]
                others = [k for k in keys if k != key]
                yield module.finding(
                    "GL007",
                    node,
                    f"metric key {key!r} collides with {others!r} after "
                    f"sanitize_metric_name (both export as {san!r}) — the "
                    "PR 9 exporter keeps only the last writer",
                )


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

PER_MODULE_RULES = [
    ("GL001", check_gl001),
    ("GL002", check_gl002),
    ("GL003", check_gl003),
    ("GL004", check_gl004),
    ("GL005", check_gl005),
    ("GL006", check_gl006),
]

GLOBAL_RULES = [
    ("GL007", check_gl007),
]

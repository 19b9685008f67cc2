"""Runtime dispatch/donation sanitizer, armed by ``TRLX_TPU_SANITIZE``.

The static pass (trlx_tpu/analysis, GL001/GL002) proves the *lexical*
discipline; this module checks the *dynamic* half at runtime when armed:

    TRLX_TPU_SANITIZE=dispatch,donation python -m pytest tests/...

- ``dispatch``: every registered jitted-program wrapper asserts dispatch-lock
  ownership at call time whenever another ``trlx-*`` worker thread is alive
  (the PR 5 hazard: two threads enqueueing programs concurrently interleave
  per-device order and deadlock XLA's cross-program rendezvous). Violations
  raise :class:`DispatchLockViolation` naming the program and thread instead
  of hanging a fleet.
- ``donation``: snapshot/donation handoff points mark donated pytrees
  (:func:`mark_donated`); any later host read that flows through a
  :func:`check_host_read` checkpoint raises :class:`DonatedBufferRead`
  naming the donation site — instead of jax's anonymous
  "Array has been deleted" somewhere downstream.
- ``race``: an Eraser-style per-field lockset tracker (the runtime dual of
  GL008). Locks built through :func:`make_lock` / :func:`make_condition` /
  :func:`make_dispatch_lock` register in a thread-local held-lock set;
  declared hot shared fields (producer flags, engine slot state,
  exporter gauges, heartbeat state) report each access through
  :func:`race_access`. Once a field has been touched by two threads with at
  least one write, the intersection of held-lock sets must stay non-empty —
  when it empties, :class:`RaceViolation` names BOTH conflicting sites
  (thread, stack, locks held). :func:`race_forget` models legitimate
  ownership transfer (a joined worker, an explicit weight handoff): it
  resets a field's history so the post-join reader is not a false positive.

Contract when the env var is unset: ZERO overhead and byte-identical
behavior — :func:`make_dispatch_lock` returns a plain ``threading.RLock``,
:func:`make_lock`/:func:`make_condition` return plain threading primitives,
:func:`wrap_dispatch` returns the function object unchanged (identity), and
the mark/check/access hooks return immediately on a single attribute test.

stdlib-only imports: this module is imported by jax-heavy modules, never the
other way around, so the analysis suite can exercise it without jax.
"""

from __future__ import annotations

import os
import sys
import threading
from collections import OrderedDict
from typing import Any, Dict, Iterator, Optional, Tuple

ENV_VAR = "TRLX_TPU_SANITIZE"
_VALID_MODES = ("dispatch", "donation", "race")


class SanitizeError(RuntimeError):
    """Base class for sanitizer violations."""


class DispatchLockViolation(SanitizeError):
    """A jitted program was dispatched without holding the dispatch lock
    while other trlx-* threads were alive."""


class DonatedBufferRead(SanitizeError):
    """A host read touched a buffer that was donated to a jitted program."""


class RaceViolation(SanitizeError):
    """Two threads accessed a declared shared field (at least one write)
    with an empty held-lock intersection — the Eraser lockset condition."""


def _parse_modes(raw: Optional[str]) -> frozenset:
    if not raw:
        return frozenset()
    modes = {m.strip() for m in raw.split(",") if m.strip()}
    unknown = modes - set(_VALID_MODES)
    if unknown:
        raise ValueError(
            f"{ENV_VAR} has unknown mode(s) {sorted(unknown)}; "
            f"valid: {','.join(_VALID_MODES)}"
        )
    return frozenset(modes)


_MODES = _parse_modes(os.environ.get(ENV_VAR))
_RACE_ON = "race" in _MODES  # fast-path flag for the race_access hot hook


def refresh() -> frozenset:
    """Re-read ``TRLX_TPU_SANITIZE`` (tests toggle the env mid-process;
    trainers/engines call this implicitly via make_dispatch_lock)."""
    global _MODES, _RACE_ON
    _MODES = _parse_modes(os.environ.get(ENV_VAR))
    _RACE_ON = "race" in _MODES
    return _MODES


def armed(mode: str) -> bool:
    return mode in _MODES


# --------------------------------------------------------------------------
# dispatch mode
# --------------------------------------------------------------------------


class SanitizedDispatchLock:
    """An RLock that knows its owner, so dispatch wrappers can assert
    ownership. Context-manager compatible with threading.RLock (the only
    protocol the dispatch sites use)."""

    #: name under which this lock appears in race-mode lockset reports.
    name = "_dispatch_lock"

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._owner: Optional[int] = None
        self._depth = 0

    def __enter__(self) -> "SanitizedDispatchLock":
        self._lock.acquire()
        self._owner = threading.get_ident()
        self._depth += 1
        _held_locks().append(self)
        return self

    def __exit__(self, *exc_info) -> bool:
        self._depth -= 1
        if self._depth == 0:
            self._owner = None
        _held_locks().remove(self)
        self._lock.release()
        return False

    # RLock API compatibility for non-context callers.
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._owner = threading.get_ident()
            self._depth += 1
            _held_locks().append(self)
        return ok

    def release(self) -> None:
        self._depth -= 1
        if self._depth == 0:
            self._owner = None
        _held_locks().remove(self)
        self._lock.release()

    def owned(self) -> bool:
        return self._owner == threading.get_ident()


def make_dispatch_lock():
    """The trainer/engine dispatch-lock factory. Unarmed: a plain
    threading.RLock — the serial path is byte-identical. Armed with
    ``dispatch``: an ownership-tracking lock the wrappers can interrogate.
    Armed with ``race`` only: a lockset-tracked RLock, so dispatch sections
    still count toward race-mode lock intersections."""
    refresh()
    if armed("dispatch"):
        return SanitizedDispatchLock()
    if armed("race"):
        return TrackedLock("_dispatch_lock", reentrant=True)
    return threading.RLock()


def _other_trlx_thread_alive() -> bool:
    """The PR 5 hazard predicate: is any OTHER thread that participates in
    the trlx dispatch machinery alive? Worker threads are all named
    ``trlx-*`` (rollout-producer, score-worker, prefetch, heartbeat, ...);
    from a worker's point of view the main thread is always the other
    dispatcher."""
    cur = threading.current_thread()
    if cur.name.startswith("trlx-"):
        return True  # the main thread exists and dispatches
    return any(
        t.name.startswith("trlx-") and t.is_alive() and t is not cur
        for t in threading.enumerate()
    )


def wrap_dispatch(name: str, fn, lock):
    """Wrap a jitted-program wrapper with the dispatch-ownership assertion.

    Identity unless ``lock`` is a :class:`SanitizedDispatchLock` (i.e. the
    sanitizer was armed when the lock was built) — callers can wrap
    unconditionally and pay nothing when unarmed."""
    if not isinstance(lock, SanitizedDispatchLock):
        return fn

    def checked(*args, **kwargs):
        if not lock.owned() and _other_trlx_thread_alive():
            raise DispatchLockViolation(
                f"jitted program {name!r} dispatched from thread "
                f"{threading.current_thread().name!r} without holding the "
                "dispatch lock while other trlx-* threads are alive; "
                "concurrent dispatch interleaves per-device enqueue order "
                "and can deadlock XLA collectives (see RUNBOOK §11 / GL001)"
            )
        return fn(*args, **kwargs)

    checked.__name__ = f"sanitized_{name.replace('/', '_')}"
    checked.__wrapped__ = fn
    return checked


# --------------------------------------------------------------------------
# donation mode
# --------------------------------------------------------------------------

# id(buffer) → (buffer, site). Strong refs are cheap: donated buffers are
# already deleted on device, only the small host handle stays alive — and the
# strong ref is what makes the id() key collision-free.
_DONATED: "OrderedDict[int, Tuple[Any, str]]" = OrderedDict()
_DONATED_CAP = 4096
_DONATED_LOCK = threading.Lock()


def _iter_leaves(tree: Any) -> Iterator[Any]:
    """Generic pytree-ish walk without importing jax: dicts (incl. flax
    FrozenDict — it is a Mapping), sequences, and flax struct dataclasses."""
    if tree is None:
        return
    if isinstance(tree, (list, tuple)):
        for item in tree:
            yield from _iter_leaves(item)
        return
    if hasattr(tree, "items"):
        try:
            for _, v in tree.items():
                yield from _iter_leaves(v)
            return
        except TypeError:
            pass
    fields = getattr(tree, "__dataclass_fields__", None)
    if fields:
        for f in fields:
            yield from _iter_leaves(getattr(tree, f, None))
        return
    yield tree


def _is_buffer(leaf: Any) -> bool:
    return hasattr(leaf, "dtype") and hasattr(leaf, "shape")


def mark_donated(tree: Any, site: str) -> None:
    """Record every array leaf of ``tree`` as donated at ``site``. No-op
    unless donation mode is armed. Call it with the PRE-dispatch reference
    right after a donating dispatch returns."""
    if "donation" not in _MODES:
        return
    with _DONATED_LOCK:
        for leaf in _iter_leaves(tree):
            if _is_buffer(leaf):
                _DONATED[id(leaf)] = (leaf, site)
        while len(_DONATED) > _DONATED_CAP:
            _DONATED.popitem(last=False)


def check_host_read(tree: Any, context: str) -> None:
    """Raise :class:`DonatedBufferRead` if any array leaf of ``tree`` was
    previously marked donated. No-op unless donation mode is armed. Wired at
    host-read checkpoints (to_local_host, engine.update_weights, snapshot
    paths)."""
    if "donation" not in _MODES:
        return
    for leaf in _iter_leaves(tree):
        if not _is_buffer(leaf):
            continue
        with _DONATED_LOCK:
            hit = _DONATED.get(id(leaf))
        if hit is not None and hit[0] is leaf:
            raise DonatedBufferRead(
                f"{context} reads a buffer (shape={getattr(leaf, 'shape', '?')}, "
                f"dtype={getattr(leaf, 'dtype', '?')}) that was donated at "
                f"{hit[1]!r}; donated buffers are deleted at dispatch — use "
                "the post-dispatch result or snapshot before dispatch "
                "(see RUNBOOK §11 / GL002)"
            )


def clear_donated() -> None:
    """Drop all donation records (tests; also useful after a rollback
    rebuilds the train state wholesale)."""
    with _DONATED_LOCK:
        _DONATED.clear()


# --------------------------------------------------------------------------
# race mode — Eraser-style lockset tracking (runtime dual of GL008)
# --------------------------------------------------------------------------

_TLS = threading.local()


def _held_locks() -> list:
    held = getattr(_TLS, "held", None)
    if held is None:
        held = _TLS.held = []
    return held


class TrackedLock:
    """A lock that registers itself in the thread-local held-lock set, so
    :func:`race_access` can compute lockset intersections. Built only when
    race mode is armed — :func:`make_lock` returns a plain ``threading.Lock``
    otherwise, keeping the unarmed path byte-identical."""

    def __init__(self, name: str, reentrant: bool = False):
        self.name = name
        self._lock = threading.RLock() if reentrant else threading.Lock()

    def __enter__(self) -> "TrackedLock":
        self._lock.acquire()
        _held_locks().append(self)
        return self

    def __exit__(self, *exc_info) -> bool:
        _held_locks().remove(self)
        self._lock.release()
        return False

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            _held_locks().append(self)
        return ok

    def release(self) -> None:
        _held_locks().remove(self)
        self._lock.release()


class TrackedCondition:
    """Condition-variable counterpart of :class:`TrackedLock` (the producer's
    ``_cv``). ``wait`` releases the underlying lock internally but the
    bookkeeping keeps it in the held set — no access by THIS thread can race
    while it sleeps, and accesses after wake are again genuinely locked."""

    def __init__(self, name: str):
        self.name = name
        self._cond = threading.Condition()

    def __enter__(self) -> "TrackedCondition":
        self._cond.acquire()
        _held_locks().append(self)
        return self

    def __exit__(self, *exc_info) -> bool:
        _held_locks().remove(self)
        self._cond.release()
        return False

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._cond.wait(timeout)

    def wait_for(self, predicate, timeout: Optional[float] = None):
        return self._cond.wait_for(predicate, timeout)

    def notify(self, n: int = 1) -> None:
        self._cond.notify(n)

    def notify_all(self) -> None:
        self._cond.notify_all()


def make_lock(name: str):
    """Race-mode-aware lock factory for hot shared structures
    (exporter gauges, heartbeat state). Unarmed: plain Lock."""
    refresh()
    if _RACE_ON:
        return TrackedLock(name)
    return threading.Lock()


def make_condition(name: str):
    """Race-mode-aware condition factory (the rollout producer's ``_cv``).
    Unarmed: plain Condition."""
    refresh()
    if _RACE_ON:
        return TrackedCondition(name)
    return threading.Condition()


# (id(owner), field) → Eraser state. Bounded like _DONATED; evicted oldest.
_RACE_FIELDS: "OrderedDict[Tuple[int, str], Dict[str, Any]]" = OrderedDict()
_RACE_CAP = 8192
_RACE_LOCK = threading.Lock()
_THIS_FILE = os.path.abspath(__file__)


def _race_site(skip: int = 2) -> str:
    """Short caller-stack summary: up to 3 frames outside this module."""
    parts = []
    try:
        f = sys._getframe(skip)
    except ValueError:  # pragma: no cover — shallow stack
        return "<unknown>"
    while f is not None and len(parts) < 3:
        fname = f.f_code.co_filename
        if os.path.abspath(fname) != _THIS_FILE:
            parts.append(
                f"{os.path.basename(fname)}:{f.f_lineno} in {f.f_code.co_name}"
            )
        f = f.f_back
    return " <- ".join(parts) if parts else "<unknown>"


def _lock_names(held) -> Tuple[str, ...]:
    return tuple(sorted(getattr(l, "name", "?") for l in held))


def race_access(owner: Any, field: str, write: bool = False) -> None:
    """Record one access to a declared hot shared field.

    Implements the Eraser lockset state machine: the first thread owns the
    field exclusively (initialization is forgiven); from the second thread
    on, the candidate lockset is intersected with the locks held at each
    access. When the intersection goes empty and the history contains a
    write, :class:`RaceViolation` names both conflicting sites. No-op (one
    global flag test) unless race mode is armed."""
    if not _RACE_ON:
        return
    ident = threading.get_ident()
    held = frozenset(id(l) for l in _held_locks())
    record = (
        threading.current_thread().name,
        _race_site(),
        _lock_names(_held_locks()),
        write,
    )
    with _RACE_LOCK:
        key = (id(owner), field)
        st = _RACE_FIELDS.get(key)
        if st is None:
            st = _RACE_FIELDS[key] = {
                "threads": {ident},
                "lockset": None,  # None while single-thread exclusive
                "written": bool(write),
                "last": {ident: record},
            }
            while len(_RACE_FIELDS) > _RACE_CAP:
                _RACE_FIELDS.popitem(last=False)
            return
        st["written"] = st["written"] or bool(write)
        st["last"][ident] = record
        if ident in st["threads"] and len(st["threads"]) == 1:
            return  # still exclusive: init/handoff phase, nothing to check
        st["threads"].add(ident)
        st["lockset"] = held if st["lockset"] is None else (st["lockset"] & held)
        if st["lockset"] or not st["written"]:
            return
        other = next(
            (
                rec
                for tid, rec in sorted(
                    st["last"].items(), key=lambda kv: kv[1][3], reverse=True
                )
                if tid != ident
            ),
            None,
        )
        # reset to the current thread so one bug raises once per access
        # pair, not once per subsequent access forever.
        _RACE_FIELDS[key] = {
            "threads": {ident},
            "lockset": None,
            "written": bool(write),
            "last": {ident: record},
        }
    tname, site, locks, _w = record
    o_tname, o_site, o_locks, o_write = other if other else ("?", "?", (), False)
    owner_desc = type(owner).__name__
    raise RaceViolation(
        f"field {field!r} of {owner_desc} accessed with an empty lockset "
        f"intersection: {'write' if write else 'read'} at [{site}] on thread "
        f"{tname!r} holding {list(locks)!r} conflicts with "
        f"{'write' if o_write else 'read'} at [{o_site}] on thread "
        f"{o_tname!r} holding {list(o_locks)!r} — hold one common lock at "
        "both sites, hand the value off via a queue/event, or mark the "
        "ownership transfer with sanitize.race_forget() "
        "(see RUNBOOK §13 / GL008)"
    )


def race_forget(owner: Any) -> None:
    """Drop race history for every field of ``owner`` — the happens-before
    edge the lockset model cannot see. Call it where ownership genuinely
    transfers: after joining a worker thread, or at an explicit versioned
    handoff (engine.update_weights). No-op unless race mode is armed."""
    if not _RACE_ON:
        return
    oid = id(owner)
    with _RACE_LOCK:
        for key in [k for k in _RACE_FIELDS if k[0] == oid]:
            del _RACE_FIELDS[key]


def clear_races() -> None:
    """Drop ALL race records (tests)."""
    with _RACE_LOCK:
        _RACE_FIELDS.clear()

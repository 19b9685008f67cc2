"""Crash-proof run journal for the bench harnesses (``RunManifest``).

Lives outside ``trlx_tpu.observability`` so that importing it pulls in
nothing but the stdlib: ``bench.py``'s parent process journals every child
it starts and must stay off JAX — on a TPU host the chip belongs to one
process at a time, and the children need it.
"""

import os
import time

from trlx_tpu.utils import jsonl

MANIFEST_FILENAME = "BENCH_MANIFEST.jsonl"


class RunManifest:
    """Crash-proof run journal: every record is one line-atomic append
    (utils/jsonl — open-append-close, O_APPEND, single write(2)), so a run
    killed at ANY instant (``timeout -k``, SIGKILL, OOM) leaves a parseable
    journal that says when and during what it died.

    Record vocabulary (``event`` field): ``begin`` (pid/cmd/meta),
    ``heartbeat`` (phase + free-form fields), ``child`` (subprocess label +
    rc + stderr tail), ``partial`` (best results so far), ``end`` (rc +
    reason). :meth:`read` folds any prefix of that stream — including one
    with no ``end`` — into a summary with a human-readable ``reason``.
    """

    STDERR_TAIL_CHARS = 2000

    def __init__(self, path, cmd=None, **meta):
        self.path = path
        self._finished = False
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
        self._append(
            {"event": "begin", "pid": os.getpid(), "cmd": cmd, **meta}
        )

    def _append(self, record):
        record.setdefault("t", time.time())
        try:
            jsonl.append_record(self.path, record)
        except OSError:
            # Forensics must never take down the run they journal.
            pass

    def heartbeat(self, phase, **fields):
        self._append({"event": "heartbeat", "phase": phase, **fields})

    def child(self, label, rc, stderr_tail=""):
        self._append(
            {
                "event": "child",
                "label": label,
                "rc": rc,
                "stderr_tail": (stderr_tail or "")[-self.STDERR_TAIL_CHARS :],
            }
        )

    def partial(self, metrics):
        self._append({"event": "partial", "metrics": metrics})

    def finish(self, rc, reason=None, **fields):
        # Idempotent: a crash handler and the normal exit path may both
        # reach here — the first verdict stands.
        if self._finished:
            return
        self._finished = True
        self._append({"event": "end", "rc": rc, "reason": reason, **fields})

    @staticmethod
    def read(path):
        """Fold a manifest (possibly torn, possibly end-less) into
        ``{"valid", "complete", "rc", "reason", "last_heartbeat",
        "partial", "children", "events"}``. bench_trajectory.py carries an
        inline stdlib copy of this logic (it must not import the
        observability package); test_observability asserts parity."""
        try:
            records = jsonl.read_jsonl(path)
        except (OSError, ValueError):
            records = []
        begin = next((r for r in records if r.get("event") == "begin"), None)
        if begin is None:
            return {"valid": False, "complete": False, "rc": None, "reason": "unreadable manifest", "events": len(records)}
        end = next((r for r in reversed(records) if r.get("event") == "end"), None)
        heartbeats = [r for r in records if r.get("event") == "heartbeat"]
        children = [r for r in records if r.get("event") == "child"]
        partial = next(
            (r.get("metrics") for r in reversed(records) if r.get("event") == "partial"),
            None,
        )
        if end is not None:
            reason = end.get("reason") or f"completed rc={end.get('rc')}"
            rc = end.get("rc")
        else:
            rc = None
            if heartbeats:
                last = heartbeats[-1]
                where = last.get("phase", "?")
                cand = last.get("candidate")
                reason = f"run killed mid-flight during {where}" + (
                    f" (candidate {cand})" if cand else ""
                )
            else:
                reason = "run killed before first heartbeat"
            failed = [c for c in children if c.get("rc") not in (0, None)]
            if failed:
                tail = (failed[-1].get("stderr_tail") or "").strip().splitlines()
                last_line = tail[-1][:160] if tail else ""
                reason += (
                    f"; last child failure {failed[-1].get('label')} "
                    f"rc={failed[-1].get('rc')}"
                ) + (f": {last_line}" if last_line else "")
        return {
            "valid": True,
            "complete": end is not None,
            "rc": rc,
            "reason": reason,
            "last_heartbeat": heartbeats[-1] if heartbeats else None,
            "partial": partial,
            "children": [
                {"label": c.get("label"), "rc": c.get("rc")} for c in children
            ],
            "events": len(records),
        }

"""graftrace static-half fixtures (GL008-GL011): every concurrency rule
fires on its violating fixture, stays suppressed with a reason, and passes
on the clean variant — including the PR 5 two-thread dispatch deadlock
re-expressed as a GL009 lock-order cycle and the trlx-* thread-naming
contract the teardown leak assertions depend on.

Same contract as test_analysis.py: stdlib ast only, no jax on the lint path.
"""

import os
import subprocess
import sys
import textwrap

from trlx_tpu.analysis import lint_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_source(tmp_path, source, relpath="fixture.py", select=None):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings, _ = lint_paths([str(path)], select=select)
    return findings


def _active(findings, rule):
    return [f for f in findings if not f.suppressed and f.rule == rule]


# ------------------------------------------------------------------- GL008


GL008_VIOLATION = """
import threading

class Producer:
    def start(self):
        self._thread = threading.Thread(
            target=self._run, name="trlx-producer", daemon=True
        )
        self._thread.start()

    def _run(self):
        self.count += 1

    def snapshot(self):
        return self.count

    def close(self):
        self._thread.join(timeout=5)
"""


def test_gl008_fires_on_unlocked_cross_thread_write(tmp_path):
    hits = _active(_lint_source(tmp_path, GL008_VIOLATION), "GL008")
    assert len(hits) == 1
    assert "self.count" in hits[0].message and "_run" in hits[0].message


def test_gl008_clean_under_common_lock(tmp_path):
    src = """
    import threading

    class Producer:
        def start(self):
            self._thread = threading.Thread(
                target=self._run, name="trlx-producer", daemon=True
            )
            self._thread.start()

        def _run(self):
            with self._lock:
                self.count += 1

        def snapshot(self):
            with self._lock:
                return self.count

        def close(self):
            self._thread.join(timeout=5)
    """
    assert _active(_lint_source(tmp_path, src), "GL008") == []


def test_gl008_allowlists_bounded_deque_handoff(tmp_path):
    # deque(maxlen=...) is the overlap pipeline's handoff structure — the
    # producer appends, the consumer pops, and the allowlist covers both
    # mutation directions without a lock.
    src = """
    import threading
    from collections import deque

    class Producer:
        def __init__(self):
            self._ready = deque(maxlen=4)

        def start(self):
            self._thread = threading.Thread(
                target=self._run, name="trlx-producer", daemon=True
            )
            self._thread.start()

        def _run(self):
            self._ready.append(1)

        def take(self):
            return self._ready.popleft()

        def close(self):
            self._thread.join(timeout=5)
    """
    assert _active(_lint_source(tmp_path, src), "GL008") == []


def test_gl008_resolves_helper_and_callback_one_level(tmp_path):
    # The write hides one call deep (the producer loop calls self._step());
    # the entry-point expansion must still attribute it to the worker thread.
    src = """
    import threading

    class Producer:
        def start(self):
            self._thread = threading.Thread(
                target=self._run, name="trlx-producer", daemon=True
            )
            self._thread.start()

        def _run(self):
            while True:
                self._step()

        def _step(self):
            self.count += 1

        def snapshot(self):
            return self.count

        def close(self):
            self._thread.join(timeout=5)
    """
    hits = _active(_lint_source(tmp_path, src), "GL008")
    assert len(hits) == 1 and "self.count" in hits[0].message


def test_gl008_suppressed_with_reason(tmp_path):
    src = GL008_VIOLATION.replace(
        "self.count += 1",
        "self.count += 1  # graftlint: disable=GL008 -- fixture: benign stat",
    )
    findings = _lint_source(tmp_path, src)
    assert _active(findings, "GL008") == []
    assert any(f.suppressed and f.rule == "GL008" for f in findings)


# ------------------------------------------------------------------- GL009


GL009_VIOLATION = """
class Trainer:
    def dispatch_then_stats(self):
        with self._dispatch_lock:
            with self._stats_lock:
                self.n += 1

    def stats_then_dispatch(self):
        with self._stats_lock:
            with self._dispatch_lock:
                self.m += 1
"""


def test_gl009_fires_on_lock_order_cycle(tmp_path):
    # The PR 5 incident shape: one thread holds the dispatch lock and wants
    # the tracker lock, the other holds the tracker lock and wants dispatch.
    hits = _active(_lint_source(tmp_path, GL009_VIOLATION), "GL009")
    assert len(hits) == 1
    assert "_dispatch_lock" in hits[0].message
    assert "Trainer._stats_lock" in hits[0].message


def test_gl009_clean_with_consistent_order(tmp_path):
    src = """
    class Trainer:
        def a(self):
            with self._dispatch_lock:
                with self._stats_lock:
                    self.n += 1

        def b(self):
            with self._dispatch_lock:
                with self._stats_lock:
                    self.m += 1
    """
    assert _active(_lint_source(tmp_path, src), "GL009") == []


def test_gl009_same_lock_name_in_unrelated_classes_does_not_merge(tmp_path):
    # Both classes have a `_lock` and a `_q_lock` acquired in opposite
    # nesting order — but each class's locks are distinct objects; the
    # class-scoped node names must keep the graphs separate.
    src = """
    class A:
        def f(self):
            with self._lock:
                with self._q_lock:
                    self.n = 1

    class B:
        def g(self):
            with self._q_lock:
                with self._lock:
                    self.m = 1
    """
    assert _active(_lint_source(tmp_path, src), "GL009") == []


def test_gl009_cycle_through_helper_call(tmp_path):
    # Edge discovered through one-level call resolution: f holds the stats
    # lock and calls a helper that takes the dispatch lock.
    src = """
    class Trainer:
        def f(self):
            with self._stats_lock:
                self._flush()

        def _flush(self):
            with self._dispatch_lock:
                self.n += 1

        def g(self):
            with self._dispatch_lock:
                with self._stats_lock:
                    self.m += 1
    """
    hits = _active(_lint_source(tmp_path, src), "GL009")
    assert len(hits) == 1


# ------------------------------------------------------------------- GL010


def test_gl010_fires_on_unjoined_undaemonized_thread(tmp_path):
    src = """
    import threading

    def kick(work):
        t = threading.Thread(target=work)
        t.start()
    """
    hits = _active(_lint_source(tmp_path, src), "GL010")
    assert len(hits) == 1 and "neither daemonized nor joined" in hits[0].message


def test_gl010_fires_on_unnamed_worker_stored_on_self(tmp_path):
    src = """
    import threading

    class Worker:
        def start(self):
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

        def _run(self):
            pass

        def close(self):
            self._thread.join(timeout=5)
    """
    hits = _active(_lint_source(tmp_path, src), "GL010")
    assert len(hits) == 1 and "trlx-" in hits[0].message


def test_gl010_clean_named_daemon_joined_worker(tmp_path):
    src = """
    import threading

    class Worker:
        def start(self):
            self._thread = threading.Thread(
                target=self._run, name="trlx-worker", daemon=True
            )
            self._thread.start()

        def _run(self):
            pass

        def close(self):
            self._thread.join(timeout=5)
    """
    assert _active(_lint_source(tmp_path, src), "GL010") == []


def test_gl010_timer_exempt_from_naming_contract(tmp_path):
    # threading.Timer accepts no name= and a cancelled Timer can linger
    # briefly — the deadline-timer idiom (collective_guard) is cancelled,
    # not joined-by-name, so the naming half must not fire on Timers.
    src = """
    import threading

    class Guard:
        def arm(self):
            self._timer = threading.Timer(5.0, self._fire)
            self._timer.start()

        def _fire(self):
            pass

        def disarm(self):
            self._timer.cancel()
    """
    assert _active(_lint_source(tmp_path, src), "GL010") == []


# ------------------------------------------------------------------- GL011


def test_gl011_fires_on_sleep_under_dispatch_lock(tmp_path):
    src = """
    import time

    class Trainer:
        def step(self):
            with self._dispatch_lock:
                time.sleep(0.5)
                out = self._train_fn(self.state)
            return out
    """
    hits = _active(_lint_source(tmp_path, src), "GL011")
    assert len(hits) == 1 and "time.sleep" in hits[0].message


def test_gl011_fires_on_untimed_queue_get_under_dispatch_lock(tmp_path):
    src = """
    class Trainer:
        def step(self):
            with self._dispatch_lock:
                item = self._pending.get()
            return item
    """
    hits = _active(_lint_source(tmp_path, src), "GL011")
    assert len(hits) == 1 and "no timeout" in hits[0].message


def test_gl011_fires_on_collective_under_dispatch_lock(tmp_path):
    src = """
    class Trainer:
        def sync(self):
            with self._dispatch_lock:
                collective_guard("sync", lambda: None)
    """
    hits = _active(_lint_source(tmp_path, src), "GL011")
    assert len(hits) == 1 and "collective_guard" in hits[0].message


def test_gl011_clean_timed_get_and_outside_sleep(tmp_path):
    src = """
    import time

    class Trainer:
        def step(self):
            time.sleep(0.5)
            with self._dispatch_lock:
                item = self._pending.get(timeout=1.0)
                out = self._train_fn(self.state)
            return out
    """
    assert _active(_lint_source(tmp_path, src), "GL011") == []


def test_gl011_other_locks_unrestricted(tmp_path):
    src = """
    import time

    class Tracker:
        def flush(self):
            with self._stats_lock:
                time.sleep(0.01)
    """
    assert _active(_lint_source(tmp_path, src), "GL011") == []


# ----------------------------------------------------------------- CLI/meta


def test_list_rules_groups_families_and_states_reason_contract():
    out = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", "--list-rules"],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert out.returncode == 0
    assert "invariant (graftlint, PR 11):" in out.stdout
    assert "concurrency (graftrace, PR 13):" in out.stdout
    for rule in ("GL008", "GL009", "GL010", "GL011"):
        assert rule in out.stdout
    assert "REQUIRED" in out.stdout


def test_scripts_lint_clean_with_script_rule_subset():
    # The Makefile's second lint pass: the top-level scripts under the
    # rule families that apply outside the package.
    scripts = [
        os.path.join(REPO, name)
        for name in (
            "chip_smoke.py",
            "acceptance_network.py",
            "bench_flash.py",
            "bench_kda.py",
            "bench_moe.py",
        )
        if os.path.exists(os.path.join(REPO, name))
    ]
    assert scripts, "expected top-level scripts in the repo root"
    findings, _ = lint_paths(
        scripts,
        select=["GL003", "GL004", "GL007", "GL008", "GL009", "GL010", "GL011"],
    )
    assert [f for f in findings if not f.suppressed] == []

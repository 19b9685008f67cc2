"""graftlint (trlx_tpu.analysis) fixtures: every rule fires on its violating
fixture, stays suppressed with a reason, and passes on the clean variant —
plus the tree-wide zero-findings gate, the CLI contract, and the
no-jax-import contract that keeps `make lint` CPU-only and fast.

These tests never import jax themselves on the lint path: the whole suite
runs on the stdlib ast machinery.
"""

import json
import os
import subprocess
import sys
import textwrap

from trlx_tpu.analysis import RULE_TITLES, lint_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_source(tmp_path, source, relpath="fixture.py", select=None):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings, _ = lint_paths([str(path)], select=select)
    return findings


def _active(findings, rule):
    return [f for f in findings if not f.suppressed and f.rule == rule]


# ------------------------------------------------------------------- GL001


GL001_VIOLATION = """
class Trainer:
    def rollout(self, batch):
        tokens = self._generate_fn(self.state.params, batch)
        return tokens
"""

GL001_CLEAN = """
class Trainer:
    def rollout(self, batch):
        with self._dispatch_lock:
            tokens = self._generate_fn(self.state.params, batch)
        return tokens
"""


def test_gl001_fires_on_unlocked_dispatch(tmp_path):
    findings = _lint_source(tmp_path, GL001_VIOLATION)
    hits = _active(findings, "GL001")
    assert len(hits) == 1 and "_generate_fn" in hits[0].message


def test_gl001_clean_under_lock(tmp_path):
    assert _active(_lint_source(tmp_path, GL001_CLEAN), "GL001") == []


def test_gl001_engine_dispatch_context_counts_as_lock(tmp_path):
    src = """
    class Engine:
        def step(self):
            with self._dispatch():
                state, live = self._decode(self._variables, self._state)
            self._state = state
    """
    assert _active(_lint_source(tmp_path, src), "GL001") == []


def test_gl001_builder_call_of_call_fires(tmp_path):
    src = """
    class Trainer:
        def score(self, chunk):
            return self._score_fn_for(chunk.shape[1])(self.state.params, chunk)
    """
    hits = _active(_lint_source(tmp_path, src), "GL001")
    assert len(hits) == 1 and "_score_fn_for" in hits[0].message


def test_gl001_pr5_unlocked_producer_fixture_is_flagged(tmp_path):
    # The PR 5 incident shape: the rollout-producer thread dispatching the
    # generate program concurrently with the main thread's train_step —
    # exactly the interleaved-enqueue deadlock the rule encodes.
    src = """
    class OverlappedTrainer:
        def _producer_loop(self):
            while not self._stop.is_set():
                chunk = self.queue.get()
                ids, mask = self._generate_fn(self.state.params, chunk)
                self.out.put((ids, mask))
    """
    hits = _active(_lint_source(tmp_path, src), "GL001")
    assert len(hits) == 1


def test_gl001_suppression_with_reason_waives(tmp_path):
    src = """
    class Trainer:
        def rollout(self, batch):
            tokens = self._generate_fn(self.state.params, batch)  # graftlint: disable=GL001 -- serial harness, no worker threads
            return tokens
    """
    findings = _lint_source(tmp_path, src)
    assert _active(findings, "GL001") == []
    waived = [f for f in findings if f.suppressed and f.rule == "GL001"]
    assert len(waived) == 1 and "serial harness" in waived[0].reason


def test_gl000_suppression_without_reason_is_itself_a_finding(tmp_path):
    src = """
    class Trainer:
        def rollout(self, batch):
            tokens = self._generate_fn(self.state.params, batch)  # graftlint: disable=GL001
            return tokens
    """
    findings = _lint_source(tmp_path, src)
    assert len(_active(findings, "GL000")) == 1
    # a reasonless disable still waives nothing
    assert len(_active(findings, "GL001")) == 1


# ------------------------------------------------------------------- GL002


def test_gl002_fires_on_read_after_donate(tmp_path):
    src = """
    class Trainer:
        def learn(self, batch):
            new_state, stats = self.train_step(self.state, batch)
            grad_norm = self.state.params["w"]
            return new_state, grad_norm
    """
    hits = _active(_lint_source(tmp_path, src), "GL002")
    assert len(hits) == 1 and "self.state" in hits[0].message


def test_gl002_same_statement_rebind_is_clean(tmp_path):
    src = """
    class Trainer:
        def learn(self, batch):
            self.state, stats = self.train_step(self.state, batch)
            grad_norm = self.state.params["w"]
            return grad_norm
    """
    assert _active(_lint_source(tmp_path, src), "GL002") == []


def test_gl002_discovers_local_jit_donations(tmp_path):
    src = """
    import jax

    class Engine:
        def build(self):
            self._advance = jax.jit(self._advance_impl, donate_argnums=(1,))

        def run(self, carry, x):
            out = self._advance(self.vars, carry)
            stale = carry["kv"]
            return out, stale
    """
    hits = _active(_lint_source(tmp_path, src), "GL002")
    assert len(hits) == 1 and "'carry'" in hits[0].message


def test_gl002_rebind_then_read_is_clean(tmp_path):
    src = """
    class Engine:
        def run(self, carry, x):
            carry = self._decode(self.vars, carry)
            fresh = carry["kv"]
            return fresh
    """
    assert _active(_lint_source(tmp_path, src), "GL002") == []


# ------------------------------------------------------------------- GL003


def test_gl003_fires_on_host_side_effect_in_traced_body(tmp_path):
    src = """
    import jax

    def step_body(x):
        print("tracing", x)
        return x * 2

    step = jax.jit(step_body)
    """
    hits = _active(_lint_source(tmp_path, src), "GL003")
    assert len(hits) == 1 and "print()" in hits[0].message


def test_gl003_fires_on_time_call_in_scan_body(tmp_path):
    src = """
    import time
    import jax

    def scan_body(carry, x):
        t0 = time.time()
        return carry + x, t0

    out = jax.lax.scan(scan_body, 0, xs)
    """
    hits = _active(_lint_source(tmp_path, src), "GL003")
    assert len(hits) == 1 and "time.time" in hits[0].message


def test_gl003_pure_traced_body_is_clean(tmp_path):
    src = """
    import jax
    import jax.numpy as jnp

    def step_body(x):
        return jnp.tanh(x) * 2

    step = jax.jit(step_body)
    """
    assert _active(_lint_source(tmp_path, src), "GL003") == []


def test_gl003_host_calls_outside_traced_bodies_are_fine(tmp_path):
    src = """
    def host_loop(xs):
        print("host side is allowed to print")
        return [x * 2 for x in xs]
    """
    assert _active(_lint_source(tmp_path, src), "GL003") == []


# ------------------------------------------------------------------- GL004


def test_gl004_fires_on_bare_collective(tmp_path):
    src = """
    from jax.experimental import multihost_utils

    def agree(v):
        return multihost_utils.broadcast_one_to_all(v)
    """
    hits = _active(_lint_source(tmp_path, src), "GL004")
    assert len(hits) == 1 and "broadcast_one_to_all" in hits[0].message


def test_gl004_guarded_collective_is_clean(tmp_path):
    src = """
    from jax.experimental import multihost_utils
    from trlx_tpu.resilience.distributed import collective_guard

    def agree(v):
        with collective_guard("agree"):
            return multihost_utils.broadcast_one_to_all(v)
    """
    assert _active(_lint_source(tmp_path, src), "GL004") == []


def test_gl004_guard_home_is_exempt(tmp_path):
    src = """
    from jax.experimental import multihost_utils

    def _impl(v):
        return multihost_utils.broadcast_one_to_all(v)
    """
    findings = _lint_source(tmp_path, src, relpath="resilience/distributed.py")
    assert _active(findings, "GL004") == []


# ------------------------------------------------------------------- GL005


def test_gl005_fires_on_truthy_new_knob_default(tmp_path):
    src = """
    from dataclasses import dataclass

    @dataclass
    class FixtureConfig:
        shiny_new_feature: bool = True
    """
    findings = _lint_source(tmp_path, src, relpath="data/configs.py")
    hits = _active(findings, "GL005")
    assert len(hits) == 1 and "shiny_new_feature" in hits[0].message


def test_gl005_off_default_knob_is_clean(tmp_path):
    src = """
    from dataclasses import dataclass

    @dataclass
    class FixtureConfig:
        shiny_new_feature: bool = False
        optional_depth: int = 0
    """
    findings = _lint_source(tmp_path, src, relpath="data/configs.py")
    assert _active(findings, "GL005") == []


def test_gl005_fires_on_undeclared_knob_read(tmp_path):
    src = """
    def setup(config):
        depth = config.method.totally_undeclared_knob
        fallback = getattr(config.method, "typo_knbo", None)
        return depth, fallback
    """
    hits = _active(_lint_source(tmp_path, src), "GL005")
    assert len(hits) == 2
    assert any("totally_undeclared_knob" in f.message for f in hits)
    assert any("typo_knbo" in f.message for f in hits)


def test_gl005_declared_knob_read_is_clean(tmp_path):
    src = """
    def setup(config):
        g = config.method.gamma
        ci = config.train.checkpoint_interval
        m = config.method
        return g, ci, getattr(m, "chunk_size", 1)
    """
    assert _active(_lint_source(tmp_path, src), "GL005") == []


# ------------------------------------------------------------------- GL006


def test_gl006_fires_on_adhoc_blockspec_in_ops(tmp_path):
    src = """
    from jax.experimental import pallas as pl

    def kernel(x):
        spec = pl.BlockSpec((128, 128), lambda i: (i, 0))
        return spec
    """
    findings = _lint_source(tmp_path, src, relpath="ops/custom_kernel.py")
    hits = _active(findings, "GL006")
    assert len(hits) == 1 and "BlockSpec" in hits[0].message


def test_gl006_clean_with_tiling_provenance(tmp_path):
    src = """
    from jax.experimental import pallas as pl

    from trlx_tpu.ops.tiling import check_layout, flash_block_layout

    def kernel(x, bq, bk):
        check_layout(flash_block_layout(8, 128, 64, (bq, 128, bk)))
        spec = pl.BlockSpec((bq, 64), lambda i: (i, 0))
        return spec
    """
    findings = _lint_source(tmp_path, src, relpath="ops/custom_kernel.py")
    assert _active(findings, "GL006") == []


def test_gl006_only_applies_under_ops(tmp_path):
    src = """
    from jax.experimental import pallas as pl

    def helper(x):
        return pl.BlockSpec((8, 8), lambda i: (i, 0))
    """
    findings = _lint_source(tmp_path, src, relpath="pipeline/helper.py")
    assert _active(findings, "GL006") == []


# ------------------------------------------------------------------- GL007


def test_gl007_fires_on_unsanitizable_key(tmp_path):
    src = """
    def stats():
        return {"rollout/mean reward": 1.0}
    """
    hits = _active(_lint_source(tmp_path, src), "GL007")
    assert len(hits) == 1 and "mean reward" in hits[0].message


def test_gl007_fires_on_cross_key_collision(tmp_path):
    src = """
    def stats(tracker):
        tracker.log({"engine/tps": 1.0})
        tracker.log({"engine.tps": 2.0})
    """
    hits = _active(_lint_source(tmp_path, src), "GL007")
    assert len(hits) == 2 and all("collides" in f.message for f in hits)


def test_gl007_namespaced_keys_are_clean(tmp_path):
    src = """
    def stats(tracker):
        tracker.log({"ppo/policy_loss": 0.1, "engine/slot_occupancy": 0.9})
        tracker.log_histogram("rollout/response_len", [1, 2, 3])
    """
    assert _active(_lint_source(tmp_path, src), "GL007") == []


# --------------------------------------------------------- tree-wide gates


def test_real_tree_lints_clean():
    """Tier-1 gate: the shipped tree must carry zero unsuppressed findings —
    new violations fail here before they fail in production."""
    findings, n_files = lint_paths([os.path.join(REPO, "trlx_tpu")])
    offenders = [f.render() for f in findings if not f.suppressed]
    assert offenders == [], "\n".join(offenders)
    assert n_files > 50  # the walk actually covered the tree


def test_rule_titles_cover_all_registered_rules():
    from trlx_tpu.analysis.rules import GLOBAL_RULES, PER_MODULE_RULES

    registered = {rid for rid, _ in PER_MODULE_RULES + GLOBAL_RULES}
    assert registered <= set(RULE_TITLES)


def test_gl007_sanitize_mirror_matches_exporter():
    """The lint-side sanitizer must not drift from the runtime exporter's
    (they are separate implementations so the lint path stays jax-free)."""
    from trlx_tpu.analysis.rules import _sanitize
    from trlx_tpu.observability.export import sanitize_metric_name

    for name in [
        "ppo/policy_loss", "engine.tps", "a b", "9lives", "watchdog-fires",
        "nested/a.b-c", "ok_name", ":colon", "Ünïcode/x",
    ]:
        assert _sanitize(name) == sanitize_metric_name(name), name


# ------------------------------------------------------------------- CLI


def test_cli_json_output_and_exit_code_on_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(GL001_VIOLATION))
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", str(bad), "--json"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["tool"] == "graftlint" and payload["files"] == 1
    assert any(f["rule"] == "GL001" for f in payload["findings"])


def test_cli_exit_zero_on_clean_fixture(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent(GL001_CLEAN))
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", str(good)],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rejects_unknown_rule_selector(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", "--select", "GL999", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 2


def test_lint_path_never_imports_jax():
    """`make lint` must run on CPU-only CI images in seconds: importing the
    analysis package and linting the full tree may not pull in jax."""
    code = (
        "import sys\n"
        "from trlx_tpu.analysis import lint_paths\n"
        "findings, n = lint_paths(['trlx_tpu'])\n"
        "assert n > 50\n"
        "assert 'jax' not in sys.modules, 'lint path imported jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr

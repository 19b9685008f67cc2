"""Start-up imports only what the path to the first train step uses
(utils/startup.py): transformers, orbax.checkpoint and wandb load where they
are first called, and every step record says how long the package's import
took and whether a deferred package has been loaded.

What `sys.modules` holds is asked in a fresh interpreter: an xdist worker's
own has whatever its earlier tests imported.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "examples"))

from randomwalks import base_config  # noqa: E402

# Neither flax nor optax pulls any of these (jax 0.9.0, flax 0.12.3): the list
# is the issue's whole, plus what `transformers` dragged in beside them.
UNLOADED = ("transformers", "orbax", "torch", "sklearn", "google.cloud.logging", "scipy", "pandas", "wandb")
N_NODES, MAX_LENGTH = 15, 8


def _tiny_ilql(tmp_path, **train):
    config = base_config("ilql", N_NODES, MAX_LENGTH)
    config.train.batch_size = 16  # shards over the suite's eight CPU devices
    config.train.checkpoint_dir = str(tmp_path)
    for key, value in train.items():
        setattr(config.train, key, value)
    return config


def _fresh_python(script, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "examples")]))
    env.pop("XLA_FLAGS", None)  # one CPU device: nothing here needs the suite's eight
    done = subprocess.run([sys.executable, "-c", textwrap.dedent(script), *argv], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_importing_the_api_loads_no_deferred_package():
    out = _fresh_python(
        """
        import json, sys
        import trlx_tpu.trainer.api
        from trlx_tpu.utils.startup import DEFERRED, startup_counters
        print(json.dumps({"loaded": [m for m in sys.argv[1:] if m in sys.modules], "deferred": list(DEFERRED),
                          "counters": startup_counters()}))
        """, *UNLOADED)
    assert out["loaded"] == []
    assert set(out["deferred"]) <= set(UNLOADED) | {"orbax.checkpoint"}
    assert out["counters"]["setup/deferred_loaded"] == 0.0 and out["counters"]["setup/import_s"] > 0


def test_a_run_without_tokenizer_or_saves_leaves_them_unloaded_and_says_so_in_every_step_record(tmp_path):
    out = _fresh_python(
        """
        import json, sys
        import trlx_tpu
        from randomwalks import base_config, generate_random_walks
        walks, logit_mask, metric_fn, reward_fn = generate_random_walks(n_nodes=15, max_length=8, n_walks=40, seed=1000)
        config = base_config("ilql", 15, 8)
        config.train.total_steps, config.train.epochs, config.train.batch_size = 2, 2, 20
        config.train.eval_interval, config.train.checkpoint_interval = 100, 0
        config.train.checkpoint_dir = sys.argv[1]
        assert config.model.tokenizer_path == ""
        trainer = trlx_tpu.train(dataset=(walks, reward_fn(walks)), eval_prompts=[[1]], metric_fn=metric_fn,
                                 config=config, logit_mask=logit_mask)
        print(json.dumps({"loaded": [m for m in sys.argv[2:] if m in sys.modules], "steps": trainer.iter_count,
                          "has_checkpointer": trainer._ckptr is not None}))
        """, str(tmp_path), *UNLOADED)
    assert out == {"loaded": [], "steps": 2, "has_checkpointer": False}
    records = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    steps = [r for r in records if "step_time" in r]
    assert len(steps) == 2
    for r in steps:
        assert r["setup/import_s"] > 0 and r["setup/deferred_loaded"] == 0.0
    assert len({r["setup/import_s"] for r in steps}) == 1  # taken once
    assert not os.path.exists(tmp_path / "latest.txt")  # and no save at the end


@pytest.mark.parametrize("interval", [0, 3])
def test_the_checkpointer_exists_from_init_where_saves_are_scheduled_else_from_the_first_save(tmp_path, interval):
    from trlx_tpu.trainer.ilql import ILQLTrainer

    trainer = ILQLTrainer(_tiny_ilql(tmp_path, checkpoint_interval=interval))
    assert (trainer._ckptr is not None) == (interval > 0)
    saved = jax.device_get(trainer.state)
    trainer.save()
    assert trainer._ckptr is not None and "orbax.checkpoint" in sys.modules
    trainer.state = jax.tree_util.tree_map(lambda x: x + 1, trainer.state)
    trainer.load()
    for want, got in zip(jax.tree_util.tree_leaves(saved), jax.tree_util.tree_leaves(jax.device_get(trainer.state))):
        np.testing.assert_array_equal(want, got)
    trainer.tracker.finish()


def test_a_resume_that_finds_no_checkpoint_builds_no_checkpointer(tmp_path):
    from trlx_tpu.trainer.ilql import ILQLTrainer

    config = _tiny_ilql(tmp_path, checkpoint_interval=0, resume_from_checkpoint=True)
    trainer = ILQLTrainer(config)
    assert trainer._ckptr is None and not trainer._resumed
    trainer.save()
    trainer.tracker.finish()
    resumed = ILQLTrainer(config)  # finds latest.txt: the restore builds the checkpointer inside __init__
    assert resumed._resumed and resumed._ckptr is not None
    resumed.tracker.finish()


def test_a_deferred_import_runs_under_a_named_span(tmp_path):
    from trlx_tpu.observability import spans
    from trlx_tpu.utils.startup import deferred_import

    spans.configure(str(tmp_path / "spans.jsonl"))
    try:
        assert deferred_import("json") is json
        spans.flush()
    finally:
        spans.shutdown()
    (event,) = [e for e in spans.read_spans(str(tmp_path / "spans.jsonl")) if e.get("ph") == "X"]
    assert event["name"] == "setup/import" and event["args"]["module"] == "json"

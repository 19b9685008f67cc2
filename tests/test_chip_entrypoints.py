"""The entry points that touch the chip decide device, process and cache the
way the chip needs (CPU, tier-1, seconds):

- the compile-cache rule (trlx_tpu/utils/compile_cache.py): placed from
  outside through JAX_COMPILATION_CACHE_DIR, else one fixed path under the
  checkout — the same from every call and every process;
- chip_smoke.py without a TPU stops at the device check, before it builds
  anything, with a non-zero exit code and no result line.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, **env):
    args = code_or_args if isinstance(code_or_args, list) else ["-c", code_or_args]
    full_env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    full_env.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=full_env,
        capture_output=True, text=True, timeout=120,
    )


_CACHE_PROBE = """
import json, sys
sys.path.insert(0, %r)
from trlx_tpu.utils.compile_cache import setup_compile_cache
calls = [setup_compile_cache(), setup_compile_cache()]
configured = None
if "jax" in sys.modules:  # only the unplaced branch has any use for jax
    import jax
    configured = jax.config.jax_compilation_cache_dir
print(json.dumps({"calls": calls, "configured": configured}))
""" % REPO


def test_cache_placed_from_outside_is_not_set_in_code(tmp_path):
    placed = str(tmp_path / "placed")
    out = _run(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=placed)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["calls"] == [placed, placed]
    # the helper made no jax.config.update: it never imported jax, which
    # reads the variable itself
    assert got["configured"] is None


def test_unplaced_cache_is_one_fixed_path_under_the_checkout():
    from trlx_tpu.utils.compile_cache import DEFAULT_DIR  # this process ...

    out = _run(_CACHE_PROBE)  # ... and another one, two calls
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    expected = os.path.join(REPO, ".jax_cache")
    assert got["calls"] == [expected, expected]
    assert got["configured"] == expected == DEFAULT_DIR


def test_chip_smoke_without_a_tpu_fails_at_the_device_check():
    out = _run([os.path.join(REPO, "chip_smoke.py")])
    assert out.returncode == 2, (out.returncode, out.stderr[-500:])
    assert "platform=cpu" in out.stdout
    assert "no TPU" in out.stderr
    # stopped before the kernel phase, a model or a result line
    assert "phase" not in out.stdout and '"ok"' not in out.stdout


def test_chip_smoke_schedules_no_checkpoint(tmp_path):
    """chip_smoke.py's run writes no checkpoint (the flagship state is 5.7 GB
    in files of over a gigabyte; a machine with a file-size limit refuses
    them), and `checkpoint_interval: 0` is the rule that says so: neither the
    periodic save nor the one `learn` leaves at its end."""
    from types import SimpleNamespace

    sys.path.insert(0, REPO)
    import chip_smoke
    from trlx_tpu.trainer.base import JaxBaseTrainer

    config = chip_smoke.ppo_config(chip_smoke.REHEARSAL, 1, str(tmp_path), True)
    assert config.train.checkpoint_interval == 0

    saves = []
    stub = SimpleNamespace(config=config, save=lambda: saves.append(1))
    assert not any(JaxBaseTrainer.intervals(stub, s)["do_checkpoint"] for s in range(4))
    JaxBaseTrainer._save_at_end(stub)
    assert saves == []
    config.train.checkpoint_interval = 2
    assert [JaxBaseTrainer.intervals(stub, s)["do_checkpoint"] for s in (1, 2)] == [False, True]
    JaxBaseTrainer._save_at_end(stub)
    assert saves == [1]

"""The one place that decides where JAX's persistent compilation cache lives.

Rule (the trainer, benchmark/harness.py and chip_smoke.py all call ``setup_compile_cache``
and nothing else in the tree sets a directory):

- ``JAX_COMPILATION_CACHE_DIR`` set → the program sets NO directory in code;
  JAX reads the variable itself. This is how a caller outside the program
  (a chip tool whose machine is thrown away after each call, a CI job with a
  restored cache) places the cache where it survives.
- unset → ``<checkout>/.jax_cache``, derived from this package's own path.
  The directory is part of the cache key, so it must not move between runs:
  never ``~``, a temporary name, a pid or the time. ``.gitignore`` lists it.

Nothing else about the cache (entry-size or compile-time thresholds) is set
here; JAX's defaults stand.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def setup_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache will use.

    Call before the process's first compilation: JAX binds the cache at its
    first use, and a directory set after that is ignored for the rest of the
    process."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

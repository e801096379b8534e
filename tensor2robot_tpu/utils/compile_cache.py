"""One place that decides where XLA's persistent compile cache lives.

The directory is part of the cache key, so it must not move between
runs: a tempfile/pid/time-derived path never hits. Entry points call
`configure()` before their first compile.

  - `JAX_COMPILATION_CACHE_DIR` set: nothing is done. JAX reads the
    variable itself and the program sets no other directory in code.
  - unset: the cache goes to `<checkout>/.jax_compile_cache` (ignored by
    git), derived from this package's own location so every cwd and
    every process of one checkout agree on it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")


def configure() -> str:
  """Returns the cache directory in force for this process."""
  from_env = os.environ.get(ENV_VAR)
  if from_env:
    return from_env
  import jax
  jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
  return DEFAULT_DIR

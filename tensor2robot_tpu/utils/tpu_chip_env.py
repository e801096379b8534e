"""Env construction for giving one process a subset of a host's chips.

A TPU chip belongs to one process at a time, and a process that
initializes JAX claims every chip it can see: a parent that has touched
JAX holds the host's chips and a child that needs one fails or hangs.
Processes that must share a host (a Sebulba learner and its actor
processes) therefore each start with their own visibility, set BEFORE
their JAX initializes. Like cpu_mesh_env, this must stay import-safe
before JAX loads (no jax import here).
"""

from __future__ import annotations

import os
from typing import Mapping, MutableMapping, Sequence

VISIBLE_CHIPS_VAR = "TPU_VISIBLE_CHIPS"
# Chips-per-process grid by chip count, for a 2x2 four-chip host. One
# chip always comes up. Two chips must be ICI neighbours along the
# grid's second axis, and which device indices are varies by host: on
# some v5e hosts indices 0,1 fail slice init ("duplicate coordinate
# assignment"); nothing here picks the pair from the physical layout.
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def tpu_chip_env(
    chips: Sequence[int],
    base: Mapping[str, str] | None = None,
) -> MutableMapping[str, str]:
  """Returns a copy of `base` (default os.environ) under which a fresh
  interpreter's TPU runtime opens only the host chips `chips`."""
  if len(chips) not in _PROCESS_BOUNDS:
    raise ValueError(
        f"a process takes {sorted(_PROCESS_BOUNDS)} chips, got {list(chips)}")
  env = dict(os.environ if base is None else base)
  env[VISIBLE_CHIPS_VAR] = ",".join(str(int(c)) for c in chips)
  env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _PROCESS_BOUNDS[len(chips)]
  env["TPU_PROCESS_BOUNDS"] = "1,1,1"
  return env


def visible_chips(env: Mapping[str, str] | None = None) -> list[int] | None:
  """The chips `env` restricts this process to; None if unrestricted."""
  env = os.environ if env is None else env
  value = env.get(VISIBLE_CHIPS_VAR)
  if not value:
    return None
  return [int(c) for c in value.split(",")]

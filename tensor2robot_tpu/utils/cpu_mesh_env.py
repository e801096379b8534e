"""Env construction for the virtual multi-device CPU mesh.

JAX fixes its platform and device count when it first initializes, so
an entry point that needs an n-device CPU mesh (tests, the multi-chip
dry run, the chipless worker subprocesses) launches its interpreter
with JAX_PLATFORMS=cpu and --xla_force_host_platform_device_count=<n>.
This module is the single source of truth for that environment, shared
by tests/conftest.py and __graft_entry__.dryrun_multichip. It must stay
import-safe before JAX initializes (no jax import here).
"""

from __future__ import annotations

import os
from typing import Mapping, MutableMapping

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def cpu_mesh_env(
    n_devices: int,
    base: Mapping[str, str] | None = None,
) -> MutableMapping[str, str]:
  """Returns a copy of `base` (default os.environ) reconfigured so a fresh
  interpreter exposes `n_devices` virtual CPU devices."""
  env = dict(os.environ if base is None else base)
  env["JAX_PLATFORMS"] = "cpu"
  flags = [f for f in env.get("XLA_FLAGS", "").split()
           if not f.startswith(_COUNT_FLAG)]
  flags.append(f"{_COUNT_FLAG}={n_devices}")
  env["XLA_FLAGS"] = " ".join(flags)
  env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
  return env


def is_cpu_mesh_env(n_devices: int,
                    env: Mapping[str, str] | None = None) -> bool:
  """True if `env` forces a CPU backend with >= n_devices."""
  env = os.environ if env is None else env
  if env.get("JAX_PLATFORMS", "") != "cpu":
    return False
  for flag in env.get("XLA_FLAGS", "").split():
    if flag.startswith(_COUNT_FLAG + "="):
      try:
        return int(flag.split("=", 1)[1]) >= n_devices
      except ValueError:
        return False
  return False

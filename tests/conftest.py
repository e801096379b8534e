"""Test harness: an 8-device virtual CPU mesh, set up before JAX loads.

Tests want JAX_PLATFORMS=cpu with
--xla_force_host_platform_device_count=8 so collectives/sharding get real
multi-device coverage in CI (SURVEY.md §4: the reference never had this).
JAX reads both when it is first imported/initialized, and nothing pytest
loads before this file imports jax, so pytest_configure writes them into
os.environ (test subprocesses inherit the same mesh). Under --tpu the
environment is left alone: that lane runs on the machine's real chip.
"""

import os
import sys

# Repo root on sys.path so `import tensor2robot_tpu` works without install.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
  sys.path.insert(0, _REPO_ROOT)

from tensor2robot_tpu.utils.cpu_mesh_env import cpu_mesh_env

_N_DEVICES = 8


def pytest_addoption(parser):
  parser.addoption(
      "--tpu", action="store_true", default=False,
      help="Run the on-chip TPU lane: the machine's real TPU backend, "
           "only @pytest.mark.tpu tests (real Pallas kernels + "
           "per-family on-chip smokes). No TPU is a failure.")


# Minutes-long files (research-model training loops and the heaviest
# end-to-end integration suites): auto-marked `slow` so the inner loop
# can run `-m "not slow"` (~threefold faster); plain `pytest tests/`
# still runs everything (the nightly bar). test_anakin.py and
# test_faults.py moved here in round 18 — the two slowest integration
# files (~185s of the tier-1 budget between them) per the ROADMAP note
# about keeping the not-slow suite under the 1200s ceiling.
_SLOW_FILES = frozenset({
    "test_research_models.py",
    "test_research.py",
    "test_maml.py",
    "test_train_eval.py",
    "test_anakin.py",
    "test_faults.py",
})


def pytest_collection_modifyitems(config, items):
  import pytest
  on_chip = config.getoption("--tpu")
  for item in items:
    if os.path.basename(str(item.fspath)) in _SLOW_FILES:
      item.add_marker(pytest.mark.slow)
    if "tpu" in item.keywords and not on_chip:
      item.add_marker(pytest.mark.skip(
          reason="on-chip test; run with --tpu on a TPU-attached host"))
  if on_chip:
    # --tpu runs only the on-chip lane. Deselect the rest, so "skipped"
    # in that lane's summary can only mean an on-chip test did not run.
    lane = [item for item in items if "tpu" in item.keywords]
    config.hook.pytest_deselected(
        items=[item for item in items if "tpu" not in item.keywords])
    items[:] = lane


def pytest_configure(config):
  config.addinivalue_line(
      "markers", "tpu: on-chip TPU lane (run via `pytest tests/ --tpu`)")
  config.addinivalue_line(
      "markers", "slow: research-model training tests (skip with "
                 "`-m 'not slow'` for the fast inner loop)")
  if config.getoption("--tpu"):
    return
  if "jax" in sys.modules:
    raise RuntimeError(
        "jax was imported before tests/conftest.py could select the "
        "8-device CPU mesh; a pytest plugin is importing it early")
  os.environ.update(cpu_mesh_env(_N_DEVICES))

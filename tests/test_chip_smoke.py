"""Chipless contract tests for chip_smoke.py and the on-chip test lane.

Neither can succeed here (no accelerator); what CAN regress silently on
a CPU box is how they fail: a missing chip must be a prompt non-zero
exit with no result line, never a skip and never a CPU run reported as
a pass.
"""

import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _result_lines(stdout: str):
  """Stdout lines that parse as the smoke's result object."""
  found = []
  for line in stdout.splitlines():
    try:
      obj = json.loads(line)
    except ValueError:
      continue
    if isinstance(obj, dict) and "ok" in obj:
      found.append(obj)
  return found


class TestChipSmokeWithoutAChip:

  def test_cpu_platform_exits_nonzero_in_seconds_without_training(self):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    start = time.monotonic()
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    elapsed = time.monotonic() - start
    assert res.returncode not in (0, None), res.stdout[-800:]
    assert _result_lines(res.stdout) == []
    assert res.stdout.strip() == ""  # refused before any phase printed
    lines = [l for l in res.stderr.splitlines() if "chip_smoke:" in l]
    assert len(lines) == 1, res.stderr[-800:]
    assert "'tpu'" in lines[0] and "'cpu'" in lines[0]
    assert elapsed < 60, f"took {elapsed:.0f}s: it did more than refuse"

  def test_alone_in_a_directory_exits_nonzero_without_a_result(
      self, tmp_path):
    """The driver also runs the script with nothing else of the repo
    beside it: that must fail, not find some other way to 'pass'."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env=env, cwd=str(tmp_path))
    assert res.returncode not in (0, None)
    assert _result_lines(res.stdout) == []

  def test_it_is_one_process_and_catches_nothing(self):
    """One process per chip: the script starts no child, and no check
    is caught and summarised."""
    import ast
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
      source = f.read()
    tree = ast.parse(source)
    imported = {alias.name.split(".")[0] for node in ast.walk(tree)
                if isinstance(node, (ast.Import, ast.ImportFrom))
                for alias in node.names}
    imported |= {node.module.split(".")[0] for node in ast.walk(tree)
                 if isinstance(node, ast.ImportFrom) and node.module}
    assert not imported & {"subprocess", "multiprocessing"}
    assert "os.system" not in source and "os.exec" not in source
    handlers = [node.lineno for node in ast.walk(tree)
                if isinstance(node, ast.ExceptHandler)]
    assert handlers == [], f"except clauses at lines {handlers}"


class TestTpuLaneWithoutATpu:

  def test_tpu_lane_fails_rather_than_skips(self):
    """`pytest --tpu` on a CPU-only box: every on-chip test FAILS. A
    skip here is how a missing chip once read as a green lane."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(ROOT, "tests", "test_tpu.py"), "--tpu", "-q",
         "-p", "no:cacheprovider", "-k", "spatial_softmax or max_pool"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    summary = res.stdout.strip().splitlines()[-1]
    assert res.returncode == 1, res.stdout[-1500:]
    assert "2 failed" in summary or "2 errors" in summary, summary
    assert "skipped" not in summary and "passed" not in summary, summary
    assert "needs platform 'tpu'" in res.stdout

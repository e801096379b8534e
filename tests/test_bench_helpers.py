"""Device-free contract tests for bench.py's measurement helpers.

The bench itself needs the real chip; these pin the parts a driver run
depends on that CAN regress silently under CPU CI: the spread shape
every doc citation relies on, the round/artifact-name pairing
docs/ARTIFACTS.md binds, the absence of hardcoded measured constants in
emitted note strings, and that bench.py cannot hide a failure (no CPU
fallback, no swallowed phase).
"""

import ast
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_source():
  with open(os.path.join(ROOT, "bench.py")) as f:
    return f.read()


class TestSpread:

  def test_spread_shape_and_values(self):
    import bench
    out = bench._spread([3.0, 1.0, 2.0])
    assert out == {"median": 2.0, "min": 1.0, "max": 3.0, "trials": 3}

  def test_spread_single_value(self):
    import bench
    out = bench._spread([4.5])
    assert out["median"] == out["min"] == out["max"] == 4.5
    assert out["trials"] == 1

  def test_spread_rounding(self):
    import bench
    out = bench._spread([1.23456], digits=2)
    assert out["median"] == 1.23


class TestArtifactContract:

  def test_detail_file_matches_round(self):
    import bench
    assert f"r{bench.ROUND:02d}" in bench.DETAIL_FILE

  def test_artifacts_doc_names_current_round(self):
    """docs/ARTIFACTS.md is THE current-round pointer; it must agree
    with bench.py's round or every doc citation dangles."""
    import bench
    with open(os.path.join(ROOT, "docs", "ARTIFACTS.md")) as f:
      doc = f.read()
    assert f"Current round: {bench.ROUND}" in doc
    assert bench.DETAIL_FILE in doc

  def test_no_hardcoded_measured_constants_in_strings(self):
    """Emitted note strings must not bake in dated one-shot figures
    (the '1827 vs 879' anti-pattern): no 4+ digit number other than
    shape/protocol constants may appear in any string literal."""
    allowed = {"472", "1000"}  # image size; unit conversions
    tree = ast.parse(_load_bench_source())
    offenders = []
    for node in ast.walk(tree):
      if isinstance(node, ast.Constant) and isinstance(node.value, str):
        for num in re.findall(r"\d{4,}", node.value):
          if num not in allowed and not num.startswith("472"):
            offenders.append((node.lineno, num, node.value[:60]))
    assert not offenders, offenders


def _bench_tree():
  return ast.parse(_load_bench_source())


def _call_sites_inside_try(tree, callee: str):
  """Line numbers of calls to `callee` that sit under any `try:`."""
  inside = []
  for node in ast.walk(tree):
    if isinstance(node, ast.Try):
      for child in ast.walk(node):
        if (isinstance(child, ast.Call)
            and isinstance(child.func, ast.Name)
            and child.func.id == callee):
          inside.append(child.lineno)
  return inside


class TestBenchCannotHideAFailure:
  """`python bench.py` is main() in one process: it refuses a platform
  that is not `tpu`, and a phase that raises ends the run non-zero —
  nothing converts a failure into rc 0 or into a JSON field."""

  def test_cli_refuses_cpu_with_one_line_naming_the_platform(self):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    start = time.monotonic()
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode not in (0, None), res.stdout
    assert res.stdout.strip() == "", res.stdout  # no contract line
    lines = [l for l in res.stderr.splitlines() if "bench.py:" in l]
    assert len(lines) == 1, res.stderr[-800:]
    assert "'tpu'" in lines[0] and "'cpu'" in lines[0]
    assert time.monotonic() - start < 120  # refused before any phase

  def test_no_exception_handler_anywhere_in_bench(self):
    tree = _bench_tree()
    handlers = [node.lineno for node in ast.walk(tree)
                if isinstance(node, (ast.Try, ast.ExceptHandler))]
    assert handlers == [], f"try/except at bench.py lines {handlers}"

  def test_orchestrator_and_its_knobs_are_gone(self):
    src = _load_bench_source()
    for name in ("_emit_error_line", "_orchestrate", "_probe_backend",
                 "_run_inner", "T2R_BENCH_", "import subprocess"):
      assert name not in src, name
    # The default entry is main() itself and its return is the rc.
    assert "sys.exit(main())" in src

  def test_raising_phase_propagates_out_of_main(self, monkeypatch):
    import types

    import bench

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(bench.jax, "devices", lambda: [fake])

    def boom(*args, **kwargs):
      raise RuntimeError("synthetic phase failure")
    monkeypatch.setattr(bench, "_measure_config", boom)
    try:
      bench.main()
    except RuntimeError as e:
      assert "synthetic phase failure" in str(e)
    else:
      raise AssertionError("main() swallowed a phase failure")

  def test_main_refuses_cpu_before_any_phase(self, monkeypatch, capsys):
    import bench
    monkeypatch.setattr(
        bench, "_measure_config",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("a phase ran on cpu")))
    assert bench.main() == 2  # conftest: this process is on cpu
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "'cpu'" in captured.err

  def test_cost_analysis_without_flops_raises(self):
    import bench

    class _Compiled:
      def __init__(self, analysis):
        self._analysis = analysis

      def cost_analysis(self):
        return self._analysis

    assert bench._cost_analysis_flops(_Compiled({"flops": 12.0})) == 12.0
    for broken in ({}, {"flops": 0.0}):
      try:
        bench._cost_analysis_flops(_Compiled(broken))
      except (KeyError, RuntimeError):
        continue
      raise AssertionError(f"accepted cost_analysis {broken}")
    # No analytic stand-in is left to publish an MFU from.
    assert "ANALYTIC_PARITY_FLOPS" not in _load_bench_source()

  def test_contract_line_names_the_device(self):
    """Every number names the device it ran on: platform, device_kind
    and device count ride the compact line and the detail file."""
    src = _load_bench_source()
    assert src.count("**device_summary(),") == 2
    from tensor2robot_tpu.utils.device_info import device_summary
    assert device_summary() == {
        "platform": "cpu", "device_kind": "cpu", "device_count": 8}


class TestServingDetailBlock:
  """VERDICT r5 Next #3: the bench detail carries a compact serving
  measurement so a driver-only chip window also refreshes serving
  evidence. Chipless contract: the block runs on CPU at a tiny image
  size and every citable field carries the spread shape."""

  def test_compact_serving_emits_spread_fields_for_both_wires(self):
    import bench
    out = bench._bench_serving_compact(trials=2, control_steps=2,
                                       image_size=16)
    for wire in ("float32", "uint8"):
      for field in ("closed_loop_hz", "closed_loop_ms"):
        spread = out[wire][field]
        assert set(spread) == {"median", "min", "max", "trials"}
        assert spread["trials"] == 2
        assert spread["min"] <= spread["median"] <= spread["max"]
      assert out[wire]["image_bytes"] > 0
    # uint8 wire moves 4x fewer bytes than float32 — the block must
    # preserve that wire distinction or the two rows measure one thing.
    assert out["float32"]["image_bytes"] == 4 * out["uint8"]["image_bytes"]
    assert "bench_serving" in out["note"]

  def test_serving_block_failure_is_not_contained(self):
    """A failing serving measurement fails the run: the call site sits
    under no try, and its result still lands in the detail file."""
    src = _load_bench_source()
    assert "serving = _bench_serving_compact()" in src
    assert _call_sites_inside_try(
        _bench_tree(), "_bench_serving_compact") == []
    assert '"serving": serving' in src


class TestLearnerDetailBlock:
  """ISSUE 4: the bench detail carries the learner-throughput block so
  a driver-only chip window re-measures the fused-megastep-vs-host
  ratio on the real chip. Functional coverage (spread shapes, speedup,
  ledger) lives in tests/test_device_replay.py's CLI smoke — here we
  pin the wiring only: the block runs unwrapped, like every section."""

  def test_learner_block_failure_is_not_contained(self):
    src = _load_bench_source()
    assert "learner = _bench_learner_compact()" in src
    assert _call_sites_inside_try(
        _bench_tree(), "_bench_learner_compact") == []
    assert '"learner": learner' in src

  def test_compact_line_carries_learner_speedup(self):
    src = _load_bench_source()
    assert '"learner_megastep_speedup"' in src


def _expand_braces(name):
  """`a_{x,y}.b` -> [`a_x.b`, `a_y.b`] (single brace group)."""
  m = re.match(r"^(.*)\{([^}]+)\}(.*)$", name)
  if not m:
    return [name]
  return [m.group(1) + alt + m.group(3) for alt in m.group(2).split(",")]


class TestArtifactsPointerTable:
  """VERDICT r4 #4/Weak #5: docs/ARTIFACTS.md is the single
  current-round pointer; a row marked `committed` must name files that
  exist, anything else must carry an explicit absent-with-reason
  marker. Dangling pointers fail here instead of reaching the judge."""

  def _rows(self):
    with open(os.path.join(ROOT, "docs", "ARTIFACTS.md")) as f:
      doc = f.read()
    rows = []
    for line in doc.splitlines():
      if not line.startswith("|"):
        continue
      cells = [c.strip() for c in line.strip().strip("|").split("|")]
      if len(cells) >= 3 and cells[1].startswith("`"):
        rows.append(cells)
    return doc, rows

  def test_every_row_exists_or_is_explicitly_absent(self):
    _, rows = self._rows()
    assert rows, "no artifact rows parsed from docs/ARTIFACTS.md"
    problems = []
    for cells in rows:
      artifact, status = cells[1].strip("`"), cells[2]
      if status.startswith("committed"):
        for name in _expand_braces(artifact):
          if not os.path.exists(os.path.join(ROOT, name)):
            problems.append(f"{name}: marked committed but missing")
      elif not re.match(r"^absent \(.+\)$", status):
        problems.append(f"{artifact}: status neither 'committed' nor "
                        f"'absent (<reason>)': {status!r}")
    assert not problems, problems

  def test_round_number_binds_table_and_prose(self):
    """#8: the round number and the per-round filenames must move
    together — every artifact in the table carries the prose round."""
    import bench
    doc, rows = self._rows()
    assert f"Current round: {bench.ROUND}" in doc
    tag = f"r{bench.ROUND:02d}"
    for cells in rows:
      assert tag in cells[1], (
          f"artifact {cells[1]} does not carry {tag}")

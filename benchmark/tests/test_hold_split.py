"""The three readers of the turn's hold over a ring of flushes:
`flush_turn_wait_share.serve` and `transfer_hidden_share.serve` (the
`serve/turn` spans that carry `landed`), `flush_transfer_wait_share.serve`
(`serve/transfer_wait`, on the flushes whose hold the policy split).
Nothing to read where the program records none of it (an earlier
program) or the ring has dropped spans of the window."""

import pytest

from benchmark import harness
from benchmark.tests.hold_rings import CASES, SPLIT, WINDOW_S, ring_of
from tensor2robot_tpu.obs import trace as trace_lib



def _read(monkeypatch, metric, ring):
  monkeypatch.setattr(trace_lib, "get_tracer", lambda: ring)
  return harness._load_module("layer_metrics", metric).read(
      {"window": {"window_s": WINDOW_S}, "chips": 1, "trace": None})


@pytest.mark.parametrize("metric", sorted(CASES))
def test_declared_on_the_serving_cell(metric):
  layer, better, _ = CASES[metric]
  declared = {m["name"]: m for m in harness.load_cell(
      "qtopt_serve_closed64").spec["per_layer"]}[metric]
  assert declared["source"] == "program_span"
  assert declared["unit"] == "%"
  assert (declared["layer"], declared["better"]) == (layer, better)
  assert declared["moves"] == "serve_actions_per_s"
  assert declared["workloads"] == ["qtopt_serve_closed64"]


@pytest.mark.parametrize("metric, flushes, reading", [
    pytest.param(metric, flushes, reading, id=f"{metric}-{i}")
    for metric, (_, _, cases) in sorted(CASES.items())
    for i, (flushes, reading) in enumerate(cases)])
def test_reading_over_a_ring_of_flushes(monkeypatch, metric, flushes, reading):
  value = _read(monkeypatch, metric, ring_of(flushes))
  assert value == (None if reading is None else pytest.approx(reading))


@pytest.mark.parametrize("metric", sorted(CASES))
def test_a_ring_that_wrapped_inside_the_window_reads_nothing(monkeypatch,
                                                             metric):
  whole = _read(monkeypatch, metric, ring_of([SPLIT] * 2))
  assert whole is not None
  old = {"name": "serve/enqueue", "ts_s": 50.0, "dur_s": 0.001}
  assert _read(monkeypatch, metric,
               ring_of([SPLIT] * 2, dropped=7, before=[old])) == whole
  assert _read(monkeypatch, metric, ring_of([SPLIT] * 2, dropped=7)) is None

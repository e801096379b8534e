"""The trace reducer on a small trace recorded on the chip (35
dispatches of the flagship train step at a tiny preset, TPU v5e,
PR 29) and on hand-made intervals."""

import os

import pytest

from benchmark.trace import reduce as reduce_lib

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "trace", "fixtures",
    "qtopt_train_tiny.xplane.pb")


@pytest.fixture(scope="module")
def loaded():
  return reduce_lib.load(FIXTURE)


def test_finds_the_device_plane_and_the_harness_spans(loaded):
  assert list(loaded["devices"]) == ["/device:TPU:0"]
  assert len(loaded["devices"]["/device:TPU:0"]) == 13195
  names = {name for name, _, _ in loaded["spans"]}
  assert names == {"bench/dispatch", "bench/readback", "bench/drain"}


def test_busy_union_top_ops_and_gaps(loaded):
  summary = reduce_lib.summarize(loaded)
  assert summary["busy_s"] == pytest.approx(0.008676468, rel=1e-6)
  # 35 dispatches in a 0.0518 s window: the device idles most of it.
  assert summary["busy_s"] < 0.0518
  top = summary["device_ops"]
  assert len(top) == 10
  assert top[0][0] == "%convert_reduce_fusion.29 fusion"
  assert all(len(name) <= 64 for name, _ in top)
  # Self times: the table never exceeds the busy union.
  ops = loaded["devices"]["/device:TPU:0"]
  assert sum(reduce_lib.self_seconds(ops).values()) == pytest.approx(
      summary["busy_s"], rel=1e-3)
  gaps = dict(summary["idle_gaps"])
  assert max(gaps, key=gaps.get) == "bench/dispatch"
  assert sum(gaps.values()) == pytest.approx(0.0408, abs=2e-3)


def test_busy_intervals_merge_overlaps_and_nesting():
  ops = [("a", 0, 10), ("b", 5, 12), ("c", 20, 30), ("d", 22, 25)]
  assert reduce_lib.busy_intervals(ops) == [[0, 12], [20, 30]]


def test_self_seconds_takes_the_body_out_of_the_loop():
  ops = [("%while.1 = () while()", 0.0, 100e9),
         ("%fusion.2 = f32[] fusion()", 10e9, 40e9),
         ("%fusion.2 = f32[] fusion()", 50e9, 80e9)]
  assert reduce_lib.self_seconds(ops) == {
      "%while.1": 40.0, "%fusion.2": 60.0}


def test_short_name():
  assert reduce_lib.short_name(
      "%convert.108 = bf16[2,16,4]{1,2,0} convert(f32[2,16,4] %p)"
  ) == "%convert.108"
  assert reduce_lib.short_name(
      "%loop_fusion.3 = f32[8]{0} fusion(f32[8] %p), kind=kLoop"
  ) == "%loop_fusion.3 fusion"


def test_a_trace_with_no_device_op_reduces_to_nothing():
  assert reduce_lib.summarize({"devices": {"/device:TPU:0": []},
                               "spans": []}) is None

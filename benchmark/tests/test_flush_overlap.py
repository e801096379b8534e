"""`flush_overlap_share.serve` over a ring of `serve/flush` spans: the
share with `in_flight` >= 1, and nothing to read where no span carries
the attr (an earlier program) or the ring is empty."""

import pytest

from benchmark import harness
from benchmark.trace import program_spans
from tensor2robot_tpu.obs import trace as trace_lib

NAME = "flush_overlap_share.serve"
RUN = {"window": {"window_s": 1.0}, "chips": 1, "trace": None}


def _read(in_flight):
  """The reader over a ring holding one flush per entry of `in_flight`
  (None: a span without the attr) and spans of other names."""
  tracer = trace_lib.get_tracer()
  tracer.clear()
  for value in in_flight:
    attrs = {} if value is None else {"in_flight": value}
    with tracer.span(program_spans.FLUSH, batch=32, **attrs):
      with tracer.span("serve/dispatch", in_flight=1):
        pass
  return harness._load_module("layer_metrics", NAME).read(RUN)


def test_declared_on_the_serving_cell():
  declared = {m["name"]: m for m in harness.load_cell(
      "qtopt_serve_closed64").spec["per_layer"]}
  assert declared[NAME]["source"] == "program_span"
  assert declared[NAME]["moves"] == "serve_actions_per_s"
  assert declared[NAME]["workloads"] == ["qtopt_serve_closed64"]


@pytest.mark.parametrize("in_flight, share", [
    ([0, 1, 1, 1], 75.0), ([0, 0], 0.0), ([1], 100.0),
    ([0, 1, None], 50.0)])
def test_share_of_flushes_popped_beside_another(in_flight, share):
  assert _read(in_flight) == pytest.approx(share)


@pytest.mark.parametrize("in_flight", [[None, None], []])
def test_nothing_to_read_without_the_attr(in_flight):
  assert _read(in_flight) is None

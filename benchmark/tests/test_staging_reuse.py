"""`staging_reuse_share.serve` over a ring of `serve/stack` spans: the
share with `reused` >= 1, and nothing to read where no span carries the
attr (an earlier program) or the ring is empty."""

import pytest

from benchmark import harness
from benchmark.trace import program_spans
from tensor2robot_tpu.obs import trace as trace_lib

NAME = "staging_reuse_share.serve"
RUN = {"window": {"window_s": 1.0}, "chips": 1, "trace": None}


def _read(reused):
  """The reader over a ring holding one flush per entry of `reused`
  (None: a `serve/stack` span without the attr); the spans around it
  carry the attr too and are not its to read."""
  tracer = trace_lib.get_tracer()
  tracer.clear()
  for value in reused:
    attrs = {} if value is None else {"reused": value}
    with tracer.span(program_spans.FLUSH, batch=32, reused=1):
      with tracer.span("serve/stack", rows=32, bytes=1, **attrs):
        pass
      with tracer.span("serve/pad", bucket=32, reused=0):
        pass
  return harness._load_module("layer_metrics", NAME).read(RUN)


def test_declared_on_the_serving_cell():
  declared = {m["name"]: m for m in harness.load_cell(
      "qtopt_serve_closed64").spec["per_layer"]}
  assert declared[NAME]["source"] == "program_span"
  assert declared[NAME]["layer"] == "CEM policy"
  assert declared[NAME]["moves"] == "serve_actions_per_s"
  assert declared[NAME]["workloads"] == ["qtopt_serve_closed64"]


@pytest.mark.parametrize("reused, share", [
    ([1, 1, 1], 100.0), ([0, 1, 1, 1], 75.0), ([0, 0], 0.0),
    ([0, 1, None], 50.0)])
def test_share_of_flushes_stacked_into_a_kept_array(reused, share):
  assert _read(reused) == pytest.approx(share)


@pytest.mark.parametrize("reused", [[None, None], []])
def test_nothing_to_read_without_the_attr(reused):
  assert _read(reused) is None

"""The per-layer metrics of source `program_span`, read from the
program's own ring after a tiny run of each kind of cell (untraced: the
ring is filled either way), and nothing to read on an empty ring."""

import json

import jax
import pytest

from benchmark import harness
from benchmark.tests import tiny
from benchmark.trace import program_spans
from tensor2robot_tpu.obs import trace as trace_lib

SERVE = ("queue_wait_share.serve", "dispatcher_busy_share.serve",
         "flush_assemble_share.serve", "flush_put_share.serve",
         "flush_device_wait_share.serve")
TRAIN = ("dispatch_host_ms.train",)


def _read(name, run):
  return harness._load_module("layer_metrics", name).read(run)


def _run(cell, devices, capsys):
  """A hand-built `run` as the harness hands one to `read`: the window
  from the run's own `[bench] window` line."""
  trace_lib.get_tracer().clear()
  result = tiny.run(cell, devices)
  assert result["correct"], result["compared"]
  (line,) = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[bench] window ")]
  window = json.loads(line[len("[bench] window "):])
  return {"cell": cell, "window": window, "chips": len(devices),
          "trace": None}


def test_declared_with_a_reader_each():
  declared = {m["name"]: m for m in harness.load_cell(
      "qtopt_serve_closed64").spec["per_layer"]}
  for name in SERVE + TRAIN:
    assert declared[name]["source"] == "program_span"
    assert callable(harness._load_module("layer_metrics", name).read)


def test_serving_readers_after_a_tiny_run(capsys):
  devices = jax.devices()[:2]
  run = _run(tiny.serve_cell(), devices, capsys)
  values = {name: _read(name, run) for name in SERVE}
  assert all(0.0 < v <= 100.0 for v in values.values()), values
  phases = sum(values[name] for name in SERVE[2:])
  assert 50.0 < phases <= 100.0, values
  spans = program_spans.window_spans(run, program_spans.FLUSH)
  flushes = program_spans.durations(spans, program_spans.FLUSH)
  # The window's flushes and no warm-up: every phase span of the window
  # lies in a flush, and the flushes answered what the window counted.
  for name in ("serve/stack", "serve/put", "serve/readback"):
    assert len(program_spans.durations(spans, name)) == len(flushes)
  assert not program_spans.durations(spans, "serve/compile")
  assert sum(s["batch"] for s in spans
             if s["name"] == program_spans.FLUSH) == run["window"]["attempted"]
  assert all(_read(name, run) is None for name in TRAIN)


@pytest.mark.parametrize("workload, size", [
    ("qtopt_train_resident", dict(image=48, batch=8)),
    ("grasp2vec_train_resident", dict(image=32, batch=4))])
def test_train_reader_after_a_tiny_run(workload, size, capsys):
  run = _run(tiny.train_cell(workload, **size), jax.devices()[:1], capsys)
  value = _read("dispatch_host_ms.train", run)
  assert 0.0 < value < 1e3 * run["window"]["window_s"]
  found = program_spans.durations(
      program_spans.window_spans(run, program_spans.DISPATCH),
      program_spans.DISPATCH)
  # The window's dispatches; set-up's two may start inside the drain's
  # length before it.
  done = run["window"]["attempted"]
  assert done <= len(found) <= done + 2
  assert all(_read(name, run) is None for name in SERVE)


def test_nothing_to_read_on_an_empty_ring():
  trace_lib.get_tracer().clear()
  run = {"window": {"window_s": 1.0}, "chips": 1, "trace": None}
  for name in SERVE + TRAIN:
    assert _read(name, run) is None


def test_spans_of_an_earlier_program_give_none_not_zero():
  """A program with `serve/flush` but none of this PR's phase spans or
  attrs (the parent commit): only the dispatcher's busy share reads."""
  tracer = trace_lib.get_tracer()
  tracer.clear()
  with tracer.span(program_spans.FLUSH, batch=2):
    pass
  run = {"window": {"window_s": 1.0}, "chips": 1, "trace": None}
  values = {name: _read(name, run) for name in SERVE}
  assert values.pop("dispatcher_busy_share.serve") is not None
  assert all(v is None for v in values.values()), values

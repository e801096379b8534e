"""The controls at a size a test run can hold: the reference one
precision below what the configuration states, and each planted fault,
put in the program's place, fail at least one of the cell's numbers by
the cell's own limits (benchmark/limits/). On the chip, at the cells'
own sizes, they were read on three seeds each (PERF.md section 2)."""

import importlib

import jax
import pytest

from benchmark.tests import tiny

CELLS = {
    "qtopt_train_resident": lambda: tiny.train_cell(
        "qtopt_train_resident", image=64, batch=16, steps=2),
    "qtopt_serve_closed64": lambda: tiny.serve_cell(
        "qtopt_serve_closed64", image=64),
    "grasp2vec_train_resident": lambda: tiny.train_cell(
        "grasp2vec_train_resident", image=64, batch=16, steps=2),
}


@pytest.fixture(scope="module", params=sorted(CELLS))
def finished(request):
  cell = CELLS[request.param]()
  driver = importlib.import_module(
      "benchmark.drivers." + cell.traffic["driver"])
  session = driver.Session(cell, 2147483777, jax.devices()[:1],
                           jax.profiler.TraceAnnotation)
  session.run_window(0.3)
  session.release()
  return cell, session


def test_every_control_comes_out_not_correct(finished):
  cell, session = finished
  for name, kwargs in session.controls().items():
    rows = session.check(cell.limits, **kwargs)
    over = [n for n, value, limit in rows
            if limit is not None and not value <= limit]
    assert over, (name, rows)


def test_every_limit_of_the_cell_is_of_a_number_the_driver_reads(finished):
  cell, session = finished
  rows = session.check(cell.limits)
  assert set(cell.limits) <= {name for name, _, _ in rows}
  for name, value, limit in rows:
    assert value == value and (limit is None or limit >= 0), name

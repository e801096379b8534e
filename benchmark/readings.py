"""python3 benchmark/readings.py --workload <name> --seeds 1,2,3 [--controls 1,2,3] [--seconds 3]
    [--stand-ins name,name] [--extra '{"name": {check's keywords}}']

The readings a cell's limits are set from, many seeds in one process:
for each seed in --seeds one run of the cell (a short window) and the
numbers it compared; for each seed in --controls the same numbers with
the control and with each planted fault in the program's place
(Session.controls() names them; --stand-ins keeps some of them, --extra
adds others, such as the reference in the stated precision as a witness).
Writes chiprun_out/readings_<cell>.json.
Not part of a benchmark run.
"""

import time

_T0 = time.time()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seeds", default="")
  parser.add_argument("--controls", default="")
  parser.add_argument("--seconds", type=float, default=3.0)
  parser.add_argument("--stand-ins", default="")
  parser.add_argument("--extra", default="{}")
  args = parser.parse_args(argv)

  import importlib
  from benchmark import harness
  cell = harness.load_cell(args.workload)
  harness.configure_jax()
  import jax
  devices = harness.find_chips(cell)
  if devices is None:
    return 2
  driver = importlib.import_module(
      "benchmark.drivers." + cell.traffic["driver"])
  span = jax.profiler.TraceAnnotation
  rows = []
  ints = lambda text: [int(s) for s in text.split(",") if s]
  control_seeds = ints(args.controls)
  for seed in sorted(set(ints(args.seeds)) | set(control_seeds)):
    start = time.time()
    session = driver.Session(cell, seed, devices, span)
    window = session.run_window(args.seconds)
    peak = harness.memory_peak(devices)
    session.release()
    row = {"seed": seed, "as": "program", "attempted": window["attempted"],
           "failed": window["failed"], "memory_peak_bytes": peak,
           "metrics": window["metrics"]}
    row["compared"] = {name: float(value)
                       for name, value, _ in session.check(cell.limits)}
    row["seconds"] = round(time.time() - start, 1)
    rows.append(row)
    print(json.dumps(row), flush=True)
    if seed in control_seeds:
      stand_ins = dict(session.controls(), **json.loads(args.extra))
      keep = [n for n in args.stand_ins.split(",") if n] or list(stand_ins)
      for name, kwargs in stand_ins.items():
        if name not in keep:
          continue
        start = time.time()
        row = {"seed": seed, "as": name}
        row["compared"] = {
            n: float(v) for n, v, _ in session.check(cell.limits, **kwargs)}
        row["seconds"] = round(time.time() - start, 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
    del session
  out = os.path.join(harness.ROOT, "chiprun_out")
  os.makedirs(out, exist_ok=True)
  with open(os.path.join(out, f"readings_{cell.name}.json"), "w") as f:
    json.dump(rows, f, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())

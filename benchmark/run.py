"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell on the machine it is started on. The last line of
standard output is the result object; without a TPU, or with fewer
chips than the cell asks for, there is no result and the exit code is 2.
See benchmark/README.md.
"""

import time

_T0 = time.time()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--seconds", type=float, default=None)
  parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = parser.parse_args(argv)

  from benchmark import harness
  cell = harness.load_cell(args.workload)
  seconds = (args.seconds if args.seconds is not None
             else cell.spec["run_seconds"])
  cache_dir = harness.configure_jax()
  devices = harness.find_chips(cell)
  if devices is None:
    return 2
  harness.say("run", {"workload": cell.name, "seed": args.seed,
                      "seconds": seconds, "trace": args.trace,
                      "device": harness.device_record(devices),
                      "compile_cache": cache_dir})
  result = harness.run_cell(cell, args.seed, seconds, bool(args.trace),
                            devices, _T0)
  harness.print_compared(result, sys.stdout)
  sys.stdout.flush()
  harness.print_compared(result, sys.stderr)
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())

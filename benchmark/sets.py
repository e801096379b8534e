"""python3 benchmark/sets.py --workload <cell> [--runs 6] [--sets 2] [--seconds S] [--traced 0]

Measures a cell the way a bound is set: `--sets` sets of `--runs` runs,
each run a fresh process, the same seeds in every set; for each
end-to-end metric each set's spread (distance between the first and third
quartile, `statistics.quantiles(n=4)`, as a share of the median) and
median. `--traced N` adds N traced runs on further seeds. Stays off JAX
itself, so every child has the chip to itself. Writes
chiprun_out/sets_<cell>.json. Not part of a benchmark run.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_once(command, workload, seed, seconds, trace):
  start = time.time()
  proc = subprocess.run(
      command + ["--workload", workload, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", str(trace)],
      cwd=ROOT, capture_output=True, text=True)
  lines = proc.stdout.strip().splitlines()
  notes = [l for l in lines if l.startswith((
      "[bench] setup", "[bench] check", "[bench] read_not_compared",
      "[bench] served", "[bench] window"))]
  if proc.returncode != 0 or not lines or not lines[-1].startswith("{"):
    print(proc.stdout[-3000:], proc.stderr[-3000:], flush=True)
    raise SystemExit(f"run failed: seed {seed} rc {proc.returncode}")
  result = json.loads(lines[-1])
  result["seed"], result["wall_s"] = seed, round(time.time() - start, 1)
  result["notes"] = notes
  return result


def spread(values):
  q1, _, q3 = statistics.quantiles(values, n=4)
  return (q3 - q1) / statistics.median(values)


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument("--workload", required=True)
  parser.add_argument("--runs", type=int, default=6)
  parser.add_argument("--sets", type=int, default=2)
  parser.add_argument("--seconds", type=float, default=None)
  parser.add_argument("--traced", type=int, default=0)
  parser.add_argument("--first-seed", type=int, default=2147480000)
  args = parser.parse_args()
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    spec = json.load(f)
  seconds = args.seconds or spec["run_seconds"]
  command = [sys.executable] + spec["command"][1:]
  seeds = [args.first_seed + 7919 * i for i in range(args.runs)]
  out = {"workload": args.workload, "seconds": seconds, "sets": [],
         "traced": []}
  for s in range(args.sets):
    rows = []
    for seed in seeds:
      row = run_once(command, args.workload, seed, seconds, 0)
      rows.append(row)
      print(json.dumps({"set": s, "seed": seed, "correct": row["correct"],
                        "wall_s": row["wall_s"],
                        **{k: v["value"] for k, v in row["metrics"].items()},
                        "compared": {k: v["value"] for k, v in
                                     row["compared"].items()}}), flush=True)
    out["sets"].append(rows)
  for i in range(args.traced):
    row = run_once(command, args.workload, args.first_seed - 1 - i,
                   seconds, 1)
    out["traced"].append(row)
    print(json.dumps({"traced": i, "correct": row["correct"],
                      "wall_s": row["wall_s"], "metrics": row["metrics"],
                      "device": row["device"],
                      "breakdown": row.get("breakdown")}), flush=True)
  summary = {}
  for name in out["sets"][0][0]["metrics"]:
    per_set = [[r["metrics"][name]["value"] for r in rows]
               for rows in out["sets"]]
    # The first run of the first set compiles; set-up is judged without it.
    if name == "setup_s":
      per_set[0] = per_set[0][1:]
    summary[name] = {
        "medians": [statistics.median(v) for v in per_set],
        "spreads": [spread(v) for v in per_set if len(v) >= 2],
    }
  out["summary"] = summary
  print(json.dumps({"summary": summary}), flush=True)
  os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
  with open(os.path.join(ROOT, "chiprun_out",
                         f"sets_{args.workload}.json"), "w") as f:
    json.dump(out, f)


if __name__ == "__main__":
  main()

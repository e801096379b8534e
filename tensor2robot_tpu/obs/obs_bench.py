"""Obs bench: the observability spine exercised end to end — OBS_r13.

The ISSUE 11 acceptance instrument, extended for round 13 (ISSUE 12):
after the three original phases it exports the process registry
snapshot, runs the watchdog positive/negative controls, then runs
``obs/aggregate.py`` over its OWN phase artifacts and asserts the
merged view is self-consistent — so the committed artifact validates
the aggregator, not just the spine. One run, ONE JSON line:

1. **replay** — the replay-smoke protocol (the r10 shape:
   ``run_qtopt_replay --smoke --anakin --mesh DP,1`` built via the
   CLI's own ``build_config``) with the loop's ``ExecutableLedger``
   collecting per-executable dispatch counts + wall seconds joined with
   ``cost_analysis`` FLOPs/bytes → the per-executable device-time-share
   / estimated-MFU attribution block. Shares sum to <= 1.0 (sequential
   host dispatch windows over the run's wall clock) and every
   executable the smoke dispatched appears exactly once.
2. **host_loop** — a short host-path loop (threaded collectors +
   per-step sample/label/train): the configuration whose act / extend /
   learn stages are distinct host phases, so the exported Chrome trace
   carries >= 1 span per loop stage (the fused anakin path folds
   act/step/extend/learn into ONE ``learn/anakin_step`` span by
   construction — that is the point of fusing).
3. **serve** — a FleetRouter window over every device (per-device
   ledger rows via the policies' ``@device`` keys), live traffic for
   ``serve/flush`` spans, then an INJECTED SLO breach under
   ``hold_flushes()``: a capacity burst whose sheds trigger the flight
   recorder — the dump is schema-validated here and by tier-1.
4. **trace / registry / flightrec** — the Chrome-trace export (valid
   JSON, per-stage span counts), the process registry snapshot, and
   the breach dump's path + schema.
5. **watchdog** (round 13) — an injected stall (a busy component that
   never progresses) must produce a schema-valid ``watchdog_stall``
   flight-recorder dump, and a healthy beating component must produce
   ZERO events (the false-positive negative control; deadlines scale
   with the cpu_count >= 4 gating convention).
6. **fleetobs** (round 13) — ``aggregate_logdir`` over this run's own
   logdir: the merged view's shed rollup must be consistent (global
   counters == per-class sums across sources), the breach request's
   correlation timeline must link enqueue → flush → dispatch in the
   merged trace, and the hosts_merged / stall counts land in the
   artifact's compact keys. The MULTI-process version of this merge is the
   separate committed FLEETOBS artifact (bin/obs_aggregate --smoke).

HONESTY CAVEAT (mirrors MULTICHIP/FLEET): chipless, the mesh is 8
virtual CPU devices sharing this host's cores — `estimated_mfu` is
null (no CPU peak-FLOPs model) and shares are host wall-clock
attribution, structural evidence rather than chip rates. Real-chip
attribution is not measured (ROADMAP D9).
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import time
from typing import Dict, Optional

from tensor2robot_tpu.serving.slo import SLOClass


def _largest_pow2_dp(n_devices: int, cap: int = 8) -> int:
  dp = 1
  while dp * 2 <= min(n_devices, cap):
    dp *= 2
  return dp


def _run_replay_phase(anakin: bool, steps: int, mesh_dp: int,
                      logdir: str, seed: int) -> Dict:
  """One ReplayTrainLoop run (the smoke protocol) + its attribution."""
  import jax
  import optax

  from tensor2robot_tpu.bin.run_qtopt_replay import build_config
  from tensor2robot_tpu.replay.loop import ReplayTrainLoop
  from tensor2robot_tpu.replay.smoke import TinyQCriticModel

  config = build_config(
      smoke=True, seed=seed, device_resident=anakin, anakin=anakin,
      mesh=(mesh_dp, 1) if anakin else (0, 1))
  if not anakin:
    # Host-path phase: short, stage-diverse, still off-policy end to
    # end — sized for span coverage, not for the learning bar (the
    # replay phase and tier-1's smokes carry that).
    from dataclasses import replace
    config = replace(config, capacity=256, min_fill=64,
                     eval_every=max(8, steps // 2),
                     log_every=max(4, steps // 4))
  model = TinyQCriticModel(
      image_size=config.image_size, action_size=config.action_size,
      optimizer_fn=lambda: optax.adam(config.learning_rate))
  loop = ReplayTrainLoop(config, logdir, model=model)
  start = time.perf_counter()
  results = loop.run(steps)
  wall = time.perf_counter() - start
  attribution = loop.obs_ledger.attribution(
      wall_seconds=wall, device_kind=jax.devices()[0].device_kind)
  return {
      "protocol": ("run_qtopt_replay --smoke --anakin "
                   f"--mesh {mesh_dp},1" if anakin
                   else "run_qtopt_replay --smoke (host path, reduced)"),
      "steps": results["steps"],
      "eval_td_reduction": results["eval_td_reduction"],
      "compile_counts": results["compile_counts"],
      "mesh_shape": results.get("mesh_shape"),
      "wall_seconds": round(wall, 3),
      "attribution": attribution,
  }


def _run_serve_phase(duration_s: float, ladder_sizes, max_queue: int,
                     dump_dir: str, seed: int) -> Dict:
  """Router traffic + the injected hold_flushes SLO breach."""
  import jax
  import numpy as np

  from tensor2robot_tpu.obs import flight_recorder as flight_lib
  from tensor2robot_tpu.obs import ledger as ledger_lib
  from tensor2robot_tpu.serving.router import FleetRouter
  from tensor2robot_tpu.serving.smoke import TinyQPredictor
  from tensor2robot_tpu.serving.stats import ServingStats

  recorder = flight_lib.get_recorder()
  recorder.configure(dump_dir=dump_dir, min_dump_interval_s=1.0)

  devices = jax.devices()
  predictor = TinyQPredictor(seed=seed)
  stats = ServingStats()
  ledger = ledger_lib.ExecutableLedger()
  router = FleetRouter(
      predictor, devices=devices, num_samples=16, num_elites=4,
      iterations=2, ladder_sizes=ladder_sizes, max_queue=max_queue,
      dispatch_margin_ms=20.0, stats=stats, seed=seed, ledger=ledger)
  images = [predictor.make_image(seed + i) for i in range(16)]
  compile_start = time.perf_counter()
  router.warmup(predictor.make_image)
  warmup_s = time.perf_counter() - compile_start

  interactive = SLOClass("interactive", priority=1, deadline_ms=250.0)
  batch_class = SLOClass("batch", priority=0, deadline_ms=2000.0)
  serve_start = time.perf_counter()
  with router:
    # Live window: steady paced traffic through the routed fleet. A
    # contended host may shed some of it (counted, not fatal — that is
    # the serving layer's contract).
    futures = []
    i = 0
    stop_at = time.perf_counter() + duration_s
    while time.perf_counter() < stop_at:
      futures.append(router.submit(images[i % len(images)],
                                   slo=interactive))
      i += 1
      time.sleep(0.01)
    completed = 0
    for future in futures:
      try:
        future.result(timeout=30)
        completed += 1
      except Exception:
        pass

    # INJECTED SLO BREACH under held flushes (the FLEET overload-burst
    # idiom): admission/shedding become a pure function of arrivals +
    # the queue bound, the lowest-priority burst sheds, and the first
    # shed triggers the flight-recorder dump being validated.
    burst = 2 * max_queue * len(router.replicas)
    breach_futures = []
    with contextlib.ExitStack() as stack:
      for replica in router.replicas:
        stack.enter_context(replica.batcher.hold_flushes())
      for j in range(burst):
        breach_futures.append(
            router.submit(images[j % len(images)], slo=batch_class))
    shed = 0
    for future in breach_futures:
      try:
        future.result(timeout=60)
      except Exception:
        shed += 1
  serve_wall = time.perf_counter() - serve_start

  snapshot = stats.snapshot()
  counts = ledger.compile_counts
  expected = len(devices) * len(tuple(ladder_sizes))
  ledger_ok = (len(counts) == expected
               and all(value == 1 for value in counts.values()))
  dump_path = recorder.last_dump_path
  dump = None
  if dump_path and os.path.exists(dump_path):
    with open(dump_path) as f:
      payload = json.load(f)
    dump = {
        "path": os.path.basename(dump_path),
        "schema": payload.get("schema"),
        "reason": payload.get("reason"),
        "events": len(payload.get("events", [])),
    }
  return {
      "devices": len(devices),
      "bucket_ladder": [int(size) for size in ladder_sizes],
      "warmup_compile_s": round(warmup_s, 2),
      "requests_completed": completed,
      "breach": {
          "burst": burst,
          "shed": shed,
          "shed_total": snapshot.get("shed_total", 0),
          "flightrec": dump,
      },
      "attribution": ledger.attribution(
          wall_seconds=serve_wall,
          device_kind=devices[0].device_kind),
      "compile_counts": counts,
      "ledger_ok": bool(ledger_ok),
  }


def measure_obs(
    replay_steps: int = 300,
    host_steps: int = 40,
    serve_duration_s: float = 2.0,
    mesh_dp: Optional[int] = None,
    ladder_sizes=(1, 2, 4),
    max_queue: int = 8,
    seed: int = 0,
    logdir: Optional[str] = None,
) -> Dict:
  """Runs the full protocol (replay/host/serve phases + the registry
  export, watchdog controls, and aggregator self-check); returns the
  OBS_r13 artifact dict."""
  import jax

  from tensor2robot_tpu.obs import trace as trace_lib

  logdir = logdir or tempfile.mkdtemp(prefix="obs_bench_")
  devices = jax.devices()
  device_kind = devices[0].device_kind
  dp = mesh_dp or _largest_pow2_dp(len(devices))

  replay = _run_replay_phase(
      anakin=True, steps=replay_steps, mesh_dp=dp,
      logdir=os.path.join(logdir, "replay"), seed=seed)
  host_loop = _run_replay_phase(
      anakin=False, steps=host_steps, mesh_dp=1,
      logdir=os.path.join(logdir, "host"), seed=seed + 1)
  serve = _run_serve_phase(
      serve_duration_s, ladder_sizes, max_queue,
      dump_dir=os.path.join(logdir, "serve"), seed=seed + 2)

  tracer = trace_lib.get_tracer()
  trace_path = os.path.join(logdir, "trace.json")
  tracer.export_chrome_trace(trace_path)
  stage_counts = tracer.stage_counts()

  from tensor2robot_tpu.obs import registry as registry_lib
  registry_snapshot = {
      key: value
      for key, value in registry_lib.get_registry().snapshot().items()
      if not key.endswith(("/p90", "/max", "/mean"))}

  # Round 13: watchdog controls + the aggregator run over THIS run's
  # own artifacts (metrics.jsonl from the replay/host phases, the
  # registry snapshot exported here, the Chrome trace, the breach +
  # watchdog flightrec dumps) — so the committed artifact proves the
  # MERGE, not just the spine. The multi-process form of the same
  # merge is the separate FLEETOBS artifact (bin/obs_aggregate).
  from tensor2robot_tpu.obs import aggregate as aggregate_lib
  registry_lib.get_registry().export_snapshot(
      os.path.join(logdir, "registry.json"))
  watchdog = aggregate_lib.watchdog_controls(logdir, ci=True)
  fleet = aggregate_lib.aggregate_logdir(logdir)
  assert fleet["slo"]["consistent"], fleet["slo"]
  assert fleet["slo"]["shed_total"] >= serve["breach"]["shed"], (
      fleet["slo"], serve["breach"])
  assert fleet["trace"]["linked_serve_timelines"] >= 1, fleet["trace"]
  assert watchdog["injected_stall"]["ok"], watchdog
  assert watchdog["healthy_control"]["ok"], watchdog
  fleetobs = {
      "hosts_merged": fleet["hosts_merged"],
      "inputs": fleet["inputs"],
      "slo": fleet["slo"],
      "trace": {key: fleet["trace"][key]
                for key in ("file", "events", "request_ids_seen",
                            "flows_linked", "linked_serve_timelines",
                            "example_timeline")},
      "flightrec_reasons": fleet["flightrec"]["reasons"],
      "stragglers": fleet["stragglers"],
      "consistent": fleet["slo"]["consistent"],
  }

  return {
      "round": 13,
      "metric": ("observability spine: per-executable device-time "
                 "attribution + spans + metric registry + flight "
                 "recorder across the production loop, plus (r13) "
                 "correlation-linked request timelines, the fleet "
                 "aggregator self-check, and the stall watchdog "
                 "controls"),
      "device_kind": device_kind,
      "virtual_mesh": device_kind.lower() == "cpu",
      "devices": len(devices),
      "mesh_dp": dp,
      "replay": replay,
      "host_loop": host_loop,
      "serve": serve,
      "trace": {
          "file": os.path.basename(trace_path),
          "logdir": logdir,
          "spans_total": tracer.total_spans,
          "stage_counts": stage_counts,
      },
      "registry": registry_snapshot,
      "watchdog": watchdog,
      "fleetobs": fleetobs,
      "flightrec_schema": "t2r-flightrec-1",
      "note": (
          "Attribution shares are host wall-clock dispatch windows "
          "over each phase's run window (sum <= 1.0; the remainder is "
          "host work outside any executable). estimated_mfu is null "
          "with virtual_mesh=true — no peak-FLOPs model for this host "
          "(the MULTICHIP caveat applied to utilization); real-chip "
          "attribution is not measured. "
          "The Chrome trace and flight-recorder dump live in the "
          "run's logdir (paths are run-local, basenames recorded "
          "here); the fused anakin path reports act/step/extend/learn "
          "as ONE learn/anakin_step span by construction — the "
          "host_loop phase is where the act/extend/learn stages are "
          "separate host spans."),
  }


def main(argv=None) -> None:
  """CLI: ONE JSON line (the bench contract). --smoke bootstraps the
  8-virtual-device CPU mesh (re-exec with the canonical env) and runs
  the committed OBS_r13 protocol; --ci is the reduced tier-1 lane."""
  import argparse
  import sys

  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--smoke", action="store_true",
                      help="chipless committed-artifact lane (OBS_r13): "
                           "8 virtual CPU devices, full protocol")
  parser.add_argument("--ci", action="store_true",
                      help="reduced chipless lane for tier-1 tests")
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--logdir", default=None,
                      help="trace/flightrec output dir (default: a "
                           "tempdir; printed in the artifact)")
  parser.add_argument("--out", default=None,
                      help="also write the JSON line to this file")
  args = parser.parse_args(argv)
  if args.smoke or args.ci:
    from tensor2robot_tpu.utils.cpu_mesh_env import (cpu_mesh_env,
                                                     is_cpu_mesh_env)
    if not is_cpu_mesh_env(8):
      if argv is not None:
        raise RuntimeError(
            "--smoke/--ci need the 8-virtual-device CPU mesh "
            "configured before JAX initializes; call main() with "
            "argv=None (the CLI re-execs itself).")
      os.execve(sys.executable,
                [sys.executable, "-m", "tensor2robot_tpu.obs.obs_bench",
                 *sys.argv[1:]],
                cpu_mesh_env(8))
  kwargs = dict(seed=args.seed, logdir=args.logdir)
  if args.ci:
    kwargs.update(replay_steps=40, host_steps=12, serve_duration_s=1.0)
  results = measure_obs(**kwargs)
  line = json.dumps(results)
  if args.out:
    with open(args.out, "w") as f:
      f.write(line + "\n")
  print(line)


if __name__ == "__main__":
  main()

"""Serving benchmark: QT-Opt CEM control, single-robot and fleet modes.

Single-robot mode (default; the classic `SERVING_r*` fields): per
control step, CEMPolicy ships one camera image to the device, runs all
CEM iterations (sample → score → elite refit) inside one compiled
program, and returns one action. Latency is weight-independent, so a
randomly initialized Q-function measures the same control rate a
trained one serves at.

    python -m tensor2robot_tpu.bin.bench_serving

Fleet mode (`--fleet`; the fleet fields of the `SERVING_r*` schema):
N synthetic clients drive the serving/ stack — deadline micro-batcher
→ bucket ladder → ONE batched CEM executable per bucket — either
closed-loop (each client blocks on its action) or at a target offered
load (`--target-hz`). Emits aggregate images/sec, per-request p50/p99
latency, batch occupancy, padding waste, and the compiled-executable
ledger. `--fleet --smoke` swaps in the millisecond-scale
serving.smoke.TinyQPredictor and runs on CPU: the tier-1 lane that
exercises the whole serving path on every PR, no TPU pool required.

Both modes print ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import numpy as np


def bench_policy(uint8_images: bool, control_steps: int = 30) -> dict:
  import jax

  from tensor2robot_tpu.predictors.checkpoint_predictor import (
      CheckpointPredictor)
  from tensor2robot_tpu.research.qtopt.cem import CEMPolicy
  from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel

  model = QTOptGraspingModel(uint8_images=uint8_images)
  predictor = CheckpointPredictor(model)
  predictor.init_randomly()
  policy = CEMPolicy(predictor, action_size=4, num_samples=64,
                     num_elites=6, iterations=3, seed=0)
  size = model.get_feature_specification("train")["image"].shape[0]
  rng = np.random.default_rng(0)

  def make_image():
    if uint8_images:
      return rng.integers(0, 255, (size, size, 3), np.uint8)
    return rng.random((size, size, 3)).astype(np.float32)

  # closed_loop: block on every action before the next frame — the
  # rate a real robot loop gets (it needs action N before frame N+1).
  # pipelined: block only at the end — async dispatch overlaps host
  # transfer with device compute, an offline-throughput ceiling, NOT a
  # control rate. Both on fresh frames (distinct camera image per
  # step, paying host→device transfer each time).
  frames = [make_image() for _ in range(control_steps)]
  jax.block_until_ready(policy(frames[0]))  # compile the control step

  out = {}
  start = time.perf_counter()
  for image in frames:
    jax.block_until_ready(policy(image))
  elapsed = time.perf_counter() - start
  out["closed_loop_hz"] = round(control_steps / elapsed, 1)
  out["closed_loop_ms"] = round(1e3 * elapsed / control_steps, 2)

  start = time.perf_counter()
  for image in frames:
    action = policy(image)
  jax.block_until_ready(action)
  elapsed = time.perf_counter() - start
  out["pipelined_hz"] = round(control_steps / elapsed, 1)

  out["image_wire_format"] = "uint8" if uint8_images else "float32"
  out["image_size"] = int(size)
  out["image_bytes"] = int(frames[0].nbytes)
  return out


# --- fleet mode ------------------------------------------------------------


def _cem_kwargs(smoke: bool) -> dict:
  """CEM config shared by the fleet policy AND the single-client
  baseline (the amortization ratio must compare like with like). The
  smoke lane shrinks it: per-client CEM compute scales linearly with
  batch on any backend, so a small config keeps per-flush DISPATCH —
  the cost micro-batching actually amortizes — dominant on CPU, which
  is the property the smoke asserts."""
  if smoke:
    return dict(action_size=4, num_samples=32, num_elites=4,
                iterations=2, seed=0)
  return dict(action_size=4, num_samples=64, num_elites=6,
              iterations=3, seed=0)


def _make_fleet_policy(smoke: bool, uint8_images: bool):
  """(predictor, policy, make_image) for the fleet sweep."""
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy

  if smoke:
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    predictor = TinyQPredictor()
    make_image = predictor.make_image
  else:
    from tensor2robot_tpu.predictors.checkpoint_predictor import (
        CheckpointPredictor)
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        QTOptGraspingModel)
    model = QTOptGraspingModel(uint8_images=uint8_images)
    predictor = CheckpointPredictor(model)
    predictor.init_randomly()
    size = model.get_feature_specification("train")["image"].shape[0]
    rng = np.random.default_rng(0)

    def make_image(seed: int):
      del seed
      if uint8_images:
        return rng.integers(0, 255, (size, size, 3), np.uint8)
      return rng.random((size, size, 3)).astype(np.float32)

  policy = CEMFleetPolicy(predictor, **_cem_kwargs(smoke))
  return predictor, policy, make_image


def _run_clients(server, n_clients: int, frames: int, make_image,
                 target_hz: float) -> float:
  """Drives n closed-loop (or paced open-loop) clients; returns seconds."""
  errors = []

  def closed_loop(client: int):
    image = make_image(client)
    try:
      for _ in range(frames):
        server.act(image)
    except Exception as e:  # surface, don't hang the join
      errors.append(e)

  def open_loop(client: int):
    image = make_image(client)
    period = 1.0 / target_hz
    futures = []
    next_at = time.perf_counter()
    try:
      for _ in range(frames):
        delay = next_at - time.perf_counter()
        if delay > 0:
          time.sleep(delay)
        futures.append(server.submit(image))
        next_at += period
      for future in futures:
        future.result()
    except Exception as e:
      errors.append(e)

  run = open_loop if target_hz > 0 else closed_loop
  threads = [threading.Thread(target=run, args=(i,), daemon=True)
             for i in range(n_clients)]
  start = time.perf_counter()
  for thread in threads:
    thread.start()
  for thread in threads:
    thread.join()
  elapsed = time.perf_counter() - start
  if errors:
    raise errors[0]
  return elapsed


def bench_fleet(smoke: bool, clients: list, frames: int,
                deadline_ms: float, target_hz: float,
                uint8_images: bool = True, repeats: int = 3) -> dict:
  import statistics

  from tensor2robot_tpu.serving.server import FleetServer
  from tensor2robot_tpu.serving.stats import ServingStats

  predictor, policy, make_image = _make_fleet_policy(smoke, uint8_images)
  ladder = policy.ladder

  # Precompile the whole ladder up front (server warmup): measured
  # sweep points then assert zero mid-flight compiles — the bounded-
  # executables property the ladder exists for.
  for bucket in ladder.sizes:
    policy([make_image(i) for i in range(bucket)])

  # Single-client closed loop through the single-robot path (CEMPolicy:
  # one fused control step per frame, no batching) — the amortization
  # baseline the fleet numbers are read against. Median over `repeats`
  # trials: a contended host's one-off stall must not set the baseline.
  from tensor2robot_tpu.research.qtopt.cem import CEMPolicy
  import jax
  single_policy = CEMPolicy(predictor, **_cem_kwargs(smoke))
  image = make_image(0)
  jax.block_until_ready(single_policy(image))
  single_rates = []
  for _ in range(max(1, repeats)):
    start = time.perf_counter()
    for _ in range(frames):
      jax.block_until_ready(single_policy(image))
    single_rates.append(frames / (time.perf_counter() - start))
  single_hz = statistics.median(single_rates)

  sweep = []
  for n in clients:
    stats = ServingStats()
    server = FleetServer(policy, max_batch=min(n, ladder.max_batch),
                         deadline_ms=deadline_ms, stats=stats)
    rates = []
    with server:
      # One throwaway round primes the batcher threads.
      [f.result() for f in [server.submit(make_image(i))
                            for i in range(n)]]
      for _ in range(max(1, repeats)):
        elapsed = _run_clients(server, n, frames, make_image, target_hz)
        rates.append(n * frames / elapsed)
    snap = server.snapshot()
    point = {
        "clients": n,
        "offered_hz_per_client": target_hz if target_hz > 0
        else "closed_loop",
        "aggregate_images_per_sec": round(statistics.median(rates), 1),
        "aggregate_trials": [round(r, 1) for r in rates],
        "latency_p50_ms": snap.get("latency_p50_ms"),
        "latency_p99_ms": snap.get("latency_p99_ms"),
        "batch_occupancy": snap.get("batch_occupancy"),
        "padding_waste": snap.get("padding_waste"),
        "mean_batch_size": snap.get("mean_batch_size"),
        "flushes": snap.get("flushes"),
        "deadline_flushes": snap.get("deadline_flushes"),
    }
    sweep.append(point)

  top = sweep[-1]
  cem_kwargs = _cem_kwargs(smoke)
  return {
      "mode": "smoke" if smoke else "full",
      "cem": {k: cem_kwargs[k]
              for k in ("num_samples", "num_elites", "iterations")},
      "bucket_ladder": list(ladder.sizes),
      "compile_counts": {str(k): v
                         for k, v in sorted(policy.compile_counts.items())},
      "deadline_ms": deadline_ms,
      "frames_per_client": frames,
      "repeats": max(1, repeats),
      "single_client_closed_loop_hz": round(single_hz, 1),
      "single_client_trials_hz": [round(r, 1) for r in single_rates],
      "fleet_sweep": sweep,
      "amortization_at_max_clients": round(
          top["aggregate_images_per_sec"] / single_hz, 2),
  }


def _parse_args(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--fleet", action="store_true",
                      help="multi-client micro-batching sweep")
  parser.add_argument("--smoke", action="store_true",
                      help="CPU smoke: TinyQPredictor, runs chipless "
                           "(tier-1 CI lane)")
  parser.add_argument("--clients", default="1,2,4,8,16",
                      help="comma-separated concurrent-client sweep")
  parser.add_argument("--frames", type=int, default=0,
                      help="frames per client (0 = mode default)")
  parser.add_argument("--deadline-ms", type=float, default=5.0,
                      help="micro-batcher deadline budget")
  parser.add_argument("--target-hz", type=float, default=0.0,
                      help="offered load per client; 0 = closed loop")
  parser.add_argument("--repeats", type=int, default=3,
                      help="measurement trials per point (median wins)")
  parser.add_argument("--float32", action="store_true",
                      help="fleet full mode: float32 wire instead of "
                           "uint8")
  args = parser.parse_args(argv)
  if args.smoke and not args.fleet:
    # --smoke pins JAX to CPU; letting it combine with the single-robot
    # default would grind the 472x472 model on CPU and emit a normal-
    # looking classic serving line measured on the wrong backend.
    parser.error("--smoke is a fleet-mode lane; pass --fleet --smoke")
  return args


def main(argv=None) -> None:
  args = _parse_args(argv)
  if args.smoke:
    # Chipless lane: must pick the CPU backend, and only can before
    # JAX initializes (imports below are deliberately lazy).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
  from tensor2robot_tpu.utils import compile_cache
  from tensor2robot_tpu.utils.device_info import device_summary
  compile_cache.configure()

  if args.fleet:
    clients = [int(c) for c in args.clients.split(",") if c]
    frames = args.frames or (60 if args.smoke else 30)
    fleet = bench_fleet(args.smoke, clients, frames, args.deadline_ms,
                        args.target_hz,
                        uint8_images=not args.float32,
                        repeats=args.repeats)
    print(json.dumps({
        "metric": "QT-Opt fleet serving: deadline micro-batch + "
                  "bucketed CEM",
        **device_summary(),
        **fleet,
        "reference_note": "the reference ran robot fleets at 10-30 Hz "
                          "through one batched session.run per CEM "
                          "iteration (SURVEY.md §3.3)",
    }))
    return

  results = [bench_policy(uint8_images=False),
             bench_policy(uint8_images=True)]
  print(json.dumps({
      "metric": "QT-Opt fused CEM control rate (64 samples x 3 iters)",
      **device_summary(),
      "results": results,
      "reference_note": "the reference's robot fleets ran 10-30 Hz "
                        "with a batched session.run per CEM iteration "
                        "(SURVEY.md §3.3)",
  }))


if __name__ == "__main__":
  main()

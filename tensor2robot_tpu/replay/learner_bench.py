"""Learner-throughput bench: host-path BellmanUpdater vs fused megastep.

The ISSUE 4 acceptance instrument: at ONE batch shape, time the PR 2
host learner hot path (numpy sample → compiled Bellman targets →
shard+train → compiled TD → numpy priority write-back; four dispatches
plus host work per optimizer step) against the device-resident megastep
(one donated executable per K steps). Collectors are deliberately out
of the picture — both paths train from an identical pre-filled buffer —
so the numbers isolate the learner, not env throughput.

Emitted block (every citable field carries the repo's
{median,min,max,trials} spread shape):

  host_path / device_megastep:
    train_steps_per_sec    optimizer steps per wall second
    transitions_per_sec    steps/sec x batch (the replay-consumption rate)
    host_blocked_fraction  1 - (time inside compiled-executable calls /
                           wall time): the fraction of the wall the chip
                           spends serialized behind host work (numpy
                           sampling, sum-tree updates, H2D staging, D2H
                           reads). The megastep's is ~0 by construction
                           — that IS the design claim, stated as a
                           measurement.
  speedup                  per-trial device/host steps-per-sec ratio.
"""

from __future__ import annotations

import statistics
import time
from typing import Dict

import numpy as np


def _spread(values, digits=3):
  """{median,min,max,trials} — the committed field shape of a timing.

  Shared with replay/actor_bench.py (as is `_synthetic_transitions`):
  the learner and actor throughput blocks must carry the same citable
  field shape, so there is exactly one definition of it here."""
  vals = [float(v) for v in values]
  return {
      "median": round(statistics.median(vals), digits),
      "min": round(min(vals), digits),
      "max": round(max(vals), digits),
      "trials": len(vals),
  }


def _synthetic_transitions(n, image_size, action_size, seed):
  rng = np.random.default_rng(seed)
  return {
      "image": rng.integers(0, 255, (n, image_size, image_size, 3),
                            np.uint8),
      "action": rng.uniform(-1, 1, (n, action_size)).astype(np.float32),
      "reward": (rng.random(n) < 0.3).astype(np.float32),
      "done": (rng.random(n) < 0.3).astype(np.float32),
      "next_image": rng.integers(0, 255, (n, image_size, image_size, 3),
                                 np.uint8),
  }


def measure_learner_throughput(
    batch_size: int = 32,
    image_size: int = 16,
    action_size: int = 4,
    capacity: int = 256,
    steps_per_trial: int = 30,
    inner_steps: int = 10,
    trials: int = 3,
    gamma: float = 0.8,
    learning_rate: float = 3e-3,
    cem_num_samples: int = 16,
    cem_num_elites: int = 4,
    cem_iterations: int = 2,
    seed: int = 0,
) -> Dict:
  """Times both learner paths on identical pre-filled replay content.

  steps_per_trial must be a multiple of inner_steps (whole megasteps).
  Warmup (all compiles + one full cycle) happens before any timing; the
  spread over `trials` repeated timed windows is what makes the ratio
  citable on a contended host.
  """
  import jax
  import optax

  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.replay.bellman import BellmanUpdater
  from tensor2robot_tpu.replay.device_buffer import (DeviceReplayBuffer,
                                                     MegastepLearner)
  from tensor2robot_tpu.replay.loop import transition_spec
  from tensor2robot_tpu.replay.ring_buffer import ReplayBuffer
  from tensor2robot_tpu.replay.smoke import TinyQCriticModel
  from tensor2robot_tpu.train.trainer import Trainer

  if steps_per_trial % inner_steps:
    raise ValueError(
        f"steps_per_trial {steps_per_trial} must be a multiple of "
        f"inner_steps {inner_steps}")
  # Per-chip basis: BOTH paths run on a single-device mesh. The CI
  # harness virtualizes 8 CPU "devices" on one core, where cross-device
  # rendezvous is pure overhead that lands differently on the two paths
  # (the host path's target/TD executables are unsharded, the fused
  # program inherits the mesh) — that artifact would measure the
  # virtualization, not the fusion. Multi-chip scaling is the loop's
  # (sharded) job; this block isolates the learner hot path.
  mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
  spec = transition_spec(image_size, action_size)
  fill = _synthetic_transitions(capacity, image_size, action_size,
                                seed + 17)
  cem_kwargs = dict(num_samples=cem_num_samples,
                    num_elites=cem_num_elites, iterations=cem_iterations)

  def make_model():
    return TinyQCriticModel(
        image_size=image_size, action_size=action_size,
        optimizer_fn=lambda: optax.adam(learning_rate))

  # --- host path: the PR 2 per-step loop, executable time instrumented --
  model = make_model()
  trainer = Trainer(model, mesh=mesh, seed=seed)
  state = trainer.create_train_state(batch_size=batch_size)
  from tensor2robot_tpu.export import export_utils
  host_variables = export_utils.fetch_variables_to_host(
      state.variables(use_ema=True))
  buffer = ReplayBuffer(spec, capacity, batch_size, seed=seed,
                        prioritized=True)
  buffer.extend(fill)
  updater = BellmanUpdater(model, host_variables,
                           action_size=action_size, gamma=gamma,
                           seed=seed + 13, **cem_kwargs)
  train_exec = None
  exec_seconds = [0.0]

  def timed(fn, *args):
    start = time.perf_counter()
    out = fn(*args)
    exec_seconds[0] += time.perf_counter() - start
    return out

  def host_step(state, train_exec):
    batch, info = buffer.sample()
    targets, _ = timed(updater.compute_targets, batch)
    features = {"image": np.asarray(batch["image"]),
                "action": np.asarray(batch["action"])}
    sharded = trainer.shard_batch((features, {"target_q": targets}))
    if train_exec is None:
      train_exec = trainer.aot_train_step(state, *sharded)
    state, metrics = timed(train_exec, state, *sharded)
    online = state.variables(use_ema=True)
    td = timed(updater.td_errors, online, batch, targets)
    buffer.update_priorities(info.indices, td)
    return state, train_exec, metrics

  for _ in range(3):  # compiles + warm caches, outside all timing
    state, train_exec, _ = host_step(state, train_exec)
  host_sps, host_blocked = [], []
  for _ in range(trials):
    exec_seconds[0] = 0.0
    start = time.perf_counter()
    for _ in range(steps_per_trial):
      state, train_exec, metrics = host_step(state, train_exec)
    float(metrics["loss"])  # sync
    elapsed = time.perf_counter() - start
    host_sps.append(steps_per_trial / elapsed)
    host_blocked.append(max(0.0, 1.0 - exec_seconds[0] / elapsed))

  # --- device path: same content, same shapes, one fused executable ----
  model = make_model()
  trainer = Trainer(model, mesh=mesh, seed=seed)
  state = trainer.create_train_state(batch_size=batch_size)
  host_variables = export_utils.fetch_variables_to_host(
      state.variables(use_ema=True))
  dbuffer = DeviceReplayBuffer(
      spec, capacity, batch_size, seed=seed, prioritized=True,
      ingest_chunk=min(64, capacity), mesh=trainer.mesh)
  dbuffer.extend(fill)
  learner = MegastepLearner(model, trainer, dbuffer,
                            action_size=action_size, gamma=gamma,
                            inner_steps=inner_steps, seed=seed + 13,
                            **cem_kwargs)
  learner.refresh(host_variables, step=0)
  state, _ = learner.step(state)  # compile + warm, outside timing
  dispatches = steps_per_trial // inner_steps
  device_sps, device_blocked = [], []
  for _ in range(trials):
    in_exec = 0.0
    start = time.perf_counter()
    for _ in range(dispatches):
      t0 = time.perf_counter()
      state, metrics = learner.step(state)
      in_exec += time.perf_counter() - t0
    elapsed = time.perf_counter() - start
    device_sps.append(steps_per_trial / elapsed)
    device_blocked.append(max(0.0, 1.0 - in_exec / elapsed))

  return {
      "batch_size": batch_size,
      "inner_steps": inner_steps,
      "steps_per_trial": steps_per_trial,
      "prioritized": True,
      "host_path": {
          "train_steps_per_sec": _spread(host_sps, 2),
          "transitions_per_sec": _spread(
              [s * batch_size for s in host_sps], 1),
          "host_blocked_fraction": _spread(host_blocked, 3),
      },
      "device_megastep": {
          "train_steps_per_sec": _spread(device_sps, 2),
          "transitions_per_sec": _spread(
              [s * batch_size for s in device_sps], 1),
          "host_blocked_fraction": _spread(device_blocked, 3),
      },
      "speedup": _spread(
          [d / h for d, h in zip(device_sps, host_sps)], 2),
      "compile_counts": {
          **learner.compile_counts, **dbuffer.compile_counts},
      "note": (
          "same batch shape, same pre-filled replay content, no "
          "collectors: host path = sample/label/train/TD/reprioritize "
          "with four dispatches + numpy tree work per optimizer step; "
          "device path = one donated megastep executable per "
          "inner_steps steps. host_blocked_fraction counts wall time "
          "OUTSIDE compiled-executable calls. Both paths run on a "
          "single-device mesh (per-chip basis; CI's virtual 8-device "
          "CPU mesh would measure rendezvous artifacts, not fusion)."),
  }

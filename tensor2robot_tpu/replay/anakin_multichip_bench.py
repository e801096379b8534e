"""Pod-scale Anakin scaling bench: ONE fused executable, 1→N devices.

The ISSUE 7 acceptance instrument (the MULTICHIP_r06 artifact): hold
the GLOBAL workload fixed — same env fleet width, same sample batch,
same CEM policy and critic — and run the fused act→step→extend→learn
executable over data-parallel meshes of 1, 2, 4, and 8 devices,
measuring transitions/s and env steps/s at each scale. Per Podracer
(PAPERS.md, arXiv:2104.06272) the fused loop is exactly the program
that scales across a pod: each device steps num_envs / d envs, holds
capacity / d replay slots, and trains on batch / d transitions with
the gradient all-reduced — so on real chips the per-dispatch work
drops ~linearly with d and transitions/s rises near-linearly at fixed
global batch.

HONESTY CAVEAT (the artifact carries it as `virtual_mesh`): on a
chipless host the "devices" are XLA's virtual CPU devices — slices of
the same cores. Virtual-mesh scaling measures partitioning OVERHEAD,
not pod speedup: efficiency well below 1 is expected and is NOT a
regression (the 2-core CI box typically sits far below it). What this
bench proves chiplessly is structural: the SAME one-executable ledger
(`anakin_step` == 1 at every scale), host-blocked ~0, per-shard env
fleets, capacity-sharded ring, and a learn body whose metrics match
the 1-device oracle (the parity suite's claim) — the scaling NUMBERS
become meaningful only when this runs on real chips.

Emitted block (every citable field carries the repo's
{median,min,max,trials} spread shape):

  scales[i]:
    devices                      mesh size d (data axis; tp = 1)
    env_steps_per_sec            global fused-loop rate at this d
    transitions_per_sec          == env steps/s (one transition per
                                 env step enters the sharded ring)
    per_device_transitions_per_sec   transitions/s / d — the per-chip
                                 ingest rate the ring actually holds
    train_steps_per_sec          optimizer steps inside the number
    host_blocked_fraction        1 - in-executable / wall (per scale)
    speedup_vs_1dev              median ratio vs the d=1 run
    scaling_efficiency_vs_1dev   speedup / d (1.0 = linear)
    zero1                        ZeRO-1 weight-update sharding active
    compile_counts               exactly one anakin_step per scale
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

from tensor2robot_tpu.replay.learner_bench import _spread


def default_device_counts(available: int) -> list:
  """Powers of two up to min(available, 8) — the 1/2/4/8 ladder where
  the hardware (or virtual mesh) permits, honest about fewer."""
  counts = []
  d = 1
  while d <= min(available, 8):
    counts.append(d)
    d *= 2
  return counts


def measure_anakin_multichip(
    device_counts: Optional[Sequence[int]] = None,
    num_envs: int = 32,
    image_size: int = 16,
    action_size: int = 4,
    max_attempts: int = 3,
    grasp_radius: float = 0.4,
    exploration_epsilon: float = 0.25,
    scripted_fraction: float = 0.25,
    cem_num_samples: int = 16,
    cem_num_elites: int = 4,
    cem_iterations: int = 2,
    inner_steps: int = 64,
    train_every: int = 8,
    bank_scenes: int = 256,
    window_s: float = 0.8,
    trials: int = 3,
    batch_size: int = 32,
    capacity: int = 512,
    gamma: float = 0.8,
    learning_rate: float = 3e-3,
    seed: int = 0,
) -> Dict:
  """Times the fused loop at each mesh size; returns the
  `anakin_multichip` block.

  All compiles happen before any timing (one fused executable per
  scale — the ledger proves it stays one). The workload is globally
  fixed: every entry of `device_counts` must divide `num_envs`,
  `batch_size`, and `capacity` (the loop refuses otherwise, naming the
  fix). Citable numbers come from a quiet process (the CLI subprocess
  protocol), same rule as every replay bench.
  """
  import jax
  import optax

  from tensor2robot_tpu.export import export_utils
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.replay.anakin import AnakinLoop
  from tensor2robot_tpu.replay.device_buffer import DeviceReplayBuffer
  from tensor2robot_tpu.replay.loop import transition_spec
  from tensor2robot_tpu.replay.smoke import TinyQCriticModel
  from tensor2robot_tpu.research.qtopt.jax_grasping import (JaxGraspEnv,
                                                            make_scene_bank)
  from tensor2robot_tpu.train.trainer import Trainer

  devices = jax.devices()
  if device_counts is None:
    device_counts = default_device_counts(len(devices))
  # The vs-1dev fields need their actual baseline: always measure the
  # 1-device run (prepended if the caller's ladder skipped it), and
  # ascend so `base_median` is bound before any larger scale reads it.
  device_counts = sorted(set(int(d) for d in device_counts))
  if device_counts and device_counts[0] < 1:
    raise ValueError(
        f"device_counts must be positive mesh sizes, got {device_counts}")
  if not device_counts or device_counts[0] != 1:
    device_counts.insert(0, 1)
  if max(device_counts) > len(devices):
    raise ValueError(
        f"device_counts {device_counts} exceed the {len(devices)} "
        "visible device(s); on a chipless host run the CLI --smoke "
        "lane (it bootstraps an 8-virtual-device CPU mesh).")
  device_kind = devices[0].device_kind
  spec = transition_spec(image_size, action_size)
  # ONE bank render for every scale: scene content identical across
  # mesh sizes (the equalized global stream of the parity suite).
  bank = make_scene_bank(bank_scenes, image_size=image_size,
                         base_seed=seed)

  scales = []
  base_median = None
  for d in device_counts:
    mesh = mesh_lib.create_mesh({"data": d, "model": 1},
                                devices=devices[:d])
    zero1 = d > 1
    model = TinyQCriticModel(
        image_size=image_size, action_size=action_size,
        optimizer_fn=lambda: optax.adam(learning_rate))
    trainer = Trainer(model, mesh=mesh, seed=seed,
                      shard_optimizer_state=zero1)
    state = trainer.create_train_state(batch_size=batch_size)
    host_variables = export_utils.fetch_variables_to_host(
        state.variables(use_ema=True))
    buffer = DeviceReplayBuffer(
        spec, capacity, batch_size, seed=seed, prioritized=True,
        ingest_chunk=num_envs, mesh=mesh)
    env = JaxGraspEnv(num_envs, image_size=image_size,
                      max_attempts=max_attempts, radius=grasp_radius,
                      bank=bank)
    loop = AnakinLoop(
        model, trainer, buffer, env, action_size=action_size,
        gamma=gamma, num_samples=cem_num_samples,
        num_elites=cem_num_elites, iterations=cem_iterations,
        inner_steps=inner_steps, train_every=train_every,
        min_fill=min(batch_size, capacity),
        exploration_epsilon=exploration_epsilon,
        scripted_fraction=scripted_fraction, seed=seed + 13)
    loop.refresh(host_variables, step=0)
    state, _ = loop.step(state)  # compile + warm + fill past min-fill

    sps, tps, blocked = [], [], []
    for _ in range(trials):
      steps = trained = 0
      exec0 = loop.exec_seconds
      start = time.perf_counter()
      while time.perf_counter() - start < window_s:
        state, metrics = loop.step(state)
        steps += inner_steps * num_envs
        trained += metrics["trained_steps"]
      elapsed = time.perf_counter() - start
      sps.append(steps / elapsed)
      tps.append(trained / elapsed)
      blocked.append(
          max(0.0, 1.0 - (loop.exec_seconds - exec0) / elapsed))

    median = _spread(sps, 1)["median"]
    if base_median is None:
      base_median = median
    speedup = median / max(base_median, 1e-9)
    scales.append({
        "devices": d,
        "env_steps_per_sec": _spread(sps, 1),
        "transitions_per_sec": _spread(sps, 1),
        "per_device_transitions_per_sec": _spread(
            [s / d for s in sps], 1),
        "train_steps_per_sec": _spread(tps, 2),
        "host_blocked_fraction": _spread(blocked, 3),
        "speedup_vs_1dev": round(speedup, 3),
        "scaling_efficiency_vs_1dev": round(speedup / d, 3),
        "zero1": zero1,
        "compile_counts": dict(loop.compile_counts),
    })
    # Free this scale's device state before the next mesh allocates.
    del loop, buffer, env, state, trainer, model

  return {
      "num_envs": num_envs,
      "batch_size": batch_size,
      "capacity": capacity,
      "inner_steps": inner_steps,
      "train_every": train_every,
      "window_s": window_s,
      "trials": trials,
      "probed_device_kind": device_kind,
      "virtual_mesh": device_kind.lower() == "cpu",
      "device_counts": device_counts,
      "scales": scales,
      "note": (
          "Fixed GLOBAL workload at every mesh size: same env fleet "
          f"({num_envs} envs), same sample batch ({batch_size}), same "
          f"ring capacity ({capacity}), same CEM policy over the same "
          "TinyQ critic and the same prerendered scene bank. Each "
          "scale compiles ONE fused anakin_step executable over a "
          "{'data': d} mesh with per-shard env fleets, the ring "
          "capacity-sharded per device, data-parallel learn with "
          "gradient all-reduce, and ZeRO-1 weight-update sharding for "
          "d > 1. scaling_efficiency_vs_1dev = (env_steps/s at d) / "
          "(d * env_steps/s at 1): 1.0 is linear. With "
          "virtual_mesh=true the devices are slices of the same host "
          "cores, so efficiency measures XLA partitioning overhead, "
          "not pod speedup — the structural claims (one executable, "
          "host_blocked ~0, sharded state) are the chipless evidence; "
          "re-run on real chips for citable scaling."),
  }


def main(argv=None) -> None:
  """CLI: ONE JSON line (the bench contract); --smoke bootstraps an
  8-virtual-device CPU mesh (re-exec with the canonical env)."""
  import argparse
  import json
  import os
  import sys

  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--smoke", action="store_true",
                      help="chipless lane: 8 virtual CPU devices, "
                           "reduced windows")
  parser.add_argument("--devices", default=None,
                      help="comma-separated mesh sizes "
                           "(default: 1,2,4,8 where available)")
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--out", default=None,
                      help="also write the JSON line to this file")
  args = parser.parse_args(argv)
  if args.smoke:
    from tensor2robot_tpu.utils.cpu_mesh_env import (cpu_mesh_env,
                                                     is_cpu_mesh_env)
    if not is_cpu_mesh_env(8):
      if argv is not None:
        raise RuntimeError(
            "--smoke needs the 8-virtual-device CPU mesh configured "
            "before JAX initializes; call main() with argv=None (the "
            "CLI re-execs itself).")
      os.execve(sys.executable,
                [sys.executable, "-m",
                 "tensor2robot_tpu.replay.anakin_multichip_bench",
                 *sys.argv[1:]],
                cpu_mesh_env(8))
  device_counts = ([int(x) for x in args.devices.split(",")]
                   if args.devices else None)
  kwargs = dict(device_counts=device_counts, seed=args.seed)
  if args.smoke:
    # CI scale: smaller windows/fleet, same structure (the committed
    # artifact uses the defaults via a quiet full run).
    kwargs.update(num_envs=16, inner_steps=32, window_s=0.5, trials=2,
                  bank_scenes=128)
  results = measure_anakin_multichip(**kwargs)
  line = json.dumps(results)
  if args.out:
    with open(args.out, "w") as f:
      f.write(line + "\n")
  print(line)


if __name__ == "__main__":
  main()

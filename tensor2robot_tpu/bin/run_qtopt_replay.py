"""Run the closed QT-Opt loop: collect → replay → Bellman-label → train.

The continuous-learning entry the reference never shipped in-repo
(its collectors/replay/Bellman fleet ran off-repo — SURVEY.md §2),
driving tensor2robot_tpu/replay end to end: CEMFleetPolicy collectors
on synthetic grasping, a sharded prioritized ring buffer, CEM-maximized
Bellman targets against a lagged target net, and the Trainer's AOT
train step — with the compiled-program ledger in the output.

    python -m tensor2robot_tpu.bin.run_qtopt_replay --smoke
    python -m tensor2robot_tpu.bin.run_qtopt_replay --smoke --device-resident
    python -m tensor2robot_tpu.bin.run_qtopt_replay --smoke \
        --device-resident --vector-actors
    python -m tensor2robot_tpu.bin.run_qtopt_replay --smoke --anakin

`--device-resident` (ISSUE 4) keeps replay state on device and fuses
K = megastep_inner sample→CEM-label→train→reprioritize iterations into
ONE donated megastep executable (replay/device_buffer.py); the default
is the PR 2 host-path loop, kept as the fallback. With
`--device-resident` the output additionally carries a
`learner_throughput` block (train steps/s, transitions/s, host-blocked
fraction, device-vs-host speedup at the same batch shape — the
replay/learner_bench.py comparison; skip with `--no-learner-bench`).

`--vector-actors` (ISSUE 5) replaces the threaded scalar collectors
with the vectorized actor fleet (replay/actor.py): every env steps in
lockstep through ONE fused CEM bucket executable, feeding the queue in
fixed fleet-size chunks, overlapped with the learner. Collection
semantics (retry budget, exploration mix, scene-seed stream) are
unchanged; the threaded path stays the default and the measured
fallback. The output additionally carries an `actor_throughput` block
(env steps/s, transitions/s, vector-vs-threaded speedup at the same
policy and env count, and the acting/learning overlap fraction — the
replay/actor_bench.py comparison; skip with `--no-actor-bench`).

`--anakin` (ISSUE 6) fuses the WHOLE loop: the JAX-native grasping env
(research/qtopt/jax_grasping.py), CEM acting, fixed-chunk replay
extend, and the learner inner body compile into ONE donated executable
(replay/anakin.py) scanning `anakin_inner` control steps per dispatch
— no collector threads, no queue, zero host work in the steady state.
The output carries an `anakin_throughput` block (fused vs numpy-fleet
env steps/s at the same env count and policy — both in their full
production shape, the collect-only baseline alongside — plus the
host-blocked fraction and the CEM scoring `dtype`; skip with
`--no-anakin-bench`). The vector-actor and threaded paths stay the
measured fallbacks.

`--mesh DP[,TP]` (ISSUE 7) runs the loop over an explicit dp×tp device
mesh instead of the single-process default. With `--anakin` this is
the pod-scale configuration: per-shard env fleets, the replay ring
capacity-sharded per device, the fused learn body data-parallel with
gradient all-reduce, and ZeRO-1 weight-update sharding applied inside
the scan — still exactly ONE `anakin_step` executable. In `--smoke`
mode a DP*TP > 1 mesh bootstraps DP*TP virtual CPU devices by
re-exec'ing with the canonical CPU-mesh environment (the
tests/conftest.py idiom); on a chip it meshes the first DP*TP real
devices. The r10 smoke protocol is `--smoke --anakin --mesh 8,1`; the
single-device `--anakin` run stays the unchanged semantics oracle.

Prints ONE JSON line (the repo's bench/driver contract): initial/final
eval Bellman residual, the reduction fraction, replay health counters,
and `compile_counts` (every value must be 1 — fixed-shape sampling
never recompiles; on the device path that includes exactly one
megastep executable, with vector actors exactly one acting executable
per bucket, and with --anakin exactly one fused anakin_step
executable). `--smoke` is the chipless CI scale (tier-1 asserts a
>= 30% residual reduction on it); the default scale is the same loop
with a bigger buffer/budget for on-chip runs. `--out` additionally
writes the same JSON to a file (the committed smoke artifact,
REPLAY_SMOKE_r09.json for this round).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def parse_profile(spec):
  """'START,END' -> (start, end) optimizer-step window; None passthrough."""
  if not spec:
    return None
  parts = spec.split(",")
  if len(parts) != 2:
    raise ValueError(f"--profile takes START,END steps, got {spec!r}")
  try:
    start, end = int(parts[0]), int(parts[1])
  except ValueError:
    raise ValueError(f"--profile takes integers, got {spec!r}")
  if start < 0 or end <= start:
    raise ValueError(
        f"--profile needs 0 <= START < END, got {spec!r}")
  return start, end


def parse_mesh(spec: str):
  """'8' or '4,2' -> (dp, tp). '0' keeps the mode default mesh."""
  parts = spec.split(",")
  if len(parts) > 2:
    raise ValueError(f"--mesh takes DP or DP,TP, got {spec!r}")
  try:
    dp = int(parts[0])
    tp = int(parts[1]) if len(parts) == 2 else 1
  except ValueError:
    raise ValueError(f"--mesh takes integers, got {spec!r}")
  if dp < 0 or tp < 1:
    raise ValueError(
        f"--mesh takes DP >= 1 (or 0 for the mode default) and "
        f"TP >= 1, got {spec!r}")
  if dp == 0 and tp != 1:
    # dp=0 keeps the mode-default mesh, which would silently discard
    # the requested TP degree — refuse instead.
    raise ValueError(
        f"--mesh 0,{tp} mixes the keep-default sentinel with an "
        "explicit TP degree; name DP explicitly (e.g. "
        f"--mesh 1,{tp}).")
  return dp, tp


def build_config(smoke: bool, seed: int, device_resident: bool = False,
                 vector_actors: bool = False, anakin: bool = False,
                 mesh=(0, 1), profile_window=None, precision: str = "f32"):
  from tensor2robot_tpu.replay.loop import ReplayLoopConfig
  dp, tp = mesh
  if smoke:
    # The sharded smoke keeps the r09 scale but rounds the env fleet,
    # sample batch, and ring capacity up to multiples of the data axis
    # (all three must shard over it, and the smoke CLI exposes no knob
    # to fix them by hand); power-of-two dp <= 8 keeps the exact
    # 4-env / batch-32 / capacity-512 oracle shapes.
    up = lambda v: -(-v // dp) * dp if (anakin and dp > 1) else v
    return ReplayLoopConfig(seed=seed, device_resident=device_resident,
                            vector_actors=vector_actors, anakin=anakin,
                            envs_per_collector=up(4), batch_size=up(32),
                            capacity=up(512), mesh_dp=dp, mesh_tp=tp,
                            profile_window=profile_window,
                            precision=precision)
  return ReplayLoopConfig(
      image_size=64, batch_size=32, capacity=50_000, min_fill=2_000,
      num_buffer_shards=4, num_collectors=4, envs_per_collector=8,
      queue_capacity=10_000, cem_num_samples=64, cem_num_elites=6,
      cem_iterations=3, refresh_every=200, eval_every=500,
      eval_batches=8, log_every=50, learning_rate=1e-4, seed=seed,
      device_resident=device_resident, megastep_inner=50,
      ingest_chunk=256, vector_actors=vector_actors, anakin=anakin,
      anakin_inner=200, anakin_bank_scenes=4096, mesh_dp=dp, mesh_tp=tp,
      profile_window=profile_window, precision=precision)


def run(steps: int, smoke: bool, logdir: str, seed: int,
        device_resident: bool = False, learner_bench: bool = True,
        vector_actors: bool = False, actor_bench: bool = True,
        anakin: bool = False, anakin_bench: bool = True,
        mesh=(0, 1), profile_window=None, precision: str = "f32") -> dict:
  from tensor2robot_tpu.replay.loop import ReplayTrainLoop
  config = build_config(smoke, seed, device_resident, vector_actors,
                        anakin, mesh=mesh, profile_window=profile_window,
                        precision=precision)
  model = None  # default: the flagship QTOptGraspingModel
  if smoke:
    # CI-scale critic (replay/smoke.py): the flagship's conv tower
    # cannot learn to discriminate within a smoke budget, so it would
    # prove the plumbing but not the learning claim.
    import optax
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
  loop = ReplayTrainLoop(config, logdir, model=model)
  results = loop.run(steps)
  if device_resident and learner_bench:
    # The ISSUE 4 acceptance block: device-vs-host learner throughput
    # at the same batch shape (collector-free; replay/learner_bench).
    from tensor2robot_tpu.replay.learner_bench import (
        measure_learner_throughput)
    results["learner_throughput"] = measure_learner_throughput(
        batch_size=config.batch_size,
        image_size=config.image_size if smoke else 16,
        action_size=config.action_size,
        inner_steps=config.megastep_inner if smoke else 10,
        steps_per_trial=3 * (config.megastep_inner if smoke else 10),
        cem_num_samples=config.cem_num_samples,
        cem_num_elites=config.cem_num_elites,
        cem_iterations=config.cem_iterations,
        gamma=config.gamma, seed=seed)
  if vector_actors and actor_bench:
    # The ISSUE 5 acceptance block: vector-vs-threaded actor throughput
    # at the same policy and env count, plus the acting/learning
    # overlap fraction (collector-free ratio; replay/actor_bench).
    from tensor2robot_tpu.replay.actor_bench import (
        measure_actor_throughput)
    results["actor_throughput"] = measure_actor_throughput(
        image_size=config.image_size if smoke else 16,
        action_size=config.action_size,
        max_attempts=config.max_attempts,
        grasp_radius=config.grasp_radius,
        exploration_epsilon=config.exploration_epsilon,
        scripted_fraction=config.scripted_fraction,
        cem_num_samples=config.cem_num_samples,
        cem_num_elites=config.cem_num_elites,
        cem_iterations=config.cem_iterations,
        batch_size=config.batch_size, gamma=config.gamma, seed=seed)
  if anakin and anakin_bench:
    # The ISSUE 6 acceptance block: fused-anakin vs numpy-vector-fleet
    # env throughput at the same env count and policy, plus the fused
    # loop's host-blocked fraction (replay/anakin_bench).
    from tensor2robot_tpu.replay.anakin_bench import (
        measure_anakin_throughput)
    results["anakin_throughput"] = measure_anakin_throughput(
        image_size=config.image_size if smoke else 16,
        action_size=config.action_size,
        max_attempts=config.max_attempts,
        grasp_radius=config.grasp_radius,
        exploration_epsilon=config.exploration_epsilon,
        scripted_fraction=config.scripted_fraction,
        cem_num_samples=config.cem_num_samples,
        cem_num_elites=config.cem_num_elites,
        cem_iterations=config.cem_iterations,
        train_every=config.anakin_train_every,
        batch_size=config.batch_size, gamma=config.gamma, seed=seed)
  results["mode"] = "smoke" if smoke else "full"
  results["metric"] = ("QT-Opt off-policy replay loop: eval Bellman "
                       "residual reduction")
  return results


def main(argv=None) -> None:
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--steps", type=int, default=0,
                      help="optimizer steps (0 = mode default)")
  parser.add_argument("--smoke", action="store_true",
                      help="chipless CI scale on the CPU backend")
  parser.add_argument("--device-resident", action="store_true",
                      help="device-resident replay + fused megastep "
                           "learner (numpy host path is the default)")
  parser.add_argument("--no-learner-bench", action="store_true",
                      help="skip the learner_throughput comparison "
                           "block on --device-resident runs")
  parser.add_argument("--vector-actors", action="store_true",
                      help="vectorized actor fleet: batched env "
                           "stepping through one fused CEM bucket "
                           "executable (threaded scalar collectors "
                           "are the default fallback)")
  parser.add_argument("--no-actor-bench", action="store_true",
                      help="skip the actor_throughput comparison "
                           "block on --vector-actors runs")
  parser.add_argument("--anakin", action="store_true",
                      help="fully fused Anakin loop: JAX-native env + "
                           "acting + replay extend + learner in ONE "
                           "donated executable (replay/anakin.py); "
                           "the vector-actor and threaded paths stay "
                           "the measured fallbacks")
  parser.add_argument("--no-anakin-bench", action="store_true",
                      help="skip the anakin_throughput comparison "
                           "block on --anakin runs")
  parser.add_argument("--mesh", default="0",
                      help="DP or DP,TP device mesh for the loop "
                           "(default: the mode's single-mesh default; "
                           "with --anakin this is the pod-scale "
                           "sharded configuration — ISSUE 7)")
  parser.add_argument("--precision", default="f32",
                      choices=("f32", "bf16"),
                      help="CEM Q-scoring tier (ISSUE 13): f32 = the "
                           "unchanged oracle (bit-identical lowering); "
                           "bf16 = low-precision scoring matmuls for "
                           "acting, Bellman labeling, and the "
                           "collectors' CEM policy — gradients, "
                           "optimizer state, TD priorities, and the "
                           "eval-vs-Q* metric stay f32")
  parser.add_argument("--profile", default=None,
                      help="START,END optimizer-step window for a "
                           "jax.profiler device-trace capture into "
                           "<logdir>/profile (the train ProfilerHook's "
                           "windowed capture, now on every replay "
                           "path; the window snaps outward to the "
                           "loop's dispatch boundaries, and the "
                           "guarded start_trace prevents a double "
                           "capture when another window is active)")
  parser.add_argument("--logdir", default=None,
                      help="metric_writer logdir (default: a tempdir)")
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--out", default=None,
                      help="also write the JSON line to this file")
  args = parser.parse_args(argv)
  mesh = parse_mesh(args.mesh)
  profile_window = parse_profile(args.profile)
  if args.smoke:
    n_devices = mesh[0] * mesh[1]
    if n_devices > 1:
      # A multi-device smoke needs the virtual CPU mesh configured
      # BEFORE JAX initializes: re-exec with the canonical
      # environment. is_cpu_mesh_env is the loop guard: the re-exec'd
      # process passes it and falls through.
      from tensor2robot_tpu.utils.cpu_mesh_env import (cpu_mesh_env,
                                                       is_cpu_mesh_env)
      if not is_cpu_mesh_env(n_devices):
        if argv is not None:
          raise RuntimeError(
              "a multi-device --smoke mesh needs the virtual CPU mesh "
              "set up before JAX initializes; call main() with "
              "argv=None (the CLI re-execs itself) or pre-set "
              "cpu_mesh_env in the parent.")
        os.execve(sys.executable,
                  [sys.executable, "-m",
                   "tensor2robot_tpu.bin.run_qtopt_replay",
                   *sys.argv[1:]],
                  cpu_mesh_env(n_devices))
    # Chipless lane: pin the CPU backend before JAX initializes
    # (mirrors bench_serving --smoke; imports above are lazy for this).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
  from tensor2robot_tpu.utils import compile_cache
  from tensor2robot_tpu.utils.device_info import device_summary
  compile_cache.configure()
  steps = args.steps or (300 if args.smoke else 10_000)
  logdir = args.logdir or tempfile.mkdtemp(prefix="qtopt_replay_")
  results = run(steps, args.smoke, logdir, args.seed,
                device_resident=args.device_resident,
                learner_bench=not args.no_learner_bench,
                vector_actors=args.vector_actors,
                actor_bench=not args.no_actor_bench,
                anakin=args.anakin,
                anakin_bench=not args.no_anakin_bench,
                mesh=mesh, profile_window=profile_window,
                precision=args.precision)
  line = json.dumps({**results, **device_summary()})
  if args.out:
    with open(args.out, "w") as f:
      f.write(line + "\n")
  print(line)


if __name__ == "__main__":
  main()

"""Flagship-at-mesh-scale bench: rule-partitioned TP + int8 serving — TPQUANT_r17.

The ISSUE 16 acceptance instrument. Two claims, one JSON line (the
repo's bench/driver contract):

1. **TP scaling ladder** — the flagship `QTOptGraspingModel` (the
   production conv tower, uint8 wire, GroupNorm) runs the FUSED anakin
   loop at tp ∈ {1, 2, 4, 8} on a {"data": 1, "model": tp} mesh, with
   partition specs derived from the model's own regex rules
   (`QTOptGraspingModel.partition_rules` → `tp_rules.
   partition_specs_for_model`) threaded through `Trainer` into the ONE
   donated `anakin_step` executable. Acceptance is STRUCTURAL, not
   timing: every rung compiles exactly one `anakin_step`; every tp > 1
   rung's final TrainState has its critic params ACTUALLY partitioned
   (leaf shardings carry the model axis — `param_sharding.
   model_sharded_leaves`, not just a mesh shape claim) with per-replica
   param bytes shrunk ~tp×; and the tp = 1 rung is the r09/r10 oracle —
   it lowers with NO partition specs, zero model-sharded leaves, and
   two identically-seeded runs are BITWISE equal (eval history and
   train metrics), so the flag-off path is provably untouched. The
   measured step rates are published as diagnostics with the honest
   `virtual_mesh` caveat: XLA virtual CPU devices share one physical
   socket, so partitioning OVERHEAD is visible but chip SPEEDUP is not
   — the compact `tp_scaling_efficiency` is null on a virtual mesh.
2. **int8 served-params tier** — per-output-channel symmetric weight
   quantization of the SERVED tree (`cem.cast_scoring_variables
   (variables, "int8")` at policy placement time; activations and the
   CEM search run the bf16 tier contract, scores return f32 before
   top_k). Proven the same way bf16 was in r14: paired f32/int8
   `CEMFleetPolicy` requests over the committed jax_grasping scene
   corpus on a TRAINED critic, q-oracle VALUE agreement ≥ 0.99 at the
   rollout gate's q_tol; per-tier exactly-once compile ledger
   (`cem_bucket_<n>` + `cem_bucket_<n>_int8`) with `tier_shares` split
   per dtype; served-bytes reduction ≥ 3× on the flagship tree (the
   HBM-bandwidth win the tier exists for); and the tier enters the
   fleet ONLY through the shadow→canary→promote gate — an injected
   q-delta breach auto-rolls back with the fleet untouched, then the
   healthy int8 tier walks the full cycle and the fleet actually
   serves it on the 8-virtual-device mesh.

HONESTY CAVEAT (carried as `virtual_mesh`): chipless, every timing
figure here is a virtual-CPU-mesh diagnostic. int8 agreement, ledger
structure, sharding evidence, and byte counts are device-independent
claims and stand; `tp_scaling_efficiency` (a chip claim) is null by
rule until this bench runs on real chips.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import numpy as np

R17_TP_LADDER = (1, 2, 4, 8)
R17_BUCKETS = (1, 4, 8)
R17_Q_TOL = 0.05             # value-space q-delta bar (rollout gate figure)
R17_INT8_AGREEMENT_BAR = 0.99
R17_INT8_BYTES_REDUCTION_BAR = 3.0


def _run_flagship_anakin(tp: int, steps: int, seed: int,
                         image_size: int) -> Dict:
  """One ladder rung: the DEFAULT (flagship) model through the fused
  anakin loop on a {"data": 1, "model": tp} mesh. Returns the loop
  result plus wall-clock per optimizer step."""
  import tempfile

  from tensor2robot_tpu.replay.loop import ReplayLoopConfig, ReplayTrainLoop

  config = ReplayLoopConfig(
      anakin=True, mesh_dp=1, mesh_tp=tp, image_size=image_size,
      seed=seed, batch_size=8, capacity=128, min_fill=32,
      anakin_bank_scenes=32, anakin_inner=16, anakin_train_every=8,
      cem_num_samples=8, cem_num_elites=2, cem_iterations=1,
      eval_every=max(steps, 1), eval_batches=1, num_buffer_shards=1)
  loop = ReplayTrainLoop(config, tempfile.mkdtemp(prefix=f"tpq{tp}_"))
  start = time.perf_counter()
  result = loop.run(steps)
  elapsed = time.perf_counter() - start
  result["wall_seconds"] = elapsed
  result["steps_per_sec"] = result["steps"] / max(elapsed, 1e-9)
  return result


def _rung_summary(tp: int, result: Dict) -> Dict:
  sharding = result["param_sharding"]
  return {
      "tp": tp,
      "mesh_shape": {str(k): int(v)
                     for k, v in dict(result["mesh_shape"]).items()},
      "anakin_step_compiles": result["compile_counts"].get("anakin_step"),
      "ledger_all_one": all(
          v == 1 for v in result["compile_counts"].values()),
      "param_sharding": sharding,
      "replica_bytes_factor": round(
          sharding["param_bytes_total"]
          / max(sharding["param_bytes_per_replica"], 1), 3),
      "steps": result["steps"],
      "steps_per_sec": round(result["steps_per_sec"], 4),
      "final_eval_td": result["final_eval"]["eval_td_error"],
  }


def _measure_tp_ladder(ladder: Sequence[int], steps: int, seed: int,
                       image_size: int) -> Dict:
  """The flagship scaling ladder + the tp=1 bitwise oracle pair."""
  rungs = {}
  oracle = None
  for tp in ladder:
    result = _run_flagship_anakin(tp, steps, seed, image_size)
    rungs[str(tp)] = _rung_summary(tp, result)
    if tp == 1:
      # Oracle pair: the SAME tp=1 config again — the flag-off path
      # must be deterministic to the bit (eval history and the final
      # train metrics), and carry zero model-sharded leaves. (HEAD
      # bit-identity itself is pinned by the committed REPLAY_SMOKE
      # r09/r10 regression suite; this proves the TP wiring left the
      # lowered tp=1 program deterministic and unsharded.)
      rerun = _run_flagship_anakin(1, steps, seed, image_size)
      histories_equal = all(
          a.keys() == b.keys()
          and all(a[key] == b[key] for key in a)
          for a, b in zip(result["eval_history"], rerun["eval_history"]))
      oracle = {
          "bitwise_equal": bool(
              histories_equal
              and len(result["eval_history"]) == len(
                  rerun["eval_history"])
              and result["final_eval"] == rerun["final_eval"]),
          "model_sharded_leaves": result["param_sharding"][
              "model_sharded_leaves"],
      }
  base_rate = rungs[str(ladder[0])]["steps_per_sec"]
  top = str(max(ladder))
  return {
      "ladder": [int(tp) for tp in ladder],
      "steps": steps,
      "rungs": rungs,
      "tp1_oracle": oracle,
      # Diagnostic only on a virtual mesh: all rungs share one socket,
      # so this measures partitioning OVERHEAD, not chip scaling.
      "scaling_efficiency_diagnostic": round(
          rungs[top]["steps_per_sec"] / max(base_rate, 1e-9), 4),
      "note": ("fixed per-rung workload; virtual CPU devices time-share "
               "one socket, so rates are partitioning-overhead "
               "diagnostics — the chip claim stays null (virtual_mesh)."),
  }


def _int8_bytes_reduction(variables) -> float:
  """Dense-f32 vs int8-wrapper served bytes for one variables tree."""
  import jax

  from tensor2robot_tpu.research.qtopt import cem

  def tree_bytes(tree) -> int:
    return sum(
        int(np.asarray(leaf).nbytes)
        for leaf in jax.tree_util.tree_leaves(tree))

  dense = tree_bytes(variables)
  quantized = tree_bytes(cem.cast_scoring_variables(variables, "int8"))
  return dense / max(quantized, 1)


def _flagship_bytes_reduction(image_size: int, seed: int) -> Dict:
  """The flagship tree's int8 served-bytes reduction (TinyQ alongside
  for scale). Both are kernel-dominated so both land near the 4x
  weight-width ceiling (per-channel scales + replicated biases/norms
  cost the gap to 4.0); the bar is on the FLAGSHIP — the tree whose
  HBM traffic the tier exists to cut."""
  import jax
  import optax

  from tensor2robot_tpu.replay.smoke import TinyQCriticModel
  from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel

  flagship = QTOptGraspingModel(
      image_size=image_size, action_size=4, uint8_images=True,
      norm="group", optimizer_fn=lambda: optax.adam(3e-3))
  tiny = TinyQCriticModel(optimizer_fn=lambda: optax.adam(3e-3))
  rng = jax.random.key(seed)
  out = {}
  for name, model in (("flagship", flagship), ("tinyq", tiny)):
    variables = jax.device_get(
        model.init_variables(rng, batch_size=1))
    out[name] = round(_int8_bytes_reduction(variables), 3)
  return out


def _measure_int8_agreement(model, variables, buckets: Sequence[int],
                            corpus_scenes: int, q_tolerance: float,
                            cem_num_samples: int, cem_num_elites: int,
                            cem_iterations: int, action_size: int,
                            image_size: int, seed: int, ledger) -> Dict:
  """f32-vs-int8 paired policies on the committed scene corpus.

  The r14 agreement protocol with the int8 tier in the candidate seat:
  both policies share the predictor, CEM budget, and per-request
  fold_in seed stream; a pair agrees when the int8-selected action's
  VALUE under the f32 oracle is within `q_tolerance` of the
  f32-selected action's (value space — action identity is not the
  serving contract in continuous-action QT-Opt, see
  precision_bench._measure_agreement)."""
  import jax
  import jax.numpy as jnp

  from tensor2robot_tpu.replay.loop import _HotReloadPredictor
  from tensor2robot_tpu.research.qtopt.jax_grasping import make_scene_bank
  from tensor2robot_tpu.serving.bucketing import BucketLadder
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy

  predictor = _HotReloadPredictor(model, variables)
  bank = make_scene_bank(corpus_scenes, image_size=image_size,
                         base_seed=seed + 5)
  scenes = np.asarray(bank.images)
  q_oracle = jax.jit(
      lambda features: model.q_value(model.predict_fn(variables,
                                                      features)))

  def oracle_values(frames, actions):
    return np.asarray(q_oracle({
        "image": jnp.asarray(np.stack(frames)),
        "action": jnp.asarray(actions, jnp.float32)})).reshape(-1)

  per_bucket = {}
  agree_total = 0
  pairs_total = 0
  for bucket in buckets:
    policies = {
        precision: CEMFleetPolicy(
            predictor, action_size=action_size,
            num_samples=cem_num_samples, num_elites=cem_num_elites,
            iterations=cem_iterations, seed=seed + 7,
            ladder=BucketLadder((bucket,)), ledger=ledger,
            precision=precision)
        for precision in ("f32", "int8")}
    q_deltas = []
    calls = max(1, corpus_scenes // bucket)
    for call in range(calls):
      idx = (np.arange(bucket) + call * bucket) % corpus_scenes
      frames = [scenes[i] for i in idx]
      seeds = np.arange(call * bucket, (call + 1) * bucket,
                        dtype=np.uint32)
      actions = {precision: np.asarray(policy(frames, seeds))
                 for precision, policy in policies.items()}
      q_deltas.append(oracle_values(frames, actions["f32"])
                      - oracle_values(frames, actions["int8"]))
    q_deltas = np.concatenate(q_deltas)
    agree = int(np.sum(q_deltas <= q_tolerance))
    agree_total += agree
    pairs_total += q_deltas.size
    per_bucket[str(bucket)] = {
        "pairs": int(q_deltas.size),
        "agreement_rate": round(agree / q_deltas.size, 4),
        "q_delta_mean": round(float(q_deltas.mean()), 5),
        "q_delta_p99": round(float(np.percentile(q_deltas, 99)), 5),
        "q_delta_max": round(float(q_deltas.max()), 5),
    }
  return {
      "q_tolerance": q_tolerance,
      "corpus_scenes": corpus_scenes,
      "per_bucket": per_bucket,
      "pairs": pairs_total,
      "overall_rate": round(agree_total / max(pairs_total, 1), 4),
  }


def _measure_rollout_int8(n_devices: Optional[int], cem_num_samples: int,
                          cem_num_elites: int, cem_iterations: int,
                          min_shadow: int, min_canary: int,
                          cycle_bound_s: float, seed: int) -> Dict:
  """The promotion gate with int8 in the candidate seat: an injected
  q-delta breach (corrupted tree scored through the int8 tier) must
  auto-roll back with the fleet untouched on f32, then the healthy
  int8 tier walks shadow→canary→promote and the fleet actually serves
  it. One ledger across everything — exactly-once per (bucket, device,
  tier)."""
  import jax

  from tensor2robot_tpu.serving.rollout import (RolloutConfig,
                                                RolloutController)
  from tensor2robot_tpu.serving.router import FleetRouter
  from tensor2robot_tpu.serving.smoke import TinyQPredictor

  devices = jax.devices()
  if n_devices is not None:
    devices = devices[:n_devices]
  predictor = TinyQPredictor(seed=seed)
  router = FleetRouter(
      predictor, devices=devices, num_samples=cem_num_samples,
      num_elites=cem_num_elites, iterations=cem_iterations,
      ladder_sizes=(1, 2, 4), max_queue=32, seed=seed)
  router.warmup(predictor.make_image)
  controller = RolloutController(
      router, predictor,
      RolloutConfig(mirror_fraction=1.0, canary_fraction=0.5,
                    min_shadow_samples=min_shadow,
                    min_canary_samples=min_canary, seed=seed))
  frames = [predictor.make_image(seed + i) for i in range(16)]

  def drive_until_serving(i0: int) -> int:
    stop_at = time.monotonic() + cycle_bound_s
    i = i0
    while controller.state != "serving" and time.monotonic() < stop_at:
      controller.submit(frames[i % len(frames)]).result(30.0)
      i += 1
    return i

  with router, controller:
    breach = predictor.make_candidate_variables(jitter=5.0,
                                                seed=seed + 7)
    # Explicit raises (offer_* STARTS the cycle; python -O would skip
    # asserts and emit a no-protocol artifact).
    if not controller.offer_precision_candidate("int8", variables=breach):
      raise RuntimeError("breach candidate not accepted (rollout busy)")
    i = drive_until_serving(0)
    precision_after_breach = router.precision
    breach_events = [e["event"] for e in controller.timeline()]
    if not controller.offer_precision_candidate("int8"):
      raise RuntimeError("tier candidate not accepted (rollout busy)")
    i = drive_until_serving(i)
    timeline = controller.timeline()
    precision_served = router.precision
    post_promote_action = np.asarray(
        controller.act(frames[0], timeout=30.0))

  events = [entry["event"] for entry in timeline]
  return {
      "devices": len(devices),
      "events": events,
      "promotions": events.count("promote"),
      "auto_rollbacks": events.count("auto_rollback"),
      "breach_rolled_back": ("auto_rollback" in breach_events
                             and precision_after_breach == "f32"),
      "precision_served": precision_served,
      "post_promote_action_ok": bool(
          np.all(np.isfinite(post_promote_action))),
      "cycle_ok": ("promote" in events and "auto_rollback" in events
                   and precision_served == "int8"),
      "compile_ledger": router.ledger.compile_counts,
      "tier_shares": {
          tier: share["executables"]
          for tier, share in router.ledger.attribution()
          ["tier_shares"].items()},
  }


def measure_tpquant(
    tp_ladder: Sequence[int] = R17_TP_LADDER,
    ladder_steps: int = 4,
    ladder_image_size: int = 24,
    buckets: Sequence[int] = R17_BUCKETS,
    corpus_scenes: int = 64,
    q_tolerance: float = R17_Q_TOL,
    pretrain_steps: int = 250,
    rollout_devices: Optional[int] = None,
    rollout_min_shadow: int = 8,
    rollout_min_canary: int = 4,
    rollout_cycle_s: float = 90.0,
    cem_num_samples: int = 16,
    cem_num_elites: int = 4,
    cem_iterations: int = 2,
    image_size: int = 16,
    action_size: int = 4,
    gamma: float = 0.8,
    grasp_radius: float = 0.4,
    seed: int = 0,
    enforce_bars: bool = True,
) -> Dict:
  """Runs the TP-ladder + int8 protocol; returns the TPQUANT_r17
  artifact dict. `enforce_bars` (the --smoke lane) raises if any
  committed acceptance bar fails AT GENERATION TIME — a committed
  artifact that does not meet its own bars must not exist."""
  import jax

  from tensor2robot_tpu.obs import ledger as ledger_lib
  from tensor2robot_tpu.replay.precision_bench import _pretrain_critic

  device_kind = jax.devices()[0].device_kind
  virtual_mesh = device_kind.lower() == "cpu"
  usable_tp = [tp for tp in tp_ladder if tp <= len(jax.devices())]

  tp = _measure_tp_ladder(usable_tp, ladder_steps, seed,
                          ladder_image_size)

  model, variables, pretrain_loss = _pretrain_critic(
      image_size, action_size, gamma, grasp_radius, pretrain_steps,
      batch_size=64, seed=seed)

  agreement_ledger = ledger_lib.ExecutableLedger()
  agreement = _measure_int8_agreement(
      model, variables, buckets, corpus_scenes, q_tolerance,
      cem_num_samples, cem_num_elites, cem_iterations, action_size,
      image_size, seed, agreement_ledger)

  bytes_reduction = _flagship_bytes_reduction(ladder_image_size, seed)

  rollout = _measure_rollout_int8(
      rollout_devices, cem_num_samples, cem_num_elites, cem_iterations,
      rollout_min_shadow, rollout_min_canary, rollout_cycle_s, seed)

  agreement_counts = agreement_ledger.compile_counts
  per_tier_ok = (
      all(v == 1 for v in agreement_counts.values())
      and all(f"cem_bucket_{b}" in agreement_counts for b in buckets)
      and all(f"cem_bucket_{b}_int8" in agreement_counts
              for b in buckets))
  tier_shares = agreement_ledger.attribution()["tier_shares"]

  sharded_rungs = [r for r in tp["rungs"].values() if r["tp"] > 1]
  result = {
      "round": 17,
      "metric": ("flagship critic at mesh scale: rule-partitioned TP "
                 "through the fused loop + int8-served params through "
                 "the promotion gate"),
      "device_kind": device_kind,
      "virtual_mesh": virtual_mesh,
      "cem": {"num_samples": cem_num_samples,
              "num_elites": cem_num_elites,
              "iterations": cem_iterations},
      "tp": tp,
      "pretrain": {"steps": pretrain_steps,
                   "final_loss": round(pretrain_loss, 5)},
      "int8_agreement": agreement,
      "int8_agreement_bar": R17_INT8_AGREEMENT_BAR,
      "int8_bytes_reduction": bytes_reduction,
      "int8_bytes_reduction_bar": R17_INT8_BYTES_REDUCTION_BAR,
      "tier_ledger": {
          "compile_counts": agreement_counts,
          "per_tier_exactly_once": bool(per_tier_ok),
          "tier_shares": tier_shares,
      },
      "rollout": rollout,
      # Compact sentinels (round 17; null-safe): agreement and
      # byte counts are device-independent; scaling efficiency is a
      # CHIP claim and stays null on a virtual mesh.
      "tp_scaling_efficiency": (
          None if virtual_mesh else tp["scaling_efficiency_diagnostic"]),
      "int8_q_agreement": agreement["overall_rate"],
      "int8_param_bytes_reduction": bytes_reduction["flagship"],
      "note": (
          "flagship conv tower through ONE fused anakin_step at "
          "tp=1/2/4/8 with regex-rule partition specs (leaf shardings "
          "asserted, per-replica bytes ~tp x smaller; tp=1 is the "
          "bitwise oracle), plus the int8 served-weights tier: "
          "q-oracle value agreement vs f32 on the committed scene "
          "corpus, per-tier exactly-once ledger, >= 3x served-bytes "
          "reduction on the flagship tree, and the full shadow/canary "
          "promotion gate with an injected-breach auto-rollback. "
          "virtual_mesh=true: every timing figure is a diagnostic and "
          "tp_scaling_efficiency is null by rule; sharding structure, "
          "agreement, ledger, and byte claims are device-independent."),
  }

  if enforce_bars:
    failures = []
    for rung in tp["rungs"].values():
      if rung["anakin_step_compiles"] != 1:
        failures.append(
            f"tp={rung['tp']}: anakin_step compiled "
            f"{rung['anakin_step_compiles']} times (want 1)")
    for rung in sharded_rungs:
      if rung["param_sharding"]["model_sharded_leaves"] <= 0:
        failures.append(
            f"tp={rung['tp']}: no model-sharded param leaves")
      if rung["replica_bytes_factor"] < 0.9 * rung["tp"]:
        failures.append(
            f"tp={rung['tp']}: replica bytes factor "
            f"{rung['replica_bytes_factor']} < 0.9*tp")
    if tp["tp1_oracle"] is not None:
      if not tp["tp1_oracle"]["bitwise_equal"]:
        failures.append("tp=1 oracle pair not bitwise equal")
      if tp["tp1_oracle"]["model_sharded_leaves"] != 0:
        failures.append("tp=1 oracle has model-sharded leaves")
    if agreement["overall_rate"] < R17_INT8_AGREEMENT_BAR:
      failures.append(
          f"int8 agreement {agreement['overall_rate']} < "
          f"{R17_INT8_AGREEMENT_BAR}")
    if bytes_reduction["flagship"] < R17_INT8_BYTES_REDUCTION_BAR:
      failures.append(
          f"flagship int8 bytes reduction {bytes_reduction['flagship']} "
          f"< {R17_INT8_BYTES_REDUCTION_BAR}")
    if not per_tier_ok:
      failures.append(f"tier ledger not exactly-once: {agreement_counts}")
    if not rollout["cycle_ok"] or not rollout["breach_rolled_back"]:
      failures.append(f"rollout cycle failed: {rollout['events']}")
    if failures:
      raise AssertionError(
          "TPQUANT_r17 acceptance bars failed: " + "; ".join(failures))
  return result


def main(argv=None) -> None:
  """CLI: ONE JSON line. --smoke bootstraps the 8-virtual-device CPU
  mesh (re-exec with the canonical env) and runs the committed
  TPQUANT_r17 protocol with generation-time bar enforcement; --ci is
  the reduced tier-1 lane (structural checks only — quantitative bars
  live in tests/test_tpquant.py behind the cpu_count gate)."""
  import argparse
  import json
  import os
  import sys

  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--smoke", action="store_true",
                      help="chipless committed-artifact lane: full "
                           "protocol, bars enforced at generation time")
  parser.add_argument("--ci", action="store_true",
                      help="reduced chipless lane for tier-1 tests")
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--out", default=None,
                      help="also write the JSON line to this file")
  args = parser.parse_args(argv)
  if args.smoke or args.ci:
    from tensor2robot_tpu.utils.cpu_mesh_env import (cpu_mesh_env,
                                                     is_cpu_mesh_env)
    n = 8 if args.smoke else 2
    if not is_cpu_mesh_env(n):
      if argv is not None:
        raise RuntimeError(
            "--smoke/--ci need the virtual CPU mesh configured before "
            "JAX initializes; call main() with argv=None (the CLI "
            "re-execs itself).")
      os.execve(sys.executable,
                [sys.executable, "-m",
                 "tensor2robot_tpu.replay.tpquant_bench",
                 *sys.argv[1:]],
                cpu_mesh_env(n))
  if args.ci:
    results = measure_tpquant(
        tp_ladder=(1, 2), ladder_steps=2, buckets=(1, 2),
        corpus_scenes=24, pretrain_steps=120, rollout_devices=2,
        rollout_min_shadow=6, rollout_min_canary=3,
        rollout_cycle_s=60.0, seed=args.seed, enforce_bars=False)
  else:
    results = measure_tpquant(rollout_devices=8 if args.smoke else None,
                              seed=args.seed)
  line = json.dumps(results)
  if args.out:
    with open(args.out, "w") as f:
      f.write(line + "\n")
  print(line)


if __name__ == "__main__":
  main()

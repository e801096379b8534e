"""Precision-tier bench: bf16 CEM scoring vs the f32 oracle — PRECISION_r14.

The ISSUE 13 acceptance instrument. Q-inference inside CEM dominates
acting, Bellman labeling, and serving; this bench proves the bf16
scoring tier safe against the f32 oracle FOUR ways and emits ONE JSON
line (the repo's bench/driver contract):

1. **Selected-action agreement** — a TinyQ critic is first TRAINED to
   the retry env's analytic fixed point (Q* = success ? 1 : gamma, the
   replay loop's eval recipe) so the agreement bar runs on a REAL Q
   landscape, not random-init noise; then, for every ladder bucket, the
   same (scene, seed) requests go through an f32 and a bf16
   `CEMFleetPolicy` (identical CEM hyperparameters and fold_in seed
   stream — the only difference is the scoring tier) over scenes from
   the committed jax_grasping scene-bank corpus. Agreement = the pair's
   bf16-selected action scores within `q_tol` of the f32-selected
   action UNDER THE F32 ORACLE (value space — the per-request form of
   the rollout gate's q-delta bar; in continuous-action QT-Opt the
   action's value, not its identity, is the serving contract — the
   geometric deltas are reported as diagnostics next to a
   seed-noise control pinning the search's own floor). Acceptance:
   overall rate >= 0.95.
2. **Fused-loop TD bar** — the full anakin replay smoke protocol runs
   once per tier (`ReplayLoopConfig(precision=...)`); the bf16 loop's
   eval-TD reduction (measured by the f32-always eval metric, as the
   converged-phase mean over every eval point past steps/3 — the
   converged loop's eval TD oscillates identically for both tiers, so
   the comparison statistic averages the phase out) must land within
   0.05 of the f32 bar.
3. **Per-tier compile ledger** — the shared obs ledger must show every
   bucket executable exactly once PER TIER (tier-suffixed keys), and
   `attribution()["tier_shares"]` splits the device time per dtype.
4. **Live-traffic rollout** — the PR 7 shadow→canary→promote harness
   drives a bf16 candidate TIER over paired live traffic: an injected
   q-delta breach (a corrupted tree scored through the candidate tier)
   must auto-roll back with the fleet untouched, then the healthy tier
   must walk shadow→canary→promote and the fleet actually serve bf16 —
   the first live-traffic promotion gate for a numerics change.

HONESTY CAVEAT (carried as `virtual_mesh`): chipless, the devices are
XLA virtual CPU devices and bf16 matmuls are emulated — the measured
scoring rates say nothing about chip speedups (CPU bf16 is typically
SLOWER), so the compact `cem_bf16_speedup` is null on a virtual mesh
and the chipless artifact's claims are structure + parity. The real
speedup is not measured: a served-tier cell decides it (ROADMAP B10).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import numpy as np

R14_BUCKETS = (1, 2, 4, 8, 16)
R14_Q_TOL = 0.05          # per-request q-delta bar, value space [0, 1]
                          # (the RolloutConfig.max_q_regression figure)
R14_GEO_TOL = 0.1         # max-abs action delta diagnostic, [-1, 1] box
R14_AGREEMENT_BAR = 0.95  # committed acceptance rate
R14_TD_DELTA_BAR = 0.05   # |bf16 - f32| eval-TD-reduction ceiling


def _pretrain_critic(image_size: int, action_size: int, gamma: float,
                     grasp_radius: float, steps: int, batch_size: int,
                     seed: int):
  """A TinyQ critic fitted to the analytic Q* (the loop's eval oracle).

  Supervised on (scene, action) -> (success ? 1 : gamma) with the
  class-balanced action recipe of ReplayTrainLoop._eval_transitions, so
  the CEM landscape the agreement bar searches is the trained one
  production would serve. Returns (model, host_variables, final_loss).
  """
  import jax
  import optax

  from tensor2robot_tpu.export import export_utils
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.replay.smoke import TinyQCriticModel
  from tensor2robot_tpu.research.qtopt import synthetic_grasping as sg
  from tensor2robot_tpu.train.trainer import Trainer

  model = TinyQCriticModel(image_size=image_size, action_size=action_size,
                           optimizer_fn=lambda: optax.adam(3e-3))
  # Single-device mesh: the agreement phase is a numerics comparison;
  # sharding is PR 6's axis, deliberately out of frame here.
  mesh = mesh_lib.create_mesh({"data": 1, "model": 1},
                              devices=jax.devices()[:1])
  trainer = Trainer(model, mesh=mesh, seed=seed)
  state = trainer.create_train_state(batch_size=batch_size)

  n = batch_size * 16
  rng = np.random.default_rng(seed + 77)
  images, targets = sg.sample_scenes(n, image_size=image_size,
                                     seed=seed + 78, num_distractors=0,
                                     occlusion=False)
  actions = rng.uniform(-1.0, 1.0, (n, action_size)).astype(np.float32)
  near = rng.random(n) < 0.5
  noise = rng.normal(0.0, 0.12, (n, 2)).astype(np.float32)
  actions[near, :2] = np.clip(targets[near] + noise[near], -1.0, 1.0)
  success = sg.grasp_success(targets, actions,
                             grasp_radius).astype(np.float32)
  q_star = np.where(success > 0, 1.0, gamma).astype(np.float32)

  compiled = None
  loss = None
  for step in range(steps):
    part = np.arange(step * batch_size, (step + 1) * batch_size) % n
    features = {"image": images[part], "action": actions[part]}
    labels = {model.target_key: q_star[part]}
    sharded = trainer.shard_batch((features, labels))
    if compiled is None:
      compiled = trainer.aot_train_step(state, *sharded)
    state, metrics = compiled(state, *sharded)
    loss = float(metrics["loss"])
  host_variables = export_utils.fetch_variables_to_host(
      state.variables(use_ema=True))
  return model, host_variables, loss


def _measure_agreement(model, variables, buckets: Sequence[int],
                       corpus_scenes: int, q_tolerance: float,
                       geo_tolerance: float,
                       cem_num_samples: int, cem_num_elites: int,
                       cem_iterations: int, action_size: int,
                       image_size: int, seed: int, ledger) -> Dict:
  """f32-vs-bf16 selected actions, per bucket, on the committed corpus.

  Both policies share the predictor, the CEM budget, and the per-request
  fold_in seed stream; requests are paired on (scene, seed), so every
  action delta is the scoring tier's numerics and nothing else.

  SELECTED-ACTION AGREEMENT — the committed bar — is VALUE agreement
  under the f32 oracle: a pair agrees when
  Q_f32(s, a_f32) - Q_f32(s, a_bf16) <= `q_tolerance` (value space;
  the same per-request form of the rollout gate's q-delta bar). In
  continuous-action QT-Opt the action's IDENTITY is not the serving
  contract — the trained Q's success basin is deliberately wide
  (grasp_radius), every point in it is an argmax, and which one a
  CEM elite-mean lands on is undetermined at the search's own noise
  floor. The geometric max-abs deltas are reported as diagnostics, and
  the `seed_noise_control` pins the floor: two f32 policies differing
  ONLY in their CEM sampling seed disagree geometrically about as much
  as the bf16 tier does — the tier adds nothing the search itself
  left undetermined. Also measures each tier's warmed dispatch rate
  (the chip-window speedup source; host rates carry the virtual-mesh
  caveat).
  """
  import jax
  import jax.numpy as jnp

  from tensor2robot_tpu.replay.loop import _HotReloadPredictor
  from tensor2robot_tpu.research.qtopt.jax_grasping import make_scene_bank
  from tensor2robot_tpu.serving.bucketing import BucketLadder
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy

  predictor = _HotReloadPredictor(model, variables)
  # The committed scene corpus: the jax env's oracle-rendered bank
  # (PR 5's bit-exactness corpus), cycled per bucket.
  bank = make_scene_bank(corpus_scenes, image_size=image_size,
                         base_seed=seed + 5)
  scenes = np.asarray(bank.images)
  # The f32 oracle's value function (value space: [0, 1] for the
  # cross-entropy head), compiled once at one flat shape per bucket.
  q_oracle = jax.jit(
      lambda features: model.q_value(model.predict_fn(variables,
                                                      features)))

  def oracle_values(frames, actions):
    return np.asarray(q_oracle({
        "image": jnp.asarray(np.stack(frames)),
        "action": jnp.asarray(actions, jnp.float32)})).reshape(-1)

  def make_policy(precision, policy_seed, bucket, with_ledger=True):
    # The seed-noise control stays OFF the shared ledger: it would
    # re-register the measured f32 policy's bucket key and break the
    # per-tier exactly-once claim it has nothing to do with.
    return CEMFleetPolicy(
        predictor, action_size=action_size,
        num_samples=cem_num_samples, num_elites=cem_num_elites,
        iterations=cem_iterations, seed=policy_seed,
        ladder=BucketLadder((bucket,)),
        ledger=ledger if with_ledger else None,
        precision=precision)

  per_bucket = {}
  rates = {"f32": [], "bf16": []}
  agree_total = 0
  pairs_total = 0
  control_geo = []
  control_qd = []
  for bucket in buckets:
    policies = {precision: make_policy(precision, seed + 7, bucket)
                for precision in ("f32", "bf16")}
    # The seed-noise control rides the FIRST bucket only (one extra
    # ladder compile; the floor is bucket-independent — the search is
    # per-state).
    control = (make_policy("f32", seed + 8, bucket, with_ledger=False)
               if bucket == buckets[0] else None)
    geo_diffs, q_deltas = [], []
    calls = max(1, corpus_scenes // bucket)
    timing = {"f32": 0.0, "bf16": 0.0}
    for call in range(calls):
      idx = (np.arange(bucket) + call * bucket) % corpus_scenes
      frames = [scenes[i] for i in idx]
      seeds = np.arange(call * bucket, (call + 1) * bucket,
                        dtype=np.uint32)
      actions = {}
      for precision, policy in policies.items():
        start = time.perf_counter()
        actions[precision] = np.asarray(policy(frames, seeds))
        elapsed = time.perf_counter() - start
        if call:  # first call pays the bucket compile — excluded
          timing[precision] += elapsed
      geo_diffs.append(
          np.max(np.abs(actions["f32"] - actions["bf16"]), axis=1))
      q_f32 = oracle_values(frames, actions["f32"])
      q_bf16 = oracle_values(frames, actions["bf16"])
      q_deltas.append(q_f32 - q_bf16)
      if control is not None:
        control_actions = np.asarray(control(frames, seeds))
        control_geo.append(
            np.max(np.abs(actions["f32"] - control_actions), axis=1))
        control_qd.append(q_f32 - oracle_values(frames, control_actions))
    geo_diffs = np.concatenate(geo_diffs)
    q_deltas = np.concatenate(q_deltas)
    agree = int(np.sum(q_deltas <= q_tolerance))
    agree_total += agree
    pairs_total += q_deltas.size
    if calls > 1:
      for precision in ("f32", "bf16"):
        rates[precision].append(
            (calls - 1) * bucket / max(timing[precision], 1e-9))
    per_bucket[str(bucket)] = {
        "pairs": int(q_deltas.size),
        "agreement_rate": round(agree / q_deltas.size, 4),
        "q_delta_mean": round(float(q_deltas.mean()), 5),
        "q_delta_p99": round(float(np.percentile(q_deltas, 99)), 5),
        "q_delta_max": round(float(q_deltas.max()), 5),
        "action_maxabs_mean": round(float(geo_diffs.mean()), 5),
        "action_maxabs_p99": round(
            float(np.percentile(geo_diffs, 99)), 5),
        "geo_within_tol": round(
            float(np.mean(geo_diffs <= geo_tolerance)), 4),
    }
  control_geo = np.concatenate(control_geo)
  control_qd = np.concatenate(control_qd)
  f32_hz = float(np.mean(rates["f32"])) if rates["f32"] else None
  bf16_hz = float(np.mean(rates["bf16"])) if rates["bf16"] else None
  return {
      "q_tolerance": q_tolerance,
      "geo_tolerance": geo_tolerance,
      "corpus_scenes": corpus_scenes,
      "per_bucket": per_bucket,
      "pairs": pairs_total,
      "overall_rate": round(agree_total / max(pairs_total, 1), 4),
      "seed_noise_control": {
          "note": "two f32 policies, different CEM sampling seeds, "
                  "same requests — the search's own geometric noise "
                  "floor; the bf16 tier's geometric deltas sit at or "
                  "below it, and its q-agreement matches.",
          "pairs": int(control_geo.size),
          "action_maxabs_mean": round(float(control_geo.mean()), 5),
          "geo_within_tol": round(
              float(np.mean(control_geo <= geo_tolerance)), 4),
          "q_agreement_rate": round(
              float(np.mean(control_qd <= q_tolerance)), 4),
      },
      "scoring_rate": {
          "f32_actions_per_sec": round(f32_hz, 1) if f32_hz else None,
          "bf16_actions_per_sec": round(bf16_hz, 1) if bf16_hz else None,
          "bf16_speedup": (round(bf16_hz / f32_hz, 3)
                           if f32_hz and bf16_hz else None),
          "note": "warmed dispatch rate, compile excluded; on a "
                  "virtual CPU mesh bf16 is emulated and the ratio "
                  "says nothing about chips (see virtual_mesh).",
      },
  }


def _measure_fused_loop(steps: int, seed: int) -> Dict:
  """The anakin replay smoke protocol once per tier; the f32 run IS the
  oracle bar the bf16 reduction is held against (both reductions are
  measured by the f32-always eval-TD metric against analytic Q*)."""
  import tempfile

  import optax

  from tensor2robot_tpu.replay.loop import ReplayLoopConfig, ReplayTrainLoop
  from tensor2robot_tpu.replay.smoke import TinyQCriticModel

  out = {"steps": steps}
  for precision in ("f32", "bf16"):
    # Explicit 1-device mesh: the tier comparison runs on the unsharded
    # oracle path (sharding is PR 6's axis; on a multi-device bench env
    # the trainer default would otherwise mesh every visible device).
    # Dense eval cadence (every 15 steps): the comparison statistic
    # below averages the converged phase, and more points buy variance.
    config = ReplayLoopConfig(anakin=True, precision=precision, seed=seed,
                              mesh_dp=1, mesh_tp=1, eval_every=15)
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    loop = ReplayTrainLoop(config, tempfile.mkdtemp(prefix="prec_"),
                           model=model)
    result = loop.run(steps)
    ledger_counts = dict(result["compile_counts"])
    initial = result["initial_eval"]["eval_td_error"]
    # The COMPARISON statistic is the CONVERGED-PHASE mean reduction:
    # mean eval TD over every point in the last two-thirds of the run
    # vs step 0. The converged loop's eval TD oscillates (~0.13-0.25
    # at this scale) with the replay mixture, identically for both
    # tiers, so the single final-point reduction (REPLAY_SMOKE's
    # own-run convention, kept as a diagnostic) is an oscillation-
    # phase lottery no 0.05 cross-RUN bar can ride on — the window is
    # fixed (step > steps/3), declared up front, same for both tiers.
    converged = [entry["eval_td_error"]
                 for entry in result["eval_history"]
                 if entry["step"] > steps // 3]
    converged_reduction = 1.0 - (float(np.mean(converged))
                                 / max(initial, 1e-9))
    out[precision] = {
        "eval_td_reduction_converged": round(converged_reduction, 4),
        "converged_eval_points": len(converged),
        "eval_td_reduction_final_point": result["eval_td_reduction"],
        "initial_eval_td": initial,
        "final_eval_td": result["final_eval"]["eval_td_error"],
        "eval_history": [
            {"step": entry["step"],
             "eval_td_error": round(entry["eval_td_error"], 5)}
            for entry in result["eval_history"]],
        "anakin_step_compiles": ledger_counts.get("anakin_step"),
        "ledger_all_one": all(v == 1 for v in ledger_counts.values()),
    }
  out["td_delta"] = round(
      abs(out["bf16"]["eval_td_reduction_converged"]
          - out["f32"]["eval_td_reduction_converged"]), 4)
  return out


def _measure_rollout(n_devices: Optional[int], cem_num_samples: int,
                     cem_num_elites: int, cem_iterations: int,
                     min_shadow: int, min_canary: int, cycle_bound_s: float,
                     seed: int) -> Dict:
  """The live-traffic gate: breach first (bf16 tier over a corrupted
  tree -> auto_rollback, fleet untouched), then the healthy bf16 tier
  shadow→canary→promote, with the fleet verified actually serving the
  promoted tier. One ledger across warmup, both cycles, and the
  post-promote traffic — exactly-once per bucket per device per tier."""
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
    # Injected q-delta breach: a jittered tree scored THROUGH the bf16
    # candidate tier — the numerics-change analogue of fleet_bench's
    # regressed checkpoint. Must roll back in shadow; the fleet stays
    # on its live tier.
    breach = predictor.make_candidate_variables(jitter=5.0,
                                                seed=seed + 7)
    # Explicit raises, not asserts: offer_precision_candidate has the
    # side effect of STARTING the cycle — under python -O an assert
    # would silently skip both cycles and emit a no-protocol artifact.
    if not controller.offer_precision_candidate("bf16", variables=breach):
      raise RuntimeError("breach candidate not accepted (rollout busy)")
    i = drive_until_serving(0)
    precision_after_breach = router.precision
    breach_events = [e["event"] for e in controller.timeline()]
    # The healthy tier candidate: live params, bf16 executables.
    if not controller.offer_precision_candidate("bf16"):
      raise RuntimeError("tier candidate not accepted (rollout busy)")
    i = drive_until_serving(i)
    timeline = controller.timeline()
    precision_served = router.precision
    # Post-promote traffic through the promoted tier.
    post_promote_action = np.asarray(
        controller.act(frames[0], timeout=30.0))

  events = [entry["event"] for entry in timeline]
  return {
      "devices": len(devices),
      "timeline": timeline,
      "events": events,
      "promotions": events.count("promote"),
      "auto_rollbacks": events.count("auto_rollback"),
      "breach_rolled_back": ("auto_rollback" in breach_events
                             and precision_after_breach == "f32"),
      "precision_served": precision_served,
      "post_promote_action_ok": bool(
          np.all(np.isfinite(post_promote_action))),
      "cycle_ok": ("promote" in events and "auto_rollback" in events
                   and precision_served == "bf16"),
      "compile_ledger": router.ledger.compile_counts,
      "tier_shares": {
          tier: share["executables"]
          for tier, share in router.ledger.attribution()
          ["tier_shares"].items()},
  }


def measure_precision(
    buckets: Sequence[int] = R14_BUCKETS,
    corpus_scenes: int = 64,
    q_tolerance: float = R14_Q_TOL,
    geo_tolerance: float = R14_GEO_TOL,
    pretrain_steps: int = 250,
    loop_steps: int = 300,
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
  """Runs the four-phase precision protocol; returns the PRECISION_r14
  artifact dict. `enforce_bars` (the --smoke lane) raises if any
  committed acceptance bar fails AT GENERATION TIME — a committed
  artifact that does not meet its own bars must not exist."""
  import jax

  from tensor2robot_tpu.obs import ledger as ledger_lib

  device_kind = jax.devices()[0].device_kind
  virtual_mesh = device_kind.lower() == "cpu"

  model, variables, pretrain_loss = _pretrain_critic(
      image_size, action_size, gamma, grasp_radius, pretrain_steps,
      batch_size=64, seed=seed)

  agreement_ledger = ledger_lib.ExecutableLedger()
  agreement = _measure_agreement(
      model, variables, buckets, corpus_scenes, q_tolerance,
      geo_tolerance, cem_num_samples, cem_num_elites, cem_iterations,
      action_size, image_size, seed, agreement_ledger)

  fused = _measure_fused_loop(loop_steps, seed)

  rollout = _measure_rollout(
      rollout_devices, cem_num_samples, cem_num_elites, cem_iterations,
      rollout_min_shadow, rollout_min_canary, rollout_cycle_s, seed)

  # Per-tier exactly-once over the agreement phase's shared ledger: one
  # f32 and one bf16 executable per bucket (tier-suffixed keys).
  agreement_counts = agreement_ledger.compile_counts
  per_tier_ok = (
      all(v == 1 for v in agreement_counts.values())
      and all(f"cem_bucket_{b}" in agreement_counts for b in buckets)
      and all(f"cem_bucket_{b}_bf16" in agreement_counts
              for b in buckets))
  tier_shares = agreement_ledger.attribution()["tier_shares"]

  speedup = agreement["scoring_rate"]["bf16_speedup"]
  result = {
      "round": 14,
      "metric": "precision-tiered CEM: bf16 Q-scoring vs the f32 oracle",
      "device_kind": device_kind,
      "virtual_mesh": virtual_mesh,
      "cem": {"num_samples": cem_num_samples,
              "num_elites": cem_num_elites,
              "iterations": cem_iterations},
      "buckets": [int(b) for b in buckets],
      "pretrain": {"steps": pretrain_steps,
                   "final_loss": round(pretrain_loss, 5)},
      "agreement": agreement,
      "agreement_bar": R14_AGREEMENT_BAR,
      "fused_loop": fused,
      "td_delta_bar": R14_TD_DELTA_BAR,
      "tier_ledger": {
          "compile_counts": agreement_counts,
          "per_tier_exactly_once": bool(per_tier_ok),
          "tier_shares": tier_shares,
      },
      "rollout": rollout,
      # Compact sentinels (round 14; null-safe): the agreement
      # rate is meaningful chipless (numerics, not timing); the speedup
      # is a CHIP claim and stays null on a virtual mesh.
      "cem_bf16_action_agreement": agreement["overall_rate"],
      "cem_bf16_speedup": None if virtual_mesh else speedup,
      "note": (
          "bf16 scoring tier vs the f32 oracle: selected-action "
          "agreement on a trained critic over the committed scene "
          "corpus at every ladder bucket, the fused anakin loop's "
          "eval-TD reduction per tier (f32-always eval metric), "
          "per-tier exactly-once compile ledger, and the live-traffic "
          "shadow/canary gate with an injected-breach auto-rollback. "
          "virtual_mesh=true means bf16 is CPU-emulated: rates and "
          "cem_bf16_speedup are not chip claims (the null is "
          "deliberate); agreement/TD parity and every structural "
          "claim stand. Real-chip speedups are not measured."),
  }

  if enforce_bars:
    failures = []
    if agreement["overall_rate"] < R14_AGREEMENT_BAR:
      failures.append(
          f"agreement {agreement['overall_rate']} < {R14_AGREEMENT_BAR}")
    if fused["td_delta"] > R14_TD_DELTA_BAR:
      failures.append(f"td_delta {fused['td_delta']} > {R14_TD_DELTA_BAR}")
    if not per_tier_ok:
      failures.append(f"tier ledger not exactly-once: {agreement_counts}")
    if not rollout["cycle_ok"] or not rollout["breach_rolled_back"]:
      failures.append(f"rollout cycle failed: {rollout['events']}")
    if not (fused["f32"]["ledger_all_one"]
            and fused["bf16"]["ledger_all_one"]):
      failures.append("fused-loop compile ledger not all ones")
    if failures:
      raise AssertionError(
          "PRECISION_r14 acceptance bars failed: " + "; ".join(failures))
  return result


def main(argv=None) -> None:
  """CLI: ONE JSON line. --smoke bootstraps the 8-virtual-device CPU
  mesh (re-exec with the canonical env) and runs the committed
  PRECISION_r14 protocol with generation-time bar enforcement; --ci is
  the reduced tier-1 lane (structural checks only — quantitative bars
  live in tests/test_precision.py behind the cpu_count gate)."""
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
                 "tensor2robot_tpu.replay.precision_bench",
                 *sys.argv[1:]],
                cpu_mesh_env(n))
  if args.ci:
    results = measure_precision(
        buckets=(1, 2, 4), corpus_scenes=24, pretrain_steps=120,
        loop_steps=40, rollout_devices=2, rollout_min_shadow=6,
        rollout_min_canary=3, rollout_cycle_s=60.0, seed=args.seed,
        enforce_bars=False)
  else:
    results = measure_precision(rollout_devices=8 if args.smoke else None,
                                seed=args.seed)
  line = json.dumps(results)
  if args.out:
    with open(args.out, "w") as f:
      f.write(line + "\n")
  print(line)


if __name__ == "__main__":
  main()

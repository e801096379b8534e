"""Reproduces the per-family capability checks from README/DESIGN.

One command per model family (or all), each running the REAL pipeline —
data generation → record parsing → training → export → serving — and
printing one JSON line with the measured outcome:

    python -m tensor2robot_tpu.bin.run_t2r_trainer  # normal training
    python -m tensor2robot_tpu.bin.run_capability_checks \
        --checks pose_env,qtopt,grasp2vec,vrgripper,maml \
        --scale fast

`--scale full` matches the README numbers (minutes per check on a
chip); `fast` shrinks images/steps for a quicker signal (still real
training, looser expectations). Exit code is non-zero if any check
misses its expectation, so this doubles as an acceptance test on real
hardware.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np


# (fast, full) per-check knobs.
_SCALES = {
    "pose_env": {"fast": dict(episodes=1000, steps=800, image=64),
                 "full": dict(episodes=2000, steps=1500, image=64)},
    "qtopt": {"fast": dict(grasps=3000, steps=1200, image=64),
              "full": dict(grasps=8000, steps=2500, image=128)},
    "grasp2vec": {"fast": dict(triplets=2048, steps=600, image=64),
                  "full": dict(triplets=8192, steps=1500, image=64)},
    "vrgripper": {"fast": dict(demos=2000, steps=800, image=64),
                  "full": dict(demos=4000, steps=1500, image=64)},
    "maml": {"fast": dict(steps=800, image=64),
             "full": dict(steps=2000, image=64)},
}
# Expectation per (check, scale), set just-under-measured (10-15%
# slack) from the r2 runs on cluttered scenes (one v5e, 2026-07-30;
# the records are no longer in the tree): pose_env 0.765 fast / 0.925 full (tight 0.05 gate),
# qtopt 0.47/0.85 (random 0.05), grasp2vec 0.453/0.734 (chance 0.016).
# vrgripper: recalibrated r3 — the r3 pose_env occluder randomization
# hardened its training scenes (measured r3: 0.75 fast / 0.925 full vs
# 0.86/0.95 at r2), so the bars moved to keep the 10-15% slack
# (2026-07-31). maml: recalibrated r3 (VERDICT r2 #6 — the old
# gate was saturated at 1.0): noisy-demonstrations regime (sigma=0.22
# condition labels) scored at half the object radius measured 0.879
# fast / 0.922 full (one v5e, 2026-07-31), so the gate now sits in the
# sensitive region with the usual 10-15% slack; a secondary
# adapted-vs-unadapted margin assertion (>=0.5 at the object radius)
# still catches the historical total-collapse failure mode.
_EXPECT = {
    ("pose_env", "fast"): 0.65, ("pose_env", "full"): 0.80,
    ("qtopt", "fast"): 0.40, ("qtopt", "full"): 0.72,
    ("grasp2vec", "fast"): 0.38, ("grasp2vec", "full"): 0.62,
    ("vrgripper", "fast"): 0.65, ("vrgripper", "full"): 0.80,
    ("maml", "fast"): 0.75, ("maml", "full"): 0.80,
}


def _train_and_restore_predictor(model, record_path, steps, run_dir):
  """Shared record-pipeline half: train -> native export -> predictor."""
  from tensor2robot_tpu.data.default_input_generator import (
      DefaultRecordInputGenerator)
  from tensor2robot_tpu.export.native_export_generator import (
      NativeExportGenerator)
  from tensor2robot_tpu.predictors.exported_model_predictor import (
      ExportedModelPredictor)
  from tensor2robot_tpu.train.train_eval import train_eval_model

  train_eval_model(
      model,
      input_generator_train=DefaultRecordInputGenerator(
          file_patterns=record_path, batch_size=64, seed=1),
      max_train_steps=steps, iterations_per_loop=50,
      model_dir=run_dir, export_generator=NativeExportGenerator(),
      log_every_steps=max(100, steps))
  predictor = ExportedModelPredictor(
      export_root=os.path.join(run_dir, "export", "latest"))
  if not predictor.restore(timeout_s=10.0):
    raise RuntimeError(
        f"No export appeared under {run_dir}/export/latest")
  return predictor


def check_pose_env(scale: str, workdir: str) -> dict:
  import optax

  from tensor2robot_tpu.research.pose_env import pose_env
  from tensor2robot_tpu.research.pose_env.eval_policy import evaluate_policy
  from tensor2robot_tpu.research.pose_env.pose_env_models import (
      PoseEnvRegressionModel)

  knobs = _SCALES["pose_env"][scale]
  rec = os.path.join(workdir, "pose.tfrecord")
  pose_env.write_tfrecords(rec, num_episodes=knobs["episodes"], seed=0,
                           image_size=knobs["image"])
  model = PoseEnvRegressionModel(image_size=knobs["image"],
                                 optimizer_fn=lambda: optax.adam(1e-3))
  predictor = _train_and_restore_predictor(
      model, rec, knobs["steps"], os.path.join(workdir, "pose_run"))
  # Gate on a TIGHT reach threshold: at the env default (0.10) the
  # check saturates at 1.0 even with scene clutter (measured r2 full),
  # so a 2x quality regression would still "pass". 0.05 is inside the
  # rasterized target disc radius — still a legitimate "reach success",
  # but sensitive to localization error. The 0.10 figure comes from the
  # SAME 200 rollouts (extra_thresholds re-buckets the distances).
  result = evaluate_policy(predictor, num_episodes=200, seed=1234,
                           image_size=knobs["image"],
                           success_threshold=0.05,
                           extra_thresholds=(0.10,))
  # Key derived the same way evaluate_policy builds it (f"{t:g}") so a
  # 0.10-vs-0.1 formatting drift cannot KeyError.
  return {"success_rate": result["success_rate"],
          "success_rate_at_0p10": result[f"success_rate_at_{0.10:g}"],
          "mean_reward": result["mean_reward"],
          "metric": "reach success within 0.05"}


def check_qtopt(scale: str, workdir: str) -> dict:
  import optax

  from tensor2robot_tpu.research.qtopt import synthetic_grasping as sg
  from tensor2robot_tpu.research.qtopt.cem import CEMPolicy
  from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel

  knobs = _SCALES["qtopt"][scale]
  rec = os.path.join(workdir, "grasps.tfrecord")
  sg.write_tfrecords(rec, num_examples=knobs["grasps"],
                     image_size=knobs["image"], seed=0)
  model = QTOptGraspingModel(image_size=knobs["image"],
                             in_image_size=knobs["image"],
                             optimizer_fn=lambda: optax.adam(1e-3))
  predictor = _train_and_restore_predictor(
      model, rec, knobs["steps"], os.path.join(workdir, "qtopt_run"))
  policy = CEMPolicy(predictor, action_size=4, num_samples=128,
                     num_elites=10, iterations=4, seed=7)
  cem = sg.evaluate_grasp_policy(policy, num_scenes=200, seed=5555,
                                 image_size=knobs["image"])
  rng = np.random.default_rng(0)
  rand = sg.evaluate_grasp_policy(
      lambda im: rng.uniform(-1, 1, 4), num_scenes=200, seed=5555,
      image_size=knobs["image"])
  return {"success_rate": cem["success_rate"],
          "random_success_rate": rand["success_rate"]}


def check_grasp2vec(scale: str, workdir: str) -> dict:
  import optax

  from tensor2robot_tpu.research.grasp2vec import synthetic_scenes as ss
  from tensor2robot_tpu.research.grasp2vec.grasp2vec_model import (
      Grasp2VecModel)
  from tensor2robot_tpu.specs import tensorspec_utils as ts
  from tensor2robot_tpu.train.trainer import Trainer

  knobs = _SCALES["grasp2vec"][scale]
  model = Grasp2VecModel(image_size=knobs["image"], depth=18,
                         norm="group",
                         optimizer_fn=lambda: optax.adam(1e-3))
  trainer = Trainer(model, seed=0)
  batch = 64
  state = trainer.create_train_state(batch_size=batch)
  data = ss.sample_triplets(knobs["triplets"], image_size=knobs["image"],
                            seed=0)
  rng = np.random.default_rng(1)
  for _ in range(knobs["steps"]):
    idx = rng.choice(knobs["triplets"], batch, replace=False)
    feats = ts.TensorSpecStruct(ss.as_model_batch(data, idx))
    sharded, _ = trainer.shard_batch((feats, None))
    state, _ = trainer.train_step(state, sharded, None)
  heldout = ss.sample_triplets(64, image_size=knobs["image"], seed=777)
  feats = ts.TensorSpecStruct(ss.as_model_batch(heldout, np.arange(64)))
  sharded, _ = trainer.shard_batch((feats, None))
  metrics = trainer.eval_step(state, sharded, None)
  return {"success_rate": float(metrics["retrieval_accuracy"]),
          "metric": "held-out 64-way retrieval accuracy"}


def check_vrgripper(scale: str, workdir: str, seed_offset: int = 0) -> dict:
  import jax
  import optax

  from tensor2robot_tpu.research.pose_env import pose_env
  from tensor2robot_tpu.research.pose_env.eval_policy import evaluate_policy
  from tensor2robot_tpu.research.vrgripper.vrgripper_env_models import (
      VRGripperRegressionModel)
  from tensor2robot_tpu.specs import tensorspec_utils as ts
  from tensor2robot_tpu.train.trainer import Trainer

  knobs = _SCALES["vrgripper"][scale]
  model = VRGripperRegressionModel(image_size=knobs["image"],
                                   action_size=2, gripper_pose_size=4,
                                   optimizer_fn=lambda: optax.adam(1e-3))
  # seed_offset varies TRAINING randomness (init, demos, batch order)
  # for seed-spread measurement (VERDICT r3 #8); the eval episodes stay
  # fixed so runs are comparable.
  trainer = Trainer(model, seed=seed_offset)
  batch = 64
  state = trainer.create_train_state(batch_size=batch)
  images, targets = pose_env.collect_episodes(
      knobs["demos"], seed=seed_offset, image_size=knobs["image"])
  rng = np.random.default_rng(1 + seed_offset)
  proprio = rng.normal(0, 1, (knobs["demos"], 4)).astype(np.float32)
  for _ in range(knobs["steps"]):
    idx = rng.choice(knobs["demos"], batch, replace=False)
    feats = ts.TensorSpecStruct({
        "image": images[idx].astype(np.float32) / 255.0,
        "gripper_pose": proprio[idx]})
    labels = ts.TensorSpecStruct({"action": targets[idx]})
    sharded_f, sharded_l = trainer.shard_batch((feats, labels))
    state, _ = trainer.train_step(state, sharded_f, sharded_l)

  from tensor2robot_tpu.export import export_utils
  variables = export_utils.fetch_variables_to_host(
      state.variables(use_ema=True))
  predict = jax.jit(model.predict_fn)
  zero_proprio = np.zeros((1, 4), np.float32)

  def policy(features):
    feats = ts.TensorSpecStruct({"image": features["image"],
                                 "gripper_pose": zero_proprio})
    return predict(variables, feats)

  result = evaluate_policy(policy, num_episodes=200, seed=4321,
                           image_size=knobs["image"])
  return {"success_rate": result["success_rate"]}


def check_maml(scale: str, workdir: str) -> dict:
  import jax
  import jax.numpy as jnp
  import optax

  from tensor2robot_tpu.research.pose_env import meta_reaching as mr
  from tensor2robot_tpu.research.pose_env.pose_env_maml_models import (
      pose_env_maml_model)
  from tensor2robot_tpu.train.trainer import Trainer

  knobs = _SCALES["maml"][scale]
  k_c = k_i = 4
  # Noisy demonstrations (meta_reaching.sample_meta_batch docstring):
  # condition labels jittered at BOTH train and eval by sigma = the
  # object radius (0.22; objects are >=0.48 apart). Measured r3
  # calibration path: with clean labels OR sigma=0.10 the check
  # saturates at 1.0 — the position comes from vision, label noise
  # only matters once it can flip which object the condition evidence
  # points at. At sigma=0.22 a fraction of tasks carry genuinely
  # misleading demonstrations, so success measures how well the
  # adapted policy integrates K noisy examples — a graded signal.
  noise = 0.22

  def build(num_inner_steps):
    return pose_env_maml_model(
        num_inner_steps=num_inner_steps, inner_lr=0.05,
        num_condition_samples=k_c, num_inference_samples=k_i,
        image_size=knobs["image"],
        optimizer_fn=lambda: optax.adam(1e-3))

  model = build(3)
  trainer = Trainer(model, seed=0)
  state = trainer.create_train_state()
  for step in range(knobs["steps"]):
    meta, _ = mr.sample_meta_batch(8, k_c, k_i, image_size=knobs["image"],
                                   seed=100_000 + step,
                                   condition_label_noise=noise)
    feats = trainer.shard_batch(jax.tree_util.tree_map(jnp.asarray, meta))
    state, _ = trainer.train_step(state, feats, None)
  meta, info = mr.sample_meta_batch(64, k_c, k_i,
                                    image_size=knobs["image"], seed=9999,
                                    condition_label_noise=noise)
  feats = jax.tree_util.tree_map(jnp.asarray, meta)
  variables = jax.device_get(state.variables())

  def predictions(m_eval):
    out, _ = m_eval.inference_network_fn(variables, feats, "eval")
    return np.asarray(out["inference_output"], np.float32)

  # Gate on a TIGHT reach radius (same design as the pose_env check):
  # at the full object radius (0.22) adapted success saturates — so the
  # gate would only catch the total-collapse failure mode. Half the
  # object radius under the sigma=0.22 condition noise above lands the
  # measured figure in the sensitive region (see _EXPECT), so subtler
  # adaptation-quality regressions move the gated number. The 0.22
  # figure (same predictions, re-bucketed) and the adapted-vs-unadapted
  # margin are also emitted; the margin is asserted as a secondary
  # check.
  tight = mr.OBJECT_RADIUS / 2
  adapted_preds = predictions(model)  # one adaptation+forward pass,
  # scored at both radii (the full inference over 64 tasks is the
  # expensive part, not the bucketing).
  adapted = mr.reach_success(adapted_preds, info, radius=tight)
  adapted_full = mr.reach_success(adapted_preds, info,
                                  radius=mr.OBJECT_RADIUS)
  unadapted = mr.reach_success(predictions(build(0)), info,
                               radius=mr.OBJECT_RADIUS)
  margin_ok = (adapted_full["success_rate"]
               >= unadapted["success_rate"] + 0.5)
  return {"success_rate": (adapted["success_rate"] if margin_ok
                           else 0.0),
          "success_rate_at_object_radius": adapted_full["success_rate"],
          "unadapted_success_rate": unadapted["success_rate"],
          "adapted_vs_unadapted_margin_ok": margin_ok,
          "metric": f"query reach within {tight:g} (half object "
                    "radius), gated on adapted-unadapted margin"}


_CHECKS = {
    "pose_env": check_pose_env,
    "qtopt": check_qtopt,
    "grasp2vec": check_grasp2vec,
    "vrgripper": check_vrgripper,
    "maml": check_maml,
}


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--checks", default="all",
                      help="comma list of %s or 'all'" % sorted(_CHECKS))
  parser.add_argument("--scale", choices=("fast", "full"), default="fast")
  parser.add_argument("--workdir", default=None,
                      help="scratch dir (default: a TemporaryDirectory)")
  parser.add_argument("--seed-offset", type=int, default=0,
                      help="offsets TRAINING seeds in checks that "
                           "support it (currently vrgripper) for "
                           "seed-spread measurement; eval episodes "
                           "stay fixed")
  args = parser.parse_args(argv)
  names = (sorted(_CHECKS) if args.checks == "all"
           else [n.strip() for n in args.checks.split(",")])
  unknown = [n for n in names if n not in _CHECKS]
  if unknown:
    parser.error(f"Unknown checks {unknown}; have {sorted(_CHECKS)}")

  failures = 0
  with tempfile.TemporaryDirectory() as default_dir:
    workdir_root = args.workdir or default_dir
    for name in names:
      start = time.time()
      # Per-(check, scale) scratch dir, cleared first: train_eval_model
      # is resume-aware, so reusing a populated run dir would train 0
      # steps (or crash on shape mismatch across scales).
      workdir = os.path.join(workdir_root, f"{name}_{args.scale}")
      if os.path.isdir(workdir):
        import shutil
        shutil.rmtree(workdir)
      os.makedirs(workdir)
      record = {"check": name, "scale": args.scale}
      if args.seed_offset:
        record["seed_offset"] = args.seed_offset
      try:
        import inspect
        check_fn = _CHECKS[name]
        kwargs = {}
        if "seed_offset" in inspect.signature(check_fn).parameters:
          kwargs["seed_offset"] = args.seed_offset
        elif args.seed_offset:
          record["seed_offset_ignored"] = True
        result = check_fn(args.scale, workdir, **kwargs)
        expect = _EXPECT[(name, args.scale)]
        passed = bool(result["success_rate"] >= expect)
        record.update(
            {k: (round(float(v), 4) if isinstance(v, (int, float))
                 else v)
             for k, v in result.items()})
        record["expected_at_least"] = expect
      except Exception as e:  # isolate: one crashing family must not
        passed = False        # silence the remaining checks' report.
        record["error"] = f"{type(e).__name__}: {e}"
      failures += not passed
      record["passed"] = passed
      record["seconds"] = round(time.time() - start, 1)
      print(json.dumps(record), flush=True)
  return 1 if failures else 0


if __name__ == "__main__":
  sys.exit(main())

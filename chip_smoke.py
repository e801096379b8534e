"""chip_smoke.py — the flagship path, once, on the chip, at full width.

    python chip_smoke.py

train -> export -> CEM fleet serving through the entry points a user
calls, on every local TPU device (one chip or a four-chip host), in ONE
process. Weights are random from a seed, the data is a TFRecord shard
written from a seed by research/qtopt/synthetic_grasping (the repo's
record writer), and nothing needs the network.

What runs:
  train   `bin.run_t2r_trainer.main` with the shipped
          research/qtopt/configs/qtopt_train.cfg: `QTOptGraspingModel()`
          at its published width (472x472x3 float32 image, 64-channel
          tower, action_size 4), `DefaultRecordInputGenerator` over
          JPEG-wire records (the config's default wire format),
          `NativeExportGenerator`. Bindings override sizes and cadences
          only: file pattern, batch (8 per chip), 6 steps as 3 scanned
          dispatches of 2, a checkpoint every 4 steps, a log line per
          dispatch, model_dir. A hook (the framework's own observer
          seam) records the losses and where the train state lives.
  serve   the export the train phase wrote, loaded by
          `ExportedModelPredictor`, behind `FleetRouter` — one
          `CEMFleetPolicy` replica per device, CEM at 64 samples x 3
          iterations x 6 elites. 32 burst + 8 single 472x472 requests.
          The bucket ladder is cut from (1, 2, 4, 8, 16) to (1, 8): a
          single-robot rung and a fleet rung bound the cold compiles
          (each rung is one 472x472 CEM program per device).

Any failed check raises; nothing is caught and summarised. The last
stdout line is the result object; no accelerator means no result and a
non-zero exit.
"""

from __future__ import annotations

import contextlib
import importlib.metadata
import json
import logging
import os
import shutil
import sys
import threading
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "chip_smoke_out")
_CONFIG = os.path.join(_ROOT, "tensor2robot_tpu", "research", "qtopt",
                       "configs", "qtopt_train.cfg")

_SEED = 0
_IMAGE_SIZE = 472
_BATCH_PER_CHIP = 8
_ITERATIONS_PER_LOOP = 2
_TRAIN_STEPS = 6
_CHECKPOINT_EVERY = 4
_NUM_RECORDS = 64
_LADDER = (1, 8)
_BURST_REQUESTS = 32
_SINGLE_REQUESTS = 8
# The served Q of the chosen action, re-scored through predict(): the
# same bf16 tower, possibly at another batch shape. The eight fleet-rung
# robots' Qs sit ~1e-2 apart, so a cross-robot mixup cannot hide in it.
_Q_REFERENCE_ATOL = 2e-3


def _require(ok: bool, what: str) -> None:
  if not ok:
    raise RuntimeError(f"chip_smoke: {what}")


def _say(tag: str, evidence) -> None:
  print(f"[chip_smoke] {tag} {json.dumps(evidence)}", flush=True)


class _Clock:
  """Per-phase wall seconds, with JAX's own compile seconds apart.

  Compile seconds are the trace + lower + backend-compile durations JAX
  reports through jax.monitoring (the backend figure spans
  compile-or-fetch-from-the-persistent-cache), summed over threads."""

  _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

  def __init__(self):
    import jax.monitoring
    self.phases = {}
    self._current = None
    self._lock = threading.Lock()
    jax.monitoring.register_event_duration_secs_listener(self._on_duration)
    jax.monitoring.register_event_listener(self._on_event)

  def _on_duration(self, event, seconds, **_):
    with self._lock:
      if self._current is not None and event in self._EVENTS:
        self._current["compile_s"] += seconds

  def _on_event(self, event, **_):
    with self._lock:
      if self._current is None:
        return
      if event == "/jax/compilation_cache/cache_hits":
        self._current["cache_hits"] += 1
      elif event == "/jax/compilation_cache/cache_misses":
        self._current["cache_misses"] += 1

  @contextlib.contextmanager
  def phase(self, name: str):
    entry = {"wall_s": 0.0, "compile_s": 0.0, "cache_hits": 0,
             "cache_misses": 0}
    with self._lock:
      self._current = entry
    start = time.perf_counter()
    try:
      yield entry
    finally:
      entry["wall_s"] = round(time.perf_counter() - start, 2)
      with self._lock:
        self._current = None
        entry["compile_s"] = round(entry["compile_s"], 2)
      self.phases[name] = entry
      _say(f"phase {name}:", entry)


class _PipelineStatsCapture(logging.Handler):
  """train_eval_model logs the input generator's pipeline_stats dict
  (the native/python parser decision) as a log-record argument."""

  def __init__(self):
    super().__init__(level=logging.INFO)
    self.stats = None

  def emit(self, record):
    if (isinstance(record.msg, str)
        and record.msg.startswith("train input pipeline")):
      self.stats = record.args[0] if isinstance(
          record.args, tuple) else record.args


def _device_sets(tree) -> set:
  import jax
  return {frozenset(leaf.sharding.device_set)
          for leaf in jax.tree_util.tree_leaves(tree)}


def _peak_hbm_bytes(devices) -> list:
  return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def _train(devices, shard_path: str, model_dir: str) -> dict:
  import jax

  from tensor2robot_tpu import config as t2r_config
  from tensor2robot_tpu.bin import run_t2r_trainer
  from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder

  n = len(devices)
  batch = _BATCH_PER_CHIP * n
  seen = {"losses": {}}

  class _Probe(Hook):

    def begin(self, trainer, state, model_dir):
      seen["mesh_devices"] = trainer.mesh.devices.size
      seen["batch_shard_rows"] = trainer.batch_sharding.shard_shape(
          (batch, _IMAGE_SIZE, _IMAGE_SIZE, 3))[0]
      seen["batch_devices"] = len(trainer.batch_sharding.device_set)

    def after_step(self, state, metrics):
      seen["losses"][int(state.step)] = metrics["loss"]
      seen["params_device_sets"] = _device_sets(state.params)
      seen["opt_state_device_sets"] = _device_sets(state.opt_state)

  @t2r_config.configurable(name="ChipSmokeProbe")
  class _ProbeBuilder(HookBuilder):

    def create_hooks(self, trainer, model_dir):
      return [_Probe()]

  capture = _PipelineStatsCapture()
  train_log = logging.getLogger("tensor2robot_tpu.train.train_eval")
  train_log.addHandler(capture)
  train_log.setLevel(logging.INFO)  # whatever the root logger is left at
  try:
    rc = run_t2r_trainer.main([
        "--config", _CONFIG,
        "--import_module", "tensor2robot_tpu.research.qtopt.t2r_models",
        "--model_dir", model_dir,
        "--binding",
        f"DefaultRecordInputGenerator.file_patterns = {shard_path!r}",
        "--binding", f"DefaultRecordInputGenerator.batch_size = {batch}",
        "--binding", f"train_eval_model.max_train_steps = {_TRAIN_STEPS}",
        "--binding",
        f"train_eval_model.iterations_per_loop = {_ITERATIONS_PER_LOOP}",
        "--binding",
        f"train_eval_model.save_checkpoints_steps = {_CHECKPOINT_EVERY}",
        "--binding",
        f"train_eval_model.log_every_steps = {_ITERATIONS_PER_LOOP}",
        "--binding", "train_eval_model.hook_builders = [@ChipSmokeProbe()]",
    ])
  finally:
    train_log.removeHandler(capture)
  _require(rc == 0, f"run_t2r_trainer.main returned {rc}")

  # A non-finite loss anywhere inside a scanned dispatch poisons the
  # params, so the last-step loss of every dispatch covers every step.
  expected = list(range(_ITERATIONS_PER_LOOP, _TRAIN_STEPS + 1,
                        _ITERATIONS_PER_LOOP))
  _require(sorted(seen["losses"]) == expected,
           f"losses logged at {sorted(seen['losses'])}, want {expected}")
  _require(all(np.isfinite(v) for v in seen["losses"].values()),
           f"non-finite loss: {seen['losses']}")

  all_devices = frozenset(devices)
  _require(seen["mesh_devices"] == n,
           f"trainer mesh spans {seen['mesh_devices']} of {n} devices")
  _require(seen["batch_devices"] == n
           and seen["batch_shard_rows"] == _BATCH_PER_CHIP,
           f"batch not split {_BATCH_PER_CHIP} rows x {n} devices: {seen}")
  for name in ("params_device_sets", "opt_state_device_sets"):
    _require(seen[name] == {all_devices},
             f"{name}: some leaf does not live on all {n} devices")
  peaks = _peak_hbm_bytes(devices)
  # Every device held its replica of the state and its batch shard's
  # activations: none sat idle while device 0 did the work.
  _require(min(peaks) > 0.5 * max(peaks),
           f"uneven peak HBM use across devices: {peaks}")

  steps = sorted(int(s) for s in os.listdir(
      os.path.join(model_dir, "checkpoints")) if s.isdigit())
  _require(steps == [_CHECKPOINT_EVERY, _TRAIN_STEPS],
           f"checkpoints on disk: {steps}")
  export_root = os.path.join(model_dir, "export", "latest")
  versions = sorted(os.listdir(export_root))
  _require(len(versions) == 1, f"exports on disk: {versions}")
  export_files = sorted(os.listdir(os.path.join(export_root, versions[0])))
  _require("serving_fn.bin" in export_files
           and "variables.npz" in export_files,
           f"export incomplete: {export_files}")
  _require(capture.stats is not None, "no input-pipeline stats were logged")

  return {
      "entry": "tensor2robot_tpu.bin.run_t2r_trainer.main",
      "model": "QTOptGraspingModel()", "image": [_IMAGE_SIZE] * 2 + [3],
      "global_batch": batch, "steps": _TRAIN_STEPS,
      "iterations_per_loop": _ITERATIONS_PER_LOOP,
      "loss_by_step": {str(k): round(float(v), 5)
                       for k, v in sorted(seen["losses"].items())},
      "state_and_batch_devices": n,
      "batch_rows_per_device": seen["batch_shard_rows"],
      "peak_hbm_bytes_per_device": peaks,
      "checkpoints": steps, "export": f"export/latest/{versions[0]}",
      "export_files": export_files,
      "native_calibration": capture.stats.get("native_calibration"),
      "export_root": export_root,
  }


def _serve(devices, export_root: str) -> dict:
  from tensor2robot_tpu.obs.ledger import check_compile_ledger
  from tensor2robot_tpu.predictors.exported_model_predictor import (
      ExportedModelPredictor)
  from tensor2robot_tpu.serving.router import FleetRouter

  n = len(devices)
  predictor = ExportedModelPredictor(export_root)
  _require(predictor.restore(), f"no export under {export_root}")
  spec = predictor.get_feature_specification()["image"]
  _require(tuple(spec.shape) == (_IMAGE_SIZE, _IMAGE_SIZE, 3),
           f"served image spec {spec}")
  rng = np.random.default_rng(_SEED + 1)

  def frame():
    return rng.random(tuple(spec.shape)).astype(spec.dtype)

  # CEM at its published 64 samples x 3 iterations x 6 elites (the
  # router's defaults, spelled out). jax.devices() -> one replica each.
  router = FleetRouter(predictor, devices=devices, action_size=4,
                       num_samples=64, num_elites=6, iterations=3,
                       seed=_SEED, ladder_sizes=_LADDER, deadline_ms=50.0)
  _require(len({replica.device for replica in router.replicas}) == n,
           "replicas do not sit on distinct devices")
  warm = frame()
  router.warmup(lambda i: warm)

  burst_frames = [frame() for _ in range(_BURST_REQUESTS)]
  with router:
    burst = [router.submit(image) for image in burst_frames]
    actions = [future.result(timeout=300) for future in burst]
    for _ in range(_SINGLE_REQUESTS):
      actions.append(router.submit(frame()).result(timeout=300))
    # Reference on one small input: the action the fleet returns for
    # (image, seed) is the one the policy computes directly, and the Q
    # it reports for that action is the Q predict() gives at batch 1.
    image, seed = frame(), 12345
    routed = router.submit(image, seed=seed).result(timeout=300)
    direct, scores = router.replicas[0].policy(
        [image], [seed], return_scores=True)
    # And at the fleet rung, on the last replica: eight robots whose
    # frames differ in brightness each get the Q of their OWN frame.
    fleet_images = np.stack([
        burst_frames[i] * ((i + 1) / _LADDER[-1])
        for i in range(_LADDER[-1])]).astype(spec.dtype)
    fleet_actions, fleet_scores = router.replicas[-1].policy(
        list(fleet_images), list(range(100, 100 + _LADDER[-1])),
        return_scores=True)
  actions = np.asarray(actions)
  total = _BURST_REQUESTS + _SINGLE_REQUESTS
  _require(actions.shape == (total, 4), f"actions shape {actions.shape}")
  _require(bool(np.all(np.isfinite(actions))), "non-finite action served")
  _require(bool(np.all(np.abs(actions) <= 1.0)), "action outside [-1, 1]")
  _require(scores is not None, "policy fell to the host path (no scores)")
  _require(np.allclose(routed, direct[0], atol=1e-4),
           f"routed action {routed} != direct policy action {direct[0]}")
  q_reference = float(predictor.predict({
      "image": image[None], "action": direct.astype(np.float32)
  })["q_predicted"][0])
  q_error = abs(float(scores[0]) - q_reference)
  _require(q_error <= _Q_REFERENCE_ATOL,
           f"CEM-reported Q {float(scores[0])} vs predict() {q_reference}")
  fleet_reference = predictor.predict({
      "image": fleet_images, "action": fleet_actions.astype(np.float32)
  })["q_predicted"]
  fleet_q_error = float(np.max(np.abs(fleet_scores - fleet_reference)))
  _require(fleet_q_error <= _Q_REFERENCE_ATOL,
           f"fleet-rung Q {fleet_scores} vs predict() {fleet_reference}")
  fleet_q_spread = float(np.ptp(fleet_reference))
  _require(fleet_q_spread > 2 * _Q_REFERENCE_ATOL,
           f"fleet-rung robots' Qs only {fleet_q_spread} apart: the "
           "reference check above cannot tell them from one another")

  compile_ledger = router.compile_ledger()
  check_compile_ledger(compile_ledger)
  _require(all(sorted(per) == list(_LADDER)
               for per in compile_ledger.values()),
           f"not every rung compiled on every device: {compile_ledger}")
  # The device path returns scores and the replica feeds them to its Q
  # sketch; the host fallback returns None and feeds nothing.
  sketches = router.stats.q_sketch_summaries()
  dispatches = {row["name"]: row["dispatches"]
                for row in router.ledger.attribution()["executables"]}
  for device in devices:
    label = str(device)
    _require(sketches.get(label, {}).get("count", 0) >= 1,
             f"no device-path scores recorded for {label}: {sketches}")
    for bucket in _LADDER:
      key = f"cem_bucket_{bucket}@{label}"
      # warmup dispatched each rung once; traffic must have added more.
      _require(dispatches.get(key, 0) >= 2,
               f"{key} answered no traffic: {dispatches}")
  scored = sum(entry["count"] for entry in sketches.values())
  # + the routed reference request; warmup and the direct call bypass
  # the replicas' sketch feed.
  _require(scored == total + 1,
           f"{scored} of {total + 1} requests took the device path")
  snapshot = router.stats.snapshot()
  _require(snapshot.get("shed_total", 0) == 0, f"requests shed: {snapshot}")

  return {
      "predictor": "ExportedModelPredictor", "router": "FleetRouter",
      "replicas": n, "replica_devices": [str(d) for d in devices],
      "cem": {"num_samples": 64, "iterations": 3, "num_elites": 6},
      "ladder": list(_LADDER), "requests_answered": total + 1,
      "device_path_requests": scored,
      "compile_counts": {dev: {str(b): c for b, c in per.items()}
                         for dev, per in compile_ledger.items()},
      "dispatches": dispatches,
      "q_reference_abs_error": round(q_error, 5),
      "fleet_rung_q_abs_error": round(fleet_q_error, 5),
      "fleet_rung_q_spread": round(fleet_q_spread, 5),
      # Where the flushes' device turns went (ServingStats): the chip
      # or the wire.
      **{key: snapshot.get(key) for key in (
          "turn_wait_p50_ms", "turn_wait_p95_ms", "transfer_wait_p50_ms",
          "transfer_wait_p95_ms", "program_p50_ms", "program_p95_ms",
          "transfers_hidden", "program_busy_share")},
  }


def main() -> int:
  from tensor2robot_tpu.utils import compile_cache
  cache_dir = compile_cache.configure()
  import jax
  devices = jax.devices()
  if devices[0].platform != "tpu":
    print(f"chip_smoke: needs platform 'tpu'; jax.devices()[0].platform is "
          f"{devices[0].platform!r}", file=sys.stderr)
    return 2
  device = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
  versions = {name: importlib.metadata.version(name) for name in (
      "jax", "jaxlib", "libtpu", "flax", "optax", "orbax-checkpoint")}
  _say("device", device)
  _say("versions", versions)
  _say("compile cache", {
      "dir": cache_dir,
      "from": (compile_cache.ENV_VAR
               if os.environ.get(compile_cache.ENV_VAR)
               else "in-checkout default")})

  clock = _Clock()
  shutil.rmtree(_OUT, ignore_errors=True)
  os.makedirs(_OUT)
  shard_path = os.path.join(_OUT, "grasps-00000.tfrecord")
  model_dir = os.path.join(_OUT, "model")
  with clock.phase("data"):
    # The repo's own logged-grasp generator: cluttered synthetic scenes
    # on the JPEG wire, float32 actions, success labels as target_q.
    from tensor2robot_tpu.research.qtopt import synthetic_grasping
    synthetic_grasping.write_tfrecords(
        shard_path, _NUM_RECORDS, image_size=_IMAGE_SIZE, seed=_SEED)
    shard_bytes = os.path.getsize(shard_path)
  _say("data", {"records": _NUM_RECORDS, "wire_format": "jpeg",
                "bytes": shard_bytes})
  with clock.phase("train"):
    train = _train(devices, shard_path, model_dir)
  export_root = train.pop("export_root")
  _say("train", train)
  with clock.phase("serve"):
    serve = _serve(devices, export_root)
  _say("serve", serve)
  _say("phases", clock.phases)
  print(json.dumps({"ok": True, "device": device}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())

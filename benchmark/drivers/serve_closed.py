"""Driver `serve_closed`: robots in a closed loop behind `FleetRouter`.

Normal path: the benchmark's seeded variables -> the repo's native
exporter -> `ExportedModelPredictor` -> `FleetRouter` with one
`CEMFleetPolicy` replica per chip. Each robot submits a float32 frame
from host memory, waits for its action and submits the next; one load
thread does all the submitting, fed by the futures' done callbacks.

Traffic parameters (benchmark/traffic/<name>.json):
  robots_per_chip    closed-loop clients per chip
  frames_per_chip    distinct seeded frames in host memory per chip
  think_time_s       pause between an answer and the next request
  ladder_sizes       bucket ladder of every replica
  compare_requests   finished requests re-scored by the reference

What is compared (see `check`), for a seeded sample of the requests the
window finished:

- the Q that the CEM program reported for the action it served against
  the reference's Q of that frame and that action: the root mean square
  of the gaps (the widest gap swings from seed to seed), in units of the
  gap that the precision the configuration states puts between the
  reference's own two answers on the same frames and actions. How far
  rounding moves Q depends on the seed's weights (some put Q at 8
  logits, where bfloat16's last bit is 0.03); the unit takes that out;
- how good the served action is: the reference runs the configuration's
  CEM itself on each of those frames, with draws of its own, and the
  reference's Q of the served actions may fall short of the Q it finds
  by a share of what its own search gains between its first refit and
  its last. A search cut to one iteration reads 1, a sound one 0 to
  the noise of the draws, whatever the seed's weights make of Q.
"""

from __future__ import annotations

import os
import queue
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness

_DRAIN_S = 60.0


def _make_tap():
  from tensor2robot_tpu.serving.stats import ServingStats

  class Tap(ServingStats):
    """The router's stats sink and its served-batch recorder in one:
    counts flushes as the batcher reports them, and keeps each served
    request's Q beside its action (the replica hands the scores to
    `record_q_values` and then the batch to `record_served`, on one
    thread)."""

    def __init__(self):
      super().__init__()
      self._thread = threading.local()
      self.served = {}
      self.reset_counts()

    def reset_counts(self):
      self.flushes = self.occupied_slots = self.padded_slots = 0

    def record_flush(self, batch_size, bucket, queue_depth_after,
                     deadline_expired):
      self.flushes += 1
      self.occupied_slots += int(batch_size)
      self.padded_slots += int(bucket)
      super().record_flush(batch_size, bucket, queue_depth_after,
                           deadline_expired)

    def record_q_values(self, replica, values):
      self._thread.scores = np.array(values, np.float32)
      super().record_q_values(replica, values)

    def record_served(self, items, actions, device, params_version=None):
      del device, params_version
      scores = self._thread.scores
      self._thread.scores = None
      for (_, seed), action, score in zip(items, actions, scores):
        self.served[int(seed)] = (np.array(action, np.float32),
                                  float(score))

  return Tap()


class Session:

  def __init__(self, cell, seed, devices, span):
    from tensor2robot_tpu.export.native_export_generator import (
        NativeExportGenerator)
    from tensor2robot_tpu.predictors.exported_model_predictor import (
        ExportedModelPredictor)
    from tensor2robot_tpu.serving.router import FleetRouter

    self._cell, self._seed, self._span = cell, seed, span
    config, traffic = cell.config, cell.traffic
    self._module = cell.reference
    self._chips = len(devices)
    self._robots = int(traffic["robots_per_chip"]) * self._chips
    self._think = float(traffic["think_time_s"])

    clock = harness.Phases()
    variables = jax.device_get(jax.jit(
        lambda key: self._module.init_variables(key, config))(
            self._weights_key()))
    model = harness.build_model(config)
    export_root = os.path.join(harness.ROOT, "benchmark_out", "export",
                               cell.name)
    shutil.rmtree(export_root, ignore_errors=True)
    exporter = NativeExportGenerator(export_root=export_root)
    exporter.set_specification_from_model(model)
    exporter.export(variables)
    self._predictor = ExportedModelPredictor(export_root)
    if not self._predictor.restore():
      raise RuntimeError(f"no export under {export_root}")

    clock.mark("weights_export_restore")
    self._frames = self._make_frames(
        int(traffic["frames_per_chip"]) * self._chips)
    clock.mark("seeded_frames")
    self._tap = _make_tap()
    self._router = FleetRouter(
        self._predictor, devices=list(devices),
        action_size=config["action_size"],
        num_samples=config["cem_num_samples"],
        num_elites=config["cem_num_elites"],
        iterations=config["cem_iterations"],
        ladder_sizes=traffic["ladder_sizes"],
        precision=config["serving_precision"],
        stats=self._tap, episode_recorder=self._tap)
    with span("bench/warmup"):
      self._router.warmup(lambda i: self._frames[i % len(self._frames)])
    clock.mark("router_warmup")
    self._router.start()
    self._next_id = 0
    # The run's seed reaches CEM through each request's own seed; the
    # router's base seed is a constant of the compiled CEM programs, so
    # it stays at its default and every seed finds them in the cache.
    self._seed_base = (seed * 2654435761) % (2 ** 32)
    self._order = np.random.default_rng(seed).permutation(len(self._frames))
    with span("bench/warm_traffic"):
      self._closed_loop(1.0)
    clock.mark("warm_traffic")
    clock.say()
    self._tap.reset_counts()
    self._tap.served.clear()

  def _weights_key(self):
    return jax.random.fold_in(jax.random.key(self._seed % (2 ** 31)), 1)

  def _make_frames(self, count):
    size = self._cell.config["image_size"]
    rng = np.random.default_rng(self._seed)
    level = rng.uniform(0.2, 0.8, (count, 1, 1, 3)).astype(np.float32)
    contrast = rng.uniform(0.05, 0.2, (count, 1, 1, 1)).astype(np.float32)
    noise = rng.random((count, size, size, 3), np.float32) * 2.0 - 1.0
    return np.clip(level + contrast * noise, 0.0, 1.0)

  # --- the measured window -------------------------------------------------

  def _closed_loop(self, seconds):
    """Returns [(CEM seed, frame, t_submit, t_done, action or None)] for every
    request submitted, and the window's opening and closing times."""
    router, frames, order, span = (self._router, self._frames, self._order,
                                   self._span)
    done = queue.SimpleQueue()
    records, outstanding = [], 0

    def submit(robot):
      request = self._next_id
      self._next_id += 1
      frame = int(order[request % len(order)])
      cem_seed = (self._seed_base + request) % (2 ** 32)
      t_submit = time.perf_counter()
      future = router.submit(frames[frame], seed=cem_seed)
      future.add_done_callback(
          lambda f: done.put((robot, cem_seed, frame, t_submit,
                              time.perf_counter(), f)))

    start = time.perf_counter()
    close = start + seconds
    with span("bench/submit_first"):
      for robot in range(self._robots):
        submit(robot)
        outstanding += 1
    while outstanding:
      wait = (close + _DRAIN_S) - time.perf_counter()
      if wait <= 0:
        break
      try:
        robot, request, frame, t_submit, t_done, future = done.get(
            timeout=wait)
      except queue.Empty:
        break
      outstanding -= 1
      action = None if future.exception() else np.asarray(future.result())
      records.append((request, frame, t_submit, t_done, action))
      if time.perf_counter() < close:
        if self._think:
          time.sleep(self._think)
        with span("bench/submit"):
          submit(robot)
        outstanding += 1
    return records, start, close, outstanding

  def run_window(self, seconds):
    """Robots submit for `seconds`; the window runs from the first
    submit to the last answer, so every flush counts whole: both the
    actions it answered and the time it took."""
    records, start, close, never = self._closed_loop(seconds)
    self._records = records
    answered = [r for r in records if r[4] is not None]
    window_s = max(r[3] for r in records) - start
    latencies = np.array([(r[3] - r[2]) * 1e3 for r in answered])
    failed = len(records) - len(answered) + never
    tap = self._tap
    return {
        "attempted": len(records) + never, "failed": failed,
        "window_s": window_s, "actions": len(answered),
        "metrics": {
            "serve_actions_per_s": len(answered) / window_s / self._chips,
            "serve_p95_ms": float(np.percentile(latencies, 95)),
        },
        "counters": {"flushes": tap.flushes,
                     "occupied_slots": tap.occupied_slots,
                     "padded_slots": tap.padded_slots,
                     "submitting_s": close - start,
                     "p50_ms": float(np.percentile(latencies, 50)),
                     "p99_ms": float(np.percentile(latencies, 99))},
    }

  def release(self):
    self._router.stop()
    self._predictor.close()
    self._router = self._predictor = None

  # --- the comparison ------------------------------------------------------

  def controls(self):
    """Stand-ins that have to come out not correct: the reference one
    precision below what the configuration states reporting the Q,
    every served action negated where it is produced, and the
    reference's CEM cut to one iteration or to a quarter of its samples
    choosing the action."""
    config = self._cell.config
    return {"control_fp8": {"precision": "fp8"},
            "fault_action_negated": {"negate_action": True},
            "fault_one_iteration": {"cem": {"cem_iterations": 1}},
            "fault_quarter_samples": {"cem": {
                "cem_num_samples": max(config["cem_num_elites"] + 1,
                                       config["cem_num_samples"] // 4)}}}

  def _reference_cem(self, q_rows, count, config, stream):
    """The configuration's CEM written out plainly over the reference's
    Q, one search per compared request with the reference's own draws:
    normal samples around the mean, clipped to the box, the elites'
    mean and deviation refitted, the last mean the action. Returns the
    actions and the mean after the first refit."""
    rng = np.random.default_rng([self._seed, stream])
    low, high = config["cem_action_box"]
    samples, size = config["cem_num_samples"], config["action_size"]
    mean = np.zeros((count, 1, size), np.float32)
    std = np.full((count, 1, size), config["cem_initial_std"], np.float32)
    first = None
    for _ in range(config["cem_iterations"]):
      draws = rng.standard_normal((count, samples, size)).astype(np.float32)
      actions = np.clip(mean + std * draws, low, high)
      top = np.argsort(-q_rows(actions), axis=1)[:, :config["cem_num_elites"]]
      elites = np.take_along_axis(actions, top[:, :, None], axis=1)
      mean = elites.mean(axis=1, keepdims=True)
      std = elites.std(axis=1, keepdims=True) + 1e-3
      if first is None:
        first = np.clip(mean[:, 0], low, high)
    return np.clip(mean[:, 0], low, high), first

  def check(self, limits, precision=None, negate_action=False, cem=None):
    """[(name, value, limit)]. `precision`, `negate_action` and `cem`
    (keys of the configuration's CEM to override) put the reference
    itself, computed lower or broken, in the program's place: the
    controls."""
    config = self._cell.config
    answered = [r for r in self._records if r[4] is not None]
    served = self._tap.served
    unscored = sum(1 for r in answered if r[0] not in served)
    out_of_box = sum(
        1 for r in answered
        if not (np.all(np.isfinite(r[4])) and np.all(np.abs(r[4]) <= 1.0)))
    scored = [r for r in answered if r[0] in served]
    rng = np.random.default_rng(self._seed)
    count = min(int(self._cell.traffic["compare_requests"]), len(scored))
    picks = rng.choice(len(scored), size=count, replace=False)
    sample = [scored[i] for i in picks]
    mismatched = sum(
        1 for r in sample if not np.array_equal(served[r[0]][0], r[4]))
    if not sample:
      inf = float("inf")
      return [("served_q_gap_ratio", inf, limits["served_q_gap_ratio"]),
              ("cem_refinement_shortfall", inf,
               limits.get("cem_refinement_shortfall")),
              ("unscored_answers", unscored, 0)]

    variables = jax.jit(
        lambda key: self._module.init_variables(key, config))(
            self._weights_key())
    stated = config["reference_precision_stated"]
    frames = jnp.asarray(self._frames[[r[1] for r in sample]])
    block = max(1, 256 // config["cem_num_samples"])

    def q_fn(p):
      """Q of `actions` (requests, n, action) on the compared requests'
      frames, each frame tiled n times on the device, in blocks."""
      @jax.jit
      def rows(variables, images, actions):
        n = actions.shape[1]
        features = {"image": jnp.repeat(images, n, axis=0),
                    "action": actions.reshape((-1, actions.shape[-1]))}
        q = self._module.forward(variables, features, False, p)[0]
        return q["q_predicted"].reshape((-1, n))

      def q(actions):
        actions = np.asarray(actions, np.float32)
        step = block if actions.shape[1] > 1 else 32
        return np.concatenate([
            np.asarray(rows(variables, frames[i:i + step],
                            jnp.asarray(actions[i:i + step])))
            for i in range(0, count, step)])
      return q

    q32 = q_fn("f32")
    best, first = self._reference_cem(q32, count, config, stream=1)
    actions = np.stack([r[4] for r in sample])
    reported = np.array([served[r[0]][1] for r in sample])
    if cem:
      actions, _ = self._reference_cem(
          q32, count, dict(config, **cem), stream=2)
    if negate_action:
      actions = -actions
    readout = lambda q, a: q(a[:, None])[:, 0]
    reference = readout(q32, actions)
    rounding = np.abs(readout(q_fn(stated), actions) - reference)
    if precision or cem:
      reported = readout(q_fn(precision or stated), actions)
    gaps = np.abs(reported - reference)
    found = readout(q32, best) - reference
    to_find = readout(q32, best) - readout(q32, first)
    rms = lambda values: float(np.sqrt(np.mean(np.square(values))))
    print(f"[bench] served Q gap over {count} answers: rms {rms(gaps):.6g} "
          f"widest {gaps.max():.6g}; the stated precision's own gap rms "
          f"{rms(rounding):.6g}; reference Q rms {rms(reference):.6g}; the "
          f"reference's CEM gains {to_find.mean():.6g} after its first "
          f"refit, the served actions fall {found.mean():.6g} short of it",
          flush=True)
    return [("served_q_gap_ratio", rms(gaps) / rms(rounding),
             limits["served_q_gap_ratio"]),
            ("cem_refinement_shortfall", found.sum() / to_find.sum(),
             limits.get("cem_refinement_shortfall")),
            ("served_q_rms_gap", rms(gaps), limits.get("served_q_rms_gap")),
            ("served_q_relative_gap", rms(gaps) / max(1.0, rms(reference)),
             limits.get("served_q_relative_gap")),
            ("unscored_answers", unscored, 0),
            ("tap_action_mismatch", mismatched, 0),
            ("actions_out_of_box", out_of_box, 0)]

"""Tier-1 tests for the silent-failure sentinel (ISSUE 15).

Covers the three tentpole layers plus the numeric fault kinds:

- HealthRule / HealthMonitor units: hard (nonfinite==0), EWMA z-score
  drift (baseline freeze on breach, relative-std floor), bound rules,
  warmup arming, escalation (registry counters, schema-valid
  ``health_breach`` dump, callback, snapshot auto-action, HealthHalt).
- The in-program summary reductions and scan aggregation helpers.
- obs/faults.py numeric kinds: returned (not raised) by perturb,
  deterministic, and the corruption helpers.
- The injected-NaN-through-anakin detection path: a REAL fused loop,
  params poisoned at the seam, the in-program summary catches it, the
  loop halts, the dump carries the step.
- The value_scale-through-the-host-loop path: finite, plausible, 50x
  wrong targets; the EWMA drift rules name it on the very next step.
- Healthy-control zero-false-positive runs (fused loop AND fleet).
- The fleet Q-drift guard against a LIVE 2-device router: a
  corrupt_served_variables replica detected and named; the aggregate
  rollup reaches the same verdict from exported reservoirs.

Timing-bar convention: these are detection-STRUCTURE tests (step
windows, schemas, verdicts), not latency bars, so they run ungated;
the one statistical margin assert (healthy z headroom) follows the
repo's ``os.cpu_count() >= 4`` gate.
"""

import json
import math
import os
import tempfile
import unittest

import numpy as np

from tensor2robot_tpu.obs import faults as faults_lib
from tensor2robot_tpu.obs import health as health_lib
from tensor2robot_tpu.obs.flight_recorder import FlightRecorder
from tensor2robot_tpu.obs.registry import MetricRegistry

_SMALL_HOST = (os.cpu_count() or 1) < 4


class TestSummaryHelpers(unittest.TestCase):

  def test_tree_nonfinite_count_and_norm(self):
    import jax.numpy as jnp
    tree = {"a": jnp.asarray([1.0, jnp.nan, jnp.inf]),
            "b": jnp.asarray([[3.0, 4.0]]),
            "ints": jnp.asarray([7, 8])}  # non-float leaves ignored
    self.assertEqual(float(health_lib.tree_nonfinite_count(tree)), 2.0)
    clean = {"b": tree["b"], "ints": tree["ints"]}
    self.assertEqual(float(health_lib.tree_nonfinite_count(clean)), 0.0)
    self.assertAlmostEqual(float(health_lib.tree_global_norm(clean)),
                           5.0, places=5)

  def test_zero_summary_schema(self):
    summary = health_lib.zero_summary()
    self.assertEqual(sorted(summary), sorted(health_lib.SUMMARY_KEYS))
    for value in summary.values():
      self.assertEqual(float(value), 0.0)

  def test_scan_aggregation_max_vs_last(self):
    import jax.numpy as jnp
    stacked = {
        "health/td_max": jnp.asarray([1.0, 9.0, 2.0]),
        "health/td_mean": jnp.asarray([1.0, 9.0, 2.0]),
    }
    reduced = health_lib.reduce_scanned_metrics(stacked)
    self.assertEqual(float(reduced["health/td_max"]), 9.0)   # max key
    self.assertEqual(float(reduced["health/td_mean"]), 2.0)  # last
    # Carry merge: gate=False keeps the old carry entirely.
    new = {"health/td_max": jnp.asarray(5.0),
           "health/td_mean": jnp.asarray(5.0)}
    old = {"health/td_max": jnp.asarray(7.0),
           "health/td_mean": jnp.asarray(1.0)}
    merged = health_lib.merge_scan_metrics(new, old, jnp.asarray(True))
    self.assertEqual(float(merged["health/td_max"]), 7.0)
    self.assertEqual(float(merged["health/td_mean"]), 5.0)
    merged = health_lib.merge_scan_metrics(new, old, jnp.asarray(False))
    self.assertEqual(float(merged["health/td_max"]), 7.0)
    self.assertEqual(float(merged["health/td_mean"]), 1.0)


class TestHealthMonitor(unittest.TestCase):

  def _monitor(self, rules, **kwargs):
    registry = MetricRegistry()
    dump_dir = tempfile.mkdtemp(prefix="health_mon_")
    recorder = FlightRecorder(dump_dir=dump_dir,
                              min_dump_interval_s=0.0)
    monitor = health_lib.HealthMonitor(
        rules=rules, registry=registry, recorder=recorder, **kwargs)
    return monitor, registry, dump_dir

  def test_hard_rule_fires_immediately_with_schema_valid_dump(self):
    rule = health_lib.HealthRule("nonfinite_grads",
                                 "health/nonfinite_grads",
                                 kind="max", limit=0.0, warmup=0)
    monitor, registry, dump_dir = self._monitor([rule])
    self.assertEqual(
        monitor.observe(1, {"health/nonfinite_grads": 0.0}), [])
    breaches = monitor.observe(2, {"health/nonfinite_grads": 3.0})
    self.assertEqual(len(breaches), 1)
    self.assertEqual(breaches[0]["rule"], "nonfinite_grads")
    self.assertEqual(breaches[0]["step"], 2)
    self.assertEqual(registry.counter("health/breaches").value, 1)
    self.assertEqual(
        registry.counter("health/nonfinite_grads").value, 1)
    dumps = [name for name in os.listdir(dump_dir)
             if "health_breach" in name]
    self.assertEqual(len(dumps), 1)
    with open(os.path.join(dump_dir, dumps[0])) as f:
      payload = json.load(f)
    self.assertEqual(payload["schema"], "t2r-flightrec-1")
    for field in health_lib.BREACH_FIELDS:
      self.assertIn(field, payload["trigger"])
    self.assertEqual(payload["trigger"]["step"], 2)

  def test_drift_rule_warmup_freeze_and_relative_floor(self):
    rule = health_lib.HealthRule("td_drift", "health/td_mean",
                                 kind="drift", z_threshold=8.0,
                                 warmup=5, ewma_alpha=0.2)
    monitor, _, _ = self._monitor([rule])
    # Warmup: wild early values never breach while unarmed.
    for step, value in enumerate([0.1, 5.0, 0.2, 4.0, 0.3]):
      self.assertEqual(
          monitor.observe(step, {"health/td_mean": value}), [])
    # Settle the baseline near 0.4, then explode 50x.
    for step in range(5, 25):
      self.assertEqual(
          monitor.observe(step,
                          {"health/td_mean": 0.4 + 0.01 * (step % 3)}),
          [], f"false positive at step {step}")
    breaches = monitor.observe(25, {"health/td_mean": 20.0})
    self.assertEqual([b["rule"] for b in breaches], ["td_drift"])
    # Baseline FROZE on the breach: the same bad value keeps breaching
    # instead of becoming the new normal.
    for step in range(26, 30):
      self.assertTrue(monitor.observe(step, {"health/td_mean": 20.0}))
    # NaN values are the hard rules' jurisdiction; drift skips them
    # without poisoning the EWMA.
    self.assertEqual(
        monitor.observe(30, {"health/td_mean": float("nan")}), [])
    self.assertTrue(monitor.observe(31, {"health/td_mean": 20.0}))

  def test_min_rule_floor_and_missing_metric_skipped(self):
    rule = health_lib.HealthRule("entropy_floor",
                                 "health/priority_entropy",
                                 kind="min", limit=0.05, warmup=2)
    monitor, _, _ = self._monitor([rule])
    # warmup observations (even below the floor) never breach
    self.assertEqual(
        monitor.observe(0, {"health/priority_entropy": 0.01}), [])
    self.assertEqual(
        monitor.observe(1, {"health/priority_entropy": 0.01}), [])
    self.assertTrue(
        monitor.observe(2, {"health/priority_entropy": 0.01}))
    self.assertEqual(monitor.observe(3, {"other": 1.0}), [])

  def test_halt_snapshot_and_callback_escalation(self):
    rule = health_lib.HealthRule("nonfinite_params",
                                 "health/nonfinite_params",
                                 kind="max", limit=0.0, warmup=0,
                                 halt=True)
    seen = []
    snapshots = []
    monitor, _, _ = self._monitor([rule], on_breach=seen.append,
                                  halt_on_breach=True)
    with self.assertRaises(health_lib.HealthHalt) as ctx:
      monitor.observe_with_snapshot(
          7, {"health/nonfinite_params": 1.0},
          snapshot_fn=lambda: snapshots.append(True))
    self.assertEqual(ctx.exception.step, 7)
    # The escalation chain ran BEFORE the halt: callback + snapshot.
    self.assertEqual(len(seen), 1)
    self.assertEqual(snapshots, [True])
    snap = monitor.snapshot()
    self.assertEqual(snap["breach_count"], 1)
    self.assertEqual(snap["breaches_per_rule"],
                     {"nonfinite_params": 1})

  def test_default_rules_cover_the_summary_schema(self):
    rules = health_lib.default_rules(capacity=512)
    metrics = {rule.metric for rule in rules}
    for key in ("health/nonfinite_grads", "health/nonfinite_params",
                "health/nonfinite_targets", "health/grad_norm",
                "health/td_mean", "health/q_max",
                "health/priority_entropy", "health/sample_age"):
      self.assertIn(key, metrics)
    halting = {rule.name for rule in rules if rule.halt}
    self.assertEqual(halting, {"nonfinite_grads", "nonfinite_params",
                               "nonfinite_targets"})


class TestNumericFaultKinds(unittest.TestCase):

  def test_perturb_returns_numeric_specs_without_raising(self):
    plan = faults_lib.FaultPlan([
        faults_lib.FaultSpec(kind="value_scale", point="learner_step",
                             site="learner", at=2, scale=50.0)])
    self.assertEqual(
        plan.perturb("learner_step", site="learner", index=1), [])
    fired = plan.perturb("learner_step", site="learner", index=2)
    self.assertEqual([spec.kind for spec in fired], ["value_scale"])
    self.assertEqual(plan.fired_counts(), {"value_scale": 1})

  def test_numeric_schedule_is_deterministic(self):
    def run():
      plan = faults_lib.FaultPlan([
          faults_lib.FaultSpec(kind="nan_grads", point="learner_step",
                               site="s", probability=0.3, count=3)],
          seed=11)
      fired = []
      for index in range(20):
        fired.extend(spec.kind for spec in plan.perturb(
            "learner_step", site="s", index=index))
      return fired, [r["tick"] for r in plan.snapshot()["fired"]]

    self.assertEqual(run(), run())

  def test_apply_numeric_to_targets(self):
    targets = np.full((8,), 0.5, np.float32)
    nan_spec = faults_lib.FaultSpec(kind="nan_grads",
                                    point="learner_step", at=0)
    poisoned = faults_lib.apply_numeric_to_targets(targets, [nan_spec])
    self.assertTrue(math.isnan(float(poisoned[0])))
    self.assertEqual(float(np.nansum(poisoned)), 0.5 * 7)
    self.assertFalse(np.isnan(targets).any())  # input untouched
    scale_spec = faults_lib.FaultSpec(kind="value_scale",
                                      point="learner_step", at=0,
                                      scale=4.0)
    scaled = faults_lib.apply_numeric_to_targets(targets, [scale_spec])
    np.testing.assert_allclose(scaled, 2.0)

  def test_corrupt_variables_scales_float_leaves_only(self):
    import jax.numpy as jnp
    variables = {"params": {"w": jnp.ones((2, 2)),
                            "steps": jnp.asarray([1, 2])}}
    corrupted = faults_lib.corrupt_variables(variables, 8.0)
    np.testing.assert_allclose(
        np.asarray(corrupted["params"]["w"]), 8.0)
    np.testing.assert_array_equal(
        np.asarray(corrupted["params"]["steps"]), [1, 2])
    np.testing.assert_allclose(  # original untouched
        np.asarray(variables["params"]["w"]), 1.0)

  def test_unknown_kind_still_rejected(self):
    with self.assertRaises(ValueError):
      faults_lib.FaultSpec(kind="nan_everything", point="x", at=0)


class TestQDriftReport(unittest.TestCase):

  @staticmethod
  def _summary(mean, spread=0.01, count=64):
    return {"count": count, "mean": mean, "p50": mean,
            "p90": mean + spread}

  def test_insufficient_then_ok_then_divergent(self):
    one = {"a": self._summary(0.5)}
    self.assertEqual(health_lib.q_drift_report(one)["verdict"],
                     "insufficient")
    below_min = {"a": self._summary(0.5),
                 "b": self._summary(9.0, count=3)}
    self.assertEqual(health_lib.q_drift_report(below_min)["verdict"],
                     "insufficient")
    healthy = {f"r{i}": self._summary(0.5 + 0.002 * i)
               for i in range(4)}
    self.assertEqual(health_lib.q_drift_report(healthy)["verdict"],
                     "ok")
    corrupted = dict(healthy)
    corrupted["r9"] = self._summary(8.0)
    report = health_lib.q_drift_report(corrupted)
    self.assertEqual(report["verdict"], "divergent")
    self.assertEqual(report["divergent"], ["r9"])
    self.assertTrue(report["replicas"]["r9"]["z"] > 8.0)

  def test_scale_free_across_q_magnitudes(self):
    # The same relative corruption must read the same verdict whether
    # the head emits ~1e-3 logits or order-1 values.
    for scale in (1e-3, 1.0, 100.0):
      replicas = {f"r{i}": self._summary(0.5 * scale,
                                         spread=0.01 * scale)
                  for i in range(3)}
      replicas["bad"] = self._summary(8.0 * scale,
                                      spread=0.16 * scale)
      report = health_lib.q_drift_report(replicas)
      self.assertEqual(report["divergent"], ["bad"],
                       f"scale {scale}: {report}")


class TestAnakinNaNDetection(unittest.TestCase):
  """The injected-NaN-through-anakin path: a REAL fused loop, the
  in-program summary, the hard rule, the dump, the halt."""

  def _make_loop(self, logdir, plan, halt=True, steps_cfg=None):
    import optax

    from tensor2robot_tpu.replay.loop import (ReplayLoopConfig,
                                              ReplayTrainLoop)
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    config = ReplayLoopConfig(
        seed=0, anakin=True, anakin_inner=20, anakin_train_every=4,
        min_fill=64, eval_every=10, health_halt=halt,
        mesh_dp=1, mesh_tp=1, **(steps_cfg or {}))
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    return ReplayTrainLoop(config, logdir, model=model,
                           fault_plan=plan), config

  def test_injected_nan_detected_flight_recorded_and_halts(self):
    logdir = tempfile.mkdtemp(prefix="health_anakin_")
    plan = faults_lib.FaultPlan([
        faults_lib.FaultSpec(kind="nan_grads", point="learner_step",
                             site="anakin", at=10, every=1, count=1)])
    loop, config = self._make_loop(logdir, plan)
    with self.assertRaises(health_lib.HealthHalt) as ctx:
      loop.run(40)
    self.assertIn("nonfinite_grads",
                  {b["rule"] for b in ctx.exception.breaches})
    injected = plan.snapshot()["fired"][0]["tick"]
    snap = loop.health_monitor.snapshot()
    detected = snap["breaches"][0]["step"]
    window = 2 * (config.anakin_inner // config.anakin_train_every)
    self.assertLessEqual(injected, detected)
    self.assertLessEqual(detected, injected + window)
    dumps = [name for name in os.listdir(logdir)
             if name.startswith("flightrec-")
             and "health_breach" in name]
    self.assertTrue(dumps)
    with open(os.path.join(logdir, dumps[0])) as f:
      payload = json.load(f)
    self.assertEqual(payload["trigger"]["step"], detected)
    for field in health_lib.BREACH_FIELDS:
      self.assertIn(field, payload["trigger"])

  def test_healthy_fused_run_records_zero_breaches(self):
    logdir = tempfile.mkdtemp(prefix="health_anakin_ok_")
    loop, _ = self._make_loop(logdir, plan=None)
    result = loop.run(20)
    self.assertIsNotNone(result["health"])
    self.assertGreater(result["health"]["observations"], 0)
    self.assertEqual(result["health"]["breach_count"], 0,
                     result["health"]["breaches"])
    self.assertEqual(
        sorted(result["health"]["last_summary"]),
        sorted(health_lib.SUMMARY_KEYS))
    # Zero new executables: the fused ledger is exactly the anakin
    # set — no health executable rides the fused path.
    self.assertNotIn("health_summary", result["compile_counts"])
    self.assertEqual(result["compile_counts"]["anakin_step"], 1)


class TestValueScaleHostDetection(unittest.TestCase):
  """A finite corruption through the HOST learner path: the Bellman
  targets scaled 50x at the learner seam. Nothing is non-finite, so no
  hard rule can see it; the drift rules must, within the steps that
  follow, and the breach must be flight-recorded under its rule's name."""

  def test_scaled_targets_trip_a_drift_rule_and_are_recorded(self):
    import optax

    from tensor2robot_tpu.replay.loop import (ReplayLoopConfig,
                                              ReplayTrainLoop)
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    inject_at = 15
    logdir = tempfile.mkdtemp(prefix="health_scale_")
    plan = faults_lib.FaultPlan([
        faults_lib.FaultSpec(kind="value_scale", point="learner_step",
                             site="learner", at=inject_at, scale=50.0)])
    config = ReplayLoopConfig(
        seed=0, eval_every=15, mesh_dp=1, mesh_tp=1, health=True,
        health_halt=False, anakin=False, min_fill=96)
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    # Every trigger dumps: at the default rate limit a queue-overflow dump
    # a moment earlier would swallow this one (it would ride the ring only).
    recorder = FlightRecorder(dump_dir=logdir, min_dump_interval_s=0.0)
    loop = ReplayTrainLoop(config, logdir, model=model, fault_plan=plan,
                           flight_recorder=recorder)
    snapshot = loop.run(30)["health"]
    self.assertEqual(plan.fired_counts().get("value_scale"), 1,
                     plan.snapshot())
    drift_rules = {"td_drift", "q_drift", "grad_norm_drift"}
    self.assertTrue(drift_rules & set(snapshot["breaches_per_rule"]),
                    snapshot["breaches_per_rule"])
    # The fault fires at the END of step inject_at and corrupts the next
    # step's targets: that step is where the drift rules must speak, and
    # no rule may have spoken before it.
    detected = sorted({b["step"] for b in snapshot["breaches"]})
    self.assertGreaterEqual(detected[0], inject_at + 1)
    self.assertLessEqual(detected[0], inject_at + 3)
    triggers = []
    for name in sorted(os.listdir(logdir)):
      if name.startswith("flightrec-") and "health_breach" in name:
        with open(os.path.join(logdir, name)) as f:
          triggers.append(json.load(f)["trigger"])
    self.assertTrue(triggers, os.listdir(logdir))
    for trigger in triggers:
      for field in health_lib.BREACH_FIELDS:
        self.assertIn(field, trigger)
    self.assertTrue(
        any(t["step"] in detected and t["rule"] in drift_rules
            for t in triggers), triggers)


class TestQDriftRouterLive(unittest.TestCase):
  """The fleet Q-drift guard against a LIVE 2-device router."""

  def _run_window(self, corrupt=False, requests=160):
    import jax

    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    from tensor2robot_tpu.serving.stats import ServingStats
    devices = jax.devices()[:2]
    self.assertEqual(len(devices), 2)
    dump_dir = tempfile.mkdtemp(prefix="health_router_")
    recorder = FlightRecorder(dump_dir=dump_dir,
                              min_dump_interval_s=0.0)
    plan = None
    if corrupt:
      plan = faults_lib.FaultPlan([
          faults_lib.FaultSpec(kind="corrupt_served_variables",
                               point="replica_dispatch",
                               site=str(devices[1]), at=0,
                               scale=16.0)], recorder=recorder)
    predictor = TinyQPredictor(seed=0)
    registry = MetricRegistry()
    stats = ServingStats(registry=registry)
    router = FleetRouter(predictor, devices=devices,
                         ladder_sizes=(1, 2), seed=0, stats=stats,
                         fault_plan=plan, flight_recorder=recorder)
    router.warmup(predictor.make_image)
    images = [predictor.make_image(i) for i in range(8)]
    with router:
      futures = [router.submit(images[i % 8])
                 for i in range(requests)]
      for future in futures:
        future.result(60)
      snapshot = router.health_snapshot()
    registry.export_snapshot(os.path.join(dump_dir, "registry.json"))
    return snapshot, devices, dump_dir, plan, stats

  def test_corrupted_replica_detected_named_and_dumped(self):
    snapshot, devices, dump_dir, plan, stats = self._run_window(
        corrupt=True)
    drift = snapshot["q_drift"]
    self.assertEqual(drift["verdict"], "divergent")
    self.assertIn(str(devices[1]), drift["divergent"])
    self.assertEqual(snapshot["health"], "degraded")
    self.assertIn("replica_divergent",
                  [entry["event"] for entry in snapshot["timeline"]])
    # One divergent TRANSITION fires one dump: the snapshot's single
    # check_q_drift pass, no more, no less.
    dumps = [name for name in os.listdir(dump_dir)
             if "replica_divergent" in name]
    self.assertEqual(len(dumps), 1, dumps)
    # The injected fault's own dump carries the batch's request ids
    # (it fired inside the dispatch span) — the correlation contract.
    fired = plan.snapshot()["fired"]
    self.assertTrue(any(record.get("request_ids")
                        or record.get("request_id")
                        for record in fired), fired)
    # Per-replica sketches exported to the registry ride the snapshot.
    self.assertIn("q_sketches", stats.snapshot())

  def test_aggregate_reaches_the_verdict_from_the_exported_registry(self):
    """The cross-process rollup over what a LIVE router exported (the
    rollup's own tests feed it hand-made reservoirs): divergent naming
    the corrupted replica, ok on the healthy control."""
    from tensor2robot_tpu.obs import aggregate as aggregate_lib
    _, devices, dump_dir, _, _ = self._run_window(corrupt=True)
    health = aggregate_lib.aggregate_logdir(
        dump_dir, merged_trace=False)["health"]
    self.assertEqual(health["verdict"], "divergent", health)
    self.assertTrue(any(name.endswith("/" + str(devices[1]))
                        for name in health["q_drift"]["divergent"]),
                    health["q_drift"])
    _, _, dump_dir, _, _ = self._run_window(corrupt=False)
    health = aggregate_lib.aggregate_logdir(
        dump_dir, merged_trace=False)["health"]
    self.assertEqual(health["verdict"], "ok", health)

  def test_healthy_fleet_reads_ok_with_margin(self):
    snapshot, _, _, _, _ = self._run_window(corrupt=False)
    drift = snapshot["q_drift"]
    self.assertEqual(drift["verdict"], "ok", drift)
    self.assertEqual(snapshot["health"], "ok")
    if not _SMALL_HOST:
      # Quantitative margin bar (cpu_count >= 4 convention): healthy
      # z-scores must sit well inside the threshold, not graze it.
      for name, entry in drift["replicas"].items():
        self.assertLess(entry["z"], 0.75 * drift["z_threshold"],
                        (name, entry))


class TestAggregateHealthRollup(unittest.TestCase):
  """The cross-process health verdict from exported streams alone."""

  @staticmethod
  def _snapshot_file(logdir, name, pid, q_by_replica, counters=None):
    payload = {
        "schema": "t2r-registry-1", "host": "hostA", "pid": pid,
        "counters": counters or {}, "gauges": {},
        "histograms": {
            f"serving/replica/{replica}/q_value": {
                "count": len(samples), "samples": samples}
            for replica, samples in q_by_replica.items()},
    }
    with open(os.path.join(logdir, name), "w") as f:
      json.dump(payload, f)

  def test_divergent_replica_found_across_processes(self):
    from tensor2robot_tpu.obs import aggregate as aggregate_lib
    logdir = tempfile.mkdtemp(prefix="health_agg_")
    rng = np.random.default_rng(0)
    healthy = lambda: list(rng.normal(0.5, 0.01, 64))
    self._snapshot_file(logdir, "registry-1.json", 1,
                        {"d0": healthy(), "d1": healthy()})
    self._snapshot_file(logdir, "registry-2.json", 2,
                        {"d0": healthy(),
                         "d1": list(rng.normal(8.0, 0.16, 64))})
    fleet = aggregate_lib.aggregate_logdir(logdir, merged_trace=False)
    health = fleet["health"]
    self.assertEqual(health["verdict"], "divergent")
    self.assertEqual(health["q_drift"]["divergent"],
                     ["hostA:2/d1"])

  def test_breaching_and_ok_verdicts(self):
    from tensor2robot_tpu.obs import aggregate as aggregate_lib
    logdir = tempfile.mkdtemp(prefix="health_agg_ok_")
    rng = np.random.default_rng(1)
    healthy = lambda: list(rng.normal(0.5, 0.01, 64))
    self._snapshot_file(logdir, "registry-1.json", 1,
                        {"d0": healthy(), "d1": healthy()})
    fleet = aggregate_lib.aggregate_logdir(logdir, merged_trace=False)
    self.assertEqual(fleet["health"]["verdict"], "ok")
    self._snapshot_file(
        logdir, "registry-2.json", 2, {"d0": healthy()},
        counters={"health/breaches": 2, "health/td_drift": 2})
    fleet = aggregate_lib.aggregate_logdir(logdir, merged_trace=False)
    self.assertEqual(fleet["health"]["verdict"], "breaching")
    self.assertEqual(fleet["health"]["breach_counters"]["td_drift"], 2)


if __name__ == "__main__":
  unittest.main()

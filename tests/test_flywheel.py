"""Tier-1 tests for the fleet data flywheel (ISSUE 18).

Covers the capture seam, the spec-validated re-ingest gate, the
poisoning-interlock rules, and the satellite plumbing:

- EpisodeRecorder units: capture keyed by the batcher-bound
  ``request_ids`` context attr, first-capture-wins duplicates,
  unattributed items, FIFO eviction, blocking ``wait_for``.
- FlywheelIngest: a served episode spec ROUND-TRIPS (same keys,
  shapes, dtypes the synthetic path produces) into the queue with
  "served" provenance; every malformation — shape drift, non-castable
  dtype, a missing outcome stream, a transition without its
  correlation id or serving version — is REFUSED with the offending
  field NAMED, counted, and dumped; never silently dropped.
- flywheel_rules: the staleness/coverage/mix HealthRules breach on the
  metrics the ingest gate emits.
- Provenance ledgers (satellite 2): ReplayBuffer and
  ShardedReplayBuffer counters, per-row labels sliced per shard, and
  BIT-EXACT preservation across state_dict → load_state_dict
  crash-resume, plus pre-ISSUE-18 checkpoint compatibility.
- TransitionQueue provenance tagging through drain_batch_with_
  provenance and the ReplayFeeder pass-through.
- The serving seam (satellite 1): ``logical_requests`` counts client
  submits 1:1 on a live single-device router, the capture hook records
  the served action, and ``_HotReloadPredictor.set_variables`` carries
  the promoted version.
"""

import os
import tempfile
import threading
import types
import unittest

import numpy as np

from tensor2robot_tpu.flywheel.capture import (EpisodeRecorder,
                                               FlywheelIngest,
                                               IngestRejected,
                                               flywheel_rules)
from tensor2robot_tpu.obs import context as context_lib
from tensor2robot_tpu.obs.flight_recorder import FlightRecorder
from tensor2robot_tpu.obs.health import HealthMonitor
from tensor2robot_tpu.obs.registry import MetricRegistry
from tensor2robot_tpu.replay.ingest import (ReplayFeeder,
                                            TransitionQueue)
from tensor2robot_tpu.replay.loop import transition_spec
from tensor2robot_tpu.replay.ring_buffer import (ReplayBuffer,
                                                 ShardedReplayBuffer)

IMAGE, ACTION = 8, 3


def _episode(steps=3, seed=0, image=IMAGE, action=ACTION):
  rng = np.random.default_rng(seed)
  return {
      "images": rng.integers(0, 255, (steps + 1, image, image, 3),
                             dtype=np.uint8),
      "actions": rng.uniform(-1, 1, (steps, action)).astype(np.float32),
      "rewards": np.zeros((steps,), np.float32),
      "dones": np.zeros((steps,), np.float32),
  }


def _transitions(n, seed=0, image=IMAGE, action=ACTION):
  rng = np.random.default_rng(seed)
  return {
      "image": rng.integers(0, 255, (n, image, image, 3),
                            dtype=np.uint8),
      "action": rng.uniform(-1, 1, (n, action)).astype(np.float32),
      "reward": rng.random((n,)).astype(np.float32),
      "done": np.zeros((n,), np.float32),
      "next_image": rng.integers(0, 255, (n, image, image, 3),
                                 dtype=np.uint8),
  }


def _ingest(queue=None, monitor=None, step=10, **kwargs):
  return FlywheelIngest(
      queue if queue is not None else TransitionQueue(64),
      transition_spec(IMAGE, ACTION), learner_step_fn=lambda: step,
      monitor=monitor, registry=MetricRegistry(), **kwargs)


class TestEpisodeRecorder(unittest.TestCase):

  def _record(self, recorder, ids, n=None, version=4):
    n = len(ids) if n is None else n
    items = [(np.full((IMAGE, IMAGE, 3), i, np.uint8), 100 + i)
             for i in range(n)]
    actions = [np.full((ACTION,), float(i), np.float32)
               for i in range(n)]
    with context_lib.bind(request_ids=context_lib.join_ids(ids)):
      return recorder.record_served(items, actions, device="cpu:0",
                                    params_version=version)

  def test_capture_and_wait_for(self):
    recorder = EpisodeRecorder()
    fresh = self._record(recorder, ["r0", "r1"], version=7)
    self.assertEqual(fresh, 2)
    record = recorder.wait_for("r1", timeout=1.0)
    self.assertEqual(record.request_id, "r1")
    self.assertEqual(record.seed, 101)
    self.assertEqual(record.params_version, 7)
    np.testing.assert_array_equal(record.action,
                                  np.full((ACTION,), 1.0, np.float32))
    np.testing.assert_array_equal(
        record.image, np.full((IMAGE, IMAGE, 3), 1, np.uint8))
    # Collected records pop: a second wait misses.
    self.assertIsNone(recorder.wait_for("r1", timeout=0.05))
    snap = recorder.snapshot()
    self.assertEqual(snap["captured"], 2)
    self.assertEqual(snap["collected"], 1)
    self.assertEqual(snap["pending"], 1)

  def test_first_capture_wins_and_unattributed(self):
    recorder = EpisodeRecorder()
    self._record(recorder, ["r0"], version=3)
    # A retry re-flushes the same id with a different answer: the first
    # record (the one whose answer the client got) must survive.
    items = [(np.zeros((IMAGE, IMAGE, 3), np.uint8), 999)]
    with context_lib.bind(request_ids="r0"):
      recorder.record_served(items, [np.ones((ACTION,), np.float32) * 9],
                             device="cpu:1", params_version=8)
    record = recorder.wait_for("r0", timeout=0.5)
    self.assertEqual(record.params_version, 3)
    self.assertEqual(recorder.duplicates, 1)
    # No bound ids at all → every item is unattributed, none stored.
    recorder.record_served(items, [np.zeros((ACTION,), np.float32)],
                           device="cpu:0")
    self.assertEqual(recorder.unattributed, 1)
    self.assertEqual(recorder.pending(), 0)

  def test_eviction_bound(self):
    recorder = EpisodeRecorder(max_pending=2)
    self._record(recorder, ["a", "b", "c"])
    self.assertEqual(recorder.pending(), 2)
    self.assertEqual(recorder.evicted, 1)
    self.assertIsNone(recorder.wait_for("a", timeout=0.05))
    self.assertIsNotNone(recorder.wait_for("c", timeout=0.05))

  def test_wait_for_blocks_until_record_lands(self):
    recorder = EpisodeRecorder()
    timer = threading.Timer(0.1, self._record, (recorder, ["late"]))
    timer.start()
    try:
      record = recorder.wait_for("late", timeout=2.0)
    finally:
      timer.join()
    self.assertIsNotNone(record)
    self.assertEqual(record.request_id, "late")


class TestFlywheelIngest(unittest.TestCase):

  def _submit(self, ingest, episode, steps=3, rids=None, versions=None):
    return ingest.submit_episode(
        episode, scene_seed=42,
        request_ids=(rids if rids is not None
                     else [f"r{i}" for i in range(steps)]),
        params_versions=(versions if versions is not None
                         else [5] * steps))

  def test_served_episode_spec_round_trip(self):
    queue = TransitionQueue(64)
    ingest = _ingest(queue)
    self.assertEqual(self._submit(ingest, _episode()), 3)
    batch, labels = queue.drain_batch_with_provenance()
    self.assertEqual(list(labels), ["served"] * 3)
    spec = transition_spec(IMAGE, ACTION)
    # The re-ingested batch is INDISTINGUISHABLE from the synthetic
    # path's: same keys, shapes, dtypes — the ring accepts it as-is.
    buffer = ReplayBuffer(spec, 16, 4, seed=0)
    buffer.extend(batch, provenance=labels)
    self.assertEqual(buffer.provenance_counts(), {"served": 3})
    self.assertEqual(ingest.snapshot()["unique_request_ids"], 3)
    self.assertEqual(ingest.snapshot()["last_staleness_lag"], 5)

  def test_malformed_refused_with_field_named(self):
    logdir = tempfile.mkdtemp(prefix="fw_ingest_")
    ingest = _ingest(flight_recorder=FlightRecorder(
        dump_dir=logdir, min_dump_interval_s=0.0))
    cases = []
    episode = _episode(seed=1)
    episode["images"] = episode["images"][:, : IMAGE // 2]
    cases.append((episode, None, None, "image"))
    episode = _episode(seed=2)
    episode["actions"] = episode["actions"].astype(np.complex64)
    cases.append((episode, None, None, "action"))
    episode = _episode(seed=3)
    episode["rewards"] = episode["rewards"][:-1]
    cases.append((episode, None, None, "episode_streams"))
    cases.append((_episode(seed=4), ["r0", "r1"], None, "request_ids"))
    cases.append((_episode(seed=5), None, [5, None, 5],
                  "params_versions"))
    for episode, rids, versions, want_field in cases:
      with self.assertRaises(IngestRejected) as ctx:
        self._submit(ingest, episode, rids=rids, versions=versions)
      self.assertEqual(ctx.exception.field, want_field)
      self.assertIn(want_field, str(ctx.exception))
    snap = ingest.snapshot()
    self.assertEqual(snap["rejected"], len(cases))
    self.assertEqual(snap["transitions_ingested"], 0)
    dumps = [name for name in os.listdir(logdir)
             if "flywheel_ingest_rejected" in name]
    # Refusals raise AND count AND dump, one file each: the dump names
    # carry a per-process sequence, so back-to-back refusals cannot
    # coalesce into one file.
    self.assertEqual(len(dumps), len(cases), dumps)

  def test_mark_cutover_rebases_mix_fraction(self):
    queue = TransitionQueue(64)
    ingest = _ingest(queue)
    queue.put_batch(_transitions(10), provenance="synthetic")
    ingest.mark_cutover()
    registry = ingest._registry
    self._submit(ingest, _episode())
    # Post-cutover stream is all served: fraction 1.0, not 3/13.
    self.assertAlmostEqual(
        registry.gauge("flywheel/served_fraction").value, 1.0)

  def test_rules_breach_on_ingested_metrics(self):
    rules = flywheel_rules(20.0, coverage_floor=4.0,
                           served_mix_floor=0.05, coverage_warmup=0,
                           mix_warmup=0)
    self.assertEqual([rule.name for rule in rules],
                     ["flywheel_staleness_ceiling",
                      "flywheel_scene_coverage_floor",
                      "flywheel_served_mix_floor"])
    monitor = HealthMonitor(rules, registry=MetricRegistry())
    ingest = _ingest(monitor=monitor, step=40)  # lag 35 > ceiling 20
    self._submit(ingest, _episode())
    snap = monitor.snapshot()
    self.assertIn("flywheel_staleness_ceiling",
                  snap["breaches_per_rule"])
    # Coverage 1 < 4 with warmup 0 also trips; mix is 1.0, green.
    self.assertIn("flywheel_scene_coverage_floor",
                  snap["breaches_per_rule"])
    self.assertNotIn("flywheel_served_mix_floor",
                     snap["breaches_per_rule"])


class TestProvenanceLedgers(unittest.TestCase):

  def test_replay_buffer_counts_and_metrics(self):
    spec = transition_spec(IMAGE, ACTION)
    buffer = ReplayBuffer(spec, 32, 4, seed=0)
    rows = _transitions(6)
    buffer.extend({k: v[:4] for k, v in rows.items()},
                  provenance="synthetic")
    buffer.extend({k: v[4:] for k, v in rows.items()},
                  provenance=np.asarray(["served", "synthetic"]))
    buffer.append({k: v[0] for k, v in rows.items()},
                  provenance="served")
    self.assertEqual(buffer.provenance_counts(),
                     {"served": 2, "synthetic": 5})
    self.assertEqual(buffer.metrics()["replay/provenance/served"], 2)

  def test_per_row_label_length_enforced(self):
    spec = transition_spec(IMAGE, ACTION)
    buffer = ReplayBuffer(spec, 32, 4, seed=0)
    with self.assertRaisesRegex(ValueError, "provenance labels"):
      buffer.extend(_transitions(4), provenance=np.asarray(["served"]))

  def test_state_dict_round_trip_bit_exact(self):
    spec = transition_spec(IMAGE, ACTION)
    buffer = ReplayBuffer(spec, 32, 4, seed=0)
    buffer.extend(_transitions(5), provenance="synthetic")
    buffer.extend(_transitions(3, seed=9), provenance="served")
    resumed = ReplayBuffer(spec, 32, 4, seed=1)
    resumed.load_state_dict(*buffer.state_dict())
    self.assertEqual(resumed.provenance_counts(),
                     {"served": 3, "synthetic": 5})
    # Counters keep advancing from the restored ledger, not from zero.
    resumed.append({k: v[0] for k, v in _transitions(1).items()},
                   provenance="served")
    self.assertEqual(resumed.provenance_counts()["served"], 4)

  def test_pre_provenance_checkpoint_still_loads(self):
    spec = transition_spec(IMAGE, ACTION)
    buffer = ReplayBuffer(spec, 32, 4, seed=0)
    buffer.extend(_transitions(4), provenance="served")
    arrays, meta = buffer.state_dict()
    del meta["provenance"]  # a checkpoint from before ISSUE 18
    resumed = ReplayBuffer(spec, 32, 4, seed=1)
    resumed.load_state_dict(arrays, meta)
    self.assertEqual(resumed.provenance_counts(), {})
    self.assertEqual(resumed.size, 4)

  def test_sharded_slices_labels_and_resumes(self):
    spec = transition_spec(IMAGE, ACTION)
    buffer = ShardedReplayBuffer(spec, 32, 8, num_shards=2, seed=0)
    labels = np.asarray(["served", "synthetic"] * 4)
    buffer.extend(_transitions(8), provenance=labels)
    self.assertEqual(buffer.provenance_counts(),
                     {"served": 4, "synthetic": 4})
    # Crash-resume through the wrapper state dict (per-shard ledgers
    # under shard<i>/ prefixes): the summed ledger must be bit-exact.
    resumed = ShardedReplayBuffer(spec, 32, 8, num_shards=2, seed=3)
    resumed.load_state_dict(*buffer.state_dict())
    self.assertEqual(resumed.provenance_counts(),
                     {"served": 4, "synthetic": 4})
    for shard in resumed._shards:
      self.assertEqual(sum(shard.provenance_counts().values()), 4)


class TestQueueProvenance(unittest.TestCase):

  def test_drain_batch_with_provenance_labels(self):
    queue = TransitionQueue(64)
    queue.put_batch(_transitions(2), provenance="synthetic")
    queue.put_episode(_episode(steps=2, seed=3), provenance="served")
    batch, labels = queue.drain_batch_with_provenance()
    self.assertEqual(batch["image"].shape[0], 4)
    self.assertEqual(list(labels),
                     ["synthetic", "synthetic", "served", "served"])

  def test_overflow_keeps_provenance(self):
    queue = TransitionQueue(4)
    queue.put_batch(_transitions(3), provenance="synthetic")
    queue.put_batch(_transitions(3, seed=5), provenance="served")
    batch, labels = queue.drain_batch_with_provenance()
    # Capacity 4: the oldest synthetic rows were dropped, never the
    # labels' alignment with their rows.
    self.assertEqual(batch["image"].shape[0], 4)
    self.assertEqual(list(labels)[-3:], ["served"] * 3)

  def test_feeder_passes_provenance_through(self):
    spec = transition_spec(IMAGE, ACTION)
    queue = TransitionQueue(64)
    buffer = ReplayBuffer(spec, 32, 4, seed=0)
    feeder = ReplayFeeder(queue, buffer, min_fill=2)
    queue.put_batch(_transitions(3), provenance="served")
    queue.put_batch(_transitions(2, seed=7), provenance="synthetic")
    feeder.drain()
    self.assertEqual(buffer.provenance_counts(),
                     {"served": 3, "synthetic": 2})


class TestServingSeam(unittest.TestCase):

  def test_logical_request_counter_unit(self):
    from tensor2robot_tpu.serving.stats import ServingStats
    stats = ServingStats(registry=MetricRegistry())
    for _ in range(3):
      stats.record_logical_request()
    self.assertEqual(stats.snapshot()["logical_requests"], 3)

  def test_set_variables_carries_promoted_version(self):
    from tensor2robot_tpu.replay.loop import _HotReloadPredictor
    predictor = _HotReloadPredictor(
        types.SimpleNamespace(predict_fn=lambda variables, batch: batch),
        {"w": np.zeros(1)})
    predictor.update({"w": np.ones(1)})
    self.assertEqual(predictor.model_version, 1)
    predictor.set_variables({"w": np.ones(1) * 2}, version=90)
    self.assertEqual(predictor.model_version, 90)
    predictor.set_variables({"w": np.ones(1) * 3})
    self.assertEqual(predictor.model_version, 91)

  def test_router_counts_and_captures_live_traffic(self):
    import jax

    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    from tensor2robot_tpu.serving.stats import ServingStats

    predictor = TinyQPredictor(seed=0)
    stats = ServingStats(registry=MetricRegistry())
    recorder = EpisodeRecorder()
    router = FleetRouter(predictor, devices=jax.devices()[:1],
                         ladder_sizes=(1,), seed=0, stats=stats,
                         episode_recorder=recorder)
    router.warmup(predictor.make_image)
    image = predictor.make_image(3)
    with router:
      for i in range(2):
        router.submit(image, request_id=f"fw-{i}").result(30)
    self.assertEqual(stats.snapshot()["logical_requests"], 2)
    self.assertEqual(recorder.captured, 2)
    record = recorder.wait_for("fw-1", timeout=1.0)
    self.assertIsNotNone(record)
    self.assertEqual(record.action.shape, (4,))
    self.assertEqual(record.params_version,
                     predictor.model_version)


_SMALL_HOST = (os.cpu_count() or 1) < 4


@unittest.skipIf(_SMALL_HOST, "closed-loop lane wants >= 4 cpus")
class TestFlywheelClosedLoop(unittest.TestCase):
  """The closed loop in tier-1, trimmed: re-proves on every PR that the
  LOOP still closes — collectors retired at cutover, a live promote
  cycle completing mid-run, every ingested transition traceable to
  its serving request, counts reconciling against the router, and
  the ingest interlock green. Improvement is recorded, not barred:
  16 fleet steps is too short a window to assert learning."""

  def test_loop_closes_on_served_stream(self):
    from tensor2robot_tpu.flywheel.loop import (FlywheelConfig,
                                                FlywheelLoop)
    config = FlywheelConfig(
        warm_steps=12, fleet_steps=16, export_every=8, min_fill=48,
        capacity=512, batch_size=16, warm_envs=2, eval_batches=2,
        refresh_every=8, deadline_ms=150.0, min_shadow_samples=4,
        min_canary_samples=2, seed=3)
    result = FlywheelLoop(config).run()
    self.assertIsNone(result["client"]["error"])
    self.assertGreaterEqual(result["promotes"]["completed"], 1)
    self.assertTrue(result["reconcile"]["ok"], result["reconcile"])
    ingest = result["ingest"]
    self.assertGreater(ingest["transitions_ingested"], 0)
    self.assertEqual(ingest["unique_request_ids"],
                     ingest["transitions_ingested"])
    self.assertEqual(result["capture"]["unattributed"], 0)
    self.assertTrue(result["health"]["ok"], result["health"])
    self.assertTrue(result["ledger"]["exactly_once"],
                    result["ledger"])
    self.assertGreater(result["provenance"].get("served", 0), 0)


class TestFlywheelStaleParamsControl(unittest.TestCase):
  """The interlock's positive control, through the LOOP (the rule's own
  test above feeds a monitor by hand): with the export path severed the
  fleet serves the warm-start params while the learner advances, and the
  staleness ceiling must breach, with its ``health_breach`` dump. A
  guard that cannot see its own promote path stall is decoration. The
  lag counts learner steps, not seconds, so no host is too small."""

  def test_severed_exports_breach_the_staleness_ceiling(self):
    import json

    from tensor2robot_tpu.flywheel.loop import (FlywheelConfig,
                                                FlywheelLoop)
    healthy = FlywheelConfig(warm_steps=16, fleet_steps=30,
                             export_every=15, seed=0)
    # The healthy run's ceiling, resolved the same way: the control and
    # the healthy run disagree ONLY on whether exports flow.
    config = FlywheelConfig(
        warm_steps=16, fleet_steps=60, export_every=15, seed=0,
        promotes=False,
        staleness_ceiling=healthy.resolved_staleness_ceiling())
    result = FlywheelLoop(config).run()
    self.assertIsNone(result["client"]["error"])
    self.assertEqual(result["promotes"]["completed"], 0)
    self.assertIn("flywheel_staleness_ceiling",
                  result["health"]["breaches_per_rule"], result["health"])
    self.assertFalse(result["health"]["ok"])
    self.assertGreater(result["ingest"]["max_staleness_lag"],
                       result["config"]["staleness_ceiling"])
    dump_dir = os.path.join(result["workdir"], "flightrec")
    rules = []
    for name in sorted(os.listdir(dump_dir)):
      if name.startswith("flightrec-") and "health_breach" in name:
        with open(os.path.join(dump_dir, name)) as f:
          rules.append(json.load(f)["trigger"]["rule"])
    self.assertIn("flywheel_staleness_ceiling", rules)


if __name__ == "__main__":
  unittest.main()

"""Chaos bench: scripted faults against the live fleet — FAULTS_r15.

The ISSUE 14 acceptance instrument. Every failure mode the
fault-tolerance layer claims to absorb is INJECTED deterministically
(obs/faults.FaultPlan — explicit seams, seeded schedules, no
monkeypatching) against live machinery, and the recovery behavior is
measured and bar-checked AT GENERATION TIME. Five phases, ONE JSON
line (the repo's bench/driver contract):

1. **router_chaos** — paced multi-class traffic through an
   8-replica FleetRouter while the plan throws replica dispatch
   exceptions (enough to trip the circuit breaker), latency spikes, a
   hung flush, and a dispatcher thread kill. Bars: ZERO client-visible
   raw errors (every future resolves with a result or a typed
   ``RequestShed``); the health timeline records the full
   quarantine→probe→reinstate arc; the killed dispatcher restarted
   within its budget; and a post-chaos clean window puts every class's
   p99 back inside its budget.
2. **degraded** — every replica's breaker tripped, then a held-flush
   burst at 2× the fleet's queue slots: the router keeps routing
   (degraded mode) and the existing SLO machinery sheds
   lowest-priority-first — measured shed ordering, completions > 0,
   zero raw errors. The priming failures themselves resolve as typed
   ``shed_fault`` (deadline slack can't cover a retry with the whole
   fleet throwing).
3. **dispatcher** — a standalone MicroBatcher killed mid-flush twice:
   once inside its restart budget (queue survives, later requests
   served), once past it (EVERY pending future resolves
   ``DispatcherDead`` — clients never hang on a dead dispatcher).
4. **export_watcher** — a publish stream where the plan corrupts one
   export and truncates another mid-write: both are rejected with
   flight-recorder records and never swapped in; the good versions
   around them load normally.
5. **learner** — crash-resume, proven twice: (a) BIT-PARITY on a
   deterministic pre-training stream (no collector threads): train k1
   steps, checkpoint, restore into FRESH objects, train k2 more — the
   post-resume per-step TD stream must be bit-identical to an
   uninterrupted k1+k2 run's tail, and the restored ring bit-equal at
   the cut; (b) LIVE kill-and-resume: a real ReplayTrainLoop killed
   by an injected crash at step k, resumed from its checkpoint, must
   land its converged-phase eval-TD within the r14 tolerance (0.05)
   of an uninterrupted control run.

HONESTY CAVEAT (carried as ``virtual_mesh``): chipless, the replicas
are XLA virtual CPU devices sharing this host's cores. What the
chipless artifact proves is STRUCTURE and ORDERING — the breaker state
machine against real dispatch failures, typed-not-hung futures, shed
ordering, checkpoint/restore fidelity. Recovery LATENCY on real chips
(how fast p99 re-converges after a real device fault) is a chip claim
that is not measured here.
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tensor2robot_tpu.obs import faults as faults_lib
from tensor2robot_tpu.serving.slo import (DispatcherDead, HealthConfig,
                                          RequestShed, SLOClass)

R15_TD_DELTA_BAR = 0.05   # live kill-resume converged-TD tolerance (r14's)

# Host-scale class ladder for the chaos window: budgets are generous
# enough that an absorbed fault (retry + latency spike) still lands
# inside them on a CPU host — the bar is "recovery keeps the budget",
# not raw speed (virtual_mesh caveat).
R15_CLASSES: Tuple[Tuple[SLOClass, int, float], ...] = (
    (SLOClass("interactive", priority=2, deadline_ms=500.0), 8, 1.0),
    (SLOClass("standard", priority=1, deadline_ms=1200.0), 12, 1.0),
    (SLOClass("batch", priority=0, deadline_ms=3000.0), 8, 1.0),
)


def _class_images(predictor, classes, seed: int) -> Dict[str, list]:
  images = {}
  for class_index, (slo_class, clients, _) in enumerate(classes):
    images[slo_class.name] = [
        predictor.make_image(seed + 10_000 * (class_index + 1) + c)
        for c in range(clients)]
  return images


def _counters_block(point: Dict, stats_snapshot: Dict, classes) -> Dict:
  per_class = {}
  failed_total = 0
  for slo_class, _, _ in classes:
    counter = point["counters"][slo_class.name]
    snap = stats_snapshot.get("per_class", {}).get(slo_class.name, {})
    failed_total += counter.failed
    per_class[slo_class.name] = {
        "budget_ms": slo_class.deadline_ms,
        "priority": slo_class.priority,
        "submitted": counter.submitted,
        "completed": counter.completed,
        "client_shed": counter.shed,
        "client_failed": counter.failed,
        "shed_fault": snap.get("shed_fault", 0),
        "shed_capacity": snap.get("shed_capacity", 0),
        "shed_expired": snap.get("shed_expired", 0),
        "latency_p50_ms": snap.get("latency_p50_ms"),
        "latency_p99_ms": snap.get("latency_p99_ms"),
    }
  return {"per_class": per_class, "client_failed_total": failed_total}


def _measure_router_chaos(devices, classes, health: HealthConfig,
                          chaos_s: float, recovery_s: float,
                          seed: int) -> Dict:
  """Phase 1: scripted faults under paced live traffic + clean recovery."""
  from tensor2robot_tpu.obs import flight_recorder as flight_lib
  from tensor2robot_tpu.serving.fleet_bench import _run_open_loop_point
  from tensor2robot_tpu.serving.router import FleetRouter
  from tensor2robot_tpu.serving.smoke import TinyQPredictor
  from tensor2robot_tpu.serving.stats import ServingStats

  recorder = flight_lib.FlightRecorder()
  specs = [
      # Replica 0: enough consecutive dispatch errors to trip the
      # breaker (threshold failures), then healthy — the
      # quarantine→probe→reinstate arc.
      faults_lib.FaultSpec(kind="dispatch_error",
                           point="replica_dispatch",
                           site=str(devices[0]), at=0, every=1,
                           count=health.failure_threshold),
  ]
  if len(devices) > 1:
    specs.append(faults_lib.FaultSpec(
        kind="latency_spike", point="replica_dispatch",
        site=str(devices[1 % len(devices)]), at=1, every=3, count=3,
        latency_s=0.05))
  if len(devices) > 2:
    specs.append(faults_lib.FaultSpec(
        kind="hung_flush", point="batcher_flush",
        site=f"batcher@{devices[2]}", at=1, count=1, latency_s=0.1))
  if len(devices) > 3:
    specs.append(faults_lib.FaultSpec(
        kind="thread_kill", point="batcher_flush",
        site=f"batcher@{devices[3]}", at=0, count=1))
  plan = faults_lib.FaultPlan(specs, seed=seed, recorder=recorder)

  predictor = TinyQPredictor(seed=seed)
  router = FleetRouter(
      predictor, devices=devices, ladder_sizes=(1, 2, 4),
      max_queue=32, dispatch_margin_ms=100.0, seed=seed,
      health=health, fault_plan=plan)
  router.warmup(predictor.make_image)
  images = _class_images(predictor, classes, seed)

  with router:
    chaos_stats = ServingStats()
    router.use_stats(chaos_stats)
    chaos_point = _run_open_loop_point(
        lambda image, slo: router.submit(image, slo=slo),
        classes, images, 1.0, chaos_s, seed)
    chaos = _counters_block(chaos_point, chaos_stats.snapshot(), classes)
    # Let any remaining quarantine window lapse, then measure the
    # recovered fleet on a CLEAN window (faults exhausted by count).
    time.sleep(health.quarantine_s + 0.2)
    recovery_stats = ServingStats()
    router.use_stats(recovery_stats)
    recovery_point = _run_open_loop_point(
        lambda image, slo: router.submit(image, slo=slo),
        classes, images, 1.0, recovery_s, seed + 1)
    recovery = _counters_block(recovery_point, recovery_stats.snapshot(),
                               classes)
    health_snap = router.health_snapshot()

  events = [entry["event"] for entry in health_snap["timeline"]]
  recovery_ok = all(
      entry["latency_p99_ms"] is not None
      and entry["latency_p99_ms"] <= entry["budget_ms"]
      for entry in recovery["per_class"].values())
  restarts = sum(entry["dispatcher_restarts"]
                 for entry in health_snap["replicas"].values())
  return {
      "faults_fired": plan.fired_counts(),
      "fault_records": plan.snapshot()["fired"],
      "chaos": chaos,
      "recovery": recovery,
      "health_timeline": health_snap["timeline"],
      "replica_states_final": {
          name: entry["state"]
          for name, entry in health_snap["replicas"].items()},
      "quarantine_probe_reinstate_ok": (
          "quarantine" in events and "probe" in events
          and "reinstate" in events),
      "dispatcher_restarts": restarts,
      "zero_client_errors": chaos["client_failed_total"] == 0
                            and recovery["client_failed_total"] == 0,
      "post_quarantine_p99_ok": bool(recovery_ok),
      "correlated_fault_dumps": sum(
          1 for record in plan.snapshot()["fired"]
          if record.get("request_id") or record.get("request_ids")),
  }


def _measure_degraded(devices, classes, seed: int) -> Dict:
  """Phase 2: whole-fleet quarantine → typed shed_fault + degraded
  lowest-priority-first shedding on the existing SLO machinery."""
  from tensor2robot_tpu.serving.fleet_bench import _overload_burst
  from tensor2robot_tpu.serving.router import FleetRouter
  from tensor2robot_tpu.serving.smoke import TinyQPredictor

  devices = devices[:2]
  health = HealthConfig(failure_threshold=2, quarantine_s=60.0,
                        retry_cost_ms=10.0, max_retries=2)
  # Exactly threshold failures per replica: the breakers trip, then
  # the batchers work again — degraded mode with a SERVING fleet, so
  # the burst measures admission shedding, not fault shedding.
  plan = faults_lib.FaultPlan([
      faults_lib.FaultSpec(kind="dispatch_error",
                           point="replica_dispatch", site=str(device),
                           at=0, every=1,
                           count=health.failure_threshold)
      for device in devices
  ], seed=seed)
  predictor = TinyQPredictor(seed=seed)
  router = FleetRouter(
      predictor, devices=devices, ladder_sizes=(1, 2, 4), max_queue=12,
      dispatch_margin_ms=100.0, seed=seed, health=health,
      fault_plan=plan)
  router.warmup(predictor.make_image)
  images = _class_images(predictor, classes, seed)
  prime_class = classes[0][0]
  typed_sheds = 0
  raw_errors = 0
  # Exactly failure_threshold priming requests: each one burns one
  # dispatch attempt on EVERY replica (the retry excludes the failed
  # one), so after the threshold-th request both breakers have exactly
  # threshold consecutive failures and trip — with the per-replica
  # fault budgets exhausted at the same moment, leaving the fleet
  # quarantined-but-servable for the degraded burst below. One more
  # request would dispatch SUCCESSFULLY and close a breaker
  # (degraded_success) before the degraded state could be observed.
  primed = health.failure_threshold
  with router:
    for i in range(primed):
      future = router.submit(images[prime_class.name][0],
                             slo=prime_class)
      try:
        future.result(30.0)
      except RequestShed:
        typed_sheds += 1
      except Exception:
        raw_errors += 1
    snap = router.health_snapshot()
    degraded_entered = any(entry["event"] == "degraded_enter"
                           for entry in snap["timeline"])
    all_open = all(entry["state"] == "open"
                   for entry in snap["replicas"].values())
    # Read the priming window's fault-shed accounting BEFORE the burst
    # helper swaps in its own fresh stats window.
    shed_fault_total = sum(
        entry.get("shed_fault", 0)
        for entry in router.stats.snapshot()["per_class"].values())
    # The deterministic burst: held flushes, 2x queue slots — the
    # fleet is degraded but its SLO machinery still sheds
    # lowest-priority-first and SERVES what it admits.
    burst = _overload_burst(router, classes, images)
  return {
      "primed_requests": primed,
      "typed_sheds": typed_sheds,
      "raw_errors": raw_errors,
      "degraded_entered": bool(degraded_entered),
      "all_replicas_open": bool(all_open),
      "burst": burst,
      "burst_completed": sum(entry["completed"]
                             for entry in burst["per_class"].values()),
      "shed_fault_total_phase": shed_fault_total,
      "ok": (raw_errors == 0 and typed_sheds > 0 and degraded_entered
             and all_open and shed_fault_total > 0
             and burst["priority_ordering_ok"]
             and sum(entry["completed"]
                     for entry in burst["per_class"].values()) > 0),
  }


def _measure_dispatcher(seed: int) -> Dict:
  """Phase 3: dispatcher kill inside and past the restart budget."""
  from tensor2robot_tpu.serving.batcher import MicroBatcher

  # (a) one kill, budget 1: the in-flight batch fails typed, the
  # dispatcher restarts, later requests are served.
  plan_a = faults_lib.FaultPlan([
      faults_lib.FaultSpec(kind="thread_kill", point="batcher_flush",
                           site="d1", at=1)], seed=seed)
  batcher_a = MicroBatcher(lambda items: [x * 2 for x in items],
                           max_batch=2, deadline_ms=30.0,
                           fault_plan=plan_a, site="d1",
                           restart_budget=1)
  killed_typed = served_after_restart = False
  with batcher_a:
    assert batcher_a.submit(1).result(10.0) == 2
    poison_a, poison_b = batcher_a.submit(10), batcher_a.submit(11)
    killed = 0
    for future in (poison_a, poison_b):
      try:
        future.result(10.0)
      except DispatcherDead:
        killed += 1
      except Exception:
        pass
    killed_typed = killed == 2
    deadline = time.monotonic() + 10.0
    while (batcher_a.dispatcher_restarts < 1
           and time.monotonic() < deadline):
      time.sleep(0.01)
    served_after_restart = batcher_a.submit(3).result(10.0) == 6
  restarts_a = batcher_a.dispatcher_restarts

  # (b) budget 0: the kill takes the batcher down; EVERY queued future
  # resolves DispatcherDead (never a hang), and submits raise typed.
  plan_b = faults_lib.FaultPlan([
      faults_lib.FaultSpec(kind="thread_kill", point="batcher_flush",
                           site="d2", at=0)], seed=seed)
  batcher_b = MicroBatcher(lambda items: [x * 2 for x in items],
                           max_batch=8, deadline_ms=50.0,
                           fault_plan=plan_b, site="d2",
                           restart_budget=0)
  batcher_b.start()
  with batcher_b.hold_flushes():
    futures = [batcher_b.submit(i) for i in range(5)]
  resolved_typed = 0
  for future in futures:
    try:
      future.result(10.0)
    except DispatcherDead:
      resolved_typed += 1
    except Exception:
      pass
  deadline = time.monotonic() + 10.0
  while not batcher_b.dispatcher_dead and time.monotonic() < deadline:
    time.sleep(0.01)
  submit_raises_typed = False
  try:
    batcher_b.submit(1)
  except DispatcherDead:
    submit_raises_typed = True
  except Exception:
    pass
  batcher_b.stop()
  return {
      "restart": {
          "restarts": restarts_a,
          "in_flight_resolved_typed": bool(killed_typed),
          "served_after_restart": bool(served_after_restart),
      },
      "unrecoverable": {
          "pending": len(futures),
          "resolved_typed": resolved_typed,
          "dead": bool(batcher_b.dispatcher_dead),
          "submit_raises_typed": bool(submit_raises_typed),
      },
      "ok": (killed_typed and served_after_restart and restarts_a == 1
             and resolved_typed == len(futures)
             and batcher_b.dispatcher_dead and submit_raises_typed),
  }


def _publish_export(root: str, version: int, seed: int) -> str:
  """A minimal native-layout export (variables npz) the watcher loads."""
  from tensor2robot_tpu.export import variables_io
  from tensor2robot_tpu.export.native_export_generator import (
      VARIABLES_NPZ)
  rng = np.random.default_rng(seed + version)
  export_dir = os.path.join(root, str(version))
  os.makedirs(export_dir, exist_ok=True)
  variables_io.save_variables(
      os.path.join(export_dir, VARIABLES_NPZ),
      {"params": {"w": rng.standard_normal((4, 2)).astype(np.float32)}})
  return export_dir


def _measure_export_watcher(seed: int) -> Dict:
  """Phase 4: corrupt/partial exports rejected with flightrec records,
  never swapped in; the good versions around them load normally."""
  from tensor2robot_tpu.obs import flight_recorder as flight_lib
  from tensor2robot_tpu.serving.rollout import ExportWatcher

  root = tempfile.mkdtemp(prefix="faults_exports_")
  dump_dir = os.path.join(root, "dumps")
  recorder = flight_lib.FlightRecorder(dump_dir=dump_dir,
                                       min_dump_interval_s=0.0)
  plan = faults_lib.FaultPlan([
      faults_lib.FaultSpec(kind="export_partial_write",
                           point="export_load", site="2", at=0),
      faults_lib.FaultSpec(kind="export_corrupt",
                           point="export_load", site="4", at=0),
  ], seed=seed, recorder=recorder)
  watcher = ExportWatcher(root, fault_plan=plan,
                          flight_recorder=recorder)
  accepted: List[int] = []
  for version in (1, 2, 3, 4, 5):
    _publish_export(root, version, seed)
    # Two polls per publish: the first may reject (damaged), the
    # second proves a rejected version is not silently marked seen
    # yet also never accepted while damaged.
    for _ in range(2):
      found = watcher.poll()
      if found is not None:
        accepted.append(found[0])
  rejected_versions = sorted({entry["version"]
                              for entry in watcher.rejections})
  dumps = (sorted(os.listdir(dump_dir))
           if os.path.isdir(dump_dir) else [])
  return {
      "published": [1, 2, 3, 4, 5],
      "accepted": accepted,
      "rejected_versions": rejected_versions,
      "rejections": watcher.rejections[:8],
      "rejection_dumps": len([d for d in dumps
                              if "export_rejected" in d]),
      "ok": (accepted == [1, 3, 5] and rejected_versions == [2, 4]
             and len([d for d in dumps
                      if "export_rejected" in d]) >= 1),
  }


# -- phase 5: learner crash-resume ------------------------------------------


def _fixed_stream(n: int, image_size: int, action_size: int,
                  grasp_radius: float, gamma: float, seed: int) -> Dict:
  """A deterministic pre-training transition stream (the replay loop's
  eval recipe, reused as ingest): class-balanced actions over sampled
  scenes, reward = analytic grasp success."""
  from tensor2robot_tpu.research.qtopt import synthetic_grasping as sg
  images, targets = sg.sample_scenes(n, image_size=image_size,
                                     seed=seed + 101,
                                     num_distractors=0, occlusion=False)
  rng = np.random.default_rng(seed + 102)
  actions = rng.uniform(-1.0, 1.0, (n, action_size)).astype(np.float32)
  near = rng.random(n) < 0.5
  noise = rng.normal(0.0, 0.12, (n, 2)).astype(np.float32)
  actions[near, :2] = np.clip(targets[near] + noise[near], -1.0, 1.0)
  success = sg.grasp_success(targets, actions,
                             grasp_radius).astype(np.float32)
  return {
      "image": images,
      "action": actions,
      "reward": success,
      "done": success,
      "next_image": images,
  }


class _DeterministicLearner:
  """The host-path learn step (sample→label→train→reprioritize) with
  NO collector threads: every source of nondeterminism is a seeded rng
  or a checkpointed counter, so crash-at-k-then-resume must reproduce
  the uninterrupted run BIT FOR BIT — the parity harness both the
  bench and tests/test_faults.py drive."""

  def __init__(self, stream: Dict, image_size: int, action_size: int,
               batch_size: int, capacity: int, gamma: float,
               refresh_every: int, seed: int):
    import optax

    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.replay.bellman import BellmanUpdater
    from tensor2robot_tpu.replay.loop import transition_spec
    from tensor2robot_tpu.replay.ring_buffer import ReplayBuffer
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    from tensor2robot_tpu.train.trainer import Trainer
    import jax

    self.refresh_every = refresh_every
    self.model = TinyQCriticModel(
        image_size=image_size, action_size=action_size,
        optimizer_fn=lambda: optax.adam(3e-3))
    mesh = mesh_lib.create_mesh({"data": 1, "model": 1},
                                devices=jax.devices()[:1])
    self.trainer = Trainer(self.model, mesh=mesh, seed=seed)
    self.state = self.trainer.create_train_state(batch_size=batch_size)
    self.buffer = ReplayBuffer(
        transition_spec(image_size, action_size), capacity, batch_size,
        seed=seed, prioritized=True)
    self.buffer.extend(stream)
    host_variables = self._host_variables()
    self.updater = BellmanUpdater(
        self.model, host_variables, action_size=action_size,
        gamma=gamma, num_samples=16, num_elites=4, iterations=2,
        seed=seed + 13)
    self.step = 0
    self._train_step = None

  def _host_variables(self):
    from tensor2robot_tpu.export import export_utils
    return export_utils.fetch_variables_to_host(
        self.state.variables(use_ema=True))

  def run_steps(self, n: int) -> List[np.ndarray]:
    """n optimizer steps; returns the per-step TD-error arrays (the
    bit-parity comparison stream)."""
    tds = []
    for _ in range(n):
      batch, info = self.buffer.sample()
      targets, _ = self.updater.compute_targets(batch)
      features = {"image": np.asarray(batch["image"]),
                  "action": np.asarray(batch["action"])}
      labels = {self.model.target_key: targets}
      sharded = self.trainer.shard_batch((features, labels))
      if self._train_step is None:
        self._train_step = self.trainer.aot_train_step(self.state,
                                                       *sharded)
      self.state, _ = self._train_step(self.state, *sharded)
      online = self.state.variables(use_ema=True)
      td = self.updater.td_errors(online, batch, targets)
      self.buffer.update_priorities(info.indices, td)
      self.step += 1
      if self.step % self.refresh_every == 0:
        self.updater.refresh(self._host_variables(), self.step)
      tds.append(np.asarray(td).copy())
    return tds

  def save(self, root: str) -> None:
    from tensor2robot_tpu.train import checkpoints as checkpoints_lib
    from tensor2robot_tpu.train.checkpoints import CheckpointManager
    manager = CheckpointManager(root, max_to_keep=2,
                                async_checkpointing=False)
    manager.save(self.step, self.state, force=True)
    manager.wait()
    manager.close()
    target_vars, target_meta = self.updater.target_state()
    buffer_arrays, buffer_meta = self.buffer.state_dict()
    checkpoints_lib.save_sidecar(
        root, self.step,
        trees={} if target_vars is None else {"target": target_vars},
        flats={"buffer": buffer_arrays},
        meta={"target": target_meta,
              "next_label_seed": self.updater.next_label_seed,
              "buffer_meta": buffer_meta})

  def restore(self, root: str) -> int:
    from tensor2robot_tpu.train import checkpoints as checkpoints_lib
    from tensor2robot_tpu.train.checkpoints import CheckpointManager
    step = checkpoints_lib.latest_resumable_step(root)
    if step is None:
      raise FileNotFoundError(f"no resumable checkpoint under {root}")
    manager = CheckpointManager(root, max_to_keep=2,
                                async_checkpointing=False)
    self.state = manager.restore(self.state, step=step)
    manager.close()
    trees, flats, meta = checkpoints_lib.load_sidecar(root, step)
    self.buffer.load_state_dict(flats["buffer"], meta["buffer_meta"])
    self.updater.restore_target_state(trees.get("target"),
                                      meta["target"])
    self.updater.restore_label_seed(meta["next_label_seed"])
    self.step = int(step)
    self._train_step = None  # recompiles against the restored avals
    return self.step


def _measure_resume_parity(k1: int, k2: int, seed: int) -> Dict:
  """Phase 5a: crash-at-k1 + resume ≡ uninterrupted, bit for bit, on
  the deterministic pre-training stream."""
  kwargs = dict(image_size=16, action_size=4, batch_size=32,
                capacity=256, gamma=0.8, refresh_every=10, seed=seed)
  stream = _fixed_stream(256, 16, 4, 0.4, 0.8, seed)

  # Uninterrupted oracle: k1 + k2 straight through.
  oracle = _DeterministicLearner(stream, **kwargs)
  oracle_tds = oracle.run_steps(k1 + k2)

  # Interrupted: k1 steps, checkpoint, "crash" (objects discarded),
  # FRESH learner restores and runs k2 more.
  root = tempfile.mkdtemp(prefix="faults_ckpt_")
  first = _DeterministicLearner(stream, **kwargs)
  first_tds = first.run_steps(k1)
  first.save(root)
  saved_buffer_arrays, saved_buffer_meta = first.buffer.state_dict()
  del first

  resumed = _DeterministicLearner(stream, **kwargs)
  restored_step = resumed.restore(root)
  restored_arrays, restored_meta = resumed.buffer.state_dict()
  buffer_bit_equal = (
      all(np.array_equal(saved_buffer_arrays[key], restored_arrays[key])
          for key in saved_buffer_arrays)
      and saved_buffer_meta["next"] == restored_meta["next"]
      and saved_buffer_meta["append_count"]
      == restored_meta["append_count"]
      and saved_buffer_meta["rng_state"] == restored_meta["rng_state"])
  resumed_tds = resumed.run_steps(k2)

  pre_crash_equal = all(
      np.array_equal(a, b) for a, b in zip(oracle_tds[:k1], first_tds))
  post_resume_equal = all(
      np.array_equal(a, b) for a, b in zip(oracle_tds[k1:], resumed_tds))
  max_post_delta = max(
      (float(np.max(np.abs(a - b)))
       for a, b in zip(oracle_tds[k1:], resumed_tds)), default=0.0)
  parity_ok = (restored_step == k1 and buffer_bit_equal
               and pre_crash_equal and post_resume_equal)
  return {
      "k1": k1, "k2": k2,
      "restored_step": restored_step,
      "buffer_bit_equal": bool(buffer_bit_equal),
      "pre_crash_stream_bit_equal": bool(pre_crash_equal),
      "post_resume_stream_bit_equal": bool(post_resume_equal),
      "max_post_resume_td_delta": max_post_delta,
      "parity_ok": bool(parity_ok),
  }


def _measure_live_resume(steps: int, crash_at: int,
                         checkpoint_every: int, seed: int) -> Dict:
  """Phase 5b: a REAL ReplayTrainLoop (collector threads and all)
  killed by an injected crash, resumed, compared converged-phase
  against an uninterrupted control run."""
  from tensor2robot_tpu.replay.loop import (ReplayLoopConfig,
                                            ReplayTrainLoop)

  def make_loop(logdir, resume=False, plan=None):
    import optax

    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    config = ReplayLoopConfig(
        seed=seed, checkpoint_every=checkpoint_every, resume=resume,
        eval_every=15, mesh_dp=1, mesh_tp=1)
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    return ReplayTrainLoop(config, logdir, model=model,
                           fault_plan=plan), config

  def converged_mean(result):
    points = [entry["eval_td_error"]
              for entry in result["eval_history"]
              if entry["step"] > steps // 3]
    return float(np.mean(points)), len(points)

  control_dir = tempfile.mkdtemp(prefix="faults_ctrl_")
  control_loop, _ = make_loop(control_dir)
  control = control_loop.run(steps)
  control_mean, control_points = converged_mean(control)

  crash_dir = tempfile.mkdtemp(prefix="faults_crash_")
  plan = faults_lib.FaultPlan([
      faults_lib.FaultSpec(kind="crash", point="learner_step",
                           site="learner", at=crash_at)], seed=seed)
  crash_loop, _ = make_loop(crash_dir, plan=plan)
  crashed_at = None
  try:
    crash_loop.run(steps)
  except faults_lib.InjectedCrash as e:
    crashed_at = e.step
  resumed_loop, _ = make_loop(crash_dir, resume=True)
  resumed = resumed_loop.run(steps)
  resumed_mean, resumed_points = converged_mean(resumed)
  delta = abs(resumed_mean - control_mean)
  return {
      "steps": steps,
      "crash_at": crash_at,
      "crashed_at": crashed_at,
      "checkpoint_every": checkpoint_every,
      "resumed_from": crash_at - (crash_at % checkpoint_every),
      "control": {
          "eval_td_reduction": control["eval_td_reduction"],
          "converged_mean_td": round(control_mean, 5),
          "converged_points": control_points,
      },
      "resumed": {
          "eval_td_reduction": resumed["eval_td_reduction"],
          "converged_mean_td": round(resumed_mean, 5),
          "converged_points": resumed_points,
          "ledger_all_one": all(
              v == 1 for v in resumed["compile_counts"].values()),
      },
      "converged_td_delta": round(delta, 4),
      "td_delta_bar": R15_TD_DELTA_BAR,
      "ok": (crashed_at == crash_at
             and delta <= R15_TD_DELTA_BAR
             and resumed["eval_td_reduction"] >= 0.3
             and control["eval_td_reduction"] >= 0.3),
  }


def measure_faults(
    n_devices: Optional[int] = None,
    classes: Sequence[Tuple[SLOClass, int, float]] = R15_CLASSES,
    chaos_s: float = 4.0,
    recovery_s: float = 3.0,
    parity_steps: Tuple[int, int] = (30, 30),
    # Live kill-resume protocol scale (ISSUE 15 satellite, de-risking
    # the r15 session note): the converged-TD bar is STATISTICAL —
    # thread timing varies ring contents — and the committed r15
    # margin (delta 0.0458 of the 0.05 bar) sat one flake from a
    # backstop-regen failure at 90 steps / 4 converged eval points.
    # 150 steps with the same eval_every=15 cadence averages 7
    # converged points (steps > 50) on each side of the comparison,
    # roughly 1.3x tighter on the mean's noise, WITHOUT loosening the
    # bar itself (R15_TD_DELTA_BAR stays 0.05, the r14 tolerance).
    live_steps: int = 150,
    live_crash_at: int = 90,
    live_checkpoint_every: int = 30,
    live_resume: bool = True,
    seed: int = 0,
    enforce_bars: bool = True,
) -> Dict:
  """Runs the five-phase chaos protocol; returns the FAULTS_r15
  artifact dict. `enforce_bars` (the --smoke lane) raises if any
  committed acceptance bar fails AT GENERATION TIME — a committed
  chaos artifact that does not meet its own bars must not exist."""
  import jax

  devices = jax.devices()
  if n_devices is not None:
    if n_devices > len(devices):
      raise ValueError(
          f"asked for {n_devices} devices, have {len(devices)}; on a "
          "chipless host run the CLI --smoke lane (it bootstraps an "
          "8-virtual-device CPU mesh).")
    devices = devices[:n_devices]
  device_kind = devices[0].device_kind
  health = HealthConfig(failure_threshold=3, quarantine_s=1.0,
                        retry_cost_ms=20.0, max_retries=2,
                        restart_budget=2)

  router_chaos = _measure_router_chaos(devices, classes, health,
                                       chaos_s, recovery_s, seed)
  degraded = _measure_degraded(devices, classes, seed)
  dispatcher = _measure_dispatcher(seed)
  export_watcher = _measure_export_watcher(seed)
  parity = _measure_resume_parity(*parity_steps, seed=seed)
  live = (_measure_live_resume(live_steps, live_crash_at,
                               live_checkpoint_every, seed)
          if live_resume else None)

  result = {
      "round": 15,
      "metric": ("fault-tolerant fleet: deterministic injection, "
                 "quarantine + deadline-aware retry, crash-resume"),
      "device_kind": device_kind,
      "virtual_mesh": device_kind.lower() == "cpu",
      "devices": len(devices),
      "health": {
          "failure_threshold": health.failure_threshold,
          "quarantine_s": health.quarantine_s,
          "retry_cost_ms": health.retry_cost_ms,
          "max_retries": health.max_retries,
          "restart_budget": health.restart_budget,
      },
      "classes": [{
          "name": slo_class.name, "priority": slo_class.priority,
          "budget_ms": slo_class.deadline_ms, "clients": clients,
          "hz_per_client": hz,
      } for slo_class, clients, hz in classes],
      "router_chaos": router_chaos,
      "degraded": degraded,
      "dispatcher": dispatcher,
      "export_watcher": export_watcher,
      "learner": {"parity": parity, "live": live},
      # Compact sentinels (round 15; null-safe): recovery is
      # meaningful chipless as STRUCTURE (typed sheds, ordering, the
      # breaker arc, bit-parity resume); recovery LATENCY on real
      # chips is the queued chip claim.
      "fault_recovery_p99_ok": router_chaos["post_quarantine_p99_ok"],
      "learner_resume_parity": parity["parity_ok"],
      "note": (
          "Scripted deterministic faults (obs/faults.FaultPlan) "
          "against live machinery on the virtual mesh: replica "
          "dispatch errors -> circuit-breaker quarantine -> half-open "
          "probe -> reinstate under paced multi-class traffic with "
          "zero raw client errors; whole-fleet quarantine degrades to "
          "lowest-priority-first shedding (typed shed_fault, never a "
          "hang); dispatcher kills absorbed by a capped restart "
          "budget, then resolved typed past it; corrupt/partial "
          "exports rejected with flightrec records; learner "
          "crash-resume proven bit-exact on a deterministic stream "
          "and within the r14 TD tolerance on live threaded runs. "
          "virtual_mesh=true: structure/ordering claims only — "
          "recovery latency on real chips is not measured."),
  }

  if enforce_bars:
    failures = []
    if not router_chaos["zero_client_errors"]:
      failures.append(
          f"client-visible raw errors: "
          f"{router_chaos['chaos']['client_failed_total']} chaos / "
          f"{router_chaos['recovery']['client_failed_total']} recovery")
    if not router_chaos["quarantine_probe_reinstate_ok"]:
      failures.append(
          "health timeline missing quarantine/probe/reinstate: "
          f"{[e['event'] for e in router_chaos['health_timeline']]}")
    if not router_chaos["post_quarantine_p99_ok"]:
      failures.append("post-quarantine p99 outside budget")
    if router_chaos["dispatcher_restarts"] < 1 and len(devices) > 3:
      failures.append("killed dispatcher did not restart")
    if not degraded["ok"]:
      failures.append(f"degraded phase failed: {degraded}")
    if not dispatcher["ok"]:
      failures.append(f"dispatcher phase failed: {dispatcher}")
    if not export_watcher["ok"]:
      failures.append(f"export watcher phase failed: "
                      f"{export_watcher['accepted']} / "
                      f"{export_watcher['rejected_versions']}")
    if not parity["parity_ok"]:
      failures.append(f"resume parity failed: {parity}")
    if live is not None and not live["ok"]:
      failures.append(
          f"live resume failed: delta {live['converged_td_delta']} "
          f"(bar {R15_TD_DELTA_BAR}), crashed_at {live['crashed_at']}")
    if failures:
      raise AssertionError(
          "FAULTS_r15 acceptance bars failed: " + "; ".join(failures))
  return result


def main(argv=None) -> None:
  """CLI: ONE JSON line. --smoke bootstraps the 8-virtual-device CPU
  mesh (re-exec with the canonical env) and runs the committed
  FAULTS_r15 protocol with generation-time bar enforcement; --ci is
  the reduced tier-1 lane (structural checks only — quantitative bars
  live in tests/test_faults.py behind the cpu_count gate)."""
  import argparse
  import json
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
                 "tensor2robot_tpu.serving.fault_bench",
                 *sys.argv[1:]],
                cpu_mesh_env(n))
  if args.ci:
    results = measure_faults(
        n_devices=2,
        classes=tuple((slo_class, max(2, clients // 4), hz)
                      for slo_class, clients, hz in R15_CLASSES),
        chaos_s=2.0, recovery_s=1.5, parity_steps=(8, 8),
        live_resume=False, seed=args.seed, enforce_bars=False)
  else:
    results = measure_faults(seed=args.seed)
  line = json.dumps(results)
  if args.out:
    with open(args.out, "w") as f:
      f.write(line + "\n")
  print(line)


if __name__ == "__main__":
  main()

"""Vectorized actor fleet (ISSUE 5 acceptance).

Covers the tentpole contracts chiplessly: property-tested equivalence
of `VectorGraspEnv` with N scalar `GraspRetryEnv`s (scenes, outcomes,
auto-reset boundaries, episode bookkeeping — bit-identical under a
shared seed stream), auto-reset correctness at episode boundaries
(terminal transitions carry done=1, truncation bootstraps with done=0,
and next_image never leaks the post-reset scene — bit-identical Bellman
targets vs the scalar collector path), the VectorActor's fixed-chunk
queue feeding and one-acting-executable-per-bucket ledger (hot param
refresh never recompiles), the vectorized `evaluate_grasp_policy`'s
seeded determinism vs the scalar loop, and the CLI-subprocess smoke for
`run_qtopt_replay --vector-actors`: >= 30% eval TD reduction through
the full vector-actor + megastep stack plus the actor-throughput
block's vector-vs-threaded speedup at the same policy and env count.
"""

import json
import os
import time

import numpy as np
import optax
import pytest

from tensor2robot_tpu.replay.actor import ActorFleet, VectorActor
from tensor2robot_tpu.replay.bellman import BellmanUpdater
from tensor2robot_tpu.replay.ingest import TransitionQueue
from tensor2robot_tpu.replay.smoke import TinyQCriticModel
from tensor2robot_tpu.research.qtopt.synthetic_grasping import (
    GraspRetryEnv, VectorGraspEnv, evaluate_grasp_policy)

IMG = 12  # tiny scenes for the structural tests


def _seed_stream(base):
  """The CollectorWorker._scene_seed formula as a closure: one
  monotonic counter, seed = base * 1_000_003 + counter."""
  counter = [0]

  def seed_fn():
    seed = base * 1_000_003 + counter[0]
    counter[0] += 1
    return seed

  return seed_fn


class TestVectorGraspEnvEquivalence:

  @pytest.mark.parametrize("seed", [0, 3])
  def test_lockstep_bit_identical_to_scalar_envs(self, seed):
    """The tentpole property: with the same seed stream and the same
    action sequence, EVERY observable of the vector env — scene images
    and targets at every step, rewards/dones/truncations, auto-reset
    boundaries, episode/success counts — matches N scalar envs driven
    in env order, bit for bit."""
    n, max_attempts = 4, 3
    vec_seeds, scalar_seeds = _seed_stream(seed), _seed_stream(seed)
    venv = VectorGraspEnv(n, image_size=IMG, max_attempts=max_attempts,
                          radius=0.4)
    venv.reset([vec_seeds() for _ in range(n)])
    senvs = [GraspRetryEnv(image_size=IMG, max_attempts=max_attempts,
                           radius=0.4) for _ in range(n)]
    for env in senvs:
      env.reset(scalar_seeds())

    rng = np.random.default_rng(seed + 100)
    episodes = successes = 0
    for _ in range(20):
      np.testing.assert_array_equal(
          venv.images, np.stack([env.image for env in senvs]))
      np.testing.assert_array_equal(
          venv.targets, np.stack([env.target for env in senvs]))
      actions = rng.uniform(-1, 1, (n, 4)).astype(np.float32)
      rewards, dones, truncated = venv.step(actions,
                                            seed_fn=vec_seeds)
      for i, env in enumerate(senvs):
        reward, done, trunc = env.step(actions[i])
        assert rewards[i] == reward
        assert dones[i] == float(done)
        assert truncated[i] == trunc
        if done or trunc:
          episodes += 1
          successes += int(done)
          env.reset(scalar_seeds())
    assert venv.episodes == episodes and venv.successes == successes
    assert episodes > 0  # the property actually crossed boundaries

  def test_reset_and_step_validate_fleet_width(self):
    venv = VectorGraspEnv(3, image_size=IMG)
    with pytest.raises(ValueError, match="3 seeds"):
      venv.reset([0, 1])
    venv.reset([0, 1, 2])
    with pytest.raises(ValueError, match="3 actions"):
      venv.step(np.zeros((2, 4), np.float32))


class TestAutoResetBoundaries:
  """ISSUE 5 satellite: episode-boundary transitions are leak-free."""

  def _action(self, target, hit):
    action = np.full((4,), 0.9, np.float32)
    # Hit: the oracle pose. Miss: the opposite-side corner — per-dim
    # distance >= 0.95 whatever the target, far outside any radius.
    action[:2] = target if hit else np.where(target >= 0, -0.95, 0.95)
    return action

  def _vector_transitions(self, plan, seed=5):
    """Drives a 1-env VectorGraspEnv through the actor's transition
    recipe (pre-step scene snapshot, next_image == scene)."""
    seeds = _seed_stream(seed)
    venv = VectorGraspEnv(1, image_size=IMG, max_attempts=3, radius=0.4)
    venv.reset([seeds()])
    queue = TransitionQueue(256)
    scene_ids = []
    for hit in plan:
      scene = venv.images.copy()
      action = self._action(venv.targets[0], hit)[None]
      rewards, dones, _ = venv.step(action, seed_fn=seeds)
      scene_ids.append(scene.tobytes())
      queue.put_batch({"image": scene, "action": action,
                       "reward": rewards, "done": dones,
                       "next_image": scene})
    return queue.drain_batch(), scene_ids, venv

  def _scalar_transitions(self, plan, seed=5):
    """The CollectorWorker episode recipe over the same plan."""
    seeds = _seed_stream(seed)
    env = GraspRetryEnv(image_size=IMG, max_attempts=3, radius=0.4)
    env.reset(seeds())
    queue = TransitionQueue(256)
    record = {"actions": [], "rewards": [], "dones": []}
    for hit in plan:
      scene = env.image
      action = self._action(env.target, hit)
      reward, done, truncated = env.step(action)
      record["actions"].append(action)
      record["rewards"].append(reward)
      record["dones"].append(float(done))
      if done or truncated:
        t = len(record["actions"])
        queue.put_episode({
            "images": np.stack([scene] * (t + 1)),
            "actions": np.stack(record["actions"]),
            "rewards": np.asarray(record["rewards"], np.float32),
            "dones": np.asarray(record["dones"], np.float32),
        })
        record = {"actions": [], "rewards": [], "dones": []}
        env.reset(seeds())
    return queue.drain_batch()

  # A plan crossing every boundary kind: success mid-budget (reset),
  # three misses (truncation + reset), then a fresh-scene success.
  PLAN = (False, True, False, False, False, True)

  def test_terminal_done_flags_and_no_bootstrap_leak(self):
    batch, scene_ids, _ = self._vector_transitions(self.PLAN)
    # Step 1 is a success: done=1 (value terminates). Steps 2-4 are the
    # full failed budget: truncation is NOT done (bootstraps through).
    np.testing.assert_array_equal(batch["done"],
                                  [0.0, 1.0, 0.0, 0.0, 0.0, 1.0])
    np.testing.assert_array_equal(batch["reward"], batch["done"])
    # next_image NEVER shows the post-reset scene: every transition's
    # next_image is its own episode's (static) scene.
    np.testing.assert_array_equal(batch["next_image"], batch["image"])
    # The resets actually happened: scene changes exactly after the
    # success (step 1) and after the truncation (step 4).
    changes = [scene_ids[i] != scene_ids[i + 1]
               for i in range(len(scene_ids) - 1)]
    assert changes == [False, True, False, False, True]

  def test_bit_identical_transitions_and_bellman_targets(self):
    """The vector actor path and the scalar collector path emit the
    SAME transitions for the same seed stream and action plan, so the
    Bellman targets computed from them are bit-identical — the scalar
    path's learning behavior carries over unchanged."""
    vector_batch, _, _ = self._vector_transitions(self.PLAN)
    scalar_batch = self._scalar_transitions(self.PLAN)
    for key in ("image", "action", "reward", "done", "next_image"):
      np.testing.assert_array_equal(vector_batch[key],
                                    scalar_batch[key], err_msg=key)
    import jax
    model = TinyQCriticModel(image_size=IMG,
                             optimizer_fn=lambda: optax.adam(1e-3))
    variables = jax.device_get(
        model.init_variables(jax.random.key(0), batch_size=2))
    updater = BellmanUpdater(model, variables, action_size=4,
                             gamma=0.8, num_samples=8, num_elites=2,
                             iterations=2, seed=0)
    seeds = np.arange(len(self.PLAN), dtype=np.uint32)
    vector_targets, _ = updater.compute_targets(vector_batch,
                                                seeds=seeds)
    scalar_targets, _ = updater.compute_targets(scalar_batch,
                                                seeds=seeds)
    np.testing.assert_array_equal(vector_targets, scalar_targets)
    # Terminal targets ARE the reward (bootstrap masked); truncated
    # steps bootstrap (target = gamma * q_next > 0 under a fresh net).
    np.testing.assert_allclose(vector_targets[[1, 5]], [1.0, 1.0],
                               atol=1e-6)
    assert np.all(vector_targets[[0, 2, 3, 4]] > 0.0)


class _CountingPolicy:
  """Batched stub policy recording every request batch shape."""

  def __init__(self, action_size=4):
    self.calls = []
    self._action_size = action_size

  def __call__(self, images):
    batch = np.stack([np.asarray(image) for image in images])
    self.calls.append(batch.shape[0])
    return np.zeros((batch.shape[0], self._action_size), np.float32)


class TestVectorActor:

  def test_fixed_chunk_puts_and_step_accounting(self):
    policy = _CountingPolicy()
    queue = TransitionQueue(4096)
    actor = VectorActor(policy, queue, IMG, num_envs=8,
                        max_attempts=3, seed=0, grasp_radius=0.4)
    actor._env.reset([actor._scene_seed() for _ in range(8)])
    for _ in range(6):
      actor.step_once()
    # One fleet-wide policy call and ONE fixed-size chunk per step.
    assert policy.calls == [8] * 6
    assert actor.env_steps == 48
    assert queue.stats()["enqueued"] == 48
    batch = queue.drain_batch(max_items=8)
    assert batch["image"].shape == (8, IMG, IMG, 3)
    assert batch["done"].dtype == np.float32
    stats = queue.stats()
    assert stats["enqueued"] == (stats["dropped"] + stats["dequeued"]
                                 + stats["pending"])

  def test_one_acting_executable_hot_refresh_never_recompiles(self):
    """The acting bucket compiles ONCE; a param hot-reload (the loop's
    refresh_every path) swaps predictor variables without adding an
    executable — the same never-recompile discipline the megastep
    holds for its target net."""
    import jax
    from tensor2robot_tpu.replay.loop import _HotReloadPredictor
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    model = TinyQCriticModel(image_size=IMG,
                             optimizer_fn=lambda: optax.adam(1e-3))
    variables = jax.device_get(
        model.init_variables(jax.random.key(0), batch_size=2))
    predictor = _HotReloadPredictor(model, variables)
    policy = CEMFleetPolicy(predictor, action_size=4, num_samples=8,
                            num_elites=2, iterations=2, seed=7,
                            ladder=BucketLadder((4,)))
    queue = TransitionQueue(4096)
    actor = VectorActor(policy, queue, IMG, num_envs=4,
                        max_attempts=3, seed=0, grasp_radius=0.4)
    actor._env.reset([actor._scene_seed() for _ in range(4)])
    for _ in range(2):
      actor.step_once()
    bumped = jax.tree_util.tree_map(lambda x: x + 0.05, variables)
    predictor.update(bumped)  # the hot param refresh
    for _ in range(2):
      actor.step_once()
    assert policy.compile_counts == {4: 1}
    assert actor.episodes >= 0 and queue.stats()["enqueued"] == 16

  def test_fleet_splits_envs_and_aggregates(self):
    policy = _CountingPolicy()
    queue = TransitionQueue(4096)
    fleet = ActorFleet(policy, queue, IMG, total_envs=8, num_actors=2,
                       max_attempts=3, seed=0, grasp_radius=0.4)
    assert [actor.num_envs for actor in fleet.actors] == [4, 4]
    with pytest.raises(ValueError, match="split evenly"):
      ActorFleet(policy, queue, IMG, total_envs=7, num_actors=2)


class TestEvaluateVectorized:

  def test_same_seed_same_numbers_as_scalar_loop(self):
    """ISSUE 5 satellite: the vectorized evaluation returns THE SAME
    success rate (and mean distance) as the per-scene Python loop for
    the same seed — scenes come from the same sample_scenes call and
    the reductions match bit for bit."""

    def scalar_policy(image):
      mean = image.mean()
      return np.array([np.cos(mean), np.sin(mean), 0.0, 0.0],
                      np.float32)

    def batch_policy(images):
      means = images.mean(axis=(1, 2, 3))
      return np.stack([np.cos(means), np.sin(means),
                       np.zeros_like(means), np.zeros_like(means)], -1)

    kwargs = dict(num_scenes=32, image_size=IMG, seed=11,
                  num_distractors=0, occlusion=False)
    scalar = evaluate_grasp_policy(scalar_policy, **kwargs)
    vector = evaluate_grasp_policy(batch_policy, vectorized=True,
                                   **kwargs)
    assert scalar == vector
    # And a different seed actually changes the measurement (the
    # determinism assert above is not vacuous).
    other = evaluate_grasp_policy(batch_policy, vectorized=True,
                                  **dict(kwargs, seed=12))
    assert other != vector


@pytest.fixture(scope="module")
def vector_smoke_results(tmp_path_factory):
  """ONE vector-actor smoke shared by the acceptance assertions — the
  CLI in a subprocess under the ARTIFACT environment (plain
  single-device CPU backend, same rationale as the device-resident
  smoke fixture: the harness's 8-virtual-device mesh measures
  virtualization, not the batching). Protocol = REPLAY_SMOKE_r08.json's
  minus the learner_throughput block (already re-proved every PR by
  tests/test_device_replay.py; skipping it keeps tier-1 inside its
  runtime budget)."""
  import subprocess
  import sys
  tmp = tmp_path_factory.mktemp("vector_actor_smoke")
  logdir = str(tmp / "logs")
  out = tmp / "smoke.json"
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env["JAX_PLATFORMS"] = "cpu"
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
  res = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.run_qtopt_replay",
       "--smoke", "--device-resident", "--vector-actors",
       "--no-learner-bench", "--steps", "300",
       "--logdir", logdir, "--out", str(out)],
      capture_output=True, text=True, timeout=480, env=env, cwd=root)
  assert res.returncode == 0, res.stderr[-2000:]
  lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
  assert len(lines) == 1, res.stdout  # the ONE-JSON-line contract
  return json.loads(lines[0])


class TestVectorActorSmokeCLI:
  """ISSUE 5 acceptance: the vector-actor loop holds the >= 30% eval TD
  bar end to end, the ledger shows exactly ONE acting executable per
  bucket (param refresh never recompiles), and the actor-throughput
  block reports the vector-vs-threaded speedup at the same policy and
  env count plus the acting/learning overlap fraction."""

  def test_td_reduction_still_meets_bar(self, vector_smoke_results):
    results = vector_smoke_results
    assert results["vector_actors"] is True
    assert results["device_resident"] is True
    assert results["eval_td_reduction"] >= 0.30, results["eval_history"]

  def test_one_acting_executable_per_bucket(self, vector_smoke_results):
    ledger = vector_smoke_results["compile_counts"]
    buckets = [key for key in ledger if key.startswith("cem_bucket_")]
    assert len(buckets) == 1, ledger  # the pinned actor-batch bucket
    assert ledger["megastep"] == 1
    assert all(value == 1 for value in ledger.values()), ledger
    # >= 10 hot refreshes happened against that single executable.
    assert vector_smoke_results["param_refreshes"] >= 10

  def test_collection_actually_vectorized(self, vector_smoke_results):
    results = vector_smoke_results
    assert results["env_steps_collected"] > 0
    assert results["episodes_collected"] > 50
    stats = results["queue"]
    assert stats["enqueued"] == (stats["dropped"] + stats["dequeued"]
                                 + stats["pending"])

  def test_actor_throughput_block(self, vector_smoke_results):
    """The committed artifact (REPLAY_SMOKE_r08.json) carries the
    quiet-run medians and the >= 3x acceptance bar; under CI contention
    timing asserts flake (the serving smoke's known failure mode), so
    the in-CI bar is conservative — contention hits the GIL-bound
    scalar path harder, so the ratio only ever looks BETTER under
    load, but the floor stays defensive."""
    block = vector_smoke_results["actor_throughput"]
    for path in ("scalar_threads", "vector_actor"):
      for field in ("env_steps_per_sec", "transitions_per_sec"):
        spread = block[path][field]
        assert set(spread) == {"median", "min", "max", "trials"}
    assert block["speedup"]["max"] >= 2.5, block["speedup"]
    assert block["speedup"]["median"] >= 1.5, block["speedup"]
    overlap = block["overlap"]["acting_learning_overlap_fraction"]
    assert overlap["median"] >= 0.5, block["overlap"]
    counts = block["compile_counts"]
    assert counts["megastep"] == 1
    assert sum(1 for key in counts if key.startswith("scalar_cem")) == 1
    assert sum(1 for key in counts if key.startswith("vector_cem")) == 1
    assert all(value == 1 for value in counts.values()), counts


def _hold_until_probe_admitted(chunks, spool_dir, actor_id, resume_seq,
                               hold_after, timeout_s=180.0):
  """The learner's chunk stream, held after `hold_after` chunks until the
  dead actor's probe incarnation has had a chunk admitted.

  Left alone, the learner finishes on the survivor's stream in about a
  second, and whether the probe is back by then is the scheduler's to
  say. Held, it is not: the bounded queue fills, admission stops within
  a dozen chunks, and the learner takes a further chunk only once the
  probe's first chunk (`resume_seq`) is on disk, to make the room that
  admits it. Everything waited on is a file the transport writes; the
  timeout only bounds a run in which the probe never comes back."""
  from tensor2robot_tpu.parallel import sebulba
  probe_chunk = sebulba.chunk_path(spool_dir, actor_id, resume_seq)
  acks_path = os.path.join(spool_dir, sebulba.ACKS_FILE)

  def admitted():
    acks = sebulba._read_json(acks_path) or {}
    return int(acks.get(str(actor_id), 0)) > resume_seq

  def wait(condition, seconds):
    deadline = time.monotonic() + seconds
    while not condition():
      if time.monotonic() > deadline:
        return False
      time.sleep(0.01)
    return True

  for taken, chunk in enumerate(chunks):
    yield chunk
    if taken + 1 < hold_after or admitted():
      continue
    if not wait(lambda: os.path.exists(probe_chunk), timeout_s):
      raise TimeoutError(f"actor{actor_id}'s probe never landed chunk "
                         f"{resume_seq} in {timeout_s}s")
    # One more chunk leaves the queue per pass (the `yield` above); give
    # the ingest thread a moment to admit into the room before the next.
    wait(admitted, 1.0)


class TestActorProcessCrashRecovery:
  """ISSUE 20 satellite: a Sebulba actor PROCESS dies mid-stream; the
  learner-side watchdog flags the silent spool, the breaker walks
  quarantine -> half-open probe -> reinstate, and the learner trains
  through on the survivor at fixed shapes with zero recompiles.

  No assertion here is a deadline: the learner is held on the
  transport's own files until the probe is back (above), and the stall
  deadline (5 s against a 0.05 s chunk cadence) is wide enough that a
  live actor starved by six test workers is not taken for a dead one
  (at 2 s one was, with the cores three times oversubscribed)."""

  _DIE_AFTER = 3

  @pytest.fixture(scope="class")
  def crash_run(self, tmp_path_factory):
    from tensor2robot_tpu.parallel import sebulba
    config = sebulba.SebulbaConfig(
        seed=11, num_actors=2, envs_per_actor=8, capacity=64,
        batch_size=8, inner_steps=1, chunks_per_megastep=2,
        # 40 chunks: the manifest is the first 40 ADMITTED, and the
        # probe's first chunk is admitted 24th at the latest (4 taken +
        # 12 the queue holds + at most 2 a pass for actor0's chunks 0-3,
        # should the survivor have filled the queue before actor0 began).
        num_megasteps=20, mesh_devices=2, queue_capacity=96,
        synthetic_actors=True, actor_max_chunks=512,
        actor_deadline_s=5.0, quarantine_s=0.5,
        actor_step_sleep_s=0.05)
    workdir = str(tmp_path_factory.mktemp("sebulba_crash"))
    spool_dir = os.path.join(workdir, "spool")
    drive = sebulba.SebulbaLearner.drive

    def held_drive(learner, host_chunks, **kwargs):
      # The probe resumes at the dead incarnation's last landed chunk + 1.
      return drive(learner, _hold_until_probe_admitted(
          host_chunks, spool_dir, actor_id=0, resume_seq=self._DIE_AFTER,
          hold_after=self._DIE_AFTER + 1), **kwargs)

    with pytest.MonkeyPatch.context() as patch:
      patch.setattr(sebulba.SebulbaLearner, "drive", held_drive)
      live = sebulba.run_live(config, workdir,
                              die_after={0: self._DIE_AFTER},
                              timeout_s=240.0)
    return config, live

  def test_two_real_processes_and_rc3_crash(self, crash_run):
    _, live = crash_run
    quarantine = next(entry for entry in live["supervisor"]["timeline"]
                      if entry["event"] == "quarantine")
    assert quarantine["actor"] == 0
    assert quarantine["rc"] == 3  # the injected os._exit(3), not a kill
    spawn_pids = {entry["pid"] for entry in live["supervisor"]["timeline"]
                  if entry["event"] == "spawn"}
    assert len(spawn_pids) == 2 and os.getpid() not in spawn_pids

  def test_watchdog_flagged_the_silent_actor(self, crash_run):
    _, live = crash_run
    stalls = [event for event in live["watchdog_events"]
              if event["event"] == "watchdog_stall"]
    assert any(event["component"].startswith("sebulba/actor0")
               for event in stalls), live["watchdog_events"]
    for event in stalls:  # PR 9 typed stall schema rides along
      assert {"component", "stalled_for_s", "deadline_s",
              "beats"} <= set(event)

  def test_quarantine_probe_reinstate_in_order(self, crash_run):
    _, live = crash_run
    events0 = [entry["event"] for entry in live["supervisor"]["timeline"]
               if entry["actor"] == 0 and entry["event"] != "spawn"]
    assert events0 == ["quarantine", "probe", "reinstate"], events0
    breaker0 = [entry["state"] for entry
                in live["supervisor"]["breaker_events"]["0"]]
    assert breaker0 == ["open", "half_open", "closed"], breaker0

  def test_probe_resumes_seq_and_refeeds_learner(self, crash_run):
    config, live = crash_run
    probe = next(entry for entry in live["supervisor"]["timeline"]
                 if entry["event"] == "probe")
    # never overwrites landed chunks
    assert probe["start_seq"] >= self._DIE_AFTER
    consumed0 = [entry["seq"] for entry in live["manifest"]
                 if entry["actor"] == 0]
    # post-death chunk ingested
    assert max(consumed0) >= self._DIE_AFTER, consumed0
    assert any(entry["actor"] == 1 for entry in live["manifest"])

  def test_overlap_instruments_and_one_merged_fleet_view(self, crash_run):
    """What only the removed generator's CEM-actor phase looked at: the
    acting/learning overlap instruments are present and sane (magnitudes
    are the scheduler's), and obs/aggregate merges the registry snapshots
    the actor processes and the learner each exported under their own
    host label into one view."""
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    _, live = crash_run
    overlap = live["overlap"]
    assert 0.0 < overlap["overlap_fraction"] <= 1.0, overlap
    assert overlap["learner_stall_s"] >= 0.0
    assert overlap["learn_busy_s"] > 0.0
    assert overlap["queue_occupancy"]["samples"] > 0
    fleet = aggregate_logdir(live["obs_logdir"], merged_trace=False)
    hosts = {key.split(":")[0]
             for key in fleet["registry"]["gauges_per_host"]}
    assert {"actor0", "actor1", "learner"} <= hosts, hosts

  def test_learner_trained_through_at_fixed_shapes(self, crash_run):
    config, live = crash_run
    assert live["drive"]["megasteps"] == config.num_megasteps
    assert live["compile_counts"] == {"device_extend": 1, "megastep": 1}
    assert live["queue"]["dropped"] == 0

"""Fused Anakin loop + JAX-native grasping env (ISSUE 6 acceptance).

Covers the tentpole contracts chiplessly: seeded-parity property tests
pinning `JaxGraspEnv` BIT-IDENTICAL to the numpy semantics oracle
(`VectorGraspEnv`) over matched seed streams — observations, targets,
outcomes, episode bookkeeping, >= 3 auto-reset boundaries, and the
truncation-bootstrap boundary from the r08 tests — plus the device
rasterizer's exact-match corpus; the factored CEM score's equivalence
to the tiled serving contract; the device ring's extend running inside
a jitted scan with donated state (no recompile, no silent copy); the
AnakinLoop's one-executable ledger, in-program min-fill gating, and
determinism; and the CLI-subprocess smoke for `run_qtopt_replay
--anakin`: >= 30% eval TD reduction end-to-end through the fused loop
plus the anakin-throughput block (fused vs numpy-fleet env steps/s at
the same env count and policy, host-blocked fraction ~0).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensor2robot_tpu.replay.device_buffer import DeviceReplayBuffer
from tensor2robot_tpu.replay.loop import transition_spec
from tensor2robot_tpu.replay.smoke import TinyQCriticModel
from tensor2robot_tpu.research.qtopt import jax_grasping as jg
from tensor2robot_tpu.research.qtopt.synthetic_grasping import (
    GraspRetryEnv, VectorGraspEnv)

IMG = 12  # tiny scenes for the structural tests


def _seed_stream(base):
  """CollectorWorker._scene_seed as a closure (the oracle's stream)."""
  counter = [0]

  def seed_fn():
    seed = base * 1_000_003 + counter[0]
    counter[0] += 1
    return seed

  return seed_fn


class TestJaxGraspEnvParity:
  """ISSUE 6 satellite: the JAX env vs the numpy semantics oracle."""

  @pytest.mark.parametrize("seed", [0, 3])
  def test_lockstep_bit_identical_to_vector_env(self, seed):
    """The tentpole property: with the bank built from the oracle's
    seed stream and the same action sequence, EVERY observable of the
    JAX env — images and targets at every step, rewards/dones/
    truncations, auto-reset boundaries, episode/success counts —
    matches the numpy `VectorGraspEnv` bit for bit."""
    n, max_attempts = 4, 3
    bank = jg.make_scene_bank(96, image_size=IMG, base_seed=seed)
    env = jg.JaxGraspEnv(n, image_size=IMG, max_attempts=max_attempts,
                         radius=0.4, bank=bank)
    state = env.init_state(jax.random.key(0))
    step = jax.jit(env.step_fn())
    venv = VectorGraspEnv(n, image_size=IMG, max_attempts=max_attempts,
                          radius=0.4)
    seeds = _seed_stream(seed)
    venv.reset([seeds() for _ in range(n)])
    rng = np.random.default_rng(seed + 100)
    boundaries = 0
    for t in range(20):
      np.testing.assert_array_equal(np.asarray(state.images),
                                    venv.images)
      np.testing.assert_array_equal(np.asarray(state.targets),
                                    venv.targets)
      actions = rng.uniform(-1, 1, (n, 4)).astype(np.float32)
      o_rewards, o_dones, o_trunc = venv.step(actions, seed_fn=seeds)
      state, (rewards, dones, trunc) = step(state, jnp.asarray(actions),
                                            jax.random.key(t))
      np.testing.assert_array_equal(np.asarray(rewards), o_rewards)
      np.testing.assert_array_equal(np.asarray(dones), o_dones)
      np.testing.assert_array_equal(np.asarray(trunc), o_trunc)
      boundaries += int((o_dones > 0).sum() + o_trunc.sum())
    assert int(state.episodes) == venv.episodes
    assert int(state.successes) == venv.successes
    assert boundaries >= 3  # the property actually crossed resets

  def test_truncation_bootstrap_boundary_transitions(self):
    """The r08 boundary case through the FUSED transition recipe: a
    success mid-budget (done=1, reset), a full failed budget
    (truncation: done=0, bootstraps, reset), then a fresh-scene
    success — transitions bit-identical to the vector actor's."""
    plan = (False, True, False, False, False, True)
    max_attempts = 3

    def hit_action(target, hit):
      action = np.full((1, 4), 0.9, np.float32)
      action[0, :2] = (target if hit
                       else np.where(target >= 0, -0.95, 0.95))
      return action

    # JAX env, the anakin recipe: obs snapshot, next_image == obs.
    bank = jg.make_scene_bank(64, image_size=IMG, base_seed=5)
    env = jg.JaxGraspEnv(1, image_size=IMG, max_attempts=max_attempts,
                         radius=0.4, bank=bank)
    state = env.init_state(jax.random.key(0))
    step = jax.jit(env.step_fn())
    jax_rows = []
    scene_ids = []
    for t, hit in enumerate(plan):
      obs = np.asarray(state.images)
      action = hit_action(np.asarray(state.targets)[0], hit)
      state, (rewards, dones, trunc) = step(state, jnp.asarray(action),
                                            jax.random.key(t))
      scene_ids.append(obs.tobytes())
      jax_rows.append((obs, action, np.asarray(rewards),
                       np.asarray(dones), np.asarray(trunc)))

    # Oracle env through the identical plan.
    seeds = _seed_stream(5)
    venv = VectorGraspEnv(1, image_size=IMG, max_attempts=max_attempts,
                          radius=0.4)
    venv.reset([seeds()])
    for (obs, action, rewards, dones, trunc) in jax_rows:
      np.testing.assert_array_equal(obs, venv.images)
      o_rewards, o_dones, o_trunc = venv.step(action, seed_fn=seeds)
      np.testing.assert_array_equal(rewards, o_rewards)
      np.testing.assert_array_equal(dones, o_dones)
      np.testing.assert_array_equal(trunc, o_trunc)
    dones = np.concatenate([row[3] for row in jax_rows])
    truncs = np.concatenate([row[4] for row in jax_rows])
    np.testing.assert_array_equal(dones, [0., 1., 0., 0., 0., 1.])
    # Truncation flags ONLY the failed budget exhaustion (step 4).
    np.testing.assert_array_equal(truncs.astype(np.float32),
                                  [0., 0., 0., 0., 1., 0.])
    # Resets actually happened: scene changes exactly after the
    # success (step 1) and after the truncation (step 4).
    changes = [scene_ids[i] != scene_ids[i + 1]
               for i in range(len(scene_ids) - 1)]
    assert changes == [False, True, False, False, True]

  def test_bank_rows_match_scalar_resets(self):
    """Bank row j is bit-identical to GraspRetryEnv.reset(seed_j) for
    the stream's j-th seed (the scene-assignment parity anchor)."""
    bank = jg.make_scene_bank(6, image_size=IMG, base_seed=7)
    seeds = _seed_stream(7)
    env = GraspRetryEnv(image_size=IMG, max_attempts=3, radius=0.4)
    for j in range(6):
      env.reset(seeds())
      np.testing.assert_array_equal(np.asarray(bank.images[j]),
                                    env.image)
      np.testing.assert_array_equal(np.asarray(bank.targets[j]),
                                    env.target)

  def test_device_rasterizer_bit_exact_on_oracle_corpus(self):
    """`render_scenes` (the procedural mode's observation source)
    reproduces the oracle renderer's uint8 images EXACTLY on a
    128-scene corpus — the compensated-arithmetic disc decision vs
    pose_env's float64 rasterization."""
    bank = jg.make_scene_bank(128, image_size=IMG, base_seed=11)
    env = jg.JaxGraspEnv(4, image_size=IMG, bank=None)
    rendered = np.asarray(jax.jit(env.render_scenes)(bank.targets))
    np.testing.assert_array_equal(rendered, np.asarray(bank.images))

  def test_procedural_mode_runs_without_bank(self):
    """Per-env PRNG resets + on-device rendering (the domain-
    randomization substrate): distinct scenes, deterministic in key."""
    env = jg.JaxGraspEnv(4, image_size=IMG, max_attempts=2, radius=0.4)
    state = env.init_state(jax.random.key(1))
    assert not np.array_equal(np.asarray(state.images[0]),
                              np.asarray(state.images[1]))
    state2 = env.init_state(jax.random.key(1))
    np.testing.assert_array_equal(np.asarray(state.images),
                                  np.asarray(state2.images))
    step = jax.jit(env.step_fn())
    # Force terminals (hit every target): resets draw FRESH scenes.
    actions = np.zeros((4, 4), np.float32)
    actions[:, :2] = np.asarray(state.targets)
    before = np.asarray(state.images).copy()
    state, (rewards, _, _) = step(state, jnp.asarray(actions),
                                  jax.random.key(9))
    assert np.all(np.asarray(rewards) == 1.0)
    assert not np.array_equal(np.asarray(state.images), before)
    assert int(state.episodes) == 4 and int(state.successes) == 4


class TestFactoredScore:
  """The factored CEM contract: identical Q, image tower hoisted."""

  def _model(self):
    return TinyQCriticModel(image_size=IMG,
                            optimizer_fn=lambda: optax.adam(1e-3))

  def test_factored_composes_to_predict_fn(self):
    model = self._model()
    variables = jax.device_get(
        model.init_variables(jax.random.key(0), batch_size=2))
    rng = np.random.default_rng(2)
    features = {
        "image": rng.integers(0, 255, (6, IMG, IMG, 3), np.uint8),
        "action": rng.uniform(-1, 1, (6, 4)).astype(np.float32),
    }
    encode_fn, q_from_code_fn = model.factored_cem_fns()
    code = encode_fn(variables, {"image": features["image"]})
    split = q_from_code_fn(variables, {"image": code,
                                       "action": features["action"]})
    whole = model.predict_fn(variables, features)
    np.testing.assert_allclose(np.asarray(split["q_predicted"]),
                               np.asarray(whole["q_predicted"]),
                               rtol=1e-6)

  def test_factored_bellman_targets_match_tiled(self):
    """make_bellman_targets_fn(factored=True) computes the SAME
    targets as the tiled serving-score recipe — the score contract
    holds with the image tower hoisted out of the sample loop."""
    from tensor2robot_tpu.replay.bellman import make_bellman_targets_fn
    model = self._model()
    variables = jax.device_get(
        model.init_variables(jax.random.key(0), batch_size=2))
    rng = np.random.default_rng(3)
    next_images = jnp.asarray(
        rng.integers(0, 255, (6, IMG, IMG, 3), np.uint8))
    rewards = jnp.asarray(rng.random(6, np.float32))
    dones = jnp.asarray((rng.random(6) < 0.5).astype(np.float32))
    keys = jax.random.split(jax.random.key(4), 6)
    kwargs = dict(action_size=4, gamma=0.8, num_samples=8,
                  num_elites=2, iterations=2, clip_targets=True)
    tiled, _ = jax.jit(make_bellman_targets_fn(model, **kwargs))(
        variables, next_images, rewards, dones, keys)
    factored, _ = jax.jit(
        make_bellman_targets_fn(model, factored=True, **kwargs))(
            variables, next_images, rewards, dones, keys)
    np.testing.assert_allclose(np.asarray(factored), np.asarray(tiled),
                               atol=1e-6)

  def test_unfactored_model_falls_back(self):
    """Models without a factored form return None (generic tiled path
    stays the contract) and factored=True refuses loudly."""
    import flax.linen as nn
    from tensor2robot_tpu.replay.bellman import make_bellman_targets_fn

    class WholeQ(nn.Module):
      """Frame and action mixed from the first layer on: no pair."""

      @nn.compact
      def __call__(self, features, mode):
        image = features["image"].astype(jnp.float32) / 255.0
        x = jnp.concatenate([image.reshape((image.shape[0], -1)),
                             features["action"]], axis=-1)
        return {"q_predicted": nn.Dense(1)(x)[:, 0]}

    class WholeQModel(TinyQCriticModel):

      def build_module(self):
        return WholeQ()

    model = WholeQModel(image_size=IMG)
    assert model.factored_cem_fns() is None
    with pytest.raises(ValueError, match="no factored CEM form"):
      make_bellman_targets_fn(model, 4, 0.9, 8, 2, 2, True,
                              factored=True)


class TestExtendInsideJittedScan:
  """ISSUE 6 satellite: DeviceReplayBuffer.extend inside a jitted scan
  with donated state — no recompile, no silent copy."""

  def _buffer(self, capacity=32, chunk=4):
    return DeviceReplayBuffer(
        transition_spec(IMG, 4), capacity=capacity, sample_batch_size=8,
        seed=0, prioritized=True, ingest_chunk=chunk)

  def _chunks(self, steps, chunk, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": rng.integers(0, 255, (steps, chunk, IMG, IMG, 3),
                              np.uint8),
        "action": rng.uniform(-1, 1, (steps, chunk, 4)).astype(
            np.float32),
        "reward": rng.random((steps, chunk), dtype=np.float32),
        "done": (rng.random((steps, chunk)) < 0.5).astype(np.float32),
        "next_image": rng.integers(0, 255, (steps, chunk, IMG, IMG, 3),
                                   np.uint8),
    }

  def test_scan_extend_donates_and_matches_host_path(self):
    steps, chunk = 6, 4
    buf = self._buffer(chunk=chunk)
    extend = buf.extend_fn()

    def scan_extend(state, stacked):
      return jax.lax.scan(
          lambda s, batch: (extend(s, batch), None), state, stacked)[0]

    stacked = {k: jnp.asarray(v) for k, v in
               self._chunks(steps, chunk).items()}
    # ONE AOT executable (the repo's ledger idiom) with the state
    # donated — the megastep/anakin compilation shape.
    exec_ = jax.jit(scan_extend, donate_argnums=(0,)).lower(
        buf.state, stacked).compile()
    state_in = buf.state
    in_buffers = jax.tree_util.tree_leaves(state_in.storage)
    state_out = exec_(state_in, stacked)
    # Donation actually happened: the input storage buffers are DEAD
    # (updated in place), not silently copied into fresh allocations.
    assert all(buffer.is_deleted() for buffer in in_buffers)
    # No recompile channel exists: AOT rejects shape drift outright.
    with pytest.raises(Exception):
      exec_(state_out, {k: v[:, :2] for k, v in stacked.items()})

    # Contents: bit-identical to the host-facing chunked extend path.
    host = self._buffer(chunk=chunk)
    chunks = self._chunks(steps, chunk)
    for t in range(steps):
      host.extend({k: v[t] for k, v in chunks.items()})
    assert host.compile_counts["device_extend"] == 1
    for key in state_out.storage:
      np.testing.assert_array_equal(
          np.asarray(state_out.storage[key]),
          np.asarray(host.state.storage[key]), err_msg=key)
    assert int(state_out.append_count) == steps * chunk
    np.testing.assert_array_equal(np.asarray(state_out.tree),
                                  np.asarray(host.state.tree))


class _AnakinSetup:

  def build(self, num_envs=4, inner_steps=8, train_every=2,
            min_fill=0, seed=0, factored=True, num_devices=1,
            capacity=64, batch=8, zero1=None):
    """Builds the fused-loop quartet on an EXPLICIT num_devices dp
    mesh. The default (1 device) is the oracle configuration the
    structural tests pin; the sharded-parity suite passes
    num_devices=8 (the harness's full virtual mesh) with zero1
    defaulting to num_devices > 1 — the production pod shape."""
    from tensor2robot_tpu.export import export_utils
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.replay.anakin import AnakinLoop
    from tensor2robot_tpu.train.trainer import Trainer
    model = TinyQCriticModel(image_size=IMG,
                             optimizer_fn=lambda: optax.adam(1e-3))
    if not factored:
      model.factored_cem_fns = lambda: None  # generic tiled path
    mesh = mesh_lib.create_mesh({"data": num_devices},
                                devices=jax.devices()[:num_devices])
    if zero1 is None:
      zero1 = num_devices > 1
    trainer = Trainer(model, mesh=mesh, seed=seed,
                      shard_optimizer_state=zero1)
    state = trainer.create_train_state(batch_size=batch)
    variables = export_utils.fetch_variables_to_host(
        state.variables(use_ema=True))
    buf = DeviceReplayBuffer(
        transition_spec(IMG, 4), capacity=capacity,
        sample_batch_size=batch,
        seed=seed, prioritized=True, ingest_chunk=num_envs,
        mesh=trainer.mesh)
    bank = jg.make_scene_bank(64, image_size=IMG, base_seed=seed)
    env = jg.JaxGraspEnv(num_envs, image_size=IMG, max_attempts=3,
                         radius=0.4, bank=bank)
    loop = AnakinLoop(model, trainer, buf, env, action_size=4,
                      gamma=0.8, num_samples=4, num_elites=2,
                      iterations=2, inner_steps=inner_steps,
                      train_every=train_every, min_fill=min_fill,
                      seed=seed + 13)
    loop.refresh(variables, step=0)
    return state, loop, buf, variables


class TestAnakinLoop(_AnakinSetup):

  def test_one_executable_min_fill_gate_and_counters(self):
    # min_fill = 40: dispatch 1 collects 4 * 8 = 32 < 40 -> the
    # in-program lax.cond gate must hold ALL training back; dispatch 2
    # crosses the fill mid-scan and trains the gated remainder.
    state, loop, buf, variables = self.build(min_fill=40)
    state, metrics = loop.step(state)
    assert metrics["trained_steps"] == 0
    assert int(jax.device_get(state.step)) == 0
    assert buf.size == 32
    state, metrics = loop.step(state)
    assert metrics["trained_steps"] > 0
    assert loop.trained_steps == metrics["trained_steps"]
    assert int(jax.device_get(state.step)) == loop.trained_steps
    # Target refresh swaps arrays, never recompiles (megastep parity).
    bumped = jax.tree_util.tree_map(lambda x: x + 0.05, variables)
    loop.refresh(bumped, step=8)
    state, metrics = loop.step(state)
    assert loop.compile_counts == {"anakin_step": 1}
    assert buf.compile_counts == {}  # extend lives INSIDE the program
    assert loop.env_steps == 3 * 8 * 4
    assert loop.episodes > 0
    for value in metrics.values():
      assert np.isfinite(value)

  def test_deterministic_across_rebuilds(self):
    def metrics_stream(seed):
      state, loop, _, _ = self.build(seed=seed, min_fill=8)
      out = []
      for _ in range(2):
        state, metrics = loop.step(state)
        out.append(metrics)
      return out

    a, b = metrics_stream(0), metrics_stream(0)
    assert a == b
    assert metrics_stream(1) != a

  def test_tiled_fallback_compiles_and_trains(self):
    """A model with no factored form runs the generic serving-score
    path inside the same fused program."""
    state, loop, _, _ = self.build(factored=False, min_fill=8)
    state, metrics = loop.step(state)
    assert metrics["trained_steps"] > 0
    assert loop.compile_counts == {"anakin_step": 1}

  def test_validates_chunk_and_cadence(self):
    from tensor2robot_tpu.replay.anakin import AnakinLoop
    state, loop, buf, _ = self.build()
    env = loop._env
    with pytest.raises(ValueError, match="ingest_chunk"):
      AnakinLoop(loop._model, loop._trainer,
                 DeviceReplayBuffer(transition_spec(IMG, 4), 64, 8,
                                    ingest_chunk=8),
                 env, inner_steps=8, train_every=2)
    with pytest.raises(ValueError, match="multiple"):
      AnakinLoop(loop._model, loop._trainer, buf, env,
                 inner_steps=8, train_every=3)


class TestShardedAnakinParity(_AnakinSetup):
  """ISSUE 7: the fused executable over the full 8-virtual-device dp
  mesh vs the 1-device semantics oracle, SAME seeds, same global
  stream (8 envs — one per shard at dp=8).

  The parity contract, documented where exactness ends:
  - BIT-IDENTICAL across mesh shapes: acting/exploration/env-reset/
    label randomness (one GLOBAL fold_in key stream; each device
    materializes its slice), scene assignment (replicated cursor), env
    stepping, ring contents, episode bookkeeping. Pinned below on a
    pre-training dispatch (min-fill gate held shut), where no
    cross-replica reduction exists.
  - TOLERANCE-BOUND once training fires: the gradient all-reduce (and
    mean-TD metrics) sum float32 partials in a different order on 8
    shards than on 1 device — float addition is non-associative, so
    exact parity is IMPOSSIBLE by construction there (the reference's
    CrossShardOptimizer had the same property). Measured divergence is
    ~1e-7 relative per dispatch on this suite; asserted at 1e-4
    relative over 3 dispatches as the documented loose bound.
  """

  def test_pretrain_stream_bit_identical_across_meshes(self):
    outs = {}
    for ndev in (1, 8):
      state, loop, buf, _ = self.build(
          num_envs=8, capacity=128, min_fill=10**6, num_devices=ndev)
      state, metrics = loop.step(state)
      assert metrics["trained_steps"] == 0  # the gate held: pure stream
      outs[ndev] = (
          {key: np.asarray(value)
           for key, value in buf.state.storage.items()},
          np.asarray(loop._env_state.images),
          np.asarray(loop._env_state.targets),
          loop.episodes, loop.successes)
    storage_1, images_1, targets_1, episodes_1, successes_1 = outs[1]
    storage_8, images_8, targets_8, episodes_8, successes_8 = outs[8]
    for key in storage_1:
      np.testing.assert_array_equal(storage_1[key], storage_8[key],
                                    err_msg=key)
    np.testing.assert_array_equal(images_1, images_8)
    np.testing.assert_array_equal(targets_1, targets_8)
    assert episodes_1 == episodes_8 and successes_1 == successes_8
    assert episodes_1 > 0  # the stream actually crossed resets

  def test_trained_trajectories_match_within_collective_tolerance(self):
    streams = {}
    for ndev in (1, 8):
      state, loop, buf, _ = self.build(
          num_envs=8, capacity=128, min_fill=8, num_devices=ndev)
      metrics_stream = []
      for _ in range(3):
        state, metrics = loop.step(state)
        metrics_stream.append(metrics)
      streams[ndev] = metrics_stream
      # Still exactly ONE fused executable on the pod mesh.
      assert loop.compile_counts == {"anakin_step": 1}
    for metrics_1, metrics_8 in zip(streams[1], streams[8]):
      assert metrics_1["trained_steps"] == metrics_8["trained_steps"]
      for key in ("loss", "td_error", "q_next", "staleness"):
        np.testing.assert_allclose(
            metrics_1[key], metrics_8[key], rtol=1e-4, atol=1e-6,
            err_msg=f"{key}: beyond collective-reduction tolerance")

  def test_sharded_placements_and_zero1(self):
    """The pod run actually shards: env fleet + ring storage split
    over the data axis, some optimizer-state leaf splits (ZeRO-1),
    params replicated."""
    from jax.sharding import PartitionSpec
    state, loop, buf, _ = self.build(
        num_envs=8, capacity=128, min_fill=8, num_devices=8)
    assert tuple(buf.state.storage["image"].sharding.spec) == ("data",)
    assert tuple(loop._env_state.images.sharding.spec) == ("data",)
    state, _ = loop.step(state)
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(leaf.sharding.is_fully_replicated for leaf in leaves)
    opt_specs = {tuple(leaf.sharding.spec)
                 for leaf in jax.tree_util.tree_leaves(state.opt_state)
                 if hasattr(leaf, "sharding")}
    assert any("data" in spec for spec in opt_specs), opt_specs

  def test_refuses_indivisible_fleet_and_batch(self):
    """Actionable divisibility errors name the nearest fix (the
    ring-sharding refusal discipline applied to fleet and batch)."""
    with pytest.raises(ValueError,
                       match="fleet width 4 .*Use a fleet of 8"):
      self.build(num_envs=4, capacity=128, num_devices=8)
    with pytest.raises(ValueError, match="sample batch 12 .*8 or 16"):
      self.build(num_envs=8, capacity=128, batch=12, num_devices=8)


@pytest.fixture(scope="module")
def anakin_smoke_results(tmp_path_factory):
  """ONE anakin smoke shared by the acceptance assertions — the CLI in
  a subprocess under the ARTIFACT environment (plain single-device CPU
  backend; same rationale as the device-resident and vector-actor
  smoke fixtures: the harness's 8-virtual-device mesh measures
  virtualization, not fusion). Protocol = REPLAY_SMOKE_r09.json's."""
  import subprocess
  import sys
  tmp = tmp_path_factory.mktemp("anakin_smoke")
  logdir = str(tmp / "logs")
  out = tmp / "smoke.json"
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env["JAX_PLATFORMS"] = "cpu"
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
  res = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.run_qtopt_replay",
       "--smoke", "--anakin", "--steps", "300",
       "--logdir", logdir, "--out", str(out)],
      capture_output=True, text=True, timeout=480, env=env, cwd=root)
  assert res.returncode == 0, res.stderr[-2000:]
  lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
  assert len(lines) == 1, res.stdout  # the ONE-JSON-line contract
  results = json.loads(lines[0])
  assert json.loads(out.read_text()) == results
  return results, logdir


class TestAnakinSmokeCLI:
  """ISSUE 6 acceptance: the fused loop holds the >= 30% eval TD bar
  end to end, the ledger shows exactly ONE anakin_step executable, and
  the anakin-throughput block reports the fused-vs-numpy-fleet env
  rate at the same env count and policy with host-blocked ~0."""

  def test_td_reduction_through_fused_loop(self, anakin_smoke_results):
    results, _ = anakin_smoke_results
    assert results["anakin"] is True
    assert results["device_resident"] is True
    assert results["eval_td_reduction"] >= 0.30, results["eval_history"]

  def test_ledger_exactly_one_anakin_executable(self,
                                                anakin_smoke_results):
    from tensor2robot_tpu.obs.ledger import check_compile_ledger
    results, _ = anakin_smoke_results
    ledger = results["compile_counts"]
    # The shared smoke helper (ISSUE 11 satellite): exactly-once
    # everywhere, and the fused program subsumes every hot-path
    # executable — no megastep, no host train step, no host-fed extend.
    check_compile_ledger(
        ledger, require=("anakin_step",),
        forbid=("megastep", "train_step", "device_extend"))
    assert not any(key.startswith("cem_bucket_") for key in ledger)

  def test_loop_collected_on_device(self, anakin_smoke_results):
    results, _ = anakin_smoke_results
    assert results["steps"] >= 300
    assert results["env_steps_collected"] > 0
    assert results["episodes_collected"] > 50
    assert 0 < results["collector_success_rate"] <= 1
    # No queue, no feeder: the host never touched a transition.
    stats = results["queue"]
    assert stats["enqueued"] == 0 and stats["dequeued"] == 0
    assert results["param_refreshes"] >= 10

  def test_anakin_throughput_block(self, anakin_smoke_results):
    """Block structure always; the >= 5x acceptance bar itself lives
    in the committed artifact (quiet-run medians) and is asserted at
    full strength only on >= 4-core hosts — on the 2-core CI box the
    floors below stay far above the noise floor (measured ~10x) while
    staying out of the flaky-under-contention class (the ROADMAP
    maintenance rule the r09 de-flake satellite applies repo-wide)."""
    results, _ = anakin_smoke_results
    block = results["anakin_throughput"]
    assert block["dtype"] == "float32"
    assert block["anakin"]["dtype"] == "float32"
    for path, field in (
        ("vector_fleet", "env_steps_per_sec"),
        ("vector_fleet", "collect_only_env_steps_per_sec"),
        ("vector_fleet", "learner_steps_per_sec"),
        ("anakin", "env_steps_per_sec"),
        ("anakin", "train_steps_per_sec"),
        ("anakin", "host_blocked_fraction"),
    ):
      assert set(block[path][field]) == {"median", "min", "max",
                                         "trials"}, (path, field)
    # The zero-host-work claim, honestly measured: blocked = wall time
    # outside AnakinLoop's own in-executable clock, so step()'s host
    # bookkeeping COUNTS against the bar. Sub-millisecond bookkeeping
    # vs ~0.1-0.3s dispatches keeps 5% far from the noise floor even
    # on the 2-core box.
    assert block["anakin"]["host_blocked_fraction"]["median"] <= 0.05
    counts = block["compile_counts"]
    assert counts["anakin_step"] == 1
    assert sum(1 for key in counts
               if key.startswith("vector_cem_bucket_")) == 1
    assert all(value == 1 for value in counts.values()), counts
    if (os.cpu_count() or 1) >= 4:
      assert block["speedup"]["median"] >= 5.0, block["speedup"]
    else:
      assert block["speedup"]["max"] >= 3.0, block["speedup"]
      assert block["speedup"]["median"] >= 2.0, block["speedup"]

  def test_metrics_flow_through_metric_writer(self, anakin_smoke_results):
    _, logdir = anakin_smoke_results
    path = os.path.join(logdir, "metrics.jsonl")
    assert os.path.exists(path)
    seen = set()
    with open(path) as f:
      for line in f:
        seen.update(json.loads(line).keys())
    for key in ("replay/fill_fraction", "replay/sample_staleness",
                "replay/target_lag", "replay/eval_td_error",
                "replay/train_loss", "replay/env_steps"):
      assert key in seen, (key, sorted(seen))


def _run_cli_subprocess(args, tmp, timeout=480):
  """The artifact-environment subprocess protocol shared by the
  sharded smokes: JAX_PLATFORMS=cpu, XLA_FLAGS stripped — a CLI that
  needs a multi-device mesh must BOOTSTRAP it (the re-exec path under
  test), exactly as a user invocation would."""
  import subprocess
  import sys
  out = tmp / "out.json"
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env["JAX_PLATFORMS"] = "cpu"
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
  res = subprocess.run(
      [sys.executable, *args, "--out", str(out)],
      capture_output=True, text=True, timeout=timeout, env=env,
      cwd=root)
  assert res.returncode == 0, res.stderr[-2000:]
  lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
  assert len(lines) == 1, res.stdout  # the ONE-JSON-line contract
  results = json.loads(lines[0])
  assert json.loads(out.read_text()) == results
  return results


@pytest.fixture(scope="module")
def sharded_smoke_results(tmp_path_factory):
  """The r10 SHARDED smoke protocol in a subprocess: `--mesh 8,1`
  forces the CLI's virtual-CPU-mesh bootstrap (re-exec with the
  canonical env), then runs the fused loop over the 8-device dp mesh.
  Reduced step budget + no anakin-bench block: this fixture gates the
  sharded path's structure/learning claims; the full-protocol numbers
  live in the committed REPLAY_SMOKE_r10.json."""
  tmp = tmp_path_factory.mktemp("sharded_smoke")
  return _run_cli_subprocess(
      ["-m", "tensor2robot_tpu.bin.run_qtopt_replay", "--smoke",
       "--anakin", "--mesh", "8,1", "--steps", "150",
       "--no-anakin-bench", "--logdir", str(tmp / "logs")], tmp)


class TestShardedAnakinSmokeCLI:
  """ISSUE 7 acceptance: the SHARDED fused loop still learns (>= 30%
  eval TD bar), still compiles exactly ONE anakin_step, still does
  zero host-side transition work — structure + ledger asserted
  everywhere (no timing bars here: those live in the committed
  artifacts and the multichip CLI's gated asserts)."""

  def test_mesh_and_zero1_recorded(self, sharded_smoke_results):
    results = sharded_smoke_results
    assert results["anakin"] is True
    assert results["mesh_shape"] == {"data": 8, "model": 1}
    assert results["zero1"] is True

  def test_td_reduction_through_sharded_loop(self, sharded_smoke_results):
    results = sharded_smoke_results
    assert results["steps"] >= 150
    assert results["eval_td_reduction"] >= 0.30, results["eval_history"]

  def test_ledger_one_executable_on_the_pod_mesh(self,
                                                 sharded_smoke_results):
    from tensor2robot_tpu.obs.ledger import check_compile_ledger
    check_compile_ledger(
        sharded_smoke_results["compile_counts"],
        require=("anakin_step",),
        forbid=("megastep", "train_step", "device_extend"))

  def test_host_never_touches_a_transition(self, sharded_smoke_results):
    results = sharded_smoke_results
    stats = results["queue"]
    assert stats["enqueued"] == 0 and stats["dequeued"] == 0
    assert results["env_steps_collected"] > 0
    assert results["episodes_collected"] > 0

  def test_parse_mesh_flag(self):
    from tensor2robot_tpu.bin.run_qtopt_replay import parse_mesh
    assert parse_mesh("8") == (8, 1)
    assert parse_mesh("4,2") == (4, 2)
    assert parse_mesh("0") == (0, 1)
    for bad in ("8,2,1", "a", "8,-1", "0,2"):
      with pytest.raises(ValueError):
        parse_mesh(bad)


@pytest.fixture(scope="module")
def multichip_bench_results(tmp_path_factory):
  """The scaling-ladder CLI at its two endpoints (1 and 8 devices):
  structure everywhere; the full 1/2/4/8 ladder is the committed
  MULTICHIP_r06.json."""
  tmp = tmp_path_factory.mktemp("multichip_bench")
  return _run_cli_subprocess(
      ["-m", "tensor2robot_tpu.replay.anakin_multichip_bench",
       "--smoke", "--devices", "1,8"], tmp)


class TestAnakinMultichipBenchCLI:
  """ISSUE 7: the MULTICHIP_r06-schema block. Structure + per-scale
  one-executable ledger asserted everywhere; the only quantitative
  bars (host-blocked, a token efficiency floor) are gated on
  `os.cpu_count() >= 4` per the repo-wide timing-bar rule — on the
  virtual mesh efficiency measures partitioning overhead, so no
  near-linear bar exists chiplessly by design."""

  def test_block_structure(self, multichip_bench_results):
    results = multichip_bench_results
    assert results["probed_device_kind"] == "cpu"
    assert results["virtual_mesh"] is True
    assert results["device_counts"] == [1, 8]
    assert len(results["scales"]) == 2
    for scale in results["scales"]:
      for field in ("env_steps_per_sec", "transitions_per_sec",
                    "per_device_transitions_per_sec",
                    "train_steps_per_sec", "host_blocked_fraction"):
        assert set(scale[field]) == {"median", "min", "max",
                                     "trials"}, field
      assert scale["compile_counts"] == {"anakin_step": 1}
      assert np.isfinite(scale["scaling_efficiency_vs_1dev"])
      assert scale["scaling_efficiency_vs_1dev"] > 0
    assert results["scales"][0]["devices"] == 1
    assert results["scales"][0]["zero1"] is False
    assert results["scales"][1]["devices"] == 8
    assert results["scales"][1]["zero1"] is True
    assert results["scales"][0]["scaling_efficiency_vs_1dev"] == 1.0

  def test_fixed_global_workload_recorded(self, multichip_bench_results):
    results = multichip_bench_results
    # One global workload across scales — the whole point of the
    # ladder; per-device == global / d at each scale.
    for scale in results["scales"]:
      ratio = (scale["transitions_per_sec"]["median"]
               / max(scale["per_device_transitions_per_sec"]["median"],
                     1e-9))
      assert abs(ratio - scale["devices"]) / scale["devices"] < 0.05

  def test_gated_quantitative_bars(self, multichip_bench_results):
    results = multichip_bench_results
    for scale in results["scales"]:
      # Zero-host-work holds at every scale (sub-ms bookkeeping vs
      # multi-second dispatches keeps this off the noise floor even
      # on the 2-core box).
      assert scale["host_blocked_fraction"]["median"] <= 0.05
    if (os.cpu_count() or 1) >= 4:
      # Token floor only: virtual-mesh partitioning overhead is the
      # measured quantity chiplessly (documented in the note field).
      assert results["scales"][-1]["scaling_efficiency_vs_1dev"] >= 0.005

  def test_committed_artifact_matches_schema(self):
    """MULTICHIP_r06.json (the committed acceptance artifact) parses
    against the same schema the live CLI just produced — the
    machine-check that keeps the artifact from going stale."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "MULTICHIP_r06.json")) as f:
      artifact = json.load(f)
    assert artifact["virtual_mesh"] is True
    assert artifact["device_counts"] == [1, 2, 4, 8]
    assert [s["devices"] for s in artifact["scales"]] == [1, 2, 4, 8]
    for scale in artifact["scales"]:
      assert scale["compile_counts"] == {"anakin_step": 1}
      assert set(scale["env_steps_per_sec"]) == {"median", "min",
                                                 "max", "trials"}
      assert scale["host_blocked_fraction"]["median"] <= 0.05
    assert artifact["scales"][0]["scaling_efficiency_vs_1dev"] == 1.0

"""AnakinLoop: act→env-step→extend→learn fused into ONE executable.

ISSUE 6 tentpole, second half. PR 3 fused the learner (MegastepLearner)
and PR 4 batched the actors (VectorActor), but the two halves still
meet on the HOST: the actor dispatches a CEM executable per control
step, steps numpy, enqueues, and the feeder re-stages the same bytes
back to the device — at ~3.7k env steps/s the loop is bounded by that
host choreography, not by any compiled program. This module is the
full Anakin architecture from Podracer (PAPERS.md, arXiv:2104.06272):
environment, action selection, replay extend, AND the optimizer step
all live inside one donated AOT executable that lax.scans K control
steps per dispatch. In the steady state the host's only work is
reading back a handful of scalar metrics and promoting checkpoints —
and because the whole loop is one jitted program, it later shards over
the dp×tp mesh like any other step (arXiv:2204.06514), which is what
unblocks ROADMAP open item 1.

Per scanned control step:

  obs       = env_state.images                (uint8, pre-step snapshot)
  act       : CEM through the SAME fleet_cem_optimize /
              make_tiled_q_score_fn contract serving uses, on the LIVE
              online params (strictly fresher than the actors' hot
              reload); models exposing `factored_cem_fns` encode each
              scene once and search over the code (identical Q, the
              image tower hoisted out of the sample loop), plus the
              collectors' epsilon-uniform + scripted-near-object
              exploration mix — same fractions and per-step draw
              order, drawn from JAX RNG instead of the numpy stream.
  env step  : jax_grasping.JaxGraspEnv.step_fn (pure; lax.select
              auto-reset; property-tested bit-identical to the numpy
              oracle).
  extend    : DeviceReplayBuffer.extend_fn at ONE fixed chunk — the
              fleet width — so the ring ingests in place with no
              recompile and no host staging (next_image == image: the
              scene is static within an episode, the numpy collectors'
              transition recipe).
  learn     : every `train_every`-th step, gated on min-fill via
              lax.cond, the EXACT megastep inner body
              (device_buffer.make_learn_iteration_fn): sample →
              CEM-Bellman label vs the target net → Trainer
              grad/apply → TD → in-place reprioritize.

The target network stays an executable ARGUMENT (refresh never
recompiles) and ``compile_counts['anakin_step']`` extends the replay
ledger: exactly one fused executable for the life of the loop. The
min-fill gate lives INSIDE the program (buffer size test), so there is
no host-side warm-up phase either — dispatch 0 already runs the final
steady-state code path.

Pod scale (ISSUE 7): the SAME single executable is mesh-native. On a
dp×tp mesh (the trainer's), the env fleet shards over the data axis
(`parallel.mesh.env_sharding` via `JaxGraspEnv.state_shardings`: each
device steps num_envs / dp envs in its own HBM — Podracer's per-core
environment slices), the replay ring capacity-shards per device
(`DeviceReplayBuffer`'s `ring_sharding`, which REFUSES indivisible
capacities), the sampled learn batch is pinned back onto the data axis
so the label→grad→apply chain runs data-parallel with XLA inserting
the gradient all-reduce against replicated params, and — when the
Trainer is built with `shard_optimizer_state=True` — the ZeRO-1
cross-replica weight-update sharding (arXiv:2004.13336) applies INSIDE
the scanned train body, exactly as in the supervised path. Still ONE
`anakin_step` in the ledger; the host work is unchanged (zero in the
steady state). Per-shard PRNG streams need no extra machinery: acting,
exploration, and label keys are already derived per-env/per-sample via
`fold_in` over a global index, so each device materializes only its
slice of the key array — the GLOBAL stream is identical on every mesh
shape, which is what makes the 1-device run the semantics oracle for
the sharded one (tests/test_anakin.py pins this).

Determinism: acting, exploration, env-reset, sampling, and label
randomness are all pure functions of (seed, outer, inner[, position])
via fold_in — one dispatch stream is replayable and independent of
wall-clock or host state.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from tensor2robot_tpu.obs import ledger as obs_ledger
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.parallel import distributed as dist_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.replay.bellman import (TargetNetwork,
                                             make_bellman_targets_fn)
from tensor2robot_tpu.replay.device_buffer import (DeviceReplayBuffer,
                                                   make_learn_iteration_fn)
from tensor2robot_tpu.research.qtopt import cem
from tensor2robot_tpu.research.qtopt.jax_grasping import JaxGraspEnv


class AnakinLoop(TargetNetwork):
  """The fused act→step→extend→learn loop around a JaxGraspEnv.

  Args:
    model/trainer/buffer: the MegastepLearner trio; the buffer's
      `ingest_chunk` MUST equal the env fleet width (one extend shape).
    env: a JaxGraspEnv (bank or procedural scene source).
    inner_steps: env control steps per dispatch (the scan length K).
    train_every: optimizer steps fire every `train_every`-th control
      step (must divide inner_steps). The numpy loop trained on its own
      thread at whatever cadence the box allowed; fused, the replay
      ratio is an explicit, reproducible knob.
    min_fill: optimizer steps are lax.cond-gated until the ring holds
      this many transitions — the ReplayFeeder.ready() gate, moved
      inside the program.
    exploration_epsilon / scripted_fraction: the collectors' mix.
  """

  def __init__(
      self,
      model,
      trainer,
      buffer: DeviceReplayBuffer,
      env: JaxGraspEnv,
      action_size: int = 4,
      gamma: float = 0.9,
      num_samples: int = 32,
      num_elites: int = 4,
      iterations: int = 2,
      inner_steps: int = 40,
      train_every: int = 8,
      min_fill: int = 0,
      exploration_epsilon: float = 0.2,
      scripted_fraction: float = 0.25,
      seed: int = 0,
      polyak_tau: Optional[float] = None,
      ledger: Optional[obs_ledger.ExecutableLedger] = None,
      precision: str = "f32",
      health: bool = False,
  ):
    """`precision` (ISSUE 13, cem.SCORING_PRECISIONS) is the CEM
    Q-scoring tier INSIDE the fused executable: acting's score calls
    and the label stage's target-net max run at the tier; the env step,
    replay extend, gradients, optimizer state, and the TD-priority
    arithmetic (the learn body's fresh-params forward) stay f32 — the
    low-precision-matmuls / f32-updates convention. "f32" (default)
    lowers the program bit-identically to r10.

    `health` (ISSUE 15): the scanned learn body additionally computes
    the fixed health-summary pytree (obs/health.SUMMARY_KEYS) —
    non-finite counts over grads/params/targets, grad/param norms,
    TD/Q mean/max, priority entropy, sample age — accumulated in the
    scan carry (running max for the spike-sensitive keys) and returned
    with the metrics. Still ONE `anakin_step` in the ledger: the cost
    is a few scalar reductions riding the existing metrics D2H, so
    host-blocked stays at its r09 level."""
    if inner_steps < 1 or train_every < 1 or inner_steps % train_every:
      raise ValueError(
          f"inner_steps {inner_steps} must be a positive multiple of "
          f"train_every {train_every}")
    if buffer.ingest_chunk != env.num_envs:
      raise ValueError(
          f"buffer ingest_chunk {buffer.ingest_chunk} must equal the "
          f"env fleet width {env.num_envs}: the fused extend runs at "
          "ONE fixed chunk shape — the fleet's")
    # Mesh-native placement (ISSUE 7): the trainer's mesh is THE mesh —
    # env fleet and learn batch shard over its data axis, so both must
    # divide it (an indivisible fleet/batch would silently replicate,
    # the exact trap the ring sharding refuses).
    self.mesh = trainer.mesh
    self._data_axis = trainer.data_axis
    axis_size = self.mesh.shape[self._data_axis]
    if env.num_envs % axis_size:
      raise ValueError(
          f"env fleet width {env.num_envs} is not divisible by the "
          f"{self._data_axis!r} mesh axis size ({axis_size} devices), so "
          f"the per-shard env fleets cannot form. Use a fleet of "
          f"{mesh_lib.nearest_multiples(env.num_envs, axis_size)} envs, or a "
          f"data axis that divides {env.num_envs}.")
    if buffer.sample_batch_size % axis_size:
      raise ValueError(
          f"sample batch {buffer.sample_batch_size} is not divisible by "
          f"the {self._data_axis!r} mesh axis size ({axis_size} devices), "
          f"so the fused learn body cannot run data-parallel. Use a batch "
          f"of "
          f"{mesh_lib.nearest_multiples(buffer.sample_batch_size, axis_size)}.")
    # Mesh placement is gated on the WHOLE mesh, not the data axis: a
    # dp=1/tp>1 mesh (the rule-partitioned flagship) still needs env
    # state and targets placed on the mesh — params shard over the
    # model axis, and un-placed host trees next to sharded params would
    # mix devices inside the fused jit. The 1-device mesh keeps the
    # r09 plain-copy path — the unchanged semantics oracle.
    self._sharded = self.mesh.size > 1
    # Target variables live replicated ON THE MESH when sharded (the
    # AOT executable is lowered against this placement; a host-numpy
    # refresh landing on device 0 only would make every shard read CEM
    # labels across the mesh).
    super().__init__(
        polyak_tau=polyak_tau,
        sharding=(mesh_lib.replicated_sharding(self.mesh)
                  if self._sharded else None))
    self._model = model
    self._trainer = trainer
    self._buffer = buffer
    self._env = env
    self._action_size = action_size
    self._gamma = gamma
    self._num_samples = num_samples
    self._num_elites = num_elites
    self._iterations = iterations
    self.inner_steps = inner_steps
    self.train_every = train_every
    self.min_fill = min_fill
    self._epsilon = exploration_epsilon
    self._scripted = scripted_fraction
    self._seed = seed
    self._clip_targets = getattr(model, "loss_type",
                                 "cross_entropy") == "cross_entropy"
    # CEM scoring precision (ISSUE 13, the ROADMAP item 3 bf16 tier):
    # `precision` is the policy knob, `dtype` the jnp name surfaced in
    # detail["anakin"]["dtype"] / the smoke artifact.
    self.precision = cem.validate_precision(precision)
    self.dtype = jnp.dtype(cem.scoring_dtype(precision)).name
    self.health = bool(health)
    self.compile_counts: Dict[str, int] = {}
    self._ledger = ledger
    self._exec = None
    self._outer = 0
    # Per-shard env fleets: the fleet-width leaves split over the data
    # axis at PLACEMENT time, so the executable is lowered (and its
    # donation paired) against the sharded layout from dispatch 0.
    self._env_shardings = env.state_shardings(self.mesh, self._data_axis)
    env_state = env.init_state(jax.random.key(seed + 21))
    if self._sharded:
      # global_put IS device_put single-process; multi-process (ISSUE
      # 19) it assembles each leaf as a global array from the identical
      # seeded init every process computes.
      env_state = dist_lib.global_put(env_state, self._env_shardings)
    self._env_state = env_state
    # Device counters snapshot (dispatch granularity, no mid-scan D2H).
    self.env_steps = 0
    self.trained_steps = 0
    # Cumulative wall time inside the fused executable (dispatch through
    # the metrics D2H) — the bench's host_blocked_fraction denominator;
    # host bookkeeping in step() deliberately falls OUTSIDE this clock.
    self.exec_seconds = 0.0

  # --- fleet bookkeeping (ActorFleet-shaped instruments) -------------------

  @property
  def mesh_shape(self) -> Dict[str, int]:
    """{axis: size} of the mesh the fused executable spans (the smoke
    artifact's record of HOW the loop was sharded)."""
    return dict(self.mesh.shape)

  @property
  def episodes(self) -> int:
    return int(jax.device_get(self._env_state.episodes))

  @property
  def successes(self) -> int:
    return int(jax.device_get(self._env_state.successes))

  # --- fused crash-resume (ISSUE 19: the donated state's only seam) --------

  def checkpoint_state(self):
    """The carried device state as one pytree for the checkpoint
    manager — env fleet, replay ring, target net, exactly the arrays
    the donated executable threads between dispatches. Taken BETWEEN
    dispatches (the only moment the donated buffers are live on the
    host side of the seam). TrainState stays with the caller (the loop
    owns it), completing the composite."""
    return {
        "env": self._env_state,
        "buffer": self._buffer.state,
        "target": self._target_variables,
    }

  def checkpoint_meta(self):
    """Host counters the device pytree does not carry (episodes and
    successes DO live in the env state and restore with it)."""
    return {
        "outer": self._outer,
        "env_steps": self.env_steps,
        "trained_steps": self.trained_steps,
        "refresh_count": self._refresh_count,
        "last_refresh_step": self.last_refresh_step,
    }

  def restore_checkpoint_state(self, composite, meta) -> None:
    """Installs a restored composite (arrays already placed on THIS
    loop's shardings by the checkpoint manager's template restore) and
    replays the host counters, so the next dispatch continues the
    (seed, outer, inner) RNG streams exactly where the crash cut them."""
    self._env_state = composite["env"]
    self._buffer.set_state(composite["buffer"])
    self._target_variables = composite["target"]
    self._outer = int(meta["outer"])
    self.env_steps = int(meta["env_steps"])
    self.trained_steps = int(meta["trained_steps"])
    self._refresh_count = int(meta["refresh_count"])
    self.last_refresh_step = int(meta["last_refresh_step"])

  # --- the fused program ---------------------------------------------------

  def _build_anakin_fn(self):
    model = self._model
    env_step = self._env.step_fn()
    extend = self._buffer.extend_fn()
    sample = self._buffer.sample_fn()
    update_priorities = self._buffer.update_priorities_fn()
    factored = model.factored_cem_fns()
    # The label stage's CEM max runs at the scoring tier; the learn
    # body's grads/optimizer/TD-priority forward stay f32 (the targets
    # come back f32 from q_value_from_logits — see
    # make_bellman_targets_fn's precision contract).
    targets_fn = make_bellman_targets_fn(
        model, self._action_size, self._gamma, self._num_samples,
        self._num_elites, self._iterations, self._clip_targets,
        factored=factored is not None, precision=self.precision)
    # Data-parallel pins for the multi-device mesh. All three are None/
    # identity on the 1-device mesh, so the single-device program — the
    # semantics oracle and measured fallback — lowers exactly as in r09.
    if self._sharded:
      batch_rule = mesh_lib.batch_sharding(self.mesh, self._data_axis)
      fleet_rule = mesh_lib.env_sharding(self.mesh, self._data_axis)
      env_shardings = self._env_shardings
      buffer_shardings = self._buffer.state_shardings()
      # The sampled gather out of the capacity-sharded ring re-lands
      # batch-split over the data axis, so label→grad→apply runs
      # data-parallel (XLA inserts the gradient all-reduce; with the
      # trainer's shard_optimizer_state the ZeRO-1 update sharding
      # applies inside this same scanned body).
      constrain_batch = (
          lambda batch: jax.lax.with_sharding_constraint(batch, batch_rule))
      constrain_carry = (
          lambda e, b: (jax.lax.with_sharding_constraint(e, env_shardings),
                        jax.lax.with_sharding_constraint(b, buffer_shardings)))
      constrain_actions = (
          lambda a: jax.lax.with_sharding_constraint(a, fleet_rule))
    else:
      constrain_batch = None
      constrain_carry = lambda e, b: (e, b)
      constrain_actions = lambda a: a
    learn = make_learn_iteration_fn(
        model, self._trainer.train_step_fn(with_health=self.health),
        sample, update_priorities,
        targets_fn, getattr(model, "target_key", "target_q"),
        self._clip_targets, constrain_batch=constrain_batch,
        health_entropy_fn=(self._buffer.priority_entropy_fn()
                           if self.health else None))
    n = self._env.num_envs
    batch_size = self._buffer.sample_batch_size
    k = self.inner_steps
    train_every = self.train_every
    min_fill = self.min_fill
    epsilon = self._epsilon
    scripted_fraction = self._scripted
    cem_kwargs = dict(num_samples=self._num_samples,
                      num_elites=self._num_elites,
                      iterations=self._iterations)
    action_size = self._action_size
    precision = self.precision
    act_base = jax.random.key(self._seed + 7)
    explore_base = jax.random.key(self._seed + 555)
    env_base = jax.random.key(self._seed + 31)
    sample_base = jax.random.key(self._seed)
    label_base = jax.random.key(self._seed + 1)

    def act(online_variables, obs, targets, tick):
      """CEM + exploration mix for the whole fleet, on device."""
      keys = jax.vmap(
          lambda j: jax.random.fold_in(
              jax.random.fold_in(act_base, tick), j))(
                  jnp.arange(n, dtype=jnp.uint32))
      states, score = cem.make_cem_states_and_score(
          model.predict_fn, factored, online_variables, obs,
          precision=precision)
      best, _ = cem.fleet_cem_optimize(score, states, keys, action_size,
                                       precision=precision, **cem_kwargs)
      # The collectors' exploration recipe (actor.py VectorActor
      # step_once): one epsilon draw per env, uniform actions, scripted
      # near-object grasps from the oracle pose — same fractions and
      # per-step draw order, from folded JAX keys instead of the shared
      # numpy stream (formula-level parity; the ENV is the bit-exact
      # contract, exploration is policy, not environment).
      ekey = jax.random.fold_in(explore_base, tick)
      dkey, ukey, nkey = jax.random.split(ekey, 3)
      draw = jax.random.uniform(dkey, (n,))
      uniform = jax.random.uniform(ukey, (n, action_size), jnp.float32,
                                   -1.0, 1.0)
      noise = jax.random.normal(nkey, (n, 2), jnp.float32) * 0.12
      scripted = uniform.at[:, :2].set(
          jnp.clip(targets + noise, -1.0, 1.0))
      actions = jnp.where((draw < epsilon)[:, None], uniform, best)
      # In-shard acting: pin the fleet's actions back onto the env
      # slices (per-env fold_in keys already shard with the arange).
      return constrain_actions(
          jnp.where((draw >= 1.0 - scripted_fraction)[:, None],
                    scripted, actions))

    zero_metrics = {
        "loss": jnp.zeros((), jnp.float32),
        "td_error": jnp.zeros((), jnp.float32),
        "q_next": jnp.zeros((), jnp.float32),
        "staleness": jnp.zeros((), jnp.float32),
    }
    if self.health:
      from tensor2robot_tpu.obs import health as health_lib
      zero_metrics.update(health_lib.zero_summary())

    def anakin_step(train_state, env_state, buffer_state,
                    target_variables, outer_step):

      def body(carry, inner):
        train_state, env_state, buffer_state, last_metrics = carry
        tick = outer_step * jnp.int32(k) + inner
        obs = env_state.images  # PRE-step snapshot: the observation
        actions = act(train_state.variables(use_ema=True), obs,
                      env_state.targets, tick)
        env_state, (rewards, dones, _) = env_step(
            env_state, actions, jax.random.fold_in(env_base, tick))
        # Static scene: next_image == image; truncation already
        # bootstraps through done=0 (the env's contract).
        buffer_state = extend(buffer_state, {
            "image": obs,
            "action": actions.astype(jnp.float32),
            "reward": rewards,
            "done": dones,
            "next_image": obs,
        })
        do_train = jnp.logical_and(
            buffer_state.size >= min_fill,
            (inner + 1) % train_every == 0)

        def run_learn(train_state, buffer_state):
          skey = jax.random.fold_in(sample_base, tick)
          label_keys = jax.vmap(
              lambda j: jax.random.fold_in(
                  jax.random.fold_in(label_base, tick), j))(
                      jnp.arange(batch_size, dtype=jnp.uint32))
          return learn(train_state, buffer_state, target_variables,
                       skey, label_keys)

        def skip_learn(train_state, buffer_state):
          return train_state, buffer_state, zero_metrics

        train_state, buffer_state, metrics = jax.lax.cond(
            do_train, run_learn, skip_learn, train_state, buffer_state)
        # Hold the carried env/ring layouts shard-stable through every
        # scan iteration (and therefore across dispatches: the donated
        # outputs re-enter at the same shardings the AOT lowering saw).
        env_state, buffer_state = constrain_carry(env_state, buffer_state)
        # Keep the LAST TRAINED metrics (skipped steps report zeros);
        # the spike-sensitive health keys instead accumulate a RUNNING
        # MAX in the carry so a transient mid-scan NaN or norm spike
        # survives to the dispatch readout (obs/health.SCAN_MAX_KEYS;
        # without health keys this reduces to the plain last-trained
        # merge).
        from tensor2robot_tpu.obs import health as health_lib
        last_metrics = health_lib.merge_scan_metrics(
            metrics, last_metrics, do_train)
        trained = do_train.astype(jnp.int32)
        return (train_state, env_state, buffer_state,
                last_metrics), trained

      (train_state, env_state, buffer_state, metrics), trained = (
          jax.lax.scan(
              body,
              (train_state, env_state, buffer_state, zero_metrics),
              jnp.arange(k, dtype=jnp.int32)))
      metrics = dict(metrics)
      metrics["trained_steps"] = jnp.sum(trained)
      return train_state, env_state, buffer_state, metrics

    return anakin_step

  def compiled(self, train_state):
    """The fused executable, AOT-compiled once (ledger: exactly 1).

    Donates (train_state, env_state, buffer_state): params, opt state,
    the episode state, the replay storage, and the sum tree all update
    in place in device memory — the donation + fixed-shape discipline
    of arXiv:2204.06514 applied to the WHOLE production loop.
    """
    if self._exec is None:
      fn = self._build_anakin_fn()
      if self._sharded:
        # Donated AOT boundary stability: every dispatch's OUTPUT state
        # must carry the same layout as its input, or the second
        # dispatch rejects its own carried state. Warm-up dispatches
        # route params through the skip branch of the min-fill cond
        # (no in-body constraint lands), so XLA propagation is free to
        # pick a different output layout for TP-catch-all leaves —
        # pin the whole TrainState to the caller's concrete shardings.
        state_shardings = jax.tree_util.tree_map(
            lambda leaf: leaf.sharding, train_state)
        inner_fn = fn

        def fn(ts, env_state, buffer_state, target_variables, outer):
          ts, env_state, buffer_state, metrics = inner_fn(
              ts, env_state, buffer_state, target_variables, outer)
          ts = jax.lax.with_sharding_constraint(ts, state_shardings)
          return ts, env_state, buffer_state, metrics

      args = (train_state, self._env_state, self._buffer.state,
              self._target_variables,
              dist_lib.global_scalar(0, self.mesh, jnp.int32))
      self._exec = jax.jit(
          fn, donate_argnums=(0, 1, 2)).lower(*args).compile()
      self.compile_counts["anakin_step"] = (
          self.compile_counts.get("anakin_step", 0) + 1)
      if self._ledger is not None:
        self._ledger.register(
            "anakin_step", compiled=self._exec,
            device=f"mesh{dict(self.mesh.shape)}",
            dtype=self.precision,
            shapes={"inner_steps": self.inner_steps,
                    "fleet": self._env.num_envs,
                    "batch": self._buffer.sample_batch_size})
    return self._exec

  def step(self, train_state):
    """One dispatch = `inner_steps` control steps (and up to
    inner_steps / train_every optimizer steps, min-fill permitting).
    Returns (train_state', metrics) with metrics as host floats — the
    only D2H of the steady state.
    """
    if self._target_variables is None:
      raise ValueError("call refresh(variables, step=0) before step()")
    exec_ = self.compiled(train_state)
    with trace_lib.span("learn/anakin_step", inner=self.inner_steps,
                        fused="act,step,extend,learn"):
      t0 = time.perf_counter()
      train_state, env_state, buffer_state, metrics = exec_(
          train_state, self._env_state, self._buffer.state,
          self._target_variables,
          dist_lib.global_scalar(self._outer, self.mesh, jnp.int32))
      # device_get blocks until the fused program finishes: the clock
      # stops exactly at the end of device work + the scalar D2H, so the
      # bookkeeping below is measurable host time, not hidden inside the
      # "in executable" bucket.
      metrics = jax.device_get(metrics)
      dispatch_seconds = time.perf_counter() - t0
    self.exec_seconds += dispatch_seconds
    if self._ledger is not None:
      self._ledger.record_dispatch("anakin_step", dispatch_seconds)
    self._env_state = env_state
    self._buffer.set_state(buffer_state)
    self._outer += 1
    self.env_steps += self.inner_steps * self._env.num_envs
    host_metrics = {key: float(value) for key, value in metrics.items()}
    host_metrics["trained_steps"] = int(host_metrics["trained_steps"])
    self.trained_steps += host_metrics["trained_steps"]
    return train_state, host_metrics

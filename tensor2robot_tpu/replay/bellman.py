"""BellmanUpdater: CEM-maximized Q-targets against a lagged target net.

The defining QT-Opt computation (PAPER.md / SURVEY.md §2): the Bellman
updater fleet consumed sampled transitions and produced training
targets

    target(s, a) = r + gamma * (1 - done) * max_a' Q_target(s', a')

where the max is the SAME cross-entropy-method search serving uses —
QT-Opt's whole trick is that argmax-free Q-learning over continuous
actions reuses one CEM routine at collect, label, and serve time. Here
the max runs through `cem.fleet_cem_optimize` (the serving-grade
variant with caller-supplied per-state keys), so label randomness is a
pure function of (transition position, seed), independent of batch
composition — the same determinism contract the fleet server holds.

TPU-native shape discipline (Podracer, arXiv:2104.06272): the target
computation is AOT-compiled ONCE at the replay buffer's fixed batch
shape. The target network is a pytree ARGUMENT of that executable, not
a captured constant — refresh (hard lag or polyak) swaps arrays, never
recompiles — and `compile_counts` is the ledger tests assert stays at
exactly one executable per function for the life of the updater.

The reference used a hard lagged target (push params every N steps to
the updater fleet); polyak averaging is the small generalization most
later off-policy systems settled on, so both are offered: pass
`polyak_tau` for soft updates, leave it None for hard copies.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.obs import ledger as obs_ledger
from tensor2robot_tpu.research.qtopt import cem


def q_value_from_logits(logits: jnp.ndarray,
                        clip_targets: bool) -> jnp.ndarray:
  """Logit → value space (mirrors CriticModel.q_value on arrays)."""
  logits = logits.astype(jnp.float32)
  return jax.nn.sigmoid(logits) if clip_targets else logits


def make_bellman_targets_fn(model, action_size: int, gamma: float,
                            num_samples: int, num_elites: int,
                            iterations: int, clip_targets: bool,
                            factored: bool = False,
                            precision: str = "f32"):
  """THE Bellman target body, as one pure jittable closure.

  (target_variables, next_images, rewards, dones, keys) ->
  (targets, q_next): CEM-maximized ``r + gamma * (1 - done) * max_a'
  Q_target(s', a')`` through the serving score contract
  (make_tiled_q_score_fn / fleet_cem_optimize). Both the host
  ``BellmanUpdater`` and the fused megastep
  (replay/device_buffer.MegastepLearner) compile THIS function — the
  target recipe cannot silently diverge between the two learners, the
  exact failure mode the tiled-score contract exists to prevent.

  factored=True (requires `model.factored_cem_fns()`): each next-state
  image is encoded ONCE and the CEM max runs over the code through the
  SAME make_tiled_q_score_fn / fleet_cem_optimize pair — identical Q
  function and search, the image tower hoisted out of the sample loop
  (the fused Anakin loop's configuration; equivalence to the tiled
  recipe is property-tested in tests/test_anakin.py). The default
  stays the tiled score: the one contract every learner shares.

  precision (cem.SCORING_PRECISIONS): the Q-scoring tier of the CEM
  max. Only the target-net forward inside the search runs at the tier
  — q_value_from_logits casts the best logits to float32, so the
  Bellman arithmetic (reward add, gamma discount, done mask, the clip)
  and everything downstream (grads, optimizer, TD priorities) stays
  f32 under every tier.
  """
  cem.validate_precision(precision)
  fns = model.factored_cem_fns() if factored else None
  if factored and fns is None:
    raise ValueError(
        f"{type(model).__name__} has no factored CEM form "
        "(factored_cem_fns() returned None); use factored=False")

  def targets_fn(target_variables, next_images, rewards, dones, keys):
    states, score = cem.make_cem_states_and_score(
        model.predict_fn, fns, target_variables, next_images,
        precision=precision)
    _, best_logits = cem.fleet_cem_optimize(
        score, states, keys, action_size,
        num_samples=num_samples, num_elites=num_elites,
        iterations=iterations, precision=precision)
    q_next = q_value_from_logits(best_logits, clip_targets)
    targets = (rewards.astype(jnp.float32)
               + gamma * (1.0 - dones.astype(jnp.float32)) * q_next)
    if clip_targets:
      targets = jnp.clip(targets, 0.0, 1.0)
    return targets, q_next

  return targets_fn


class TargetNetwork:
  """Target-net lifecycle shared by the host and device learners:
  hard-lag or polyak refresh (a pure array swap — consumers take the
  target as an executable ARGUMENT, so refresh never recompiles),
  plus the lag/refresh-count health metrics.

  `sharding` (a NamedSharding, normally the consumer mesh's replicated
  rule) pins where refresh PLACES the copied target pytree. The fused
  mesh-native learners need this: their AOT executables are lowered
  against the target's placement, and a refresh fed from host numpy
  would otherwise land the arrays on device 0 only — every shard's CEM
  labeling then reads across the mesh instead of from local HBM, and a
  later refresh with a different placement would be rejected by the
  executable outright. With sharding=None (the host BellmanUpdater)
  refresh keeps today's plain-copy behavior.
  """

  def __init__(self, variables=None, polyak_tau: Optional[float] = None,
               sharding=None):
    self._polyak_tau = polyak_tau
    self._target_sharding = sharding
    self._target_variables = (
        None if variables is None
        else self._place(jax.tree_util.tree_map(jnp.copy, variables)))
    self._refresh_count = 0
    self.last_refresh_step = 0

  def _place(self, variables):
    if self._target_sharding is None:
      return variables
    # global_put IS device_put single-process; multi-process (ISSUE 19)
    # the replicated target must be a GLOBAL array — every process
    # holds the identical refreshed copy and contributes its shards.
    from tensor2robot_tpu.parallel import distributed as dist_lib
    return dist_lib.global_put(variables, self._target_sharding)

  def refresh(self, variables, step: int) -> None:
    """Pulls the online variables into the target net (lag or polyak;
    the first refresh of a cold target is always a hard copy)."""
    if self._polyak_tau is None or self._target_variables is None:
      target = jax.tree_util.tree_map(jnp.copy, variables)
    else:
      tau = self._polyak_tau
      old_target = self._target_variables
      if jax.process_count() > 1:
        # Eager arithmetic on process-spanning arrays raises; the
        # target is replicated, so each process blends its own full
        # host copy and _place reassembles the global array.
        old_target = jax.tree_util.tree_map(np.asarray, old_target)
      target = jax.tree_util.tree_map(
          lambda online, target: tau * online + (1.0 - tau) * target,
          variables, old_target)
    self._target_variables = self._place(target)
    self._refresh_count += 1
    self.last_refresh_step = int(step)

  def target_lag(self, step: int) -> int:
    """Optimizer steps since the target net last saw online params."""
    return int(step) - self.last_refresh_step

  @property
  def refresh_count(self) -> int:
    return self._refresh_count

  # -- checkpoint state (ISSUE 14: learner crash-resume) -------------------

  def target_state(self):
    """(host target variables tree, bookkeeping meta) for a loop
    checkpoint — the target net is NOT derivable from TrainState (it
    lags by up to refresh_every steps), so resume must carry it or the
    first post-resume labels bootstrap off the wrong Q."""
    variables = (None if self._target_variables is None else
                 jax.tree_util.tree_map(np.asarray,
                                        self._target_variables))
    return variables, {"refresh_count": self._refresh_count,
                       "last_refresh_step": self.last_refresh_step}

  def restore_target_state(self, variables, meta) -> None:
    """Inverse of target_state (placement rule re-applied)."""
    self._target_variables = (
        None if variables is None else
        self._place(jax.tree_util.tree_map(jnp.asarray, variables)))
    self._refresh_count = int(meta["refresh_count"])
    self.last_refresh_step = int(meta["last_refresh_step"])


class BellmanUpdater(TargetNetwork):
  """Q-target labeller over a critic model with a ``q_predicted`` head."""

  def __init__(
      self,
      model,
      variables,
      action_size: int = 4,
      gamma: float = 0.9,
      num_samples: int = 32,
      num_elites: int = 4,
      iterations: int = 2,
      seed: int = 0,
      polyak_tau: Optional[float] = None,
      ledger: Optional[obs_ledger.ExecutableLedger] = None,
      precision: str = "f32",
  ):
    """Args:
      model: a CriticModel (loss_type decides target value space: the
        cross-entropy head clips targets to [0, 1], the published
        QT-Opt grasping formulation; mse leaves them unclipped).
      variables: initial online variables; the target net starts as a
        copy (a random target bootstraps garbage, but min-fill gating
        plus the first refresh bound how long that lasts — same as the
        reference's cold-start).
      action_size / num_samples / num_elites / iterations: the CEM
        search budget for the max (the reference used the serving
        config here too).
      polyak_tau: None = hard copy on refresh(); else
        target <- tau * online + (1 - tau) * target per refresh call.
      precision: the CEM Q-scoring tier for compute_targets
        (cem.SCORING_PRECISIONS; "f32" = the unchanged oracle). The TD
        executable (td_errors — priorities AND the eval-vs-analytic-Q*
        metric) deliberately stays f32 under every tier: priorities and
        eval bars are f32-updates territory, not scoring.
    """
    super().__init__(variables, polyak_tau=polyak_tau)
    self.precision = cem.validate_precision(precision)
    self._model = model
    self._action_size = action_size
    self._gamma = gamma
    self._num_samples = num_samples
    self._num_elites = num_elites
    self._iterations = iterations
    self._seed = seed
    self._clip_targets = getattr(model, "loss_type",
                                 "cross_entropy") == "cross_entropy"
    # fn name -> number of XLA compiles; the replay smoke asserts every
    # value is exactly 1 (fixed-shape sampling never recompiles).
    self.compile_counts: Dict[str, int] = {}
    self._ledger = ledger
    self._targets_exec = None
    self._td_exec = None
    self._next_label_seed = 0

  # --- compiled computations ----------------------------------------------

  def _q_value(self, logits: jnp.ndarray) -> jnp.ndarray:
    return q_value_from_logits(logits, self._clip_targets)

  def _build_targets_fn(self):
    seed = self._seed
    # The shared pure target body (also compiled by the megastep): the
    # updater only adds its uint32-counter → key fold in front.
    targets_fn = make_bellman_targets_fn(
        self._model, self._action_size, self._gamma, self._num_samples,
        self._num_elites, self._iterations, self._clip_targets,
        precision=self.precision)

    def seeded_targets_fn(target_variables, next_images, rewards, dones,
                          seeds):
      base = jax.random.key(seed)
      keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(seeds)
      return targets_fn(target_variables, next_images, rewards, dones,
                        keys)

    return seeded_targets_fn

  def _build_td_fn(self):
    model = self._model

    def td_fn(variables, images, actions, targets):
      outputs = model.predict_fn(
          variables,
          {"image": images, "action": actions.astype(jnp.float32)})
      q = self._q_value(jnp.reshape(outputs["q_predicted"], (-1,)))
      return jnp.abs(q - targets.astype(jnp.float32))

    return td_fn

  def _compile(self, name: str, fn, args, dtype: Optional[str] = None):
    """AOT lower+compile at the args' (fixed) shapes, ledger bumped.

    AOT executables REJECT any later shape drift instead of silently
    recompiling — the ledger plus this hard failure is what makes
    "compiles exactly once" an enforced property, not a hope. `dtype`
    tags the ledger row with the executable's scoring tier so
    attribution can split device time per precision.
    """
    executable = jax.jit(fn).lower(*args).compile()
    self.compile_counts[name] = self.compile_counts.get(name, 0) + 1
    if self._ledger is not None:
      self._ledger.register(name, compiled=executable, dtype=dtype)
    return executable

  def compute_targets(
      self, batch, seeds: Optional[np.ndarray] = None
  ) -> Tuple[np.ndarray, np.ndarray]:
    """Labels one fixed-shape transition batch.

    Args:
      batch: mapping with next_image / reward / done leaves (the
        ReplayBuffer's sampled batch).
      seeds: (B,) uint32 CEM label seeds; default: a monotonic counter
        so every label draw in the run is distinct but replayable.

    Returns:
      (targets (B,), q_next (B,)) as host numpy.
    """
    next_images = jnp.asarray(batch["next_image"])
    rewards = jnp.asarray(batch["reward"])
    dones = jnp.asarray(batch["done"])
    n = next_images.shape[0]
    if seeds is None:
      seeds = np.arange(self._next_label_seed,
                        self._next_label_seed + n, dtype=np.uint32)
      self._next_label_seed += n
    seeds = jnp.asarray(seeds, jnp.uint32)
    args = (self._target_variables, next_images, rewards, dones, seeds)
    if self._targets_exec is None:
      self._targets_exec = self._compile(
          "bellman_targets", self._build_targets_fn(), args,
          dtype=self.precision)
    start = time.perf_counter()
    targets, q_next = self._targets_exec(*args)
    targets, q_next = np.asarray(targets), np.asarray(q_next)
    if self._ledger is not None:
      self._ledger.record_dispatch("bellman_targets",
                                   time.perf_counter() - start)
    return targets, q_next

  @property
  def next_label_seed(self) -> int:
    """The label-seed counter (checkpointed so a resumed loop's CEM
    label draws CONTINUE the interrupted stream instead of replaying
    seed 0 — part of the resume-equals-uninterrupted parity bar)."""
    return self._next_label_seed

  def restore_label_seed(self, next_label_seed: int) -> None:
    self._next_label_seed = int(next_label_seed)

  def td_errors(self, variables, batch,
                targets: np.ndarray) -> np.ndarray:
    """|Q(s, a) - target| per transition, in value space.

    Drives BOTH prioritized-replay updates (sampled batch, online
    params) and the loop's eval metric (held-out batch). One tiny
    forward, compiled once at the fixed batch shape.
    """
    images = jnp.asarray(batch["image"])
    actions = jnp.asarray(batch["action"])
    targets = jnp.asarray(targets)
    args = (variables, images, actions, targets)
    if self._td_exec is None:
      self._td_exec = self._compile("td_error", self._build_td_fn(), args,
                                    dtype="f32")
    start = time.perf_counter()
    td = np.asarray(self._td_exec(*args))
    if self._ledger is not None:
      self._ledger.record_dispatch("td_error",
                                   time.perf_counter() - start)
    return td

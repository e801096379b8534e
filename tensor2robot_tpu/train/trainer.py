"""Trainer: builds the jit-compiled, mesh-sharded train/eval steps.

Reference parity: the device-side path of SURVEY.md §3.1 —
models/abstract_model.py §model_fn(TRAIN) + §create_train_op +
CrossShardOptimizer — rebuilt as one functional step:

    (state, batch) -> (state', metrics)

traced once, compiled by XLA for the whole mesh. Gradient all-reduce is
not written anywhere: the batch is sharded over the `data` axis, params
are replicated, so XLA inserts the psum over ICI where the reference
called cross_replica_sum.

TPU notes:
  - The state pytree is donated — params/opt-state buffers are updated in
    place in HBM, no per-step reallocation.
  - RNG is folded from a base key and the step counter inside the compiled
    step, so resuming from a checkpoint replays the identical randomness
    stream without any host-side key threading.
  - EMA (use_avg_model_params) runs inside the same fused step.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from tensor2robot_tpu import modes
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import tp_rules
from tensor2robot_tpu.train.train_state import TrainState


class Trainer:
  """Owns mesh, optimizer, and the compiled step functions for one model."""

  def __init__(
      self,
      model,
      mesh: Optional[jax.sharding.Mesh] = None,
      seed: int = 0,
      data_axis: str = "data",
      param_specs=None,
      shard_optimizer_state: bool = False,
  ):
    """Args:
      param_specs: optional PartitionSpec pytree (or prefix) for params —
        tensor parallelism over extra mesh axes
        (parallel.tp_rules.infer_dense_tp_specs) or FSDP/ZeRO-3 over the
        data axis (infer_fsdp_specs). None = replicated params, pure DP
        (the reference's only strategy).
      shard_optimizer_state: ZeRO-1-style cross-replica weight-update
        sharding (Xu et al. 2020, arXiv:2004.13336): optimizer-state
        leaves are partitioned over the data axis (largest divisible
        dim), cutting per-chip Adam m/v memory by the DP degree while
        params stay replicated — XLA turns the gradient all-reduce +
        sharded update into reduce-scatter + all-gather. COMPOSES with
        param_specs (the pjit/TPUv4-paper layering): each opt-state
        leaf first inherits its parameter's model-axis spec (matched by
        param-path suffix), then additionally scatters over the data
        axis on its largest divisible UNCLAIMED dim — with no
        param_specs this reduces exactly to the pure-DP ZeRO-1 rule.
    """
    self.model = model
    self.mesh = mesh if mesh is not None else mesh_lib.create_mesh()
    self.data_axis = data_axis
    self.param_specs = param_specs
    self._shard_opt = shard_optimizer_state
    # Pure DP = every TrainState leaf replicated, so the jits can pin
    # explicit in/out shardings; any other mode (TP, sharded opt state)
    # relies on in-step constraints + propagation. Branch on THIS
    # everywhere — per-site predicates drift when modes are added.
    self._pure_dp = param_specs is None and not shard_optimizer_state
    self._base_rng = jax.random.key(seed)
    self._optimizer = model.create_optimizer()
    self._batch_sharding = mesh_lib.batch_sharding(self.mesh, data_axis)
    self._replicated = mesh_lib.replicated_sharding(self.mesh)
    self._train_step = None
    self._train_step_health = None
    self._train_steps = None
    self._train_step_accum = None
    self._eval_step = None

  def _constrain_params(self, params):
    """Pins params to their TP shardings inside jit; opt-state shardings
    propagate from these constraints automatically."""
    if self.param_specs is None:
      if self._shard_opt:
        # Weight-update sharding keeps params explicitly replicated
        # (the jit has no out_shardings in this mode, so propagation
        # from the sharded opt state must not leak into params).
        return jax.lax.with_sharding_constraint(params, self._replicated)
      return params
    return jax.lax.with_sharding_constraint(
        params, tp_rules.specs_to_shardings(self.param_specs, self.mesh))

  def _constrain_opt_state(self, opt_state):
    """Pins optimizer-state leaves to their ZeRO-1 shardings.

    Pure DP: each leaf shards its largest data-axis-divisible dim (the
    same rule FSDP applies to params); scalars and indivisible leaves
    stay replicated — byte-identical to the pre-TP behavior. Under
    param_specs the two layouts COMPOSE: an opt-state leaf whose path
    suffix names a parameter (optax states mirror the param tree —
    ``0/0/mu/pre_conv0/kernel`` ends with ``pre_conv0/kernel``) first
    inherits that parameter's model-axis spec, then the data axis lands
    on its largest divisible dim the spec leaves unclaimed
    (tp_rules.compose_data_axis_spec), so Adam m/v shard over BOTH
    axes and no constraint fights the parameter layout.

    TP without ZeRO-1 still pins: each opt-state leaf mirrors its
    parameter's model-axis spec exactly (no data scatter). Leaving
    these leaves to XLA propagation gives the AOT fused consumers an
    UNSTABLE boundary — the init executable and the step executable
    can pick different layouts for the same leaf, and a donated
    carry-back then rejects its own state on the second dispatch."""
    if not self._shard_opt and self.param_specs is None:
      return opt_state
    from jax.sharding import NamedSharding, PartitionSpec
    axis_size = self.mesh.shape[self.data_axis]
    base_specs = {}
    if self.param_specs is not None:
      flat, _ = jax.tree_util.tree_flatten_with_path(
          self.param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
      base_specs = {tp_rules.path_key(path): spec for path, spec in flat}

    def base_for(key: str) -> PartitionSpec:
      best, best_len = PartitionSpec(), -1
      for param_path, spec in base_specs.items():
        if ((key == param_path or key.endswith("/" + param_path))
            and len(param_path) > best_len):
          best, best_len = spec, len(param_path)
      return best

    def constrain(path, leaf):
      base = base_for(tp_rules.path_key(path))
      if self._shard_opt:
        spec = tp_rules.compose_data_axis_spec(
            getattr(leaf, "shape", ()), base, self.data_axis, axis_size)
      else:
        spec = base  # TP-only: mirror the parameter layout exactly
      return jax.lax.with_sharding_constraint(
          leaf, NamedSharding(self.mesh, spec))

    return jax.tree_util.tree_map_with_path(constrain, opt_state)

  # --- state ---------------------------------------------------------------

  def create_train_state(self, batch_size: int = 1) -> TrainState:
    """Initializes (or re-initializes) replicated training state."""
    def _init(rng: jax.Array) -> TrainState:
      variables = self.model.init_variables(rng, batch_size=batch_size)
      variables = dict(variables)
      params = self._constrain_params(variables.pop("params"))
      ema = (self._constrain_params(
          jax.tree_util.tree_map(jnp.copy, params))
             if self.model.use_avg_model_params else None)
      return TrainState(
          step=jnp.zeros((), jnp.int32),
          params=params,
          model_state=variables,
          opt_state=self._constrain_opt_state(
              self._optimizer.init(params)),
          ema_params=ema)

    if self._pure_dp:
      init = jax.jit(_init, out_shardings=self._replicated)
    else:
      # TP / sharded opt state: pinned by the constraints inside.
      init = jax.jit(_init)
    state = init(self._base_rng)
    if self.model.init_from_checkpoint:
      state = self._warm_start(state, self.model.init_from_checkpoint)
    return state

  def _warm_start(self, state: TrainState, checkpoint_path: str) -> TrainState:
    """Reference §init_from_checkpoint: load matching params by name."""
    from tensor2robot_tpu.train import checkpoints
    restored = checkpoints.restore_params(checkpoint_path)
    params = checkpoints.merge_params(
        state.params, restored,
        assignment_map=self.model.init_from_checkpoint_assignment_map)
    if self.param_specs is None:
      params = jax.device_put(params, self._replicated)
    else:
      params = jax.device_put(
          params, tp_rules.specs_to_shardings(self.param_specs, self.mesh))
    # EMA re-seeds from the warm-started params: at decay ~0.9999 an
    # EMA left on the random init would poison eval/export for tens of
    # thousands of steps.
    ema = state.ema_params
    if ema is not None:
      ema = jax.tree_util.tree_map(jnp.copy, params)
    return state.replace(params=params, ema_params=ema)

  # --- steps ---------------------------------------------------------------

  @jax.named_scope("apply_grads")
  def _apply_grads(self, state: TrainState, grads, new_model_state
                   ) -> TrainState:
    """Optimizer update + EMA + step bump, shared by the single-step and
    gradient-accumulation bodies (the reference's §create_train_op
    apply_gradients half)."""
    updates, new_opt_state = self._optimizer.update(
        grads, state.opt_state, state.params)
    new_opt_state = self._constrain_opt_state(new_opt_state)
    new_params = self._constrain_params(
        optax.apply_updates(state.params, updates))
    new_ema = state.ema_params
    if new_ema is not None:
      new_ema = optax.incremental_update(
          new_params, new_ema,
          step_size=1.0 - self.model.avg_model_params_decay)
      # EMA mirrors the param layout; pinning it keeps the donated AOT
      # boundary stable under TP (same rationale as _constrain_opt_state).
      new_ema = self._constrain_params(new_ema)
    return state.replace(
        step=state.step + 1,
        params=new_params,
        model_state=new_model_state,
        opt_state=new_opt_state,
        ema_params=new_ema)

  def _make_train_step_fn(self, with_health: bool = False):
    """The uncompiled (state, features, labels) -> (state', metrics) body
    shared by the single-step and scanned multi-step compilations.

    with_health (ISSUE 15): the metrics dict additionally carries
    ``grad_norm`` (global L2) and ``grads_nonfinite`` (non-finite
    element count) computed from the RAW gradients before the
    optimizer apply — the two reductions the health sentinel cannot
    reconstruct after the fact (a clipped/NaN-propagated param delta
    is not the gradient). A few extra reductions inside the same
    compiled step; the training math is untouched."""
    model = self.model
    base_rng = self._base_rng

    def step_fn(state: TrainState, features, labels
                ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
      rng = jax.random.fold_in(base_rng, state.step)

      def loss_fn(params):
        variables = {"params": params, **state.model_state}
        loss, (metrics, new_model_state) = model.model_train_fn(
            variables, features, labels, rngs={"dropout": rng})
        return loss, (metrics, new_model_state)

      grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
      (_, (metrics, new_model_state)), grads = grad_fn(state.params)
      if with_health:
        from tensor2robot_tpu.obs import health as health_lib
        metrics = dict(metrics)
        metrics["grad_norm"] = health_lib.tree_global_norm(grads)
        metrics["grads_nonfinite"] = health_lib.tree_nonfinite_count(
            grads)
      return self._apply_grads(state, grads, new_model_state), metrics

    return step_fn

  def _make_train_step_accum_fn(self):
    """One optimizer step over K sequential microbatches (leading axis on
    every leaf): gradients are averaged across microbatches before a
    single apply, so the effective batch is K× what fits in HBM at once
    — the memory-bound complement to `train_steps`' scan. Mutable model
    state (batch_stats) threads through the microbatches sequentially;
    metrics are microbatch means. RNG folds (step, microbatch index), so
    each microbatch draws distinct dropout."""
    model = self.model
    base_rng = self._base_rng

    def accum_fn(state: TrainState, features, labels
                 ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
      rng = jax.random.fold_in(base_rng, state.step)
      num_micro = jax.tree_util.tree_leaves(features)[0].shape[0]

      def loss_fn(params, model_state, feat, lab, micro_rng):
        variables = {"params": params, **model_state}
        loss, (metrics, new_model_state) = model.model_train_fn(
            variables, feat, lab, rngs={"dropout": micro_rng})
        return loss, (metrics, new_model_state)

      grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

      def body(carry, xs):
        acc, model_state, idx = carry
        feat, lab = xs
        micro_rng = jax.random.fold_in(rng, idx)
        (_, (metrics, new_model_state)), grads = grad_fn(
            state.params, model_state, feat, lab, micro_rng)
        acc = jax.tree_util.tree_map(jnp.add, acc, grads)
        return (acc, new_model_state, idx + 1), metrics

      zero = jax.tree_util.tree_map(jnp.zeros_like, state.params)
      (acc, new_model_state, _), metrics = jax.lax.scan(
          body, (zero, state.model_state, jnp.zeros((), jnp.int32)),
          (features, labels))
      grads = jax.tree_util.tree_map(lambda g: g / num_micro, acc)
      metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0),
                                       metrics)
      return self._apply_grads(state, grads, new_model_state), metrics

    return accum_fn

  def _build_train_step(self, with_health: bool = False):
    step_fn = self._make_train_step_fn(with_health=with_health)
    if self._pure_dp:
      return jax.jit(
          step_fn,
          in_shardings=(self._replicated, self._batch_sharding,
                        self._batch_sharding),
          out_shardings=(self._replicated, self._replicated),
          donate_argnums=(0,))
    # TP / sharded opt state: shardings inferred from the (already
    # correctly placed) inputs plus the in-step constraints.
    return jax.jit(step_fn, donate_argnums=(0,))

  def _build_train_steps(self):
    """K optimizer steps in one executable via lax.scan over a stacked
    batch — the TPU-native `iterations_per_loop`: host dispatch, metric
    sync, and Python loop overhead are amortized over K steps exactly
    like TPUEstimator's in-device training loop (SURVEY.md §3.1
    TPUConfig(iterations_per_loop)). RNG folds from the carried step
    counter, so the randomness stream is identical to K single steps.
    Returns the final state and the last step's metrics."""
    step_fn = self._make_train_step_fn()

    def many_fn(state: TrainState, features, labels):
      def body(carry, batch):
        new_state, metrics = step_fn(carry, batch[0], batch[1])
        return new_state, metrics
      state, metrics = jax.lax.scan(body, state, (features, labels))
      return state, jax.tree_util.tree_map(lambda x: x[-1], metrics)

    if self._pure_dp:
      stacked = mesh_lib.stacked_batch_sharding(self.mesh, self.data_axis)
      return jax.jit(
          many_fn,
          in_shardings=(self._replicated, stacked, stacked),
          out_shardings=(self._replicated, self._replicated),
          donate_argnums=(0,))
    return jax.jit(many_fn, donate_argnums=(0,))

  def _build_train_step_accum(self):
    accum_fn = self._make_train_step_accum_fn()
    if self._pure_dp:
      stacked = mesh_lib.stacked_batch_sharding(self.mesh, self.data_axis)
      return jax.jit(
          accum_fn,
          in_shardings=(self._replicated, stacked, stacked),
          out_shardings=(self._replicated, self._replicated),
          donate_argnums=(0,))
    return jax.jit(accum_fn, donate_argnums=(0,))

  def _build_eval_step(self):
    model = self.model

    def step_fn(state: TrainState, features, labels
                ) -> Dict[str, jnp.ndarray]:
      variables = state.variables(use_ema=True)
      return model.model_eval_fn(variables, features, labels)

    if self._pure_dp:
      return jax.jit(
          step_fn,
          in_shardings=(self._replicated, self._batch_sharding,
                        self._batch_sharding),
          out_shardings=self._replicated)
    return jax.jit(step_fn)

  # --- public API ----------------------------------------------------------

  def train_step_fn(self, with_health: bool = False):
    """The UNCOMPILED (state, features, labels) -> (state', metrics) body.

    For fused consumers that inline the optimizer step into a larger
    compiled program (replay/device_buffer.py's megastep scans it K
    times inside one donated executable). Callers own compilation;
    the body carries the trainer's RNG fold-from-step discipline, so a
    scan over it replays the identical randomness stream as K separate
    `train_step` calls. ``with_health`` adds the grad_norm /
    grads_nonfinite reductions to the metrics (see
    _make_train_step_fn) — the fused health summaries ride them."""
    return self._make_train_step_fn(with_health=with_health)

  def train_step(self, state: TrainState, features, labels=None
                 ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    """One compiled optimizer step. Donates `state`."""
    if self._train_step is None:
      self._train_step = self._build_train_step()
    # Host time to flatten the TensorSpecStructs and enqueue.
    with trace_lib.span("train/dispatch", kind="step"):
      return self._train_step(state, features, labels)

  def train_steps(self, state: TrainState, features, labels=None
                  ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    """K compiled optimizer steps over a K-stacked batch (leading loop
    axis on every leaf). Donates `state`; returns last-step metrics.
    Different K values compile separate executables — keep K fixed
    except for one possible partial final loop."""
    if self._train_steps is None:
      self._train_steps = self._build_train_steps()
    with trace_lib.span("train/dispatch", kind="steps"):
      return self._train_steps(state, features, labels)

  def aot_train_step(self, state: TrainState, features, labels=None,
                     with_health: bool = False):
    """AOT-lowered+compiled SINGLE train step for the same arguments.

    The replay loop's recompile ledger hangs on this: an AOT executable
    rejects any later shape/dtype drift instead of silently retracing,
    turning "the fixed-shape sampler never recompiles the train step"
    from a hope into an enforced invariant. Shares `train_step`'s
    donation semantics (pass back the state it returns).
    ``with_health`` compiles the health-instrumented body (grad_norm /
    grads_nonfinite in the metrics) — cached separately so the plain
    step is untouched for callers that never opt in."""
    if with_health:
      if self._train_step_health is None:
        self._train_step_health = self._build_train_step(
            with_health=True)
      return self._train_step_health.lower(state, features,
                                           labels).compile()
    if self._train_step is None:
      self._train_step = self._build_train_step()
    return self._train_step.lower(state, features, labels).compile()

  def aot_train_steps(self, state: TrainState, features, labels=None):
    """AOT-lowered+compiled `train_steps` executable for the same
    arguments. Exposes XLA's per-executable introspection
    (`.cost_analysis()` → flops / bytes accessed). The executable
    shares `train_steps`' donation semantics."""
    if self._train_steps is None:
      self._train_steps = self._build_train_steps()
    return self._train_steps.lower(state, features, labels).compile()

  def train_step_accum(self, state: TrainState, features, labels=None
                       ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    """One optimizer step over K stacked microbatches (leading axis on
    every leaf): grads averaged, single apply — K× effective batch in
    O(1-microbatch) activation memory. Donates `state`."""
    if self._train_step_accum is None:
      self._train_step_accum = self._build_train_step_accum()
    with trace_lib.span("train/dispatch", kind="accum"):
      return self._train_step_accum(state, features, labels)

  def eval_step(self, state: TrainState, features, labels=None
                ) -> Dict[str, jnp.ndarray]:
    """One compiled eval step (EMA params when enabled)."""
    if self._eval_step is None:
      self._eval_step = self._build_eval_step()
    return self._eval_step(state, features, labels)

  @property
  def batch_sharding(self):
    """Public sharding for batched inputs (prefetch/infeed consumers)."""
    return self._batch_sharding

  @property
  def shards_optimizer_state(self) -> bool:
    """True when ZeRO-1 weight-update sharding is active. Fused
    consumers that inline `train_step_fn` into their own executables
    (replay/anakin.py) inherit it automatically — the in-body
    constraints ride along with the body — and record this flag in
    their result artifacts."""
    return self._shard_opt

  @property
  def data_axis_size(self) -> int:
    """Devices on the data axis — the DP degree fused consumers must
    divide their fleet/batch sizes by."""
    return self.mesh.shape[self.data_axis]

  def shard_batch(self, batch: Any) -> Any:
    """Host batch → mesh, split over the data axis (the infeed)."""
    return mesh_lib.shard_batch(self.mesh, batch, self.data_axis)

  def predict_fn(self, state: TrainState):
    """Jitted PREDICT-mode closure over current (EMA) params, for export
    and predictors (SURVEY.md §3.3). Variables are a jit argument, not
    baked-in constants — keeps the executable weight-free."""
    # Host snapshot: the state's device buffers are donated to the next
    # train_step and would be invalidated under the closure's feet.
    # Multihost-safe fetch: TP params may be sharded across processes.
    from tensor2robot_tpu.export import export_utils
    variables = export_utils.fetch_variables_to_host(
        state.variables(use_ema=True))
    model = self.model
    jitted = jax.jit(model.predict_fn)

    def predict(features):
      return jitted(variables, features)

    return predict

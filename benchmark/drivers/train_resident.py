"""Driver `train_resident`: K scanned optimizer steps per dispatch
through `Trainer.train_steps`, the K-stack of seeded batches resident
on the device and re-fed every dispatch, a bounded number in flight.

Traffic parameters (benchmark/traffic/<name>.json):
  batch_per_chip   rows per chip and step
  scan_steps       K, optimizer steps per dispatch
  in_flight        dispatches the host may run ahead of the device

What is compared (see `check`): the first dispatch, which set-up drives
through the window's own call on the window's own K-stack, against the
plain reference following the same K steps from the same seed: the last
loss, the optimizer's first moment and the parameters' change by the
worst leaf (against the leaf's norm or the median leaf's, and the
change against the leaf's own norm too, so that no leaf is small enough
to stay unmoved unseen), and the change of the running statistics.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.reference import train as ref_train


def _first_moment(opt_state):
  """The optimizer's first moment (optax `trace` of SGD with momentum,
  `mu` of Adam), wherever the chain keeps it."""
  is_holder = lambda x: hasattr(x, "trace") or hasattr(x, "mu")
  for node in jax.tree_util.tree_leaves(opt_state, is_leaf=is_holder):
    if hasattr(node, "trace"):
      return node.trace
    if hasattr(node, "mu"):
      return node.mu
  raise ValueError("no first moment (trace/mu) in the optimizer state")


class Session:

  def __init__(self, cell, seed, devices, span):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.specs import tensorspec_utils as ts
    from tensor2robot_tpu.train.trainer import Trainer

    self._cell, self._seed, self._span = cell, seed, span
    config, traffic = cell.config, cell.traffic
    self._module = cell.reference
    self._chips = len(devices)
    self._steps = int(traffic["scan_steps"])
    self._batch = int(traffic["batch_per_chip"]) * self._chips
    self._in_flight = int(traffic["in_flight"])

    clock = harness.Phases()
    mesh = mesh_lib.create_mesh(devices=devices)
    self._trainer = Trainer(harness.build_model(config), mesh=mesh)
    state = self._trainer.create_train_state()
    jax.block_until_ready(state)
    clock.mark("create_train_state")
    replicated = mesh_lib.replicated_sharding(mesh)
    stacked = mesh_lib.stacked_batch_sharding(mesh)

    # The benchmark's own weights, in the program's layout.
    variables = jax.jit(
        lambda key: self._module.init_variables(key, config),
        out_shardings=replicated)(self._weights_key())
    ours = {"params": state.params, **state.model_state}
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    if shape(ours) != shape(variables):
      raise RuntimeError("the reference's parameter tree is not the "
                         "program's: " + str(shape(ours)))
    state = state.replace(
        params=variables["params"],
        model_state={"batch_stats": variables["batch_stats"]})

    jax.block_until_ready(state)
    clock.mark("seeded_weights")
    features, labels = jax.jit(
        self._make_stack, out_shardings=stacked)(self._data_key())
    self._features = ts.TensorSpecStruct(features)
    self._labels = ts.TensorSpecStruct(labels) if labels else None
    jax.block_until_ready(features)
    clock.mark("seeded_batches")

    # First dispatch: the window's own call on the window's own feed.
    with span("bench/first_dispatch"):
      state, metrics = self._trainer.train_steps(
          state, self._features, self._labels)
      copy = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
      self._first = {
          "params": copy(state.params),
          "moment": copy(_first_moment(state.opt_state)),
          "batch_stats": copy(state.model_state["batch_stats"]),
          "loss": metrics["loss"],
      }
      jax.block_until_ready(self._first)
    clock.mark("first_dispatch")
    # Second dispatch: every later call of the window is this one.
    state, metrics = self._trainer.train_steps(
        state, self._features, self._labels)
    jax.block_until_ready(metrics["loss"])
    clock.mark("second_dispatch")
    clock.say()
    self._state = state
    self._dispatches = 2
    self._last_loss = None

  def _weights_key(self):
    return jax.random.fold_in(jax.random.key(self._seed % (2 ** 31)), 1)

  def _data_key(self):
    return jax.random.fold_in(jax.random.key(self._seed % (2 ** 31)), 2)

  def _make_stack(self, key):
    keys = jax.random.split(key, self._steps)
    return jax.vmap(lambda k: self._module.make_batch(
        k, self._cell.config, self._batch))(keys)

  # --- the measured window -------------------------------------------------

  def run_window(self, seconds):
    span, trainer = self._span, self._trainer
    state, features, labels = self._state, self._features, self._labels
    pending = []
    done = 0
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
      with span("bench/dispatch"):
        state, metrics = trainer.train_steps(state, features, labels)
      pending.append(metrics["loss"])
      if len(pending) > self._in_flight:
        with span("bench/readback"):
          self._last_loss = float(pending.pop(0))
        done += 1
    with span("bench/drain"):
      for loss in pending:
        self._last_loss = float(loss)
        done += 1
    window_s = time.perf_counter() - start
    self._state = state
    self._dispatches += done
    examples = done * self._steps * self._batch
    return {
        "attempted": done, "failed": 0, "window_s": window_s,
        "examples": examples,
        "metrics": {
            "train_examples_per_s": examples / window_s / self._chips},
        "counters": {"dispatches": done, "scan_steps": self._steps,
                     "global_batch": self._batch},
    }

  def release(self):
    """Brings what the comparison needs to the host and frees the
    program's state."""
    self._final_step = int(self._state.step)
    self._first = jax.device_get(self._first)
    for leaf in jax.tree_util.tree_leaves(
        (self._state, self._features, self._labels)):
      leaf.delete()
    self._state = self._features = self._labels = None
    self._trainer = None

  # --- the comparison ------------------------------------------------------

  def controls(self):
    """Stand-ins for the program that have to come out not correct:
    the reference one precision below what the configuration states,
    the reference with half of every batch left out, and the reference
    with the leaf that moves least returned unchanged."""
    return {"control_fp8": {"precision": "fp8"},
            "fault_half_batch": {"keep_rows": self._batch // 2},
            "fault_smallest_leaf_frozen": {"freeze_smallest": True}}

  def check(self, limits, precision="f32", keep_rows=None,
            freeze_smallest=False):
    """[(name, value, limit)]; `precision`/`keep_rows`/`freeze_smallest`
    other than the defaults put the reference itself, computed lower or
    broken, in the program's place (the controls)."""
    config = self._cell.config
    variables = jax.jit(
        lambda key: self._module.init_variables(key, config))(
            self._weights_key())
    features, labels = jax.jit(self._make_stack)(self._data_key())
    followed = ref_train.follow(
        self._module, config["optimizer"], variables, features, labels)
    if precision != "f32" or keep_rows is not None:
      stand_in = ref_train.follow(
          self._module, config["optimizer"], variables, features, labels,
          precision=precision, keep_rows=keep_rows)
      first = dict(stand_in, loss=stand_in["losses"][-1])
    elif freeze_smallest:
      skip = ref_train.flat_gradient_leaves(followed["first_grad"])
      first = dict(followed, loss=followed["losses"][-1],
                   params=ref_train.with_smallest_leaf_unmoved(
                       followed["params"], variables["params"], skip))
    else:
      first = self._first
    return compare(first, followed, variables, limits) + [
        ("step_count_gap",
         abs(self._final_step - self._dispatches * self._steps), 0)]


def compare(first, followed, variables, limits):
  """[(name, value, limit)]; a number the cell's limits file does not
  name has the limit None: it is read and printed, not compared."""
  ref_loss = float(followed["losses"][-1])
  skip = ref_train.flat_gradient_leaves(followed["first_grad"])
  delta = lambda run, key: ref_train.tree_delta(run[key], variables[key])
  moments = ref_train.leaf_gaps(first["moment"], followed["moment"], skip)
  changes = ref_train.leaf_gaps(
      delta(first, "params"), delta(followed, "params"), skip)
  stats = ref_train.leaf_differences(
      delta(first, "batch_stats"), delta(followed, "batch_stats"))
  # The same two without the median leaf's floor: a small leaf that has
  # not moved reads 1 here.
  own_moments = ref_train.own_gaps(first["moment"], followed["moment"], skip)
  own_changes = ref_train.own_gaps(
      delta(first, "params"), delta(followed, "params"), skip)
  (moment_gap, moment_leaf), (change_gap, change_leaf), (
      stats_gap, stats_leaf), (own_moment, own_moment_leaf), (
          own_change, own_change_leaf) = map(
              ref_train.worst_of,
              (moments, changes, stats, own_moments, own_changes))
  print(f"[bench] worst leaves: moment {moment_leaf} change {change_leaf} "
        f"stats {stats_leaf} own moment {own_moment_leaf} own change "
        f"{own_change_leaf} skipped {len(skip)}; the smallest counted "
        "leaf's change is %.4g of the median leaf's (%s)"
        % ref_train.smallest_leaf(delta(followed, "params"), skip),
        flush=True)
  numbers = {
      "last_loss_gap": abs(float(first["loss"]) - ref_loss) / abs(ref_loss),
      "moment_norm_gap": moment_gap,
      "moment_median_gap": statistics.median(moments.values()),
      "change_norm_gap": change_gap,
      "change_median_gap": statistics.median(changes.values()),
      "moment_own_gap": own_moment,
      "change_own_gap": own_change,
      "smallest_leaf_share": ref_train.smallest_leaf(
          delta(followed, "params"), skip)[0],
      "stats_change_difference": stats_gap,
  }
  return [(name, value, limits.get(name)) for name, value in numbers.items()]

"""Driver `train_resident_sala`: `train_resident_hybrid` for a dense
(expert-free) sequence model whose mixers are block-sparse attention
and Lightning attention. K scanned optimizer steps per dispatch through
`Trainer.train_steps`, the K-stack of seeded token sequences resident on
the device and re-fed every dispatch, a bounded number in flight; one
example is one sequence.

Traffic parameters (benchmark/traffic/<name>.json): as
`train_resident_tokens`'s (sequence_length, batch_per_chip, scan_steps,
in_flight).

Why a driver of its own: the others read the program's expert counters
(`moe/*`) and compare the tokens each expert saw; this model has no
experts, and its counters are the sparse layers' `sala/*`. What serves
as it is comes from there (the seeded weights and the leaf norms, the
frozen-leaf fault, the compiled step's instructions under a scope, and
of the `Session` the seeds, the K-stack and the list of stand-ins). The
rest is `ScopedSession`, which reads the step metrics it compares and
carries, and the scopes it times, off its subclass, and `scoped_seconds`,
one trace reader for any scope; `Session` is this cell's subclass. The
follower takes the planted fault as a traced index, so that one compiled
reference step serves the sound reference and every fault (a compile of
the float32 step at 32,768 tokens is minutes).

What is compared (see `check`): the first dispatch, which set-up drives
through the window's own call on the window's own K-stack, against the
plain reference following the same K steps from the same seed: the last
step's loss, Adam's first moment and the parameters' change by the worst
leaf, each sparse layer's selected mass (the share of the selection
scores that falls inside the blocks attended to) and each Lightning
layer's mean decay at the last step, and the step counter. Printed
beside them, not compared: the same gaps by the median leaf and each
leaf's own norm, and the smallest mean square of a Lightning head's
output before its norm in the reference (`lightning_output_floor`).

In a traced run `release()` reads out of the trace, which still stands
then, the device time of every operation the compiled step names under
`sala/select` (the selection), under `sala/attend` (the visit plan and
the block-sparse kernel's two programs) and under `lightning/scan`
(Lightning's chunk-local part and its walk's two programs), with the
kernels' calls, and hands them on in the window record.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.drivers import train_resident_tokens as tokens_driver
from benchmark.drivers.train_resident import _first_moment
from benchmark.drivers.train_resident_hybrid import scope_instructions
from benchmark.drivers.train_resident_tokens import (
    _norm, _seed_fns, _sizes_json, with_smallest_leaf_unmoved)
from benchmark.reference import train as ref_train

SELECT_SCOPE, ATTEND_SCOPE, SCAN_SCOPE = (
    "sala/select", "sala/attend", "lightning/scan")


@functools.lru_cache(maxsize=None)
def _step_fn(module, config_json, precision, names):
  """One jitted Adam step of the reference `module`, the planted fault a
  traced index (-1: none), handing back the reference's outputs `names`
  beside the loss; kept, so that following again (a fault, another
  seed) traces nothing anew."""
  config = json.loads(config_json)
  opt = {k: v for k, v in config["optimizer"].items()
         if isinstance(v, (int, float))}
  update = ref_train._OPTIMIZERS[config["optimizer"]["kind"]]

  def loss_fn(params, features, fault):
    outputs, _ = module.forward({"params": params}, features, True,
                                precision, config, fault)
    total, _ = module.loss(outputs, features, None, config, fault)
    return total, {name: outputs[name] for name in names}

  @functools.partial(jax.jit, donate_argnums=(0, 1))
  def step(params, state, features, index, fault):
    (value, counters), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, features, fault)
    norms = jax.tree_util.tree_map(_norm, grads)
    params, state = update(opt, params, grads, state, index)
    return params, state, value, counters, norms

  return step


def follow(module, config, key, features, precision="f32", fault=None,
           names=None):
  """One optimizer step per leading index of `features`, from the
  parameters the reference seeds from `key`. Returns, on the host,
  "loss" and the reference's outputs `names` (by default
  `Session.FOLLOWED`) of the last step, "change" and "moment" (each
  leaf's norm of the parameters' change and of Adam's first moment after
  the K steps) and "first_grad" (each leaf's norm of the first step's
  gradients)."""
  sizes = _sizes_json(config)
  step = _step_fn(module, sizes, precision,
                  tuple(Session.FOLLOWED if names is None else names))
  seeded, norms_fn = _seed_fns(module, sizes)
  planted = jnp.asarray(-1 if fault is None else module.FAULTS.index(fault),
                        jnp.int32)
  params = seeded(key)
  state = {"moment": ref_train.init_moment(params),
           "nu": ref_train.init_moment(params)}
  first_grad = None
  num_steps = jax.tree_util.tree_leaves(features)[0].shape[0]
  for k in range(num_steps):
    feats = jax.tree_util.tree_map(lambda x: x[k], features)
    params, state, value, counters, norms = step(
        params, state, feats, jnp.asarray(k, jnp.float32), planted)
    if k == 0:
      first_grad = norms
  out = jax.device_get(dict(
      norms_fn(key, params, state["moment"]), loss=value,
      first_grad=first_grad, **counters))
  for leaf in jax.tree_util.tree_leaves((params, state)):
    leaf.delete()
  return out


def scoped_seconds(trace_dir, instructions, kernels=None):
  """{"scope_seconds": device self time of every operation of the trace
  under `trace_dir` whose instruction is one of `instructions` (the
  compiled step's under one scope), and with `kernels` ({key: a Pallas
  program's name}) "kernel_seconds" and "calls" of those programs}; None
  where there is no trace, where a program is not in it, or where a call
  of one is not among `instructions` (the names of the trace and of the
  compiled text did not join: a share of part of the work's time against
  all of it is never made)."""
  from benchmark.trace import reduce as reduce_lib
  try:
    loaded = reduce_lib.load(reduce_lib.find_xplane(trace_dir))
  except FileNotFoundError:
    return None
  kernels = kernels or {}
  seconds = {key: 0.0 for key in kernels}
  calls = {key: 0 for key in kernels}
  instruction = lambda op_name: op_name.split(" ")[0].lstrip("%")
  program_of = lambda op_name: next(
      (key for key, name in kernels.items()
       if instruction(op_name).startswith(name)), None)
  scope_seconds = 0.0
  for ops in loaded["devices"].values():
    for op in ops:
      if program_of(op[0]):
        if instruction(op[0]) not in instructions:
          return None
        calls[program_of(op[0])] += 1
    for op_name, total in reduce_lib.self_seconds(ops).items():
      if instruction(op_name) in instructions:
        scope_seconds += total
      if program_of(op_name):
        seconds[program_of(op_name)] += total
  if not all(calls.values()):
    return None
  found = {"scope_seconds": scope_seconds}
  if kernels:
    found.update(kernel_seconds=seconds, calls=calls)
  return found


class ScopedSession(tokens_driver.Session):
  """`train_resident_tokens.Session`'s seeds, K-stack and stand-ins (the
  reference one precision down, the frozen leaf, the module's `FAULTS`)
  with a set-up, window, `release` and comparison read off class
  attributes, for a sequence model whose step metrics and scopes are
  its own:

    COMPARED   {name: the program's step metric}: read at the first
               dispatch's last step and held against the reference's
               output `name`;
    FOLLOWED   the reference's outputs the follower hands back (those
               of COMPARED and any printed beside them);
    COUNTERS   the step metrics the window record carries, last step.

  A subclass gives these, `compare(first, followed, limits)` and
  `scopes()`, and may add to the window record (`window_extras`)."""
  COMPARED = {}
  FOLLOWED = ()
  COUNTERS = ()

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
    self._tokens = int(traffic["sequence_length"])
    if self._tokens != config["sequence_length"]:
      raise ValueError("the traffic's sequence_length is not the "
                       "configuration's")
    self._followed = None
    self._window = None

    clock = harness.Phases()
    mesh = mesh_lib.create_mesh(devices=devices)
    self._trainer = Trainer(harness.build_model(config), mesh=mesh)
    state = self._trainer.create_train_state()
    jax.block_until_ready(state)
    clock.mark("create_train_state")
    # The benchmark's own weights, in the program's layout; the
    # program's own are given up first.
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    ours = shape({"params": state.params, **state.model_state})
    for leaf in jax.tree_util.tree_leaves(state.params):
      leaf.delete()
    _, self._norms = _seed_fns(self._module, _sizes_json(config))
    params = jax.jit(
        lambda key: self._module.init_variables(key, config)["params"],
        out_shardings=mesh_lib.replicated_sharding(mesh))(self._weights_key())
    if ours != shape({"params": params}):
      raise RuntimeError("the reference's parameter tree is not the "
                         "program's: " + str(ours))
    state = state.replace(params=params)
    del params
    jax.block_until_ready(state)
    clock.mark("seeded_weights")
    features, _ = jax.jit(
        self._make_stack, out_shardings=mesh_lib.stacked_batch_sharding(
            mesh))(self._data_key())
    self._features = ts.TensorSpecStruct(features)
    jax.block_until_ready(features)
    clock.mark("seeded_batches")

    # First dispatch: the window's own call on the window's own feed;
    # what the comparison needs of it goes to the host at once.
    with span("bench/first_dispatch"):
      state, metrics = self._trainer.train_steps(state, self._features)
      self._first = jax.device_get(dict(
          self._norms(self._weights_key(), state.params,
                      _first_moment(state.opt_state)),
          loss=metrics["loss"],
          **{ours: metrics[theirs] for ours, theirs in self.COMPARED.items()}))
    clock.mark("first_dispatch")
    # Second dispatch: every later call of the window is this one.
    state, metrics = self._trainer.train_steps(state, self._features)
    jax.block_until_ready(metrics["loss"])
    clock.mark("second_dispatch")
    clock.say()
    self._state = state
    self._dispatches = 2
    self._last_loss = None

  # --- the measured window -------------------------------------------------

  def run_window(self, seconds):
    span, trainer = self._span, self._trainer
    state, features = self._state, self._features
    pending = []
    done = 0
    metrics = None
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
      with span("bench/dispatch"):
        state, metrics = trainer.train_steps(state, features)
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
    counters = {"dispatches": done, "scan_steps": self._steps,
                "global_batch": self._batch,
                "tokens": examples * self._tokens}
    if metrics is not None:
      # The last step's counters, read after the window's clock stopped;
      # the compared ones at the first dispatch's last step beside them.
      counters["loss_main"] = float(metrics["loss_main"])
      for name in self.COUNTERS:
        counters[name] = np.asarray(metrics[name]).tolist()
      for ours, theirs in self.COMPARED.items():
        counters["first/" + theirs] = np.asarray(self._first[ours]).tolist()
    self._window = {
        "attempted": done, "failed": 0, "window_s": window_s,
        "examples": examples,
        "metrics": {
            "train_examples_per_s": examples / window_s / self._chips},
        "counters": counters,
    }
    if metrics is not None:
      self._window.update(self.window_extras(counters))
    return self._window

  def window_extras(self, counters):
    """Records beside the window's counters, read from them."""
    del counters
    return {}

  def scopes(self):
    """((record, named scope, its Pallas programs' names), ...): in a
    traced run each scope's device seconds go into the window record
    under `record`."""
    return ()

  def release(self):
    """Frees the program's state, and in a traced run reads each of
    `scopes()`' seconds out of the trace into the window record. Their
    operations are told by the compiled step's own text, asked of the
    trainer while its arguments still stand (the program is in the
    compile cache by now)."""
    from benchmark.trace import reduce as reduce_lib
    trace_dir = os.path.join(harness.ROOT, "benchmark_out", "trace",
                             self._cell.name)
    text = None
    if self._window is not None:
      try:
        reduce_lib.find_xplane(trace_dir)
        text = self._trainer.aot_train_steps(
            self._state, self._features).as_text()
      except FileNotFoundError:
        pass
    self._final_step = int(self._state.step)
    for leaf in jax.tree_util.tree_leaves((self._state, self._features)):
      leaf.delete()
    self._state = self._features = None
    self._trainer = None
    if text is None:
      return
    for name, scope, kernels in self.scopes():
      found = scoped_seconds(trace_dir, scope_instructions(text, scope),
                             dict(zip(("fwd", "bwd"), kernels)))
      if found is not None and found["scope_seconds"]:
        self._window[name] = found
        print(f"[bench] {name} " + json.dumps(found), flush=True)

  # --- the comparison ------------------------------------------------------

  def _follow(self, precision="f32", fault=None):
    features, _ = jax.jit(self._make_stack)(self._data_key())
    return follow(self._module, self._cell.config, self._weights_key(),
                  features, precision, fault, self.FOLLOWED)

  def check(self, limits, precision="f32", fault=None,
            freeze_smallest=False):
    """[(name, value, limit)]; `precision`/`fault`/`freeze_smallest`
    other than the defaults put the reference itself, computed lower or
    broken, in the program's place (the controls)."""
    if self._followed is None:
      self._followed = self._follow()
    followed = self._followed
    if precision != "f32" or fault is not None:
      first = self._follow(precision, fault)
    elif freeze_smallest:
      first = dict(followed, change=with_smallest_leaf_unmoved(followed))
    else:
      first = self._first
    return self.compare(first, followed, limits) + [
        ("step_count_gap",
         abs(self._final_step - self._dispatches * self._steps), 0)]


def leaf_numbers(first, followed):
  """The numbers every token cell reads of two runs' leaf norms: the
  last step's loss, Adam's first moment and the parameters' change by
  the worst and the median leaf, each against its own norm, and the
  smallest counted leaf's share. The worst leaves are printed, each
  with its first gradient's share of the median leaf's (the reference's
  first step)."""
  skip = ref_train.flat_gradient_leaves(followed["first_grad"])
  first_change, ref_change = first["change"], followed["change"]
  moments = ref_train.leaf_gaps(first["moment"], followed["moment"], skip)
  changes = ref_train.leaf_gaps(first_change, ref_change, skip)
  # The same two without the median leaf's floor: a small leaf that has
  # not moved reads 1 here.
  own_moments = ref_train.own_gaps(first["moment"], followed["moment"], skip)
  own_changes = ref_train.own_gaps(first_change, ref_change, skip)
  worst = dict(zip(("moment", "change", "own moment", "own change"), map(
      ref_train.worst_of, (moments, changes, own_moments, own_changes))))
  smallest = ref_train.smallest_leaf(ref_change, skip)
  grads = ref_train._leaf_norms(followed["first_grad"])
  median_grad = statistics.median(grads.values())
  print("[bench] worst leaves: " + " ".join(
      f"{kind} {leaf} (first gradient %.3g of the median leaf's)"
      % (grads.get(leaf, float("nan")) / median_grad)
      for kind, (_, leaf) in worst.items())
        + f" skipped {len(skip)}; the smallest counted leaf's change is "
        "%.4g of the median leaf's (%s)" % smallest, flush=True)
  return {
      "last_loss_gap": (abs(float(first["loss"]) - float(followed["loss"]))
                        / abs(float(followed["loss"]))),
      "moment_norm_gap": worst["moment"][0],
      "moment_median_gap": statistics.median(moments.values()),
      "change_norm_gap": worst["change"][0],
      "change_median_gap": statistics.median(changes.values()),
      "moment_own_gap": worst["own moment"][0],
      "change_own_gap": worst["own change"][0],
      "smallest_leaf_share": smallest[0],
  }


def compare(first, followed, limits):
  """[(name, value, limit)] from two runs' leaf norms and last-step
  numbers; a number the cell's limits file does not name has the limit
  None: it is read and printed, not compared."""
  # Each layer's number, the widest gap of the layers, against the
  # reference's own.
  layer_gap = lambda name: float(np.max(
      np.abs(np.asarray(first[name], np.float64)
             - np.asarray(followed[name], np.float64))
      / np.abs(np.asarray(followed[name], np.float64))))
  numbers = dict(
      leaf_numbers(first, followed),
      # The share of the selection scores inside the blocks attended to.
      sala_mass_gap=layer_gap("sala_selected_mass"),
      # The decays Lightning applied.
      lightning_decay_gap=layer_gap("lightning_decay_mean"),
      # The reference's own: the smallest mean square of a Lightning
      # head's output before its norm, over tokens and layers. Near
      # `rms_norm_eps`, the norm divides that token's gradients by its
      # root, and the q/k projections' worst-leaf gaps read rounding
      # magnified so.
      lightning_output_floor=float(np.min(followed["lightning_output_floor"])))
  return [(name, value, limits.get(name)) for name, value in numbers.items()]


class Session(ScopedSession):
  """The sparse/linear cell's: each sparse layer's selected mass and
  each Lightning layer's mean decay compared, the visits counted, the
  selection, the block-sparse kernel and Lightning's walk timed."""
  COMPARED = {"sala_selected_mass": "sala/selected_mass",
              "lightning_decay_mean": "lightning/decay_mean"}
  FOLLOWED = tuple(COMPARED) + ("lightning_output_floor",)
  COUNTERS = ("sala/selected_mass", "sala/visited_blocks",
              "sala/selected_blocks", "lightning/decay_mean")
  compare = staticmethod(compare)

  def scopes(self):
    from tensor2robot_tpu.ops.lightning_attention import (
        KERNEL_NAMES as LIGHTNING_KERNELS)
    from tensor2robot_tpu.ops.sparse_attention import (
        KERNEL_NAMES as SPARSE_KERNELS)
    return (("block_selection", SELECT_SCOPE, ()),
            ("sparse_attention", ATTEND_SCOPE, SPARSE_KERNELS),
            ("lightning_attention", SCAN_SCOPE, LIGHTNING_KERNELS))

  def window_extras(self, counters):
    # For `sparse_visit_share.train`: over the sparse layers.
    return {"sparse_visits": {
        "visited": sum(counters["sala/visited_blocks"]),
        "selected": sum(counters["sala/selected_blocks"])}}

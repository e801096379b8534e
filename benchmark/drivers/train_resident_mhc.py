"""Driver `train_resident_mhc`: `train_resident_tokens` for a sequence
model whose residual is several streams wide (manifold-constrained
hyper-connections) and whose MTP head the cell leaves out. K scanned
optimizer steps per dispatch through `Trainer.train_steps`, the K-stack
of seeded token sequences resident on the device and re-fed every
dispatch, a bounded number in flight; one example is one sequence.

Traffic parameters (benchmark/traffic/<name>.json): as
`train_resident_tokens`'s (sequence_length, batch_per_chip, scan_steps,
in_flight).

Why a driver of its own: `train_resident_tokens` reads the program's
`loss_mtp` and divides by the reference's in its comparison,
`train_resident_hybrid` reads `metrics["gdn/..."]`, and neither hands
back an `mhc/*` counter or reads the stream passes' seconds out of the
trace. What they have that serves as it is comes from there (the seeded
weights and the leaf norms, the flash kernel's reader, the frozen-leaf
fault, the compiled step's instructions under a scope, and of the
`Session` the seeds, the K-stack and the list of stand-ins); the set-up,
the follower and the comparison are this file's.

What is compared (see `check`): the first dispatch, which set-up drives
through the window's own call on the window's own K-stack, against the
plain reference following the same K steps from the same seed: the last
step's loss, Adam's first moment and the parameters' change by the worst
leaf, how many leaves did not move, the tokens each held expert saw in
each layer at the last step, each sublayer's mean diagonal of H_res,
mean H_pre and mean H_post over the tokens at the last step, and the
step counter.

In a traced run `release()` reads out of the trace, which still stands
then, the device time of the flash kernel's programs
(`attention_kernel`) and of the hyper-connections' stream passes
(`hyper_connection`: every device operation whose instruction the
compiled step names under the scope `mhc/`, the Pallas programs and
XLA's operations around them, first run, recomputed and backward), and
hands them on in the window record.
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
    _norm, _seed_fns, _sizes_json, attention_kernel_seconds,
    with_smallest_leaf_unmoved)
from benchmark.reference import train as ref_train

# The reference's outputs beside the losses, and the program's step
# metrics they are held against.
_COUNTERS = {"expert_tokens": "moe/expert_tokens",
             "mhc_res_diag_mean": "mhc/res_diag_mean",
             "mhc_pre_mean": "mhc/pre_mean",
             "mhc_post_mean": "mhc/post_mean",
             "mhc_sinkhorn_gap": "mhc/sinkhorn_gap"}
MHC_SCOPE = "mhc/"
_KERNEL_PREFIX = "hyper_connection_"


@functools.lru_cache(maxsize=None)
def _step_fn(module, config_json, precision, fault):
  """One jitted Adam step of the reference `module`; kept, so that
  following again (a control, another seed) traces nothing anew."""
  config = json.loads(config_json)
  opt = {k: v for k, v in config["optimizer"].items()
         if isinstance(v, (int, float))}
  update = ref_train._OPTIMIZERS[config["optimizer"]["kind"]]

  def loss_fn(params, features):
    outputs, _ = module.forward({"params": params}, features, True,
                                precision, config, fault)
    total, _ = module.loss(outputs, features, None, config, fault)
    return total, {name: outputs[name] for name in _COUNTERS}

  @functools.partial(jax.jit, donate_argnums=(0, 1))
  def step(params, state, features, index):
    (value, counters), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, features)
    norms = jax.tree_util.tree_map(_norm, grads)
    params, state = update(opt, params, grads, state, index)
    return params, state, value, counters, norms

  return step


def follow(module, config, key, features, precision="f32", fault=None):
  """One optimizer step per leading index of `features`, from the
  parameters the reference seeds from `key`. Returns, on the host,
  {"loss", "expert_tokens", "mhc_*"} of the last step, "change" and
  "moment" (each leaf's norm of the parameters' change and of Adam's
  first moment after the K steps) and "first_grad" (each leaf's norm of
  the first step's gradients)."""
  sizes = _sizes_json(config)
  step = _step_fn(module, sizes, precision, fault)
  seeded, norms_fn = _seed_fns(module, sizes)
  params = seeded(key)
  state = {"moment": ref_train.init_moment(params),
           "nu": ref_train.init_moment(params)}
  first_grad = None
  num_steps = jax.tree_util.tree_leaves(features)[0].shape[0]
  for k in range(num_steps):
    feats = jax.tree_util.tree_map(lambda x: x[k], features)
    params, state, value, counters, norms = step(
        params, state, feats, jnp.asarray(k, jnp.float32))
    if k == 0:
      first_grad = norms
  out = jax.device_get(dict(
      norms_fn(key, params, state["moment"]), loss=value,
      first_grad=first_grad, **counters))
  for leaf in jax.tree_util.tree_leaves((params, state)):
    leaf.delete()
  return out


def hyper_connection_seconds(trace_dir, scope_names):
  """{"scope_seconds": device self time of every operation of the trace
  under `trace_dir` whose instruction is one of `scope_names` (the
  compiled step's under `mhc/`), "kernel_seconds": {program: self time}
  and "calls": {program: n} of the stream passes' Pallas programs
  ("pre_fwd", "post_fwd", ...: `ops/hyper_connection.KERNEL_NAMES`
  without their common prefix) among them}; None where there is no
  trace, where the program has no such kernels, where one of its
  programs is not in the trace, or where a call of them is not among
  `scope_names` (the names of the trace and of the compiled text did not
  join: a share of part of the passes' time against all of their work is
  never made)."""
  from benchmark.trace import reduce as reduce_lib
  try:
    from tensor2robot_tpu.ops.hyper_connection import KERNEL_NAMES
    loaded = reduce_lib.load(reduce_lib.find_xplane(trace_dir))
  except (ImportError, FileNotFoundError):
    return None
  kernels = {name[len(_KERNEL_PREFIX):]: name for name in KERNEL_NAMES}
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
        if instruction(op[0]) not in scope_names:
          return None
        calls[program_of(op[0])] += 1
    for op_name, total in reduce_lib.self_seconds(ops).items():
      if instruction(op_name) in scope_names:
        scope_seconds += total
      if program_of(op_name):
        seconds[program_of(op_name)] += total
  if not all(calls.values()):
    return None
  return {"scope_seconds": scope_seconds, "kernel_seconds": seconds,
          "calls": calls}


class Session(tokens_driver.Session):
  """`train_resident_tokens.Session`'s seeds, K-stack and stand-ins (the
  reference one precision down, the frozen leaf, the module's `FAULTS`);
  the set-up, the window's record, `release` and the comparison its
  own."""

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
    replicated = mesh_lib.replicated_sharding(mesh)
    stacked = mesh_lib.stacked_batch_sharding(mesh)

    # The benchmark's own weights, in the program's layout. The
    # program's own are given up first: the draw and its leaves do not
    # fit beside them and the optimizer's state.
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    ours = shape({"params": state.params, **state.model_state})
    for leaf in jax.tree_util.tree_leaves(state.params):
      leaf.delete()
    _, self._norms = _seed_fns(self._module, _sizes_json(config))
    params = jax.jit(
        lambda key: self._module.init_variables(key, config)["params"],
        out_shardings=replicated)(self._weights_key())
    if ours != shape({"params": params}):
      raise RuntimeError("the reference's parameter tree is not the "
                         "program's: " + str(ours))
    state = state.replace(params=params)
    del params
    jax.block_until_ready(state)
    clock.mark("seeded_weights")
    features, _ = jax.jit(
        self._make_stack, out_shardings=stacked)(self._data_key())
    self._features = ts.TensorSpecStruct(features)
    jax.block_until_ready(features)
    clock.mark("seeded_batches")

    # First dispatch: the window's own call on the window's own feed;
    # what the comparison needs of it (each leaf's norm, the change
    # against the seeded weights made again) goes to the host at once.
    with span("bench/first_dispatch"):
      state, metrics = self._trainer.train_steps(state, self._features)
      self._first = jax.device_get(dict(
          self._norms(self._weights_key(), state.params,
                      _first_moment(state.opt_state)),
          loss=metrics["loss"],
          **{ours: metrics[theirs] for ours, theirs in _COUNTERS.items()}))
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
    pending, held = [], []
    done = 0
    metrics = None
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
      with span("bench/dispatch"):
        state, metrics = trainer.train_steps(state, features)
      pending.append(metrics["loss"])
      held.append(metrics["moe/held_assignments"])
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
      # the run trains as it goes, so the router's choices and the maps
      # drift: the same counts at the first dispatch's last step beside
      # them, the held assignments' mean over the window's dispatches,
      # and a sequence's operations with the routed experts counted at
      # that mean (`step_mfu.train` counts them at a balanced router's).
      counters.update({
          name: float(metrics[name]) for name in (
              "moe/held_assignments", "moe/total_assignments",
              "moe/max_expert_tokens", "moe/min_expert_tokens",
              "loss_main")})
      first = np.asarray(self._first["expert_tokens"])
      held_mean = float(np.mean([float(h) for h in held]))
      counters.update({
          "moe/held_assignments_window_mean": held_mean,
          "train_flops_per_example_at_window_mean": (
              self._cell.flops.train_per_example(
                  self._cell.config, held_mean / (
                      self._batch * self._tokens * first.shape[0]))),
          "first/held_assignments": float(first.sum()),
          "first/max_expert_tokens": float(first.max()),
          "first/min_expert_tokens": float(first.min())})
      # (layers, 2) each, by sublayer (attention, feed-forward).
      for ours, theirs in _COUNTERS.items():
        if theirs.startswith("mhc/"):
          counters[theirs] = np.asarray(metrics[theirs]).tolist()
          counters["first/" + theirs] = np.asarray(
              self._first[ours]).tolist()
    self._window = {
        "attempted": done, "failed": 0, "window_s": window_s,
        "examples": examples,
        "metrics": {
            "train_examples_per_s": examples / window_s / self._chips},
        "counters": counters,
    }
    return self._window

  def release(self):
    """Frees the program's state, and in a traced run reads the flash
    kernel's and the stream passes' seconds out of the trace into the
    window record. The passes' operations are told by the compiled
    step's own text, asked of the trainer while its arguments still
    stand (the program is in the compile cache by now)."""
    from benchmark.trace import reduce as reduce_lib
    trace_dir = os.path.join(harness.ROOT, "benchmark_out", "trace",
                             self._cell.name)
    scope_names = None
    if self._window is not None:
      try:
        reduce_lib.find_xplane(trace_dir)
        scope_names = scope_instructions(
            self._trainer.aot_train_steps(
                self._state, self._features).as_text(), MHC_SCOPE)
      except FileNotFoundError:
        pass
    self._final_step = int(self._state.step)
    for leaf in jax.tree_util.tree_leaves((self._state, self._features)):
      leaf.delete()
    self._state = self._features = None
    self._trainer = None
    if scope_names is not None:
      for name, found in (
          ("attention_kernel", attention_kernel_seconds(trace_dir)),
          ("hyper_connection", hyper_connection_seconds(
              trace_dir, scope_names))):
        if found is not None:
          self._window[name] = found
          print(f"[bench] {name} " + json.dumps(found), flush=True)

  # --- the comparison ------------------------------------------------------

  def _follow(self, precision="f32", fault=None):
    features, _ = jax.jit(self._make_stack)(self._data_key())
    return follow(self._module, self._cell.config, self._weights_key(),
                  features, precision, fault)

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
    return compare(first, followed, limits) + [
        ("step_count_gap",
         abs(self._final_step - self._dispatches * self._steps), 0)]


def unmoved_leaves(change, ref_change, skip=(), share=0.1):
  """How many counted leaves changed by under `share` of what the
  reference's did (by norm). `change_own_gap` reads 1 for a leaf left
  unmoved, but here it reads 0.1-0.7 on sound runs too: where a map's
  gradient is rounding alone (the first sublayer's H_res mixes four
  copies of the embedding and moves nothing), Adam steps by rounding's
  signs, and a leaf of 3 or 24 numbers with such entries moves as far as
  the reference's without moving alike. A count of leaves that did not
  move at all does not see that."""
  ours = ref_train._leaf_norms(change)
  return sum(1 for name, norm in ref_train._leaf_norms(ref_change).items()
             if name not in skip and norm > 0 and ours[name] < share * norm)


def compare(first, followed, limits):
  """[(name, value, limit)] from two runs' leaf norms and last-step
  numbers; a number the cell's limits file does not name has the limit
  None: it is read and printed, not compared."""
  skip = ref_train.flat_gradient_leaves(followed["first_grad"])
  first_change, ref_change = first["change"], followed["change"]
  moments = ref_train.leaf_gaps(first["moment"], followed["moment"], skip)
  changes = ref_train.leaf_gaps(first_change, ref_change, skip)
  # The same two without the median leaf's floor: a small leaf that has
  # not moved reads 1 here.
  own_moments = ref_train.own_gaps(first["moment"], followed["moment"], skip)
  own_changes = ref_train.own_gaps(first_change, ref_change, skip)
  (moment_gap, moment_leaf), (change_gap, change_leaf), (
      own_moment, own_moment_leaf), (own_change, own_change_leaf) = map(
          ref_train.worst_of, (moments, changes, own_moments, own_changes))
  smallest = ref_train.smallest_leaf(ref_change, skip)
  print(f"[bench] worst leaves: moment {moment_leaf} change {change_leaf} "
        f"own moment {own_moment_leaf} own change {own_change_leaf} "
        f"skipped {len(skip)}; the smallest counted leaf's change is "
        "%.4g of the median leaf's (%s)" % smallest, flush=True)
  counts = np.asarray(followed["expert_tokens"], np.float64)
  # Each sublayer's mean over the tokens, the widest gap of the (layers,
  # 2) sublayers, against the reference's own mean.
  sublayer_gap = lambda name: float(np.max(
      np.abs(np.asarray(first[name], np.float64)
             - np.asarray(followed[name], np.float64))
      / np.abs(np.asarray(followed[name], np.float64))))
  numbers = {
      "last_loss_gap": (abs(float(first["loss"]) - float(followed["loss"]))
                        / abs(float(followed["loss"]))),
      "moment_norm_gap": moment_gap,
      "moment_median_gap": statistics.median(moments.values()),
      "change_norm_gap": change_gap,
      "change_median_gap": statistics.median(changes.values()),
      "moment_own_gap": own_moment,
      "change_own_gap": own_change,
      "smallest_leaf_share": smallest[0],
      # Tokens on each held expert in each layer at the last step: L1
      # distance over the reference's total.
      "expert_count_gap": float(
          np.abs(np.asarray(first["expert_tokens"], np.float64)
                 - counts).sum() / counts.sum()),
      "mhc_res_diag_gap": sublayer_gap("mhc_res_diag_mean"),
      "mhc_pre_gap": sublayer_gap("mhc_pre_mean"),
      "mhc_post_gap": sublayer_gap("mhc_post_mean"),
      # The largest |row sum - 1| of any token's H_res: the program's
      # own, not a gap (Sinkhorn's last column division leaves it).
      "mhc_sinkhorn_gap": float(np.max(first["mhc_sinkhorn_gap"])),
      "unmoved_leaf_count": unmoved_leaves(first_change, ref_change, skip),
  }
  return [(name, value, limits.get(name)) for name, value in numbers.items()]

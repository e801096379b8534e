"""Driver `train_resident_tokens`: `train_resident` for a sequence model
whose state nearly fills the chip. K scanned optimizer steps per
dispatch through `Trainer.train_steps`, the K-stack of seeded token
sequences resident on the device and re-fed every dispatch, a bounded
number in flight; one example is one sequence.

Traffic parameters (benchmark/traffic/<name>.json):
  sequence_length  tokens a sequence (the configuration's own)
  batch_per_chip   sequences per chip and step
  scan_steps       K, optimizer steps per dispatch
  in_flight        dispatches the host may run ahead of the device

Why a driver of its own: the model has no `batch_stats`; device copies of
the first dispatch's parameters and first moment (2 x 2.7 GB) do not fit
beside a 10.9 GB state, and host copies of them, of the reference's and
of every control's met the machine's 40 GiB; and the reference has to be
followed with four parameter-sized trees on the device and no more
(parameters, both Adam moments, gradients). The measures of
`reference/train.py`, unchanged, read nothing of a leaf but its norm, so
what leaves the device is each leaf's norm: of the first moment, of the
parameters' change (against the seeded weights, made again from the seed
once the steps are done) and of the first gradients, as trees of
one-element leaves.

What is compared (see `check`): the first dispatch, which set-up drives
through the window's own call on the window's own K-stack, against the
plain reference following the same K steps from the same seed: the last
step's loss (whole, main and MTP), Adam's first moment and the
parameters' change by the worst leaf, the tokens each held expert saw in
each layer at the last step, and the step counter.

In a traced run `release()` also reads the attention kernel's device
time out of the trace, which still stands then, and hands it on in the
window record for `mla_attention_roofline.train`.
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
from benchmark.drivers.train_resident import _first_moment
from benchmark.reference import train as ref_train

def _norm(x):
  return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))).reshape(1)


def _sizes_json(config):
  """The sizes as the reference reads them (not the program's model
  class and arguments), as a cache's key."""
  return json.dumps({k: v for k, v in config.items() if k != "model"},
                    sort_keys=True)


@functools.lru_cache(maxsize=None)
def _seed_fns(module, config_json):
  """(key -> the seeded parameters; (key, params, moment) -> {"change":
  each leaf's norm of `params` less the seeded parameters, "moment":
  each leaf's norm of `moment`}), jitted. The norms are trees of
  one-element leaves, which the measures read as they read the trees
  themselves; the seeded parameters the change is taken against are
  made again inside that program, as a temporary of it."""
  config = json.loads(config_json)
  seeded = lambda key: module.init_variables(key, config)["params"]

  def norms(key, params, moment):
    return {"change": jax.tree_util.tree_map(
        lambda a, b: _norm(a - b), params, seeded(key)),
            "moment": jax.tree_util.tree_map(_norm, moment)}

  return jax.jit(seeded), jax.jit(norms)


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
    total, parts = module.loss(outputs, features, None, config, fault)
    return total, (parts, outputs["expert_tokens"])

  @functools.partial(jax.jit, donate_argnums=(0, 1))
  def step(params, state, features, index):
    (value, (parts, counts)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, features)
    norms = jax.tree_util.tree_map(_norm, grads)
    params, state = update(opt, params, grads, state, index)
    return params, state, value, parts, counts, norms

  return step


def follow(module, config, key, features, precision="f32", fault=None):
  """One optimizer step per leading index of `features`, from the
  parameters the reference seeds from `key`. Returns, on the host,
  {"loss", "loss_main", "loss_mtp", "expert_tokens"} of the last step,
  "change" and "moment" (each leaf's norm of the parameters' change and
  of Adam's first moment after the K steps) and "first_grad" (each
  leaf's norm of the first step's gradients)."""
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
    params, state, value, parts, counts, norms = step(
        params, state, feats, jnp.asarray(k, jnp.float32))
    if k == 0:
      first_grad = norms
  out = jax.device_get(dict(
      norms_fn(key, params, state["moment"]),
      loss=value, loss_main=parts["loss_main"],
      loss_mtp=parts.get("loss_mtp", jnp.zeros(())),
      expert_tokens=counts, first_grad=first_grad))
  for leaf in jax.tree_util.tree_leaves((params, state)):
    leaf.delete()
  return out


def attention_kernel_seconds(trace_dir):
  """{"seconds": {program: device self time}, "calls": {program: n}} of
  the attention kernel's three programs over every device operation of
  the trace under `trace_dir`; None where there is no trace or where one
  of the three is not in it (a share of part of the kernel's time
  against all of its work is never made)."""
  from benchmark.trace import reduce as reduce_lib
  from tensor2robot_tpu.ops.flash_attention import KERNEL_NAMES
  # As the device trace names their operations:
  # '%flash_attention_fwd.82 = ... custom-call(...)'.
  kernels = dict(zip(("fwd", "dq", "dkv"), KERNEL_NAMES))
  try:
    loaded = reduce_lib.load(reduce_lib.find_xplane(trace_dir))
  except FileNotFoundError:
    return None
  seconds = {key: 0.0 for key in kernels}
  calls = {key: 0 for key in kernels}
  program_of = lambda op_name: next(
      (key for key, name in kernels.items()
       if op_name.lstrip("%").startswith(name)), None)
  for ops in loaded["devices"].values():
    for op in ops:
      if program_of(op[0]):
        calls[program_of(op[0])] += 1
    for op_name, total in reduce_lib.self_seconds(ops).items():
      if program_of(op_name):
        seconds[program_of(op_name)] += total
  if not all(calls.values()):
    return None
  return {"seconds": seconds, "calls": calls}


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
          loss=metrics["loss"], loss_main=metrics["loss_main"],
          loss_mtp=metrics["loss_mtp"],
          expert_tokens=metrics["moe/expert_tokens"]))
    clock.mark("first_dispatch")
    # Second dispatch: every later call of the window is this one.
    state, metrics = self._trainer.train_steps(state, self._features)
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
      # the run trains as it goes, so the router's choices drift: the
      # same counts at the first dispatch's last step beside them, the
      # held assignments' mean over the window's dispatches, and a
      # sequence's operations with the routed experts counted at that
      # mean (`step_mfu.train` counts them at a balanced router's).
      counters.update({
          name: float(metrics[name]) for name in (
              "moe/held_assignments", "moe/total_assignments",
              "moe/max_expert_tokens", "moe/min_expert_tokens",
              "loss_main", "loss_mtp")})
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
    self._window = {
        "attempted": done, "failed": 0, "window_s": window_s,
        "examples": examples,
        "metrics": {
            "train_examples_per_s": examples / window_s / self._chips},
        "counters": counters,
    }
    return self._window

  def release(self):
    """Frees the program's state, and in a traced run reads the
    attention kernel's seconds out of the trace into the window
    record."""
    self._final_step = int(self._state.step)
    for leaf in jax.tree_util.tree_leaves((self._state, self._features)):
      leaf.delete()
    self._state = self._features = None
    self._trainer = None
    if self._window is not None:
      found = attention_kernel_seconds(os.path.join(
          harness.ROOT, "benchmark_out", "trace", self._cell.name))
      if found is not None:
        self._window["attention_kernel"] = found

  # --- the comparison ------------------------------------------------------

  def controls(self):
    """Stand-ins for the program that have to come out not correct: the
    reference one precision below what the configuration states, and
    the reference with one fault planted (half the positions left out
    of the loss, the leaf that moves least returned unchanged, 4 experts
    a token for 8, weights normalized over the held experts only, the
    choice without the correction bias, the loss without its MTP
    term)."""
    stand_ins = {"control_fp8": {"precision": "fp8"},
                 "fault_smallest_leaf_frozen": {"freeze_smallest": True}}
    for fault in self._module.FAULTS:
      stand_ins["fault_" + fault] = {"fault": fault}
    return stand_ins

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


def with_smallest_leaf_unmoved(followed):
  """The followed reference's change with the counted leaf that moved
  least not moved at all (its norm nought): a planted fault."""
  skip = ref_train.flat_gradient_leaves(followed["first_grad"])
  _, name = ref_train.smallest_leaf(followed["change"], skip)
  return jax.tree_util.tree_map_with_path(
      lambda path, x: (np.zeros_like(x)
                       if jax.tree_util.keystr(path) == name else x),
      followed["change"])


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
  gap = lambda name: (abs(float(first[name]) - float(followed[name]))
                      / abs(float(followed[name])))
  counts = np.asarray(followed["expert_tokens"], np.float64)
  numbers = {
      "last_loss_gap": gap("loss"),
      "last_main_loss_gap": gap("loss_main"),
      "last_mtp_loss_gap": gap("loss_mtp"),
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
  }
  return [(name, value, limits.get(name)) for name, value in numbers.items()]

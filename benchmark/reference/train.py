"""Plain training reference: follows K optimizer steps of a
configuration's reference module and measures a program's state
against what it finds.

The optimizers are written out (no optax): SGD with momentum as
`trace = g + m * trace; w -= lr * trace`, Adam with bias correction as
Kingma & Ba 2015. Imports nothing of the program.
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np


def init_moment(params):
  return jax.tree_util.tree_map(jnp.zeros_like, params)


def _sgd_momentum(opt, params, grads, state, step):
  del step
  trace = jax.tree_util.tree_map(
      lambda g, t: g + opt["momentum"] * t, grads, state["moment"])
  params = jax.tree_util.tree_map(
      lambda w, t: w - opt["learning_rate"] * t, params, trace)
  return params, {"moment": trace}


def _adam(opt, params, grads, state, step):
  b1, b2, eps = opt.get("b1", 0.9), opt.get("b2", 0.999), opt.get("eps", 1e-8)
  mu = jax.tree_util.tree_map(
      lambda g, m: b1 * m + (1 - b1) * g, grads, state["moment"])
  nu = jax.tree_util.tree_map(
      lambda g, v: b2 * v + (1 - b2) * jnp.square(g), grads, state["nu"])
  t = step + 1
  c1, c2 = 1 - b1 ** t, 1 - b2 ** t
  params = jax.tree_util.tree_map(
      lambda w, m, v: w - opt["learning_rate"] * (m / c1)
      / (jnp.sqrt(v / c2) + eps), params, mu, nu)
  return params, {"moment": mu, "nu": nu}


_OPTIMIZERS = {"sgd_momentum": _sgd_momentum, "adam": _adam}


@functools.lru_cache(maxsize=None)
def _step_fn(module, kind, hyper, precision, keep_rows):
  """One jitted optimizer step of `module`; kept, so that following
  again (a control, another seed) traces and loads nothing anew."""
  opt = dict(hyper)
  update = _OPTIMIZERS[kind]

  def loss_fn(params, stats, feats, labs):
    if keep_rows is not None:
      feats, labs = jax.tree_util.tree_map(
          lambda x: x[:keep_rows], (feats, labs))
    outputs, new_stats = module.forward(
        {"params": params, "batch_stats": stats}, feats, True, precision)
    return module.loss(outputs, feats, labs), new_stats

  @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
  def step(params, stats, state, feats, labs, index):
    (value, new_stats), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, stats, feats, labs)
    params, state = update(opt, params, grads, state, index)
    return params, new_stats, state, value, grads

  return step


def follow(module, opt, variables, features, labels, precision="f32",
           keep_rows=None):
  """Runs one optimizer step per leading index of `features`/`labels`.

  Returns {"losses": (K,), "params": final, "batch_stats": final,
  "moment": the optimizer's first moment after the K steps,
  "first_grad": the gradients of step one}. `keep_rows` (a fault for
  the controls) takes the loss over the first `keep_rows` rows of every
  batch only.
  """
  hyper = tuple(sorted((k, v) for k, v in opt.items()
                       if isinstance(v, (int, float))))
  step = _step_fn(module, opt["kind"], hyper, precision, keep_rows)
  params = jax.tree_util.tree_map(jnp.copy, variables["params"])
  stats = jax.tree_util.tree_map(jnp.copy, variables["batch_stats"])
  state = {"moment": init_moment(params)}
  if opt["kind"] == "adam":
    state["nu"] = init_moment(params)
  losses, first_grad = [], None
  num_steps = jax.tree_util.tree_leaves(features)[0].shape[0]
  for k in range(num_steps):
    feats, labs = jax.tree_util.tree_map(lambda x: x[k], (features, labels))
    params, stats, state, value, grads = step(
        params, stats, state, feats, labs, jnp.asarray(k, jnp.float32))
    losses.append(value)
    if k == 0:
      first_grad = grads
  return {"losses": jnp.stack(losses), "params": params,
          "batch_stats": stats, "moment": state["moment"],
          "first_grad": first_grad}


# --- measures (on the host: a norm per leaf on the device is a program
# per leaf shape for the compiler) -----------------------------------------


class _HostLeaves(dict):
  """{leaf name: float32 numpy array}, already on the host."""


def _host_leaves(tree):
  if isinstance(tree, _HostLeaves):
    return tree
  leaves = jax.tree_util.tree_leaves_with_path(jax.device_get(tree))
  return _HostLeaves(
      (jax.tree_util.keystr(path), np.asarray(leaf, np.float32))
      for path, leaf in leaves)


def _leaf_norms(tree):
  return {name: float(np.linalg.norm(leaf.ravel()))
          for name, leaf in _host_leaves(tree).items()}


def leaf_gaps(program_tree, reference_tree, skip=()):
  """{leaf name: gap between the program's norm and the reference's,
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger}."""
  prog, ref = _leaf_norms(program_tree), _leaf_norms(reference_tree)
  floor = statistics.median(ref.values())
  return {name: abs(prog[name] - ref_norm) / max(ref_norm, floor)
          for name, ref_norm in ref.items() if name not in skip}


def own_gaps(program_tree, reference_tree, skip=()):
  """{leaf name: gap between the program's norm and the reference's,
  against the reference's norm of that same leaf}: no floor, so a leaf
  far under the median leaf that has not moved, or has moved double,
  reads 1 here where `leaf_gaps` reads its share of the median."""
  prog, ref = _leaf_norms(program_tree), _leaf_norms(reference_tree)
  return {name: abs(prog[name] - ref_norm) / ref_norm
          for name, ref_norm in ref.items()
          if name not in skip and ref_norm > 0}


def smallest_leaf(reference_tree, skip=()):
  """(the smallest counted leaf's norm as a share of the median leaf's,
  the leaf's name): how far under its floor `leaf_gaps` has to see."""
  norms = _leaf_norms(reference_tree)
  floor = statistics.median(norms.values())
  name = min((n for n in norms if n not in skip and norms[n] > 0),
             key=norms.get)
  return norms[name] / floor, name


def with_smallest_leaf_unmoved(after, before, skip=()):
  """`after` with the counted leaf whose change has the smallest norm
  put back to `before`: a planted fault."""
  _, name = smallest_leaf(tree_delta(after, before), skip)
  leaves = _HostLeaves(_host_leaves(after))
  leaves[name] = _host_leaves(before)[name]
  return leaves


def worst_of(gaps):
  """(widest of {leaf name: gap}, its leaf's name); a NaN is the widest."""
  worst, where = 0.0, ""
  for name, gap in gaps.items():
    if gap > worst or not gap == gap:
      worst, where = gap, name
  return worst, where


def worst_leaf_gap(program_tree, reference_tree, skip=()):
  return worst_of(leaf_gaps(program_tree, reference_tree, skip))


def leaf_differences(program_tree, reference_tree):
  """{leaf name: norm of the difference between the program's leaf and
  the reference's, over the reference's norm of that leaf}. For
  quantities that are means over the whole batch (the normalisation
  statistics' change), where rounding moves every leaf in proportion to
  its precision and a gap of norms would hide it."""
  prog = _host_leaves(program_tree)
  return {name: float(np.linalg.norm((prog[name] - ref).ravel())
                      / np.linalg.norm(ref.ravel()))
          for name, ref in _host_leaves(reference_tree).items()}


def flat_gradient_leaves(first_grad, share=1e-3):
  """Leaves whose first gradient is under `share` of the median leaf's:
  nought to rounding in the reference, so Adam moves them by round-off
  alone and their change is not compared."""
  norms = _leaf_norms(first_grad)
  floor = share * statistics.median(norms.values())
  return tuple(name for name, norm in norms.items() if norm < floor)


def tree_delta(after, before):
  after, before = _host_leaves(after), _host_leaves(before)
  return _HostLeaves((name, after[name] - before[name]) for name in after)

"""Each plain reference against the model it stands for, at a small
size on seeded weights: same parameter tree, same forward pass, same
loss and gradients, to float32 when the model computes in float32."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import train as ref_train
from tensor2robot_tpu import modes

CASES = {
    "qtopt_grasping_472": dict(image=48, batch=4),
    "grasp2vec_resnet50_224": dict(image=64, batch=4),
}


def _setup(name):
  with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
    config = json.load(f)
  case = CASES[name]
  config["image_size"] = case["image"]
  spec = config["model"]
  module = __import__(spec["module"], fromlist=[spec["class"]])
  model = getattr(module, spec["class"])(
      image_size=case["image"], compute_dtype=jnp.float32)
  reference = harness._load_module("reference", name)
  variables = reference.init_variables(jax.random.key(3), config)
  features, labels = reference.make_batch(
      jax.random.key(5), config, case["batch"])
  return config, model, reference, variables, features, labels


@pytest.mark.parametrize("name", sorted(CASES))
def test_parameter_tree_is_the_programs(name):
  _, model, _, variables, _, _ = _setup(name)
  theirs = jax.eval_shape(model.init_variables, jax.random.key(0))
  shape = lambda tree: jax.tree_util.tree_map(
      lambda x: (x.shape, str(x.dtype)), dict(tree))
  assert shape(theirs) == shape(variables)


@pytest.mark.parametrize("name", sorted(CASES))
def test_loss_and_gradients_match_in_float32(name):
  _, model, reference, variables, features, labels = _setup(name)
  labels_in = labels or None

  def theirs(params):
    loss, _ = model.model_train_fn(
        {"params": params, "batch_stats": variables["batch_stats"]},
        features, labels_in)
    return loss

  def ours(params):
    outputs, _ = reference.forward(
        {"params": params, "batch_stats": variables["batch_stats"]},
        features, True, "f32")
    return reference.loss(outputs, features, labels)

  with jax.default_matmul_precision("highest"):
    loss_a, grads_a = jax.value_and_grad(theirs)(variables["params"])
  loss_b, grads_b = jax.value_and_grad(ours)(variables["params"])
  assert abs(float(loss_a) - float(loss_b)) < 1e-3 * abs(float(loss_b))
  gap, leaf = ref_train.worst_leaf_gap(
      grads_a, grads_b, skip=ref_train.flat_gradient_leaves(grads_b))
  assert gap < 2e-2, (gap, leaf)


def test_qtopt_predict_mode_matches_in_float32():
  _, model, reference, variables, features, _ = _setup("qtopt_grasping_472")
  with jax.default_matmul_precision("highest"):
    theirs = model.predict_fn(variables, features)["q_predicted"]
  ours, _ = reference.forward(variables, features, False, "f32")
  np.testing.assert_allclose(np.asarray(theirs),
                             np.asarray(ours["q_predicted"]), atol=1e-4)


def test_lower_precisions_read_further_from_float32():
  _, _, reference, variables, features, _ = _setup("qtopt_grasping_472")
  q = {p: np.asarray(reference.forward(variables, features, False, p)[0][
      "q_predicted"]) for p in ("f32", "bf16", "fp8")}
  bf16 = np.max(np.abs(q["bf16"] - q["f32"]))
  fp8 = np.max(np.abs(q["fp8"] - q["f32"]))
  assert 0 < bf16 < fp8


def test_follow_matches_optax_adam_and_momentum():
  import optax
  params = {"w": jnp.arange(6.0).reshape(2, 3) / 7, "b": jnp.ones((3,))}
  grads = jax.tree_util.tree_map(lambda x: jnp.cos(x) * 0.3, params)
  for kind, tx in (
      ("sgd_momentum", optax.sgd(1e-2, momentum=0.9)),
      ("adam", optax.adam(1e-2))):
    opt = {"kind": kind, "learning_rate": 1e-2, "momentum": 0.9}
    state = {"moment": ref_train.init_moment(params),
             "nu": ref_train.init_moment(params)}
    theirs, tx_state, ours = params, tx.init(params), params
    for step in range(3):
      updates, tx_state = tx.update(grads, tx_state, theirs)
      theirs = optax.apply_updates(theirs, updates)
      ours, state = ref_train._OPTIMIZERS[kind](
          opt, ours, grads, state, float(step))
      state.setdefault("nu", ref_train.init_moment(params))
    for a, b in zip(jax.tree_util.tree_leaves(theirs),
                    jax.tree_util.tree_leaves(ours)):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)

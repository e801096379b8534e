"""The analytic operation counts against XLA's own count of one
unscanned forward pass and one unscanned train step (where the count,
blind to loops, is right), at a small size."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness

# Large enough that the taps SAME padding puts outside the image, which
# XLA leaves out and the analytic count keeps, are a few percent.
CASES = {"qtopt_grasping_472": 236, "grasp2vec_resnet50_224": 160}


def _setup(name):
  with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
    config = json.load(f)
  config["image_size"] = CASES[name]
  reference = harness._load_module("reference", name)
  flops = harness._load_module("flops", name)
  variables = jax.eval_shape(
      lambda k: reference.init_variables(k, config), jax.random.key(0))
  batch = jax.eval_shape(
      lambda k: reference.make_batch(k, config, 4), jax.random.key(0))
  return config, reference, flops, variables, batch


def _xla_flops(fn, *args):
  return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("name", sorted(CASES))
def test_forward_count(name):
  config, reference, flops, variables, (features, _) = _setup(name)
  counted = _xla_flops(
      lambda v, f: reference.forward(v, f, False, "f32")[0], variables,
      features)
  per_row = (flops.forward_per_row(config)["total"]
             if hasattr(flops, "forward_per_row")
             else flops.forward_per_example(config)["total"])
  assert 4 * per_row == pytest.approx(counted, rel=0.08)


@pytest.mark.parametrize("name", sorted(CASES))
def test_train_step_count(name):
  config, reference, flops, variables, (features, labels) = _setup(name)

  def grads(params, features, labels):
    def loss(p):
      out, _ = reference.forward(
          {"params": p, "batch_stats": variables["batch_stats"]}, features,
          True, "f32")
      return reference.loss(out, features, labels)
    return jax.grad(loss)(params)

  stats = jax.tree_util.tree_map(
      lambda s: jnp.ones(s.shape, s.dtype), variables["batch_stats"])
  variables = dict(variables, batch_stats=stats)
  counted = _xla_flops(grads, variables["params"], features, labels)
  expected = flops.train_per_example(config)
  if hasattr(flops, "forward_per_example"):
    # This reference rematerialises its blocks: XLA counts a second
    # forward pass, which the analytic count rightly leaves out.
    expected += flops.forward_per_example(config)["total"]
  assert 4 * expected == pytest.approx(counted, rel=0.12)


def test_cem_action_counts_every_scored_row():
  config, _, flops, _, _ = _setup("qtopt_grasping_472")
  rows = config["cem_num_samples"] * config["cem_iterations"] + 1
  assert flops.serve_per_action(config) == rows * flops.forward_per_row(
      config)["total"]

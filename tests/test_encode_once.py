"""Serving encodes each camera frame once (ISSUE 35).

`_GraspingQModule` splits into `encode` (everything that depends on the
frame alone) and `q_from_code` (the action's half); the native artifact
and the in-process predictors offer the pair beside `device_fn()`, and
`CEMFleetPolicy` then encodes a bucket's frames once, outside the
per-robot vmap and the CEM loop, and searches over the code. Pinned here
on the CPU: the split is the same function and the same parameter tree
as the one-method module, serving answers are the tiled path's, the
hoist is in the compiled program, and the counter says when it engages.

Since ISSUE 39 the recipe, where it holds the pair, expands each code
across its candidates on the merged (state, candidate) row axis
(`cem.MergedRowScore`), one Q call for the whole batch: section 3 holds
it to the per-state broadcast form it replaced.
"""

import glob
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import modes
from tensor2robot_tpu.export.native_export_generator import (
    ENCODE_FN_NAME, Q_FROM_CODE_FN_NAME, NativeExportGenerator)
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.predictors.checkpoint_predictor import (
    CheckpointPredictor)
from tensor2robot_tpu.predictors.exported_model_predictor import (
    ExportedModelPredictor)
from tensor2robot_tpu.replay.bellman import make_bellman_targets_fn
from tensor2robot_tpu.replay.smoke import TinyQCriticModel
from tensor2robot_tpu.research.qtopt import cem
from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel
from tensor2robot_tpu.serving.bucketing import BucketLadder
from tensor2robot_tpu.serving.policy import CEMFleetPolicy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 64  # 64 -> 16 -> 8 -> 4 -> 2 -> 1: every layer has something to do


# --- 1. the split module ------------------------------------------------------

_VARIANTS = [
    pytest.param({}, id="parity"),
    pytest.param({"impl": "fast"}, id="impl_fast"),
    pytest.param({"stem": "space_to_depth"}, id="stem_s2d"),
    pytest.param({"norm": "group"}, id="norm_group"),
]


def _features(rng, rows, state_size=0):
  features = {
      "image": rng.random((rows, SIZE, SIZE, 3)).astype(np.float32),
      "action": rng.uniform(-1, 1, (rows, 4)).astype(np.float32)}
  if state_size:
    features["state"] = rng.uniform(
        -1, 1, (rows, state_size)).astype(np.float32)
  return features


def _seeded(model, seed=3):
  """Weights and running statistics away from their initial values, so
  that a statistic read from the wrong place would show."""
  variables = jax.device_get(
      model.init_variables(jax.random.key(seed), batch_size=2))
  rng = np.random.default_rng(seed)
  return jax.tree_util.tree_map(
      lambda leaf: leaf + 0.1 * np.abs(rng.standard_normal(
          leaf.shape)).astype(leaf.dtype), variables)


@pytest.mark.parametrize("state_size", [0, 3], ids=["no_state", "state"])
@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
@pytest.mark.parametrize("kwargs", _VARIANTS)
def test_the_pair_composes_to_predict_fn(kwargs, compute, state_size):
  model = QTOptGraspingModel(
      image_size=SIZE, state_size=state_size,
      compute_dtype=jnp.dtype(compute), **kwargs)
  variables = _seeded(model)
  features = _features(np.random.default_rng(0), 5, state_size)
  encode_fn, q_from_code_fn = model.factored_cem_fns()
  code = encode_fn(variables, {"image": features["image"]})
  assert code.shape == (5, SIZE // 8, SIZE // 8, 64)
  assert code.dtype == jnp.dtype(compute)
  split = q_from_code_fn(variables, dict(features, image=code))
  whole = model.predict_fn(variables, features)
  split, whole = (np.asarray(out["q_predicted"], np.float32)
                  for out in (split, whole))
  if compute == "float32":
    np.testing.assert_array_equal(split, whole)
  else:  # two programs may fuse the bf16 casts apart
    np.testing.assert_allclose(split, whole, rtol=2e-2, atol=2e-2)


# The parent commit's tree (4f9ab92, `QTOptGraspingModel()`): checkpoints
# and the benchmark's seeded weights are handed over by these names.
_CONV = lambda cin, k: [("kernel", (k, k, cin, 64)), ("bias", (64,))]
_PINNED_PARAMS = {
    "stem": _CONV(3, 6),
    **{f"{side}_conv{i}": _CONV(64, 3)
       for side in ("pre", "post") for i in range(3)},
    **{name: [("scale", (64,)), ("bias", (64,))]
       for name in ["stem_bn"] + [f"{side}_bn{i}" for side in ("pre", "post")
                                  for i in range(3)]},
    "action_fc1": [("kernel", (4, 64)), ("bias", (64,))],
    "action_fc2": [("kernel", (64, 64)), ("bias", (64,))],
    "fc1": [("kernel", (64, 64)), ("bias", (64,))],
    "q_head": [("kernel", (64, 1)), ("bias", (1,))],
}
_PINNED_STATS = {
    name: [("mean", (64,)), ("var", (64,))]
    for name in ["stem_bn"] + [f"{side}_bn{i}" for side in ("pre", "post")
                               for i in range(3)]}


def _paths(tree):
  return sorted(
      ("/".join(str(k.key) for k in path), tuple(leaf.shape), str(leaf.dtype))
      for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])


def _pinned(collection, table):
  return sorted((f"{collection}/{module}/{leaf}", shape, "float32")
                for module, leaves in table.items() for leaf, shape in leaves)


@pytest.mark.parametrize("kwargs, renamed", [
    pytest.param({}, {}, id="parity"),
    pytest.param({"impl": "fast"}, {}, id="impl_fast"),
    pytest.param({"stem": "space_to_depth"}, {
        "params/stem/kernel": ("params/stem_s2d_kernel", (8, 2, 12, 64)),
        "params/stem/bias": ("params/stem_s2d_bias", (64,))}, id="stem_s2d"),
])
def test_parameter_and_statistics_trees_are_the_pinned_ones(kwargs, renamed):
  model = QTOptGraspingModel(image_size=SIZE, **kwargs)
  variables = model.init_variables(jax.random.key(0))
  assert set(variables) == {"params", "batch_stats"}
  wanted = _pinned("params", _PINNED_PARAMS) + _pinned(
      "batch_stats", _PINNED_STATS)
  wanted = sorted(
      renamed.get(path, (path, shape)) + (dtype,)
      for path, shape, dtype in wanted)
  assert _paths(variables) == wanted
  assert sum(int(np.prod(shape)) for path, shape, _ in wanted
             if path.startswith("params/")) == (
                 238145 if not kwargs.get("stem") else 238145 + 5376)


def test_predict_is_the_parent_commits_to_the_digit():
  """Q of four seeded rows through the float32-compute module at the
  parent commit (4f9ab92), read there before the split."""
  model = QTOptGraspingModel(image_size=SIZE, compute_dtype=jnp.float32)
  variables = model.init_variables(jax.random.key(3), batch_size=2)
  features = _features(np.random.default_rng(0), 4)
  q = model.predict_fn(variables, features)["q_predicted"]
  np.testing.assert_allclose(
      np.asarray(q),
      [-0.02788444608449936, -0.03690137714147568, -0.06902018189430237,
       -0.028534704819321632], rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("kwargs", _VARIANTS)
def test_train_step_is_the_composed_forms(kwargs):
  """Loss, gradients and new running statistics of a TRAIN-mode step
  through `__call__` against the pair applied one after the other."""
  model = QTOptGraspingModel(image_size=SIZE, **kwargs)
  module = model.module
  variables = _seeded(model)
  rng = np.random.default_rng(1)
  features = _features(rng, 6)
  target = rng.random(6).astype(np.float32)
  rest = {k: v for k, v in variables.items() if k != "params"}

  def loss_of(q):
    q = q["q_predicted"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(q, 0) - q * target
                    + jnp.log1p(jnp.exp(-jnp.abs(q))))

  def whole(params):
    q, state = module.apply({"params": params, **rest}, features,
                            modes.TRAIN, mutable=["batch_stats"])
    return loss_of(q), state

  def composed(params):
    tree = {"params": params, **rest}
    code, first = module.apply(
        tree, {"image": features["image"]}, modes.TRAIN,
        method=module.encode, mutable=["batch_stats"])
    q, second = module.apply(
        tree, dict(features, image=code), modes.TRAIN,
        method=module.q_from_code, mutable=["batch_stats"])
    # Each half hands back the whole collection, its own layers updated.
    state = {name: (first if name.startswith(("stem", "pre")) else second)[
        "batch_stats"][name] for name in first.get("batch_stats", {})}
    return loss_of(q), {"batch_stats": state} if state else {}

  (loss_a, state_a), grads_a = jax.value_and_grad(whole, has_aux=True)(
      variables["params"])
  (loss_b, state_b), grads_b = jax.value_and_grad(composed, has_aux=True)(
      variables["params"])
  assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
  for a, b in zip(jax.tree_util.tree_leaves((grads_a, dict(state_a))),
                  jax.tree_util.tree_leaves((grads_b, dict(state_b)))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
  if kwargs.get("norm") != "group":
    moved = jax.tree_util.tree_map(
        lambda new, old: float(jnp.max(jnp.abs(new - old))),
        dict(state_a)["batch_stats"], variables["batch_stats"])
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every layer updated


# --- 2. serving ---------------------------------------------------------------


class _PairHidden:
  """The same predictor with the pair taken away: the tiled path."""

  def __init__(self, inner):
    self._inner = inner

  def factored_device_fns(self):
    return None

  def __getattr__(self, name):
    return getattr(self._inner, name)


class _WholeQModule(nn.Module):
  """A critic that mixes frame and action from its first layer on: no
  factored form to offer."""

  @nn.compact
  def __call__(self, features, mode):
    del mode
    image = features["image"].astype(jnp.float32) / 255.0
    x = jnp.concatenate(
        [image.reshape((image.shape[0], -1)), features["action"]], axis=-1)
    return {"q_predicted": nn.Dense(1)(nn.relu(nn.Dense(16)(x)))[:, 0]}


class _WholeQModel(TinyQCriticModel):

  def build_module(self):
    return _WholeQModule()


@pytest.fixture(scope="module")
def flagship():
  model = QTOptGraspingModel(image_size=SIZE)
  return model, _seeded(model, seed=5)


def _export(model, variables, root):
  generator = NativeExportGenerator(export_root=str(root))
  generator.set_specification_from_model(model)
  return generator.export(variables)


def _exported(model, variables, root):
  _export(model, variables, root)
  predictor = ExportedModelPredictor(str(root))
  assert predictor.restore()
  return predictor


def _checkpoint(model, variables):
  predictor = CheckpointPredictor(model)
  predictor.init_randomly()
  predictor.set_variables(variables)
  return predictor


@pytest.fixture(scope="module")
def predictors(flagship, tmp_path_factory):
  model, variables = flagship
  return {"exported": _exported(model, variables,
                                tmp_path_factory.mktemp("export")),
          "checkpoint": _checkpoint(model, variables)}


def _frames(count, seed=0):
  rng = np.random.default_rng(seed)
  return [rng.random((SIZE, SIZE, 3)).astype(np.float32)
          for _ in range(count)]


_CEM = dict(action_size=4, num_samples=8, num_elites=2, iterations=2, seed=0)

# The artifact's calls are exported at the float32 wire: the bf16 and int8
# tiers hand any exported call bf16 leaves, which it refuses on either
# path, before and after this change.
_TIERS = [("exported", "f32"), ("checkpoint", "f32"),
          ("checkpoint", "bf16"), ("checkpoint", "int8")]


@pytest.mark.parametrize("kind, precision", _TIERS)
def test_same_answers_with_the_pair_and_with_it_hidden(predictors, kind,
                                                       precision):
  predictor = predictors[kind]
  frames, seeds = _frames(3), np.array([11, 12, 13], np.uint32)
  answers = {}
  for name, served in (("pair", predictor), ("tiled", _PairHidden(predictor))):
    policy = CEMFleetPolicy(served, ladder=BucketLadder((1, 4)),
                            precision=precision, **_CEM)
    answers[name] = policy(frames, seeds, return_scores=True)
    assert policy(frames[:1], seeds[:1]).shape == (1, 4)
    assert policy.compile_counts == {1: 1, 4: 1}
    assert policy.encode_once == {1: name == "pair", 4: name == "pair"}
  for pair, tiled in zip(answers["pair"], answers["tiled"]):
    np.testing.assert_allclose(pair, tiled, rtol=1e-5, atol=1e-6)
  assert np.all(np.abs(answers["pair"][0]) <= 1.0)


@pytest.mark.parametrize("kind", ["exported", "checkpoint"])
def test_one_compile_a_bucket_through_reloads_and_overrides(predictors, kind):
  predictor = predictors[kind]
  policy = CEMFleetPolicy(predictor, ladder=BucketLadder((1, 2, 4)), **_CEM)
  frames = _frames(4, seed=2)
  for n in (1, 2, 3, 4, 2, 1):
    policy(frames[:n], np.arange(n, dtype=np.uint32))
  _, live = predictor.device_fn()
  candidate = jax.tree_util.tree_map(lambda leaf: leaf * 1.01, live)
  seeds = np.arange(4, dtype=np.uint32)
  _, shadow = policy(frames, seeds, variables=candidate, return_scores=True)
  _, served = policy(frames, seeds, return_scores=True)
  assert not np.array_equal(shadow, served)  # the override reached the pair
  assert policy.compile_counts == {1: 1, 2: 1, 4: 1}
  assert policy.encode_once == {1: True, 2: True, 4: True}


@pytest.mark.parametrize("kind", ["exported", "checkpoint"])
def test_an_answer_does_not_depend_on_who_shared_the_flush(predictors, kind):
  policy = CEMFleetPolicy(predictors[kind], ladder=BucketLadder((1, 4)),
                          **_CEM)
  frames = _frames(4, seed=4)
  seeds = np.array([7, 8, 9, 10], np.uint32)
  together = policy(frames, seeds, return_scores=True)
  for i in (0, 3):  # alone, and padded up to the bucket beside one other
    alone = policy([frames[i]], seeds[i:i + 1], return_scores=True)
    pair = policy([frames[3 - i], frames[i]], seeds[[3 - i, i]],
                  return_scores=True)
    for whole, one, two in zip(together, alone, pair):
      np.testing.assert_allclose(one[0], whole[i], rtol=1e-5, atol=1e-6)
      np.testing.assert_allclose(two[1], whole[i], rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def flagship_472(tmp_path_factory):
  model = QTOptGraspingModel()
  variables = jax.device_get(model.init_variables(jax.random.key(0)))
  return {"exported": _exported(model, variables,
                                tmp_path_factory.mktemp("export472")),
          "checkpoint": _checkpoint(model, variables)}


def _stem_conv_batches(predictor, fns, bucket, samples):
  """Batch sizes of the convolutions that read a 472x472 input (those
  whose output is 118x118: the 6x6 stem at stride 4) in the rung's
  compiled program."""
  policy = CEMFleetPolicy(predictor, ladder=BucketLadder((bucket,)),
                          **dict(_CEM, num_samples=samples))
  fn, live = predictor.device_fn()
  compiled = jax.jit(policy._build_control(fn, fns)).lower(
      live, jax.ShapeDtypeStruct((bucket, 472, 472, 3), jnp.float32),
      jax.ShapeDtypeStruct((bucket,), jnp.uint32)).compile()
  return sorted(int(n) for n in re.findall(
      r"\[(\d+),118,118,64\]\S* convolution\(", compiled.as_text()))


@pytest.mark.parametrize("kind", ["exported", "checkpoint"])
def test_the_rung_program_reads_each_frame_once(flagship_472, kind):
  """The hoist, in the compiled program and not assumed: exactly one
  convolution over 472x472 and its batch is the bucket. Tiled, the same
  reading finds the search's rows: the test sees what it claims to."""
  predictor = flagship_472[kind]
  bucket, samples = 4, 8
  assert _stem_conv_batches(
      predictor, predictor.factored_device_fns(), bucket, samples) == [bucket]
  assert _stem_conv_batches(predictor, None, bucket, samples) == [
      bucket, bucket * samples]  # the read-out's one row each, the search's


def test_an_artifact_of_a_model_without_the_pair_serves_tiled(tmp_path):
  model = _WholeQModel(image_size=8)
  assert model.factored_cem_fns() is None
  variables = jax.device_get(model.init_variables(jax.random.key(0)))
  export_dir = _export(model, variables, tmp_path)
  assert not glob.glob(os.path.join(export_dir, "*code*"))
  with open(os.path.join(export_dir, "t2r_assets.json")) as f:
    assert "factored_cem" not in json.load(f)["extra"]
  predictor = ExportedModelPredictor(str(tmp_path))
  assert predictor.restore()
  assert predictor.factored_device_fns() is None
  policy = CEMFleetPolicy(predictor, ladder=BucketLadder((2,)), **_CEM)
  frames = [np.random.default_rng(i).integers(0, 255, (8, 8, 3), np.uint8)
            for i in range(2)]
  assert policy(frames, [1, 2]).shape == (2, 4)
  assert policy.encode_once == {2: False}


def test_a_legacy_artifact_restores_and_serves_as_before(flagship, tmp_path):
  """An artifact from before the pair was exported: `serving_fn.bin`
  alone and assets that say nothing of a pair."""
  model, variables = flagship
  export_dir = _export(model, variables, tmp_path)
  with_pair = ExportedModelPredictor(str(tmp_path))
  assert with_pair.restore()
  assert with_pair.factored_device_fns() is not None
  frames, seeds = _frames(2, seed=6), np.array([3, 4], np.uint32)
  new = CEMFleetPolicy(with_pair, ladder=BucketLadder((2,)), **_CEM)(
      frames, seeds, return_scores=True)

  for name in (ENCODE_FN_NAME, Q_FROM_CODE_FN_NAME, "t2r_assets.pb"):
    os.remove(os.path.join(export_dir, name))
  assets = os.path.join(export_dir, "t2r_assets.json")
  with open(assets) as f:
    payload = json.load(f)
  del payload["extra"]["factored_cem"]
  with open(assets, "w") as f:
    json.dump(payload, f)
  legacy = ExportedModelPredictor(str(tmp_path))
  assert legacy.restore()
  assert legacy.factored_device_fns() is None
  policy = CEMFleetPolicy(legacy, ladder=BucketLadder((2,)), **_CEM)
  old = policy(frames, seeds, return_scores=True)
  assert policy.encode_once == {2: False}
  for a, b in zip(new, old):
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
  q = legacy.predict({"image": np.stack(frames),
                      "action": np.zeros((2, 4), np.float32)})
  assert q["q_predicted"].shape == (2,)


def test_the_tiny_critics_artifact_carries_its_pair(tmp_path):
  """Any model with a factored form, not the flagship alone: the uint8
  wire and a (B, 32) code."""
  model = TinyQCriticModel(image_size=8)
  variables = jax.device_get(model.init_variables(jax.random.key(0)))
  predictor = _exported(model, variables, tmp_path)
  frames = [np.random.default_rng(i).integers(0, 255, (8, 8, 3), np.uint8)
            for i in range(2)]
  answers = [CEMFleetPolicy(served, ladder=BucketLadder((2,)), **_CEM)(
      frames, [5, 6], return_scores=True)
             for served in (predictor, _PairHidden(predictor))]
  for pair, tiled in zip(*answers):
    np.testing.assert_allclose(pair, tiled, rtol=1e-5, atol=1e-6)


# --- 3. the expansion on the merged row axis ----------------------------------


@pytest.fixture(scope="module")
def plain_predictors(tmp_path_factory):
  """The flagship as initialised: `_seeded`'s shifted statistics drive
  every Q to 1e11, where a frame's candidates tie in bfloat16 and the
  search returns the same action whatever it scored."""
  model = QTOptGraspingModel(image_size=SIZE)
  variables = jax.device_get(model.init_variables(jax.random.key(0)))
  return {"exported": _exported(model, variables,
                                tmp_path_factory.mktemp("plain")),
          "checkpoint": _checkpoint(model, variables)}


def _per_state_recipe(fns, variables, images, precision="f32"):
  """(states, score) as the recipe built them before ISSUE 39: the same
  encode (a finite code comes back from `states_of` as it went in), and
  `make_tiled_q_score_fn` over the code, one state's `broadcast_to`
  under a vmap over the states."""
  states, score = cem.make_cem_states_and_score(
      None, fns, variables, images, precision=precision)
  codes = states[0] if isinstance(score, cem.MergedRowScore) else states
  return codes, jax.vmap(cem.make_tiled_q_score_fn(fns[1], variables,
                                                   precision=precision))


def _search(recipe, fns, variables, images, keys, precision="f32"):
  def run(variables, images, keys):
    states, score = recipe(fns, variables, images, precision)
    return cem.fleet_cem_optimize(
        score, states, keys, 4, precision=precision, num_samples=8,
        num_elites=2, iterations=2)
  return jax.device_get(jax.jit(run)(variables, images, keys))


def _merged_recipe(fns, variables, images, precision="f32"):
  states, score = cem.make_cem_states_and_score(
      None, fns, variables, images, precision=precision)
  assert isinstance(score, cem.MergedRowScore)
  return states, score


@pytest.mark.parametrize("kind, precision", _TIERS)
def test_merged_rows_give_the_per_state_forms_answers(plain_predictors, kind,
                                                      precision):
  predictor = plain_predictors[kind]
  _, variables = predictor.device_fn()
  fns = predictor.factored_device_fns()
  images = np.stack(_frames(5, seed=9))
  keys = jax.random.split(jax.random.key(4), 5)
  merged = _search(_merged_recipe, fns, variables, images, keys, precision)
  per_state = _search(_per_state_recipe, fns, variables, images, keys,
                      precision)
  assert len(np.unique(merged[0], axis=0)) == 5  # scores chose the actions
  for new, old in zip(merged, per_state):
    assert np.all(np.isfinite(new))
    np.testing.assert_array_equal(new, old)


@pytest.mark.parametrize("compute, wanted", [
    ("bfloat16", None), ("float32", jax.lax.Precision.HIGHEST)])
def test_the_product_is_exact_for_the_codes_dtype(compute, wanted):
  """A bfloat16 code passes the MXU as it is; a float32 one would be
  rounded to bfloat16 at the TPU's default precision, so the recipe
  asks for the highest. Here: what it asks for, and that the expanded
  rows are the code's own bits."""
  model = QTOptGraspingModel(image_size=SIZE,
                             compute_dtype=jnp.dtype(compute))
  variables = _seeded(model)
  encode_fn, _ = model.factored_cem_fns()
  code = encode_fn(variables, {"image": np.stack(_frames(3, seed=1))})
  assert code.dtype == jnp.dtype(compute)
  rows = {}

  def capture(_, features):
    rows["code"] = features["image"]
    return {"q_predicted": jnp.zeros(features["action"].shape[0])}

  score = cem.MergedRowScore(capture, variables)
  states = cem.MergedRowScore.states_of(code)
  jaxpr = jax.make_jaxpr(score)(states, jnp.zeros((3, 4, 4)))
  dots = [eqn for eqn in jaxpr.jaxpr.eqns
          if eqn.primitive.name == "dot_general"]
  assert len(dots) == 1
  precision = dots[0].params["precision"]
  assert (precision if precision is None else precision[0]) == wanted
  score(states, jnp.zeros((3, 4, 4)))
  np.testing.assert_array_equal(
      np.asarray(rows["code"], np.float32),
      np.repeat(np.asarray(code, np.float32), 4, axis=0))


@pytest.mark.parametrize("bad", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("kind", ["exported", "checkpoint"])
def test_a_frame_that_is_not_finite_spoils_its_own_row_alone(
    plain_predictors, kind, bad):
  """0 * NaN is NaN: unguarded, the merged product would hand one
  robot's bad frame to every row of its flush."""
  policy = CEMFleetPolicy(plain_predictors[kind], ladder=BucketLadder((4,)),
                          **_CEM)
  frames = _frames(4, seed=12)
  seeds = np.array([21, 22, 23, 24], np.uint32)
  spoiled = list(frames)
  spoiled[2] = frames[2].copy()
  spoiled[2][5, 7, 1] = bad
  sound = policy(frames, seeds, return_scores=True)
  served = policy(spoiled, seeds, return_scores=True)
  assert not np.isfinite(served[1][2])
  for i in (0, 1, 3):
    alone = policy([frames[i]], seeds[i:i + 1], return_scores=True)
    for with_bad, without, one in zip(served, sound, alone):
      np.testing.assert_array_equal(with_bad[i], without[i])
      np.testing.assert_array_equal(with_bad[i], one[0])
  assert policy.compile_counts == {4: 1}


def test_the_serving_buckets_compile_once_each_and_expand_in_the_conv(
    predictors):
  policy = CEMFleetPolicy(predictors["checkpoint"],
                          ladder=BucketLadder((1, 8, 32)), **_CEM)
  frames = _frames(32, seed=13)
  for n in (1, 8, 32, 5, 20, 1):
    assert policy(frames[:n], np.arange(n, dtype=np.uint32)).shape == (n, 4)
  assert policy.compile_counts == {1: 1, 8: 1, 32: 1}
  assert policy.expand_in_conv == {1: True, 8: True, 32: True}


@pytest.mark.parametrize("batch", [96, 128, 300])
@pytest.mark.parametrize("consumer", ["bellman", "acting"])
def test_training_consumers_read_the_per_state_forms_answers(consumer, batch):
  """Bellman's target max and Anakin's acting step through the recipe,
  below, at and above the 128 states one product expands: past it the
  recipe is the per-state form itself."""
  model = TinyQCriticModel(image_size=8)
  variables = jax.device_get(model.init_variables(jax.random.key(1)))
  fns = model.factored_cem_fns()
  rng = np.random.default_rng(batch)
  images = rng.integers(0, 255, (batch, 8, 8, 3), np.uint8)
  keys = jax.random.split(jax.random.key(2), batch)
  best, scores = _search(_per_state_recipe, fns, variables, images, keys)
  def recipe(fns, variables, images, precision):
    states, score = cem.make_cem_states_and_score(
        None, fns, variables, images, precision=precision)
    assert isinstance(score, cem.MergedRowScore) == (batch <= 128)
    return states, score

  if consumer == "acting":  # anakin.act: the recipe, then the best action
    new, _ = _search(recipe, fns, variables, images, keys)
    np.testing.assert_allclose(new, best, rtol=1e-5, atol=1e-6)
    return
  rewards = rng.random(batch).astype(np.float32)
  dones = (rng.random(batch) < 0.3).astype(np.float32)
  targets, q_next = jax.jit(make_bellman_targets_fn(
      model, 4, 0.9, 8, 2, 2, True, factored=True))(
          variables, images, rewards, dones, keys)
  wanted = 1.0 / (1.0 + np.exp(-scores.astype(np.float64)))
  np.testing.assert_allclose(q_next, wanted, rtol=1e-5, atol=1e-6)
  np.testing.assert_allclose(
      targets, np.clip(rewards + 0.9 * (1.0 - dones) * wanted, 0.0, 1.0),
      rtol=1e-5, atol=1e-6)


# --- 4. the counter -----------------------------------------------------------


@pytest.mark.parametrize("offered", [True, False], ids=["pair", "tiled"])
def test_spans_and_stats_say_whether_the_frame_was_encoded_once(
    predictors, offered):
  from tensor2robot_tpu.obs import registry as registry_lib
  from tensor2robot_tpu.serving.router import FleetRouter
  from tensor2robot_tpu.serving.stats import ServingStats
  predictor = predictors["checkpoint"]
  stats = ServingStats(registry=registry_lib.MetricRegistry())
  router = FleetRouter(
      predictor if offered else _PairHidden(predictor),
      devices=jax.devices()[:1], ladder_sizes=(2,), max_batch=2,
      deadline_ms=5.0, stats=stats, **_CEM)
  tracer = trace_lib.get_tracer()
  tracer.clear()
  frames = _frames(4, seed=8)
  with router:
    for future in [router.submit(frame, seed=i)
                   for i, frame in enumerate(frames)]:
      future.result(timeout=60)
  snapshot = stats.snapshot()
  assert snapshot["flushes"] >= 2
  assert snapshot["encode_once_flushes"] == (
      snapshot["flushes"] if offered else 0)
  spans = tracer.spans()
  executes = [s for s in spans if s["name"] == "serve/execute"]
  compiles = [s for s in spans if s["name"] == "serve/compile"]
  assert len(executes) == snapshot["flushes"] and len(compiles) == 1
  assert {s["encode_once"] for s in executes + compiles} == {int(offered)}
  assert {s["expand_in_conv"] for s in executes} == {int(offered)}


@pytest.mark.parametrize("attrs, share", [
    ([1, 1, 1], 100.0), ([0, 0], 0.0), ([1, 0, None, 1], 200.0 / 3),
    ([None, None], None), ([], None)])
def test_the_benchmarks_reader_over_a_ring_of_execute_spans(attrs, share):
  """`encode_once_share.serve`: of the window's `serve/execute` spans
  that carry the attr the share with 1; nothing to read where none does
  (the parent commit's program)."""
  import sys
  sys.path.insert(0, ROOT)
  from benchmark import harness
  tracer = trace_lib.get_tracer()
  tracer.clear()
  for value in attrs:
    with tracer.span("serve/flush", batch=32, encode_once=1):
      kwargs = {} if value is None else {"encode_once": value}
      with tracer.span("serve/execute", bucket=32, **kwargs):
        pass
  read = harness._load_module(
      "layer_metrics", "encode_once_share.serve").read
  value = read({"window": {"window_s": 5.0}, "chips": 1, "trace": None})
  assert value == (None if share is None else pytest.approx(share))
  declared = {m["name"]: m for m in harness.load_cell(
      "qtopt_serve_closed64").spec["per_layer"]}["encode_once_share.serve"]
  assert (declared["layer"], declared["source"], declared["moves"]) == (
      "CEM policy", "program_span", "serve_actions_per_s")

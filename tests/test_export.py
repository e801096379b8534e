"""Tests for export generators + predictors.

The SavedModel (TF) path runs in a subprocess: executing TF kernels
in-process starves XLA's CPU collective rendezvous on low-core hosts
(see test_models.py::test_distortion_math_matches_tf).
"""

import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest

from tensor2robot_tpu import modes
from tensor2robot_tpu.data.default_input_generator import (
    DefaultRandomInputGenerator,
)
from tensor2robot_tpu.export import export_utils
from tensor2robot_tpu.export.native_export_generator import (
    NativeExportGenerator,
)
from tensor2robot_tpu.predictors.checkpoint_predictor import (
    CheckpointPredictor,
)
from tensor2robot_tpu.predictors.exported_model_predictor import (
    ExportedModelPredictor,
)
from tensor2robot_tpu.specs import tensorspec_utils as ts
from tensor2robot_tpu.train.checkpoints import CheckpointManager
from tensor2robot_tpu.train.trainer import Trainer
from tensor2robot_tpu.utils.mocks import MockT2RModel


def _trained_state(model, steps=2):
  trainer = Trainer(model, seed=0)
  state = trainer.create_train_state()
  gen = DefaultRandomInputGenerator(batch_size=8, seed=0)
  gen.set_specification_from_model(model, modes.TRAIN)
  it = gen.create_dataset_fn(modes.TRAIN)()
  for _ in range(steps):
    features, labels = trainer.shard_batch(next(it))
    state, _ = trainer.train_step(state, features, labels)
  return trainer, state


class TestExportUtils:

  def test_versioned_dirs_monotonic(self, tmp_path):
    root = str(tmp_path / "exports")
    tmp1, final1 = export_utils.versioned_export_dir(root)
    os.makedirs(tmp1)
    export_utils.publish(tmp1, final1)
    tmp2, final2 = export_utils.versioned_export_dir(root)
    assert int(os.path.basename(final2)) > int(os.path.basename(final1))

  def test_publish_refuses_existing_target_by_name(self, tmp_path):
    """ISSUE 19 regression: a reused workdir re-reaching a step-named
    export dir used to die with a bare OSError errno 39 (directory not
    empty) naming neither path; publish now refuses up front with the
    offending path in the message."""
    root = str(tmp_path / "exports")
    final = os.path.join(root, "1234")
    os.makedirs(os.path.join(final, "old_contents"))
    tmp = os.path.join(root, ".tmp-1234")
    os.makedirs(tmp)
    with pytest.raises(FileExistsError, match="1234"):
      export_utils.publish(tmp, final)
    # The refused publish leaves both dirs intact: nothing clobbered,
    # nothing half-moved.
    assert os.path.isdir(os.path.join(final, "old_contents"))
    assert os.path.isdir(tmp)

  def test_gc(self, tmp_path):
    root = str(tmp_path / "exports")
    for v in (100, 200, 300):
      os.makedirs(os.path.join(root, str(v)))
    export_utils.garbage_collect_exports(root, keep=2)
    assert export_utils.list_export_versions(root) == [200, 300]

  def test_spec_assets_round_trip(self, tmp_path):
    spec = ts.TensorSpecStruct(
        {"x": ts.ExtendedTensorSpec((3,), np.float32, name="x")})
    export_utils.write_spec_assets(str(tmp_path), spec, extra={"k": "v"})
    feature_spec, label_spec, extra = export_utils.read_spec_assets(
        str(tmp_path))
    assert feature_spec["x"].shape == (3,)
    assert label_spec is None
    assert extra["k"] == "v"


class TestNativeExportRoundTrip:

  def test_export_predict_matches_model(self, tmp_path):
    model = MockT2RModel()
    trainer, state = _trained_state(model)
    root = str(tmp_path / "exports")
    gen = NativeExportGenerator(export_root=root)
    gen.set_specification_from_model(model)
    export_dir = gen.export(jax.device_get(state.variables(use_ema=True)))
    assert os.path.basename(os.path.dirname(export_dir)) == "exports"

    predictor = ExportedModelPredictor(root)
    assert predictor.model_version == -1
    assert predictor.restore()
    assert predictor.model_version == int(os.path.basename(export_dir))

    x = np.random.default_rng(0).random((4, 3)).astype(np.float32)
    out = predictor.predict({"x": x})
    expected = model.predict_fn(
        jax.device_get(state.variables(use_ema=True)),
        ts.TensorSpecStruct({"x": x}))
    np.testing.assert_allclose(
        out["inference_output"], np.asarray(expected["inference_output"]),
        atol=1e-5)

  def test_polymorphic_batch(self, tmp_path):
    model = MockT2RModel()
    _, state = _trained_state(model)
    root = str(tmp_path / "exports")
    gen = NativeExportGenerator(export_root=root)
    gen.set_specification_from_model(model)
    gen.export(jax.device_get(state.variables()))
    predictor = ExportedModelPredictor(root)
    predictor.restore()
    for batch in (1, 5, 64):
      out = predictor.predict(
          {"x": np.zeros((batch, 3), np.float32)})
      assert out["inference_output"].shape == (batch, 1)

  def test_hot_reload_and_timeout(self, tmp_path):
    model = MockT2RModel()
    _, state = _trained_state(model)
    root = str(tmp_path / "exports")
    predictor = ExportedModelPredictor(root)
    # Nothing exported yet: restore times out politely.
    assert not predictor.restore(timeout_s=0.2)
    gen = NativeExportGenerator(export_root=root)
    gen.set_specification_from_model(model)
    first = gen.export(jax.device_get(state.variables()))
    assert predictor.restore()
    v1 = predictor.model_version
    second = gen.export(jax.device_get(state.variables()))
    assert predictor.restore()
    assert predictor.model_version > v1
    # No newer version: restore keeps serving the current one.
    assert predictor.restore()

  def test_predict_examples_tf_free(self, tmp_path):
    """The native (StableHLO) predictor consumes serialized tf.Example
    records with NO TF: parsing runs through the packaged spec and the
    repo codec. Covers the dense-float wire (MockT2RModel) and the
    raw-uint8 image wire (the robot format VERDICT r3 #7 closed for
    the SavedModel path)."""
    model = MockT2RModel()
    _, state = _trained_state(model)
    root = str(tmp_path / "exports")
    gen = NativeExportGenerator(export_root=root)
    gen.set_specification_from_model(model)
    gen.export(jax.device_get(state.variables()))
    predictor = ExportedModelPredictor(root)
    assert predictor.restore()
    from tensor2robot_tpu.data.example_proto import encode_example
    rng = np.random.default_rng(0)
    xs = rng.random((3, 3)).astype(np.float32)
    records = [encode_example({"x": xs[i]}) for i in range(3)]
    out = predictor.predict_examples(records)
    np.testing.assert_allclose(
        out["inference_output"],
        predictor.predict({"x": xs})["inference_output"], atol=1e-6)

    # Raw-uint8 image wire through the native path.
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        QTOptGraspingModel)
    qmodel = QTOptGraspingModel(image_size=32, in_image_size=32,
                                uint8_images=True, wire_format="raw")
    variables = jax.device_get(
        qmodel.init_variables(jax.random.key(0), batch_size=2))
    qroot = str(tmp_path / "q_exports")
    qgen = NativeExportGenerator(export_root=qroot)
    qgen.set_specification_from_model(qmodel)
    qgen.export(variables)
    qpred = ExportedModelPredictor(qroot)
    assert qpred.restore()
    spec = qpred.get_feature_specification()
    assert np.dtype(spec["image"].dtype) == np.uint8
    images = rng.integers(0, 256, (2, 32, 32, 3)).astype(np.uint8)
    actions = rng.standard_normal((2, 4)).astype(np.float32)
    qrecords = [encode_example({
        "image": [images[i].tobytes()], "action": actions[i]})
        for i in range(2)]
    out_records = qpred.predict_examples(qrecords)
    out_numpy = qpred.predict({"image": images, "action": actions})
    np.testing.assert_allclose(out_records["q_predicted"],
                               out_numpy["q_predicted"], atol=1e-6)

  def test_predict_validates_spec(self, tmp_path):
    model = MockT2RModel()
    _, state = _trained_state(model)
    root = str(tmp_path / "exports")
    gen = NativeExportGenerator(export_root=root)
    gen.set_specification_from_model(model)
    gen.export(jax.device_get(state.variables()))
    predictor = ExportedModelPredictor(root)
    predictor.restore()
    with pytest.raises(ValueError):
      predictor.predict({"x": np.zeros((2, 7), np.float32)})
    with pytest.raises(ValueError):
      predictor.predict({"wrong_key": np.zeros((2, 3), np.float32)})


class TestExportedArtifactUnderFleetCEM:
  """The flagship serving path is export -> ExportedModelPredictor ->
  CEMFleetPolicy, whose control step vmaps a per-robot CEM search over
  the predictor's device fn. jax.export's call primitive has no
  batching rule; the predictor supplies one (fold the mapped axis into
  the artifact's symbolic batch rows)."""

  @pytest.fixture(scope="class")
  def served(self, tmp_path_factory):
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        QTOptGraspingModel)
    model = QTOptGraspingModel(image_size=32)
    variables = jax.device_get(
        model.init_variables(jax.random.key(3), batch_size=4))
    root = str(tmp_path_factory.mktemp("fleet_export"))
    gen = NativeExportGenerator(export_root=root)
    gen.set_specification_from_model(model)
    gen.export(variables)
    exported = ExportedModelPredictor(root)
    assert exported.restore()
    live = CheckpointPredictor(model)
    live.init_randomly()
    live.set_variables(variables)
    return exported, live

  def test_vmap_over_device_fn_matches_row_by_row_predict(self, served):
    exported, _ = served
    fn, variables = exported.device_fn()
    rng = np.random.default_rng(0)
    images = rng.random((3, 5, 32, 32, 3)).astype(np.float32)
    actions = rng.standard_normal((3, 5, 4)).astype(np.float32)
    mapped = jax.jit(jax.vmap(
        lambda image, action: fn(
            variables, {"image": image, "action": action})["q_predicted"]
    ))(images, actions)
    assert mapped.shape == (3, 5)
    # bf16 tower at another batch shape: ~1e-4 apart. Rows are told
    # apart at a scale 10x the tolerance, so a mixup cannot hide in it.
    assert np.abs(mapped - np.roll(mapped, 1, axis=0)).max() > 1e-2
    for robot in range(3):
      want = exported.predict(
          {"image": images[robot], "action": actions[robot]})["q_predicted"]
      np.testing.assert_allclose(mapped[robot], want, atol=1e-3)
    # An unmapped argument (one image shared by every robot's actions)
    # is broadcast, not rejected.
    shared = jax.vmap(
        lambda action: fn(variables, {
            "image": images[0], "action": action})["q_predicted"]
    )(actions)
    np.testing.assert_allclose(shared[0], mapped[0], atol=1e-3)

  def test_fleet_policy_on_the_artifact_takes_the_device_path(self, served):
    """Same weights behind the artifact and behind the live model: the
    fleet control step answers each (image, seed) alike, with scores —
    i.e. the artifact did NOT drop to the host loop."""
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    exported, live = served
    kwargs = dict(action_size=4, num_samples=16, num_elites=4,
                  iterations=2, seed=0, ladder=BucketLadder((1, 4)))
    rng = np.random.default_rng(1)
    images = [rng.random((32, 32, 3)).astype(np.float32) for _ in range(3)]
    seeds = [7, 8, 9]
    got, got_scores = CEMFleetPolicy(exported, **kwargs)(
        images, seeds, return_scores=True)
    want, want_scores = CEMFleetPolicy(live, **kwargs)(
        images, seeds, return_scores=True)
    assert got_scores is not None
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(got_scores, want_scores, atol=1e-4)

  def test_vmap_over_the_served_variables_is_refused(self, served):
    exported, _ = served
    fn, variables = exported.device_fn()
    stacked = jax.tree_util.tree_map(lambda x: np.stack([x, x]), variables)
    features = {"image": np.zeros((1, 32, 32, 3), np.float32),
                "action": np.zeros((1, 4), np.float32)}
    with pytest.raises(NotImplementedError, match="served variables"):
      jax.vmap(lambda v: fn(v, features)["q_predicted"])(stacked)


class TestCheckpointPredictor:

  def test_restore_and_predict(self, tmp_path):
    model = MockT2RModel(use_avg_model_params=True)
    trainer, state = _trained_state(model, steps=3)
    ckpt_dir = str(tmp_path / "ckpt")
    manager = CheckpointManager(ckpt_dir)
    manager.save(int(state.step), state)
    manager.close()

    predictor = CheckpointPredictor(model, ckpt_dir)
    assert predictor.restore()
    assert predictor.model_version == 3
    x = np.random.default_rng(1).random((2, 3)).astype(np.float32)
    out = predictor.predict({"x": x})
    # EMA params are what gets served.
    expected = model.predict_fn(
        jax.device_get(state.variables(use_ema=True)),
        ts.TensorSpecStruct({"x": x}))
    np.testing.assert_allclose(
        out["inference_output"], np.asarray(expected["inference_output"]),
        atol=1e-5)

  def test_init_randomly(self):
    model = MockT2RModel()
    predictor = CheckpointPredictor(model)
    predictor.init_randomly()
    out = predictor.predict({"x": np.zeros((2, 3), np.float32)})
    assert out["inference_output"].shape == (2, 1)

  def test_unloaded_raises(self):
    predictor = CheckpointPredictor(MockT2RModel())
    with pytest.raises(ValueError, match="no model loaded"):
      predictor.predict({"x": np.zeros((1, 3), np.float32)})


class TestSavedModelPath:

  def test_savedmodel_round_trip_subprocess(self, tmp_path):
    """Full jax2tf export + TF load + predict parity, in a subprocess."""
    script = f"""
import os, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
from tensor2robot_tpu import modes
from tensor2robot_tpu.export.savedmodel_export_generator import (
    SavedModelExportGenerator)
from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
    ExportedSavedModelPredictor)
from tensor2robot_tpu.specs import tensorspec_utils as ts
from tensor2robot_tpu.utils.mocks import MockT2RModel

model = MockT2RModel()
variables = jax.device_get(model.init_variables(jax.random.key(0)))
root = {str(tmp_path / "sm")!r}
gen = SavedModelExportGenerator(export_root=root,
                                platforms=("cpu",))
gen.set_specification_from_model(model)
export_dir = gen.export(variables)

pred = ExportedSavedModelPredictor(root)
assert pred.restore(), "restore failed"
x = np.random.default_rng(0).random((3, 3)).astype(np.float32)
out = pred.predict({{"x": x}})
expected = model.predict_fn(variables, ts.TensorSpecStruct({{"x": x}}))
np.testing.assert_allclose(
    out["inference_output"], np.asarray(expected["inference_output"]),
    atol=1e-5)

# tf.Example signature.
import tensorflow as tf
loaded = tf.saved_model.load(export_dir)
ex = tf.train.Example(features=tf.train.Features(feature={{
    "x": tf.train.Feature(float_list=tf.train.FloatList(
        value=x[0].tolist()))}}))
out2 = loaded.signatures["tf_example"](
    tf.constant([ex.SerializeToString()]))
np.testing.assert_allclose(
    out2["inference_output"].numpy()[0], out["inference_output"][0],
    atol=1e-5)
print("SAVEDMODEL-OK")
"""
    result = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420)
    assert "SAVEDMODEL-OK" in result.stdout, (
        f"stdout={result.stdout}\nstderr={result.stderr[-3000:]}")

  @pytest.mark.slow  # fast-lane budget (VERDICT r3 #8): covered by the full suite; the float32 round-trip subprocess test stays fast
  def test_savedmodel_uint8_raw_bytes_signature_subprocess(self, tmp_path):
    """uint8-wire model: tf.io.parse_example can't parse uint8, so the
    tf_example signature must take the raw-bytes tensor convention
    (array.tobytes()) and decode_raw it — exercised end to end."""
    script = f"""
import os, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
from tensor2robot_tpu.export.savedmodel_export_generator import (
    SavedModelExportGenerator)
from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel

model = QTOptGraspingModel(image_size=32, uint8_images=True)
variables = jax.device_get(
    model.init_variables(jax.random.key(0), batch_size=2))
gen = SavedModelExportGenerator(export_root={str(tmp_path / "sm")!r},
                                platforms=("cpu",))
gen.set_specification_from_model(model)
export_dir = gen.export(variables)

import tensorflow as tf
loaded = tf.saved_model.load(export_dir)
rng = np.random.default_rng(0)
image = rng.integers(0, 255, (32, 32, 3)).astype(np.uint8)
action = rng.standard_normal((4,)).astype(np.float32)
ex = tf.train.Example(features=tf.train.Features(feature={{
    "image": tf.train.Feature(bytes_list=tf.train.BytesList(
        value=[image.tobytes()])),
    "action": tf.train.Feature(float_list=tf.train.FloatList(
        value=action.tolist()))}}))
out = loaded.signatures["tf_example"](
    tf.constant([ex.SerializeToString()]))
from tensor2robot_tpu.specs import tensorspec_utils as ts
expected = model.predict_fn(variables, ts.TensorSpecStruct(
    {{"image": image[None], "action": action[None]}}))
np.testing.assert_allclose(
    out["q_predicted"].numpy(), np.asarray(expected["q_predicted"]),
    atol=1e-4)
print("UINT8-SAVEDMODEL-OK")
"""
    result = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420)
    assert "UINT8-SAVEDMODEL-OK" in result.stdout, (
        f"stdout={result.stdout}\nstderr={result.stderr[-3000:]}")

  def test_raw_wire_uint8_end_to_end_through_predictor_subprocess(
      self, tmp_path):
    """VERDICT r3 #7 — the full robot wire loop for the raw-uint8
    format: export a wire_format='raw', uint8_images=True model, load
    it through ExportedSavedModelPredictor (poll/restore path, not a
    bare tf.saved_model.load), assert the serving signature takes
    uint8 end-to-end, and drive BOTH entry points: numpy uint8 batches
    (predict) and serialized uint8 tf.Example records exactly as the
    training pipeline writes them (predict_examples)."""
    script = f"""
import os, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
from tensor2robot_tpu.export.savedmodel_export_generator import (
    SavedModelExportGenerator)
from tensor2robot_tpu.predictors.exported_savedmodel_predictor import (
    ExportedSavedModelPredictor)
from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel
from tensor2robot_tpu.specs import tensorspec_utils as ts

model = QTOptGraspingModel(image_size=32, uint8_images=True,
                           wire_format="raw")
variables = jax.device_get(
    model.init_variables(jax.random.key(0), batch_size=2))
export_root = {str(tmp_path / "sm_raw")!r}
gen = SavedModelExportGenerator(export_root=export_root,
                                platforms=("cpu",))
gen.set_specification_from_model(model)
gen.export(variables)

predictor = ExportedSavedModelPredictor(export_root)
assert predictor.restore(timeout_s=5.0)
# The serving contract is uint8 end-to-end: the packaged spec AND the
# loaded signature both take uint8 images.
spec = predictor.get_feature_specification()
assert np.dtype(spec["image"].dtype) == np.uint8, spec["image"].dtype
import tensorflow as tf
sig_inputs = {{
    i.name.split(":")[0]: i.dtype
    for i in predictor._fn.inputs if i.dtype != tf.resource}}
assert sig_inputs.get("image") == tf.uint8, sig_inputs

rng = np.random.default_rng(0)
images = rng.integers(0, 256, (2, 32, 32, 3)).astype(np.uint8)
actions = rng.standard_normal((2, 4)).astype(np.float32)
expected = model.predict_fn(variables, ts.TensorSpecStruct(
    {{"image": images, "action": actions}}))

# Path 1: numpy uint8 feed through serving_default.
out_np = predictor.predict({{"image": images, "action": actions}})
np.testing.assert_allclose(
    out_np["q_predicted"], np.asarray(expected["q_predicted"]),
    atol=1e-3)  # bf16 compute: jax2tf CPU vs jax differ O(1e-4)

# Path 2: serialized uint8 tf.Example records — the same encoding the
# raw-wire training pipeline writes (image tensor's own bytes).
from tensor2robot_tpu.data.example_proto import encode_example
records = [encode_example({{
    "image": [images[i].tobytes()],
    "action": actions[i],
}}) for i in range(2)]
out_ex = predictor.predict_examples(records)
np.testing.assert_allclose(
    out_ex["q_predicted"], np.asarray(expected["q_predicted"]),
    atol=1e-3)  # bf16 compute: jax2tf CPU vs jax differ O(1e-4)
predictor.close()
print("RAW-UINT8-PREDICTOR-OK")
"""
    result = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420)
    assert "RAW-UINT8-PREDICTOR-OK" in result.stdout, (
        f"stdout={result.stdout}\nstderr={result.stderr[-3000:]}")


class TestFetchVariablesToHost:

  def test_replicated_and_sharded_leaves(self):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from tensor2robot_tpu.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    replicated = jax.device_put(
        jnp.arange(16.0), NamedSharding(mesh, PartitionSpec()))
    sharded = jax.device_put(
        jnp.arange(16.0), NamedSharding(mesh, PartitionSpec("data")))
    out = export_utils.fetch_variables_to_host(
        {"r": replicated, "s": sharded, "scalar": jnp.float32(3.0)})
    np.testing.assert_array_equal(out["r"], np.arange(16.0))
    np.testing.assert_array_equal(out["s"], np.arange(16.0))
    assert float(out["scalar"]) == 3.0

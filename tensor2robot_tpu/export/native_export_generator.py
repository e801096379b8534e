"""Native export: jax.export (StableHLO) + npz variables + spec assets.

The TPU-native serving format (replaces the reference's SavedModel for
pure-JAX consumers): the PREDICT computation is serialized as portable
StableHLO compiled-for {cpu, tpu}, so a robot-side process deserializes
and calls it with zero model Python code — the same decoupling as
SURVEY.md §3.3's SavedModel contract.

Artifact layout (one versioned dir):
    serving_fn.bin     jax.export.Exported.serialize() of
                       serve(variables, *features_in_key_order) -> {name: out}
    variables.npz      flat npz of the variables dict (export/variables_io.py;
                       numpy is the only robot-side dependency)
    encode_fn.bin, q_from_code_fn.bin
                       only where the model has a factored CEM pair
                       (CriticModel.factored_cem_fns):
                       encode(variables, image) -> code and
                       q_from_code(variables, code, *other features)
                       -> {name: out}, so a CEM policy encodes each
                       frame once (predictors' factored_device_fns)
    t2r_assets.json    feature specs + feature key order + metadata
                       (+ "factored_cem": the pair's two files)
    t2r_assets.pb      proto twin of the JSON assets (proto/t2r.proto)

Batch dim is exported symbolically ("b") so serving batch size is free —
QT-Opt's CEM sweeps batch sizes at inference (SURVEY.md §3.3).
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
import jax.export  # the jax.export submodule is lazy: attribute access
# alone raises AttributeError in a process where nothing else has
# imported it (bare multi-host workers; the in-process test suite gets
# it transitively and never sees this).
import numpy as np

from tensor2robot_tpu.export import export_utils, variables_io
from tensor2robot_tpu.export.abstract_export_generator import (
    AbstractExportGenerator,
)

SERVING_FN_NAME = "serving_fn.bin"
ENCODE_FN_NAME = "encode_fn.bin"
Q_FROM_CODE_FN_NAME = "q_from_code_fn.bin"
VARIABLES_DIR = "variables"  # legacy orbax layout, still readable
VARIABLES_NPZ = "variables.npz"


class NativeExportGenerator(AbstractExportGenerator):
  """Emits the native StableHLO serving artifact."""

  def __init__(
      self,
      export_root: Optional[str] = None,
      platforms: Sequence[str] = ("cpu", "tpu"),
      polymorphic_batch: bool = True,
  ):
    super().__init__(export_root)
    self._platforms = tuple(platforms)
    self._polymorphic_batch = polymorphic_batch

  def export(self, variables: Any, global_step: int = 0) -> str:
    model = self._model
    feature_spec = self.feature_spec
    keys = list(feature_spec.keys())

    def serve(variables, *feature_arrays):
      features = type(feature_spec)(zip(keys, feature_arrays))
      # Plain dict out: stable across deserialization without custom
      # pytree registration on the consumer side.
      return export_utils.normalize_serving_outputs(
          model.predict_fn(variables, features))

    if self._polymorphic_batch:
      batch = jax.export.symbolic_shape("b")[0]
    else:
      batch = 1
    arg_shapes = [
        jax.ShapeDtypeStruct((batch,) + spec.shape, spec.dtype)
        for spec in feature_spec.values()
    ]
    variables = jax.device_get(variables)
    var_shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        variables)
    from tensor2robot_tpu.ops import dispatch
    with dispatch.xla_only():
      # Multi-platform artifacts lower every branch for every platform;
      # compiled Pallas calls cannot lower for the CPU target.
      export = lambda fn, *args: jax.export.export(
          jax.jit(fn), platforms=self._platforms)(var_shapes, *args)
      calls = {SERVING_FN_NAME: export(serve, *arg_shapes),
               **self._export_factored_pair(export, keys, arg_shapes)}

    tmp_dir, final_dir = export_utils.versioned_export_dir(self.export_root)
    os.makedirs(tmp_dir, exist_ok=True)
    for name, exported in calls.items():
      with open(os.path.join(tmp_dir, name), "wb") as f:
        f.write(exported.serialize())
    # Variables as one flat npz (variables_io): numpy-only on the robot
    # side, and no checkpoint-library global state in this (possibly
    # worker) thread while the trainer checkpoints concurrently.
    variables_io.save_variables(
        os.path.join(tmp_dir, VARIABLES_NPZ), variables)
    export_utils.write_spec_assets(
        tmp_dir, feature_spec,
        extra={
            "format": "jax_export_stablehlo",
            "feature_keys": keys,
            "platforms": list(self._platforms),
            **({"factored_cem": {"encode_fn": ENCODE_FN_NAME,
                                 "q_from_code_fn": Q_FROM_CODE_FN_NAME}}
               if ENCODE_FN_NAME in calls else {}),
        },
        global_step=global_step)
    return export_utils.publish(tmp_dir, final_dir)

  def _export_factored_pair(self, export, keys, arg_shapes):
    """{file name: Exported} of the model's factored CEM pair, empty
    where it has none over the `image` feature. The code's shape and
    dtype are whatever `encode` gives at the serving signature;
    `q_from_code` takes the code in the image's place and every other
    feature as `serve` does."""
    fns = self._model.factored_cem_fns()
    if fns is None or "image" not in keys:
      return {}
    encode_fn, q_from_code_fn = fns
    image = keys.index("image")

    def encode(variables, image):
      return encode_fn(variables, {"image": image})

    def q_from_code(variables, *feature_arrays):
      return export_utils.normalize_serving_outputs(
          q_from_code_fn(variables, dict(zip(keys, feature_arrays))))

    encode_call = export(encode, arg_shapes[image])
    (code,) = encode_call.out_avals
    code_args = list(arg_shapes)
    code_args[image] = jax.ShapeDtypeStruct(code.shape, code.dtype)
    return {ENCODE_FN_NAME: encode_call,
            Q_FROM_CODE_FN_NAME: export(q_from_code, *code_args)}

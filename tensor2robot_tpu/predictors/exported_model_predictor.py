"""ExportedModelPredictor: serve a native (jax.export) artifact.

Reference parity: predictors/exported_savedmodel_predictor.py
§ExportedSavedModelPredictor (SURVEY.md §3.3): poll an export root for the
newest version, block-with-timeout until the first export exists, predict
on numpy dicts, hot-reload on newer versions. The artifact carries the
whole computation (StableHLO) + weights + specs, so no model Python code
is needed on the robot.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.export import export_utils, variables_io
from tensor2robot_tpu.export.native_export_generator import (
    SERVING_FN_NAME,
    VARIABLES_DIR,
    VARIABLES_NPZ,
)
from tensor2robot_tpu.predictors.abstract_predictor import AbstractPredictor
from tensor2robot_tpu.specs import tensorspec_utils as ts


def _row_batched(call):
  """`exported.call` plus the vmap rule jax.export does not provide.

  The fleet CEM step vmaps a per-robot search over the predictor's
  device fn (cem.fleet_cem_optimize); `call_exported` has no batching
  rule, so an artifact could serve one robot but not a bucket of them.
  The artifact's leading dim is the symbolic batch "b" and PREDICT is
  row-independent, so a vmapped (B, n, ...) call IS the flat (B*n, ...)
  call: fold the mapped axis into the rows, call, unfold.
  """

  @jax.custom_batching.custom_vmap
  def serve(variables, *arrays):
    return call(variables, *arrays)

  @serve.def_vmap
  def _fold_into_rows(axis_size, in_batched, variables, *arrays):
    if any(jax.tree_util.tree_leaves(in_batched[0])):
      raise NotImplementedError(
          "vmap over the served variables of an exported artifact")
    rows = []
    for array, batched in zip(arrays, in_batched[1:]):
      if not batched:
        array = jnp.broadcast_to(array[None], (axis_size,) + array.shape)
      rows.append(array.reshape((-1,) + array.shape[2:]))
    outputs = jax.tree_util.tree_map(
        lambda out: out.reshape((axis_size, -1) + out.shape[1:]),
        serve(variables, *rows))
    return outputs, jax.tree_util.tree_map(lambda _: True, outputs)

  return serve


class ExportedModelPredictor(AbstractPredictor):
  """Polls export_root and serves the newest native artifact."""

  def __init__(self, export_root: str):
    self._export_root = export_root
    self._version = -1
    self._call = None
    self._device_serve = None
    self._device_pair = None
    self._variables = None
    self._feature_spec: Optional[ts.TensorSpecStruct] = None
    self._feature_keys = None
    self._example_parser = None

  # --- loading -------------------------------------------------------------

  def restore(self, timeout_s: float = 0.0,
              raise_on_timeout: bool = False) -> bool:
    newest = self._poll_newer_version(self._export_root, timeout_s)
    if newest is None:
      return self._timeout_unloaded(
          f"a native export under {self._export_root}", timeout_s,
          raise_on_timeout)
    export_dir = os.path.join(self._export_root, str(newest))

    def load(name):
      with open(os.path.join(export_dir, name), "rb") as f:
        return jax.export.deserialize(bytearray(f.read()))

    exported = load(SERVING_FN_NAME)
    npz_path = os.path.join(export_dir, VARIABLES_NPZ)
    if os.path.exists(npz_path):
      variables = variables_io.load_variables(npz_path)
    else:  # legacy orbax-layout artifact
      import orbax.checkpoint as ocp
      variables = ocp.StandardCheckpointer().restore(
          os.path.abspath(os.path.join(export_dir, VARIABLES_DIR)))
    feature_spec, _, extra = export_utils.read_spec_assets(export_dir)
    self._device_serve = _row_batched(exported.call)
    # An artifact of a model without the pair, or from before it was
    # exported, records none and serves through `serving_fn.bin` alone.
    pair = extra.get("factored_cem")
    self._device_pair = pair and tuple(
        _row_batched(load(pair[name]).call)
        for name in ("encode_fn", "q_from_code_fn"))
    self._call = jax.jit(exported.call)
    self._variables = jax.tree_util.tree_map(jnp.asarray, variables)
    self._feature_spec = feature_spec
    self._feature_keys = extra["feature_keys"]
    self._example_parser = None  # rebuilt on demand for the new spec
    self._version = newest
    return True

  # --- serving -------------------------------------------------------------

  def predict(
      self, features: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    self.assert_is_loaded()
    flat = self._validate_features(features)
    missing = [key for key in self._feature_keys if key not in flat]
    if missing:
      raise ValueError(
          f"Features {missing} are required by this export (all exported "
          "keys are positional inputs of the serialized computation, "
          "including specs marked optional at training time).")
    args = [np.asarray(flat[key]) for key in self._feature_keys]
    outputs = self._call(self._variables, *args)
    return {k: np.asarray(v) for k, v in outputs.items()}

  def predict_examples(self, serialized) -> Dict[str, np.ndarray]:
    """Serves a batch of SERIALIZED tf.Example records — TF-free.

    The SavedModel path parses records inside the loaded graph
    (`ExportedSavedModelPredictor.predict_examples`); the native
    artifact carries only the computation, so parsing happens here
    through the packaged feature spec and the repo's dependency-free
    tf.Example codec (with the C++ whole-batch fast path when the
    library is available) — a robot without TF can still consume the
    exact wire format the data-collection fleet logs (raw uint8 bytes,
    encoded jpeg/png, dense numerics alike, per the spec's
    data_format).
    """
    from tensor2robot_tpu.data.parser import ExampleParser
    self.assert_is_loaded()
    if getattr(self, "_example_parser", None) is None:
      self._example_parser = ExampleParser(self._feature_spec)
    features, _ = self._example_parser.parse_batch(list(serialized))
    return self.predict(features)

  def device_fn(self):
    """See AbstractPredictor.device_fn: the deserialized StableHLO call
    is traceable under an outer jit (it inlines as a call op)."""
    self.assert_is_loaded()
    serve = self._device_serve
    keys = tuple(self._feature_keys)

    def fn(variables, features):
      return serve(variables, *[features[key] for key in keys])

    return fn, self._variables

  def factored_device_fns(self):
    """See AbstractPredictor.factored_device_fns: the artifact's two
    further calls, where it carries them."""
    self.assert_is_loaded()
    if not self._device_pair:
      return None
    encode, q_from_code = self._device_pair
    keys = tuple(self._feature_keys)

    def encode_fn(variables, features):
      return encode(variables, features["image"])

    def q_from_code_fn(variables, features):
      return q_from_code(variables, *[features[key] for key in keys])

    return encode_fn, q_from_code_fn

  def get_feature_specification(self) -> ts.TensorSpecStruct:
    self.assert_is_loaded()
    return self._feature_spec

  @property
  def model_version(self) -> int:
    return self._version

  def close(self) -> None:
    self._call = None
    self._device_serve = None
    self._device_pair = None
    self._variables = None
    self._example_parser = None
    self._version = -1  # assert_is_loaded fails cleanly after close()

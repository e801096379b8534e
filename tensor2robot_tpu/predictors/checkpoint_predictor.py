"""CheckpointPredictor: rebuild the model in-process, restore a checkpoint.

Reference parity: predictors/checkpoint_predictor.py §CheckpointPredictor
(SURVEY.md §2): no export needed — the predictor owns the model's Python
code, restores the latest checkpoint from a training run dir, and serves
predict(). Uses EMA params when the run trained with use_avg_model_params
(the reference's eval/export swap).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from tensor2robot_tpu import modes
from tensor2robot_tpu.predictors.abstract_predictor import AbstractPredictor
from tensor2robot_tpu.specs import tensorspec_utils as ts


class CheckpointPredictor(AbstractPredictor):
  """Serves a T2R model directly from its checkpoint directory."""

  def __init__(self, model, checkpoint_dir: Optional[str] = None):
    """Args:
      model: an AbstractT2RModel instance (provides module + specs).
      checkpoint_dir: the training run's checkpoint dir; None allows only
        init_randomly.
    """
    self._model = model
    self._checkpoint_dir = checkpoint_dir
    self._variables = None
    self._version = -1
    self._predict = None
    self._manager = None

  def _build_predict(self):
    from tensor2robot_tpu.export import export_utils
    model = self._model

    def predict(variables, features):
      return export_utils.normalize_serving_outputs(
          model.predict_fn(variables, features))

    return jax.jit(predict)

  def restore(self, timeout_s: float = 0.0,
              raise_on_timeout: bool = False) -> bool:
    if self._checkpoint_dir is None:
      raise ValueError("No checkpoint_dir given; use init_randomly().")
    import os
    directory = os.path.abspath(self._checkpoint_dir)

    def _latest():
      if self._manager is None:
        if not os.path.isdir(directory):
          # Trainer hasn't created the run dir yet; keep polling without
          # creating it (create=True would defeat typo detection).
          return None
        self._manager = ocp.CheckpointManager(
            directory, options=ocp.CheckpointManagerOptions(create=False))
      self._manager.reload()  # pick up steps written since construction
      step = self._manager.latest_step()
      if step is None or step <= self._version:
        return None
      return step, self._manager.restore(
          step, args=ocp.args.StandardRestore())

    result = self._wait_for(
        _latest, timeout_s,
        description=f"a checkpoint under {directory}")
    if not result:
      return self._timeout_unloaded(
          f"a checkpoint under {directory}", timeout_s, raise_on_timeout)
    step, restored = result
    ema = restored.get("ema_params")
    params = ema if ema is not None else restored["params"]
    model_state = restored.get("model_state")
    # Device-resident: orbax restores host arrays, and keeping numpy here
    # would re-upload the whole weight pytree on every predict()/fused
    # control step (cf. ExportedModelPredictor.restore).
    self._variables = jax.tree_util.tree_map(jax.numpy.asarray, {
        "params": params,
        **(model_state if model_state is not None else {}),
    })
    self._version = int(step)
    if self._predict is None:
      self._predict = self._build_predict()
    return True

  def init_randomly(self) -> None:
    variables = self._model.init_variables(jax.random.key(0))
    self._variables = jax.tree_util.tree_map(jax.numpy.asarray, variables)
    self._version = 0
    if self._predict is None:
      self._predict = self._build_predict()

  def set_variables(self, variables,
                    version: Optional[int] = None,
                    cast: bool = False) -> None:
    """See AbstractPredictor.set_variables: the rollout promotion path.
    Structure must match the loaded tree — a mismatched candidate must
    fail HERE (actionable), not as a shape error inside some replica's
    next flush. Pass the candidate's export step as `version` so a
    later restore() poll cannot mistake an older on-disk checkpoint
    for news.

    cast=True is the intentional precision-cast seam (ISSUE 13): a
    dtype-drifted candidate (e.g. bf16-exported params promoted onto
    this f32-serving predictor) is cast leaf-by-leaf onto the LIVE
    tree's dtypes before installing, so the served avals — and every
    replica's compiled bucket executable — are untouched while the
    candidate's values land. Without it, dtype drift rejects exactly
    as before (an unintentional cast is a fleet-wide aval mismatch
    waiting to happen)."""
    self.assert_is_loaded()

    def check(old, new):
      if np.shape(old) != np.shape(new):
        raise ValueError(
            f"hot-swap shape mismatch: {np.shape(old)} -> "
            f"{np.shape(new)} (a reshaped candidate would recompile "
            "every bucket executable; promote via a new export "
            "instead).")
      old_dtype = np.asarray(old).dtype
      new_dtype = np.asarray(new).dtype
      if old_dtype != new_dtype:
        # jnp.issubdtype, not np: bfloat16 is an ml_dtypes extension
        # numpy's floating hierarchy does not recognize.
        floating = (jax.numpy.issubdtype(old_dtype, jax.numpy.floating)
                    and jax.numpy.issubdtype(new_dtype,
                                             jax.numpy.floating))
        if cast and floating:
          # The explicit seam: candidate values at the live avals.
          # Scoped to floating->floating — the documented precision
          # drift. A non-float mismatch (an int counter arriving as
          # float, a uint8 table as f32) is STRUCTURAL drift; casting
          # it would silently truncate/wrap values fleet-wide, so it
          # rejects below regardless of `cast`.
          return jax.numpy.asarray(new).astype(old_dtype)
        raise ValueError(
            f"hot-swap dtype mismatch: {old_dtype} -> {new_dtype} "
            "(the fleet's AOT executables were compiled against the "
            "old avals; a dtype change would fail every replica's "
            "next flush — promote via a new export"
            + (", or pass cast=True for an intentional precision "
               "cast onto the served dtypes" if floating else
               "; a non-floating mismatch is structural drift the "
               "cast seam refuses") + ").")
      return new

    checked = jax.tree_util.tree_map(check, self._variables, variables)
    self._variables = jax.tree_util.tree_map(jax.numpy.asarray, checked)
    self._version = self._next_swap_version(version)

  def predict(
      self, features: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    self.assert_is_loaded()
    flat = self._validate_features(features)
    outputs = self._predict(self._variables, flat)
    return {k: np.asarray(v) for k, v in outputs.items()}

  def device_fn(self):
    """See AbstractPredictor.device_fn: the model's predict_fn is plain
    traced JAX, directly composable under an outer jit."""
    self.assert_is_loaded()
    from tensor2robot_tpu.export import export_utils
    model = self._model

    def fn(variables, features):
      return export_utils.normalize_serving_outputs(
          model.predict_fn(variables, ts.TensorSpecStruct(features)))

    return fn, self._variables

  def factored_device_fns(self):
    """See AbstractPredictor.factored_device_fns: the model's own pair."""
    return self._model.factored_cem_fns()

  def get_feature_specification(self) -> ts.TensorSpecStruct:
    return ts.flatten_spec_structure(
        self._model.preprocessor.get_out_feature_specification(
            modes.PREDICT))

  @property
  def model_version(self) -> int:
    return self._version

  def close(self) -> None:
    self._variables = None
    self._predict = None
    self._version = -1  # assert_is_loaded fails cleanly after close()
    if self._manager is not None:
      self._manager.close()
      self._manager = None

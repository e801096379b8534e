"""AbstractPredictor: the robot-facing inference contract.

Reference parity: predictors/abstract_predictor.py §AbstractPredictor
(SURVEY.md §2): predict/restore/init_randomly/model_version/
get_feature_specification/close, with restore-with-timeout semantics.
"""

from __future__ import annotations

import abc
from typing import Dict, Optional

import numpy as np

from tensor2robot_tpu.specs import tensorspec_utils as ts


class AbstractPredictor(abc.ABC):
  """Loads a trained artifact and serves predict() on the robot."""

  @abc.abstractmethod
  def restore(self, timeout_s: float = 0.0,
              raise_on_timeout: bool = False) -> bool:
    """Loads (or hot-reloads) the newest available model.

    Blocks up to timeout_s waiting for a first model to appear (robots
    start before the trainer's first export — SURVEY.md §2 predictors
    row), polling with jittered exponential backoff
    (utils/backoff.py: a robot fleet restarting together must not
    hammer the export filesystem in lockstep). Returns True when a
    model is loaded. With ``raise_on_timeout``, a timeout that leaves
    NO model loaded raises ``utils.backoff.PollTimeout`` naming the
    path that was being waited on instead of returning False — the
    loud form for deployments where silently proceeding without a
    model is worse than crashing with the path in the message.
    """

  @abc.abstractmethod
  def predict(
      self, features: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Runs inference on a batched numpy feature dict."""

  def predict_batched(
      self, features: Dict[str, np.ndarray],
      ladder=None) -> Dict[str, np.ndarray]:
    """predict() with the batch dim padded to a bounded size ladder.

    Fleet serving flushes batches of whatever size the deadline caught;
    calling predict() raw would compile one executable per distinct
    size (and per CEM sample multiple on the host path). This pads the
    leading dim up to a fixed rung — a `serving.BucketLadder` when
    given, else the next power of two — runs predict(), and slices the
    outputs back, so the executable count stays bounded no matter what
    request sizes arrive. Padding repeats the last row (numerically
    benign through normalization layers); padded outputs are dropped.
    """
    from tensor2robot_tpu.serving.bucketing import pad_to
    sizes = {np.asarray(v).shape[0] for v in dict(features).values()}
    if len(sizes) != 1:
      raise ValueError(f"inconsistent leading batch dims: {sizes}")
    n = sizes.pop()
    bucket = ladder.bucket_for(n) if ladder is not None else (
        1 << max(0, (n - 1).bit_length()))
    if bucket == n:
      return self.predict(features)
    padded = {k: pad_to(np.asarray(v), bucket)
              for k, v in dict(features).items()}
    return {k: v[:n] for k, v in self.predict(padded).items()}

  @abc.abstractmethod
  def get_feature_specification(self) -> ts.TensorSpecStruct:
    """The (flat) feature spec predict() expects."""

  @property
  @abc.abstractmethod
  def model_version(self) -> int:
    """Monotonic version of the loaded model; -1 before restore."""

  def init_randomly(self) -> None:
    """Initializes with random weights (debug/bring-up; reference
    §init_randomly). Optional: default raises."""
    raise NotImplementedError(
        f"{type(self).__name__} does not support init_randomly.")

  def set_variables(self, variables,
                    version: Optional[int] = None,
                    cast: bool = False) -> None:
    """Hot-swaps the served params in place (same tree structure/shapes).

    `cast` is the explicit precision-cast seam (ISSUE 13): a candidate
    whose leaves arrive at a different floating dtype than the served
    tree (e.g. a bf16-exported checkpoint promoted onto an f32-serving
    predictor, or vice versa) is REJECTED by default — the fleet's AOT
    executables were compiled against the live avals, and a silent
    dtype change would fail every replica's next flush. Passing
    cast=True declares the drift intentional: implementations cast the
    candidate onto the LIVE tree's dtypes before installing it, so the
    served avals (and therefore every compiled consumer) are untouched
    while the candidate's VALUES land. Note the scoring-precision tier
    itself never needs this — bf16 scoring quantizes inside the tier's
    executables (cem.cast_scoring_variables) and the master params stay
    f32; the seam exists for params that were ALREADY cast on disk.

    The rollout controller's promotion path (serving/rollout.py): a
    canary-validated candidate cuts over by swapping the variables the
    predictor hands out — an atomic pointer swap under the GIL.
    `version` is the candidate's step in the SAME namespace
    model_version lives in (checkpoint/export global step): passing it
    keeps restore()'s newest-wins staleness check honest — without it,
    a promotion from export step 250 onto a predictor at checkpoint
    step 100 would leave model_version at 101, and a later restore()
    poll finding checkpoint 150 would silently overwrite the promoted
    params with OLDER ones. When None, the version bumps by one
    (in-memory predictors with counter versions). Implementations
    clamp to stay monotonic. Compiled consumers (the fleet policies'
    bucket executables, AOT CEM programs) take variables as an
    ARGUMENT, so a swap is never a recompile; the hot-reload ledger
    test pins that. Optional: predictors whose params live inside an
    opaque artifact (e.g. a TF SavedModel) raise, and rollout for them
    goes through restore() on a new artifact instead.
    """
    raise NotImplementedError(
        f"{type(self).__name__} does not support in-place variable "
        "hot-swap; publish a new export and call restore().")

  def _next_swap_version(self, version: Optional[int]) -> int:
    """Monotonic model_version for a set_variables swap (shared rule)."""
    bumped = self.model_version + 1
    return bumped if version is None else max(bumped, int(version))

  def device_fn(self):
    """Device-resident serving entry for jit-composed policies.

    Returns (fn, variables) where ``fn(variables, flat_features) ->
    outputs dict`` is traceable under jax.jit — so wrappers like the
    QT-Opt CEM loop can fuse sampling + scoring + refitting into ONE
    compiled program per control step instead of shipping sample
    batches across the host boundary every predict() (the host path
    moves the tiled image H2D per CEM iteration; this path moves it
    once). Optional: predictors without a JAX-native computation
    (e.g. the TF SavedModel predictor) raise, and callers fall back
    to predict().
    """
    raise NotImplementedError(
        f"{type(self).__name__} has no device-resident serving path.")

  def factored_device_fns(self):
    """The model's factored scoring pair beside `device_fn`, or None.

    ``(encode_fn, q_from_code_fn)``, both ``(variables, features)``
    like `device_fn`'s fn and over the same variables:
    ``encode_fn(variables, {"image"})`` gives the code of each frame,
    ``q_from_code_fn(variables, {"image": code, "action"})`` the
    outputs dict (`CriticModel.factored_cem_fns`). A CEM search then
    encodes each frame once and scores candidate actions over the code
    (`cem.make_cem_states_and_score`). None where the model, or the
    artifact, has no such pair: callers score through `device_fn`.
    """
    return None

  def close(self) -> None:
    """Releases resources."""

  def assert_is_loaded(self) -> None:
    if self.model_version < 0:
      raise ValueError("Predictor has no model loaded; call restore().")

  def _validate_features(
      self, features: Dict[str, np.ndarray]) -> ts.TensorSpecStruct:
    """Validates a batched feature dict against the spec (batch dim free)."""
    spec = self.get_feature_specification()
    flat = ts.TensorSpecStruct(
        (k, np.asarray(v)) for k, v in dict(features).items())
    return ts.validate_and_flatten(spec, flat, batched=True)

  def _poll_newer_version(self, export_root: str,
                          timeout_s: float) -> Optional[int]:
    """Waits for an export version newer than model_version; None if the
    timeout expires first (shared by the export-dir predictors)."""
    from tensor2robot_tpu.export import export_utils

    def newest():
      versions = export_utils.list_export_versions(export_root)
      candidate = versions[-1] if versions else None
      if candidate is not None and candidate > self.model_version:
        return candidate
      return None

    return self._wait_for(newest, timeout_s,
                          description=f"an export under {export_root}")

  @staticmethod
  def _wait_for(predicate, timeout_s: float,
                description: Optional[str] = None):
    """Polls predicate() until truthy or timeout; returns its value.

    Jittered exponential backoff (utils/backoff.py) instead of the old
    fixed 0.5s cadence: a restarting robot fleet decorrelates instead
    of stampeding the export filesystem, and a long wait backs off to
    ~2s polls. `description` names the awaited path for the loud
    restore(raise_on_timeout=True) form.
    """
    from tensor2robot_tpu.utils import backoff
    return backoff.poll_with_backoff(
        predicate, timeout_s, initial_s=0.1, max_s=2.0,
        description=description)

  def _timeout_unloaded(self, description: str, timeout_s: float,
                        raise_on_timeout: bool) -> bool:
    """Shared restore() timeout exit: False when a model is already
    serving (a hot-reload poll that found nothing new is healthy), a
    PollTimeout naming `description` when raise_on_timeout and NOTHING
    was ever loaded (the robot would otherwise start serving thin
    air)."""
    if self.model_version >= 0:
      return True
    if raise_on_timeout:
      from tensor2robot_tpu.utils import backoff
      raise backoff.PollTimeout(description, timeout_s, 0)
    return False

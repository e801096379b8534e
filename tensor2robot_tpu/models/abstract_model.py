"""AbstractT2RModel — the portable model abstraction, rebuilt functional-first.

Reference parity: models/model_interface.py §ModelInterface,
models/abstract_model.py §AbstractT2RModel (SURVEY.md §2, §3.1). The
reference model owned: spec declaration, network fn, loss fn, metrics fn,
optimizer factory, and the Estimator model_fn glue. The rebuild keeps the
first five and deletes the glue — a JAX train step is just

    grads = jax.grad(model.model_train_fn)(params, features, labels, rng)

pjit-sharded by the trainer (train/train_eval.py), so there is no
device_type branching (same XLA program serves CPU/GPU/TPU), no
TPUEstimatorSpec, and no host_call: metrics are returned as arrays and the
host loop writes them. EMA ("use_avg_model_params") and warm-start
("init_from_checkpoint") are declared here and executed by the trainer.

Model contract:
  - ``build_module()`` returns a Flax module whose ``__call__(features,
    mode)`` maps a TensorSpecStruct of arrays → TensorSpecStruct/dict of
    outputs. Modules run in ``compute_dtype`` (bfloat16 by default — MXU
    native) with parameters kept in ``param_dtype`` (float32 master copy).
  - ``loss_fn(outputs, features, labels)`` → (scalar loss, metrics dict).
  - Everything is pure: RNGs are passed explicitly, mutable collections
    (batch_stats) are threaded functionally.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from tensor2robot_tpu import modes
from tensor2robot_tpu.preprocessors.abstract_preprocessor import (
    AbstractPreprocessor,
    ModelNoOpPreprocessor,
)
from tensor2robot_tpu.specs import tensorspec_utils as ts

# variables = {"params": ..., **model_state}; model_state holds non-param
# collections (batch_stats, ...).
Variables = Mapping[str, Any]
Metrics = Dict[str, jnp.ndarray]


class AbstractT2RModel(abc.ABC):
  """Spec-declaring, loss-defining, optimizer-providing model base."""

  def __init__(
      self,
      optimizer_fn: Optional[Callable[[], optax.GradientTransformation]] = None,
      use_avg_model_params: bool = False,
      avg_model_params_decay: float = 0.9999,
      init_from_checkpoint: Optional[str] = None,
      init_from_checkpoint_assignment_map: Optional[Dict[str, str]] = None,
      compute_dtype: Any = jnp.bfloat16,
      param_dtype: Any = jnp.float32,
  ):
    """See class docstring.

    Args:
      optimizer_fn: factory returning an optax transformation; None →
        ``create_optimizer``'s default (Adam 1e-4, the reference default).
      use_avg_model_params: maintain a Polyak/EMA copy of params, used for
        eval and export (reference §use_avg_model_params).
      avg_model_params_decay: EMA decay.
      init_from_checkpoint: checkpoint path to warm-start from (reference
        §init_from_checkpoint); applied by the trainer before step 0.
      init_from_checkpoint_assignment_map: optional {source_prefix:
        target_prefix} param renaming for the warm-start, in
        tf.train.init_from_checkpoint's direction — checkpoint name on
        the left (see train.checkpoints.merge_params).
      compute_dtype: activation dtype inside the network (bfloat16 keeps
        matmuls on the MXU's native path).
      param_dtype: master parameter dtype.
    """
    self._optimizer_fn = optimizer_fn
    self.use_avg_model_params = use_avg_model_params
    self.avg_model_params_decay = avg_model_params_decay
    self.init_from_checkpoint = init_from_checkpoint
    self.init_from_checkpoint_assignment_map = (
        init_from_checkpoint_assignment_map)
    self.compute_dtype = compute_dtype
    self.param_dtype = param_dtype
    self._module: Optional[nn.Module] = None
    self._preprocessor: Optional[AbstractPreprocessor] = None

  # --- specs (reference §get_feature_specification et al.) ----------------

  @abc.abstractmethod
  def get_feature_specification(self, mode: str) -> ts.SpecStructure:
    """Model-consumed feature specs for `mode`."""

  def get_label_specification(self, mode: str) -> ts.SpecStructure:
    """Model-consumed label specs for `mode` (default: none)."""
    del mode
    return ts.TensorSpecStruct()

  @property
  def preprocessor(self) -> AbstractPreprocessor:
    """The preprocessor pairing this model with the input pipeline.

    Default: identity, resolving the model's own specs per mode. Models
    with image pipelines override with e.g. preprocessors.ImagePreprocessor.
    """
    if self._preprocessor is None:
      self._preprocessor = self.create_preprocessor()
    return self._preprocessor

  def create_preprocessor(self) -> AbstractPreprocessor:
    return ModelNoOpPreprocessor(self)

  # --- network ------------------------------------------------------------

  @abc.abstractmethod
  def build_module(self) -> nn.Module:
    """Builds the Flax module; called once and cached."""

  @property
  def module(self) -> nn.Module:
    if self._module is None:
      self._module = self.build_module()
    return self._module

  def init_variables(
      self,
      rng: jax.Array,
      batch_size: int = 1,
      mode: str = modes.TRAIN,
  ) -> Variables:
    """Initializes variables from the declared specs (no data needed)."""
    spec = self.preprocessor.get_out_feature_specification(mode)
    features = jax.tree_util.tree_map(
        lambda s: jnp.zeros((batch_size,) + s.shape, s.dtype),
        ts.flatten_spec_structure(spec),
        is_leaf=lambda x: isinstance(x, ts.ExtendedTensorSpec))
    param_rng, dropout_rng = jax.random.split(rng)
    return self.module.init(
        {"params": param_rng, "dropout": dropout_rng}, features, mode)

  def inference_network_fn(
      self,
      variables: Variables,
      features: ts.TensorSpecStruct,
      mode: str,
      rngs: Optional[Dict[str, jax.Array]] = None,
  ) -> Tuple[Any, Dict[str, Any]]:
    """Functional forward pass (reference §inference_network_fn).

    Returns:
      (outputs, new_model_state): new_model_state carries updated mutable
      collections (batch_stats) in train mode; empty otherwise.
    """
    mutable = self.mutable_collections() if mode == modes.TRAIN else []
    if mutable:
      outputs, new_state = self.module.apply(
          variables, features, mode, rngs=rngs, mutable=mutable)
      return outputs, dict(new_state)
    outputs = self.module.apply(variables, features, mode, rngs=rngs)
    return outputs, {}

  def mutable_collections(self) -> Tuple[str, ...]:
    """Non-param variable collections updated during training."""
    return ("batch_stats",)

  # --- loss / metrics -----------------------------------------------------

  @abc.abstractmethod
  def loss_fn(
      self,
      outputs: Any,
      features: ts.TensorSpecStruct,
      labels: Optional[ts.TensorSpecStruct],
  ) -> Tuple[jnp.ndarray, Metrics]:
    """Scalar training loss + metrics (reference §model_train_fn core)."""

  def model_train_fn(
      self,
      variables: Variables,
      features: ts.TensorSpecStruct,
      labels: Optional[ts.TensorSpecStruct],
      rngs: Optional[Dict[str, jax.Array]] = None,
  ) -> Tuple[jnp.ndarray, Tuple[Metrics, Dict[str, Any]]]:
    """loss + (metrics, updated model state); differentiate w.r.t. params.

    The trainer wraps this in jax.value_and_grad(..., has_aux=True) inside
    the pjit'd step (SURVEY.md §3.1 device-side path).
    """
    outputs, new_state = self.inference_network_fn(
        variables, features, modes.TRAIN, rngs=rngs)
    with jax.named_scope("loss"):
      loss, metrics = self.loss_fn(outputs, features, labels)
    metrics = dict(metrics)
    metrics.setdefault("loss", loss)
    return loss, (metrics, new_state)

  def model_eval_fn(
      self,
      variables: Variables,
      features: ts.TensorSpecStruct,
      labels: Optional[ts.TensorSpecStruct],
  ) -> Metrics:
    """Eval metrics (reference §model_eval_fn). EMA params are swapped in
    by the trainer before this runs when use_avg_model_params is set."""
    outputs, _ = self.inference_network_fn(variables, features, modes.EVAL)
    loss, metrics = self.loss_fn(outputs, features, labels)
    metrics = dict(metrics)
    metrics.setdefault("loss", loss)
    return metrics

  def model_image_summaries_fn(
      self,
      variables: Variables,
      features: ts.TensorSpecStruct,
  ) -> Optional[Dict[str, Any]]:
    """Optional eval-time image summaries: {tag: HWC/HW uint8 or [0,1]
    float image} rendered from one eval batch (reference: tf.summary
    image summaries through host_call — e.g. grasp2vec localization
    heatmaps). Default None = no images. Called by the eval loop with
    the (EMA) eval variables and the last eval batch; written via
    MetricWriter.write_images."""
    del variables, features
    return None

  # --- optimizer (reference §create_optimizer / §create_train_op) ---------

  def create_optimizer(self) -> optax.GradientTransformation:
    """The gradient transformation for training.

    Cross-replica gradient averaging is NOT here (the reference wrapped
    CrossShardOptimizer at this point): under pjit, gradients of a
    data-sharded batch are reduced by XLA automatically — the mesh is the
    all-reduce.
    """
    if self._optimizer_fn is not None:
      return self._optimizer_fn()
    return optax.adam(1e-4)

  # --- serving ------------------------------------------------------------

  def factored_cem_fns(self):
    """(encode_fn, q_from_code_fn) where a CEM search can encode each
    state once and score candidate actions over the code, else None:
    see `CriticModel.factored_cem_fns`, the one implementation."""
    return None

  def predict_fn(
      self,
      variables: Variables,
      features: ts.TensorSpecStruct,
  ) -> Any:
    """Pure inference entry used by export/predictors (PREDICT mode)."""
    outputs, _ = self.inference_network_fn(variables, features,
                                           modes.PREDICT)
    return outputs

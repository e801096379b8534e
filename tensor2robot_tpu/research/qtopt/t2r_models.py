"""QT-Opt grasping Q-function — the legacy grasping net, TPU-first.

Reference parity: research/qtopt/t2r_models.py §LegacyGraspingModelQ /
grasping Q-model (SURVEY.md §2): conv tower over a 472×472 camera image;
the action/state vector is embedded with FCs, tiled over the spatial map
and merged into the tower mid-way; more convs → FC → sigmoid Q ∈ [0,1];
cross-entropy loss against Bellman-target labels (produced off-repo by
the QT-Opt Bellman updater — SURVEY.md notes that fleet is not part of
the reference either). CEM action optimization at serving lives in
research/qtopt/cem.py.

TPU design notes:
  - The whole net is static-shape NHWC bfloat16; the stem uses strided
    convs + max-pool to collapse 472² to 59² quickly, putting >90% of
    FLOPs in MXU-friendly 3×3 convs at modest spatial sizes.
  - Action merge is add-after-projection (FiLM-lite): tile-free
    broadcast of a (B, 1, 1, C) embedding, fusing into the surrounding
    convs under XLA instead of materializing a tiled tensor.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import modes
from tensor2robot_tpu.config import configurable
from tensor2robot_tpu.layers.vision_layers import normalize_image
from tensor2robot_tpu.models.critic_model import CriticModel
from tensor2robot_tpu.ops import stem_conv, strided_conv
from tensor2robot_tpu.ops.pool import max_pool_reshape
from tensor2robot_tpu.preprocessors.image_preprocessors import (
    ImagePreprocessor,
)
from tensor2robot_tpu.specs import tensorspec_utils as ts

IMAGE_SIZE = 472
ACTION_SIZE = 4  # cartesian displacement (3) + gripper command (1)


class _GraspingQModule(nn.Module):
  """The legacy grasping net as one Flax module."""

  action_size: int = ACTION_SIZE
  compute_dtype: Any = jnp.bfloat16
  # "batch" is the reference-parity line. "group" (GroupNorm) removes
  # BN's cross-batch statistics passes — measured on v5e: NOT faster
  # (BENCH_r02), which is how we know the tower is MXU-tiling-bound,
  # not bandwidth-bound.
  norm_kind: str = "batch"
  # "conv" (parity): Conv 64×(6,6)/4 straight on the 3-channel image —
  # 3 of the MXU's 128 input lanes do work (~3% stem MFU measured,
  # ~40% of the whole train step). "space_to_depth": the same
  # block-to-channels idea (8×8 window, stride 4 — covers the parity
  # stem's (6,6) receptive field; strictly larger stem function
  # class), implemented via ops/stem_conv.folded_s2d_stem: one
  # standard (8,2)/(4,1) conv over a reshaped view, NO transpose.
  # Round 2's naive 6D-transpose space-to-depth measured SLOWER than
  # parity (159 vs 189 steps/s, v5e, 2026-07-30) because the 472²
  # transpose outweighed the lane gain; the folded formulation keeps
  # the lane gain and drops the transpose (stem fwd+grad_w 1269 µs vs
  # 1701 µs parity, 2026-07-31 — ops/stem_conv.py docstring).
  stem_kind: str = "conv"
  # "parity": flax nn.max_pool + strided nn.Conv lowerings (the
  # reference-shaped defaults). "fast": the SAME functions via the
  # TPU-friendlier formulations — ops/pool.max_pool_reshape (no
  # SelectAndScatter backward) and ops/strided_conv.strided3x3_same
  # (lanes-folded strided conv) — with IDENTICAL param names/shapes
  # (post_conv{i}/kernel+bias), so checkpoints interchange freely.
  # Outputs differ only by float reassociation (tested). Adoption as
  # default awaits one paired chip run (ROADMAP S3b).
  impl: str = "parity"

  def setup(self):
    """setup()-structured so that the frame's half and the action's half
    are callable apart (`encode`, `q_from_code`): attribute names are
    the parameter names, the same as the one-method form had, so
    checkpoints interchange."""
    dtype = self.compute_dtype
    if self.norm_kind == "batch":
      # use_running_average is given at the call: it follows the mode.
      norm = lambda: nn.BatchNorm(dtype=dtype)
    elif self.norm_kind == "group":
      norm = lambda: nn.GroupNorm(num_groups=8, dtype=dtype)
    else:
      raise ValueError(f"Unknown norm_kind {self.norm_kind!r}")
    if self.stem_kind == "conv":
      self.stem = nn.Conv(64, (6, 6), strides=(4, 4), dtype=dtype)
    elif self.stem_kind == "space_to_depth":
      # 3: the image spec's channels (setup sees no input to ask).
      self.stem_s2d_kernel = self.param(
          "stem_s2d_kernel",
          lambda key: stem_conv.init_folded_stem_weights(key, 3, 64))
      self.stem_s2d_bias = self.param(
          "stem_s2d_bias", nn.initializers.zeros, (64,))
    else:
      raise ValueError(f"Unknown stem_kind {self.stem_kind!r}")
    self.stem_bn = norm()
    for i in range(3):
      setattr(self, f"pre_conv{i}", nn.Conv(64, (3, 3), dtype=dtype))
      setattr(self, f"pre_bn{i}", norm())
      if self.impl == "fast":
        post = strided_conv.FoldedStridedConv3x3(features=64, dtype=dtype)
      else:
        post = nn.Conv(64, (3, 3), strides=(2, 2), dtype=dtype)
      setattr(self, f"post_conv{i}", post)
      setattr(self, f"post_bn{i}", norm())
    self.action_fc1 = nn.Dense(64, dtype=dtype)
    self.action_fc2 = nn.Dense(64, dtype=dtype)
    self.fc1 = nn.Dense(64, dtype=dtype)
    self.q_head = nn.Dense(1, dtype=jnp.float32)

  @nn.nowrap  # a helper, not a scope of its own in the compiled HLO
  def _norm(self, name: str, x, mode: str):
    layer = getattr(self, name)
    if self.norm_kind == "batch":
      return layer(x, use_running_average=mode != modes.TRAIN)
    return layer(x)

  def encode(self, features, mode: str = modes.PREDICT):
    """{"image"} → the (B, 59, 59, 64) compute-dtype code at 472²:
    everything that depends on the frame alone (92% of a row's FLOPs).
    In PREDICT mode BatchNorm reads running statistics, so rows are
    independent and a CEM search encodes each frame once
    (`CriticModel.factored_cem_fns`)."""
    dtype = self.compute_dtype
    # Scopes for what is no flax module (those have their own): every
    # device op of the step then has a stable path in the compiled HLO.
    with jax.named_scope("normalize_image"):
      x = normalize_image(features["image"], dtype)
    # Stem: 472 -> 118 -> 59.
    if self.stem_kind == "conv":
      x = self.stem(x)
    else:
      x = (stem_conv.folded_s2d_stem(x, self.stem_s2d_kernel.astype(dtype))
           + self.stem_s2d_bias.astype(dtype))
    x = nn.relu(self._norm("stem_bn", x, mode))
    with jax.named_scope("stem_pool"):
      if (self.impl == "fast" and x.shape[1] % 2 == 0
          and x.shape[2] % 2 == 0):
        x = max_pool_reshape(x)
      else:
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
    for i in range(3):
      x = nn.relu(self._norm(
          f"pre_bn{i}", getattr(self, f"pre_conv{i}")(x), mode))
    return x

  def q_from_code(self, features, mode: str = modes.PREDICT):
    """{"image": the code, "action"[, "state"]} → the Q logit. The code
    rides the `image` key, so the tiled score's broadcast applies to it
    unchanged."""
    dtype = self.compute_dtype
    x = features["image"]
    # Action (and optional state vector) merge.
    with jax.named_scope("wire_cast"):
      action = features["action"].astype(dtype)
    if action.shape[-1] != self.action_size:
      raise ValueError(
          f"Expected action dim {self.action_size}, got "
          f"{action.shape[-1]}.")
    merge_inputs = [action]
    if "state" in features:
      with jax.named_scope("wire_cast"):
        merge_inputs.append(features["state"].astype(dtype))
    embedding = jnp.concatenate(merge_inputs, axis=-1)
    embedding = nn.relu(self.action_fc1(embedding))
    embedding = self.action_fc2(embedding)
    x = nn.relu(x + embedding[:, None, None, :])

    # Post-merge tower: 59 -> 30 -> 15 -> 8 (SAME/2 each).
    for i in range(3):
      x = nn.relu(self._norm(
          f"post_bn{i}", getattr(self, f"post_conv{i}")(x), mode))

    x = jnp.mean(x, axis=(1, 2))  # global pool → (B, 64)
    x = nn.relu(self.fc1(x))
    q_logit = self.q_head(x)[:, 0]
    return ts.TensorSpecStruct({"q_predicted": q_logit})

  def __call__(self, features, mode: str):
    # The pair composed: the same ops in the same order in every mode.
    rest = {key: features[key] for key in ("action", "state")
            if key in features}
    return self.q_from_code(
        {"image": self.encode(features, mode), **rest}, mode)


@configurable
class QTOptGraspingModel(CriticModel):
  """(image, action) → grasp-success Q, cross-entropy vs Bellman target."""

  def __init__(self, image_size: int = IMAGE_SIZE,
               in_image_size: Optional[int] = None,
               action_size: int = ACTION_SIZE,
               state_size: int = 0,
               distort: bool = False,
               uint8_images: bool = False,
               norm: str = "batch",
               stem: str = "conv",
               wire_format: str = "jpeg",
               impl: str = "parity",
               **kwargs):
    """state_size > 0 adds a proprioceptive `state` vector feature
    (gripper status etc., reference's non-image state).

    uint8_images keeps camera images uint8 all the way to the device
    (the cast + 1/255 rescale runs on-chip, fused into the stem conv):
    4x less host→device and robot→predictor bandwidth for identical
    math. Changes the serving signature — robots send uint8.

    wire_format: how images arrive in tf.Example records — "jpeg"
    (reference parity: encoded, host-decoded) or "raw" (the image
    tensor's own bytes, zero decode cost; 472²×3 ≈ 668 KB/record vs
    ~16 KB JPEG — the trade robots make when host CPU, not disk or
    network, bounds the pipeline).

    norm: "batch" (reference parity) or "group"; stem: "conv" (parity)
    or "space_to_depth" (MXU-friendly stem lanes); impl: "parity" or
    "fast" (same function + same checkpoint layout via TPU-friendlier
    pool/strided-conv formulations) — see _GraspingQModule field
    docs."""
    super().__init__(**kwargs)
    if wire_format not in ("jpeg", "raw"):
      raise ValueError(f"wire_format must be 'jpeg' or 'raw', got "
                       f"{wire_format!r}")
    if impl not in ("parity", "fast"):
      raise ValueError(f"impl must be 'parity' or 'fast', got {impl!r}")
    self._image_size = image_size
    self._in_image_size = in_image_size or image_size
    self._action_size = action_size
    self._state_size = state_size
    self._distort = distort
    self._image_dtype = np.uint8 if uint8_images else np.float32
    self._norm = norm
    self._stem = stem
    self._wire_format = wire_format
    self._impl = impl

  def get_feature_specification(self, mode: str) -> ts.TensorSpecStruct:
    del mode
    spec = ts.TensorSpecStruct({
        "image": ts.ExtendedTensorSpec(
            (self._image_size, self._image_size, 3), self._image_dtype,
            name="image"),
        "action": ts.ExtendedTensorSpec(
            (self._action_size,), np.float32, name="action"),
    })
    if self._state_size:
      spec["state"] = ts.ExtendedTensorSpec(
          (self._state_size,), np.float32, name="state")
    return spec

  def get_label_specification(self, mode: str) -> ts.TensorSpecStruct:
    del mode
    return ts.TensorSpecStruct({
        self.target_key: ts.ExtendedTensorSpec(
            (), np.float32, name=self.target_key),
    })

  def create_preprocessor(self):
    return ImagePreprocessor(
        feature_spec=self.get_feature_specification(modes.TRAIN),
        label_spec=self.get_label_specification(modes.TRAIN),
        image_key="image",
        in_image_shape=(self._in_image_size, self._in_image_size, 3),
        data_format=None if self._wire_format == "raw" else "jpeg",
        distort=self._distort,
    )

  def build_module(self) -> nn.Module:
    return _GraspingQModule(
        action_size=self._action_size,
        compute_dtype=self.compute_dtype,
        norm_kind=self._norm,
        stem_kind=self._stem,
        impl=self._impl)

  def partition_rules(self, axis: str = "model"):
    """Regex partition rules → PartitionSpecs for tensor parallelism.

    The tower is column-parallel on its 64-wide channel dim: every conv
    kernel (HWIO, both stems, the parity and fast post-conv forms share
    names by construction) and dense kernel splits its OUTPUT features
    over `axis`, and the per-channel vectors riding those outputs
    (biases, norm scale/bias) split the same way, so each shard owns a
    contiguous channel slice end to end — the only cross-shard
    collectives are where channels actually mix (the next layer's
    input contraction). The f32 ``q_head`` (64→1) stays replicated:
    splitting a width-1 output buys nothing. Matched first-hit-wins by
    ``parallel.tp_rules.match_partition_rules``; the catch-all keeps
    future scalars/aux leaves replicated rather than unmatched.
    """
    from jax.sharding import PartitionSpec as P
    return (
        (r"(stem|pre_conv\d|post_conv\d)/kernel", P(None, None, None, axis)),
        (r"stem_s2d_kernel", P(None, None, None, axis)),
        (r"(action_fc\d|fc1)/kernel", P(None, axis)),
        (r"(stem|pre_conv\d|post_conv\d|action_fc\d|fc1)/bias", P(axis)),
        (r"stem_s2d_bias", P(axis)),
        (r"(stem_bn|pre_bn\d|post_bn\d)/(scale|bias)", P(axis)),
        (r".*", P()),
    )

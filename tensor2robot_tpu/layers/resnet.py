"""ResNet v1 + FiLM-conditioned variant.

Reference parity: layers/resnet.py §resnet_model and
layers/film_resnet_model.py (SURVEY.md §2): ResNet feature towers
(grasp2vec uses ResNet-50) and the FiLM variant where a task/context
embedding modulates each residual block (VRGripper). TPU-first: NHWC,
bfloat16 activations with float32 batch-norm statistics, static shapes.

FiLM (feature-wise linear modulation): per-block (gamma, beta) projected
from a conditioning embedding scale/shift the post-BN activations —
`film_gamma * x + film_beta` — so one tower serves many tasks.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers.vision_layers import make_norm, normalize_image
from tensor2robot_tpu.ops.strided_conv import FoldedStridedConv3x3

# depth -> (block sizes, bottleneck?)
_CONFIGS = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
}


class _Film(nn.Module):
  """Projects a context embedding to (gamma, beta) for `width` channels."""

  width: int
  dtype: Any

  @nn.compact
  def __call__(self, x: jnp.ndarray, context: jnp.ndarray) -> jnp.ndarray:
    gamma_beta = nn.Dense(2 * self.width, dtype=self.dtype,
                          name="film_proj")(context.astype(self.dtype))
    gamma, beta = jnp.split(gamma_beta[:, None, None, :], 2, axis=-1)
    # Residual formulation (1 + gamma): identity at init.
    return x * (1.0 + gamma) + beta


class _Block(nn.Module):
  """Basic (2-conv) or bottleneck (3-conv) residual block, optional FiLM."""

  width: int
  stride: int
  bottleneck: bool
  use_film: bool
  dtype: Any
  norm_kind: str = "batch"
  # "parity" = nn.Conv strided lowerings; "fast" = the 3×3 stride-2
  # convs go through ops/strided_conv.FoldedStridedConv3x3 — same
  # function, same param layout (checkpoints interchange), folded
  # backward shapes. Stride-1 and 1×1 convs are unaffected.
  impl: str = "parity"

  def _conv3x3_strided(self, features: int, name: str):
    if self.impl == "fast" and self.stride == 2:
      return FoldedStridedConv3x3(features, use_bias=False,
                                  dtype=self.dtype, name=name)
    return nn.Conv(features, (3, 3), strides=(self.stride,) * 2,
                   use_bias=False, dtype=self.dtype, name=name)

  @nn.compact
  def __call__(self, x, context, train: bool):
    norm = make_norm(self.norm_kind, train, self.dtype)
    out_width = self.width * (4 if self.bottleneck else 1)
    residual = x
    if residual.shape[-1] != out_width or self.stride != 1:
      residual = nn.Conv(out_width, (1, 1), strides=(self.stride,) * 2,
                         use_bias=False, dtype=self.dtype,
                         name="proj_conv")(x)
      residual = norm("proj_bn")(residual)

    if self.bottleneck:
      y = nn.Conv(self.width, (1, 1), use_bias=False, dtype=self.dtype,
                  name="conv1")(x)
      y = nn.relu(norm("bn1")(y))
      y = self._conv3x3_strided(self.width, "conv2")(y)
      y = nn.relu(norm("bn2")(y))
      y = nn.Conv(out_width, (1, 1), use_bias=False, dtype=self.dtype,
                  name="conv3")(y)
      y = norm("bn3")(y)
    else:
      y = self._conv3x3_strided(self.width, "conv1")(x)
      y = nn.relu(norm("bn1")(y))
      y = nn.Conv(out_width, (3, 3), use_bias=False, dtype=self.dtype,
                  name="conv2")(y)
      y = norm("bn2")(y)

    if self.use_film:
      y = _Film(out_width, self.dtype, name="film")(y, context)
    return nn.relu(y + residual)


class ResNet(nn.Module):
  """ResNet v1 feature tower; num_classes=0 → pooled features.

  Reference §resnet_model. `film=True` turns every block into a
  FiLM-conditioned block (call with `context`).
  """

  depth: int = 50
  width: int = 64
  num_classes: int = 0
  film: bool = False
  return_spatial: bool = False  # also return the pre-pool feature map
  remat: bool = False  # rematerialize each block on the backward pass
  norm: str = "batch"  # 'batch' (reference) or 'group' (vision_layers.make_norm)
  impl: str = "parity"  # 'fast' folds the stride-2 3×3 convs (see _Block)
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, images, context: Optional[jnp.ndarray] = None,
               train: bool = False):
    if self.depth not in _CONFIGS:
      raise ValueError(f"Unsupported depth {self.depth}; "
                       f"have {sorted(_CONFIGS)}")
    if self.film and context is None:
      raise ValueError("FiLM ResNet requires a context embedding.")
    block_sizes, bottleneck = _CONFIGS[self.depth]

    with jax.named_scope("normalize_image"):
      x = normalize_image(images, self.dtype)  # uint8 wire → [0,1] on-chip
    x = nn.Conv(self.width, (7, 7), strides=(2, 2), use_bias=False,
                dtype=self.dtype, name="stem_conv")(x)
    x = make_norm(self.norm, train, self.dtype)("stem_bn")(x)
    x = nn.relu(x)
    with jax.named_scope("stem_pool"):
      x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

    # remat=True drops each block's activations after the forward pass and
    # recomputes them during backprop (jax.checkpoint): activation memory
    # goes from O(depth) to O(1) blocks — the HBM-for-FLOPs trade that
    # lets deep towers train at large batch/resolution on one chip.
    # (self, x, context, train) → train is static arg index 3.
    block_cls = (nn.remat(_Block, static_argnums=(3,)) if self.remat
                 else _Block)
    for stage, num_blocks in enumerate(block_sizes):
      for block in range(num_blocks):
        x = block_cls(
            width=self.width * (2 ** stage),
            stride=2 if (block == 0 and stage > 0) else 1,
            bottleneck=bottleneck,
            use_film=self.film,
            dtype=self.dtype,
            norm_kind=self.norm,
            impl=self.impl,
            name=f"stage{stage}_block{block}")(x, context, train)

    features = jnp.mean(x, axis=(1, 2))  # global average pool
    if self.num_classes:
      features = nn.Dense(self.num_classes, dtype=jnp.float32,
                          name="classifier")(features)
    if self.return_spatial:
      return features, x
    return features


def FilmResNet(depth: int = 18, **kwargs) -> ResNet:
  """The reference's film_resnet_model: ResNet with FiLM conditioning."""
  return ResNet(depth=depth, film=True, **kwargs)

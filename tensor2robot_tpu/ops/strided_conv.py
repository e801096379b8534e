"""Folded formulation of 3×3 stride-2 SAME convolution.

Same idea as the stem fold (`ops/stem_conv.py`), applied to the
flagship's post-merge tower (reference grasping net via SURVEY.md §2:
three Conv 64×(3,3)/2 layers, 59²→30²→15²→8²): express the strided
conv as a stride-(2, 1) conv over a lanes-folded VIEW of the input —
the W-direction stride phases live in the channel dimension, so both
the forward and (the actual motivation) the BACKWARD see
larger-contraction, stride-1-in-minor-dim shapes instead of XLA's
strided/dilated grad convolutions.

Construction, for x (B, H, W, C) → y (B, ⌈H/2⌉, ⌈W/2⌉, O):

  pad x with SAME-exact lo/hi zeros to (B, 2·HO+2, 2·WO+2, C);
  view rows as (B, H_p, W_p/2, 2C)       # reshape only, free
  y = conv(view, w_folded, strides=(2, 1), VALID)

  w_folded (4, 2, 2C, O): w_folded[r, s, qC+c, o] = w[r, 2s+q, c, o]
  for r < 3 and 2s+q < 3, zero elsewhere (the r=3 row and the (s,q)
  combination addressing kernel column 3 are structurally zero taps).

The function is EXACTLY the parity convolution — same taps, same
SAME-padding offsets (including the even-size case where SAME pads
only on the high side) — up to float reassociation of the contraction.
Weights stay in the parity (3, 3, C, O) layout; the fold runs inside
jit on the tiny kernel tensor, so checkpoints and the model's param
tree are untouched and autodiff transposes the fold for free.

Adopted only where the chip shows a measured win (one paired run on
the `qtopt_train_resident` cell, ROADMAP S3b, not yet made);
correctness is pinned CPU-side in tests/test_ops.py either way.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def fold_strided3x3_weights(w: jax.Array) -> jax.Array:
  """(3, 3, C, O) parity layout → (4, 2, 2C, O) folded layout."""
  kh, kw, c, o = w.shape
  if (kh, kw) != (3, 3):
    raise ValueError(f"expected a (3, 3, C, O) kernel, got {w.shape}")
  # (r, s, q, c, o) with kernel column = 2s + q; column 3 and row 3
  # are structural zeros.
  wf = jnp.zeros((4, 2, 2, c, o), w.dtype)
  wf = wf.at[0:3, 0, 0].set(w[:, 0])   # s=0, q=0 → col 0
  wf = wf.at[0:3, 0, 1].set(w[:, 1])   # s=0, q=1 → col 1
  wf = wf.at[0:3, 1, 0].set(w[:, 2])   # s=1, q=0 → col 2
  return wf.reshape(4, 2, 2 * c, o)


def strided3x3_same(x: jax.Array, w: jax.Array) -> jax.Array:
  """conv2d(x, w, strides=(2, 2), padding='SAME') via the folded view.

  x: (B, H, W, C) NHWC; w: (3, 3, C, O) — the PARITY weight layout.
  Bit-compatible function with `lax.conv_general_dilated(..., (2, 2),
  'SAME')` up to float reassociation.
  """
  b, h, wd, c = x.shape
  out_h, out_w = -(-h // 2), -(-wd // 2)   # ceil: SAME output sizes
  # SAME pad_lo is pad_total // 2; pad hi is topped up so the folded
  # view is rectangular: H_p = 2·out_h + 2 covers the last window's
  # r<3 taps (the r=3 tap row is structurally zero), W_p likewise and
  # even by construction (the 2C fold needs even W_p).
  pad_total_h = max((out_h - 1) * 2 + 3 - h, 0)
  pad_total_w = max((out_w - 1) * 2 + 3 - wd, 0)
  lo_h, lo_w = pad_total_h // 2, pad_total_w // 2
  hp, wp = 2 * out_h + 2, 2 * out_w + 2
  x = jnp.pad(x, ((0, 0), (lo_h, hp - lo_h - h), (lo_w, wp - lo_w - wd),
                  (0, 0)))
  view = x.reshape(b, hp, wp // 2, 2 * c)
  y = jax.lax.conv_general_dilated(
      view, fold_strided3x3_weights(w), window_strides=(2, 1),
      padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
  assert y.shape == (b, out_h, out_w, w.shape[-1]), y.shape
  return y


class FoldedStridedConv3x3(nn.Module):
  """Flax wrapper with nn.Conv-IDENTICAL param layout (`kernel`
  (3, 3, C, O), optional `bias` (O,)) — parity and folded checkpoints
  interchange with no conversion. Drop-in for
  `nn.Conv(features, (3, 3), strides=(2, 2))` (SAME padding)."""

  features: int
  use_bias: bool = True
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array) -> jax.Array:
    kernel = self.param(
        "kernel", nn.initializers.lecun_normal(),
        (3, 3, x.shape[-1], self.features))
    y = strided3x3_same(x.astype(self.dtype), kernel.astype(self.dtype))
    if self.use_bias:
      bias = self.param("bias", nn.initializers.zeros, (self.features,))
      y = y + bias.astype(self.dtype)
    return y

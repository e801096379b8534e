"""Causal depthwise convolutions over time, and the gated short
convolution that LFM2 uses as a token mixer (the `Lfm2ShortConv` of its
modeling code):

  [B | C | x~] = bcx   (the input projection's output, (B, T, 3D));
  u = B ⊙ x~;  z_t = Σ_j w_j ⊙ u_{t-(L-1)+j}  (L taps, zeros before the
  start, nothing ahead of t read);  y = C ⊙ z.

Both are XLA: elementwise passes that fuse with their neighbours,
forward and backward (JAX differentiates the shifted sums).
"""

from __future__ import annotations

import jax.numpy as jnp


def causal_conv(x, kernel):
  """Depthwise convolution over time of (B, T, C) with (taps, C): y_t =
  Σ_j kernel[j] · x[t − (taps − 1) + j]; nothing ahead of t is read, and
  before the start there are zeros. Float32 sums."""
  taps, t = kernel.shape[0], x.shape[1]
  padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
  return sum(kernel[j] * padded[:, j:j + t].astype(jnp.float32)
             for j in range(taps))


def gated_short_conv(bcx, taps):
  """(B, T, 3D) [B | C | x~] and (L, D) taps -> ((B, T, D) C ⊙ conv(B ⊙
  x~) in bcx's dtype, the mean square of u = B ⊙ x~): the gates' products
  and the convolution's sums in float32, one rounding after."""
  d = bcx.shape[-1] // 3
  f32 = lambda a: a.astype(jnp.float32)
  u = f32(bcx[..., :d]) * f32(bcx[..., 2 * d:])
  y = (f32(bcx[..., d:2 * d]) * causal_conv(u, taps)).astype(bcx.dtype)
  return y, jnp.mean(jnp.square(u))

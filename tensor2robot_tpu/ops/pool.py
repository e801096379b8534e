"""Reshape-formulation max pooling for non-overlapping windows.

`nn.max_pool` lowers to XLA reduce-window, whose gradient is a
SelectAndScatter op — historically one of the slowest TPU lowerings
(it re-scans every window serially to find the argmax). For the
NON-OVERLAPPING case (window == strides), the same function is
expressible as a reshape + max over the split axes: the backward then
compiles to a compare/mask/multiply fusion with no SelectAndScatter.

Forward parity with `nn.max_pool(x, (2, 2), strides=(2, 2))` is exact
(same elements, same max). The BACKWARD differs only on exact ties
within a window: reduce-max's gradient splits evenly among tied maxima
where SelectAndScatter routes everything to the first — both are valid
subgradients of the same function (ties are common after relu, where
whole windows can be 0). tests/test_ops.py pins both contracts.

Measured use: a candidate swap for the QT-Opt stem's 118²→59² pool —
adopted only if the chip shows a real win: one paired run on the
`qtopt_train_resident` cell (ROADMAP S3b), not yet made.
"""

from __future__ import annotations

import jax.numpy as jnp


def max_pool_reshape(x: jnp.ndarray, window: int = 2) -> jnp.ndarray:
  """Non-overlapping `window`×`window` max pool over NHWC, stride ==
  window (the `nn.max_pool(x, (w, w), strides=(w, w))` case).

  H and W must be divisible by `window` (the flagship's 118² is; callers
  with ragged sizes should crop first — VALID padding drops the ragged
  edge anyway, but silently reproducing that here would hide a
  mismatch).
  """
  b, h, w, c = x.shape
  if h % window or w % window:
    raise ValueError(
        f"max_pool_reshape needs H, W divisible by {window}, got "
        f"{(h, w)}; crop first (VALID-pool semantics drop the edge).")
  x = x.reshape(b, h // window, window, w // window, window, c)
  return x.max(axis=(2, 4))

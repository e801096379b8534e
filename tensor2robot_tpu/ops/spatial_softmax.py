"""Fused spatial-softmax expectation (Pallas TPU kernel).

The keypoint pooling between every conv tower and pose head
(layers/vision_layers.py §spatial_softmax; reference
§BuildImageFeaturesToPoseModel's spatial softmax): per-channel softmax
over the H×W grid followed by expected-(x, y) coordinates. The XLA form
materializes the (B, C, H, W) attention tensor in HBM between the
softmax and the two weighted reductions; this kernel keeps one
(H·W, C-tile) block resident in VMEM and does max → exp → three
reductions in a single pass, so HBM traffic drops from ~4 passes over
the activation to one read + one (B, 2, C) write.

Gradient: custom_jvp whose rule routes through the XLA reference, so
reverse-mode — including the higher-order reverse MAML's second-order
outer gradient needs — derives from plain jnp ops; the kernel serves
every non-differentiated forward (serving, eval, CEM sweeps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import dispatch

_LANES = 128
# The kernel keeps one (H·W, C_TILE) f32 block resident, and VMEM pads
# C_TILE to the full 128 lanes whatever C is: count the block at 128.
_MAX_VMEM_BLOCK_ELEMS = 1 << 21  # 2M f32 elems = 8 MiB
# Mosaic double-buffers the input block and the body holds block-sized
# f32 temporaries (scaled logits, exp weights, a weighted product):
# measured on v5e the scoped allocation is 3.1-3.8x the padded block,
# past the 16 MiB default budget for any block over ~4 MiB. The call
# asks for what it needs (v5e VMEM is 128 MiB).
_VMEM_BLOCK_MULTIPLE = 5
_MIN_VMEM_LIMIT_BYTES = 16 << 20


def spatial_softmax_reference(features: jnp.ndarray,
                              temperature: float = 1.0) -> jnp.ndarray:
  """XLA reference: identical math, O(B·H·W·C) intermediate in HBM."""
  b, h, w, c = features.shape
  dtype = features.dtype
  logits = features.astype(jnp.float32).transpose(0, 3, 1, 2)
  logits = logits.reshape(b, c, h * w) / temperature
  attention = jax.nn.softmax(logits, axis=-1).reshape(b, c, h, w)
  xs = jnp.linspace(-1.0, 1.0, w)
  ys = jnp.linspace(-1.0, 1.0, h)
  expected_x = jnp.sum(attention * xs[None, None, None, :], axis=(2, 3))
  expected_y = jnp.sum(attention * ys[None, None, :, None], axis=(2, 3))
  return jnp.concatenate([expected_x, expected_y], axis=-1).astype(dtype)


def _kernel(x_ref, out_ref, *, height: int, width: int,
            inv_temperature: float):
  """One (1, H·W, C_TILE) block: softmax + expected coords, fused."""
  logits = x_ref[0].astype(jnp.float32) * inv_temperature  # (HW, CT)
  hw = height * width
  row = jax.lax.broadcasted_iota(jnp.int32, (hw, 1), 0)
  col_in_image = (row % width).astype(jnp.float32)
  row_in_image = (row // width).astype(jnp.float32)
  # linspace(-1, 1, n)[i] == -1 + 2*i/(n-1); n==1 degenerates to [-1],
  # which the same formula yields with the max() guard (i is then 0).
  x_coord = -1.0 + 2.0 * col_in_image / max(width - 1, 1)
  y_coord = -1.0 + 2.0 * row_in_image / max(height - 1, 1)

  maxes = jnp.max(logits, axis=0, keepdims=True)          # (1, CT)
  weights = jnp.exp(logits - maxes)                       # (HW, CT)
  denom = jnp.sum(weights, axis=0, keepdims=True)         # (1, CT)
  inv_denom = 1.0 / denom
  out_ref[0, 0, :] = jnp.sum(weights * x_coord, axis=0) * inv_denom[0]
  out_ref[0, 1, :] = jnp.sum(weights * y_coord, axis=0) * inv_denom[0]


def _pallas_forward(features: jnp.ndarray,
                    temperature: float) -> jnp.ndarray:
  interpret = jax.default_backend() != "tpu"
  b, h, w, c = features.shape
  hw = h * w
  c_tile = min(c, _LANES)
  x = features.reshape(b, hw, c)
  grid = (b, pl.cdiv(c, c_tile))
  vmem_limit = max(_MIN_VMEM_LIMIT_BYTES,
                   _VMEM_BLOCK_MULTIPLE * hw * _LANES * 4)
  out = pl.pallas_call(
      functools.partial(_kernel, height=h, width=w,
                        inv_temperature=1.0 / temperature),
      out_shape=jax.ShapeDtypeStruct((b, 2, c), jnp.float32),
      grid=grid,
      in_specs=[pl.BlockSpec((1, hw, c_tile), lambda i, j: (i, 0, j),
                             memory_space=pltpu.VMEM)],
      out_specs=pl.BlockSpec((1, 2, c_tile), lambda i, j: (i, 0, j),
                             memory_space=pltpu.VMEM),
      compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
      interpret=interpret,
  )(x)
  return jnp.concatenate([out[:, 0, :], out[:, 1, :]],
                         axis=-1).astype(features.dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _spatial_softmax_pallas(features: jnp.ndarray,
                            temperature: float) -> jnp.ndarray:
  return _pallas_forward(features, temperature)


@_spatial_softmax_pallas.defjvp
def _jvp(temperature, primals, tangents):
  # Differentiation routes through the XLA reference (the fused forward
  # never materializes the attention weights the chain rule needs).
  # custom_jvp rather than custom_vjp: the rule below is plain jnp, so
  # reverse-mode — and higher-order reverse, which MAML's second-order
  # outer gradient needs — both derive from it. The Pallas kernel then
  # serves every non-differentiated forward (serving, eval, CEM sweeps).
  (features,), (features_dot,) = primals, tangents
  return jax.jvp(lambda f: spatial_softmax_reference(f, temperature),
                 (features,), (features_dot,))


def _supported(features: jnp.ndarray) -> bool:
  _, h, w, _ = features.shape
  return h * w * _LANES <= _MAX_VMEM_BLOCK_ELEMS


def spatial_softmax(features: jnp.ndarray, temperature: float = 1.0,
                    implementation: str = "auto") -> jnp.ndarray:
  """Expected (x, y) image-coordinates per channel ("feature points").

  Args:
    features: (B, H, W, C) activations.
    temperature: softmax temperature.
    implementation: "pallas", "xla", or "auto" (pallas whenever the
      block fits VMEM; the kernel runs interpreted off-TPU).

  Returns:
    (B, 2*C): per-channel expected coordinates in [-1, 1], x block
    then y block — same contract as the reference's spatial softmax.
  """
  if implementation not in ("auto", "pallas", "xla"):
    raise ValueError(
        f"implementation must be 'auto', 'pallas', or 'xla'; got "
        f"{implementation!r}")
  if implementation == "xla":
    return spatial_softmax_reference(features, temperature)
  if implementation == "pallas":
    # Explicit request: kernel on every platform (interpreted off-TPU) —
    # the path CPU CI uses to exercise the kernel body.
    return _spatial_softmax_pallas(features, temperature)
  if (dispatch.use_xla_only() or jax.default_backend() != "tpu"
      or not _supported(features)):
    # xla_only: multi-platform export tracing (see ops/dispatch.py) — a
    # compiled pallas_call cannot lower for the artifact's CPU target.
    # Off-TPU, auto means XLA: an interpreted kernel is strictly slower
    # there (explicit implementation="pallas" remains the CI coverage
    # path).
    return spatial_softmax_reference(features, temperature)
  return _spatial_softmax_pallas(features, temperature)

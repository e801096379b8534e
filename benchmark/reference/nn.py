"""Plain jax.numpy building blocks shared by the references.

Nothing here imports the program under test. Every function takes a
`precision`:

  "f32"   float32 with matmul precision "highest" (the reference proper);
  "bf16"  operands rounded to bfloat16, f32 accumulate (what the
          configurations state the program computes in);
  "fp8"   the same roundings to float8 (e4m3 forward, e5m2 backward,
          each tensor scaled to the type's range): the control, one
          step below what the configurations state.

The rounding is applied to both operands of every conv and dense and to
the output of every batch norm, forward, and to the gradients that flow
back through those points: where a step computed in that precision
rounds.

Parameter trees use the published names of the repo's checkpoint layout
({"kernel", "bias"} for conv/dense, {"scale", "bias"} + {"mean", "var"}
for batch norm) so a tree made here can be handed to the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.scipy.special
from jax import lax

PRECISIONS = ("f32", "bf16", "fp8")
BN_MOMENTUM = 0.99
BN_EPS = 1e-5


# (type on the way forward, type of what flows back): float8 as it is
# trained in, e4m3 operands and e5m2 gradients, each tensor scaled so
# that its largest magnitude is the type's largest (without the scale
# a gradient of 1e-6 is nought in either type, which is a breakdown
# and not a precision).
_ROUND_TO = {"bf16": (jnp.bfloat16, jnp.bfloat16),
             "fp8": (jnp.float8_e4m3fn, jnp.float8_e5m2)}


def _round(x, dtype):
  if jnp.finfo(dtype).bits > 8:
    return x.astype(dtype).astype(jnp.float32)
  top = float(jnp.finfo(dtype).max)
  scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
  # Clipped: a quotient a rounding above `top` converts to NaN (e4m3
  # has no infinity) or to infinity (e5m2).
  scaled = jnp.clip(x / scale, -top, top)
  return scaled.astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, precision):
  return _round(x, _ROUND_TO[precision][0])


def _rounded_fwd(x, precision):
  return _round(x, _ROUND_TO[precision][0]), None


def _rounded_bwd(precision, _, g):
  # A step computed in that precision rounds what flows backward too.
  return (_round(g, _ROUND_TO[precision][1]),)


_rounded.defvjp(_rounded_fwd, _rounded_bwd)


def _operand(x, precision):
  """x rounded to `precision` on the way forward, its gradient rounded
  on the way back; float32 throughout for "f32"."""
  x = x.astype(jnp.float32)
  if precision == "f32":
    return x
  if precision not in _ROUND_TO:
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")
  return _rounded(x, precision)


def conv(x, p, stride, padding, precision):
  """NHWC conv with an HWIO kernel; `padding` is "SAME" or "VALID"."""
  y = lax.conv_general_dilated(
      _operand(x, precision), _operand(p["kernel"], precision),
      window_strides=(stride, stride), padding=padding,
      dimension_numbers=("NHWC", "HWIO", "NHWC"),
      precision=lax.Precision.HIGHEST)
  if "bias" in p:
    y = y + p["bias"]
  return y


def dense(x, p, precision):
  y = jnp.dot(_operand(x, precision), _operand(p["kernel"], precision),
              precision=lax.Precision.HIGHEST)
  return y + p["bias"]


def batch_norm(x, p, stats, train, precision="f32"):
  """Returns (y, new_stats). Train mode normalises with the batch's own
  biased statistics over every axis but the last and moves the running
  ones by BN_MOMENTUM; otherwise the running statistics normalise."""
  if train:
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axes)
    var = jnp.mean(jnp.square(x - mean), axes)
    new_stats = {
        "mean": BN_MOMENTUM * stats["mean"] + (1 - BN_MOMENTUM) * mean,
        "var": BN_MOMENTUM * stats["var"] + (1 - BN_MOMENTUM) * var,
    }
  else:
    mean, var, new_stats = stats["mean"], stats["var"], stats
  y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
  return _operand(y, precision), new_stats


def max_pool(x, window, stride, padding):
  return lax.reduce_window(
      x, -jnp.inf, lax.max, (1, window, window, 1),
      (1, stride, stride, 1), padding)


# --- seeded weights --------------------------------------------------------


class Pool:
  """Seeded numbers for a whole parameter tree from ONE normal draw,
  handed out slice by slice: a draw per leaf is a kernel per leaf for
  the compiler, minutes of set-up for a ResNet-50. `Pool.fill(key,
  build)` calls `build(pool)` twice: once abstractly to count what it
  takes, once for real."""

  def __init__(self, values):
    self._values, self.taken = values, 0

  @classmethod
  def fill(cls, key, build):
    counting = cls(None)
    jax.eval_shape(lambda: build(counting))
    return build(cls(jax.random.normal(key, (counting.taken,), jnp.float32)))

  def normal(self, shape):
    size = 1
    for d in shape:
      size *= d
    start, self.taken = self.taken, self.taken + size
    if self._values is None:
      return jnp.zeros(shape, jnp.float32)
    return self._values[start:start + size].reshape(shape)

  def uniform(self, shape, low, high):
    return low + (high - low) * jax.scipy.special.ndtr(self.normal(shape))


def he_kernel(pool, shape):
  """He-normal over fan-in (all dims but the last)."""
  fan_in = 1
  for d in shape[:-1]:
    fan_in *= d
  return pool.normal(shape) * (2.0 / fan_in) ** 0.5


def conv_params(pool, kh, kw, cin, cout, bias=True):
  p = {"kernel": he_kernel(pool, (kh, kw, cin, cout))}
  if bias:
    p["bias"] = jnp.zeros((cout,), jnp.float32)
  return p


def dense_params(pool, cin, cout):
  return {"kernel": he_kernel(pool, (cin, cout)),
          "bias": 0.1 * pool.normal((cout,))}


def bn_params(pool, c):
  """Scale and bias away from (1, 0), running statistics away from
  (0, 1): a fresh-initialised norm layer hides a swapped or dropped
  one."""
  params = {"scale": pool.uniform((c,), 0.5, 1.5),
            "bias": 0.2 * pool.normal((c,))}
  stats = {"mean": 0.1 * pool.normal((c,)),
           "var": pool.uniform((c,), 0.5, 1.5)}
  return params, stats

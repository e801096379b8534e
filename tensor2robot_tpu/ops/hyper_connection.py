"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
two passes a sublayer makes over an n-stream residual, with Pallas TPU
kernels that read and write the stream once each.

Per token, X its (n, D) streams, `phi` (nD, n² + 2n), `alpha` (3,),
`base` (n² + 2n,), columns [pre (n) | post (n) | res (n², row-major)]:

  x̄ = vec(X) · rsqrt(mean(vec(X)²) + eps);  m = x̄ · phi
  H_pre  = σ(α_pre m_pre + b_pre);  H_post = 2 σ(α_post m_post + b_post)
  M = exp(clip(α_res mat(m_res) + b_res, clamp)), then `sinkhorn_iters`
      times M ← M / (rowsum(M) + hc_eps), M ← M / (colsum(M) + hc_eps)
  H_res = M

  pre:   u = Σ_j H_pre[j] X[j]                       (read n rows, write 1)
  post:  X'[i] = Σ_j H_res[i, j] X[j] + H_post[i] y  (read n + 1, write n)

The stream is held as (B, T, n·D), a token's n streams side by side
along the last axis (stream j is `x[..., jD:(j + 1)D]`), which is
vec(X) and what the kernels tile. As (B, T, n, D) the device tiles the
(n, D) faces n sublanes high, and every pass then starts and ends with a
relayout copy of the whole stream (22 copies of 117 MB a step at 4,096
tokens of 4 × 3,584, a third of the time under the scope; PR 38).

`implementation="xla"` is the same in `jax.numpy` under autodiff (the
CPU path and the kernels' oracle). The Pallas programs take tiles of
tokens with a token's whole stream, (n·D,) wide, in VMEM: `pre` makes
the RMS, m (on the MXU, `phi` split in two bf16 parts so that the
product keeps 16 bits of it), the maps with Sinkhorn unrolled over
(n², tokens) registers (tokens along the lanes) and u from one read of
the tile; `post` reads stream and y once and writes the stream once.
Their backward programs likewise make one pass each: `post`'s reads X,
y and dX' and writes dX, dy and the maps' cotangents; `pre`'s reads X
and du, runs Sinkhorn again and then backwards, writes dX and sums
dphi, dalpha and dbase over the tiles.

Precision follows the stream: X, y, u and X' in the dtype they come in
(bf16 in a bf16 model); the RMS, m, the sigmoids, Sinkhorn, the maps and
every weighted sum's accumulation float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import dispatch

# The four kernels' names, as the device trace shows them: the two
# forward passes (run again where a block is rematerialized) and their
# backward passes.
KERNEL_NAMES = ("hyper_connection_pre_fwd", "hyper_connection_post_fwd",
                "hyper_connection_pre_bwd", "hyper_connection_post_bwd")

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class MapConfig:
  """What the maps take from the model's configuration."""
  sinkhorn_iters: int = 20
  hc_eps: float = 1e-6
  clamp_min: float = -30.0
  clamp_max: float = 30.0
  rms_eps: float = 1e-6


# --- the "xla" form --------------------------------------------------------


def sinkhorn(mixed, iters: int, hc_eps: float):
  """(..., n, n) positive -> rows, then columns, normalized `iters`
  times."""
  for _ in range(iters):
    mixed = mixed / (jnp.sum(mixed, axis=-1, keepdims=True) + hc_eps)
    mixed = mixed / (jnp.sum(mixed, axis=-2, keepdims=True) + hc_eps)
  return mixed


def maps_from_products(m, alpha, base, n: int, config: MapConfig):
  """m (..., n² + 2n) float32 -> (H_pre (..., n), H_post (..., n), H_res
  (..., n, n))."""
  affine = lambda k, cols: alpha[k] * m[..., cols] + base[cols]
  h_pre = jax.nn.sigmoid(affine(0, slice(0, n)))
  h_post = 2.0 * jax.nn.sigmoid(affine(1, slice(n, 2 * n)))
  a = jnp.clip(affine(2, slice(2 * n, None)), config.clamp_min,
               config.clamp_max)
  h_res = sinkhorn(jnp.exp(a).reshape(a.shape[:-1] + (n, n)),
                   config.sinkhorn_iters, config.hc_eps)
  return h_pre, h_post, h_res


def num_streams(phi) -> int:
  """n of a `phi` with n² + 2n columns."""
  n = math.isqrt(phi.shape[1] + 1) - 1
  if n * n + 2 * n != phi.shape[1] or phi.shape[0] % n:
    raise ValueError(f"phi {phi.shape} is not (n·D, n² + 2n)")
  return n


def _streams(x, n: int):
  """(B, T, n·D) -> its n streams, float32."""
  d = x.shape[-1] // n
  return [x[..., j * d:(j + 1) * d].astype(jnp.float32) for j in range(n)]


def _pre_xla(x, phi, alpha, base, config: MapConfig):
  n = num_streams(phi)
  flat = x.astype(jnp.float32)
  r = jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                    + config.rms_eps)
  m = jnp.dot(flat, phi, precision=_HIGHEST) * r
  h_pre, h_post, h_res = maps_from_products(m, alpha, base, n, config)
  u = sum(h_pre[..., j:j + 1] * stream
          for j, stream in enumerate(_streams(x, n)))
  return u.astype(x.dtype), h_pre, h_post, h_res


def _post_xla(x, y, h_post, h_res):
  n = h_post.shape[-1]
  streams, y = _streams(x, n), y.astype(jnp.float32)
  written = [h_post[..., i:i + 1] * y + sum(
      h_res[..., i, j:j + 1] * stream for j, stream in enumerate(streams))
             for i in range(n)]
  return jnp.concatenate(written, axis=-1).astype(x.dtype)


# --- the Pallas programs -----------------------------------------------------
#
# Two layouts of a tile's maps. Token-major, (tokens, 128) float32 with
# columns [pre (n) | post (n) | res (n², row-major)]: what scales a
# token's rows of the stream (a column broadcasts along the lanes), and
# what goes to and from HBM. Map-major, (rows, tokens) with the tokens
# along the lanes, each group of n in a sublane tile of its own (row i
# of H_res at 8i, pre at 8n, post at 8(n + 1)): what the product with
# `phi` gives and what Sinkhorn runs on, a row of H_res one (n, tokens)
# array. A (128, tokens) scratch and one transpose turn one into the
# other.

_SUBLANES = 8
_TOKEN_TILE = 128
_VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))   # a b^T


def _map_rows(n: int) -> int:
  return _SUBLANES * (n + 2)


def _token_tile(tokens: int):
  """Tokens a grid step takes, or None where no tiling is possible."""
  if tokens % _TOKEN_TILE == 0:
    return _TOKEN_TILE
  return tokens if tokens < _TOKEN_TILE and tokens % 16 == 0 else None


@functools.lru_cache(maxsize=None)
def _selection(n: int):
  """(8(n + 2), n² + 2n) of 0 and 1: row r of the map-major order takes
  column k of [pre | post | res]; the rows between the groups take none."""
  rows = np.zeros((_map_rows(n), n * n + 2 * n), np.float32)
  for k in range(n):
    rows[_SUBLANES * n + k, k] = 1.0
    rows[_SUBLANES * (n + 1) + k, n + k] = 1.0
    for j in range(n):
      rows[_SUBLANES * k + j, 2 * n + n * k + j] = 1.0
  return rows


def _map_major(columns, n: int):
  """(n² + 2n, ...) in column order -> (8(n + 2), ...) in row order, as
  one product with `_selection` (exact: every row takes one column or
  none), not some tens of slices, pads and a concatenation."""
  return jnp.tensordot(_selection(n), columns, 1, precision=_HIGHEST)


def _from_map_major(rows, n: int):
  """(8(n + 2), ...) in row order -> (n² + 2n, ...) in column order."""
  return jnp.tensordot(_selection(n).T, rows, 1, precision=_HIGHEST)


def _alpha_of_columns(n: int):
  """(n² + 2n, 3) of 0 and 1: which of α_pre, α_post, α_res scales a
  column."""
  return np.repeat(np.eye(3, dtype=np.float32), [n, n, n * n], axis=0)


def _split_bf16(a):
  """a ≈ hi + lo, both bfloat16: 16 bits of a float32 operand through
  two single-pass products."""
  hi = a.astype(jnp.bfloat16)
  return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _for_row_blocks(ref, body):
  """`body(rows, size)` for every block of a tile's tokens, a packed
  vreg's sublanes each, `rows` the block's slice of the tile. One loop,
  so that the body is traced and compiled once, not once a block."""
  size = 32 // ref.dtype.itemsize if ref.dtype.itemsize < 4 else _SUBLANES
  size = min(size, ref.shape[0])

  def step(i, carry):
    body(pl.ds(pl.multiple_of(i * size, size), size), size)
    return carry

  jax.lax.fori_loop(0, ref.shape[0] // size, step, 0)


def _lanes(c: int, offset: int = 0):
  return slice(offset + c * _LANES, offset + (c + 1) * _LANES)


def _maps_map_major(z, n: int, config: MapConfig, steps=None):
  """z = α m + b, map-major (rows, tokens) -> (pre (n, tokens), post,
  [row i of H_res (n, tokens)]). `steps`, a list, is given every
  Sinkhorn half-step's (result, inverse of its sums) for the backward
  pass."""
  group = lambda g: z[_SUBLANES * g:_SUBLANES * g + n, :]
  mixed = [jnp.exp(jnp.clip(group(i), config.clamp_min, config.clamp_max))
           for i in range(n)]
  keep = (lambda *step: None) if steps is None else (
      lambda *step: steps.append(step))
  keep(mixed, None)
  for _ in range(config.sinkhorn_iters):
    inverse = [1.0 / (jnp.sum(row, axis=0, keepdims=True) + config.hc_eps)
               for row in mixed]
    mixed = [row * by for row, by in zip(mixed, inverse)]
    keep(mixed, inverse)
    inverse = 1.0 / (functools.reduce(jnp.add, mixed) + config.hc_eps)
    mixed = [row * inverse for row in mixed]
    keep(mixed, inverse)
  return (jax.nn.sigmoid(group(n)), 2.0 * jax.nn.sigmoid(group(n + 1)),
          mixed)


def _maps_backward(z, pre, post, steps, d_pre, d_post, d_mixed, n: int,
                   config: MapConfig):
  """The cotangents of `_maps_map_major`'s results -> z's, by group:
  [row i of H_res, ..., pre, post], each (n, tokens). A half-step P = M ·
  inverse of M's sums has dM = (dP − Σ dP · P) · inverse, the sum over
  what the step normalized."""
  for result, inverse in reversed(steps[1:]):
    if isinstance(inverse, list):  # a row step: sums over the sublanes
      d_mixed = [(g - jnp.sum(g * p, axis=0, keepdims=True)) * by
                 for g, p, by in zip(d_mixed, result, inverse)]
    else:                          # a column step: sums over the list
      total = functools.reduce(
          jnp.add, (g * p for g, p in zip(d_mixed, result)))
      d_mixed = [(g - total) * inverse for g in d_mixed]
  group = lambda g: z[_SUBLANES * g:_SUBLANES * g + n, :]
  inside = lambda a: (a > config.clamp_min) & (a < config.clamp_max)
  d_z = [jnp.where(inside(group(i)), g * first, 0.0)
         for i, (g, first) in enumerate(zip(d_mixed, steps[0][0]))]
  return d_z + [d_pre * pre * (1.0 - pre), d_post * post * (1.0 - 0.5 * post)]


def _pre_kernel(x_ref, phi_hi_ref, phi_lo_ref, scale_ref, bias_ref,
                u_ref, h_ref, turn_ref, *, n: int, config: MapConfig):
  tokens, width = x_ref.shape
  d = width // n
  f32 = jnp.float32
  # The stream's RMS, a row block at a time: squares summed lane by
  # lane, the lanes once.
  def sum_squares(rows, size):
    squares = functools.reduce(jnp.add, (
        jnp.square(x_ref[rows, _lanes(c)].astype(f32))
        for c in range(width // _LANES)))
    turn_ref[rows, :] = jnp.broadcast_to(
        jnp.sum(squares, axis=1, keepdims=True), (size, _LANES))

  _for_row_blocks(x_ref, sum_squares)
  inverse_rms = jax.lax.rsqrt(
      turn_ref[...].T[0:1, :tokens] / width + config.rms_eps)   # (1, tokens)
  x = x_ref[...]
  product = functools.partial(jax.lax.dot_general, dimension_numbers=_NT,
                              preferred_element_type=f32)
  m = (product(phi_hi_ref[...], x) + product(phi_lo_ref[...], x)) * inverse_rms
  pre, post, mixed = _maps_map_major(
      scale_ref[...] * m + bias_ref[...], n, config)
  turn_ref[...] = jnp.zeros_like(turn_ref)
  for k, rows in enumerate([pre, post] + mixed):
    turn_ref[n * k:n * (k + 1), :tokens] = rows
  h_ref[...] = turn_ref[...].T[:tokens, :]                    # token-major

  def read_out(rows, size):
    maps = h_ref[rows, :]
    weights = [jnp.broadcast_to(maps[:, j:j + 1], (size, _LANES))
               for j in range(n)]
    for c in range(d // _LANES):
      read = functools.reduce(jnp.add, (
          weights[j] * x_ref[rows, _lanes(c, j * d)].astype(f32)
          for j in range(n)))
      u_ref[rows, _lanes(c)] = read.astype(u_ref.dtype)

  _for_row_blocks(x_ref, read_out)


def _post_kernel(x_ref, y_ref, h_ref, o_ref, *, n: int):
  d = y_ref.shape[1]
  f32 = jnp.float32
  def write_back(rows, size):
    del size
    maps = h_ref[rows, :]
    weight = lambda k: jnp.broadcast_to(maps[:, k:k + 1], maps.shape)
    post = [weight(n + i) for i in range(n)]
    mixed = [[weight(2 * n + n * i + j) for j in range(n)] for i in range(n)]
    for c in range(d // _LANES):
      y = y_ref[rows, _lanes(c)].astype(f32)
      streams = [x_ref[rows, _lanes(c, j * d)].astype(f32) for j in range(n)]
      for i in range(n):
        written = functools.reduce(
            jnp.add, (mixed[i][j] * streams[j] for j in range(n)),
            post[i] * y)
        o_ref[rows, _lanes(c, i * d)] = written.astype(o_ref.dtype)

  _for_row_blocks(x_ref, write_back)


def _place(columns, like):
  """[(k, (rows, 1) column)] -> (rows, 128) with column k at lane k,
  nought elsewhere."""
  lane = jax.lax.broadcasted_iota(jnp.int32, like, 1)
  out = jnp.zeros(like, jnp.float32)
  for k, column in columns:
    out = jnp.where(lane == k, column, out)
  return out


def _lane_sum(a):
  return jnp.sum(a, axis=1, keepdims=True)


def _post_bwd_kernel(x_ref, y_ref, h_ref, g_ref, dx_ref, dy_ref, dh_ref, *,
                     n: int):
  """dX[j] = Σ_i H_res[i, j] dX'[i]; dy = Σ_i H_post[i] dX'[i];
  dH_res[i, j] = <dX'[i], X[j]>; dH_post[i] = <dX'[i], y>."""
  d = y_ref.shape[1]
  f32 = jnp.float32
  def block(rows, size):
    del size
    maps = h_ref[rows, :]
    weight = lambda k: jnp.broadcast_to(maps[:, k:k + 1], maps.shape)
    post = [weight(n + i) for i in range(n)]
    mixed = [[weight(2 * n + n * i + j) for j in range(n)] for i in range(n)]
    d_post = [jnp.zeros(maps.shape, f32) for _ in range(n)]
    d_mixed = [[jnp.zeros(maps.shape, f32) for _ in range(n)]
               for _ in range(n)]
    for c in range(d // _LANES):
      y = y_ref[rows, _lanes(c)].astype(f32)
      streams = [x_ref[rows, _lanes(c, j * d)].astype(f32) for j in range(n)]
      given = [g_ref[rows, _lanes(c, i * d)].astype(f32) for i in range(n)]
      dy_ref[rows, _lanes(c)] = functools.reduce(jnp.add, (
          post[i] * given[i] for i in range(n))).astype(dy_ref.dtype)
      for j in range(n):
        dx_ref[rows, _lanes(c, j * d)] = functools.reduce(jnp.add, (
            mixed[i][j] * given[i] for i in range(n))).astype(dx_ref.dtype)
      for i in range(n):
        d_post[i] = d_post[i] + given[i] * y
        for j in range(n):
          d_mixed[i][j] = d_mixed[i][j] + given[i] * streams[j]
    dh_ref[rows, :] = _place(
        [(n + i, _lane_sum(d_post[i])) for i in range(n)]
        + [(2 * n + n * i + j, _lane_sum(d_mixed[i][j]))
           for i in range(n) for j in range(n)], maps.shape)

  _for_row_blocks(x_ref, block)


def _pre_bwd_kernel(x_ref, du_ref, h_ref, dh_ref, phi_hi_ref, phi_lo_ref,
                    scale_ref, bias_ref, dx_ref, dphi_ref, dscale_ref,
                    dbias_ref, turn_ref, rows_ref, *, n: int,
                    config: MapConfig):
  """The read-out's and the maps' backward pass over one tile: the maps
  made again map-major with Sinkhorn's half-steps kept, then backwards;
  dX = H_pre[j] du + r (dm · phi) − x r² <dm, m> / nD; dphi, dalpha's
  and dbase's parts summed over the grid's tiles."""
  tokens, width = x_ref.shape
  d = width // n
  f32 = jnp.float32

  @pl.when(pl.program_id(0) == 0)
  def _():
    dphi_ref[...] = jnp.zeros_like(dphi_ref)
    dscale_ref[...] = jnp.zeros_like(dscale_ref)
    dbias_ref[...] = jnp.zeros_like(dbias_ref)

  # Pass one over the tile: the stream's squares and <du, X[j]>.
  def pass_one(rows, size):
    shape = (size, _LANES)
    squares = functools.reduce(jnp.add, (
        jnp.square(x_ref[rows, _lanes(c)].astype(f32))
        for c in range(width // _LANES)))
    reads = [jnp.zeros(shape, f32) for _ in range(n)]
    for c in range(d // _LANES):
      du = du_ref[rows, _lanes(c)].astype(f32)
      for j in range(n):
        reads[j] = reads[j] + du * x_ref[rows, _lanes(c, j * d)].astype(f32)
    # Token-major: lane n² + 2n the squares' sum, lanes [pre | post |
    # res] the maps' cotangents, pre's with the read-out's part.
    turn_ref[rows, :] = dh_ref[rows, :] + _place(
        [(j, _lane_sum(reads[j])) for j in range(n)]
        + [(n * n + 2 * n, _lane_sum(squares))], shape)

  _for_row_blocks(x_ref, pass_one)
  turned = turn_ref[...].T                                   # map-major
  inverse_rms = jax.lax.rsqrt(
      turned[n * n + 2 * n:n * n + 2 * n + 1, :tokens] / width
      + config.rms_eps)                                      # (1, tokens)
  x = x_ref[...]
  product = functools.partial(jax.lax.dot_general, dimension_numbers=_NT,
                              preferred_element_type=f32)
  m = (product(phi_hi_ref[...], x) + product(phi_lo_ref[...], x)) * inverse_rms
  z = scale_ref[...] * m + bias_ref[...]
  steps = []
  pre, post, _ = _maps_map_major(z, n, config, steps)
  given = lambda k: turned[n * k:n * (k + 1), :tokens]
  d_groups = _maps_backward(
      z, pre, post, steps, given(0), given(1),
      [given(2 + i) for i in range(n)], n, config)
  rows_ref[...] = jnp.zeros_like(rows_ref)
  for g, d_group in enumerate(d_groups):
    rows_ref[_SUBLANES * g:_SUBLANES * g + n, :tokens] = d_group
  d_z = rows_ref[:, :tokens]                                 # (rows, tokens)
  dbias_ref[:, :tokens] += d_z
  dscale_ref[:, :tokens] += d_z * m
  d_m = scale_ref[...] * d_z
  d_mu = d_m * inverse_rms                 # of the products before the RMS
  # -r² <dm, m> / nD, a token's factor on its own stream; token-major
  # through the turn.
  factor = -inverse_rms * inverse_rms * jnp.sum(
      d_m * m, axis=0, keepdims=True) / width
  turn_ref[...] = jnp.zeros_like(turn_ref)
  turn_ref[0:1, :tokens] = factor
  factor = turn_ref[...].T[:tokens, 0:1]                     # (tokens, 1)
  hi, lo = _split_bf16(d_mu)
  nn = (((1,), (0,)), ((), ()))
  dphi_ref[...] += (
      jax.lax.dot_general(hi, x, nn, preferred_element_type=f32)
      + jax.lax.dot_general(lo, x, nn, preferred_element_type=f32))
  # Pass two: dX, a chunk of lanes at a time.
  tn = (((0,), (0,)), ((), ()))
  chunk = min(4 * _LANES, d)
  for j in range(n):
    weight = h_ref[:, j:j + 1]
    for c in range(d // chunk):
      lanes = slice(j * d + c * chunk, j * d + (c + 1) * chunk)
      across = functools.partial(jax.lax.dot_general, dimension_numbers=tn,
                                 preferred_element_type=f32)
      through_phi = (across(hi, phi_hi_ref[:, lanes])
                     + across(lo, phi_hi_ref[:, lanes])
                     + across(hi, phi_lo_ref[:, lanes]))
      dx = (through_phi + factor * x_ref[:, lanes].astype(f32)
            + weight * du_ref[:, c * chunk:(c + 1) * chunk].astype(f32))
      dx_ref[:, lanes] = dx.astype(dx_ref.dtype)


def _params(semantics: str = "parallel"):
  return pltpu.CompilerParams(dimension_semantics=(semantics,),
                              vmem_limit_bytes=_VMEM_LIMIT)


def _tiled(tile: int, width: int):
  return pl.BlockSpec((tile, width), lambda i: (i, 0),
                      memory_space=pltpu.VMEM)


def _whole(shape):
  return pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                      memory_space=pltpu.VMEM)


def _pre_forward(x, phi, alpha, base, config: MapConfig):
  """-> (u (B, T, D), the maps token-major (B·T, 128))."""
  n = num_streams(phi)
  b, t, d = x.shape[0], x.shape[1], x.shape[2] // n
  tokens, tile, rows = b * t, _token_tile(b * t), _map_rows(n)
  u, maps = pl.pallas_call(
      functools.partial(_pre_kernel, n=n, config=config),
      out_shape=[jax.ShapeDtypeStruct((tokens, d), x.dtype),
                 jax.ShapeDtypeStruct((tokens, _LANES), jnp.float32)],
      grid=(tokens // tile,),
      in_specs=[_tiled(tile, n * d), _whole((rows, n * d)),
                _whole((rows, n * d)), _whole((rows, 1)), _whole((rows, 1))],
      out_specs=[_tiled(tile, d), _tiled(tile, _LANES)],
      scratch_shapes=[pltpu.VMEM((_LANES, _LANES), jnp.float32)],
      compiler_params=_params(),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[0],
  )(x.reshape(tokens, n * d), *_pre_operands(phi, alpha, base, n))
  return u.reshape(b, t, d), maps


def _unpack(maps, b: int, t: int, n: int):
  """Token-major (B·T, 128) -> (H_pre, H_post, H_res)."""
  maps = maps.reshape(b, t, _LANES)
  return (maps[..., :n], maps[..., n:2 * n],
          maps[..., 2 * n:2 * n + n * n].reshape(b, t, n, n))


def _pack(h_post, h_res, h_pre=None):
  """-> token-major (B·T, 128); without `h_pre` its columns are nought."""
  b, t, n = h_post.shape
  columns = jnp.concatenate(
      [jnp.zeros_like(h_post) if h_pre is None else h_pre, h_post,
       h_res.reshape(b, t, n * n)], axis=-1)
  return jnp.pad(columns.reshape(b * t, -1).astype(jnp.float32),
                 ((0, 0), (0, _LANES - n * n - 2 * n)))


def _post_forward(x, y, maps):
  b, t, d = y.shape
  n = x.shape[2] // d
  tokens, tile = b * t, _token_tile(b * t)
  out = pl.pallas_call(
      functools.partial(_post_kernel, n=n),
      out_shape=jax.ShapeDtypeStruct((tokens, n * d), x.dtype),
      grid=(tokens // tile,),
      in_specs=[_tiled(tile, n * d), _tiled(tile, d), _tiled(tile, _LANES)],
      out_specs=_tiled(tile, n * d),
      compiler_params=_params(),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[1],
  )(x.reshape(tokens, n * d), y.reshape(tokens, d), maps)
  return out.reshape(x.shape)


def _pre_operands(phi, alpha, base, n: int):
  """`phi` map-major in two bfloat16 parts, and each row's α and b."""
  phi_rows = jnp.einsum("rk,dk->rd", _selection(n), phi, precision=_HIGHEST)
  scale = _map_major(jnp.dot(_alpha_of_columns(n), alpha,
                             precision=_HIGHEST), n)
  return _split_bf16(phi_rows) + (scale[:, None],
                                  _map_major(base, n)[:, None])


def _pre_backward(x, phi, alpha, base, maps, du, d_maps, config: MapConfig):
  """-> (dx, dphi, dalpha, dbase); `d_maps` the maps' cotangents
  token-major."""
  n = num_streams(phi)
  b, t, d = x.shape[0], x.shape[1], x.shape[2] // n
  tokens, tile, rows = b * t, _token_tile(b * t), _map_rows(n)
  summed = lambda width: pl.BlockSpec((rows, width), lambda i: (0, 0),
                                      memory_space=pltpu.VMEM)
  dx, dphi, dscale, dbias = pl.pallas_call(
      functools.partial(_pre_bwd_kernel, n=n, config=config),
      out_shape=[jax.ShapeDtypeStruct((tokens, n * d), x.dtype),
                 jax.ShapeDtypeStruct((rows, n * d), jnp.float32),
                 jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                 jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)],
      grid=(tokens // tile,),
      in_specs=[_tiled(tile, n * d), _tiled(tile, d), _tiled(tile, _LANES),
                _tiled(tile, _LANES), _whole((rows, n * d)),
                _whole((rows, n * d)), _whole((rows, 1)), _whole((rows, 1))],
      out_specs=[_tiled(tile, n * d), summed(n * d), summed(_LANES),
                 summed(_LANES)],
      scratch_shapes=[pltpu.VMEM((_LANES, _LANES), jnp.float32),
                      pltpu.VMEM((rows, _LANES), jnp.float32)],
      compiler_params=_params("arbitrary"),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[2],
  )(x.reshape(tokens, n * d), du.reshape(tokens, d), maps, d_maps,
    *_pre_operands(phi, alpha, base, n))
  dalpha = jnp.dot(_from_map_major(jnp.sum(dscale, axis=1), n),
                   _alpha_of_columns(n), precision=_HIGHEST)
  dphi = jnp.einsum("rk,rd->dk", _selection(n), dphi, precision=_HIGHEST)
  return (dx.reshape(x.shape), dphi, dalpha,
          _from_map_major(jnp.sum(dbias, axis=1), n))


def _post_backward(x, y, maps, g):
  """-> (dx, dy, the maps' cotangents token-major)."""
  b, t, d = y.shape
  n = x.shape[2] // d
  tokens, tile = b * t, _token_tile(b * t)
  dx, dy, d_maps = pl.pallas_call(
      functools.partial(_post_bwd_kernel, n=n),
      out_shape=[jax.ShapeDtypeStruct((tokens, n * d), x.dtype),
                 jax.ShapeDtypeStruct((tokens, d), y.dtype),
                 jax.ShapeDtypeStruct((tokens, _LANES), jnp.float32)],
      grid=(tokens // tile,),
      in_specs=[_tiled(tile, n * d), _tiled(tile, d), _tiled(tile, _LANES),
                _tiled(tile, n * d)],
      out_specs=[_tiled(tile, n * d), _tiled(tile, d), _tiled(tile, _LANES)],
      compiler_params=_params(),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[3],
  )(x.reshape(tokens, n * d), y.reshape(tokens, d), maps,
    g.reshape(tokens, n * d))
  return dx.reshape(x.shape), dy.reshape(y.shape), d_maps


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre_pallas(x, phi, alpha, base, config):
  u, maps = _pre_forward(x, phi, alpha, base, config)
  return (u,) + _unpack(maps, *x.shape[:2], num_streams(phi))


def _pre_pallas_fwd(x, phi, alpha, base, config):
  u, maps = _pre_forward(x, phi, alpha, base, config)
  return ((u,) + _unpack(maps, *x.shape[:2], num_streams(phi)),
          (x, phi, alpha, base, maps))


def _pre_pallas_bwd(config, residuals, cotangents):
  du, d_pre, d_post, d_res = cotangents
  return _pre_backward(*residuals, du, _pack(d_post, d_res, d_pre), config)


_pre_pallas.defvjp(_pre_pallas_fwd, _pre_pallas_bwd)


@jax.custom_vjp
def _post_pallas(x, y, h_post, h_res):
  return _post_forward(x, y, _pack(h_post, h_res))


def _post_pallas_fwd(x, y, h_post, h_res):
  maps = _pack(h_post, h_res)
  return _post_forward(x, y, maps), (x, y, maps)


def _post_pallas_bwd(residuals, cotangent):
  x, y, _ = residuals
  dx, dy, d_maps = _post_backward(*residuals, cotangent)
  _, d_post, d_res = _unpack(d_maps, *y.shape[:2], x.shape[2] // y.shape[2])
  return dx, dy, d_post, d_res


_post_pallas.defvjp(_post_pallas_fwd, _post_pallas_bwd)


# --- dispatch ----------------------------------------------------------------


def _unsupported(x, n: int):
  """None if the Pallas programs can run on an n-stream `x`, else the
  reason they cannot."""
  b, t, width = x.shape
  if width % (n * _LANES):
    return (f"a stream's width must be a multiple of {_LANES}; got "
            f"{width // n}")
  if n > _SUBLANES:
    return f"at most {_SUBLANES} streams; got {n}"
  if _token_tile(b * t) is None:
    return (f"the tokens of a batch must be a multiple of {_TOKEN_TILE}, or "
            f"fewer and a multiple of 16; got {b * t}")
  return None


def _use_xla(x, n: int, implementation: str) -> bool:
  if implementation not in ("auto", "pallas", "xla"):
    raise ValueError(
        f"implementation must be 'auto', 'pallas', or 'xla'; got "
        f"{implementation!r}")
  unsupported = _unsupported(x, n)
  use_xla = implementation == "xla" or (implementation == "auto" and (
      unsupported is not None or dispatch.use_xla_only()
      or jax.default_backend() != "tpu"))
  if not use_xla and unsupported is not None:
    raise ValueError(f"hyper_connection pallas path: {unsupported}")
  return use_xla


def hyper_connection_pre(x, phi, alpha, base, config: MapConfig = MapConfig(),
                         implementation: str = "auto"):
  """The read-out and the maps of one sublayer.

  Args:
    x: (B, T, n·D), a token's n streams side by side.
    phi: (n·D, n² + 2n) float32; alpha: (3,); base: (n² + 2n,).
    implementation: "pallas", "xla", or "auto" (pallas on a TPU where
      the shapes allow: D a multiple of 128, B·T of 128).

  Returns:
    (u (B, T, D) in x's dtype, H_pre (B, T, n), H_post (B, T, n), H_res
    (B, T, n, n), float32).
  """
  if _use_xla(x, num_streams(phi), implementation):
    return _pre_xla(x, phi, alpha, base, config)
  return _pre_pallas(x, phi, alpha, base, config)


def hyper_connection_post(x, y, h_post, h_res, implementation: str = "auto"):
  """X'[i] = Σ_j H_res[i, j] X[j] + H_post[i] y: (B, T, n·D) in x's dtype
  from x (B, T, n·D), y (B, T, D) and `hyper_connection_pre`'s maps."""
  if _use_xla(x, h_post.shape[-1], implementation):
    return _post_xla(x, y, h_post, h_res)
  return _post_pallas(x, y, h_post, h_res)

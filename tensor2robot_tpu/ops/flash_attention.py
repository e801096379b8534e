"""Blockwise (flash) multi-head attention — Pallas TPU kernel.

Single-device counterpart of parallel/ring_attention.py: the same
running-max/denominator accumulation, but blocked over VMEM tiles inside
one chip instead of over ring hops. O(T) HBM traffic for the forward
pass instead of materializing the (B, H, T, T) score tensor (which is
what the XLA reference below does). Used for long in-device sequences;
ring_attention composes it across chips for sequences that exceed one
device.

Gradient: custom_vjp with Pallas backward kernels (the standard flash
backward — residuals are q, k, v, the output, and the per-row
logsumexp; dq and dk/dv are recomputed blockwise in two passes), so
training memory stays O(T) end to end. First-order only — custom_vjp
does not compose with forward-over-reverse, so models differentiated
twice (MAML inner loops) must pass implementation="xla".

Precision follows the inputs: every product takes q, k, v and dO as
they are staged, and p and dS cast to that dtype, and sums in float32
(bf16 inputs: what a dense layer of a bf16 model does; float32 inputs:
float32 operands at Mosaic's default precision). The scale, max, exp,
sums, lse, delta and all accumulators are float32 whatever comes in.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import dispatch

# The three kernels' names, as the device trace shows them: forward
# (run again where a block is rematerialized), dq and dk/dv.
KERNEL_NAMES = ("flash_attention_fwd", "flash_attention_dq",
                "flash_attention_dkv")

# Tile sides, tried in this order: the first that divides T is taken.
# A probe of the three programs alone on v5e at T=8192, 32 heads, widths
# 192/128, bf16 (ms a call with the layout change around it, forward /
# dq / dkv): 128 29.3 / 30.3 / 40.7, 256 13.3 / 12.9 / 15.5, 512 9.1 /
# 12.0 / 14.1; 256x512, 256x1024 and 1024x1024 read as 512 does.
_BLOCKS = (512, 256, 128)
_MAX_SINGLE_BLOCK_T = 1024
# K and V (in the dk/dv program Q and dO) are staged whole per (b·h)
# row, and Pallas double-buffers pipelined inputs — so the resident
# footprint is 2× their size. Each call asks for the scoped VMEM it
# reckons (_compiler_params: the staged blocks and the tiles it works
# on); the rows are bounded here so that the sum stays a small part of
# the chip's 128 MiB of VMEM (T=8192 in bf16: widths 192/128 stage
# 10 MiB of rows and ask for 20-21 MiB, widths 256/256 stage 16 MiB and
# ask for 27-28 MiB). Longer sequences belong to ring_attention.
_MAX_KV_VMEM_BYTES = 16 * 1024 * 1024
_PIPELINE_BUFFERS = 2
_MIN_VMEM_LIMIT_BYTES = 16 * 1024 * 1024

# Q Kᵀ, dO Vᵀ and their transposes contract the last axis of both
# operands.
_NT = (((1,), (1,)), ((), ()))


def flash_attention_reference(q, k, v, causal: bool = False,
                              scale: Optional[float] = None):
  """XLA reference: materializes (B, H, T, T) scores. (B, T, H, D) in/out;
  v (and so the output) may have a head width of its own, and k and v
  fewer heads than q, each serving a group of consecutive query heads."""
  if scale is None:
    scale = 1.0 / math.sqrt(q.shape[-1])
  group = q.shape[2] // k.shape[2]
  if group > 1:
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
  scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale
  if causal:
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
  weights = jax.nn.softmax(scores, axis=-1)
  out = jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32))
  return out.astype(v.dtype)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
  """An MXU product: the operands in the dtype they come in, never
  cast up, the sum in float32."""
  return jax.lax.dot_general(a, b, dims,
                             preferred_element_type=jnp.float32)


def _causal_mask(s, row0, col0, transposed: bool = False):
  """Mask a score tile whose first query is row0 and first key col0 to
  the causal triangle with -inf; queries run down the tile, or across
  it where it is `transposed`."""
  query_axis = 1 if transposed else 0
  ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - query_axis)
           - jax.lax.broadcasted_iota(jnp.int32, s.shape, query_axis))
  return jnp.where(ahead <= row0 - col0, s, -jnp.inf)


def _tile_loop(body, carry, lower, upper, *, masked: bool):
  return jax.lax.fori_loop(
      lower, upper, functools.partial(body, masked=masked), carry)


def _key_tiles(body, carry, row0, *, causal: bool, block_q: int,
               block_k: int, seq_len: int):
  """Runs `body` over the K tiles a Q tile starting at row `row0` sees:
  all of them, or causally those under the diagonal unmasked and then
  the few the diagonal crosses, masked."""
  if not causal:
    return _tile_loop(body, carry, 0, seq_len // block_k, masked=False)
  below = (row0 + 1) // block_k          # last column <= first row
  reached = (row0 + block_q + block_k - 1) // block_k
  carry = _tile_loop(body, carry, 0, below, masked=False)
  return _tile_loop(body, carry, below, reached, masked=True)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
            causal: bool, block_q: int, block_k: int, seq_len: int):
  """One (block_q, D) query tile vs all K/V tiles of this (b·h) row.

  Also emits the per-row logsumexp (the flash-backward residual)."""
  q = q_ref[0]                                             # (BQ, D)
  row0 = pl.program_id(1) * block_q
  head_dim = v_ref.shape[-1]

  def body(kj, carry, masked):
    m, l, acc = carry
    col0 = pl.multiple_of(kj * block_k, block_k)
    k_blk = k_ref[0, pl.ds(col0, block_k), :]
    v_blk = v_ref[0, pl.ds(col0, block_k), :]
    s = _dot(q, k_blk, _NT) * scale                        # (BQ, BK)
    if masked:
      s = _causal_mask(s, row0, col0)
    # Every row sees column 0 in the first tile, so m is finite from
    # there on and a tile that masks a whole row adds exp(-inf) = 0.
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * correction + _dot(p.astype(v_blk.dtype), v_blk)
    return m_new, l_new, acc_new

  init = (jnp.full((block_q, 1), -jnp.inf, jnp.float32),
          jnp.zeros((block_q, 1), jnp.float32),
          jnp.zeros((block_q, head_dim), jnp.float32))
  m, l, acc = _key_tiles(body, init, row0, causal=causal, block_q=block_q,
                         block_k=block_k, seq_len=seq_len)
  # Shape (BQ, 1): the lse array carries a trailing unit dim so its
  # blocks satisfy the TPU (8, 128) block-shape rule.
  lse_ref[0] = m + jnp.log(l)
  o_ref[0] = (acc / l).astype(o_ref.dtype)


def _block_sizes(t: int):
  """(block_q, block_k) for a sequence of T, or None: the widest tile
  side that divides T, or all of a short T as one tile. A tile's chain
  of products and softmax passes runs start to end before the next
  tile's begins, so a wide tile is what keeps the MXU fed; a wider K
  tile also pays the running-max rescale of the accumulator once per
  more keys. Head widths and dtype do not enter: the tiles' float32
  working set is the same whatever is staged, and _supported bounds
  the staged rows."""
  for block in _BLOCKS:
    if t % block == 0:
      return block, block
  if t <= _MAX_SINGLE_BLOCK_T:
    return t, t
  return None


def _supported(q, k, v) -> Optional[str]:
  """None if the Pallas path can run, else the reason it cannot."""
  t = q.shape[1]
  if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
    return (f"query heads must be a multiple of the key/value heads; got "
            f"{q.shape[2]} on {k.shape[2]}/{v.shape[2]}")
  if _block_sizes(t) is None:
    return (f"T must be divisible by {_BLOCKS[-1]} or <= "
            f"{_MAX_SINGLE_BLOCK_T}; got T={t}")
  # K and V each at its own head width (MLA: 192 and 128).
  kv_bytes = _PIPELINE_BUFFERS * t * (
      k.shape[3] * k.dtype.itemsize + v.shape[3] * v.dtype.itemsize)
  if kv_bytes > _MAX_KV_VMEM_BYTES:
    return (f"double-buffered K+V row ({kv_bytes} bytes at T={t}, "
            f"D={k.shape[3]}/{v.shape[3]}) exceeds the "
            f"{_MAX_KV_VMEM_BYTES}-byte VMEM budget; use "
            "ring_attention for sequences this long")
  return None


def _compiler_params(block_q: int, block_k: int, d: int, dv: int,
                     *blocks):
  """Scoped-VMEM limit for a call that stages `blocks` ((rows, width,
  dtype) each, double-buffered, counted at padded sublanes and lanes: a
  (rows, 1) float32 column takes a whole 128-lane tile per 8 rows) and
  works on (block_q, block_k) tiles: beside the staged blocks a kernel
  keeps float32 tiles of the scores' shape (s, p, dP, dS, and p and dS
  again in the operands' dtype: six at most) and float32 accumulators
  d and dv wide."""
  pad = lambda n, to: -(-n // to) * to
  staged = sum(_PIPELINE_BUFFERS * pad(rows, 8) * pad(width, 128)
               * jnp.dtype(dtype).itemsize for rows, width, dtype in blocks)
  working = 4 * (6 * block_q * pad(block_k, 128)
                 + max(block_q, block_k) * (pad(d, 128) + pad(dv, 128)))
  return pltpu.CompilerParams(vmem_limit_bytes=max(
      _MIN_VMEM_LIMIT_BYTES, staged + working))


def _to_rows(x):
  b, t, h, d = x.shape
  return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_rows(x, b, h):
  bh, t, d = x.shape
  return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _pallas_forward(q, k, v, causal: bool, scale: float,
                    with_residuals: bool = False):
  b, t, h, d = q.shape
  dv = v.shape[3]
  block_q, block_k = _block_sizes(t)
  # (B, T, H, D) → (B·H, T, D): heads become independent grid rows.
  # Grouped queries: query row i reads key/value row i // group, which
  # stays staged while the group's rows go by.
  group = h // k.shape[2]
  qr, kr, vr = _to_rows(q), _to_rows(k), _to_rows(v)
  grid = (b * h, t // block_q)
  tile = lambda i, qi: (i, qi, 0)
  full = lambda i, qi: (i // group, 0, 0)
  out, lse = pl.pallas_call(
      functools.partial(_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k, seq_len=t),
      out_shape=(jax.ShapeDtypeStruct((b * h, t, dv), v.dtype),
                 jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32)),
      grid=grid,
      in_specs=[
          pl.BlockSpec((1, block_q, d), tile, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, d), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, dv), full, memory_space=pltpu.VMEM),
      ],
      out_specs=(
          pl.BlockSpec((1, block_q, dv), tile, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, 1), tile, memory_space=pltpu.VMEM),
      ),
      compiler_params=_compiler_params(
          block_q, block_k, d, dv,
          (block_q, d, q.dtype), (t, d, k.dtype), (t, dv, v.dtype),
          (block_q, dv, v.dtype), (block_q, 1, jnp.float32)),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[0],
  )(qr, kr, vr)
  out4 = _from_rows(out, b, h)
  if with_residuals:
    return out4, lse
  return out4


def _kernel_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, scale: float, causal: bool, block_q: int, block_k: int,
               seq_len: int):
  """dq for one query tile: dq_i = Σ_j (P_ij ⊙ (dO_i V_jᵀ − Δ_i)) K_j."""
  q = q_ref[0]                                             # (BQ, D)
  do = do_ref[0]                                           # (BQ, Dv)
  lse = lse_ref[0]                                         # (BQ, 1)
  delta = delta_ref[0]                                     # (BQ, 1)
  row0 = pl.program_id(1) * block_q

  def body(kj, dq_acc, masked):
    col0 = pl.multiple_of(kj * block_k, block_k)
    k_blk = k_ref[0, pl.ds(col0, block_k), :]
    v_blk = v_ref[0, pl.ds(col0, block_k), :]
    s = _dot(q, k_blk, _NT) * scale                        # (BQ, BK)
    if masked:
      s = _causal_mask(s, row0, col0)
    p = jnp.exp(s - lse)
    ds = p * (_dot(do, v_blk, _NT) - delta)                # (BQ, BK)
    return dq_acc + _dot(ds.astype(k_blk.dtype), k_blk)

  dq = _key_tiles(body, jnp.zeros(q.shape, jnp.float32), row0,
                  causal=causal, block_q=block_q, block_k=block_k,
                  seq_len=seq_len)
  dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _kernel_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale: float, causal: bool,
                block_q: int, block_k: int, seq_len: int):
  """dk/dv for one key tile: dV_j = Σ_i P_ijᵀ dO_i;
  dK_j = Σ_i (P_ij ⊙ (dO_i V_jᵀ − Δ_i))ᵀ Q_i · scale.

  The score tile is built transposed, (BK, BQ), with lse and delta as
  rows, one (1, BQ) row per Q tile: Pᵀ and dSᵀ are then what the two
  accumulating products take as they are, where (BQ, BK) tiles would
  be transposed once each per tile."""
  k_tile = k_ref[0]                                        # (BK, D)
  v_tile = v_ref[0]                                        # (BK, Dv)
  col0 = pl.program_id(1) * block_k

  def body(qi, carry, masked):
    dk_acc, dv_acc = carry
    row0 = pl.multiple_of(qi * block_q, block_q)
    q_blk = q_ref[0, pl.ds(row0, block_q), :]
    do_blk = do_ref[0, pl.ds(row0, block_q), :]
    lse_blk = lse_ref[0, pl.ds(qi, 1), :]                   # (1, BQ)
    delta_blk = delta_ref[0, pl.ds(qi, 1), :]
    s_t = _dot(k_tile, q_blk, _NT) * scale                 # (BK, BQ)
    if masked:
      s_t = _causal_mask(s_t, row0, col0, transposed=True)
    p_t = jnp.exp(s_t - lse_blk)
    dv_acc = dv_acc + _dot(p_t.astype(do_blk.dtype), do_blk)
    ds_t = p_t * (_dot(v_tile, do_blk, _NT) - delta_blk)
    dk_acc = dk_acc + _dot(ds_t.astype(q_blk.dtype), q_blk)
    return dk_acc, dv_acc

  num_q = seq_len // block_q
  carry = (jnp.zeros(k_tile.shape, jnp.float32),
           jnp.zeros(v_tile.shape, jnp.float32))
  if causal:
    # Only Q tiles whose last row reaches this K tile contribute: first
    # those the diagonal crosses, masked, then the ones wholly under it.
    reached = col0 // block_q
    below = jnp.minimum(
        (col0 + block_k - 1 + block_q - 1) // block_q, num_q)
    carry = _tile_loop(body, carry, reached, below, masked=True)
    dk, dv = _tile_loop(body, carry, below, num_q, masked=False)
  else:
    dk, dv = _tile_loop(body, carry, 0, num_q, masked=False)
  dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
  dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_backward(q, k, v, out, lse, do, causal: bool,
                     scale: float):
  """Two-pass flash backward over the row layout; returns (dq, dk, dv)
  in the original (B, T, H, D) layout.

  `out` is the forward output in its original (B, T, H, D) layout — the
  same array the caller's graph already keeps alive as the next layer's
  activation, so saving it as a residual costs no extra memory.
  """
  b, t, h, d = q.shape
  dv = v.shape[3]
  kv_heads = k.shape[2]
  group = h // kv_heads
  block_q, block_k = _block_sizes(t)
  qr, kr, vr, dor = _to_rows(q), _to_rows(k), _to_rows(v), _to_rows(do)
  # Δ_i = Σ_d dO_id · O_id — cheap elementwise reduction, XLA fuses it.
  # Trailing unit dim: see the lse shape note in _kernel.
  delta = _to_rows(jnp.sum(do.astype(jnp.float32)
                           * out.astype(jnp.float32), axis=-1,
                           keepdims=True))                  # (BH, T, 1)
  # The dk/dv program reads both as one row per Q tile, see _kernel_dkv.
  lse_rows, delta_rows = (x.reshape(b * h, t // block_q, block_q)
                          for x in (lse, delta))
  interpret = jax.default_backend() != "tpu"
  tile_q = lambda i, qi: (i, qi, 0)
  tile_k = lambda i, kj: (i, kj, 0)
  tile_kv = lambda i, kj: (i // group, kj, 0)
  full = lambda i, _: (i, 0, 0)
  full_kv = lambda i, _: (i // group, 0, 0)
  # Grouped queries: the dk/dv program runs a row per QUERY head and
  # writes that head's part, float32; the group's parts are summed after.
  dk_dtype, dv_dtype = ((jnp.float32, jnp.float32) if group > 1
                        else (k.dtype, v.dtype))
  kwargs = dict(scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, seq_len=t)
  dq = pl.pallas_call(
      functools.partial(_kernel_dq, **kwargs),
      out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
      grid=(b * h, t // block_q),
      in_specs=[
          pl.BlockSpec((1, block_q, d), tile_q, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, d), full_kv, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, dv), full_kv, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, dv), tile_q, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, 1), tile_q, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, 1), tile_q, memory_space=pltpu.VMEM),
      ],
      out_specs=pl.BlockSpec((1, block_q, d), tile_q,
                             memory_space=pltpu.VMEM),
      compiler_params=_compiler_params(
          block_q, block_k, d, dv,
          (block_q, d, q.dtype), (t, d, k.dtype), (t, dv, v.dtype),
          (block_q, dv, do.dtype), (block_q, 1, jnp.float32),
          (block_q, 1, jnp.float32), (block_q, d, q.dtype)),
      interpret=interpret,
      name=KERNEL_NAMES[1],
  )(qr, kr, vr, dor, lse, delta)
  dk, dv = pl.pallas_call(
      functools.partial(_kernel_dkv, **kwargs),
      out_shape=(jax.ShapeDtypeStruct((b * h, t, d), dk_dtype),
                 jax.ShapeDtypeStruct((b * h, t, dv), dv_dtype)),
      grid=(b * h, t // block_k),
      in_specs=[
          pl.BlockSpec((1, t, d), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_k, d), tile_kv, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_k, dv), tile_kv, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, dv), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t // block_q, block_q), full,
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t // block_q, block_q), full,
                       memory_space=pltpu.VMEM),
      ],
      out_specs=(
          pl.BlockSpec((1, block_k, d), tile_k, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_k, dv), tile_k, memory_space=pltpu.VMEM),
      ),
      compiler_params=_compiler_params(
          block_q, block_k, d, dv,
          (t, d, q.dtype), (block_k, d, k.dtype), (block_k, dv, v.dtype),
          (t, dv, do.dtype), (t // block_q, block_q, jnp.float32),
          (t // block_q, block_q, jnp.float32),
          (block_k, d, dk_dtype), (block_k, dv, dv_dtype)),
      interpret=interpret,
      name=KERNEL_NAMES[2],
  )(qr, kr, vr, dor, lse_rows, delta_rows)
  if group > 1:
    dk, dv = (x.reshape(b * kv_heads, group, t, -1).sum(axis=1).astype(
        like.dtype) for x, like in ((dk, k), (dv, v)))
  return (_from_rows(dq, b, h), _from_rows(dk, b, kv_heads),
          _from_rows(dv, b, kv_heads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention_pallas(q, k, v, causal: bool, scale: float):
  return _pallas_forward(q, k, v, causal, scale)


def _fwd(q, k, v, causal, scale):
  out, lse = _pallas_forward(q, k, v, causal, scale, with_residuals=True)
  return out, (q, k, v, out, lse)


def _bwd(causal, scale, residuals, grad):
  q, k, v, out, lse = residuals
  return _pallas_backward(q, k, v, out, lse, grad, causal, scale)


_flash_attention_pallas.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    implementation: str = "auto"):
  """Multi-head attention over (B, T, H, D) without the (T, T) tensor.

  Args:
    q, k, v: (B, T, H, D) arrays (same layout as ring_attention); q and
      k share one head width, v may have its own (MLA: 192 and 128),
      which is then the output's. k and v may have fewer heads than q
      (grouped queries): each serves H / Hkv consecutive query heads,
      and is neither repeated in HBM nor staged again for each.
    causal: apply a causal mask.
    scale: attention scale; default 1/sqrt(D).
    implementation: "pallas", "xla", or "auto" (pallas when T is
      blockable: divisible by 128, in tiles of the widest of 512, 256
      and 128 that divides it, or ≤ 1024 as one block).

  Returns:
    (B, T, H, Dv) attention output in v's dtype.
  """
  if implementation not in ("auto", "pallas", "xla"):
    raise ValueError(
        f"implementation must be 'auto', 'pallas', or 'xla'; got "
        f"{implementation!r}")
  if scale is None:
    scale = 1.0 / math.sqrt(q.shape[-1])
  unsupported = _supported(q, k, v)
  if implementation == "xla" or (implementation == "auto"
                                 and (unsupported is not None
                                      or dispatch.use_xla_only()
                                      or jax.default_backend() != "tpu")):
    return flash_attention_reference(q, k, v, causal, scale)
  if unsupported is not None:
    raise ValueError(f"flash_attention pallas path: {unsupported}")
  return _flash_attention_pallas(q, k, v, causal, scale)

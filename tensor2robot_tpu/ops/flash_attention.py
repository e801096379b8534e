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

_BLOCK = 128
_MAX_SINGLE_BLOCK_T = 1024
# K and V are staged whole per (b·h) row, and Pallas double-buffers
# pipelined inputs — so the resident K/V footprint is 2× their size.
# Bound that under the ~16 MB scoped-VMEM budget with headroom for the
# Q/O/lse tiles and f32 working set (measured on v5e: T=8192, D=128
# bf16 fits; T=16384 overflows the 16 MB limit by the double buffer).
# Longer sequences belong to ring_attention.
_MAX_KV_VMEM_BYTES = 14 * 1024 * 1024
_PIPELINE_BUFFERS = 2
# Room beside the staged blocks for a kernel's float32 working set
# (the cast blocks, the score tile, the accumulators).
_VMEM_WORKING_BYTES = 8 * 1024 * 1024
_MIN_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def flash_attention_reference(q, k, v, causal: bool = False,
                              scale: Optional[float] = None):
  """XLA reference: materializes (B, H, T, T) scores. (B, T, H, D) in/out;
  v (and so the output) may have a head width of its own."""
  if scale is None:
    scale = 1.0 / math.sqrt(q.shape[-1])
  scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale
  if causal:
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
  weights = jax.nn.softmax(scores, axis=-1)
  out = jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32))
  return out.astype(v.dtype)


def _causal_mask(s, qi, kj, block_q: int, block_k: int):
  """Mask the (BQ, BK) score tile to the causal triangle with -inf."""
  rows = qi * block_q + jax.lax.broadcasted_iota(
      jnp.int32, (block_q, block_k), 0)
  cols = kj * block_k + jax.lax.broadcasted_iota(
      jnp.int32, (block_q, block_k), 1)
  return jnp.where(rows >= cols, s, -jnp.inf)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
            causal: bool, block_q: int, block_k: int, seq_len: int):
  """One (block_q, D) query tile vs all K/V tiles of this (b·h) row.

  Also emits the per-row logsumexp (the flash-backward residual)."""
  q = q_ref[0].astype(jnp.float32) * scale                 # (BQ, D)
  qi = pl.program_id(1)
  head_dim = v_ref.shape[-1]

  def body(kj, carry):
    m, l, acc = carry
    k_blk = k_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
    v_blk = v_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (BQ, BK)
    if causal:
      s = _causal_mask(s, qi, kj, block_q, block_k)
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    safe_max = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    correction = jnp.exp(m - safe_max)
    p = jnp.exp(s - safe_max)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * correction + jnp.dot(
        p, v_blk, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new

  if causal:
    # Only K blocks that intersect the causal triangle of this Q tile.
    num_k = (qi * block_q + block_q + block_k - 1) // block_k
  else:
    num_k = seq_len // block_k
  init = (jnp.full((block_q, 1), -jnp.inf, jnp.float32),
          jnp.zeros((block_q, 1), jnp.float32),
          jnp.zeros((block_q, head_dim), jnp.float32))
  m, l, acc = jax.lax.fori_loop(0, num_k, body, init)
  safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
  # Fully-masked rows (l == 0, only possible non-causally with explicit
  # masks) get a large-negative finite lse via the 1e-37 clamp; the
  # backward's exp(s - lse) is still 0 there because s is -inf. Shape
  # (BQ, 1): the lse array carries a trailing unit dim so its blocks
  # satisfy the TPU (8, 128) block-shape rule.
  lse_ref[0] = safe_m + jnp.log(jnp.maximum(l, 1e-37))
  l = jnp.where(l == 0.0, 1.0, l)
  o_ref[0] = (acc / l).astype(o_ref.dtype)


def _block_sizes(t: int):
  if t % _BLOCK == 0:
    return _BLOCK, _BLOCK
  if t <= _MAX_SINGLE_BLOCK_T:
    return t, t
  return None


def _supported(q, k, v) -> Optional[str]:
  """None if the Pallas path can run, else the reason it cannot."""
  t = q.shape[1]
  if _block_sizes(t) is None:
    return (f"T must be divisible by {_BLOCK} or <= "
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


def _compiler_params(*blocks):
  """Scoped-VMEM limit for a call that stages `blocks` ((rows, width,
  dtype) each, double-buffered): a (rows, 1) float32 column takes a
  whole 128-lane tile per 8 rows, so the backward's full-row lse and
  delta outgrow Mosaic's 16 MiB default from T = 4096 on."""
  staged = 0
  for rows, width, dtype in blocks:
    lanes = -(-width // 128) * 128
    staged += _PIPELINE_BUFFERS * rows * lanes * jnp.dtype(dtype).itemsize
  return pltpu.CompilerParams(vmem_limit_bytes=max(
      _MIN_VMEM_LIMIT_BYTES, staged + _VMEM_WORKING_BYTES))


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
  qr, kr, vr = _to_rows(q), _to_rows(k), _to_rows(v)
  grid = (b * h, t // block_q)
  tile = lambda i, qi: (i, qi, 0)
  full = lambda i, qi: (i, 0, 0)
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
  q = q_ref[0].astype(jnp.float32)                         # (BQ, D)
  do = do_ref[0].astype(jnp.float32)                       # (BQ, D)
  lse = lse_ref[0]                                         # (BQ, 1)
  delta = delta_ref[0]                                     # (BQ, 1)
  qi = pl.program_id(1)

  def body(kj, dq_acc):
    k_blk = k_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
    v_blk = v_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (BQ, BK)
    if causal:
      s = _causal_mask(s, qi, kj, block_q, block_k)
    p = jnp.exp(s - lse)
    dpv = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (BQ, BK)
    ds = p * (dpv - delta)
    return dq_acc + jnp.dot(ds, k_blk,
                            preferred_element_type=jnp.float32) * scale

  if causal:
    num_k = (qi * block_q + block_q + block_k - 1) // block_k
  else:
    num_k = seq_len // block_k
  dq = jax.lax.fori_loop(
      0, num_k, body, jnp.zeros((block_q, q.shape[-1]), jnp.float32))
  dq_ref[0] = dq.astype(dq_ref.dtype)


def _kernel_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale: float, causal: bool,
                block_q: int, block_k: int, seq_len: int):
  """dk/dv for one key tile: dV_j = Σ_i P_ijᵀ dO_i;
  dK_j = Σ_i (P_ij ⊙ (dO_i V_jᵀ − Δ_i))ᵀ Q_i · scale."""
  k_tile = k_ref[0].astype(jnp.float32)                    # (BK, D)
  v_tile = v_ref[0].astype(jnp.float32)                    # (BK, D)
  kj = pl.program_id(1)

  def body(qi, carry):
    dk_acc, dv_acc = carry
    q_blk = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
    do_blk = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(
        jnp.float32)
    lse_blk = lse_ref[0, pl.ds(qi * block_q, block_q), :]   # (BQ, 1)
    delta_blk = delta_ref[0, pl.ds(qi * block_q, block_q), :]
    s = jax.lax.dot_general(
        q_blk, k_tile, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (BQ, BK)
    if causal:
      s = _causal_mask(s, qi, kj, block_q, block_k)
    p = jnp.exp(s - lse_blk)                               # (BQ, BK)
    dv_acc = dv_acc + jax.lax.dot_general(
        p, do_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (BK, D)
    dpv = jax.lax.dot_general(
        do_blk, v_tile, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (BQ, BK)
    ds = p * (dpv - delta_blk)
    dk_acc = dk_acc + jax.lax.dot_general(
        ds, q_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (BK, D)
    return dk_acc, dv_acc

  num_q = seq_len // block_q
  # Causal: only Q tiles whose last row reaches this K tile contribute.
  start = (kj * block_k) // block_q if causal else 0
  init = (jnp.zeros(k_tile.shape, jnp.float32),
          jnp.zeros(v_tile.shape, jnp.float32))
  dk, dv = jax.lax.fori_loop(start, num_q, body, init)
  dk_ref[0] = dk.astype(dk_ref.dtype)
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
  block_q, block_k = _block_sizes(t)
  qr, kr, vr, dor = _to_rows(q), _to_rows(k), _to_rows(v), _to_rows(do)
  # Δ_i = Σ_d dO_id · O_id — cheap elementwise reduction, XLA fuses it.
  # Trailing unit dim: see the lse shape note in _kernel.
  delta = _to_rows(jnp.sum(do.astype(jnp.float32)
                           * out.astype(jnp.float32), axis=-1,
                           keepdims=True))                  # (BH, T, 1)
  interpret = jax.default_backend() != "tpu"
  tile_q = lambda i, qi: (i, qi, 0)
  tile_k = lambda i, kj: (i, kj, 0)
  full = lambda i, _: (i, 0, 0)
  kwargs = dict(scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, seq_len=t)
  dq = pl.pallas_call(
      functools.partial(_kernel_dq, **kwargs),
      out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
      grid=(b * h, t // block_q),
      in_specs=[
          pl.BlockSpec((1, block_q, d), tile_q, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, d), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, dv), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, dv), tile_q, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, 1), tile_q, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_q, 1), tile_q, memory_space=pltpu.VMEM),
      ],
      out_specs=pl.BlockSpec((1, block_q, d), tile_q,
                             memory_space=pltpu.VMEM),
      compiler_params=_compiler_params(
          (block_q, d, q.dtype), (t, d, k.dtype), (t, dv, v.dtype),
          (block_q, dv, do.dtype), (block_q, 1, jnp.float32),
          (block_q, 1, jnp.float32), (block_q, d, q.dtype)),
      interpret=interpret,
      name=KERNEL_NAMES[1],
  )(qr, kr, vr, dor, lse, delta)
  dk, dv = pl.pallas_call(
      functools.partial(_kernel_dkv, **kwargs),
      out_shape=(jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
                 jax.ShapeDtypeStruct((b * h, t, dv), v.dtype)),
      grid=(b * h, t // block_k),
      in_specs=[
          pl.BlockSpec((1, t, d), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_k, d), tile_k, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_k, dv), tile_k, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, dv), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, 1), full, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, t, 1), full, memory_space=pltpu.VMEM),
      ],
      out_specs=(
          pl.BlockSpec((1, block_k, d), tile_k, memory_space=pltpu.VMEM),
          pl.BlockSpec((1, block_k, dv), tile_k, memory_space=pltpu.VMEM),
      ),
      compiler_params=_compiler_params(
          (t, d, q.dtype), (block_k, d, k.dtype), (block_k, dv, v.dtype),
          (t, dv, do.dtype), (t, 1, jnp.float32), (t, 1, jnp.float32),
          (block_k, d, k.dtype), (block_k, dv, v.dtype)),
      interpret=interpret,
      name=KERNEL_NAMES[2],
  )(qr, kr, vr, dor, lse, delta)
  return (_from_rows(dq, b, h), _from_rows(dk, b, h),
          _from_rows(dv, b, h))


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
      which is then the output's.
    causal: apply a causal mask.
    scale: attention scale; default 1/sqrt(D).
    implementation: "pallas", "xla", or "auto" (pallas when T is
      blockable: divisible by 128 or ≤ 1024 as one block).

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

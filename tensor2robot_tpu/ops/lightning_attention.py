"""Lightning attention (arXiv:2401.04658; the decayed form MiniMax-01,
arXiv:2501.08313, and MiniCPM-SALA run) — linear attention with a fixed
decay for each head, chunked, through the gated delta rule's walk.

Per head, a (Dk, Dv) float32 state S from nought, decay lambda:

  S_t = lambda S_{t-1} + k_t^T v_t,   o_t = q_t S_t

Chunks of C = 64 tokens, i, j places inside a chunk, every decay formed
as exp(log(lambda) x a difference that is never negative), so that no
power of lambda is above 1 (a fast head's lambda is about 0.43, and
lambda^-63 beside lambda^63 would lose the product in bf16):

  qg = q lambda^(i+1),  kg = k lambda^(C-1-i),
  M_ij = (q_i . k_j) lambda^(i-j) for i >= j, else 0,  a = lambda^C
  per chunk, from the state S it starts with:
    o = qg S + M v;   S <- a S + kg^T v

This is the gated delta rule's chunked form with no write correction (w
= 0, u = v) and g = log(lambda) at every token: the walk is its two
Pallas programs' bodies (`gated_delta_rule._fwd_kernel`, `_bwd_kernel`)
without the w operand, so that no w S product is made, under names of
their own. The chunk-local part needs no inverse: plain XLA, batched
over the chunks, differentiated by autodiff.

`implementation="xla"` (and "auto" off a TPU or under
`dispatch.xla_only()`) walks the chunks as the delta rule's `lax.scan`
with w = 0: the CPU path and the programs' oracle.

Precision follows the inputs, as in the delta rule: products take q, k,
v and the state in their dtype and sum in float32; the decays and the
state are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import dispatch
from tensor2robot_tpu.ops import gated_delta_rule as rule

# The walk's two programs, as the device trace shows them: the forward
# (run again where a block is rematerialized) and the backward.
KERNEL_NAMES = ("lightning_attention_fwd", "lightning_attention_bwd")

CHUNK = rule.CHUNK


def log_decays(heads: int, first_head: int, held: int, layer: int,
               layers: int):
  """(held,) float32 log(lambda_h) of the heads `first_head` ..
  `first_head + held - 1` of `heads` in layer `layer` of `layers`:
  -m_h (1 - layer / (layers - 1) + 1e-5), m_h = 2^(-8 (h + 1) / heads)
  (MiniMax-01's schedule: slopes as ALiBi's, each layer slower than the
  one before it)."""
  h = jnp.arange(first_head, first_head + held, dtype=jnp.float32)
  slope = 2.0 ** (-8.0 * (h + 1.0) / heads)
  return -slope * (1.0 - layer / (layers - 1) + 1e-5)


def _prepare(q, k, v, log_decay, chunk: int):
  """(B, T, H, D) q, k, v and (H,) log-decays -> qg, kg, u (= v), M (B,
  H, N, C, .) in v's dtype and the chunks' decay a (B, H, N) float32."""
  b, t, h, _ = v.shape
  n, dtype, f32 = t // chunk, v.dtype, jnp.float32
  chunks = lambda x: x.transpose(0, 2, 1, 3).reshape(b, h, n, chunk,
                                                     x.shape[3])
  q, k, v = chunks(q), chunks(k), chunks(v)
  log_decay = log_decay.astype(f32)[None, :, None, None]      # (1, H, 1, 1)
  place = jnp.arange(chunk, dtype=f32)
  grown = jnp.exp(log_decay * (place + 1.0))[..., None]       # lambda^(i+1)
  to_end = jnp.exp(log_decay * (chunk - 1.0 - place))[..., None]
  apart = place[:, None] - place[None, :]                     # i - j
  # Masked before the exponential: above the diagonal i - j < 0.
  decay = jnp.where(apart >= 0, jnp.exp(log_decay * jnp.maximum(apart, 0.0)),
                    0.0)                                       # (1, H, C, C)
  m = jnp.einsum("bhnid,bhnjd->bhnij", q, k,
                 preferred_element_type=f32) * decay[:, :, None]
  qg = (q.astype(f32) * grown).astype(dtype)
  kg = (k.astype(f32) * to_end).astype(dtype)
  a = jnp.broadcast_to(jnp.exp(log_decay[..., 0] * chunk), (b, h, n))
  return qg, kg, v, m.astype(dtype), a


@jax.jit
def _pallas_forward(qg, kg, u, m, a):
  """The walk, keeping each chunk's starting state for the backward."""
  b, h, n, chunk, dk = qg.shape
  dv = u.shape[-1]
  nb = rule._block_chunks(n)
  specs = rule._specs(nb * chunk, nb, dv)
  o, states = pl.pallas_call(
      functools.partial(_fwd_kernel, chunk=chunk, block_chunks=nb),
      out_shape=[jax.ShapeDtypeStruct((b * h, n * chunk, dv), u.dtype),
                 jax.ShapeDtypeStruct((b * h, n, dk, dv), u.dtype)],
      grid=(b * h, n // nb),
      in_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dv),
                specs["rows"](chunk), specs["scalars"]],
      out_specs=[specs["rows"](dv), specs["states"](dk, dv)],
      scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
      compiler_params=rule._PARAMS,
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[0],
  )(rule._rows(qg), rule._rows(kg), rule._rows(u), rule._rows(m),
    rule._lanes(a, dv))
  return o.reshape(b, h, n, chunk, dv), states


def _fwd_kernel(qg_ref, kg_ref, u_ref, m_ref, a_ref, o_ref, h_ref, state_ref,
                **sizes):
  rule._fwd_kernel(qg_ref, kg_ref, None, u_ref, m_ref, a_ref, o_ref, h_ref,
                   state_ref, **sizes)


def _bwd_kernel(qg_ref, kg_ref, u_ref, m_ref, a_ref, h_ref, do_ref, dqg_ref,
                dkg_ref, du_ref, dm_ref, da_ref, ds_ref, **sizes):
  rule._bwd_kernel(qg_ref, kg_ref, None, u_ref, m_ref, a_ref, h_ref, do_ref,
                   dqg_ref, dkg_ref, None, du_ref, dm_ref, da_ref, ds_ref,
                   **sizes)


@jax.jit
def _pallas_backward(qg, kg, u, m, a, states, do):
  b, h, n, chunk, dk = qg.shape
  dv = u.shape[-1]
  nb = rule._block_chunks(n)
  specs = rule._specs(nb * chunk, nb, dv, reverse_of=n // nb)
  like = lambda x: jax.ShapeDtypeStruct(rule._rows(x).shape, x.dtype)
  dqg, dkg, du, dm, da = pl.pallas_call(
      functools.partial(_bwd_kernel, chunk=chunk, block_chunks=nb),
      out_shape=[like(qg), like(kg), like(u), like(m),
                 jax.ShapeDtypeStruct((b * h, n, dv), jnp.float32)],
      grid=(b * h, n // nb),
      in_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dv),
                specs["rows"](chunk), specs["scalars"],
                specs["states"](dk, dv), specs["rows"](dv)],
      out_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dv),
                 specs["rows"](chunk), specs["scalars"]],
      scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
      compiler_params=rule._PARAMS,
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[1],
  )(rule._rows(qg), rule._rows(kg), rule._rows(u), rule._rows(m),
    rule._lanes(a, dv), states, rule._rows(do))
  unrow = lambda x, like: x.reshape(like.shape)
  return (unrow(dqg, qg), unrow(dkg, kg), unrow(du, u), unrow(dm, m),
          jnp.sum(da, axis=-1).reshape(a.shape))


@jax.custom_vjp
def _walk_pallas(qg, kg, u, m, a):
  return _pallas_forward(qg, kg, u, m, a)[0]


def _walk_fwd(qg, kg, u, m, a):
  o, states = _pallas_forward(qg, kg, u, m, a)
  return o, (qg, kg, u, m, a, states)


def _walk_bwd(residuals, do):
  return _pallas_backward(*residuals, do)


_walk_pallas.defvjp(_walk_fwd, _walk_bwd)


def lightning_attention(q, k, v, log_decay, implementation: str = "auto"):
  """o_t = q_t S_t, S_t = lambda S_{t-1} + k_t^T v_t, from a zero state.

  Args:
    q, k: (B, T, H, Dk), as the recurrence takes them (the caller
      normalizes, turns and scales); v: (B, T, H, Dv).
    log_decay: (H,) log(lambda) of each head, < 0; not differentiated.
    implementation: "pallas", "xla", or "auto" (pallas on a TPU where
      the shapes allow: head widths multiples of 128, the number of
      chunks under or a multiple of 8). T is a multiple of CHUNK, or one
      shorter chunk.

  Returns:
    (B, T, H, Dv) in v's dtype.
  """
  if implementation not in ("auto", "pallas", "xla"):
    raise ValueError(
        f"implementation must be 'auto', 'pallas', or 'xla'; got "
        f"{implementation!r}")
  b, t, h, dv = v.shape
  chunk = min(CHUNK, t)
  if t % chunk:
    raise ValueError(f"T must be a multiple of the chunk ({chunk}); got {t}")
  unsupported = rule._supported(chunk, t // chunk, q.shape[3], dv)
  use_xla = implementation == "xla" or (implementation == "auto" and (
      unsupported is not None or dispatch.use_xla_only()
      or jax.default_backend() != "tpu"))
  if not use_xla and unsupported is not None:
    raise ValueError(f"lightning_attention pallas path: {unsupported}")
  qg, kg, u, m, a = _prepare(q, k, v, jax.lax.stop_gradient(log_decay),
                             chunk)
  if use_xla:
    o = rule._walk_xla(qg, kg, jnp.zeros_like(qg), u, m, a)
  else:
    o = _walk_pallas(qg, kg, u, m, a)
  return o.reshape(b, h, t, dv).transpose(0, 2, 1, 3)

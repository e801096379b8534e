"""Gated delta rule (Gated Delta Networks, arXiv:2412.06464) — the
linear-attention recurrence, chunked, with Pallas TPU kernels for the
part that is sequential.

Per value head, with a (Dk, Dv) float32 state S starting at nought:

  S'_t = exp(g_t) S_{t-1}
  S_t  = S'_t + b_t k_t (v_t - S'_t^T k_t)^T
  o_t  = S_t^T q_t

Chunked (WY) form, chunks of C = 64 tokens, G_i the running sum of g
inside the chunk, D_ij = exp(G_i - G_j) for i >= j, else 0:

  A = strictly-lower((b k) k^T . D),  T = (I + A)^-1
  u = T (b v),  w = T (b k exp(G)),  M = q k^T . D
  per chunk, from the state S it starts with:
    v' = u - w S
    o  = (q exp(G)) S + M v'
    S <- exp(G_C) S + (k exp(G_C - G))^T v'

Everything chunk-local (the decays, T, u, w, M) is batched XLA over all
chunks at once and differentiated by autodiff. The walk over chunks is
what the Pallas programs do: heads on a parallel grid axis, blocks of
chunks on an "arbitrary" one with the head's state in VMEM scratch;
the backward walks the chunks in reverse carrying dS, from the
chunk-start states the forward kept ((chunks, Dk, Dv) a head, in the
operands' dtype: as the products take them). Of the chunk-local part
only T is kept for the backward pass and the rest is made again
(`jax.checkpoint`): at T = 8,192 and 32 heads its float32 intermediates
are 67-134 MB each, and T is the ten products of the whole part.
`implementation="xla"` runs the same walk as a `lax.scan` (the CPU path
and the kernels' oracle).

Precision follows the inputs: products take q, k, v-derived operands
in the dtype they come in (bf16 in a bf16 model) and the state cast to
it, and sum in float32; g, its running sums and exponentials, b, T and
the state are float32 whatever comes in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import dispatch

# The two kernels' names, as the device trace shows them: the forward
# walk (run again where a block is rematerialized) and the backward.
KERNEL_NAMES = ("gated_delta_rule_fwd", "gated_delta_rule_bwd")

CHUNK = 64
# Chunks a grid step walks: the blocks it stages are then 512 rows, and
# the grid 1/8 as many steps as chunks.
_BLOCK_CHUNKS = 8
_LANES = 128

_NT = (((1,), (1,)), ((), ()))   # a b^T
_TN = (((0,), (0,)), ((), ()))   # a^T b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
  return jax.lax.dot_general(a, b, dims,
                             preferred_element_type=jnp.float32)


# Three bf16 passes: float32's accuracy to about 1e-6, at half the
# passes of "highest". The inverse is cast to the operands' dtype before
# it is used; what the chain must not do is compound bf16's own rounding
# through its five squarings.
_matmul = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGH)
# The inverse's name under `jax.checkpoint`: a caller that recomputes a
# block may keep it too (`save_only_these_names`).
INVERSE_NAME = "gated_delta_rule_inverse"


@jax.custom_vjp
def _unit_lower_inverse(a):
  """(I + A)^-1 for strictly lower-triangular A (..., C, C), float32:
  A is nilpotent, so the Neumann series ends, and its C terms factor
  into log2(C) products: (I - A)(I + A^2)(I + A^4)... The backward is
  the inverse's own, dA = -T^T dT T^T, so that only T is kept."""
  c = a.shape[-1]
  eye = jnp.eye(c, dtype=a.dtype)
  inverse, power, reach = eye - a, a, 2
  while reach < c:
    power = _matmul(power, power)
    inverse = _matmul(inverse, eye + power)
    reach *= 2
  return inverse


def _unit_lower_inverse_fwd(a):
  inverse = _unit_lower_inverse(a)
  return inverse, inverse


def _unit_lower_inverse_bwd(inverse, cotangent):
  transposed = jnp.swapaxes(inverse, -1, -2)
  return (-_matmul(_matmul(transposed, cotangent), transposed),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _prepare(q, k, v, g, beta, chunk: int):
  """The chunk-local part. (B, T, H, D) inputs (q, k with Hk heads, each
  serving Hv / Hk value heads) -> qg, kg, w (B, Hv, N, C, Dk), u (B, Hv,
  N, C, Dv), m (B, Hv, N, C, C) in v's dtype, and the chunks' whole
  decay exp(G_C) (B, Hv, N) float32."""
  b, t, hv, _ = v.shape
  hk = q.shape[2]
  n, dtype, f32 = t // chunk, v.dtype, jnp.float32
  chunks = lambda x: x.transpose(0, 2, 1, 3).reshape(
      b, x.shape[2], n, chunk, x.shape[3])
  q, k = (jnp.repeat(chunks(x), hv // hk, axis=1) for x in (q, k))
  v = chunks(v)
  g, beta = (x.astype(f32).transpose(0, 2, 1).reshape(b, hv, n, chunk)
             for x in (g, beta))
  gamma = jnp.cumsum(g, axis=-1)
  row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
  # Masked before the exponential: above the diagonal the difference is
  # positive and unbounded.
  decay = jnp.exp(jnp.where(
      row >= col, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
  k_beta = k.astype(f32) * beta[..., None]
  product = functools.partial(jnp.einsum,
                              preferred_element_type=jnp.float32)
  a = jnp.where(row > col, product(
      "bhnid,bhnjd->bhnij", k_beta.astype(dtype), k) * decay, 0.0)
  t_inv = checkpoint_name(_unit_lower_inverse(a), INVERSE_NAME).astype(dtype)
  apply = lambda rows: product("bhnij,bhnjd->bhnid", t_inv,
                               rows.astype(dtype)).astype(dtype)
  u = apply(v.astype(f32) * beta[..., None])
  w = apply(k_beta * jnp.exp(gamma)[..., None])
  m = (product("bhnid,bhnjd->bhnij", q, k) * decay).astype(dtype)
  qg = (q.astype(f32) * jnp.exp(gamma)[..., None]).astype(dtype)
  last = gamma[..., -1:]
  kg = (k.astype(f32) * jnp.exp(last - gamma)[..., None]).astype(dtype)
  return qg, kg, w, u, m, jnp.exp(last[..., 0])


def _walk_xla(qg, kg, w, u, m, a):
  """The walk over chunks as a scan: (B, H, N, C, .) -> o (B, H, N, C,
  Dv) in u's dtype."""
  dtype, f32 = u.dtype, jnp.float32
  product = functools.partial(jnp.einsum, preferred_element_type=f32)

  def step(state, chunk):
    qg, kg, w, u, m, a = chunk
    held = state.astype(dtype)
    fresh = u.astype(f32) - product("bhck,bhkv->bhcv", w, held)
    o = (product("bhck,bhkv->bhcv", qg, held)
         + product("bhij,bhjv->bhiv", m, fresh.astype(dtype)))
    state = a[..., None, None] * state + product(
        "bhck,bhcv->bhkv", kg, fresh.astype(dtype))
    return state, o.astype(dtype)

  first = lambda x: jnp.moveaxis(x, 2, 0)
  b, h, _, _, dk = qg.shape
  _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, u.shape[-1]), f32),
                      tuple(map(first, (qg, kg, w, u, m, a))))
  return jnp.moveaxis(o, 0, 2)


# --- the Pallas walk ---------------------------------------------------------


def _fwd_kernel(qg_ref, kg_ref, w_ref, u_ref, m_ref, a_ref, o_ref, *rest,
                chunk: int, block_chunks: int):
  """One head's next `block_chunks` chunks from the state in scratch;
  with a states output, each chunk's starting state is kept."""
  h_ref, state_ref = rest if len(rest) == 2 else (None, rest[0])

  @pl.when(pl.program_id(1) == 0)
  def _():
    state_ref[...] = jnp.zeros_like(state_ref)

  state = state_ref[...]
  dtype = u_ref.dtype
  for j in range(block_chunks):
    rows = slice(j * chunk, (j + 1) * chunk)
    held = state.astype(dtype)
    if h_ref is not None:
      h_ref[0, j] = held
    fresh = u_ref[0, rows, :].astype(jnp.float32) - _dot(
        w_ref[0, rows, :], held)
    fresh_in = fresh.astype(dtype)
    o = _dot(qg_ref[0, rows, :], held) + _dot(m_ref[0, rows, :], fresh_in)
    o_ref[0, rows, :] = o.astype(o_ref.dtype)
    state = a_ref[0, j:j + 1, :] * state + _dot(
        kg_ref[0, rows, :], fresh_in, _TN)
  state_ref[...] = state


def _bwd_kernel(qg_ref, kg_ref, w_ref, u_ref, m_ref, a_ref, h_ref, do_ref,
                dqg_ref, dkg_ref, dw_ref, du_ref, dm_ref, da_ref, ds_ref,
                *, chunk: int, block_chunks: int):
  """The same chunks last to first, dS (the cotangent of the state a
  chunk leaves) in scratch. With S the chunk's starting state, v' = u -
  w S, and dO given:
    dv' = M^T dO + kg dS;   du = dv';   dw = -dv' S^T
    dqg = dO S^T;  dM = dO v'^T;  dkg = v' dS^T;  da = <dS, S>
    dS <- a dS + qg^T dO - w^T dv'"""

  @pl.when(pl.program_id(1) == 0)
  def _():
    ds_ref[...] = jnp.zeros_like(ds_ref)

  ds = ds_ref[...]
  dtype = u_ref.dtype
  for j in reversed(range(block_chunks)):
    rows = slice(j * chunk, (j + 1) * chunk)
    held, ds_in = h_ref[0, j], ds.astype(dtype)
    qg, kg, w = qg_ref[0, rows, :], kg_ref[0, rows, :], w_ref[0, rows, :]
    do = do_ref[0, rows, :]
    fresh = (u_ref[0, rows, :].astype(jnp.float32)
             - _dot(w, held)).astype(dtype)
    dfresh = _dot(m_ref[0, rows, :], do, _TN) + _dot(kg, ds_in)
    dfresh_in = dfresh.astype(dtype)
    du_ref[0, rows, :] = dfresh.astype(du_ref.dtype)
    dw_ref[0, rows, :] = (-_dot(dfresh_in, held, _NT)).astype(dw_ref.dtype)
    dqg_ref[0, rows, :] = _dot(do, held, _NT).astype(dqg_ref.dtype)
    dm_ref[0, rows, :] = _dot(do, fresh, _NT).astype(dm_ref.dtype)
    dkg_ref[0, rows, :] = _dot(fresh, ds_in, _NT).astype(dkg_ref.dtype)
    # Summed over Dk here, over the lanes (Dv) by the caller.
    da_ref[0, j:j + 1, :] = jnp.sum(ds * held.astype(jnp.float32), axis=0,
                                    keepdims=True)
    ds = (a_ref[0, j:j + 1, :] * ds + _dot(qg, do, _TN)
          - _dot(w, dfresh_in, _TN))
  ds_ref[...] = ds


def _block_chunks(n: int) -> Optional[int]:
  """Chunks a grid step walks, or None where the Pallas walk cannot run:
  a block's second-last side is a multiple of 8 or the whole axis."""
  if n % _BLOCK_CHUNKS == 0:
    return _BLOCK_CHUNKS
  return n if n < _BLOCK_CHUNKS else None


def _supported(chunk: int, n: int, dk: int, dv: int) -> Optional[str]:
  """None if the Pallas walk can run, else the reason it cannot."""
  if dk % _LANES or dv % _LANES:
    return (f"head widths must be multiples of {_LANES}; got {dk}/{dv}")
  if chunk % 8:
    return f"chunk must be a multiple of 8; got {chunk}"
  if _block_chunks(n) is None:
    return (f"the number of chunks must be under or a multiple of "
            f"{_BLOCK_CHUNKS}; got {n}")
  return None


def _rows(x):
  """(B, H, N, C, D) -> (B·H, N·C, D): heads become grid rows."""
  b, h, n, c, d = x.shape
  return x.reshape(b * h, n * c, d)


def _lanes(a, width: int):
  """(B, H, N) -> (B·H, N, width): a chunk's scalar along a row of
  lanes, so that a block of it is a tile and scales a (Dk, width) state
  by broadcast."""
  b, h, n = a.shape
  return jnp.broadcast_to(a.reshape(b * h, n, 1), (b * h, n, width))


def _specs(block_rows: int, nb: int, dv: int,
           reverse_of: Optional[int] = None):
  """BlockSpecs by kind for a (heads, blocks of chunks) grid; with
  `reverse_of` blocks, the blocks run last to first."""
  at = (lambda c: c) if reverse_of is None else (
      lambda c: reverse_of - 1 - c)
  vmem = pltpu.VMEM
  return {
      "rows": lambda d: pl.BlockSpec(
          (1, block_rows, d), lambda i, c: (i, at(c), 0), memory_space=vmem),
      "scalars": pl.BlockSpec(
          (1, nb, dv), lambda i, c: (i, at(c), 0), memory_space=vmem),
      "states": lambda dk, dv: pl.BlockSpec(
          (1, nb, dk, dv), lambda i, c: (i, at(c), 0, 0), memory_space=vmem),
  }


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _pallas_forward(qg, kg, w, u, m, a, with_states: bool):
  b, h, n, chunk, dk = qg.shape
  dv = u.shape[-1]
  nb = _block_chunks(n)
  specs = _specs(nb * chunk, nb, dv)
  out_shape = [jax.ShapeDtypeStruct((b * h, n * chunk, dv), u.dtype)]
  out_specs = [specs["rows"](dv)]
  if with_states:
    out_shape.append(jax.ShapeDtypeStruct((b * h, n, dk, dv), u.dtype))
    out_specs.append(specs["states"](dk, dv))
  out = pl.pallas_call(
      functools.partial(_fwd_kernel, chunk=chunk, block_chunks=nb),
      out_shape=out_shape,
      grid=(b * h, n // nb),
      in_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dk),
                specs["rows"](dv), specs["rows"](chunk), specs["scalars"]],
      out_specs=out_specs,
      scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
      compiler_params=_PARAMS,
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[0],
  )(_rows(qg), _rows(kg), _rows(w), _rows(u), _rows(m), _lanes(a, dv))
  o = out[0].reshape(b, h, n, chunk, dv)
  return (o, out[1]) if with_states else o


def _pallas_backward(qg, kg, w, u, m, a, states, do):
  b, h, n, chunk, dk = qg.shape
  dv = u.shape[-1]
  nb = _block_chunks(n)
  specs = _specs(nb * chunk, nb, dv, reverse_of=n // nb)
  like = lambda x: jax.ShapeDtypeStruct(_rows(x).shape, x.dtype)
  dqg, dkg, dw, du, dm, da = pl.pallas_call(
      functools.partial(_bwd_kernel, chunk=chunk, block_chunks=nb),
      out_shape=[like(qg), like(kg), like(w), like(u), like(m),
                 jax.ShapeDtypeStruct((b * h, n, dv), jnp.float32)],
      grid=(b * h, n // nb),
      in_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dk),
                specs["rows"](dv), specs["rows"](chunk), specs["scalars"],
                specs["states"](dk, dv), specs["rows"](dv)],
      out_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dk),
                 specs["rows"](dv), specs["rows"](chunk), specs["scalars"]],
      scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
      compiler_params=_PARAMS,
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[1],
  )(_rows(qg), _rows(kg), _rows(w), _rows(u), _rows(m), _lanes(a, dv), states,
    _rows(do))
  unrow = lambda x, like: x.reshape(like.shape)
  return (unrow(dqg, qg), unrow(dkg, kg), unrow(dw, w), unrow(du, u),
          unrow(dm, m), jnp.sum(da, axis=-1).reshape(a.shape))


@jax.custom_vjp
def _walk_pallas(qg, kg, w, u, m, a):
  return _pallas_forward(qg, kg, w, u, m, a, with_states=False)


def _walk_fwd(qg, kg, w, u, m, a):
  o, states = _pallas_forward(qg, kg, w, u, m, a, with_states=True)
  return o, (qg, kg, w, u, m, a, states)


def _walk_bwd(residuals, do):
  return _pallas_backward(*residuals, do)


_walk_pallas.defvjp(_walk_fwd, _walk_bwd)


def gated_delta_rule(q, k, v, g, beta, implementation: str = "auto"):
  """o_t = S_t^T q_t under the gated delta rule, from a zero state.

  Args:
    q, k: (B, T, Hk, Dk), as the rule takes them (the caller normalizes
      and scales); each head serves Hv / Hk consecutive value heads.
    v: (B, T, Hv, Dv).
    g: (B, T, Hv) log-decays (<= 0); beta: (B, T, Hv) write strengths.
    implementation: "pallas", "xla", or "auto" (pallas on a TPU where
      the shapes allow: head widths multiples of 128, the number of
      chunks under or a multiple of 8). T is a multiple of CHUNK, or
      one shorter chunk.

  Returns:
    (B, T, Hv, Dv) in v's dtype.
  """
  if implementation not in ("auto", "pallas", "xla"):
    raise ValueError(
        f"implementation must be 'auto', 'pallas', or 'xla'; got "
        f"{implementation!r}")
  b, t, hv, dv = v.shape
  chunk = min(CHUNK, t)
  if t % chunk or hv % q.shape[2]:
    raise ValueError(
        f"T must be a multiple of the chunk ({chunk}) and the value heads "
        f"of the q/k heads; got T={t}, heads {q.shape[2]}/{hv}")
  unsupported = _supported(chunk, t // chunk, q.shape[3], dv)
  use_xla = implementation == "xla" or (implementation == "auto" and (
      unsupported is not None or dispatch.use_xla_only()
      or jax.default_backend() != "tpu"))
  if not use_xla and unsupported is not None:
    raise ValueError(f"gated_delta_rule pallas path: {unsupported}")
  prepared = jax.checkpoint(
      _prepare, static_argnums=(5,),
      policy=jax.checkpoint_policies.save_only_these_names(INVERSE_NAME))(
          q, k, v, g, beta, chunk)
  o = (_walk_xla if use_xla else _walk_pallas)(*prepared)
  return o.reshape(b, hv, t, dv).transpose(0, 2, 1, 3)

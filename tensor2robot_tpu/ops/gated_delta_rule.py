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

The walk over chunks is two Pallas programs: heads on a parallel grid
axis, blocks of chunks on an "arbitrary" one with the head's state in
VMEM scratch; the backward walks the chunks in reverse carrying dS,
from the chunk-start states the forward kept ((chunks, Dk, Dv) a head,
in the operands' dtype: as the products take them).

The chunk-local part (the decays, T, u, w, M) is two more, under a
`custom_vjp` whose residuals are its inputs. `gated_delta_rule_prep`
reads q and k at their key head straight from (B, T, Hk·Dk), v, g and
b, and builds each chunk's part in VMEM, writing the rows the walk
reads; `gated_delta_rule_prep_bwd` builds it again, T included, and
from the walk's cotangents writes dq and dk (a key head's summed over
the value heads it serves, in float32 scratch), dv, dg and db. A grid
step holds 8 chunks; their inverses' chains of ten products go level
by level, so that independent products overlap. None of the part's
float32 intermediates reaches HBM.

`implementation="xla"` (and "auto" off a TPU or under
`dispatch.xla_only()`) runs the part as batched XLA over all chunks,
differentiated by autodiff, and the walk as a `lax.scan`: the CPU and
export path and the programs' oracle. Of its part only T is kept for
the backward pass (`jax.checkpoint`); at T = 8,192 and 32 heads its
float32 intermediates are 67-134 MB each.

Precision follows the inputs: products take q, k, v-derived operands
in the dtype they come in (bf16 in a bf16 model) and the state cast to
it, and sum in float32; g, its running sums and exponentials, b, T and
the state are float32 whatever comes in, and the inverse's products
take three bf16 passes on both paths.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.obs.registry import get_registry
from tensor2robot_tpu.ops import dispatch

# The two kernels' names, as the device trace shows them: the forward
# walk (run again where a block is rematerialized) and the backward.
KERNEL_NAMES = ("gated_delta_rule_fwd", "gated_delta_rule_bwd",
                "gated_delta_rule_prep", "gated_delta_rule_prep_bwd")

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
# The inverse's name under the XLA path's `jax.checkpoint`, which keeps
# it and makes the rest of the chunk-local part again.
_INVERSE_NAME = "gated_delta_rule_inverse"


def _neumann(a, eye, matmul):
  """(I + A)^-1 for each strictly lower-triangular A (..., C, C) of the
  list `a`, float32: A is nilpotent, so the Neumann series ends, and its
  C terms factor into log2(C) products: (I - A)(I + A^2)(I + A^4)...
  The list's chains go level by level, so that a kernel's independent
  products can overlap where one chain would wait on each."""
  inverse, power, reach = [eye - x for x in a], list(a), 2
  while reach < a[0].shape[-1]:
    power = [matmul(x, x) for x in power]
    inverse = [matmul(x, eye + y) for x, y in zip(inverse, power)]
    reach *= 2
  return inverse


@jax.custom_vjp
def _unit_lower_inverse(a):
  """`_neumann` over a batch; the backward is the inverse's own, dA =
  -T^T dT T^T, so that only T is kept."""
  return _neumann([a], jnp.eye(a.shape[-1], dtype=a.dtype), _matmul)[0]


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
  t_inv = checkpoint_name(_unit_lower_inverse(a), _INVERSE_NAME).astype(dtype)
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


# Jitted, as the prep programs are below.
@functools.partial(jax.jit, static_argnames=("with_states",))
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


@jax.jit
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


# --- the chunk-local part in VMEM --------------------------------------------


def _dot_high(a, b, dims=(((1,), (0,)), ((), ()))):
  """A product of float32 operands in three bf16 passes, each operand a
  bf16 part and its bf16 remainder, the remainders' product dropped:
  `_matmul`'s `Precision.HIGH`, which Mosaic does not take by name."""
  high = lambda x: x.astype(jnp.bfloat16)
  low = lambda x: (x - high(x).astype(jnp.float32)).astype(jnp.bfloat16)
  return _dot(high(a), high(b), dims) + (
      _dot(high(a), low(b), dims) + _dot(low(a), high(b), dims))


def _turn(x, eye):
  """A (1, C) row to a (C, 1) column, or back: a sum with one term that
  is not nought, so exactly."""
  return jnp.sum(jnp.where(eye, x, 0.0), axis=1 if x.shape[0] == 1 else 0,
                 keepdims=True)


def _local(q, k, v, g, beta):
  """One chunk's part as `_prepare` makes it, but T, from (C, D) rows of
  q, k, v in their dtype and (1, C) rows of g and b, float32. G is made
  as a column and turned (`_turn`), so that G_i - G_i is nought on the
  diagonal."""
  f32, c = jnp.float32, q.shape[0]
  row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
  eye = row == col
  gamma = jnp.sum(jnp.where(col <= row, g, 0.0), axis=1, keepdims=True)
  gamma_row = _turn(gamma, eye)
  last = jnp.sum(jnp.where(col[:1] == c - 1, gamma_row, 0.0), axis=1,
                 keepdims=True)
  beta = _turn(beta, eye)
  # Masked before the exponential, as in `_prepare`.
  decay = jnp.exp(jnp.where(row >= col, gamma - gamma_row, -jnp.inf))
  k_beta = k.astype(f32) * beta
  k_beta_in = k_beta.astype(v.dtype)
  product = _dot(k_beta_in, k, _NT)
  growth = jnp.exp(gamma)
  return dict(
      row=row, col=col, eye=eye, beta=beta, decay=decay, k_beta=k_beta,
      k_beta_in=k_beta_in, product=product, growth=growth,
      a=jnp.where(row > col, product * decay, 0.0),
      v_beta=(v.astype(f32) * beta).astype(v.dtype),
      k_grown=(k_beta * growth).astype(v.dtype),
      to_end=jnp.exp(last - gamma), whole=jnp.exp(last))


def _block_local(q_ref, k_ref, v_ref, g_ref, beta_ref, chunk: int,
                 block_chunks: int):
  """[(rows, q, k, v, `_local`'s values and T)] of the block's chunks;
  their inverses' chains in step, so that their products overlap."""
  chunks = []
  for j in range(block_chunks):
    rows = slice(j * chunk, (j + 1) * chunk)
    q, k, v = q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :]
    chunks.append((rows, q, k, v, _local(q, k, v, g_ref[0, j:j + 1, :],
                                         beta_ref[0, j:j + 1, :])))
  eye = chunks[0][4]["eye"].astype(jnp.float32)
  inverses = _neumann([x["a"] for *_, x in chunks], eye, _dot_high)
  for (*_, x), inverse in zip(chunks, inverses):
    x["inverse"] = inverse
  return chunks


def _prep_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                     qg_ref, kg_ref, w_ref, u_ref, m_ref, a_ref,
                     *, chunk: int, block_chunks: int):
  """One value head's next `block_chunks` chunks: qg, kg, w, u, M and
  the chunks' decay, as `_prepare` makes them."""
  dtype = v_ref.dtype
  for j, (rows, q, k, _, x) in enumerate(_block_local(
      q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, block_chunks)):
    inverse = x["inverse"].astype(dtype)
    u_ref[0, rows, :] = _dot(inverse, x["v_beta"]).astype(dtype)
    w_ref[0, rows, :] = _dot(inverse, x["k_grown"]).astype(dtype)
    m_ref[0, rows, :] = (_dot(q, k, _NT) * x["decay"]).astype(dtype)
    qg_ref[0, rows, :] = (q.astype(jnp.float32) * x["growth"]).astype(dtype)
    kg_ref[0, rows, :] = (k.astype(jnp.float32) * x["to_end"]).astype(dtype)
    a_ref[0, j:j + 1, :] = jnp.broadcast_to(x["whole"], (1, a_ref.shape[2]))


def _prep_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, dqg_ref, dkg_ref,
                     dw_ref, du_ref, dm_ref, da_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                     dq_acc, dk_acc, *, chunk: int, block_chunks: int):
  """The chunk-local part's backward, T made again from the inputs. A
  key head's dq and dk are summed in float32 scratch over the value
  heads it serves (the grid's last axis) and written once.
    u = T (b v),  w = T (b k e^G):  dT = du (b v)^T + dw (b k e^G)^T
    T = (I + A)^-1:  dA = -T^T dT T^T, strictly lower
    A = P . D, P = (b k) k^T;  M = Q . D, Q = q k^T
    qg = q e^G,  kg = k e^(G_C - G),  a = e^(G_C)
    D_ij = e^(G_i - G_j):  dG_i += sum_j dD_ij D_ij,  dG_j -= the same
    G = running sum of g:  dg_l = sum over i >= l of dG_i"""
  f32, dtype = jnp.float32, v_ref.dtype
  member = pl.program_id(2)

  @pl.when(member == 0)
  def _():
    dq_acc[...] = jnp.zeros_like(dq_acc)
    dk_acc[...] = jnp.zeros_like(dk_acc)

  chunks = _block_local(q_ref, k_ref, v_ref, g_ref, beta_ref, chunk,
                        block_chunks)
  # The inverse's backward in step over the chunks, as its forward.
  d_inverse = [_dot(du_ref[0, rows, :], x["v_beta"], _NT)
               + _dot(dw_ref[0, rows, :], x["k_grown"], _NT)
               for rows, *_, x in chunks]
  d_inverse = [_dot_high(x["inverse"], d, _TN)
               for (*_, x), d in zip(chunks, d_inverse)]
  d_a = [jnp.where(x["row"] > x["col"], -_dot_high(d, x["inverse"], _NT), 0.0)
         for (*_, x), d in zip(chunks, d_inverse)]
  for j, ((rows, q, k, v, x), da) in enumerate(zip(chunks, d_a)):
    row, col, eye = x["row"], x["col"], x["eye"]
    dqg, dkg = (r[0, rows, :].astype(f32) for r in (dqg_ref, dkg_ref))
    du, dw = du_ref[0, rows, :], dw_ref[0, rows, :]
    dm = dm_ref[0, rows, :].astype(f32)
    inverse_in = x["inverse"].astype(dtype)
    dv_beta = _dot(inverse_in, du, _TN)
    dk_grown = _dot(inverse_in, dw, _TN)
    scores = _dot(q, k, _NT)
    d_decay = da * x["product"] + dm * scores
    d_product = (da * x["decay"]).astype(dtype)
    d_scores = (dm * x["decay"]).astype(dtype)
    growth, to_end, beta = x["growth"], x["to_end"], x["beta"]
    qf, kf = q.astype(f32), k.astype(f32)
    dk_beta = _dot(d_product, k) + dk_grown * growth
    dq_acc[rows, :] += _dot(d_scores, k) + dqg * growth
    dk_acc[rows, :] += (_dot(d_product, x["k_beta_in"], _TN)
                        + _dot(d_scores, q, _TN) + dkg * to_end
                        + dk_beta * beta)
    dv_ref[0, rows, :] = (dv_beta * beta).astype(dv_ref.dtype)
    d_beta = (jnp.sum(dv_beta * v.astype(f32), axis=1, keepdims=True)
              + jnp.sum(dk_beta * kf, axis=1, keepdims=True))
    dbeta_ref[0, j:j + 1, :] = _turn(d_beta, eye)
    d_exp = d_decay * x["decay"]
    ends = jnp.sum(dkg * kf * to_end, axis=1, keepdims=True)
    d_gamma = (jnp.sum(dqg * qf * growth, axis=1, keepdims=True)
               + jnp.sum(dk_grown * x["k_beta"] * growth, axis=1,
                         keepdims=True)
               - ends + jnp.sum(d_exp, axis=1, keepdims=True)
               - _turn(jnp.sum(d_exp, axis=0, keepdims=True), eye))
    d_last = (jnp.sum(ends, axis=0, keepdims=True)
              + da_ref[0, j:j + 1, :1] * x["whole"])
    dg_ref[0, j:j + 1, :] = d_last + jnp.sum(
        jnp.where(row >= col, d_gamma, 0.0), axis=0, keepdims=True)

  @pl.when(member == pl.num_programs(2) - 1)
  def _():
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
    dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)


def _prep_grid(q, k, v, g, beta):
  """What both prep programs share, for a (B·Hk key heads, blocks of
  chunks, the value heads each serves) grid: its shapes, BlockSpecs by
  kind (q and k read at the key head straight from (B, T, Hk·Dk), v at
  the value head from (B, T, Hv·Dv); g, b and the part's rows at the
  value head's row of (B·Hv, ., .)), and the five inputs so laid out
  with their specs."""
  b, t, hk, dk = q.shape
  hv, dv = v.shape[2:]
  group, chunk = hv // hk, min(CHUNK, t)
  n = t // chunk
  nb = _block_chunks(n)
  spec = lambda shape, index: pl.BlockSpec(shape, index,
                                           memory_space=pltpu.VMEM)
  specs = {
      "key": spec((1, nb * chunk, dk), lambda h, c, j: (h // hk, c, h % hk)),
      "value": spec((1, nb * chunk, dv),
                    lambda h, c, j: (h // hk, c, h % hk * group + j)),
      "rows": lambda d: spec((1, nb * chunk, d),
                             lambda h, c, j: (h * group + j, c, 0)),
      "per_chunk": lambda d: spec((1, nb, d),
                                  lambda h, c, j: (h * group + j, c, 0)),
  }
  # (B, T, Hv) -> (B·Hv, N, C) float32: a chunk's gates along lanes.
  gates = lambda x: x.astype(jnp.float32).transpose(0, 2, 1).reshape(
      b * hv, n, chunk)
  inputs = (q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
            v.reshape(b, t, hv * dv), gates(g), gates(beta))
  input_specs = [specs["key"], specs["key"], specs["value"],
                 specs["per_chunk"](chunk), specs["per_chunk"](chunk)]
  grid = dict(b=b, t=t, hv=hv, dk=dk, dv=dv, chunk=chunk, n=n, nb=nb,
              shape=(b * hk, n // nb, group))
  return grid, specs, inputs, input_specs


# Jitted, so that the unrolled kernels are traced and lowered about once
# a shape, not once for each call in a step (a first run, a rerun, three
# layers): 1 s of a step's 5 s of lowering here at the hybrid cell's.
@jax.jit
def _prep_forward(q, k, v, g, beta):
  """`_prepare`'s outputs, in the same shapes, from one Pallas program."""
  grid, specs, inputs, input_specs = _prep_grid(q, k, v, g, beta)
  b, hv, n, chunk = grid["b"], grid["hv"], grid["n"], grid["chunk"]
  dk, dv = grid["dk"], grid["dv"]
  rows = lambda d: jax.ShapeDtypeStruct((b * hv, grid["t"], d), v.dtype)
  qg, kg, w, u, m, a = pl.pallas_call(
      functools.partial(_prep_fwd_kernel, chunk=chunk,
                        block_chunks=grid["nb"]),
      out_shape=[rows(dk), rows(dk), rows(dk), rows(dv), rows(chunk),
                 jax.ShapeDtypeStruct((b * hv, n, _LANES), jnp.float32)],
      grid=grid["shape"],
      in_specs=input_specs,
      out_specs=[specs["rows"](dk), specs["rows"](dk), specs["rows"](dk),
                 specs["rows"](dv), specs["rows"](chunk),
                 specs["per_chunk"](_LANES)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "parallel")),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[2],
  )(*inputs)
  chunks = lambda x: x.reshape(b, hv, n, chunk, x.shape[-1])
  return (chunks(qg), chunks(kg), chunks(w), chunks(u), chunks(m),
          a[..., 0].reshape(b, hv, n))


@jax.jit
def _prep_backward(q, k, v, g, beta, cotangents):
  grid, specs, inputs, input_specs = _prep_grid(q, k, v, g, beta)
  chunk, dk, dv = grid["chunk"], grid["dk"], grid["dv"]
  dqg, dkg, dw, du, dm, da = cotangents
  like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
  dq, dk, dv, dg, dbeta = pl.pallas_call(
      functools.partial(_prep_bwd_kernel, chunk=chunk,
                        block_chunks=grid["nb"]),
      out_shape=[like(x) for x in inputs],
      grid=grid["shape"],
      in_specs=input_specs + [
          specs["rows"](dk), specs["rows"](dk), specs["rows"](dk),
          specs["rows"](dv), specs["rows"](chunk), specs["per_chunk"](_LANES)],
      out_specs=input_specs,
      scratch_shapes=[pltpu.VMEM((grid["nb"] * chunk, dk), jnp.float32)] * 2,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[3],
  )(*inputs, _rows(dqg), _rows(dkg), _rows(dw), _rows(du), _rows(dm),
    _lanes(da, _LANES))
  gate = lambda x, like: x.reshape(like.shape[0], like.shape[2], -1).transpose(
      0, 2, 1).astype(like.dtype)
  return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
          gate(dg, g), gate(dbeta, beta))


@jax.custom_vjp
def _prep_pallas(q, k, v, g, beta):
  return _prep_forward(q, k, v, g, beta)


def _prep_fwd(q, k, v, g, beta):
  return _prep_forward(q, k, v, g, beta), (q, k, v, g, beta)


def _prep_bwd(inputs, cotangents):
  return _prep_backward(*inputs, cotangents)


_prep_pallas.defvjp(_prep_fwd, _prep_bwd)


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
  # Counted as the call is traced: which path the chunk-local part took.
  if use_xla:
    get_registry().counter("gated_delta_rule/prep_xla").inc()
    o = _walk_xla(*jax.checkpoint(
        _prepare, static_argnums=(5,),
        policy=jax.checkpoint_policies.save_only_these_names(_INVERSE_NAME))(
            q, k, v, g, beta, chunk))
  else:
    get_registry().counter("gated_delta_rule/prep_programs").inc()
    o = _walk_pallas(*_prep_pallas(q, k, v, g, beta))
  return o.reshape(b, hv, t, dv).transpose(0, 2, 1, 3)

"""Expert parallelism: one drop-free mixture-of-experts layer that is
told which experts it holds.

Every holder routes over ALL the layer's experts (`route`: sigmoid
scores, the top k of score + correction bias, weights the scores of the
chosen k over their sum, DeepSeek-V3's `noaux_tc`; or a softmax over all
the experts, its top k renormalized, no bias), and computes the part of the
result its own experts give: tokens are sorted by expert, the held
experts' gated MLPs run as grouped matrix products over the sorted rows
(`jax.lax.ragged_dot`: on TPU a grouped kernel that visits only the
tiles the groups fill), and the rows go back weighted. No capacity, so
no assignment is ever dropped, whatever the imbalance.

Two entry points over the same routing and grouping:

  `moe_share`            one holder, no exchange: the sum over its held
                         experts only. What the absent experts would add
                         is left out (a model cut to one chip's share of
                         a wider expert group trains on this). The held
                         rows sort first, and every pass over rows, the
                         gather, the products and the weighted sum into
                         the tokens, forward and backward, steps through
                         chunks of them up to the chunk that holds the
                         last held row: the passes cover the held
                         assignments, not all N·k (`visited_rows`); only
                         the sorted buffers the weights' gradients read
                         are N·k rows long, zero-filled once.
  `expert_parallel_moe`  experts sharded over an `expert` mesh axis,
                         tokens sharded over the same axis, a pair of
                         `all_to_all`s carrying each row to its
                         expert's holder and back: the whole layer.

Summed over all shares, `moe_share` equals `expert_parallel_moe`.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec


class MoEParams(NamedTuple):
  """Router over all E experts + the H held experts' stacked gated MLPs.

  router: (D, E). bias: (E,), the score-correction bias: it moves the
  choice and not the weights, and the loss gives it no gradient; None
  under softmax scoring, which has none.
  gate/up: (H, D, F). down: (H, F, D): the leading axis is what the
  `expert` mesh axis shards (then H = E).
  """
  router: jnp.ndarray
  bias: jnp.ndarray
  gate: jnp.ndarray
  up: jnp.ndarray
  down: jnp.ndarray


def init_moe_params(rng: jax.Array, num_experts: int, d_model: int,
                    d_hidden: int, experts_held: Optional[int] = None,
                    dtype=jnp.float32) -> MoEParams:
  held = num_experts if experts_held is None else experts_held
  k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
  normal = lambda k, shape, fan_in: (
      jax.random.normal(k, shape, dtype) / jnp.sqrt(fan_in).astype(dtype))
  return MoEParams(
      router=normal(k1, (d_model, num_experts), d_model),
      bias=0.1 * jax.random.normal(k2, (num_experts,), dtype),
      gate=normal(k3, (held, d_model, d_hidden), d_model),
      up=normal(k4, (held, d_model, d_hidden), d_model),
      down=normal(k5, (held, d_hidden, d_model), d_hidden),
  )


SCORINGS = ("sigmoid", "softmax")


def route(tokens: jnp.ndarray, router: jnp.ndarray,
          bias: Optional[jnp.ndarray], top_k: int, scale: float = 1.0,
          scoring: str = "sigmoid", norm_eps: float = 1e-20
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """(N, D) tokens → ((N, k) expert ids, (N, k) float32 weights).

  `scoring` "sigmoid": each expert's own sigmoid, the choice by score +
  `bias`; "softmax": a softmax over all E experts, the choice by score,
  `bias` not read. Either way the weights are the chosen k scores over
  their sum (under sigmoid scoring the sum plus `norm_eps`: LFM2's 1e-6,
  DeepSeek-V3's 1e-20), times `scale`. Float32 throughout, the product
  at full precision: a choice that flipped on rounding would move a
  token's whole result."""
  if scoring not in SCORINGS:
    raise ValueError(f"scoring must be one of {SCORINGS}; got {scoring!r}")
  logits = jnp.dot(
      tokens.astype(jnp.float32), router.astype(jnp.float32),
      precision=jax.lax.Precision.HIGHEST)                     # (N, E)
  if scoring == "softmax":
    chosen, index = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return index, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
  scores = jax.nn.sigmoid(logits)
  _, index = jax.lax.top_k(
      scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
  chosen = jnp.take_along_axis(scores, index, axis=-1)
  weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + norm_eps)
  return index, weight * scale


def _sort_by_expert(expert_of_row: jnp.ndarray, num_held: int):
  """Order that groups rows by held expert (rows of no held expert,
  marked `num_held`, last) and the groups' sizes."""
  order = jnp.argsort(expert_of_row, stable=True)
  sizes = jnp.bincount(expert_of_row, length=num_held + 1)[:num_held]
  return order, sizes.astype(jnp.int32)


def _grouped_mlp(rows: jnp.ndarray, sizes: jnp.ndarray, params: MoEParams,
                 compute_dtype) -> jnp.ndarray:
  """Gated MLP of expert g over its `sizes[g]` consecutive rows; rows
  past the last group come out zero."""
  # Every product leaves in the compute dtype, as a dense layer's does
  # (float32 accumulation inside): the rows are N·k by D, and a float32
  # product would hand float32 cotangents of that size back.
  dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                          preferred_element_type=compute_dtype)
  cast = lambda w: w.astype(compute_dtype)
  # The grouped kernel leaves the rows past the last group unwritten
  # (on TPU: whatever the buffer held), forward and backward alike. The
  # mask on the way out zeroes the result there; the same mask on the
  # way in is a no-op forward and zeroes those rows' cotangent, which
  # would otherwise be summed into their tokens' gradients.
  in_a_group = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
  mask = lambda x: jnp.where(in_a_group, x, jnp.zeros((), x.dtype))
  rows = mask(rows.astype(compute_dtype))
  with jax.named_scope("moe/experts"):
    gate = dot(rows, cast(params.gate)).astype(jnp.float32)
    up = dot(rows, cast(params.up)).astype(jnp.float32)
    out = dot((jax.nn.silu(gate) * up).astype(compute_dtype),
              cast(params.down))
    return mask(out)


# A chunk is a whole number of the grouped kernel's row tiles (512 rows
# on TPU), and the chunks of a layer number about CHUNKS when every row
# is held.
ROW_TILE = 512
CHUNKS = 32


def chunk_rows(num_rows: int) -> int:
  """Rows of one chunk of the sorted rows, from their number N·k: all of
  them when they are fewer than a tile."""
  if num_rows <= ROW_TILE:
    return num_rows
  return ROW_TILE * max(1, round(num_rows / (CHUNKS * ROW_TILE)))


def _per_group(lhs, rhs, sizes, out_dtype):
  """Σ over each group's rows of lhsᵀ rhs: (G, lhs width, rhs width),
  the weights' gradient. The grouped kernel visits only the row tiles
  its groups fill."""
  numbers = jax.lax.RaggedDotDimensionNumbers(
      dot_dimension_numbers=(((0,), (0,)), ((), ())),
      lhs_ragged_dimensions=(0,), rhs_group_dimensions=())
  return jax.lax.ragged_dot_general(lhs, rhs, sizes, numbers,
                                    preferred_element_type=out_dtype)


class _Chunk(NamedTuple):
  """Chunk i of the sorted rows: where it starts, each row's token, its
  weight and whether it is held (the held rows come first), and the
  groups' sizes inside the chunk."""
  start: jnp.ndarray
  token: jnp.ndarray
  weight: jnp.ndarray
  held: jnp.ndarray
  sizes: jnp.ndarray

  @classmethod
  def at(cls, i, chunk, top_k, order, row_weight, sizes):
    start = i * chunk
    held = start + jnp.arange(chunk) < jnp.sum(sizes)
    ends = jnp.clip(jnp.cumsum(sizes) - start, 0, chunk)
    return cls(
        start=start,
        token=jax.lax.dynamic_slice_in_dim(order, start, chunk) // top_k,
        weight=jax.lax.dynamic_slice_in_dim(row_weight, start, chunk),
        held=held[:, None],
        sizes=jnp.diff(ends, prepend=0))

  def dot(self, rows, weights, out_dtype):
    """The grouped product of the chunk's rows, 0 past the held rows:
    the kernel leaves the rows past the last group unwritten (on TPU,
    whatever the buffer held), and the select drops them."""
    return jnp.where(self.held, jax.lax.ragged_dot(
        rows, weights, self.sizes, preferred_element_type=out_dtype), 0)

  def gated(self, tokens, gate_up):
    """The chunk's token rows and their gate and up products (float32)."""
    with jax.named_scope("moe/dispatch"):
      x = tokens[self.token].astype(gate_up.dtype)
    with jax.named_scope("moe/experts"):
      g, u = jnp.split(self.dot(x, gate_up, gate_up.dtype).astype(
          jnp.float32), 2, axis=-1)
    return x, g, u


def _visited_chunks(sizes, chunk):
  return (jnp.sum(sizes) + chunk - 1) // chunk


def _padded(x, chunk):
  """`x` made a whole number of chunks long (the pad is never held)."""
  return jnp.pad(x, (0, -x.shape[0] % chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rows(chunk, top_k, tokens, row_weight, gate, up, down, order,
               sizes):
  """Σ over the held sorted rows r of row_weight[r] · E_g(r)(tokens[
  order[r] // top_k]) into each row's token, summed in float32, in the
  tokens' dtype; gate, up (H, D, F) and down (H, F, D) in the compute
  dtype. Forward and backward step through the first ceil(held / chunk)
  chunks of the sorted rows only, and nothing of the rows is kept from
  the one for the other."""
  return _held_rows_fwd(chunk, top_k, tokens, row_weight, gate, up, down,
                        order, sizes)[0]


def _held_rows_fwd(chunk, top_k, tokens, row_weight, gate, up, down, order,
                   sizes):
  gate_up = jnp.concatenate([gate, up], axis=-1)
  padded = _padded(order, chunk), _padded(row_weight, chunk)

  def body(i, y):
    c = _Chunk.at(i, chunk, top_k, *padded, sizes)
    _, g, u = c.gated(tokens, gate_up)
    with jax.named_scope("moe/experts"):
      out = c.dot((jax.nn.silu(g) * u).astype(down.dtype), down, down.dtype)
    with jax.named_scope("moe/combine"):
      return y.at[c.token].add(out.astype(jnp.float32) * c.weight[:, None])

  y = jax.lax.fori_loop(0, _visited_chunks(sizes, chunk), body,
                        jnp.zeros(tokens.shape, jnp.float32))
  return y.astype(tokens.dtype), (tokens, row_weight, gate, up, down, order,
                                  sizes)


def _held_rows_bwd(chunk, top_k, residuals, dy):
  tokens, row_weight, gate, up, down, order, sizes = residuals
  gate_up = jnp.concatenate([gate, up], axis=-1)
  compute_dtype = down.dtype
  padded = _padded(order, chunk), _padded(row_weight, chunk)
  rows, (_, width, d) = padded[0].shape[0], down.shape
  visited = _visited_chunks(sizes, chunk)
  at = lambda i: _Chunk.at(i, chunk, top_k, *padded, sizes)
  # What the weights' gradients read, by sorted row, filled chunk by
  # chunk; the kernel visits no tile past the last visited chunk. (Left
  # uninitialized, `lax.empty`, the compiler placed these buffers apart
  # from every other temporary: a whole training step of four such
  # layers on a v5e used 0.3-2.1 GB more by XLA's memory report.)
  empty = lambda cols: jnp.zeros((rows, cols), compute_dtype)
  put = lambda buffer, c, value: jax.lax.dynamic_update_slice_in_dim(
      buffer, value, c.start, 0)
  # Two walks, so that fewer rows are held at once: the first makes the
  # rows' cotangents and down's gradient, the second the tokens' and
  # [gate | up]'s.
  down_t = jnp.swapaxes(down, 1, 2)

  def cotangents(i, carry):
    dw, hs, douts, dgus = carry
    c = at(i)
    _, g, u = c.gated(tokens, gate_up)
    with jax.named_scope("moe/combine"):
      dy_rows = dy[c.token].astype(jnp.float32)
      dout = jnp.where(c.held, dy_rows * c.weight[:, None], 0).astype(
          compute_dtype)
    with jax.named_scope("moe/experts"):
      sig = jax.nn.sigmoid(g)
      h = (g * sig * u).astype(compute_dtype)
      # dy · downᵀ, unweighted: its product with h is the row weight's
      # cotangent, its weighted form h's.
      dq = c.dot(dy_rows.astype(compute_dtype), down_t, jnp.float32)
      dh = dq * c.weight[:, None]
      dgu = jnp.concatenate(
          [dh * u * sig * (1 + g * (1 - sig)), dh * g * sig],
          axis=-1).astype(compute_dtype)
    with jax.named_scope("moe/combine"):
      dw_rows = jnp.sum(h.astype(jnp.float32) * dq, axis=-1)
    return (put(dw, c, dw_rows), put(hs, c, h), put(douts, c, dout),
            put(dgus, c, dgu))

  dw, hs, douts, dgus = jax.lax.fori_loop(
      0, visited, cotangents,
      (jnp.zeros((rows,), jnp.float32), empty(width), empty(d),
       empty(2 * width)))
  with jax.named_scope("moe/experts"):
    ddown = _per_group(hs, douts, sizes, compute_dtype)
  gate_up_t = jnp.swapaxes(gate_up, 1, 2)

  def to_tokens(i, carry):
    dx, xs = carry
    c = at(i)
    with jax.named_scope("moe/dispatch"):
      x = tokens[c.token].astype(compute_dtype)
    with jax.named_scope("moe/experts"):
      dx_rows = c.dot(jax.lax.dynamic_slice_in_dim(dgus, c.start, chunk),
                      gate_up_t, jnp.float32)
    with jax.named_scope("moe/dispatch"):
      return dx.at[c.token].add(dx_rows), put(xs, c, x)

  dx, xs = jax.lax.fori_loop(
      0, visited, to_tokens,
      (jnp.zeros(tokens.shape, jnp.float32), empty(d)))
  with jax.named_scope("moe/experts"):
    dgate_up = _per_group(xs, dgus, sizes, compute_dtype)
  return (dx.astype(tokens.dtype), dw[:row_weight.shape[0]],
          dgate_up[..., :width], dgate_up[..., width:], ddown, None, None)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def _counters(sizes, held, top_k: int, chunk: int
              ) -> Dict[str, jnp.ndarray]:
  return {
      "expert_tokens": sizes,
      "held_assignments": jnp.sum(held.astype(jnp.int32)),
      "total_assignments": jnp.asarray(held.shape[0] * top_k, jnp.int32),
      "visited_rows": _visited_chunks(sizes, chunk) * chunk,
  }


def moe_share(tokens: jnp.ndarray, params: MoEParams, first_expert: int,
              top_k: int, scale: float = 1.0, compute_dtype=None,
              scoring: str = "sigmoid", norm_eps: float = 1e-20):
  """One holder's part of the layer: Σ over top-k ∩ held of w_i E_i(x).

  Args:
    tokens: (N, D).
    params: router over all E experts, H held experts' weights; the
      held experts are `first_expert .. first_expert + H - 1`.
    top_k, scale, scoring, norm_eps: experts per token; factor on the
      normalized weights; how `route` scores and what it adds to the
      chosen scores' sum.
    compute_dtype: dtype of the expert products' operands (float32
      accumulation); default the tokens'.

  Returns:
    ((N, D) in the tokens' dtype, counters: `expert_tokens` (H,) rows
    each held expert saw, `held_assignments`, `total_assignments`,
    `visited_rows`: the sorted rows the chunked passes stepped through).
  """
  n, _ = tokens.shape
  num_held = params.gate.shape[0]
  compute_dtype = compute_dtype or tokens.dtype
  chunk = chunk_rows(n * top_k)
  with jax.named_scope("moe/route"):
    index, weight = route(tokens, params.router, params.bias, top_k, scale,
                          scoring, norm_eps)
    local = index - first_expert
    held = (local >= 0) & (local < num_held)                   # (N, k)
  with jax.named_scope("moe/dispatch"):
    order, sizes = _sort_by_expert(
        jnp.where(held, local, num_held).reshape(-1), num_held)
    row_weight = weight.reshape(-1)[order]
  cast = lambda w: w.astype(compute_dtype)
  y = _held_rows(chunk, top_k, tokens, row_weight, cast(params.gate),
                 cast(params.up), cast(params.down), order, sizes)
  return y, _counters(sizes, held, top_k, chunk)


def _ep_local(tokens, params: MoEParams, *, axis_name: str, top_k: int,
              scale: float, num_devices: int):
  """Per-device body: tokens (N_local, D); expert weights (E/P, ...)."""
  n, d = tokens.shape
  num_held = params.gate.shape[0]
  index, weight = route(tokens, params.router, params.bias, top_k, scale)
  holder = (index // num_held).reshape(-1)                    # (N·k,)
  local = (index % num_held).reshape(-1)
  # One queue per holder, long enough for every row this device could
  # send it: nothing is dropped.
  queue = n * min(top_k, num_held)
  order = jnp.argsort(holder, stable=True)
  inverse = jnp.argsort(order)
  per_holder = jnp.bincount(holder, length=num_devices)
  to = holder[order]
  slot = jnp.arange(n * top_k) - (jnp.cumsum(per_holder) - per_holder)[to]
  send = jnp.zeros((num_devices, queue, d), tokens.dtype).at[to, slot].set(
      tokens[order // top_k])
  send_expert = jnp.full((num_devices, queue), num_held, jnp.int32).at[
      to, slot].set(local[order])
  # Mesh transpose: queue p goes to holder p, which receives one queue
  # from every device.
  exchange = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                               split_axis=0, concat_axis=0)
  got = exchange(send).reshape(num_devices * queue, d)
  got_expert = exchange(send_expert).reshape(-1)
  by_expert, sizes = _sort_by_expert(got_expert, num_held)
  back = jnp.zeros_like(by_expert).at[by_expert].set(
      jnp.arange(by_expert.shape[0], dtype=by_expert.dtype))
  out = _grouped_mlp(got[by_expert], sizes, params, tokens.dtype)[back]
  # Inverse transpose: results return to the rows' source device.
  out = exchange(out.reshape(num_devices, queue, d))
  out = out[to, slot][inverse].reshape(n, top_k, d)
  y = jnp.einsum("nkd,nk->nd", out.astype(jnp.float32), weight)
  # Every assignment has its holder on the mesh: all are held.
  total = jax.lax.psum(jnp.asarray(n * top_k, jnp.int32), axis_name)
  counters = {"expert_tokens": sizes, "held_assignments": total,
              "total_assignments": total}
  return y.astype(tokens.dtype), counters


def expert_parallel_moe(
    tokens: jnp.ndarray,
    params: MoEParams,
    mesh: Mesh,
    axis: str = "expert",
    top_k: int = 1,
    scale: float = 1.0,
):
  """The whole layer with its experts sharded over the `axis` mesh axis.

  Args:
    tokens: (N, D); N must divide evenly over the axis (tokens are
      data-sharded over the same axis the experts live on: each device
      routes its token shard to all expert shards via all_to_all).
    params: MoEParams holding ALL E experts; the leading expert axis
      must divide evenly over the axis and is sharded one group per
      device.
    mesh: device mesh containing `axis`.
    top_k, scale: as `moe_share`.

  Returns:
    ((N, D) output, counters as `moe_share`'s, `expert_tokens` (E,)
    over all experts): equal to `moe_share` summed over the shares.
  """
  num_devices = mesh.shape[axis]
  n, _ = tokens.shape
  num_experts = params.router.shape[-1]
  if n % num_devices != 0:
    raise ValueError(f"Token count {n} not divisible by {axis!r} axis "
                     f"size {num_devices}.")
  if (num_experts % num_devices != 0
      or params.gate.shape[0] != num_experts):
    raise ValueError(f"Expert count {params.gate.shape[0]} of "
                     f"{num_experts} routed not divisible by {axis!r} "
                     f"axis size {num_devices}.")
  token_spec = PartitionSpec(axis)
  param_specs = MoEParams(
      router=PartitionSpec(), bias=PartitionSpec(),  # every device routes
      gate=PartitionSpec(axis), up=PartitionSpec(axis),
      down=PartitionSpec(axis))
  counter_specs = {"expert_tokens": PartitionSpec(axis),
                   "held_assignments": PartitionSpec(),
                   "total_assignments": PartitionSpec()}
  fn = shard_map(
      functools.partial(_ep_local, axis_name=axis, top_k=top_k,
                        scale=scale, num_devices=num_devices),
      mesh=mesh,
      in_specs=(token_spec, param_specs),
      out_specs=(token_spec, counter_specs),
  )
  return fn(tokens, params)

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
no assignment is ever dropped, whatever the imbalance: the buffers are
sized for the worst case and the products for what arrived.

Two entry points over the same routing and grouping:

  `moe_share`            one holder, no exchange: the sum over its held
                         experts only. What the absent experts would add
                         is left out (a model cut to one chip's share of
                         a wider expert group trains on this).
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, order, inverse, repeats):
  """x[order // repeats]: row i of x `repeats` times, then permuted by
  `order`. Backward is a gather by `inverse` and a sum, not a scatter."""
  del inverse
  return x[order // repeats]


def _take_rows_fwd(x, order, inverse, repeats):
  return x[order // repeats], (inverse, x.shape[0])


def _take_rows_bwd(repeats, residuals, g):
  inverse, n = residuals
  return g[inverse].reshape(n, repeats, -1).sum(axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _sort_by_expert(expert_of_row: jnp.ndarray, num_held: int):
  """Order that groups rows by held expert (rows of no held expert,
  marked `num_held`, last), its inverse and the groups' sizes."""
  order = jnp.argsort(expert_of_row, stable=True)
  inverse = jnp.argsort(order)
  sizes = jnp.bincount(expert_of_row, length=num_held + 1)[:num_held]
  return order, inverse, sizes.astype(jnp.int32)


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


def _counters(sizes, held, top_k: int) -> Dict[str, jnp.ndarray]:
  return {
      "expert_tokens": sizes,
      "held_assignments": jnp.sum(held.astype(jnp.int32)),
      "total_assignments": jnp.asarray(held.shape[0] * top_k, jnp.int32),
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
    each held expert saw, `held_assignments`, `total_assignments`).
  """
  n, _ = tokens.shape
  num_held = params.gate.shape[0]
  compute_dtype = compute_dtype or tokens.dtype
  with jax.named_scope("moe/route"):
    index, weight = route(tokens, params.router, params.bias, top_k, scale,
                          scoring, norm_eps)
    local = index - first_expert
    held = (local >= 0) & (local < num_held)                   # (N, k)
  with jax.named_scope("moe/dispatch"):
    order, inverse, sizes = _sort_by_expert(
        jnp.where(held, local, num_held).reshape(-1), num_held)
    rows = _take_rows(tokens, order, inverse, top_k)           # (N·k, D)
  out = _grouped_mlp(rows, sizes, params, compute_dtype)
  with jax.named_scope("moe/combine"):
    out = _take_rows(out, inverse, order, 1).reshape(n, top_k, -1)
    y = jnp.einsum("nkd,nk->nd", out.astype(jnp.float32),
                   jnp.where(held, weight, 0.0))
  return y.astype(tokens.dtype), _counters(sizes, held, top_k)


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
  by_expert, back, sizes = _sort_by_expert(got_expert, num_held)
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

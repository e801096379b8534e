"""Expert parallelism: Switch-style mixture-of-experts with all-to-all
token dispatch over an `expert` mesh axis.

Beyond the reference (pure data parallelism — SURVEY.md §2 "Parallelism
strategies"): the fifth axis of the dp/tp/sp/pp/ep family. Experts are
feed-forward blocks whose weights are sharded one-group-per-device over
the `expert` mesh axis; tokens are routed top-1 (Switch) with a capacity
limit, exchanged device↔expert with a pair of `all_to_all`s (the
canonical MoE mesh transpose: (E, C, D) split over E in, concat over C),
processed by the local expert group, and combined back gate-weighted.

The dense path (`switch_moe`) is the single-device reference — identical
math, no collectives — used for tests and small models; both paths are
differentiable and share the routing implementation, so they cannot
drift.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec


class MoEParams(NamedTuple):
  """Router + stacked expert FFN weights.

  router: (D, E). w1/b1: (E, D, H). w2/b2: (E, H, D) — leading expert
  axis is what the `expert` mesh axis shards.
  """
  router: jnp.ndarray
  w1: jnp.ndarray
  b1: jnp.ndarray
  w2: jnp.ndarray
  b2: jnp.ndarray


def init_moe_params(rng: jax.Array, num_experts: int, d_model: int,
                    d_hidden: int, dtype=jnp.float32) -> MoEParams:
  k1, k2, k3 = jax.random.split(rng, 3)
  scale1 = 1.0 / jnp.sqrt(d_model).astype(dtype)
  scale2 = 1.0 / jnp.sqrt(d_hidden).astype(dtype)
  return MoEParams(
      router=jax.random.normal(k1, (d_model, num_experts), dtype) * scale1,
      w1=jax.random.normal(k2, (num_experts, d_model, d_hidden),
                           dtype) * scale1,
      b1=jnp.zeros((num_experts, d_hidden), dtype),
      w2=jax.random.normal(k3, (num_experts, d_hidden, d_model),
                           dtype) * scale2,
      b2=jnp.zeros((num_experts, d_model), dtype),
  )


class _Routing(NamedTuple):
  combine: jnp.ndarray    # (N, E, C) — one-hot dispatch/combine tensor
  gate: jnp.ndarray       # (N,) — top-1 router probability
  fraction: jnp.ndarray   # (E,) — fraction of tokens routed per expert
  mean_prob: jnp.ndarray  # (E,) — mean router probability per expert


def _route(tokens: jnp.ndarray, router: jnp.ndarray,
           capacity: int) -> _Routing:
  """Top-1 routing with per-expert capacity; overflow tokens drop (the
  residual connection around the MoE block carries them unchanged)."""
  n, _ = tokens.shape
  num_experts = router.shape[-1]
  logits = tokens.astype(jnp.float32) @ router.astype(jnp.float32)
  probs = jax.nn.softmax(logits, axis=-1)                  # (N, E)
  expert_index = jnp.argmax(probs, axis=-1)                # (N,)
  gate = jnp.take_along_axis(probs, expert_index[:, None], axis=-1)[:, 0]
  onehot = jax.nn.one_hot(expert_index, num_experts,
                          dtype=jnp.float32)               # (N, E)
  # Position of each token within its expert's queue (first-come).
  position = jnp.cumsum(onehot, axis=0) * onehot           # 1-based
  keep = (position > 0) & (position <= capacity)
  pos_onehot = jax.nn.one_hot(
      ((position - 1.0) * onehot).astype(jnp.int32), capacity,
      dtype=jnp.float32)
  combine = jnp.where(keep[..., None], onehot[..., None] * pos_onehot,
                      0.0)                                 # (N, E, C)
  return _Routing(combine=combine, gate=gate,
                  fraction=jnp.mean(onehot, axis=0),
                  mean_prob=jnp.mean(probs, axis=0))


def _aux_loss(fraction: jnp.ndarray, mean_prob: jnp.ndarray) -> jnp.ndarray:
  """Switch aux loss: E · Σ_e fraction_tokens_e · mean_router_prob_e."""
  return fraction.shape[-1] * jnp.sum(fraction * mean_prob)


def _expert_ffn(buf: jnp.ndarray, params: MoEParams) -> jnp.ndarray:
  """Applies expert e's FFN to buffer row e: (E, C, D) → (E, C, D)."""
  h = jax.nn.relu(
      jnp.einsum("ecd,edh->ech", buf, params.w1.astype(buf.dtype))
      + params.b1[:, None].astype(buf.dtype))
  return (jnp.einsum("ech,ehd->ecd", h, params.w2.astype(buf.dtype))
          + params.b2[:, None].astype(buf.dtype))


def default_capacity(num_tokens: int, num_experts: int,
                     capacity_factor: float = 1.25) -> int:
  return max(1, int(num_tokens * capacity_factor / num_experts))


def switch_moe(tokens: jnp.ndarray, params: MoEParams,
               capacity: Optional[int] = None,
               capacity_factor: float = 1.25):
  """Dense single-device Switch MoE: (N, D) tokens → ((N, D), aux_loss)."""
  n, d = tokens.shape
  num_experts = params.router.shape[-1]
  if capacity is None:
    capacity = default_capacity(n, num_experts, capacity_factor)
  routing = _route(tokens, params.router, capacity)
  f32 = tokens.astype(jnp.float32)
  buf = jnp.einsum("nec,nd->ecd", routing.combine, f32)    # (E, C, D)
  out = _expert_ffn(buf, params)
  y = jnp.einsum("nec,ecd->nd", routing.combine, out)
  y = y * routing.gate[:, None]
  return (y.astype(tokens.dtype),
          _aux_loss(routing.fraction, routing.mean_prob))


def _ep_local(tokens, params: MoEParams, *, axis_name: str, capacity: int):
  """Per-device body: tokens (N_local, D); expert weights (E/P, ...)."""
  routing = _route(tokens, params.router, capacity)
  f32 = tokens.astype(jnp.float32)
  buf = jnp.einsum("nec,nd->ecd", routing.combine, f32)    # (E, C, D)
  # Mesh transpose: every device sends expert-shard e its (C, D) queue →
  # local buffer (E/P, P·C, D) holding ALL devices' tokens for the
  # local expert group.
  buf = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                           tiled=True)
  out = _expert_ffn(buf, params)
  # Inverse transpose: results return to their source device.
  out = jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                           tiled=True)                     # (E, C, D)
  y = jnp.einsum("nec,ecd->nd", routing.combine, out)
  y = y * routing.gate[:, None]
  # Global aux statistics FIRST (token shards are equal-size, so pmean of
  # per-shard means is the global mean), then the nonlinear product —
  # this keeps the EP aux loss bit-identical to the dense path's.
  fraction = jax.lax.pmean(routing.fraction, axis_name)
  mean_prob = jax.lax.pmean(routing.mean_prob, axis_name)
  return y.astype(tokens.dtype), _aux_loss(fraction, mean_prob)


def expert_parallel_moe(
    tokens: jnp.ndarray,
    params: MoEParams,
    mesh: Mesh,
    axis: str = "expert",
    capacity: Optional[int] = None,
    capacity_factor: float = 1.25,
):
  """Switch MoE with experts sharded over the `axis` mesh axis.

  Args:
    tokens: (N, D); N must divide evenly over the axis (tokens are
      data-sharded over the same axis the experts live on — each device
      routes its token shard to all expert shards via all_to_all).
    params: MoEParams; the leading expert axis (size E) must divide
      evenly over the axis and is sharded one-group-per-device.
    mesh: device mesh containing `axis`.
    capacity: per-expert, per-source-device token queue length; default
      `default_capacity(N/P, E, capacity_factor)`.

  Returns:
    ((N, D) output, scalar load-balancing aux loss) — numerically equal
    to `switch_moe` with capacity=P·(per-device capacity) modulo
    first-come ordering of the token shards.
  """
  num_devices = mesh.shape[axis]
  n, _ = tokens.shape
  num_experts = params.router.shape[-1]
  if n % num_devices != 0:
    raise ValueError(f"Token count {n} not divisible by {axis!r} axis "
                     f"size {num_devices}.")
  if num_experts % num_devices != 0:
    raise ValueError(f"Expert count {num_experts} not divisible by "
                     f"{axis!r} axis size {num_devices}.")
  if capacity is None:
    capacity = default_capacity(n // num_devices, num_experts,
                                capacity_factor)
  token_spec = PartitionSpec(axis)
  param_specs = MoEParams(
      router=PartitionSpec(),           # replicated — every device routes
      w1=PartitionSpec(axis), b1=PartitionSpec(axis),
      w2=PartitionSpec(axis), b2=PartitionSpec(axis),
  )
  fn = shard_map(
      functools.partial(_ep_local, axis_name=axis, capacity=capacity),
      mesh=mesh,
      in_specs=(token_spec, param_specs),
      out_specs=(token_spec, PartitionSpec()),
  )
  return fn(tokens, params)

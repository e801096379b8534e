"""Ring attention: exact attention over sequence-sharded inputs.

Long-context support beyond the reference (which capped sequences at
short robot episodes — SURVEY.md §5.7): the sequence axis is sharded
over a mesh axis, each device keeps its Q shard resident and K/V shards
rotate around the ring via `jax.lax.ppermute` (one ICI hop per step),
while softmax is accumulated blockwise with the running-max trick — so
attention memory is O(T_local²-ish per block) instead of O(T²) and the
sequence length scales with the ring size.

The public entry runs under `shard_map` over the caller's mesh; K/V
rotation overlaps with the current block's compute under XLA's async
collective scheduling.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec


def _ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool,
    scale: float,
    batch_axis: Optional[str] = None,
):
  """Per-device body: q, k, v are local shards (B, T_local, H, D)."""
  num_devices = jax.lax.psum(1, axis_name)
  my_index = jax.lax.axis_index(axis_name)
  b, t_local, h, d = q.shape

  q_f32 = q.astype(jnp.float32)
  q_positions = my_index * t_local + jnp.arange(t_local)

  def block(scores_max, denom, acc, k_blk, v_blk, source_index):
    """One flash-attention accumulation step against a K/V block."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_f32,
                        k_blk.astype(jnp.float32)) * scale
    if causal:
      k_positions = source_index * t_local + jnp.arange(t_local)
      mask = q_positions[:, None] >= k_positions[None, :]
      scores = jnp.where(mask[None, None], scores, -jnp.inf)
    block_max = jnp.max(scores, axis=-1)
    new_max = jnp.maximum(scores_max, block_max)
    # Renormalize both the old accumulator and the new block. Guard
    # against all--inf rows (fully-masked): safe_new_max is finite, so
    # exp(scores_max - safe_new_max) is 0 (not nan) when scores_max is
    # still -inf.
    safe_new_max = jnp.where(jnp.isneginf(new_max), 0.0, new_max)
    correction = jnp.exp(scores_max - safe_new_max)
    weights = jnp.exp(scores - safe_new_max[..., None])
    new_denom = denom * correction + jnp.sum(weights, axis=-1)
    block_acc = jnp.einsum("bhqk,bkhd->bqhd", weights,
                           v_blk.astype(jnp.float32))
    new_acc = acc * correction.transpose(0, 2, 1)[..., None] + block_acc
    return new_max, new_denom, new_acc

  perm = [(i, (i + 1) % num_devices) for i in range(num_devices)]

  def body(step, carry):
    k_blk, v_blk, scores_max, denom, acc = carry
    # After `step` rotations this device holds the block that started
    # at ring position (my_index - step) mod n.
    source_index = (my_index - step) % num_devices
    scores_max, denom, acc = block(
        scores_max, denom, acc, k_blk, v_blk, source_index)
    k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
    v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return k_blk, v_blk, scores_max, denom, acc

  # Mark the accumulators device-varying up front (they depend on
  # axis_index — and on the batch shard when batch-sharded — from the
  # first iteration) for shard_map's VMA type check.
  vary_axes = (axis_name,) + ((batch_axis,) if batch_axis else ())
  varying = lambda x: jax.lax.pcast(x, vary_axes, to="varying")
  init = (
      k, v,
      varying(jnp.full((b, h, t_local), -jnp.inf, jnp.float32)),
      varying(jnp.zeros((b, h, t_local), jnp.float32)),
      varying(jnp.zeros((b, t_local, h, d), jnp.float32)),
  )
  # n-1 rotated steps; the final block is accumulated outside the loop
  # so no dead K/V ring hop is issued on the last iteration.
  k_last, v_last, scores_max, denom, acc = jax.lax.fori_loop(
      0, num_devices - 1, body, init)
  _, denom, acc = block(
      scores_max, denom, acc, k_last, v_last,
      (my_index - (num_devices - 1)) % num_devices)
  denom = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows → 0
  out = acc / denom.transpose(0, 2, 1)[..., None]
  return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
) -> jnp.ndarray:
  """Exact multi-head attention with the sequence sharded over `axis`.

  Args:
    q, k, v: (B, T, H, D) arrays; T must divide evenly over the mesh
      axis. Inputs may be replicated or already sequence-sharded — the
      shard_map in_specs lay them out over `axis`.
    mesh: the device mesh (e.g. create_mesh({"data": 1, "seq": 8})).
    axis: mesh axis name carrying the sequence dimension.
    causal: apply a causal mask over GLOBAL positions.
    scale: attention scale; default 1/sqrt(D).
    batch_axis: mesh axis carrying the batch dim — set this on dp×sp
      meshes so each data-row only computes its batch shard (omitting it
      there would all-gather the batch and redo it per row).

  Returns:
    (B, T, H, D) attention output, sharded like the inputs.
  """
  if scale is None:
    scale = 1.0 / math.sqrt(q.shape[-1])
  spec = PartitionSpec(batch_axis, axis, None, None)
  fn = shard_map(
      functools.partial(_ring_attention_local, axis_name=axis,
                        causal=causal, scale=scale,
                        batch_axis=batch_axis),
      mesh=mesh,
      in_specs=(spec, spec, spec),
      out_specs=spec,
  )
  return fn(q, k, v)


def dense_attention_reference(q, k, v, causal=False, scale=None):
  """Unsharded O(T²) reference used by tests and small models."""
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
  return out.astype(q.dtype)

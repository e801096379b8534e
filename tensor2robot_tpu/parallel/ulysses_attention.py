"""All-to-all (Ulysses-style) sequence parallelism for attention.

The complement to `ring_attention` for long in-context sequences
(long-context support beyond the reference, which capped sequences at
short robot episodes — SURVEY.md §5.7): instead of rotating K/V shards
around a ring (P-1 ppermute hops), one `all_to_all` re-shards the
inputs from sequence-sharded (B, T/P, H, D) to head-sharded
(B, T, H/P, D), each device runs ordinary full-sequence attention over
its head subset, and a second `all_to_all` restores sequence sharding.

Trade-off vs ring attention (pick per workload):
  - Ulysses: two all-to-all rounds total — one (q,k,v fused) in, one
    out (O(1) collective rounds, bandwidth O(B·T·H·D/P) per device) —
    but every device holds the FULL sequence
    for H/P heads — T is bounded by per-device memory unless the local
    attention is itself blockwise (use attn_impl="pallas" to keep the
    local working set O(T)).
  - Ring: P-1 ppermute rounds overlapped with compute; K/V memory stays
    at the shard size, so T scales with the ring — better for extreme T,
    more latency-sensitive on slow interconnects.
  - Head-count constraint: Ulysses needs H % P == 0; ring does not.

Fully differentiable through `jax.grad` (the collectives are plain XLA
ops); with attn_impl="pallas" the same first-order-only caveat as
ops.flash_attention applies.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec


def _local_attention(q, k, v, causal: bool, scale: float, attn_impl: str):
  if attn_impl == "pallas":
    from tensor2robot_tpu.ops.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           implementation="pallas")
  from tensor2robot_tpu.parallel.ring_attention import (
      dense_attention_reference)
  return dense_attention_reference(q, k, v, causal=causal, scale=scale)


def _ulysses_local(q, k, v, axis_name: str, causal: bool, scale: float,
                   attn_impl: str):
  """Per-device body: shards are (B, T_local, H, D)."""
  # Sequence-sharded → head-sharded: split the head axis P ways, gather
  # the sequence axis. q/k/v are stacked so the in-direction re-shard is
  # one collective launch instead of three.
  qkv = jnp.stack((q, k, v))                           # (3, B, T_loc, H, D)
  qkv = jax.lax.all_to_all(
      qkv, axis_name, split_axis=3, concat_axis=2, tiled=True)
  qh, kh, vh = qkv[0], qkv[1], qkv[2]                  # (B, T, H/P, D)
  out = _local_attention(qh, kh, vh, causal, scale, attn_impl)
  # Head-sharded → sequence-sharded: the inverse all-to-all.
  return jax.lax.all_to_all(
      out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
    attn_impl: str = "xla",
) -> jnp.ndarray:
  """Exact multi-head attention with the sequence sharded over `axis`,
  computed via head-scatter/sequence-gather all-to-alls.

  Args:
    q, k, v: (B, T, H, D) arrays; T and H must divide evenly over the
      mesh axis. Inputs may be replicated or already sequence-sharded —
      the shard_map in_specs lay them out over `axis`.
    mesh: the device mesh (e.g. create_mesh({"data": 1, "seq": 8})).
    axis: mesh axis name carrying the sequence dimension.
    causal: apply a causal mask over GLOBAL positions.
    scale: attention scale; default 1/sqrt(D).
    batch_axis: mesh axis carrying the batch dim on dp×sp meshes.
    attn_impl: "xla" (dense local attention) or "pallas" (blockwise
      flash kernel locally — keeps per-device memory O(T), TPU only).

  Returns:
    (B, T, H, D) attention output, sharded like the inputs.
  """
  if attn_impl not in ("xla", "pallas"):
    raise ValueError(
        f"attn_impl must be 'xla' or 'pallas', got {attn_impl!r} — a "
        "typo here would silently fall back to the dense O(T²) path.")
  num_shards = mesh.shape[axis]
  if q.shape[2] % num_shards != 0:
    raise ValueError(
        f"Ulysses needs heads ({q.shape[2]}) divisible by the {axis!r} "
        f"axis size ({num_shards}); use ring_attention otherwise.")
  if scale is None:
    scale = 1.0 / math.sqrt(q.shape[-1])
  spec = PartitionSpec(batch_axis, axis, None, None)
  # pallas_call's out_shape carries no varying-mesh-axes annotation,
  # which the replication/VMA type check rejects inside shard_map; the
  # explicit in/out_specs above already pin the layout, so the check
  # adds nothing here.
  fn = shard_map(
      functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                        scale=scale, attn_impl=attn_impl),
      mesh=mesh,
      in_specs=(spec, spec, spec),
      out_specs=spec,
      check_vma=attn_impl != "pallas",
  )
  return fn(q, k, v)

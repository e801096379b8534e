"""Pipeline parallelism: microbatched stage execution over a mesh axis.

Beyond the reference (whose only strategy was data parallelism —
SURVEY.md §2 "Parallelism strategies"): a GPipe-style pipeline expressed
the TPU-native way. Stages are homogeneous (same pytree structure per
stage, the usual repeated-block case); their params are stacked with a
leading stage axis sharded over the `stage` mesh axis, so each device
holds exactly one stage's weights. Under `shard_map`, activations flow
stage→stage via `jax.lax.ppermute` (one ICI hop per tick) while
microbatches stream in, filling the pipeline; the loop runs
M + P - 1 ticks (bubble fraction (P-1)/(M+P-1), amortized by more
microbatches).

Differentiating through the schedule gives the backward pipeline for
free: ppermute's transpose is the reverse-direction ppermute, so
`jax.grad` of a pipelined loss runs the textbook reverse schedule
without any hand-written backward pass.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec


def stack_stage_params(params_per_stage: Sequence[Any]) -> Any:
  """Stacks per-stage param pytrees (identical structure) along a new
  leading stage axis — the layout pipeline_apply shards over `stage`."""
  return jax.tree_util.tree_map(
      lambda *leaves: jnp.stack(leaves), *params_per_stage)


def _pipeline_local(stacked_params, microbatches, *, stage_fn,
                    axis_name: str):
  """Per-device body. stacked_params leaves are (1, ...) local slices;
  microbatches leaves are (M, mb, ...) (replicated over the axis)."""
  index = jax.lax.axis_index(axis_name)
  num_stages = jax.lax.psum(1, axis_name)
  params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
  num_microbatches = jax.tree_util.tree_leaves(microbatches)[0].shape[0]

  first = jax.tree_util.tree_map(lambda x: x[0], microbatches)
  out_struct = jax.eval_shape(stage_fn, params, first)
  zeros_like_out = jax.tree_util.tree_map(
      lambda s: jnp.zeros(s.shape, s.dtype), out_struct)
  # Activations keep the stage-output structure from tick to tick; the
  # input microbatch structure must match it (homogeneous stages).
  outputs = jax.tree_util.tree_map(
      lambda s: jnp.zeros((num_microbatches,) + s.shape, s.dtype),
      out_struct)
  forward = [(i, i + 1) for i in range(num_stages - 1)]

  def tick(t, carry):
    incoming, outputs = carry
    # Stage 0 consumes microbatch t while t < M, then recirculates its
    # last input (those trailing ticks only drain later stages; the
    # results computed from the stale input never reach `outputs`).
    feed_index = jnp.minimum(t, num_microbatches - 1)
    feed = jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, feed_index, 0,
                                               keepdims=False),
        microbatches)
    x = jax.tree_util.tree_map(
        lambda a, b: jnp.where(index == 0, a, b), feed, incoming)
    y = stage_fn(params, x)
    # The last stage finished microbatch t - (P - 1) at this tick.
    done = t - (num_stages - 1)
    write = jnp.logical_and(index == num_stages - 1, done >= 0)
    slot = jnp.maximum(done, 0)
    outputs = jax.tree_util.tree_map(
        lambda buf, val: jax.lax.dynamic_update_index_in_dim(
            buf,
            jnp.where(write, val,
                      jax.lax.dynamic_index_in_dim(buf, slot, 0, False)),
            slot, 0),
        outputs, y)
    # Hand activations to the next stage (stage 0 receives zeros).
    incoming = jax.tree_util.tree_map(
        lambda a: jax.lax.ppermute(a, axis_name, forward), y)
    return incoming, outputs

  # Mark the carried buffers device-varying up front (they depend on
  # axis_index from the first tick) for shard_map's VMA type check.
  varying = lambda tree: jax.tree_util.tree_map(
      lambda x: jax.lax.pcast(x, (axis_name,), to="varying"), tree)
  init = (varying(zeros_like_out), varying(outputs))
  _, outputs = jax.lax.fori_loop(
      0, num_microbatches + num_stages - 1, tick, init)
  # Only the last stage holds real outputs; psum over the (zero
  # elsewhere) buffers replicates them to every stage.
  return jax.lax.psum(outputs, axis_name)


def pipeline_apply(
    stacked_params: Any,
    batch: Any,
    stage_fn: Callable[[Any, Any], Any],
    mesh: Mesh,
    axis: str = "stage",
    num_microbatches: Optional[int] = None,
) -> Any:
  """Runs `batch` through P pipelined stages of `stage_fn`.

  Args:
    stacked_params: pytree whose leaves carry a leading stage axis of
      size P (see stack_stage_params); sharded over `axis`.
    batch: pytree of (B, ...) arrays; num_microbatches must divide B.
      The batch structure must equal the stage output structure
      (homogeneous stages — x and stage_fn(params, x) match).
    stage_fn: (stage_params, x) -> y for ONE stage.
    mesh: device mesh containing `axis`.
    num_microbatches: default P (one in flight per stage); more
      microbatches shrink the pipeline bubble.

  Returns:
    (B, ...) pytree: stage_fn applied P times in sequence.
  """
  num_stages = mesh.shape[axis]
  for path, leaf in jax.tree_util.tree_leaves_with_path(stacked_params):
    if leaf.shape[:1] != (num_stages,):
      raise ValueError(
          f"stacked_params leaf {jax.tree_util.keystr(path)} has leading "
          f"dim {leaf.shape[:1]}, but the {axis!r} mesh axis has "
          f"{num_stages} stages — shard_map would silently keep only "
          "the first stage of each local slice.")
  m = num_microbatches or num_stages
  leaves = jax.tree_util.tree_leaves(batch)
  b = leaves[0].shape[0]
  if b % m != 0:
    raise ValueError(f"Batch size {b} not divisible by "
                     f"num_microbatches={m}.")
  microbatched = jax.tree_util.tree_map(
      lambda x: x.reshape((m, b // m) + x.shape[1:]), batch)

  params_spec = PartitionSpec(axis)
  fn = shard_map(
      functools.partial(_pipeline_local, stage_fn=stage_fn,
                        axis_name=axis),
      mesh=mesh,
      in_specs=(params_spec, PartitionSpec()),
      out_specs=PartitionSpec(),
  )
  out = fn(stacked_params, microbatched)
  return jax.tree_util.tree_map(
      lambda x: x.reshape((b,) + x.shape[2:]), out)

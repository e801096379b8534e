"""Multi-host runtime: process bootstrap + hybrid ICI/DCN meshes.

Reference parity: the reference's multi-device story was TPUEstimator's
master RPC + per-host infeed (upstream) and NCCL MirroredStrategy (the
fork) — SURVEY.md §5.8. The JAX-native equivalent has two halves:

1. Process bootstrap: every host calls `initialize()` once, then the
   normal single-program code sees the GLOBAL device set
   (`jax.devices()`), and the existing mesh/pjit path scales to
   multi-host unchanged — XLA routes collectives over ICI within a
   slice and DCN across slices.
2. Mesh layout: `create_hybrid_mesh` keeps bandwidth-hungry axes
   (model/tensor parallel) inside a slice (ICI) and puts the
   gradient-all-reduce data axis across slices (DCN), the standard
   layout from the scaling playbook.

Nothing here opens sockets itself; `jax.distributed.initialize` speaks
the JAX coordination service (or the TPU metadata autodetect path), so
there is no NCCL/MPI dependency to replace.
"""

from __future__ import annotations

import logging
import os
from typing import Mapping, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from tensor2robot_tpu.parallel import mesh as mesh_lib

_log = logging.getLogger(__name__)
_initialized = False


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
  """Connects this process to the multi-host runtime (idempotent).

  MUST run before any other JAX API touches the backend (device
  queries included) — backend initialization is one-shot, and an
  uncoordinated backend sees only local devices. With no arguments,
  relies on `jax.distributed.initialize`'s cluster autodetection (TPU
  pod metadata / cluster env vars); when no cluster environment is
  detectable this degrades to a logged single-process no-op, so
  single-process runs may call it unconditionally.
  """
  global _initialized
  if _initialized:
    return
  explicit = (coordinator_address is not None or num_processes is not None
              or process_id is not None)
  if explicit and (num_processes or 0) > 1 and (
      os.environ.get("JAX_PLATFORMS", "").startswith("cpu")):
    # Chipless multi-controller bring-up (ISSUE 19): the CPU backend's
    # default cross-process collectives tier is "none", which makes
    # every computation spanning processes fail to compile
    # ("Multiprocess computations aren't implemented"). jaxlib ships a
    # gloo TCP tier that rides the same coordination service — select
    # it here, while the backend is still uninitialized (this function
    # is documented as the process's first JAX call, so this is the
    # one place the flag can still take effect). Real TPU/GPU pods
    # never enter this branch: their collectives are ICI/NCCL-native.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # Gloo pairs assume one in-flight collective per context; the CPU
    # client's async dispatch can issue two differently-sized
    # collectives back-to-back and cross their wire frames
    # ("op.preamble.length <= op.nbytes" aborts). Synchronous
    # dispatch serializes issue order — correctness over overlap on
    # this emulation tier.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
  try:
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
  except (RuntimeError, ValueError) as e:
    if explicit:
      raise
    # No detectable cluster environment (bare single-process run) — or
    # the backend was already initialized, in which case multi-host
    # setup either already happened (fine) or is impossible now (the
    # caller violated the call-order contract; surface that loudly).
    if "already" in str(e).lower():
      _log.warning(
          "jax.distributed.initialize skipped: backend already "
          "initialized (%s). If this is a multi-host run, initialize() "
          "must be the first JAX call in the process.", e)
    else:
      _log.info("No cluster environment detected (%s); single-process.",
                e)
  _initialized = True
  _log.info("Distributed runtime: process %d/%d, %d local of %d "
            "global devices.", jax.process_index(), jax.process_count(),
            jax.local_device_count(), jax.device_count())


def is_primary() -> bool:
  """True on the process that owns logging/checkpoint/export side
  effects (reference: the chief worker)."""
  return jax.process_index() == 0


def create_hybrid_mesh(
    ici_axes: Mapping[str, int],
    dcn_axes: Optional[Mapping[str, int]] = None,
) -> Mesh:
  """Mesh whose `ici_axes` stay within a slice and `dcn_axes` span slices.

  Args:
    ici_axes: ordered {axis: size} laid out over in-slice ICI links —
      put model/tensor/sequence axes here.
    dcn_axes: ordered {axis: size} laid out across slices over DCN —
      typically just the gradient-all-reduce `data` axis. One size may
      be -1 (fill). None/empty or single-slice topologies degrade to a
      plain `create_mesh` over everything (DCN layout is irrelevant
      when there is nothing to cross).

  Returns:
    jax.sharding.Mesh with dcn axes outermost, ici axes innermost.
  """
  dcn_axes = dict(dcn_axes or {})
  axes = {**dcn_axes, **{k: v for k, v in ici_axes.items()}}
  if len(set(axes)) != len(dcn_axes) + len(ici_axes):
    raise ValueError(
        f"Axis names repeat across ici {list(ici_axes)} and dcn "
        f"{list(dcn_axes)}.")
  if dcn_axes and any(v == -1 for v in ici_axes.values()):
    # A -1 ici axis would fill across slices, defeating the layout.
    raise ValueError(
        f"-1 (fill) is only allowed on dcn axes when dcn_axes is set; "
        f"got ici_axes={dict(ici_axes)}.")
  devices = jax.devices()
  # The DCN granule is the TPU slice when the backend reports one;
  # otherwise (CPU/GPU multi-process) the process is the granule —
  # cross-process links are the slow tier there, which is exactly the
  # boundary the dcn axes should straddle. This also lets multi-process
  # CPU CI exercise the real hybrid layout.
  process_is_granule = not hasattr(devices[0], "slice_index")
  granule = (lambda d: d.process_index) if process_is_granule else (
      lambda d: d.slice_index)
  num_slices = len({granule(d) for d in devices})
  if not dcn_axes or num_slices == 1:
    return mesh_lib.create_mesh(axes)

  from jax.experimental import mesh_utils
  ici_sizes = list(ici_axes.values())
  dcn_sizes = [v for v in dcn_axes.values()]
  fill = [i for i, v in enumerate(dcn_sizes) if v == -1]
  if len(fill) > 1:
    raise ValueError("At most one dcn axis may be -1.")
  if fill:
    fixed = int(np.prod([v for v in dcn_sizes if v != -1]))
    per_slice = int(np.prod(ici_sizes)) * fixed
    if len(devices) % per_slice != 0:
      raise ValueError(
          f"{len(devices)} devices not divisible by {per_slice} "
          f"(ici {ici_axes} × fixed dcn axes).")
    dcn_sizes[fill[0]] = len(devices) // per_slice
  # DCN axes lead: the granule index is the slowest-varying coordinate.
  device_array = mesh_utils.create_hybrid_device_mesh(
      mesh_shape=[1] * len(dcn_sizes) + ici_sizes,
      dcn_mesh_shape=dcn_sizes + [1] * len(ici_sizes),
      devices=devices,
      process_is_granule=process_is_granule)
  return Mesh(device_array, tuple(dcn_axes) + tuple(ici_axes))


def sync_global_devices(name: str) -> None:
  """Cross-host barrier (reference: implicit session-run sync points)."""
  from jax.experimental import multihost_utils
  multihost_utils.sync_global_devices(name)


def global_put(tree, shardings):
  """Places a host-local pytree onto (possibly cross-process) shardings.

  Single-process this IS `jax.device_put` — byte-for-byte the r17
  oracle path. Multi-process, `device_put` refuses shardings whose
  device set spans processes, so each leaf is assembled with
  `jax.make_array_from_callback` against the full local value: every
  process holds the identical full array (true for everything this
  repo places at bring-up — seeded env/ring init, replicated target
  variables, dispatch counters) and contributes exactly the index
  slices its local devices own. Correct for BOTH replicated and
  axis-split shardings, which is why this is the one placement
  primitive (`make_array_from_process_local_data` would need the
  per-process slice pre-cut for the split case).

  Args:
    tree: pytree of host/np/jnp arrays, identical on every process.
    shardings: one `jax.sharding.Sharding` applied to every leaf, or a
      pytree of shardings matching `tree`'s structure.
  """
  if jax.process_count() == 1:
    return jax.device_put(tree, shardings)

  def place(leaf, sharding):
    arr = np.asarray(leaf)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])

  if isinstance(shardings, jax.sharding.Sharding):
    return jax.tree_util.tree_map(lambda leaf: place(leaf, shardings), tree)
  return jax.tree_util.tree_map(place, tree, shardings)


def global_scalar(value, mesh, dtype=None):
  """A replicated GLOBAL scalar on `mesh` (multi-process jit operands
  must be global arrays even when every shard holds the same value —
  the dispatch-counter seam of the fused loops). Single-process this
  is a plain `jnp.asarray`, the unchanged oracle path."""
  import jax.numpy as jnp
  arr = jnp.asarray(value, dtype)
  if jax.process_count() == 1:
    return arr
  return global_put(arr, mesh_lib.replicated_sharding(mesh))

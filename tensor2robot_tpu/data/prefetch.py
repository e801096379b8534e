"""Double-buffered host→device prefetch under explicit shardings.

The TPU-native replacement for TPUEstimator's infeed queues (SURVEY.md §2
native-components table, "Host→device feeding"): while the device crunches
step N, the next host batch is already being transferred — `jax.device_put`
with a `NamedSharding` is asynchronous, so holding `depth` in-flight batches
overlaps H2D DMA with compute without any explicit infeed machinery.

ISSUE 20 instruments the seam: in-flight depth and bytes flow through the
typed `obs/registry` writer (gauges ``<name>/depth`` and
``<name>/in_flight_bytes``, counter ``<name>/batches``), and a consumer
that must distinguish "stream ended" from "iterator bug" can opt into the
typed `PrefetchExhausted` instead of a bare `StopIteration` escaping a
generator frame (which Python would mangle into a RuntimeError anyway).
"""

from __future__ import annotations

import collections
from typing import Any, Iterator, Optional

import jax
import numpy as np

from tensor2robot_tpu.obs import trace as trace_lib


class PrefetchExhausted(Exception):
  """The upstream host iterator ended and every in-flight transfer has
  been yielded. Raised (instead of bare StopIteration) when the
  consumer passed ``exhaust_error=True`` — a learner loop catches THIS
  at its ingest seam rather than letting generator-protocol mechanics
  leak through as RuntimeError('generator raised StopIteration')."""

  def __init__(self, name: str, batches: int):
    super().__init__(
        f"prefetch stream {name!r} exhausted after {batches} batches")
    self.name = name
    self.batches = batches


def _host_nbytes(batch: Any) -> int:
  """Byte size of a host pytree BEFORE transfer (what H2D will move)."""
  return sum(np.asarray(leaf).nbytes
             for leaf in jax.tree_util.tree_leaves(batch))


def prefetch_to_device(
    iterator: Iterator[Any],
    sharding: Optional[Any] = None,
    depth: int = 2,
    registry: Optional[Any] = None,
    name: str = "prefetch",
    exhaust_error: bool = False,
) -> Iterator[Any]:
  """Yields batches moved to device, keeping `depth` transfers in flight.

  Args:
    iterator: host iterator of pytrees of numpy arrays (e.g. the
      (features, labels) tuples input generators yield).
    sharding: a `jax.sharding.Sharding` (or pytree of them matching the
      batch structure) describing how the global batch lays out over the
      mesh; None = default device placement.
    depth: number of batches resident on device. 2 = classic double
      buffering; more helps jittery input pipelines at the cost of HBM.
    registry: a `MetricRegistry`; defaults to the process registry.
      Gauges ``<name>/depth`` / ``<name>/in_flight_bytes`` track the
      buffer after every transition; counter ``<name>/batches`` counts
      yields.
    name: metric namespace for this stream.
    exhaust_error: when True, raise `PrefetchExhausted` after the final
      buffered batch instead of ending by StopIteration.
  """
  if depth < 1:
    raise ValueError(f"depth must be >= 1, got {depth}")
  if registry is None:
    from tensor2robot_tpu.obs.registry import get_registry
    registry = get_registry()
  depth_gauge = registry.gauge(f"{name}/depth")
  bytes_gauge = registry.gauge(f"{name}/in_flight_bytes")
  batches_counter = registry.counter(f"{name}/batches")

  def transfer(batch: Any) -> Any:
    if sharding is None:
      return jax.device_put(batch)
    return jax.device_put(batch, sharding)

  buffer: collections.deque = collections.deque()
  in_flight_bytes: collections.deque = collections.deque()
  yielded = 0

  def push(batch: Any) -> None:
    in_flight_bytes.append(_host_nbytes(batch))
    with trace_lib.span("input/put", bytes=in_flight_bytes[-1]):
      buffer.append(transfer(batch))
    depth_gauge.set(len(buffer))
    bytes_gauge.set(sum(in_flight_bytes))

  def pop() -> Any:
    in_flight_bytes.popleft()
    batch = buffer.popleft()
    depth_gauge.set(len(buffer))
    bytes_gauge.set(sum(in_flight_bytes))
    batches_counter.inc()
    return batch

  iterator = iter(iterator)
  exhausted = object()
  while True:
    # What the consumer waits for when the buffer is not ahead of it:
    # the host pipeline's next batch.
    with trace_lib.span("input/wait"):
      batch = next(iterator, exhausted)
    if batch is exhausted:
      break
    push(batch)
    if len(buffer) >= depth:
      yielded += 1
      yield pop()
  while buffer:
    yielded += 1
    yield pop()
  if exhaust_error:
    raise PrefetchExhausted(name, yielded)

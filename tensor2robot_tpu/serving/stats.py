"""Serving observability: latency histograms + batching counters.

The fleet numbers the serving artifacts and the benchmark's serving
cells carry: per-request latency p50/p99, queue depth at flush, batch
occupancy (real requests / compiled bucket slots), and padding waste.
Since round 11 every counter is additionally kept PER SLO CLASS
(serving/slo.py): class-keyed latency histograms plus shed counters
split by reason ("expired" at enqueue vs "capacity" overload), because
the fleet's graceful-degradation claim is exactly "batch sheds before
standard, standard before interactive, and interactive p99 holds its
budget" — a global p99 cannot carry that. Everything is plain host
floats, so a snapshot can go straight into
``utils/metric_writer.MetricWriter.write_scalars`` or a JSON artifact.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Optional

from tensor2robot_tpu.obs import registry as registry_lib
# ONE percentile convention in the repo: the nearest-rank helper lives
# with the obs registry's histograms; the serving histograms reuse it
# so the two layers cannot drift.
from tensor2robot_tpu.obs.registry import _nearest_rank


class LatencyHistogram:
  """Bounded reservoir of latency samples with percentile readout."""

  def __init__(self, max_samples: int = 16384):
    self._samples: collections.deque = collections.deque(maxlen=max_samples)
    self._lock = threading.Lock()

  def record(self, latency_ms: float) -> None:
    with self._lock:
      self._samples.append(float(latency_ms))

  def percentile(self, pct: float) -> Optional[float]:
    with self._lock:
      if not self._samples:
        return None
      ordered = sorted(self._samples)
    return _nearest_rank(ordered, pct)

  def summary(self, digits: int = 3) -> Dict[str, float]:
    with self._lock:
      samples = list(self._samples)
    if not samples:
      return {"count": 0}
    ordered = sorted(samples)

    def at(pct):
      return round(_nearest_rank(ordered, pct), digits)

    return {
        "count": len(samples),
        "p50_ms": at(50),
        "p90_ms": at(90),
        "p99_ms": at(99),
        "max_ms": round(ordered[-1], digits),
        "mean_ms": round(sum(samples) / len(samples), digits),
    }


class QSketch:
  """Streaming quantile sketch of one replica's SERVED Q-values.

  A bounded reservoir (newest ``max_samples``) for the statistics plus
  an exact lifetime count — the per-replica input of the fleet Q-drift
  guard (obs/health.q_drift_report): every replica serves the same
  request distribution through the same params, so the sketches must
  agree; one that doesn't is serving a different function (a corrupted
  replica or a botched hot-swap that still returns finite numbers).
  Every statistic except ``count`` is computed over the RETAINED
  reservoir — the sketch describes what the replica serves NOW, so a
  corrective hot-swap lets a once-divergent replica read healthy again
  once fresh traffic refills the window (and the router-side guard
  agrees with the aggregator, which only ever sees the exported
  reservoir). ``count`` stays lifetime: it gates on evidence volume.
  """

  __slots__ = ("_samples", "_count", "_lock")

  def __init__(self, max_samples: int = 4096):
    self._samples: collections.deque = collections.deque(
        maxlen=max_samples)
    self._count = 0
    self._lock = threading.Lock()

  def record_many(self, values) -> None:
    with self._lock:
      for value in values:
        self._samples.append(float(value))
        self._count += 1

  def summary(self, digits: int = 6) -> Dict[str, float]:
    """{count, p50, p90, mean, min, max} — p-quantiles by the repo's
    one nearest-rank convention; all but ``count`` over the retained
    reservoir (see class docstring)."""
    with self._lock:
      samples = list(self._samples)
      count = self._count
    if not samples:
      return {"count": 0, "p50": None}
    ordered = sorted(samples)
    return {
        "count": count,
        "p50": round(_nearest_rank(ordered, 50), digits),
        "p90": round(_nearest_rank(ordered, 90), digits),
        "mean": round(sum(samples) / len(samples), digits),
        "min": round(ordered[0], digits),
        "max": round(ordered[-1], digits),
    }


class _ClassStats:
  """Per-SLO-class counters (guarded by the owning ServingStats lock)."""

  __slots__ = ("requests", "shed_expired", "shed_capacity", "shed_fault",
               "latency")

  def __init__(self):
    self.requests = 0
    self.shed_expired = 0
    self.shed_capacity = 0
    self.shed_fault = 0
    self.latency = LatencyHistogram()


class ServingStats:
  """Thread-safe counters for the micro-batching serving path.

  Each instance is a WINDOWED view (benches swap a fresh one per sweep
  point); every record additionally flows through the process-wide
  ``obs.registry`` (ISSUE 11), so the registry holds process-lifetime
  serving totals/latency under ``serving/...`` regardless of how many
  windowed instances came and went. Pass ``registry=None`` explicitly
  via ``obs.registry.MetricRegistry()`` to isolate (tests).
  """

  def __init__(self,
               registry: Optional[registry_lib.MetricRegistry] = None):
    self._lock = threading.Lock()
    self._registry = registry or registry_lib.get_registry()
    self.latency = LatencyHistogram()
    self.queue_wait = LatencyHistogram()
    self._requests = 0
    self._logical_requests = 0
    self._flushes = 0
    self._occupied_slots = 0   # sum of real requests over flushes
    self._padded_slots = 0     # sum of compiled bucket sizes over flushes
    self._deadline_flushes = 0  # flushed by deadline, not by a full batch
    self._overlapped_flushes = 0  # popped while another flush was open
    self._encode_once_flushes = 0  # dispatched to an encode-once program
    self._staged_flushes = 0  # stacked into a staging array that was kept
    self._queue_depth_sum = 0   # queue depth left behind at flush time
    # Where the device turns went (record_flush_phases): one histogram
    # a phase, the flushes whose frames had landed when their turn
    # came, and for the busy share the sums of the split holds'
    # program times and of all holds' periods, each with its count.
    self.turn_wait = LatencyHistogram()
    self.transfer_wait = LatencyHistogram()
    self.program = LatencyHistogram()
    self._transfers_hidden = 0
    self._program_ms = [0.0, 0]  # sum, holds that were split
    self._period_ms = [0.0, 0]   # sum, holds after a policy's first
    self._per_class: Dict[str, _ClassStats] = {}
    self._q_sketches: Dict[str, QSketch] = {}

  def _class(self, class_name: Optional[str]) -> Optional[_ClassStats]:
    """Lazily creates the class bucket; caller holds the lock."""
    if class_name is None:
      return None
    stats = self._per_class.get(class_name)
    if stats is None:
      stats = self._per_class[class_name] = _ClassStats()
    return stats

  def record_request(self, class_name: Optional[str] = None) -> None:
    with self._lock:
      self._requests += 1
      cls = self._class(class_name)
      if cls is not None:
        cls.requests += 1
    self._registry.counter("serving/requests").inc()
    # Class-less traffic buckets under "default" — the same key
    # record_shed uses, so the registry's per-class shed RATES always
    # have a request denominator.
    self._registry.counter(
        f"serving/class/{class_name or 'default'}/requests").inc()

  def record_logical_request(self) -> None:
    """One LOGICAL request at the router front door (ISSUE 18).

    ``record_request`` counts dispatch ATTEMPTS — a faulted dispatch
    that retries on a second replica records twice — so benches have
    historically kept client-side truth to reconcile against. The
    flywheel needs that reconciliation without external bookkeeping:
    this counter increments exactly once per ``FleetRouter.submit``
    call, before any dispatch, so

        logical_requests == client submits
        logical_requests - shed_total == answered requests

    holds regardless of retry amplification.
    """
    with self._lock:
      self._logical_requests += 1
    self._registry.counter("serving/logical_requests").inc()

  def record_shed(self, class_name: Optional[str], reason: str) -> None:
    """One shed request: reason is "expired" (deadline already past at
    enqueue), "capacity" (queue bound exceeded, lowest-priority
    victim), or "fault" (a replica dispatch failed and the remaining
    deadline slack could not cover a retry — ISSUE 14). Sheds are
    counted on top of record_request — a shed request was offered load
    too."""
    with self._lock:
      cls = self._class(class_name or "default")
      if reason == "expired":
        cls.shed_expired += 1
      elif reason == "capacity":
        cls.shed_capacity += 1
      elif reason == "fault":
        cls.shed_fault += 1
      else:
        raise ValueError(f"unknown shed reason {reason!r}")
    self._registry.counter(f"serving/shed_{reason}").inc()
    self._registry.counter(
        f"serving/class/{class_name or 'default'}/shed_{reason}").inc()

  def record_q_values(self, replica: str, values) -> None:
    """Served Q-scores from one replica dispatch (ISSUE 15): feeds the
    per-replica streaming sketch AND the registry histogram
    ``serving/replica/<replica>/q_value`` — the reservoir the fleet
    aggregator unions, so the Q-drift check runs cross-process through
    the same snapshot machinery every other metric rides."""
    with self._lock:
      sketch = self._q_sketches.get(replica)
      if sketch is None:
        sketch = self._q_sketches[replica] = QSketch()
    sketch.record_many(values)
    hist = self._registry.histogram(
        f"serving/replica/{replica}/q_value")
    for value in values:
      hist.record(float(value))

  def q_sketch_summaries(self) -> Dict[str, Dict[str, float]]:
    """{replica: sketch summary} — the Q-drift guard's input."""
    with self._lock:
      sketches = dict(self._q_sketches)
    return {replica: sketch.summary()
            for replica, sketch in sorted(sketches.items())}

  def record_flush(self, batch_size: int, bucket: int,
                   queue_depth_after: int, deadline_expired: bool) -> None:
    with self._lock:
      self._flushes += 1
      self._occupied_slots += int(batch_size)
      self._padded_slots += int(bucket)
      self._queue_depth_sum += int(queue_depth_after)
      if deadline_expired:
        self._deadline_flushes += 1

  def record_overlapped_flush(self) -> None:
    """One flush (already counted by `record_flush`) that was popped
    while another flush of its batcher was still open: its host work
    ran beside the other's device time (MicroBatcher `flush_depth`)."""
    with self._lock:
      self._overlapped_flushes += 1

  def record_encode_once_flush(self) -> None:
    """One replica dispatch whose bucket program encoded each frame
    once and searched over the code (`CEMFleetPolicy.encode_once`),
    not over tiled copies of the frame."""
    with self._lock:
      self._encode_once_flushes += 1

  def record_staged_flush(self) -> None:
    """One replica dispatch whose frames were stacked into a host
    staging array the policy already held (the `serve/stack` span's
    `reused`), not into one mapped and faulted in for this flush."""
    with self._lock:
      self._staged_flushes += 1

  def record_flush_phases(self, turn_wait_ms: float, landed: int,
                          period_ms: Optional[float] = None,
                          transfer_wait_ms: Optional[float] = None,
                          program_ms: Optional[float] = None) -> None:
    """Where one replica dispatch spent its device turn
    (`CEMFleetPolicy.last_call_phases`, the flush's own span reads):
    the wait for the turn, whether the frames had landed when it came,
    the time since the policy's previous hold ended and, of the one
    hold in a few that the policy splits, the wait for what of its
    frames' transfer its enqueued program still had to wait for and
    the program's time with its actions' way back (the
    `serve/readback` span's `device_ms`). "Is this replica's limit the
    chip or the wire": `program_busy_share` near 1 is the chip; a long
    `transfer_wait_ms` with few `transfers_hidden` is the wire."""
    with self._lock:
      self._transfers_hidden += int(landed)
      for sums, value in ((self._period_ms, period_ms),
                          (self._program_ms, program_ms)):
        if value is not None:
          sums[0] += value
          sums[1] += 1
    for name, hist, value in (
        ("turn_wait_ms", self.turn_wait, turn_wait_ms),
        ("transfer_wait_ms", self.transfer_wait, transfer_wait_ms),
        ("program_ms", self.program, program_ms)):
      if value is not None:
        hist.record(value)
        self._registry.histogram(f"serving/{name}").record(value)

  def record_latency_ms(self, latency_ms: float,
                        class_name: Optional[str] = None) -> None:
    self.latency.record(latency_ms)
    self._registry.histogram("serving/latency_ms").record(latency_ms)
    if class_name is not None:
      with self._lock:
        hist = self._class(class_name).latency
      hist.record(latency_ms)
      self._registry.histogram(
          f"serving/class/{class_name}/latency_ms").record(latency_ms)

  def record_queue_wait_ms(self, wait_ms: float,
                           class_name: Optional[str] = None) -> None:
    """One flushed request's time from enqueue to the start of its
    flush: the part of its latency that no flush of its own explains."""
    self.queue_wait.record(wait_ms)
    self._registry.histogram("serving/queue_wait_ms").record(wait_ms)
    if class_name is not None:
      self._registry.histogram(
          f"serving/class/{class_name}/queue_wait_ms").record(wait_ms)

  def snapshot(self) -> Dict[str, float]:
    """One dict: counters + derived ratios + latency percentiles, plus
    a ``per_class`` sub-dict keyed by SLO class name (empty when no
    class-tagged traffic was recorded)."""
    with self._lock:
      flushes = self._flushes
      out = {
          "requests": self._requests,
          "logical_requests": self._logical_requests,
          "flushes": flushes,
          "deadline_flushes": self._deadline_flushes,
          "overlapped_flushes": self._overlapped_flushes,
          "encode_once_flushes": self._encode_once_flushes,
          "staged_flushes": self._staged_flushes,
          "flush_overlap_share": round(
              self._overlapped_flushes / flushes, 4) if flushes else None,
          "batch_occupancy": round(
              self._occupied_slots / self._padded_slots, 4)
          if self._padded_slots else None,
          "padding_waste": round(
              1.0 - self._occupied_slots / self._padded_slots, 4)
          if self._padded_slots else None,
          "mean_batch_size": round(self._occupied_slots / flushes, 3)
          if flushes else None,
          "mean_queue_depth_after_flush": round(
              self._queue_depth_sum / flushes, 3) if flushes else None,
      }
      # Per-class entries are built while still holding the lock so
      # sum(per_class shed) always equals shed_total within ONE
      # snapshot, even with dispatcher threads recording concurrently.
      # (Lock order ServingStats -> LatencyHistogram; no path takes
      # the reverse order.)
      per_class = {name: self._class_snapshot(cls)
                   for name, cls in sorted(self._per_class.items())}
      shed_total = sum(entry["shed"] for entry in per_class.values())
      if self._program_ms[1]:  # a flush took the device path
        out["transfers_hidden"] = self._transfers_hidden
        # The mean program of the holds that were split over the mean
        # period of all holds, each replica's holds against its own
        # periods: of the time between the replicas' first holds and
        # their last (a replica gone quiet keeps its share).
        (program_ms, split), (period_ms, periods) = (self._program_ms,
                                                     self._period_ms)
        out["program_busy_share"] = round(
            program_ms / split * periods / period_ms, 4) if periods else None
        for name, hist in (("turn_wait", self.turn_wait),
                           ("transfer_wait", self.transfer_wait),
                           ("program", self.program)):
          for pct in (50, 95):
            out[f"{name}_p{pct}_ms"] = hist.percentile(pct)
    out["shed_total"] = shed_total
    for key, value in self.latency.summary().items():
      out["latency_" + key if not key.startswith("count") else
          "latency_samples"] = value
    for pct in (50, 99):
      out[f"queue_wait_p{pct}_ms"] = self.queue_wait.percentile(pct)
    out["per_class"] = per_class
    q_sketches = self.q_sketch_summaries()
    if q_sketches:
      out["q_sketches"] = q_sketches
    return out

  @staticmethod
  def _class_snapshot(cls: _ClassStats) -> Dict[str, float]:
    shed = cls.shed_expired + cls.shed_capacity + cls.shed_fault
    entry = {
        "requests": cls.requests,
        "shed": shed,
        "shed_expired": cls.shed_expired,
        "shed_capacity": cls.shed_capacity,
        "shed_fault": cls.shed_fault,
        "shed_rate": round(shed / cls.requests, 4) if cls.requests else 0.0,
    }
    for key, value in cls.latency.summary().items():
      entry["latency_" + key if not key.startswith("count") else
            "latency_samples"] = value
    return entry

  def write_to(self, metric_writer, step: int,
               prefix: str = "serving/") -> None:
    """Routes the snapshot's numeric fields through a MetricWriter.

    Per-class fields flatten onto the existing schema as
    ``{prefix}class/{name}/{field}`` — the same write_scalars call the
    global counters use, so a dashboard keyed on the serving/ namespace
    picks up class latency/shed series with no new plumbing.
    """
    snap = self.snapshot()
    scalars = {prefix + k: v for k, v in snap.items()
               if isinstance(v, (int, float)) and v is not None}
    for name, entry in snap.get("per_class", {}).items():
      scalars.update({
          f"{prefix}class/{name}/{k}": v for k, v in entry.items()
          if isinstance(v, (int, float)) and v is not None})
    metric_writer.write_scalars(step, scalars)

"""Fleet serving layer: batcher, bucketing, batched CEM, smoke CLI.

CPU-mesh tests for the properties the serving subsystem exists to
provide (ISSUE 1): deadline-driven flushing, bucket padding that never
recompiles within the ladder, FIFO fairness, per-request determinism
(a request's action is independent of flush composition), and the
`--fleet --smoke` CLI lane that exercises the whole path — micro-batch
amortization included — on every PR without a TPU.
"""

import json
import os
import queue
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestBucketLadder:

  def test_bucket_for(self):
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    ladder = BucketLadder((1, 2, 4, 8, 16))
    assert [ladder.bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9, 16)] == [
        1, 2, 4, 4, 8, 8, 16, 16]
    with pytest.raises(ValueError):
      ladder.bucket_for(0)
    with pytest.raises(ValueError):
      ladder.bucket_for(17)

  def test_pad_to_repeats_last_row(self):
    from tensor2robot_tpu.serving.bucketing import BucketLadder, pad_to
    ladder = BucketLadder((1, 2, 4))
    batch = np.arange(6, dtype=np.float32).reshape(3, 2)
    bucket = ladder.bucket_for(len(batch))
    padded = pad_to(batch, bucket)
    assert bucket == 4 and padded.shape == (4, 2)
    np.testing.assert_array_equal(padded[:3], batch)
    np.testing.assert_array_equal(padded[3], batch[2])
    exact = batch[:2]  # an exact fit is handed back as it came
    assert ladder.bucket_for(2) == 2 and pad_to(exact, 2) is exact

  def test_invalid_ladder(self):
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    with pytest.raises(ValueError):
      BucketLadder(())
    with pytest.raises(ValueError):
      BucketLadder((0, 2))


class TestLatencyHistogram:

  def test_percentiles(self):
    from tensor2robot_tpu.serving.stats import LatencyHistogram
    hist = LatencyHistogram()
    for v in range(1, 101):  # 1..100 ms
      hist.record(float(v))
    summary = hist.summary()
    assert summary["count"] == 100
    assert summary["p50_ms"] == 50.0
    assert summary["p99_ms"] == 99.0
    assert summary["max_ms"] == 100.0

  def test_empty(self):
    from tensor2robot_tpu.serving.stats import LatencyHistogram
    assert LatencyHistogram().summary() == {"count": 0}
    assert LatencyHistogram().percentile(50) is None


class TestMicroBatcher:

  def _collecting_batcher(self, flush_sizes, **kwargs):
    from tensor2robot_tpu.serving.batcher import MicroBatcher

    def batch_fn(items):
      flush_sizes.append(len(items))
      return list(items)  # identity: result == submitted item

    return MicroBatcher(batch_fn, **kwargs)

  def test_deadline_flushes_partial_batch(self):
    """A lone client's frame must not wait for a batch that will never
    fill: the flush fires once the oldest request's budget expires."""
    sizes = []
    with self._collecting_batcher(sizes, max_batch=8,
                                  deadline_ms=30.0) as batcher:
      start = time.perf_counter()
      futures = [batcher.submit(i) for i in (10, 11, 12)]
      results = [f.result(timeout=10) for f in futures]
      elapsed = time.perf_counter() - start
    assert results == [10, 11, 12]
    assert sizes == [3]          # one partial flush, not three singles
    assert elapsed >= 0.025      # ... but only after the deadline budget
    assert elapsed < 5.0

  def test_full_batch_flushes_immediately(self):
    """max_batch pending requests flush without waiting the deadline."""
    sizes = []
    with self._collecting_batcher(sizes, max_batch=4,
                                  deadline_ms=10_000.0) as batcher:
      futures = [batcher.submit(i) for i in range(8)]
      results = [f.result(timeout=10) for f in futures]
    assert results == list(range(8))
    assert sizes == [4, 4]       # never waited the 10s deadline

  def test_fifo_fairness(self):
    """Flushes take the HEAD of the queue: early requests are never
    starved by later arrivals, and results map back to their futures."""
    order = []
    from tensor2robot_tpu.serving.batcher import MicroBatcher

    def batch_fn(items):
      order.extend(items)
      time.sleep(0.005)  # keep a backlog while more requests arrive
      return [item * 100 for item in items]

    with MicroBatcher(batch_fn, max_batch=2, deadline_ms=5.0) as batcher:
      futures = [batcher.submit(i) for i in range(10)]
      results = [f.result(timeout=10) for f in futures]
    assert order == sorted(order), f"flushes reordered requests: {order}"
    assert results == [i * 100 for i in range(10)]

  def test_batch_fn_exception_fails_only_that_flush(self):
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    calls = {"n": 0}

    def flaky(items):
      calls["n"] += 1
      if calls["n"] == 1:
        raise RuntimeError("boom")
      return list(items)

    with MicroBatcher(flaky, max_batch=2, deadline_ms=5.0) as batcher:
      first = [batcher.submit(i) for i in range(2)]
      for f in first:
        with pytest.raises(RuntimeError):
          f.result(timeout=10)
      # The dispatcher survived; the next flush succeeds.
      assert batcher.submit(7).result(timeout=10) == 7

  def test_cancelled_request_does_not_kill_dispatcher(self):
    """A client that gives up (future.cancel() after a result timeout)
    must not poison the flush: the cancelled request is dropped and the
    dispatcher keeps serving everyone else (regression: set_result on a
    cancelled future raised on the dispatcher thread and hung the
    whole batcher)."""
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    release = threading.Event()

    def slow(items):
      release.wait(5)
      return list(items)

    with MicroBatcher(slow, max_batch=4, deadline_ms=1.0) as batcher:
      first = batcher.submit(1)   # deadline-flushes alone; blocks in slow
      time.sleep(0.05)
      second = batcher.submit(2)  # queued behind the in-flight flush
      assert second.cancel()      # client gives up while still pending
      release.set()
      assert first.result(timeout=10) == 1
      # The dispatcher survived the cancelled request.
      assert batcher.submit(3).result(timeout=10) == 3
    assert second.cancelled()

  def test_stop_drains_queue(self):
    sizes = []
    batcher = self._collecting_batcher(sizes, max_batch=4,
                                       deadline_ms=10_000.0)
    batcher.start()
    futures = [batcher.submit(i) for i in range(3)]
    batcher.stop()  # queue below max_batch, deadline far away: drained
    assert [f.result(timeout=1) for f in futures] == [0, 1, 2]
    with pytest.raises(RuntimeError):
      batcher.submit(99)

  def test_stats_recorded(self):
    from tensor2robot_tpu.serving.stats import ServingStats
    stats = ServingStats()
    sizes = []
    with self._collecting_batcher(
        sizes, max_batch=8, deadline_ms=20.0, stats=stats,
        bucket_for=lambda n: 8) as batcher:
      [f.result(timeout=10) for f in [batcher.submit(i) for i in range(3)]]
    snap = stats.snapshot()
    assert snap["requests"] == 3
    assert snap["flushes"] == 1
    assert snap["deadline_flushes"] == 1
    assert snap["batch_occupancy"] == pytest.approx(3 / 8)
    assert snap["padding_waste"] == pytest.approx(5 / 8)
    assert snap["latency_samples"] == 3
    # Waited out the ~20ms deadline (small slack: cond.wait may return
    # a hair early on coarse clocks).
    assert snap["latency_p50_ms"] >= 18.0

  def test_queue_wait_is_exported_apart_from_service_time(self):
    """Held for a known time, every request's wait (enqueue to the
    start of its flush) reads at least that time and no more than its
    latency: in the stats, in the registry histogram and on the
    serve/flush span, whose duration is the rest of the latency."""
    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving.stats import ServingStats

    registry = MetricRegistry()
    stats = ServingStats(registry=registry)
    hold_s, service_s = 0.08, 0.03

    def batch_fn(items):
      time.sleep(service_s)
      return list(items)

    from tensor2robot_tpu.serving.batcher import MicroBatcher
    with MicroBatcher(batch_fn, max_batch=4, deadline_ms=0.0,
                      stats=stats) as batcher:
      with batcher.hold_flushes():
        futures = [batcher.submit(i, request_id=f"wait-{i}")
                   for i in range(3)]
        time.sleep(hold_s)
      [f.result(timeout=10) for f in futures]
    snap = stats.snapshot()
    assert hold_s * 1e3 <= snap["queue_wait_p50_ms"] <= snap[
        "queue_wait_p99_ms"]
    assert snap["queue_wait_p99_ms"] <= snap["latency_max_ms"] - (
        service_s * 1e3 * 0.9)
    waits = registry.snapshot()
    assert waits["serving/queue_wait_ms/count"] == 3
    assert waits["serving/queue_wait_ms/p50"] >= hold_s * 1e3
    (flush,) = [s for s in trace_lib.get_tracer().spans()
                if s["name"] == "serve/flush"
                and "wait-0" in str(s.get("request_ids"))]
    assert flush["batch"] == 3
    assert hold_s * 1e3 <= flush["queue_wait_ms_max"] <= snap[
        "latency_max_ms"]
    assert (3 * hold_s * 1e3 <= flush["queue_wait_ms_sum"]
            <= 3 * flush["queue_wait_ms_max"])
    # Wait + the span = the latency (the future resolves just after).
    assert flush["queue_wait_ms_max"] + flush["dur_s"] * 1e3 == (
        pytest.approx(snap["latency_max_ms"], abs=5.0))

  def test_snapshot_without_flushes_has_no_queue_wait(self):
    from tensor2robot_tpu.serving.stats import ServingStats
    snap = ServingStats().snapshot()
    assert snap["queue_wait_p50_ms"] is None
    assert snap["queue_wait_p99_ms"] is None


class TestSLOBatcher:
  """ISSUE 10: EDF admission, priority shedding, and the deadline edge
  cases (expired-at-enqueue sheds immediately; zero-slack deadlines
  must not busy-spin the dispatcher)."""

  def test_expired_at_enqueue_shed_immediately(self):
    """A request whose deadline is already past when it reaches the
    queue (an upstream hop ate the budget) is shed on arrival: counted
    per class, NEVER dispatched, and the shed is visible to the client
    as RequestShed."""
    import time as time_mod

    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import RequestShed, SLOClass
    from tensor2robot_tpu.serving.stats import ServingStats

    dispatched = []
    stats = ServingStats()
    with MicroBatcher(lambda items: [dispatched.append(i) or i
                                     for i in items],
                      max_batch=4, deadline_ms=50.0,
                      stats=stats) as batcher:
      expired = batcher.submit(
          "dead", slo=SLOClass("interactive", 2, 30.0),
          deadline_at=time_mod.perf_counter() - 0.01)
      with pytest.raises(RequestShed) as info:
        expired.result(timeout=5)
      assert info.value.reason == "expired"
      assert info.value.class_name == "interactive"
      # A negative class budget is the same case without deadline_at.
      with pytest.raises(RequestShed):
        batcher.submit("dead2",
                       slo=SLOClass("stale", 0, -1.0)).result(timeout=5)
      # The batcher still serves live traffic afterwards.
      assert batcher.submit("alive").result(timeout=5) == "alive"
    assert "dead" not in dispatched and "dead2" not in dispatched
    snap = stats.snapshot()
    assert snap["per_class"]["interactive"]["shed_expired"] == 1
    assert snap["per_class"]["stale"]["shed_expired"] == 1
    assert snap["shed_total"] == 2
    # Shed requests were still offered load: counted as requests.
    assert snap["per_class"]["interactive"]["requests"] == 1

  def test_zero_slack_deadline_does_not_busy_spin(self):
    """deadline_ms=0 means "flush me immediately" — it must flush (not
    shed) and must not leave the dispatcher re-arming a zero-length
    wait in a loop. Regression guard: the dispatcher's loop-iteration
    counter stays bounded while the batcher sits idle after zero-slack
    traffic."""
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import SLOClass

    zero = SLOClass("now", 1, 0.0)
    with MicroBatcher(lambda items: list(items), max_batch=8,
                      deadline_ms=10_000.0) as batcher:
      for i in range(5):
        assert batcher.submit(i, slo=zero).result(timeout=5) == i
      settle = batcher._dispatch_iterations
      time.sleep(0.25)  # idle window: a spinner racks up iterations
      assert batcher._dispatch_iterations - settle <= 2, (
          "dispatcher busy-spun while idle")
      # Still responsive after the idle window.
      assert batcher.submit(99, slo=zero).result(timeout=5) == 99

  def test_expired_submit_on_stopped_batcher_raises(self):
    """Lifecycle beats shedding: an expired-deadline submit on a
    stopped (or never-started) batcher raises RuntimeError like any
    other submit — a dead batcher must not dress the caller's bug up
    as ordinary load shedding."""
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import SLOClass

    batcher = MicroBatcher(lambda items: list(items))
    with pytest.raises(RuntimeError):
      batcher.submit("x", slo=SLOClass("stale", 0, -1.0))

  def test_stop_during_hold_flushes_drains(self):
    """stop() overrides an active hold: the queued requests drain
    instead of the join deadlocking behind the gate."""
    from tensor2robot_tpu.serving.batcher import MicroBatcher

    with MicroBatcher(lambda items: list(items), max_batch=4,
                      deadline_ms=10_000.0) as batcher:
      with batcher.hold_flushes():
        futures = [batcher.submit(i) for i in range(3)]
        batcher.stop()  # must drain despite the hold, not hang
      assert [f.result(timeout=5) for f in futures] == [0, 1, 2]

  def test_edf_tighter_class_overtakes(self):
    """A later-arriving tighter-deadline request flushes before an
    earlier lax one (EDF), while same-class traffic stays FIFO."""
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import SLOClass

    lax = SLOClass("lax", 0, 500.0)
    tight = SLOClass("tight", 2, 10.0)
    order = []

    def batch_fn(items):
      order.extend(items)
      return list(items)

    with MicroBatcher(batch_fn, max_batch=1,
                      deadline_ms=500.0) as batcher:
      futures = [batcher.submit(("lax", i), slo=lax) for i in range(2)]
      futures.append(batcher.submit(("tight", 0), slo=tight))
      for f in futures:
        f.result(timeout=10)
    assert order[0] == ("tight", 0), order
    assert order[1:] == [("lax", 0), ("lax", 1)], order

  def test_capacity_shed_lowest_priority_first(self):
    """With the queue at its bound, an arrival evicts the LOWEST
    priority pending request — high-priority traffic rides through an
    overload while the batch tier sheds, with per-class accounting."""
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import RequestShed, SLOClass
    from tensor2robot_tpu.serving.stats import ServingStats

    high = SLOClass("high", 2, 5_000.0)
    low = SLOClass("low", 0, 5_000.0)
    stats = ServingStats()
    release = threading.Event()

    def slow(items):
      release.wait(10)
      return list(items)

    with MicroBatcher(slow, max_batch=1, deadline_ms=0.0, stats=stats,
                      max_queue=2) as batcher:
      blocker = batcher.submit("blocker")   # in flight, holds the loop
      time.sleep(0.05)
      low_fut = batcher.submit("low", slo=low)       # queued
      high1 = batcher.submit("high1", slo=high)      # queued (full now)
      high2 = batcher.submit("high2", slo=high)      # evicts "low"
      with pytest.raises(RequestShed) as info:
        low_fut.result(timeout=5)
      assert info.value.reason == "capacity"
      # An arrival that is ITSELF the lowest priority is the victim.
      with pytest.raises(RequestShed):
        batcher.submit("low2", slo=low).result(timeout=5)
      release.set()
      assert blocker.result(timeout=10) == "blocker"
      assert high1.result(timeout=10) == "high1"
      assert high2.result(timeout=10) == "high2"
    snap = stats.snapshot()
    assert snap["per_class"]["low"]["shed_capacity"] == 2
    assert snap["per_class"]["high"]["shed"] == 0
    assert snap["per_class"]["high"]["requests"] == 2

  def test_per_class_stats_metric_writer_emission(self, tmp_path):
    """ISSUE 10 satellite: class-keyed latency histograms and shed
    counters flow through the EXISTING metric_writer schema as
    serving/class/<name>/<field> scalars, alongside the global p50/p99."""
    import json as json_mod

    from tensor2robot_tpu.serving.stats import ServingStats
    from tensor2robot_tpu.utils.metric_writer import MetricWriter

    stats = ServingStats()
    for latency in (5.0, 10.0, 15.0):
      stats.record_request("interactive")
      stats.record_latency_ms(latency, "interactive")
    stats.record_request("batch")
    stats.record_shed("batch", "capacity")
    stats.record_request("batch")
    stats.record_shed("batch", "expired")

    snap = stats.snapshot()
    assert snap["per_class"]["interactive"]["latency_p50_ms"] == 10.0
    assert snap["per_class"]["interactive"]["shed"] == 0
    assert snap["per_class"]["batch"]["shed_capacity"] == 1
    assert snap["per_class"]["batch"]["shed_expired"] == 1
    assert snap["per_class"]["batch"]["shed_rate"] == 1.0
    assert snap["shed_total"] == 2

    writer = MetricWriter(str(tmp_path))
    stats.write_to(writer, step=7)
    writer.close()
    with open(tmp_path / "metrics.jsonl") as f:
      record = json_mod.loads(f.readlines()[-1])
    assert record["serving/class/interactive/latency_p50_ms"] == 10.0
    assert record["serving/class/interactive/requests"] == 3
    assert record["serving/class/batch/shed_capacity"] == 1
    assert record["serving/class/batch/shed_expired"] == 1
    assert record["serving/shed_total"] == 2
    # The pre-existing global fields survive unchanged.
    assert record["serving/requests"] == 5
    assert "serving/latency_p50_ms" in record


def _wait_until(predicate, timeout=10.0):
  """Waits on STATE, with a limit: polls until `predicate()` holds."""
  deadline = time.monotonic() + timeout
  while not predicate():
    assert time.monotonic() < deadline, "condition not reached in time"
    time.sleep(0.001)


class _GatedFn:
  """A batch_fn whose every call announces itself on `entered` (its
  items) and blocks until `release(first item)`; `log` keeps the order
  of entries and exits, `calls` each call's items."""

  def __init__(self):
    self.entered = queue.Queue()
    self.log = []
    self.calls = []
    self._gates = {}
    self._lock = threading.Lock()

  def _gate(self, key):
    with self._lock:
      return self._gates.setdefault(key, threading.Event())

  def __call__(self, items):
    items = list(items)
    self.log.append(("enter", items[0]))
    self.calls.append(items)
    self.entered.put(items)
    assert self._gate(items[0]).wait(timeout=10), "gate never released"
    self.log.append(("exit", items[0]))
    return items

  def release(self, first_item):
    self._gate(first_item).set()


def _flush_span(request_id):
  from tensor2robot_tpu.obs import trace as trace_lib
  (span,) = [s for s in trace_lib.get_tracer().spans()
             if s["name"] == "serve/flush"
             and request_id in str(s.get("request_ids", "")).split(",")]
  return span


class TestOverlappedFlushes:
  """ISSUE 31: `flush_depth` dispatchers share the queue. A full batch
  is popped while another flush is open; a partial one only once none
  is, so a load under one full batch per flush time is served exactly
  as at depth 1. Every flush is gated on events: nothing here waits on
  elapsed time."""

  def _batcher(self, fn, depth, **kwargs):
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    kwargs.setdefault("deadline_ms", 10_000.0)
    return MicroBatcher(fn, flush_depth=depth, **kwargs)

  @pytest.mark.parametrize("depth", [1, 2])
  def test_second_full_batch_enters_while_the_first_is_blocked(
      self, depth):
    from tensor2robot_tpu.serving.stats import ServingStats
    fn, stats = _GatedFn(), ServingStats()
    tag = f"overlap-d{depth}"
    with self._batcher(fn, depth, max_batch=2, stats=stats) as batcher:
      futures = [batcher.submit(i, request_id=f"{tag}-{i}")
                 for i in range(4)]
      assert fn.entered.get(timeout=10) == [0, 1]
      if depth == 2:
        # In batch_fn while the first flush is still blocked in it.
        assert fn.entered.get(timeout=10) == [2, 3]
        assert batcher.pending() == 4
      fn.release(0)
      fn.release(2)
      assert [f.result(timeout=10) for f in futures] == [0, 1, 2, 3]
    order = [event for event, _ in fn.log]
    assert order == (["enter", "enter", "exit", "exit"] if depth == 2
                     else ["enter", "exit", "enter", "exit"]), fn.log
    assert _flush_span(f"{tag}-0")["in_flight"] == 0
    assert _flush_span(f"{tag}-2")["in_flight"] == depth - 1
    snap = stats.snapshot()
    assert snap["flushes"] == 2
    assert snap["overlapped_flushes"] == depth - 1
    assert snap["flush_overlap_share"] == pytest.approx((depth - 1) / 2)

  def test_partial_batch_waits_for_the_open_flush_then_ships_at_once(
      self):
    """deadline 0: the partial batch's flush time has passed the
    moment it arrives, and still it is not popped while a flush is
    open; the end of that flush ships it (no timer involved)."""
    from tensor2robot_tpu.serving.stats import ServingStats
    fn, stats = _GatedFn(), ServingStats()
    with self._batcher(fn, 2, max_batch=4, deadline_ms=0.0,
                       stats=stats) as batcher:
      full = [batcher.submit(i) for i in range(4)]
      assert fn.entered.get(timeout=10) == [0, 1, 2, 3]
      seen = batcher._dispatch_iterations
      partial = [batcher.submit(i, request_id=f"partial-{i}")
                 for i in (4, 5)]
      # The free dispatcher has looked at the queue and declined.
      _wait_until(lambda: batcher._dispatch_iterations > seen)
      assert fn.entered.empty() and batcher._open_flushes == 1
      settle = batcher._dispatch_iterations
      fn.release(0)
      assert [f.result(timeout=10) for f in full] == [0, 1, 2, 3]
      assert fn.entered.get(timeout=10) == [4, 5]
      fn.release(4)
      assert [f.result(timeout=10) for f in partial] == [4, 5]
      # Declining did not spin: a handful of passes, not a loop.
      assert batcher._dispatch_iterations - settle <= 8
    assert _flush_span("partial-4")["in_flight"] == 0
    snap = stats.snapshot()
    assert snap["overlapped_flushes"] == 0
    assert snap["deadline_flushes"] == 1  # the partial one

  @pytest.mark.parametrize("depth", [1, 2])
  def test_under_a_full_batch_per_flush_the_sizes_are_depth_ones(
      self, depth):
    """One scripted arrival sequence, never a full batch pending:
    whatever arrives during a flush ships together when it ends."""
    fn = _GatedFn()
    with self._batcher(fn, depth, max_batch=4,
                       deadline_ms=0.0) as batcher:
      futures = []
      with batcher.hold_flushes():
        futures += [batcher.submit(i) for i in (0, 1, 2)]
      assert fn.entered.get(timeout=10) == [0, 1, 2]
      open_flush = 0
      for arrivals in ([3, 4], [5]):
        seen = batcher._dispatch_iterations
        futures.append(batcher.submit(arrivals[0]))
        if depth == 2:  # the free dispatcher wakes, and has to decline
          _wait_until(lambda: batcher._dispatch_iterations > seen)
        futures += [batcher.submit(i) for i in arrivals[1:]]
        fn.release(open_flush)
        assert fn.entered.get(timeout=10) == arrivals
        open_flush = arrivals[0]
      fn.release(open_flush)
      assert [f.result(timeout=10) for f in futures] == list(range(6))
    assert fn.calls == [[0, 1, 2], [3, 4], [5]]

  def test_stop_with_two_flushes_open_resolves_every_future(self):
    fn = _GatedFn()
    batcher = self._batcher(fn, 2, max_batch=2).start()
    futures = [batcher.submit(i) for i in range(5)]
    assert sorted([fn.entered.get(timeout=10),
                   fn.entered.get(timeout=10)]) == [[0, 1], [2, 3]]
    stopper = threading.Thread(target=batcher.stop)
    stopper.start()  # joins both dispatchers: blocks on the gates
    fn.release(0)
    fn.release(2)
    assert fn.entered.get(timeout=10) == [4]  # the drain's partial batch
    fn.release(4)
    stopper.join(timeout=10)
    assert not stopper.is_alive()
    assert [f.result(timeout=10) for f in futures] == list(range(5))
    assert batcher._open_flushes == 0 and batcher.pending() == 0
    with pytest.raises(RuntimeError):
      batcher.submit(99)

  def test_hold_flushes_gates_both_dispatchers(self):
    fn = _GatedFn()
    with self._batcher(fn, 2, max_batch=2) as batcher:
      with batcher.hold_flushes():
        seen = batcher._dispatch_iterations
        futures = [batcher.submit(i, request_id=f"held-{i}")
                   for i in range(4)]
        # Two full batches pending and both dispatchers have looked.
        _wait_until(lambda: batcher._dispatch_iterations >= seen + 2)
        assert fn.entered.empty() and batcher._open_flushes == 0
        assert batcher.pending() == 4
      assert sorted([fn.entered.get(timeout=10),
                     fn.entered.get(timeout=10)]) == [[0, 1], [2, 3]]
      fn.release(0)
      fn.release(2)
      assert [f.result(timeout=10) for f in futures] == [0, 1, 2, 3]
    assert sorted(_flush_span(f"held-{i}")["in_flight"]
                  for i in (0, 2)) == [0, 1]

  def test_thread_kill_in_one_open_flush_fails_only_that_batch(self):
    from tensor2robot_tpu.obs import faults
    from tensor2robot_tpu.serving.slo import DispatcherDead
    fn = _GatedFn()
    plan = faults.FaultPlan([faults.FaultSpec(
        kind="thread_kill", point="batcher_flush", site="two", at=1)])
    with self._batcher(fn, 2, max_batch=2, fault_plan=plan, site="two",
                       restart_budget=1) as batcher:
      first = [batcher.submit(i) for i in (0, 1)]
      assert fn.entered.get(timeout=10) == [0, 1]
      # The second flush is popped beside the first and dies at the
      # fault seam: its batch, and only its batch, fails typed.
      killed = [batcher.submit(i) for i in (2, 3)]
      for future in killed:
        with pytest.raises(DispatcherDead):
          future.result(timeout=10)
      _wait_until(lambda: batcher.dispatcher_restarts == 1)
      assert not batcher.dispatcher_dead and not first[0].done()
      # The restarted dispatcher serves beside the still-open flush.
      later = [batcher.submit(i) for i in (4, 5)]
      assert fn.entered.get(timeout=10) == [4, 5]
      fn.release(4)
      assert [f.result(timeout=10) for f in later] == [4, 5]
      fn.release(0)
      assert [f.result(timeout=10) for f in first] == [0, 1]
    assert batcher.dispatcher_restarts == 1
    assert plan.fired_counts() == {"thread_kill": 1}

  def test_hung_flush_is_reported_while_the_other_dispatcher_runs(self):
    from tensor2robot_tpu.obs import watchdog as watchdog_lib
    from tensor2robot_tpu.obs.registry import MetricRegistry
    fn = _GatedFn()
    watchdog = watchdog_lib.Watchdog(default_deadline_s=30.0,
                                     registry=MetricRegistry())
    with self._batcher(fn, 2, max_batch=2,
                       watchdog=watchdog) as batcher:
      hung = [batcher.submit(i) for i in (0, 1)]
      assert fn.entered.get(timeout=10) == [0, 1]  # never released: hung
      for first in (2, 4, 6):  # the other dispatcher keeps beating
        served = [batcher.submit(i) for i in (first, first + 1)]
        assert fn.entered.get(timeout=10) == [first, first + 1]
        fn.release(first)
        assert [f.result(timeout=10) for f in served] == [first, first + 1]
      heartbeats = list(batcher._heartbeats)
      _wait_until(lambda: sum(h.is_idle for h in heartbeats) == 1)
      (running,) = [h for h in heartbeats if h.is_idle]
      (wedged,) = [h for h in heartbeats if not h.is_idle]
      assert running.beats == 3 and wedged.beats == 1
      events = watchdog.check_once(now=time.monotonic() + 60.0)
      assert [e["component"] for e in events] == [wedged.name]
      assert events[0]["event"] == "watchdog_stall"
      fn.release(0)
      assert [f.result(timeout=10) for f in hung] == [0, 1]

  def test_depth_must_be_positive(self):
    with pytest.raises(ValueError, match="flush_depth"):
      self._batcher(lambda items: list(items), 0)


class TestHotReloadLedger:

  def test_param_refresh_never_recompiles_bucket_executables(self):
    """ISSUE 10 satellite: the RolloutController promotion path is
    predictor.set_variables on a live CEMFleetPolicy — across >= 3
    refreshes the compile ledger must be BIT-stable: same buckets, all
    counts exactly 1, and the very same executable objects serving
    (params are arguments, never baked in)."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    from tensor2robot_tpu.serving.smoke import TinyQPredictor

    predictor = TinyQPredictor(image_size=8, action_size=4, seed=0)
    original_w = np.array(predictor._variables["params"]["w"])
    policy = CEMFleetPolicy(predictor, action_size=4, num_samples=32,
                            num_elites=4, iterations=2, seed=0)
    for n in (1, 2, 3, 8, 16):  # touches every ladder bucket
      policy([predictor.make_image(i) for i in range(n)])
    ledger_before = dict(policy.compile_counts)
    executables_before = {bucket: id(executable) for bucket, executable
                          in policy._executables.items()}
    assert all(count == 1 for count in ledger_before.values())

    for refresh in range(3):
      predictor.set_variables(
          predictor.make_candidate_variables(jitter=0.1,
                                             seed=refresh + 1))
      for n in (2, 5, 16):
        actions = policy([predictor.make_image(10 * refresh + i)
                          for i in range(n)])
        assert actions.shape == (n, 4)
      assert dict(policy.compile_counts) == ledger_before, (
          f"refresh {refresh} changed the ledger")
      assert {bucket: id(executable) for bucket, executable
              in policy._executables.items()} == executables_before, (
                  f"refresh {refresh} swapped an executable object")
    assert predictor.model_version == 3
    # The refreshed params actually serve: the action lands closer to
    # the NEW weights' optimum than the original weights' (a stale
    # variables cache would still answer the old one).
    image = predictor.make_image(77)
    action = policy([image])[0]
    flat = np.asarray(image, np.float32).reshape(1, -1)
    old_optimum = np.tanh(flat @ original_w)[0]
    new_optimum = predictor.best_action(image)
    assert (np.linalg.norm(action - new_optimum)
            < np.linalg.norm(action - old_optimum))

  def test_checkpoint_predictor_rejects_shape_or_dtype_drift(self):
    """The promotion guard must fail a malformed candidate HERE, not
    as an aval mismatch inside some replica's next AOT flush: both a
    reshape and a dtype change are rejected; a well-formed swap with a
    version lands."""
    import jax

    from tensor2robot_tpu.predictors.checkpoint_predictor import (
        CheckpointPredictor)
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel

    predictor = CheckpointPredictor(
        TinyQCriticModel(image_size=8, action_size=4))
    predictor.init_randomly()
    good = jax.tree_util.tree_map(np.asarray, predictor._variables)
    wrong_dtype = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float64), good)
    with pytest.raises(ValueError, match="dtype"):
      predictor.set_variables(wrong_dtype)
    wrong_shape = jax.tree_util.tree_map(
        lambda x: np.concatenate([x, x], axis=0), good)
    with pytest.raises(ValueError, match="shape"):
      predictor.set_variables(wrong_shape)
    predictor.set_variables(good, version=42)
    assert predictor.model_version == 42

  def test_set_variables_version_keeps_staleness_namespace(self):
    """A promotion carries the candidate's export step: model_version
    adopts it (so a restore() poll finding an OLDER on-disk checkpoint
    cannot overwrite the promoted params), stays monotonic when the
    passed version would regress, and falls back to +1 without one."""
    from tensor2robot_tpu.serving.smoke import TinyQPredictor

    predictor = TinyQPredictor(seed=0)
    predictor.set_variables(predictor.make_candidate_variables(),
                            version=250)
    assert predictor.model_version == 250
    predictor.set_variables(predictor.make_candidate_variables(),
                            version=150)  # older step: clamp, not regress
    assert predictor.model_version == 251
    predictor.set_variables(predictor.make_candidate_variables())
    assert predictor.model_version == 252


@pytest.fixture(scope="module")
def tiny_predictor():
  from tensor2robot_tpu.serving.smoke import TinyQPredictor
  return TinyQPredictor(image_size=8, action_size=4, seed=0)


@pytest.fixture(scope="module")
def fleet_policy(tiny_predictor):
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy
  return CEMFleetPolicy(tiny_predictor, action_size=4, num_samples=64,
                        num_elites=6, iterations=3, seed=0)


class TestCEMFleetPolicy:

  def test_bucketed_execution_never_recompiles_within_ladder(
      self, fleet_policy, tiny_predictor):
    """Every batch size in 1..16 is served by the fixed ladder with
    EXACTLY one compiled executable per bucket — the bounded-signature
    property (pjit playbook) the ladder exists for."""
    for n in (1, 2, 3, 4, 5, 7, 8, 11, 16, 3, 16, 1):
      images = [tiny_predictor.make_image(i) for i in range(n)]
      actions = fleet_policy(images)
      assert actions.shape == (n, 4)
    assert list(fleet_policy.executable_buckets) == [1, 2, 4, 8, 16]
    assert all(count == 1
               for count in fleet_policy.compile_counts.values()), (
                   fleet_policy.compile_counts)

  def test_per_request_results_independent_of_flush_composition(
      self, fleet_policy, tiny_predictor):
    """A request's action depends on (image, seed) only — not on batch
    position, co-batched requests, or bucket padding."""
    images = [tiny_predictor.make_image(i) for i in range(3)]
    seeds = [5, 9, 13]
    together = fleet_policy(images, seeds)          # bucket 4 (padded)
    alone = np.concatenate([
        fleet_policy([img], [seed])                 # bucket 1
        for img, seed in zip(images, seeds)])
    np.testing.assert_allclose(together, alone, atol=1e-4)
    reversed_out = fleet_policy(images[::-1], seeds[::-1])
    np.testing.assert_allclose(together, reversed_out[::-1], atol=1e-4)

  def test_cem_finds_each_requests_own_optimum(self, fleet_policy,
                                               tiny_predictor):
    """Each fleet request converges toward ITS image's analytic argmax:
    any cross-request mixup in the vmapped CEM or the padding would
    drag an action toward a different request's optimum."""
    images = [tiny_predictor.make_image(100 + i) for i in range(5)]
    optima = np.stack([tiny_predictor.best_action(im) for im in images])
    actions = fleet_policy(images)
    for i, action in enumerate(actions):
      distances = np.linalg.norm(optima - action, axis=-1)
      assert np.argmin(distances) == i, (
          f"request {i} answered toward optimum {np.argmin(distances)}")

  def test_host_call_exact_fit_skips_padding_and_executables(
      self, tiny_predictor, monkeypatch):
    """ISSUE 5 satellite: when the request count already equals a
    ladder rung, the host fallback performs ZERO padding work (no
    pad_to call, no copy) and scores every CEM iteration through ONE
    flat shape per bucket — the old path re-derived a power-of-two
    bucket for the flat (B*num_samples) batch inside predict_batched
    on EVERY iteration, re-padding and re-slicing each time."""
    from tensor2robot_tpu.serving import bucketing
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    pad_sizes = []
    real_pad_to = bucketing.pad_to

    def spying_pad_to(batch, size):
      pad_sizes.append(size)
      return real_pad_to(batch, size)

    monkeypatch.setattr(bucketing, "pad_to", spying_pad_to)
    flat_sizes = []

    class HostOnly:
      def __init__(self, inner):
        self._inner = inner

      def device_fn(self):
        raise NotImplementedError

      def predict(self, features):
        flat_sizes.append(np.asarray(features["image"]).shape[0])
        return self._inner.predict(features)

      def __getattr__(self, name):
        return getattr(self._inner, name)

    iterations, num = 3, 32
    policy = CEMFleetPolicy(HostOnly(tiny_predictor), action_size=4,
                            num_samples=num, num_elites=4,
                            iterations=iterations, seed=3)
    images = [tiny_predictor.make_image(i) for i in range(4)]
    actions = policy(images)  # 4 is a ladder rung: exact fit
    assert actions.shape == (4, 4)
    assert pad_sizes == []  # no padding work at exact fit
    # One flat scoring shape (one executable's worth of work), one
    # call per CEM iteration — nothing extra.
    assert flat_sizes == [4 * num] * iterations
    # Non-exact fit pads ONCE up front (the seeds through pad_to, the
    # frames in place in the staging array), never per iteration, and
    # scores the same bucket shape.
    pad_sizes.clear()
    flat_sizes.clear()
    assert policy(images[:3]).shape == (3, 4)
    assert pad_sizes == [4]
    assert flat_sizes == [4 * num] * iterations

  def test_host_fallback_matches_device_path(self, tiny_predictor):
    """Without device_fn the policy pads to its bucket once and scores
    through predict(); the sampling sequence mirrors the compiled path,
    so both agree (the fleet version of CEMPolicy's device/host parity
    test)."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    class HostOnly:
      def __init__(self, inner):
        self._inner = inner

      def device_fn(self):
        raise NotImplementedError

      def __getattr__(self, name):
        return getattr(self._inner, name)

    kwargs = dict(action_size=4, num_samples=32, num_elites=4,
                  iterations=2, seed=3)
    images = [tiny_predictor.make_image(i) for i in range(3)]
    seeds = [2, 4, 6]
    device_out = CEMFleetPolicy(tiny_predictor, **kwargs)(images, seeds)
    host_out = CEMFleetPolicy(HostOnly(tiny_predictor), **kwargs)(
        images, seeds)
    np.testing.assert_allclose(device_out, host_out, atol=1e-4)

  def test_flush_phases_nest_under_the_replica_dispatch(
      self, tiny_predictor, monkeypatch):
    """One flush through a replica leaves exactly one each of the six
    phase spans, nested under serve/dispatch (itself under the
    batcher's serve/flush), inside it in time, carrying the flush's
    request_ids, and under serve/readback, where the hold is split,
    its two waits; a compile inside a flush has a span of its own and
    lies outside serve/put."""
    import jax

    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving import policy as policy_lib
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.stats import ServingStats

    monkeypatch.setattr(policy_lib, "_SPLIT_EVERY", 1)
    router = FleetRouter(
        tiny_predictor, devices=jax.devices()[:1], num_samples=16,
        num_elites=4, iterations=2, seed=0, ladder_sizes=(1, 4),
        stats=ServingStats(registry=MetricRegistry()))
    router.warmup(tiny_predictor.make_image)
    ids = [f"phase-{i}" for i in range(3)]
    with router:
      with router.replicas[0].batcher.hold_flushes():
        futures = [router.submit(tiny_predictor.make_image(i),
                                 request_id=rid)
                   for i, rid in enumerate(ids)]
      [f.result(timeout=30) for f in futures]
    spans = trace_lib.get_tracer().spans()
    mine = [s for s in spans
            if str(s.get("request_ids", "")) == ",".join(ids)]
    by_name = {}
    for s in mine:
      by_name.setdefault(s["name"], []).append(s)
    phases = ("serve/stack", "serve/pad", "serve/put", "serve/turn",
              "serve/execute", "serve/readback")
    waits = ("serve/transfer_wait", "serve/program_wait")
    assert sorted(by_name) == sorted(
        phases + waits + ("serve/flush", "serve/dispatch")), sorted(by_name)
    assert all(len(rows) == 1 for rows in by_name.values())
    (dispatch,), (flush,) = by_name["serve/dispatch"], by_name["serve/flush"]
    assert dispatch["parent"] == "serve/flush"
    end = lambda s: s["ts_s"] + s["dur_s"]
    for name in phases:
      (phase,) = by_name[name]
      assert phase["parent"] == "serve/dispatch"
      assert phase["tid"] == dispatch["tid"] == flush["tid"]
      assert dispatch["ts_s"] <= phase["ts_s"]
      assert end(phase) <= end(dispatch) + 1e-5
    assert sum(by_name[n][0]["dur_s"] for n in phases) <= (
        dispatch["dur_s"] + 1e-5)
    assert dispatch["dur_s"] <= flush["dur_s"] + 1e-5
    assert by_name["serve/stack"][0]["rows"] == 3
    assert by_name["serve/stack"][0]["bytes"] == 3 * np.asarray(
        tiny_predictor.make_image(0)).nbytes
    assert by_name["serve/pad"][0]["bucket"] == 4
    assert by_name["serve/execute"][0]["bucket"] == 4
    assert by_name["serve/turn"][0]["bucket"] == 4
    assert flush["in_flight"] == 0
    # The hold, split: first the wait for the flush's own frames, then
    # the wait for its program, both inside serve/readback on its
    # thread; what they leave of it is D2H.
    (readback,), (execute,) = by_name["serve/readback"], by_name["serve/execute"]
    (transfer_wait,), (program_wait,) = (by_name[name] for name in waits)
    for wait in (transfer_wait, program_wait):
      assert wait["parent"] == "serve/readback"
      assert wait["tid"] == readback["tid"]
      assert wait["bucket"] == 4
      assert readback["ts_s"] <= wait["ts_s"]
      assert end(wait) <= end(readback) + 1e-5
    assert end(transfer_wait) <= program_wait["ts_s"] + 1e-5
    assert transfer_wait["dur_s"] + program_wait["dur_s"] <= (
        readback["dur_s"] + 1e-5)
    image = np.asarray(tiny_predictor.make_image(0))
    assert transfer_wait["bytes"] == 4 * image.nbytes + 4 * 4  # + seeds
    assert by_name["serve/turn"][0]["landed"] in (0, 1)
    assert 0.0 <= readback["device_ms"] <= 1e3 * (
        execute["dur_s"] + readback["dur_s"]) + 1e-2
    # Warm-up compiled both rungs, each under its own span, none of
    # them inside a put.
    compiles = [s for s in spans if s["name"] == "serve/compile"
                and s["tid"] == threading.get_ident()]
    assert {s["bucket"] for s in compiles[-2:]} == {1, 4}
    assert all(s.get("parent") != "serve/put" for s in compiles)

  def test_concurrent_calls_take_turns_on_the_device(self, tiny_predictor,
                                                     monkeypatch):
    """Two callers at once (a replica with two flushes open): each gets
    the sequential call's actions and scores for every (image, seed),
    nothing recompiles, and the second's program is not enqueued until
    the first's answer is back — while its stack and put run beside it."""
    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.serving import policy as policy_lib
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    monkeypatch.setattr(policy_lib, "_SPLIT_EVERY", 1)
    policy = CEMFleetPolicy(tiny_predictor, action_size=4, num_samples=32,
                            num_elites=4, iterations=2, seed=3)
    batches = [([tiny_predictor.make_image(10 * k + i) for i in range(n)],
                np.arange(100 * k, 100 * k + n, dtype=np.uint32))
               for k, n in ((1, 4), (2, 3))]  # both bucket 4
    with trace_lib.get_tracer().span("test/first_call") as first_call:
      pass
    sequential = [policy(images, seeds, return_scores=True)
                  for images, seeds in batches]
    # The first caller is held inside its device turn until the second
    # has put its inputs and queues for the turn.
    real, gate, inside = policy._executables[4], threading.Event(), []

    def held(*args):
      inside.append(threading.get_ident())
      if len(inside) == 1:
        assert gate.wait(timeout=10)
      return real(*args)

    policy._executables[4] = held
    results, tids = [None, None], [None, None]
    with trace_lib.get_tracer().span("test/concurrent_calls") as mark:
      pass  # thread ids are reused: only spans from here on count

    def call(k):
      tids[k] = threading.get_ident()
      results[k] = policy(*batches[k], return_scores=True)

    threads = [threading.Thread(target=call, args=(k,)) for k in (0, 1)]
    threads[0].start()
    _wait_until(lambda: len(inside) == 1)
    threads[1].start()
    spans_of = lambda k, name: [
        s for s in trace_lib.get_tracer().spans()
        if s["tid"] == tids[k] and s["name"] == name
        and s["ts_s"] >= mark["ts_s"]]
    _wait_until(lambda: tids[1] is not None and spans_of(1, "serve/put"))
    assert len(inside) == 1 and policy._turn.locked()
    gate.set()
    for thread in threads:
      thread.join(timeout=30)
      assert not thread.is_alive()
    for (actions, scores), (seq_actions, seq_scores) in zip(results,
                                                            sequential):
      np.testing.assert_array_equal(actions, seq_actions)
      np.testing.assert_array_equal(scores, seq_scores)
    assert policy.compile_counts == {4: 1}
    end = lambda s: s["ts_s"] + s["dur_s"]
    (execute0,), (readback0,) = (spans_of(0, "serve/execute"),
                                 spans_of(0, "serve/readback"))
    (execute1,), (turn1,), (stack1,) = (
        spans_of(1, "serve/execute"), spans_of(1, "serve/turn"),
        spans_of(1, "serve/stack"))
    assert execute1["ts_s"] >= end(readback0)
    assert turn1["ts_s"] < end(readback0) <= end(turn1) + 1e-5
    # The host phases did overlap the first caller's device turn.
    assert execute0["ts_s"] < stack1["ts_s"] < end(readback0)
    # The programs' own intervals on the device, [end - device_ms, end]
    # with end the end of serve/program_wait, never overlap: the
    # sequential calls', then the two concurrent ones'.
    tracer_spans = [s for s in trace_lib.get_tracer().spans()
                    if s["ts_s"] >= first_call["ts_s"]]
    program_ends = [end(s) for s in tracer_spans
                    if s["name"] == "serve/program_wait"]
    device_ms = [s["device_ms"] for s in tracer_spans
                 if s["name"] == "serve/readback"]
    assert len(program_ends) == len(device_ms) == 4
    intervals = sorted((stop - ms / 1e3, stop)
                       for stop, ms in zip(program_ends, device_ms))
    for (_, stop), (start, _) in zip(intervals, intervals[1:]):
      assert stop <= start + 2e-6, intervals  # the records' rounding

  def test_same_answers_with_and_without_a_ledger(self, tiny_predictor):
    """The device path is one path: a ledger only adds the dispatch
    record (seconds from the phase spans' own clock reads)."""
    from tensor2robot_tpu.obs.ledger import ExecutableLedger
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    kwargs = dict(action_size=4, num_samples=32, num_elites=4,
                  iterations=2, seed=3)
    images = [tiny_predictor.make_image(i) for i in range(3)]
    seeds = [2, 4, 6]
    plain = CEMFleetPolicy(tiny_predictor, **kwargs)
    ledger = ExecutableLedger()
    ledgered = CEMFleetPolicy(tiny_predictor, ledger=ledger, **kwargs)
    actions, scores = plain(images, seeds, return_scores=True)
    ledger_actions, ledger_scores = ledgered(images, seeds,
                                             return_scores=True)
    np.testing.assert_array_equal(actions, ledger_actions)
    np.testing.assert_array_equal(scores, ledger_scores)
    np.testing.assert_array_equal(plain(images, seeds), actions)
    (row,) = ledger.attribution()["executables"]
    assert row["compiles"] == 1 and row["dispatches"] == 1
    assert 0.0 < row["seconds_total"] < 60.0

  # -- staging buffers (ISSUE 37) -------------------------------------------

  _STAGED = dict(action_size=4, num_samples=32, num_elites=4, iterations=2,
                 seed=3)

  @staticmethod
  def _round(predictor, caller, r):
    """Caller `caller`'s round `r`: its own frames and seeds, 4 rows or
    3 (both rung 4, so both callers share one key of the pool)."""
    n = 4 - (r + caller) % 2
    base = 1000 * (caller + 1) + 10 * r
    return ([predictor.make_image(base + i) for i in range(n)],
            np.arange(base, base + n, dtype=np.uint32))

  def _two_callers(self, policy, predictor, rounds):
    """Both callers' rounds from two threads at once: [[(actions,
    scores)] per round] per caller."""
    results, barrier = [[], []], threading.Barrier(2)

    def call(caller):
      barrier.wait(timeout=10)
      for r in range(rounds):
        results[caller].append(policy(*self._round(predictor, caller, r),
                                      return_scores=True))

    threads = [threading.Thread(target=call, args=(k,)) for k in (0, 1)]
    for thread in threads:
      thread.start()
    for thread in threads:
      thread.join(timeout=120)
      assert not thread.is_alive()
    return results

  def test_two_callers_side_by_side_get_a_lone_callers_answers(
      self, tiny_predictor):
    """Two threads calling one policy with different frames for some
    dozens of rounds get, row for row, what one caller alone gets for
    the same frames and seeds: no staging array is written while
    another flush's program still reads it (on a backend whose device
    array is the host memory, or whose transfer is still under way)."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    rounds = 36
    alone = CEMFleetPolicy(tiny_predictor, **self._STAGED)
    expected = [[alone(*self._round(tiny_predictor, k, r), return_scores=True)
                 for r in range(rounds)] for k in (0, 1)]
    policy = CEMFleetPolicy(tiny_predictor, **self._STAGED)
    results = self._two_callers(policy, tiny_predictor, rounds)
    for got_rounds, want_rounds in zip(results, expected):
      assert len(got_rounds) == rounds
      for (actions, scores), (want_actions, want_scores) in zip(
          got_rounds, want_rounds):
        np.testing.assert_array_equal(actions, want_actions)
        np.testing.assert_array_equal(scores, want_scores)
    assert policy.compile_counts == {4: 1}

  def test_pool_grows_to_the_callers_inside_at_once_and_no_further(
      self, tiny_predictor):
    """A sequential caller keeps one array per (bucket and row shape,
    dtype), two callers side by side at most two; while a call's
    program runs, its array is out of the pool."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    policy = CEMFleetPolicy(tiny_predictor, **self._STAGED)
    key = ((4, 8, 8, 3), np.dtype(np.float32))
    for r in range(6):
      policy(*self._round(tiny_predictor, 0, r))
    assert policy._staging.sizes() == {key: 1}
    policy([tiny_predictor.make_image(0)])
    assert policy._staging.sizes() == {
        key: 1, ((1, 8, 8, 3), np.dtype(np.float32)): 1}
    real, free_inside = policy._executables[4], []

    def watched(*args):
      free_inside.append(policy._staging.sizes()[key])
      return real(*args)

    policy._executables[4] = watched
    policy(*self._round(tiny_predictor, 0, 0))
    assert free_inside == [0] and policy._staging.sizes()[key] == 1
    policy._executables[4] = real
    self._two_callers(policy, tiny_predictor, rounds=24)
    assert 1 <= policy._staging.sizes()[key] <= 2
    # A swapped-in policy (use_policy, a tier change) brings its own.
    assert CEMFleetPolicy(tiny_predictor, **self._STAGED)._staging.sizes() == {}

  def test_partial_flush_is_padded_in_place_with_its_last_row(
      self, tiny_predictor):
    """3 frames on rung 4: each scores as it does alone, and the
    staging array's fourth row is its third (bucketing.pad_to's rule),
    whatever an earlier, fuller flush left there."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    policy = CEMFleetPolicy(tiny_predictor, **self._STAGED)
    images, seeds = self._round(tiny_predictor, 0, 0)
    assert len(images) == 4
    full_actions, full_scores = policy(images, seeds, return_scores=True)
    actions, scores = policy(images[:3], seeds[:3], return_scores=True)
    np.testing.assert_array_equal(actions, full_actions[:3])
    np.testing.assert_array_equal(scores, full_scores[:3])
    for i in range(3):
      lone_action, lone_score = policy(images[i:i + 1], seeds[i:i + 1],
                                       return_scores=True)
      np.testing.assert_allclose(actions[i], lone_action[0], atol=1e-5)
      np.testing.assert_allclose(scores[i], lone_score[0], atol=1e-5)
    (staged,) = policy._staging._free[((4, 8, 8, 3), np.dtype(np.float32))]
    np.testing.assert_array_equal(staged[:3], np.stack(images[:3]))
    np.testing.assert_array_equal(staged[3], staged[2])

  def test_stack_span_says_reused_and_the_stats_count_it(
      self, tiny_predictor):
    """`serve/stack` carries `reused` 0 on a key's first call and 1
    after; `snapshot()["staged_flushes"]` counts the replica's flushes
    that found their array in the pool."""
    import jax

    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.stats import ServingStats

    stats = ServingStats(registry=MetricRegistry())
    router = FleetRouter(
        tiny_predictor, devices=jax.devices()[:1], num_samples=16,
        num_elites=4, iterations=2, seed=0, ladder_sizes=(1, 4),
        stats=stats)
    assert stats.snapshot()["staged_flushes"] == 0
    reused = []
    with router:  # not warmed: the first flush of a rung makes its array
      for flush, n in enumerate((3, 4, 2, 1, 1)):
        ids = [f"staged-{flush}-{i}" for i in range(n)]
        with router.replicas[0].batcher.hold_flushes():
          futures = [router.submit(tiny_predictor.make_image(i),
                                   request_id=rid)
                     for i, rid in enumerate(ids)]
        [f.result(timeout=30) for f in futures]
        (stack,) = [s for s in trace_lib.get_tracer().spans()
                    if s["name"] == "serve/stack"
                    and str(s.get("request_ids", "")) == ",".join(ids)]
        assert stack["rows"] == n
        reused.append(stack["reused"])
    assert reused == [0, 1, 1, 0, 1]
    snapshot = stats.snapshot()
    assert snapshot["flushes"] == 5 and snapshot["staged_flushes"] == 3

  # -- the hold, split (ISSUE 40) --------------------------------------------

  _HOLD_KEYS = {"turn_wait_p50_ms", "turn_wait_p95_ms",
                "transfer_wait_p50_ms", "transfer_wait_p95_ms",
                "program_p50_ms", "program_p95_ms", "transfers_hidden",
                "program_busy_share"}

  def _replica(self, predictor, stats):
    """One replica on the default device, not started: `_flush` is
    called on the test's thread."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    from tensor2robot_tpu.serving.router import PolicyReplica
    return PolicyReplica(
        CEMFleetPolicy(predictor, **self._STAGED), max_batch=4,
        deadline_ms=50.0, stats=stats, max_queue=None,
        dispatch_margin_ms=0.0)

  @pytest.mark.parametrize("path", ["device", "host"])
  def test_snapshot_carries_the_hold_split_of_device_flushes_only(
      self, tiny_predictor, path, tmp_path):
    """After a replica's flush `snapshot()` (and `write_to`, and the
    registry) say where the device turn went; the host fallback, which
    has no device turn to split, leaves none of it."""
    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving import policy as policy_lib
    from tensor2robot_tpu.serving.stats import ServingStats
    from tensor2robot_tpu.utils.metric_writer import MetricWriter

    class HostOnly:
      def __init__(self, inner):
        self._inner = inner

      def device_fn(self):
        raise NotImplementedError

      def __getattr__(self, name):
        return getattr(self._inner, name)

    registry = MetricRegistry()
    stats = ServingStats(registry=registry)
    assert not self._HOLD_KEYS & set(stats.snapshot())
    replica = self._replica(
        tiny_predictor if path == "device" else HostOnly(tiny_predictor),
        stats)
    with trace_lib.get_tracer().span("test/hold_split") as mark:
      pass
    holds = policy_lib._SPLIT_EVERY + 1  # the first and the last are split
    for r in range(holds):
      images, seeds = self._round(tiny_predictor, 0, r)
      assert len(replica._flush(list(zip(images, seeds)))) == len(images)
    snapshot = stats.snapshot()
    with MetricWriter(str(tmp_path)) as writer:
      stats.write_to(writer, step=1)
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
      written = json.loads(f.readline())
    histograms = {name.rsplit("/", 1)[0] for name in registry.snapshot()
                  if name.endswith("_ms/count")}
    if path == "host":
      assert replica.policy.last_call_phases is None
      assert not self._HOLD_KEYS & set(snapshot)
      assert not any("program" in key or "wait" in key for key in written)
      assert not histograms
      return
    assert self._HOLD_KEYS <= set(snapshot)
    assert {"serving/" + key for key in self._HOLD_KEYS} <= set(written)
    assert histograms == {"serving/turn_wait_ms",
                          "serving/transfer_wait_ms", "serving/program_ms"}
    # (Two split holds stand for eight here, the first of them the
    # program's first run: an estimate, not bounded by 1.)
    assert snapshot["program_busy_share"] > 0.0
    for name in ("turn_wait", "transfer_wait", "program"):
      assert 0.0 <= snapshot[f"{name}_p50_ms"] <= snapshot[f"{name}_p95_ms"]
    # The same reads as the ring's. Every turn says whether the frames
    # had landed; one hold in `_SPLIT_EVERY` is split into its two
    # waits and carries `device_ms`, the others' serve/readback is
    # whole.
    mine = [s for s in trace_lib.get_tracer().spans()
            if s["ts_s"] >= mark["ts_s"]
            and s["tid"] == threading.get_ident()]
    turns = [s for s in mine if s["name"] == "serve/turn"]
    readbacks = [s for s in mine if s["name"] == "serve/readback"]
    assert len(turns) == len(readbacks) == holds
    assert snapshot["transfers_hidden"] == sum(s["landed"] for s in turns)
    assert ["device_ms" in s for s in readbacks] == (
        [True] + [False] * (holds - 2) + [True])
    for wait in ("serve/transfer_wait", "serve/program_wait"):
      assert [s["ts_s"] >= readbacks[-1]["ts_s"] for s in mine
              if s["name"] == wait] == [False, True]
    device_ms = [s["device_ms"] for s in (readbacks[0], readbacks[-1])]
    assert snapshot["program_p95_ms"] == pytest.approx(max(device_ms),
                                                       abs=1e-3)
    # The mean program of the split holds over the mean period of all.
    end = lambda s: s["ts_s"] + s["dur_s"]
    period_ms = 1e3 * (end(readbacks[-1]) - end(readbacks[0])) / (holds - 1)
    assert snapshot["program_busy_share"] == pytest.approx(
        sum(device_ms) / 2 / period_ms, abs=2e-4)
    phases = replica.policy.last_call_phases
    assert phases["program_ms"] == pytest.approx(device_ms[-1], abs=1e-3)
    assert set(phases) == {"turn_wait_ms", "landed", "period_ms",
                           "transfer_wait_ms", "program_ms"}

  def test_busy_share_is_each_replicas_holds_against_its_own_periods(self):
    """Two replicas whose programs each fill half of the same seconds
    are half busy, not wholly: every hold brings its own policy's
    period, and the split holds' programs stand for the others'."""
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving.stats import ServingStats

    stats = ServingStats(registry=MetricRegistry())
    for landed in (1, 0):  # a replica each
      for k in range(7):  # 100 ms of program in every 200 ms
        phases = dict(turn_wait_ms=5.0, landed=landed)
        if k:
          phases["period_ms"] = 200.0
        if k % 3 == 0:  # the policy split this hold
          phases.update(transfer_wait_ms=7.0 * (1 - landed), program_ms=100.0)
        stats.record_flush_phases(**phases)
    snapshot = stats.snapshot()
    assert snapshot["transfers_hidden"] == 7
    assert snapshot["program_busy_share"] == pytest.approx(0.5, abs=1e-4)
    assert snapshot["transfer_wait_p95_ms"] == 7.0
    assert snapshot["program_p50_ms"] == snapshot["turn_wait_p95_ms"] * 20
    # Before a second hold there is no period to set a program against.
    first = ServingStats(registry=MetricRegistry())
    first.record_flush_phases(turn_wait_ms=5.0, landed=0,
                              transfer_wait_ms=7.0, program_ms=100.0)
    assert first.snapshot()["program_busy_share"] is None

  def test_a_phase_feed_that_raises_does_not_fail_the_flush(
      self, tiny_predictor):
    """The stats feed is diagnostics: a sink that raises on the phases
    costs the flush nothing, and the feeds before it were taken."""
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving.stats import ServingStats

    class Raising(ServingStats):
      def record_flush_phases(self, *args, **kwargs):
        raise RuntimeError("no room for phases")

    stats = Raising(registry=MetricRegistry())
    sound = self._replica(tiny_predictor,
                          ServingStats(registry=MetricRegistry()))
    replica = self._replica(tiny_predictor, stats)
    images, seeds = self._round(tiny_predictor, 1, 0)
    items = list(zip(images, seeds))
    np.testing.assert_array_equal(np.stack(replica._flush(items)),
                                  np.stack(sound._flush(items)))
    snapshot = stats.snapshot()
    assert snapshot["q_sketches"] and not self._HOLD_KEYS & set(snapshot)

  @pytest.mark.parametrize("what", ["the program", "the frames' readiness"])
  def test_a_call_that_raises_drops_its_array(self, tiny_predictor, what,
                                              monkeypatch):
    """A program that fails, or a device array that cannot say whether
    it has landed once the turn is held, leaves the pool without that
    call's array (nobody knows what still reads it), the turn free and
    the next call sound."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    policy = CEMFleetPolicy(tiny_predictor, **self._STAGED)
    key = ((4, 8, 8, 3), np.dtype(np.float32))
    images, seeds = self._round(tiny_predictor, 0, 0)
    want_actions, want_scores = policy(images, seeds, return_scores=True)

    def failing(*args):
      raise RuntimeError("device lost")

    class Lost:
      is_ready = failing

    with monkeypatch.context() as patched:
      if what == "the program":
        patched.setitem(policy._executables, 4, failing)
      else:
        patched.setattr(policy, "_put", lambda array: Lost())
      with pytest.raises(RuntimeError, match="device lost"):
        policy(images, seeds)
    assert policy._staging.sizes()[key] == 0
    assert not policy._turn.locked()
    assert policy.last_call_phases is None
    actions, scores = policy(*self._round(tiny_predictor, 1, 0),
                             return_scores=True)  # other frames between
    assert not policy.last_call_reused_staging
    actions, scores = policy(images, seeds, return_scores=True)
    assert policy.last_call_reused_staging
    np.testing.assert_array_equal(actions, want_actions)
    np.testing.assert_array_equal(scores, want_scores)
    assert policy._staging.sizes()[key] == 1

  @pytest.mark.parametrize("path", ["host_fallback", "variables_override"])
  def test_every_path_stacks_into_the_same_arrays(self, tiny_predictor, path):
    """The host fallback (a predictor without device_fn) and a shadow
    call with `variables=` take their array from the policy's pool and
    give it back, as a live flush does."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    class HostOnly:
      def __init__(self, inner):
        self._inner = inner

      def device_fn(self):
        raise NotImplementedError

      def __getattr__(self, name):
        return getattr(self._inner, name)

    key = ((4, 8, 8, 3), np.dtype(np.float32))
    images, seeds = self._round(tiny_predictor, 0, 1)
    assert len(images) == 3
    want = CEMFleetPolicy(tiny_predictor, **self._STAGED)(images, seeds)
    if path == "host_fallback":
      policy = CEMFleetPolicy(HostOnly(tiny_predictor), **self._STAGED)
      call = lambda: policy(images, seeds)
      atol = 1e-4  # test_host_fallback_matches_device_path's
    else:
      policy = CEMFleetPolicy(tiny_predictor, **self._STAGED)
      healthy = tiny_predictor.make_candidate_variables()  # bit-equal Q
      call = lambda: policy(images, seeds, variables=healthy)
      atol = 0.0
    policy(*self._round(tiny_predictor, 1, 0))  # a live flush of 4 before
    assert not policy.last_call_reused_staging
    for _ in range(2):
      np.testing.assert_allclose(call(), want, atol=atol)
      assert policy.last_call_reused_staging
      assert policy._staging.sizes() == {key: 1}
    (staged,) = policy._staging._free[key]
    np.testing.assert_array_equal(staged[:3], np.stack(images))
    np.testing.assert_array_equal(staged[3], staged[2])

  @pytest.mark.parametrize("odd", ["shape", "dtype"])
  def test_a_frame_unlike_the_first_is_refused(self, tiny_predictor, odd):
    """A frame of another shape or dtype than the flush's first raises
    ValueError before any program runs; the flush after it is sound."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    policy = CEMFleetPolicy(tiny_predictor, **self._STAGED)
    images, seeds = self._round(tiny_predictor, 0, 0)
    want = policy(images, seeds)
    bad = list(images)
    bad[2] = (np.zeros((4, 8, 3), np.float32) if odd == "shape"
              else images[2].astype(np.float64))
    with pytest.raises(ValueError, match=f"same {odd}"):
      policy(bad, seeds)
    np.testing.assert_array_equal(policy(images, seeds), want)


class TestPredictBatched:

  def test_pads_to_bounded_bucket_and_slices_back(self, tiny_predictor):
    seen_sizes = []
    inner_predict = tiny_predictor.predict

    class Recording:
      def __getattr__(self, name):
        return getattr(tiny_predictor, name)

      def predict(self, features):
        seen_sizes.append(np.asarray(features["image"]).shape[0])
        return inner_predict(features)

    from tensor2robot_tpu.predictors.abstract_predictor import (
        AbstractPredictor)
    recording = Recording()
    images = np.stack([tiny_predictor.make_image(i) for i in range(5)])
    actions = np.zeros((5, 4), np.float32)
    out = AbstractPredictor.predict_batched(
        recording, {"image": images, "action": actions})
    # 5 rows ran as one power-of-two bucket of 8; outputs sliced to 5
    # and equal to the unpadded answer row-for-row.
    assert seen_sizes == [8]
    assert out["q_predicted"].shape == (5,)
    direct = tiny_predictor.predict(
        {"image": images, "action": actions})
    np.testing.assert_allclose(out["q_predicted"],
                               direct["q_predicted"], atol=1e-6)

  def test_inconsistent_batch_dims_rejected(self, tiny_predictor):
    with pytest.raises(ValueError):
      tiny_predictor.predict_batched({
          "image": np.zeros((2, 8, 8, 3), np.float32),
          "action": np.zeros((3, 4), np.float32)})


class TestFleetServer:

  def test_concurrent_clients_get_their_own_answers(self, fleet_policy,
                                                    tiny_predictor):
    """16 threads × distinct images through the full stack; every
    client's action lands nearest its own optimum, and the stats carry
    the occupancy/latency fields the artifact schema names."""
    from tensor2robot_tpu.serving.server import FleetServer
    n_clients, frames = 16, 4
    images = [tiny_predictor.make_image(200 + i) for i in range(n_clients)]
    optima = np.stack([tiny_predictor.best_action(im) for im in images])
    results = [None] * n_clients
    errors = []

    server = FleetServer(fleet_policy, max_batch=16, deadline_ms=20.0)

    def client(i):
      try:
        for _ in range(frames):
          results[i] = server.act(images[i], timeout=30)
      except Exception as e:
        errors.append(e)

    with server:
      threads = [threading.Thread(target=client, args=(i,))
                 for i in range(n_clients)]
      for t in threads:
        t.start()
      for t in threads:
        t.join()
    assert not errors, errors
    # Every client's action converged near ITS OWN optimum (own-dist
    # stays well under the ~1.0 typical inter-optima distance a result
    # mixup would show; exact batched-vs-unbatched equality is pinned
    # in TestCEMFleetPolicy).
    for i, action in enumerate(results):
      own = float(np.linalg.norm(action - optima[i]))
      assert own < 0.75, (i, own)
    snap = server.snapshot()
    assert snap["requests"] == n_clients * frames
    assert snap["latency_samples"] == n_clients * frames
    assert snap["latency_p50_ms"] is not None
    assert snap["latency_p99_ms"] >= snap["latency_p50_ms"]
    assert 0 < snap["batch_occupancy"] <= 1
    assert set(snap["executable_buckets"]) <= {1, 2, 4, 8, 16}

  def test_metric_writer_integration(self, fleet_policy, tiny_predictor,
                                     tmp_path):
    from tensor2robot_tpu.serving.server import FleetServer
    from tensor2robot_tpu.utils.metric_writer import MetricWriter
    writer = MetricWriter(str(tmp_path))
    server = FleetServer(fleet_policy, max_batch=2, deadline_ms=5.0,
                         metric_writer=writer)
    with server:
      [f.result(timeout=30) for f in
       [server.submit(tiny_predictor.make_image(i)) for i in range(4)]]
      server.write_metrics()
    writer.close()
    with open(tmp_path / "metrics.jsonl") as f:
      record = json.loads(f.readlines()[-1])
    assert "serving/requests" in record
    assert "serving/latency_p50_ms" in record

  def test_max_batch_cannot_exceed_ladder(self, fleet_policy):
    from tensor2robot_tpu.serving.server import FleetServer
    with pytest.raises(ValueError):
      FleetServer(fleet_policy, max_batch=32)


class TestFleetSmokeCLI:
  """The tier-1 CI lane (ISSUE 1 satellite): `--fleet --smoke` runs the
  whole serving path chiplessly on every PR and must demonstrate the
  batching amortization the subsystem exists for."""

  _FRAMES = 80

  def _run_smoke(self):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.bin.bench_serving",
         "--fleet", "--smoke", "--clients", "1,16",
         "--frames", str(self._FRAMES)],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, res.stdout
    return json.loads(lines[0])

  def test_fleet_smoke_contract_and_amortization(self):
    obj = self._run_smoke()
    assert obj["mode"] == "smoke"
    assert (obj["platform"], obj["device_kind"]) == ("cpu", "cpu")
    assert obj["device_count"] >= 1
    assert obj["bucket_ladder"] == [1, 2, 4, 8, 16]
    # Exactly one compiled executable per ladder bucket over the whole
    # run — warmup, partial deadline flushes, and full batches included.
    assert obj["compile_counts"] == {str(b): 1 for b in (1, 2, 4, 8, 16)}
    single, point = obj["fleet_sweep"]
    assert (single["clients"], point["clients"]) == (1, 16)
    # The artifact schema's fleet fields are present and sane.
    assert point["latency_p50_ms"] > 0
    assert point["latency_p99_ms"] >= point["latency_p50_ms"]
    assert 0 < point["batch_occupancy"] <= 1
    assert obj["single_client_closed_loop_hz"] > 0

    # Batching amortization, from the run's own counts (ServingStats):
    # what micro-batching amortizes is the per-flush dispatch, so 16
    # concurrent closed-loop clients must be answered with at most a third
    # of the flushes per request that one client costs (one each), in
    # buckets that are mostly real rows. A count cannot depend on who
    # shares the cores; the ratio of two host rates this asserted before
    # did (the rates are still printed, and speed is the serving cell's to
    # say: `qtopt_serve_closed64`).
    def answered(p):
      return round(p["flushes"] * p["mean_batch_size"])

    for p in (single, point):
      # Every request answered: the priming round + frames x repeats.
      assert answered(p) == p["clients"] * (
          1 + self._FRAMES * obj["repeats"]), p
    assert single["flushes"] == answered(single), single  # one each
    assert single["batch_occupancy"] == 1.0
    flushes_per_request = point["flushes"] / answered(point)
    assert flushes_per_request <= 1 / 3, json.dumps(obj, indent=2)
    assert point["batch_occupancy"] >= 0.5, json.dumps(obj, indent=2)

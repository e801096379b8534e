"""Deadline-driven, SLO-aware micro-batcher for multi-client inference.

Concurrent clients enqueue one item each (``submit`` returns a Future);
a dispatcher thread flushes pending requests into ``batch_fn`` when
either (a) ``max_batch`` requests are pending, or (b) the pending
request with the EARLIEST deadline has exhausted its budget — so a lone
robot never waits longer than its class's deadline, and a busy fleet
always ships full batches.

With ``flush_depth`` above 1 that many dispatchers share the queue, so
up to that many flushes are open at once: a FULL batch is popped at
once even while another flush is in flight (its host work then runs
while the device computes the other), a PARTIAL batch only once none
is — the device could not have started it anyway, and popped early it
would ship short, padded, and leave a smaller one behind. A load under
one full batch per flush time is therefore served exactly as at depth 1.

Ordering is **earliest-deadline-first** (serving/slo.py): every request
carries an SLO class whose ``deadline_ms`` budget sets its absolute
deadline at enqueue, and a flush takes the pending requests whose
deadlines expire soonest. With a single class every deadline is
enqueue-time + constant, so EDF degrades to exactly the FIFO the
pre-SLO batcher shipped — no client is starved by later arrivals of its
own class; a later arrival of a TIGHTER class overtakes by design.

Overload is handled by shedding, not by queue collapse: with a
``max_queue`` bound, an arrival into a full queue evicts the
lowest-priority pending request (latest deadline breaks ties; the
arrival itself is evicted if IT is lowest), failing its Future with
``RequestShed`` and counting the shed per class — graceful degradation
the fleet artifact can measure. A request whose deadline is already
past at enqueue (e.g. a router hop consumed its whole budget) is shed
immediately: counted, never dispatched, never occupying a bucket slot.
"""

from __future__ import annotations

import contextlib
import heapq
import itertools
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Optional, Sequence

from tensor2robot_tpu.obs import context as context_lib
from tensor2robot_tpu.obs import faults as faults_lib
from tensor2robot_tpu.obs import flight_recorder as flight_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.obs import watchdog as watchdog_lib
from tensor2robot_tpu.serving.slo import (DispatcherDead, RequestShed,
                                          SLOClass)
from tensor2robot_tpu.serving.stats import ServingStats


class _Request:
  __slots__ = ("item", "future", "enqueued_at", "deadline", "flush_at",
               "slo", "shed", "request_id")

  def __init__(self, item: Any, slo: SLOClass,
               deadline_at: Optional[float], margin_s: float,
               request_id: Optional[str] = None):
    self.item = item
    self.future: Future = Future()
    # Correlation (ISSUE 12): the id every span/dump this request
    # touches will carry. Inherit the caller's bound id (the router's
    # ingress bind); a bare batcher submit mints its own so direct
    # clients get timelines too.
    self.request_id = (request_id or context_lib.current_request_id()
                       or context_lib.new_request_id())
    self.enqueued_at = time.perf_counter()
    # `deadline` is the CLIENT's latency budget (expiry/shed basis);
    # `flush_at` is when the dispatcher must ship a partial batch so
    # the answer lands INSIDE that budget — deadline minus the
    # dispatch margin (the flush's own cost). Without the margin a
    # lone request waits out its whole budget and then pays the flush
    # on top, putting p99 structurally ABOVE the class budget at light
    # load.
    self.deadline = (self.enqueued_at + slo.deadline_ms / 1e3
                     if deadline_at is None else deadline_at)
    self.flush_at = max(self.enqueued_at, self.deadline - margin_s)
    self.slo = slo
    self.shed = False  # lazy heap deletion marker


class MicroBatcher:
  """Batches concurrent ``submit`` calls into ``batch_fn`` flushes.

  Args:
    batch_fn: callable taking the list of pending items (EDF order)
      and returning one result per item, same order. Runs on the
      dispatcher thread; an exception fails every request in the flush
      (never the batcher itself).
    max_batch: flush immediately once this many requests are pending.
    deadline_ms: budget of the DEFAULT class — the latency budget a
      class-less submit pays (back-compat: the pre-SLO constructor
      signature keeps working and behaves identically).
    stats: optional ServingStats; flush/occupancy/latency/shed counters
      are recorded when given. `bucket_for` (e.g.
      BucketLadder.bucket_for) maps a flush size to the compiled batch
      slots it occupies for the occupancy/waste counters; identity when
      absent.
    max_queue: pending-queue bound (admission control). None =
      unbounded, the pre-SLO behavior. With a bound, an arrival into a
      full queue sheds the lowest-priority pending request
      (lowest SLOClass.priority; latest deadline breaks ties).
    flush_depth: how many flushes may be open at once (one dispatcher
      thread each; see the module docstring for what may overlap).
      Above 1, `batch_fn` is called concurrently: only the owner of a
      `batch_fn` that is safe to call so raises it.
  """

  def __init__(self, batch_fn: Callable[[Sequence[Any]], Sequence[Any]],
               max_batch: int = 16, deadline_ms: float = 5.0,
               stats: Optional[ServingStats] = None,
               bucket_for: Optional[Callable[[int], int]] = None,
               max_queue: Optional[int] = None,
               dispatch_margin_ms: float = 0.0,
               flight_recorder: Optional[flight_lib.FlightRecorder] = None,
               watchdog: Optional[watchdog_lib.Watchdog] = None,
               fault_plan: Optional[faults_lib.FaultPlan] = None,
               site: str = "batcher",
               restart_budget: int = 3,
               flush_depth: int = 1):
    """See class docstring. `dispatch_margin_ms` budgets the flush's own
    cost: a partial batch ships `margin` BEFORE its head's deadline, so
    a class's p99 can actually sit inside its budget (set it to a
    comfortable bound on one flush; 0 keeps the legacy flush-AT-deadline
    behavior). `flight_recorder` (default: the process recorder)
    receives every shed as an SLO-breach trigger and the dispatcher's
    unhandled exceptions — dumps fire only once a dump_dir is
    configured on it. `watchdog` (default: the process watchdog) gets a
    heartbeat per dispatcher: beats per flush, idle while the queue is
    empty (or holds only a partial batch that waits for another
    dispatcher's open flush), so a dispatcher stuck with pending work
    (a wedged batch_fn, a hold that outlived its test) is flagged as a
    stall whatever the other dispatchers do — but only once the owning
    deployment STARTS the watchdog monitor.

    `fault_plan` (ISSUE 14) is the deterministic injection seam: each
    flush checks the plan's ``batcher_flush`` point under this
    batcher's `site` before calling batch_fn — a ``hung_flush`` wedges
    the flush, a ``thread_kill`` dies as a non-Exception exactly where
    a poison request would. `restart_budget` bounds the self-healing
    that answers it: a dead dispatcher thread is restarted up to this
    many times (each death fails only its in-flight batch, typed, and
    dumps to the flight recorder); past the budget the batcher goes
    DOWN deliberately — every pending future resolves with
    ``DispatcherDead`` (clients never hang on a dead dispatcher), new
    submits raise, and the heartbeat is left armed-busy so a running
    watchdog monitor escalates the outage instead of reading a dead
    component as idle."""
    if max_batch < 1:
      raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if deadline_ms < 0:
      raise ValueError(f"deadline_ms must be >= 0, got {deadline_ms}")
    if max_queue is not None and max_queue < 1:
      raise ValueError(f"max_queue must be >= 1, got {max_queue}")
    if dispatch_margin_ms < 0:
      raise ValueError(
          f"dispatch_margin_ms must be >= 0, got {dispatch_margin_ms}")
    if restart_budget < 0:
      raise ValueError(
          f"restart_budget must be >= 0, got {restart_budget}")
    if flush_depth < 1:
      raise ValueError(f"flush_depth must be >= 1, got {flush_depth}")
    self._batch_fn = batch_fn
    self._max_batch = max_batch
    self._margin_s = dispatch_margin_ms / 1e3
    self._default_slo = SLOClass("default", 0, deadline_ms)
    self._stats = stats
    self._bucket_for = bucket_for or (lambda n: n)
    self._max_queue = max_queue
    self._recorder = flight_recorder or flight_lib.get_recorder()
    self._watchdog = watchdog or watchdog_lib.get_watchdog()
    # One dispatcher thread and one heartbeat per slot of the depth.
    self._heartbeats: list = [None] * flush_depth
    self._threads: list = [None] * flush_depth
    # Min-heap of (deadline, seq, request); shed entries stay in the
    # heap with request.shed=True and are skipped on pop (lazy
    # deletion), _live tracks the real pending count.
    self._heap: list = []
    self._live = 0
    self._in_flight = 0  # requests popped and not yet answered
    self._open_flushes = 0  # batches popped and not yet finished
    self._seq = itertools.count()
    self._cond = threading.Condition()
    self._running = False
    self._release = threading.Event()  # hold_flushes gate; normally set
    self._release.set()
    # Fault-tolerance state (ISSUE 14): the injection seam and the
    # dispatcher-death recovery it exercises.
    self._faults = fault_plan
    self._site = site
    self._restart_budget = restart_budget
    self.dispatcher_restarts = 0
    self.dispatcher_dead = False
    # Test-only observability (the zero-slack no-busy-spin regression
    # test): how many times the dispatcher loop body ran. A spinning
    # dispatcher shows unbounded growth while idle.
    self._dispatch_iterations = 0

  # -- lifecycle -----------------------------------------------------------

  def start(self) -> "MicroBatcher":
    with self._cond:
      if self._running:
        return self
      if self.dispatcher_dead:
        raise DispatcherDead("cannot restart a batcher that exhausted "
                             "its dispatcher restart budget")
      self._running = True
    for slot in range(len(self._threads)):
      self._heartbeats[slot] = self._watchdog.register("serve/batcher")
      self._spawn_dispatcher(slot)
    return self

  def _spawn_dispatcher(self, slot: int) -> None:
    thread = threading.Thread(
        target=self._dispatcher_main, args=(slot,), name="micro-batcher",
        daemon=True)
    self._threads[slot] = thread
    thread.start()

  def stop(self) -> None:
    """Stops accepting work, drains what is queued, joins the threads.

    Safe on a batcher whose dispatcher already died (the heartbeats are
    unregistered either way), and against a concurrent dispatcher
    RESTART: each slot's join loops until its thread reference stops
    changing, so a death-and-respawn racing the stop cannot leak a live
    thread.
    """
    with self._cond:
      self._running = False
      self._cond.notify_all()
    for slot in range(len(self._threads)):
      while True:
        thread = self._threads[slot]
        if thread is None or thread is threading.current_thread():
          break
        thread.join()
        if self._threads[slot] is thread:
          self._threads[slot] = None
          break
        # A restart swapped the thread mid-join; join the successor too.
      if self._heartbeats[slot] is not None:
        self._watchdog.unregister(self._heartbeats[slot])
        self._heartbeats[slot] = None

  def __enter__(self) -> "MicroBatcher":
    return self.start()

  def __exit__(self, *exc_info) -> None:
    self.stop()

  # -- client side ---------------------------------------------------------

  @property
  def max_batch(self) -> int:
    return self._max_batch

  @property
  def max_queue(self) -> Optional[int]:
    return self._max_queue

  def use_stats(self, stats: Optional[ServingStats]) -> None:
    """Swaps the stats sink (between measurement phases, while idle):
    records are cheap reads of this attribute, so a swap is an atomic
    pointer store — the fleet bench re-points all replicas per sweep
    point rather than rebuilding batchers (which would recompile)."""
    self._stats = stats

  def pending(self) -> int:
    """Pending + in-flight request count — the router's load signal."""
    with self._cond:
      return self._live + self._in_flight

  def _raise_not_running_locked(self) -> None:
    """A stopped batcher raises RuntimeError (the caller's lifecycle
    bug); a DEAD one raises the typed DispatcherDead so the router's
    fault machinery treats the synchronous submit failure exactly like
    an asynchronous dispatch failure (retry elsewhere or shed_fault)."""
    if self.dispatcher_dead:
      raise DispatcherDead("restart budget exhausted; batcher is down")
    raise RuntimeError("MicroBatcher is not running; call start().")

  @contextlib.contextmanager
  def hold_flushes(self):
    """Blocks dispatch (not admission) until exit: requests queue and
    shed per the EDF/priority rules, but none are POPPED for a flush
    while held (a flush already past the gate when the hold starts
    just completes). Makes offered-load-vs-capacity behavior
    DETERMINISTIC for overload tests and the fleet bench's burst
    phase — the shed composition becomes a pure function of the
    arrival sequence and the queue bound, not of how fast this host
    happens to drain."""
    self._release.clear()
    try:
      yield self
    finally:
      self._release.set()
      with self._cond:
        self._cond.notify_all()

  def submit(self, item: Any, slo: Optional[SLOClass] = None,
             deadline_at: Optional[float] = None,
             request_id: Optional[str] = None) -> Future:
    """Enqueues one item; the Future resolves to its batch_fn result.

    Args:
      item: opaque payload handed to batch_fn.
      slo: the request's SLO class; None uses the default class built
        from the constructor's deadline_ms (priority 0).
      deadline_at: absolute deadline (time.perf_counter() basis) for
        requests whose budget started at an upstream hop (the router's
        ingress clock); overrides the class budget. A deadline already
        in the past sheds the request immediately.
      request_id: correlation id minted at an upstream ingress (router
        / server); None inherits the caller's bound obs.context id or
        mints one here. The id rides every span and flight-recorder
        trigger this request touches.
    """
    slo = slo or self._default_slo
    request = _Request(item, slo, deadline_at, self._margin_s,
                       request_id=request_id)
    # The enqueue span is the request timeline's first hop: it covers
    # expiry check + EDF admission (+ a capacity eviction when one
    # fires) and carries the correlation id, so the exported flow
    # links it to the serve/flush that later ships the request.
    with trace_lib.span("serve/enqueue", request_id=request.request_id,
                        slo=slo.name):
      # Expired at enqueue: the budget was consumed before the request
      # ever reached this queue (negative class budget, or an upstream
      # hop ate it). Shed immediately — counted, never dispatched, and
      # never even enqueued, so an expired flood cannot wake the
      # dispatcher into a shed-purge spin. The lifecycle check still
      # applies first: a stopped batcher must raise, not dress the
      # caller's bug up as ordinary load shedding.
      if request.deadline < request.enqueued_at:
        with self._cond:
          if not self._running:
            self._raise_not_running_locked()
        if self._stats is not None:
          self._stats.record_request(slo.name)
        self._shed(request, "expired")
        return request.future
      with self._cond:
        if not self._running:
          self._raise_not_running_locked()
        victim = None
        if self._max_queue is not None and self._live >= self._max_queue:
          victim = self._pick_victim_locked(request)
        if victim is not request:
          head_flush_at = self._head_flush_at_locked()
          heapq.heappush(self._heap,
                         (request.flush_at, next(self._seq), request))
          self._live += 1
          # Wake the dispatcher only when its state actually changes:
          # the first pending item (or a new EARLIEST deadline) re-arms
          # the timed wait, and reaching max_batch triggers an
          # immediate flush. Other arrivals ride the already-armed
          # wait — on a busy fleet this cuts dispatcher wakeups from
          # one per request to about two per flush, most of the
          # batching win on a GIL-bound host.
          if (head_flush_at is None or request.flush_at < head_flush_at
              or self._live >= self._max_batch):
            self._cond.notify()
      if self._stats is not None:
        self._stats.record_request(slo.name)
      if victim is not None:
        self._shed(victim, "capacity")
      return request.future

  def _pick_victim_locked(self, incoming: _Request) -> Optional[_Request]:
    """Lowest-priority pending request (latest deadline breaks ties),
    the incoming request included; None if nothing can be evicted (all
    pending entries already shed — then the queue isn't really full)."""
    victim = incoming
    for _, _, request in self._heap:
      if request.shed:
        continue
      if (request.slo.priority, -request.deadline) < (
          victim.slo.priority, -victim.deadline):
        victim = request
    if victim is not incoming:
      victim.shed = True
      self._live -= 1
    return victim

  def _head_flush_at_locked(self) -> Optional[float]:
    """Earliest live flush time; purges shed entries off the heap top."""
    while self._heap and self._heap[0][2].shed:
      heapq.heappop(self._heap)
    return self._heap[0][0] if self._heap else None

  def _shed(self, request: _Request, reason: str) -> None:
    if self._stats is not None:
      self._stats.record_shed(request.slo.name, reason)
    # Resolve the victim's future FIRST: the diagnostics below must
    # never leave a shed client blocked on result().
    if request.future.set_running_or_notify_cancel():
      request.future.set_exception(RequestShed(request.slo.name, reason))
    # Every shed is an SLO breach the fleet promised to account for:
    # trigger a flight-recorder dump (rate-limited; ring-only when no
    # dump_dir is configured) so the spans/events leading up to the
    # breach survive for the post-mortem. Best-effort: a failing dump
    # (full disk, unwritable dir) must not convert a correctly-shed
    # request into a submit()-side storage error.
    try:
      self._recorder.trigger("slo_breach", slo_class=request.slo.name,
                             shed_reason=reason,
                             request_id=request.request_id)
    except Exception:
      pass

  # -- dispatcher ----------------------------------------------------------

  def _dispatcher_main(self, slot: int) -> None:
    """Thread entry: the loop plus the DEATH handler (ISSUE 14). An
    escaping non-Exception (a poison request aborting the thread, an
    injected thread_kill) used to leave every queued client hanging —
    now it either restarts the dispatcher (capped budget; the queue
    survives, only the in-flight batch failed) or takes the batcher
    down LOUDLY: all pending futures resolve DispatcherDead, and the
    heartbeat stays armed-busy for the watchdog escalation."""
    try:
      self._dispatch_loop(slot)
    except BaseException as e:  # noqa: BLE001 — the death handler
      self._on_dispatcher_death(e, slot)

  def _on_dispatcher_death(self, exc: BaseException, slot: int) -> None:
    detail = f"{type(exc).__name__}: {exc}"
    with self._cond:
      restart = (self._running
                 and self.dispatcher_restarts < self._restart_budget)
      if restart:
        self.dispatcher_restarts += 1
      else:
        self.dispatcher_dead = True
        self._running = False
    self._recorder.trigger(
        "batcher_dispatcher_death", site=self._site, error=detail,
        restarts=self.dispatcher_restarts,
        restart_budget=self._restart_budget, recovered=restart)
    try:
      from tensor2robot_tpu.obs import registry as registry_lib
      registry_lib.get_registry().counter(
          "serving/dispatcher_restarts" if restart
          else "serving/dispatcher_deaths").inc()
    except Exception:
      pass  # diagnostics never block the recovery path
    if restart:
      # The queue (and its futures) survive: only the batch that was
      # in flight ON THIS THREAD when it died has already been failed
      # typed; another dispatcher's open flush finishes as it would.
      self._spawn_dispatcher(slot)
      return
    # Unrecoverable: resolve EVERY pending future — a dead dispatcher
    # must never leave a client blocked in result(). The heartbeat is
    # deliberately left registered and flipped busy: a component that
    # is down with work it will never do is a stall, and a running
    # watchdog monitor escalates it (counter -> dump -> callback);
    # stop() unregisters it when the owner shuts the batcher down.
    self._fail_all_pending(DispatcherDead(detail))
    heartbeat = self._heartbeats[slot]
    if heartbeat is not None:
      heartbeat.busy()

  @staticmethod
  def _resolve_failed(future: Future, exc: Exception) -> None:
    """Best-effort typed resolution for a future in ANY state:
    set_exception lands from PENDING and RUNNING alike; a future the
    client already cancelled (or a flush already resolved) is left
    alone — the death paths must never themselves raise on a racing
    client."""
    try:
      future.set_exception(exc)
    except Exception:
      pass

  def _fail_all_pending(self, exc: Exception) -> None:
    with self._cond:
      pending = [request for _, _, request in self._heap
                 if not request.shed]
      self._heap.clear()
      self._live = 0
    for request in pending:
      self._resolve_failed(request.future, exc)

  def _dispatch_loop(self, slot: int) -> None:
    while True:
      batch, deadline_expired, others_open = self._next_batch(slot)
      if batch is None:
        return
      try:
        self._flush(batch, deadline_expired, others_open)
      except Exception as e:  # e.g. a raising bucket_for/stats hook —
        # the dispatcher must outlive ANY flush failure or every
        # queued and future request hangs unresolved.
        self._recorder.trigger("batcher_dispatcher_exception",
                               error=f"{type(e).__name__}: {e}")
        for request in batch:
          if not request.future.done():
            try:
              request.future.set_exception(e)
            except Exception:
              pass
      except BaseException as e:  # dying — but THIS batch still
        # resolves typed before the death handler decides the
        # batcher's fate (clients of the killed flush never hang).
        detail = f"{type(e).__name__}: {e}"
        for request in batch:
          self._resolve_failed(request.future, DispatcherDead(detail))
        raise
      finally:
        with self._cond:
          self._in_flight -= len(batch)
          self._open_flushes -= 1
          # A partial batch that waited for this flush may be due now.
          self._cond.notify_all()

  def _next_batch(self, slot: int):
    """Blocks until a flush is due; returns (requests, deadline_expired,
    others_open): the last is how many other flushes of this batcher
    were open when this one was popped.

    (None, _, _) signals shutdown with an empty queue — on stop() the
    queue is drained (every accepted Future resolves) before exit.

    What may be popped: a full batch always, even while another flush
    is open; a partial one (its head's flush time passed, or draining
    on stop()) only while none is — the flush that ends notifies.

    No-busy-spin invariant: every pass either returns a batch, or waits
    with a STRICTLY positive timeout (now < head deadline on that
    branch), or waits untimed (an empty queue, or a partial batch
    behind an open flush) — a zero-slack deadline therefore flushes
    immediately rather than re-arming a zero-length wait in a loop.
    """
    heartbeat = self._heartbeats[slot]
    with self._cond:
      while True:
        self._dispatch_iterations += 1
        full = self._live >= self._max_batch
        may_pop = full or self._open_flushes == 0
        # Liveness: work this dispatcher may take arms the stall clock
        # (busy); an empty queue, or a partial batch that waits for
        # another dispatcher's flush, is intentional waiting (idle) —
        # so a dispatcher wedged with live requests is a stall, a quiet
        # fleet is not, and a wedged flush is its own dispatcher's.
        if heartbeat is not None:
          if self._live > 0 and may_pop:
            heartbeat.busy()
          else:
            heartbeat.idle()
        if not self._release.is_set() and self._running:
          # hold_flushes active: nothing is popped while held. The
          # timed wait covers the (benign) race of a release landing
          # between this check and the wait. stop() OVERRIDES the hold
          # (the `and self._running`): drain must always complete, so
          # a stop racing a held burst flushes instead of deadlocking
          # the join.
          self._cond.wait(timeout=0.05)
          continue
        head = self._head_flush_at_locked()
        if head is not None:
          now = time.perf_counter()
          if full or (may_pop and (now >= head or not self._running)):
            n = min(self._live, self._max_batch)
            batch = []
            while len(batch) < n:
              _, _, request = heapq.heappop(self._heap)
              if not request.shed:
                batch.append(request)
            self._live -= n
            self._in_flight += n
            others_open = self._open_flushes
            self._open_flushes += 1
            expired = now >= head and n < self._max_batch
            if heartbeat is not None:
              heartbeat.beat()
            return batch, expired, others_open
          # Untimed behind an open flush: its end notifies.
          self._cond.wait(timeout=head - now if may_pop else None)
        elif not self._running:
          return None, False, 0
        else:
          self._cond.wait()

  def _flush(self, batch, deadline_expired: bool,
             others_open: int) -> None:
    # Transition each future to RUNNING first: a request whose client
    # gave up (future.cancel() after a result() timeout) is dropped
    # from the flush, and the ones that remain can no longer be
    # cancelled — so set_result below cannot raise InvalidStateError
    # and kill the dispatcher thread with the queue still live.
    batch = [r for r in batch if r.future.set_running_or_notify_cancel()]
    if not batch:
      return
    # The dispatcher is a different thread from the enqueuers, so the
    # contextvar binding does NOT carry over — re-bind the batch's ids
    # here. The serve/flush span (and any span batch_fn opens below
    # it, e.g. the replica's device dispatch) carries them as one
    # comma-joined `request_ids` attr; the trace exporter fans it back
    # out into per-request flows.
    batch_ids = context_lib.join_ids(r.request_id for r in batch)
    with context_lib.bind(request_ids=batch_ids):
      # Fault seam (ISSUE 14): the ONE point a scheduled hung_flush or
      # thread_kill enters this batcher. Inside the bind, so the
      # fault's flight-recorder dump carries the batch's correlation
      # ids; a kill raised here is failed typed by the dispatch loop's
      # death path (_resolve_failed handles the RUNNING futures).
      if self._faults is not None:
        self._faults.perturb("batcher_flush", site=self._site)
      # A request's latency is its wait up to here plus this span.
      flush_start = time.perf_counter()
      waits_ms = [(flush_start - r.enqueued_at) * 1e3 for r in batch]
      with trace_lib.span("serve/flush", batch=len(batch),
                          queue_wait_ms_sum=round(sum(waits_ms), 3),
                          queue_wait_ms_max=round(max(waits_ms), 3),
                          in_flight=others_open):
        try:
          results = self._batch_fn([r.item for r in batch])
        except Exception as e:  # fail the flush's requests, not the loop
          self._recorder.record("event", "flush_failed",
                                error=f"{type(e).__name__}: {e}",
                                batch=len(batch))
          for request in batch:
            request.future.set_exception(e)
          return
    done = time.perf_counter()
    for request, result, wait_ms in zip(batch, results, waits_ms):
      request.future.set_result(result)
      if self._stats is not None:
        self._stats.record_latency_ms(
            (done - request.enqueued_at) * 1e3, request.slo.name)
        self._stats.record_queue_wait_ms(wait_ms, request.slo.name)
    if self._stats is not None:
      with self._cond:
        depth_after = self._live
      self._stats.record_flush(
          len(batch), self._bucket_for(len(batch)), depth_after,
          deadline_expired)
      if others_open:
        self._stats.record_overlapped_flush()

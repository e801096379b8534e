"""Host-side structured spans for the whole production loop.

``span("replay/learn", **attrs)`` is a thread-safe, nestable context
manager. Completed spans land in a bounded in-memory ring and are
exportable as ONE Chrome-trace/Perfetto JSON file per run
(``Tracer.export_chrome_trace``), so "where did the wall-clock go"
is answerable for any run without a debugger attached.

Span names are ``stage/detail`` — the first path segment is the loop
stage (``act``, ``extend``, ``learn``, ``serve``, ``replay``), which
``stage_counts()`` aggregates and the obs bench asserts coverage over.

Every span ALSO enters a ``jax.profiler.TraceAnnotation`` of the same
name, with its scalar attrs as the annotation's stats: whoever opens a
profiler session (``utils.profiling``'s guarded window or a plain
``jax.profiler.start_trace``) finds the program's spans on ``/host:CPU``
beside the device lanes, on the profiler's clock. With no session open
the annotation is one inactive TraceMe (about half a microsecond
against the span's four).

Listeners (``add_listener``) receive every completed span dict — the
flight recorder subscribes so the last N spans are always available for
a post-mortem dump.

Correlation (ISSUE 12): spans completed while ``obs.context`` has a
bound ``request_id``/``step_id`` carry those ids as attrs
automatically, and ``export_chrome_trace`` links every request id seen
on >= 2 spans into one Perfetto *flow* (arrow chain across thread
lanes) — the per-request timeline the fleet aggregator merges across
processes.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from tensor2robot_tpu.obs import context as context_lib

_log = logging.getLogger(__name__)


class Tracer:
  """Bounded ring of completed spans + per-thread nesting state."""

  def __init__(self, max_spans: int = 65536):
    self._epoch = time.perf_counter()
    self._spans: collections.deque = collections.deque(maxlen=max_spans)
    self._total = 0
    self._lock = threading.Lock()
    self._local = threading.local()
    self._listeners: List[Callable[[dict], None]] = []

  # -- recording -----------------------------------------------------------

  def _stack(self) -> list:
    stack = getattr(self._local, "stack", None)
    if stack is None:
      stack = self._local.stack = []
    return stack

  @contextlib.contextmanager
  def span(self, name: str, **attrs):
    """One nestable span; attrs must be JSON-serializable scalars.
    Yields the span's record, which holds its times (``ts_s``,
    ``dur_s``) once the block has ended."""
    stack = self._stack()
    parent = stack[-1] if stack else None
    depth = len(stack)
    stack.append(name)
    # Explicit attrs win over inherited context attrs. Read at entry: a
    # bind() inside the span is undone before it ends, so the context
    # is the same at both ends.
    attrs = {**context_lib.context_attrs(), **attrs}
    annotation = TraceAnnotation(
        name, **{key: _annotation_stat(value)
                 for key, value in attrs.items()
                 if isinstance(value, (str, int, float))})
    annotation.__enter__()
    record = {"name": name}
    start = time.perf_counter()
    try:
      yield record
    finally:
      duration = time.perf_counter() - start
      annotation.__exit__(None, None, None)
      stack.pop()
      record.update(ts_s=round(start - self._epoch, 6),
                    dur_s=round(duration, 6),
                    tid=threading.get_ident(), depth=depth)
      if parent is not None:
        record["parent"] = parent
      record.update(attrs)
      with self._lock:
        self._spans.append(record)
        self._total += 1
      for listener in list(self._listeners):
        try:
          listener(record)
        except Exception:  # diagnostics must never crash the path
          _log.warning("span listener %r failed", listener,
                       exc_info=True)

  def add_listener(self, listener: Callable[[dict], None]) -> None:
    """Registers a completed-span callback (e.g. the flight recorder)."""
    with self._lock:
      if listener not in self._listeners:
        self._listeners.append(listener)

  def remove_listener(self, listener: Callable[[dict], None]) -> None:
    """Unsubscribes a listener; unknown listeners are a no-op (a
    recorder detaching twice must not raise in a finally block)."""
    with self._lock:
      if listener in self._listeners:
        self._listeners.remove(listener)

  # -- readout -------------------------------------------------------------

  def spans(self) -> List[dict]:
    with self._lock:
      return list(self._spans)

  @property
  def total_spans(self) -> int:
    """Spans ever recorded (the ring may have dropped the oldest)."""
    with self._lock:
      return self._total

  def stage_counts(self) -> Dict[str, int]:
    """{first path segment of span name: count} over the retained ring."""
    counts: Dict[str, int] = {}
    for record in self.spans():
      stage = record["name"].split("/", 1)[0]
      counts[stage] = counts.get(stage, 0) + 1
    return counts

  def clear(self) -> None:
    with self._lock:
      self._spans.clear()
      self._total = 0

  def export_chrome_trace(self, path: str,
                          label: Optional[str] = None) -> str:
    """Writes the retained spans as Chrome-trace JSON (atomic tmp→mv).

    Loads directly in Perfetto / chrome://tracing; complete events
    ("ph": "X") with microsecond timestamps relative to this tracer's
    epoch, one row per Python thread. Every request id carried by
    >= 2 spans (the ``request_id``/``request_ids`` attr convention,
    obs/context.py) additionally becomes one flow — "s"/"t"/"f"
    arrow events with a shared id — so a request's enqueue → flush →
    dispatch hops across threads read as one clickable timeline.

    ``label`` overrides the ``host:pid`` process_name metadata — the
    front door (serving/frontdoor.py) exports its OWN tracer under its
    own label so the fleet merge gives the ingress hop its own lane
    and cross-lane request flows (ISSUE 19).
    """
    retained = self.spans()
    pid = os.getpid()
    # Wall-clock anchor for the fleet merge: span timestamps are
    # relative to THIS tracer's construction-time perf_counter epoch,
    # which is meaningless across processes — epoch_wall_s is that
    # epoch on the shared wall clock, so obs/aggregate.py can offset
    # each process's lane onto one comparable timeline.
    epoch_wall_s = time.time() - (time.perf_counter() - self._epoch)
    events = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": label or f"{socket.gethostname()}:{pid}",
                 "epoch_wall_s": round(epoch_wall_s, 6)},
    }]
    by_request: Dict[str, list] = {}
    for record in retained:
      args = {key: value for key, value in record.items()
              if key not in ("name", "ts_s", "dur_s", "tid")}
      events.append({
          "name": record["name"],
          "ph": "X",
          "ts": round(record["ts_s"] * 1e6, 3),
          "dur": round(record["dur_s"] * 1e6, 3),
          "pid": pid,
          "tid": record["tid"],
          "args": args,
      })
      for request_id in context_lib.span_request_ids(record):
        by_request.setdefault(request_id, []).append(record)
    events.extend(request_flow_events(by_request, pid))
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
      json.dump(payload, f)
    os.replace(tmp, path)
    return path


def _annotation_stat(value):
  """TraceMe's ``name#k=v,k=v#`` encoding cuts a value at a bare comma
  and keeps one inside brackets: the comma-joined ``request_ids``."""
  if isinstance(value, str) and "," in value:
    return f"[{value}]"
  return value


def request_flow_events(by_request: Dict[str, list], pid: int,
                        flow_ids: Optional[Dict[str, int]] = None) -> list:
  """Perfetto flow events linking each request's spans in time order.

  ``by_request`` maps request id → span records (the tracer's dict
  shape); ids with fewer than two spans emit nothing (an arrow needs
  two ends). ``flow_ids`` lets the fleet aggregator keep flow ids
  stable while merging several processes' traces — same request id in
  two files, one arrow chain across both. A record carrying its own
  ``pid`` (the aggregator's remapped per-process lanes) overrides the
  default ``pid``.
  """
  flow_ids = {} if flow_ids is None else flow_ids
  events = []
  for request_id, records in sorted(by_request.items()):
    if len(records) < 2:
      continue
    flow_id = flow_ids.setdefault(request_id, len(flow_ids) + 1)
    ordered = sorted(records, key=lambda r: r["ts_s"])
    for index, record in enumerate(ordered):
      if index == 0:
        phase = "s"
      elif index == len(ordered) - 1:
        phase = "f"
      else:
        phase = "t"
      event = {
          "name": f"request {request_id}",
          "cat": "request",
          "ph": phase,
          "id": flow_id,
          # Bind the arrow end INSIDE its slice (not at the edge) so
          # Perfetto attaches it to the enclosing span unambiguously.
          "ts": round((record["ts_s"] + record["dur_s"] / 2) * 1e6, 3),
          "pid": record.get("pid", pid),
          "tid": record["tid"],
      }
      if phase == "f":
        event["bp"] = "e"
      events.append(event)
  return events


_DEFAULT: Optional[Tracer] = None
_DEFAULT_LOCK = threading.Lock()


def get_tracer() -> Tracer:
  """The process-wide tracer every wired component records into."""
  global _DEFAULT
  with _DEFAULT_LOCK:
    if _DEFAULT is None:
      _DEFAULT = Tracer()
    return _DEFAULT


def span(name: str, **attrs):
  """``with obs.trace.span("learn/megastep", k=10): ...``"""
  return get_tracer().span(name, **attrs)


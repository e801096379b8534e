"""Flight recorder: the last N spans/events, dumped on failure.

A production fleet's worst bugs are the ones whose evidence scrolled
away: the SLO breach that shed a burst of interactive traffic, the
canary that auto-rolled-back, the collector thread that died at 3am.
The recorder keeps a BOUNDED in-memory ring of recent events (completed
spans via a tracer listener, plus explicit ``record`` calls from the
serving/replay/rollout layers) and dumps it ATOMICALLY to
``<dump_dir>/flightrec-*.json`` when a trigger fires:

- SLO breach: any shed in ``serving.batcher.MicroBatcher`` (expired at
  enqueue or capacity eviction);
- rollout auto-rollback (``serving.rollout.RolloutController``);
- an unhandled exception in any loop thread (batcher dispatcher,
  rollout worker, collector threads, the replay train loop).

Dumps are rate-limited (``min_dump_interval_s``) so an overload burst
produces one post-mortem, not a dump per shed — every trigger is still
RECORDED in the ring either way. Without a configured ``dump_dir`` the
recorder runs ring-only (record everything, write nothing): safe to
wire into every component by default.

Dump schema::

    {"schema": "t2r-flightrec-1", "host": ..., "pid": ...,
     "reason": ..., "dumped_at": <unix s>, "events_total": N,
     "trigger": {<the triggering event's fields>},   # when triggered
     "request_id": ...,   # when the trigger named one (ISSUE 12)
     "events": [{"t_s": ..., "wall_time": ..., "kind":
                 "span"|"event"|"trigger", "name": ..., ...}, ...]}
"""

from __future__ import annotations

import json
import logging
import os
import re
import socket
import threading
import time
from collections import deque
from typing import Optional

_log = logging.getLogger(__name__)

SCHEMA = "t2r-flightrec-1"

# Per-process monotonic dump sequence, shared across ALL recorder
# instances (two recorders pointed at one dir must not coalesce
# either). See dump() — ISSUE 19.
import itertools

_DUMP_SEQ = itertools.count()
_SEQ_LOCK = threading.Lock()


class FlightRecorder:
  """Bounded event ring with rate-limited atomic post-mortem dumps."""

  def __init__(self, capacity: int = 4096,
               dump_dir: Optional[str] = None,
               min_dump_interval_s: float = 5.0):
    self._events: deque = deque(maxlen=capacity)
    self._lock = threading.Lock()
    self._epoch = time.perf_counter()
    self.dump_dir = dump_dir
    self.min_dump_interval_s = min_dump_interval_s
    self._last_dump_at = -float("inf")
    self.events_total = 0
    self.dumps_written = 0
    self.dumps_suppressed = 0
    self.last_dump_path: Optional[str] = None

  def configure(self, dump_dir: Optional[str] = None,
                min_dump_interval_s: Optional[float] = None) -> None:
    """Late wiring for the process-default recorder: components record
    from construction; dumps start once someone (the owning loop/bench)
    names a directory.

    Repointing an already-configured recorder at a DIFFERENT directory
    logs a warning: on the shared process recorder that is
    last-configured-wins — the previous owner's triggers now dump into
    the new owner's logdir. Two loops in one process should each own a
    ``FlightRecorder`` instance instead (ReplayTrainLoop does since
    round 13) and leave the process recorder to the serving tier.
    """
    if dump_dir is not None:
      if self.dump_dir is not None and self.dump_dir != dump_dir:
        _log.warning(
            "flight recorder dump_dir repointed %r -> %r "
            "(last-configured-wins on a shared recorder; use "
            "per-component FlightRecorder instances to keep dumps "
            "apart)", self.dump_dir, dump_dir)
      self.dump_dir = dump_dir
    if min_dump_interval_s is not None:
      self.min_dump_interval_s = min_dump_interval_s

  # -- recording -----------------------------------------------------------

  def record(self, kind: str, name: str, **fields) -> None:
    event = {
        "t_s": round(time.perf_counter() - self._epoch, 6),
        "wall_time": time.time(),
        "kind": kind,
        "name": name,
    }
    for key, value in fields.items():
      event[key] = value if isinstance(
          value, (int, float, str, bool, type(None))) else repr(value)
    with self._lock:
      self._events.append(event)
      self.events_total += 1

  def record_span(self, span: dict) -> None:
    """Tracer-listener entry: completed spans join the ring. Attr
    values are sanitized like record()'s — a numpy scalar riding a
    span attr must not make a later dump's json.dump raise."""
    event = {}
    for key, value in span.items():
      event[key] = value if isinstance(
          value, (int, float, str, bool, type(None))) else repr(value)
    event["kind"] = "span"
    event["wall_time"] = time.time()
    with self._lock:
      self._events.append(event)
      self.events_total += 1

  def attach(self, tracer) -> None:
    tracer.add_listener(self.record_span)

  def detach(self, tracer) -> None:
    """Unsubscribes from the tracer (idempotent). Per-loop recorder
    instances attach for their run and MUST detach after it, or every
    later span in the process pays a listener call per dead loop."""
    tracer.remove_listener(self.record_span)

  def events(self) -> list:
    with self._lock:
      return list(self._events)

  # -- dumping -------------------------------------------------------------

  def dump(self, reason: str, dump_dir: Optional[str] = None,
           context: Optional[dict] = None) -> Optional[str]:
    """Writes the ring atomically (tmp → rename); returns the path, or
    None when no dump directory is configured. ``context`` (the
    triggering event's fields) lands top-level as ``trigger`` — a
    breach dump names its ``request_id`` without the reader fishing
    through the ring."""
    directory = dump_dir or self.dump_dir
    if directory is None:
      return None
    os.makedirs(directory, exist_ok=True)
    with self._lock:
      events = list(self._events)
      events_total = self.events_total
    slug = re.sub(r"[^A-Za-z0-9_-]+", "_", reason)[:48] or "unknown"
    # Monotonic per-process sequence (ISSUE 19): ms-stamped names alone
    # coalesce back-to-back dumps — two triggers inside one millisecond
    # (or two recorders sharing a dir) silently overwrote each other,
    # which is why the flywheel/health bars were stuck at "dumps >= 1".
    # N triggers now yield N files.
    with _SEQ_LOCK:
      seq = next(_DUMP_SEQ)
    path = os.path.join(
        directory,
        f"flightrec-{int(time.time() * 1e3)}-{seq:04d}-{slug}.json")
    payload = {
        "schema": SCHEMA,
        "host": socket.gethostname(),
        "pid": os.getpid(),
        "reason": reason,
        "dumped_at": time.time(),
        "events_total": events_total,
        "events": events,
    }
    if context:
      payload["trigger"] = {
          key: value if isinstance(
              value, (int, float, str, bool, type(None))) else repr(value)
          for key, value in context.items()}
      if "request_id" in context:
        payload["request_id"] = payload["trigger"]["request_id"]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
      # default=repr as a belt: a post-mortem writer must not itself
      # crash on an exotic value that slipped past sanitization.
      json.dump(payload, f, default=repr)
    os.replace(tmp, path)
    with self._lock:
      self.dumps_written += 1
      self.last_dump_path = path
    return path

  def trigger(self, reason: str, **fields) -> Optional[str]:
    """Records the trigger event, then dumps (rate-limited).

    Returns the dump path, or None when suppressed by the rate limit
    or when no dump_dir is configured — the trigger EVENT lands in the
    ring regardless, so the next written dump still carries it.
    """
    self.record("trigger", reason, **fields)
    now = time.perf_counter()
    with self._lock:
      if now - self._last_dump_at < self.min_dump_interval_s:
        self.dumps_suppressed += 1
        return None
      self._last_dump_at = now
    return self.dump(reason, context=fields)


_DEFAULT: Optional[FlightRecorder] = None
_DEFAULT_LOCK = threading.Lock()


def get_recorder() -> FlightRecorder:
  """The process-wide recorder; subscribed to the process tracer on
  first access so recent spans are always part of a post-mortem."""
  global _DEFAULT
  with _DEFAULT_LOCK:
    if _DEFAULT is None:
      _DEFAULT = FlightRecorder()
      from tensor2robot_tpu.obs import trace
      _DEFAULT.attach(trace.get_tracer())
    return _DEFAULT

"""The program's own spans (`tensor2robot_tpu.obs.trace`, the process's
ring) of a run's window, for the per-layer metrics of source
`program_span`.

The harness runs the program in its own process, so the ring is read in
place: it holds 65,536 spans, and a serving window makes a few thousand.
The window's spans are those that start in the `window_s` seconds that
end with the last span named `closing` (`serve/flush` in a serving
cell, `train/dispatch` in a training cell): set-up's warm-up and warm
traffic lie before them. A program without these spans (an earlier
commit) gives no spans and every reader None.
"""

from __future__ import annotations

import statistics

FLUSH = "serve/flush"
DISPATCH = "train/dispatch"


def window_spans(run, closing):
  try:
    from tensor2robot_tpu.obs import trace
  except ImportError:
    return []
  spans = trace.get_tracer().spans()
  ends = [s["ts_s"] + s["dur_s"] for s in spans if s["name"] == closing]
  if not ends:
    return []
  end = max(ends)
  start = end - run["window"]["window_s"]
  return [s for s in spans if start <= s["ts_s"] <= end]


def durations(spans, *names):
  return [s["dur_s"] for s in spans if s["name"] in names]


def flush_share_percent(run, *names):
  """Seconds in the spans `names` over seconds in `serve/flush`, all
  flushes of the window, in percent; None without either."""
  spans = window_spans(run, FLUSH)
  part, whole = durations(spans, *names), sum(durations(spans, FLUSH))
  if not part or not whole:
    return None
  return 100.0 * sum(part) / whole


def median_ms(run, name):
  """Median duration of the window's spans `name` (which also close
  the window), in ms; None without one."""
  found = durations(window_spans(run, name), name)
  return 1e3 * statistics.median(found) if found else None

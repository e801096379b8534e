"""From a profiler trace (.xplane.pb) to device busy time, idle gaps by
what the host was doing, and the device operations that took most time.

Only `jax.profiler.ProfileData` is used to read the file. A device plane
is one whose name starts with "/device:TPU:"; its operations are the
events of the line named "XLA Ops". Host spans are the events named
"bench/..." (the harness's own `TraceAnnotation`s) on any line of the
plane "/host:CPU"; the runtime's own host events of MIN_HOST_EVENT_NS or
longer are kept too, to name a gap no span of the harness covers.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
HOST_PLANE = "/host:CPU"
MIN_HOST_EVENT_NS = 100e3


def find_xplane(trace_dir):
  paths = sorted(glob.glob(os.path.join(
      trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
  if not paths:
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
  return paths[-1]


def load(path):
  """{"devices": {plane: [(name, start_ns, end_ns)]}, "spans": [...],
  "host_events": [...]}"""
  from jax.profiler import ProfileData
  data = ProfileData.from_file(path)
  devices, spans, host_events = {}, [], []
  for plane in data.planes:
    if plane.name.startswith(DEVICE_PREFIX):
      ops = []
      for line in plane.lines:
        if line.name != OPS_LINE:
          continue
        for event in line.events:
          start = float(event.start_ns)
          ops.append((event.name, start, start + float(event.duration_ns)))
      devices[plane.name] = ops
    elif plane.name == HOST_PLANE:
      for line in plane.lines:
        for event in line.events:
          start, length = float(event.start_ns), float(event.duration_ns)
          if event.name.startswith(SPAN_PREFIX):
            spans.append((event.name, start, start + length))
          elif length >= MIN_HOST_EVENT_NS:
            host_events.append((event.name[:64], start, start + length))
  return {"devices": devices, "spans": spans, "host_events": host_events}


def short_name(name, limit=64):
  """'%fusion.12 = bf16[...] fusion(...)' -> '%fusion.12 fusion'."""
  head, _, rest = name.partition(" = ")
  match = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
  if match and not head.lstrip("%").startswith(match.group(1)):
    head = f"{head} {match.group(1)}"
  return head[:limit]


def self_seconds(ops):
  """{short name: seconds} with the time of the ops an op contains (a
  while loop's body) taken out of it, so that the table adds up to the
  busy time."""
  totals, stack = {}, []

  def close(upto):
    while stack and stack[-1][2] <= upto:
      name, start, end, inner = stack.pop()
      own = (end - start) - inner
      totals[name] = totals.get(name, 0.0) + own / 1e9
      if stack:
        stack[-1][3] += end - start

  for name, start, end in sorted(ops, key=lambda op: (op[1], -op[2])):
    close(start)
    stack.append([short_name(name), start, end, 0.0])
  close(float("inf"))
  return totals


def busy_intervals(ops):
  """Union of [start, end) intervals, sorted and merged."""
  merged = []
  for _, start, end in sorted(ops, key=lambda op: op[1]):
    if merged and start <= merged[-1][1]:
      if end > merged[-1][1]:
        merged[-1][1] = end
    else:
      merged.append([start, end])
  return merged


def _span_at(spans, t):
  """Innermost (shortest) host span covering time t, or None."""
  best = None
  for name, start, end in spans:
    if start <= t < end and (best is None or end - start < best[1]):
      best = (name, end - start)
  return best[0] if best else None


def summarize(loaded, top=10):
  """busy_s averaged over the device planes that ran anything, the top
  device ops by summed seconds (over all devices), and the longest
  idle gaps of the busiest device named by the host span at each gap's
  middle."""
  planes = {name: ops for name, ops in loaded["devices"].items() if ops}
  if not planes:
    return None
  busy_by_plane, totals = {}, {}
  for name, ops in planes.items():
    merged = busy_intervals(ops)
    busy_by_plane[name] = sum(end - start for start, end in merged) / 1e9
    for op, seconds in self_seconds(ops).items():
      totals[op] = totals.get(op, 0.0) + seconds
  busiest = max(busy_by_plane, key=busy_by_plane.get)
  merged = busy_intervals(planes[busiest])
  gaps = {}
  for (_, prev_end), (next_start, _) in zip(merged, merged[1:]):
    gap = (next_start - prev_end) / 1e9
    middle = (prev_end + next_start) / 2
    what = (_span_at(loaded["spans"], middle)
            or _span_at(loaded.get("host_events", ()), middle) or "no_span")
    gaps[what] = gaps.get(what, 0.0) + gap
  rank = lambda table: [[k, v] for k, v in sorted(
      table.items(), key=lambda kv: -kv[1])[:top]]
  return {
      "busy_s": sum(busy_by_plane.values()) / len(busy_by_plane),
      "busy_by_device": busy_by_plane,
      "device_ops": rank(totals),
      "idle_gaps": rank(gaps),
  }


def idle_share_percent(run):
  """The per-layer metrics `device_idle_share.*`: 1 - (union of
  device-op intervals / traced window), mean over chips, in percent.
  `run` is the harness's record of a run; untraced, nothing to read."""
  trace = run["trace"]
  if trace is None:
    return None
  return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""The window's spans, or none where the ring no longer holds them all.

The ring (`tensor2robot_tpu.obs.trace`) keeps the newest 65,536 spans
and drops the oldest as each further one completes. A share read over a
window whose first spans were dropped is a share of another, shorter
window under the whole one's name: an untraced 30 s serving window at
1,430 actions/s makes about 55,000 spans (a `serve/enqueue` an action
and ten or so a flush), and a faster program makes more. A reader
that goes through `spans` here gives None then, as it does where the
program has no such span.
"""

from benchmark.trace import program_spans


def spans(run, closing):
  """`program_spans.window_spans(run, closing)`; [] where the ring has
  dropped spans and the oldest it still holds ended inside the window:
  whatever was dropped completed before that one, so nothing of the
  window is missing only if that one ended before the window began."""
  try:
    from tensor2robot_tpu.obs import trace
  except ImportError:
    return []
  tracer = trace.get_tracer()
  retained = tracer.spans()
  found = program_spans.window_spans(run, closing)
  if not found or tracer.total_spans <= len(retained):
    return found
  end = max(s["ts_s"] + s["dur_s"] for s in found if s["name"] == closing)
  oldest = retained[0]
  if oldest["ts_s"] + oldest["dur_s"] > end - run["window"]["window_s"]:
    return []
  return found


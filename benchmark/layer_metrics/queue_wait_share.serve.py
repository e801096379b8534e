"""Of the time the window's requests spent in a replica (enqueue to
answer), the share spent waiting for their flush to start: over the
`serve/flush` spans, sum of `queue_wait_ms_sum` / sum of
(`queue_wait_ms_sum` + `batch` x the span's duration)."""

from benchmark.trace import program_spans


def read(run):
  flushes = [s for s in program_spans.window_spans(run, program_spans.FLUSH)
             if s["name"] == program_spans.FLUSH
             and "queue_wait_ms_sum" in s]
  waited = sum(s["queue_wait_ms_sum"] for s in flushes)
  served = sum(s["batch"] * s["dur_s"] * 1e3 for s in flushes)
  if not flushes or not waited + served:
    return None
  return 100.0 * waited / (waited + served)

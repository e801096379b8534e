"""Share of the window the replicas' dispatcher threads spent inside a
flush: sum of `serve/flush` durations / (window x replicas, one a
chip). Well under 100 means the load, not the server, sets the rate."""

from benchmark.trace import program_spans


def read(run):
  flushes = program_spans.durations(
      program_spans.window_spans(run, program_spans.FLUSH),
      program_spans.FLUSH)
  if not flushes:
    return None
  return 100.0 * sum(flushes) / (run["window"]["window_s"] * run["chips"])

"""Of the window's flushes, the share popped while another flush of the
same batcher was still open (the `serve/flush` span's `in_flight` attr,
0 or 1 at a depth of two): that flush's stack, pad and put ran beside
the other's device time. Near 100 where two full batches always exist;
0 where the load stays under one full batch per flush time. None where
no span carries the attr (a program with one flush at a time)."""

from benchmark.trace import program_spans


def read(run):
  flushes = [s for s in program_spans.window_spans(run, program_spans.FLUSH)
             if s["name"] == program_spans.FLUSH and "in_flight" in s]
  if not flushes:
    return None
  return 100.0 * sum(s["in_flight"] >= 1 for s in flushes) / len(flushes)

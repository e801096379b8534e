"""Share of a flush that its enqueued program spent waiting for the
flush's own frames to land: the mean `serve/transfer_wait` / the mean
`serve/flush`. The span is the first child of `serve/readback` on the
holds the policy splits (one in a few: the wait wakes the dispatcher
once more, so the flushes that carry it stand for the others): the
wait, on the thread that is blocked there anyway, for what of the
runtime's relayout and H2D copy of this flush's frames was still
outstanding once its program was enqueued; 0 where the transfer was
hidden under the other flush's turn. None where the program has no such
span, or the ring no longer holds the window."""

import statistics

from benchmark.trace import program_spans
from benchmark.trace import whole_window

TRANSFER_WAIT = "serve/transfer_wait"


def read(run):
  found = whole_window.spans(run, program_spans.FLUSH)
  waits = program_spans.durations(found, TRANSFER_WAIT)
  flushes = program_spans.durations(found, program_spans.FLUSH)
  if not waits or not flushes:
    return None
  return 100.0 * statistics.mean(waits) / statistics.mean(flushes)

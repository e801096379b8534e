"""Of the window's flushes, the share whose frames had landed on the
device when the flush's turn came (the `serve/turn` span's `landed`, 1
or 0, read once the turn lock is held): their relayout and H2D copy was
hidden under the other flush's program. 100 where the wire keeps ahead
of the device, 0 where every program waits for its own frames. None
where no turn carries the attr (a program from before it), or the ring
no longer holds the window."""

from benchmark.trace import program_spans
from benchmark.trace import whole_window

TURN = "serve/turn"


def read(run):
  turns = [s for s in whole_window.spans(run, program_spans.FLUSH)
           if s["name"] == TURN and "landed" in s]
  if not turns:
    return None
  return 100.0 * sum(s["landed"] >= 1 for s in turns) / len(turns)

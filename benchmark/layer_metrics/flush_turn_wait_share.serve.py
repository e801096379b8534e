"""Share of the flush time spent waiting for the policy's device turn,
the other flush's program being on the device: `serve/turn` /
`serve/flush`, over the turns that carry `landed` (a program that says
whether the flush's frames had landed when its turn came: the turn is
then one of the seven parts a flush adds up from, beside assemble, put,
`serve/execute`, the transfer wait, the program wait and D2H). None
where no turn carries it, or the ring no longer holds the window."""

from benchmark.trace import program_spans
from benchmark.trace import whole_window

TURN = "serve/turn"


def read(run):
  found = whole_window.spans(run, program_spans.FLUSH)
  turns = [s["dur_s"] for s in found if s["name"] == TURN and "landed" in s]
  flushes = sum(program_spans.durations(found, program_spans.FLUSH))
  if not turns or not flushes:
    return None
  return 100.0 * sum(turns) / flushes

"""Host time of one train dispatch (flattening the arguments and the
enqueue, `train/dispatch`): the median over the window, in ms."""

from benchmark.trace import program_spans


def read(run):
  return program_spans.median_ms(run, program_spans.DISPATCH)

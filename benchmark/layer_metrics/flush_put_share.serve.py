"""Share of the flush time spent in `device_put` of the padded frames
and seeds: `serve/put` / `serve/flush`. The call returns once the
runtime has the transfer, so this is the hand-off; the relayout and the
H2D copy themselves are waited for in `serve/readback`."""

from benchmark.trace import program_spans


def read(run):
  return program_spans.flush_share_percent(run, "serve/put")

"""Share of the flush time spent stacking the frames and padding them
to the bucket on the host: (`serve/stack` + `serve/pad`) /
`serve/flush`."""

from benchmark.trace import program_spans


def read(run):
  return program_spans.flush_share_percent(run, "serve/stack", "serve/pad")

"""Share of the flush time spent calling the CEM program and waiting
for its answer, which is the wait for the runtime's transfer of the
frames and for the device: (`serve/execute` + `serve/readback`) /
`serve/flush`."""

from benchmark.trace import program_spans


def read(run):
  return program_spans.flush_share_percent(
      run, "serve/execute", "serve/readback")

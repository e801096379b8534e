"""Of the window's flushes, the share whose frames were stacked into a
host staging array the policy already held (the `serve/stack` span's
`reused` attr, 1 or 0), not into one made, faulted in and unmapped for
that flush. 100 but for each dispatcher's first flush at a rung. None
where no span carries the attr (a program that stacks into a new array
every time)."""

from benchmark.trace import program_spans

STACK = "serve/stack"


def read(run):
  stacks = [s for s in program_spans.window_spans(run, program_spans.FLUSH)
            if s["name"] == STACK and "reused" in s]
  if not stacks:
    return None
  return 100.0 * sum(s["reused"] >= 1 for s in stacks) / len(stacks)

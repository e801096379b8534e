"""Of the window's flushes, the share whose bucket program encoded each
frame once and ran the CEM search over the code (the `serve/execute`
span's `encode_once` attr, 1 or 0): the predictor offered the model's
factored pair when the bucket compiled. 100 where it did; 0 where the
program tiles each frame across its candidate actions. None where no
span carries the attr (a program that only tiles)."""

from benchmark.trace import program_spans

EXECUTE = "serve/execute"


def read(run):
  executes = [s for s in program_spans.window_spans(run, program_spans.FLUSH)
              if s["name"] == EXECUTE and "encode_once" in s]
  if not executes:
    return None
  return 100.0 * sum(s["encode_once"] >= 1 for s in executes) / len(executes)

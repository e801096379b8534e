"""Of the window's flushes, the share whose bucket program expanded each
frame's code across its candidate actions inside the post tower's first
convolution (the `serve/execute` span's `expand_in_conv` attr, 1 or 0):
the CEM recipe held the factored pair and wrote the expansion on the
merged (frame, candidate) row axis. 100 where it did; 0 where the
program writes a tiled copy of the code, or of the frame, in every CEM
iteration. None where no span carries the attr (a program from before
the attr)."""

from benchmark.trace import program_spans

EXECUTE = "serve/execute"


def read(run):
  executes = [s for s in program_spans.window_spans(run, program_spans.FLUSH)
              if s["name"] == EXECUTE and "expand_in_conv" in s]
  if not executes:
    return None
  return 100.0 * sum(
      s["expand_in_conv"] >= 1 for s in executes) / len(executes)

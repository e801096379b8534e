"""How much of the step the gated short convolutions are: the device
self time of every operation under the scope `short_conv/` (each conv
layer's input projection, the gates and taps, and the output
projection; first run, recomputation and backward) / the trace's busy
time. Lower is the mixer made cheaper. Nothing to read without the
driver's `short_conv` record (an untraced run, a program without the
scope)."""


def read(run):
  found = run["window"].get("short_conv")
  if not found or not run["trace"] or not run["trace"]["busy_s"]:
    return None
  return 100.0 * found["scope_seconds"] / run["trace"]["busy_s"]

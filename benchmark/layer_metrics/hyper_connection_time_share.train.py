"""How much of the step the multi-stream residual is: the device self
time of every operation under the scope `mhc/` (the hyper-connections'
Pallas programs and XLA's operations around them, first run,
recomputation and backward) / the trace's busy time. Lower is the
stream passes made cheaper; the sublayers between them (attention, the
MLP, the expert layer) are not in it. Nothing to read without the
driver's `hyper_connection` record (an untraced run, a program without
the passes)."""


def read(run):
  found = run["window"].get("hyper_connection")
  if not found or not run["trace"] or not run["trace"]["busy_s"]:
    return None
  return 100.0 * found["scope_seconds"] / run["trace"]["busy_s"]

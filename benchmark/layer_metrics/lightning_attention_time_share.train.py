"""How much of the step Lightning attention's walk is: the device self
time of every operation under the scope `lightning/scan` (the
chunk-local part and the walk's two Pallas programs, first run,
recomputation and backward) / the trace's busy time. Lower is the walk
made cheaper; the layers' projections, norms and output gate
(`lightning/proj`, `lightning/out`) are not in it. Nothing to read
without the driver's `lightning_attention` record (an untraced run, a
program without the scope)."""


def read(run):
  found = run["window"].get("lightning_attention")
  if not found or not run["trace"] or not run["trace"]["busy_s"]:
    return None
  return 100.0 * found["scope_seconds"] / run["trace"]["busy_s"]

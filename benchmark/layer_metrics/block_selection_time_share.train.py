"""How much of the step the sparse layers' block selection is: the device
self time of every operation under the scope `sala/select` (compressed
keys, their scores, the block maxima and the top-k, first run and
recomputation; it has no backward) / the trace's busy time. Lower is the
selection made cheaper. Nothing to read without the driver's
`block_selection` record (an untraced run, a program without the
scope)."""


def read(run):
  found = run["window"].get("block_selection")
  if not found or not run["trace"] or not run["trace"]["busy_s"]:
    return None
  return 100.0 * found["scope_seconds"] / run["trace"]["busy_s"]

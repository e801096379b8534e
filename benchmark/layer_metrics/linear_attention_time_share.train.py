"""How much of the step the linear-attention mechanism is: the device
self time of every operation under the scope `gdn/rule` (the gated delta
rule's two Pallas programs and XLA's chunk-local products around them,
first run, recomputation and backward) / the trace's busy time. Lower is
the rule made cheaper; the layers' projections, convolution and output
gate (`gdn/proj`, `gdn/conv`, `gdn/out`) are not in it. Nothing to read
without the driver's `gated_delta_rule` record (an untraced run, a
program without the rule)."""


def read(run):
  found = run["window"].get("gated_delta_rule")
  if not found or not run["trace"] or not run["trace"]["busy_s"]:
    return None
  return 100.0 * found["scope_seconds"] / run["trace"]["busy_s"]

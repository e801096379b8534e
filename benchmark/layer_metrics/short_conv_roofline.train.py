"""The gated short convolutions' share of their roofline: the least time
the chip could take for the mixer's work (`flops/<config>.py:
short_conv_kernel`: its three parts' operations and bytes at their
boundaries, forward and backward, nothing recomputed counted; per part
the larger of operations / bf16 peak and bytes / HBM peak) for every
conv layer of every step the traced window ran / the device self time
of every operation under the scope `short_conv/` (projections, gates
and taps, first run, recomputation and backward). The same yardstick
whatever implements the parts. The driver reads those seconds out of
the trace in `release()`, while it stands; without them there is
nothing to read."""


def read(run):
  found = run["window"].get("short_conv")
  work = getattr(run["cell"].flops, "short_conv_kernel", None)
  layers = getattr(run["cell"].flops, "_layers", None)
  counters = run["window"].get("counters", {})
  if (not found or work is None or layers is None
      or not found["scope_seconds"] or not counters.get("dispatches")):
    return None
  config, peaks = run["cell"].config, run["peaks"]
  least = lambda part: max(part["flops"] / peaks["bf16_flops_per_s"],
                           part["bytes"] / peaks["hbm_bytes_per_s"])
  per_layer = sum(least(part) for part in work(config).values())
  sequences = (counters["dispatches"] * counters["scan_steps"]
               * counters["global_batch"])
  needed = sequences * layers(config)[0] * per_layer
  return 100.0 * needed / found["scope_seconds"]

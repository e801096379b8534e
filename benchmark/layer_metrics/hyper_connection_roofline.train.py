"""The hyper-connections' stream passes' share of their roofline: the
least time the chip could take for the passes' work
(`flops/<config>.py: hyper_connection_kernel`: operations and bytes at
the boundary of `hyper_connection_pre()` / `hyper_connection_post()` and
of their backward passes at the configuration's stream dtype, the same
whatever implements them; per run the larger of operations / bf16 peak
and bytes / HBM peak, which at these shapes is the bytes) over every run
the trace holds of them (one run a call of the matching Pallas program,
also where a block is recomputed; in every sublayer of every block) /
the device self time of every operation under the scope `mhc/`: the
Pallas programs and XLA's operations around them alike, so that work
moved from the one to the other moves nothing here but the time it
saves. The driver reads those seconds out of the trace in `release()`,
while it stands; without them (an untraced run, a program without the
passes, one of their programs missing, names that do not join) there is
nothing to read."""


def read(run):
  found = run["window"].get("hyper_connection")
  work = getattr(run["cell"].flops, "hyper_connection_kernel", None)
  if not found or work is None or not found["scope_seconds"]:
    return None
  per_run = work(run["cell"].config)
  peaks = run["peaks"]
  least = lambda part: max(part["flops"] / peaks["bf16_flops_per_s"],
                           part["bytes"] / peaks["hbm_bytes_per_s"])
  sequences = run["cell"].traffic["batch_per_chip"]
  needed = sum(runs * sequences * least(per_run[program])
               for program, runs in found["calls"].items())
  return 100.0 * needed / found["scope_seconds"]

"""The gated delta rule's share of its roofline: the least time the chip
could take for the rule's work (`flops/<config>.py:
gated_delta_rule_kernel`: the recurrent form's operations and bytes at
the boundary of `gated_delta_rule()`, nothing recomputed counted; per
run the larger of operations / bf16 peak and bytes / HBM peak, which at
these shapes is the bytes) over every run the trace holds of the rule
(one forward run a call of its forward program, also where a block is
recomputed; one backward run a call of its backward program; in every
delta-net layer) / the device self time of every operation under the
scope `gdn/rule`: the rule's two Pallas programs and XLA's chunk-local
products around them alike, so that work moved from the one to the
other moves nothing here but the time it saves. The driver reads those
seconds out of the trace in `release()`, while it stands; without them
(an untraced run, a program without the rule, one of its programs
missing, names that do not join) there is nothing to read."""


def read(run):
  found = run["window"].get("gated_delta_rule")
  work = getattr(run["cell"].flops, "gated_delta_rule_kernel", None)
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

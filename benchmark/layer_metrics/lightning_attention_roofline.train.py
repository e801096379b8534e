"""Lightning attention's share of its roofline: the least time the chip
could take for its work (`flops/<config>.py:
lightning_attention_kernel`: the recurrent form's operations and bytes
at the boundary of `lightning_attention()`, nothing recomputed counted;
per run the larger of operations / bf16 peak and bytes / HBM peak) over
every run the trace holds (one forward run a call of its forward walk,
also where a block is recomputed; one backward run a call of its
backward walk; in every Lightning layer) / the device self time of every
operation under the scope `lightning/scan`: the walk's two Pallas
programs and the chunk-local part around them. The driver reads those
seconds out of the trace in `release()`, while it stands; without them
there is nothing to read."""


def read(run):
  found = run["window"].get("lightning_attention")
  work = getattr(run["cell"].flops, "lightning_attention_kernel", None)
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

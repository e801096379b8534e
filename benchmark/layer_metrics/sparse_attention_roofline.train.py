"""The block-sparse attention kernel's share of its roofline: the least
time the chip could take for the kernel's work (`flops/<config>.py:
sparse_attention_kernel`: the operations and bytes of attention over the
keys the queries select, nothing recomputed and nothing a tile visits
for another of its tokens counted; per run the larger of operations /
bf16 peak and bytes / HBM peak) over every run the trace holds (one
forward run a call of its forward program, also where a block is
recomputed; one backward run a call of its backward program; in every
sparse layer) / the device self time of every operation under the scope
`sala/attend`: the kernel's two Pallas programs, the visit plan and the
layout around them. The driver reads those seconds out of the trace in
`release()`, while it stands; without them (an untraced run, a program
without the kernel, one of its programs missing, names that do not
join) there is nothing to read."""


def read(run):
  found = run["window"].get("sparse_attention")
  work = getattr(run["cell"].flops, "sparse_attention_kernel", None)
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

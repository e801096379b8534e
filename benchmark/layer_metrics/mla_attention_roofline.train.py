"""The flash-attention kernel's share of the chip's bf16 peak: the
operations its algorithm needs (`flops/<config>.py: attention_kernel`,
causal, nothing recomputed counted) over every call the trace holds of
its three programs (forward, also where a block is recomputed, dq and
dk/dv, in every block) / the device self time of those same calls /
peak. At these shapes the kernel is bound by operations, not bytes
(forward: 687 GFLOP against 0.34 GB a call). The driver reads the calls'
seconds out of the trace in `release()`, while it stands; without them
(an untraced run, a program whose kernel has other names, one of the
three programs missing) there is nothing to read."""


def read(run):
  found = run["window"].get("attention_kernel")
  kernel = getattr(run["cell"].flops, "attention_kernel", None)
  if not found or kernel is None:
    return None
  per_call = kernel(run["cell"].config)
  sequences = run["cell"].traffic["batch_per_chip"]
  needed = sum(calls * sequences * per_call[program]["flops"]
               for program, calls in found["calls"].items())
  spent = sum(found["seconds"].values())
  if not spent:
    return None
  return 100.0 * needed / spent / run["peaks"]["bf16_flops_per_s"]

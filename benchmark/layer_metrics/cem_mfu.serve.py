"""CEM control steps' share of the chip's bf16 peak: operations per
action from the configuration's shapes x actions answered per second
per chip of this (traced) run / peak. Padding rows count as no work."""


def read(run):
  window, cell = run["window"], run["cell"]
  if "actions" not in window:
    return None
  rate = window["actions"] / window["window_s"] / run["chips"]
  flops = cell.flops.serve_per_action(cell.config)
  return 100.0 * flops * rate / run["peaks"]["bf16_flops_per_s"]

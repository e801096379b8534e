"""Whole train step's share of the chip's bf16 peak: operations per
example from the configuration's shapes x examples per second per chip
of this (traced) run / peak."""


def read(run):
  window, cell = run["window"], run["cell"]
  if "examples" not in window:
    return None
  rate = window["examples"] / window["window_s"] / run["chips"]
  flops = cell.flops.train_per_example(cell.config)
  return 100.0 * flops * rate / run["peaks"]["bf16_flops_per_s"]

"""Driver `train_resident_conv`: `train_resident_sala`'s scoped session for
a sequence model whose token mixers are gated short convolutions beside
grouped-query attention, over sparse experts (LFM2). K scanned optimizer
steps per dispatch through `Trainer.train_steps`, the K-stack of seeded
token sequences resident on the device and re-fed every dispatch, a
bounded number in flight; one example is one sequence.

Traffic parameters (benchmark/traffic/<name>.json): as
`train_resident_tokens`'s (sequence_length, batch_per_chip, scan_steps,
in_flight).

Why a driver of its own: the sparse/linear driver's session reads the
step metrics it compares, and the scopes it times, off its subclass;
this one compares the conv layers' `conv/*` and the expert layers'
`moe/expert_tokens`, times the scope `short_conv/` (and `short_conv/op`
within it; the program's `SHORT_CONV_SCOPE`), and reads the flash
kernel's calls. Everything else is
imported: the seeds, the K-stack, the stand-ins, the set-up, window and
follower from `train_resident_sala`, the flash kernel's reader from
`train_resident_tokens`.

What is compared (see `compare`): the first dispatch, which set-up
drives through the window's own call on the window's own K-stack,
against the plain reference following the same K steps from the same
seed: the last step's loss, Adam's first moment and the parameters'
change by the worst and the median leaf, the tokens each held expert saw
in each layer at the last step, each conv layer's root mean square of
B * x~ and of C * z at the last step, and the step counter.

In a traced run `release()` reads out of the trace, which still stands
then, the device time of every operation the compiled step names under
`short_conv/` (the mixers' projections and the op, first run,
recomputation and backward) and under `short_conv/op`, and the flash
kernel's calls and seconds, and hands them on in the window record.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark import harness
from benchmark.drivers.train_resident_sala import ScopedSession, leaf_numbers
from benchmark.drivers.train_resident_tokens import attention_kernel_seconds
from tensor2robot_tpu.layers.sequence import SHORT_CONV_SCOPE

MIXER_SCOPE, OP_SCOPE = SHORT_CONV_SCOPE + "/", SHORT_CONV_SCOPE + "/op"


def compare(first, followed, limits):
  """[(name, value, limit)] from two runs' leaf norms and last-step
  numbers; a number the cell's limits file does not name has the limit
  None: it is read and printed, not compared."""
  # Each conv layer's two numbers: the widest gap against the
  # reference's own.
  rms_gap = max(float(np.max(
      np.abs(np.asarray(first[name], np.float64)
             - np.asarray(followed[name], np.float64))
      / np.abs(np.asarray(followed[name], np.float64))))
                for name in ("conv_u_rms", "conv_y_rms"))
  counts = np.asarray(followed["expert_tokens"], np.float64)
  numbers = dict(
      leaf_numbers(first, followed),
      conv_rms_gap=rms_gap,
      # Tokens on each held expert in each layer at the last step: L1
      # distance over the reference's total.
      expert_count_gap=float(
          np.abs(np.asarray(first["expert_tokens"], np.float64)
                 - counts).sum() / counts.sum()))
  return [(name, value, limits.get(name)) for name, value in numbers.items()]


class Session(ScopedSession):
  """The conv/attention expert cell's: the conv layers' gated RMS and the
  held experts' loads compared, the short-convolution mixer timed."""
  COMPARED = {"conv_u_rms": "conv/u_rms", "conv_y_rms": "conv/y_rms",
              "expert_tokens": "moe/expert_tokens"}
  FOLLOWED = tuple(COMPARED)
  COUNTERS = ("conv/u_rms", "conv/y_rms", "moe/expert_tokens",
              "moe/held_assignments", "moe/total_assignments",
              "moe/max_expert_tokens", "moe/min_expert_tokens")
  compare = staticmethod(compare)

  def scopes(self):
    return (("short_conv", MIXER_SCOPE, ()), ("short_conv_op", OP_SCOPE, ()))

  def release(self):
    super().release()
    if self._window is None:
      return
    found = attention_kernel_seconds(os.path.join(
        harness.ROOT, "benchmark_out", "trace", self._cell.name))
    if found is not None:
      self._window["attention_kernel"] = found

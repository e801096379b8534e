"""Tiny preset of the conv/attention expert cell for the CPU rehearsals:
the cell's own files with every size cut down (the published layer
pattern and the held slice of it kept), the model computing in float32
(few positions to average bfloat16's rounding over), and the expert
bias drawn ten times wider (over 64 tokens a bias of 0.01 moves few
choices, and the planted fault that ignores it would move nothing):
importing the preset widens the reference's draw."""

import jax.numpy as jnp

from benchmark import harness
from benchmark.reference import lfm2_8b_a1b_ep4 as reference

reference.EXPERT_BIAS_DRAW = 0.1

SIZES = dict(
    sequence_length=32, vocab_size=64, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=48, moe_intermediate_size=16)


def train_cell(workload="lfm2_moe_train_seq8k", batch=2, steps=2,
               routed=16, held=4, first=4):
  """`held` of `routed` experts from `first`: a share in the middle."""
  cell = harness.load_cell(workload)
  cell.config.update(SIZES, num_experts=held, router_width=routed,
                     first_expert=first)
  cell.config["model"]["kwargs"] = dict(
      cell.config["model"]["kwargs"], **SIZES, n_routed_experts=routed,
      experts_held=held, first_expert=first, compute_dtype=jnp.float32)
  cell.traffic.update(sequence_length=SIZES["sequence_length"],
                      batch_per_chip=batch, scan_steps=steps)
  return cell

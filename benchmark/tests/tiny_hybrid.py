"""Tiny preset of the hybrid sequence cell for the CPU rehearsals: the
cell's own files with every size cut down and the model computing in
float32 (few positions to average bfloat16's rounding over)."""

import jax.numpy as jnp

from benchmark import harness

SIZES = dict(
    sequence_length=32, vocab_size=64, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    partial_rotary_factor=0.5, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_num_key_heads=2,
    linear_num_value_heads=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_hidden_layers=4,
    num_experts_per_tok=3)


def train_cell(workload="qwen3_next_train_seq8k", batch=2, steps=2,
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

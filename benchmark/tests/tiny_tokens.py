"""Tiny preset of the sequence cell for the CPU rehearsals: the cell's
own files with every size cut down and the model computing in float32
(few positions to average bfloat16's rounding over)."""

import jax.numpy as jnp

from benchmark import harness

SIZES = dict(
    sequence_length=32, vocab_size=64, hidden_size=32,
    num_attention_heads=2, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    intermediate_size=48, moe_intermediate_size=16, num_hidden_layers=3,
    num_experts_per_tok=3)


def train_cell(workload="joyai_flash_train_seq8k", batch=2, steps=2,
               routed=16, held=4, first=4):
  """`held` of `routed` experts from `first`: a share in the middle."""
  cell = harness.load_cell(workload)
  cell.config.update(SIZES, n_routed_experts=held, router_width=routed,
                     first_expert=first)
  cell.config["model"]["kwargs"] = dict(
      SIZES, n_routed_experts=routed, experts_held=held, first_expert=first,
      compute_dtype=jnp.float32)
  cell.traffic.update(sequence_length=SIZES["sequence_length"],
                      batch_per_chip=batch, scan_steps=steps)
  return cell

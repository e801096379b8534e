"""Tiny preset of the multi-stream sequence cell for the CPU rehearsals:
the cell's own files with every size cut down and the model computing in
float32 (few positions to average bfloat16's rounding over). YaRN's
original context is cut with the sequence, so that the ramp still lies
inside the rotary pairs."""

import jax.numpy as jnp

from benchmark import harness

SIZES = dict(
    sequence_length=32, vocab_size=64, hidden_size=32,
    num_attention_heads=2, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    intermediate_size=48, moe_intermediate_size=16, num_hidden_layers=3,
    num_experts_per_tok=2)


def train_cell(workload="xing4_train_seq4k", batch=2, steps=2, routed=8,
               held=4, first=2, mtp=0):
  """`held` of `routed` experts from `first`: a share in the middle."""
  cell = harness.load_cell(workload)
  scaling = dict(cell.config["rope_scaling"],
                 original_max_position_embeddings=16)
  cell.config.update(SIZES, n_routed_experts=held, router_width=routed,
                     first_expert=first, rope_scaling=scaling,
                     num_nextn_predict_layers=mtp)
  cell.config["model"]["kwargs"] = dict(
      cell.config["model"]["kwargs"], **SIZES, n_routed_experts=routed,
      experts_held=held, first_expert=first, rope_scaling=scaling,
      num_nextn_predict_layers=mtp, compute_dtype=jnp.float32)
  cell.traffic.update(sequence_length=SIZES["sequence_length"],
                      batch_per_chip=batch, scan_steps=steps)
  return cell

"""Tiny preset of the sparse/linear-attention sequence cell for the CPU
rehearsals: the cell's own files with every size cut down, the sequence
long enough (256 tokens in blocks of 8) that the selection is sparse,
and the model computing in float32 (few positions to average bfloat16's
rounding over)."""

import jax.numpy as jnp

from benchmark import harness

SPARSE = {"block_size": 8, "kernel_size": 4, "kernel_stride": 2,
          "init_blocks": 1, "window_size": 16, "topk": 4, "dense_len": 128}
SIZES = dict(
    sequence_length=256, vocab_size=64, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=1, head_dim=16,
    lightning_nh=2, lightning_nkv=2, lightning_head_dim=16,
    num_hidden_layers=4)


def train_cell(workload="minicpm_sala_train_seq32k", batch=2, steps=2,
               width=24, first_head=0):
  """The FFN `width` columns wide, heads from `first_head`."""
  cell = harness.load_cell(workload)
  cell.config.update(SIZES, intermediate_held=width, first_head=first_head,
                     sparse_config=SPARSE)
  cell.config["model"]["kwargs"] = dict(
      cell.config["model"]["kwargs"], **SIZES, intermediate_size=width,
      first_head=first_head, sparse_config=SPARSE,
      compute_dtype=jnp.float32)
  cell.traffic.update(sequence_length=SIZES["sequence_length"],
                      batch_per_chip=batch, scan_steps=steps)
  return cell

"""Operations of MiniCPM-SALA cut to one chip's share, from the
configuration's shapes: 2 per multiply-add; norms, rotary turns,
activations, softmax, the decays and the selection's top-k are not
counted. The sparse layer's attention is counted at the keys each query
selects and sees: the initial block, the window's blocks and the top-k
blocks, at most its causal keys (`selected_keys`), whatever the kernel
visits beyond them; its selection at the compressed keys each query
scores. Lightning is counted at its recurrent form, whatever chunked
form runs: two Dk x Dv products a token and head forward (the write
k^T v and the read q S). Recomputation is never counted.

Also, per sequence and layer, forward and backward, for their rooflines:
the block-sparse kernel's own operations and bytes
(`sparse_attention_kernel`) and Lightning's (`lightning_attention_kernel`).
"""

import numpy as np


def _layers(c):
  """(sparse layers, Lightning layers) among those held."""
  kinds = c["mixer_types"][:c["num_hidden_layers"]]
  sparse = sum(kind == "minicpm4" for kind in kinds)
  return sparse, len(kinds) - sparse


def selected_keys(c):
  """The keys a query attends to, summed over the sequence's positions:
  min(t + 1, the blocks selected x block), the selected blocks the
  initial ones, the window's (window / block + 1 of them) and the top-k;
  every causal key under `dense_len`."""
  sizes, t = c["sparse_config"], c["sequence_length"]
  position = np.arange(t, dtype=np.float64)
  if t < sizes["dense_len"]:
    return float(np.sum(position + 1))
  blocks = (sizes["init_blocks"] + sizes["window_size"] // sizes["block_size"]
            + 1 + sizes["topk"])
  return float(np.sum(np.minimum(position + 1, blocks * sizes["block_size"])))


def forward_per_token(c):
  """{part: operations a token, forward}."""
  d, t = c["hidden_size"], c["sequence_length"]
  sparse, lightning = _layers(c)
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  lh, lhd = c["lightning_nh"], c["lightning_head_dim"]
  sizes = c["sparse_config"]
  compressed = (t - sizes["kernel_size"]) // sizes["kernel_stride"] + 1
  return {
      "sparse_projections": sparse * 2 * (
          3 * d * heads * hd + 2 * d * kv * hd),
      "sparse_selection": sparse * 2 * heads * hd * (compressed + 1) / 2,
      "sparse_attention": sparse * 2 * 2 * heads * hd * selected_keys(c) / t,
      "lightning_projections": lightning * 2 * 5 * d * lh * lhd,
      "lightning_recurrence": lightning * 2 * 2 * lh * lhd * lhd,
      "mlp": c["num_hidden_layers"] * 2 * 3 * d * c["intermediate_held"],
      "head": 2 * d * c["vocab_size"],
  }


def forward_per_example(c):
  """One sequence, forward."""
  return c["sequence_length"] * sum(forward_per_token(c).values())


def train_per_example(c):
  """Forward and backward of one sequence: 3 x forward (every product
  has two in the backward pass; the selection has none, and is counted
  forward only; the embedding's gather is not counted)."""
  selection = c["sequence_length"] * forward_per_token(c)["sparse_selection"]
  return 3 * forward_per_example(c) - 2 * selection


def sparse_attention_kernel(c, itemsize=2):
  """{"fwd" | "bwd": {"flops", "bytes"}} of one call of each of the
  block-sparse kernel's programs: one sequence through one sparse layer.

  Operations the algorithm needs at the keys the queries select: forward
  q K^T and P V, backward dV, dP, dQ and dK (S made again from the
  logsumexp is the backward's own cost); the keys a tile visits for
  another of its tokens are not counted. Bytes: q and the output at the
  query heads, K and V read once at the key/value heads, the logsumexp
  in float32; backward those and dO read, dq written, dK and dV written
  once in float32.
  """
  t, heads, kv, hd = (c["sequence_length"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
  pairs = heads * selected_keys(c)
  q = heads * t * hd * itemsize
  k = kv * t * hd * itemsize
  column = heads * t * 4
  return {
      "fwd": {"flops": 2 * pairs * 2 * hd,
              "bytes": q + 2 * k + q + column},
      "bwd": {"flops": 2 * pairs * 4 * hd,
              "bytes": 3 * q + 2 * k + column + q + 2 * kv * t * hd * 4},
  }


def lightning_attention_kernel(c, itemsize=2):
  """{"fwd" | "bwd": {"flops", "bytes"}} of one forward and one backward
  run of Lightning attention at the boundary of `lightning_attention()`
  (a run is a call of the matching Pallas program and the chunk-local
  products around it): one sequence through one Lightning layer.

  The recurrent form's work, the same whatever chunk size or layout
  implements it: forward two Dk x Dv products a token and head, backward
  two for each of them; the chunked form's intra-chunk products are its
  own cost. Bytes: q, k, v read once and o written once forward;
  backward q, k, v and dO read and dq, dk, dv written once.
  """
  t, heads, d = (c["sequence_length"], c["lightning_nh"],
                 c["lightning_head_dim"])
  products = t * heads * 2 * d * d
  rows = t * heads * d * itemsize
  return {
      "fwd": {"flops": 2 * products, "bytes": 4 * rows},
      "bwd": {"flops": 4 * products, "bytes": 7 * rows},
  }

"""Operations of Qwen3-Next-80B-A3B cut to one chip's share, from the
configuration's shapes: 2 per multiply-add; norms, rotary turns,
activations, softmax, the decays and the routing's sort are not counted.
Causal attention counts half the square. The gated delta rule is counted
at its recurrent form, whatever chunked form runs: three Dk x Dv
products a token and value head forward (the read S^T k, the write
k (.)^T, the read S^T q). A routed expert is counted at the assignments
a token is expected to give the experts held here under a balanced
router: experts per token x held / routed; `held_per_token` puts a
measured count in its place (the program's counter
`moe/held_assignments` over the tokens and layers of a step).
Recomputation is never counted.

Also, per sequence and layer, forward and backward, for their rooflines:
the flash kernel's own operations and bytes (`attention_kernel`) and the
whole delta rule's (`gated_delta_rule_kernel`).
"""


def _layers(c):
  """(delta-net layers, full-attention layers)."""
  full = c["num_hidden_layers"] // c["full_attention_interval"]
  return c["num_hidden_layers"] - full, full


def forward_per_token(c, held_per_token=None):
  """{part: operations a token, forward}; attention's scores and values
  at the mean number of keys a query sees, (T + 1) / 2;
  `held_per_token`: assignments a token gives the held experts of one
  layer, default the balanced router's."""
  d, t = c["hidden_size"], c["sequence_length"]
  linear, full = _layers(c)
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  kh, dk = c["linear_num_key_heads"], c["linear_key_head_dim"]
  vh, dv = c["linear_num_value_heads"], c["linear_value_head_dim"]
  key, value = kh * dk, vh * dv
  width = c["moe_intermediate_size"]
  expected = (c["num_experts_per_tok"] * c["num_experts"]
              / c["router_width"]) if held_per_token is None else (
                  held_per_token)
  expert_layer = (
      2 * d * c["router_width"]
      + 2 * 3 * d * c["shared_expert_intermediate_size"] + 2 * d
      + expected * 2 * 3 * d * width)
  return {
      "delta_net_projections": linear * 2 * (
          d * (2 * key + 2 * value) + d * 2 * vh + value * d),
      "delta_net_conv": linear * 2 * c["linear_conv_kernel_dim"] * (
          2 * key + value),
      "delta_rule": linear * 3 * 2 * vh * dk * dv,
      "attention_projections": full * 2 * (
          d * heads * 2 * hd + 2 * d * kv * hd + heads * hd * d),
      "attention_scores_values": full * 2 * (t + 1) / 2 * heads * 2 * hd,
      "expert_layers": c["num_hidden_layers"] * expert_layer,
      "head": 2 * d * c["vocab_size"],
  }


def forward_per_example(c, held_per_token=None):
  """One sequence, forward."""
  return c["sequence_length"] * sum(
      forward_per_token(c, held_per_token).values())


def train_per_example(c, held_per_token=None):
  """Forward and backward of one sequence: 3 x forward (every product
  has two in the backward pass; the embedding's gather has none and is
  not counted forward either)."""
  return 3 * forward_per_example(c, held_per_token)


def attention_kernel(c, itemsize=2):
  """{"fwd" | "dq" | "dkv": {"flops", "bytes"}} of one call of each of
  the flash kernel's three programs: one sequence through one
  full-attention layer, 16 query heads on 2 key/value heads.

  Operations the algorithm needs, causal (half the square), as
  `flops/joyai_llm_flash_ep16.py` counts them; the dkv program's second
  S and dP are the two-pass layout's own cost and are not counted.
  Bytes: q, the output, dO and dq at the query heads, K and V read once
  at the key/value heads, dk and dv written once there (the group's
  parts the program writes before they are summed are its own cost), lse
  and delta in float32.
  """
  t, heads, kv = (c["sequence_length"], c["num_attention_heads"],
                  c["num_key_value_heads"])
  d = c["head_dim"]
  pairs = heads * t * (t + 1) / 2
  q = heads * t * d * itemsize
  k = kv * t * d * itemsize
  column = heads * t * 4
  return {
      "fwd": {"flops": 2 * pairs * (d + d),
              "bytes": q + 2 * k + q + column},
      "dq": {"flops": 2 * pairs * (d + d + d),
             "bytes": q + 2 * k + q + 2 * column + q},
      "dkv": {"flops": 2 * pairs * (d + d),
              "bytes": q + 2 * k + q + 2 * column + 2 * k},
  }


def gated_delta_rule_kernel(c, itemsize=2):
  """{"fwd" | "bwd": {"flops", "bytes"}} of one forward and one backward
  run of the delta rule, at the boundary of `gated_delta_rule()` (a run
  is a call of the matching Pallas program and the chunk-local products
  around it): one sequence through one delta-net layer.

  The recurrent form's work, the same whatever chunk size or layout
  implements it: forward three Dk x Dv products a token and value head,
  backward two for each of them; nothing recomputed is counted (the
  chunked form's inverse and its products with the state are its own
  cost). Bytes: q, k (at the key heads), v, g, beta read once and o
  written once forward; backward those read again with dO, and dq, dk,
  dv, dg, dbeta written once (g, beta and theirs in float32).
  """
  t = c["sequence_length"]
  kh, dk = c["linear_num_key_heads"], c["linear_key_head_dim"]
  vh, dv = c["linear_num_value_heads"], c["linear_value_head_dim"]
  products = t * vh * 2 * dk * dv
  qk = 2 * t * kh * dk * itemsize
  v = t * vh * dv * itemsize
  gates = 2 * t * vh * 4
  return {
      "fwd": {"flops": 3 * products, "bytes": qk + v + gates + v},
      "bwd": {"flops": 6 * products,
              "bytes": 2 * (qk + v + gates) + v},
  }

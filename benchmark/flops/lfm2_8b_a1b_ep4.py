"""Operations of LFM2-8B-A1B cut to one chip's share, from the
configuration's shapes: 2 per multiply-add; norms, rotary turns,
activations, softmax and the routing's sort are not counted. The gated
short convolution counts its two gates' products and its taps'
multiply-adds. Causal attention counts half the square. A routed expert
is counted at the assignments a token is expected to give the experts
held here under a balanced router: experts per token x held / routed;
`held_per_token` puts a measured count in its place (the program's
counter `moe/held_assignments` over the tokens and layers of a step).
The head is the tied embedding's transpose. Recomputation is never
counted.

Also, per sequence and layer, forward and backward, for their rooflines:
the flash kernel's own operations and bytes (`attention_kernel`) and the
short-convolution mixer's (`short_conv_kernel`).
"""


def _layers(c):
  """(conv layers, attention layers, dense layers, expert layers) among
  those held."""
  held = range(c["first_layer"], c["first_layer"] + c["num_hidden_layers"])
  conv = sum(c["layer_types"][i] == "conv" for i in held)
  dense = sum(i < c["num_dense_layers"] for i in held)
  return conv, len(held) - conv, dense, len(held) - dense


def forward_per_token(c, held_per_token=None):
  """{part: operations a token, forward}; attention's scores and values
  at the mean number of keys a query sees, (T + 1) / 2;
  `held_per_token`: assignments a token gives the held experts of one
  layer, default the balanced router's."""
  d, t = c["hidden_size"], c["sequence_length"]
  conv, full, dense, experts = _layers(c)
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  expected = (c["num_experts_per_tok"] * c["num_experts"]
              / c["router_width"]) if held_per_token is None else (
                  held_per_token)
  return {
      "conv_projections": conv * 2 * (d * 3 * d + d * d),
      "conv_gates_and_taps": conv * (2 + 2 * c["conv_L_cache"]) * d,
      "attention_projections": full * 2 * (
          d * heads * hd + 2 * d * kv * hd + heads * hd * d),
      "attention_scores_values": full * 2 * (t + 1) / 2 * heads * 2 * hd,
      "dense_mlp": dense * 2 * 3 * d * c["intermediate_size"],
      "expert_layers": experts * (
          2 * d * c["router_width"]
          + expected * 2 * 3 * d * c["moe_intermediate_size"]),
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
  the flash kernel's three programs: one sequence through one attention
  layer, 32 query heads on 8 key/value heads of 64.

  Operations the algorithm needs, causal (half the square), as
  `flops/joyai_llm_flash_ep16.py` counts them; the dkv program's second
  S and dP are the two-pass layout's own cost and are not counted.
  Bytes: q, the output, dO and dq at the query heads, K and V read once
  at the key/value heads, dk and dv written once there, lse and delta in
  float32.
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


def short_conv_kernel(c, itemsize=2):
  """{part: {"flops", "bytes"}} of one sequence through one conv layer's
  mixer, forward and backward, at the boundaries of its three parts:
  `in_proj_fwd`, `op_fwd`, `out_proj_fwd`, `in_proj_bwd`, `op_bwd`,
  `out_proj_bwd`. The same whatever implements them (XLA's fusions, a
  kernel, the gates moved into the projections' epilogues); nothing
  recomputed is counted.

  Operations: a projection 2 per multiply-add forward, twice that
  backward (the input's and the weight's gradient); the op (u = B x~,
  the taps, C z) 2 + 2 L a channel and token forward, twice that
  backward. Bytes: activations in the compute dtype (`itemsize`), each
  read or written once at its part's boundary; a projection's weight
  read once in the compute dtype and its gradient written once in
  float32; the taps and their gradient in float32.
  """
  t, d, taps = c["sequence_length"], c["hidden_size"], c["conv_L_cache"]
  act = lambda width: t * width * itemsize
  weight = lambda rows, cols: rows * cols * itemsize
  grad = lambda rows, cols: rows * cols * 4
  proj = lambda n_in, n_out: {
      "fwd": {"flops": 2 * t * n_in * n_out,
              "bytes": act(n_in) + weight(n_in, n_out) + act(n_out)},
      # dx = dy W^T and dW = x^T dy: dy, x and W read, dx and dW written.
      "bwd": {"flops": 2 * 2 * t * n_in * n_out,
              "bytes": act(n_out) + act(n_in) + weight(n_in, n_out)
                       + act(n_in) + grad(n_in, n_out)}}
  op = (2 + 2 * taps) * t * d
  in_proj, out_proj = proj(d, 3 * d), proj(d, d)
  return {
      "in_proj_fwd": in_proj["fwd"],
      "op_fwd": {"flops": op,
                 "bytes": act(3 * d) + 4 * taps * d + act(d)},
      "out_proj_fwd": out_proj["fwd"],
      "in_proj_bwd": in_proj["bwd"],
      # [B | C | x~] and dy read, their gradient and the taps' written.
      "op_bwd": {"flops": 2 * op,
                 "bytes": act(3 * d) + 4 * taps * d + act(d) + act(3 * d)
                          + 4 * taps * d},
      "out_proj_bwd": out_proj["bwd"],
  }

"""Operations of JoyAI-LLM-Flash cut to one chip's share, from the
configuration's shapes: 2 per multiply-add; norms, rotary turns,
activations, softmax and the routing's sort are not counted. Causal
attention counts half the square. A routed expert is counted at the
assignments a token is expected to give the experts held here under a
balanced router: experts per token x held / routed; `held_per_token`
puts a measured count in its place (the program's counter
`moe/held_assignments` over the tokens and expert layers of a step).
Recomputation is never counted.

Also the attention kernel's own operations and bytes, per sequence and
block, forward and backward (`attention_kernel`), for its roofline.
"""


def _blocks(c):
  """Blocks that run attention: dense, expert, and the MTP module's."""
  return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def _expert_blocks(c):
  return (c["num_hidden_layers"] - c["first_k_dense_replace"]
          + c["num_nextn_predict_layers"])


def forward_per_token(c, held_per_token=None):
  """{part: operations a token, forward}; attention's scores and values
  at the mean number of keys a query sees, (T + 1) / 2;
  `held_per_token`: assignments a token gives the held experts of one
  layer, default the balanced router's."""
  d, heads = c["hidden_size"], c["num_attention_heads"]
  nope, rope, vdim = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
  rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
  t, width = c["sequence_length"], c["moe_intermediate_size"]
  projections = 2 * (d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
                     + rkv * heads * (nope + vdim) + heads * vdim * d)
  keys = (t + 1) / 2
  scores_values = 2 * keys * heads * ((nope + rope) + vdim)
  expected = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["router_width"]) if held_per_token is None else (
                  held_per_token)
  expert_layer = (2 * d * c["router_width"]
                  + (c["n_shared_experts"] + expected) * 2 * 3 * d * width)
  heads_out = 2 * d * c["vocab_size"] * (1 + c["num_nextn_predict_layers"])
  return {
      "mla_projections": _blocks(c) * projections,
      "attention_scores_values": _blocks(c) * scores_values,
      "dense_mlp": c["first_k_dense_replace"] * 2 * 3 * d
                   * c["intermediate_size"],
      "expert_layers": _expert_blocks(c) * expert_layer,
      "mtp_projection": c["num_nextn_predict_layers"] * 2 * 2 * d * d,
      "heads": heads_out,
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
  the flash kernel's three programs: one sequence through one block.

  Operations the algorithm needs, causal (half the square): forward
  Q K^T and P V; backward S again (it is not kept), dP = dO V^T and
  dQ = dS K in the dq program, dV = P^T dO and dK = dS^T Q in the dkv
  program. The dkv program computes S and dP a second time: that is the
  two-pass layout's own cost and is not counted. Bytes: every operand
  read once, every result written once, at `itemsize` (lse and delta in
  float32).
  """
  t, heads = c["sequence_length"], c["num_attention_heads"]
  dk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  dv = c["v_head_dim"]
  pairs = heads * t * (t + 1) / 2
  rows = heads * t
  qk, v = rows * dk * itemsize, rows * dv * itemsize
  column = rows * 4
  return {
      "fwd": {"flops": 2 * pairs * (dk + dv),
              "bytes": 2 * qk + v + v + column},
      "dq": {"flops": 2 * pairs * (dk + dv + dk),
             "bytes": 2 * qk + v + v + 2 * column + qk},
      "dkv": {"flops": 2 * pairs * (dv + dk),
              "bytes": 2 * qk + v + v + 2 * column + qk + v},
  }

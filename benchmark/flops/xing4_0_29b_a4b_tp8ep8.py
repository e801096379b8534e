"""Operations of Xing4.0-29B-A4B cut to one chip's share, from the
configuration's shapes: 2 per multiply-add; norms, rotary turns,
activations, softmax, Sinkhorn's divisions and the routing's sort are
not counted. MLA is counted at the heads held here, causal attention at
half the square. The hyper-connections' maps are counted at their
product with `phi` (2 nD (n^2 + 2n) a token and sublayer) and their
weighted sums (the read-out's n rows, the write-back's n^2 + n). A
routed expert is counted at the assignments a token is expected to give
the experts held here under a balanced router: experts per token x held
/ routed; `held_per_token` puts a measured count in its place (the
program's counter `moe/held_assignments` over the tokens and expert
layers of a step). Recomputation is never counted.

Also, per sequence, forward and backward, for their rooflines: the flash
kernel's own operations and bytes for one block (`attention_kernel`) and
the two stream passes' for one sublayer (`hyper_connection_kernel`).
"""


def _blocks(c):
  """Blocks that run attention: dense, expert, and the MTP module's."""
  return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def _expert_blocks(c):
  return (c["num_hidden_layers"] - c["dense_blocks_run"]
          + c["num_nextn_predict_layers"])


def _maps(c):
  """Maps a token and sublayer: H_pre (n), H_post (n), H_res (n^2)."""
  return c["hc_mult"] ** 2 + 2 * c["hc_mult"]


def forward_per_token(c, held_per_token=None):
  """{part: operations a token, forward}; attention's scores and values
  at the mean number of keys a query sees, (T + 1) / 2;
  `held_per_token`: assignments a token gives the held experts of one
  layer, default the balanced router's."""
  d, heads = c["hidden_size"], c["num_attention_heads"]
  nope, rope, vdim = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
  rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
  t, width, n = (c["sequence_length"], c["moe_intermediate_size"],
                 c["hc_mult"])
  projections = 2 * (d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
                     + rkv * heads * (nope + vdim) + heads * vdim * d)
  keys = (t + 1) / 2
  scores_values = 2 * keys * heads * ((nope + rope) + vdim)
  expected = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["router_width"]) if held_per_token is None else (
                  held_per_token)
  expert_layer = (2 * d * c["router_width"]
                  + (c["n_shared_experts"] + expected) * 2 * 3 * d * width)
  sublayers = 2 * _blocks(c)
  return {
      "mla_projections": _blocks(c) * projections,
      "attention_scores_values": _blocks(c) * scores_values,
      "dense_mlp": c["dense_blocks_run"] * 2 * 3 * d
                   * c["intermediate_size"],
      "expert_layers": _expert_blocks(c) * expert_layer,
      "hyper_connection_maps": sublayers * 2 * n * d * _maps(c),
      "hyper_connection_sums": sublayers * 2 * (n + n * n + n) * d,
      "mtp_projection": c["num_nextn_predict_layers"] * 2 * 2 * d * d,
      "heads": 2 * d * c["vocab_size"] * (1 + c["num_nextn_predict_layers"]),
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
  the flash kernel's three programs: one sequence through one block, the
  heads held here.

  Operations the algorithm needs, causal (half the square), as
  `flops/joyai_llm_flash_ep16.py` counts them: forward Q K^T and P V;
  backward S again, dP = dO V^T and dQ = dS K in the dq program, dV =
  P^T dO and dK = dS^T Q in the dkv program, whose second S and dP are
  the two-pass layout's own cost and are not counted. Bytes: every
  operand read once, every result written once, at `itemsize` (lse and
  delta in float32).
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


def hyper_connection_kernel(c, itemsize=2):
  """{"pre_fwd" | "post_fwd" | "pre_bwd" | "post_bwd": {"flops",
  "bytes"}} of one run of each stream pass: one sequence through one
  sublayer, at the boundary of `hyper_connection_pre()` /
  `hyper_connection_post()` and of their backward passes, the same
  whatever implements them.

  Bytes, the stream and the sublayer's output at `itemsize` (the
  configuration's stream dtype), the maps, `phi` and their cotangents in
  float32; a token's stream is n rows of D:
    pre       reads the stream (n rows) and `phi`; writes u (1 row) and
              the n^2 + 2n maps.
    post      reads the stream, y (n + 1 rows) and H_post, H_res (n^2 +
              n); writes the stream (n rows).
    post bwd  reads the stream, y, dX' (2n + 1 rows) and the maps; writes
              dX, dy (n + 1 rows) and the maps' cotangents.
    pre bwd   reads the stream, du (n + 1 rows), `phi` and the maps'
              cotangents; writes dX (n rows) and dphi.
  The sum of the two passes' dX is the caller's and is not counted.
  Operations: the product with `phi`, the stream's squares and the
  weighted sums forward; each product's two in the backward pass
  (Sinkhorn run again there is the implementation's own cost).
  """
  t, d, n = c["sequence_length"], c["hidden_size"], c["hc_mult"]
  maps = _maps(c)
  row = t * d * itemsize
  phi = n * d * maps * 4
  product = 2 * t * n * d * maps
  return {
      "pre_fwd": {"flops": product + 2 * t * n * d + 2 * t * n * d,
                  "bytes": n * row + phi + row + t * maps * 4},
      "post_fwd": {"flops": 2 * t * (n * n + n) * d,
                   "bytes": (n + 1) * row + t * (n * n + n) * 4 + n * row},
      "post_bwd": {"flops": 2 * 2 * t * (n * n + n) * d,
                   "bytes": (2 * n + 1) * row + 2 * t * (n * n + n) * 4
                            + (n + 1) * row},
      "pre_bwd": {"flops": 2 * product + 2 * 2 * t * n * d,
                  "bytes": (n + 1) * row + 2 * phi + t * maps * 4
                           + n * row},
  }

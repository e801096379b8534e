"""Plain reference of LFM2-8B-A1B cut to one chip's share of a 4-chip
expert group (config.json of LiquidAI/LFM2-8B-A1B; the layers as the
family's modeling code builds them, `Lfm2ShortConv`, `Lfm2Attention`,
`Lfm2MoeSparseMoeBlock`): the published layers
`layer_types[first_layer : first_layer + num_hidden_layers]`, pre-norm,

  h = x + Mixer(N(x));  y = h + FFN(N(h));  logits = N(x_L) E^T (tied),

  conv:       [B | C | x~] = x W_in;  u = B * x~;
              z_t = sum_{j<3} w_j * u_{t-2+j} (depthwise, causal, zeros
              before the start);  out = (C * z) W_out
  attention:  32 query heads on 8 key/value heads of 64; q and k
              RMS-normed per head, rotary in half-split pairs over the
              whole head (theta 1e6); softmax(q k^T / 8, causal) v, W_o
  FFN:        a dense SwiGLU in the layers before `num_dense_layers`,
              else sum over i in top4(sigmoid(x R) + b) of
              sigmoid(x R)_i / (sum of the chosen 4 + 1e-6) E_i(x),
              E_i(x) = W2 (silu(W1 x) * W3 x); b moves the choice only.

N is RMSNorm with `norm_eps` and a weight. The loss is next-token
cross-entropy over the vocabulary slice.

The share: the router scores all `router_width` experts; only the
`num_experts` held here (from `first_expert`) are computed, and what the
absent ones would add is left out. Departures from the published
description: none in the equations; the cut (above) and what the config
leaves unsaid (the configuration file's `assumed`: the sigmoid, the
1e-6, the chunk order [B | C | x], the tie) are taken from the family's
modeling code.

Everything is float32 `jax.numpy` with matmul precision "highest".
Attention is a masked softmax a block of queries at a time, the held
experts are a loop, the logits are made a chunk of positions at a time;
`jax.checkpoint` appears only so that the K steps fit the chip beside
four parameter-sized trees. Imports nothing of the program.

`fault` plants one departure, for the controls (each has to come out
not correct): "taps_ahead" (the convolution reads one token ahead:
z_t = sum_j w_j u_{t-1+j}), "no_b_gate" (u = x~), "no_c_gate" (out =
z W_out), "no_qk_norm" (q and k not normed), "half_rotary" (rotary over
the first half of the head), "softmax_routing" (a softmax over the 32 in
place of the sigmoids), "bias_ignored" (the choice by the scores alone),
"no_renormalize" (the chosen scores not divided by their sum),
"half_positions" (loss over the first half of the positions). It is a
name, or a traced index into FAULTS (-1: none), so that one compiled
step serves every fault.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import nn

FAULTS = ("taps_ahead", "no_b_gate", "no_c_gate", "no_qk_norm",
          "half_rotary", "softmax_routing", "bias_ignored",
          "no_renormalize", "half_positions")
# The correction bias's draw, × normal (the configuration's `assumed`).
EXPERT_BIAS_DRAW = 0.01
_QUERY_BLOCK = 256
_CHUNKS = 8
_HIGHEST = lax.Precision.HIGHEST


def planted(fault, name):
  """Whether `fault` (None, a name, or a traced index into FAULTS) is
  `name`: a Python bool or a traced one."""
  if fault is None or isinstance(fault, str):
    return fault == name
  return fault == FAULTS.index(name)


def layer_of(config, block):
  """(the published layer of held block `block`, its mixer's name,
  whether its FFN is the dense MLP)."""
  layer = config["first_layer"] + block
  return (layer, config["layer_types"][layer],
          layer < config["num_dense_layers"])


# --- seeded weights --------------------------------------------------------


def _kernel(pool, *shape):
  """Normal over fan-in (the second-last axis)."""
  return pool.normal(shape) * shape[-2] ** -0.5


def _scale(pool, *shape):
  """A norm's weight away from 1: a fresh norm hides a dropped one."""
  return pool.uniform(shape, 0.5, 1.5)


def _mixer_params(pool, c, kind):
  d = c["hidden_size"]
  if kind == "conv":
    return {"in_proj": {"kernel": _kernel(pool, d, 3 * d)},
            # Depthwise, (taps, channels): tap j weighs u[t - 2 + j].
            "conv": pool.normal((c["conv_L_cache"], d))
                    * c["conv_L_cache"] ** -0.5,
            "out_proj": {"kernel": _kernel(pool, d, d)}}
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  return {"q_proj": {"kernel": _kernel(pool, d, heads * hd)},
          "k_proj": {"kernel": _kernel(pool, d, kv * hd)},
          "v_proj": {"kernel": _kernel(pool, d, kv * hd)},
          "q_norm": {"scale": _scale(pool, hd)},
          "k_norm": {"scale": _scale(pool, hd)},
          "o_proj": {"kernel": _kernel(pool, heads * hd, d)}}


def _ffn_params(pool, c, dense):
  d = c["hidden_size"]
  if dense:
    width = c["intermediate_size"]
    return "mlp", {"gate": {"kernel": _kernel(pool, d, width)},
                   "up": {"kernel": _kernel(pool, d, width)},
                   "down": {"kernel": _kernel(pool, width, d)}}
  held, width = c["num_experts"], c["moe_intermediate_size"]
  return "moe", {
      "router": _kernel(pool, d, c["router_width"]),
      "correction_bias": EXPERT_BIAS_DRAW * pool.normal(
          (c["router_width"],)),
      "experts_gate": _kernel(pool, held, d, width),
      "experts_up": _kernel(pool, held, d, width),
      "experts_down": _kernel(pool, held, width, d)}


def init_variables(key, config):
  """{"params"} from one key, float32, in the program's layout: blocks
  `block0`.. in held order, each its mixer under "attn" and its FFN
  under "mlp" or "moe"; no head (tied)."""
  c, d = config, config["hidden_size"]

  def build(pool):
    params = {"embed": {"embedding": pool.normal((c["vocab_size"], d))}}
    for i in range(c["num_hidden_layers"]):
      _, kind, dense = layer_of(c, i)
      name, ffn = _ffn_params(pool, c, dense)
      params[f"block{i}"] = {"attn_norm": {"scale": _scale(pool, d)},
                             "attn": _mixer_params(pool, c, kind),
                             "ffn_norm": {"scale": _scale(pool, d)},
                             name: ffn}
    params["final_norm"] = {"scale": _scale(pool, d)}
    return {"params": params}

  return nn.Pool.fill(key, build)


def make_batch(key, config, batch_size):
  """(features, labels): ids uniform over the vocabulary slice, one
  document a sequence; the targets are the sequence itself, shifted."""
  tokens = jax.random.randint(
      key, (batch_size, config["sequence_length"]), 0, config["vocab_size"],
      jnp.int32)
  return {"tokens": tokens}, {}


# --- layers ----------------------------------------------------------------


def _dot(x, w, precision):
  return jnp.dot(nn._operand(x, precision), nn._operand(w, precision),
                 precision=_HIGHEST)


def rms_norm(x, scale, eps):
  return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                       + eps) * scale


def partial_rotary(x, theta, width):
  """(T, H, D): the first `width` dims turn, in half-split pairs
  (x[i], x[i + width/2]) by t theta^(-2i/width); the rest pass."""
  t, half = x.shape[0], width // 2
  inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
  angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq)[:, None, :]
  first, second, rest = x[..., :half], x[..., half:width], x[..., width:]
  return jnp.concatenate(
      [first * jnp.cos(angle) - second * jnp.sin(angle),
       second * jnp.cos(angle) + first * jnp.sin(angle), rest], axis=-1)


def short_conv(x, p, c, precision, fault=None):
  """One sequence (T, D) -> ((T, D), (mean of u^2, mean of (C * z)^2))."""
  t, d = x.shape
  bcx = _dot(x, p["in_proj"]["kernel"], precision)
  gate_b, gate_c, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
  u = jnp.where(planted(fault, "no_b_gate"), xs, gate_b * xs)
  taps = p["conv"]
  width = taps.shape[0]
  padded = jnp.concatenate(
      [jnp.zeros((width - 1, d)), u, jnp.zeros((1, d))], axis=0)
  conv = lambda ahead: sum(taps[j] * padded[j + ahead:j + ahead + t]
                           for j in range(width))
  z = jnp.where(planted(fault, "taps_ahead"), conv(1), conv(0))
  y = jnp.where(planted(fault, "no_c_gate"), z, gate_c * z)
  return (_dot(y, p["out_proj"]["kernel"], precision),
          (jnp.mean(u * u), jnp.mean(y * y)))


def causal_attention(q, k, v, scale):
  """(T, H, D) each -> (T, H, D): softmax over all keys up to the
  query's own, a block of queries at a time."""
  t = q.shape[0]
  block = min(_QUERY_BLOCK, t)

  @jax.checkpoint
  def one(args):
    first, q_block = args
    scores = jnp.einsum("qhd,khd->hqk", q_block, k,
                        precision=_HIGHEST) * scale
    rows = first + jnp.arange(block)
    seen = rows[:, None] >= jnp.arange(t)[None, :]
    weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", weights, v, precision=_HIGHEST)

  out = lax.map(one, (jnp.arange(0, t, block),
                      q.reshape((t // block, block) + q.shape[1:])))
  return out.reshape((t,) + v.shape[1:])


def attention(x, p, c, precision, fault=None):
  """One sequence (T, D): grouped-query attention without an output
  gate."""
  t = x.shape[0]
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  eps, theta = c["norm_eps"], c["rope_theta"]
  q = _dot(x, p["q_proj"]["kernel"], precision).reshape(t, heads, hd)
  k = _dot(x, p["k_proj"]["kernel"], precision).reshape(t, kv, hd)
  v = _dot(x, p["v_proj"]["kernel"], precision).reshape(t, kv, hd)
  unnormed = planted(fault, "no_qk_norm")
  q = jnp.where(unnormed, q, rms_norm(q, p["q_norm"]["scale"], eps))
  k = jnp.where(unnormed, k, rms_norm(k, p["k_norm"]["scale"], eps))
  half = planted(fault, "half_rotary")
  turn = lambda a: nn._operand(jnp.where(
      half, partial_rotary(a, theta, hd // 2), partial_rotary(a, theta, hd)),
                               precision)
  # Each key/value head serves heads / kv consecutive query heads.
  k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (turn(k), v))
  out = causal_attention(turn(q), k, nn._operand(v, precision), hd ** -0.5)
  return _dot(out.reshape(t, heads * hd), p["o_proj"]["kernel"], precision)


def gated_mlp(x, gate, up, down, precision):
  return _dot(jax.nn.silu(_dot(x, gate, precision))
              * _dot(x, up, precision), down, precision)


def route(x, p, c, fault=None):
  """(T, D) -> (ids (T, k), weights (T, k)) over ALL the experts."""
  logits = jnp.dot(x, p["router"], precision=_HIGHEST)
  scores = jnp.where(planted(fault, "softmax_routing"),
                     jax.nn.softmax(logits, axis=-1), jax.nn.sigmoid(logits))
  choice = jnp.where(planted(fault, "bias_ignored"), scores,
                     scores + p["correction_bias"])
  _, index = lax.top_k(choice, c["num_experts_per_tok"])
  chosen = jnp.take_along_axis(scores, index, axis=-1)
  chosen = jnp.where(
      planted(fault, "no_renormalize"), chosen,
      chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                + c["router_norm_eps"]))
  return index, chosen * c["routed_scaling_factor"]


def expert_layer(x, p, c, precision, fault=None):
  """The held experts' part: (y, tokens on each held expert)."""
  index, weight = route(x, p, c, fault)

  @jax.checkpoint
  def one(y, args):
    expert, gate, up, down = args
    mine = index == c["first_expert"] + expert                  # (T, k)
    w = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
    y = y + w[:, None] * gated_mlp(x, gate, up, down, precision)
    return y, jnp.sum(mine)

  return lax.scan(one, jnp.zeros_like(x), (
      jnp.arange(c["num_experts"]), p["experts_gate"], p["experts_up"],
      p["experts_down"]))


def block(x, p, c, i, precision, fault=None):
  """Held block `i`, (T, D) -> ((T, D), expert counts or None, the conv
  layer's mean squares or None)."""
  _, kind, dense = layer_of(c, i)
  eps = c["norm_eps"]
  inner = rms_norm(x, p["attn_norm"]["scale"], eps)
  squares = None
  if kind == "conv":
    mixed, squares = short_conv(inner, p["attn"], c, precision, fault)
  else:
    mixed = attention(inner, p["attn"], c, precision, fault)
  h = x + mixed
  inner = rms_norm(h, p["ffn_norm"]["scale"], eps)
  if dense:
    m = p["mlp"]
    return h + gated_mlp(inner, m["gate"]["kernel"], m["up"]["kernel"],
                         m["down"]["kernel"], precision), None, squares
  y, counts = expert_layer(inner, p["moe"], c, precision, fault)
  return h + y, counts, squares


def token_losses(hidden, head, targets, precision):
  """Cross-entropy of every position, (T,), a chunk of logits at a time."""
  t, d = hidden.shape

  @jax.checkpoint
  def one(args):
    rows, wanted = args
    logits = _dot(rows, head, precision)
    picked = jnp.take_along_axis(logits, wanted[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked

  return lax.map(one, (hidden.reshape(_CHUNKS, t // _CHUNKS, d),
                       targets.reshape(_CHUNKS, t // _CHUNKS))).reshape(t)


def _forward_one(p, tokens, c, precision, fault):
  """One sequence: per-position losses, expert counts (expert layers,
  held) and each conv layer's mean squares of u and of C * z, in layer
  order."""
  embedding = p["embed"]["embedding"]
  x = nn._operand(embedding[tokens], precision)
  counts, squares = [], []
  for i in range(c["num_hidden_layers"]):
    x, count, square = jax.checkpoint(
        lambda x, q, i=i: block(x, q, c, i, precision, fault))(
            x, p[f"block{i}"])
    if count is not None:
      counts.append(count)
    if square is not None:
      squares.append(square)
  hidden = rms_norm(x, p["final_norm"]["scale"], c["norm_eps"])
  return {"token_loss_main": token_losses(
              hidden, embedding.T, jnp.roll(tokens, -1), precision),
          "expert_tokens": jnp.stack(counts),
          "conv_u_square": jnp.stack([s[0] for s in squares]),
          "conv_y_square": jnp.stack([s[1] for s in squares])}


def forward(variables, features, train=True, precision="f32", config=None,
            fault=None):
  """Returns ({per-position losses (B, T), "expert_tokens" (expert
  layers, held) summed over the batch, "conv_u_rms" and "conv_y_rms"
  (conv layers,): the root mean square of u and of C * z over the
  batch's tokens and channels}, {}): there are no running statistics.
  `config` is the configuration file's object."""
  del train
  out = jax.vmap(lambda tokens: _forward_one(
      variables["params"], tokens, config, precision, fault))(
          features["tokens"])
  out["expert_tokens"] = jnp.sum(out["expert_tokens"], axis=0)
  for name in ("u", "y"):
    out[f"conv_{name}_rms"] = jnp.sqrt(jnp.mean(
        out.pop(f"conv_{name}_square"), axis=0))
  return out, {}


def loss(outputs, features, labels=None, config=None, fault=None):
  """Mean cross-entropy over the valid positions: the last has no next
  token."""
  del labels, config
  t = features["tokens"].shape[-1]
  valid = jnp.where(planted(fault, "half_positions"), (t - 1) // 2, t - 1)
  losses = outputs["token_loss_main"]
  total = jnp.mean(jnp.sum(
      jnp.where(jnp.arange(t) < valid, losses, 0.0), axis=-1) / valid)
  return total, {"loss_main": total}

"""Plain reference of Qwen3-Next-80B-A3B-Instruct cut to one chip's share
of a 16-chip expert group (config.json of Qwen/Qwen3-Next-80B-A3B-Instruct;
the linear-attention layer is the gated delta rule of arXiv:2412.06464):
periods of `full_attention_interval` blocks, three gated-delta-net
blocks and one gated grouped-query attention block, every block's FFN
the expert layer (softmax over all experts, top-10, renormalized) plus a
shared expert gated by sigmoid(x . w_s); zero-centred RMSNorm (1 + w);
rotary over the first quarter of the head in half-split pairs; the loss
next-token cross-entropy over the vocabulary slice. No MTP module: the
config has no key for one.

The share: the router scores all `router_width` experts; only the
`num_experts` held here (from `first_expert`) are computed, and what the
absent ones would add is left out.

Everything is float32 `jax.numpy` with matmul precision "highest". The
delta rule is the recurrence itself, token by token:

  S'_t = exp(g_t) S_{t-1};  S_t = S'_t + b_t k_t (v_t - S'_t^T k_t)^T;
  o_t = S_t^T q_t,

a `lax.scan` over T whose body is checkpointed a block of tokens at a
time (kept whole, the 8,192 states of 32 x 128 x 128 float32 are 17 GB).
Attention is a masked softmax a block of queries at a time, the held
experts are a loop, the logits are made a chunk of positions at a time.
Imports nothing of the program.

Our column order (seeded weights make it immaterial; loading published
weights would have to permute): `in_proj_qkvz` is [q | k | v | z], each
whole and head-major (upstream groups q, k, v, z per key head);
`in_proj_ba` is [b | a]; `q_proj` is per head [q_h | gate_h], as
upstream.

`fault` plants one departure, for the controls (each has to come out
not correct): "no_decay" (g = 0), "beta_one" (b = 1), "no_output_gate"
(attention without sigmoid(gate)), "full_rotary" (rotary over the whole
head), "sigmoid_scores" (sigmoid router scores in place of the softmax),
"no_renormalize" (top-10 weights not renormalized), "no_shared_gate"
(the shared expert ungated), "half_positions" (loss over the first half
of the positions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import nn

FAULTS = ("no_decay", "beta_one", "no_output_gate", "full_rotary",
          "sigmoid_scores", "no_renormalize", "no_shared_gate",
          "half_positions")
_QUERY_BLOCK = 256
_TOKEN_BLOCK = 64
_HIGHEST = lax.Precision.HIGHEST


# --- seeded weights --------------------------------------------------------


def _kernel(pool, *shape):
  """Normal over fan-in (the second-last axis)."""
  return pool.normal(shape) * shape[-2] ** -0.5


def _centred(pool, *shape):
  """A zero-centred norm's weight away from 0 (1 + w in [0.5, 1.5]): a
  fresh norm hides a dropped one."""
  return pool.uniform(shape, -0.5, 0.5)


def is_full(config, layer):
  return (layer + 1) % config["full_attention_interval"] == 0


def _delta_net_params(pool, c, lead):
  d = c["hidden_size"]
  key = c["linear_num_key_heads"] * c["linear_key_head_dim"]
  heads, vdim = c["linear_num_value_heads"], c["linear_value_head_dim"]
  value = heads * vdim
  return {
      "in_proj_qkvz": {"kernel": _kernel(pool, *lead, d, 2 * key + 2 * value)},
      "in_proj_ba": {"kernel": _kernel(pool, *lead, d, 2 * heads)},
      # Depthwise, (taps, channels): tap j weighs x[t - (taps - 1) + j].
      "conv_kernel": pool.normal(
          lead + (c["linear_conv_kernel_dim"], 2 * key + value))
                     * c["linear_conv_kernel_dim"] ** -0.5,
      # A = exp(A_log) uniform in (0, 16) as upstream draws it; with the
      # bias spread over (-4, 0) the decays exp(g) cover (0, 1).
      "A_log": jnp.log(pool.uniform(lead + (heads,), 0.0, 16.0)),
      "dt_bias": pool.uniform(lead + (heads,), -4.0, 0.0),
      "norm": {"scale": pool.uniform(lead + (vdim,), 0.5, 1.5)},
      "out_proj": {"kernel": _kernel(pool, *lead, value, d)},
  }


def _attention_params(pool, c, lead):
  d, heads, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
  return {
      "q_proj": {"kernel": _kernel(pool, *lead, d, heads * 2 * hd)},
      "k_proj": {"kernel": _kernel(pool, *lead, d, kv * hd)},
      "v_proj": {"kernel": _kernel(pool, *lead, d, kv * hd)},
      "q_norm": {"scale": _centred(pool, *lead, hd)},
      "k_norm": {"scale": _centred(pool, *lead, hd)},
      "o_proj": {"kernel": _kernel(pool, *lead, heads * hd, d)},
  }


def _mlp_params(pool, d, width, lead):
  return {"gate": {"kernel": _kernel(pool, *lead, d, width)},
          "up": {"kernel": _kernel(pool, *lead, d, width)},
          "down": {"kernel": _kernel(pool, *lead, width, d)}}


def _block_params(pool, c, full, lead):
  d, width = c["hidden_size"], c["moe_intermediate_size"]
  held, routed = c["num_experts"], c["router_width"]
  mixer = _attention_params if full else _delta_net_params
  return {
      "attn_norm": {"scale": _centred(pool, *lead, d)},
      "attn": mixer(pool, c, lead),
      "ffn_norm": {"scale": _centred(pool, *lead, d)},
      "moe": {
          "router": _kernel(pool, *lead, d, routed),
          "experts_gate": _kernel(pool, *lead, held, d, width),
          "experts_up": _kernel(pool, *lead, held, d, width),
          "experts_down": _kernel(pool, *lead, held, width, d),
          "shared": _mlp_params(
              pool, d, c["shared_expert_intermediate_size"], lead),
          "shared_gate": {"kernel": _kernel(pool, *lead, d, 1)},
      },
  }


def init_variables(key, config):
  """{"params"} from one key, float32, in the program's layout: the
  periods stacked on a leading axis (the program scans them), each
  holding its `full_attention_interval` blocks by position."""
  c, d = config, config["hidden_size"]
  interval = c["full_attention_interval"]
  periods = c["num_hidden_layers"] // interval

  def build(pool):
    return {"params": {
        "embed": {"embedding": pool.normal((c["vocab_size"], d))},
        "periods": {
            f"block{i}": _block_params(pool, c, is_full(c, i), (periods,))
            for i in range(interval)},
        "final_norm": {"scale": _centred(pool, d)},
        "head": _kernel(pool, d, c["vocab_size"]),
    }}

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


def centred_norm(x, weight, eps, precision):
  """x rsqrt(mean(x^2) + eps) (1 + w)."""
  y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
  return nn._operand(y * (1.0 + weight), precision)


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


def causal_attention(q, k, v, scale):
  """(T, H, D), (T, H, D), (T, H, D) -> (T, H, D): softmax over all
  keys up to the query's own, a block of queries at a time."""
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


def gated_attention(x, p, c, precision, fault=None):
  """One sequence (T, D): 16 query heads on 2 key/value heads, q and k
  normed per head, partial rotary, the output gated per channel."""
  t = x.shape[0]
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  eps = c["rms_norm_eps"]
  q_gate = _dot(x, p["q_proj"]["kernel"], precision).reshape(t, heads, 2 * hd)
  q, gate = q_gate[..., :hd], q_gate[..., hd:]
  k = _dot(x, p["k_proj"]["kernel"], precision).reshape(t, kv, hd)
  v = _dot(x, p["v_proj"]["kernel"], precision).reshape(t, kv, hd)
  q = centred_norm(q, p["q_norm"]["scale"], eps, "f32")
  k = centred_norm(k, p["k_norm"]["scale"], eps, "f32")
  width = hd if fault == "full_rotary" else int(
      c["partial_rotary_factor"] * hd)
  q = nn._operand(partial_rotary(q, c["rope_theta"], width), precision)
  k = nn._operand(partial_rotary(k, c["rope_theta"], width), precision)
  # Each key/value head serves heads / kv consecutive query heads.
  k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
  out = causal_attention(q, k, nn._operand(v, precision), hd ** -0.5)
  if fault != "no_output_gate":
    out = out * jax.nn.sigmoid(gate)
  return _dot(out.reshape(t, heads * hd), p["o_proj"]["kernel"], precision)


def causal_conv(x, kernel):
  """(T, C) depthwise over time, (taps, C): y_t = sum_j kernel[j]
  x[t - (taps - 1) + j], nothing ahead of t, zeros before the start."""
  taps, t = kernel.shape[0], x.shape[0]
  padded = jnp.concatenate(
      [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
  return sum(kernel[j] * padded[j:j + t] for j in range(taps))


def _l2_normalized(x, eps=1e-6):
  return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta):
  """The recurrence, token by token. q, k (T, H, Dk), v (T, H, Dv),
  g, beta (T, H) -> (T, H, Dv); the state starts at nought."""
  t, heads, dk = q.shape
  dv = v.shape[-1]

  def token(state, args):
    q_t, k_t, v_t, g_t, b_t = args
    state = state * jnp.exp(g_t)[:, None, None]
    read = jnp.einsum("hkv,hk->hv", state, k_t, precision=_HIGHEST)
    write = (v_t - read) * b_t[:, None]
    state = state + k_t[:, :, None] * write[:, None, :]
    return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=_HIGHEST)

  @jax.checkpoint
  def tokens(state, args):
    return lax.scan(token, state, args)

  block = min(_TOKEN_BLOCK, t)
  blocked = lambda a: a.reshape((t // block, block) + a.shape[1:])
  _, out = lax.scan(tokens, jnp.zeros((heads, dk, dv), jnp.float32),
                    tuple(map(blocked, (q, k, v, g, beta))))
  return out.reshape(t, heads, dv)


def gated_delta_net(x, p, c, precision, fault=None):
  """One sequence (T, D) -> ((T, D), mean decay exp(g), mean beta)."""
  t = x.shape[0]
  kh, dk = c["linear_num_key_heads"], c["linear_key_head_dim"]
  vh, dv = c["linear_num_value_heads"], c["linear_value_head_dim"]
  key, value = kh * dk, vh * dv
  qkvz = _dot(x, p["in_proj_qkvz"]["kernel"], precision)
  ba = _dot(x, p["in_proj_ba"]["kernel"], precision)
  mixed = jax.nn.silu(causal_conv(qkvz[:, :2 * key + value],
                                  p["conv_kernel"]))
  z = qkvz[:, 2 * key + value:].reshape(t, vh, dv)
  q = mixed[:, :key].reshape(t, kh, dk)
  k = mixed[:, key:2 * key].reshape(t, kh, dk)
  v = mixed[:, 2 * key:].reshape(t, vh, dv)
  beta = jax.nn.sigmoid(ba[:, :vh])
  g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, vh:] + p["dt_bias"])
  if fault == "no_decay":
    g = jnp.zeros_like(g)
  if fault == "beta_one":
    beta = jnp.ones_like(beta)
  q = _l2_normalized(q) * dk ** -0.5
  k = _l2_normalized(k)
  # Each q/k head serves vh / kh consecutive value heads.
  q, k = (nn._operand(jnp.repeat(a, vh // kh, axis=1), precision)
          for a in (q, k))
  out = delta_rule(q, k, nn._operand(v, precision), g, beta)
  out = out * lax.rsqrt(
      jnp.mean(jnp.square(out), axis=-1, keepdims=True) + c["rms_norm_eps"])
  out = nn._operand(out * p["norm"]["scale"], precision) * jax.nn.silu(z)
  return (_dot(out.reshape(t, value), p["out_proj"]["kernel"], precision),
          jnp.mean(jnp.exp(g)), jnp.mean(beta))


def gated_mlp(x, gate, up, down, precision):
  return _dot(jax.nn.silu(_dot(x, gate, precision))
              * _dot(x, up, precision), down, precision)


def route(x, p, c, fault=None):
  """(T, D) -> (ids (T, k), weights (T, k)) over ALL the experts."""
  logits = jnp.dot(x, p["router"], precision=_HIGHEST)
  scores = (jax.nn.sigmoid(logits) if fault == "sigmoid_scores"
            else jax.nn.softmax(logits, axis=-1))
  chosen, index = lax.top_k(scores, c["num_experts_per_tok"])
  if c["norm_topk_prob"] and fault != "no_renormalize":
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
  return index, chosen


def expert_layer(x, p, c, precision, fault=None):
  """The held experts' part and the gated shared expert: (y, tokens on
  each held expert)."""
  index, weight = route(x, p, c, fault)

  @jax.checkpoint
  def one(y, args):
    expert, gate, up, down = args
    mine = index == c["first_expert"] + expert                  # (T, k)
    w = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
    y = y + w[:, None] * gated_mlp(x, gate, up, down, precision)
    return y, jnp.sum(mine)

  y, counts = lax.scan(one, jnp.zeros_like(x), (
      jnp.arange(c["num_experts"]), p["experts_gate"],
      p["experts_up"], p["experts_down"]))
  s = p["shared"]
  shared = gated_mlp(x, s["gate"]["kernel"], s["up"]["kernel"],
                     s["down"]["kernel"], precision)
  if fault != "no_shared_gate":
    shared = shared * jax.nn.sigmoid(
        _dot(x, p["shared_gate"]["kernel"], precision))
  return y + shared, counts


def block(x, p, c, precision, full, fault=None):
  """(T, D) -> ((T, D), expert counts, (mean decay, mean beta) or
  None)."""
  eps = c["rms_norm_eps"]
  inner = centred_norm(x, p["attn_norm"]["scale"], eps, precision)
  if full:
    mixed, gates = gated_attention(inner, p["attn"], c, precision,
                                   fault), None
  else:
    mixed, decay, beta = gated_delta_net(inner, p["attn"], c, precision,
                                         fault)
    gates = (decay, beta)
  h = x + mixed
  y, counts = expert_layer(
      centred_norm(h, p["ffn_norm"]["scale"], eps, precision), p["moe"], c,
      precision, fault)
  return h + y, counts, gates


def token_losses(hidden, head, targets, precision, chunks=8):
  """Cross-entropy of every position, (T,), a chunk of logits at a time."""
  t, d = hidden.shape

  @jax.checkpoint
  def one(args):
    rows, wanted = args
    logits = _dot(rows, head, precision)
    picked = jnp.take_along_axis(logits, wanted[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked

  return lax.map(one, (hidden.reshape(chunks, t // chunks, d),
                       targets.reshape(chunks, t // chunks))).reshape(t)


def _forward_one(p, tokens, c, precision, fault):
  """One sequence: per-position losses, expert counts (layers, held) and
  the delta-net layers' mean decay and beta, in layer order."""
  interval = c["full_attention_interval"]
  x = nn._operand(p["embed"]["embedding"][tokens], precision)

  def period(x, q):
    counts, decays, betas = [], [], []
    for i in range(interval):
      full = is_full(c, i)
      x, count, gates = jax.checkpoint(
          lambda x, r, full=full: block(x, r, c, precision, full, fault))(
              x, q[f"block{i}"])
      counts.append(count)
      if gates is not None:
        decays.append(gates[0])
        betas.append(gates[1])
    return x, (jnp.stack(counts), jnp.stack(decays), jnp.stack(betas))

  x, (counts, decays, betas) = lax.scan(period, x, p["periods"])
  return {
      "token_loss_main": token_losses(
          centred_norm(x, p["final_norm"]["scale"], c["rms_norm_eps"],
                       precision),
          p["head"], jnp.roll(tokens, -1), precision),
      "expert_tokens": counts.reshape((-1,) + counts.shape[2:]),
      "gdn_decay_mean": decays.reshape(-1),
      "gdn_beta_mean": betas.reshape(-1),
  }


def forward(variables, features, train=True, precision="f32", config=None,
            fault=None):
  """Returns ({per-position losses (B, T), "expert_tokens" (layers,
  held) summed over the batch, "gdn_decay_mean" and "gdn_beta_mean"
  (linear layers,) averaged over it}, {}): there are no running
  statistics. `config` is the configuration file's object."""
  del train
  out = jax.vmap(lambda tokens: _forward_one(
      variables["params"], tokens, config, precision, fault))(
          features["tokens"])
  out["expert_tokens"] = jnp.sum(out["expert_tokens"], axis=0)
  for name in ("gdn_decay_mean", "gdn_beta_mean"):
    out[name] = jnp.mean(out[name], axis=0)
  return out, {}


def loss(outputs, features, labels=None, config=None, fault=None):
  """Mean cross-entropy over the valid positions: the last has no next
  token."""
  del labels, config
  t = features["tokens"].shape[-1]
  valid = (t - 1) // (2 if fault == "half_positions" else 1)
  losses = outputs["token_loss_main"]
  total = jnp.mean(jnp.sum(
      jnp.where(jnp.arange(t) < valid, losses, 0.0), axis=-1) / valid)
  return total, {"loss_main": total}

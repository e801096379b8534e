"""Plain reference of JoyAI-LLM-Flash cut to one chip's share of a
16-chip expert group (config.json of jdopensource/JoyAI-LLM-Flash; the
layer layout is DeepSeek-V3's, arXiv:2412.19437): MLA attention, one
dense block, expert blocks of routed top-8 experts (sigmoid scores,
`noaux_tc` correction bias, weights normalized over the 8 chosen, x2.5)
plus a shared expert, a depth-1 multi-token-prediction module, and the
loss CE_main + 0.3 CE_mtp over the vocabulary slice.

The share: the router scores all `router_width` experts;
only the `n_routed_experts` held here (from `first_expert`) are
computed, and what the absent ones would add is left out.

Everything is float32 `jax.numpy` with matmul precision "highest".
Attention is a masked softmax over all keys, computed a block of
queries at a time; the held experts are a loop, each over every token
with its weight (zero where the token did not choose it); the logits
are made a chunk of positions at a time. `jax.checkpoint` appears only
so that the backward pass fits the chip beside four parameter-sized
trees: per block, per query block, per expert, per chunk of logits.
Imports nothing of the program.

`fault` plants one departure, for the controls (each has to come out
not correct): "top4" (4 experts a token), "normalize_held" (weights over
the held experts chosen, not all 8), "no_bias" (choice without the
correction bias), "no_mtp" (loss without the MTP term),
"half_positions" (loss over the first half of the positions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import nn

FAULTS = ("top4", "normalize_held", "no_bias", "no_mtp", "half_positions")
_QUERY_BLOCK = 256


# --- seeded weights --------------------------------------------------------


def _kernel(pool, *shape):
  """Normal over fan-in (the second-last axis)."""
  return pool.normal(shape) * shape[-2] ** -0.5


def _scale(pool, *shape):
  """A norm's scale away from 1: a fresh norm hides a dropped one."""
  return pool.uniform(shape, 0.5, 1.5)


def _attention_params(pool, c, lead=()):
  heads = c["num_attention_heads"]
  nope, rope, vdim = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
  d, rq, rkv = c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"]
  dense = lambda cin, cout: {"kernel": _kernel(pool, *lead, cin, cout)}
  return {
      "q_a": dense(d, rq), "q_a_norm": {"scale": _scale(pool, *lead, rq)},
      "q_b": dense(rq, heads * (nope + rope)),
      "kv_a": dense(d, rkv + rope),
      "kv_a_norm": {"scale": _scale(pool, *lead, rkv)},
      "kv_b": dense(rkv, heads * (nope + vdim)),
      "o": dense(heads * vdim, d),
  }


def _mlp_params(pool, d, width, lead=()):
  return {"gate": {"kernel": _kernel(pool, *lead, d, width)},
          "up": {"kernel": _kernel(pool, *lead, d, width)},
          "down": {"kernel": _kernel(pool, *lead, width, d)}}


def _block_params(pool, c, experts, lead=()):
  d, width = c["hidden_size"], c["moe_intermediate_size"]
  block = {"attn_norm": {"scale": _scale(pool, *lead, d)},
           "attn": _attention_params(pool, c, lead),
           "ffn_norm": {"scale": _scale(pool, *lead, d)}}
  if not experts:
    block["mlp"] = _mlp_params(pool, d, c["intermediate_size"], lead)
    return block
  held, routed = c["n_routed_experts"], c["router_width"]
  block["moe"] = {
      "router": _kernel(pool, *lead, d, routed),
      # Away from 0, a fifth of the scores' own spread: it moves one
      # choice in a few and leaves the weights alone.
      "correction_bias": 0.05 * pool.normal(lead + (routed,)),
      "experts_gate": _kernel(pool, *lead, held, d, width),
      "experts_up": _kernel(pool, *lead, held, d, width),
      "experts_down": _kernel(pool, *lead, held, width, d),
      "shared": _mlp_params(pool, d, c["n_shared_experts"] * width, lead),
  }
  return block


def expert_layers(config):
  return config["num_hidden_layers"] - config["first_k_dense_replace"]


def init_variables(key, config):
  """{"params"} from one key, float32, in the program's layout: the
  expert blocks stacked on a leading axis (the program scans them)."""
  c, d = config, config["hidden_size"]

  def build(pool):
    params = {
        "embed": {"embedding": pool.normal((c["vocab_size"], d))},
        "expert_blocks": _block_params(pool, c, True, (expert_layers(c),)),
        "final_norm": {"scale": _scale(pool, d)},
        "head": _kernel(pool, d, c["vocab_size"]),
    }
    for i in range(c["first_k_dense_replace"]):
      params[f"dense_block{i}"] = _block_params(pool, c, False)
    if c["num_nextn_predict_layers"]:
      params["mtp"] = {
          "embed_norm": {"scale": _scale(pool, d)},
          "hidden_norm": {"scale": _scale(pool, d)},
          "eh_proj": {"kernel": _kernel(pool, 2 * d, d)},
          "block": _block_params(pool, c, True),
          "final_norm": {"scale": _scale(pool, d)},
      }
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
                 precision=lax.Precision.HIGHEST)


def rms_norm(x, scale, eps, precision):
  y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
  return nn._operand(y * scale, precision)


def rotary(x, theta):
  """(T, ..., R): the pair (x[2i], x[2i+1]) turns by t theta^(-2i/R)
  (`rope_interleave`)."""
  t, r = x.shape[0], x.shape[-1]
  inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
  angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
  angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
  even, odd = x[..., 0::2], x[..., 1::2]
  turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1)
  return turned.reshape(x.shape)


def causal_attention(q, k, v, scale):
  """(T, H, Dk), (T, H, Dk), (T, H, Dv) -> (T, H, Dv): softmax over all
  keys up to the query's own, a block of queries at a time."""
  t = q.shape[0]
  block = min(_QUERY_BLOCK, t)

  @jax.checkpoint
  def one(args):
    first, q_block = args
    scores = jnp.einsum("qhd,khd->hqk", q_block, k,
                        precision=lax.Precision.HIGHEST) * scale
    rows = first + jnp.arange(block)
    seen = rows[:, None] >= jnp.arange(t)[None, :]
    weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", weights, v,
                      precision=lax.Precision.HIGHEST)

  out = lax.map(one, (jnp.arange(0, t, block),
                      q.reshape((t // block, block) + q.shape[1:])))
  return out.reshape((t,) + v.shape[1:])


def mla(x, p, c, precision):
  """One sequence (T, D). Training form: no cache, nothing absorbed."""
  t = x.shape[0]
  heads, nope, rope, vdim = (c["num_attention_heads"], c["qk_nope_head_dim"],
                             c["qk_rope_head_dim"], c["v_head_dim"])
  eps, rank = c["rms_norm_eps"], c["kv_lora_rank"]
  c_q = rms_norm(_dot(x, p["q_a"]["kernel"], precision),
                 p["q_a_norm"]["scale"], eps, precision)
  q = _dot(c_q, p["q_b"]["kernel"], precision).reshape(t, heads, nope + rope)
  kv = _dot(x, p["kv_a"]["kernel"], precision)
  c_kv = rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], eps, precision)
  k_rope = rotary(kv[:, None, rank:], c["rope_theta"])      # one head
  kv = _dot(c_kv, p["kv_b"]["kernel"], precision).reshape(
      t, heads, nope + vdim)
  q = jnp.concatenate(
      [q[..., :nope], rotary(q[..., nope:], c["rope_theta"])], axis=-1)
  k = jnp.concatenate(
      [kv[..., :nope], jnp.broadcast_to(k_rope, (t, heads, rope))], axis=-1)
  out = causal_attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
  return _dot(out.reshape(t, heads * vdim), p["o"]["kernel"], precision)


def gated_mlp(x, gate, up, down, precision):
  return _dot(jax.nn.silu(_dot(x, gate, precision))
              * _dot(x, up, precision), down, precision)


def route(x, p, c, fault=None):
  """(T, D) -> (ids (T, k), weights (T, k)) over ALL the experts."""
  top_k = 4 if fault == "top4" else c["num_experts_per_tok"]
  scores = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                  precision=lax.Precision.HIGHEST))
  choice = scores if fault == "no_bias" else scores + p["correction_bias"]
  _, index = lax.top_k(choice, top_k)
  chosen = jnp.take_along_axis(scores, index, axis=-1)
  if fault == "normalize_held":
    local = index - c["first_expert"]
    here = (local >= 0) & (local < c["n_routed_experts"])
    total = jnp.sum(jnp.where(here, chosen, 0.0), axis=-1, keepdims=True)
  else:
    total = jnp.sum(chosen, axis=-1, keepdims=True)
  weight = chosen / (total + 1e-20) * c["routed_scaling_factor"]
  return index, weight


def expert_layer(x, p, c, precision, fault=None):
  """The held experts' part and the shared expert: (y, tokens on each
  held expert)."""
  index, weight = route(x, p, c, fault)

  @jax.checkpoint
  def one(y, args):
    expert, gate, up, down = args
    mine = index == c["first_expert"] + expert                  # (T, k)
    w = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
    y = y + w[:, None] * gated_mlp(x, gate, up, down, precision)
    return y, jnp.sum(mine)

  y, counts = lax.scan(one, jnp.zeros_like(x), (
      jnp.arange(c["n_routed_experts"]), p["experts_gate"],
      p["experts_up"], p["experts_down"]))
  if c["n_shared_experts"]:
    s = p["shared"]
    y = y + gated_mlp(x, s["gate"]["kernel"], s["up"]["kernel"],
                      s["down"]["kernel"], precision)
  return y, counts


def block(x, p, c, precision, fault=None):
  """(T, D) -> ((T, D), counts or None)."""
  eps = c["rms_norm_eps"]
  h = x + mla(rms_norm(x, p["attn_norm"]["scale"], eps, precision),
              p["attn"], c, precision)
  inner = rms_norm(h, p["ffn_norm"]["scale"], eps, precision)
  if "moe" in p:
    y, counts = expert_layer(inner, p["moe"], c, precision, fault)
  else:
    m = p["mlp"]
    y, counts = gated_mlp(inner, m["gate"]["kernel"], m["up"]["kernel"],
                          m["down"]["kernel"], precision), None
  return h + y, counts


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
  """One sequence: per-position losses of both heads, expert counts."""
  eps = c["rms_norm_eps"]
  embedding = p["embed"]["embedding"]
  x = nn._operand(embedding[tokens], precision)
  for i in range(c["first_k_dense_replace"]):
    x, _ = jax.checkpoint(
        lambda x, q: block(x, q, c, precision))(x, p[f"dense_block{i}"])
  x, counts = lax.scan(
      jax.checkpoint(lambda x, q: block(x, q, c, precision, fault)),
      x, p["expert_blocks"])
  out = {"token_loss_main": token_losses(
      rms_norm(x, p["final_norm"]["scale"], eps, precision), p["head"],
      jnp.roll(tokens, -1), precision)}
  if c["num_nextn_predict_layers"]:
    m = p["mtp"]
    following = nn._operand(embedding[jnp.roll(tokens, -1)], precision)
    joined = jnp.concatenate(
        [rms_norm(following, m["embed_norm"]["scale"], eps, precision),
         rms_norm(x, m["hidden_norm"]["scale"], eps, precision)], axis=-1)
    h = _dot(joined, m["eh_proj"]["kernel"], precision)
    h, extra = jax.checkpoint(
        lambda x, q: block(x, q, c, precision, fault))(h, m["block"])
    out["token_loss_mtp"] = token_losses(
        rms_norm(h, m["final_norm"]["scale"], eps, precision), p["head"],
        jnp.roll(tokens, -2), precision)
    counts = jnp.concatenate([counts, extra[None]])
  out["expert_tokens"] = counts
  return out


def forward(variables, features, train=True, precision="f32", config=None,
            fault=None):
  """Returns ({per-position losses (B, T) of both heads, "expert_tokens"
  (layers, held) summed over the batch}, {}): there are no running
  statistics. `config` is the configuration file's object (the sizes
  are not all to be read off the parameters' shapes)."""
  del train
  out = jax.vmap(lambda tokens: _forward_one(
      variables["params"], tokens, config, precision, fault))(
          features["tokens"])
  out["expert_tokens"] = jnp.sum(out["expert_tokens"], axis=0)
  return out, {}


def loss(outputs, features, labels=None, config=None, fault=None):
  """CE_main + w CE_mtp, each a mean over its valid positions: the last
  position has no next token, the last two have no token after it."""
  del labels
  t = features["tokens"].shape[-1]
  position = jnp.arange(t)
  cut = 2 if fault == "half_positions" else 1

  def mean_over(losses, valid):
    valid = valid // cut
    return jnp.mean(jnp.sum(jnp.where(position < valid, losses, 0.0),
                            axis=-1) / valid)

  parts = {"loss_main": mean_over(outputs["token_loss_main"], t - 1)}
  total = parts["loss_main"]
  if "token_loss_mtp" in outputs:
    parts["loss_mtp"] = mean_over(outputs["token_loss_mtp"], t - 2)
    if fault != "no_mtp":
      total = total + config["mtp_loss_weight"] * parts["loss_mtp"]
  return total, parts

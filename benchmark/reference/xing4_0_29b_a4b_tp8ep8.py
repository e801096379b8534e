"""Plain reference of Xing4.0-29B-A4B cut to one chip's share of an
8-chip tensor- and expert-parallel group (config.json of
XingChen-AGI/Xing4.0-29B-A4B; the layer layout is DeepSeek-V3's,
arXiv:2412.19437, the residual path that of "mHC: Manifold-Constrained
Hyper-Connections", arXiv:2512.24880): MLA attention under YaRN, leading
dense blocks, expert blocks of routed top-4 experts (sigmoid scores,
`noaux_tc` correction bias, weights normalized over the 4 chosen, x2)
plus a shared expert, a depth-1 multi-token-prediction module where the
configuration builds one, and the loss over the vocabulary slice.

The residual is `hc_mult` streams wide. Every sublayer F (attention,
then the dense MLP or the expert layer) of every block has its own
`phi` (nD, n^2 + 2n), `alpha` (3,) and `base` (n^2 + 2n,), columns
[pre (n) | post (n) | res (n^2, row-major)], and per token, with X the
token's (n, D) streams:

  xbar = vec(X) rsqrt(mean(vec(X)^2) + eps);  m = xbar phi
  H_pre = sigmoid(a_pre m_pre + b_pre)
  H_post = 2 sigmoid(a_post m_post + b_post)
  M = exp(clip(a_res mat(m_res) + b_res, clamp_min, clamp_max)), then
  `hc_sinkhorn_iters` times M <- M / (rowsum(M) + hc_eps),
  M <- M / (colsum(M) + hc_eps);  H_res = M
  u = sum_j H_pre[j] X[j];  y = F(N(u))
  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

X_0[i] = Emb(t) for every i; the output is N_final(sum_i X_L[i]).

The share: attention has the `num_attention_heads` held here (their
columns of `q_b` and `kv_b`, their rows of `o`); the router scores all
`router_width` experts and only the `n_routed_experts` held here (from
`first_expert`) are computed; what the absent heads and experts would
add is left out.

Everything is float32 `jax.numpy` with matmul precision "highest". The
maps are made token by token (a `vmap` of one token's function, Sinkhorn
a Python loop), attention is a masked softmax over all keys a block of
queries at a time, the held experts are a loop, the logits are made a
chunk of positions at a time. `jax.checkpoint` appears only so that the
backward pass fits the chip beside four parameter-sized trees. Imports
nothing of the program.

`fault` plants one departure, for the controls (each has to come out
not correct): "sinkhorn_2" (2 iterations), "res_identity" (H_res = I),
"post_no_2" (H_post without its factor 2), "pre_no_sigmoid" (H_pre the
affine map itself), "no_stream_rms" (the maps read the stream
unnormalized), "plain_rotary" (no YaRN blend), "no_mscale" (softmax
scale without m^2), "no_renormalize" (top-4 weights not normalized),
"half_positions" (loss over the first half of the positions). Streams
averaged, not summed, at the end is no fault to plant: the final norm
divides the factor out again, and the function is the same.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import nn

FAULTS = ("sinkhorn_2", "res_identity", "post_no_2", "pre_no_sigmoid",
          "no_stream_rms", "plain_rotary", "no_mscale", "no_renormalize",
          "half_positions")
_QUERY_BLOCK = 256
_COUNTERS = ("mhc_res_diag_mean", "mhc_pre_mean", "mhc_post_mean",
             "mhc_sinkhorn_gap")


# --- seeded weights --------------------------------------------------------


class _Draws:
  """Seeded numbers leaf by leaf, a key folded from the one key for
  each. (`nn.Pool`'s one draw cut into leaves does not serve here: the
  narrow leaves, 3, 24 and 64 wide, make the compiler keep the whole
  draw in several tilings at once, 11 to 21 GB of temporaries in the
  program that seeds the weights.)"""

  def __init__(self, key):
    self._key, self._leaves = key, 0

  def _next(self):
    self._leaves += 1
    return jax.random.fold_in(self._key, self._leaves)

  def normal(self, shape):
    return jax.random.normal(self._next(), shape, jnp.float32)

  def uniform(self, shape, low, high):
    return jax.random.uniform(self._next(), shape, jnp.float32, low, high)


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


def _hyper_params(pool, c, lead=()):
  """Away from the trivial: at the paper's alpha = 0.01 the maps hardly
  see the data, and with a flat `base` Sinkhorn has converged in two
  iterations."""
  n, d = c["hc_mult"], c["hidden_size"]
  maps = n * n + 2 * n
  return {"phi": _kernel(pool, *lead, n * d, maps),
          "alpha": pool.uniform(lead + (3,), 0.5, 1.5),
          "base": pool.normal(lead + (maps,))}


def _mlp_params(pool, d, width, lead=()):
  return {"gate": {"kernel": _kernel(pool, *lead, d, width)},
          "up": {"kernel": _kernel(pool, *lead, d, width)},
          "down": {"kernel": _kernel(pool, *lead, width, d)}}


def _block_params(pool, c, experts, lead=()):
  d, width = c["hidden_size"], c["moe_intermediate_size"]
  block = {"attn_norm": {"scale": _scale(pool, *lead, d)},
           "attn": _attention_params(pool, c, lead),
           "attn_hc": _hyper_params(pool, c, lead),
           "ffn_norm": {"scale": _scale(pool, *lead, d)},
           "ffn_hc": _hyper_params(pool, c, lead)}
  if not experts:
    block["mlp"] = _mlp_params(pool, d, c["intermediate_size"], lead)
    return block
  held, routed = c["n_routed_experts"], c["router_width"]
  block["moe"] = {
      "router": _kernel(pool, *lead, d, routed),
      # Away from 0 and small: top-4 of 64 sits in the scores' tail,
      # where a bias of 0.05 hands single experts four times their share
      # and the eight held ones 10-15% of the assignments from seed to
      # seed (a rate spread of 0.9%); 0.01 moves one choice in some tens
      # and leaves the held share at 12.5 +- 0.3%.
      "correction_bias": 0.01 * pool.normal(lead + (routed,)),
      "experts_gate": _kernel(pool, *lead, held, d, width),
      "experts_up": _kernel(pool, *lead, held, d, width),
      "experts_down": _kernel(pool, *lead, held, width, d),
      "shared": _mlp_params(pool, d, c["n_shared_experts"] * width, lead),
  }
  return block


def expert_layers(config):
  return config["num_hidden_layers"] - config["dense_blocks_run"]


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
    for i in range(c["dense_blocks_run"]):
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

  return build(_Draws(key))


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


def inverse_frequencies(width, theta, scaling):
  """(width / 2,) float32: theta^(-2i/width), under YaRN
  (`rope_scaling`, as the DeepSeek-V2/V3 family's public code has it)
  blended with the same over `factor`: pairs that turn more than
  `beta_fast` times over the original context stay as they are, those
  that turn less than `beta_slow` times are slowed by `factor`, a linear
  ramp between."""
  index = jnp.arange(0, width, 2, dtype=jnp.float32)
  plain = theta ** (-index / width)
  if scaling is None:
    return plain
  original = scaling["original_max_position_embeddings"]
  turns_at = lambda turns: (width * math.log(original / (2 * math.pi * turns))
                            / (2 * math.log(theta)))
  low = max(math.floor(turns_at(scaling["beta_fast"])), 0)
  high = min(math.ceil(turns_at(scaling["beta_slow"])), width - 1)
  if low == high:
    high += 0.001
  ramp = jnp.clip((jnp.arange(width // 2, dtype=jnp.float32) - low)
                  / (high - low), 0.0, 1.0)
  return plain * (1.0 - ramp) + plain / scaling["factor"] * ramp


def softmax_mscale(scaling):
  """m of the softmax scale's m^2: 0.1 mscale_all_dim ln(factor) + 1.
  (cos and sin are scaled by the ratio of that with `mscale` in place of
  `mscale_all_dim`; both are 1 here, the ratio 1.)"""
  if scaling is None or scaling["factor"] <= 1:
    return 1.0
  ratio = ((0.1 * scaling["mscale"] * math.log(scaling["factor"]) + 1.0)
           / (0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"])
              + 1.0))
  if ratio != 1.0:
    raise NotImplementedError("cos/sin scaled by mscale / mscale_all_dim")
  return 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0


def rotary(x, inv_freq):
  """(T, ..., R): the pair (x[2i], x[2i+1]) turns by t inv_freq[i]
  (interleaved pairs, the family's default)."""
  t, r = x.shape[0], x.shape[-1]
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


def mla(x, p, c, precision, fault=None):
  """One sequence (T, D), the held heads. Training form: no cache,
  nothing absorbed."""
  t = x.shape[0]
  heads, nope, rope, vdim = (c["num_attention_heads"], c["qk_nope_head_dim"],
                             c["qk_rope_head_dim"], c["v_head_dim"])
  eps, rank = c["rms_norm_eps"], c["kv_lora_rank"]
  scaling = c["rope_scaling"]
  inv_freq = inverse_frequencies(
      rope, c["rope_theta"], None if fault == "plain_rotary" else scaling)
  m = 1.0 if fault == "no_mscale" else softmax_mscale(scaling)
  c_q = rms_norm(_dot(x, p["q_a"]["kernel"], precision),
                 p["q_a_norm"]["scale"], eps, precision)
  q = _dot(c_q, p["q_b"]["kernel"], precision).reshape(t, heads, nope + rope)
  kv = _dot(x, p["kv_a"]["kernel"], precision)
  c_kv = rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], eps, precision)
  k_rope = rotary(kv[:, None, rank:], inv_freq)               # one head
  kv = _dot(c_kv, p["kv_b"]["kernel"], precision).reshape(
      t, heads, nope + vdim)
  q = jnp.concatenate(
      [q[..., :nope], rotary(q[..., nope:], inv_freq)], axis=-1)
  k = jnp.concatenate(
      [kv[..., :nope], jnp.broadcast_to(k_rope, (t, heads, rope))], axis=-1)
  out = causal_attention(q, k, kv[..., nope:],
                         (nope + rope) ** -0.5 * m * m)
  return _dot(out.reshape(t, heads * vdim), p["o"]["kernel"], precision)


def gated_mlp(x, gate, up, down, precision):
  return _dot(jax.nn.silu(_dot(x, gate, precision))
              * _dot(x, up, precision), down, precision)


def route(x, p, c, fault=None):
  """(T, D) -> (ids (T, k), weights (T, k)) over ALL the experts."""
  scores = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                  precision=lax.Precision.HIGHEST))
  _, index = lax.top_k(scores + p["correction_bias"],
                       c["num_experts_per_tok"])
  chosen = jnp.take_along_axis(scores, index, axis=-1)
  if fault != "no_renormalize":
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
  return index, chosen * c["routed_scaling_factor"]


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


def token_maps(streams, p, c, fault=None):
  """One token's (n, D) streams -> (H_pre (n,), H_post (n,), H_res
  (n, n), the largest |row sum - 1| Sinkhorn left)."""
  n = c["hc_mult"]
  flat = streams.reshape(-1)
  if fault != "no_stream_rms":
    flat = flat * lax.rsqrt(jnp.mean(jnp.square(flat)) + c["rms_norm_eps"])
  m = jnp.dot(flat, p["phi"], precision=lax.Precision.HIGHEST)
  alpha, base = p["alpha"], p["base"]
  pre = alpha[0] * m[:n] + base[:n]
  if fault != "pre_no_sigmoid":
    pre = jax.nn.sigmoid(pre)
  post = jax.nn.sigmoid(alpha[1] * m[n:2 * n] + base[n:2 * n])
  if fault != "post_no_2":
    post = 2.0 * post
  mixed = jnp.exp(jnp.clip(
      alpha[2] * m[2 * n:].reshape(n, n) + base[2 * n:].reshape(n, n),
      c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]))
  for _ in range(2 if fault == "sinkhorn_2" else c["hc_sinkhorn_iters"]):
    mixed = mixed / (jnp.sum(mixed, axis=1, keepdims=True) + c["hc_eps"])
    mixed = mixed / (jnp.sum(mixed, axis=0, keepdims=True) + c["hc_eps"])
  gap = jnp.max(jnp.abs(jnp.sum(mixed, axis=1) - 1.0))
  if fault == "res_identity":
    mixed = jnp.eye(n, dtype=mixed.dtype)
  return pre, post, mixed, gap


def hyper_step(streams, p, c, inner, precision, fault=None):
  """One sublayer over the (T, n, D) streams: `inner` maps the (T, D)
  read-out u to (y, extra). Returns (streams', extra, the maps' step
  counters (4,): mean diagonal of H_res, mean H_pre, mean H_post, the
  largest Sinkhorn gap)."""
  pre, post, mixed, gap = jax.vmap(
      lambda x: token_maps(x, p, c, fault))(streams)
  u = jnp.einsum("tj,tjd->td", pre, streams,
                 precision=lax.Precision.HIGHEST)
  y, extra = inner(u)
  out = (jnp.einsum("tij,tjd->tid", mixed, streams,
                    precision=lax.Precision.HIGHEST)
         + post[:, :, None] * y[:, None, :])
  counters = jnp.stack([
      jnp.mean(jnp.diagonal(mixed, axis1=1, axis2=2)), jnp.mean(pre),
      jnp.mean(post), jnp.max(gap)])
  # The program keeps the stream in its compute dtype.
  return nn._operand(out, precision), extra, counters


def block(streams, p, c, precision, fault=None):
  """(T, n, D) -> ((T, n, D), expert counts or None, counters (2, 4))."""
  eps = c["rms_norm_eps"]
  attend = lambda u: (mla(rms_norm(u, p["attn_norm"]["scale"], eps,
                                   precision), p["attn"], c, precision,
                          fault), None)
  streams, _, first = hyper_step(streams, p["attn_hc"], c, attend,
                                 precision, fault)

  def feed_forward(u):
    inner = rms_norm(u, p["ffn_norm"]["scale"], eps, precision)
    if "moe" in p:
      return expert_layer(inner, p["moe"], c, precision, fault)
    m = p["mlp"]
    return gated_mlp(inner, m["gate"]["kernel"], m["up"]["kernel"],
                     m["down"]["kernel"], precision), None

  streams, counts, second = hyper_step(streams, p["ffn_hc"], c, feed_forward,
                                       precision, fault)
  return streams, counts, jnp.stack([first, second])


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


def _fan_out(x, c):
  return jnp.broadcast_to(x[:, None, :], (x.shape[0], c["hc_mult"],
                                          x.shape[1]))


def _forward_one(p, tokens, c, precision, fault):
  """One sequence: per-position losses, expert counts, the maps'
  counters by layer and sublayer."""
  eps = c["rms_norm_eps"]
  embedding = p["embed"]["embedding"]
  run = jax.checkpoint(lambda x, q: block(x, q, c, precision, fault))
  streams = _fan_out(nn._operand(embedding[tokens], precision), c)
  maps = []
  for i in range(c["dense_blocks_run"]):
    streams, _, counters = run(streams, p[f"dense_block{i}"])
    maps.append(counters[None])

  def scanned(x, q):
    x, counts, counters = run(x, q)
    return x, (counts, counters)

  streams, (counts, counters) = lax.scan(scanned, streams,
                                         p["expert_blocks"])
  maps.append(counters)
  x = jnp.sum(streams, axis=1)
  out = {"token_loss_main": token_losses(
      rms_norm(x, p["final_norm"]["scale"], eps, precision), p["head"],
      jnp.roll(tokens, -1), precision)}
  if c["num_nextn_predict_layers"]:
    m = p["mtp"]
    following = nn._operand(embedding[jnp.roll(tokens, -1)], precision)
    joined = jnp.concatenate(
        [rms_norm(following, m["embed_norm"]["scale"], eps, precision),
         rms_norm(x, m["hidden_norm"]["scale"], eps, precision)], axis=-1)
    h = _fan_out(_dot(joined, m["eh_proj"]["kernel"], precision), c)
    h, extra, counters = run(h, m["block"])
    out["token_loss_mtp"] = token_losses(
        rms_norm(jnp.sum(h, axis=1), m["final_norm"]["scale"], eps,
                 precision), p["head"], jnp.roll(tokens, -2), precision)
    counts = jnp.concatenate([counts, extra[None]])
    maps.append(counters[None])
  out["expert_tokens"] = counts
  out["mhc"] = jnp.concatenate(maps)                  # (layers, 2, 4)
  return out


def forward(variables, features, train=True, precision="f32", config=None,
            fault=None):
  """Returns ({per-position losses (B, T), "expert_tokens" (expert
  layers, held) summed over the batch, "mhc_res_diag_mean" |
  "mhc_pre_mean" | "mhc_post_mean" (layers, 2) means over the batch's
  tokens, "mhc_sinkhorn_gap" (layers, 2) the largest}, {}): there are no
  running statistics. `config` is the configuration file's object (the
  sizes are not all to be read off the parameters' shapes)."""
  del train
  out = jax.vmap(lambda tokens: _forward_one(
      variables["params"], tokens, config, precision, fault))(
          features["tokens"])
  out["expert_tokens"] = jnp.sum(out["expert_tokens"], axis=0)
  maps = out.pop("mhc")                               # (B, layers, 2, 4)
  for i, name in enumerate(_COUNTERS):
    reduce = jnp.max if name == "mhc_sinkhorn_gap" else jnp.mean
    out[name] = reduce(maps[..., i], axis=0)
  return out, {}


def loss(outputs, features, labels=None, config=None, fault=None):
  """CE_main (+ w CE_mtp where the module is built), each a mean over
  its valid positions: the last position has no next token, the last two
  have no token after it."""
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
    total = total + config["mtp_loss_weight"] * parts["loss_mtp"]
  return total, parts

"""Plain reference of MiniCPM-SALA cut to one chip's share of a 2-chip
tensor-parallel group (config.json of openbmb/MiniCPM-SALA; the sparse
layer is MiniCPM4's InfLLM-v2, arXiv:2506.07900 §2.1, the linear layer
Lightning attention, arXiv:2401.04658, with MiniMax-01's decays,
arXiv:2501.08313): the layers `mixer_types` names, "minicpm4" sparse
attention and "lightning-attn" Lightning attention, each block's FFN a
dense SiLU-gated MLP, under muP's scales:

  x_0 = scale_emb Emb(t)
  h = x + r Mixer(N(x));  y = h + r MLP(N(h)),  r = scale_depth / sqrt(L)
  logits = head(N(x_L) / (hidden_size / dim_model_base))

with L the published layer count. Per held head:

  sparse:    q = N_q(x W_q), k = N_k(x W_k), v = x W_v (no rotary);
             K~_j = mean(k[stride j : stride j + kernel]);
             s(t, j) = sum over the group's heads of softmax_j(q . K~_j /
             sqrt(d)) over the j that end at or before t;
             S(t, b) = max of s(t, j) over the j whose span meets block b;
             I(t) = the first `init_blocks` blocks, the blocks that meet
             [t - window + 1, t] and the top `topk` of S(t, .) among the
             other causal blocks (lax.top_k: ties to the lower block);
             o = softmax over the keys s <= t in I(t), scale 1/sqrt(d);
             y = W_o (sigmoid(x W_g) o).
  lightning: q = RoPE(N_q(x W_q)) / sqrt(d), k = RoPE(N_k(x W_k)),
             v = x W_v, RoPE in half-split pairs over the whole head;
             S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t;
             lambda_h = exp(-m_h (1 - l / (L - 1) + 1e-5)),
             m_h = 2^(-8 (h + 1) / H) over the published H heads, l the
             layer; y = W_o (sigmoid(x W_g) N_head(o)).

The share: the chip holds query heads `first_head` on (their key/value
head with them) and the same Lightning heads, and the first
`intermediate_held` columns of each MLP; the all-reduce after W_o and
`down` that would add the other chip's part is left out, here as in the
program. The Lightning output norm is taken per head.

Everything is float32 `jax.numpy` with matmul precision "highest". The
sparse layer is dense attention under the mask of its own selection, a
block of queries at a time (the selection made for that block beside
it); the Lightning recurrence is the recurrence itself, token by token,
a `lax.scan` checkpointed 64 tokens at a time; the MLP and the logits
are made a chunk of positions at a time. `jax.checkpoint` appears only
so that two steps fit the chip beside four parameter-sized trees.
Imports nothing of the program.

`fault` plants one departure, for the controls (each has to come out
not correct): "dense_attention" (every causal key), "per_head_selection"
(each head selects alone, not its group), "window_only" (the initial
block and the window, nothing chosen), "sparse_rotary" (rotary on the
sparse layer's q and k), "no_decay" (lambda = 1), "decay_16_heads" (the
slopes over 16 heads, not the published 32), "no_scale_depth" (r = 1),
"logits_undivided" (the final hidden not divided), "no_output_gate"
(neither layer's output gated), "half_positions" (loss over the first
half of the positions). It is a name, or a traced index into FAULTS (-1:
none), so that one compiled step serves every fault.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import nn

FAULTS = ("dense_attention", "per_head_selection", "window_only",
          "sparse_rotary", "no_decay", "decay_16_heads", "no_scale_depth",
          "logits_undivided", "no_output_gate", "half_positions")
_QUERY_BLOCK = 128
_TOKEN_BLOCK = 64
_CHUNKS = 8
_HIGHEST = lax.Precision.HIGHEST


def planted(fault, name):
  """Whether `fault` (None, a name, or a traced index into FAULTS) is
  `name`: a Python bool or a traced one."""
  if fault is None or isinstance(fault, str):
    return fault == name
  return fault == FAULTS.index(name)


# --- seeded weights --------------------------------------------------------


def _kernel(pool, *shape):
  """Normal over fan-in (the second-last axis)."""
  return pool.normal(shape) * shape[-2] ** -0.5


def _scale(pool, *shape):
  """A norm's weight away from 1: a fresh norm hides a dropped one."""
  return pool.uniform(shape, 0.5, 1.5)


def is_sparse(config, layer):
  return config["mixer_types"][layer] == "minicpm4"


def _mixer_params(pool, c, sparse):
  d = c["hidden_size"]
  if sparse:
    heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
  else:
    heads = kv = c["lightning_nh"]
    hd = c["lightning_head_dim"]
  p = {
      "q_proj": {"kernel": _kernel(pool, d, heads * hd)},
      "k_proj": {"kernel": _kernel(pool, d, kv * hd)},
      "v_proj": {"kernel": _kernel(pool, d, kv * hd)},
      "q_norm": {"scale": _scale(pool, hd)},
      "k_norm": {"scale": _scale(pool, hd)},
      "o_gate": {"kernel": _kernel(pool, d, heads * hd)},
      "o_proj": {"kernel": _kernel(pool, heads * hd, d)},
  }
  if not sparse:
    p["o_norm"] = _scale(pool, heads * hd)
  return p


def init_variables(key, config):
  """{"params"} from one key, float32, in the program's layout: blocks
  `block0`.. by layer, each its mixer under "attn" and its MLP under
  "mlp"."""
  c, d = config, config["hidden_size"]
  width = c["intermediate_held"]

  def build(pool):
    params = {"embed": {"embedding": pool.normal((c["vocab_size"], d))}}
    for i in range(c["num_hidden_layers"]):
      params[f"block{i}"] = {
          "attn_norm": {"scale": _scale(pool, d)},
          "attn": _mixer_params(pool, c, is_sparse(c, i)),
          "ffn_norm": {"scale": _scale(pool, d)},
          "mlp": {"gate": {"kernel": _kernel(pool, d, width)},
                  "up": {"kernel": _kernel(pool, d, width)},
                  "down": {"kernel": _kernel(pool, width, d)}},
      }
    params["final_norm"] = {"scale": _scale(pool, d)}
    params["head"] = _kernel(pool, d, c["vocab_size"])
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


def half_rotary(x, theta, first=0):
  """(T, H, D) at positions first.. : pairs (x[i], x[i + D/2]) turn by
  t theta^(-2i/D)."""
  t, half = x.shape[0], x.shape[-1] // 2
  inv_freq = theta ** (-jnp.arange(0, 2 * half, 2, dtype=jnp.float32)
                       / (2 * half))
  position = first + jnp.arange(t, dtype=jnp.float32)
  angle = (position[:, None] * inv_freq)[:, None, :]
  first_half, second = x[..., :half], x[..., half:]
  return jnp.concatenate(
      [first_half * jnp.cos(angle) - second * jnp.sin(angle),
       second * jnp.cos(angle) + first_half * jnp.sin(angle)], axis=-1)


def _chunked(fn, *arrays, chunks=_CHUNKS):
  """fn(first position, *rows) over the positions a chunk at a time,
  each chunk checkpointed; the chunks' outputs joined again."""
  t = arrays[0].shape[0]
  size = t // chunks
  out = lax.map(jax.checkpoint(lambda args: fn(*args)), (
      jnp.arange(0, t, size),) + tuple(
          a.reshape((chunks, size) + a.shape[1:]) for a in arrays))
  return jax.tree_util.tree_map(
      lambda o: o.reshape((t,) + o.shape[2:]), out)


def _selection(s, rows, c):
  """s (..., Tq, NC) the selection scores of the queries `rows` ->
  (..., Tq, NB) bool, I(t) as the module's docstring says."""
  sizes, t = c["sparse_config"], c["sequence_length"]
  block, kernel, stride = (sizes["block_size"], sizes["kernel_size"],
                           sizes["kernel_stride"])
  nb, nc = t // block, s.shape[-1]
  # The compressed keys that meet block b: stride j <= last key of b and
  # stride j + kernel - 1 >= first key of b; the sizes are static.
  j, b = np.arange(nc)[:, None], np.arange(nb)[None, :]
  meets = ((j * stride <= b * block + block - 1)
           & (j * stride + kernel - 1 >= b * block))           # (NC, NB)
  span = int(meets.sum(axis=0).max())
  first = np.argmax(meets, axis=0)                             # (NB,)
  index = np.minimum(first[:, None] + np.arange(span), nc - 1)
  valid = meets[index, np.arange(nb)[:, None]]                 # (NB, span)
  best = jnp.max(jnp.where(valid, s[..., index], 0.0), axis=-1)
  start = jnp.arange(nb) * block
  causal = start[None, :] <= rows[:, None]
  forced = causal & ((jnp.arange(nb)[None, :] < sizes["init_blocks"])
                     | (start[None, :] + block - 1
                        >= rows[:, None] - sizes["window_size"] + 1))
  values, chosen = lax.top_k(
      jnp.where(causal & ~forced, best, -jnp.inf), min(sizes["topk"], nb))
  picked = jnp.any((chosen[..., None] == jnp.arange(nb))
                   & jnp.isfinite(values)[..., None], axis=-2)
  return forced | picked


def sparse_sublayer(x, norm, p, c, r, precision, fault=None):
  """x + r Sparse(N(x)) for one sequence (T, D), and the mean share of
  the group's selection scores on compressed keys inside the selection.
  A block of queries at a time: its projections, its selection, its
  attention over every key and its rows of the output."""
  t = x.shape[0]
  heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
  group, eps = heads // kv, c["rms_norm_eps"]
  sizes = c["sparse_config"]
  block, kernel, stride = (sizes["block_size"], sizes["kernel_size"],
                           sizes["kernel_stride"])
  inner = lambda rows: nn._operand(rms_norm(rows, norm, eps), precision)
  turned = planted(fault, "sparse_rotary")
  turn = lambda a, first: jnp.where(
      turned, half_rotary(a, c["rope_theta"], first), a)

  def keys_values(first, rows):
    n = inner(rows)
    k = rms_norm(_dot(n, p["k_proj"]["kernel"], precision).reshape(
        -1, kv, hd), p["k_norm"]["scale"], eps)
    return (turn(k, first),
            _dot(n, p["v_proj"]["kernel"], precision).reshape(-1, kv, hd))

  k, v = _chunked(keys_values, x)
  # Compressed keys, (kv, NC, hd), and where each span ends and lies.
  count = (t - kernel) // stride + 1
  starts = jnp.arange(count) * stride
  windows = starts[:, None] + jnp.arange(kernel)
  keys = jnp.mean(k[windows], axis=1).transpose(1, 0, 2)
  first, last = starts // block, (starts + kernel - 1) // block
  k_in, v_in = (nn._operand(a, precision) for a in (k, v))
  scale = hd ** -0.5
  rows_of = min(_QUERY_BLOCK, t)

  @jax.checkpoint
  def one(args):
    first_row, x_rows = args
    rows = first_row + jnp.arange(rows_of)
    n = inner(x_rows)
    q_block = turn(rms_norm(_dot(n, p["q_proj"]["kernel"], precision).reshape(
        rows_of, heads, hd), p["q_norm"]["scale"], eps), first_row)
    # The selection, float32, no gradient.
    q_sel = lax.stop_gradient(q_block).reshape(rows_of, kv, group, hd)
    scores = jnp.einsum("tkgd,kcd->kgtc", q_sel, lax.stop_gradient(keys),
                        precision=_HIGHEST) * scale
    seen = (starts + kernel - 1)[None, :] <= rows[:, None]    # (Tq, NC)
    weights = jnp.where(seen, jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), 0.0)     # (kv, g, Tq, NC)
    s = jnp.sum(weights, axis=1)                              # (kv, Tq, NC)
    chosen = _selection(s, rows, c)                           # (kv, Tq, NB)
    alone = _selection(weights, rows, c)                      # (kv, g, Tq, NB)
    blocks = jnp.where(planted(fault, "per_head_selection"),
                       alone.reshape(heads, rows_of, -1),
                       jnp.repeat(chosen, group, axis=0))     # (heads, Tq, NB)
    # The initial block and the window alone, nothing chosen.
    start = jnp.arange(t // block) * block
    forced = ((jnp.arange(t // block)[None, :] < sizes["init_blocks"])
              | (start[None, :] + block - 1
                 >= rows[:, None] - sizes["window_size"] + 1))
    blocks = jnp.where(planted(fault, "window_only"), forced[None], blocks)
    blocks = jnp.where(planted(fault, "dense_attention"), True, blocks)
    # The share of the group's scores inside the blocks each head
    # attends to (the group's selection, in the sound reference).
    inside = blocks[..., first] & blocks[..., last]           # (heads, Tq, NC)
    s_heads = jnp.repeat(s, group, axis=0)
    total = jnp.sum(s_heads, axis=-1)
    mass = jnp.where(total > 0, jnp.sum(jnp.where(inside, s_heads, 0.0), -1)
                     / jnp.where(total > 0, total, 1.0), 1.0)
    causal = jnp.arange(t)[None, :] <= rows[:, None]
    visible = causal & jnp.repeat(blocks, block, axis=-1)     # (heads, Tq, T)
    # Each key/value head serves its group of consecutive query heads.
    logits = jnp.einsum(
        "qkgd,skd->kgqs",
        nn._operand(q_block, precision).reshape(rows_of, kv, group, hd),
        k_in, precision=_HIGHEST) * scale
    visible = visible.reshape(kv, group, rows_of, t)
    attend = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("kgqs,skd->qkgd", attend, v_in,
                     precision=_HIGHEST).reshape(rows_of, heads * hd)
    gate = jax.nn.sigmoid(_dot(n, p["o_gate"]["kernel"], precision))
    out = out * jnp.where(planted(fault, "no_output_gate"), 1.0, gate)
    return x_rows + r * _dot(out, p["o_proj"]["kernel"], precision), jnp.mean(
        mass)

  out, mass = lax.map(one, (jnp.arange(0, t, rows_of),
                            x.reshape(t // rows_of, rows_of, -1)))
  return out.reshape(x.shape), jnp.mean(mass)


def decays(c, layer, fault=None):
  """(held heads,) lambda_h of the Lightning heads held, in `layer`."""
  heads = c["published"]["lightning_nh"]
  heads = jnp.where(planted(fault, "decay_16_heads"), 16, heads)
  h = c["first_head"] + jnp.arange(c["lightning_nh"], dtype=jnp.float32)
  slope = 2.0 ** (-8.0 * (h + 1.0) / heads)
  layers = c["published"]["num_hidden_layers"]
  lam = jnp.exp(-slope * (1.0 - layer / (layers - 1) + 1e-5))
  return jnp.where(planted(fault, "no_decay"), 1.0, lam)


def recurrence(q, k, v, lam):
  """The decayed recurrence, token by token. q, k (T, H, Dk), v (T, H,
  Dv), lam (H,) -> (T, H, Dv); the state starts at nought."""
  t, heads, dk = q.shape

  def token(state, args):
    q_t, k_t, v_t = args
    state = state * lam[:, None, None] + k_t[:, :, None] * v_t[:, None, :]
    return state, jnp.einsum("hk,hkv->hv", q_t, state, precision=_HIGHEST)

  @jax.checkpoint
  def tokens(state, args):
    return lax.scan(token, state, args)

  block = min(_TOKEN_BLOCK, t)
  blocked = lambda a: a.reshape((t // block, block) + a.shape[1:])
  _, out = lax.scan(tokens, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                    tuple(map(blocked, (q, k, v))))
  return out.reshape(t, heads, v.shape[-1])


def lightning_sublayer(x, norm, p, c, r, layer, precision, fault=None):
  """x + r Lightning(N(x)) for one sequence (T, D), and the mean of the
  held heads' decays: the projections and the output a chunk of
  positions at a time, the recurrence over all."""
  heads, hd, eps = c["lightning_nh"], c["lightning_head_dim"], c[
      "rms_norm_eps"]
  inner = lambda rows: nn._operand(rms_norm(rows, norm, eps), precision)

  def qkv(first, rows):
    n = inner(rows)
    project = lambda name: _dot(n, p[name]["kernel"], precision).reshape(
        -1, heads, hd)
    q = half_rotary(rms_norm(project("q_proj"), p["q_norm"]["scale"], eps),
                    c["rope_theta"], first) * hd ** -0.5
    k = half_rotary(rms_norm(project("k_proj"), p["k_norm"]["scale"], eps),
                    c["rope_theta"], first)
    return tuple(nn._operand(a, precision) for a in (q, k, project("v_proj")))

  lam = decays(c, layer, fault)
  o = recurrence(*_chunked(qkv, x), lam)

  def out(first, rows, o_rows):
    del first
    o_rows = rms_norm(o_rows, p["o_norm"].reshape(heads, hd), eps)
    gate = jax.nn.sigmoid(_dot(inner(rows), p["o_gate"]["kernel"], precision))
    o_rows = o_rows.reshape(-1, heads * hd) * jnp.where(
        planted(fault, "no_output_gate"), 1.0, gate)
    return rows + r * _dot(o_rows, p["o_proj"]["kernel"], precision)

  # The smallest mean square of a held head's output before its norm:
  # where a token's output nearly cancels, the norm divides its gradient
  # by about that root.
  return _chunked(out, x, o), (jnp.mean(lam), jnp.min(jnp.mean(o * o, -1)))


def ffn_sublayer(h, norm, p, c, r, precision):
  """h + r down(silu(N(h) W_gate) N(h) W_up), a chunk of positions at a
  time."""
  def one(first, rows):
    del first
    n = nn._operand(rms_norm(rows, norm, c["rms_norm_eps"]), precision)
    hidden = (jax.nn.silu(_dot(n, p["gate"]["kernel"], precision))
              * _dot(n, p["up"]["kernel"], precision))
    return rows + r * _dot(hidden, p["down"]["kernel"], precision)

  return _chunked(one, h)


def block(x, p, c, layer, precision, fault=None):
  """(T, D) -> ((T, D), the sparse layer's selected mass or the Lightning
  layer's (mean decay, smallest mean square of a head's output))."""
  r = c["scale_depth"] / math.sqrt(c["published"]["num_hidden_layers"])
  r = jnp.where(planted(fault, "no_scale_depth"), 1.0, r)
  norm = p["attn_norm"]["scale"]
  if is_sparse(c, layer):
    h, mass = sparse_sublayer(x, norm, p["attn"], c, r, precision, fault)
  else:
    h, mass = lightning_sublayer(x, norm, p["attn"], c, r, layer, precision,
                                 fault)
  return ffn_sublayer(h, p["ffn_norm"]["scale"], p["mlp"], c, r,
                      precision), mass


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
  """One sequence: per-position losses, each sparse layer's selected mass
  and each Lightning layer's mean decay and smallest mean square of a
  head's output, in layer order."""
  x = nn._operand(p["embed"]["embedding"][tokens] * c["scale_emb"],
                  precision)
  masses, decays_of, floors = [], [], []
  for i in range(c["num_hidden_layers"]):
    x, counter = jax.checkpoint(
        lambda x, q, i=i: block(x, q, c, i, precision, fault))(
            x, p[f"block{i}"])
    if is_sparse(c, i):
      masses.append(counter)
    else:
      decays_of.append(counter[0])
      floors.append(counter[1])
  divisor = jnp.where(planted(fault, "logits_undivided"), 1.0,
                      c["hidden_size"] / c["dim_model_base"])
  hidden = rms_norm(x, p["final_norm"]["scale"], c["rms_norm_eps"]) / divisor
  return {"token_loss_main": token_losses(
              nn._operand(hidden, precision), p["head"], jnp.roll(tokens, -1),
              precision),
          "sala_selected_mass": jnp.stack(masses),
          "lightning_decay_mean": jnp.stack(decays_of),
          "lightning_output_floor": jnp.stack(floors)}


def forward(variables, features, train=True, precision="f32", config=None,
            fault=None):
  """Returns ({per-position losses (B, T), "sala_selected_mass" (sparse
  layers,) and "lightning_decay_mean" (Lightning layers,) averaged over
  the batch, "lightning_output_floor" (Lightning layers,) the smallest
  over the batch}, {}): there are no running statistics. `config` is the
  configuration file's object."""
  del train
  out = jax.vmap(lambda tokens: _forward_one(
      variables["params"], tokens, config, precision, fault))(
          features["tokens"])
  for name in ("sala_selected_mass", "lightning_decay_mean"):
    out[name] = jnp.mean(out[name], axis=0)
  out["lightning_output_floor"] = jnp.min(out["lightning_output_floor"],
                                          axis=0)
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

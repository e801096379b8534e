"""Block-sparse attention as InfLLM-v2 trains it (MiniCPM4, MiniCPM-SALA;
arXiv:2506.07900 §2.1): each query attends only to the key blocks its
group of query heads selected, with Pallas TPU kernels for the attention
over the selected blocks.

Selection (`select_blocks`, plain XLA, no gradient), per key/value head
and its group of query heads, blocks of `block` keys:

  K~_j   = mean(k[stride j : stride j + kernel])          compressed keys
  s(t,j) = sum over the group's heads h of softmax_j(q_th . K~_j / sqrt(D))
           over the j whose span ends at or before t
  S(t,b) = max of s(t,j) over the j whose span meets block b
  I(t)   = the first `init_blocks` blocks, the blocks that meet
           [t - window + 1, t], and the `top_k` best of S(t, .) among the
           other causal blocks (exact top-k, ties to the lower block);
           every causal block where the sequence is under `dense_len`.

Attention (`block_sparse_attention`): o_th = softmax over the keys s <= t
in the blocks of I(t), with the caller's scale. The kernels take a tile
of `TILE_TOKENS` consecutive queries with every head of their group (a
(tokens x group, D) tile: 128 rows at 16 heads), and visit the union of
the tile's tokens' blocks, whose indices come in scalar memory, in
ascending order; inside, a bit per token says which of them selected
the block, and the causal mask cuts the diagonal. The key/value head's
whole K and V rows sit in VMEM (single-buffered: their block never
moves), so a visit is a slice, not a copy. A loop iteration takes
`VISIT_KEYS` keys: that many / `block` of the union's blocks, their rows
stacked into one operand, so that the score tile fills its lanes and the
chain of products, max, exponentials and sums is paid once for all of
them; the last iteration's slots past the union are blocks no token
selected, masked. The backward is one program: per tile it makes p
again from the forward's logsumexp, writes dq, and adds the tile's dK
and dV into the head's whole float32 rows, which stay in VMEM across
the (sequential) grid.

The union is the cost of the tile: a block one token of the tile chose
is computed for all of them (masked). `plan` counts the (token, block)
pairs the tokens selected, those of the unions, and the slots the loops
step through (the unions rounded up to whole iterations).

`implementation="xla"` (and "auto" off a TPU or under
`dispatch.xla_only()`) computes the same attention as a dense masked
softmax (B, H, T, T): the CPU path and the kernels' oracle.

Precision follows the inputs: products take q, k, v, dO as they come in
and p and dS cast to their dtype, and sum in float32; the scale, max,
exponentials, sums and logsumexp are float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import dispatch

# The two kernels' names, as the device trace shows them: the forward
# (run again where a block is rematerialized) and the backward.
KERNEL_NAMES = ("sparse_attention_fwd", "sparse_attention_bwd")

# Query tokens a grid step takes, each with all of its group's heads.
# The bit that says which of them selected a block is one bit of an
# int32, so at most 31.
TILE_TOKENS = 8
# Keys a loop iteration of the kernels takes: max(1, VISIT_KEYS // block)
# of a tile's blocks at once (8 at blocks of 64), at most all of them.
# At T = 32,768, blocks of 64, 16 heads of 128 on one key/value head, on
# one v5e, ms a forward / backward call by keys an iteration: 64: 318 /
# 371, 128: 172 / 235, 256: 93 / 153, 512: 49 / 90; the padding of the
# last iteration rounds 217 blocks a tile up by 1.6% at 512, and twice
# that a width wider.
VISIT_KEYS = 512
# Query tokens a step of the selection takes: their (group, tokens,
# compressed keys) float32 scores are its largest temporary.
_SELECT_TOKENS = 1024
# A masked score: finite, so that a row with nothing visible yet keeps a
# finite running max and exp(m - m') is never nan.
_MASKED = -1e30

_NT = (((1,), (1,)), ((), ()))   # a b^T
_TN = (((0,), (0,)), ((), ()))   # a^T b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
  return jax.lax.dot_general(a, b, dims,
                             preferred_element_type=jnp.float32)


# --- selection ------------------------------------------------------------------


def compressed_keys(k, kernel: int, stride: int):
  """(B, T, Hkv, D) -> (B, Hkv, NC, D) float32: the mean of each window of
  `kernel` keys, windows `stride` apart, NC = (T - kernel) / stride + 1."""
  b, t, hkv, d = k.shape
  count = (t - kernel) // stride + 1
  k = k.astype(jnp.float32).transpose(0, 2, 1, 3)
  # A window is kernel / stride consecutive strides.
  pieces = k.reshape(b, hkv, t // stride, stride, d).sum(axis=3)
  parts = kernel // stride
  return sum(pieces[:, :, i:i + count] for i in range(parts)) / kernel


def select_blocks(q, k, *, block: int, kernel: int, stride: int, top_k: int,
                  init_blocks: int, window: int, dense_len: int):
  """Which key blocks each query's group selected.

  Args:
    q: (B, T, Hq, D); k: (B, T, Hkv, D); each key/value head serves Hq /
      Hkv consecutive query heads. Taken under `stop_gradient`.
    block, kernel, stride, top_k, init_blocks, window, dense_len: the
      published `sparse_config` sizes (see the module's docstring).

  Returns:
    (selected (B, Hkv, T, NB) bool: query t of that group selected key
    block b; selected_mass: the mean over queries and groups of the share
    of s(t, .) that falls on compressed keys whose spans lie inside I(t),
    float32; 1 where no compressed key is causal yet, or the sequence is
    dense).
  """
  q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
  b, t, hq, d = q.shape
  hkv = k.shape[2]
  group, nb = hq // hkv, t // block
  position = jnp.arange(t)
  causal = jnp.arange(nb)[None, :] * block <= position[:, None]   # (T, NB)
  if t < dense_len:
    selected = jnp.broadcast_to(causal, (b, hkv, t, nb))
    return selected, jnp.ones((), jnp.float32)
  keys = compressed_keys(k, kernel, stride)                    # (B, Hkv, NC, D)
  count = keys.shape[2]
  ends = jnp.arange(count) * stride + kernel - 1
  # The blocks each compressed key's span meets: its first and last.
  first_block = jnp.arange(count) * stride // block
  last_block = (jnp.arange(count) * stride + kernel - 1) // block
  # Block b meets the compressed keys per * b - reach ... per * b + per -
  # 1 (`stride` divides `block`): a window of per + reach of them.
  per, reach = block // stride, (kernel - 1) // stride
  scale = 1.0 / math.sqrt(d)
  tokens = min(_SELECT_TOKENS, t)

  def one(args):
    rows, q_rows = args          # (tokens,), (B, tokens, Hkv, group, D)
    scores = jnp.einsum("bthgd,bhcd->bhgtc", q_rows,
                        keys.astype(q_rows.dtype),
                        preferred_element_type=jnp.float32) * scale
    seen = ends[None, :] <= rows[:, None]                       # (tokens, NC)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    weights = jnp.where(seen, weights, 0.0)    # no causal key: nan -> 0
    s = jnp.sum(weights, axis=2)                               # (B, Hkv, tokens, NC)
    # S(t, b): the largest s(t, j) over the j that meet block b; s >= 0,
    # so the zeros padded in at either end change no maximum.
    padded = jnp.pad(s, [(0, 0)] * 3 + [(reach, per * nb + per - count)])
    best = functools.reduce(jnp.maximum, (
        padded[..., i:i + per * nb:per] for i in range(per + reach)))
    row_causal = causal[rows]                                  # (tokens, NB)
    block_index = jnp.arange(nb)[None, :]
    forced = row_causal & ((block_index < init_blocks) | (
        (block_index + 1) * block > rows[:, None] - window + 1))
    candidate = row_causal & ~forced
    values, index = jax.lax.top_k(
        jnp.where(candidate, best, -jnp.inf), min(top_k, nb))
    chosen = jnp.any((index[..., None] == jnp.arange(nb))
                     & (values[..., None] > -jnp.inf), axis=-2)
    selected = forced | chosen                                 # (B, Hkv, tokens, NB)
    inside = (jnp.take(selected, first_block, axis=-1)
              & jnp.take(selected, last_block, axis=-1))
    total = jnp.sum(s, axis=-1)
    mass = jnp.where(total > 0, jnp.sum(jnp.where(inside, s, 0.0), axis=-1)
                     / jnp.where(total > 0, total, 1.0), 1.0)
    return selected, mass

  steps = t // tokens
  q_steps = q.reshape(b, steps, tokens, hkv, group, d).transpose(
      1, 0, 2, 3, 4, 5)
  selected, mass = jax.lax.map(
      one, (position.reshape(steps, tokens), q_steps))
  # (steps, B, Hkv, tokens, .) -> (B, Hkv, T, .)
  selected = selected.transpose(1, 2, 0, 3, 4).reshape(b, hkv, t, nb)
  return selected, jnp.mean(mass)


# --- the visit plan ---------------------------------------------------------------


def visit_blocks(block: int, nb: int) -> int:
  """Blocks a loop iteration takes, of `nb` blocks of `block` keys."""
  return max(1, min(VISIT_KEYS // block, nb))


def plan(selected, tokens: int = TILE_TOKENS, per_visit: int = 1):
  """What the kernels visit, from `selected` (G, T, NB) bool, `per_visit`
  blocks an iteration.

  Returns (blocks (G·tiles, 1, NB') int32: each tile's union of its
  tokens' blocks in ascending order, then the rest, then block 0 up to
  NB', the next multiple of `per_visit`; bits (G·tiles, 1, NB') int32:
  bit i set where the tile's token i selected that block, 0 past the
  union; counts (G·tiles,) int32: the union's size; float32 counts of
  (token, block) pairs: those visited (the unions'), those selected, and
  the slots the loops step through (each union rounded up to a multiple
  of `per_visit`))."""
  g, t, nb = selected.shape
  tiles = t // tokens
  per_token = selected.reshape(g * tiles, tokens, nb)
  union = jnp.any(per_token, axis=1)
  bits = jnp.sum(per_token.astype(jnp.int32)
                 << jnp.arange(tokens, dtype=jnp.int32)[:, None], axis=1)
  order = jnp.argsort(jnp.where(union, 0, 1), axis=-1, stable=True)
  bits = jnp.take_along_axis(bits, order, -1)
  pad = [(0, 0), (0, -nb % per_visit)]
  counts = jnp.sum(union, axis=-1, dtype=jnp.int32)
  visited = jnp.sum(counts, dtype=jnp.float32) * tokens
  chosen = jnp.sum(selected, dtype=jnp.float32)
  steps = (counts + per_visit - 1) // per_visit
  slots = jnp.sum(steps, dtype=jnp.float32) * per_visit * tokens
  # (G·tiles, 1, NB'): a block of one row in scalar memory is its whole
  # last two dimensions.
  return (jnp.pad(order.astype(jnp.int32), pad)[:, None],
          jnp.pad(bits, pad)[:, None], counts, visited, chosen, slots)


# --- layouts -------------------------------------------------------------------


def _tiles(x, hkv: int, tokens: int):
  """(B, T, Hq, D) -> (B·Hkv, T / tokens, tokens · group, D): a tile's
  rows token-major, each token's group of heads together."""
  b, t, hq, d = x.shape
  group = hq // hkv
  x = x.reshape(b, t, hkv, group, d).transpose(0, 2, 1, 3, 4)
  return x.reshape(b * hkv, t // tokens, tokens * group, d)


def _untiles(x, b: int, t: int, hkv: int, group: int):
  d = x.shape[-1]
  x = x.reshape(b, hkv, t, group, d).transpose(0, 2, 1, 3, 4)
  return x.reshape(b, t, hkv * group, d)


def _rows(x):
  """(B, T, Hkv, D) -> (B·Hkv, T, D)."""
  b, t, h, d = x.shape
  return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


# --- the Pallas programs --------------------------------------------------------


def _row_tokens(rows: int, tokens: int, group: int):
  """(rows, 1) int32 columns: each row's bit (1 << its token's place in
  the tile) and that place."""
  r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
  bit, place = jnp.zeros_like(r), jnp.zeros_like(r)
  for i in range(tokens):
    mine = (r >= i * group) & (r < (i + 1) * group)
    bit = jnp.where(mine, 1 << i, bit)
    place = jnp.where(mine, i, place)
  return bit, place


def _turn(x, eye):
  """A (1, n) row to an (n, 1) column, or back: a sum with one term that
  is not nought, so exactly."""
  return jnp.sum(jnp.where(eye, x, 0.0), axis=1 if x.shape[0] == 1 else 0,
                 keepdims=True)


def _eye(rows: int):
  return (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
          == jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1))


def _visible(blocks_ref, bits_ref, i, bit, position, block: int, n: int):
  """(the first key of each of iteration i's n blocks, (rows, n·block)
  bool: which of their keys each row's token selected and sees)."""
  col = jax.lax.broadcasted_iota(jnp.int32, (1, n * block), 1)
  starts, key, bits = [], col, jnp.zeros_like(col)
  for u in range(n):
    start = pl.multiple_of(blocks_ref[0, 0, i * n + u] * block, block)
    mine = (col >= u * block) & (col < (u + 1) * block)
    key = jnp.where(mine, col + (start - u * block), key)
    bits = jnp.where(mine, bits_ref[0, 0, i * n + u], bits)
    starts.append(start)
  return starts, ((bits & bit) != 0) & (key <= position)


def _stacked(ref, starts, block: int):
  """The blocks' rows of a head's whole (1, T, D) rows, one under the
  next: (n·block, D), each piece at a sublane-aligned offset."""
  return jnp.concatenate([ref[0, pl.ds(start, block), :] for start in starts],
                         axis=0)


def _steps(counts_ref, index, n: int):
  """Iterations of n blocks that cover a tile's union."""
  return (counts_ref[index] + n - 1) // n


def _fwd_kernel(counts_ref, blocks_ref, bits_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, *, scale: float, block: int, tokens: int,
                group: int, tiles: int, per_visit: int):
  """One tile of queries over its union of blocks, online softmax,
  `per_visit` blocks an update."""
  g, tile = pl.program_id(0), pl.program_id(1)
  q = q_ref[0, 0]                                          # (rows, D)
  rows = q.shape[0]
  bit, place = _row_tokens(rows, tokens, group)
  position = tile * tokens + place

  def body(i, carry):
    m, l, acc = carry
    starts, keep = _visible(blocks_ref, bits_ref, i, bit, position, block,
                            per_visit)
    k, v = _stacked(k_ref, starts, block), _stacked(v_ref, starts, block)
    s = jnp.where(keep, _dot(q, k, _NT) * scale, _MASKED)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
            alpha * acc + _dot(p.astype(v.dtype), v))

  m, l, acc = jax.lax.fori_loop(
      0, _steps(counts_ref, g * tiles + tile, per_visit), body,
      (jnp.full((rows, 1), _MASKED, jnp.float32),
       jnp.zeros((rows, 1), jnp.float32),
       jnp.zeros((rows, v_ref.shape[-1]), jnp.float32)))
  # Every token sees its sequence's first key, so l > 0.
  o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
  lse_ref[0, 0] = _turn(m + jnp.log(l), _eye(rows))


def _bwd_kernel(counts_ref, blocks_ref, bits_ref, q_ref, k_ref, v_ref,
                o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref, *,
                scale: float, block: int, tokens: int, group: int,
                tiles: int, per_visit: int):
  """One tile's dq, and its part of the head's dK and dV, which the
  program keeps in VMEM across the tiles:
    p = exp(s - lse);  dV += p^T dO;  dP = dO V^T;  dS = p (dP - delta)
    dq = scale dS K;   dK += scale dS^T q;  delta = rowsum(dO . O)
  `per_visit` blocks an iteration: dV's and dK's parts are made for all
  of them at once and added block by block."""
  g, tile = pl.program_id(0), pl.program_id(1)

  @pl.when(tile == 0)
  def _():
    dk_ref[...] = jnp.zeros_like(dk_ref)
    dv_ref[...] = jnp.zeros_like(dv_ref)

  q, do = q_ref[0, 0], do_ref[0, 0]
  rows, dtype = q.shape[0], q.dtype
  bit, place = _row_tokens(rows, tokens, group)
  position = tile * tokens + place
  lse = _turn(lse_ref[0, 0], _eye(rows))                     # (rows, 1)
  delta = jnp.sum(do.astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
                  axis=1, keepdims=True)

  def body(i, dq):
    starts, keep = _visible(blocks_ref, bits_ref, i, bit, position, block,
                            per_visit)
    k, v = _stacked(k_ref, starts, block), _stacked(v_ref, starts, block)
    s = _dot(q, k, _NT) * scale
    p = jnp.where(keep, jnp.exp(s - lse), 0.0)
    dv = _dot(p.astype(dtype), do, _TN)                      # (n·block, D)
    ds = (p * (_dot(do, v, _NT) - delta)).astype(dtype)
    dk = _dot(ds, q, _TN) * scale
    for u, start in enumerate(starts):
      rows_u = slice(u * block, (u + 1) * block)
      dv_ref[0, pl.ds(start, block), :] += dv[rows_u]
      dk_ref[0, pl.ds(start, block), :] += dk[rows_u]
    return dq + _dot(ds, k)

  dq = jax.lax.fori_loop(0, _steps(counts_ref, g * tiles + tile, per_visit),
                         body, jnp.zeros(q.shape, jnp.float32))
  dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _grid(q_tiles, keys, plan_arrays):
  """What both programs share: the grid, the specs of the plan's rows in
  scalar memory, of a tile and of a head's whole rows, and the scoped
  VMEM they reckon (the whole rows once, the tiles twice)."""
  heads, tiles, rows, d = q_tiles.shape
  t, nb = keys.shape[1], plan_arrays[0].shape[-1]
  smem = lambda: pl.BlockSpec(
      (1, 1, nb), lambda g, i, counts: (g * tiles + i, 0, 0),
      memory_space=pltpu.SMEM)
  tile = pl.BlockSpec((1, 1, rows, d), lambda g, i, counts: (g, i, 0, 0),
                      memory_space=pltpu.VMEM)
  whole = lambda: pl.BlockSpec((1, t, d), lambda g, i, counts: (g, 0, 0),
                               memory_space=pltpu.VMEM,
                               pipeline_mode=pl.Buffered(1))
  row = pl.BlockSpec((1, 1, 1, rows), lambda g, i, counts: (g, i, 0, 0),
                     memory_space=pltpu.VMEM)
  return dict(grid=(heads, tiles), smem=smem, tile=tile, whole=whole, row=row,
              tiles=tiles)


def _params(whole_bytes: int, semantics):
  return pltpu.CompilerParams(
      dimension_semantics=semantics,
      vmem_limit_bytes=int(whole_bytes + 16 * 1024 * 1024))


@functools.partial(jax.jit,
                   static_argnames=("scale", "block", "tokens", "per_visit"))
def _pallas_forward(q_tiles, keys, values, blocks, bits, counts, *,
                    scale: float, block: int, tokens: int, per_visit: int):
  heads, tiles, rows, d = q_tiles.shape
  grid = _grid(q_tiles, keys, (blocks,))
  whole_bytes = 2 * keys.size // heads * keys.dtype.itemsize
  o, lse = pl.pallas_call(
      functools.partial(_fwd_kernel, scale=scale, block=block, tokens=tokens,
                        group=rows // tokens, tiles=tiles,
                        per_visit=per_visit),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1, grid=grid["grid"],
          in_specs=[grid["smem"](), grid["smem"](), grid["tile"],
                    grid["whole"](), grid["whole"]()],
          out_specs=[grid["tile"], grid["row"]]),
      out_shape=[jax.ShapeDtypeStruct(q_tiles.shape, values.dtype),
                 jax.ShapeDtypeStruct((heads, tiles, 1, rows), jnp.float32)],
      compiler_params=_params(whole_bytes, ("parallel", "arbitrary")),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[0],
  )(counts, blocks, bits, q_tiles, keys, values)
  return o, lse


@functools.partial(jax.jit,
                   static_argnames=("scale", "block", "tokens", "per_visit"))
def _pallas_backward(q_tiles, keys, values, o, lse, do, blocks, bits, counts,
                     *, scale: float, block: int, tokens: int,
                     per_visit: int):
  heads, tiles, rows, d = q_tiles.shape
  grid = _grid(q_tiles, keys, (blocks,))
  row_bytes = keys.size // heads
  # K and V in their dtype, dK and dV in float32.
  whole_bytes = row_bytes * (2 * keys.dtype.itemsize + 2 * 4)
  f32_rows = jax.ShapeDtypeStruct(keys.shape, jnp.float32)
  dq, dk, dv = pl.pallas_call(
      functools.partial(_bwd_kernel, scale=scale, block=block, tokens=tokens,
                        group=rows // tokens, tiles=tiles,
                        per_visit=per_visit),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1, grid=grid["grid"],
          in_specs=[grid["smem"](), grid["smem"](), grid["tile"],
                    grid["whole"](), grid["whole"](), grid["tile"],
                    grid["tile"], grid["row"]],
          out_specs=[grid["tile"], grid["whole"](), grid["whole"]()]),
      out_shape=[jax.ShapeDtypeStruct(q_tiles.shape, q_tiles.dtype),
                 f32_rows, f32_rows],
      # dK and dV gather every tile of their head: the tiles in order.
      compiler_params=_params(whole_bytes, ("parallel", "arbitrary")),
      interpret=jax.default_backend() != "tpu",
      name=KERNEL_NAMES[1],
  )(counts, blocks, bits, q_tiles, keys, values, o, do, lse)
  return dq, dk.astype(keys.dtype), dv.astype(values.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _attend_pallas(q_tiles, keys, values, blocks, bits, counts, scale, block,
                   tokens, per_visit):
  o, _ = _pallas_forward(q_tiles, keys, values, blocks, bits, counts,
                         scale=scale, block=block, tokens=tokens,
                         per_visit=per_visit)
  return o


def _attend_fwd(q_tiles, keys, values, blocks, bits, counts, scale, block,
                tokens, per_visit):
  o, lse = _pallas_forward(q_tiles, keys, values, blocks, bits, counts,
                           scale=scale, block=block, tokens=tokens,
                           per_visit=per_visit)
  return o, (q_tiles, keys, values, o, lse, blocks, bits, counts)


def _attend_bwd(scale, block, tokens, per_visit, residuals, do):
  q_tiles, keys, values, o, lse, blocks, bits, counts = residuals
  dq, dk, dv = _pallas_backward(q_tiles, keys, values, o, lse, do, blocks,
                                bits, counts, scale=scale, block=block,
                                tokens=tokens, per_visit=per_visit)
  return dq, dk, dv, None, None, None


_attend_pallas.defvjp(_attend_fwd, _attend_bwd)


def masked_attention(q, k, v, selected, *, block: int, scale: float):
  """The dense form: (B, T, Hq, D) queries over (B, T, Hkv, D) keys and
  values, softmax over the keys s <= t in the blocks `selected` (B, Hkv,
  T, NB) marks for t; scores (B, Hq, T, T) in float32."""
  b, t, hq, d = q.shape
  hkv = k.shape[2]
  group = hq // hkv
  keys_of = jnp.repeat(selected, block, axis=-1)             # (B, Hkv, T, T)
  seen = keys_of & jnp.tril(jnp.ones((t, t), bool))
  seen = jnp.repeat(seen, group, axis=1)                     # (B, Hq, T, T)
  k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale
  weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
  return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
                    preferred_element_type=jnp.float32).astype(v.dtype)


def block_sparse_attention(q, k, v, selected, *, block: int, scale: float,
                           implementation: str = "auto",
                           _per_visit: int | None = None):
  """Attention over the selected key blocks.

  Args:
    q: (B, T, Hq, D); k, v: (B, T, Hkv, D), each key/value head serving
      Hq / Hkv consecutive query heads.
    selected: (B, Hkv, T, NB) bool, as `select_blocks` makes it; every
      query selects the block of the sequence's first key.
    block: keys a block; scale: of the scores.
    implementation: "pallas", "xla", or "auto" (pallas on a TPU).
    _per_visit: blocks a loop iteration of the kernels takes, where a
      test sets it; else `visit_blocks(block, NB)`.

  Returns:
    (o (B, T, Hq, D) in v's dtype, {"visited": the (token, block) pairs
    of the tiles' unions, "selected": those the tokens selected, "slots":
    those the kernels' loops step through, the last iteration's padding
    included}, float32 sums over the batch and groups).
  """
  if implementation not in ("auto", "pallas", "xla"):
    raise ValueError(
        f"implementation must be 'auto', 'pallas', or 'xla'; got "
        f"{implementation!r}")
  b, t, hq, d = q.shape
  hkv = k.shape[2]
  tokens = TILE_TOKENS
  if t % block or t % tokens or hq % hkv:
    raise ValueError(f"T must be a multiple of the block ({block}) and of "
                     f"{tokens}, the heads of the key/value heads; got T={t}, "
                     f"heads {hq}/{hkv}")
  per_visit = _per_visit or visit_blocks(block, t // block)
  blocks, bits, counts, visited, chosen, slots = plan(
      selected.reshape(b * hkv, t, t // block), tokens, per_visit)
  use_xla = implementation == "xla" or (implementation == "auto" and (
      dispatch.use_xla_only() or jax.default_backend() != "tpu"))
  visits = {"visited": visited, "selected": chosen, "slots": slots}
  if use_xla:
    return masked_attention(q, k, v, selected, block=block,
                            scale=scale), visits
  o = _attend_pallas(_tiles(q, hkv, tokens), _rows(k), _rows(v), blocks,
                     bits, counts, scale, block, tokens, per_visit)
  return _untiles(o, b, t, hkv, hq // hkv), visits

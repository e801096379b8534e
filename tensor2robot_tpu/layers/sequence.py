"""Decoder blocks of a sparse-expert sequence model: RMSNorm (plain or
zero-centred), rotary positions (interleaved over the whole width, plain
or under YaRN, or half-split over the head's first part), three token
mixers (multi-head latent attention, gated grouped-query attention, the
gated delta net; training forms), gated MLP, the expert layer (routing
and grouping in `parallel/expert_parallel`), the residual rule (plain,
or manifold-constrained hyper-connections over `hc_mult` streams) and a
chunked next-token loss.

Layer equations: DeepSeek-V2/V3's, which the JoyAI-LLM-Flash and
Xing4.0 configs follow, Qwen3-Next's (gated attention and Gated Delta
Networks, arXiv:2412.06464) and mHC's (arXiv:2512.24880); see
`SequenceConfig`'s fields and each module.
Activations run in `dtype` (bfloat16), parameters are float32; norms,
rotary angles, the router, the softmax statistics, the delta net's
decays g, write strengths b, L2 norms and state, and the
hyper-connections' stream RMS, maps, Sinkhorn and weighted sums'
accumulation are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops import dispatch
from tensor2robot_tpu.ops.flash_attention import flash_attention
from tensor2robot_tpu.ops.gated_delta_rule import gated_delta_rule
from tensor2robot_tpu.ops.hyper_connection import (
    MapConfig, hyper_connection_post, hyper_connection_pre)
from tensor2robot_tpu.parallel import expert_parallel


@dataclasses.dataclass(frozen=True)
class SequenceConfig:
  """Sizes under their names in the published `config.json`; the chip's
  share (`experts_held`, `first_expert`, the vocabulary slice) beside
  them."""
  vocab_size: int = 129280
  hidden_size: int = 2048
  num_attention_heads: int = 32
  q_lora_rank: int = 1536
  kv_lora_rank: int = 512
  qk_nope_head_dim: int = 128
  qk_rope_head_dim: int = 64
  v_head_dim: int = 128
  rope_theta: float = 32e6
  rms_norm_eps: float = 1e-6
  intermediate_size: int = 7168
  moe_intermediate_size: int = 768
  n_shared_experts: int = 1
  n_routed_experts: int = 256      # the router's width
  num_experts_per_tok: int = 8
  routed_scaling_factor: float = 2.5
  experts_held: int = 256          # of n_routed_experts, from first_expert
  first_expert: int = 0
  first_k_dense_replace: int = 1
  num_hidden_layers: int = 40
  num_nextn_predict_layers: int = 1
  mtp_loss_weight: float = 0.3
  scoring_func: str = "sigmoid"    # or "softmax": over all, no bias
  # > 0: the shared expert is this wide and gated by sigmoid(x · w_s)
  # (Qwen3-Next); 0: n_shared_experts · moe_intermediate_size, ungated.
  shared_expert_intermediate_size: int = 0
  # The blocks' norms and gated attention's q/k norms: ⊙ (1 + w), w from 0.
  zero_centered_norm: bool = False
  # > 0: the hybrid layout. Layer i (from 0) is gated attention where
  # (i + 1) % full_attention_interval == 0, else the gated delta net.
  # 0: every layer MLA. The hybrid layout's own sizes have no default: a
  # configuration of it states them.
  full_attention_interval: int = 0
  num_key_value_heads: int = 0
  head_dim: int = 0
  partial_rotary_factor: float = 0.0
  linear_conv_kernel_dim: int = 0
  linear_key_head_dim: int = 0
  linear_value_head_dim: int = 0
  linear_num_key_heads: int = 0
  linear_num_value_heads: int = 0
  # YaRN, as the published `rope_scaling` group (factor,
  # original_max_position_embeddings, beta_fast, beta_slow, mscale,
  # mscale_all_dim); given as a dict, kept as its sorted items. None:
  # plain rotary and a softmax scale of 1/sqrt(head width).
  rope_scaling: Optional[Any] = None
  # > 0: the residual is this many streams wide, read, written and
  # mixed through `HyperConnection`'s maps (mHC). 0: h = x + F(N(x)).
  hc_mult: int = 0
  hc_sinkhorn_iters: int = 20
  hc_eps: float = 1e-6
  mhc_h_res_clamp_min: float = -30.0
  mhc_h_res_clamp_max: float = 30.0

  def __post_init__(self):
    if isinstance(self.rope_scaling, dict):
      object.__setattr__(self, "rope_scaling",
                         tuple(sorted(self.rope_scaling.items())))

  @property
  def num_expert_layers(self) -> int:
    return self.num_hidden_layers - self.first_k_dense_replace

  @property
  def hybrid(self) -> bool:
    return self.full_attention_interval > 0

  def layer_kind(self, layer: int) -> str:
    """"mla", or in the hybrid layout "full" | "linear"."""
    if not self.hybrid:
      return "mla"
    return ("full" if (layer + 1) % self.full_attention_interval == 0
            else "linear")


class RMSNorm(nn.Module):
  """x · rsqrt(mean(x²) + eps) ⊙ w, w from 1; `zero_centered`: ⊙ (1 + w),
  w from 0."""
  eps: float = 1e-6
  dtype: Any = jnp.bfloat16
  zero_centered: bool = False

  @nn.compact
  def __call__(self, x):
    init = (nn.initializers.zeros if self.zero_centered
            else nn.initializers.ones)
    scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
    if self.zero_centered:
      scale = 1.0 + scale
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
    return (y * scale).astype(self.dtype)


def rotary_frequencies(width: int, theta: float, scaling=None):
  """(width / 2,) float32: theta^(-2i/width); under YaRN (`scaling`, the
  published `rope_scaling` items) blended with the same over `factor`:
  pairs that turn more than `beta_fast` times over the original context
  stay plain, those that turn less than `beta_slow` times are slowed by
  `factor`, a linear ramp over the pairs between."""
  plain = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
  if scaling is None:
    return plain
  s = dict(scaling)
  pair_of = lambda turns: (
      width * math.log(s["original_max_position_embeddings"]
                       / (2 * math.pi * turns)) / (2 * math.log(theta)))
  low = max(math.floor(pair_of(s["beta_fast"])), 0)
  high = min(math.ceil(pair_of(s["beta_slow"])), width - 1)
  if low == high:
    high += 0.001
  ramp = jnp.clip((jnp.arange(width // 2, dtype=jnp.float32) - low)
                  / (high - low), 0.0, 1.0)
  return plain * (1.0 - ramp) + plain / s["factor"] * ramp


def yarn_softmax_mscale(scaling) -> float:
  """m of YaRN's softmax scale m² / sqrt(head width): 0.1 ·
  mscale_all_dim · ln(factor) + 1; 1 without scaling. cos and sin would
  be scaled by the ratio of that at `mscale` and at `mscale_all_dim`;
  only a ratio of 1 is built."""
  if scaling is None:
    return 1.0
  s = dict(scaling)
  if s["factor"] <= 1:
    return 1.0
  at = lambda mscale: 0.1 * mscale * math.log(s["factor"]) + 1.0
  if at(s["mscale"]) != at(s["mscale_all_dim"]):
    raise NotImplementedError(
        "rope_scaling with mscale != mscale_all_dim scales cos and sin")
  return at(s["mscale_all_dim"])


def rotary(x, theta: float, scaling=None):
  """Rotary positions over the last axis of (B, T, ..., R), pairs
  interleaved: (x[2i], x[2i+1]) turns by t · theta^(-2i/R), or by t ·
  YaRN's blended frequency under `scaling`. Float32."""
  t, r = x.shape[1], x.shape[-1]
  inv_freq = rotary_frequencies(r, theta, scaling)
  angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq  # (T, R/2)
  angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
  cos, sin = jnp.cos(angle), jnp.sin(angle)
  pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
  even, odd = pairs[..., 0], pairs[..., 1]
  turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1)
  return turned.reshape(x.shape).astype(x.dtype)


def rotary_half_split(x, theta: float, width: int):
  """Rotary positions over the first `width` dims of (B, T, H, D), pairs
  half-split: (x[i], x[i + width/2]) turns by t · theta^(-2i/width); the
  rest pass. Float32."""
  t, half = x.shape[1], width // 2
  inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
  angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
  cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
  x32 = x.astype(jnp.float32)
  first, second = x32[..., :half], x32[..., half:width]
  return jnp.concatenate(
      [first * cos - second * sin, second * cos + first * sin,
       x32[..., width:]], axis=-1).astype(x.dtype)


def _dense(features: int, dtype, name: str) -> nn.Dense:
  return nn.Dense(features, use_bias=False, dtype=dtype,
                  param_dtype=jnp.float32, name=name)


class MLAttention(nn.Module):
  """Multi-head latent attention, training form (no cache): queries
  and keys/values through low-rank latents with a norm each, one rotary
  key head shared by all heads, q/k heads `nope + rope` wide and v heads
  `v_head_dim` wide, causal; under `rope_scaling` YaRN's frequencies and
  softmax scale. `num_attention_heads` are the heads held here."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    c = self.config
    b, t, _ = x.shape
    heads, nope, rope, vdim = (c.num_attention_heads, c.qk_nope_head_dim,
                               c.qk_rope_head_dim, c.v_head_dim)
    norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
    with jax.named_scope("mla"):
      c_q = norm("q_a_norm")(_dense(c.q_lora_rank, self.dtype, "q_a")(x))
      q = _dense(heads * (nope + rope), self.dtype, "q_b")(c_q)
      q = q.reshape(b, t, heads, nope + rope)
      kv = _dense(c.kv_lora_rank + rope, self.dtype, "kv_a")(x)
      c_kv = norm("kv_a_norm")(kv[..., :c.kv_lora_rank])
      k_rope = rotary(kv[..., None, c.kv_lora_rank:], c.rope_theta,
                      c.rope_scaling)
      kv = _dense(heads * (nope + vdim), self.dtype, "kv_b")(c_kv)
      kv = kv.reshape(b, t, heads, nope + vdim)
      q = jnp.concatenate(
          [q[..., :nope],
           rotary(q[..., nope:], c.rope_theta, c.rope_scaling)], axis=-1)
      k = jnp.concatenate(
          [kv[..., :nope],
           jnp.broadcast_to(k_rope, (b, t, heads, rope))], axis=-1)
      out = flash_attention(
          q, k, kv[..., nope:], causal=True,
          scale=(yarn_softmax_mscale(c.rope_scaling) ** 2
                 / math.sqrt(nope + rope)))
      return _dense(c.hidden_size, self.dtype, "o")(
          out.reshape(b, t, heads * vdim))


class GatedAttention(nn.Module):
  """Gated grouped-query attention, training form: `num_attention_heads`
  query heads on `num_key_value_heads` key/value heads of `head_dim`, q
  and k normed per head (one weight for all heads), rotary
  over the head's first `partial_rotary_factor`, causal; each head's
  output times sigmoid(gate_h), the gate the other half of q's
  projection."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    c = self.config
    b, t, _ = x.shape
    heads, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    # Float32 out of the norm and through the turn: one rounding, after.
    norm = lambda name: RMSNorm(c.rms_norm_eps, jnp.float32,
                                c.zero_centered_norm, name=name)
    turn = lambda a: rotary_half_split(
        a, c.rope_theta, int(c.partial_rotary_factor * hd)).astype(self.dtype)
    with jax.named_scope("gqa"):
      q_gate = _dense(heads * 2 * hd, self.dtype, "q_proj")(x).reshape(
          b, t, heads, 2 * hd)
      q, gate = q_gate[..., :hd], q_gate[..., hd:]
      k = _dense(kv * hd, self.dtype, "k_proj")(x).reshape(b, t, kv, hd)
      v = _dense(kv * hd, self.dtype, "v_proj")(x).reshape(b, t, kv, hd)
      # On a TPU the kernel or an error, never the (H, T, T) scores.
      on_tpu = (jax.default_backend() == "tpu"
                and not dispatch.use_xla_only())
      out = flash_attention(
          turn(norm("q_norm")(q)), turn(norm("k_norm")(k)), v, causal=True,
          scale=1.0 / math.sqrt(hd),
          implementation="pallas" if on_tpu else "auto")
      out = (out.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
      return _dense(c.hidden_size, self.dtype, "o_proj")(
          out.reshape(b, t, heads * hd))


def causal_conv(x, kernel):
  """Depthwise convolution over time of (B, T, C) with (taps, C): y_t =
  Σ_j kernel[j] · x[t − (taps − 1) + j]; nothing ahead of t is read, and
  before the start there are zeros. Float32 sums."""
  taps, t = kernel.shape[0], x.shape[1]
  padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
  return sum(kernel[j] * padded[:, j:j + t].astype(jnp.float32)
             for j in range(taps))


def _l2_normalized(x, eps: float = 1e-6):
  x = x.astype(jnp.float32)
  return x * jax.lax.rsqrt(
      jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


class GatedDeltaNet(nn.Module):
  """The gated delta net, training form: q, k (`linear_num_key_heads`
  of `linear_key_head_dim`), v, z (`linear_num_value_heads` of
  `linear_value_head_dim`) and the per-head b, a from two projections;
  [q; k; v] through a causal depthwise convolution and silu; β =
  sigmoid(b), g = −exp(A_log) ⊙ softplus(a + dt_bias); q, k L2-normed,
  q over √Dk; the gated delta rule; RMSNorm(o) ⊙ silu(z); the output
  projection. `in_proj_qkvz`'s columns are [q | k | v | z], `in_proj_ba`'s
  [b | a]. Returns (y, {"gdn/decay_mean": mean exp(g), "gdn/beta_mean"})."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    c = self.config
    b, t, _ = x.shape
    kh, dk = c.linear_num_key_heads, c.linear_key_head_dim
    vh, dv = c.linear_num_value_heads, c.linear_value_head_dim
    key, value = kh * dk, vh * dv
    with jax.named_scope("gdn/proj"):
      qkvz = _dense(2 * key + 2 * value, self.dtype, "in_proj_qkvz")(x)
      ba = _dense(2 * vh, jnp.float32, "in_proj_ba")(x)
    with jax.named_scope("gdn/conv"):
      taps = c.linear_conv_kernel_dim
      kernel = self.param(
          "conv_kernel", nn.initializers.variance_scaling(
              1.0, "fan_in", "normal", in_axis=0, out_axis=1),
          (taps, 2 * key + value), jnp.float32)
      mixed = nn.silu(causal_conv(qkvz[..., :2 * key + value], kernel))
    with jax.named_scope("gdn/rule"):
      a_log = self.param(
          "A_log", lambda rng, shape: jnp.log(jax.random.uniform(
              rng, shape, jnp.float32, 1e-3, 16.0)), (vh,))
      dt_bias = self.param("dt_bias", nn.initializers.zeros, (vh,),
                           jnp.float32)
      beta = jax.nn.sigmoid(ba[..., :vh])
      g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., vh:] + dt_bias)
      q = (_l2_normalized(mixed[..., :key].reshape(b, t, kh, dk))
           / math.sqrt(dk)).astype(self.dtype)
      k = _l2_normalized(
          mixed[..., key:2 * key].reshape(b, t, kh, dk)).astype(self.dtype)
      v = mixed[..., 2 * key:].reshape(b, t, vh, dv).astype(self.dtype)
      out = gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope("gdn/out"):
      z = qkvz[..., 2 * key + value:].reshape(b, t, vh, dv)
      out = RMSNorm(c.rms_norm_eps, self.dtype, name="norm")(out)
      out = (out.astype(jnp.float32)
             * nn.silu(z.astype(jnp.float32))).astype(self.dtype)
      y = _dense(c.hidden_size, self.dtype, "out_proj")(
          out.reshape(b, t, value))
    return y, {"gdn/decay_mean": jnp.mean(jnp.exp(g)),
               "gdn/beta_mean": jnp.mean(beta)}


class GatedMLP(nn.Module):
  """down(silu(gate x) ⊙ up x), no biases."""
  width: int
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    hidden = (nn.silu(_dense(self.width, self.dtype, "gate")(x))
              * _dense(self.width, self.dtype, "up")(x))
    return _dense(x.shape[-1], self.dtype, "down")(hidden)


class ExpertLayer(nn.Module):
  """This holder's share of the routed experts plus the shared expert:
  Σ_{i ∈ top-k ∩ held} w_i E_i(x) + Shared(x). Returns (y, counters)."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    c = self.config
    b, t, d = x.shape
    held, width = c.experts_held, c.moe_intermediate_size
    fan_in = nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
    params = expert_parallel.MoEParams(
        router=self.param("router", nn.initializers.lecun_normal(),
                          (d, c.n_routed_experts), jnp.float32),
        bias=(self.param("correction_bias", nn.initializers.zeros,
                         (c.n_routed_experts,), jnp.float32)
              if c.scoring_func == "sigmoid" else None),
        gate=self.param("experts_gate", fan_in, (held, d, width),
                        jnp.float32),
        up=self.param("experts_up", fan_in, (held, d, width), jnp.float32),
        down=self.param("experts_down", fan_in, (held, width, d),
                        jnp.float32))
    y, counters = expert_parallel.moe_share(
        x.reshape(b * t, d), params, first_expert=c.first_expert,
        top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
        compute_dtype=self.dtype, scoring=c.scoring_func)
    y = y.reshape(b, t, d)
    if c.shared_expert_intermediate_size:
      with jax.named_scope("moe/shared"):
        shared = GatedMLP(c.shared_expert_intermediate_size, self.dtype,
                          name="shared")(x)
      with jax.named_scope("moe/shared_gate"):
        gate = jax.nn.sigmoid(
            _dense(1, jnp.float32, "shared_gate")(x))
        y = y + (shared.astype(jnp.float32) * gate).astype(self.dtype)
    elif c.n_shared_experts:
      with jax.named_scope("moe/shared"):
        y = y + GatedMLP(c.n_shared_experts * width, self.dtype,
                         name="shared")(x)
    return y, counters


class HyperConnection(nn.Module):
  """One sublayer's read, write and mixing of the `hc_mult`-stream
  residual (mHC): `pre` reads the streams, side by side in (B, T, n·D),
  into the sublayer's input u through H_pre and makes H_post and H_res,
  the latter doubly stochastic by Sinkhorn; `post` writes the sublayer's
  output back through H_post beside the streams mixed by H_res. `phi`
  (nD, n² + 2n), `alpha` (3,) and `base` (n² + 2n,) are float32, columns
  [pre | post | res]. Fresh, the maps are near a plain residual on every
  stream: H_res about I, H_post 1, H_pre 1/2."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  def setup(self):
    c = self.config
    n = c.hc_mult
    maps = n * n + 2 * n

    def base_init(rng, shape, dtype):
      del rng
      off_diagonal = -8.0 * (1.0 - jnp.eye(n, dtype=dtype))
      return jnp.concatenate(
          [jnp.zeros((2 * n,), dtype), off_diagonal.reshape(-1)]
      ).reshape(shape)

    self.phi = self.param("phi", nn.initializers.lecun_normal(),
                          (n * c.hidden_size, maps), jnp.float32)
    self.alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                            jnp.float32)
    self.base = self.param("base", base_init, (maps,), jnp.float32)

  def pre(self, x):
    """(B, T, n·D) -> (u (B, T, D), (H_post, H_res), the step's
    counters (4,): mean diagonal of H_res, mean H_pre, mean H_post, the
    largest |row sum − 1| Sinkhorn left)."""
    c = self.config
    with jax.named_scope("mhc"), jax.named_scope("pre"):
      u, h_pre, h_post, h_res = hyper_connection_pre(
          x, self.phi, self.alpha, self.base, MapConfig(
              c.hc_sinkhorn_iters, c.hc_eps, c.mhc_h_res_clamp_min,
              c.mhc_h_res_clamp_max, c.rms_norm_eps))
      n = c.hc_mult
      counters = jax.lax.stop_gradient(jnp.stack([
          jnp.mean(h_res * jnp.eye(n, dtype=h_res.dtype)) * n,
          jnp.mean(h_pre), jnp.mean(h_post),
          jnp.max(jnp.abs(jnp.sum(h_res, axis=-1) - 1.0))]))
    return u, (h_post, h_res), counters

  def post(self, x, y, maps):
    with jax.named_scope("mhc"), jax.named_scope("post"):
      return hyper_connection_post(x, y.astype(self.dtype), *maps)


MHC_COUNTERS = ("mhc/res_diag_mean", "mhc/pre_mean", "mhc/post_mean",
                "mhc/sinkhorn_gap")


class DecoderBlock(nn.Module):
  """h = x + Mixer(norm(x)); y = h + FFN(norm(h)); the mixer by the
  layer's `kind` ("mla", "full": gated attention, "linear": the gated
  delta net), FFN the dense gated MLP or the expert layer. With
  `hc_mult` streams x is (B, T, n·D) and each of the two sublayers
  reads, writes and mixes it through its own `HyperConnection`. Returns
  (y, the expert layer's counters, with a delta net's `gdn/*` and the
  hyper-connections' `mhc/*` (each (2,), by sublayer) beside them; None
  where the block has none)."""
  config: SequenceConfig
  experts: bool
  dtype: Any = jnp.bfloat16
  kind: str = "mla"

  @nn.compact
  def __call__(self, x, _=None):
    c = self.config
    norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype,
                                c.zero_centered_norm, name=name)
    extra, maps = {}, []

    def sublayer(x, name, inner):
      """`inner` under the block's residual rule."""
      if not c.hc_mult:
        return x + inner(norm(name + "_norm")(x))
      hc = HyperConnection(c, self.dtype, name=name + "_hc")
      u, h, counters = hc.pre(x)
      maps.append(counters)
      return hc.post(x, inner(norm(name + "_norm")(u)), h)

    def mixer(inner):
      if self.kind == "linear":
        mixed, gates = GatedDeltaNet(c, self.dtype, name="attn")(inner)
        extra.update(gates)
        return mixed
      if self.kind == "full":
        return GatedAttention(c, self.dtype, name="attn")(inner)
      return MLAttention(c, self.dtype, name="attn")(inner)

    def feed_forward(inner):
      if not self.experts:
        return GatedMLP(c.intermediate_size, self.dtype, name="mlp")(inner)
      y, counters = ExpertLayer(c, self.dtype, name="moe")(inner)
      extra.update(counters)
      return y

    x = sublayer(sublayer(x, "attn", mixer), "ffn", feed_forward)
    if maps:
      extra.update(zip(MHC_COUNTERS, jnp.stack(maps).T))
    return x, extra or None


_LOSS_CHUNKS = 8


def token_losses(hidden, head_kernel, targets):
  """Cross-entropy of every position against `targets`, (B, T) float32,
  the logits made and dropped an eighth of the positions at a time
  (whole, they are T × V float32 for each head)."""
  b, t, d = hidden.shape
  kernel = head_kernel.astype(hidden.dtype)

  @jax.checkpoint
  def one(args):
    rows, wanted = args
    logits = jnp.dot(rows, kernel, preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, wanted[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked

  with jax.named_scope("lm_head"):
    losses = jax.lax.map(one, (hidden.reshape(_LOSS_CHUNKS, -1, d),
                               targets.reshape(_LOSS_CHUNKS, -1)))
  return losses.reshape(b, t)

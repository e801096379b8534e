"""Decoder blocks of a sequence model: RMSNorm (plain or zero-centred),
rotary positions (interleaved over the whole width, plain or under
YaRN, or half-split over the head's first part), six token mixers
(multi-head latent attention, grouped-query attention with or without
its output gate, the gated delta net, block-sparse attention, Lightning
attention, the gated short convolution; training forms), gated MLP, the
expert layer (routing and grouping in `parallel/expert_parallel`), the
residual rule (plain, scaled, or manifold-constrained hyper-connections
over `hc_mult` streams) and a chunked next-token loss.

Layer equations: DeepSeek-V2/V3's, which the JoyAI-LLM-Flash and
Xing4.0 configs follow, Qwen3-Next's (gated attention and Gated Delta
Networks, arXiv:2412.06464), mHC's (arXiv:2512.24880) and
MiniCPM-SALA's (InfLLM-v2's sparse attention, arXiv:2506.07900, beside
Lightning attention, arXiv:2401.04658, under muP scalings) and LFM2's
(gated short convolutions beside plain grouped-query attention); see
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
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.ops import dispatch
from tensor2robot_tpu.ops.flash_attention import flash_attention
from tensor2robot_tpu.ops.gated_delta_rule import gated_delta_rule
from tensor2robot_tpu.ops.hyper_connection import (
    MapConfig, hyper_connection_post, hyper_connection_pre)
from tensor2robot_tpu.ops.lightning_attention import (
    lightning_attention, log_decays)
from tensor2robot_tpu.ops.short_conv import causal_conv, gated_short_conv
from tensor2robot_tpu.ops.sparse_attention import (
    block_sparse_attention, select_blocks)
from tensor2robot_tpu.parallel import expert_parallel


@dataclasses.dataclass(frozen=True)
class SequenceConfig:
  """Sizes under their names in the published `config.json`; the chip's
  share (`experts_held`, `first_expert`, `first_head`, the vocabulary
  slice) beside them."""
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
  # Set: each layer's mixer by name, "minicpm4" (block-sparse attention)
  # or "lightning-attn" (Lightning attention), the first
  # `num_hidden_layers` of them held; every FFN then the dense gated MLP
  # (MiniCPM-SALA). None: the layouts above.
  mixer_types: Optional[Any] = None
  # The sparse layer: q and k normed per head (one weight for all heads),
  # rotary or not, the output times sigmoid(x . W_g).
  qk_norm: bool = False
  attn_use_rope: bool = True
  attn_use_output_gate: bool = False
  # InfLLM-v2's selection, as MiniCPM4's published `sparse_config`
  # (block_size, kernel_size, kernel_stride, init_blocks, window_size,
  # topk, dense_len); given as a dict, kept as its sorted items.
  sparse_config: Optional[Any] = None
  # The Lightning layer: `lightning_nh` heads of `lightning_head_dim`
  # held (q, k, v alike), q and k normed per head, rotary over the whole
  # head (half-split) where `lightning_use_rope`, q over sqrt(width); the
  # output normed per head (`use_output_norm`) and times sigmoid(x . W_g)
  # (`use_output_gate`).
  lightning_nh: int = 0
  lightning_nkv: int = 0
  lightning_head_dim: int = 0
  lightning_use_rope: bool = False
  use_output_gate: bool = False
  use_output_norm: bool = False
  # muP: the embedding times `scale_emb`, each residual branch times
  # scale_depth / sqrt(the published layer count) (0: unscaled), the
  # final hidden over hidden_size / dim_model_base (0: not divided).
  scale_emb: float = 1.0
  scale_depth: float = 0.0
  dim_model_base: int = 0
  # The share of a tensor-parallel deployment: the first query head (and
  # Lightning head) held; the published head and layer counts the
  # decays and the residual scale read (0: the held ones).
  first_head: int = 0
  published_lightning_nh: int = 0
  published_num_hidden_layers: int = 0
  # Set: each published layer's mixer by name, "conv" (the gated short
  # convolution over `conv_L_cache` taps) or "full_attention" (grouped-
  # query attention without an output gate), the `num_hidden_layers`
  # from `first_layer` held; a layer before `first_k_dense_replace`
  # (the published `num_dense_layers`) has the dense gated MLP, the
  # others the expert layer (LFM2). None: the layouts above.
  layer_types: Optional[Any] = None
  first_layer: int = 0
  conv_L_cache: int = 0
  conv_bias: bool = False
  # The head is the embedding's transpose: no `head` parameter.
  tie_word_embeddings: bool = False
  # Added to the sum of the chosen sigmoid scores before they divide it.
  router_norm_eps: float = 1e-20

  def __post_init__(self):
    for name in ("rope_scaling", "sparse_config"):
      if isinstance(getattr(self, name), dict):
        object.__setattr__(self, name,
                           tuple(sorted(getattr(self, name).items())))
    for name in ("mixer_types", "layer_types"):
      if isinstance(getattr(self, name), list):
        object.__setattr__(self, name, tuple(getattr(self, name)))
    if self.conv_bias:
      raise NotImplementedError("conv_bias: the short convolution and its "
                                "projections are built without biases")

  @property
  def num_expert_layers(self) -> int:
    return self.num_hidden_layers - self.first_k_dense_replace

  @property
  def hybrid(self) -> bool:
    return self.full_attention_interval > 0

  @property
  def residual_scale(self) -> float:
    """scale_depth / sqrt(the published layer count), or 0: unscaled."""
    if not self.scale_depth:
      return 0.0
    layers = self.published_num_hidden_layers or self.num_hidden_layers
    return self.scale_depth / math.sqrt(layers)

  def layer_kind(self, layer: int) -> str:
    """"mla", in the hybrid layout "full" | "linear", under `mixer_types`
    "sparse" | "lightning", under `layer_types` "conv" | "attention" (of
    the published layer `layer`)."""
    if self.layer_types:
      return {"conv": "conv",
              "full_attention": "attention"}[self.layer_types[layer]]
    if self.mixer_types:
      return {"minicpm4": "sparse",
              "lightning-attn": "lightning"}[self.mixer_types[layer]]
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
  """Grouped-query attention, training form: `num_attention_heads`
  query heads on `num_key_value_heads` key/value heads of `head_dim`, q
  and k normed per head (one weight for all heads; zero-centred where
  the config's norms are), rotary over the head's first
  `partial_rotary_factor`, causal; with `output_gate` each head's output
  times sigmoid(gate_h), the gate the other half of q's projection
  (Qwen3-Next), without it q's projection is q alone (LFM2)."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16
  output_gate: bool = True

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
      width = 2 * hd if self.output_gate else hd
      q_gate = _dense(heads * width, self.dtype, "q_proj")(x).reshape(
          b, t, heads, width)
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
      if self.output_gate:
        out = (out.astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
      return _dense(c.hidden_size, self.dtype, "o_proj")(
          out.reshape(b, t, heads * hd))


# The scope the short convolution's work runs under, its parts as
# `in_proj`, `op` and `out_proj` inside it: the benchmark times it by
# this name.
SHORT_CONV_SCOPE = "short_conv"


class ShortConv(nn.Module):
  """LFM2's gated short convolution, training form: [B | C | x~] = x
  W_in (`in_proj`, 3 · hidden wide), then `ops/short_conv`'s C ⊙ conv(B
  ⊙ x~) over `conv_L_cache` taps (`conv`, (taps, hidden), depthwise and
  causal), then W_out (`out_proj`); under the scopes `short_conv/in_proj`,
  `short_conv/op` and `short_conv/out_proj`. Returns (y, {"conv/u_rms",
  "conv/y_rms"}: the root mean square of B ⊙ x~ and of C ⊙ z over tokens
  and channels)."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    c = self.config
    d = c.hidden_size
    with jax.named_scope(SHORT_CONV_SCOPE):
      with jax.named_scope("in_proj"):
        bcx = _dense(3 * d, self.dtype, "in_proj")(x)
      taps = self.param("conv", nn.initializers.variance_scaling(
          1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                        (c.conv_L_cache, d), jnp.float32)
      with jax.named_scope("op"):
        y, u_square = gated_short_conv(bcx, taps)
      counters = jax.lax.stop_gradient({
          "conv/u_rms": jnp.sqrt(u_square),
          "conv/y_rms": jnp.sqrt(jnp.mean(jnp.square(
              y.astype(jnp.float32))))})
      with jax.named_scope("out_proj"):
        return _dense(d, self.dtype, "out_proj")(y), counters


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


def _head_norm(x, eps: float):
  """x · rsqrt(mean(x²) + eps) over the last axis, float32."""
  x = x.astype(jnp.float32)
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                           + eps)


class SparseAttention(nn.Module):
  """Block-sparse grouped-query attention (MiniCPM4's InfLLM-v2, as
  MiniCPM-SALA's "minicpm4" layers run it), training form:
  `num_attention_heads` query heads on `num_key_value_heads` key/value
  heads of `head_dim`, q and k normed per head (`qk_norm`, one weight for
  all heads), rotary over the whole head (half-split) only where
  `attn_use_rope`; each group selects its key blocks (`select_blocks`,
  under `sala/select`, no gradient) and its queries attend to those
  alone (`block_sparse_attention`, under `sala/attend`), scale
  1/sqrt(head_dim); the output times sigmoid(x · W_g) where
  `attn_use_output_gate`. Returns (y, {"sala/selected_mass",
  "sala/visited_blocks", "sala/selected_blocks", "sala/visit_slots"}:
  the share of the selection scores on the selected blocks, the (token,
  block) pairs the kernels visited and the tokens selected, and the
  slots the kernels' loops stepped through, padding included)."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    c = self.config
    b, t, _ = x.shape
    heads, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    sizes = dict(c.sparse_config)
    norm = lambda name: RMSNorm(c.rms_norm_eps, jnp.float32, name=name)
    turn = lambda a: (rotary_half_split(a, c.rope_theta, hd)
                      if c.attn_use_rope else a).astype(self.dtype)
    with jax.named_scope("sala/proj"):
      q = _dense(heads * hd, self.dtype, "q_proj")(x).reshape(b, t, heads, hd)
      k = _dense(kv * hd, self.dtype, "k_proj")(x).reshape(b, t, kv, hd)
      v = _dense(kv * hd, self.dtype, "v_proj")(x).reshape(b, t, kv, hd)
      if c.qk_norm:
        q, k = norm("q_norm")(q), norm("k_norm")(k)
      q, k = turn(q), turn(k)
    with jax.named_scope("sala/select"):
      selected, mass = select_blocks(
          q, k, block=sizes["block_size"], kernel=sizes["kernel_size"],
          stride=sizes["kernel_stride"], top_k=sizes["topk"],
          init_blocks=sizes["init_blocks"], window=sizes["window_size"],
          dense_len=sizes["dense_len"])
    with jax.named_scope("sala/attend"):
      out, visits = block_sparse_attention(
          q, k, v, selected, block=sizes["block_size"],
          scale=1.0 / math.sqrt(hd))
    with jax.named_scope("sala/out"):
      if c.attn_use_output_gate:
        gate = _dense(heads * hd, self.dtype, "o_gate")(x)
        out = (out.reshape(b, t, heads * hd).astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
      y = _dense(c.hidden_size, self.dtype, "o_proj")(
          out.reshape(b, t, heads * hd))
    return y, {"sala/selected_mass": mass,
               "sala/visited_blocks": visits["visited"],
               "sala/selected_blocks": visits["selected"],
               "sala/visit_slots": visits["slots"]}


class LightningAttention(nn.Module):
  """Lightning attention (MiniCPM-SALA's "lightning-attn" layers),
  training form: `lightning_nh` heads of `lightning_head_dim` for q, k
  and v, q and k normed per head (`qk_norm`, one weight for all heads),
  rotary over the whole head (half-split) where `lightning_use_rope`, q
  over sqrt(width); the decayed linear recurrence with each head's fixed
  decay (`ops/lightning_attention`, heads `first_head` on of the
  published count, at this block's `layer` of the published layers),
  under `lightning/scan`; the output normed per head with a weight per
  channel (`use_output_norm`) and times sigmoid(x · W_g)
  (`use_output_gate`). Returns (y, {"lightning/decay_mean": the mean of
  the held heads' decays})."""
  config: SequenceConfig
  dtype: Any = jnp.bfloat16
  layer: int = 0

  @nn.compact
  def __call__(self, x):
    c = self.config
    b, t, _ = x.shape
    heads, hd = c.lightning_nh, c.lightning_head_dim
    norm = lambda name: RMSNorm(c.rms_norm_eps, jnp.float32, name=name)
    turn = lambda a: (rotary_half_split(a, c.rope_theta, hd)
                      if c.lightning_use_rope else a)
    with jax.named_scope("lightning/proj"):
      q, k, v = (_dense(heads * hd, self.dtype, name)(x).reshape(
          b, t, heads, hd) for name in ("q_proj", "k_proj", "v_proj"))
      if c.qk_norm:
        q, k = norm("q_norm")(q), norm("k_norm")(k)
      q = (turn(q).astype(jnp.float32) / math.sqrt(hd)).astype(self.dtype)
      k = turn(k).astype(self.dtype)
    with jax.named_scope("lightning/scan"):
      log_decay = log_decays(
          c.published_lightning_nh or heads, c.first_head, heads, self.layer,
          c.published_num_hidden_layers or c.num_hidden_layers)
      out = lightning_attention(q, k, v, log_decay)
    with jax.named_scope("lightning/out"):
      out = out.reshape(b, t, heads, hd)
      if c.use_output_norm:
        scale = self.param("o_norm", nn.initializers.ones, (heads * hd,),
                           jnp.float32)
        out = _head_norm(out, c.rms_norm_eps) * scale.reshape(heads, hd)
      out = out.reshape(b, t, heads * hd).astype(jnp.float32)
      if c.use_output_gate:
        out = out * jax.nn.sigmoid(
            _dense(heads * hd, self.dtype, "o_gate")(x).astype(jnp.float32))
      y = _dense(c.hidden_size, self.dtype, "o_proj")(out.astype(self.dtype))
    return y, {"lightning/decay_mean": jnp.mean(jnp.exp(log_decay))}


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
        compute_dtype=self.dtype, scoring=c.scoring_func,
        norm_eps=c.router_norm_eps)
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
  layer's `kind` ("mla", "full": gated attention, "attention": grouped-
  query attention without the gate, "linear": the gated delta net,
  "sparse": block-sparse attention, "lightning": Lightning attention at
  the published `layer`, "conv": the gated short convolution), FFN the
  dense gated MLP or the expert layer; under `scale_depth` each branch
  is scaled by the config's `residual_scale`. With `hc_mult` streams x
  is (B, T, n·D) and each of the two sublayers reads, writes and mixes
  it through its own `HyperConnection`. Returns (y, the expert layer's
  counters, with a delta net's `gdn/*`, a sparse layer's `sala/*`, a
  Lightning layer's `lightning/*`, a short convolution's `conv/*` and
  the hyper-connections' `mhc/*` (each (2,), by sublayer) beside them;
  None where the block has none)."""
  config: SequenceConfig
  experts: bool
  dtype: Any = jnp.bfloat16
  kind: str = "mla"
  layer: int = 0

  @nn.compact
  def __call__(self, x, _=None):
    c = self.config
    norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype,
                                c.zero_centered_norm, name=name)
    extra, maps = {}, []

    def sublayer(x, name, inner):
      """`inner` under the block's residual rule."""
      if c.residual_scale:
        branch = inner(norm(name + "_norm")(x)).astype(jnp.float32)
        return x + (branch * c.residual_scale).astype(x.dtype)
      if not c.hc_mult:
        return x + inner(norm(name + "_norm")(x))
      hc = HyperConnection(c, self.dtype, name=name + "_hc")
      u, h, counters = hc.pre(x)
      maps.append(counters)
      return hc.post(x, inner(norm(name + "_norm")(u)), h)

    def mixer(inner):
      if self.kind == "sparse":
        mixed, visits = SparseAttention(c, self.dtype, name="attn")(inner)
        extra.update(visits)
        return mixed
      if self.kind == "lightning":
        mixed, decays = LightningAttention(c, self.dtype, self.layer,
                                           name="attn")(inner)
        extra.update(decays)
        return mixed
      if self.kind == "linear":
        mixed, gates = GatedDeltaNet(c, self.dtype, name="attn")(inner)
        extra.update(gates)
        return mixed
      if self.kind == "conv":
        mixed, rms = ShortConv(c, self.dtype, name="attn")(inner)
        extra.update(rms)
        return mixed
      if self.kind in ("full", "attention"):
        return GatedAttention(c, self.dtype, self.kind == "full",
                              name="attn")(inner)
      return MLAttention(c, self.dtype, name="attn")(inner)

    def feed_forward(inner):
      if not self.experts:
        return GatedMLP(c.intermediate_size, self.dtype, name="mlp")(inner)
      y, counters = ExpertLayer(c, self.dtype, name="moe")(inner)
      extra.update(counters)
      if c.hc_mult:
        # The stream write's backward reads y: kept, so that the block's
        # recomputation walks none of the layer's held rows forward again.
        y = checkpoint_name(y, KEPT)
      return y

    x = sublayer(sublayer(x, "attn", mixer), "ffn", feed_forward)
    if maps:
      extra.update(zip(MHC_COUNTERS, jnp.stack(maps).T))
    return x, extra or None


# What a block names so its recomputation keeps (`recomputed_block`).
KEPT = "decoder_block_kept"


def recomputed_block(**kwargs):
  """`DecoderBlock` recomputed on the backward pass, all but what it
  names `KEPT`."""
  return nn.remat(
      DecoderBlock,
      policy=jax.checkpoint_policies.save_only_these_names(KEPT), **kwargs)


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

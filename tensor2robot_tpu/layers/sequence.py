"""Decoder blocks of a sparse-expert sequence model: RMSNorm, rotary
positions, multi-head latent attention (MLA, training form), gated MLP,
the expert layer (routing and grouping in `parallel/expert_parallel`),
a depth-1 multi-token-prediction module and a chunked next-token loss.

Layer equations (DeepSeek-V2/V3's, which the JoyAI-LLM-Flash config
follows): see `SequenceConfig`'s fields and each module. Activations run
in `dtype` (bfloat16), parameters are float32, norms, rotary angles, the
router and the softmax statistics are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops.flash_attention import flash_attention
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

  @property
  def num_expert_layers(self) -> int:
    return self.num_hidden_layers - self.first_k_dense_replace


class RMSNorm(nn.Module):
  eps: float = 1e-6
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x):
    scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
    return (y * scale).astype(self.dtype)


def rotary(x, theta: float):
  """Rotary positions over the last axis of (B, T, ..., R), pairs
  interleaved: (x[2i], x[2i+1]) turns by t · theta^(-2i/R). Float32."""
  t, r = x.shape[1], x.shape[-1]
  inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
  angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq  # (T, R/2)
  angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
  cos, sin = jnp.cos(angle), jnp.sin(angle)
  pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
  even, odd = pairs[..., 0], pairs[..., 1]
  turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1)
  return turned.reshape(x.shape).astype(x.dtype)


def _dense(features: int, dtype, name: str) -> nn.Dense:
  return nn.Dense(features, use_bias=False, dtype=dtype,
                  param_dtype=jnp.float32, name=name)


class MLAttention(nn.Module):
  """Multi-head latent attention, training form (no cache): queries
  and keys/values through low-rank latents with a norm each, one rotary
  key head shared by all heads, q/k heads `nope + rope` wide and v heads
  `v_head_dim` wide, causal."""
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
      k_rope = rotary(kv[..., None, c.kv_lora_rank:], c.rope_theta)
      kv = _dense(heads * (nope + vdim), self.dtype, "kv_b")(c_kv)
      kv = kv.reshape(b, t, heads, nope + vdim)
      q = jnp.concatenate(
          [q[..., :nope], rotary(q[..., nope:], c.rope_theta)], axis=-1)
      k = jnp.concatenate(
          [kv[..., :nope],
           jnp.broadcast_to(k_rope, (b, t, heads, rope))], axis=-1)
      out = flash_attention(
          q, k, kv[..., nope:], causal=True,
          scale=1.0 / math.sqrt(nope + rope))
      return _dense(c.hidden_size, self.dtype, "o")(
          out.reshape(b, t, heads * vdim))


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
        bias=self.param("correction_bias", nn.initializers.zeros,
                        (c.n_routed_experts,), jnp.float32),
        gate=self.param("experts_gate", fan_in, (held, d, width),
                        jnp.float32),
        up=self.param("experts_up", fan_in, (held, d, width), jnp.float32),
        down=self.param("experts_down", fan_in, (held, width, d),
                        jnp.float32))
    y, counters = expert_parallel.moe_share(
        x.reshape(b * t, d), params, first_expert=c.first_expert,
        top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
        compute_dtype=self.dtype)
    y = y.reshape(b, t, d)
    if c.n_shared_experts:
      with jax.named_scope("moe/shared"):
        y = y + GatedMLP(c.n_shared_experts * width, self.dtype,
                         name="shared")(x)
    return y, counters


class DecoderBlock(nn.Module):
  """h = x + MLA(norm(x)); y = h + FFN(norm(h)); FFN the dense gated
  MLP or the expert layer. Returns (y, the expert layer's counters)."""
  config: SequenceConfig
  experts: bool
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x, _=None):
    c = self.config
    norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
    h = x + MLAttention(c, self.dtype, name="attn")(norm("attn_norm")(x))
    inner = norm("ffn_norm")(h)
    if self.experts:
      y, counters = ExpertLayer(c, self.dtype, name="moe")(inner)
    else:
      y, counters = GatedMLP(c.intermediate_size, self.dtype,
                             name="mlp")(inner), None
    return h + y, counters


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

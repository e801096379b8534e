"""SequenceMoEModel: a decoder-only sparse-expert language model on the
`AbstractT2RModel` contract, trained through `Trainer.train_steps`.

Two layouts, by the configuration's sizes under their published names:
JoyAI-LLM-Flash's (DeepSeek-V3: MLA attention, `first_k_dense_replace`
dense blocks, then equal expert blocks of routed top-k experts plus a
shared expert, a depth-1 multi-token prediction module), and, where
`full_attention_interval` is set, Qwen3-Next's hybrid (periods of that
many expert blocks, gated delta nets and then one gated grouped-query
attention block; no dense lead). Where `hc_mult` is set the residual is
that many streams wide (Xing4.0's manifold-constrained
hyper-connections): the embedding fans out to the streams, the blocks
carry them side by side, (B, T, n·D), and the final norm reads their
sum. The MTP module is built where `num_nextn_predict_layers` says so.
The model
is told its share of a deployment: which `experts_held` of the
`n_routed_experts` it computes (the router keeps its full width) and how
many rows of the vocabulary it holds; ids, logits and loss are over that
slice.

Features: `tokens`, int32 (B, T); no labels, no `batch_stats`. The
module computes in `compute_dtype` with float32 parameters, recomputes
each block on the backward pass and scans the equal expert blocks (or
the equal periods), so the program holds one of them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import modes
from tensor2robot_tpu.config import configurable
from tensor2robot_tpu.layers import sequence
from tensor2robot_tpu.models.abstract_model import AbstractT2RModel, Metrics
from tensor2robot_tpu.specs import tensorspec_utils as ts

_CONFIG_FIELDS = frozenset(
    f.name for f in dataclasses.fields(sequence.SequenceConfig))


def _fan_out(x, config):
  """(B, T, D) -> the residual a block takes: itself, or with `hc_mult`
  a copy for each stream side by side, (B, T, n·D)."""
  if not config.hc_mult:
    return x
  return jnp.tile(x, (1, 1, config.hc_mult))


def _collapse(x, config):
  """The blocks' residual -> (B, T, D): itself, or the streams' sum
  (float32 accumulation)."""
  if not config.hc_mult:
    return x
  d = x.shape[-1] // config.hc_mult
  return sum(x[..., j * d:(j + 1) * d].astype(jnp.float32)
             for j in range(config.hc_mult)).astype(x.dtype)


def _split_counters(counters, prefix):
  """({those named `prefix`...}, the rest) of a block's counters."""
  named = {k: v for k, v in counters.items() if k.startswith(prefix)}
  return named, {k: v for k, v in counters.items() if k not in named}


class _MTPModule(nn.Module):
  """Depth-1 multi-token prediction (DeepSeek-V3 §2.2): position i's
  hidden state and the embedding of token i+1, a norm each, joined by a
  projection, through one expert block (over streams of its own where
  the residual has them) and a final norm; the shared head then predicts
  token i+2."""
  config: sequence.SequenceConfig
  dtype: Any

  @nn.compact
  def __call__(self, next_embedded, hidden):
    c = self.config
    norm = lambda name: sequence.RMSNorm(c.rms_norm_eps, self.dtype,
                                         name=name)
    joined = jnp.concatenate(
        [norm("embed_norm")(next_embedded), norm("hidden_norm")(hidden)],
        axis=-1)
    h = nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                 param_dtype=jnp.float32, name="eh_proj")(joined)
    h, counters = nn.remat(sequence.DecoderBlock)(
        c, True, self.dtype, name="block")(_fan_out(h, c))
    return norm("final_norm")(_collapse(h, c)), counters


class _Period(nn.Module):
  """`full_attention_interval` expert blocks, each recomputed on the
  backward pass, their mixers by position in the period. Returns (x,
  {"moe": the blocks' counters stacked, "gdn": the delta nets'})."""
  config: sequence.SequenceConfig
  dtype: Any

  @nn.compact
  def __call__(self, x, _=None):
    c = self.config
    moe, gdn = [], []
    for i in range(c.full_attention_interval):
      # CSE prevented: a scan of one period is unrolled, and the blocks'
      # recomputation would then be merged with their first run.
      x, counters = nn.remat(sequence.DecoderBlock)(
          c, True, self.dtype, c.layer_kind(i), name=f"block{i}")(x)
      gates = {k: counters.pop(k) for k in list(counters)
               if k.startswith("gdn/")}
      moe.append(counters)
      if gates:
        gdn.append(gates)
    stack = lambda rows: jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *rows)
    return x, {"moe": stack(moe), "gdn": stack(gdn)}


class _SequenceModule(nn.Module):
  config: sequence.SequenceConfig
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, features, mode: str):
    c = self.config
    tokens = features["tokens"]
    embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                     param_dtype=jnp.float32, name="embed")
    head = self.param("head", nn.initializers.lecun_normal(),
                      (c.hidden_size, c.vocab_size), jnp.float32)
    x = _fan_out(embed(tokens), c)
    mhc = []  # each block's `mhc/*` counters, in layer order
    for i in range(c.first_k_dense_replace):
      x, counters = nn.remat(sequence.DecoderBlock)(
          c, False, self.dtype, name=f"dense_block{i}")(x)
      if c.hc_mult:
        mhc.append(jax.tree_util.tree_map(lambda a: a[None], counters))
    outputs = {}
    if c.hybrid:
      stack = nn.scan(
          _Period, variable_axes={"params": 0}, split_rngs={"params": True},
          length=c.num_hidden_layers // c.full_attention_interval)
      x, counters = stack(c, self.dtype, name="periods")(x, None)
      # (periods, blocks a period, ...) -> (layers, ...), in layer order.
      counters = jax.tree_util.tree_map(
          lambda a: a.reshape((-1,) + a.shape[2:]), counters)
      outputs["gdn_counters"], counters = counters["gdn"], counters["moe"]
    else:
      stack = nn.scan(
          nn.remat(sequence.DecoderBlock, prevent_cse=False),
          variable_axes={"params": 0}, split_rngs={"params": True},
          length=c.num_expert_layers)
      x, counters = stack(c, True, self.dtype, name="expert_blocks")(x, None)
    if c.hc_mult:
      maps, counters = _split_counters(counters, "mhc/")
      mhc.append(maps)
    x = _collapse(x, c)
    final = sequence.RMSNorm(c.rms_norm_eps, self.dtype,
                             c.zero_centered_norm, name="final_norm")
    if mode == modes.PREDICT:
      with jax.named_scope("lm_head"):
        return {"logits": jnp.dot(final(x), head.astype(self.dtype),
                                  preferred_element_type=jnp.float32)}
    outputs["token_loss_main"] = sequence.token_losses(
        final(x), head, jnp.roll(tokens, -1, axis=1))
    if c.num_nextn_predict_layers:
      with jax.named_scope("mtp"):
        h, extra = _MTPModule(c, self.dtype, name="mtp")(
            embed(jnp.roll(tokens, -1, axis=1)), x)
        outputs["token_loss_mtp"] = sequence.token_losses(
            h, head, jnp.roll(tokens, -2, axis=1))
      if c.hc_mult:
        maps, extra = _split_counters(extra, "mhc/")
        mhc.append(jax.tree_util.tree_map(lambda a: a[None], maps))
      counters = jax.tree_util.tree_map(
          lambda a, b: jnp.concatenate([a, b[None]]), counters, extra)
    outputs["moe_counters"] = counters
    if mhc:
      outputs["mhc_counters"] = jax.tree_util.tree_map(
          lambda *rows: jnp.concatenate(rows), *mhc)
    return outputs


@configurable
class SequenceMoEModel(AbstractT2RModel):
  """Next-token (+ MTP where configured) training of one chip's share
  of the model."""

  def __init__(self, sequence_length: int = 8192, **kwargs):
    """`sequence_length` tokens a sequence; every field of
    `layers.sequence.SequenceConfig` by its name; the rest is the base
    class's (optimizer_fn, compute_dtype, ...)."""
    sizes = {k: kwargs.pop(k) for k in list(kwargs) if k in _CONFIG_FIELDS}
    super().__init__(**kwargs)
    self._sequence_length = sequence_length
    self._config = sequence.SequenceConfig(**sizes)

  @property
  def config(self) -> sequence.SequenceConfig:
    return self._config

  def get_feature_specification(self, mode: str) -> ts.TensorSpecStruct:
    del mode
    return ts.TensorSpecStruct({"tokens": ts.ExtendedTensorSpec(
        (self._sequence_length,), np.int32, name="tokens")})

  def build_module(self) -> nn.Module:
    return _SequenceModule(self._config, self.compute_dtype)

  def mutable_collections(self) -> Tuple[str, ...]:
    return ()

  def loss_fn(self, outputs, features, labels
              ) -> Tuple[jnp.ndarray, Metrics]:
    """CE_main + w · CE_mtp, each a mean over its valid positions: the
    last position has no next token, the last two no token after it."""
    del labels
    t = features["tokens"].shape[-1]
    position = jnp.arange(t)

    def mean_over(losses, valid):
      return (jnp.sum(jnp.where(position < valid, losses, 0.0))
              / (losses.shape[0] * valid))

    loss_main = mean_over(outputs["token_loss_main"], t - 1)
    metrics = {"loss_main": loss_main}
    loss = loss_main
    if "token_loss_mtp" in outputs:
      metrics["loss_mtp"] = mean_over(outputs["token_loss_mtp"], t - 2)
      loss = loss + self._config.mtp_loss_weight * metrics["loss_mtp"]
    counters = outputs["moe_counters"]
    per_expert = counters["expert_tokens"]          # (layers, held)
    metrics.update({
        "moe/expert_tokens": per_expert,
        "moe/held_assignments": jnp.sum(counters["held_assignments"]),
        "moe/total_assignments": jnp.sum(counters["total_assignments"]),
        "moe/max_expert_tokens": jnp.max(per_expert),
        "moe/min_expert_tokens": jnp.min(per_expert),
    })
    # (linear layers,): the mean of exp(g) and of β over tokens and heads;
    # a decay stuck at 0 or 1 is a layer that forgets everything or
    # nothing.
    metrics.update(outputs.get("gdn_counters", {}))
    # (layers, 2), by sublayer: the mean diagonal of H_res over tokens (1
    # is a plain residual, 1/n full mixing), the mean H_pre and H_post,
    # and the largest |row sum − 1| Sinkhorn's last iteration left.
    metrics.update(outputs.get("mhc_counters", {}))
    return loss, metrics

"""SequenceMoEModel: a decoder-only sparse-expert language model on the
`AbstractT2RModel` contract, trained through `Trainer.train_steps`.

The architecture is JoyAI-LLM-Flash's (DeepSeek-V3 layout: MLA
attention, `first_k_dense_replace` dense blocks, then expert blocks of
routed top-k experts plus a shared expert, a depth-1 multi-token
prediction module), its sizes under their published names. The model
is told its share of a deployment: which `experts_held` of the
`n_routed_experts` it computes (the router keeps its full width) and how
many rows of the vocabulary it holds; ids, logits and loss are over that
slice.

Features: `tokens`, int32 (B, T); no labels, no `batch_stats`. The
module computes in `compute_dtype` with float32 parameters, recomputes
each block on the backward pass and scans the equal expert blocks, so
the program holds one of them.
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


class _MTPModule(nn.Module):
  """Depth-1 multi-token prediction (DeepSeek-V3 §2.2): position i's
  hidden state and the embedding of token i+1, a norm each, joined by a
  projection, through one expert block and a final norm; the shared head
  then predicts token i+2."""
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
        c, True, self.dtype, name="block")(h)
    return norm("final_norm")(h), counters


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
    x = embed(tokens)
    for i in range(c.first_k_dense_replace):
      x, _ = nn.remat(sequence.DecoderBlock)(
          c, False, self.dtype, name=f"dense_block{i}")(x)
    stack = nn.scan(
        nn.remat(sequence.DecoderBlock, prevent_cse=False),
        variable_axes={"params": 0}, split_rngs={"params": True},
        length=c.num_expert_layers)
    x, counters = stack(c, True, self.dtype, name="expert_blocks")(x, None)
    final = sequence.RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")
    if mode == modes.PREDICT:
      with jax.named_scope("lm_head"):
        return {"logits": jnp.dot(final(x), head.astype(self.dtype),
                                  preferred_element_type=jnp.float32)}
    outputs = {"token_loss_main": sequence.token_losses(
        final(x), head, jnp.roll(tokens, -1, axis=1))}
    if c.num_nextn_predict_layers:
      with jax.named_scope("mtp"):
        h, extra = _MTPModule(c, self.dtype, name="mtp")(
            embed(jnp.roll(tokens, -1, axis=1)), x)
        outputs["token_loss_mtp"] = sequence.token_losses(
            h, head, jnp.roll(tokens, -2, axis=1))
      counters = jax.tree_util.tree_map(
          lambda a, b: jnp.concatenate([a, b[None]]), counters, extra)
    outputs["moe_counters"] = counters
    return outputs


@configurable
class SequenceMoEModel(AbstractT2RModel):
  """Next-token (+ MTP) training of one chip's share of the model."""

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
    return loss, metrics

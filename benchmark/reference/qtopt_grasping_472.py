"""Plain reference of this repo's port of the QT-Opt grasping critic
(Kalashnikov et al. 2018, arXiv:1806.10293): 7 convolutions where the
published tower has 16 (the configuration file lists both). Image
tower, action embedding added mid-way, three strided convs, global
pool, two dense layers, one Q logit; sigmoid cross-entropy against the
Bellman target.

Follows the layer equations of the configuration file beside
benchmark/configs/; imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import nn

_BN_NAMES = ("stem_bn", "pre_bn0", "pre_bn1", "pre_bn2",
             "post_bn0", "post_bn1", "post_bn2")


def init_variables(key, config):
  """{"params", "batch_stats"} from one key, f32."""
  c = config["tower_channels"]
  a = config["action_size"]

  def build(pool):
    params, stats = {}, {}
    params["stem"] = nn.conv_params(pool, 6, 6, 3, c)
    for i in range(3):
      params[f"pre_conv{i}"] = nn.conv_params(pool, 3, 3, c, c)
      params[f"post_conv{i}"] = nn.conv_params(pool, 3, 3, c, c)
    for name in _BN_NAMES:
      params[name], stats[name] = nn.bn_params(pool, c)
    params["action_fc1"] = nn.dense_params(pool, a, c)
    params["action_fc2"] = nn.dense_params(pool, c, c)
    params["fc1"] = nn.dense_params(pool, c, c)
    params["q_head"] = nn.dense_params(pool, c, 1)
    return {"params": params, "batch_stats": stats}

  return nn.Pool.fill(key, build)


def make_batch(key, config, batch_size):
  """(features, labels): textured images whose brightness and contrast
  differ from row to row, actions in the CEM box, targets in [0, 1]."""
  s = config["image_size"]
  k1, k2, k3, k4, k5 = jax.random.split(key, 5)
  level = jax.random.uniform(k1, (batch_size, 1, 1, 3), jnp.float32,
                             0.2, 0.8)
  contrast = jax.random.uniform(k2, (batch_size, 1, 1, 1), jnp.float32,
                                0.05, 0.2)
  noise = jax.random.uniform(k3, (batch_size, s, s, 3), jnp.float32,
                             -1.0, 1.0)
  features = {
      "image": jnp.clip(level + contrast * noise, 0.0, 1.0),
      "action": jax.random.uniform(
          k4, (batch_size, config["action_size"]), jnp.float32, -1.0, 1.0),
  }
  labels = {"target_q": jax.random.uniform(k5, (batch_size,), jnp.float32)}
  return features, labels


def forward(variables, features, train, precision="f32"):
  """Returns ({"q_predicted": (B,) logits}, new batch_stats)."""
  p, stats = variables["params"], variables["batch_stats"]
  new_stats = {}

  def bn_relu(x, name):
    y, new_stats[name] = nn.batch_norm(x, p[name], stats[name], train,
                                      precision)
    return jax.nn.relu(y)

  x = features["image"].astype(jnp.float32)
  x = bn_relu(nn.conv(x, p["stem"], 4, "SAME", precision), "stem_bn")
  x = nn.max_pool(x, 2, 2, "VALID")
  for i in range(3):
    x = bn_relu(nn.conv(x, p[f"pre_conv{i}"], 1, "SAME", precision),
                f"pre_bn{i}")
  e = jax.nn.relu(nn.dense(features["action"].astype(jnp.float32),
                           p["action_fc1"], precision))
  e = nn.dense(e, p["action_fc2"], precision)
  x = jax.nn.relu(x + e[:, None, None, :])
  for i in range(3):
    x = bn_relu(nn.conv(x, p[f"post_conv{i}"], 2, "SAME", precision),
                f"post_bn{i}")
  x = jnp.mean(x, axis=(1, 2))
  x = jax.nn.relu(nn.dense(x, p["fc1"], precision))
  q = nn.dense(x, p["q_head"], precision)[:, 0]
  return {"q_predicted": q}, new_stats


def loss(outputs, features, labels):
  del features
  logit = outputs["q_predicted"]
  target = labels["target_q"]
  # sigmoid cross-entropy, the numerically plain form
  return jnp.mean(jnp.maximum(logit, 0) - logit * target
                  + jnp.log1p(jnp.exp(-jnp.abs(logit))))

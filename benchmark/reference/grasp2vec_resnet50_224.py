"""Plain reference of Grasp2Vec (Jang et al. 2018, arXiv:1811.06964;
upstream tensor2robot research/grasp2vec): a scene tower shared by the
pre- and post-grasp images and an outcome tower, both ResNet-50 v1 with
bottleneck blocks (He et al. 2015), each pooled and projected to the
embedding; n-pairs loss between phi(pre) - phi(post) and phi(outcome)
with an L2 penalty on both.

Every residual block is rematerialised on the backward pass so that the
float32 reference fits beside nothing else on one chip; that changes no
value. Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import nn

_BLOCKS = {50: (3, 4, 6, 3)}


def _block_plan(config):
  """[(name, width, stride, has_projection, cin)] in call order."""
  width, cin, plan = config["resnet_width"], config["resnet_width"], []
  for stage, blocks in enumerate(_BLOCKS[config["resnet_depth"]]):
    w = width * 2 ** stage
    for block in range(blocks):
      stride = 2 if (block == 0 and stage > 0) else 1
      plan.append((f"stage{stage}_block{block}", w, stride,
                   cin != 4 * w or stride != 1, cin))
      cin = 4 * w
  return plan, cin


def _init_tower(pool, config):
  plan, _ = _block_plan(config)
  width = config["resnet_width"]
  params = {"stem_conv": nn.conv_params(
      pool, 7, 7, config["image_channels"], width, bias=False)}
  stats = {}
  params["stem_bn"], stats["stem_bn"] = nn.bn_params(pool, width)
  for name, w, _, has_proj, cin in plan:
    p, s = {}, {}
    p["conv1"] = nn.conv_params(pool, 1, 1, cin, w, bias=False)
    p["bn1"], s["bn1"] = nn.bn_params(pool, w)
    p["conv2"] = nn.conv_params(pool, 3, 3, w, w, bias=False)
    p["bn2"], s["bn2"] = nn.bn_params(pool, w)
    p["conv3"] = nn.conv_params(pool, 1, 1, w, 4 * w, bias=False)
    p["bn3"], s["bn3"] = nn.bn_params(pool, 4 * w)
    # The residual branch enters at a quarter of the skip's scale, so
    # that sixteen blocks do not multiply the activations' size.
    p["bn3"] = jax.tree_util.tree_map(lambda x: 0.25 * x, p["bn3"])
    if has_proj:
      p["proj_conv"] = nn.conv_params(pool, 1, 1, cin, 4 * w, bias=False)
      p["proj_bn"], s["proj_bn"] = nn.bn_params(pool, 4 * w)
    params[name], stats[name] = p, s
  return params, stats


def init_variables(key, config):
  _, features = _block_plan(config)

  def build(pool):
    params, stats = {}, {}
    for tower in ("scene_tower", "outcome_tower"):
      params[tower], stats[tower] = _init_tower(pool, config)
    for name in ("scene_proj", "outcome_proj"):
      p = nn.dense_params(pool, features, config["embedding_size"])
      # Embeddings of order 0.3, so the n-pairs logits are of order 1.
      params[name] = jax.tree_util.tree_map(lambda x: 0.3 * x, p)
    return {"params": params, "batch_stats": stats}

  return nn.Pool.fill(key, build)


def make_batch(key, config, batch_size):
  """Three independent textured images per row, brightness and contrast
  differing from image to image; no labels (self-supervised)."""
  s = config["image_size"]

  def images(k):
    k1, k2, k3 = jax.random.split(k, 3)
    level = jax.random.uniform(k1, (batch_size, 1, 1, 3), jnp.float32,
                               0.2, 0.8)
    contrast = jax.random.uniform(k2, (batch_size, 1, 1, 1), jnp.float32,
                                  0.05, 0.2)
    noise = jax.random.uniform(k3, (batch_size, s, s, 3), jnp.float32,
                               -1.0, 1.0)
    return jnp.clip(level + contrast * noise, 0.0, 1.0)

  k1, k2, k3 = jax.random.split(key, 3)
  return {"pre_image": images(k1), "post_image": images(k2),
          "goal_image": images(k3)}, {}


def _block(x, p, stats, stride, train, precision):
  new = {}

  def bn(y, name):
    out, new[name] = nn.batch_norm(y, p[name], stats[name], train, precision)
    return out

  residual = x
  if "proj_conv" in p:
    residual = bn(nn.conv(x, p["proj_conv"], stride, "SAME", precision),
                  "proj_bn")
  y = jax.nn.relu(bn(nn.conv(x, p["conv1"], 1, "SAME", precision), "bn1"))
  y = jax.nn.relu(bn(nn.conv(y, p["conv2"], stride, "SAME", precision),
                     "bn2"))
  y = bn(nn.conv(y, p["conv3"], 1, "SAME", precision), "bn3")
  return jax.nn.relu(y + residual), new


def _tower(images, p, stats, config, train, precision):
  new = {}
  x = nn.conv(images.astype(jnp.float32), p["stem_conv"], 2, "SAME",
              precision)
  x, new["stem_bn"] = nn.batch_norm(x, p["stem_bn"], stats["stem_bn"],
                                    train, precision)
  x = nn.max_pool(jax.nn.relu(x), 3, 2, "SAME")
  plan, _ = _block_plan(config)
  for name, _, stride, _, _ in plan:
    block = jax.checkpoint(
        lambda x, p, s, stride=stride: _block(x, p, s, stride, train,
                                              precision))
    x, new[name] = block(x, p[name], stats[name])
  return jnp.mean(x, axis=(1, 2)), new


def forward(variables, features, train, precision="f32", config=None):
  p, stats = variables["params"], variables["batch_stats"]
  config = config or _config_from_tree(p)
  pre, scene_stats = _tower(features["pre_image"], p["scene_tower"],
                            stats["scene_tower"], config, train, precision)
  # The shared tower's second call starts from the first's statistics.
  post, scene_stats = _tower(features["post_image"], p["scene_tower"],
                             scene_stats, config, train, precision)
  goal, outcome_stats = _tower(features["goal_image"], p["outcome_tower"],
                               stats["outcome_tower"], config, train,
                               precision)
  outputs = {
      "pre_embedding": nn.dense(pre, p["scene_proj"], "f32"),
      "post_embedding": nn.dense(post, p["scene_proj"], "f32"),
      "outcome_embedding": nn.dense(goal, p["outcome_proj"], "f32"),
  }
  return outputs, {"scene_tower": scene_stats,
                   "outcome_tower": outcome_stats}


def _config_from_tree(params):
  """Depth and width read off the parameter tree (forward() gets no
  configuration from reference/train.py)."""
  tower = params["scene_tower"]
  stages = sorted({name.split("_")[0] for name in tower
                   if name.startswith("stage")})
  blocks = tuple(sum(1 for name in tower if name.startswith(stage + "_"))
                 for stage in stages)
  depth = [d for d, b in _BLOCKS.items() if b == blocks][0]
  return {"resnet_depth": depth,
          "resnet_width": tower["stem_conv"]["kernel"].shape[-1]}


L2_REG = 2e-3


def loss(outputs, features, labels):
  del features, labels
  anchors = outputs["pre_embedding"] - outputs["post_embedding"]
  positives = outputs["outcome_embedding"]
  logits = jnp.dot(anchors, positives.T,
                   precision=jax.lax.Precision.HIGHEST)
  log_probs = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
  ce = -jnp.mean(jnp.diagonal(log_probs))
  reg = (jnp.mean(jnp.sum(jnp.square(anchors), -1))
         + jnp.mean(jnp.sum(jnp.square(positives), -1)))
  return ce + L2_REG * reg

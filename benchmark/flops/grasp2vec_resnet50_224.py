"""Operations of Grasp2Vec's two ResNet-50 towers over three images per
example, from the configuration's shapes (2 per multiply-add; norms,
activations, pooling and the loss are not counted)."""

import math

_BLOCKS = {50: (3, 4, 6, 3)}


def _tower_forward(config):
  width, size = config["resnet_width"], config["image_size"]
  s = math.ceil(size / 2)
  stem = s * s * width * (7 * 7 * config["image_channels"]) * 2
  s = math.ceil(s / 2)  # 3x3 max pool, stride 2, SAME
  total, cin = stem, width
  for stage, blocks in enumerate(_BLOCKS[config["resnet_depth"]]):
    w = width * 2 ** stage
    for block in range(blocks):
      stride = 2 if (block == 0 and stage > 0) else 1
      out = math.ceil(s / stride)
      total += s * s * w * cin * 2              # conv1 1x1
      total += out * out * w * (9 * w) * 2      # conv2 3x3 (strided)
      total += out * out * 4 * w * w * 2        # conv3 1x1
      if cin != 4 * w or stride != 1:
        total += out * out * 4 * w * cin * 2    # projection
      s, cin = out, 4 * w
  return {"stem": stem, "total": total, "features": cin}


def forward_per_example(config):
  t = _tower_forward(config)
  proj = t["features"] * config["embedding_size"] * 2
  return {"stem": 3 * t["stem"], "total": 3 * (t["total"] + proj)}


def train_per_example(config):
  f = forward_per_example(config)
  return 3 * f["total"] - f["stem"]

"""Operations of this repo's 7-conv port of the QT-Opt critic, from the
configuration's shapes alone (2 per multiply-add; norms, activations,
pooling and the loss are not counted)."""

import math


def _same(size, stride):
  return math.ceil(size / stride)


def forward_per_row(config):
  c, cin = config["tower_channels"], config["image_channels"]
  a = config["action_size"]
  s = _same(config["image_size"], 4)
  stem = s * s * c * (6 * 6 * cin) * 2
  s //= 2  # 2x2 max pool, VALID
  pre = 3 * s * s * c * (3 * 3 * c) * 2
  post = 0
  for _ in range(3):
    s = _same(s, 2)
    post += s * s * c * (3 * 3 * c) * 2
  dense = (a * c + c * c + c * c + c) * 2
  return {"stem": stem, "total": stem + pre + post + dense}


def train_per_example(config):
  """Forward, gradient with respect to activations, gradient with
  respect to weights; the image needs no gradient, so the stem counts
  twice and everything else three times."""
  f = forward_per_row(config)
  return 3 * f["total"] - f["stem"]


def serve_per_action(config):
  """One CEM control step: samples x iterations rows scored, and the
  final mean scored once."""
  rows = config["cem_num_samples"] * config["cem_iterations"] + 1
  return rows * forward_per_row(config)["total"]

"""The main path's Pallas kernels compiled for a v5e chip that is
described, not attached (the TPU's compiler is installed here): what
interpret mode cannot show — a block Mosaic refuses, more scoped VMEM
than a kernel may use. Nothing runs; a compile that passes is not a
chip run. All such compiles live in this one file: the worker that is
handed it loads the TPU's library, once."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  from jax.experimental import topologies
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no compiler for that chip here
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
  """The kernels ask `jax.default_backend()` whether to interpret; here
  it says cpu. A compile for the chip cannot be read back from the
  persistent cache without one, so the cache is off around it."""
  from jax.experimental.compilation_cache import compilation_cache
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  before = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield
  jax.config.update("jax_enable_compilation_cache", before)
  compilation_cache.reset_cache()


@pytest.mark.parametrize("qk_width, v_width", [(192, 128), (128, 128)])
def test_flash_attention_compiles_at_8k_forward_and_backward(
    one_chip, as_on_tpu, qk_width, v_width):
  """MLA's widths (q/k 128 + 64 rotary, v 128) and equal widths at
  T = 8,192, 32 heads, bf16: the dk/dv program stages (T, 1) float32
  columns that VMEM pads 128x, past Mosaic's 16 MiB default."""
  from tensor2robot_tpu.ops.flash_attention import flash_attention
  shape = lambda d: jax.ShapeDtypeStruct((1, 8192, 32, d), jnp.bfloat16,
                                         sharding=one_chip)
  q, k, v = shape(qk_width), shape(qk_width), shape(v_width)

  def loss(q, k, v):
    out = flash_attention(q, k, v, causal=True, implementation="pallas")
    return jnp.sum(out.astype(jnp.float32))

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      q, k, v).compile()
  text = compiled.as_text()
  for name in ("flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv"):
    assert name in text
  assert text.count("tpu_custom_call") >= 3


def test_flash_attention_compiles_at_4k_with_a_share_of_the_heads(one_chip,
                                                                  as_on_tpu):
  """MLA's widths at the head-sharded model's shapes: 4 of 32 heads over
  all of T = 4,096, bf16; the three programs at a head count no other
  configuration runs."""
  from tensor2robot_tpu.ops.flash_attention import flash_attention
  shape = lambda d: jax.ShapeDtypeStruct((1, 4096, 4, d), jnp.bfloat16,
                                         sharding=one_chip)

  def loss(q, k, v):
    out = flash_attention(q, k, v, causal=True, implementation="pallas")
    return jnp.sum(out.astype(jnp.float32))

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      shape(192), shape(192), shape(128)).compile()
  text = compiled.as_text()
  for name in ("flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv"):
    assert name in text
  assert text.count("tpu_custom_call") >= 3


def test_flash_attention_compiles_at_8k_grouped_256_wide(one_chip, as_on_tpu):
  """The hybrid model's full-attention shapes: 16 query heads on 2
  key/value heads, 256-wide, T = 8,192, bf16. K and V rows are staged
  whole, 16 MiB double-buffered, and the dk/dv program writes the
  group's parts in float32."""
  from tensor2robot_tpu.ops.flash_attention import flash_attention
  shape = lambda heads: jax.ShapeDtypeStruct(
      (1, 8192, heads, 256), jnp.bfloat16, sharding=one_chip)

  def loss(q, k, v):
    out = flash_attention(q, k, v, causal=True, implementation="pallas")
    return jnp.sum(out.astype(jnp.float32))

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      shape(16), shape(2), shape(2)).compile()
  text = compiled.as_text()
  for name in ("flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv"):
    assert name in text
  assert text.count("tpu_custom_call") >= 3


def test_gated_delta_rule_compiles_at_8k_forward_and_backward(one_chip,
                                                              as_on_tpu):
  """The delta net's shapes: 16 q/k heads and 32 value heads of 128,
  T = 8,192 in chunks of 64, bf16 operands with float32 decays: both
  walks contract over the chunk's rows (a transposed left operand), and
  the prep programs transpose 64x64 float32 products at float32's
  precision, which interpret mode takes whatever Mosaic makes of them."""
  import importlib
  rule = importlib.import_module("tensor2robot_tpu.ops.gated_delta_rule")
  shape = lambda heads, dtype=jnp.bfloat16, width=(128,): (
      jax.ShapeDtypeStruct((1, 8192, heads) + width, dtype,
                           sharding=one_chip))
  gate = shape(32, jnp.float32, ())

  def loss(q, k, v, g, beta):
    out = rule.gated_delta_rule(q, k, v, g, beta, implementation="pallas")
    return jnp.sum(out.astype(jnp.float32))

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
      shape(16), shape(16), shape(32), gate, gate).compile()
  text = compiled.as_text()
  # The two walks and the two prep programs, each an instruction of its
  # own ("gated_delta_rule_prep" is also the start of the backward's
  # name, so the instruction's whole name is matched).
  assert len(rule.KERNEL_NAMES) == 4
  for name in rule.KERNEL_NAMES:
    assert re.search(rf"{name}_*\.\d+ = ", text), name
  assert text.count("tpu_custom_call") >= 4


def test_hyper_connection_compiles_at_4k_forward_and_backward(one_chip,
                                                              as_on_tpu):
  """The four-stream residual's shapes: T = 4,096 tokens of 4 x 3,584 side
  by side in bf16, float32 maps: tiles of 128 tokens with a token's whole
  14,336-wide stream in VMEM (past Mosaic's 16 MiB default with `phi`'s two parts and
  dphi's sums beside it), two (128, 128) transposes a tile between the
  token-major and the map-major layout, and products that contract over
  the tokens (a transposed left operand), which interpret mode takes
  whatever Mosaic makes of them."""
  import importlib
  hc = importlib.import_module("tensor2robot_tpu.ops.hyper_connection")
  streams, width, tokens = 4, 3584, 4096
  maps = streams * streams + 2 * streams
  shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
      dims, dtype, sharding=one_chip)

  def loss(x, y, phi, alpha, base):
    u, h_pre, h_post, h_res = hc.hyper_connection_pre(
        x, phi, alpha, base, implementation="pallas")
    out = hc.hyper_connection_post(x, y + u, h_post, h_res,
                                   implementation="pallas")
    return jnp.sum(out.astype(jnp.float32)) + jnp.sum(h_pre)

  compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
      shape((1, tokens, streams * width)), shape((1, tokens, width)),
      shape((streams * width, maps), jnp.float32),
      shape((3,), jnp.float32), shape((maps,), jnp.float32)).compile()
  text = compiled.as_text()
  for name in hc.KERNEL_NAMES:
    assert name in text
  assert text.count("tpu_custom_call") >= 4


def _written_outside_fusions(text, dims):
  """Instructions whose result is an array of `dims` in a computation
  that is no fusion's body (the entry, a loop's body): buffers the
  program writes, where an instruction inside a fusion names a value
  that may never leave the core."""
  fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
  current, found = None, []
  for line in text.splitlines():
    header = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
    if header:
      current = header.group(1)
    elif current not in fused and re.search(
        r" = \w+\[%s\]" % re.escape(dims), line):
      found.append(line.strip()[:120])
  return found


@pytest.mark.parametrize("bucket", [32, 8])
def test_serving_rung_expands_the_code_inside_the_first_post_conv(
    one_chip, as_on_tpu, bucket):
  """The rung's control program over the flagship's pair (f32 tier, CEM
  64 x 3 x 6). Before ISSUE 39 each iteration wrote the code tiled
  across its candidates, bf16[bucket,64,59,59,64] with the candidates
  padded to 128 lanes, and re-laid it into the post convolutions' rows,
  bf16[bucket*64,59,59,64]: 2.83 GB of temporaries at rung 32. Now the
  first does not exist, and the second only inside the fusion of the
  first post convolution."""
  from tensor2robot_tpu.predictors.checkpoint_predictor import (
      CheckpointPredictor)
  from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel
  from tensor2robot_tpu.serving.bucketing import BucketLadder
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy
  predictor = CheckpointPredictor(QTOptGraspingModel())
  predictor.init_randomly()
  fn, live = predictor.device_fn()
  policy = CEMFleetPolicy(predictor, ladder=BucketLadder((bucket,)),
                          num_samples=64, num_elites=6, iterations=3)
  shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                   sharding=one_chip)
  compiled = jax.jit(
      policy._build_control(fn, predictor.factored_device_fns())).lower(
          jax.tree_util.tree_map(lambda leaf: shape(leaf.shape, leaf.dtype),
                                 live),
          shape((bucket, 472, 472, 3), jnp.float32),
          shape((bucket,), jnp.uint32)).compile()
  text = compiled.as_text()
  assert f"[{bucket},64,59,59,64]" not in text
  rows = f"{bucket * 64},59,59,64"
  assert f"[{rows}]" in text  # the test sees the rows it says are fused
  assert _written_outside_fusions(text, rows) == []
  # The first post convolution's output is a buffer, and is found.
  assert _written_outside_fusions(text, f"{bucket * 64},30,30,64")
  assert compiled.memory_analysis().temp_size_in_bytes < 1e9

"""On-chip TPU lane: `python -m pytest tests/ --tpu -q`.

Runs against the machine's real TPU backend (conftest.py leaves the
environment alone under --tpu). Everything here is skipped in the
normal CPU-mesh suite and vice versa (tests/conftest.py collection
rules). Under --tpu a missing TPU is a failure, never a skip.

Covers what the CPU suite cannot: Pallas kernels compiled by Mosaic
(numerics vs the XLA reference, the Mosaic custom call present in the
compiled HLO, a timing sanity bound), and one real train→export→predict
smoke per model family on the chip.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(autouse=True)
def _tpu_backend():
  platform = jax.devices()[0].platform
  if platform != "tpu":
    pytest.fail(f"--tpu lane needs platform 'tpu'; jax reports "
                f"{platform!r}")


def _assert_mosaic(fn, *args):
  """The compiled program must hold the Mosaic kernel: an "auto" (or a
  refactored "pallas") path that gives way to the XLA reference still
  passes every numerics check below."""
  hlo = jax.jit(fn).lower(*args).compile().as_text()
  assert "tpu_custom_call" in hlo, "no Mosaic custom call in compiled HLO"


def _median_time(fn, n=5):
  """Median wall time of fn() with a forced host readback."""
  times = []
  for _ in range(n):
    start = time.perf_counter()
    jax.block_until_ready(fn())
    times.append(time.perf_counter() - start)
  return sorted(times)[n // 2]


class TestPallasKernelsOnChip:
  """ops/ kernels compiled for real (interpret=False on the tpu
  backend) — the CPU suite only ever runs them interpreted."""

  # float32 at equal widths in one 256 tile, and what the sequence model
  # hands the kernel: bf16 at MLA's widths, two 512 tiles.
  _FLASH_CASES = [(jnp.float32, 256, 64, 64), (jnp.bfloat16, 1024, 192, 128)]

  @pytest.mark.parametrize("dtype,t,d,dv", _FLASH_CASES)
  def test_flash_attention_numerics(self, dtype, t, d, dv):
    from tensor2robot_tpu.ops import flash_attention
    from tensor2robot_tpu.ops.flash_attention import (
        flash_attention_reference)

    rng = np.random.default_rng(0)
    b, h = 2, 4
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, width)), dtype)
               for width in (d, d, dv))
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    for causal in (False, True):
      ref = flash_attention_reference(q, k, v, causal=causal)
      pallas_fn = lambda q, k, v: flash_attention(
          q, k, v, causal=causal, implementation="pallas")
      _assert_mosaic(pallas_fn, q, k, v)
      out = pallas_fn(q, k, v)
      # TPU tolerance: both sides run their f32 matmuls as MXU bf16
      # passes (default precision), in different orders — observed
      # divergence ~1.6e-3 absolute at O(1) values; a bf16 output is
      # rounded 2^-7 to 2^-6 apart besides. A masking or normalization
      # bug shows up at O(1), far above this bar.
      tol = 5e-3 if dtype == jnp.float32 else 2e-2
      np.testing.assert_allclose(f32(out), f32(ref), atol=tol, rtol=tol)

  @pytest.mark.parametrize("dtype,t,d,dv", _FLASH_CASES)
  def test_flash_attention_grads(self, dtype, t, d, dv):
    from tensor2robot_tpu.ops import flash_attention
    from tensor2robot_tpu.ops.flash_attention import (
        flash_attention_reference)

    rng = np.random.default_rng(1)
    b, h = 1, 2
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, width)), dtype)
               for width in (d, d, dv))
    loss_p = lambda q, k, v: flash_attention(
        q, k, v, causal=True,
        implementation="pallas").astype(jnp.float32).sum()
    loss_r = lambda q, k, v: flash_attention_reference(
        q, k, v, causal=True).astype(jnp.float32).sum()
    _assert_mosaic(jax.grad(loss_p, argnums=(0, 1, 2)), q, k, v)
    grads_p = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    grads_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(grads_p, grads_r):
      # Grad path accumulates two MXU-bf16 matmul chains (see fwd test
      # note); observed on-chip divergence O(1e-3) on O(1) grads.
      np.testing.assert_allclose(
          np.asarray(gp.astype(jnp.float32)),
          np.asarray(gr.astype(jnp.float32)), atol=2e-2, rtol=2e-2)

  def test_flash_attention_timing_sane(self):
    """The O(T) kernel must not be pathologically slow vs the O(T²)
    XLA reference at a length where both comfortably fit (T=2048).
    Loose bound: this catches orders-of-magnitude regressions (e.g.
    silent interpret mode), not percent-level ones."""
    from tensor2robot_tpu.ops import flash_attention
    from tensor2robot_tpu.ops.flash_attention import (
        flash_attention_reference)

    rng = np.random.default_rng(2)
    b, t, h, d = 2, 2048, 4, 64
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
               for _ in range(3))
    pallas_fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, implementation="pallas"))
    ref_fn = jax.jit(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=True))
    _assert_mosaic(pallas_fn, q, k, v)
    jax.block_until_ready(pallas_fn(q, k, v))  # compile
    jax.block_until_ready(ref_fn(q, k, v))
    t_pallas = _median_time(lambda: pallas_fn(q, k, v))
    t_ref = _median_time(lambda: ref_fn(q, k, v))
    assert t_pallas < 0.25, f"flash fwd took {t_pallas:.3f}s at T={t}"
    assert t_pallas < 5 * t_ref, (
        f"flash {t_pallas * 1e3:.1f}ms vs dense {t_ref * 1e3:.1f}ms — "
        "kernel likely running interpreted or badly tiled")

  def test_spatial_softmax_numerics_and_grad(self):
    from tensor2robot_tpu.ops.spatial_softmax import (
        spatial_softmax, spatial_softmax_reference)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 32, 32, 16)), jnp.float32)
    _assert_mosaic(spatial_softmax, x)  # "auto" must pick the kernel here
    out = spatial_softmax(x)
    ref = spatial_softmax_reference(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda x: spatial_softmax(x).sum())(x)
    g_ref = jax.grad(lambda x: spatial_softmax_reference(x).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=5e-5, rtol=5e-5)

  def test_snail_attention_flash_path_on_chip(self):
    """The use_flash wiring (layers/snail.py) through the REAL kernel."""
    from tensor2robot_tpu.layers import snail

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.random((2, 128, 8)), jnp.float32)
    dense = snail.AttentionBlock(key_size=64, value_size=64,
                                 dtype=jnp.float32)
    flash = snail.AttentionBlock(key_size=64, value_size=64,
                                 dtype=jnp.float32, use_flash=True)
    variables = dense.init(jax.random.key(0), x)
    _assert_mosaic(flash.apply, variables, x)
    np.testing.assert_allclose(
        np.asarray(flash.apply(variables, x)),
        np.asarray(dense.apply(variables, x)), atol=5e-3, rtol=5e-3)


  def test_max_pool_reshape_on_chip(self):
    """ops/pool.py reshape formulation: exact forward parity with
    nn.max_pool and tie-free gradient parity, ON CHIP (the backward
    lowers through compare/mask vs SelectAndScatter — both must agree
    numerically where the function is differentiable)."""
    import flax.linen as nn

    from tensor2robot_tpu.ops.pool import max_pool_reshape

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((8, 118, 118, 64)), jnp.bfloat16)
    got = jax.jit(max_pool_reshape)(x)
    want = jax.jit(lambda x: nn.max_pool(x, (2, 2), strides=(2, 2)))(x)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))
    # Tie-free grads (permutation => distinct values) must match.
    xf = jnp.asarray(
        rng.permutation(4 * 16 * 16 * 8).reshape(4, 16, 16, 8),
        jnp.float32)
    g1 = jax.jit(jax.grad(lambda x: jnp.sum(max_pool_reshape(x))))(xf)
    g2 = jax.jit(jax.grad(lambda x: jnp.sum(
        nn.max_pool(x, (2, 2), strides=(2, 2)))))(xf)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


class TestFamilySmokesOnChip:
  """Real train steps per model family on the chip — small shapes so
  each compile stays in the tens of seconds."""

  def _smoke(self, model, batch_size=4):
    from tensor2robot_tpu.utils.t2r_test_fixture import T2RModelFixture
    return T2RModelFixture().random_train(
        model, max_train_steps=2, eval_steps=1, batch_size=batch_size)

  def test_mock_and_export_predict_roundtrip(self, tmp_path):
    """Mock family + the full export→predict loop on-chip."""
    from tensor2robot_tpu import modes
    from tensor2robot_tpu.data.default_input_generator import (
        DefaultRandomInputGenerator)
    from tensor2robot_tpu.export.native_export_generator import (
        NativeExportGenerator)
    from tensor2robot_tpu.predictors.exported_model_predictor import (
        ExportedModelPredictor)
    from tensor2robot_tpu.train.trainer import Trainer
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    model = MockT2RModel()
    trainer = Trainer(model, seed=0)
    state = trainer.create_train_state()
    gen = DefaultRandomInputGenerator(batch_size=8, seed=0)
    gen.set_specification_from_model(model, modes.TRAIN)
    it = gen.create_dataset_fn(modes.TRAIN)()
    for _ in range(2):
      features, labels = trainer.shard_batch(next(it))
      state, metrics = trainer.train_step(state, features, labels)
    assert np.isfinite(float(metrics["loss"]))

    root = str(tmp_path / "exports")
    export_gen = NativeExportGenerator(export_root=root)
    export_gen.set_specification_from_model(model)
    export_gen.export(jax.device_get(state.variables(use_ema=True)))
    predictor = ExportedModelPredictor(root)
    assert predictor.restore()
    out = predictor.predict(
        {"x": np.zeros((4, 3), np.float32)})
    assert out["inference_output"].shape == (4, 1)

  def test_qtopt_family(self):
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        QTOptGraspingModel)
    self._smoke(QTOptGraspingModel(image_size=64))

  def test_pose_env_family(self):
    from tensor2robot_tpu.research.pose_env.pose_env_models import (
        PoseEnvRegressionModel)
    self._smoke(PoseEnvRegressionModel(image_size=64))

  def test_grasp2vec_family(self):
    from tensor2robot_tpu.research.grasp2vec.grasp2vec_model import (
        Grasp2VecModel)
    self._smoke(Grasp2VecModel(image_size=64, depth=18, width=16),
                batch_size=4)

  def test_vrgripper_family(self):
    from tensor2robot_tpu.research.vrgripper.vrgripper_env_models import (
        VRGripperRegressionModel)
    self._smoke(VRGripperRegressionModel(image_size=64))

  def test_maml_family(self):
    from tensor2robot_tpu.meta_learning.maml_model import MAMLModel
    from tensor2robot_tpu.utils.mocks import MockT2RModel
    self._smoke(MAMLModel(MockT2RModel(), num_inner_steps=1))

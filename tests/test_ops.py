"""Tests for the Pallas hot-op kernels (ops/).

Off-TPU the kernels run in Pallas interpreter mode, so these tests
exercise the real kernel bodies, not just the XLA references.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops import (
    flash_attention,
    flash_attention_reference,
    spatial_softmax,
    spatial_softmax_reference,
)


class TestSpatialSoftmax:

  @pytest.mark.parametrize("shape", [(2, 8, 8, 16), (1, 7, 5, 3),
                                     (3, 1, 9, 130)])
  def test_matches_reference(self, shape):
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(shape), jnp.float32)
    got = spatial_softmax(x, implementation="pallas")
    want = spatial_softmax_reference(x)
    assert got.shape == (shape[0], 2 * shape[3])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)

  def test_temperature(self):
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 6, 6, 4)),
        jnp.float32)
    got = spatial_softmax(x, temperature=0.5, implementation="pallas")
    want = spatial_softmax_reference(x, temperature=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)

  def test_bfloat16_io(self):
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((2, 4, 4, 8)),
        jnp.bfloat16)
    got = spatial_softmax(x, implementation="pallas")
    assert got.dtype == jnp.bfloat16
    want = spatial_softmax_reference(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)

  def test_peak_location(self):
    # A sharp peak at (row 2, col 5) of a 8x8 map → expected coords
    # near linspace(-1,1,8)[5] (x) and [2] (y).
    x = np.full((1, 8, 8, 1), -10.0, np.float32)
    x[0, 2, 5, 0] = 10.0
    out = np.asarray(spatial_softmax(jnp.asarray(x),
                                     implementation="pallas"))
    grid = np.linspace(-1, 1, 8)
    assert abs(out[0, 0] - grid[5]) < 1e-3   # x
    assert abs(out[0, 1] - grid[2]) < 1e-3   # y

  def test_gradients_match_reference(self):
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((2, 6, 6, 4)),
        jnp.float32)
    g_pallas = jax.grad(
        lambda x: jnp.sum(spatial_softmax(x, implementation="pallas")
                          ** 2))(x)
    g_ref = jax.grad(
        lambda x: jnp.sum(spatial_softmax_reference(x) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_ref),
                               atol=1e-5)

  def test_second_order_gradients(self):
    # MAML differentiates the tower twice; the custom_jvp rule must
    # support grad-of-grad (regression: custom_vjp broke this).
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((1, 4, 4, 2)),
        jnp.float32)
    f_p = lambda x: jnp.sum(spatial_softmax(x,
                                            implementation="pallas") ** 3)
    f_r = lambda x: jnp.sum(spatial_softmax_reference(x) ** 3)
    gg_p = jax.grad(lambda x: jnp.sum(jax.grad(f_p)(x) ** 2))(x)
    gg_r = jax.grad(lambda x: jnp.sum(jax.grad(f_r)(x) ** 2))(x)
    np.testing.assert_allclose(np.asarray(gg_p), np.asarray(gg_r),
                               atol=1e-4)

  def test_jit_and_vision_layer_use(self):
    from tensor2robot_tpu.layers.vision_layers import (
        spatial_softmax as layer_op,
    )
    x = jnp.asarray(
        np.random.default_rng(4).standard_normal((2, 8, 8, 16)),
        jnp.float32)
    got = jax.jit(lambda x: spatial_softmax(x))(x)
    want = layer_op(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


class TestFlashAttention:

  def _qkv(self, b=2, t=128, h=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, t, h, d)) * 0.5, jnp.float32)
    return mk(), mk(), mk()

  @pytest.mark.parametrize("causal", [False, True])
  def test_matches_reference_blocked(self, causal):
    q, k, v = self._qkv(t=256)  # 2 blocks of 128
    got = flash_attention(q, k, v, causal=causal,
                          implementation="pallas")
    want = flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)

  @pytest.mark.parametrize("t", [16, 40])
  def test_matches_reference_single_block(self, t):
    q, k, v = self._qkv(t=t, seed=1)
    got = flash_attention(q, k, v, causal=True,
                          implementation="pallas")
    want = flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)

  @pytest.mark.parametrize("t,d,dv,causal", [
      (256, 192, 128, True),    # MLA's widths, two blocks
      (256, 24, 16, False),
      (40, 192, 128, True),     # one block
  ])
  def test_v_narrower_than_qk(self, t, d, dv, causal):
    q, k, _ = self._qkv(b=1, t=t, h=2, d=d, seed=4)
    v = self._qkv(b=1, t=t, h=2, d=dv, seed=5)[2]
    got = flash_attention(q, k, v, causal=causal,
                          implementation="pallas")
    want = flash_attention_reference(q, k, v, causal=causal)
    assert got.shape == (1, t, 2, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)

  @pytest.mark.parametrize("t,d,dv", [(256, 192, 128), (128, 24, 16)])
  def test_gradients_with_v_narrower_than_qk(self, t, d, dv):
    q, k, _ = self._qkv(b=1, t=t, h=1, d=d, seed=6)
    v = self._qkv(b=1, t=t, h=1, d=dv, seed=7)[2]
    weight = jnp.asarray(np.random.default_rng(8).standard_normal(
        (1, t, 1, dv)), jnp.float32)
    loss = lambda fn, **kw: (lambda q, k, v: jnp.sum(
        fn(q, k, v, causal=True, **kw) * weight))
    got = jax.grad(loss(flash_attention, implementation="pallas"),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(flash_attention_reference),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
      assert g.shape == w.shape
      np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)

  def test_auto_falls_back_on_odd_t(self):
    q, k, v = self._qkv(t=1030, b=1, h=1, d=8, seed=2)
    got = flash_attention(q, k, v)  # auto → XLA fallback, no error
    want = flash_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    with pytest.raises(ValueError, match="divisible"):
      flash_attention(q, k, v, implementation="pallas")

  @pytest.mark.parametrize("t,causal", [(128, True), (256, True),
                                        (256, False), (40, True)])
  def test_gradients_match_reference(self, t, causal):
    # The Pallas flash backward (dq + dkv kernels) must match the
    # dense reference for single- and multi-block T, both maskings.
    q, k, v = self._qkv(t=t, seed=3)
    loss_p = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal,
                        implementation="pallas") ** 2)
    loss_r = lambda q, k, v: jnp.sum(
        flash_attention_reference(q, k, v, causal=causal) ** 2)
    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=5e-5)

  # bf16 keeps 8 bits: the output and the gradients (entries up to 2-4
  # here) are rounded 2^-7 to 2^-6 apart once at the end, and p and dS
  # once as operands, so entries may sit 2e-2 apart and a whole array
  # 6e-3 of its norm (read: 2.0e-3 to 2.8e-3). A tile skipped, masked
  # wrongly or normalized wrongly shows at O(1).
  @pytest.mark.parametrize("t,blocks,chosen", [
      (384, (128, 128), True),    # three tiles
      (768, (256, 256), True),    # three tiles
      (1024, (512, 512), True),   # two tiles
      (200, (200, 200), True),    # one tile, the diagonal through it
      (512, (128, 256), False),   # K tiles the diagonal leaves half-way
      (512, (256, 128), False),   # a Q tile's rows end inside a K tile
      (384, (128, 384), False),   # the last Q tile alone sees all of K
  ])
  def test_bf16_at_mla_widths_matches_reference(self, monkeypatch, t,
                                                blocks, chosen):
    """`chosen`: the tiles `_block_sizes` picks itself; else tiles put in
    its place, for the loops' bounds where the two sides differ."""
    module = importlib.import_module("tensor2robot_tpu.ops.flash_attention")
    if chosen:
      assert module._block_sizes(t) == blocks
    else:
      monkeypatch.setattr(module, "_block_sizes", lambda t: blocks)
    rng = np.random.default_rng(t)
    mk = lambda d: jnp.asarray(rng.standard_normal((1, t, 2, d)),
                               jnp.bfloat16)
    q, k, v, weight = mk(192), mk(192), mk(128), mk(128)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))

    def run(fn, **kw):
      def loss(q, k, v):
        out = fn(q, k, v, causal=True, **kw)
        return jnp.sum(out.astype(jnp.float32)
                       * weight.astype(jnp.float32)), out
      grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
      return (out,) + grads

    got = run(flash_attention, implementation="pallas")
    want = run(flash_attention_reference)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
      assert g.dtype == jnp.bfloat16 and g.shape == w.shape, name
      np.testing.assert_allclose(f32(g), f32(w), atol=2e-2, rtol=2e-2,
                                 err_msg=name)
      assert (np.linalg.norm(f32(g) - f32(w))
              < 6e-3 * np.linalg.norm(f32(w))), name

  @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
  @pytest.mark.parametrize("causal", [True, False])
  def test_products_take_operands_as_staged(self, dtype, causal):
    """Every product of the three programs takes both operands in the
    inputs' own dtype (q, k, v, dO as staged; p and dS cast to it) and
    sums in float32: a cast of a block up to float32 before a product
    would show here as a float32 operand under bf16 inputs."""
    from tensor2robot_tpu.ops.flash_attention import KERNEL_NAMES
    q = jnp.zeros((1, 256, 2, 192), dtype)
    v = jnp.zeros((1, 256, 2, 128), dtype)

    def loss(q, k, v):
      return jnp.sum(flash_attention(
          q, k, v, causal=causal,
          implementation="pallas").astype(jnp.float32))

    def sub_jaxprs(eqn):
      for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else [value]:
          item = getattr(item, "jaxpr", item)
          if hasattr(item, "eqns"):
            yield item

    def find(jaxpr, primitive):
      for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
          yield eqn
        else:
          for sub in sub_jaxprs(eqn):
            yield from find(sub, primitive)

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
    kernels = {eqn.params["name"]: eqn.params["jaxpr"]
               for eqn in find(traced.jaxpr, "pallas_call")}
    assert sorted(kernels) == sorted(KERNEL_NAMES)
    # Forward S and P V; dq S, dP and dS K; dkv S, Pt dO, dP and dSt Q:
    # once in the loop, causally once more in the masked loop.
    loops = 2 if causal else 1
    for name, products in zip(KERNEL_NAMES, (2, 3, 4)):
      dots = list(find(kernels[name], "dot_general"))
      assert len(dots) == products * loops, name
      for dot in dots:
        assert [x.aval.dtype for x in dot.invars] == [dtype, dtype], name
        assert dot.outvars[0].aval.dtype == jnp.float32, name

  def test_agrees_with_ring_attention(self):
    # The in-chip blockwise kernel and the cross-chip ring must agree:
    # they are the same accumulation at different levels of the
    # hierarchy.
    from tensor2robot_tpu.parallel.mesh import create_mesh
    from tensor2robot_tpu.parallel.ring_attention import ring_attention
    q, k, v = self._qkv(t=128, seed=4)
    mesh = create_mesh({"seq": -1})
    out_ring = ring_attention(q, k, v, mesh, axis="seq", causal=True)
    out_flash = flash_attention(q, k, v, causal=True,
                                implementation="pallas")
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_ring), atol=2e-5)


class TestDispatch:

  def test_xla_only_context(self):
    from tensor2robot_tpu.ops import dispatch
    assert not dispatch.use_xla_only()
    with dispatch.xla_only():
      assert dispatch.use_xla_only()
      with dispatch.xla_only():
        assert dispatch.use_xla_only()
      assert dispatch.use_xla_only()  # nesting restores, not clears
    assert not dispatch.use_xla_only()

  def test_multi_platform_export_of_auto_op(self):
    # Regression: a model whose tower uses the auto spatial softmax must
    # export for platforms=("cpu","tpu") — compiled pallas_calls cannot
    # lower for CPU, so xla_only() must reroute the trace.
    import jax
    from tensor2robot_tpu.ops import dispatch, spatial_softmax
    x_spec = jax.ShapeDtypeStruct((2, 8, 8, 4), jnp.float32)
    with dispatch.xla_only():
      exported = jax.export.export(
          jax.jit(lambda x: spatial_softmax(x)),
          platforms=("cpu", "tpu"))(x_spec)
    back = jax.export.deserialize(bytearray(exported.serialize()))
    out = jax.jit(back.call)(np.ones((2, 8, 8, 4), np.float32))
    assert out.shape == (2, 8)

  def test_invalid_implementation_raises(self):
    x = jnp.zeros((1, 4, 4, 2), jnp.float32)
    with pytest.raises(ValueError, match="implementation"):
      spatial_softmax(x, implementation="XLA")
    q = jnp.zeros((1, 16, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="implementation"):
      flash_attention(q, q, q, implementation="Pallas")

  def test_spatial_softmax_vmem_guard_counts_padded_lanes(self):
    """VMEM pads the channel tile to 128 lanes whatever C is, so the
    guard counts every block at 128: a 16-channel map takes the room
    of a 128-channel one. (On v5e Mosaic refused 118x118x64 under the
    old min(C, 128) count and the default scoped-VMEM budget.)"""
    import importlib
    module = importlib.import_module("tensor2robot_tpu.ops.spatial_softmax")
    shape = lambda h, w, c: jax.ShapeDtypeStruct((2, h, w, c), jnp.float32)
    assert module._supported(shape(128, 128, 16))
    assert module._supported(shape(128, 128, 256))
    assert module._supported(shape(118, 118, 64))
    assert not module._supported(shape(129, 128, 16))
    assert not module._supported(shape(236, 236, 64))

  def test_flash_attention_vmem_guard(self):
    # Huge T that is 128-divisible must fall back in auto mode and
    # raise (not compile-crash) when pallas is forced.
    t = 1 << 16
    big = jnp.zeros((1, t, 1, 64), jnp.bfloat16)
    from tensor2robot_tpu.ops.flash_attention import _supported
    assert _supported(big, big, big) is not None  # exceeds VMEM budget
    with pytest.raises(ValueError, match="VMEM"):
      flash_attention(big, big, big, implementation="pallas")

  def test_flash_attention_vmem_guard_counts_k_and_v_at_own_widths(self):
    # T = 12288: K at 192 and V at 128 are 15.7 MB double-buffered,
    # inside the 16 MiB guard; both counted at q's 192 would be 18.9 MB.
    from tensor2robot_tpu.ops.flash_attention import _supported
    wide = jax.ShapeDtypeStruct((1, 12288, 1, 192), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((1, 12288, 1, 128), jnp.bfloat16)
    assert _supported(wide, wide, narrow) is None
    assert "VMEM" in _supported(wide, wide, wide)


class TestFoldedS2dStem:
  """ops/stem_conv: the folded space-to-depth stem must compute exactly
  the naive block-transpose space-to-depth function (under the
  fold_s2d_weights layout permutation) — same function class the model
  documented in round 2, minus the 6D transpose."""

  @staticmethod
  def _naive_s2d(x, w_blocks):
    b = 4
    size = x.shape[1]
    pad = (-size) % b + b
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, pad), (0, 0)))
    n, h, wd, c = xp.shape
    xs = xp.reshape(n, h // b, b, wd // b, b, c).transpose(
        0, 1, 3, 2, 4, 5).reshape(n, h // b, wd // b, b * b * c)
    return jax.lax.conv_general_dilated(
        xs, w_blocks, (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))

  def test_matches_naive_space_to_depth(self):
    from tensor2robot_tpu.ops import stem_conv
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 64, 64, 3)), jnp.float32)
    w_blocks = jnp.asarray(rng.standard_normal((2, 2, 48, 16)) * 0.1,
                           jnp.float32)
    expected = self._naive_s2d(x, w_blocks)
    got = stem_conv.folded_s2d_stem(x, stem_conv.fold_s2d_weights(w_blocks))
    assert got.shape == expected.shape == (2, 16, 16, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-4)

  def test_grad_matches_naive(self):
    from tensor2robot_tpu.ops import stem_conv
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    w_blocks = jnp.asarray(rng.standard_normal((2, 2, 48, 8)) * 0.1,
                           jnp.float32)

    def loss_naive(w):
      return jnp.sum(self._naive_s2d(x, w) ** 2)

    def loss_folded(w):
      return jnp.sum(
          stem_conv.folded_s2d_stem(x, stem_conv.fold_s2d_weights(w)) ** 2)

    g_naive = jax.grad(loss_naive)(w_blocks)
    g_folded = jax.grad(loss_folded)(w_blocks)
    np.testing.assert_allclose(np.asarray(g_folded), np.asarray(g_naive),
                               rtol=1e-4, atol=1e-4)

  def test_geometry_validation(self):
    from tensor2robot_tpu.ops import stem_conv
    with pytest.raises(ValueError, match="weights"):
      stem_conv.folded_s2d_stem(
          jnp.zeros((1, 32, 32, 3)), jnp.zeros((8, 2, 16, 4)))

  def test_init_shape_and_scale(self):
    from tensor2robot_tpu.ops import stem_conv
    w = stem_conv.init_folded_stem_weights(jax.random.key(0), 3, 64)
    assert w.shape == (8, 2, 12, 64)
    # Lecun-normal: std ≈ 1/sqrt(fan_in 192)
    assert 0.5 / np.sqrt(192) < float(jnp.std(w)) < 2.0 / np.sqrt(192)

  def test_non_multiple_of_4_sizes_pad(self):
    # Regression (r3 review): the naive space-to-depth formulation
    # accepted any size; the folded op must too, via zero-pad up.
    from tensor2robot_tpu.ops import stem_conv
    x = jnp.ones((1, 30, 30, 3), jnp.float32)
    w = stem_conv.init_folded_stem_weights(jax.random.key(0), 3, 8)
    y = stem_conv.folded_s2d_stem(x, w)
    assert y.shape == (1, 8, 8, 8)  # ceil(30/4) = 8


class TestMaxPoolReshape:
  """ops/pool.py: the reshape formulation of non-overlapping max pool."""

  def test_forward_matches_nn_max_pool(self):
    import flax.linen as nn
    from tensor2robot_tpu.ops.pool import max_pool_reshape
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 6, 3)), jnp.float32)
    got = max_pool_reshape(x)
    want = nn.max_pool(x, (2, 2), strides=(2, 2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

  def test_forward_matches_on_relu_ties(self):
    """Whole-window ties (post-relu zeros) — forward must still agree."""
    import flax.linen as nn
    from tensor2robot_tpu.ops.pool import max_pool_reshape
    rng = np.random.default_rng(1)
    x = jnp.maximum(
        jnp.asarray(rng.standard_normal((1, 4, 4, 2)), jnp.float32), 0)
    np.testing.assert_array_equal(
        np.asarray(max_pool_reshape(x)),
        np.asarray(nn.max_pool(x, (2, 2), strides=(2, 2))))

  def test_gradient_is_valid_subgradient(self):
    """No ties: gradient must equal max_pool's exactly (all mass on the
    window max). With ties the conventions differ (documented); the
    tie-free contract is the one that must hold hard."""
    import flax.linen as nn
    from tensor2robot_tpu.ops.pool import max_pool_reshape
    rng = np.random.default_rng(2)
    # Distinct values => no ties.
    x = jnp.asarray(
        rng.permutation(8 * 8 * 2).reshape(1, 8, 8, 2), jnp.float32)
    g1 = jax.grad(lambda x: jnp.sum(max_pool_reshape(x) ** 2))(x)
    g2 = jax.grad(lambda x: jnp.sum(
        nn.max_pool(x, (2, 2), strides=(2, 2)) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2))

  def test_tie_gradient_sums_to_same_mass(self):
    """On ties, total gradient mass per window must be conserved even
    though its distribution differs from SelectAndScatter's."""
    from tensor2robot_tpu.ops.pool import max_pool_reshape
    x = jnp.zeros((1, 2, 2, 1), jnp.float32)  # one fully-tied window
    g = jax.grad(lambda x: jnp.sum(max_pool_reshape(x)))(x)
    assert float(jnp.sum(g)) == 1.0

  def test_bfloat16_window4(self):
    import flax.linen as nn
    from tensor2robot_tpu.ops.pool import max_pool_reshape
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 4)), jnp.bfloat16)
    got = max_pool_reshape(x, window=4)
    want = nn.max_pool(x, (4, 4), strides=(4, 4))
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))

  def test_ragged_size_rejected(self):
    from tensor2robot_tpu.ops.pool import max_pool_reshape
    with pytest.raises(ValueError, match="divisible"):
      max_pool_reshape(jnp.zeros((1, 7, 8, 1)))


class TestFoldedStrided3x3:
  """ops/strided_conv.py: exact function parity with the strided SAME
  conv, forward and backward, across odd/even sizes."""

  def _reference(self, x, w):
    return jax.lax.conv_general_dilated(
        x, w, (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))

  @pytest.mark.parametrize("hw", [59, 118, 8, 7, 15, 30])
  def test_forward_matches_same_conv(self, hw):
    from tensor2robot_tpu.ops.strided_conv import strided3x3_same
    rng = np.random.default_rng(hw)
    x = jnp.asarray(rng.standard_normal((2, hw, hw, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 16)) * 0.1,
                    jnp.float32)
    got = strided3x3_same(x, w)
    want = self._reference(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

  def test_rectangular_input(self):
    from tensor2robot_tpu.ops.strided_conv import strided3x3_same
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 13, 22, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) * 0.1, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(strided3x3_same(x, w)),
        np.asarray(self._reference(x, w)), atol=1e-5, rtol=1e-5)

  def test_gradients_match_both_args(self):
    from tensor2robot_tpu.ops.strided_conv import strided3x3_same
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 15, 15, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 8)) * 0.1, jnp.float32)

    def loss(fn):
      return lambda x, w: jnp.sum(fn(x, w) ** 2)

    gx1, gw1 = jax.grad(loss(strided3x3_same), argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(loss(self._reference), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                               atol=1e-4, rtol=1e-4)

  def test_fold_layout(self):
    """Folded kernel places column taps at (s, q) with 2s+q = col and
    zeros the structural taps."""
    from tensor2robot_tpu.ops.strided_conv import fold_strided3x3_weights
    w = jnp.arange(3 * 3 * 2 * 1, dtype=jnp.float32).reshape(3, 3, 2, 1)
    wf = np.asarray(fold_strided3x3_weights(w)).reshape(4, 2, 2, 2, 1)
    np.testing.assert_array_equal(wf[3], 0)         # row 3 zero
    np.testing.assert_array_equal(wf[0:3, 1, 1], 0)  # col-3 phase zero
    np.testing.assert_array_equal(wf[0:3, 0, 0], np.asarray(w[:, 0]))
    np.testing.assert_array_equal(wf[0:3, 0, 1], np.asarray(w[:, 1]))
    np.testing.assert_array_equal(wf[0:3, 1, 0], np.asarray(w[:, 2]))

  def test_non_3x3_rejected(self):
    from tensor2robot_tpu.ops.strided_conv import fold_strided3x3_weights
    with pytest.raises(ValueError, match="3, 3"):
      fold_strided3x3_weights(jnp.zeros((5, 5, 2, 2)))

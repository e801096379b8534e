"""Tests for ring attention and tensor-parallel sharding (8-dev CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

from tensor2robot_tpu import modes
from tensor2robot_tpu.data.default_input_generator import (
    DefaultRandomInputGenerator,
)
from tensor2robot_tpu.parallel import (
    create_mesh,
    dense_attention_reference,
    infer_dense_tp_specs,
    expert_parallel_moe,
    infer_dense_tp_specs_from_model,
    init_moe_params,
    pipeline_apply,
    ring_attention,
    stack_stage_params,
    moe_share,
    route,
    ulysses_attention,
)
from tensor2robot_tpu.train.trainer import Trainer
from tensor2robot_tpu.utils.mocks import MockT2RModel


def _qkv(b=2, t=32, h=4, d=16, dtype=jnp.float32, seed=0):
  rng = np.random.default_rng(seed)
  mk = lambda: jnp.asarray(
      rng.standard_normal((b, t, h, d)).astype(np.float32), dtype)
  return mk(), mk(), mk()


class TestRingAttention:

  @pytest.mark.parametrize("causal", [False, True])
  def test_matches_dense_reference(self, causal):
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh, axis="seq", causal=causal)
    expected = dense_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)

  def test_bfloat16(self):
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = ring_attention(q, k, v, mesh, causal=True)
    assert out.dtype == jnp.bfloat16
    expected = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        atol=0.05)

  def test_two_axis_mesh(self):
    """Ring over 'seq' composes with a data axis on the same mesh; the
    batch is sharded over 'data' so rows don't duplicate work."""
    mesh = create_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(t=16)
    out = ring_attention(q, k, v, mesh, axis="seq", batch_axis="data")
    expected = dense_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)

  def test_gradients_flow(self):
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv(t=16)

    def loss_ring(q, k, v):
      return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
      return jnp.sum(
          dense_attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_dense):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


class TestUlyssesAttention:

  @pytest.mark.parametrize("causal", [False, True])
  def test_matches_dense_reference(self, causal):
    # 8-way sequence parallel: heads must divide by 8.
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv(h=8)
    out = ulysses_attention(q, k, v, mesh, axis="seq", causal=causal)
    expected = dense_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)

  def test_matches_ring(self):
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv(h=8)
    out_u = ulysses_attention(q, k, v, mesh, causal=True)
    out_r = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_r),
                               atol=2e-5)

  def test_dp_sp_mesh_and_bf16(self):
    mesh = create_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(t=16, h=4, dtype=jnp.bfloat16)
    out = ulysses_attention(q, k, v, mesh, axis="seq",
                            batch_axis="data", causal=True)
    assert out.dtype == jnp.bfloat16
    expected = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        atol=0.05)

  @pytest.mark.slow  # fast-lane budget (VERDICT r3 #8): all_to_all's
  # transpose is all_to_all (low-risk vjp); ring's rotated-carry grad
  # test — the risky one — stays in the fast lane.
  def test_gradients_flow(self):
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv(t=16, h=8)

    def loss_u(q, k, v):
      return jnp.sum(ulysses_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
      return jnp.sum(
          dense_attention_reference(q, k, v, causal=True) ** 2)

    g_u = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_u, g_dense):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

  def test_indivisible_heads_raises(self):
    mesh = create_mesh({"seq": -1})
    q, k, v = _qkv(h=4)  # 4 heads over 8 shards
    with pytest.raises(ValueError, match="divisible"):
      ulysses_attention(q, k, v, mesh)

  def test_pallas_local_attention(self):
    """attn_impl='pallas' (interpret mode here): the blockwise flash
    kernel must trace inside shard_map (VMA check relaxed) and match."""
    mesh = create_mesh({"seq": 2}, devices=jax.devices()[:2])
    q, k, v = _qkv(t=256, h=2, d=128)
    out = ulysses_attention(q, k, v, mesh, causal=True,
                            attn_impl="pallas")
    expected = dense_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)
    # Gradients: custom_vjp (flash backward kernels) inside shard_map
    # with the VMA check relaxed — the exact combination enabled here.
    g_p = jax.grad(lambda q, k, v: jnp.sum(ulysses_attention(
        q, k, v, mesh, causal=True, attn_impl="pallas") ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(dense_attention_reference(
        q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_p, g_d):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    with pytest.raises(ValueError, match="attn_impl"):
      ulysses_attention(q, k, v, mesh, attn_impl="flash")


class TestPipeline:

  def _stages(self, num_stages=4, width=16, seed=0):
    rng = np.random.default_rng(seed)
    params = [
        {"w": jnp.asarray(rng.standard_normal((width, width)),
                          jnp.float32) * 0.3,
         "b": jnp.asarray(rng.standard_normal((width,)), jnp.float32)}
        for _ in range(num_stages)]
    return params, stack_stage_params(params)

  def test_matches_sequential(self):
    width, num_stages, batch = 16, 4, 8
    rng = np.random.default_rng(1)
    params_list, stacked = self._stages(num_stages, width)
    x = jnp.asarray(rng.standard_normal((batch, width)), jnp.float32)

    def stage_fn(p, x):
      return jnp.tanh(x @ p["w"] + p["b"])

    mesh = create_mesh({"stage": 4}, devices=jax.devices()[:4])
    out = pipeline_apply(stacked, x, stage_fn, mesh, axis="stage",
                         num_microbatches=4)
    expected = x
    for p in params_list:
      expected = stage_fn(p, expected)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)

  def test_more_microbatches_and_dp_axis(self):
    width, num_stages, batch = 8, 2, 16
    rng = np.random.default_rng(2)
    params_list, stacked = self._stages(num_stages, width, seed=3)
    x = jnp.asarray(rng.standard_normal((batch, width)), jnp.float32)

    def stage_fn(p, x):
      return jnp.tanh(x @ p["w"] + p["b"])

    mesh = create_mesh({"data": 4, "stage": 2})
    out = pipeline_apply(stacked, x, stage_fn, mesh, axis="stage",
                         num_microbatches=8)
    expected = x
    for p in params_list:
      expected = stage_fn(p, expected)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)

  def test_gradients_match_sequential(self):
    width, num_stages, batch = 8, 4, 8
    rng = np.random.default_rng(4)
    params_list, stacked = self._stages(num_stages, width, seed=5)
    x = jnp.asarray(rng.standard_normal((batch, width)), jnp.float32)

    def stage_fn(p, x):
      return jnp.tanh(x @ p["w"] + p["b"])

    mesh = create_mesh({"stage": 4}, devices=jax.devices()[:4])

    def loss_pipe(stacked):
      return jnp.sum(
          pipeline_apply(stacked, x, stage_fn, mesh,
                         num_microbatches=4) ** 2)

    def loss_seq(stacked):
      h = x
      for i in range(num_stages):
        p = jax.tree_util.tree_map(lambda l: l[i], stacked)
        h = stage_fn(p, h)
      return jnp.sum(h ** 2)

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq)):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

  def test_stage_count_mismatch_raises(self):
    # 8 stacked stages on a 4-device stage axis must be an error, not a
    # silent every-other-stage computation.
    _, stacked = self._stages(8, 8)
    mesh = create_mesh({"data": 2, "stage": 4})
    with pytest.raises(ValueError, match="stages"):
      pipeline_apply(stacked, jnp.zeros((8, 8)), lambda p, x: x, mesh)

  def test_indivisible_microbatches_raises(self):
    _, stacked = self._stages(2, 8)
    mesh = create_mesh({"data": 4, "stage": 2})
    with pytest.raises(ValueError, match="divisible"):
      pipeline_apply(stacked, jnp.zeros((7, 8)), lambda p, x: x, mesh,
                     num_microbatches=2)


class TestExpertParallel:
  """One drop-free expert layer that is told its share (`moe_share`),
  and the same routing and grouping behind the `all_to_all` path."""

  TOP_K, SCALE = 2, 2.5

  def _setup(self, n=32, d=8, h=16, e=8, seed=0):
    params = init_moe_params(jax.random.key(seed), num_experts=e,
                             d_model=d, d_hidden=h)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    return tokens, params

  def _share(self, params, first, held):
    return params._replace(gate=params.gate[first:first + held],
                           up=params.up[first:first + held],
                           down=params.down[first:first + held])

  def test_whole_layer_matches_per_token_computation(self):
    tokens, params = self._setup()
    out, counters = moe_share(tokens, params, 0, self.TOP_K, self.SCALE)
    scores = jax.nn.sigmoid(tokens @ params.router)
    for i in range(tokens.shape[0]):
      chosen = np.argsort(-np.asarray(scores[i] + params.bias))[:self.TOP_K]
      total = sum(float(scores[i, e]) for e in chosen)
      expected = 0.0
      for e in chosen:
        hidden = (jax.nn.silu(tokens[i] @ params.gate[e])
                  * (tokens[i] @ params.up[e]))
        expected = expected + (self.SCALE * scores[i, e] / total
                               * (hidden @ params.down[e]))
      np.testing.assert_allclose(np.asarray(out[i]), np.asarray(expected),
                                 atol=1e-5)
    assert int(counters["expert_tokens"].sum()) == 32 * self.TOP_K
    assert int(counters["held_assignments"]) == 32 * self.TOP_K

  def test_expert_parallel_equals_the_shares_summed(self):
    # The all_to_all path over 4 virtual devices against the one-device
    # layer summed over the 4 shares of 2 experts.
    tokens, params = self._setup()
    mesh = create_mesh({"expert": 4}, devices=jax.devices()[:4])
    out_ep, counters = expert_parallel_moe(
        tokens, params, mesh, top_k=self.TOP_K, scale=self.SCALE)
    summed, counts = 0.0, []
    for first in range(0, 8, 2):
      y, c = moe_share(tokens, self._share(params, first, 2), first,
                       self.TOP_K, self.SCALE)
      summed = summed + y
      counts.append(np.asarray(c["expert_tokens"]))
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(summed),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counters["expert_tokens"]),
                                  np.concatenate(counts))
    assert (int(counters["expert_tokens"].sum())
            == int(counters["total_assignments"]) == 32 * self.TOP_K)

  def test_expert_parallel_matches_whole_layer_on_all_devices(self):
    tokens, params = self._setup()
    mesh = create_mesh({"expert": -1})
    out_ep, _ = expert_parallel_moe(tokens, params, mesh, top_k=self.TOP_K)
    out_one, _ = moe_share(tokens, params, 0, self.TOP_K)
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_one),
                               atol=1e-5)

  def test_nothing_drops_under_imbalance(self):
    # Every token's first choice is expert 1 (the old layer's capacity
    # of 1 kept one token of sixteen): all sixteen arrive, none drops,
    # on one device and across the mesh.
    tokens, params = self._setup(n=16, e=4)
    params = params._replace(bias=params.bias.at[1].set(10.0))
    out, counters = moe_share(tokens, params, 0, self.TOP_K)
    assert int(counters["expert_tokens"][1]) == 16
    assert int(counters["expert_tokens"].sum()) == 16 * self.TOP_K
    assert not np.any(np.all(np.asarray(out) == 0.0, axis=-1))
    mesh = create_mesh({"expert": 4}, devices=jax.devices()[:4])
    out_ep, counters_ep = expert_parallel_moe(tokens, params, mesh,
                                              top_k=self.TOP_K)
    assert int(counters_ep["expert_tokens"][1]) == 16
    assert int(counters_ep["expert_tokens"].sum()) == 16 * self.TOP_K
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out),
                               atol=1e-5)

  def test_a_share_leaves_out_what_absent_experts_would_add(self):
    tokens, params = self._setup()
    index, _ = route(tokens, params.router, params.bias, self.TOP_K)
    out, counters = moe_share(tokens, self._share(params, 4, 2), 4,
                              self.TOP_K)
    here = np.any((np.asarray(index) >= 4) & (np.asarray(index) < 6), -1)
    assert np.all(np.asarray(out)[~here] == 0.0)
    assert np.all(np.any(np.asarray(out)[here] != 0.0, axis=-1))
    assert int(counters["held_assignments"]) < int(
        counters["total_assignments"])

  @pytest.mark.slow  # fast-lane budget (VERDICT r3 #8): covered by the full suite; EP forward/dense-equivalence tests stay fast
  def test_gradients_flow_through_ep(self):
    tokens, params = self._setup()
    mesh = create_mesh({"expert": -1})

    def loss(params, fn):
      return jnp.sum(fn(params)[0] ** 2)

    grads = jax.grad(loss)(params, lambda p: expert_parallel_moe(
        tokens, p, mesh, top_k=self.TOP_K))
    want = jax.grad(loss)(params, lambda p: moe_share(
        tokens, p, 0, self.TOP_K))
    for got, ref in zip(grads, want):
      np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                 atol=1e-4)
    # The router learns through the weights; the bias only chooses.
    assert float(jnp.max(jnp.abs(grads.router))) > 0
    assert float(jnp.max(jnp.abs(grads.bias))) == 0

  def test_indivisible_raises(self):
    tokens, params = self._setup(n=30)
    mesh = create_mesh({"expert": -1})
    with pytest.raises(ValueError, match="divisible"):
      expert_parallel_moe(tokens, params, mesh)
    tokens, params = self._setup(n=32, e=6)
    with pytest.raises(ValueError, match="divisible"):
      expert_parallel_moe(tokens, params, mesh)


class TestSequenceParallelSnail:

  def test_snail_attention_ring_matches_dense(self):
    from tensor2robot_tpu.layers import snail
    mesh = create_mesh({"seq": -1})
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 16, 8)), jnp.float32)
    dense = snail.AttentionBlock(key_size=8, value_size=8,
                                 dtype=jnp.float32)
    ring = snail.AttentionBlock(key_size=8, value_size=8,
                                dtype=jnp.float32, seq_mesh=mesh)
    variables = dense.init(jax.random.key(0), x)
    out_dense = dense.apply(variables, x)
    out_ring = ring.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_ring),
                               np.asarray(out_dense), atol=2e-5)

  @pytest.mark.slow  # fast-lane budget (VERDICT r3 #8): covered by the full suite; the single-axis ring-vs-dense snail test stays fast
  def test_snail_attention_ring_dp_sp_mesh(self):
    # On a dp×sp mesh, batch_axis shards the batch over the data rows
    # (without it each row would all-gather and redo the whole batch).
    from tensor2robot_tpu.layers import snail
    mesh = create_mesh({"data": 2, "seq": 4})
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((4, 16, 8)), jnp.float32)
    dense = snail.AttentionBlock(key_size=8, value_size=8,
                                 dtype=jnp.float32)
    ring = snail.AttentionBlock(key_size=8, value_size=8,
                                dtype=jnp.float32, seq_mesh=mesh,
                                batch_axis="data")
    variables = dense.init(jax.random.key(0), x)
    out_dense = dense.apply(variables, x)
    out_ring = ring.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_ring),
                               np.asarray(out_dense), atol=2e-5)


class TestTensorParallel:

  def test_spec_inference(self):
    mesh = create_mesh({"data": 4, "model": 2})
    params = {
        "dense": {"kernel": np.zeros((32, 128)), "bias": np.zeros((128,))},
        "head": {"kernel": np.zeros((128, 3))},
        "norm": {"scale": np.zeros((128,))},
    }
    specs = infer_dense_tp_specs(params, mesh)
    assert specs["dense"]["kernel"] == PartitionSpec(None, "model")
    assert specs["dense"]["bias"] == PartitionSpec()     # 1-D
    assert specs["head"]["kernel"] == PartitionSpec()    # too narrow
    assert specs["norm"]["scale"] == PartitionSpec()

  def test_no_model_axis_means_replicated(self):
    mesh = create_mesh()  # data only
    specs = infer_dense_tp_specs(
        {"k": np.zeros((32, 128))}, mesh)
    assert specs["k"] == PartitionSpec()

  def test_tp_training_matches_dp(self):
    """DP+TP over a 4x2 mesh computes the same optimization trajectory
    as pure DP (up to float noise) — the collectives are correct."""
    def run(param_specs, mesh):
      model = MockT2RModel(hidden_size=128,
                          optimizer_fn=lambda: optax.adam(1e-2))
      trainer = Trainer(model, mesh=mesh, seed=5,
                        param_specs=param_specs)
      state = trainer.create_train_state()
      gen = DefaultRandomInputGenerator(batch_size=8, seed=0)
      gen.set_specification_from_model(model, modes.TRAIN)
      features, labels = next(gen.create_dataset_fn(modes.TRAIN)())
      features, labels = trainer.shard_batch((features, labels))
      losses = []
      for _ in range(5):
        state, metrics = trainer.train_step(state, features, labels)
        losses.append(float(metrics["loss"]))
      return losses, state

    dp_mesh = create_mesh()
    dp_losses, _ = run(None, dp_mesh)

    tp_mesh = create_mesh({"data": 4, "model": 2})
    model = MockT2RModel(hidden_size=128)
    specs = infer_dense_tp_specs_from_model(model, tp_mesh)
    # The wide hidden layer must actually be sharded for this test to
    # mean anything.
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    assert any(s != PartitionSpec() for s in flat)
    tp_losses, tp_state = run(specs, tp_mesh)

    np.testing.assert_allclose(tp_losses, dp_losses, rtol=1e-4)
    # Params really live sharded on the model axis.
    dense_kernel = tp_state.params["Dense_0"]["kernel"]
    assert "model" in tuple(dense_kernel.sharding.spec)


class TestFSDP:

  def test_spec_inference(self):
    from tensor2robot_tpu.parallel import infer_fsdp_specs
    mesh = create_mesh()  # 8-way data
    params = {
        "dense": {"kernel": np.zeros((32, 256)), "bias": np.zeros((256,))},
        "tiny": {"kernel": np.zeros((4, 4))},
        "tall": {"kernel": np.zeros((1024, 6))},
    }
    specs = infer_fsdp_specs(params, mesh, min_size=1024)
    # Largest divisible dim shards over 'data'.
    assert specs["dense"]["kernel"] == PartitionSpec(None, "data")
    assert specs["tall"]["kernel"] == PartitionSpec("data", None)
    # Below min_size → replicated.
    assert specs["tiny"]["kernel"] == PartitionSpec()
    assert specs["dense"]["bias"] == PartitionSpec()

  def test_fsdp_training_matches_dp(self):
    """FSDP (params sharded over the data axis) must follow the same
    optimization trajectory as pure DP — XLA's all-gather/reduce-scatter
    schedule is semantically invisible."""
    from tensor2robot_tpu.parallel import infer_fsdp_specs_from_model

    def run(param_specs):
      model = MockT2RModel(hidden_size=128,
                           optimizer_fn=lambda: optax.adam(1e-2))
      trainer = Trainer(model, mesh=create_mesh(), seed=5,
                        param_specs=param_specs)
      state = trainer.create_train_state()
      gen = DefaultRandomInputGenerator(batch_size=8, seed=0)
      gen.set_specification_from_model(model, modes.TRAIN)
      features, labels = next(gen.create_dataset_fn(modes.TRAIN)())
      features, labels = trainer.shard_batch((features, labels))
      losses = []
      for _ in range(5):
        state, metrics = trainer.train_step(state, features, labels)
        losses.append(float(metrics["loss"]))
      return losses, state

    dp_losses, _ = run(None)

    model = MockT2RModel(hidden_size=128)
    specs = infer_fsdp_specs_from_model(model, create_mesh(), min_size=128)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    assert any(s != PartitionSpec() for s in flat)
    fsdp_losses, fsdp_state = run(specs)

    # Looser than the TP twin: reduce-scatter/all-gather reorders the
    # bf16 reductions, so trajectories drift by ~1e-4 relative.
    np.testing.assert_allclose(fsdp_losses, dp_losses, rtol=1e-3)
    # Params + optimizer state really live sharded over the data axis.
    kernel = fsdp_state.params["Dense_0"]["kernel"]
    assert "data" in jax.tree_util.tree_flatten(
        tuple(kernel.sharding.spec))[0]
    shard_shapes = {s.data.shape for s in kernel.addressable_shards}
    assert all(np.prod(s) < np.prod(kernel.shape) for s in shard_shapes)
    opt_leaves = jax.tree_util.tree_leaves(fsdp_state.opt_state)
    assert any(
        "data" in jax.tree_util.tree_flatten(tuple(l.sharding.spec))[0]
        for l in opt_leaves if hasattr(l, "sharding")
        and l.shape == kernel.shape)


class TestMeshHelpers:
  """ISSUE 7 satellites: the env/ring sharding rules the pod-scale
  Anakin loop places state with, plus the host-boundary helpers'
  edge cases (axis size 1, non-divisible batches, nested pytrees
  with scalar leaves)."""

  def test_env_and_ring_shardings_split_the_leading_dim(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    mesh = create_mesh()
    for rule in (mesh_lib.env_sharding, mesh_lib.ring_sharding,
                 mesh_lib.batch_sharding):
      assert tuple(rule(mesh).spec) == tuple(PartitionSpec("data"))
    assert tuple(
        mesh_lib.replicated_sharding(mesh).spec) == tuple(PartitionSpec())

  def test_local_batch_slice_single_process_and_degenerate(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    # One process: the local slice IS the global batch, including the
    # degenerate batch-1 case (axis-size-1 analogue at the host tier).
    assert mesh_lib.local_batch_slice(32) == 32
    assert mesh_lib.local_batch_slice(1) == 1

  def test_local_batch_slice_indivisible_raises(self, monkeypatch):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    # local_batch_slice divides by PROCESS count (pure arithmetic, so
    # a monkeypatched count exercises the multi-host branch in CI).
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    assert mesh_lib.local_batch_slice(12) == 3
    with pytest.raises(ValueError, match="not divisible by process"):
      mesh_lib.local_batch_slice(10)

  def test_shard_batch_axis_size_one_accepts_any_batch(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    out = mesh_lib.shard_batch(mesh, {"x": np.ones((3, 2), np.float32)})
    # 3 % 1 == 0: odd batches are fine on a trivial axis.
    np.testing.assert_array_equal(np.asarray(out["x"]), np.ones((3, 2)))

  def test_shard_batch_non_divisible_raises(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    mesh = create_mesh()  # 8 virtual devices on the data axis
    with pytest.raises(ValueError, match="not divisible"):
      mesh_lib.shard_batch(mesh, {"x": np.ones((3, 2), np.float32)})

  def test_shard_batch_checks_every_batched_leaf(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    # Pre-ISSUE-7 only leaf 0 was checked: a ragged SECOND leaf slid
    # through to a late XLA error. Now every >= 1-d leaf is validated.
    mesh = create_mesh()
    batch = {"a": np.ones((16, 2), np.float32),
             "b": np.ones((3,), np.float32)}
    with pytest.raises(ValueError, match="not divisible"):
      mesh_lib.shard_batch(mesh, batch)

  def test_shard_batch_nested_pytree_with_scalar_leaves(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    mesh = create_mesh()
    batch = {
        "features": {"x": np.ones((16, 2), np.float32)},
        "aux": {"mask_weight": np.float32(0.5),
                "step": np.int32(7)},
    }
    out = mesh_lib.shard_batch(mesh, batch)
    # Batched leaves split over the data axis...
    assert tuple(out["features"]["x"].sharding.spec) == ("data",)
    # ...scalar riders replicate instead of erroring (loss masks and
    # step counters ride in batch pytrees on the megastep paths).
    for key, expected in (("mask_weight", 0.5), ("step", 7)):
      leaf = out["aux"][key]
      assert leaf.sharding.is_fully_replicated
      assert np.asarray(leaf) == expected

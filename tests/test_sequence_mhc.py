"""The multi-stream sequence model (MLA under YaRN, sigmoid-routed
experts, a four-stream residual read, written and mixed through
manifold-constrained hyper-connections) against its plain reference
(benchmark/reference/xing4_0_29b_a4b_tp8ep8.py) at tiny sizes on the CPU,
seeded weights (the op itself: `tests/test_hyper_connection.py`): the plain
residual as a special case, YaRN's frequencies and softmax scale, the
head and expert shares adding up to the uncut layer, and one
`Trainer.train_steps` dispatch against the reference following the same
steps, with and without the MTP module."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_resident_mhc as driver
from benchmark.tests.tiny_mhc import SIZES
from tensor2robot_tpu.layers import sequence
from tensor2robot_tpu.parallel import expert_parallel
from tensor2robot_tpu.research.seqlm.seqlm_model import SequenceMoEModel
from tensor2robot_tpu.specs import tensorspec_utils as ts

reference = importlib.import_module(
    "benchmark.reference.xing4_0_29b_a4b_tp8ep8")

ROUTED = 8
STREAMS = 4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
_SHARED = dict(rope_theta=1e4, rms_norm_eps=1e-6, n_shared_experts=1,
               routed_scaling_factor=2, hc_mult=STREAMS,
               hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
               mhc_h_res_clamp_max=30, rope_scaling=YARN)


def reference_config(held=4, first=2, mtp=0, **changes):
  config = dict(
      SIZES, **_SHARED, n_routed_experts=held, router_width=ROUTED,
      first_expert=first, dense_blocks_run=1, num_nextn_predict_layers=mtp,
      mtp_loss_weight=0.3, optimizer={"kind": "adam", "learning_rate": 1e-3})
  config.update(changes)
  return config


def program_config(held=4, first=2, mtp=0, **changes):
  sizes = {k: v for k, v in SIZES.items() if k != "sequence_length"}
  sizes.update(_SHARED, n_routed_experts=ROUTED, experts_held=held,
               first_expert=first, first_k_dense_replace=1,
               num_nextn_predict_layers=mtp)
  sizes.update(changes)
  return sequence.SequenceConfig(**sizes)


@pytest.fixture(scope="module")
def variables():
  return reference.init_variables(jax.random.key(7), reference_config())


@pytest.fixture(scope="module")
def hidden():
  return jnp.asarray(np.random.default_rng(3).standard_normal(
      (2, SIZES["sequence_length"], SIZES["hidden_size"])), jnp.float32)


@pytest.fixture(scope="module")
def streams():
  """(B, T, n·D): a token's streams side by side, as the program holds
  them; `_apart` gives the reference's (B, T, n, D)."""
  return jnp.asarray(np.random.default_rng(5).standard_normal(
      (2, SIZES["sequence_length"], STREAMS * SIZES["hidden_size"])),
                     jnp.float32)


def _apart(x):
  return x.reshape(x.shape[:-1] + (STREAMS, x.shape[-1] // STREAMS))


def _beside(x):
  return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


class TestResidualRule:

  def _block(self, config, x):
    block = sequence.DecoderBlock(config, False, jnp.float32)
    params = block.init(jax.random.key(0), x)["params"]
    return block, params

  def test_without_streams_it_is_today_s_block_bit_for_bit(self, hidden):
    """h = x + MLA(N(x)); y = h + MLP(N(h)), from the same parameters."""
    config = program_config(hc_mult=0, rope_scaling=None)
    block, params = self._block(config, hidden)
    assert set(params) == {"attn_norm", "attn", "ffn_norm", "mlp"}
    got, counters = block.apply({"params": params}, hidden)
    assert counters is None
    norm = lambda name, x: sequence.RMSNorm(
        config.rms_norm_eps, jnp.float32).apply({"params": params[name]}, x)
    h = hidden + sequence.MLAttention(config, jnp.float32).apply(
        {"params": params["attn"]}, norm("attn_norm", hidden))
    want = h + sequence.GatedMLP(config.intermediate_size, jnp.float32).apply(
        {"params": params["mlp"]}, norm("ffn_norm", h))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

  @pytest.mark.parametrize("stream", range(STREAMS))
  def test_one_hot_maps_give_the_plain_residual_on_that_stream(self, hidden,
                                                               stream):
    """H_res = I and H_pre, H_post one-hot on a stream: that stream is
    x + F(N(x)) twice over, the others pass."""
    config = program_config()
    plain = program_config(hc_mult=0)
    x = _beside(hidden[:, :, None, :] * (
        1.0 + jnp.arange(STREAMS, dtype=jnp.float32))[:, None])
    block, params = self._block(config, x)
    n = STREAMS
    one_hot = jnp.where(jnp.arange(n) == stream, 40.0, -40.0)
    for name in ("attn_hc", "ffn_hc"):
      params[name] = dict(
          phi=jnp.zeros_like(params[name]["phi"]),
          alpha=params[name]["alpha"],
          # sigmoid(40) = 1, 2 sigmoid(0) = 1 on the stream, and
          # exp(30) / exp(-30) on H_res's diagonal.
          base=jnp.concatenate([
              one_hot, jnp.where(jnp.arange(n) == stream, 0.0, -40.0),
              jnp.where(jnp.eye(n) > 0, 40.0, -40.0).reshape(-1)]))
    got, counters = block.apply({"params": params}, x)
    got, x = _apart(got), _apart(x)
    want, _ = sequence.DecoderBlock(plain, False, jnp.float32).apply(
        {"params": {k: v for k, v in params.items()
                    if not k.endswith("_hc")}}, x[:, :, stream])
    np.testing.assert_allclose(np.asarray(got[:, :, stream]),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    others = [j for j in range(n) if j != stream]
    np.testing.assert_allclose(np.asarray(got[:, :, others]),
                               np.asarray(x[:, :, others]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(counters["mhc/res_diag_mean"]),
                               1.0, atol=1e-6)

  def test_a_fresh_block_is_near_a_plain_residual(self, hidden):
    x = jnp.tile(hidden, (1, 1, STREAMS))
    block, params = self._block(program_config(), x)
    _, counters = block.apply({"params": params}, x)
    assert counters["mhc/res_diag_mean"].shape == (2,)
    assert float(jnp.min(counters["mhc/res_diag_mean"])) > 0.99
    np.testing.assert_allclose(np.asarray(counters["mhc/post_mean"]), 1.0,
                               atol=0.02)

  def test_block_matches_the_reference_s(self, variables, streams):
    config, p = reference_config(), variables["params"]["dense_block0"]
    want, _, want_maps = jax.vmap(
        lambda x: reference.block(x, p, config, "f32"))(_apart(streams))
    got, counters = sequence.DecoderBlock(
        program_config(), False, jnp.float32).apply({"params": p}, streams)
    np.testing.assert_allclose(np.asarray(_apart(got)), np.asarray(want),
                               atol=5e-5)
    for i, name in enumerate(sequence.MHC_COUNTERS[:3]):
      np.testing.assert_allclose(
          np.asarray(counters[name]),
          np.asarray(jnp.mean(want_maps[..., i], axis=0)), atol=1e-6)


class TestYarn:

  def test_frequencies_below_the_ramp_are_plain_above_it_a_64th(self):
    """The published group at the published width: pairs under 10 turn
    more than 32 times over 4,096 positions, pairs over 23 less than
    once."""
    scaling = dict(YARN, original_max_position_embeddings=4096)
    plain = np.asarray(sequence.rotary_frequencies(64, 1e4))
    got = np.asarray(sequence.rotary_frequencies(64, 1e4, scaling))
    want = np.asarray(reference.inverse_frequencies(64, 1e4, scaling))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    middle = got[11:23] / plain[11:23]
    assert np.all(np.diff(middle) < 0) and middle[0] < 1 and (
        middle[-1] > 1 / 64)
    # The sorted items a SequenceConfig keeps serve as the dict does.
    np.testing.assert_array_equal(got, np.asarray(sequence.rotary_frequencies(
        64, 1e4, tuple(sorted(scaling.items())))))

  def test_the_softmax_scale_carries_m_squared(self):
    m = sequence.yarn_softmax_mscale(YARN)
    assert m == pytest.approx(0.1 * math.log(64) + 1) == pytest.approx(
        1.4159, abs=1e-4)
    assert m == pytest.approx(reference.softmax_mscale(YARN))
    assert sequence.yarn_softmax_mscale(None) == 1.0
    assert sequence.yarn_softmax_mscale(dict(YARN, factor=1)) == 1.0
    with pytest.raises(NotImplementedError):
      sequence.yarn_softmax_mscale(dict(YARN, mscale=0.707))

  def test_without_scaling_rotary_is_what_it_was(self, hidden):
    x = hidden[..., None, :8]
    t, r = x.shape[1], x.shape[-1]
    inv_freq = 1e4 ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq).reshape(
        1, t, 1, r // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    want = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      odd * jnp.cos(angle) + even * jnp.sin(angle)],
                     axis=-1).reshape(x.shape)
    np.testing.assert_array_equal(np.asarray(sequence.rotary(x, 1e4)),
                                  np.asarray(want))
    assert not np.allclose(np.asarray(sequence.rotary(x, 1e4, YARN)),
                           np.asarray(want))

  @pytest.mark.parametrize("fault", [None, "plain_rotary", "no_mscale"])
  def test_attention_matches_the_reference_and_not_its_faults(
      self, variables, hidden, fault):
    p, config = variables["params"]["dense_block0"]["attn"], (
        reference_config())
    got = sequence.MLAttention(program_config(), jnp.float32).apply(
        {"params": p}, hidden)
    want = jax.vmap(lambda x: reference.mla(x, p, config, "f32", fault))(
        hidden)
    gap = float(jnp.max(jnp.abs(got - want)))
    assert (gap < 2e-5) if fault is None else (gap > 1e-3), (fault, gap)


def _moe_params(p):
  return expert_parallel.MoEParams(
      router=p["router"], bias=p["correction_bias"], gate=p["experts_gate"],
      up=p["experts_up"], down=p["experts_down"])


class TestShares:
  """Eight chips share a layer: each holds an eighth of the heads and of
  the experts; what all compute alike is counted once."""

  def test_the_head_shares_add_up_to_the_uncut_attention(self, hidden):
    heads, shares = 8, 4
    whole = reference_config(num_attention_heads=heads)
    p = reference.init_variables(jax.random.key(11), whole)[
        "params"]["dense_block0"]["attn"]
    want = jax.vmap(lambda x: reference.mla(x, p, whole, "f32"))(hidden)
    nope, rope, vdim = (SIZES["qk_nope_head_dim"], SIZES["qk_rope_head_dim"],
                        SIZES["v_head_dim"])
    held = heads // shares
    module = sequence.MLAttention(program_config(num_attention_heads=held),
                                  jnp.float32)
    total = jnp.zeros_like(want)
    for share in range(shares):
      columns = lambda width: slice(share * held * width,
                                    (share + 1) * held * width)
      part = dict(
          p, q_b={"kernel": p["q_b"]["kernel"][:, columns(nope + rope)]},
          kv_b={"kernel": p["kv_b"]["kernel"][:, columns(nope + vdim)]},
          o={"kernel": p["o"]["kernel"][columns(vdim)]})
      total = total + module.apply({"params": part}, hidden)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=3e-5)

  def test_the_expert_shares_add_up_to_the_uncut_layer(self, hidden):
    config = reference_config(held=ROUTED, first=0)
    p = jax.tree_util.tree_map(lambda x: x[0], reference.init_variables(
        jax.random.key(11), config)["params"]["expert_blocks"]["moe"])
    tokens = hidden.reshape(-1, hidden.shape[-1])
    want, want_counts = reference.expert_layer(tokens, p, config, "f32")
    shared = p["shared"]
    total, counts = reference.gated_mlp(
        tokens, shared["gate"]["kernel"], shared["up"]["kernel"],
        shared["down"]["kernel"], "f32"), []   # the shared expert, once
    for first in range(0, ROUTED, 2):          # four shares of two experts
      part = _moe_params(p)._replace(
          gate=p["experts_gate"][first:first + 2],
          up=p["experts_up"][first:first + 2],
          down=p["experts_down"][first:first + 2])
      y, counters = expert_parallel.moe_share(
          tokens, part, first, SIZES["num_experts_per_tok"], scale=2.0)
      total = total + y
      counts.append(np.asarray(counters["expert_tokens"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=3e-5)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.concatenate(counts).sum()) == (
        tokens.shape[0] * SIZES["num_experts_per_tok"])


class TestModel:

  def _model(self, mtp=0, **changes):
    from tensor2robot_tpu.utils.optimizers import create_adam_optimizer
    sizes = dict(SIZES, **_SHARED, n_routed_experts=ROUTED, experts_held=4,
                 first_expert=2, first_k_dense_replace=1,
                 num_nextn_predict_layers=mtp)
    sizes.update(changes)
    return SequenceMoEModel(
        optimizer_fn=create_adam_optimizer(learning_rate=1e-3),
        compute_dtype=jnp.float32, **sizes)

  def _tokens(self, steps=2, batch=2):
    return jax.random.randint(
        jax.random.key(1), (steps, batch, SIZES["sequence_length"]), 0,
        SIZES["vocab_size"], jnp.int32)

  @pytest.mark.parametrize("mtp", [0, 1])
  def test_parameter_tree_is_the_reference_s(self, mtp):
    ours = jax.eval_shape(self._model(mtp).init_variables,
                          jax.random.key(0))
    theirs = jax.eval_shape(lambda key: reference.init_variables(
        key, reference_config(mtp=mtp)), jax.random.key(7))
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    assert shape(ours) == shape(theirs)
    assert ("mtp" in ours["params"]) == bool(mtp)
    hyper = ours["params"]["expert_blocks"]["ffn_hc"]
    assert hyper["phi"].shape == (2, STREAMS * SIZES["hidden_size"], 24)

  @pytest.mark.parametrize("mtp", [0, 1])
  def test_loss_matches_reference(self, mtp):
    model, config = self._model(mtp), reference_config(mtp=mtp)
    variables = reference.init_variables(jax.random.key(7), config)
    features = {"tokens": self._tokens()[0]}
    loss, (metrics, _) = model.model_train_fn(
        variables, ts.TensorSpecStruct(features), None)
    outputs, _ = reference.forward(variables, features, True, "f32", config)
    want, parts = reference.loss(outputs, features, None, config)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert ("loss_mtp" in metrics) == bool(mtp)
    if mtp:
      np.testing.assert_allclose(float(metrics["loss_mtp"]),
                                 float(parts["loss_mtp"]), rtol=1e-5)
    layers = SIZES["num_hidden_layers"] + mtp
    for ours, theirs in driver._COUNTERS.items():
      got, wanted = np.asarray(metrics[theirs]), np.asarray(outputs[ours])
      if theirs.startswith("mhc/"):
        assert got.shape == (layers, 2) and got.dtype == np.float32
      np.testing.assert_allclose(got, wanted, atol=1e-5, err_msg=theirs)

  @pytest.mark.parametrize("fault", reference.FAULTS)
  def test_every_planted_fault_moves_the_reference_s_loss(self, variables,
                                                          fault):
    config = reference_config()
    features = {"tokens": self._tokens(batch=1)[0]}
    value = lambda fault: float(jax.jit(lambda v: reference.loss(
        reference.forward(v, features, True, "f32", config, fault)[0],
        features, None, config, fault)[0])(variables))
    assert abs(value(fault) - value(None)) > 1e-4 * value(None), fault

  @pytest.mark.parametrize("mtp", [0, 1])
  def test_one_dispatch_matches_the_followed_reference(self, mtp):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.train.trainer import Trainer
    key, config = jax.random.key(7), reference_config(mtp=mtp)
    trainer = Trainer(self._model(mtp), mesh=mesh_lib.create_mesh(
        devices=jax.devices()[:1]))
    state = trainer.create_train_state()
    state = state.replace(params=jax.tree_util.tree_map(
        jnp.copy, reference.init_variables(key, config)["params"]))
    tokens = self._tokens()
    state, metrics = trainer.train_steps(
        state, ts.TensorSpecStruct({"tokens": tokens}))
    assert int(state.step) == 2
    followed = driver.follow(reference, config, key, {"tokens": tokens})
    _, norms = driver._seed_fns(reference, driver._sizes_json(config))
    first = jax.device_get(dict(
        norms(key, state.params, driver._first_moment(state.opt_state)),
        loss=metrics["loss"],
        **{ours: metrics[theirs]
           for ours, theirs in driver._COUNTERS.items()}))
    numbers = {name: value for name, value, _ in driver.compare(
        first, followed, {})}
    for name in ("last_loss_gap", "moment_norm_gap", "change_median_gap",
                 "moment_own_gap", "mhc_res_diag_gap", "mhc_pre_gap",
                 "mhc_post_gap"):
      assert numbers[name] < 1e-3, (name, numbers)
    # The first sublayer mixes four copies of the embedding: H_res moves
    # nothing there, its gradient is rounding, and Adam makes steps of
    # rounding's signs: that sublayer's phi and base change alike in size,
    # not entry for entry.
    assert numbers["change_norm_gap"] < 0.05, numbers
    assert numbers["expert_count_gap"] == 0.0
    assert numbers["mhc_sinkhorn_gap"] < 1e-2
    layers = SIZES["num_hidden_layers"] - 1 + mtp
    assert int(metrics["moe/total_assignments"]) == (
        layers * 2 * SIZES["sequence_length"] * SIZES["num_experts_per_tok"])

  def test_step_metrics_carry_the_maps_and_the_expert_counters(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.train.trainer import Trainer
    trainer = Trainer(self._model(), mesh=mesh_lib.create_mesh(
        devices=jax.devices()[:1]))
    state = trainer.create_train_state()
    _, metrics = trainer.train_steps(
        state, ts.TensorSpecStruct({"tokens": self._tokens()}))
    per_expert = np.asarray(metrics["moe/expert_tokens"])
    assert per_expert.shape == (2, 4)   # 2 expert layers, 4 held
    assert int(metrics["moe/held_assignments"]) == per_expert.sum()
    for name in sequence.MHC_COUNTERS:
      values = np.asarray(metrics[name])
      assert values.shape == (3, 2) and values.dtype == np.float32, name
    # The program's own fresh maps: near a plain residual on each stream.
    assert np.all(np.asarray(metrics["mhc/res_diag_mean"]) > 0.99)
    assert np.all(np.asarray(metrics["mhc/sinkhorn_gap"]) < 1e-3)
    assert "loss_mtp" not in metrics

  def test_prediction_reads_the_streams_sum(self, variables):
    from tensor2robot_tpu import modes
    model = self._model()
    tokens = self._tokens()[0]
    got = model.build_module().apply(variables, {"tokens": tokens},
                                     modes.PREDICT)["logits"]
    assert got.shape == tokens.shape + (SIZES["vocab_size"],)
    assert bool(jnp.all(jnp.isfinite(got)))

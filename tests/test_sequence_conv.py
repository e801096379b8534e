"""The conv/attention expert sequence model (LFM2's layout: gated short
convolutions in three of four layers, grouped-query attention without an
output gate in the fourth, leading dense layers then sparse experts
routed by sigmoid scores, a tied head) against its plain reference
(benchmark/reference/lfm2_8b_a1b_ep4.py) at tiny sizes on the CPU,
seeded weights: the short-convolution operator against a loop over its
taps and its causality, both mixers, the four expert shares adding up to
the uncut layer, the router's normalization, the layer pattern, the tied
head, the loss and its gradients, and one `Trainer.train_steps` dispatch
against the reference following the same steps."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_resident_conv as driver
from benchmark.drivers import train_resident_sala as scoped
from benchmark.tests.tiny_conv import SIZES
from tensor2robot_tpu import modes
from tensor2robot_tpu.layers import sequence
from tensor2robot_tpu.ops import short_conv as conv_lib
from tensor2robot_tpu.parallel import expert_parallel
from tensor2robot_tpu.research.seqlm.seqlm_model import SequenceMoEModel
from tensor2robot_tpu.specs import tensorspec_utils as ts

reference = importlib.import_module("benchmark.reference.lfm2_8b_a1b_ep4")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2_8b_a1b_ep4.json")) as _f:
  LAYER_TYPES = json.load(_f)["layer_types"]
ROUTED, HELD, FIRST = 16, 4, 4


def reference_config(**changes):
  config = dict(
      SIZES, layer_types=LAYER_TYPES, first_layer=1, num_hidden_layers=5,
      num_dense_layers=2, conv_L_cache=3, norm_eps=1e-5, rope_theta=1e6,
      num_experts=HELD, router_width=ROUTED, first_expert=FIRST,
      num_experts_per_tok=4, routed_scaling_factor=1.0,
      router_norm_eps=1e-6,
      optimizer={"kind": "adam", "learning_rate": 1e-3})
  config.update(changes)
  return config


def program_sizes(**changes):
  sizes = {k: v for k, v in SIZES.items() if k != "sequence_length"}
  sizes.update(
      partial_rotary_factor=1.0, rope_theta=1e6, rms_norm_eps=1e-5,
      layer_types=LAYER_TYPES, first_layer=1, num_hidden_layers=5,
      first_k_dense_replace=2, conv_L_cache=3, tie_word_embeddings=True,
      n_shared_experts=0, n_routed_experts=ROUTED, num_experts_per_tok=4,
      routed_scaling_factor=1.0, scoring_func="sigmoid",
      router_norm_eps=1e-6, experts_held=HELD, first_expert=FIRST,
      num_nextn_predict_layers=0)
  sizes.update(changes)
  return sizes


@pytest.fixture(scope="module")
def variables():
  return reference.init_variables(jax.random.key(0), reference_config())


@pytest.fixture(scope="module")
def hidden():
  """(1, T, D): what a block's mixer reads."""
  return jax.random.normal(jax.random.key(3), (
      1, SIZES["sequence_length"], SIZES["hidden_size"]), jnp.float32)


def _tokens(steps=2, batch=2):
  return jax.random.randint(
      jax.random.key(1), (steps, batch, SIZES["sequence_length"]), 0,
      SIZES["vocab_size"], jnp.int32)


# --- the operator ------------------------------------------------------------


def _looped(bcx, taps):
  """C ⊙ z, z_t = Σ_j w_j (B ⊙ x~)_{t-2+j}, one token and tap at a time."""
  d = bcx.shape[-1] // 3
  gate_b, gate_c, xs = (np.asarray(bcx[..., i * d:(i + 1) * d], np.float64)
                        for i in range(3))
  taps = np.asarray(taps, np.float64)
  out = np.zeros(gate_c.shape)
  for t in range(bcx.shape[1]):
    for j in range(taps.shape[0]):
      s = t - (taps.shape[0] - 1) + j
      if s >= 0:
        out[:, t] += taps[j] * gate_b[:, s] * xs[:, s]
  return gate_c * out


def _operands(t=24, d=8, batch=2, dtype=jnp.float32):
  bcx = jax.random.normal(jax.random.key(5), (batch, t, 3 * d), dtype)
  taps = jax.random.normal(jax.random.key(6), (3, d), jnp.float32)
  return bcx, taps


class TestShortConvOp:

  def test_matches_a_loop_over_taps(self):
    bcx, taps = _operands()
    y, u_square = conv_lib.gated_short_conv(bcx, taps)
    np.testing.assert_allclose(np.asarray(y), _looped(bcx, taps), rtol=1e-5,
                               atol=1e-6)
    d = bcx.shape[-1] // 3
    u = np.asarray(bcx[..., :d], np.float64) * np.asarray(bcx[..., 2 * d:])
    np.testing.assert_allclose(float(u_square), np.mean(u ** 2), rtol=1e-5)

  @pytest.mark.parametrize("position", [0, 7, 23])
  def test_a_token_moves_nothing_before_it(self, position):
    bcx, taps = _operands()
    moved = bcx.at[:, position].add(1.0)
    before = conv_lib.gated_short_conv(bcx, taps)[0]
    after = conv_lib.gated_short_conv(moved, taps)[0]
    np.testing.assert_array_equal(np.asarray(after[:, :position]),
                                  np.asarray(before[:, :position]))
    assert float(jnp.max(jnp.abs(after[:, position] - before[:, position]))
                 ) > 0.01
    # Its taps reach two tokens further and no more.
    if position + 3 < bcx.shape[1]:
      np.testing.assert_array_equal(np.asarray(after[:, position + 3:]),
                                    np.asarray(before[:, position + 3:]))

  def test_gradients_match_the_loop_s(self):
    bcx, taps = _operands(t=12, d=4)
    weights = jax.random.normal(jax.random.key(7), (2, 12, 4))

    def looped(bcx, taps):
      d = bcx.shape[-1] // 3
      u = bcx[..., :d] * bcx[..., 2 * d:]
      z = sum(taps[j] * jnp.pad(u, ((0, 0), (2 - j, 0), (0, 0)))[:, :12]
              for j in range(3))
      return jnp.sum(bcx[..., d:2 * d] * z * weights)

    ours = jax.grad(lambda b, w: jnp.sum(
        conv_lib.gated_short_conv(b, w)[0] * weights), argnums=(0, 1))(
        bcx, taps)
    want = jax.grad(looped, argnums=(0, 1))(bcx, taps)
    for a, b in zip(ours, want):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                 atol=1e-5)

  def test_bf16_rounds_once_after_float32_sums(self):
    bcx, taps = _operands(dtype=jnp.bfloat16)
    out = conv_lib.gated_short_conv(bcx, taps)[0]
    assert out.dtype == jnp.bfloat16
    exact = _looped(bcx.astype(jnp.float32), taps)
    # Within one rounding of bf16 (8 bits of mantissa) of the exact sums.
    np.testing.assert_allclose(np.asarray(out, np.float64), exact,
                               rtol=2 ** -8, atol=1e-6)


# --- the mixers ---------------------------------------------------------------


class TestMixers:

  def test_short_conv_matches_the_reference(self, variables, hidden):
    p = variables["params"]["block0"]["attn"]
    config = sequence.SequenceConfig(**program_sizes())
    got, rms = sequence.ShortConv(config, jnp.float32).apply(
        {"params": p}, hidden)
    want, (u_square, y_square) = reference.short_conv(
        hidden[0], p, reference_config(), "f32")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(rms["conv/u_rms"]),
                               float(jnp.sqrt(u_square)), rtol=1e-5)
    np.testing.assert_allclose(float(rms["conv/y_rms"]),
                               float(jnp.sqrt(y_square)), rtol=1e-5)

  def test_plain_attention_matches_the_reference(self, variables, hidden):
    p = variables["params"]["block1"]["attn"]
    config = sequence.SequenceConfig(**program_sizes())
    got = sequence.GatedAttention(config, jnp.float32, False).apply(
        {"params": p}, hidden)
    want = reference.attention(hidden[0], p, reference_config(), "f32")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

  def test_the_gated_form_keeps_its_gate(self):
    """Qwen3-Next's layer: q's projection carries each head's gate."""
    config = sequence.SequenceConfig(**program_sizes())
    x = jnp.zeros((1, 8, SIZES["hidden_size"]))
    shapes = {gated: jax.eval_shape(
        sequence.GatedAttention(config, jnp.float32, gated).init,
        jax.random.key(0), x)["params"]["q_proj"]["kernel"].shape
              for gated in (True, False)}
    heads, hd = SIZES["num_attention_heads"], SIZES["head_dim"]
    assert shapes == {True: (SIZES["hidden_size"], heads * 2 * hd),
                      False: (SIZES["hidden_size"], heads * hd)}

  def test_a_bias_on_the_convolution_is_refused(self):
    with pytest.raises(NotImplementedError):
      sequence.SequenceConfig(**program_sizes(conv_bias=True))


# --- the expert layer ---------------------------------------------------------


class TestShares:
  """Four chips' shares of the experts add up to the uncut layer."""

  def test_four_shares_add_up_to_the_uncut_layer(self, hidden):
    routed, held = 32, 8
    whole = reference.init_variables(jax.random.key(9), reference_config(
        num_experts=routed, router_width=routed, first_expert=0))
    moe = whole["params"]["block1"]["moe"]
    uncut = reference_config(num_experts=routed, router_width=routed,
                             first_expert=0)
    want, want_counts = reference.expert_layer(hidden[0], moe, uncut, "f32")
    parts, counts = [], []
    for chip in range(4):
      share = dict(moe)
      for name in ("experts_gate", "experts_up", "experts_down"):
        share[name] = moe[name][chip * held:(chip + 1) * held]
      config = sequence.SequenceConfig(**program_sizes(
          n_routed_experts=routed, experts_held=held,
          first_expert=chip * held))
      y, counters = sequence.ExpertLayer(config, jnp.float32).apply(
          {"params": share}, hidden)
      parts.append(y[0])
      counts.append(counters["expert_tokens"])
      assert float(jnp.max(jnp.abs(y))) > 0.01     # each share does work
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))

  def test_the_weights_are_the_chosen_scores_over_their_sum_and_eps(self):
    x = jax.random.normal(jax.random.key(2), (64, 16))
    router = jax.random.normal(jax.random.key(3), (16, 8))
    bias = 0.1 * jax.random.normal(jax.random.key(4), (8,))
    index, weight = expert_parallel.route(x, router, bias, 4,
                                          norm_eps=1e-6)
    scores = jax.nn.sigmoid(jnp.dot(x, router,
                                    precision=jax.lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    np.testing.assert_allclose(
        np.asarray(weight),
        np.asarray(chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)),
        rtol=1e-6)
    _, wanted = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(np.asarray(index), np.asarray(wanted))
    # Unsaid, the eps is DeepSeek-V3's, and the configuration's default.
    _, plain = expert_parallel.route(x, router, bias, 4)
    _, tiny = expert_parallel.route(x, router, bias, 4, norm_eps=1e-20)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(tiny))
    assert sequence.SequenceConfig().router_norm_eps == 1e-20


# --- the model ----------------------------------------------------------------


def _model(**changes):
  from tensor2robot_tpu.utils.optimizers import create_adam_optimizer
  return SequenceMoEModel(
      optimizer_fn=create_adam_optimizer(learning_rate=1e-3),
      compute_dtype=jnp.float32, sequence_length=SIZES["sequence_length"],
      **program_sizes(**changes))


class TestModel:

  def test_parameter_tree_is_the_reference_s(self, variables):
    ours = _model().init_variables(jax.random.key(0))
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    assert shape(ours) == shape(variables)

  def test_the_layer_pattern_is_read_from_layer_types(self):
    config = sequence.SequenceConfig(**program_sizes())
    assert [config.layer_kind(1 + i) for i in range(5)] == [
        "conv", "attention", "conv", "conv", "conv"]
    params = _model().init_variables(jax.random.key(0))["params"]
    assert set(params) == {"embed", "block0", "block1", "block2", "block3",
                           "block4", "final_norm"}
    assert set(params["block0"]) == {"attn_norm", "attn", "ffn_norm", "mlp"}
    for i in range(1, 5):
      assert "moe" in params[f"block{i}"]
      assert "shared" not in params[f"block{i}"]["moe"]
    assert "in_proj" in params["block0"]["attn"]
    assert "q_proj" in params["block1"]["attn"]

  def test_the_head_is_the_embedding_s_transpose(self, variables):
    """A row of the embedding no input token takes moves that column of
    the logits alone."""
    model, tokens = _model(), _tokens()[0] % (SIZES["vocab_size"] // 2)
    module = model.build_module()
    logits = lambda v: module.apply(v, {"tokens": tokens},
                                    modes.PREDICT)["logits"]
    row = SIZES["vocab_size"] - 1
    embedding = variables["params"]["embed"]["embedding"]
    moved = jax.tree_util.tree_map(lambda x: x, variables)
    moved["params"]["embed"]["embedding"] = embedding.at[row].mul(2.0)
    before, after = logits(variables), logits(moved)
    np.testing.assert_allclose(np.asarray(after[..., row]),
                               2 * np.asarray(before[..., row]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(after[..., :row]),
                                  np.asarray(before[..., :row]))

  def test_loss_gradients_and_counters_match_the_reference(self, variables):
    model, config = _model(), reference_config()
    features = {"tokens": _tokens()[0]}

    def ours(params):
      loss, (metrics, _) = model.model_train_fn(
          {"params": params}, ts.TensorSpecStruct(features), None)
      return loss, metrics

    def theirs(params):
      outputs, _ = reference.forward({"params": params}, features, True,
                                     "f32", config)
      return reference.loss(outputs, features, None, config)[0], outputs

    (loss, metrics), grads = jax.value_and_grad(ours, has_aux=True)(
        variables["params"])
    (want, outputs), want_grads = jax.value_and_grad(theirs, has_aux=True)(
        variables["params"])
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for path, got in jax.tree_util.tree_leaves_with_path(grads):
      expected = np.asarray(dict(jax.tree_util.tree_leaves_with_path(
          want_grads))[path])
      scale = max(float(np.max(np.abs(expected))), 1e-6)
      np.testing.assert_allclose(np.asarray(got) / scale, expected / scale,
                                 atol=1e-4, err_msg=str(path))
    for ours_name, theirs_name in (("conv/u_rms", "conv_u_rms"),
                                   ("conv/y_rms", "conv_y_rms")):
      assert np.asarray(metrics[ours_name]).shape == (4,)
      np.testing.assert_allclose(np.asarray(metrics[ours_name]),
                                 np.asarray(outputs[theirs_name]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(metrics["moe/expert_tokens"]),
                                  np.asarray(outputs["expert_tokens"]))
    assert np.asarray(metrics["moe/expert_tokens"]).shape == (4, HELD)
    # Only the held experts' share: fewer than all the assignments.
    assert 0 < int(metrics["moe/held_assignments"]) < int(
        metrics["moe/total_assignments"])

  @pytest.fixture(scope="class")
  def losses(self, variables):
    """The reference's loss without a fault and with each, the fault a
    traced index: one compiled forward for all of them."""
    config, features = reference_config(), {"tokens": _tokens()[0]}
    total = jax.jit(lambda fault: reference.loss(reference.forward(
        variables, features, True, "f32", config, fault)[0], features, None,
        config, fault)[0])
    found = {name: float(total(jnp.asarray(i, jnp.int32)))
             for i, name in enumerate(reference.FAULTS)}
    found[None] = float(total(jnp.asarray(-1, jnp.int32)))
    return found

  @pytest.mark.parametrize("fault", reference.FAULTS)
  def test_every_planted_fault_moves_the_reference_s_loss(self, losses,
                                                          fault):
    assert abs(losses[fault] - losses[None]) > 1e-6 * abs(losses[None])

  def test_a_fault_by_name_is_the_traced_one(self, variables, losses):
    config, features = reference_config(), {"tokens": _tokens()[0]}
    by_name = lambda fault: float(reference.loss(reference.forward(
        variables, features, True, "f32", config, fault)[0], features, None,
        config, fault)[0])
    assert by_name("taps_ahead") == pytest.approx(losses["taps_ahead"],
                                                  rel=1e-6)
    assert by_name(None) == pytest.approx(losses[None], rel=1e-6)

  def test_one_dispatch_matches_the_followed_reference(self, variables):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.train.trainer import Trainer
    trainer = Trainer(_model(), mesh=mesh_lib.create_mesh(
        devices=jax.devices()[:1]))
    state = trainer.create_train_state()
    state = state.replace(params=jax.tree_util.tree_map(
        jnp.copy, variables["params"]))
    tokens = _tokens()
    state, metrics = trainer.train_steps(
        state, ts.TensorSpecStruct({"tokens": tokens}))
    assert int(state.step) == 2
    key, config = jax.random.key(0), reference_config()
    followed = scoped.follow(reference, config, key, {"tokens": tokens},
                             names=driver.Session.FOLLOWED)
    _, norms = scoped._seed_fns(reference, scoped._sizes_json(config))
    first = jax.device_get(dict(
        norms(key, state.params, scoped._first_moment(state.opt_state)),
        loss=metrics["loss"], conv_u_rms=metrics["conv/u_rms"],
        conv_y_rms=metrics["conv/y_rms"],
        expert_tokens=metrics["moe/expert_tokens"]))
    numbers = {name: value for name, value, _ in driver.compare(
        first, followed, {})}
    for name in ("last_loss_gap", "moment_norm_gap", "change_norm_gap",
                 "moment_own_gap", "change_own_gap", "conv_rms_gap",
                 "expert_count_gap"):
      assert numbers[name] < 1e-3, (name, numbers)


def test_the_configuration_builds_through_the_trainer_s_cfg():
  """The `.cfg` that `bin/run_t2r_trainer` takes and the configuration
  file that `harness.build_model` takes name one model."""
  from tensor2robot_tpu.config import config as cfg_lib
  from tensor2robot_tpu.config import registrations  # noqa: F401
  with open(os.path.join(ROOT, "benchmark", "configs",
                         "lfm2_8b_a1b_ep4.json")) as f:
    kwargs = json.load(f)["model"]["kwargs"]
  try:
    cfg_lib.parse_config_files_and_bindings([os.path.join(
        ROOT, "tensor2robot_tpu", "research", "seqlm", "configs",
        "lfm2_8b_a1b_ep4_train.cfg")], [])
    model = cfg_lib.query_binding("train_eval_model.model")
  finally:
    cfg_lib.clear_config()
  assert isinstance(model, SequenceMoEModel)
  sizes = {k: v for k, v in kwargs.items() if k != "sequence_length"}
  assert model.config == sequence.SequenceConfig(**sizes)
  assert model.get_feature_specification(modes.TRAIN)["tokens"].shape == (
      kwargs["sequence_length"],)

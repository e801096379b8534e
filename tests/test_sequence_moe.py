"""The sparse-expert sequence model against its plain reference
(benchmark/reference/joyai_llm_flash_ep16.py) at a tiny configuration
on the CPU, seeded weights: MLA with unequal q/k and v head widths, the
router, the shares of the expert layer adding up to the uncut layer,
no dropped assignment under a skewed router, the MTP loss and its
shift, and one `Trainer.train_steps` dispatch of the whole model
against the reference following the same steps."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_resident_tokens as driver
from benchmark.tests.tiny_tokens import SIZES
from tensor2robot_tpu.layers import sequence
from tensor2robot_tpu.parallel import expert_parallel
from tensor2robot_tpu.research.seqlm.seqlm_model import SequenceMoEModel
from tensor2robot_tpu.specs import tensorspec_utils as ts

reference = importlib.import_module(
    "benchmark.reference.joyai_llm_flash_ep16")

ROUTED = 16


def reference_config(held=4, first=4, **changes):
  """The reference's configuration object at the tiny sizes: `held`
  experts of ROUTED, from `first`."""
  config = dict(
      SIZES, n_routed_experts=held, router_width=ROUTED, first_expert=first,
      first_k_dense_replace=1, num_nextn_predict_layers=1,
      n_shared_experts=1, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
      rope_theta=32e6, mtp_loss_weight=0.3,
      optimizer={"kind": "adam", "learning_rate": 1e-3})
  config.update(changes)
  return config


def program_config(held=4, first=4, **changes):
  sizes = {k: v for k, v in SIZES.items() if k != "sequence_length"}
  sizes.update(n_routed_experts=ROUTED, experts_held=held,
               first_expert=first)
  sizes.update(changes)
  return sequence.SequenceConfig(**sizes)


@pytest.fixture(scope="module")
def variables():
  return reference.init_variables(jax.random.key(7), reference_config())


@pytest.fixture(scope="module")
def hidden():
  return jnp.asarray(np.random.default_rng(3).standard_normal(
      (2, SIZES["sequence_length"], SIZES["hidden_size"])), jnp.float32)


def _moe_params(p):
  return expert_parallel.MoEParams(
      router=p["router"], bias=p["correction_bias"], gate=p["experts_gate"],
      up=p["experts_up"], down=p["experts_down"])


class TestMLA:

  @pytest.fixture(params=["xla", "pallas"])
  def both(self, request, monkeypatch, variables):
    """(the dense block's attention parameters, the program's MLA, the
    reference's), the program's kernel forced to the XLA path or to the
    Pallas kernel (interpreted here): "auto" would pick for itself."""
    import functools
    from tensor2robot_tpu.ops.flash_attention import flash_attention
    monkeypatch.setattr(sequence, "flash_attention", functools.partial(
        flash_attention, implementation=request.param))
    p = variables["params"]["dense_block0"]["attn"]
    module = sequence.MLAttention(program_config(), jnp.float32)
    ours = lambda p, x: module.apply({"params": p}, x)
    theirs = lambda p, x: jax.vmap(
        lambda row: reference.mla(row, p, reference_config(), "f32"))(x)
    return p, ours, theirs

  def test_forward_matches_reference(self, both, hidden):
    # q/k heads 8 + 4 wide, v heads 8 wide.
    p, ours, theirs = both
    np.testing.assert_allclose(np.asarray(ours(p, hidden)),
                               np.asarray(theirs(p, hidden)), atol=2e-5)

  def test_gradients_match_reference(self, both, hidden):
    p, ours, theirs = both
    weight = jnp.asarray(np.random.default_rng(5).standard_normal(
        hidden.shape), jnp.float32)
    loss = lambda fn: (lambda p, x: jnp.sum(fn(p, x) * weight))
    got = jax.grad(loss(ours), argnums=(0, 1))(p, hidden)
    want = jax.grad(loss(theirs), argnums=(0, 1))(p, hidden)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)

  def test_rotary_turns_interleaved_pairs(self):
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 4)),
                    jnp.float32)
    got = np.asarray(sequence.rotary(x, 100.0))
    for t in range(5):
      for i in range(2):
        angle = t * 100.0 ** (-2 * i / 4)
        a, b = float(x[0, t, 2 * i]), float(x[0, t, 2 * i + 1])
        np.testing.assert_allclose(
            got[0, t, 2 * i:2 * i + 2],
            [a * math.cos(angle) - b * math.sin(angle),
             b * math.cos(angle) + a * math.sin(angle)], atol=1e-5)


class TestRouter:

  def _tokens_and_params(self, variables, hidden):
    p = jax.tree_util.tree_map(
        lambda x: x[0], variables["params"]["expert_blocks"]["moe"])
    return hidden.reshape(-1, hidden.shape[-1]), p

  def test_bias_moves_the_choice_not_the_weights(self, variables, hidden):
    tokens, p = self._tokens_and_params(variables, hidden)
    index, weight = expert_parallel.route(
        tokens, p["router"], p["correction_bias"], 3, 2.5)
    # A bias that lifts expert 9 above every score: chosen by all, at the
    # weight its own score gives.
    lifted = p["correction_bias"].at[9].set(10.0)
    index_l, weight_l = expert_parallel.route(
        tokens, p["router"], lifted, 3, 2.5)
    assert np.all(np.any(np.asarray(index_l) == 9, axis=-1))
    assert not np.all(np.any(np.asarray(index) == 9, axis=-1))
    scores = jax.nn.sigmoid(tokens @ p["router"])
    chosen = jnp.take_along_axis(scores, index_l, axis=-1)
    np.testing.assert_allclose(
        np.asarray(weight_l),
        np.asarray(2.5 * chosen / chosen.sum(-1, keepdims=True)), rtol=1e-5)

  def test_weights_normalized_over_the_chosen_and_scaled(self, variables,
                                                         hidden):
    tokens, p = self._tokens_and_params(variables, hidden)
    index, weight = expert_parallel.route(
        tokens, p["router"], p["correction_bias"], 3, 2.5)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, rtol=1e-5)
    want_index, want_weight = reference.route(tokens, p, reference_config())
    np.testing.assert_array_equal(np.asarray(index), np.asarray(want_index))
    np.testing.assert_allclose(np.asarray(weight), np.asarray(want_weight),
                               rtol=1e-5)

  def test_no_gradient_reaches_the_bias(self, variables, hidden):
    tokens, p = self._tokens_and_params(variables, hidden)
    params = _moe_params(p)
    grads = jax.grad(lambda q: jnp.sum(expert_parallel.moe_share(
        tokens, q, 4, 3, 2.5)[0] ** 2))(params)
    assert float(jnp.max(jnp.abs(grads.bias))) == 0.0
    assert float(jnp.max(jnp.abs(grads.router))) > 0.0


class TestShares:

  def _whole_layer(self, hidden):
    """An uncut layer: all ROUTED experts held, its own seeded weights."""
    config = reference_config(held=ROUTED, first=0)
    variables = reference.init_variables(jax.random.key(11), config)
    p = jax.tree_util.tree_map(
        lambda x: x[1], variables["params"]["expert_blocks"]["moe"])
    return config, p, hidden.reshape(-1, hidden.shape[-1])

  def test_the_shares_add_up_to_the_uncut_layer(self, hidden):
    config, p, tokens = self._whole_layer(hidden)
    want, want_counts = reference.expert_layer(tokens, p, config, "f32")
    shared = reference.gated_mlp(
        tokens, p["shared"]["gate"]["kernel"], p["shared"]["up"]["kernel"],
        p["shared"]["down"]["kernel"], "f32")
    total, counts = shared, []          # the shared expert, once
    for first in range(0, ROUTED, 4):   # four shares of four experts
      part = _moe_params(p)._replace(
          gate=p["experts_gate"][first:first + 4],
          up=p["experts_up"][first:first + 4],
          down=p["experts_down"][first:first + 4])
      y, counters = expert_parallel.moe_share(tokens, part, first, 3, 2.5)
      total = total + y
      counts.append(np.asarray(counters["expert_tokens"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.concatenate(counts).sum()) == tokens.shape[0] * 3

  def test_a_share_matches_the_reference_given_the_same_share(self, hidden):
    config, p, tokens = self._whole_layer(hidden)
    config = dict(config, n_routed_experts=4, first_expert=8)
    part = {k: (v[8:12] if k.startswith("experts_") else v)
            for k, v in p.items()}
    want, want_counts = reference.expert_layer(tokens, part, config, "f32")
    module = sequence.ExpertLayer(program_config(held=4, first=8),
                                  jnp.float32)
    got, counters = module.apply({"params": part}, tokens[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(counters["expert_tokens"]),
                                  np.asarray(want_counts))

  def test_nothing_is_dropped_under_a_router_skewed_onto_one_expert(
      self, hidden):
    config, p, tokens = self._whole_layer(hidden)
    # Every token's first choice is expert 5, whatever it holds.
    skewed = dict(p, correction_bias=p["correction_bias"].at[5].set(10.0))
    part = _moe_params(skewed)._replace(
        gate=p["experts_gate"][4:8], up=p["experts_up"][4:8],
        down=p["experts_down"][4:8])
    y, counters = expert_parallel.moe_share(tokens, part, 4, 3, 2.5)
    assert int(counters["expert_tokens"][1]) == tokens.shape[0]
    assert (int(counters["held_assignments"])
            == int(counters["expert_tokens"].sum()))
    want, _ = reference.expert_layer(
        tokens, dict(skewed, **{k: skewed[k][4:8] for k in (
            "experts_gate", "experts_up", "experts_down")}),
        dict(config, n_routed_experts=4, first_expert=4,
             n_shared_experts=0), "f32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)


class TestModel:

  def _model(self, **changes):
    from tensor2robot_tpu.utils.optimizers import create_adam_optimizer
    sizes = dict(SIZES, n_routed_experts=ROUTED, experts_held=4,
                 first_expert=4)
    sizes.update(changes)
    return SequenceMoEModel(
        optimizer_fn=create_adam_optimizer(learning_rate=1e-3),
        compute_dtype=jnp.float32, **sizes)

  def _tokens(self, steps=2, batch=2):
    return jax.random.randint(
        jax.random.key(1), (steps, batch, SIZES["sequence_length"]), 0,
        SIZES["vocab_size"], jnp.int32)

  def test_parameter_tree_is_the_reference_s(self, variables):
    ours = self._model().init_variables(jax.random.key(0))
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    assert shape(ours) == shape(variables)

  def test_mtp_loss_and_its_shift(self, variables):
    model, config = self._model(), reference_config()
    features = {"tokens": self._tokens()[0]}
    loss, (metrics, _) = model.model_train_fn(
        variables, ts.TensorSpecStruct(features), None)
    outputs, _ = reference.forward(variables, features, True, "f32", config)
    want, parts = reference.loss(outputs, features, None, config)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    np.testing.assert_allclose(float(metrics["loss_mtp"]),
                               float(parts["loss_mtp"]), rtol=1e-5)
    np.testing.assert_allclose(
        float(loss),
        float(metrics["loss_main"] + 0.3 * metrics["loss_mtp"]), rtol=1e-6)
    # The shift: the main head at position i is held against token i+1,
    # the MTP head against token i+2. A change of the LAST token moves
    # the main loss at position T-2 and the MTP loss at T-3 and nothing
    # before them (attention is causal, the last position is not
    # counted).
    tokens = features["tokens"]
    changed = {"tokens": tokens.at[:, -1].set((tokens[:, -1] + 1) % 64)}
    per_position = lambda f: self._model().inference_network_fn(
        variables, ts.TensorSpecStruct(f), "train")[0]
    before, after = per_position(features), per_position(changed)
    moved = lambda name: np.nonzero(np.any(np.abs(np.asarray(
        before[name] - after[name])) > 1e-6, axis=0))[0]
    t = SIZES["sequence_length"]
    assert set(moved("token_loss_main")) <= {t - 2, t - 1}
    assert t - 2 in moved("token_loss_main")
    assert set(moved("token_loss_mtp")) <= {t - 3, t - 2, t - 1}
    assert t - 3 in moved("token_loss_mtp")

  def test_one_dispatch_matches_the_followed_reference(self, variables):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.train.trainer import Trainer
    trainer = Trainer(self._model(), mesh=mesh_lib.create_mesh(
        devices=jax.devices()[:1]))
    state = trainer.create_train_state()
    state = state.replace(params=jax.tree_util.tree_map(
        jnp.copy, variables["params"]))
    tokens = self._tokens()
    state, metrics = trainer.train_steps(
        state, ts.TensorSpecStruct({"tokens": tokens}))
    assert int(state.step) == 2
    # `variables` are the reference's own from this key and configuration.
    key, config = jax.random.key(7), reference_config()
    followed = driver.follow(reference, config, key, {"tokens": tokens})
    _, norms = driver._seed_fns(reference, driver._sizes_json(config))
    first = jax.device_get(dict(
        norms(key, state.params, driver._first_moment(state.opt_state)),
        loss=metrics["loss"], loss_main=metrics["loss_main"],
        loss_mtp=metrics["loss_mtp"],
        expert_tokens=metrics["moe/expert_tokens"]))
    numbers = {name: value for name, value, _ in driver.compare(
        first, followed, {})}
    for name in ("last_loss_gap", "last_main_loss_gap", "last_mtp_loss_gap",
                 "moment_norm_gap", "change_norm_gap", "moment_own_gap",
                 "change_own_gap"):
      assert numbers[name] < 1e-3, (name, numbers)
    assert numbers["expert_count_gap"] == 0.0
    assert int(metrics["moe/total_assignments"]) == 3 * 2 * 32 * 3

  def test_step_metrics_carry_the_expert_layers_counters(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.train.trainer import Trainer
    trainer = Trainer(self._model(), mesh=mesh_lib.create_mesh(
        devices=jax.devices()[:1]))
    state = trainer.create_train_state()
    _, metrics = trainer.train_steps(
        state, ts.TensorSpecStruct({"tokens": self._tokens()}))
    per_expert = np.asarray(metrics["moe/expert_tokens"])
    assert per_expert.shape == (3, 4)   # 2 expert blocks + MTP's, 4 held
    assert int(metrics["moe/held_assignments"]) == per_expert.sum()
    assert int(metrics["moe/max_expert_tokens"]) == per_expert.max()
    assert int(metrics["moe/min_expert_tokens"]) == per_expert.min()
    assert (0 < per_expert.sum()
            < int(metrics["moe/total_assignments"]) == 3 * 2 * 32 * 3)

"""The hybrid sequence model (gated delta nets and gated grouped-query
attention over a softmax-routed expert layer with a gated shared expert)
against its plain reference (benchmark/reference/qwen3_next_80b_a3b_ep16.py)
at tiny sizes on the CPU, seeded weights: the chunked delta rule against
the token-by-token recurrence, the Pallas programs (interpreted here)
against the chunked XLA form, the causal convolution, partial rotary,
grouped queries through the flash kernel, softmax routing, the shares of
the expert layer adding up to the uncut layer, the layer pattern, and
one `Trainer.train_steps` dispatch against the reference following the
same steps."""

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_resident_hybrid as driver
from benchmark.tests.tiny_hybrid import SIZES
from tensor2robot_tpu.layers import sequence
from tensor2robot_tpu.parallel import expert_parallel
from tensor2robot_tpu.research.seqlm.seqlm_model import SequenceMoEModel
from tensor2robot_tpu.specs import tensorspec_utils as ts

reference = importlib.import_module(
    "benchmark.reference.qwen3_next_80b_a3b_ep16")
# `ops/__init__.py` re-exports the function under the module's name.
flash_lib = importlib.import_module("tensor2robot_tpu.ops.flash_attention")
rule_lib = importlib.import_module("tensor2robot_tpu.ops.gated_delta_rule")

ROUTED = 16
_PROGRAM_ONLY = dict(scoring_func="softmax", routed_scaling_factor=1.0,
                     first_k_dense_replace=0, num_nextn_predict_layers=0,
                     rope_theta=1e4, rms_norm_eps=1e-6,
                     zero_centered_norm=True)


def reference_config(held=4, first=4, **changes):
  config = dict(
      SIZES, num_experts=held, router_width=ROUTED, first_expert=first,
      norm_topk_prob=True, rope_theta=1e4, rms_norm_eps=1e-6,
      optimizer={"kind": "adam", "learning_rate": 1e-3})
  config.update(changes)
  return config


def program_config(held=4, first=4, **changes):
  sizes = {k: v for k, v in SIZES.items() if k != "sequence_length"}
  sizes.update(_PROGRAM_ONLY, n_routed_experts=ROUTED, experts_held=held,
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


def _block(variables, i):
  """Block i of the first period's parameters."""
  return jax.tree_util.tree_map(
      lambda x: x[0], variables["params"]["periods"][f"block{i}"])


def _assert_close(got, want, atol):
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol)


# --- the gated delta rule ------------------------------------------------------


def _rule_inputs(t, key_heads=1, value_heads=2, width=128, seed=0):
  """q, k as the rule takes them (unit k, q over sqrt(width)), decays
  from nearly none to nearly all."""
  r = np.random.default_rng(seed)
  unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
  q = unit(r.standard_normal((1, t, key_heads, width))) / math.sqrt(width)
  k = unit(r.standard_normal((1, t, key_heads, width)))
  v = r.standard_normal((1, t, value_heads, width))
  g = -np.exp(r.uniform(-4, 2, (1, t, value_heads)))
  beta = r.uniform(0, 1, (1, t, value_heads))
  return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _token_by_token(q, k, v, g, beta):
  serves = v.shape[2] // q.shape[2]
  return jax.vmap(lambda q, k, v, g, b: reference.delta_rule(
      jnp.repeat(q, serves, 1), jnp.repeat(k, serves, 1), v, g, b))(
          q, k, v, g, beta)


def _weighted(fn, shape):
  weight = jnp.asarray(np.random.default_rng(5).standard_normal(shape),
                       jnp.float32)
  return lambda *args: jnp.sum(fn(*args) * weight)


class TestGatedDeltaRule:

  # T of one chunk, of one short chunk, and of several (two grid steps'
  # worth at 1024: the state crosses from one to the next).
  @pytest.mark.parametrize("t", [64, 32, 192, 1024])
  @pytest.mark.parametrize("implementation", ["xla", "pallas"])
  def test_forward_and_gradients_match_the_recurrence(self, t,
                                                      implementation):
    args = _rule_inputs(t)
    rule = functools.partial(rule_lib.gated_delta_rule,
                             implementation=implementation)
    want = _token_by_token(*args)
    np.testing.assert_allclose(np.asarray(rule(*args)), np.asarray(want),
                               atol=2e-6)
    every = tuple(range(5))
    got = jax.grad(_weighted(rule, want.shape), every)(*args)
    wanted = jax.grad(_weighted(_token_by_token, want.shape), every)(*args)
    for g, w in zip(got, wanted):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7)

  def test_pallas_programs_match_the_xla_walk_in_bfloat16(self):
    q, k, v, g, beta = _rule_inputs(256)
    args = (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), g, beta)
    run = lambda impl: rule_lib.gated_delta_rule(*args, implementation=impl)
    np.testing.assert_allclose(
        np.asarray(run("pallas"), np.float32),
        np.asarray(run("xla"), np.float32), atol=2e-3)
    loss = lambda impl: (lambda *a: jnp.sum(rule_lib.gated_delta_rule(
        *a, implementation=impl).astype(jnp.float32) ** 2))
    got = jax.grad(loss("pallas"), (0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss("xla"), (0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
      scale = float(jnp.max(jnp.abs(b.astype(jnp.float32))))
      np.testing.assert_allclose(np.asarray(a, np.float32),
                                 np.asarray(b, np.float32), atol=0.03 * scale)

  # (key heads, value heads, T): each key head serving one value head and
  # two; one grid step of chunks (one chunk, three) and several (16
  # chunks, two steps).
  @pytest.mark.parametrize("key_heads, value_heads, t",
                           [(2, 2, 64), (1, 2, 192), (2, 2, 1024),
                            (1, 2, 1024)])
  def test_prep_programs_match_the_xla_chunk_local_part(self, key_heads,
                                                        value_heads, t):
    args = _rule_inputs(t, key_heads=key_heads, value_heads=value_heads)
    chunk = min(rule_lib.CHUNK, t)
    xla = lambda *a: rule_lib._prepare(*a, chunk)
    want, xla_vjp = jax.vjp(xla, *args)
    got, prep_vjp = jax.vjp(rule_lib._prep_pallas, *args)
    # The running sums of g are summed in another order: each decay to
    # a few float32 roundings of G, relative.
    for g, w in zip(got, want):
      assert g.shape == w.shape and g.dtype == w.dtype
      np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                 atol=2e-6 * float(jnp.max(jnp.abs(w))))
    r = np.random.default_rng(11)
    cotangents = tuple(jnp.asarray(r.standard_normal(w.shape), w.dtype)
                       for w in want)
    for g, w in zip(prep_vjp(cotangents), xla_vjp(cotangents)):
      assert g.shape == w.shape and g.dtype == w.dtype
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=2e-5 * float(jnp.max(jnp.abs(w))))

  @pytest.mark.parametrize("implementation, taken",
                           [("pallas", "prep_programs"), ("xla", "prep_xla"),
                            ("auto", "prep_xla")])
  def test_the_counters_read_the_path_each_call_took(self, implementation,
                                                      taken):
    """Off a TPU "auto" is XLA's. One count a traced call: a jit traced
    once and run twice counts once."""
    from tensor2robot_tpu.obs.registry import get_registry
    counters = {path: get_registry().counter(f"gated_delta_rule/{path}")
                for path in ("prep_programs", "prep_xla")}
    before = {path: c.value for path, c in counters.items()}
    run = jax.jit(functools.partial(rule_lib.gated_delta_rule,
                                    implementation=implementation))
    args = _rule_inputs(64)
    run(*args)
    run(*args)
    assert {path: c.value - before[path] for path, c in counters.items()} == {
        path: int(path == taken) for path in counters}

  def test_without_decay_and_full_writes_it_is_the_plain_delta_rule(self):
    q, k, v, _, _ = _rule_inputs(128)
    zeros, ones = jnp.zeros(v.shape[:3]), jnp.ones(v.shape[:3])
    got = rule_lib.gated_delta_rule(q, k, v, zeros, ones,
                                    implementation="xla")
    # S_t = S_{t-1} + k_t (v_t - S_{t-1}^T k_t)^T, by hand, head 0.
    state = np.zeros((128, 128))
    for t in range(128):
      k_t, v_t = np.asarray(k[0, t, 0], np.float64), np.asarray(v[0, t, 0])
      state = state + np.outer(k_t, v_t - state.T @ k_t)
      np.testing.assert_allclose(np.asarray(got[0, t, 0]),
                                 state.T @ np.asarray(q[0, t, 0]), atol=1e-5)

  def test_without_writes_the_state_only_decays(self):
    q, k, v, g, beta = _rule_inputs(128)
    # Writes on the first token only: every later output is that one
    # write, decayed by the running sum of g.
    first_only = jnp.zeros_like(beta).at[:, 0].set(1.0)
    got = rule_lib.gated_delta_rule(q, k, v, g, first_only,
                                    implementation="xla")
    decay = np.exp(np.cumsum(np.asarray(g[0, :, 0]))
                   - float(g[0, 0, 0]))
    want = (np.asarray(q[0, :, 0]) @ np.asarray(k[0, 0, 0]))[:, None] * (
        np.asarray(v[0, 0, 0])[None, :]) * decay[:, None]
    np.testing.assert_allclose(np.asarray(got[0, :, 0]), want, atol=1e-6)

  def test_a_shape_the_programs_cannot_take_raises_when_forced(self):
    q, k, v, g, beta = _rule_inputs(64, width=8)
    with pytest.raises(ValueError, match="multiples of 128"):
      rule_lib.gated_delta_rule(q, k, v, g, beta, implementation="pallas")
    with pytest.raises(ValueError, match="multiple of the chunk"):
      rule_lib.gated_delta_rule(*_rule_inputs(96), implementation="xla")


class TestGatedDeltaNet:

  def _both(self, variables):
    p = _block(variables, 0)["attn"]
    module = sequence.GatedDeltaNet(program_config(), jnp.float32)
    ours = lambda p, x: module.apply({"params": p}, x)[0]
    theirs = lambda p, x: jax.vmap(lambda row: reference.gated_delta_net(
        row, p, reference_config(), "f32")[0])(x)
    return p, ours, theirs

  def test_forward_and_gradients_match_reference(self, variables, hidden):
    p, ours, theirs = self._both(variables)
    np.testing.assert_allclose(np.asarray(ours(p, hidden)),
                               np.asarray(theirs(p, hidden)), atol=2e-5)
    got = jax.grad(_weighted(ours, hidden.shape), (0, 1))(p, hidden)
    want = jax.grad(_weighted(theirs, hidden.shape), (0, 1))(p, hidden)
    _assert_close(got, want, 2e-4)

  def test_gate_means_are_the_reference_s(self, variables, hidden):
    p = _block(variables, 1)["attn"]
    _, gates = sequence.GatedDeltaNet(program_config(), jnp.float32).apply(
        {"params": p}, hidden[:1])
    _, decay, beta = reference.gated_delta_net(
        hidden[0], p, reference_config(), "f32")
    np.testing.assert_allclose(float(gates["gdn/decay_mean"]), float(decay),
                               rtol=1e-5)
    np.testing.assert_allclose(float(gates["gdn/beta_mean"]), float(beta),
                               rtol=1e-5)
    assert 0.0 < float(decay) < 1.0 and 0.0 < float(beta) < 1.0

  def test_the_convolution_never_reads_ahead(self, variables, hidden):
    p, ours, _ = self._both(variables)
    later = hidden.at[:, 20:].add(1.0)
    before, after = ours(p, hidden), ours(p, later)
    np.testing.assert_array_equal(np.asarray(before[:, :20]),
                                  np.asarray(after[:, :20]))
    assert float(jnp.max(jnp.abs(before[:, 20:] - after[:, 20:]))) > 1e-3
    # And the convolution itself: tap j weighs x[t - 3 + j].
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 6, 3)),
                    jnp.float32)
    kernel = jnp.asarray(np.random.default_rng(1).standard_normal((4, 3)),
                         jnp.float32)
    got = np.asarray(sequence.causal_conv(x, kernel))
    for t in range(6):
      want = sum(np.asarray(kernel[j]) * np.asarray(x[0, t - 3 + j])
                 for j in range(4) if t - 3 + j >= 0)
      np.testing.assert_allclose(got[0, t], want, atol=1e-6)


# --- gated attention ---------------------------------------------------------


class TestGatedAttention:

  @pytest.fixture(params=["xla", "pallas"])
  def both(self, request, monkeypatch, variables):
    # The module names the path itself ("auto" off a TPU): overridden.
    monkeypatch.setattr(
        sequence, "flash_attention",
        lambda *args, **kwargs: flash_lib.flash_attention(
            *args, **dict(kwargs, implementation=request.param)))
    p = _block(variables, 3)["attn"]
    module = sequence.GatedAttention(program_config(), jnp.float32)
    ours = lambda p, x: module.apply({"params": p}, x)
    theirs = lambda p, x: jax.vmap(lambda row: reference.gated_attention(
        row, p, reference_config(), "f32"))(x)
    return p, ours, theirs

  def test_forward_matches_reference(self, both, hidden):
    p, ours, theirs = both
    np.testing.assert_allclose(np.asarray(ours(p, hidden)),
                               np.asarray(theirs(p, hidden)), atol=2e-5)

  def test_gradients_match_reference(self, both, hidden):
    p, ours, theirs = both
    got = jax.grad(_weighted(ours, hidden.shape), (0, 1))(p, hidden)
    want = jax.grad(_weighted(theirs, hidden.shape), (0, 1))(p, hidden)
    _assert_close(got, want, 1e-4)

  def test_rotary_turns_the_first_part_in_half_split_pairs(self):
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 16)),
                    jnp.float32)
    got = np.asarray(sequence.rotary_half_split(x, 100.0, 4))
    for t in range(5):
      for i in range(2):
        angle = t * 100.0 ** (-2 * i / 4)
        a, b = np.asarray(x[0, t, :, i]), np.asarray(x[0, t, :, i + 2])
        np.testing.assert_allclose(
            got[0, t, :, i], a * math.cos(angle) - b * math.sin(angle),
            atol=1e-5)
        np.testing.assert_allclose(
            got[0, t, :, i + 2], b * math.cos(angle) + a * math.sin(angle),
            atol=1e-5)
    np.testing.assert_array_equal(got[..., 4:], np.asarray(x[..., 4:]))

  def test_each_key_value_head_serves_its_group_of_query_heads(self):
    r = np.random.default_rng(2)
    draw = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    q, k, v = draw(1, 128, 8, 16), draw(1, 128, 2, 16), draw(1, 128, 2, 16)
    got = flash_lib.flash_attention(q, k, v, causal=True,
                                    implementation="pallas")
    for head in range(8):
      alone = flash_lib.flash_attention_reference(
          q[:, :, head:head + 1], k[:, :, head // 4:head // 4 + 1],
          v[:, :, head // 4:head // 4 + 1], causal=True)
      np.testing.assert_allclose(np.asarray(got[:, :, head:head + 1]),
                                 np.asarray(alone), atol=2e-6)
    # dK, dV: the group's parts summed.
    loss = lambda impl: (lambda q, k, v: jnp.sum(flash_lib.flash_attention(
        q, k, v, causal=True, implementation=impl) ** 2))
    got = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
    want = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    _assert_close(got, want, 2e-4)

  def test_flash_path_at_256_wide_heads_matches_the_reference(self):
    r = np.random.default_rng(4)
    draw = lambda heads: jnp.asarray(
        r.standard_normal((1, 256, heads, 256)), jnp.float32)
    q, k, v = draw(4), draw(2), draw(2)
    got = flash_lib.flash_attention(q, k, v, causal=True,
                                    implementation="pallas")
    want = flash_lib.flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

  def test_the_kernel_takes_this_model_s_shapes_at_8k(self):
    shape = lambda heads: jax.ShapeDtypeStruct((1, 8192, heads, 256),
                                               jnp.bfloat16)
    assert flash_lib._supported(shape(16), shape(2), shape(2)) is None
    assert "VMEM" in flash_lib._supported(
        jax.ShapeDtypeStruct((1, 16384, 16, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 16384, 2, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 16384, 2, 256), jnp.bfloat16))
    assert "multiple" in flash_lib._supported(shape(16), shape(3), shape(3))

  def test_on_a_tpu_a_refused_shape_raises(self, monkeypatch, variables):
    """Never the (H, T, T) scores in silence: T = 1100 is neither
    blockable nor one block."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = _block(variables, 3)["attn"]
    module = sequence.GatedAttention(program_config(), jnp.float32)
    x = jnp.zeros((1, 1100, SIZES["hidden_size"]), jnp.float32)
    with pytest.raises(ValueError, match="pallas path"):
      jax.eval_shape(lambda: module.apply({"params": p}, x))


# --- routing and the expert layer --------------------------------------------


def _moe_params(p):
  return expert_parallel.MoEParams(
      router=p["router"], bias=None, gate=p["experts_gate"],
      up=p["experts_up"], down=p["experts_down"])


class TestSoftmaxRouter:

  def _tokens_and_params(self, variables, hidden):
    return hidden.reshape(-1, hidden.shape[-1]), _block(variables, 0)["moe"]

  def test_weights_sum_to_one_over_the_chosen(self, variables, hidden):
    tokens, p = self._tokens_and_params(variables, hidden)
    index, weight = expert_parallel.route(tokens, p["router"], None, 3,
                                          scoring="softmax")
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 1.0, rtol=1e-6)
    scores = jax.nn.softmax(tokens @ p["router"], axis=-1)
    top, want_index = jax.lax.top_k(scores, 3)
    np.testing.assert_array_equal(np.asarray(index), np.asarray(want_index))
    np.testing.assert_allclose(
        np.asarray(weight), np.asarray(top / top.sum(-1, keepdims=True)),
        rtol=1e-5)

  def test_matches_reference_and_reads_no_bias(self, variables, hidden):
    tokens, p = self._tokens_and_params(variables, hidden)
    index, weight = expert_parallel.route(tokens, p["router"], None, 3,
                                          scoring="softmax")
    want_index, want_weight = reference.route(tokens, p, reference_config())
    np.testing.assert_array_equal(np.asarray(index), np.asarray(want_index))
    np.testing.assert_allclose(np.asarray(weight), np.asarray(want_weight),
                               rtol=1e-5)
    # A bias that would carry every choice under sigmoid scoring.
    lifted = jnp.zeros((ROUTED,)).at[9].set(10.0)
    again, _ = expert_parallel.route(tokens, p["router"], lifted, 3,
                                     scoring="softmax")
    np.testing.assert_array_equal(np.asarray(again), np.asarray(index))

  def test_an_unknown_scoring_raises(self, variables, hidden):
    tokens, p = self._tokens_and_params(variables, hidden)
    with pytest.raises(ValueError, match="scoring"):
      expert_parallel.route(tokens, p["router"], None, 3, scoring="tanh")


class TestShares:

  def _whole_layer(self, hidden):
    """An uncut layer: all ROUTED experts held, its own seeded weights."""
    config = reference_config(held=ROUTED, first=0)
    variables = reference.init_variables(jax.random.key(11), config)
    return config, _block(variables, 1)["moe"], hidden.reshape(
        -1, hidden.shape[-1])

  def test_the_shares_add_up_to_the_uncut_layer(self, hidden):
    config, p, tokens = self._whole_layer(hidden)
    want, want_counts = reference.expert_layer(tokens, p, config, "f32")
    shared = reference.gated_mlp(
        tokens, p["shared"]["gate"]["kernel"], p["shared"]["up"]["kernel"],
        p["shared"]["down"]["kernel"], "f32") * jax.nn.sigmoid(
            tokens @ p["shared_gate"]["kernel"])
    total, counts = shared, []      # the gated shared expert, once
    for first in range(0, ROUTED, 4):   # four shares of four experts
      part = _moe_params(p)._replace(
          gate=p["experts_gate"][first:first + 4],
          up=p["experts_up"][first:first + 4],
          down=p["experts_down"][first:first + 4])
      y, counters = expert_parallel.moe_share(tokens, part, first, 3,
                                              scoring="softmax")
      total = total + y
      counts.append(np.asarray(counters["expert_tokens"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.concatenate(counts).sum()) == tokens.shape[0] * 3

  def test_a_share_matches_the_reference_given_the_same_share(self, hidden):
    config, p, tokens = self._whole_layer(hidden)
    config = dict(config, num_experts=4, first_expert=8)
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

  def test_the_shared_expert_is_gated(self, hidden):
    config, p, tokens = self._whole_layer(hidden)
    module = sequence.ExpertLayer(program_config(held=ROUTED, first=0),
                                  jnp.float32)
    gated, _ = module.apply({"params": p}, tokens[None])
    # A gate shut hard leaves the routed experts' part alone.
    shut = dict(p, shared_gate={"kernel": jnp.zeros_like(
        p["shared_gate"]["kernel"])})
    half, _ = module.apply({"params": shut}, tokens[None])
    routed, _ = expert_parallel.moe_share(tokens, _moe_params(p), 0, 3,
                                          scoring="softmax")
    shared = reference.gated_mlp(
        tokens, p["shared"]["gate"]["kernel"], p["shared"]["up"]["kernel"],
        p["shared"]["down"]["kernel"], "f32")
    np.testing.assert_allclose(np.asarray(half[0]),
                               np.asarray(routed + 0.5 * shared), atol=2e-5)
    assert float(jnp.max(jnp.abs(gated - half))) > 1e-3


# --- the model -----------------------------------------------------------------


class TestModel:

  def _model(self, **changes):
    from tensor2robot_tpu.utils.optimizers import create_adam_optimizer
    sizes = dict(SIZES, **_PROGRAM_ONLY, n_routed_experts=ROUTED,
                 experts_held=4, first_expert=4)
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

  @pytest.mark.parametrize("layers", [4, 8])
  def test_the_layer_pattern_is_three_linear_to_one_full(self, layers):
    config = program_config(num_hidden_layers=layers)
    assert [config.layer_kind(i) for i in range(layers)] == (
        ["linear", "linear", "linear", "full"] * (layers // 4))
    assert [reference.is_full(reference_config(), i)
            for i in range(4)] == [False, False, False, True]
    params = self._model(num_hidden_layers=layers).init_variables(
        jax.random.key(0))["params"]
    assert set(params) == {"embed", "periods", "final_norm", "head"}
    period = params["periods"]
    assert sorted(period) == ["block0", "block1", "block2", "block3"]
    for i in range(3):
      assert "A_log" in period[f"block{i}"]["attn"]
      assert period[f"block{i}"]["attn"]["A_log"].shape[0] == layers // 4
    assert "q_norm" in period["block3"]["attn"]
    assert "correction_bias" not in period["block0"]["moe"]

  def test_the_mla_layout_is_what_it_was(self):
    """JoyAI's configuration builds the program it built before: MLA in
    every block, a correction bias, an ungated shared expert, an MTP
    module, plain norms (scale from 1)."""
    from benchmark.tests.tiny_tokens import SIZES as MLA
    from tensor2robot_tpu.utils.optimizers import create_adam_optimizer
    model = SequenceMoEModel(
        optimizer_fn=create_adam_optimizer(learning_rate=1e-3),
        compute_dtype=jnp.float32,
        **dict(MLA, n_routed_experts=ROUTED, experts_held=4, first_expert=4))
    params = model.init_variables(jax.random.key(0))["params"]
    assert set(params) == {"embed", "dense_block0", "expert_blocks",
                           "final_norm", "head", "mtp"}
    moe = params["expert_blocks"]["moe"]
    assert "correction_bias" in moe and "shared_gate" not in moe
    assert "q_a" in params["expert_blocks"]["attn"]
    assert float(params["final_norm"]["scale"][0]) == 1.0

  def test_loss_matches_reference_and_has_no_mtp_part(self, variables):
    model, config = self._model(), reference_config()
    features = {"tokens": self._tokens()[0]}
    loss, (metrics, _) = model.model_train_fn(
        variables, ts.TensorSpecStruct(features), None)
    outputs, _ = reference.forward(variables, features, True, "f32", config)
    want, _ = reference.loss(outputs, features, None, config)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert "loss_mtp" not in metrics
    np.testing.assert_allclose(float(metrics["loss_main"]), float(loss))

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
    key, config = jax.random.key(7), reference_config()
    followed = driver.follow(reference, config, key, {"tokens": tokens})
    _, norms = driver._seed_fns(reference, driver._sizes_json(config))
    first = jax.device_get(dict(
        norms(key, state.params, driver._first_moment(state.opt_state)),
        loss=metrics["loss"], expert_tokens=metrics["moe/expert_tokens"],
        gdn_decay_mean=metrics["gdn/decay_mean"],
        gdn_beta_mean=metrics["gdn/beta_mean"]))
    numbers = {name: value for name, value, _ in driver.compare(
        first, followed, {})}
    for name in ("last_loss_gap", "moment_norm_gap", "change_norm_gap",
                 "moment_own_gap", "change_own_gap", "gdn_decay_gap",
                 "gdn_beta_gap"):
      assert numbers[name] < 1e-3, (name, numbers)
    assert numbers["expert_count_gap"] == 0.0
    assert int(metrics["moe/total_assignments"]) == 4 * 2 * 32 * 3

  def test_step_metrics_carry_the_gates_and_the_expert_counters(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.train.trainer import Trainer
    trainer = Trainer(self._model(), mesh=mesh_lib.create_mesh(
        devices=jax.devices()[:1]))
    state = trainer.create_train_state()
    _, metrics = trainer.train_steps(
        state, ts.TensorSpecStruct({"tokens": self._tokens()}))
    per_expert = np.asarray(metrics["moe/expert_tokens"])
    assert per_expert.shape == (4, 4)   # 4 layers, 4 held
    assert int(metrics["moe/held_assignments"]) == per_expert.sum()
    for name in ("gdn/decay_mean", "gdn/beta_mean"):
      values = np.asarray(metrics[name])
      assert values.shape == (3,) and values.dtype == np.float32
      assert np.all((values > 0) & (values < 1)), (name, values)
    assert "loss_mtp" not in metrics


@pytest.mark.parametrize("cfg, benchmark_config", [
    ("qwen3_next_ep16_train.cfg", "qwen3_next_80b_a3b_ep16"),
    ("joyai_flash_ep16_train.cfg", "joyai_llm_flash_ep16"),
    ("xing4_tp8ep8_train.cfg", "xing4_0_29b_a4b_tp8ep8"),
])
def test_trainer_config_builds_the_benchmark_s_model(cfg, benchmark_config):
  """The `.cfg` that `bin/run_t2r_trainer` takes and the configuration
  file that `harness.build_model` takes name one model."""
  import json
  import os
  from tensor2robot_tpu.config import config as cfg_lib
  from tensor2robot_tpu.config import registrations  # noqa: F401
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  with open(os.path.join(root, "benchmark", "configs",
                         benchmark_config + ".json")) as f:
    kwargs = json.load(f)["model"]["kwargs"]
  try:
    cfg_lib.parse_config_files_and_bindings([os.path.join(
        root, "tensor2robot_tpu", "research", "seqlm", "configs", cfg)], [])
    model = cfg_lib.query_binding("train_eval_model.model")
  finally:
    cfg_lib.clear_config()
  assert isinstance(model, SequenceMoEModel)
  sizes = {k: v for k, v in kwargs.items() if k != "sequence_length"}
  assert model.config == sequence.SequenceConfig(**sizes)

"""The dense sparse/linear-attention sequence model (MiniCPM-SALA's layout:
block-sparse attention in one of four layers, Lightning attention in the
rest, a dense gated MLP, muP scales) against its plain reference
(benchmark/reference/minicpm_sala_tp2.py) at tiny sizes on the CPU, seeded
weights: the selection against a count by hand, both kernels (interpreted
here) against their plain forms, forward and backward, the tensor-parallel
shares adding up to the uncut layer, the layer pattern, the loss, and one
`Trainer.train_steps` dispatch against the reference following the same
steps."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_resident_sala as driver
from benchmark.tests.tiny_sala import SIZES, SPARSE
from tensor2robot_tpu.layers import sequence
from tensor2robot_tpu.ops import lightning_attention as lightning_lib
from tensor2robot_tpu.ops import sparse_attention as sparse_lib
from tensor2robot_tpu.research.seqlm.seqlm_model import SequenceMoEModel
from tensor2robot_tpu.specs import tensorspec_utils as ts

reference = importlib.import_module("benchmark.reference.minicpm_sala_tp2")
rule_lib = importlib.import_module("tensor2robot_tpu.ops.gated_delta_rule")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
WIDTH = 24
_PROGRAM_ONLY = dict(
    rms_norm_eps=1e-6, rope_theta=1e4, mixer_types=MIXERS, qk_norm=True,
    attn_use_rope=False, attn_use_output_gate=True, sparse_config=SPARSE,
    lightning_use_rope=True, use_output_gate=True, use_output_norm=True,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=8,
    published_lightning_nh=32, published_num_hidden_layers=32,
    n_routed_experts=0, experts_held=0, first_k_dense_replace=0,
    num_nextn_predict_layers=0)
_SELECT = dict(block=8, kernel=4, stride=2, top_k=4, init_blocks=1,
               window=16, dense_len=0)


def reference_config(first_head=0, **changes):
  config = dict(
      SIZES, mixer_types=MIXERS, intermediate_held=WIDTH,
      first_head=first_head, sparse_config=SPARSE, rms_norm_eps=1e-6,
      rope_theta=1e4, scale_emb=12.0, scale_depth=1.4, dim_model_base=8,
      published={"num_hidden_layers": 32, "lightning_nh": 32},
      optimizer={"kind": "adam", "learning_rate": 1e-3})
  config.update(changes)
  return config


def program_sizes(**changes):
  sizes = {k: v for k, v in SIZES.items() if k != "sequence_length"}
  sizes.update(_PROGRAM_ONLY, intermediate_size=WIDTH)
  sizes.update(changes)
  return sizes


@pytest.fixture(scope="module")
def variables():
  return reference.init_variables(jax.random.key(7), reference_config())


@pytest.fixture(scope="module")
def hidden():
  return jnp.asarray(np.random.default_rng(3).standard_normal(
      (2, SIZES["sequence_length"], SIZES["hidden_size"])), jnp.float32)


def _weighted(fn, shape, seed=5):
  weight = jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)
  return lambda *args: jnp.sum(fn(*args) * weight)


# --- the selection and the block-sparse kernel ---------------------------------


def _qk(t=256, heads=4, kv=2, width=16, seed=0, batch=1):
  r = np.random.default_rng(seed)
  q = jnp.asarray(r.standard_normal((batch, t, heads, width)), jnp.float32)
  k = jnp.asarray(r.standard_normal((batch, t, kv, width)), jnp.float32)
  return q, k


class TestSelection:

  def test_matches_a_count_by_hand(self):
    """Compressed keys, the group's summed softmax, a block's best key,
    the forced blocks and the exact top-k, query by query."""
    q, k = _qk(batch=2)
    selected, mass = sparse_lib.select_blocks(q, k, **_SELECT)
    t, nb = 256, 32
    keys = np.asarray(sparse_lib.compressed_keys(k, 4, 2))
    assert keys.shape == (2, 2, 127, 16)
    np.testing.assert_allclose(keys[1, 1, 5], np.asarray(k)[1, 10:14, 1].mean(0),
                               atol=1e-6)
    for b, h, row in ((0, 0, 40), (1, 1, 200), (0, 1, 255)):
      group = np.asarray(q)[b, row, 2 * h:2 * h + 2]
      scores = group @ keys[b, h].T / 4.0                  # (2, NC)
      seen = np.arange(127) * 2 + 3 <= row
      weights = np.exp(scores - scores.max(-1, keepdims=True)) * seen
      s = (weights / weights.sum(-1, keepdims=True)).sum(0)
      best = np.array([max(s[j] for j in range(127)
                           if 2 * j <= 8 * blk + 7 and 2 * j + 3 >= 8 * blk)
                       for blk in range(nb)])
      causal = np.arange(nb) * 8 <= row
      forced = causal & ((np.arange(nb) < 1)
                         | ((np.arange(nb) + 1) * 8 > row - 15))
      candidates = np.where(causal & ~forced)[0]
      top = candidates[np.argsort(-best[candidates], kind="stable")[:4]]
      want = forced.copy()
      want[top] = True
      np.testing.assert_array_equal(np.asarray(selected[b, h, row]), want)
    assert 0.0 < float(mass) < 1.0
    # Under dense_len every causal block, and the whole mass.
    dense, whole = sparse_lib.select_blocks(q, k, **dict(_SELECT,
                                                         dense_len=512))
    assert float(whole) == 1.0
    np.testing.assert_array_equal(
        np.asarray(dense[0, 0]),
        np.arange(nb)[None, :] * 8 <= np.arange(t)[:, None])

  def test_takes_no_gradient(self):
    q, k = _qk()
    grads = jax.grad(lambda q, k: sparse_lib.select_blocks(
        q, k, **_SELECT)[1], (0, 1))(q, k)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in grads)


class TestBlockSparseKernel:

  @pytest.mark.parametrize("per_visit, paired", [
      (1, False),   # a block an iteration
      (2, True),    # blocks selected in pairs: every union a multiple of 2
      (3, False),   # a padded last iteration; rows of 32 padded to 33
  ])
  @pytest.mark.parametrize("heads, kv, batch", [(4, 2, 1), (8, 1, 2)])
  def test_kernel_matches_the_masked_softmax(self, heads, kv, batch,
                                             per_visit, paired):
    """Forward and every gradient; tiles of 8 tokens whose unions the
    bits cut back to each token's own blocks, `per_visit` blocks a loop
    iteration."""
    q, k = _qk(heads=heads, kv=kv, batch=batch, width=128, seed=1)
    v = jnp.asarray(np.random.default_rng(2).standard_normal(k.shape),
                    jnp.float32)
    selected, _ = sparse_lib.select_blocks(q, k, **_SELECT)
    if paired:   # each block with its pair (a later block's keys are masked)
      pairs = jnp.any(selected.reshape(selected.shape[:-1] + (16, 2)), -1)
      selected = jnp.repeat(pairs, 2, axis=-1)
    counts = sparse_lib.plan(selected.reshape(-1, 256, 32))[2]
    assert bool(jnp.any(counts % per_visit)) == (per_visit == 3)
    run = lambda impl: (lambda q, k, v: sparse_lib.block_sparse_attention(
        q, k, v, selected, block=8, scale=0.1, implementation=impl,
        _per_visit=per_visit)[0])
    want = run("xla")(q, k, v)
    np.testing.assert_allclose(np.asarray(run("pallas")(q, k, v)),
                               np.asarray(want), atol=1e-5)
    got = jax.grad(_weighted(run("pallas"), want.shape), (0, 1, 2))(q, k, v)
    wanted = jax.grad(_weighted(run("xla"), want.shape), (0, 1, 2))(q, k, v)
    for g, w in zip(got, wanted):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=1e-5 * float(jnp.max(jnp.abs(w))))

  @pytest.mark.parametrize("per_visit", [1, 3])
  def test_a_token_sees_only_its_own_blocks(self, per_visit):
    """A key outside every block the token selected moves nothing of its
    output, though its tile visits that block for another token; nor do
    the slots that pad a tile's union to whole iterations."""
    q, k = _qk(heads=2, kv=1, width=128)
    v = jnp.zeros(k.shape, jnp.float32)
    selected = jnp.zeros((1, 1, 256, 32), bool).at[..., 0].set(True)
    selected = selected.at[0, 0, 100, 5].set(True)   # token 100 alone
    v = v.at[0, 45, 0].set(1.0)                        # a key in block 5
    out, visits = sparse_lib.block_sparse_attention(
        q, k, v, selected, block=8, scale=0.1, implementation="pallas",
        _per_visit=per_visit)
    moved = np.flatnonzero(np.abs(np.asarray(out[0, :, :, :])).sum((1, 2)))
    np.testing.assert_array_equal(moved, [100])
    # 32 tiles visit block 0; the tile of token 100 visits block 5 too.
    assert float(visits["visited"]) == (32 + 1) * 8
    assert float(visits["selected"]) == 256 + 1
    # Unions of 1 and 2 blocks, each a whole iteration at 3.
    slots = {1: 32 + 1, 3: 32 * 3}[per_visit] * 8
    assert float(visits["slots"]) == slots

  def test_the_plan_lists_each_tile_s_union_in_order(self):
    selected = np.zeros((1, 16, 6), bool)
    selected[0, 0, [0, 3]] = selected[0, 5, [0, 1]] = True
    selected[0, 8:, 0] = selected[0, 9, 4] = True
    blocks, bits, counts, visited, chosen, slots = sparse_lib.plan(
        jnp.asarray(selected), tokens=8)
    assert list(np.asarray(counts)) == [3, 2]
    assert list(np.asarray(blocks)[0, 0, :3]) == [0, 1, 3]
    assert list(np.asarray(bits)[0, 0, :3]) == [1 | 1 << 5, 1 << 5, 1]
    assert list(np.asarray(blocks)[1, 0, :2]) == [0, 4]
    assert list(np.asarray(bits)[1, 0, :2]) == [255, 1 << 1]
    assert float(visited) == 5 * 8 and float(chosen) == selected.sum()
    assert float(slots) == float(visited)

  def test_the_plan_pads_its_rows_to_whole_iterations(self):
    """6 blocks at 4 an iteration: rows of 8, the last two block 0 with
    no bit; unions of 3 and 2 blocks step through 4 slots each."""
    selected = np.zeros((1, 16, 6), bool)
    selected[0, 0, [0, 3]] = selected[0, 5, [0, 1]] = True
    selected[0, 8:, 0] = selected[0, 9, 4] = True
    one = sparse_lib.plan(jnp.asarray(selected), tokens=8)
    four = sparse_lib.plan(jnp.asarray(selected), tokens=8, per_visit=4)
    for padded, unpadded in zip(four[:2], one[:2]):
      assert padded.shape == (2, 1, 8)
      np.testing.assert_array_equal(np.asarray(padded)[..., :6],
                                    np.asarray(unpadded))
      assert not np.any(np.asarray(padded)[..., 6:])
    np.testing.assert_array_equal(np.asarray(four[2]), np.asarray(one[2]))
    assert float(four[3]) == 5 * 8 and float(four[5]) == 2 * 4 * 8
    assert sparse_lib.visit_blocks(64, 512) == 8
    assert sparse_lib.visit_blocks(8, 6) == 6
    assert sparse_lib.visit_blocks(512, 64) == 1


# --- Lightning attention -------------------------------------------------------


def _token_by_token(q, k, v, lam):
  return jax.vmap(lambda q, k, v: reference.recurrence(q, k, v, lam))(q, k, v)


class TestLightning:

  @pytest.mark.parametrize("t", [64, 192, 1024])
  @pytest.mark.parametrize("implementation", ["xla", "pallas"])
  def test_matches_the_recurrence(self, t, implementation):
    r = np.random.default_rng(0)
    q, k = (jnp.asarray(r.standard_normal((1, t, 2, 128)) / 11.3, jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.standard_normal((1, t, 2, 128)), jnp.float32)
    log_decay = lightning_lib.log_decays(32, 0, 2, 1, 32)
    run = lambda q, k, v: lightning_lib.lightning_attention(
        q, k, v, log_decay, implementation)
    want = _token_by_token(q, k, v, jnp.exp(log_decay))
    np.testing.assert_allclose(np.asarray(run(q, k, v)), np.asarray(want),
                               atol=2e-6)
    got = jax.grad(_weighted(run, want.shape), (0, 1, 2))(q, k, v)
    wanted = jax.grad(_weighted(lambda *a: _token_by_token(
        *a, jnp.exp(log_decay)), want.shape), (0, 1, 2))(q, k, v)
    for g, w in zip(got, wanted):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=2e-5 * float(jnp.max(jnp.abs(w))))

  def test_decays_are_minimax_s_schedule(self):
    """Head 0 of 32 in layer 1 of 32 is the fastest of the layer, about
    0.44; the last head barely decays; a later layer is slower."""
    lam = np.exp(np.asarray(lightning_lib.log_decays(32, 0, 32, 1, 32)))
    assert lam[0] == pytest.approx(np.exp(-2 ** -0.25 * (30 / 31 + 1e-5)))
    assert 0.43 < lam[0] < 0.45 and lam[-1] > 0.99
    assert np.all(np.diff(lam) > 0)
    later = np.exp(np.asarray(lightning_lib.log_decays(32, 0, 32, 20, 32)))
    assert np.all(later > lam)
    # The share: heads 16.. are the published heads 16.., not 0..
    np.testing.assert_allclose(
        np.asarray(lightning_lib.log_decays(32, 16, 16, 1, 32)),
        np.log(lam[16:]), rtol=1e-4)

  def test_the_delta_rule_keeps_its_programs(self):
    assert rule_lib.KERNEL_NAMES == (
        "gated_delta_rule_fwd", "gated_delta_rule_bwd",
        "gated_delta_rule_prep", "gated_delta_rule_prep_bwd")
    assert lightning_lib.KERNEL_NAMES == ("lightning_attention_fwd",
                                          "lightning_attention_bwd")
    assert sparse_lib.KERNEL_NAMES == ("sparse_attention_fwd",
                                       "sparse_attention_bwd")


# --- the shares -------------------------------------------------------------------


def _mixer(config, kind, x, params, layer=1):
  module = (sequence.SparseAttention(config, jnp.float32) if kind == "sparse"
            else sequence.LightningAttention(config, jnp.float32, layer))
  return module.apply({"params": params}, x)[0]


def _cut(kernel, axis, start, size):
  return jax.lax.slice_in_dim(kernel, start, start + size, axis=axis)


class TestShares:
  """Two chips' halves of the heads (and of their key/value heads) and of
  the MLP's columns add up to what the uncut layer gives."""

  def _uncut(self, variables, kind):
    block = variables["params"]["block0" if kind == "sparse" else "block1"]
    p = dict(block["attn"])
    wide = lambda kernel, n: jnp.concatenate([kernel] * n, axis=-1)
    # Two chips' worth of heads: each half different.
    for name in ("q_proj", "o_gate"):
      p[name] = {"kernel": jnp.concatenate(
          [p[name]["kernel"], 0.5 * p[name]["kernel"]], axis=-1)}
    p["o_proj"] = {"kernel": jnp.concatenate(
        [p["o_proj"]["kernel"], -p["o_proj"]["kernel"][::-1]], axis=0)}
    for name in ("k_proj", "v_proj"):
      p[name] = {"kernel": jnp.concatenate(
          [p[name]["kernel"], p[name]["kernel"][::-1]], axis=-1)}
    if kind == "lightning":
      p["o_norm"] = wide(p["o_norm"], 2)
    return p

  @pytest.mark.parametrize("kind", ["sparse", "lightning"])
  def test_the_head_halves_add_up_to_the_uncut_layer(self, variables, hidden,
                                                     kind):
    whole = self._uncut(variables, kind)
    sizes = program_sizes()
    heads = sizes["num_attention_heads"] if kind == "sparse" else sizes[
        "lightning_nh"]
    hd = sizes["head_dim"] if kind == "sparse" else sizes[
        "lightning_head_dim"]
    kv = sizes["num_key_value_heads"] if kind == "sparse" else heads
    uncut = sequence.SequenceConfig(**dict(
        sizes, num_attention_heads=2 * sizes["num_attention_heads"],
        num_key_value_heads=2 * sizes["num_key_value_heads"],
        lightning_nh=2 * heads if kind == "lightning" else heads,
        published_lightning_nh=2 * sizes["lightning_nh"]))
    want = _mixer(uncut, kind, hidden, whole)
    parts = []
    for chip in range(2):
      p = {}
      for name in ("q_proj", "o_gate"):
        p[name] = {"kernel": _cut(whole[name]["kernel"], 1, chip * heads * hd,
                                  heads * hd)}
      for name in ("k_proj", "v_proj"):
        p[name] = {"kernel": _cut(whole[name]["kernel"], 1, chip * kv * hd,
                                  kv * hd)}
      p["o_proj"] = {"kernel": _cut(whole["o_proj"]["kernel"], 0,
                                    chip * heads * hd, heads * hd)}
      p["q_norm"], p["k_norm"] = whole["q_norm"], whole["k_norm"]
      if kind == "lightning":
        p["o_norm"] = _cut(whole["o_norm"], 0, chip * heads * hd, heads * hd)
      config = sequence.SequenceConfig(**dict(
          sizes, first_head=chip * heads,
          published_lightning_nh=2 * sizes["lightning_nh"]))
      parts.append(_mixer(config, kind, hidden, p))
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(want), atol=2e-5)
    assert float(jnp.max(jnp.abs(parts[1]))) > 0.1   # each half does work

  def test_the_column_halves_add_up_to_the_uncut_mlp(self, variables, hidden):
    mlp = variables["params"]["block0"]["mlp"]
    uncut = sequence.GatedMLP(WIDTH, jnp.float32).apply({"params": mlp}, hidden)
    half = WIDTH // 2
    parts = sum(sequence.GatedMLP(half, jnp.float32).apply({"params": {
        "gate": {"kernel": _cut(mlp["gate"]["kernel"], 1, c * half, half)},
        "up": {"kernel": _cut(mlp["up"]["kernel"], 1, c * half, half)},
        "down": {"kernel": _cut(mlp["down"]["kernel"], 0, c * half, half)},
    }}, hidden) for c in range(2))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut),
                               atol=2e-5)


# --- the model ----------------------------------------------------------------------


class TestModel:

  def _model(self, **changes):
    from tensor2robot_tpu.utils.optimizers import create_adam_optimizer
    return SequenceMoEModel(
        optimizer_fn=create_adam_optimizer(learning_rate=1e-3),
        compute_dtype=jnp.float32, sequence_length=SIZES["sequence_length"],
        **program_sizes(**changes))

  def _tokens(self, steps=2, batch=2):
    return jax.random.randint(
        jax.random.key(1), (steps, batch, SIZES["sequence_length"]), 0,
        SIZES["vocab_size"], jnp.int32)

  def test_parameter_tree_is_the_reference_s(self, variables):
    ours = self._model().init_variables(jax.random.key(0))
    shape = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), dict(tree))
    assert shape(ours) == shape(variables)

  def test_the_layer_pattern_is_read_from_mixer_types(self):
    config = sequence.SequenceConfig(**program_sizes())
    assert [config.layer_kind(i) for i in range(4)] == [
        "sparse", "lightning", "lightning", "lightning"]
    params = self._model().init_variables(jax.random.key(0))["params"]
    assert set(params) == {"embed", "block0", "block1", "block2", "block3",
                           "final_norm", "head"}
    assert "o_norm" not in params["block0"]["attn"]
    assert "o_norm" in params["block1"]["attn"]
    for i in range(4):
      assert set(params[f"block{i}"]["mlp"]) == {"gate", "up", "down"}
    assert config.residual_scale == pytest.approx(1.4 / 32 ** 0.5)

  def test_loss_matches_reference(self, variables):
    model, config = self._model(), reference_config()
    features = {"tokens": self._tokens()[0]}
    loss, (metrics, _) = model.model_train_fn(
        variables, ts.TensorSpecStruct(features), None)
    outputs, _ = reference.forward(variables, features, True, "f32", config)
    want, _ = reference.loss(outputs, features, None, config)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(metrics["sala/selected_mass"]),
                               np.asarray(outputs["sala_selected_mass"]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(metrics["lightning/decay_mean"]),
                               np.asarray(outputs["lightning_decay_mean"]),
                               rtol=1e-6)
    assert np.asarray(metrics["lightning/decay_mean"]).shape == (3,)
    assert not any(name.startswith("moe/") for name in metrics)
    # Each Lightning layer's smallest mean square of a head's output
    # before its norm.
    floor = np.asarray(outputs["lightning_output_floor"])
    assert floor.shape == (3,) and np.all(floor > 0), floor

  @pytest.fixture(scope="class")
  def losses(self, variables):
    """The reference's loss without a fault and with each, the fault a
    traced index: one compiled forward for all of them."""
    config, features = reference_config(), {"tokens": self._tokens()[0]}
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
    config, features = reference_config(), {"tokens": self._tokens()[0]}
    by_name = lambda fault: float(reference.loss(reference.forward(
        variables, features, True, "f32", config, fault)[0], features, None,
        config, fault)[0])
    assert by_name("no_decay") == pytest.approx(losses["no_decay"], rel=1e-6)
    assert by_name(None) == pytest.approx(losses[None], rel=1e-6)

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
        loss=metrics["loss"],
        sala_selected_mass=metrics["sala/selected_mass"],
        lightning_decay_mean=metrics["lightning/decay_mean"]))
    numbers = {name: value for name, value, _ in driver.compare(
        first, followed, {})}
    for name in ("last_loss_gap", "moment_norm_gap", "change_norm_gap",
                 "moment_own_gap", "change_own_gap", "sala_mass_gap",
                 "lightning_decay_gap"):
      assert numbers[name] < 1e-3, (name, numbers)
    assert np.asarray(metrics["sala/visited_blocks"]).shape == (1,)
    assert (float(metrics["sala/selected_blocks"][0])
            < float(metrics["sala/visited_blocks"][0])
            <= float(metrics["sala/visit_slots"][0]))


def test_the_configuration_builds_through_the_trainer_s_cfg():
  """The `.cfg` that `bin/run_t2r_trainer` takes and the configuration
  file that `harness.build_model` takes name one model."""
  from tensor2robot_tpu.config import config as cfg_lib
  from tensor2robot_tpu.config import registrations  # noqa: F401
  with open(os.path.join(ROOT, "benchmark", "configs",
                         "minicpm_sala_tp2.json")) as f:
    kwargs = json.load(f)["model"]["kwargs"]
  try:
    cfg_lib.parse_config_files_and_bindings([os.path.join(
        ROOT, "tensor2robot_tpu", "research", "seqlm", "configs",
        "minicpm_sala_tp2_train.cfg")], [])
    model = cfg_lib.query_binding("train_eval_model.model")
  finally:
    cfg_lib.clear_config()
  assert isinstance(model, SequenceMoEModel)
  sizes = {k: v for k, v in kwargs.items() if k != "sequence_length"}
  assert model.config == sequence.SequenceConfig(**sizes)

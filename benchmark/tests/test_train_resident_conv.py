"""Driver `train_resident_conv` and the conv/attention expert sequence
configuration's files, rehearsed on the CPU at a tiny preset: a whole
run, the layer metrics read from a recorded run, every control and
planted fault coming out not correct by the cell's own limits, the
comparison's conv and expert numbers, the operation counts against a
hand count, and the configuration file against the catalog's published
sizes."""

import importlib
import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import train_resident_conv as driver
from benchmark.tests import tiny, tiny_conv

CELL = "lfm2_moe_train_seq8k"
CONFIG = "lfm2_8b_a1b_ep4"


def _config():
  with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
    return json.load(f)


def test_run_end_to_end():
  result = tiny.run(tiny_conv.train_cell(), jax.devices()[:1])
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
  assert result["attempted"] > 0 and result["failed"] == 0
  assert result["compared"]["step_count_gap"]["value"] == 0
  assert {"conv_rms_gap", "expert_count_gap"} <= set(result["compared"])


def test_traced_run_prints_the_cell_s_layer_metrics():
  """On the CPU the trace holds no device plane: the readers are driven
  on a run record as the harness builds it, the scopes' seconds and the
  flash kernel's calls as `release()` hands them on."""
  cell = tiny_conv.train_cell()
  conv = cell.flops.short_conv_kernel(cell.config)
  flash = cell.flops.attention_kernel(cell.config)
  window = {"window_s": 2.0, "examples": 8,
            "counters": {"dispatches": 2, "scan_steps": 2,
                         "global_batch": 2},
            "short_conv": {"scope_seconds": 0.4},
            "short_conv_op": {"scope_seconds": 0.1},
            "attention_kernel": {
                "calls": {"fwd": 8, "dq": 4, "dkv": 4},
                "seconds": {"fwd": 0.1, "dq": 0.1, "dkv": 0.2}}}
  peaks = dict(tiny.PEAKS, hbm_bytes_per_s=1e9)
  run = {"cell": cell, "window": window, "peaks": peaks, "chips": 1,
         "trace": {"busy_s": 1.6, "window_s": 2.0}, "device": {}}
  names = [e["name"] for e in harness.metrics_for(cell, "per_layer")]
  assert names == ["device_idle_share.train", "step_mfu.train",
                   "dispatch_host_ms.train", "mla_attention_roofline.train",
                   "short_conv_time_share.train",
                   "short_conv_roofline.train"]
  read = lambda name: harness._load_module("layer_metrics", name).read(run)
  least = lambda work: max(work["flops"] / peaks["bf16_flops_per_s"],
                           work["bytes"] / peaks["hbm_bytes_per_s"])
  # Four conv layers held (block 0 and blocks 2..4), 2 x 2 x 2 sequences.
  assert cell.flops._layers(cell.config) == (4, 1, 1, 4)
  assert read("short_conv_roofline.train") == pytest.approx(
      100 * 8 * 4 * sum(least(part) for part in conv.values()) / 0.4)
  assert read("short_conv_time_share.train") == pytest.approx(25.0)
  batch = cell.traffic["batch_per_chip"]
  assert read("mla_attention_roofline.train") == pytest.approx(
      100 * batch * (8 * flash["fwd"]["flops"] + 4 * flash["dq"]["flops"]
                     + 4 * flash["dkv"]["flops"]) / 0.4
      / peaks["bf16_flops_per_s"])
  assert read("device_idle_share.train") == pytest.approx(20.0)
  # Nothing to read (an untraced run, a program without the scope or the
  # kernel): the metrics are left out, nothing raises.
  for name in ("short_conv", "short_conv_op", "attention_kernel"):
    del window[name]
  for name in names[3:]:
    assert read(name) is None, name


def test_the_compiled_step_names_the_mixer_s_instructions():
  """The tiny cell's K-step program, compiled here: its text carries the
  mixer's scope on the first run and on the backward pass, and the op's
  within it, which is what `release()` joins the trace's operations
  to."""
  from benchmark.drivers.train_resident_hybrid import scope_instructions
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.specs import tensorspec_utils as ts
  from tensor2robot_tpu.train.trainer import Trainer
  import jax.numpy as jnp
  cell = tiny_conv.train_cell()
  trainer = Trainer(harness.build_model(cell.config),
                    mesh=mesh_lib.create_mesh(devices=jax.devices()[:1]))
  state = trainer.create_train_state()
  tokens = jnp.zeros((2, 2, tiny_conv.SIZES["sequence_length"]), jnp.int32)
  text = trainer.aot_train_steps(
      state, ts.TensorSpecStruct({"tokens": tokens})).as_text()
  mixer = scope_instructions(text, driver.MIXER_SCOPE)
  op = scope_instructions(text, driver.OP_SCOPE)
  assert op and op < mixer
  lines = [line for line in text.splitlines()
           for name in mixer if f"{name} = " in line]
  assert any("transpose(" in line for line in lines)


@pytest.fixture(scope="module")
def finished():
  cell = tiny_conv.train_cell()
  session = driver.Session(cell, 2147483777, jax.devices()[:1],
                           jax.profiler.TraceAnnotation)
  session.run_window(0.3)
  session.release()
  return cell, session


def test_window_counts_the_conv_layers_and_the_experts(finished):
  _, session = finished
  counters = session._window["counters"]
  for name in ("conv/u_rms", "first/conv/u_rms", "conv/y_rms",
               "first/conv/y_rms"):
    assert len(counters[name]) == 4, name        # four conv layers
    assert all(x > 0 for x in counters[name]), (name, counters[name])
  assert np.asarray(counters["moe/expert_tokens"]).shape == (4, 4)
  assert 0 < counters["moe/held_assignments"] < counters[
      "moe/total_assignments"]
  json.dumps(counters)  # the harness prints them


_CONTROLS = ["control_fp8", "fault_smallest_leaf_frozen"] + [
    "fault_" + name for name in importlib.import_module(
        "benchmark.reference." + CONFIG).FAULTS]


def test_the_controls_are_the_reference_s_faults(finished):
  _, session = finished
  assert list(session.controls()) == _CONTROLS
  assert len(_CONTROLS) == 11


@pytest.mark.parametrize("control", _CONTROLS)
def test_control_comes_out_not_correct(finished, control):
  cell, session = finished
  rows = session.check(cell.limits, **session.controls()[control])
  over = [n for n, value, limit in rows
          if limit is not None and not value <= limit]
  assert over, (control, rows)


def test_every_limit_of_the_cell_is_of_a_number_the_driver_reads(finished):
  cell, session = finished
  rows = session.check(cell.limits)
  assert set(cell.limits) <= {name for name, _, _ in rows}
  for name, value, limit in rows:
    assert value == value and (limit is None or limit >= 0), name
    assert limit is None or value <= limit, (name, value, limit)


def test_compare_reads_the_conv_rms_and_the_expert_loads():
  """Equal records read nought everywhere; a moved RMS or load reads in
  its own number alone."""
  leaf = lambda x: {"a": np.asarray([x], np.float32),
                    "b": np.asarray([2 * x], np.float32)}
  record = {"loss": 3.0, "change": leaf(1.0), "moment": leaf(0.5),
            "first_grad": leaf(1.0),
            "conv_u_rms": np.asarray([0.5, 0.6, 0.7, 0.8]),
            "conv_y_rms": np.asarray([0.2, 0.3, 0.4, 0.5]),
            "expert_tokens": np.asarray([[4, 6], [5, 5]])}
  numbers = dict((n, v) for n, v, _ in driver.compare(record, record, {}))
  assert all(v == 0 for n, v in numbers.items()
             if n != "smallest_leaf_share"), numbers
  moved = dict(record, conv_y_rms=np.asarray([0.2, 0.3, 0.44, 0.5]))
  numbers = dict((n, v) for n, v, _ in driver.compare(moved, record, {}))
  assert numbers["conv_rms_gap"] == pytest.approx(0.1)
  assert numbers["expert_count_gap"] == numbers["last_loss_gap"] == 0
  moved = dict(record, expert_tokens=np.asarray([[5, 5], [5, 5]]))
  numbers = dict((n, v) for n, v, _ in driver.compare(moved, record, {}))
  assert numbers["expert_count_gap"] == pytest.approx(0.1)
  assert numbers["conv_rms_gap"] == 0


def test_operations_against_a_hand_count():
  """By hand, layer by layer, at the configuration's sizes: four conv
  layers, one attention layer, one dense and four expert layers, a
  balanced router handing the 8 held of 32 one assignment a token."""
  config = _config()
  flops = harness._load_module("flops", CONFIG)
  assert flops._layers(config) == (4, 1, 1, 4)
  t, d = 8192, 2048
  parts = flops.forward_per_token(config)
  assert parts["conv_projections"] == 4 * 2 * 4 * d * d
  assert parts["conv_gates_and_taps"] == 4 * 8 * d
  assert parts["attention_projections"] == 2 * (2 * d * d + 2 * d * 512)
  assert parts["attention_scores_values"] == (t + 1) * 32 * 128
  assert parts["dense_mlp"] == 2 * 3 * d * 7168
  assert parts["expert_layers"] == 4 * (2 * d * 32 + 2 * 3 * d * 1792)
  assert parts["head"] == 2 * d * 16384
  assert sum(parts.values()) == pytest.approx(432.7e6, rel=0.001)
  assert flops.train_per_example(config) == 3 * t * sum(parts.values())
  kernel = flops.attention_kernel(config)
  assert kernel["fwd"]["flops"] == 2 * 32 * t * (t + 1) / 2 * 128
  conv = flops.short_conv_kernel(config)
  assert conv["in_proj_fwd"]["flops"] == 2 * t * d * 3 * d
  assert conv["in_proj_bwd"]["flops"] == 2 * conv["in_proj_fwd"]["flops"]
  assert conv["op_fwd"]["bytes"] == 2 * t * 4 * d + 4 * 3 * d
  assert conv["out_proj_fwd"]["bytes"] == 2 * (2 * t * d + d * d)


def test_configuration_keeps_every_published_width():
  config = _config()
  published = config["published"]
  with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    entry = [c for c in json.load(f)["configs"] if c["name"] == CONFIG][0]
  assert entry["source"] == config["source"]
  assert entry["reduced"] == config["reduced"] == [
      "num_hidden_layers", "num_experts", "vocab_size"]
  assert set(config["reduced_why"]) == set(config["reduced"])
  for key, value in published.items():
    if key == "where":
      continue
    if key in config["reduced"]:
      assert config[key] != value
    else:
      assert config[key] == value, key
  assert (config["num_hidden_layers"], config["num_experts"],
          config["vocab_size"]) == (5, 8, 65536 // 4)
  # The program is built with the sizes the reference reads, under the
  # class's own names where they differ.
  kwargs = config["model"]["kwargs"]
  for key, value in kwargs.items():
    if key in config:
      assert config[key] == value, key
  assert kwargs["first_k_dense_replace"] == config["num_dense_layers"]
  assert kwargs["rms_norm_eps"] == config["norm_eps"]
  assert kwargs["n_routed_experts"] == config["router_width"] == published[
      "num_experts"]
  assert kwargs["experts_held"] == config["num_experts"]
  assert kwargs["head_dim"] * kwargs["num_attention_heads"] == kwargs[
      "hidden_size"]
  assert {"deployment", "assumed", "reduced_why"} <= set(config)


def test_configuration_holds_the_catalog_s_numbers():
  """Every key of the catalog entry's `config`, under the same key; only
  the `reduced` ones differ. The catalog is a JSON-lines file named by
  `MODEL_CATALOG`."""
  catalog = os.environ.get("MODEL_CATALOG", "")
  if not catalog or not os.path.exists(catalog):
    pytest.skip("no catalog given")
  with open(catalog) as f:
    rows = [json.loads(line) for line in f]
  (row,) = [r for r in rows if r["name"] == "LFM2-8B-A1B"]
  config = _config()
  assert config["source"] == row["source_url"]
  for key, value in row["config"].items():
    assert key in config, key
    if key not in config["reduced"]:
      assert config[key] == value, key
    assert config["published"][key] == value, key


def test_parameter_count_is_the_configuration_s():
  config = _config()
  reference = importlib.import_module("benchmark.reference." + CONFIG)
  shapes = jax.eval_shape(
      lambda k: reference.init_variables(k, config), jax.random.key(0))
  count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
  assert count == config["parameters"] == 507820288

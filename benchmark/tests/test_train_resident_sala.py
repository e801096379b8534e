"""Driver `train_resident_sala` and the sparse/linear-attention sequence
configuration's files, rehearsed on the CPU at a tiny preset: a whole run,
the layer metrics read from a recorded run, the trace reader, every
control and planted fault coming out not correct by the cell's own
limits, the comparison without expert counters, the operation counts
against a hand count, and the configuration file against the catalog's
published sizes."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import train_resident_sala as driver
from benchmark.tests import tiny, tiny_sala

CELL = "minicpm_sala_train_seq32k"
CONFIG = "minicpm_sala_tp2"


def _config():
  with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
    return json.load(f)


def test_run_end_to_end():
  result = tiny.run(tiny_sala.train_cell(), jax.devices()[:1])
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
  assert result["attempted"] > 0 and result["failed"] == 0
  assert result["compared"]["step_count_gap"]["value"] == 0
  assert "sala_mass_gap" in result["compared"]
  assert not any("expert" in name or "mtp" in name
                 for name in result["compared"])


def test_traced_run_prints_the_cell_s_layer_metrics():
  """On the CPU the trace holds no device plane: the readers are driven
  on a run record as the harness builds it, the scopes' seconds as
  `release()` hands them on."""
  cell = tiny_sala.train_cell()
  sparse = cell.flops.sparse_attention_kernel(cell.config)
  lightning = cell.flops.lightning_attention_kernel(cell.config)
  window = {"window_s": 2.0, "examples": 8,
            "sparse_visits": {"visited": 800.0, "selected": 200.0},
            "block_selection": {"scope_seconds": 0.1},
            "sparse_attention": {
                "calls": {"fwd": 8, "bwd": 4}, "scope_seconds": 0.4,
                "kernel_seconds": {"fwd": 0.1, "bwd": 0.2}},
            "lightning_attention": {
                "calls": {"fwd": 24, "bwd": 12}, "scope_seconds": 0.5,
                "kernel_seconds": {"fwd": 0.02, "bwd": 0.03}}}
  peaks = dict(tiny.PEAKS, hbm_bytes_per_s=1e9)
  run = {"cell": cell, "window": window, "peaks": peaks, "chips": 1,
         "trace": {"busy_s": 1.9, "window_s": 2.0}, "device": {}}
  names = [e["name"] for e in harness.metrics_for(cell, "per_layer")]
  assert names == ["device_idle_share.train", "step_mfu.train",
                   "dispatch_host_ms.train",
                   "sparse_attention_roofline.train",
                   "lightning_attention_roofline.train",
                   "block_selection_time_share.train",
                   "sparse_visit_share.train",
                   "lightning_attention_time_share.train"]
  read = lambda name: harness._load_module("layer_metrics", name).read(run)
  least = lambda work: max(work["flops"] / peaks["bf16_flops_per_s"],
                           work["bytes"] / peaks["hbm_bytes_per_s"])
  batch = cell.traffic["batch_per_chip"]
  assert read("sparse_attention_roofline.train") == pytest.approx(
      100 * batch * (8 * least(sparse["fwd"]) + 4 * least(sparse["bwd"]))
      / 0.4)
  assert read("lightning_attention_roofline.train") == pytest.approx(
      100 * batch * (24 * least(lightning["fwd"])
                     + 12 * least(lightning["bwd"])) / 0.5)
  assert read("block_selection_time_share.train") == pytest.approx(
      100 * 0.1 / 1.9)
  assert read("lightning_attention_time_share.train") == pytest.approx(
      100 * 0.5 / 1.9)
  assert read("sparse_visit_share.train") == pytest.approx(25.0)
  assert read("device_idle_share.train") == pytest.approx(5.0)
  # Nothing to read (an untraced run, a program without the scopes or
  # the counters): the metrics are left out, nothing raises.
  for name in ("sparse_attention", "lightning_attention", "block_selection",
               "sparse_visits"):
    del window[name]
  for name in names[3:]:
    assert read(name) is None, name


def test_scoped_seconds_are_the_scope_s_or_nothing(tmp_path, monkeypatch):
  from benchmark.trace import reduce as reduce_lib
  from benchmark.drivers.train_resident_hybrid import scope_instructions
  hlo = """
  %fusion.3 = f32[2] fusion(%p), metadata={op_name="jit(f)/block0/attn/sala/attend/sort"}
  %fusion.4 = f32[2] fusion(%p), metadata={op_name="jit(f)/block0/attn/sala/select/top_k"}
  %sparse_attention_fwd.1 = bf16[2] custom-call(%p), metadata={op_name="jit(f)/checkpoint/sala/attend/pallas_call"}
  %sparse_attention_bwd.1 = (bf16[2]) custom-call(%p), metadata={op_name="jit(f)/transpose(jvp(sala/attend))/pallas_call"}
  %while.1 = (f32[2]) while(%p), metadata={op_name="jit(f)/while"}
  """
  attend = scope_instructions(hlo, driver.ATTEND_SCOPE)
  select = scope_instructions(hlo, driver.SELECT_SCOPE)
  assert attend == {"fusion.3", "sparse_attention_fwd.1",
                    "sparse_attention_bwd.1"}
  assert select == {"fusion.4"}
  ops = [("%while.1 = (f32[2]) while()", 0.0, 10e9),  # holds all the others
         ("%sparse_attention_fwd.1 = bf16[2] custom-call()", 0.0, 4e9),
         ("%sparse_attention_bwd.1 = (bf16[2]) custom-call()", 5e9, 8e9),
         ("%fusion.3 = f32[2] fusion()", 8e9, 9e9),
         ("%fusion.4 = f32[2] fusion()", 9e9, 9.5e9)]
  monkeypatch.setattr(reduce_lib, "find_xplane", lambda d: "x")
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops}})
  kernels = {"fwd": "sparse_attention_fwd", "bwd": "sparse_attention_bwd"}
  found = driver.scoped_seconds(str(tmp_path), attend, kernels)
  assert found["calls"] == {"fwd": 1, "bwd": 1}
  assert found["kernel_seconds"] == pytest.approx({"fwd": 4.0, "bwd": 3.0})
  assert found["scope_seconds"] == pytest.approx(4.0 + 3.0 + 1.0)
  assert driver.scoped_seconds(str(tmp_path), select, {}) == {
      "scope_seconds": pytest.approx(0.5)}
  # A kernel call the compiled text does not name under the scope, or a
  # program missing: nothing is read.
  assert driver.scoped_seconds(
      str(tmp_path), attend - {"sparse_attention_bwd.1"}, kernels) is None
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops[:2]}})
  assert driver.scoped_seconds(str(tmp_path), attend, kernels) is None
  monkeypatch.undo()
  assert driver.scoped_seconds(str(tmp_path), attend, kernels) is None


def test_the_compiled_step_names_the_scopes_instructions():
  """The tiny cell's K-step program, compiled here: its text carries the
  three scopes on instructions apart, the attention's on the backward
  pass too, which is what `release()` joins the trace's operations to."""
  from benchmark.drivers.train_resident_hybrid import scope_instructions
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.specs import tensorspec_utils as ts
  from tensor2robot_tpu.train.trainer import Trainer
  cell = tiny_sala.train_cell()
  trainer = Trainer(harness.build_model(cell.config),
                    mesh=mesh_lib.create_mesh(devices=jax.devices()[:1]))
  state = trainer.create_train_state()
  tokens = jnp.zeros((2, 2, tiny_sala.SIZES["sequence_length"]), jnp.int32)
  text = trainer.aot_train_steps(
      state, ts.TensorSpecStruct({"tokens": tokens})).as_text()
  found = {scope: scope_instructions(text, scope) for scope in (
      driver.SELECT_SCOPE, driver.ATTEND_SCOPE, driver.SCAN_SCOPE)}
  assert all(found.values())
  assert not found[driver.SELECT_SCOPE] & found[driver.ATTEND_SCOPE]
  lines = [line for line in text.splitlines()
           for name in found[driver.SCAN_SCOPE] if f"{name} = " in line]
  assert any("transpose(" in line for line in lines)


@pytest.fixture(scope="module")
def finished():
  cell = tiny_sala.train_cell()
  session = driver.Session(cell, 2147483777, jax.devices()[:1],
                           jax.profiler.TraceAnnotation)
  session.run_window(0.3)
  session.release()
  return cell, session


def test_window_counts_the_visits(finished):
  cell, session = finished
  counters = session._window["counters"]
  for name in ("sala/selected_mass", "first/sala/selected_mass",
               "sala/visited_blocks", "sala/selected_blocks"):
    assert len(counters[name]) == 1, name        # one sparse layer
  for name in ("lightning/decay_mean", "first/lightning/decay_mean"):
    assert len(counters[name]) == 3, name        # three Lightning layers
    assert all(0 < x < 1 for x in counters[name]), (name, counters[name])
  assert 0 < counters["sala/selected_mass"][0] < 1
  assert 0 < counters["sala/selected_blocks"][0] < counters[
      "sala/visited_blocks"][0]
  assert session._window["sparse_visits"] == {
      "visited": counters["sala/visited_blocks"][0],
      "selected": counters["sala/selected_blocks"][0]}
  assert not any(name.startswith("moe/") for name in counters)
  json.dumps(counters)  # the harness prints them


_CONTROLS = ["control_fp8", "fault_smallest_leaf_frozen"] + [
    "fault_" + name for name in importlib.import_module(
        "benchmark.reference." + CONFIG).FAULTS]


def test_the_controls_are_the_reference_s_faults(finished):
  _, session = finished
  assert list(session.controls()) == _CONTROLS
  assert len(_CONTROLS) == 12


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


def test_compare_reads_the_selected_mass():
  """Equal records read nought everywhere; a moved mass reads in its own
  number alone."""
  leaf = lambda x: {"a": np.asarray([x], np.float32),
                    "b": np.asarray([2 * x], np.float32)}
  record = {"loss": 3.0, "change": leaf(1.0), "moment": leaf(0.5),
            "first_grad": leaf(1.0),
            "sala_selected_mass": np.asarray([0.8]),
            "lightning_decay_mean": np.asarray([0.6, 0.7, 0.8]),
            "lightning_output_floor": np.asarray([0.3, 2e-6, 0.1])}
  numbers = dict((n, v) for n, v, _ in driver.compare(record, record, {}))
  assert all(v == 0 for n, v in numbers.items()
             if n not in ("smallest_leaf_share", "lightning_output_floor")
             ), numbers
  # The reference's own, over the layers: printed, never compared.
  assert numbers["lightning_output_floor"] == pytest.approx(2e-6)
  assert "lightning_output_floor" not in harness.load_cell(CELL).limits
  moved = dict(record, sala_selected_mass=np.asarray([0.76]))
  numbers = dict((n, v) for n, v, _ in driver.compare(moved, record, {}))
  assert numbers["sala_mass_gap"] == pytest.approx(0.05)
  assert numbers["last_loss_gap"] == numbers["lightning_decay_gap"] == 0
  moved = dict(record, lightning_decay_mean=np.asarray([0.6, 0.77, 0.8]))
  numbers = dict((n, v) for n, v, _ in driver.compare(moved, record, {}))
  assert numbers["lightning_decay_gap"] == pytest.approx(0.1)


def test_operations_against_a_hand_count():
  """By hand, layer by layer: a query selects 1 + 2048/64 + 1 + 64 = 98
  blocks of 64 keys (6,272), or its causal keys where fewer."""
  config = _config()
  flops = harness._load_module("flops", CONFIG)
  t = 32768
  keys = sum(min(i + 1, 6272) for i in range(t))
  assert flops.selected_keys(config) == keys
  parts = flops.forward_per_token(config)
  assert parts["sparse_projections"] == 2 * (3 * 4096 * 2048
                                             + 2 * 4096 * 128)
  assert parts["sparse_attention"] == pytest.approx(
      2 * 2 * 16 * 128 * keys / t)
  assert parts["sparse_selection"] == 2 * 16 * 128 * 2048 / 2
  assert parts["lightning_projections"] == 3 * 2 * 5 * 4096 * 2048
  assert parts["lightning_recurrence"] == 3 * 2 * 2 * 16 * 128 * 128
  assert parts["mlp"] == 4 * 2 * 3 * 4096 * 8192
  assert parts["head"] == 2 * 4096 * 9181
  assert sum(parts.values()) == pytest.approx(1.2384e9, rel=0.001)
  assert flops.train_per_example(config) == pytest.approx(
      3 * t * sum(parts.values()) - 2 * t * parts["sparse_selection"])
  kernel = flops.sparse_attention_kernel(config)
  assert kernel["fwd"]["flops"] == 2 * 16 * keys * 2 * 128
  assert kernel["bwd"]["flops"] == 2 * kernel["fwd"]["flops"]
  scan = flops.lightning_attention_kernel(config)
  assert scan["fwd"]["flops"] == t * 16 * 2 * 2 * 128 * 128
  assert scan["fwd"]["bytes"] == 4 * t * 16 * 128 * 2


def test_configuration_keeps_every_published_width():
  config = _config()
  published = config["published"]
  with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    entry = [c for c in json.load(f)["configs"] if c["name"] == CONFIG][0]
  assert entry["source"] == config["source"]
  assert entry["reduced"] == config["reduced"] == [
      "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
      "lightning_nh", "lightning_nkv", "vocab_size"]
  assert set(config["reduced_why"]) == set(config["reduced"])
  for key, value in published.items():
    if key == "where":
      continue
    if key in config["reduced"]:
      assert config[key] != value
    else:
      assert config[key] == value, key
  assert (config["num_hidden_layers"], config["num_attention_heads"],
          config["num_key_value_heads"], config["lightning_nh"],
          config["vocab_size"]) == (4, 16, 1, 16, 73448 // 8)
  # The program is built with the sizes the reference reads; the MLP's
  # held columns under the class's own name.
  kwargs = config["model"]["kwargs"]
  assert kwargs["intermediate_size"] == config["intermediate_held"] == (
      published["intermediate_size"] // 2)
  for key, value in kwargs.items():
    if key in config and key != "intermediate_size":
      assert config[key] == value, key
  assert kwargs["published_num_hidden_layers"] == published[
      "num_hidden_layers"]
  assert kwargs["published_lightning_nh"] == published["lightning_nh"]
  assert (kwargs["n_routed_experts"], kwargs["first_k_dense_replace"],
          kwargs["num_nextn_predict_layers"]) == (0, 0, 0)
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
  (row,) = [r for r in rows if r["name"] == "MiniCPM-SALA"]
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
  assert count == config["parameters"] == 629951488

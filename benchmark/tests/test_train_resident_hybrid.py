"""Driver `train_resident_hybrid` and the hybrid sequence configuration's
files, rehearsed on the CPU at a tiny preset: a whole run, the layer
metrics read from a recorded operation list, every control and planted
fault coming out not correct by the cell's own limits, the comparison
without an MTP loss, the operation counts against a hand count, and the
configuration file against the catalog's published sizes."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.drivers import train_resident_hybrid as driver
from benchmark.tests import tiny, tiny_hybrid

CELL = "qwen3_next_train_seq8k"
CONFIG = "qwen3_next_80b_a3b_ep16"


def _config():
  with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
    return json.load(f)


def test_run_end_to_end():
  result = tiny.run(tiny_hybrid.train_cell(), jax.devices()[:1])
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
  assert result["attempted"] > 0 and result["failed"] == 0
  assert result["compared"]["step_count_gap"]["value"] == 0
  assert {"gdn_decay_gap", "gdn_beta_gap"} <= set(result["compared"])
  assert not any("mtp" in name for name in result["compared"])


def test_traced_run_prints_the_cell_s_layer_metrics():
  """On the CPU the trace holds no device plane: the readers are driven
  on a run record as the harness builds it, the kernels' seconds as
  `release()` hands them on."""
  cell = tiny_hybrid.train_cell()
  attention = cell.flops.attention_kernel(cell.config)
  rule = cell.flops.gated_delta_rule_kernel(cell.config)
  window = {"window_s": 2.0, "examples": 8,
            "attention_kernel": {
                "calls": {"fwd": 8, "dq": 4, "dkv": 4},
                "seconds": {"fwd": 0.4, "dq": 0.3, "dkv": 0.3}},
            "gated_delta_rule": {
                "calls": {"fwd": 24, "bwd": 12}, "scope_seconds": 0.5,
                "kernel_seconds": {"fwd": 0.02, "bwd": 0.03}}}
  peaks = dict(tiny.PEAKS, hbm_bytes_per_s=1e9)
  run = {"cell": cell, "window": window, "peaks": peaks, "chips": 1,
         "trace": {"busy_s": 1.9, "window_s": 2.0}, "device": {}}
  names = [e["name"] for e in harness.metrics_for(cell, "per_layer")]
  assert names == ["device_idle_share.train", "step_mfu.train",
                   "dispatch_host_ms.train", "mla_attention_roofline.train",
                   "gated_delta_rule_roofline.train",
                   "linear_attention_time_share.train"]
  read = lambda name: harness._load_module("layer_metrics", name).read(run)
  batch = cell.traffic["batch_per_chip"]
  needed = batch * (8 * attention["fwd"]["flops"]
                    + 4 * attention["dq"]["flops"]
                    + 4 * attention["dkv"]["flops"])
  assert read("mla_attention_roofline.train") == pytest.approx(
      100 * needed / 1.0 / peaks["bf16_flops_per_s"])
  least = lambda work: max(work["flops"] / peaks["bf16_flops_per_s"],
                           work["bytes"] / peaks["hbm_bytes_per_s"])
  assert least(rule["fwd"]) == rule["fwd"]["bytes"] / 1e9  # by bytes here
  assert read("gated_delta_rule_roofline.train") == pytest.approx(
      100 * batch * (24 * least(rule["fwd"]) + 12 * least(rule["bwd"])) / 0.5)
  assert read("linear_attention_time_share.train") == pytest.approx(
      100 * 0.5 / 1.9)
  assert read("device_idle_share.train") == pytest.approx(5.0)
  # Nothing to read (an untraced run, a program without the kernel):
  # the metrics are left out, nothing raises.
  del window["gated_delta_rule"]
  assert read("gated_delta_rule_roofline.train") is None
  assert read("linear_attention_time_share.train") is None


def test_rule_seconds_are_the_scope_s_or_nothing(tmp_path, monkeypatch):
  from benchmark.trace import reduce as reduce_lib
  hlo = """
  %fusion.3 = f32[2] fusion(%p), metadata={op_name="jit(f)/block0/attn/gdn/rule/dot_general"}
  %fusion.4 = f32[2] fusion(%p), metadata={op_name="jit(f)/block0/attn/gdn/conv/mul"}
  ROOT %fusion.5 = f32[2] fusion(%p), metadata={op_name="jit(f)/transpose(jvp(block0))/attn/gdn/rule/mul"}
  %gated_delta_rule_fwd.1 = bf16[2] custom-call(%p), metadata={op_name="jit(f)/gdn/rule/pallas_call"}
  gated_delta_rule_fwd.2 = bf16[2] custom-call(%p), metadata={op_name="jit(f)/checkpoint/gdn/rule/pallas_call"}
  %gated_delta_rule_bwd.1 = (bf16[2]) custom-call(%p), metadata={op_name="jit(f)/transpose(jvp(gdn/rule))/pallas_call"}
  %while.1 = (f32[2]) while(%p), metadata={op_name="jit(f)/while"}
  """
  names = driver.scope_instructions(hlo, driver.RULE_SCOPE)
  assert names == {"fusion.3", "fusion.5", "gated_delta_rule_fwd.1",
                   "gated_delta_rule_fwd.2", "gated_delta_rule_bwd.1"}
  ops = [("%while.1 = (f32[2]) while()", 0.0, 14e9),  # holds all the others
         ("%gated_delta_rule_fwd.1 = bf16[2] custom-call()", 0.0, 4e9),
         ("%gated_delta_rule_fwd.2 = bf16[2] custom-call()", 5e9, 8e9),
         ("%gated_delta_rule_bwd.1 = (bf16[2]) custom-call()", 9e9, 11e9),
         ("%flash_attention_fwd.1 = bf16[2] custom-call()", 11e9, 12e9),
         ("%fusion.3 = f32[2] fusion()", 12e9, 13e9),
         ("%fusion.4 = f32[2] fusion()", 13e9, 13.5e9),
         ("%fusion.5 = f32[2] fusion()", 13.5e9, 14e9)]
  monkeypatch.setattr(reduce_lib, "find_xplane", lambda d: "x")
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops}})
  found = driver.delta_rule_seconds(str(tmp_path), names)
  assert found["calls"] == {"fwd": 2, "bwd": 1}
  assert found["kernel_seconds"] == pytest.approx({"fwd": 7.0, "bwd": 2.0})
  assert found["scope_seconds"] == pytest.approx(7.0 + 2.0 + 1.0 + 0.5)
  # A call of the rule's programs that the compiled text does not name
  # under the scope: the names did not join, nothing is read.
  assert driver.delta_rule_seconds(
      str(tmp_path), names - {"gated_delta_rule_fwd.2"}) is None
  # The backward not found: no share of part of the time.
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops[:3]}})
  assert driver.delta_rule_seconds(str(tmp_path), names) is None
  monkeypatch.undo()
  assert driver.delta_rule_seconds(str(tmp_path), names) is None  # no trace


def test_the_compiled_step_names_the_rule_s_instructions():
  """The tiny cell's K-step program, compiled here: its text carries the
  `gdn/rule` scope on instructions of the first run and of the backward
  pass, which is what `release()` joins the trace's operations to."""
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.specs import tensorspec_utils as ts
  from tensor2robot_tpu.train.trainer import Trainer
  cell = tiny_hybrid.train_cell()
  trainer = Trainer(harness.build_model(cell.config),
                    mesh=mesh_lib.create_mesh(devices=jax.devices()[:1]))
  state = trainer.create_train_state()
  tokens = jnp.zeros((2, 2, tiny_hybrid.SIZES["sequence_length"]), jnp.int32)
  text = trainer.aot_train_steps(
      state, ts.TensorSpecStruct({"tokens": tokens})).as_text()
  rule = driver.scope_instructions(text, driver.RULE_SCOPE)
  other = driver.scope_instructions(text, "gdn/conv")
  assert rule and other and not rule & other
  ops = {name: line for line in text.splitlines()
         for name in rule if f"{name} = " in line}
  assert any("transpose(" in line for line in ops.values())


@pytest.fixture(scope="module")
def finished():
  cell = tiny_hybrid.train_cell()
  session = driver.Session(cell, 2147483777, jax.devices()[:1],
                           jax.profiler.TraceAnnotation)
  session.run_window(0.3)
  session.release()
  return cell, session


def test_window_counts_the_load_and_the_gates(finished):
  cell, session = finished
  counters = session._window["counters"]
  layers = cell.config["num_hidden_layers"]
  total = (cell.config["sequence_length"] * cell.traffic["batch_per_chip"]
           * cell.config["num_experts_per_tok"] * layers)
  assert counters["moe/total_assignments"] == total
  for name in ("first/held_assignments", "moe/held_assignments",
               "moe/held_assignments_window_mean"):
    assert 0 < counters[name] <= total, name
  for name in ("gdn/decay_mean", "gdn/beta_mean", "first/gdn/decay_mean",
               "first/gdn/beta_mean"):
    assert len(counters[name]) == 3, name
    assert all(0 < x < 1 for x in counters[name]), (name, counters[name])
  assert "loss_mtp" not in counters
  json.dumps(counters)  # the harness prints them


_CONTROLS = ["control_fp8", "fault_smallest_leaf_frozen"] + [
    "fault_" + name for name in importlib.import_module(
        "benchmark.reference." + CONFIG).FAULTS]


def test_the_controls_are_the_reference_s_faults(finished):
  _, session = finished
  assert list(session.controls()) == _CONTROLS
  assert len(_CONTROLS) == 10


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


def test_compare_reads_no_mtp_loss():
  """Two runs' records without any MTP number: equal records read
  nought everywhere, a moved gate reads in its own number alone."""
  import numpy as np
  leaf = lambda x: {"a": np.asarray([x], np.float32),
                    "b": np.asarray([2 * x], np.float32)}
  record = {"loss": 3.0, "change": leaf(1.0), "moment": leaf(0.5),
            "first_grad": leaf(1.0),
            "expert_tokens": np.asarray([[3, 5], [4, 4]]),
            "gdn_decay_mean": np.asarray([0.2, 0.4, 0.5]),
            "gdn_beta_mean": np.asarray([0.5, 0.5, 0.5])}
  numbers = dict((n, v) for n, v, _ in driver.compare(record, record, {}))
  assert not any("mtp" in name for name in numbers)
  assert all(v == 0 for n, v in numbers.items()
             if n != "smallest_leaf_share"), numbers
  moved = dict(record, gdn_decay_mean=np.asarray([0.2, 0.44, 0.5]))
  numbers = dict((n, v) for n, v, _ in driver.compare(moved, record, {}))
  assert numbers["gdn_decay_gap"] == pytest.approx(0.1)
  assert numbers["gdn_beta_gap"] == 0 and numbers["last_loss_gap"] == 0


def test_operations_against_a_hand_count():
  """By hand, block by block (ISSUE 36 rounds it to 470): 460 MFLOP a
  token forward, 11.3 TFLOP a sequence trained."""
  config = _config()
  flops = harness._load_module("flops", CONFIG)
  parts = flops.forward_per_token(config)
  assert parts["delta_net_projections"] == 3 * 2 * (
      2048 * 12288 + 2048 * 64 + 4096 * 2048) == 3 * 2 * 33685504
  assert parts["delta_net_conv"] == 3 * 2 * 4 * 8192
  assert parts["delta_rule"] == 3 * 3 * 2 * 32 * 128 * 128
  assert parts["attention_projections"] == 2 * (
      2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) == 2 * 27262976
  assert parts["attention_scores_values"] == pytest.approx(
      2 * 4096.5 * 16 * 512)
  assert parts["expert_layers"] == pytest.approx(4 * (
      2 * 2048 * 512 + 2 * 3 * 2048 * 512 + 2 * 2048
      + 0.625 * 2 * 3 * 2048 * 512))
  assert parts["head"] == 2 * 2048 * 18992
  total = sum(parts.values())
  assert total == pytest.approx(460.5e6, rel=0.001)
  assert flops.train_per_example(config) == pytest.approx(11.32e12, rel=0.001)
  # A measured load in the expectation's place (10 * 32 / 512 = 0.625).
  assert flops.train_per_example(config, 0.625) == flops.train_per_example(
      config)
  assert (flops.train_per_example(config, 1.25)
          - flops.train_per_example(config)) == pytest.approx(
              3 * 8192 * 4 * 0.625 * 2 * 3 * 2048 * 512)
  kernel = flops.attention_kernel(config)
  pairs = 16 * 8192 * 8193 / 2
  assert kernel["fwd"]["flops"] == 2 * pairs * 512
  assert (kernel["dq"]["flops"] + kernel["dkv"]["flops"]
          == 2 * pairs * (3 * 256 + 2 * 256))
  # K and V are read at 2 heads, q and the output at 16.
  assert kernel["fwd"]["bytes"] == (
      2 * 16 * 8192 * 256 * 2 + 2 * 2 * 8192 * 256 * 2 + 16 * 8192 * 4)
  rule = flops.gated_delta_rule_kernel(config)
  assert rule["fwd"]["flops"] == 8192 * 32 * 3 * 2 * 128 * 128
  assert rule["bwd"]["flops"] == 2 * rule["fwd"]["flops"]
  assert rule["fwd"]["bytes"] == 8192 * (
      2 * 16 * 128 * 2 + 2 * 32 * 128 * 2 + 2 * 32 * 4)
  # By bytes, not operations, on a v5e: 0.25 ms against 0.13 ms.
  peaks = harness._load_json("peaks.json")["TPU v5 lite"]
  assert (rule["fwd"]["bytes"] / peaks["hbm_bytes_per_s"]
          > rule["fwd"]["flops"] / peaks["bf16_flops_per_s"])


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
          config["vocab_size"]) == (4, 32, 151936 // 8)
  assert config["router_width"] == published["num_experts"]
  # The program is built with the same sizes the reference reads; its
  # names for the router's width and the share are the class's own.
  kwargs = config["model"]["kwargs"]
  assert kwargs["experts_held"] == config["num_experts"]
  assert kwargs["n_routed_experts"] == config["router_width"]
  assert (kwargs["scoring_func"], kwargs["routed_scaling_factor"],
          kwargs["first_k_dense_replace"],
          kwargs["num_nextn_predict_layers"]) == ("softmax", 1.0, 0, 0)
  for key, value in kwargs.items():
    if key in config and key not in ("experts_held", "n_routed_experts"):
      assert config[key] == value, key
  for key in ("hidden_size", "head_dim", "num_attention_heads",
              "num_key_value_heads", "partial_rotary_factor",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_conv_kernel_dim", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_experts_per_tok",
              "full_attention_interval", "rope_theta"):
    assert kwargs[key] == published[key], key
  assert {"deployment", "assumed", "reduced_why"} <= set(config)


def test_configuration_holds_the_catalog_s_numbers():
  """Every key of the catalog entry's `config`, under the same key; only
  the `reduced` ones differ."""
  catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
  if not os.path.exists(catalog):
    pytest.skip("no catalog here")
  with open(catalog) as f:
    rows = [json.loads(line) for line in f]
  (row,) = [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
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
  assert count == config["parameters"] == 625667136

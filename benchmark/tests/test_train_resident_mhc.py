"""Driver `train_resident_mhc` and the multi-stream sequence
configuration's files, rehearsed on the CPU at a tiny preset: a whole
run, the layer metrics read from a recorded operation list, every
control and planted fault coming out not correct by the cell's own
limits, the comparison without an MTP loss, the operation counts against
a hand count, and the configuration file against the catalog's published
sizes."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.drivers import train_resident_mhc as driver
from benchmark.tests import tiny, tiny_mhc

CELL = "xing4_train_seq4k"
CONFIG = "xing4_0_29b_a4b_tp8ep8"
REDUCED = ["num_hidden_layers", "num_attention_heads", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]


def _config():
  with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
    return json.load(f)


def test_run_end_to_end():
  result = tiny.run(tiny_mhc.train_cell(), jax.devices()[:1])
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
  assert result["attempted"] > 0 and result["failed"] == 0
  assert result["compared"]["step_count_gap"]["value"] == 0
  assert {"mhc_res_diag_gap", "mhc_pre_gap", "mhc_post_gap"} <= set(
      result["compared"])
  assert not any("mtp" in name for name in result["compared"])


def test_traced_run_prints_the_cell_s_layer_metrics():
  """On the CPU the trace holds no device plane: the readers are driven
  on a run record as the harness builds it, the kernels' seconds as
  `release()` hands them on."""
  cell = tiny_mhc.train_cell()
  attention = cell.flops.attention_kernel(cell.config)
  passes = cell.flops.hyper_connection_kernel(cell.config)
  calls = {"pre_fwd": 12, "post_fwd": 12, "pre_bwd": 6, "post_bwd": 6}
  window = {"window_s": 2.0, "examples": 8,
            "attention_kernel": {
                "calls": {"fwd": 6, "dq": 3, "dkv": 3},
                "seconds": {"fwd": 0.4, "dq": 0.3, "dkv": 0.3}},
            "hyper_connection": {
                "calls": calls, "scope_seconds": 0.5,
                "kernel_seconds": {name: 0.1 for name in calls}}}
  peaks = dict(tiny.PEAKS, hbm_bytes_per_s=1e9)
  run = {"cell": cell, "window": window, "peaks": peaks, "chips": 1,
         "trace": {"busy_s": 1.9, "window_s": 2.0}, "device": {}}
  names = [e["name"] for e in harness.metrics_for(cell, "per_layer")]
  assert names == ["device_idle_share.train", "step_mfu.train",
                   "dispatch_host_ms.train", "mla_attention_roofline.train",
                   "hyper_connection_roofline.train",
                   "hyper_connection_time_share.train"]
  read = lambda name: harness._load_module("layer_metrics", name).read(run)
  batch = cell.traffic["batch_per_chip"]
  needed = batch * (6 * attention["fwd"]["flops"]
                    + 3 * attention["dq"]["flops"]
                    + 3 * attention["dkv"]["flops"])
  assert read("mla_attention_roofline.train") == pytest.approx(
      100 * needed / 1.0 / peaks["bf16_flops_per_s"])
  least = lambda work: max(work["flops"] / peaks["bf16_flops_per_s"],
                           work["bytes"] / peaks["hbm_bytes_per_s"])
  assert least(passes["post_fwd"]) == passes["post_fwd"]["bytes"] / 1e9
  assert read("hyper_connection_roofline.train") == pytest.approx(
      100 * batch * sum(calls[name] * least(passes[name])
                        for name in calls) / 0.5)
  assert read("hyper_connection_time_share.train") == pytest.approx(
      100 * 0.5 / 1.9)
  assert read("device_idle_share.train") == pytest.approx(5.0)
  assert read("step_mfu.train") > 0
  # Nothing to read (an untraced run, a program without the passes):
  # the metrics are left out, nothing raises.
  del window["hyper_connection"]
  assert read("hyper_connection_roofline.train") is None
  assert read("hyper_connection_time_share.train") is None


def test_pass_seconds_are_the_scope_s_or_nothing(tmp_path, monkeypatch):
  from benchmark.trace import reduce as reduce_lib
  hlo = """
  %fusion.3 = f32[2] fusion(%p), metadata={op_name="jit(f)/dense_block0/attn_hc/mhc/pre/mul"}
  %fusion.4 = f32[2] fusion(%p), metadata={op_name="jit(f)/dense_block0/attn/mla/dot_general"}
  ROOT %fusion.5 = f32[2] fusion(%p), metadata={op_name="jit(f)/transpose(jvp(dense_block0))/ffn_hc/mhc/post/reduce_sum"}
  %hyper_connection_pre_fwd.1 = bf16[2] custom-call(%p), metadata={op_name="jit(f)/mhc/pre/pallas_call"}
  hyper_connection_pre_fwd.2 = bf16[2] custom-call(%p), metadata={op_name="jit(f)/checkpoint/mhc/pre/pallas_call"}
  %hyper_connection_post_fwd.1 = bf16[2] custom-call(%p), metadata={op_name="jit(f)/mhc/post/pallas_call"}
  %hyper_connection_pre_bwd.1 = (bf16[2]) custom-call(%p), metadata={op_name="jit(f)/transpose(jvp(mhc/pre))/pallas_call"}
  %hyper_connection_post_bwd.1 = (bf16[2]) custom-call(%p), metadata={op_name="jit(f)/transpose(jvp(mhc/post))/pallas_call"}
  %while.1 = (f32[2]) while(%p), metadata={op_name="jit(f)/while"}
  """
  names = driver.scope_instructions(hlo, driver.MHC_SCOPE)
  assert names == {"fusion.3", "fusion.5", "hyper_connection_pre_fwd.1",
                   "hyper_connection_pre_fwd.2",
                   "hyper_connection_post_fwd.1",
                   "hyper_connection_pre_bwd.1",
                   "hyper_connection_post_bwd.1"}
  ops = [("%while.1 = (f32[2]) while()", 0.0, 20e9),  # holds all the others
         ("%hyper_connection_pre_fwd.1 = bf16[2] custom-call()", 0.0, 4e9),
         ("%hyper_connection_pre_fwd.2 = bf16[2] custom-call()", 5e9, 8e9),
         ("%hyper_connection_post_fwd.1 = bf16[2] custom-call()", 8e9, 9e9),
         ("%hyper_connection_pre_bwd.1 = (bf16[2]) custom-call()", 9e9, 11e9),
         ("%flash_attention_fwd.1 = bf16[2] custom-call()", 11e9, 12e9),
         ("%fusion.3 = f32[2] fusion()", 12e9, 13e9),
         ("%fusion.4 = f32[2] fusion()", 13e9, 13.5e9),
         ("%fusion.5 = f32[2] fusion()", 13.5e9, 14e9),
         ("%hyper_connection_post_bwd.1 = (bf16[2]) custom-call()", 14e9,
          17e9)]
  monkeypatch.setattr(reduce_lib, "find_xplane", lambda d: "x")
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops}})
  found = driver.hyper_connection_seconds(str(tmp_path), names)
  assert found["calls"] == {"pre_fwd": 2, "post_fwd": 1, "pre_bwd": 1,
                            "post_bwd": 1}
  assert found["kernel_seconds"] == pytest.approx(
      {"pre_fwd": 7.0, "post_fwd": 1.0, "pre_bwd": 2.0, "post_bwd": 3.0})
  assert found["scope_seconds"] == pytest.approx(13.0 + 1.0 + 0.5)
  # A call of the passes' programs that the compiled text does not name
  # under the scope: the names did not join, nothing is read.
  assert driver.hyper_connection_seconds(
      str(tmp_path), names - {"hyper_connection_pre_fwd.2"}) is None
  # A backward program not found: no share of part of the time.
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops[:5]}})
  assert driver.hyper_connection_seconds(str(tmp_path), names) is None
  monkeypatch.undo()
  assert driver.hyper_connection_seconds(str(tmp_path), names) is None


def test_the_compiled_step_names_the_passes_instructions():
  """The tiny cell's K-step program, compiled here: its text carries the
  `mhc/` scope on instructions of the first run and of the backward pass,
  and on none of attention's, which is what `release()` joins the trace's
  operations to."""
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.specs import tensorspec_utils as ts
  from tensor2robot_tpu.train.trainer import Trainer
  cell = tiny_mhc.train_cell()
  trainer = Trainer(harness.build_model(cell.config),
                    mesh=mesh_lib.create_mesh(devices=jax.devices()[:1]))
  state = trainer.create_train_state()
  tokens = jnp.zeros((2, 2, tiny_mhc.SIZES["sequence_length"]), jnp.int32)
  text = trainer.aot_train_steps(
      state, ts.TensorSpecStruct({"tokens": tokens})).as_text()
  passes = driver.scope_instructions(text, driver.MHC_SCOPE)
  other = driver.scope_instructions(text, "mla/")
  assert passes and other and not passes & other
  for part in ("mhc/pre", "mhc/post"):
    assert driver.scope_instructions(text, part) <= passes
  ops = {name: line for line in text.splitlines()
         for name in passes if f"{name} = " in line}
  assert any("transpose(" in line for line in ops.values())


@pytest.fixture(scope="module")
def finished():
  cell = tiny_mhc.train_cell()
  session = driver.Session(cell, 2147483777, jax.devices()[:1],
                           jax.profiler.TraceAnnotation)
  session.run_window(0.3)
  session.release()
  return cell, session


def test_window_counts_the_load_and_the_maps(finished):
  cell, session = finished
  counters = session._window["counters"]
  layers = cell.config["num_hidden_layers"]
  total = (cell.config["sequence_length"] * cell.traffic["batch_per_chip"]
           * cell.config["num_experts_per_tok"]
           * (layers - cell.config["dense_blocks_run"]))
  assert counters["moe/total_assignments"] == total
  for name in ("first/held_assignments", "moe/held_assignments",
               "moe/held_assignments_window_mean"):
    assert 0 < counters[name] <= total, name
  for name in ("mhc/res_diag_mean", "mhc/pre_mean", "mhc/post_mean",
               "mhc/sinkhorn_gap"):
    for rows in (counters[name], counters["first/" + name]):
      assert len(rows) == layers and all(len(row) == 2 for row in rows)
  assert all(0.1 < x < 1 for row in counters["mhc/res_diag_mean"]
             for x in row)
  assert all(0 < x < 2 for row in counters["mhc/post_mean"] for x in row)
  assert "loss_mtp" not in counters
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


def test_compare_reads_no_mtp_loss():
  """Two runs' records without any MTP number: equal records read
  nought everywhere, a moved map reads in its own number alone."""
  import numpy as np
  leaf = lambda x: {"a": np.asarray([x], np.float32),
                    "b": np.asarray([2 * x], np.float32)}
  maps = lambda x: np.full((3, 2), x)
  record = {"loss": 3.0, "change": leaf(1.0), "moment": leaf(0.5),
            "first_grad": leaf(1.0),
            "expert_tokens": np.asarray([[3, 5], [4, 4]]),
            "mhc_res_diag_mean": maps(0.4), "mhc_pre_mean": maps(0.5),
            "mhc_post_mean": maps(1.0), "mhc_sinkhorn_gap": maps(1e-5)}
  numbers = dict((n, v) for n, v, _ in driver.compare(record, record, {}))
  assert not any("mtp" in name for name in numbers)
  assert all(v == 0 for n, v in numbers.items()
             if n not in ("smallest_leaf_share", "mhc_sinkhorn_gap")), numbers
  assert numbers["mhc_sinkhorn_gap"] == pytest.approx(1e-5)
  moved = dict(record, mhc_res_diag_mean=np.where(
      np.arange(6).reshape(3, 2) == 3, 0.44, 0.4))
  numbers = dict((n, v) for n, v, _ in driver.compare(moved, record, {}))
  assert numbers["mhc_res_diag_gap"] == pytest.approx(0.1)
  assert numbers["mhc_pre_gap"] == 0 and numbers["last_loss_gap"] == 0


def test_operations_against_a_hand_count():
  """By hand, block by block (ISSUE 38: about 0.56 G a token forward, 9
  TFLOP a step with recomputation, which this count leaves out)."""
  config = _config()
  flops = harness._load_module("flops", CONFIG)
  parts = flops.forward_per_token(config)
  mla = 2 * (3584 * 768 + 768 * 4 * 192 + 3584 * 576 + 512 * 4 * 256
             + 4 * 128 * 3584)
  assert mla == 2 * 7766016
  assert parts["mla_projections"] == 5 * mla
  assert parts["attention_scores_values"] == pytest.approx(
      5 * 2 * 2048.5 * 4 * 320)
  assert parts["dense_mlp"] == 2 * 3 * 3584 * 9216
  assert parts["expert_layers"] == pytest.approx(4 * (
      2 * 3584 * 64 + (1 + 0.5) * 2 * 3 * 3584 * 1024))
  assert parts["hyper_connection_maps"] == 10 * 2 * 14336 * 24
  assert parts["hyper_connection_sums"] == 10 * 2 * 24 * 3584
  assert parts["mtp_projection"] == 0
  assert parts["heads"] == 2 * 3584 * 16384
  total = sum(parts.values())
  assert total == pytest.approx(562.06e6, rel=0.0001)
  assert flops.train_per_example(config) == pytest.approx(
      3 * 4096 * total) == pytest.approx(6.907e12, rel=0.001)
  # A measured load in the expectation's place (4 * 8 / 64 = 0.5).
  assert flops.train_per_example(config, 0.5) == flops.train_per_example(
      config)
  assert (flops.train_per_example(config, 1.0)
          - flops.train_per_example(config)) == pytest.approx(
              3 * 4096 * 4 * 0.5 * 2 * 3 * 3584 * 1024)
  kernel = flops.attention_kernel(config)
  pairs = 4 * 4096 * 4097 / 2
  assert kernel["fwd"]["flops"] == 2 * pairs * (192 + 128)
  assert (kernel["dq"]["flops"] + kernel["dkv"]["flops"]
          == 2 * pairs * (3 * 192 + 2 * 128))
  assert kernel["fwd"]["bytes"] == (
      2 * 4 * 4096 * 192 * 2 + 2 * 4 * 4096 * 128 * 2 + 4 * 4096 * 4)
  passes = flops.hyper_connection_kernel(config)
  row = 4096 * 3584 * 2
  assert passes["pre_fwd"]["bytes"] == (
      4 * row + 14336 * 24 * 4 + row + 4096 * 24 * 4)
  assert passes["post_fwd"]["bytes"] == 5 * row + 4096 * 20 * 4 + 4 * row
  assert passes["post_bwd"]["bytes"] == (
      9 * row + 2 * 4096 * 20 * 4 + 5 * row)
  assert passes["pre_bwd"]["bytes"] == (
      5 * row + 2 * 14336 * 24 * 4 + 4096 * 24 * 4 + 4 * row)
  assert passes["post_fwd"]["flops"] == 2 * 4096 * 20 * 3584
  assert passes["post_bwd"]["flops"] == 2 * passes["post_fwd"]["flops"]
  # By bytes, not operations, on a v5e, every one of the four.
  peaks = harness._load_json("peaks.json")["TPU v5 lite"]
  for name, work in passes.items():
    assert (work["bytes"] / peaks["hbm_bytes_per_s"]
            > 5 * work["flops"] / peaks["bf16_flops_per_s"]), name


def test_configuration_keeps_every_published_width():
  config = _config()
  published = config["published"]
  with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    entry = [c for c in json.load(f)["configs"] if c["name"] == CONFIG][0]
  assert entry["source"] == config["source"]
  assert entry["reduced"] == config["reduced"] == REDUCED
  assert set(config["reduced_why"]) == set(config["reduced"])
  for key, value in published.items():
    if key == "where":
      continue
    if key in config["reduced"]:
      assert config[key] != value
    else:
      assert config[key] == value, key
  assert [config[key] for key in REDUCED] == [5, 4, 8, 131072 // 8, 0]
  assert config["router_width"] == published["n_routed_experts"]
  # The leading dense layers are counted once: the pattern's key stays
  # the published 2, program and reference build 1.
  assert (config["first_k_dense_replace"], config["dense_blocks_run"]) == (
      2, 1)
  # The program is built with the same sizes the reference reads; its
  # names for the router's width and the shares are the class's own.
  kwargs = config["model"]["kwargs"]
  assert kwargs["experts_held"] == config["n_routed_experts"]
  assert kwargs["n_routed_experts"] == config["router_width"]
  assert kwargs["first_k_dense_replace"] == config["dense_blocks_run"]
  for key, value in kwargs.items():
    if key in config and key not in ("n_routed_experts",
                                     "first_k_dense_replace"):
      assert config[key] == value, key
  for key in ("hidden_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "routed_scaling_factor", "hc_mult",
              "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
              "mhc_h_res_clamp_max", "rope_theta", "rope_scaling"):
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
  (row,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
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
  assert count == config["parameters"] == 656127246

"""Driver `train_resident_tokens` and the sequence configuration's
files, rehearsed on the CPU at a tiny preset: a whole run (untraced and
traced, with the attention kernel's metric read from a recorded
operation list), every control and planted fault coming out not correct
by the cell's own limits, the operation counts against a hand count,
and the configuration file against the catalog's published sizes."""

import importlib
import json
import os

import jax
import pytest

from benchmark import harness
from benchmark.drivers import train_resident_tokens as driver
from benchmark.tests import tiny, tiny_tokens

CELL = "joyai_flash_train_seq8k"


def _config():
  with open(os.path.join(harness.HERE, "configs",
                         "joyai_llm_flash_ep16.json")) as f:
    return json.load(f)


def test_run_end_to_end():
  result = tiny.run(tiny_tokens.train_cell(), jax.devices()[:1])
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
  assert result["attempted"] > 0 and result["failed"] == 0
  assert result["compared"]["step_count_gap"]["value"] == 0


def test_traced_run_prints_the_cell_s_layer_metrics():
  """On the CPU the trace holds no device plane, so the harness has no
  summary to give: the readers are driven on a run record as the harness
  builds it, the kernel's seconds as `release()` hands them on."""
  cell = tiny_tokens.train_cell()
  flops = cell.flops.attention_kernel(cell.config)
  window = {"window_s": 2.0, "examples": 8,
            "attention_kernel": {
                "calls": {"fwd": 8, "dq": 4, "dkv": 4},
                "seconds": {"fwd": 0.4, "dq": 0.3, "dkv": 0.3}}}
  run = {"cell": cell, "window": window, "peaks": tiny.PEAKS, "chips": 1,
         "trace": {"busy_s": 1.9, "window_s": 2.0}, "device": {}}
  names = [e["name"] for e in harness.metrics_for(cell, "per_layer")]
  assert names == ["device_idle_share.train", "step_mfu.train",
                   "dispatch_host_ms.train", "mla_attention_roofline.train"]
  read = lambda name: harness._load_module("layer_metrics", name).read(run)
  batch = cell.traffic["batch_per_chip"]
  needed = batch * (8 * flops["fwd"]["flops"] + 4 * flops["dq"]["flops"]
                    + 4 * flops["dkv"]["flops"])
  assert read("mla_attention_roofline.train") == pytest.approx(
      100 * needed / 1.0 / tiny.PEAKS["bf16_flops_per_s"])
  assert read("step_mfu.train") == pytest.approx(
      100 * cell.flops.train_per_example(cell.config) * 4
      / tiny.PEAKS["bf16_flops_per_s"])
  assert read("device_idle_share.train") == pytest.approx(5.0)
  # Nothing to read (an untraced run, a program whose kernel is named
  # otherwise): the metric is left out, nothing raises.
  del window["attention_kernel"]
  assert read("mla_attention_roofline.train") is None


def test_kernel_seconds_cover_every_program_or_nothing(tmp_path, monkeypatch):
  from benchmark.trace import reduce as reduce_lib
  ops = [("%flash_attention_fwd.1 = bf16[2] custom-call()", 0.0, 4e9),
         ("%flash_attention_fwd.2 = bf16[2] custom-call()", 5e9, 8e9),
         ("%flash_attention_dq.1 = bf16[2] custom-call()", 9e9, 11e9),
         ("%flash_attention_dkv.1 = (bf16[2]) custom-call()", 11e9, 12e9),
         ("%fusion.3 = f32[2] fusion()", 12e9, 13e9)]
  monkeypatch.setattr(reduce_lib, "find_xplane", lambda d: "x")
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops}})
  found = driver.attention_kernel_seconds(str(tmp_path))
  assert found["calls"] == {"fwd": 2, "dq": 1, "dkv": 1}
  assert found["seconds"] == pytest.approx(
      {"fwd": 7.0, "dq": 2.0, "dkv": 1.0})
  # One of the three programs not found: no share of part of the time.
  monkeypatch.setattr(reduce_lib, "load",
                      lambda p: {"devices": {"/device:TPU:0": ops[:3]}})
  assert driver.attention_kernel_seconds(str(tmp_path)) is None
  monkeypatch.undo()
  assert driver.attention_kernel_seconds(str(tmp_path)) is None  # no trace


@pytest.fixture(scope="module")
def finished():
  cell = tiny_tokens.train_cell()
  session = driver.Session(cell, 2147483777, jax.devices()[:1],
                           jax.profiler.TraceAnnotation)
  session.run_window(0.3)
  session.release()
  return cell, session


def test_window_counts_the_load_at_its_start_and_at_its_end(finished):
  """The run trains, so the router's choices drift: the window reports
  the held experts' load at the first dispatch beside the last's."""
  cell, session = finished
  counters = session._window["counters"]
  layers = (cell.config["num_hidden_layers"]
            - cell.config["first_k_dense_replace"]
            + cell.config["num_nextn_predict_layers"])
  total = (cell.config["sequence_length"] * cell.traffic["batch_per_chip"]
           * cell.config["num_experts_per_tok"] * layers)
  assert counters["moe/total_assignments"] == total
  for name in ("first/held_assignments", "moe/held_assignments",
               "moe/held_assignments_window_mean"):
    assert 0 < counters[name] <= total, name
  balanced = cell.flops.train_per_example(cell.config)
  assert counters["train_flops_per_example_at_window_mean"] == pytest.approx(
      balanced + 3 * (counters["moe/held_assignments_window_mean"]
                      / cell.traffic["batch_per_chip"]
                      - cell.config["sequence_length"] * layers
                      * cell.config["num_experts_per_tok"]
                      * cell.config["n_routed_experts"]
                      / cell.config["router_width"])
      * 2 * 3 * cell.config["hidden_size"]
      * cell.config["moe_intermediate_size"])
  assert (counters["first/min_expert_tokens"]
          <= counters["first/held_assignments"]
          / (layers * cell.config["n_routed_experts"])
          <= counters["first/max_expert_tokens"])


def test_every_control_comes_out_not_correct(finished):
  cell, session = finished
  controls = session.controls()
  assert set(controls) == {
      "control_fp8", "fault_smallest_leaf_frozen", "fault_top4",
      "fault_normalize_held", "fault_no_bias", "fault_no_mtp",
      "fault_half_positions"}
  for name, kwargs in controls.items():
    rows = session.check(cell.limits, **kwargs)
    over = [n for n, value, limit in rows
            if limit is not None and not value <= limit]
    assert over, (name, rows)


def test_every_limit_of_the_cell_is_of_a_number_the_driver_reads(finished):
  cell, session = finished
  rows = session.check(cell.limits)
  assert set(cell.limits) <= {name for name, _, _ in rows}
  for name, value, limit in rows:
    assert value == value and (limit is None or limit >= 0), name
    assert limit is None or value <= limit, (name, value, limit)


def test_operations_against_a_hand_count():
  """ISSUE 32's count: 1.13 GFLOP a token forward, of it MLA's
  projections 28%, scores and values 44%, the two heads 12%, the dense
  MLP 8%, the expert layers 7%; 27.8 TFLOP a sequence, trained."""
  config = _config()
  flops = harness._load_module("flops", "joyai_llm_flash_ep16")
  parts = flops.forward_per_token(config)
  total = sum(parts.values())
  # by hand, block by block
  attention = 2 * (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                   + 4096 * 2048)
  assert parts["mla_projections"] == 6 * attention == 6 * 2 * 26345472
  assert parts["attention_scores_values"] == pytest.approx(
      6 * 2 * 4096.5 * 32 * 320)
  assert parts["dense_mlp"] == 2 * 3 * 2048 * 7168
  assert parts["expert_layers"] == pytest.approx(
      5 * (2 * 2048 * 256 + 1.5 * 2 * 3 * 2048 * 768))
  assert parts["heads"] == 2 * 2 * 2048 * 16160
  assert total == pytest.approx(1.13e9, rel=0.005)
  shares = {k: round(100 * v / total) for k, v in parts.items()}
  assert shares == {"mla_projections": 28, "attention_scores_values": 44,
                    "dense_mlp": 8, "expert_layers": 7, "mtp_projection": 1,
                    "heads": 12}
  assert flops.train_per_example(config) == pytest.approx(27.8e12, rel=0.005)
  # A measured load in the expectation's place: the balanced router's
  # own count changes nothing, twice the load adds the routed experts'
  # products once more (8 * 16 / 256 = 0.5 assignments a token).
  assert flops.train_per_example(config, 0.5) == flops.train_per_example(
      config)
  assert (flops.train_per_example(config, 1.0)
          - flops.train_per_example(config)) == pytest.approx(
              3 * 8192 * 5 * 0.5 * 2 * 3 * 2048 * 768)
  kernel = flops.attention_kernel(config)
  pairs = 32 * 8192 * 8193 / 2
  assert kernel["fwd"]["flops"] == 2 * pairs * (192 + 128)
  assert (kernel["dq"]["flops"] + kernel["dkv"]["flops"]
          == 2 * pairs * (192 + 128 + 192 + 128 + 192))
  # the kernel, forward and backward: 3.6 x its forward where v is
  # narrower than q/k (3.5 x at equal widths)
  assert sum(k["flops"] for k in kernel.values()) == pytest.approx(
      3.6 * kernel["fwd"]["flops"])


def test_configuration_keeps_every_published_width():
  config = _config()
  published = config["published"]
  with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    entry = [c for c in json.load(f)["configs"]
             if c["name"] == "joyai_llm_flash_ep16"][0]
  assert entry["reduced"] == config["reduced"] == [
      "num_hidden_layers", "n_routed_experts", "vocab_size"]
  for key, value in published.items():
    if key == "where":
      continue
    if key in config["reduced"]:
      assert config[key] != value
    else:
      assert config[key] == value, key
  assert (config["num_hidden_layers"], config["n_routed_experts"],
          config["vocab_size"]) == (5, 16, 129280 // 8)
  assert config["router_width"] == published["n_routed_experts"]
  # The program is built with the same sizes the reference reads.
  kwargs = config["model"]["kwargs"]
  assert kwargs["experts_held"] == config["n_routed_experts"]
  assert kwargs["n_routed_experts"] == config["router_width"]
  for key, value in kwargs.items():
    if key not in ("experts_held", "n_routed_experts"):
      assert config[key] == value, key
  assert {"deployment", "assumed", "reduced_why"} <= set(config)


def test_parameter_count_is_the_configuration_s():
  config = _config()
  reference = importlib.import_module(
      "benchmark.reference.joyai_llm_flash_ep16")
  shapes = jax.eval_shape(
      lambda k: reference.init_variables(k, config), jax.random.key(0))
  count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
  assert count == config["parameters"] == 680441088

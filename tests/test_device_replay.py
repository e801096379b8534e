"""Device-resident replay + fused megastep (ISSUE 4 acceptance).

Covers the tentpole contracts chiplessly on the 8-device CPU mesh:
device/host sampling agreement (seeded determinism + statistical
distribution tests for uniform and prioritized), priority round-trips
without drift, capacity-axis sharding via the existing mesh rules,
float32 dtype normalization at the SampleInfo boundary, the
one-megastep-executable ledger (target refresh never recompiles), and
the device-resident off-policy smoke: >= 30% eval TD reduction through
the fused learner plus the learner-throughput block's device-vs-host
speedup at the same batch shape.
"""

import json
import os

import jax
import numpy as np
import optax
import pytest

from tensor2robot_tpu.replay.device_buffer import (DeviceReplayBuffer,
                                                   MegastepLearner)
from tensor2robot_tpu.replay.loop import transition_spec
from tensor2robot_tpu.replay.ring_buffer import ReplayBuffer
from tensor2robot_tpu.replay.smoke import TinyQCriticModel
from tensor2robot_tpu.train.trainer import Trainer

IMG = 8


def _transitions(n, seed=0, img=IMG, action_size=4):
  rng = np.random.default_rng(seed)
  return {
      "image": rng.integers(0, 255, (n, img, img, 3), np.uint8),
      "action": rng.uniform(-1, 1, (n, action_size)).astype(np.float32),
      "reward": rng.random(n).astype(np.float32),
      "done": (rng.random(n) < 0.5).astype(np.float32),
      "next_image": rng.integers(0, 255, (n, img, img, 3), np.uint8),
  }


def _device_buffer(capacity=16, batch=8, seed=0, **kwargs):
  return DeviceReplayBuffer(
      transition_spec(IMG, 4), capacity=capacity,
      sample_batch_size=batch, seed=seed,
      ingest_chunk=kwargs.pop("ingest_chunk", capacity), **kwargs)


def _frequencies(buffer, draws, capacity):
  counts = np.zeros(capacity)
  total = 0
  while total < draws:
    _, info = buffer.sample()
    counts += np.bincount(info.indices, minlength=capacity)
    total += len(info.indices)
  return counts / counts.sum()


class TestDeviceReplayBuffer:

  def test_extend_chunking_wraparound_and_bookkeeping(self):
    buf = _device_buffer(capacity=16, ingest_chunk=4)
    buf.extend(_transitions(10))
    # 10 staged -> two full chunks flushed, 2 pending host-side.
    assert buf.size == 8 and buf.append_count == 8 and buf.pending == 2
    buf.extend(_transitions(14, seed=1))
    # 24 appended of capacity 16: the ring wrapped.
    assert buf.size == 16 and buf.append_count == 24 and buf.pending == 0
    assert buf.fill_fraction == 1.0
    assert buf.compile_counts["device_extend"] == 1  # one shape, ever

  def test_fixed_shape_and_boundary_dtypes(self):
    """ISSUE 4 dtype satellite: SampleInfo.probabilities is float32 on
    BOTH paths (the device computes float32; the host normalizes)."""
    dev = _device_buffer(prioritized=True)
    dev.extend(_transitions(16))
    host = ReplayBuffer(transition_spec(IMG, 4), capacity=16,
                        sample_batch_size=8, seed=0, prioritized=True)
    host.extend(_transitions(16))
    for buf in (dev, host):
      batch, info = buf.sample()
      assert np.asarray(batch["image"]).shape == (8, IMG, IMG, 3)
      assert info.probabilities.dtype == np.float32
      assert info.indices.dtype == np.int64
      assert info.staleness.dtype == np.int64

  def test_seeded_sampling_determinism(self):
    def stream(seed):
      buf = _device_buffer(seed=seed, prioritized=True)
      buf.extend(_transitions(16))
      return [buf.sample()[1].indices.tolist() for _ in range(5)]

    assert stream(7) == stream(7)
    assert stream(7) != stream(8)

  def test_uniform_distribution_agrees_with_host(self):
    """Statistical acceptance: device uniform sampling matches the
    host path's distribution (both ~Uniform[0, size))."""
    dev = _device_buffer()
    dev.extend(_transitions(16))
    host = ReplayBuffer(transition_spec(IMG, 4), capacity=16,
                        sample_batch_size=8, seed=1)
    host.extend(_transitions(16))
    f_dev = _frequencies(dev, 4000, 16)
    f_host = _frequencies(host, 4000, 16)
    np.testing.assert_allclose(f_dev, 1.0 / 16, atol=0.02)
    np.testing.assert_allclose(f_host, 1.0 / 16, atol=0.02)
    assert 0.5 * np.abs(f_dev - f_host).sum() < 0.05  # TV distance

  def test_prioritized_distribution_agrees_with_host(self):
    """Same known TD errors on both paths -> both empirical sampling
    distributions match the (|td| + eps)^alpha theory and each other."""
    td = np.linspace(0.0, 1.5, 16, dtype=np.float32)
    theory = (np.abs(td) + 1e-3) ** 0.6
    theory = theory / theory.sum()
    dev = _device_buffer(prioritized=True)
    dev.extend(_transitions(16))
    dev.update_priorities(np.arange(16), td)
    host = ReplayBuffer(transition_spec(IMG, 4), capacity=16,
                        sample_batch_size=8, seed=1, prioritized=True)
    host.extend(_transitions(16))
    host.update_priorities(np.arange(16), td)
    f_dev = _frequencies(dev, 6000, 16)
    f_host = _frequencies(host, 6000, 16)
    np.testing.assert_allclose(f_dev, theory, atol=0.03)
    np.testing.assert_allclose(f_host, theory, atol=0.03)
    assert 0.5 * np.abs(f_dev - f_host).sum() < 0.05

  def test_priorities_roundtrip_without_drift(self):
    """Set -> read returns (|td| + eps)^alpha at float32 precision, and
    after many scattered updates the root still equals the leaf sum
    (parents are fully recomputed, never delta-propagated)."""
    buf = _device_buffer(capacity=32, prioritized=True)
    buf.extend(_transitions(32))
    rng = np.random.default_rng(3)
    for _ in range(50):
      idx = rng.integers(0, 32, size=8)
      buf.update_priorities(idx, rng.random(8))
    td = rng.random(32).astype(np.float32)
    buf.update_priorities(np.arange(32), td)
    expected = (np.abs(td) + np.float32(1e-3)) ** np.float32(0.6)
    np.testing.assert_allclose(buf.priorities(np.arange(32)), expected,
                               rtol=1e-6)
    tree = np.asarray(jax.device_get(buf.state.tree))
    assert tree[1] == pytest.approx(expected.sum(), rel=1e-5)

  def test_duplicate_index_updates_reduce_deterministically(self):
    """Sampling with replacement can repeat a slot within one batch
    with disagreeing TDs (per-position CEM label keys): the device
    path reduces duplicates by MAX before the scatter — a commutative,
    backend-independent rule — never XLA's unspecified scatter winner."""
    buf = _device_buffer(capacity=16, prioritized=True)
    buf.extend(_transitions(16))
    buf.update_priorities([2, 2, 2, 5], [0.1, 0.9, 0.4, 0.2])
    expected_2 = (np.float32(0.9) + np.float32(1e-3)) ** np.float32(0.6)
    expected_5 = (np.float32(0.2) + np.float32(1e-3)) ** np.float32(0.6)
    assert buf.priorities([2])[0] == pytest.approx(expected_2, rel=1e-6)
    assert buf.priorities([5])[0] == pytest.approx(expected_5, rel=1e-6)

  def test_underfilled_prioritized_never_emits_unwritten_slots(self):
    buf = _device_buffer(capacity=16, ingest_chunk=8, prioritized=True)
    buf.extend(_transitions(8))
    assert buf.size == 8
    for _ in range(30):
      _, info = buf.sample()
      assert info.indices.max() < 8

  def test_capacity_sharding_uses_mesh_rule(self):
    """capacity % data axis == 0 -> storage shards over capacity via
    the ring rule; indivisible -> REFUSED with the nearest divisible
    capacities named (ISSUE 7: the silent replicated fallback would
    quietly hold the FULL ring on every chip of a pod run);
    shard_capacity=False is the explicit opt-in to replication."""
    from jax.sharding import PartitionSpec
    sharded = _device_buffer(capacity=16)
    spec = sharded.state.storage["image"].sharding.spec
    assert tuple(spec) == tuple(PartitionSpec("data"))
    with pytest.raises(ValueError, match=r"capacity 12 .*8 or 16"):
      _device_buffer(capacity=12, batch=4)
    replicated = _device_buffer(capacity=12, batch=4,
                                shard_capacity=False)
    spec = replicated.state.storage["image"].sharding.spec
    assert tuple(spec) == tuple(PartitionSpec())

  def test_capacity_refusal_names_axis_size_when_below(self):
    """capacity < axis size has no lower multiple: the error names
    the axis size itself as the fix."""
    with pytest.raises(ValueError, match="capacity 3 .*\\(8\\)"):
      _device_buffer(capacity=3, batch=2)

  def test_validation_at_the_door(self):
    buf = _device_buffer()
    bad = _transitions(4)
    bad["action"] = np.zeros((4, 5), np.float32)
    with pytest.raises(ValueError, match="action"):
      buf.extend(bad)


class TestMegastepLearner:

  def _setup(self, inner_steps=4, capacity=32, batch=16, seed=0):
    from tensor2robot_tpu.export import export_utils
    model = TinyQCriticModel(image_size=IMG,
                             optimizer_fn=lambda: optax.adam(1e-3))
    trainer = Trainer(model, seed=seed)
    state = trainer.create_train_state(batch_size=batch)
    variables = export_utils.fetch_variables_to_host(
        state.variables(use_ema=True))
    buf = DeviceReplayBuffer(
        transition_spec(IMG, 4), capacity, batch, seed=seed,
        prioritized=True, ingest_chunk=capacity, mesh=trainer.mesh)
    buf.extend(_transitions(capacity, seed=seed))
    learner = MegastepLearner(
        model, trainer, buf, action_size=4, gamma=0.8, num_samples=8,
        num_elites=2, iterations=2, inner_steps=inner_steps,
        seed=seed + 13)
    learner.refresh(variables, step=0)
    return state, learner, buf, variables

  def test_one_executable_k_steps_per_dispatch(self):
    state, learner, buf, _ = self._setup(inner_steps=4)
    for _ in range(3):
      state, metrics = learner.step(state)
    assert int(jax.device_get(state.step)) == 12  # 3 dispatches x K=4
    assert learner.compile_counts == {"megastep": 1}
    assert buf.compile_counts == {"device_extend": 1}
    for value in metrics.values():
      assert np.isfinite(value)

  def test_refresh_swaps_target_without_recompiling(self):
    state, learner, _, variables = self._setup(inner_steps=2)
    state, _ = learner.step(state)
    bumped = jax.tree_util.tree_map(lambda x: x + 0.05, variables)
    learner.refresh(bumped, step=2)
    state, _ = learner.step(state)
    assert learner.compile_counts == {"megastep": 1}
    assert learner.target_lag(10) == 8

  def test_megastep_is_deterministic(self):
    def metrics_stream(seed):
      state, learner, _, _ = self._setup(inner_steps=2, seed=seed)
      out = []
      for _ in range(2):
        state, metrics = learner.step(state)
        out.append(metrics)
      return out

    a, b = metrics_stream(0), metrics_stream(0)
    for m_a, m_b in zip(a, b):
      assert m_a == m_b
    assert metrics_stream(1) != a

  def test_priorities_move_during_training(self):
    """The in-place priority write-back is live: after megasteps, the
    tree no longer sits at the max-priority insert plateau."""
    state, learner, buf, _ = self._setup(inner_steps=4)
    before = buf.priorities(np.arange(32)).copy()
    state, _ = learner.step(state)
    after = buf.priorities(np.arange(32))
    assert not np.allclose(before, after)


@pytest.fixture(scope="module")
def device_smoke_results(tmp_path_factory):
  """ONE device-resident off-policy smoke shared by the acceptance
  assertions — run through the CLI in a subprocess under the ARTIFACT
  environment (plain single-device CPU backend), not the harness's
  8-virtual-device mesh: the virtual devices split one core's thread
  pool 8 ways, which throttles the fused executable ~2x more than the
  host path's (host-work-diluted) loop and would measure the
  virtualization artifact instead of the fusion. The in-process unit
  tests above keep the 8-device sharded-mesh coverage; this fixture
  reproduces REPLAY_SMOKE_r07.json's protocol exactly (and re-proves
  the CLI's one-JSON-line driver contract)."""
  import subprocess
  import sys
  tmp = tmp_path_factory.mktemp("device_replay_smoke")
  logdir = str(tmp / "logs")
  out = tmp / "smoke.json"
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env["JAX_PLATFORMS"] = "cpu"
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
  res = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.run_qtopt_replay",
       "--smoke", "--device-resident", "--steps", "300",
       "--logdir", logdir, "--out", str(out)],
      capture_output=True, text=True, timeout=480, env=env, cwd=root)
  assert res.returncode == 0, res.stderr[-2000:]
  lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
  assert len(lines) == 1, res.stdout  # the ONE-JSON-line contract
  results = json.loads(lines[0])
  assert json.loads(out.read_text()) == results
  return results, logdir


class TestSpread:
  """`learner_bench._spread`: the {median, min, max, trials} shape the
  smoke's learner-throughput block (below) and the actor block carry."""

  @pytest.mark.parametrize("values, digits, expected", [
      pytest.param([3.0, 1.0, 2.0], 3,
                   {"median": 2.0, "min": 1.0, "max": 3.0, "trials": 3},
                   id="shape_and_values"),
      pytest.param([4.5], 3,
                   {"median": 4.5, "min": 4.5, "max": 4.5, "trials": 1},
                   id="single_value"),
      pytest.param([1.23456], 2,
                   {"median": 1.23, "min": 1.23, "max": 1.23, "trials": 1},
                   id="rounding"),
  ])
  def test_spread(self, values, digits, expected):
    from tensor2robot_tpu.replay.learner_bench import _spread
    assert _spread(values, digits=digits) == expected


class TestDeviceResidentSmoke:
  """ISSUE 4 acceptance: the fused learner holds PR 2's >= 30% eval TD
  bar, the ledger shows exactly ONE megastep executable, and the
  learner-throughput block reports the device-vs-host speedup at the
  same batch shape."""

  def test_output_line_names_the_device(self, device_smoke_results):
    """--smoke is a declared chipless lane; its line must say so in the
    same three keys every entry point's output carries."""
    results, _ = device_smoke_results
    assert results["platform"] == "cpu"
    assert results["device_kind"] == "cpu"
    assert results["device_count"] == 1

  def test_td_reduction_still_meets_bar(self, device_smoke_results):
    results, _ = device_smoke_results
    assert results["device_resident"] is True
    assert results["eval_td_reduction"] >= 0.30, results["eval_history"]
    assert (results["final_eval"]["eval_q_loss"]
            < results["initial_eval"]["eval_q_loss"])

  def test_megastep_ledger_exactly_one_executable(self, device_smoke_results):
    from tensor2robot_tpu.obs.ledger import check_compile_ledger
    results, _ = device_smoke_results
    # The shared smoke helper (ISSUE 11 satellite) replaces the per-test
    # `all(v == 1)` copies: megastep + device extend present, the host
    # train step subsumed by the fused program.
    check_compile_ledger(
        results["compile_counts"],
        require=("megastep", "device_extend", "cem_bucket_*"),
        forbid=("train_step",))

  def test_learner_throughput_block(self, device_smoke_results):
    """>= 2x train-steps/s over the host path at the same batch shape.

    The committed artifact (REPLAY_SMOKE_r07.json) carries the quiet-
    run medians; the speedup bars themselves are GATED on
    os.cpu_count() >= 4 (ISSUE 6 de-flake satellite, per the ROADMAP
    maintenance note): on a 2-core box the 2x bar sits at the
    contention noise floor and failed ~50% at a clean HEAD — verified
    diff-independent — so below 4 cores this asserts the block's
    structure and the structural (non-timing) host-blocked claim only,
    and the quantitative bar is carried by the committed artifact's
    quiet-run medians.
    """
    results, _ = device_smoke_results
    block = results["learner_throughput"]
    assert block["batch_size"] == 32
    for path in ("host_path", "device_megastep"):
      for field in ("train_steps_per_sec", "transitions_per_sec",
                    "host_blocked_fraction"):
        spread = block[path][field]
        assert set(spread) == {"median", "min", "max", "trials"}
    if (os.cpu_count() or 1) >= 4:
      assert block["speedup"]["max"] >= 2.0, block["speedup"]
      assert block["speedup"]["median"] >= 1.5, block["speedup"]
    # The design claim, measured: the megastep host-blocked fraction
    # collapses vs the host path's (structural, not a timing race).
    assert (block["device_megastep"]["host_blocked_fraction"]["median"]
            <= 0.05)
    assert block["compile_counts"]["megastep"] == 1

  def test_loop_ran_off_policy_with_device_ring(self, device_smoke_results):
    results, _ = device_smoke_results
    assert results["steps"] == 300
    assert results["episodes_collected"] > 50
    assert results["param_refreshes"] >= 10
    assert results["buffer"]["replay/fill_fraction"] == 1.0
    stats = results["queue"]
    assert stats["enqueued"] == (stats["dropped"] + stats["dequeued"]
                                 + stats["pending"])

  def test_metrics_flow_through_metric_writer(self, device_smoke_results):
    _, logdir = device_smoke_results
    path = os.path.join(logdir, "metrics.jsonl")
    assert os.path.exists(path)
    seen = set()
    with open(path) as f:
      for line in f:
        seen.update(json.loads(line).keys())
    for key in ("replay/fill_fraction", "replay/sample_staleness",
                "replay/target_lag", "replay/eval_td_error",
                "replay/train_loss", "replay/train_td_error"):
      assert key in seen, (key, sorted(seen))

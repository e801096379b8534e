"""Sebulba decoupled tier (ISSUE 20 acceptance).

Covers the tentpole contracts chiplessly: the spool transport's dense
per-actor sequencing (atomic chunk landing, gaps mean "wait" never
"loss", ack frontier for backpressure), the prefetch seam's typed
exhaustion + registry instruments, the TransitionQueue's drop
accounting (typed-registry counter + sustained-overflow flight-recorder
dump), the device ring's `extend_device_chunk` seam (bit-parity with
host extend, one shared exactly-once executable, ordering guards), and
— marked slow — the live 2-process-actor run whose learner params must
be BIT-identical to the serialized single-process oracle replaying the
recorded manifest. The actor-crash quarantine protocol's bounded test
lives in tests/test_actor.py (satellite 4), with the overlap
instruments and the merged fleet view of that same live run.
"""

import json
import os

import numpy as np
import pytest

from tensor2robot_tpu.data.prefetch import (PrefetchExhausted,
                                            prefetch_to_device)
from tensor2robot_tpu.obs.flight_recorder import FlightRecorder
from tensor2robot_tpu.obs.registry import MetricRegistry
from tensor2robot_tpu.parallel import sebulba
from tensor2robot_tpu.replay.ingest import TransitionQueue


def _chunk(n=4, size=6, seed=0):
  rng = np.random.default_rng(seed)
  image = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
  return {
      "image": image,
      "action": rng.uniform(-1, 1, (n, 4)).astype(np.float32),
      "reward": rng.random(n).astype(np.float32),
      "done": np.zeros(n, np.float32),
      "next_image": image,
  }


class TestSpoolTransport:

  def test_roundtrip_preserves_content_and_order(self, tmp_path):
    spool = str(tmp_path)
    writer = sebulba.ChunkWriter(spool, actor_id=0)
    sent = [_chunk(seed=i) for i in range(3)]
    for chunk in sent:
      assert writer.put_batch(chunk) == 4
    reader = sebulba.SpoolReader(spool, num_actors=1)
    polled = reader.poll()
    assert [(actor, seq) for actor, seq, _ in polled] == [
        (0, 0), (0, 1), (0, 2)]
    for (_, seq, got), expected in zip(polled, sent):
      for key in expected:
        np.testing.assert_array_equal(got[key], expected[key])
    assert reader.poll() == []  # tail caught up

  def test_gap_blocks_until_filled(self, tmp_path):
    spool = str(tmp_path)
    sebulba.ChunkWriter(spool, 0, start_seq=0).put_batch(_chunk(seed=0))
    sebulba.ChunkWriter(spool, 0, start_seq=2).put_batch(_chunk(seed=2))
    reader = sebulba.SpoolReader(spool, num_actors=1)
    # seq 1 has not landed: the reader must stop at the gap (an absent
    # file means "being written", never "lost").
    assert [seq for _, seq, _ in reader.poll()] == [0]
    assert [seq for _, seq, _ in reader.poll()] == []
    sebulba.ChunkWriter(spool, 0, start_seq=1).put_batch(_chunk(seed=1))
    assert [seq for _, seq, _ in reader.poll()] == [1, 2]

  def test_heartbeat_ticks_and_acks(self, tmp_path):
    spool = str(tmp_path)
    writer = sebulba.ChunkWriter(spool, actor_id=1)
    reader = sebulba.SpoolReader(spool, num_actors=2)
    assert reader.heartbeat(1) is None
    writer.put_batch(_chunk())
    first = reader.heartbeat(1)
    writer.write_heartbeat()  # the backpressure-stall liveness path
    second = reader.heartbeat(1)
    assert second["tick"] > first["tick"]
    assert second["seq"] == 1
    reader.poll()
    reader.write_acks()
    with open(os.path.join(spool, sebulba.ACKS_FILE)) as f:
      assert json.load(f) == {"0": 0, "1": 1}

  def test_last_landed_seq_for_respawn(self, tmp_path):
    spool = str(tmp_path)
    writer = sebulba.ChunkWriter(spool, actor_id=0)
    assert sebulba.SpoolReader(spool, 1).last_landed_seq(0) == 0
    for i in range(3):
      writer.put_batch(_chunk(seed=i))
    # A respawned actor continues AFTER the last landed chunk — probe
    # incarnations must never overwrite recorded experience.
    assert sebulba.SpoolReader(spool, 1).last_landed_seq(0) == 3


class TestPrefetchInstruments:

  def test_typed_exhaustion(self):
    registry = MetricRegistry()
    stream = prefetch_to_device(
        iter([{"x": np.ones(2)} for _ in range(3)]), depth=2,
        registry=registry, name="pf", exhaust_error=True)
    got = 0
    with pytest.raises(PrefetchExhausted) as err:
      while True:
        next(stream)
        got += 1
    assert got == 3
    assert err.value.batches == 3
    assert err.value.name == "pf"

  def test_default_ends_without_error(self):
    registry = MetricRegistry()
    batches = list(prefetch_to_device(
        iter([{"x": np.ones(2)}] * 2), depth=2, registry=registry))
    assert len(batches) == 2

  def test_depth_and_bytes_through_registry(self):
    registry = MetricRegistry()
    batch_bytes = np.ones(8, np.float32).nbytes
    stream = prefetch_to_device(
        iter([{"x": np.ones(8, np.float32)} for _ in range(4)]),
        depth=2, registry=registry, name="pf")
    next(stream)
    # After the first yield the double buffer holds `depth` batches
    # again on the next pull; the gauges track the live buffer.
    assert registry.gauge("pf/depth").value <= 2
    assert registry.gauge("pf/in_flight_bytes").value % batch_bytes == 0
    for _ in stream:
      pass
    assert registry.counter("pf/batches").value == 4
    assert registry.gauge("pf/depth").value == 0
    assert registry.gauge("pf/in_flight_bytes").value == 0


class TestQueueDropAccounting:

  def test_registry_counter_counts_rows(self):
    registry = MetricRegistry()
    recorder = FlightRecorder()
    queue = TransitionQueue(8, registry=registry,
                            flight_recorder=recorder)
    for _ in range(4):
      queue.put_batch({"x": np.zeros((4, 2))})
    # capacity 8 rows: puts 3 and 4 each shed 4 rows.
    assert queue.dropped == 8
    counter = registry.counter("replay/transition_queue_dropped")
    assert counter.value == 8

  def test_sustained_overflow_dumps_flight_record(self, tmp_path):
    registry = MetricRegistry()
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    queue = TransitionQueue(8, registry=registry,
                            flight_recorder=recorder,
                            overflow_dump_threshold=3)
    for _ in range(5):  # puts 3..5 shed -> streak reaches 3 once
      queue.put_batch({"x": np.zeros((4, 2))})
    dumps = [name for name in os.listdir(tmp_path)
             if name.startswith("flightrec-")]
    assert len(dumps) == 1
    with open(tmp_path / dumps[0]) as f:
      dump = json.load(f)
    assert dump["reason"] == "transition_queue_sustained_overflow"
    trigger = next(
        event for event in dump["events"]
        if event.get("name") == "transition_queue_sustained_overflow")
    assert trigger["consecutive_overflow_puts"] == 3
    assert trigger["capacity"] == 8

  def test_streak_resets_on_clean_put(self, tmp_path):
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    queue = TransitionQueue(8, registry=MetricRegistry(),
                            flight_recorder=recorder,
                            overflow_dump_threshold=2)
    queue.put_batch({"x": np.zeros((6, 2))})
    queue.put_batch({"x": np.zeros((6, 2))})  # sheds (streak 1)
    queue.drain_batch()                       # empties the queue
    queue.put_batch({"x": np.zeros((6, 2))})  # clean -> streak reset
    queue.put_batch({"x": np.zeros((6, 2))})  # sheds (streak 1 again)
    assert os.listdir(tmp_path) == []  # threshold 2 never reached


class TestExtendDeviceChunk:

  def _buffer(self, seed=0):
    from tensor2robot_tpu.replay.device_buffer import DeviceReplayBuffer
    from tensor2robot_tpu.replay.loop import transition_spec
    return DeviceReplayBuffer(
        transition_spec(6, 4), capacity=32, sample_batch_size=4,
        seed=seed, prioritized=True, ingest_chunk=8)

  def test_bit_parity_with_host_extend(self):
    import jax
    host = self._buffer()
    device = self._buffer()
    chunk = _chunk(n=8, seed=3)
    host.extend(chunk)
    device.extend_device_chunk(jax.device_put(chunk))
    for key in chunk:
      np.testing.assert_array_equal(
          np.asarray(host.state.storage[key]),
          np.asarray(device.state.storage[key]))
    assert int(device.state.size) == 8
    assert host.compile_counts == device.compile_counts == {
        "device_extend": 1}

  def test_one_executable_across_both_seams(self):
    import jax
    buffer = self._buffer()
    buffer.extend_device_chunk(jax.device_put(_chunk(n=8, seed=0)))
    buffer.extend(_chunk(n=8, seed=1))
    buffer.extend_device_chunk(jax.device_put(_chunk(n=8, seed=2)))
    assert buffer.compile_counts == {"device_extend": 1}
    assert int(buffer.state.size) == 24

  def test_rejects_wrong_shape(self):
    import jax
    buffer = self._buffer()
    with pytest.raises(ValueError, match="ingest_chunk"):
      buffer.extend_device_chunk(jax.device_put(_chunk(n=4)))

  def test_rejects_interleaving_with_staged_host_rows(self):
    import jax
    buffer = self._buffer()
    buffer.extend(_chunk(n=4))  # below the chunk quantum: stays staged
    with pytest.raises(RuntimeError, match="staged"):
      buffer.extend_device_chunk(jax.device_put(_chunk(n=8)))


class TestActorDevicePlacement:
  """One process per chip: each actor's environment decides, before it
  spawns, which device its runtime may open — never the learner's."""

  def test_cpu_learner_gives_each_actor_a_one_device_cpu_runtime(self):
    from tensor2robot_tpu.utils.cpu_mesh_env import is_cpu_mesh_env
    envs = sebulba._actor_envs(sebulba.SebulbaConfig(num_actors=3))
    assert sorted(envs) == [0, 1, 2]
    for env in envs.values():
      assert is_cpu_mesh_env(1, env) and not is_cpu_mesh_env(2, env)
      assert env["PYTHONPATH"].split(os.pathsep)[0] == sebulba._repo_root()

  def _fake_tpu(self, monkeypatch, count):
    import types

    import jax
    chip = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda: [chip] * count)

  def test_tpu_learner_hands_out_chips_outside_its_own(self, monkeypatch):
    from tensor2robot_tpu.utils.tpu_chip_env import tpu_chip_env
    self._fake_tpu(monkeypatch, 2)
    for key, value in tpu_chip_env([0, 1], base={}).items():
      monkeypatch.setenv(key, value)
    envs = sebulba._actor_envs(
        sebulba.SebulbaConfig(num_actors=2, mesh_devices=2))
    assert [envs[i]["TPU_VISIBLE_CHIPS"] for i in (0, 1)] == ["2", "3"]
    for env in envs.values():
      assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
      assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"

  def test_learner_holding_the_whole_host_refuses_to_spawn(
      self, monkeypatch):
    """A learner that initialized JAX with every chip visible holds
    them all; an actor needing one would fail or hang."""
    self._fake_tpu(monkeypatch, 4)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    with pytest.raises(RuntimeError, match="tpu_chip_env"):
      sebulba._actor_envs(
          sebulba.SebulbaConfig(num_actors=2, mesh_devices=2))

  def test_tpu_chip_env_sets_visibility_without_touching_its_base(self):
    from tensor2robot_tpu.utils.tpu_chip_env import (tpu_chip_env,
                                                     visible_chips)
    base = {"TPU_VISIBLE_CHIPS": "0,1", "OTHER": "kept"}
    env = tpu_chip_env([3], base=base)
    assert env["TPU_VISIBLE_CHIPS"] == "3" and env["OTHER"] == "kept"
    assert base["TPU_VISIBLE_CHIPS"] == "0,1"
    assert visible_chips(env) == [3] and visible_chips(base) == [0, 1]
    assert visible_chips({}) is None
    with pytest.raises(ValueError):
      tpu_chip_env([0, 1, 2])


@pytest.mark.slow
class TestSebulbaLiveOracleParity:
  """The tentpole end-to-end: 2 real actor processes + this learner
  process, then a fresh-interpreter oracle fed the recorded stream."""

  def test_params_bit_identical_to_oracle(self, tmp_path):
    config = sebulba.SebulbaConfig(
        num_actors=2, envs_per_actor=8, capacity=64, batch_size=8,
        inner_steps=2, chunks_per_megastep=2, num_megasteps=3,
        mesh_devices=2, queue_capacity=256, synthetic_actors=True,
        actor_max_chunks=64, actor_deadline_s=2.0)
    live = sebulba.run_live(config, str(tmp_path / "live"),
                            timeout_s=300.0)
    assert live["queue"]["dropped"] == 0
    assert live["compile_counts"] == {"device_extend": 1,
                                      "megastep": 1}
    oracle = sebulba.run_oracle_subprocess(
        config, str(tmp_path / "live" / "spool"), live["manifest"],
        str(tmp_path / "oracle"))
    parity = sebulba.compare_params(live["final_params_path"],
                                    oracle["params_path"])
    assert parity["bit_identical"], parity
    assert live["drive"]["stream"] == oracle["drive"]["stream"]
    assert oracle["compile_counts"] == live["compile_counts"]
    pids = {result["pid"] for result in live["actors"].values()}
    assert len(pids) == 2 and os.getpid() not in pids

"""Observability spine (ISSUE 11 acceptance) + fleet tier (ISSUE 12).

Covers the obs layers chiplessly: structured spans (nesting,
thread-safety, Chrome-trace export), the typed metric registry and its
one MetricWriter bridge (host/pid stamped JSONL), the ExecutableLedger
(compile counts + device-time attribution + the shared
check_compile_ledger helper the replay/anakin/fleet smokes now use),
the flight recorder (bounded ring, atomic schema'd dumps, rate limit,
the INJECTED SLO breach under hold_flushes(), per-instance recorders +
the repoint warning), the guarded profiler window, the MetricWriter
lifecycle satellite, and the obs_bench CLI protocol whose committed
artifact is OBS_r13.json.

Round 13 adds the cross-process tier: correlation ids (contextvar
binding, span auto-attrs, Perfetto flows, THE tier-1 propagation test
through FleetRouter + the rollout mirror), the stall/straggler
watchdog (stall detection + escalation, the healthy-loop negative
control), the fleet aggregator (reservoir-union percentiles, SLO
rollup consistency, merged trace with cross-process flows), and the
FLEETOBS CLI protocol whose committed artifact is FLEETOBS_r13.json.
"""

import json
import os
import threading

import pytest

from tensor2robot_tpu.obs.flight_recorder import SCHEMA, FlightRecorder
from tensor2robot_tpu.obs.ledger import (ExecutableLedger,
                                         check_compile_ledger,
                                         peak_flops_for)
from tensor2robot_tpu.obs.registry import MetricRegistry
from tensor2robot_tpu.obs.trace import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestTracer:

  def test_spans_nest_and_record_parent(self):
    tracer = Tracer()
    with tracer.span("learn/outer", k=3):
      with tracer.span("learn/inner"):
        pass
    spans = tracer.spans()
    # Completion order: inner closes first.
    assert [s["name"] for s in spans] == ["learn/inner", "learn/outer"]
    assert spans[0]["parent"] == "learn/outer"
    assert spans[0]["depth"] == 1 and spans[1]["depth"] == 0
    assert spans[1]["k"] == 3
    assert spans[1]["dur_s"] >= spans[0]["dur_s"]

  def test_thread_safety_and_per_thread_nesting(self):
    tracer = Tracer()

    def worker(i):
      for _ in range(50):
        with tracer.span(f"act/t{i}"):
          pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    assert tracer.total_spans == 200
    # No cross-thread parent contamination: all spans are roots.
    assert all(s["depth"] == 0 for s in tracer.spans())

  def test_ring_is_bounded(self):
    tracer = Tracer(max_spans=10)
    for i in range(25):
      with tracer.span(f"serve/s{i}"):
        pass
    assert len(tracer.spans()) == 10
    assert tracer.total_spans == 25

  def test_stage_counts(self):
    tracer = Tracer()
    for name in ("act/a", "act/b", "learn/x", "serve/flush"):
      with tracer.span(name):
        pass
    assert tracer.stage_counts() == {"act": 2, "learn": 1, "serve": 1}

  def test_chrome_trace_export_parses(self, tmp_path):
    tracer = Tracer()
    with tracer.span("learn/step", batch=8):
      pass
    path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
      payload = json.load(f)
    events = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    assert len(events) == 1
    event = events[0]
    assert event["name"] == "learn/step"
    assert event["dur"] >= 0 and event["ts"] >= 0
    assert event["args"]["batch"] == 8
    # Metadata event names the process for Perfetto.
    assert payload["traceEvents"][0]["ph"] == "M"

  def test_listener_sees_completed_spans(self):
    tracer = Tracer()
    seen = []
    tracer.add_listener(seen.append)
    with tracer.span("extend/drain"):
      pass
    assert [s["name"] for s in seen] == ["extend/drain"]


class TestMetricRegistry:

  def test_typed_names_collide_loudly(self):
    registry = MetricRegistry()
    registry.counter("x").inc()
    with pytest.raises(TypeError, match="one name, one type"):
      registry.gauge("x")

  def test_counter_gauge_histogram_snapshot(self):
    registry = MetricRegistry()
    registry.counter("reqs").inc(5)
    registry.gauge("fill").set(0.75)
    hist = registry.histogram("lat")
    for value in range(1, 101):
      hist.record(float(value))
    snap = registry.snapshot()
    assert snap["reqs"] == 5
    assert snap["fill"] == 0.75
    assert snap["lat/p50"] == 50.0
    assert snap["lat/p99"] == 99.0
    assert snap["lat/count"] == 100

  def test_histogram_reservoir_is_bounded(self):
    registry = MetricRegistry()
    hist = registry.histogram("h")
    hist._samples = type(hist._samples)(maxlen=8)  # shrink for the test
    for value in range(100):
      hist.record(value)
    snap = hist.snapshot()
    assert snap["count"] == 100      # true count survives the window
    assert snap["p50"] >= 92         # window keeps the NEWEST samples

  def test_bridge_flushes_through_metric_writer_with_host_pid(
      self, tmp_path):
    from tensor2robot_tpu.utils.metric_writer import MetricWriter
    registry = MetricRegistry()
    registry.set_gauges({"replay/a": 1.0, "replay/b": 2.0})
    registry.counter("other").inc()
    with MetricWriter(str(tmp_path)) as writer:
      # names= restricts the flush: the record carries exactly the
      # block the caller emitted (the loops' pre-registry schema).
      registry.flush_to(writer, step=7, names=["replay/a", "replay/b"])
    with open(tmp_path / "metrics.jsonl") as f:
      record = json.loads(f.readline())
    assert record["step"] == 7
    assert record["replay/a"] == 1.0 and record["replay/b"] == 2.0
    assert "other" not in record
    # The multi-host fields (ISSUE 11: merged per-process streams).
    assert record["host"] and record["pid"] == os.getpid()


class TestMetricWriterLifecycle:
  """ISSUE 11 satellite: writes after close() raise a clear error
  instead of hitting a closed file; the writer is a context manager."""

  def test_write_after_close_raises(self, tmp_path):
    from tensor2robot_tpu.utils.metric_writer import MetricWriter
    writer = MetricWriter(str(tmp_path))
    writer.write_scalars(0, {"a": 1.0})
    writer.close()
    with pytest.raises(RuntimeError, match="closed"):
      writer.write_scalars(1, {"a": 2.0})
    with pytest.raises(RuntimeError, match="closed"):
      writer.write_images(1, {"img": None})
    writer.close()  # idempotent

  def test_context_manager(self, tmp_path):
    from tensor2robot_tpu.utils.metric_writer import MetricWriter
    with MetricWriter(str(tmp_path)) as writer:
      writer.write_scalars(0, {"a": 1.0})
    with pytest.raises(RuntimeError, match="closed"):
      writer.write_scalars(1, {"a": 2.0})


class TestExecutableLedger:

  def test_register_and_attribution_shares(self):
    ledger = ExecutableLedger()
    ledger.register("a")
    ledger.register("b")
    ledger.record_dispatch("a", 0.6)
    ledger.record_dispatch("b", 0.2)
    att = ledger.attribution(wall_seconds=2.0)
    rows = {row["name"]: row for row in att["executables"]}
    assert rows["a"]["device_time_share"] == 0.3
    assert rows["b"]["device_time_share"] == 0.1
    assert att["attributed_share"] == 0.4  # <= 1.0 by construction
    # Without a wall window shares normalize over attributed seconds.
    normalized = ledger.attribution()
    assert normalized["attributed_share"] == pytest.approx(1.0)

  def test_recompile_shows_as_compiles_2(self):
    ledger = ExecutableLedger()
    ledger.register("x")
    ledger.register("x")
    assert ledger.compile_counts == {"x": 2}
    with pytest.raises(AssertionError, match="exactly once"):
      check_compile_ledger(ledger.compile_counts)

  def test_dispatch_before_register_surfaces_as_zero_compiles(self):
    ledger = ExecutableLedger()
    ledger.record_dispatch("ghost", 0.1)
    row = ledger.attribution()["executables"][0]
    assert row["name"] == "ghost" and row["compiles"] == 0

  def test_tpu_kind_missing_from_the_peak_table_is_an_error(self):
    """cpu -> None (no peak model, MFU null). A TPU the table does not
    know is an error, not a quietly null MFU on a chip run."""
    assert peak_flops_for("cpu") is None
    assert peak_flops_for(None) is None
    with pytest.raises(KeyError, match="CHIP_PEAKS"):
      peak_flops_for("TPU v9 unheard-of")

  def test_mfu_needs_a_known_peak(self):
    assert peak_flops_for("cpu") is None
    assert peak_flops_for("TPU v5 lite") == 197e12
    ledger = ExecutableLedger()

    class _Compiled:
      def cost_analysis(self):
        return {"flops": 1e12, "bytes accessed": 1e9}

    ledger.register("k", compiled=_Compiled())
    ledger.record_dispatch("k", 1.0)
    cpu = ledger.attribution(device_kind="cpu")["executables"][0]
    assert cpu["estimated_mfu"] is None
    assert cpu["flops_per_dispatch"] == 1e12
    tpu = ledger.attribution(
        device_kind="TPU v5 lite")["executables"][0]
    # The ledger rounds MFU to 4 digits for the artifact.
    assert tpu["estimated_mfu"] == pytest.approx(1e12 / 197e12, abs=1e-4)

  def test_check_compile_ledger_contract(self):
    # Flat, nested (the fleet shape), require/forbid and prefix match.
    flat = check_compile_ledger(
        {"anakin_step": 1, "dev0": {"1": 1, "2": 1}},
        require=("anakin_step", "dev0/*"), forbid=("megastep",))
    assert flat == {"anakin_step": 1, "dev0/1": 1, "dev0/2": 1}
    with pytest.raises(AssertionError, match="missing"):
      check_compile_ledger({"a": 1}, require=("b",))
    with pytest.raises(AssertionError, match="forbidden"):
      check_compile_ledger({"a": 1, "megastep": 1}, forbid=("megastep",))
    with pytest.raises(AssertionError, match="empty"):
      check_compile_ledger({})


class TestFlightRecorder:

  def test_ring_bounded_and_dump_schema(self, tmp_path):
    recorder = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    for i in range(40):
      recorder.record("event", f"e{i}", index=i)
    path = recorder.dump("unit_test")
    with open(path) as f:
      payload = json.load(f)
    assert payload["schema"] == SCHEMA
    assert payload["reason"] == "unit_test"
    assert payload["host"] and payload["pid"] == os.getpid()
    assert payload["events_total"] == 40
    assert len(payload["events"]) == 16  # the ring bound
    assert payload["events"][-1]["name"] == "e39"
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]

  def test_disabled_without_dump_dir(self):
    recorder = FlightRecorder()
    recorder.record("event", "x")
    assert recorder.dump("nowhere") is None
    assert recorder.trigger("nowhere") is None
    # The trigger still lands in the ring for a later dump.
    assert recorder.events()[-1]["kind"] == "trigger"

  def test_trigger_rate_limit(self, tmp_path):
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=60.0)
    first = recorder.trigger("breach")
    second = recorder.trigger("breach")
    assert first is not None and second is None
    assert recorder.dumps_written == 1
    assert recorder.dumps_suppressed == 1

  def test_span_listener_feeds_ring(self):
    from tensor2robot_tpu.obs.trace import Tracer
    tracer = Tracer()
    recorder = FlightRecorder()
    recorder.attach(tracer)
    with tracer.span("serve/flush", batch=4):
      pass
    event = recorder.events()[-1]
    assert event["kind"] == "span" and event["name"] == "serve/flush"


class TestInjectedSLOBreachDump:
  """THE round-12 acceptance path: an injected SLO breach under
  hold_flushes() produces a schema-valid flight-recorder dump."""

  def test_capacity_breach_under_held_flushes_dumps(self, tmp_path):
    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import RequestShed, SLOClass
    from tensor2robot_tpu.serving.stats import ServingStats
    from tensor2robot_tpu.obs.registry import MetricRegistry

    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    stats = ServingStats(registry=MetricRegistry())
    batch_class = SLOClass("batch", priority=0, deadline_ms=2000.0)
    with MicroBatcher(lambda items: list(items), max_batch=4,
                      deadline_ms=50.0, stats=stats, max_queue=2,
                      flight_recorder=recorder) as batcher:
      with batcher.hold_flushes():
        # Deterministic overload: 6 arrivals into 2 queue slots with
        # dispatch held — exactly 4 capacity sheds, zero timing.
        futures = [batcher.submit(i, slo=batch_class) for i in range(6)]
      shed = 0
      for future in futures:
        try:
          future.result(timeout=30)
        except RequestShed:
          shed += 1
    assert shed == 4
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("flightrec-") and f.endswith(".json")]
    assert dumps, "SLO breach produced no flight-recorder dump"
    with open(tmp_path / sorted(dumps)[0]) as f:
      payload = json.load(f)
    assert payload["schema"] == SCHEMA
    assert payload["reason"] == "slo_breach"
    triggers = [e for e in payload["events"]
                if e["kind"] == "trigger" and e["name"] == "slo_breach"]
    assert triggers and triggers[0]["shed_reason"] == "capacity"
    assert triggers[0]["slo_class"] == "batch"

  def test_expired_at_enqueue_also_triggers(self, tmp_path):
    import time

    from tensor2robot_tpu.serving.batcher import MicroBatcher
    from tensor2robot_tpu.serving.slo import RequestShed

    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    with MicroBatcher(lambda items: list(items), max_batch=4,
                      flight_recorder=recorder) as batcher:
      future = batcher.submit(
          "late", deadline_at=time.perf_counter() - 1.0)
      with pytest.raises(RequestShed):
        future.result(timeout=10)
    assert recorder.dumps_written == 1
    event = [e for e in recorder.events() if e["kind"] == "trigger"][-1]
    assert event["shed_reason"] == "expired"


class TestGuardedProfiler:
  """ISSUE 11 satellite: two armed capture windows (train ProfilerHook
  + replay --profile) must not double-start jax.profiler."""

  def test_second_start_is_refused_not_fatal(self, monkeypatch):
    from tensor2robot_tpu.utils import profiling

    calls = []
    monkeypatch.setattr(profiling.jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(profiling.jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    assert profiling.start_trace("/tmp/w1") is True
    assert profiling.trace_active()
    assert profiling.start_trace("/tmp/w2") is False  # guarded, logged
    assert profiling.stop_trace() == "/tmp/w1"
    assert not profiling.trace_active()
    assert profiling.stop_trace() is None  # idempotent
    assert [c[0] for c in calls] == ["start", "stop"]

  def test_profiler_hook_skips_when_window_held(self, monkeypatch, tmp_path):
    import types

    from tensor2robot_tpu.utils import profiling

    monkeypatch.setattr(profiling.jax.profiler, "start_trace",
                        lambda d: None)
    monkeypatch.setattr(profiling.jax.profiler, "stop_trace",
                        lambda: None)
    # Another path (e.g. the replay --profile window) holds the trace.
    assert profiling.start_trace(str(tmp_path / "w1"))
    hook = profiling.ProfilerHook(start_step=1, end_step=2,
                                  log_dir=str(tmp_path / "w2"))
    hook.after_step(types.SimpleNamespace(step=1), {})
    assert hook._done and not hook._tracing  # skipped, not crashed
    assert profiling.stop_trace() == str(tmp_path / "w1")

  def test_replay_profile_window_flag_parses(self):
    from tensor2robot_tpu.bin.run_qtopt_replay import parse_profile
    assert parse_profile(None) is None
    assert parse_profile("5,10") == (5, 10)
    for bad in ("5", "a,b", "10,5", "-1,4", "3,3"):
      with pytest.raises(ValueError):
        parse_profile(bad)

  def test_spans_reach_a_plain_profiler_session(self, tmp_path):
    """One clock: a span opened while ANY profiler session is open (a
    plain jax.profiler.start_trace, as the benchmark's harness makes
    it, not utils.profiling's window) is on /host:CPU of the xplane
    under its own name, with its attrs as stats; a comma-joined
    request_ids rides whole, in brackets."""
    import glob

    import jax

    from tensor2robot_tpu.obs import context as context_lib
    from tensor2robot_tpu.obs import trace as trace_lib

    jax.profiler.start_trace(str(tmp_path))
    try:
      with context_lib.bind(request_ids="r-1,r-2"):
        with trace_lib.span("serve/flush", batch=2,
                            queue_wait_ms_max=1.5):
          jax.numpy.ones((8, 8)).sum().block_until_ready()
    finally:
      jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    (plane,) = [p for p in data.planes if p.name == "/host:CPU"]
    found = [dict(event.stats) for line in plane.lines
             for event in line.events if event.name == "serve/flush"]
    assert len(found) == 1, found
    assert found[0]["batch"] == 2
    assert found[0]["queue_wait_ms_max"] == 1.5
    assert found[0]["request_ids"] == "[r-1,r-2]"


class TestLoopSpans:
  """The train loop's spans (ISSUE 30): one train/dispatch per compiled
  call, the three waits of the loop around what they wait for."""

  def _spans_of(self, run, names):
    from tensor2robot_tpu.obs import trace as trace_lib
    before = trace_lib.get_tracer().total_spans
    run()
    spans = trace_lib.get_tracer().spans()
    new = spans[len(spans) - (trace_lib.get_tracer().total_spans - before):]
    return [s for s in new if s["name"] in names]

  def _trainer_and_feed(self, stack=None):
    import numpy as np

    from tensor2robot_tpu import modes
    from tensor2robot_tpu.data.default_input_generator import (
        DefaultRandomInputGenerator)
    from tensor2robot_tpu.train.trainer import Trainer
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    model = MockT2RModel()
    trainer = Trainer(model, seed=0)
    gen = DefaultRandomInputGenerator(batch_size=8, seed=0)
    gen.set_specification_from_model(model, modes.TRAIN)
    batches = gen.create_dataset_fn(modes.TRAIN)()
    if stack is None:
      return trainer, trainer.shard_batch(next(batches))
    import jax
    rows = [next(batches) for _ in range(stack)]
    return trainer, jax.tree_util.tree_map(
        lambda *leaves: np.stack(leaves), *rows)

  @pytest.mark.parametrize("kind, stack", [
      ("step", None), ("steps", 3), ("accum", 2)])
  def test_one_train_dispatch_span_per_call(self, kind, stack):
    trainer, (features, labels) = self._trainer_and_feed(stack)
    call = {"step": trainer.train_step, "steps": trainer.train_steps,
            "accum": trainer.train_step_accum}[kind]
    state = [trainer.create_train_state()]

    def run():
      for _ in range(3):
        state[0], _ = call(state[0], features, labels)

    spans = self._spans_of(run, {"train/dispatch"})
    assert [s["kind"] for s in spans] == [kind] * 3
    assert int(state[0].step) == 3 * (stack if kind == "steps" else 1)

  def test_prefetch_splits_input_wait_from_put(self):
    import time

    import numpy as np

    from tensor2robot_tpu.data.prefetch import prefetch_to_device

    def slow_host():
      for i in range(3):
        time.sleep(0.02)
        yield {"x": np.full((4, 2), i, np.float32)}

    spans = self._spans_of(
        lambda: list(prefetch_to_device(slow_host(), depth=2)),
        {"input/wait", "input/put"})
    waits = [s for s in spans if s["name"] == "input/wait"]
    puts = [s for s in spans if s["name"] == "input/put"]
    # One wait per batch and one that finds the stream ended.
    assert len(waits) == 4 and len(puts) == 3
    assert all(s["dur_s"] >= 0.015 for s in waits[:3])
    assert all(s["bytes"] == 32 for s in puts)

  def test_train_loop_spans_readback_and_checkpoint(self, tmp_path):
    from tensor2robot_tpu.data.default_input_generator import (
        DefaultRandomInputGenerator)
    from tensor2robot_tpu.train.train_eval import train_eval_model
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    spans = self._spans_of(
        lambda: train_eval_model(
            MockT2RModel(),
            input_generator_train=DefaultRandomInputGenerator(
                batch_size=8, seed=0),
            max_train_steps=4, log_every_steps=2,
            model_dir=str(tmp_path), save_checkpoints_steps=4),
        {"train/dispatch", "train/readback", "train/checkpoint",
         "input/wait", "input/put"})
    count = lambda name: sum(1 for s in spans if s["name"] == name)
    assert count("train/dispatch") == 4
    assert count("train/readback") == 2
    assert count("train/checkpoint") >= 1
    assert count("input/put") >= 4 and count("input/wait") >= 4


@pytest.fixture(scope="module")
def obs_bench_results(tmp_path_factory):
  """ONE obs_bench --ci run shared by the acceptance assertions — the
  CLI in a subprocess under the ARTIFACT environment (the re-exec
  bootstrap path under test)."""
  import subprocess
  import sys
  tmp = tmp_path_factory.mktemp("obs_bench")
  logdir = tmp / "logs"
  out = tmp / "obs.json"
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env["JAX_PLATFORMS"] = "cpu"
  env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
  res = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.obs.obs_bench", "--ci",
       "--logdir", str(logdir), "--out", str(out)],
      capture_output=True, text=True, timeout=480, env=env, cwd=ROOT)
  assert res.returncode == 0, res.stderr[-2000:]
  lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
  assert len(lines) == 1, res.stdout  # the ONE-JSON-line contract
  results = json.loads(lines[0])
  assert json.loads(out.read_text()) == results
  return results, str(logdir)


def _assert_obs_schema(results, committed: bool):
  """The OBS_r13 contract shared by the CLI run and the committed
  artifact: attribution completeness, shares <= 1.0, ledger_ok,
  flight-recorder schema, per-stage trace coverage, and (r13) the
  watchdog controls + the aggregator self-check blocks."""
  assert results["round"] == 13
  assert results["virtual_mesh"] is (
      results["device_kind"].lower() == "cpu")
  for phase in ("replay", "host_loop"):
    block = results[phase]
    attribution = block["attribution"]
    # Every executable in the attribution appears exactly once and
    # was actually dispatched; shares sum <= 1.0 against the wall.
    names = [row["name"] for row in attribution["executables"]]
    assert len(names) == len(set(names)), names
    assert attribution["attributed_share"] <= 1.0
    check_compile_ledger(
        {row["name"]: row["compiles"]
         for row in attribution["executables"]})
    for row in attribution["executables"]:
      assert row["dispatches"] >= 1, row
      assert row["seconds_total"] >= 0.0
    assert block["eval_td_reduction"] is not None
  # The replay phase IS the smoke protocol: the fused executable
  # dominates its ledger and the hot-path names are present.
  replay_names = [row["name"]
                  for row in results["replay"]["attribution"]["executables"]]
  assert "anakin_step" in replay_names
  host_names = [row["name"]
                for row in results["host_loop"]["attribution"]["executables"]]
  for required in ("train_step", "bellman_targets", "td_error"):
    assert required in host_names, host_names
  # Serve: one executable per bucket PER DEVICE (the fleet invariant
  # through the obs ledger), and the injected breach dumped.
  serve = results["serve"]
  assert serve["ledger_ok"] is True
  check_compile_ledger(serve["compile_counts"])
  assert len(serve["compile_counts"]) == (
      serve["devices"] * len(serve["bucket_ladder"]))
  breach = serve["breach"]
  # shed_total is the stats-side view of the whole serve window (live
  # traffic may shed under contention too), so >= the burst's sheds.
  assert breach["shed"] > 0 and breach["shed_total"] >= breach["shed"]
  assert breach["flightrec"]["schema"] == "t2r-flightrec-1"
  assert breach["flightrec"]["reason"] == "slo_breach"
  assert breach["flightrec"]["events"] > 0
  # Trace coverage: >= 1 span per loop stage (act, extend, learn,
  # serve — the acceptance bar).
  stages = results["trace"]["stage_counts"]
  for stage in ("act", "extend", "learn", "serve"):
    assert stages.get(stage, 0) >= 1, stages
  assert results["flightrec_schema"] == "t2r-flightrec-1"
  # Round 13: watchdog controls (injected stall fired + schema-valid
  # dump; healthy control silent) and the aggregator self-check over
  # the run's own artifacts (consistent rollup, >= 1 correlation-linked
  # serve timeline).
  watchdog = results["watchdog"]
  assert watchdog["injected_stall"]["ok"] is True
  assert watchdog["injected_stall"]["events"] >= 1
  assert watchdog["injected_stall"]["dump_schema"] == "t2r-flightrec-1"
  assert watchdog["healthy_control"]["ok"] is True
  assert watchdog["healthy_control"]["events"] == 0
  fleetobs = results["fleetobs"]
  assert fleetobs["consistent"] is True
  assert fleetobs["hosts_merged"] >= 1
  assert fleetobs["slo"]["shed_total"] >= breach["shed"]
  assert fleetobs["trace"]["linked_serve_timelines"] >= 1
  assert fleetobs["trace"]["example_timeline"]["spans"][:1] == [
      "serve/enqueue"]
  assert fleetobs["flightrec_reasons"].get("watchdog_stall", 0) >= 1
  if committed:
    assert results["devices"] == 8 and results["mesh_dp"] == 8


class TestObsBenchCLI:
  """The reduced --ci lane on every PR: structure/completeness always;
  quantitative attribution bars gated on os.cpu_count() >= 4 per the
  repo's timing-bar convention (ROADMAP maintenance note)."""

  def test_schema_and_completeness(self, obs_bench_results):
    results, _ = obs_bench_results
    _assert_obs_schema(results, committed=False)

  def test_chrome_trace_file_parses_with_stage_spans(
      self, obs_bench_results):
    results, logdir = obs_bench_results
    path = os.path.join(logdir, results["trace"]["file"])
    assert os.path.exists(path)
    with open(path) as f:
      payload = json.load(f)  # the acceptance: valid JSON
    names = [event["name"] for event in payload["traceEvents"]
             if event.get("ph") == "X"]
    for stage in ("act/", "extend/", "learn/", "serve/"):
      assert any(name.startswith(stage) for name in names), (
          stage, sorted(set(names))[:20])

  def test_flightrec_dump_file_validates(self, obs_bench_results):
    results, logdir = obs_bench_results
    dump_name = results["serve"]["breach"]["flightrec"]["path"]
    path = os.path.join(logdir, "serve", dump_name)
    assert os.path.exists(path)
    with open(path) as f:
      payload = json.load(f)
    assert payload["schema"] == SCHEMA
    assert payload["reason"] == "slo_breach"
    kinds = {event["kind"] for event in payload["events"]}
    assert "trigger" in kinds and "span" in kinds

  def test_registry_carried_serving_and_replay_series(
      self, obs_bench_results):
    results, _ = obs_bench_results
    registry = results["registry"]
    assert registry["serving/requests"] >= 1
    assert registry["serving/shed_capacity"] >= 1
    assert any(key.startswith("replay/") for key in registry)

  def test_attribution_bars(self, obs_bench_results):
    """Quantitative: the fused executable should own a visible share
    of the replay window. Timing-derived, so gated on >= 4 cores."""
    if (os.cpu_count() or 1) < 4:
      return
    results, _ = obs_bench_results
    rows = {row["name"]: row
            for row in results["replay"]["attribution"]["executables"]}
    assert rows["anakin_step"]["device_time_share"] >= 0.01


class TestCommittedObsArtifact:

  def test_obs_r13_json_matches_schema(self):
    """OBS_r13.json (the committed acceptance artifact) parses and
    holds the full-protocol contract: 8-virtual-device mesh, shares
    <= 1.0, every dispatched executable present, breach dump recorded,
    all four loop stages in the trace counts, the watchdog controls,
    and the aggregator self-check."""
    path = os.path.join(ROOT, "OBS_r13.json")
    assert os.path.exists(path), "committed OBS_r13.json missing"
    with open(path) as f:
      results = json.loads(f.read().strip())
    _assert_obs_schema(results, committed=True)
    # The committed run used the full smoke budget and learned.
    assert results["replay"]["steps"] >= 300
    assert results["replay"]["eval_td_reduction"] >= 0.30


class TestCorrelationContext:
  """ISSUE 12 tentpole (a), unit layer: contextvar binding, span
  auto-attrs, and the Perfetto flow linker."""

  def test_mint_is_host_pid_unique_and_monotonic(self):
    from tensor2robot_tpu.obs import context as context_lib
    a, b = context_lib.new_request_id(), context_lib.new_request_id()
    assert a != b
    assert str(os.getpid()) in a

  def test_bind_nests_and_restores(self):
    from tensor2robot_tpu.obs import context as context_lib
    assert context_lib.current_request_id() is None
    with context_lib.bind(request_id="r1"):
      assert context_lib.current_request_id() == "r1"
      with context_lib.bind(step_id=7):
        # Nested step_id bind keeps the enclosing request_id.
        attrs = context_lib.context_attrs()
        assert attrs == {"request_id": "r1", "step_id": 7}
      assert context_lib.context_attrs() == {"request_id": "r1"}
    assert context_lib.current_request_id() is None

  def test_spans_inherit_bound_ids_and_explicit_attrs_win(self):
    from tensor2robot_tpu.obs import context as context_lib
    from tensor2robot_tpu.obs.trace import Tracer
    tracer = Tracer()
    with context_lib.bind(request_id="r-auto", step_id=3):
      with tracer.span("serve/flush"):
        pass
      with tracer.span("serve/enqueue", request_id="r-explicit"):
        pass
    auto, explicit = tracer.spans()
    assert auto["request_id"] == "r-auto" and auto["step_id"] == 3
    assert explicit["request_id"] == "r-explicit"

  def test_span_request_ids_decoder(self):
    from tensor2robot_tpu.obs import context as context_lib
    assert list(context_lib.span_request_ids(
        {"request_id": "a"})) == ["a"]
    assert list(context_lib.span_request_ids(
        {"request_ids": "a,b,c"})) == ["a", "b", "c"]
    # The batch form dedupes against the single form.
    assert list(context_lib.span_request_ids(
        {"request_id": "a", "request_ids": "a,b"})) == ["a", "b"]
    assert context_lib.join_ids(["a", None, "b"]) == "a,b"

  def test_export_links_request_spans_into_flows(self, tmp_path):
    from tensor2robot_tpu.obs import context as context_lib
    from tensor2robot_tpu.obs.trace import Tracer
    tracer = Tracer()
    with context_lib.bind(request_id="req-x"):
      with tracer.span("serve/enqueue"):
        pass
    with context_lib.bind(request_ids="req-x,req-lonely"):
      with tracer.span("serve/flush", batch=2):
        pass
    path = tracer.export_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
      events = json.load(f)["traceEvents"]
    flows = [e for e in events if e.get("cat") == "request"]
    # req-x has two spans -> one s + one f arrow; req-lonely has one
    # span -> no arrow (a flow needs two ends).
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert all(e["name"] == "request req-x" for e in flows)
    assert flows[0]["id"] == flows[1]["id"]


class TestCorrelationPropagation:
  """THE tier-1 satellite: requests through FleetRouter with distinct
  SLO classes — every span and the injected-breach dump carry the
  correct request_id, and the rollout mirror inherits its parent's."""

  def _router(self, predictor, recorder, n_devices=2):
    import jax

    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.stats import ServingStats
    return FleetRouter(
        predictor, devices=jax.devices()[:n_devices], num_samples=16,
        num_elites=4, iterations=2, seed=0, ladder_sizes=(1, 2),
        max_queue=2, stats=ServingStats(registry=MetricRegistry()),
        flight_recorder=recorder)

  def test_spans_and_breach_dump_carry_request_ids(self, tmp_path):
    import contextlib

    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.serving.slo import SLOClass
    from tensor2robot_tpu.serving.smoke import TinyQPredictor

    predictor = TinyQPredictor(image_size=8, action_size=4, seed=0)
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    router = self._router(predictor, recorder)
    router.warmup(predictor.make_image)
    interactive = SLOClass("interactive", priority=2, deadline_ms=200.0)
    batch_class = SLOClass("batch", priority=0, deadline_ms=2000.0)
    with router:
      live = {}
      for i in range(4):
        rid = f"corr-live-{i}"
        live[rid] = router.submit(predictor.make_image(i),
                                  slo=interactive, request_id=rid)
      for future in live.values():
        future.result(timeout=30)
      # Injected breach under held flushes: deterministic capacity
      # sheds whose dumps must name the shed request.
      burst_ids = []
      with contextlib.ExitStack() as stack:
        for replica in router.replicas:
          stack.enter_context(replica.batcher.hold_flushes())
        for j in range(8):
          rid = f"corr-burst-{j}"
          burst_ids.append(rid)
          router.submit(predictor.make_image(j), slo=batch_class,
                        request_id=rid)
    spans = trace_lib.get_tracer().spans()
    enqueue = {s["request_id"]: s for s in spans
               if s["name"] == "serve/enqueue"
               and str(s.get("request_id", "")).startswith("corr-")}
    # Every submit produced an enqueue span with ITS id and class.
    for rid in live:
      assert enqueue[rid]["slo"] == "interactive"
    for rid in burst_ids:
      assert enqueue[rid]["slo"] == "batch"
    # Every completed live request appears in a flush span's batch ids
    # (same id across threads — the flow the exporter links).
    flush_ids = set()
    for span in spans:
      if span["name"] in ("serve/flush", "serve/dispatch"):
        flush_ids.update(str(span.get("request_ids", "")).split(","))
    assert set(live) <= flush_ids, (sorted(live), sorted(flush_ids)[:10])
    # The breach dump names the shed request, top-level and in the
    # trigger context.
    assert recorder.dumps_written >= 1
    with open(recorder.last_dump_path) as f:
      payload = json.load(f)
    assert payload["reason"] == "slo_breach"
    assert payload["request_id"].startswith("corr-burst-")
    assert payload["trigger"]["slo_class"] == "batch"
    assert payload["trigger"]["request_id"] == payload["request_id"]

  def test_rollout_mirror_inherits_parent_request_id(self, tmp_path):
    import time as time_lib

    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.serving.rollout import (RolloutConfig,
                                                  RolloutController)
    from tensor2robot_tpu.serving.slo import SLOClass
    from tensor2robot_tpu.serving.smoke import TinyQPredictor

    predictor = TinyQPredictor(image_size=8, action_size=4, seed=0)
    recorder = FlightRecorder()
    router = self._router(predictor, recorder)
    router.warmup(predictor.make_image)
    interactive = SLOClass("interactive", priority=2, deadline_ms=200.0)
    with router:
      controller = RolloutController(
          router, predictor,
          RolloutConfig(mirror_fraction=1.0, canary_fraction=1.0,
                        min_shadow_samples=1, min_canary_samples=10_000),
          flight_recorder=recorder)
      with controller:
        controller.offer_candidate(
            1, predictor.make_candidate_variables(jitter=0.0))
        deadline = time_lib.time() + 30.0
        while controller.state != "canary" and time_lib.time() < deadline:
          controller.act(predictor.make_image(100), timeout=10)
        assert controller.state == "canary", controller.state
        futures = [controller.submit(predictor.make_image(200 + i),
                                     slo=interactive)
                   for i in range(4)]
        for future in futures:
          future.result(timeout=30)
    spans = trace_lib.get_tracer().spans()
    mirror_ids = {s["request_id"] for s in spans
                  if s["name"] == "serve/enqueue"
                  and s.get("slo") == "rollout_mirror"}
    assert mirror_ids, "canary phase produced no mirror requests"
    # Each mirror id must ALSO appear on a non-mirror enqueue span —
    # the parent client request whose timeline the mirror joins.
    parent_ids = {s["request_id"] for s in spans
                  if s["name"] == "serve/enqueue"
                  and s.get("slo") not in (None, "rollout_mirror")}
    assert mirror_ids <= parent_ids, (mirror_ids, sorted(parent_ids)[-8:])


class TestWatchdog:
  """ISSUE 12 tentpole (c), unit layer."""

  def _watchdog(self, tmp_path, **kwargs):
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.obs.watchdog import Watchdog
    registry = MetricRegistry()
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    return Watchdog(poll_s=0.05, default_deadline_s=0.2,
                    recorder=recorder, registry=registry,
                    **kwargs), recorder, registry

  def test_stall_escalates_counter_dump_callback(self, tmp_path):
    import time as time_lib
    stalls = []
    watchdog, recorder, registry = self._watchdog(
        tmp_path, on_stall=stalls.append)
    heartbeat = watchdog.register("replay/learner")
    heartbeat.busy()
    time_lib.sleep(0.3)
    events = watchdog.check_once()
    assert len(events) == 1
    assert events[0]["component"] == "replay/learner"
    assert registry.counter("watchdog/stalls").value == 1
    assert registry.counter(
        "watchdog/stall/replay/learner").value == 1
    assert stalls == events
    assert recorder.dumps_written == 1
    with open(recorder.last_dump_path) as f:
      payload = json.load(f)
    assert payload["schema"] == SCHEMA
    assert payload["reason"] == "watchdog_stall"
    from tensor2robot_tpu.obs.watchdog import STALL_FIELDS
    for field in STALL_FIELDS:
      assert field in payload["trigger"], payload["trigger"]
    # One stall episode = one event: a second check does not re-fire.
    assert watchdog.check_once() == []

  def test_idle_components_never_stall_and_busy_arms(self, tmp_path):
    import time as time_lib
    watchdog, _, _ = self._watchdog(tmp_path)
    heartbeat = watchdog.register("serve/batcher")  # born idle
    time_lib.sleep(0.3)
    assert watchdog.check_once() == []
    heartbeat.busy()  # work arrives: deadline runs from NOW
    assert watchdog.check_once() == []
    time_lib.sleep(0.3)
    assert len(watchdog.check_once()) == 1
    heartbeat.idle()  # queue drained: stall clears, no new event
    assert watchdog.check_once() == []
    assert watchdog.events[-1]["event"] == "watchdog_recovered"

  def test_recovery_rearms_detection(self, tmp_path):
    import time as time_lib
    watchdog, _, registry = self._watchdog(tmp_path)
    heartbeat = watchdog.register("act/collector")
    heartbeat.beat()
    time_lib.sleep(0.3)
    assert len(watchdog.check_once()) == 1
    heartbeat.beat()  # recovers
    assert watchdog.check_once() == []
    time_lib.sleep(0.3)  # stalls AGAIN -> a second episode
    assert len(watchdog.check_once()) == 1
    assert registry.counter("watchdog/stalls").value == 2

  def test_unregister_and_name_uniquification(self, tmp_path):
    watchdog, _, _ = self._watchdog(tmp_path)
    first = watchdog.register("replay/learner")
    second = watchdog.register("replay/learner")
    assert second.name == "replay/learner#2"
    watchdog.unregister(first)
    watchdog.unregister(first)  # idempotent
    assert "replay/learner" not in watchdog.snapshot()["components"]
    assert "replay/learner#2" in watchdog.snapshot()["components"]

  def test_reregistered_name_does_not_inherit_stall(self, tmp_path):
    """A component that stalled, unregistered, and re-registered under
    the same name (a restarted batcher) starts clean: no inherited
    stall state, no phantom recovery event."""
    import time as time_lib
    watchdog, _, _ = self._watchdog(tmp_path)
    first = watchdog.register("serve/batcher")
    first.busy()
    time_lib.sleep(0.3)
    assert len(watchdog.check_once()) == 1
    watchdog.unregister(first)
    events_before = len(watchdog.events)
    fresh = watchdog.register("serve/batcher")  # born idle
    assert watchdog.check_once() == []
    assert len(watchdog.events) == events_before
    assert watchdog.snapshot()["components"]["serve/batcher"][
        "stalled"] is False
    del fresh

  def test_callback_exception_is_isolated(self, tmp_path):
    import time as time_lib

    def explode(event):
      raise RuntimeError("listener bug")

    watchdog, _, registry = self._watchdog(tmp_path, on_stall=explode)
    heartbeat = watchdog.register("replay/learner")
    heartbeat.busy()
    time_lib.sleep(0.3)
    events = watchdog.check_once()  # must not raise
    assert len(events) == 1
    assert registry.counter("watchdog/stalls").value == 1

  def test_find_stragglers(self):
    from tensor2robot_tpu.obs.watchdog import find_stragglers
    result = find_stragglers(
        {"a:1": 100.0, "b:2": 96.0, "c:3": 10.0}, fraction=0.5)
    assert result["fleet_median"] == 96.0
    assert [s["name"] for s in result["stragglers"]] == ["c:3"]
    # A stopped host (rate None/0) is the worst straggler, not an
    # excluded one.
    result = find_stragglers({"a:1": 100.0, "b:2": None})
    assert [s["name"] for s in result["stragglers"]] == ["b:2"]
    # A fleet of one has no median to straggle against.
    assert find_stragglers({"a:1": 5.0})["stragglers"] == []

  def test_scaled_deadline_follows_core_gate(self, monkeypatch):
    from tensor2robot_tpu.obs import watchdog as watchdog_lib
    monkeypatch.setattr(os, "cpu_count", lambda: 2)
    assert watchdog_lib.scaled_deadline(1.0) == 4.0
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    assert watchdog_lib.scaled_deadline(1.0) == 1.0


class TestWatchdogNegativeControl:
  """ISSUE 12 satellite: a HEALTHY loop run produces zero watchdog
  events — the guard against false-positive stall dumps from slow-CI
  scheduling noise (deadlines scale per the cpu_count >= 4 gating
  convention)."""

  def test_healthy_replay_loop_run_is_silent(self, tmp_path):
    import optax

    from tensor2robot_tpu.bin.run_qtopt_replay import build_config
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.obs.watchdog import Watchdog, scaled_deadline
    from tensor2robot_tpu.replay.loop import ReplayTrainLoop
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    from dataclasses import replace

    config = build_config(smoke=True, seed=3)
    config = replace(config, capacity=256, min_fill=64, eval_every=16,
                     log_every=8)
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    watchdog = Watchdog(
        poll_s=0.1, recorder=FlightRecorder(dump_dir=str(tmp_path)),
        registry=MetricRegistry(),
        default_deadline_s=scaled_deadline(30.0))
    loop = ReplayTrainLoop(config, str(tmp_path / "logs"), model=model,
                           watchdog=watchdog)
    with watchdog:  # the monitor REALLY runs across the whole loop
      results = loop.run(16)
    assert results["steps"] >= 16
    assert watchdog.events == [], watchdog.events
    assert watchdog.stall_count == 0
    assert not [name for name in os.listdir(tmp_path)
                if name.startswith("flightrec-")]
    # The loop's heartbeats were wired, not absent: components were
    # registered and unregistered on the way out.
    assert watchdog.snapshot()["components"] == {}


class TestAggregate:
  """ISSUE 12 tentpole (b), unit layer: synthetic multi-process logdir
  merged with known-answer checks."""

  def _write_process(self, logdir, host, pid, steps, latencies,
                     requests, shed_capacity, t0=1000.0):
    """One fake process's streams: metrics.jsonl + registry snapshot."""
    directory = os.path.join(logdir, f"{host}-{pid}")
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, "metrics.jsonl"), "w") as f:
      for index, step in enumerate(steps):
        f.write(json.dumps({
            "step": step, "wall_time": t0 + index,
            "host": host, "pid": pid,
            "serving/shed_total": shed_capacity,
        }) + "\n")
    snapshot = {
        "schema": "t2r-registry-1", "host": host, "pid": pid,
        "counters": {
            "serving/requests": requests,
            "serving/shed_capacity": shed_capacity,
            "serving/class/batch/requests": requests,
            "serving/class/batch/shed_capacity": shed_capacity,
        },
        "gauges": {"replay/fill": 0.5},
        "histograms": {
            "serving/class/batch/latency_ms": {
                "count": len(latencies), "samples": latencies},
        },
    }
    with open(os.path.join(directory, "registry.json"), "w") as f:
      json.dump(snapshot, f)
    return directory

  def test_reservoir_union_is_the_one_percentile_source(self, tmp_path):
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    # Process A holds samples 1..50, process B 51..100: the merged
    # p50/p99 must come from the UNION (50/99-ish), which neither
    # process's own percentiles (25/50 and 75/100) could produce by
    # averaging.
    self._write_process(str(tmp_path), "hostA", 11, [1, 2, 3],
                        [float(v) for v in range(1, 51)], 50, 0)
    self._write_process(str(tmp_path), "hostB", 22, [1, 2, 3],
                        [float(v) for v in range(51, 101)], 50, 0)
    fleet = aggregate_logdir(str(tmp_path))
    merged = fleet["registry"]["histograms"][
        "serving/class/batch/latency_ms"]
    assert merged["merged_samples"] == 100
    assert merged["p50"] == 50.0
    assert merged["p99"] == 99.0
    assert fleet["hosts_merged"] == 2
    assert sorted(fleet["hosts"]) == ["hostA", "hostB"]

  def test_slo_rollup_sums_classes_and_checks_consistency(self, tmp_path):
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    self._write_process(str(tmp_path), "hostA", 11, [1, 2], [5.0], 40, 8)
    self._write_process(str(tmp_path), "hostB", 22, [1, 2], [9.0], 60, 16)
    fleet = aggregate_logdir(str(tmp_path))
    slo = fleet["slo"]
    assert slo["per_class"]["batch"]["requests"] == 100
    assert slo["per_class"]["batch"]["shed_capacity"] == 24
    assert slo["shed_total"] == 24
    assert slo["consistent"] is True

  def test_inconsistent_source_is_flagged(self, tmp_path):
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    directory = self._write_process(str(tmp_path), "hostA", 11,
                                    [1], [5.0], 40, 8)
    # Corrupt the snapshot: global shed counter without the class
    # counter — sheds that bypassed class accounting.
    path = os.path.join(directory, "registry.json")
    with open(path) as f:
      snapshot = json.load(f)
    del snapshot["counters"]["serving/class/batch/shed_capacity"]
    with open(path, "w") as f:
      json.dump(snapshot, f)
    fleet = aggregate_logdir(str(tmp_path))
    assert fleet["slo"]["consistent"] is False

  def test_per_host_step_rates_feed_straggler_detection(self, tmp_path):
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    # 1 step/s vs 10 steps/s over the same wall span.
    self._write_process(str(tmp_path), "hostA", 11,
                        list(range(0, 101, 10)), [1.0], 10, 0)
    self._write_process(str(tmp_path), "hostB", 22,
                        list(range(0, 11, 1)), [1.0], 10, 0)
    self._write_process(str(tmp_path), "hostC", 33,
                        list(range(0, 101, 10)), [1.0], 10, 0)
    fleet = aggregate_logdir(str(tmp_path))
    assert fleet["per_host"]["hostA:11"]["step_rate"] == 10.0
    assert fleet["per_host"]["hostB:22"]["step_rate"] == 1.0
    assert [s["name"] for s in fleet["stragglers"]["stragglers"]] == [
        "hostB:22"]
    for entry in fleet["per_host"].values():
      assert entry["step_series"], entry  # the per-host series

  def test_wedged_stream_is_worst_straggler_not_excluded(self, tmp_path):
    """A host stuck at step N that keeps emitting health records must
    read step_rate 0.0 and be flagged — None would silently drop it
    from the fleet-median comparison (the exact host the detector
    exists for)."""
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    self._write_process(str(tmp_path), "hostA", 11,
                        list(range(0, 11)), [1.0], 10, 0)
    self._write_process(str(tmp_path), "hostB", 22,
                        list(range(0, 11)), [1.0], 10, 0)
    self._write_process(str(tmp_path), "hostC", 33,
                        [7] * 11, [1.0], 10, 0)  # wedged at step 7
    fleet = aggregate_logdir(str(tmp_path))
    assert fleet["per_host"]["hostC:33"]["step_rate"] == 0.0
    assert [s["name"] for s in fleet["stragglers"]["stragglers"]] == [
        "hostC:33"]

  def test_trace_merge_links_request_across_processes(self, tmp_path):
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir

    def chrome(host, pid, names_and_ids, path):
      events = [{"name": "process_name", "ph": "M", "pid": pid,
                 "tid": 0, "args": {"name": f"{host}:{pid}"}}]
      for index, (name, rid) in enumerate(names_and_ids):
        events.append({
            "name": name, "ph": "X", "ts": 1000.0 * index, "dur": 500.0,
            "pid": pid, "tid": 1, "args": {"request_id": rid}})
      with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)

    os.makedirs(tmp_path / "p1"), os.makedirs(tmp_path / "p2")
    chrome("hostA", 11,
           [("serve/enqueue", "req-7"), ("serve/flush", "req-7"),
            ("serve/dispatch", "req-7")],
           str(tmp_path / "p1" / "trace.json"))
    chrome("hostB", 22, [("serve/flush", "req-7")],
           str(tmp_path / "p2" / "trace.json"))
    fleet = aggregate_logdir(str(tmp_path))
    trace = fleet["trace"]
    assert trace["request_ids_seen"] == 1
    assert trace["flows_linked"] == 1
    assert trace["linked_serve_timelines"] == 1
    assert trace["cross_process_flows"] == 1
    # Time-ordered across BOTH processes (hostB's flush ties hostA's
    # enqueue at ts 0 and sorts stably after it).
    assert trace["example_timeline"]["spans"] == [
        "serve/enqueue", "serve/flush", "serve/flush", "serve/dispatch"]
    merged_path = os.path.join(tmp_path, "fleet_trace.json")
    with open(merged_path) as f:
      merged = json.load(f)["traceEvents"]
    # Host-prefixed lanes with remapped pids; flows cross the lanes.
    lanes = {e["args"]["name"]: e["pid"] for e in merged
             if e.get("ph") == "M"}
    assert set(lanes) == {"hostA:11", "hostB:22"}
    assert len(set(lanes.values())) == 2
    flow_pids = {e["pid"] for e in merged if e.get("cat") == "request"}
    assert len(flow_pids) == 2
    # A re-run must not ingest its own merged output.
    again = aggregate_logdir(str(tmp_path))
    assert again["trace"]["request_ids_seen"] == 1

  def test_trace_merge_aligns_lanes_by_wall_epoch(self, tmp_path):
    """Per-process ts is relative to each Tracer's OWN perf_counter
    epoch; the exporter's epoch_wall_s anchor lets the merge offset
    lanes onto one comparable timeline — without it every lane would
    stack at ts 0 and cross-process flows could point backward."""
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir

    def chrome(host, pid, epoch_wall, spans, path):
      events = [{"name": "process_name", "ph": "M", "pid": pid,
                 "tid": 0, "args": {"name": f"{host}:{pid}",
                                    "epoch_wall_s": epoch_wall}}]
      for name, ts in spans:
        events.append({"name": name, "ph": "X", "ts": ts, "dur": 50.0,
                       "pid": pid, "tid": 1,
                       "args": {"request_id": "req-1"}})
      with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)

    os.makedirs(tmp_path / "p1"), os.makedirs(tmp_path / "p2")
    # Process B's tracer epoch is 0.0001 wall seconds (100 us) after
    # A's; its flush at LOCAL ts 100 really happened between A's
    # enqueue (0) and dispatch (400) on the shared clock.
    chrome("hostA", 11, 100.0,
           [("serve/enqueue", 0.0), ("serve/dispatch", 400.0)],
           str(tmp_path / "p1" / "trace.json"))
    chrome("hostB", 22, 100.0001, [("serve/flush", 100.0)],
           str(tmp_path / "p2" / "trace.json"))
    fleet = aggregate_logdir(str(tmp_path))
    offsets = {s["process"]: s["offset_us"]
               for s in fleet["trace"]["sources"]}
    assert offsets == {"hostA:11": 0.0, "hostB:22": 100.0}
    with open(os.path.join(tmp_path, "fleet_trace.json")) as f:
      merged = json.load(f)["traceEvents"]
    ts_by_name = {e["name"]: e["ts"] for e in merged
                  if e.get("ph") == "X"}
    assert ts_by_name["serve/flush"] == 200.0  # 100 local + 100 offset
    # The cross-process flow chain is therefore in TRUE wall order —
    # raw concatenation would have sorted B's flush first.
    assert fleet["trace"]["example_timeline"]["spans"] == [
        "serve/enqueue", "serve/flush", "serve/dispatch"]
    assert fleet["trace"]["cross_process_flows"] == 1

  def test_watchdog_stall_dumps_validated(self, tmp_path):
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir
    from tensor2robot_tpu.obs.watchdog import Watchdog
    watchdog = Watchdog(
        poll_s=0.05, default_deadline_s=0.1,
        recorder=FlightRecorder(dump_dir=str(tmp_path / "wd"),
                                min_dump_interval_s=0.0))
    heartbeat = watchdog.register("replay/learner")
    heartbeat.busy()
    import time as time_lib
    time_lib.sleep(0.2)
    assert watchdog.check_once()
    fleet = aggregate_logdir(str(tmp_path))
    assert fleet["flightrec"]["reasons"] == {"watchdog_stall": 1}
    stall = fleet["flightrec"]["watchdog_stalls"][0]
    assert stall["schema_ok"] is True
    assert stall["component"] == "replay/learner"


class TestFrontDoor:
  """ISSUE 19 tentpole (c): the router-of-routers front door over two
  EMULATED hosts in one process — each "host" a FleetRouter with its
  own isolated registry, both over the SAME device subset so their
  replica (device) names collide on purpose. The aggregate must link
  request flows across the front-door hop (the door's private tracer
  lane vs the hosts' process lane) and keep the same-named devices on
  different hosts distinct in the fleet Q-drift view."""

  @pytest.fixture(scope="class")
  def pod(self, tmp_path_factory):
    import numpy as np

    import jax

    from tensor2robot_tpu.serving.frontdoor import FrontDoor
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    from tensor2robot_tpu.serving.stats import ServingStats

    logdir = tmp_path_factory.mktemp("pod")
    predictor = TinyQPredictor(image_size=8, action_size=4, seed=0)
    devices = jax.devices()[:2]
    registries, hosts = {}, {}
    for name in ("hostA", "hostB"):
      registry = MetricRegistry()
      registries[name] = registry
      hosts[name] = FleetRouter(
          predictor, devices=devices, num_samples=16, num_elites=4,
          iterations=2, seed=0, ladder_sizes=(1, 2),
          stats=ServingStats(registry=registry))
    door = FrontDoor(hosts)
    door.warmup(predictor.make_image)
    with door:
      futures = [door.submit(predictor.make_image(i))
                 for i in range(12)]
      for future in futures:
        assert np.asarray(future.result(timeout=30)).shape == (4,)
      yield {"door": door, "predictor": predictor,
             "registries": registries, "logdir": str(logdir),
             "devices": [str(device) for device in devices]}

  def test_flows_cross_the_hop_and_hosts_stay_distinct(self, pod):
    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.obs.aggregate import aggregate_logdir

    door = pod["door"]
    snap = door.snapshot()
    assert snap["submitted"] >= 12
    assert snap["reconciled"], snap
    # The rotating tie-break spread idle-pod traffic over both hosts.
    assert all(entry["submitted"] > 0
               for entry in snap["hosts"].values()), snap["hosts"]
    # Per-emulated-host streams: each host's isolated registry under
    # its own host label (the export_snapshot override), the hosts'
    # serve spans from the process tracer, and the door's OWN lane.
    logdir = pod["logdir"]
    for name, registry in pod["registries"].items():
      host_dir = os.path.join(logdir, name)
      os.makedirs(host_dir, exist_ok=True)
      registry.export_snapshot(
          os.path.join(host_dir, "registry.json"), host=name)
    hosts_dir = os.path.join(logdir, "hostpool")
    os.makedirs(hosts_dir, exist_ok=True)
    trace_lib.get_tracer().export_chrome_trace(
        os.path.join(hosts_dir, "trace.json"))
    door_dir = os.path.join(logdir, "frontdoor")
    os.makedirs(door_dir, exist_ok=True)
    door.export_trace(os.path.join(door_dir, "trace.json"))
    fleet = aggregate_logdir(logdir)
    # Every front-door request has its ingress span in the door's lane
    # and its enqueue/flush/dispatch spans in the hosts' lane — the
    # merged flow visibly crosses the hop.
    assert fleet["trace"]["cross_process_flows"] >= 12, fleet["trace"]
    # Same-named devices on different hosts stay distinct drift keys.
    replicas = fleet["health"]["q_drift"]["replicas"]
    for device in pod["devices"]:
      owners = sorted(key.split("/", 1)[0] for key in replicas
                      if key.endswith(f"/{device}"))
      assert [owner.split(":")[0] for owner in owners] == [
          "hostA", "hostB"], (device, sorted(replicas))

  def test_drift_rollup_quarantines_host_by_name(self, pod):
    from tensor2robot_tpu.serving.slo import RequestShed, SLOClass

    door = pod["door"]
    predictor = pod["predictor"]
    device0 = pod["devices"][0]
    # The aggregate health rollup's shape, naming hostB's replica
    # divergent under its host:pid/replica key.
    process_key = f"hostB:{os.getpid()}"
    named = door.apply_drift_rollup(
        {"q_drift": {"divergent": [f"{process_key}/{device0}"]}},
        {process_key: "hostB"})
    assert named == [f"hostB:{device0}"]
    snap = door.snapshot()
    assert snap["hosts"]["hostB"]["quarantined"], snap["hosts"]
    events = [entry for entry in snap["timeline"]
              if entry["event"] == "host_quarantined"]
    assert events and events[0]["host"] == "hostB"
    assert events[0]["replica"] == device0
    assert events[0]["reason"] == "q_drift"
    # All new ingress lands on the healthy host.
    before = door.snapshot()["hosts"]
    futures = [door.submit(predictor.make_image(100 + i))
               for i in range(6)]
    for future in futures:
      future.result(timeout=30)
    after = door.snapshot()["hosts"]
    assert after["hostB"]["submitted"] == before["hostB"]["submitted"]
    assert after["hostA"]["submitted"] == (
        before["hostA"]["submitted"] + 6)
    # The ingress deadline stamp composes across the hop: a budget
    # consumed upstream sheds as expired at the replica, not served.
    dead = SLOClass("spent", 1, -5.0)
    with pytest.raises(RequestShed) as info:
      door.act(predictor.make_image(0), slo=dead, timeout=10)
    assert info.value.reason == "expired"
    door.reinstate_host("hostB")
    final = door.snapshot()
    assert not final["hosts"]["hostB"]["quarantined"]
    assert final["reconciled"], final


class TestFlightRecorderRound13:
  """ISSUE 12 satellite: per-recorder instances + the repoint warning
  + trigger context in dumps."""

  def test_repoint_warns_same_dir_does_not(self, tmp_path, caplog):
    import logging
    recorder = FlightRecorder()
    with caplog.at_level(logging.WARNING,
                         logger="tensor2robot_tpu.obs.flight_recorder"):
      recorder.configure(dump_dir=str(tmp_path / "a"))
      recorder.configure(dump_dir=str(tmp_path / "a"))  # same: quiet
      assert not caplog.records
      recorder.configure(dump_dir=str(tmp_path / "b"))  # repoint: loud
    assert any("repointed" in record.getMessage()
               for record in caplog.records)

  def test_per_loop_instances_keep_dumps_apart(self, tmp_path):
    from tensor2robot_tpu.obs.trace import Tracer
    tracer = Tracer()
    first = FlightRecorder(dump_dir=str(tmp_path / "loop1"),
                           min_dump_interval_s=0.0)
    second = FlightRecorder(dump_dir=str(tmp_path / "loop2"),
                            min_dump_interval_s=0.0)
    first.attach(tracer)
    second.attach(tracer)
    with tracer.span("learn/step"):
      pass
    assert first.events()[-1]["name"] == "learn/step"
    assert second.events()[-1]["name"] == "learn/step"
    first.trigger("loop1_failure")
    second.trigger("loop2_failure")
    assert os.listdir(tmp_path / "loop1") != os.listdir(
        tmp_path / "loop2")
    # Detach stops the feed (the per-run listener hygiene the loop
    # relies on); detaching twice is a no-op.
    first.detach(tracer)
    first.detach(tracer)
    before = first.events_total
    with tracer.span("learn/step2"):
      pass
    assert first.events_total == before
    assert second.events()[-1]["name"] == "learn/step2"

  def test_replay_loop_owns_its_recorder(self, tmp_path):
    """Two loops in one process dump into their OWN logdirs — the
    last-configured-wins footgun PR 8 handed off is closed."""
    from tensor2robot_tpu.bin.run_qtopt_replay import build_config
    from tensor2robot_tpu.replay.loop import ReplayTrainLoop
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    import optax

    config = build_config(smoke=True, seed=0)
    model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    loop_a = ReplayTrainLoop(config, str(tmp_path / "a"), model=model)
    loop_b = ReplayTrainLoop(config, str(tmp_path / "b"), model=model)
    assert loop_a.recorder is not loop_b.recorder
    assert loop_a.recorder.dump_dir != loop_b.recorder.dump_dir
    loop_a.recorder.trigger("loop_a_event")
    assert [name for name in os.listdir(tmp_path / "a")
            if name.startswith("flightrec-")]
    assert not (tmp_path / "b").exists() or not [
        name for name in os.listdir(tmp_path / "b")
        if name.startswith("flightrec-")]

  def test_actor_death_dumps_into_injected_recorder(self, tmp_path):
    """VectorActor takes the owner's recorder/watchdog (the
    CollectorWorker contract): a dying actor thread dumps into the
    LOOP's logdir, not the unconfigured process recorder's ring."""
    import time as time_lib

    from tensor2robot_tpu.obs.watchdog import Watchdog
    from tensor2robot_tpu.replay.actor import VectorActor
    from tensor2robot_tpu.replay.ingest import TransitionQueue

    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    watchdog = Watchdog(poll_s=0.05, default_deadline_s=30.0)

    def exploding_policy(images):
      raise RuntimeError("device fell over")

    actor = VectorActor(exploding_policy, TransitionQueue(64),
                        image_size=8, num_envs=2, seed=0,
                        flight_recorder=recorder, watchdog=watchdog)
    actor.start()
    deadline = time_lib.time() + 10
    while not actor.errors and time_lib.time() < deadline:
      time_lib.sleep(0.02)
    actor._thread.join(10)
    assert actor.errors
    dumps = [name for name in os.listdir(tmp_path)
             if "actor_thread_exception" in name]
    assert dumps, os.listdir(tmp_path)
    # The heartbeat was registered on the INJECTED watchdog and
    # unregistered when the thread died.
    assert watchdog.snapshot()["components"] == {}

  def test_trigger_context_lands_top_level(self, tmp_path):
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    path = recorder.trigger("slo_breach", slo_class="batch",
                            shed_reason="capacity", request_id="req-9")
    with open(path) as f:
      payload = json.load(f)
    assert payload["request_id"] == "req-9"
    assert payload["trigger"] == {
        "slo_class": "batch", "shed_reason": "capacity",
        "request_id": "req-9"}


def _assert_fleetobs_schema(results, committed: bool):
  """The FLEETOBS_r13 contract shared by the CLI run and the committed
  artifact."""
  assert results["round"] == 13
  assert results["schema"] == "t2r-fleetobs-1"
  assert results["virtual_mesh"] is True
  workers = results["workers"]
  assert len(workers) >= 2
  assert len({worker["pid"] for worker in workers}) == len(workers)
  fleet = results["fleet"]
  assert fleet["hosts_merged"] >= len(workers)
  worker_pids = {worker["pid"] for worker in workers}
  stream_pids = {entry["pid"] for entry in fleet["per_host"].values()}
  assert worker_pids <= stream_pids
  for entry in fleet["per_host"].values():
    if entry["pid"] in worker_pids:
      assert entry["step_series"], entry
  slo = fleet["slo"]
  assert slo["consistent"] is True
  assert slo["shed_total"] >= sum(worker["shed"] for worker in workers)
  for class_entry in slo["per_class"].values():
    assert class_entry["shed"] == (class_entry["shed_expired"]
                                   + class_entry["shed_capacity"])
  trace = fleet["trace"]
  assert trace["linked_serve_timelines"] >= 1
  assert trace["example_timeline"]["spans"][0] == "serve/enqueue"
  assert {"serve/flush", "serve/dispatch"} <= set(
      trace["example_timeline"]["spans"])
  assert len(trace["sources"]) >= len(workers)
  watchdog = results["watchdog"]
  assert watchdog["injected_stall"]["ok"] is True
  assert watchdog["injected_stall"]["dump_schema"] == "t2r-flightrec-1"
  assert watchdog["healthy_control"]["ok"] is True
  assert watchdog["healthy_control"]["events"] == 0
  reasons = fleet["flightrec"]["reasons"]
  assert reasons.get("watchdog_stall", 0) >= 1
  assert reasons.get("slo_breach", 0) >= 1
  if committed:
    assert all(worker["devices"] == 8 for worker in workers)


@pytest.fixture(scope="module")
def fleetobs_results(tmp_path_factory):
  """ONE obs_aggregate --ci run (the FLEETOBS protocol, reduced):
  REAL subprocess workers against a shared logdir, merged + self-
  checked — the committed-artifact pipeline under test."""
  import subprocess
  import sys
  tmp = tmp_path_factory.mktemp("fleetobs")
  logdir = tmp / "shared"
  out = tmp / "fleetobs.json"
  env = dict(os.environ)
  env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
  res = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.obs_aggregate",
       "--ci", "--logdir", str(logdir), "--out", str(out)],
      capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
  assert res.returncode == 0, res.stderr[-2000:]
  lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
  assert len(lines) == 1, res.stdout  # the ONE-JSON-line contract
  results = json.loads(lines[0])
  assert json.loads(out.read_text()) == results
  return results, str(logdir)


class TestFleetObsCLI:

  def test_schema_and_self_checks(self, fleetobs_results):
    results, _ = fleetobs_results
    _assert_fleetobs_schema(results, committed=False)

  def test_merged_trace_file_parses_with_flows(self, fleetobs_results):
    results, logdir = fleetobs_results
    path = os.path.join(logdir, results["fleet"]["trace"]["file"])
    assert os.path.exists(path)
    with open(path) as f:
      merged = json.load(f)["traceEvents"]
    lanes = [e for e in merged if e.get("ph") == "M"]
    assert len(lanes) >= 2  # one host-prefixed lane per process
    assert any(e.get("cat") == "request" for e in merged)

  def test_plain_aggregation_cli_over_existing_logdir(
      self, fleetobs_results, tmp_path):
    """The non-smoke CLI mode: point --logdir at the protocol's shared
    dir and get the same merge (idempotent re-aggregation)."""
    import subprocess
    import sys
    results, logdir = fleetobs_results
    out = tmp_path / "again.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.bin.obs_aggregate",
         "--logdir", logdir, "--out", str(out)],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-2000:]
    again = json.loads(out.read_text())
    fleet = results["fleet"]
    assert again["hosts_merged"] == fleet["hosts_merged"]
    assert again["slo"] == fleet["slo"]
    assert again["registry"]["counters"] == fleet["registry"]["counters"]


class TestCommittedFleetObsArtifact:

  def test_fleetobs_r13_json_matches_schema(self):
    path = os.path.join(ROOT, "FLEETOBS_r13.json")
    assert os.path.exists(path), "committed FLEETOBS_r13.json missing"
    with open(path) as f:
      results = json.loads(f.read().strip())
    _assert_fleetobs_schema(results, committed=True)

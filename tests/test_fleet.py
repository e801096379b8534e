"""Fleet-scale serving (ISSUE 10): router, SLO routing, live rollout.

CPU-mesh tests for the serving/fleet subsystem's contracts: the bucket
ladder replicated one-executable-per-bucket-PER-DEVICE behind the
least-loaded router; per-request determinism surviving routing (the
single-replica FleetServer stays the semantics oracle); the
shadow→canary→promote rollout cycle with injected-regression
auto-rollback and a bit-stable compile ledger; the ExportWatcher over
the async-export-hook directory layout; and the `fleet_bench --ci` CLI
lane that exercises the whole protocol chiplessly on every PR.

Timing-bar convention: everything asserted here is STRUCTURAL (ledger,
schema, shed composition, event ordering) and runs on any host; the
quantitative p99-under-budget bars live in the committed FLEET_r11
artifact's quiet run and are additionally checked in the CLI test only
on >= 4-core hosts.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_predictor():
  from tensor2robot_tpu.serving.smoke import TinyQPredictor
  return TinyQPredictor(image_size=8, action_size=4, seed=0)


def _make_router(predictor, n_devices=2, ladder=(1, 2, 4), **kwargs):
  """Router over a TRAINING mesh's device enumeration — the documented
  wiring (`FleetRouter(devices=mesh_devices(mesh))`), so replica i is
  the same physical device the training side addresses at flat index
  i."""
  import jax

  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.serving.router import FleetRouter
  mesh = mesh_lib.create_mesh({"data": n_devices},
                              devices=jax.devices()[:n_devices])
  devices = mesh_lib.mesh_devices(mesh)
  assert len(devices) == n_devices, "conftest provides the 8-device mesh"
  return FleetRouter(predictor, devices=devices, num_samples=32,
                     num_elites=4, iterations=2, seed=0,
                     ladder_sizes=ladder, **kwargs)


def test_mesh_devices_enumeration_is_flat_row_major():
  """The router's replica numbering contract: mesh_devices of a dp×tp
  mesh is the row-major flat device list — replica i == the training
  side's flat index i, one numbering for both halves of the loop."""
  import jax

  from tensor2robot_tpu.parallel import mesh as mesh_lib
  devices = jax.devices()
  mesh = mesh_lib.create_mesh({"data": 4, "model": 2},
                              devices=devices[:8])
  assert mesh_lib.mesh_devices(mesh) == list(devices[:8])


class TestFleetRouter:

  def test_one_executable_per_bucket_per_device(self, tiny_predictor):
    """The fleet ledger invariant: after warmup plus mixed-size traffic
    on every replica, each device carries exactly one executable per
    ladder bucket — never more (recompiles) and never fewer (a replica
    that silently served through another device's program)."""
    router = _make_router(tiny_predictor, n_devices=3)
    router.warmup(tiny_predictor.make_image)
    with router:
      futures = [router.submit(tiny_predictor.make_image(i))
                 for i in range(24)]
      for future in futures:
        assert np.asarray(future.result(timeout=30)).shape == (4,)
    from tensor2robot_tpu.obs.ledger import check_compile_ledger
    ledger = router.compile_ledger()
    assert len(ledger) == 3
    for device_label, counts in ledger.items():
      assert sorted(counts) == [1, 2, 4], (device_label, counts)
    # The shared smoke helper (ISSUE 11 satellite) flattens the nested
    # {device: {bucket: count}} shape and asserts exactly-once.
    check_compile_ledger(ledger)

  def test_routing_is_action_invariant(self, tiny_predictor):
    """A request's action depends on (image, seed) only: the routed
    fleet answers bit-close to a single pinned replica for the same
    seeds — which replica served is unobservable, keeping the
    single-replica server the semantics oracle."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy

    router = _make_router(tiny_predictor, n_devices=2)
    router.warmup(tiny_predictor.make_image)
    images = [tiny_predictor.make_image(50 + i) for i in range(6)]
    with router:
      futures = [router.submit(image, seed=1000 + i)
                 for i, image in enumerate(images)]
      routed = np.stack([f.result(timeout=30) for f in futures])
    single = CEMFleetPolicy(tiny_predictor, action_size=4,
                            num_samples=32, num_elites=4, iterations=2,
                            seed=0)
    reference = single(images,
                       np.arange(1000, 1006, dtype=np.uint32))
    np.testing.assert_allclose(routed, reference, atol=1e-4)

  def test_least_loaded_spreads_concurrent_traffic(self, tiny_predictor):
    """Under concurrent multi-client load every replica takes work —
    the router is joining the shortest queue, not pinning one device."""
    router = _make_router(tiny_predictor, n_devices=2, max_batch=2)
    router.warmup(tiny_predictor.make_image)
    flushed = {0: 0, 1: 0}
    for index, replica in enumerate(router.replicas):
      original = replica.policy

      def counting(images, seeds, _index=index, _original=original):
        flushed[_index] += len(images)
        return _original(images, seeds)

      replica._flush = (
          lambda items, _fn=counting: list(
              _fn([i[0] for i in items],
                  np.asarray([i[1] for i in items], np.uint32))))
      replica.batcher._batch_fn = replica._flush
    errors = []

    def client(i):
      try:
        for frame in range(6):
          router.act(tiny_predictor.make_image(i), timeout=30)
      except Exception as e:
        errors.append(e)

    with router:
      threads = [threading.Thread(target=client, args=(i,))
                 for i in range(8)]
      for t in threads:
        t.start()
      for t in threads:
        t.join()
    assert not errors, errors
    assert min(flushed.values()) > 0, flushed

  def test_two_open_flushes_feed_the_sinks_in_order_on_one_thread(
      self, tiny_predictor):
    """A replica keeps two flushes open (ISSUE 31). Each flush still
    hands its scores to `record_q_values` and then its batch to
    `record_served` on ONE thread, so a sink that pairs the two through
    a thread-local (the benchmark's) reads every request's own Q."""
    from tensor2robot_tpu.obs.registry import MetricRegistry
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    from tensor2robot_tpu.serving.stats import ServingStats

    class Sink(ServingStats):

      def __init__(self):
        super().__init__(registry=MetricRegistry())
        self._local = threading.local()
        self.calls, self.served = [], {}

      def record_q_values(self, replica, values):
        self._local.scores = np.array(values, np.float32)
        self.calls.append((threading.get_ident(), "q", len(values)))
        super().record_q_values(replica, values)

      def record_served(self, items, actions, device, params_version=None):
        scores, self._local.scores = self._local.scores, None
        self.calls.append((threading.get_ident(), "served", len(items)))
        for (_, seed), action, score in zip(items, actions, scores):
          self.served[int(seed)] = (np.array(action), float(score))

    sink = Sink()
    router = _make_router(tiny_predictor, n_devices=1, max_batch=4,
                          stats=sink, episode_recorder=sink)
    router.warmup(tiny_predictor.make_image)
    replica = router.replicas[0]
    # Both flushes are inside the policy's call before either goes on.
    both_open = threading.Barrier(2)
    policy = replica.policy

    class Meeting:
      device, ladder, _predictor, encode_once = (
          policy.device, policy.ladder, policy._predictor,
          policy.encode_once)

      def __call__(self, *args, **kwargs):
        both_open.wait(timeout=10)
        return policy(*args, **kwargs)

    replica.policy = Meeting()
    images = [tiny_predictor.make_image(70 + i) for i in range(8)]
    with router:
      with replica.batcher.hold_flushes():
        futures = [router.submit(image, seed=500 + i)
                   for i, image in enumerate(images)]
      answers = np.stack([f.result(timeout=30) for f in futures])
    assert sink.snapshot()["overlapped_flushes"] == 1
    by_thread = {}
    for tid, kind, n in sink.calls:
      by_thread.setdefault(tid, []).append((kind, n))
    assert sorted(by_thread.values()) == [[("q", 4), ("served", 4)]] * 2
    alone = CEMFleetPolicy(tiny_predictor, action_size=4, num_samples=32,
                           num_elites=4, iterations=2, seed=0)
    actions, scores = alone(images, np.arange(500, 508, dtype=np.uint32),
                            return_scores=True)
    np.testing.assert_allclose(answers, actions, atol=1e-4)
    assert sorted(sink.served) == list(range(500, 508))
    for i in range(8):
      served_action, served_score = sink.served[500 + i]
      np.testing.assert_array_equal(served_action, answers[i])
      assert served_score == pytest.approx(float(scores[i]), abs=1e-4)

  def test_warmed_but_unstarted_router_raises_typed(self, tiny_predictor):
    """ISSUE 19 satellite: warmup() compiles the ladders but does NOT
    start the batcher dispatch threads; submit() on a warmed-but-
    unstarted router used to shed every request with an opaque
    "MicroBatcher is not running". It now fails fast with a typed
    error that names start()."""
    from tensor2robot_tpu.serving.slo import RouterNotStarted

    router = _make_router(tiny_predictor, n_devices=2)
    router.warmup(tiny_predictor.make_image)
    with pytest.raises(RouterNotStarted, match="start\\(\\)"):
      router.submit(tiny_predictor.make_image(0))
    # The same router serves normally once actually started (the
    # context manager calls start()).
    with router:
      action = router.act(tiny_predictor.make_image(0), timeout=30)
    assert np.asarray(action).shape == (4,)

  def test_router_ingress_deadline_survives_hop(self, tiny_predictor):
    """The class budget is stamped at router ingress: a deadline the
    ingress clock already consumed is shed by the replica as expired,
    not served late."""
    from tensor2robot_tpu.serving.slo import RequestShed, SLOClass

    router = _make_router(tiny_predictor, n_devices=2)
    router.warmup(tiny_predictor.make_image)
    with router:
      dead = SLOClass("spent", 1, -5.0)  # budget consumed upstream
      with pytest.raises(RequestShed) as info:
        router.act(tiny_predictor.make_image(0), slo=dead, timeout=10)
      assert info.value.reason == "expired"
      # Live classes still flow.
      live = SLOClass("fresh", 1, 200.0)
      action = router.act(tiny_predictor.make_image(1), slo=live,
                          timeout=30)
      assert np.asarray(action).shape == (4,)
    snap = router.snapshot()
    assert snap["per_class"]["spent"]["shed_expired"] == 1


class TestRolloutController:

  def _cycle(self, predictor, router, controller, version, variables,
             bound_s=30.0):
    assert controller.offer_candidate(version, variables)
    deadline = time.time() + bound_s
    i = 0
    while controller.state != "serving" and time.time() < deadline:
      controller.act(predictor.make_image(300 + i), timeout=10)
      i += 1
    assert controller.state == "serving", "rollout cycle did not finish"

  def test_promote_and_injected_regression_rollback(self):
    """The acceptance cycle: a healthy candidate walks
    shadow→canary→promote (served version bumps, actions switch to the
    new weights); an injected-regression candidate is auto-rolled-back
    in shadow (serving params untouched); the compile ledger is
    bit-stable through BOTH cycles."""
    from tensor2robot_tpu.serving.rollout import (RolloutConfig,
                                                  RolloutController)
    from tensor2robot_tpu.serving.smoke import TinyQPredictor

    predictor = TinyQPredictor(image_size=8, action_size=4, seed=0)
    router = _make_router(predictor, n_devices=2)
    router.warmup(predictor.make_image)
    ledger_before = router.compile_ledger()
    with router:
      controller = RolloutController(
          router, predictor,
          RolloutConfig(mirror_fraction=1.0, canary_fraction=0.5,
                        min_shadow_samples=6, min_canary_samples=3))
      with controller:
        healthy = predictor.make_candidate_variables(jitter=0.0)
        self._cycle(predictor, router, controller, 1, healthy)
        events = [e["event"] for e in controller.timeline()]
        assert events == ["shadow_start", "canary_start", "promote"], (
            controller.timeline())
        assert predictor.model_version == 1

        promote_event = controller.timeline()[-1]
        # The healthy candidate is weight-identical: paired comparison
        # must read EXACT agreement and zero q delta.
        assert promote_event["q_delta_mean"] == 0.0

        regressed = predictor.make_candidate_variables(jitter=5.0,
                                                       seed=9)
        self._cycle(predictor, router, controller, 2, regressed)
        events = [e["event"] for e in controller.timeline()]
        assert events[-2:] == ["shadow_start", "auto_rollback"], events
        rollback = controller.timeline()[-1]
        assert rollback["stage"] == "shadow"
        assert not rollback["q_bar_passed"]
        assert rollback["q_delta_mean"] < -0.05
        # Rollback left the promoted (healthy) params serving.
        assert predictor.model_version == 1
    assert router.compile_ledger() == ledger_before

  def test_shadow_adds_no_compiles_and_clients_see_live_params(self):
    """During the shadow phase every client answer comes from the LIVE
    params (mirroring is invisible), and scoring the candidate through
    the shared executables adds nothing to the ledger."""
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    from tensor2robot_tpu.serving.rollout import (RolloutConfig,
                                                  RolloutController)
    from tensor2robot_tpu.serving.smoke import TinyQPredictor

    predictor = TinyQPredictor(image_size=8, action_size=4, seed=0)
    router = _make_router(predictor, n_devices=2)
    router.warmup(predictor.make_image)
    ledger_before = router.compile_ledger()
    reference = CEMFleetPolicy(predictor, action_size=4, num_samples=32,
                               num_elites=4, iterations=2, seed=0)
    images = [predictor.make_image(70 + i) for i in range(4)]
    with router:
      controller = RolloutController(
          router, predictor,
          RolloutConfig(mirror_fraction=1.0, canary_fraction=0.0,
                        min_shadow_samples=10_000))  # stay in shadow
      with controller:
        controller.offer_candidate(
            1, predictor.make_candidate_variables(jitter=3.0))
        assert controller.state == "shadow"
        seeds = [5000 + i for i in range(len(images))]
        futures = [controller.submit(img) for img in images]
        del seeds  # controller assigns its own; compare via fresh seeds
        [f.result(timeout=30) for f in futures]
        # Deterministic check with caller-pinned seeds via the router.
        routed = np.stack([
            router.submit(img, seed=7000 + i).result(timeout=30)
            for i, img in enumerate(images)])
        expected = reference(images,
                             np.arange(7000, 7004, dtype=np.uint32))
        np.testing.assert_allclose(routed, expected, atol=1e-4)
    assert router.compile_ledger() == ledger_before


class TestExportWatcher:

  def test_poll_and_push_over_export_layout(self, tmp_path):
    """The watcher reads the async-export hook's output layout
    (versioned dirs + variables npz) and hands (version, variables) to
    the controller; the push path (on_export wiring) wins over polling."""
    from tensor2robot_tpu.export import variables_io
    from tensor2robot_tpu.export.native_export_generator import (
        VARIABLES_NPZ)
    from tensor2robot_tpu.serving.rollout import ExportWatcher

    root = tmp_path / "exports"

    def publish(version, value):
      export_dir = root / str(version)
      export_dir.mkdir(parents=True)
      variables_io.save_variables(
          str(export_dir / VARIABLES_NPZ),
          {"params": {"w": np.full((3, 2), value, np.float32)}})
      return str(export_dir)

    watcher = ExportWatcher(str(root))
    assert watcher.poll() is None  # empty root: nothing yet
    publish(100, 1.0)
    version, variables = watcher.poll()
    assert version == 100
    np.testing.assert_array_equal(variables["params"]["w"],
                                  np.full((3, 2), 1.0, np.float32))
    assert watcher.poll() is None  # already seen
    # Push path: the hook's on_export callback signature.
    export_dir = publish(200, 2.0)
    watcher.notify(export_dir, 200)
    version, variables = watcher.poll()
    assert version == 200
    assert float(variables["params"]["w"][0, 0]) == 2.0

  def test_async_export_hook_on_export_wiring(self):
    """AsyncExportHookBuilder forwards on_export into the hook — the
    push half of the learner→server plumbing exists end to end."""
    from tensor2robot_tpu.hooks.async_export_hook import (
        AsyncExportHookBuilder)

    seen = []
    builder = AsyncExportHookBuilder(
        export_generator=object(), on_export=lambda d, s: seen.append(
            (d, s)))
    (hook,) = builder.create_hooks(trainer=None, model_dir="/tmp/x")
    assert hook._on_export is not None
    hook._on_export("/exports/5", 5)
    assert seen == [("/exports/5", 5)]


class TestFleetBenchCLI:
  """The tier-1 lane for the FLEET_r11 protocol: `fleet_bench --ci`
  runs the whole stack — router, SLO classes, overload burst, both
  rollout cycles — chiplessly on every PR."""

  def _run_ci(self):
    env = dict(os.environ)
    res = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.serving.fleet_bench",
         "--ci"],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, res.stdout
    return json.loads(lines[0])

  def test_fleet_ci_contract(self):
    obj = self._run_ci()
    assert obj["round"] == 11
    assert obj["devices"] == 2
    assert obj["bucket_ladder"] == [1, 2, 4]
    # One executable per bucket PER DEVICE, across the sweep, the
    # overload burst, and both rollout cycles.
    assert obj["ledger_ok"] is True
    assert len(obj["compile_ledger"]) == 2
    for counts in obj["compile_ledger"].values():
      assert counts == {"1": 1, "2": 1, "4": 1} or counts == {
          1: 1, 2: 1, 4: 1}
    # Per-class schema at every sweep point.
    for point in obj["sweep"]:
      for name in ("interactive", "standard", "batch"):
        entry = point["per_class"][name]
        assert entry["latency_p50_ms"] is not None
        assert entry["latency_p99_ms"] >= entry["latency_p50_ms"]
        assert entry["budget_ms"] > 0
    # Overload burst: sheds happened and consumed the LOWEST priority
    # class first (structural: holds on any host speed).
    burst = obj["overload_burst"]
    assert burst["shed_total"] > 0
    assert burst["priority_ordering_ok"] is True
    # Rollout acceptance: one full promote cycle plus one
    # injected-regression auto-rollback in the committed timeline.
    rollout = obj["rollout"]
    assert rollout["promotions"] == 1
    assert rollout["auto_rollbacks"] == 1
    assert rollout["cycle_ok"] is True
    events = [e["event"] for e in obj["promotion_timeline"]]
    assert events.index("promote") < events.index("auto_rollback")
    # The promote stuck (version 1) and the rollback didn't (still 1).
    assert rollout["served_model_version"] == 1
    # Quantitative budget bar: gated on >= 4 cores per the repo's
    # flaky-under-contention convention (ROADMAP maintenance note); the
    # committed FLEET_r11.json quiet run carries it below that.
    if (os.cpu_count() or 1) < 4:
      return
    acceptance = obj["sweep"][-1]
    assert acceptance["all_budgets_met"] is True, json.dumps(
        acceptance, indent=2)
    assert obj["fleet_p99_headroom"] is not None
    assert obj["fleet_p99_headroom"] > 0

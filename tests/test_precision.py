"""Precision-tiered CEM (ISSUE 13): bf16 Q-scoring vs the f32 oracle.

Tier-1 contracts for the scoring-precision policy: the f32 default is
the UNCHANGED oracle (bit-identical scores, unchanged ledger keys, zero
new executables anywhere); the bf16 tier genuinely computes in bf16
(scores differ, the jaxpr carries bf16 dots) while returning f32 scores
to the search; selected-action agreement holds at every ladder bucket
under the q-oracle bar; the fused loop's `--precision bf16` lane learns
through the bf16 label stage; the fleet ledger proves exactly-once
compilation per bucket per device PER TIER; the rollout harness walks a
bf16 candidate tier through shadow→canary→promote and auto-rolls back
an injected q-delta breach; and the predictor's precision-cast seam
rejects unintentional dtype drift while allowing the explicit cast.

Timing-bar convention: quantitative bars (TD reduction through the CLI,
agreement rates on the trained critic) gate on >= 4 cores per the
repo's flaky-under-contention rule; structure asserts everywhere. The
committed PRECISION_r14.json carries the full-protocol numbers and is
schema+bar-validated here.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

QUANT = (os.cpu_count() or 1) >= 4


@pytest.fixture(scope="module")
def tiny_model_and_variables():
  """A TinyQ critic + its init variables (random init: enough for
  every structural and bit-identity contract; the AGREEMENT bars run
  on the pretrained critic fixture below)."""
  import jax

  from tensor2robot_tpu.replay.smoke import TinyQCriticModel
  model = TinyQCriticModel()
  return model, model.init_variables(jax.random.key(0))


@pytest.fixture(scope="module")
def trained_critic():
  """A briefly-trained critic (the precision bench's pretrain phase at
  reduced steps): the agreement property needs a real Q landscape."""
  from tensor2robot_tpu.replay.precision_bench import _pretrain_critic
  model, variables, _ = _pretrain_critic(
      image_size=16, action_size=4, gamma=0.8, grasp_radius=0.4,
      steps=80, batch_size=64, seed=0)
  return model, variables


class TestPrecisionPolicy:
  """The cem.py policy core: validation, casting, score-fn tiers."""

  def test_validate_rejects_unknown_tier(self):
    from tensor2robot_tpu.research.qtopt import cem
    with pytest.raises(ValueError, match="fp16"):
      cem.validate_precision("fp16")
    assert cem.validate_precision("f32") == "f32"
    assert cem.validate_precision("bf16") == "bf16"

  def test_cast_scoring_variables_f32_is_identity(self,
                                                  tiny_model_and_variables):
    from tensor2robot_tpu.research.qtopt import cem
    _, variables = tiny_model_and_variables
    assert cem.cast_scoring_variables(variables, "f32") is variables

  def test_cast_scoring_variables_bf16_casts_float_leaves_only(self):
    import jax.numpy as jnp

    from tensor2robot_tpu.research.qtopt import cem
    tree = {"w": jnp.ones((2, 2), jnp.float32),
            "steps": jnp.zeros((), jnp.int32),
            "wire": jnp.zeros((2,), jnp.uint8)}
    cast = cem.cast_scoring_variables(tree, "bf16")
    assert cast["w"].dtype == jnp.bfloat16
    assert cast["steps"].dtype == jnp.int32
    assert cast["wire"].dtype == jnp.uint8

  def test_f32_score_fn_bit_identical_to_pre_tier_body(
      self, tiny_model_and_variables):
    """The unchanged-semantics oracle: precision='f32' must produce the
    exact pre-tier closure (frozen here), bit for bit."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.research.qtopt import cem
    model, variables = tiny_model_and_variables
    rng = np.random.default_rng(0)
    image = jnp.asarray(rng.integers(0, 255, (16, 16, 3), np.uint8))
    actions = jnp.asarray(
        rng.uniform(-1, 1, (8, 4)).astype(np.float32))

    def frozen_pre_tier(img, acts):
      tiled = jnp.broadcast_to(img[None], (acts.shape[0],) + img.shape)
      outputs = model.predict_fn(
          variables, {"image": tiled,
                      "action": acts.astype(jnp.float32)})
      return jnp.reshape(outputs["q_predicted"], (-1,))

    score = cem.make_tiled_q_score_fn(model.predict_fn, variables)
    new = jax.jit(score)(image, actions)
    old = jax.jit(frozen_pre_tier)(image, actions)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))

  def test_bf16_scores_are_f32_and_genuinely_differ(
      self, tiny_model_and_variables):
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.research.qtopt import cem
    model, variables = tiny_model_and_variables
    rng = np.random.default_rng(1)
    image = jnp.asarray(rng.integers(0, 255, (16, 16, 3), np.uint8))
    actions = jnp.asarray(
        rng.uniform(-1, 1, (8, 4)).astype(np.float32))
    s32 = cem.make_tiled_q_score_fn(model.predict_fn, variables)
    sbf = cem.make_tiled_q_score_fn(model.predict_fn, variables,
                                    precision="bf16")
    a = jax.jit(s32)(image, actions)
    b = jax.jit(sbf)(image, actions)
    # f32 accumulation contract: scores return to f32 before top_k.
    assert b.dtype == jnp.float32
    # Real bf16 numerics (not a relabeled f32 path): scores differ and
    # the traced program carries bfloat16.
    assert float(jnp.max(jnp.abs(a - b))) > 0.0
    assert "bf16" in str(jax.make_jaxpr(sbf)(image, actions))

  def test_fleet_cem_optimize_validates_precision(
      self, tiny_model_and_variables):
    import jax

    from tensor2robot_tpu.research.qtopt import cem
    model, variables = tiny_model_and_variables
    score = jax.vmap(cem.make_tiled_q_score_fn(model.predict_fn, variables))
    states = np.zeros((2, 16, 16, 3), np.uint8)
    keys = jax.random.split(jax.random.key(0), 2)
    with pytest.raises(ValueError, match="precision"):
      cem.fleet_cem_optimize(score, states, keys, 4, precision="fp16")

  def test_bellman_targets_bf16_stay_f32_and_clipped(
      self, tiny_model_and_variables):
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.replay.bellman import make_bellman_targets_fn
    model, variables = tiny_model_and_variables
    with pytest.raises(ValueError):
      make_bellman_targets_fn(model, 4, 0.9, 8, 2, 1, True,
                              precision="tf32")
    targets_fn = make_bellman_targets_fn(model, 4, 0.9, 8, 2, 1, True,
                                         precision="bf16")
    rng = np.random.default_rng(2)
    n = 4
    targets, q_next = jax.jit(targets_fn)(
        variables,
        jnp.asarray(rng.integers(0, 255, (n, 16, 16, 3), np.uint8)),
        jnp.asarray(rng.random(n), jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jax.random.split(jax.random.key(3), n))
    # The Bellman arithmetic is f32-updates territory on every tier.
    assert targets.dtype == jnp.float32
    assert q_next.dtype == jnp.float32
    assert float(targets.min()) >= 0.0 and float(targets.max()) <= 1.0


class TestBucketAgreement:
  """bf16/f32 selected-action agreement across every ladder bucket —
  the q-oracle bar (the rollout gate's per-request form), plus the
  request-determinism invariance the fleet contract implies."""

  BUCKETS = (1, 2, 4, 8, 16)

  def _actions(self, model, variables, precision, bucket, scenes, seeds):
    from tensor2robot_tpu.replay.loop import _HotReloadPredictor
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    policy = CEMFleetPolicy(
        _HotReloadPredictor(model, variables), action_size=4,
        num_samples=16, num_elites=4, iterations=2, seed=7,
        ladder=BucketLadder((bucket,)), precision=precision)
    out = []
    for start in range(0, len(scenes), bucket):
      out.append(np.asarray(policy(
          scenes[start:start + bucket],
          seeds[start:start + bucket])))
    return np.concatenate(out)

  def test_agreement_across_every_bucket(self, trained_critic):
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.research.qtopt.jax_grasping import (
        make_scene_bank)
    model, variables = trained_critic
    corpus = 16
    bank = make_scene_bank(corpus, image_size=16, base_seed=5)
    scenes = [np.asarray(bank.images[i]) for i in range(corpus)]
    seeds = np.arange(corpus, dtype=np.uint32)
    q_fn = jax.jit(
        lambda feats: model.q_value(model.predict_fn(variables, feats)))

    reference = {}
    for bucket in self.BUCKETS:
      a32 = self._actions(model, variables, "f32", bucket, scenes, seeds)
      abf = self._actions(model, variables, "bf16", bucket, scenes,
                          seeds)
      # Request determinism survives the tier AND the bucket: the
      # action for (scene, seed) is independent of flush composition,
      # so every bucket size yields the same per-request answers.
      for precision, actions in (("f32", a32), ("bf16", abf)):
        if precision in reference:
          np.testing.assert_array_equal(actions, reference[precision])
        else:
          reference[precision] = actions
      images = jnp.asarray(np.stack(scenes))
      q32 = np.asarray(q_fn({"image": images,
                             "action": jnp.asarray(a32)})).reshape(-1)
      qbf = np.asarray(q_fn({"image": images,
                             "action": jnp.asarray(abf)})).reshape(-1)
      # Selected-action agreement, q-oracle form: the bf16 action must
      # score within 0.05 (value space) of the f32 action under the
      # f32 oracle. Numerics, not timing — but the rate bar itself is
      # a trained-landscape property, so it gates with the pretrain
      # budget's stability on loud hosts.
      agreement = float(np.mean((q32 - qbf) <= 0.05))
      if QUANT:
        assert agreement >= 0.95, (bucket, agreement, q32 - qbf)
      # Structure floor on any host: the actions are finite and inside
      # the box, and the two tiers are not wildly divergent.
      assert np.all(np.isfinite(abf))
      assert np.all(np.abs(abf) <= 1.0 + 1e-6)


class TestTierLedger:
  """Per-tier exactly-once compilation + tier-grouped attribution."""

  def test_two_tiers_one_ledger_distinct_keys(self,
                                              tiny_model_and_variables):
    from tensor2robot_tpu.obs import ledger as ledger_lib
    from tensor2robot_tpu.replay.loop import _HotReloadPredictor
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    model, variables = tiny_model_and_variables
    predictor = _HotReloadPredictor(model, variables)
    ledger = ledger_lib.ExecutableLedger()
    frames = [np.zeros((16, 16, 3), np.uint8)] * 2
    for precision in ("f32", "bf16"):
      policy = CEMFleetPolicy(
          predictor, action_size=4, num_samples=8, num_elites=2,
          iterations=1, seed=0, ladder=BucketLadder((2,)),
          ledger=ledger, precision=precision)
      policy(frames, np.arange(2, dtype=np.uint32))
      policy(frames, np.arange(2, dtype=np.uint32))  # no recompile
    counts = ledger.compile_counts
    assert counts == {"cem_bucket_2": 1, "cem_bucket_2_bf16": 1}, counts
    attribution = ledger.attribution(wall_seconds=10.0)
    tiers = attribution["tier_shares"]
    assert set(tiers) == {"f32", "bf16"}
    assert tiers["f32"]["executables"] == 1
    assert tiers["bf16"]["executables"] == 1
    # Rows carry the dtype tag the tier rollup groups by.
    by_name = {row["name"]: row for row in attribution["executables"]}
    assert by_name["cem_bucket_2"]["dtype"] == "f32"
    assert by_name["cem_bucket_2_bf16"]["dtype"] == "bf16"

  def test_bellman_updater_tags_scoring_dtype(self,
                                              tiny_model_and_variables):
    from tensor2robot_tpu.obs import ledger as ledger_lib
    from tensor2robot_tpu.replay.bellman import BellmanUpdater
    model, variables = tiny_model_and_variables
    ledger = ledger_lib.ExecutableLedger()
    updater = BellmanUpdater(model, variables, action_size=4,
                             num_samples=8, num_elites=2, iterations=1,
                             ledger=ledger, precision="bf16")
    rng = np.random.default_rng(0)
    batch = {
        "next_image": rng.integers(0, 255, (4, 16, 16, 3), np.uint8),
        "reward": rng.random(4).astype(np.float32),
        "done": np.zeros(4, np.float32),
        "image": rng.integers(0, 255, (4, 16, 16, 3), np.uint8),
        "action": rng.uniform(-1, 1, (4, 4)).astype(np.float32),
    }
    targets, _ = updater.compute_targets(batch)
    td = updater.td_errors(variables, batch, targets)
    assert td.dtype == np.float32
    rows = {row["name"]: row
            for row in ledger.attribution()["executables"]}
    # The label executable carries the tier; TD (priorities + eval) is
    # pinned f32 on every tier.
    assert rows["bellman_targets"]["dtype"] == "bf16"
    assert rows["td_error"]["dtype"] == "f32"


class TestF32Oracle:
  """--precision f32 changes NOTHING: keys, defaults, constructors."""

  def test_f32_policy_ledger_keys_unchanged(self,
                                            tiny_model_and_variables):
    from tensor2robot_tpu.obs import ledger as ledger_lib
    from tensor2robot_tpu.replay.loop import _HotReloadPredictor
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    model, variables = tiny_model_and_variables
    ledger = ledger_lib.ExecutableLedger()
    policy = CEMFleetPolicy(
        _HotReloadPredictor(model, variables), action_size=4,
        num_samples=8, num_elites=2, iterations=1, seed=0,
        ladder=BucketLadder((1,)), ledger=ledger)
    assert policy.precision == "f32"
    policy([np.zeros((16, 16, 3), np.uint8)],
           np.zeros(1, np.uint32))
    assert ledger.compile_counts == {"cem_bucket_1": 1}

  def test_unknown_tier_fails_at_construction_everywhere(self):
    import tempfile

    from tensor2robot_tpu.replay.loop import (ReplayLoopConfig,
                                              ReplayTrainLoop)
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    assert ReplayLoopConfig().precision == "f32"
    with pytest.raises(ValueError, match="precision"):
      ReplayTrainLoop(ReplayLoopConfig(precision="f16"),
                      tempfile.mkdtemp(), model=TinyQCriticModel())

  def test_router_default_tier_and_same_tier_candidate_rejected(self):
    from tensor2robot_tpu.serving.rollout import RolloutController
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    predictor = TinyQPredictor(seed=0)
    router = FleetRouter(predictor, ladder_sizes=(1,), num_samples=8,
                         num_elites=2, iterations=1)
    assert router.precision == "f32"
    controller = RolloutController(router, predictor)
    with pytest.raises(ValueError, match="already the fleet's"):
      controller.offer_precision_candidate("f32")
    # A same-tier no-op promotion must not rebuild the policy cache.
    before = [replica.policy for replica in router.replicas]
    router.set_precision("f32")
    assert [replica.policy for replica in router.replicas] == before


class TestPredictorCastSeam:
  """set_variables dtype drift: rejected by default, allowed via
  cast=True with the served avals untouched."""

  @pytest.fixture()
  def loaded_predictor(self):
    from tensor2robot_tpu.predictors.checkpoint_predictor import (
        CheckpointPredictor)
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    predictor = CheckpointPredictor(TinyQCriticModel())
    predictor.init_randomly()
    return predictor

  def _bf16_view(self, variables):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, variables)

  def test_dtype_drift_rejected_without_cast(self, loaded_predictor):
    drifted = self._bf16_view(loaded_predictor._variables)
    with pytest.raises(ValueError, match="cast=True"):
      loaded_predictor.set_variables(drifted)

  def test_structural_drift_rejected_even_with_cast(self,
                                                    loaded_predictor):
    """The seam is floating->floating only: a non-float mismatch is
    structural drift, and cast=True must not silently truncate it."""
    import jax
    import jax.numpy as jnp
    drifted = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.int32), loaded_predictor._variables)
    with pytest.raises(ValueError, match="structural"):
      loaded_predictor.set_variables(drifted, cast=True)

  def test_explicit_cast_installs_at_live_avals(self, loaded_predictor):
    import jax
    import jax.numpy as jnp
    version = loaded_predictor.model_version
    reference = jax.tree_util.tree_map(np.asarray,
                                       loaded_predictor._variables)
    drifted = self._bf16_view(loaded_predictor._variables)
    loaded_predictor.set_variables(drifted, cast=True)
    assert loaded_predictor.model_version == version + 1
    for leaf in jax.tree_util.tree_leaves(loaded_predictor._variables):
      assert leaf.dtype != jnp.bfloat16
    # Values are the bf16-quantized candidate's, at the f32 avals.
    new_leaf = jax.tree_util.tree_leaves(loaded_predictor._variables)[0]
    old_leaf = jax.tree_util.tree_leaves(reference)[0]
    assert new_leaf.dtype == old_leaf.dtype
    # predict still serves (the avals every executable compiled
    # against are untouched).
    out = loaded_predictor.predict({
        "image": np.zeros((2, 16, 16, 3), np.uint8),
        "action": np.zeros((2, 4), np.float32)})
    assert out["q_predicted"].shape == (2,)


class TestRolloutPrecisionCandidate:
  """The live-traffic gate at tier-1 scale: breach auto-rollback, then
  the healthy bf16 tier promoted with the fleet actually serving it and
  a per-tier exactly-once ledger across BOTH cycles."""

  def test_breach_then_promote_cycle(self):
    import time

    from tensor2robot_tpu.serving.rollout import (RolloutConfig,
                                                  RolloutController)
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    predictor = TinyQPredictor(seed=0)
    router = FleetRouter(predictor, ladder_sizes=(1, 2), num_samples=8,
                         num_elites=2, iterations=1, max_queue=16,
                         seed=0)
    router.warmup(predictor.make_image)
    controller = RolloutController(
        router, predictor,
        RolloutConfig(mirror_fraction=1.0, canary_fraction=0.5,
                      min_shadow_samples=4, min_canary_samples=2,
                      seed=0))
    frames = [predictor.make_image(i) for i in range(8)]

    def drive(i0):
      stop_at = time.monotonic() + 60.0
      i = i0
      while controller.state != "serving" and time.monotonic() < stop_at:
        controller.submit(frames[i % len(frames)]).result(30.0)
        i += 1
      return i

    with router, controller:
      # Injected q-delta breach through the candidate tier.
      breach = predictor.make_candidate_variables(jitter=5.0, seed=7)
      assert controller.offer_precision_candidate("bf16",
                                                  variables=breach)
      i = drive(0)
      assert router.precision == "f32"  # fleet untouched
      events = [e["event"] for e in controller.timeline()]
      assert events == ["shadow_start", "auto_rollback"], events
      assert controller.timeline()[-1]["precision"] == "bf16"
      assert controller.timeline()[-1]["q_bar_passed"] is False
      # Healthy tier: same executables as the breach offer (memoized
      # policy), walks the full cycle, fleet flips to bf16.
      assert controller.offer_precision_candidate("bf16")
      drive(i)
      events = [e["event"] for e in controller.timeline()[2:]]
      assert events == ["shadow_start", "canary_start", "promote"], (
          events)
      assert router.precision == "bf16"
      # Post-promote traffic serves through the promoted tier.
      action = np.asarray(controller.act(frames[0], timeout=30.0))
      assert action.shape == (4,)
    # Exactly once per bucket per TIER across warmup, both cycles, and
    # post-promote traffic — including the re-offer after rollback.
    counts = router.ledger.compile_counts
    assert counts, counts
    assert all(count == 1 for count in counts.values()), counts
    assert any(key.startswith("cem_bucket_1_bf16") for key in counts), (
        counts)


class TestThreeTierLedger:
  """Satellite (ISSUE 16): THREE concurrent tiers — f32, bf16, int8 —
  through hot reload and a promote cycle, exactly-once per
  (bucket, device, dtype)."""

  def test_three_tiers_survive_hot_reload(self,
                                          tiny_model_and_variables):
    import jax

    from tensor2robot_tpu.obs import ledger as ledger_lib
    from tensor2robot_tpu.replay.loop import _HotReloadPredictor
    from tensor2robot_tpu.serving.bucketing import BucketLadder
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    model, variables = tiny_model_and_variables
    predictor = _HotReloadPredictor(model, variables)
    ledger = ledger_lib.ExecutableLedger()
    frames = [np.zeros((16, 16, 3), np.uint8)] * 2
    policies = {
        precision: CEMFleetPolicy(
            predictor, action_size=4, num_samples=8, num_elites=2,
            iterations=1, seed=0, ladder=BucketLadder((2,)),
            ledger=ledger, precision=precision)
        for precision in ("f32", "bf16", "int8")}
    for policy in policies.values():
      policy(frames, np.arange(2, dtype=np.uint32))
    # Hot reload: new variables through every tier, zero recompiles —
    # int8 re-quantizes at placement time, same executable.
    bumped = jax.tree_util.tree_map(lambda x: x + 0.05, variables)
    predictor.update(bumped)
    actions = {
        precision: np.asarray(policy(frames,
                                     np.arange(2, dtype=np.uint32)))
        for precision, policy in policies.items()}
    counts = ledger.compile_counts
    assert counts == {"cem_bucket_2": 1, "cem_bucket_2_bf16": 1,
                      "cem_bucket_2_int8": 1}, counts
    tiers = ledger.attribution(wall_seconds=10.0)["tier_shares"]
    assert set(tiers) == {"f32", "bf16", "int8"}
    for precision, action in actions.items():
      assert np.all(np.isfinite(action)), precision

  @pytest.mark.slow
  def test_three_tiers_through_promote_cycles(self):
    import time

    from tensor2robot_tpu.serving.rollout import (RolloutConfig,
                                                  RolloutController)
    from tensor2robot_tpu.serving.router import FleetRouter
    from tensor2robot_tpu.serving.smoke import TinyQPredictor
    predictor = TinyQPredictor(seed=0)
    router = FleetRouter(predictor, ladder_sizes=(1, 2), num_samples=8,
                         num_elites=2, iterations=1, max_queue=16,
                         seed=0)
    router.warmup(predictor.make_image)
    controller = RolloutController(
        router, predictor,
        RolloutConfig(mirror_fraction=1.0, canary_fraction=0.5,
                      min_shadow_samples=4, min_canary_samples=2,
                      seed=0))
    frames = [predictor.make_image(i) for i in range(8)]

    def drive(i0):
      stop_at = time.monotonic() + 60.0
      i = i0
      while controller.state != "serving" and time.monotonic() < stop_at:
        controller.submit(frames[i % len(frames)]).result(30.0)
        i += 1
      return i

    with router, controller:
      # bf16 promotes first, then int8 on the bf16-serving fleet: the
      # three tiers' executables coexist on every replica.
      assert controller.offer_precision_candidate("bf16")
      i = drive(0)
      assert router.precision == "bf16"
      assert controller.offer_precision_candidate("int8")
      drive(i)
      assert router.precision == "int8"
      action = np.asarray(controller.act(frames[0], timeout=30.0))
      assert action.shape == (4,)
    counts = router.ledger.compile_counts
    assert counts, counts
    # Exactly once per (bucket, device, dtype) across warmup, both
    # promote cycles, and post-promote traffic.
    assert all(count == 1 for count in counts.values()), counts
    for tier in ("_bf16", "_int8"):
      assert any(tier in key for key in counts), (tier, counts)
    tiers = router.ledger.attribution(wall_seconds=10.0)["tier_shares"]
    assert {"f32", "bf16", "int8"} <= set(tiers)


class TestPrecisionBenchAndCLI:
  """The PRECISION protocol end to end at tier-1 scale (in-process:
  the full --ci subprocess lane costs minutes this suite doesn't have)
  plus the run_qtopt_replay --precision bf16 CLI contract."""

  def test_measure_precision_structure(self):
    from tensor2robot_tpu.replay.precision_bench import measure_precision
    result = measure_precision(
        buckets=(1, 2), corpus_scenes=8, pretrain_steps=40,
        loop_steps=16, rollout_devices=1, rollout_min_shadow=4,
        rollout_min_canary=2, rollout_cycle_s=60.0, seed=0,
        enforce_bars=False)
    assert result["round"] == 14
    agreement = result["agreement"]
    assert set(agreement["per_bucket"]) == {"1", "2"}
    for entry in agreement["per_bucket"].values():
      assert 0.0 <= entry["agreement_rate"] <= 1.0
      assert entry["pairs"] == 8
    control = agreement["seed_noise_control"]
    assert control["pairs"] == 8
    fused = result["fused_loop"]
    for tier in ("f32", "bf16"):
      assert fused[tier]["anakin_step_compiles"] == 1
      assert fused[tier]["ledger_all_one"] is True
    assert fused["f32"]["initial_eval_td"] == (
        fused["bf16"]["initial_eval_td"])  # same seed, same eval set
    ledger = result["tier_ledger"]
    assert ledger["per_tier_exactly_once"] is True
    assert set(ledger["tier_shares"]) == {"f32", "bf16"}
    rollout = result["rollout"]
    assert rollout["breach_rolled_back"] is True
    assert rollout["cycle_ok"] is True
    assert rollout["precision_served"] == "bf16"
    # The chipless honesty rule: the compact speedup key is null on a
    # virtual mesh no matter what the host measured.
    assert result["virtual_mesh"] is True
    assert result["cem_bf16_speedup"] is None
    assert result["cem_bf16_action_agreement"] == (
        agreement["overall_rate"])

  def test_replay_cli_precision_bf16(self):
    """`run_qtopt_replay --smoke --anakin --precision bf16`: the fused
    loop learns through the bf16 label stage (TD bar gated on cores),
    one anakin_step executable, tier recorded in the artifact."""
    # --mesh 1 pins the single-device oracle mesh (the test env's 8
    # virtual devices would otherwise become an 8-way default mesh the
    # 4-env smoke fleet cannot shard over).
    res = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.bin.run_qtopt_replay",
         "--smoke", "--anakin", "--precision", "bf16", "--steps", "40",
         "--mesh", "1", "--no-anakin-bench"],
        capture_output=True, text=True, timeout=420, cwd=ROOT,
        env=dict(os.environ))
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [l for l in res.stdout.strip().splitlines() if l.strip()]
    obj = json.loads(lines[-1])
    assert obj["precision"] == "bf16"
    assert obj["compile_counts"]["anakin_step"] == 1
    assert all(v == 1 for v in obj["compile_counts"].values()), (
        obj["compile_counts"])
    assert obj["eval_td_reduction"] is not None
    if QUANT:
      assert obj["eval_td_reduction"] >= 0.30, obj["eval_td_reduction"]


class TestCommittedPrecisionArtifact:
  """PRECISION_r14.json: schema + every acceptance bar, as committed."""

  def test_committed_artifact_meets_bars(self):
    path = os.path.join(ROOT, "PRECISION_r14.json")
    assert os.path.exists(path), "PRECISION_r14.json not committed"
    with open(path) as f:
      artifact = json.load(f)
    assert artifact["round"] == 14
    assert artifact["buckets"] == [1, 2, 4, 8, 16]
    # Bar 1: selected-action agreement >= 0.95 vs the f32 oracle on
    # the committed scene corpus, at EVERY bucket.
    assert artifact["agreement"]["overall_rate"] >= 0.95
    for entry in artifact["agreement"]["per_bucket"].values():
      assert entry["agreement_rate"] >= 0.95, entry
    # Bar 2: fused-loop TD reduction within 0.05 of the f32 bar.
    assert artifact["fused_loop"]["td_delta"] <= 0.05
    assert artifact["fused_loop"]["f32"][
        "eval_td_reduction_converged"] >= 0.30
    assert artifact["fused_loop"]["bf16"][
        "eval_td_reduction_converged"] >= 0.30
    # Bar 3: ledger exactly one executable per bucket per tier.
    assert artifact["tier_ledger"]["per_tier_exactly_once"] is True
    counts = artifact["tier_ledger"]["compile_counts"]
    for bucket in artifact["buckets"]:
      assert counts[f"cem_bucket_{bucket}"] == 1
      assert counts[f"cem_bucket_{bucket}_bf16"] == 1
    # Bar 4: a completed shadow→canary→promote timeline for the bf16
    # tier with auto-rollback proven on an injected q-delta breach.
    rollout = artifact["rollout"]
    assert rollout["breach_rolled_back"] is True
    assert rollout["promotions"] >= 1
    assert rollout["auto_rollbacks"] >= 1
    assert rollout["precision_served"] == "bf16"
    events = rollout["events"]
    assert events.index("auto_rollback") < events.index("promote")
    promote = [e for e in rollout["timeline"]
               if e["event"] == "promote"][-1]
    assert promote["precision"] == "bf16"
    # Chipless honesty: virtual mesh -> the speedup key is null.
    if artifact["virtual_mesh"]:
      assert artifact["cem_bf16_speedup"] is None

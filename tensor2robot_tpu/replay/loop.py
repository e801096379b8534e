"""The closed QT-Opt loop: collect → replay → Bellman-label → train.

This is the subsystem the reference repo never contained (SURVEY.md §2:
only the Q-function model is in-tree; the collector fleet, replay log,
and Bellman updaters ran off-repo) — rebuilt in the Podracer shape
(PAPERS.md, arXiv:2104.06272): actors and learner in one process
sharing host RAM, fixed-shape device-resident batches, and a bounded
set of compiled programs whose count is ASSERTED, not hoped for.

Data path per optimizer step:

  collectors (threads)            train thread
  ─────────────────────           ───────────────────────────────
  CEMFleetPolicy over a           feeder.drain() → ReplayBuffer
  fleet of GraspRetryEnvs         buffer.sample()      (fixed shape)
  → episodes → TransitionQueue    BellmanUpdater.compute_targets
     (bounded, drop-oldest)       trainer AOT train_step (donated)
                                  td_errors → priorities + metrics
                                  every K: push params to collectors
                                           + refresh target net

Compiled-program ledger (`compile_counts` in the result): ONE train-step
executable, ONE Bellman-target executable, ONE TD executable, ONE eval
executable, ONE CEM executable per collector bucket — everything AOT at
the buffer's fixed batch shape, so a shape regression raises instead of
silently recompiling (the recompile is the TPU production killer: a
30-second XLA compile mid-loop starves every collector).

Param refresh rides the predictors' hot-reload contract: collectors
hold a `_HotReloadPredictor` whose variables the train thread swaps —
the CEM executables are keyed on bucket size only (serving/policy.py),
so a refresh never recompiles, exactly like the fleet server's
checkpoint hot-reload.

Metrics flow through utils/metric_writer (fill fraction, sample
staleness, ingest drop rate, priority entropy, target-network lag,
train/eval TD) — the replay-health block a production loop pages on.
"""

from __future__ import annotations

import os
import threading
import time
import types
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import optax

from tensor2robot_tpu.obs import faults as faults_lib
from tensor2robot_tpu.obs import flight_recorder as flight_lib
from tensor2robot_tpu.obs import ledger as obs_ledger
from tensor2robot_tpu.obs import registry as registry_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.obs import watchdog as watchdog_lib
from tensor2robot_tpu.predictors.abstract_predictor import AbstractPredictor
from tensor2robot_tpu.replay.bellman import BellmanUpdater
from tensor2robot_tpu.replay.ingest import ReplayFeeder, TransitionQueue
from tensor2robot_tpu.replay.ring_buffer import (ReplayBuffer,
                                                 ShardedReplayBuffer)
from tensor2robot_tpu.specs import tensorspec_utils as ts


def transition_spec(image_size: int, action_size: int) -> ts.TensorSpecStruct:
  """The loop's transition schema (uint8 wire images, Bellman leaves)."""
  image = ts.ExtendedTensorSpec((image_size, image_size, 3), np.uint8,
                                name="image")
  return ts.TensorSpecStruct({
      "image": image,
      "action": ts.ExtendedTensorSpec((action_size,), np.float32,
                                      name="action"),
      "reward": ts.ExtendedTensorSpec((), np.float32, name="reward"),
      "done": ts.ExtendedTensorSpec((), np.float32, name="done"),
      "next_image": ts.ExtendedTensorSpec.from_spec(image,
                                                    name="next_image"),
  })


def _param_sharding_summary(params) -> Dict:
  """Evidence block for the TP acceptance bar: how the final TrainState's
  params are ACTUALLY laid out (leaf shardings, not mesh shape) plus the
  per-replica param bytes — one device's resident slice vs the dense
  total (the HBM figure TP exists to shrink)."""
  import jax

  leaves = jax.tree_util.tree_leaves(params)
  model_sharded = 0
  bytes_total = 0
  bytes_per_replica = 0
  for leaf in leaves:
    bytes_total += int(leaf.nbytes)
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    names = {name for entry in (spec or ())
             for name in ((entry,) if isinstance(entry, str)
                          else (entry or ()))}
    if "model" in names:
      model_sharded += 1
    shards = getattr(leaf, "addressable_shards", None)
    if shards:
      device0 = min(shards, key=lambda s: s.device.id)
      bytes_per_replica += int(device0.data.nbytes)
    else:
      bytes_per_replica += int(leaf.nbytes)
  return {
      "total_leaves": len(leaves),
      "model_sharded_leaves": model_sharded,
      "param_bytes_total": bytes_total,
      "param_bytes_per_replica": bytes_per_replica,
  }


class _HotReloadPredictor(AbstractPredictor):
  """In-memory predictor whose variables the train thread hot-swaps.

  The minimal form of the checkpoint/export predictors' hot-reload
  contract: `device_fn()` returns a STABLE fn (the model's predict_fn —
  so jit caches and AOT executables survive updates) plus whatever
  variables are current; `update()` is an atomic pointer swap (GIL) and
  bumps model_version like a new export landing.
  """

  def __init__(self, model, variables):
    import jax
    self._model = model
    self._variables = variables
    self._version = 0
    self._jitted = jax.jit(model.predict_fn)

  def update(self, variables) -> None:
    self._variables = variables
    self._version += 1

  def set_variables(self, variables, version=None, cast: bool = False
                    ) -> None:
    """The rollout promotion entry point (serving/rollout.py): the same
    atomic swap as ``update()``, but carrying the candidate's export
    version so ``model_version`` names the promoted learner step — the
    number the flywheel's staleness-lag metric subtracts from the
    current learner step (ISSUE 18)."""
    del cast  # host trees only; nothing to cast
    self._variables = variables
    self._version = self._version + 1 if version is None else int(version)

  def restore(self, timeout_s: float = 0.0,
              raise_on_timeout: bool = False) -> bool:
    return True

  def init_randomly(self) -> None:
    pass

  def predict(self, features):
    outputs = self._jitted(self._variables, dict(features))
    return {k: np.asarray(v) for k, v in outputs.items()}

  def device_fn(self):
    return self._model.predict_fn, self._variables

  def factored_device_fns(self):
    return self._model.factored_cem_fns()

  def get_feature_specification(self) -> ts.TensorSpecStruct:
    return ts.flatten_spec_structure(
        self._model.get_feature_specification("predict"))

  @property
  def model_version(self) -> int:
    return self._version


class CollectorWorker:
  """One thread driving a fleet of GraspRetryEnvs through a CEM policy.

  All `num_envs` envs step in LOCKSTEP through one batched policy call,
  so the policy compiles exactly one bucket executable; an env that
  finishes its episode flushes it to the queue and resets immediately,
  keeping the batch shape constant forever.
  """

  def __init__(self, policy, queue: TransitionQueue, image_size: int,
               num_envs: int = 4, max_attempts: int = 4,
               seed: int = 0, grasp_radius: float = 0.35,
               exploration_epsilon: float = 0.2,
               scripted_fraction: float = 0.25,
               flight_recorder=None, watchdog=None):
    from tensor2robot_tpu.research.qtopt.synthetic_grasping import (
        GraspRetryEnv)
    self._policy = policy
    self._queue = queue
    self._recorder = flight_recorder or flight_lib.get_recorder()
    # Owner-injectable watchdog (same reason as flight_recorder): the
    # loop's monitor must cover ITS collector threads, not register
    # them on the never-started process default.
    self._watchdog = watchdog or watchdog_lib.get_watchdog()
    # Exploration mix, QT-Opt parity: the reference's logs were seeded
    # by SCRIPTED grasps (its real-robot data was majority scripted
    # early on — synthetic_grasping.generate_grasps models the same
    # with positive_fraction) plus noisy on-policy actions. A cold
    # random Q CANNOT be the only success source: with rare positives
    # the critic fits the base rate (a constant) and the CEM max never
    # rises, so the loop needs scripted successes exactly like the
    # reference did. epsilon draws uniform actions; scripted_fraction
    # draws near-object actions from the env's oracle pose.
    self._epsilon = exploration_epsilon
    self._scripted = scripted_fraction
    self._explore_rng = np.random.default_rng(seed + 555)
    self._envs = [
        GraspRetryEnv(image_size=image_size, max_attempts=max_attempts,
                      radius=grasp_radius)
        for _ in range(num_envs)
    ]
    self._seed = seed
    self._next_scene = 0
    self._records: List[Dict[str, list]] = [
        {"actions": [], "rewards": [], "dones": []}
        for _ in range(num_envs)
    ]
    self.episodes = 0
    self.successes = 0
    self.env_steps = 0
    self.errors: List[BaseException] = []
    self._stop = threading.Event()
    self._thread = threading.Thread(target=self._run, daemon=True)

  def start(self) -> None:
    for env in self._envs:
      env.reset(self._scene_seed())
    self._thread.start()

  def request_stop(self) -> None:
    """Signals the thread; returns immediately (never raises)."""
    self._stop.set()

  def stop(self, timeout: float = 30.0) -> None:
    """Signal + join + surface any recorded error. A multi-collector
    owner should request_stop() on EVERY worker first, then join —
    one dead collector must not leave its siblings running."""
    self.request_stop()
    self._thread.join(timeout)
    if self.errors:
      raise RuntimeError("collector died") from self.errors[0]

  def _scene_seed(self) -> int:
    seed = self._seed * 1_000_003 + self._next_scene
    self._next_scene += 1
    return seed

  def _run(self) -> None:
    # Liveness heartbeat (ISSUE 12): one beat per lockstep control
    # step; unregistered on exit so a cleanly-stopped collector never
    # reads as stalled.
    heartbeat = self._watchdog.register("act/collector")
    try:
      while not self._stop.is_set():
        self.step_once()
        heartbeat.beat()
    except BaseException as e:  # noqa: BLE001 — surfaced via stop()
      self.errors.append(e)
      # Loop-thread death is a flight-recorder trigger: the dump holds
      # the spans/events right before this collector died.
      self._recorder.trigger("collector_thread_exception",
                             error=f"{type(e).__name__}: {e}")
    finally:
      self._watchdog.unregister(heartbeat)

  def step_once(self) -> None:
    """One lockstep control step across the whole env fleet."""
    images = [env.image for env in self._envs]
    with trace_lib.span("act/cem_policy", envs=len(self._envs)):
      actions = np.asarray(self._policy(images))
    draw = self._explore_rng.random(len(self._envs))
    uniform = self._explore_rng.uniform(
        -1.0, 1.0, actions.shape).astype(np.float32)
    scripted = uniform.copy()
    noise = self._explore_rng.normal(
        0.0, 0.12, (len(self._envs), 2)).astype(np.float32)
    scripted[:, :2] = np.clip(
        np.stack([env.target for env in self._envs]) + noise, -1.0, 1.0)
    actions = np.where((draw < self._epsilon)[:, None], uniform, actions)
    actions = np.where(
        (draw >= 1.0 - self._scripted)[:, None], scripted, actions)
    self.env_steps += len(self._envs)
    for env, record, action in zip(self._envs, self._records, actions):
      scene = env.image
      reward, done, truncated = env.step(np.asarray(action))
      record["actions"].append(np.asarray(action, np.float32))
      record["rewards"].append(reward)
      # Bootstrap through truncation: only SUCCESS terminates value.
      record["dones"].append(float(done))
      if done or truncated:
        t = len(record["actions"])
        self._queue.put_episode({
            # Static scene: every observation in the episode (including
            # the closing next-state) is the same rendered image.
            "images": np.stack([scene] * (t + 1)),
            "actions": np.stack(record["actions"]),
            "rewards": np.asarray(record["rewards"], np.float32),
            "dones": np.asarray(record["dones"], np.float32),
        })
        self.episodes += 1
        self.successes += int(done)
        record["actions"], record["rewards"], record["dones"] = [], [], []
        env.reset(self._scene_seed())


@dataclass
class ReplayLoopConfig:
  """Knobs for ReplayTrainLoop (defaults: the chipless CI smoke scale)."""
  image_size: int = 16
  action_size: int = 4
  batch_size: int = 32
  capacity: int = 512
  min_fill: int = 96
  num_buffer_shards: int = 2
  prioritized: bool = True
  gamma: float = 0.8
  learning_rate: float = 3e-3
  num_collectors: int = 1
  envs_per_collector: int = 4
  max_attempts: int = 3
  grasp_radius: float = 0.4
  queue_capacity: int = 512
  cem_num_samples: int = 16
  cem_num_elites: int = 4
  cem_iterations: int = 2
  exploration_epsilon: float = 0.25
  scripted_fraction: float = 0.25
  refresh_every: int = 15
  polyak_tau: Optional[float] = None  # None = hard target copy
  eval_every: int = 30
  eval_batches: int = 4
  log_every: int = 10
  seed: int = 0
  min_fill_timeout_s: float = 300.0
  model_kwargs: Dict = field(default_factory=dict)
  # Device-resident learner (ISSUE 4): replay state lives on device and
  # training runs as ONE donated megastep executable scanning
  # `megastep_inner` sample→label→train→reprioritize iterations per
  # dispatch; the numpy ring + per-step host path above stays the
  # fallback (device_resident=False). `ingest_chunk` is the fixed H2D
  # staging quantum (one extend executable).
  device_resident: bool = False
  megastep_inner: int = 10
  ingest_chunk: int = 64
  # Vectorized actor fleet (ISSUE 5): replace the num_collectors scalar
  # CollectorWorker threads (envs_per_collector envs each) with ONE
  # VectorActor batching the SAME total env count through one fused CEM
  # bucket executable, feeding the queue in fixed fleet-size chunks.
  # Collection SEMANTICS (retry budget, exploration-mix fractions and
  # per-step draw order, the scene-seed formula) are unchanged; the
  # single actor draws from ONE seed stream (collector 0's base seed)
  # where the threaded path runs num_collectors independent streams —
  # bit-identity is pinned at the worker level (one fleet == N scalar
  # envs sharing a stream, tests/test_actor.py), not against the
  # threaded loop, whose scene assignment is thread-timing-dependent
  # anyway. The threaded scalar path stays the default and the
  # measured fallback.
  vector_actors: bool = False
  # Fused Anakin loop (ISSUE 6): the JAX-native grasping env
  # (research/qtopt/jax_grasping.py) plus acting, replay extend, and
  # the learner inner body fused into ONE donated executable
  # (replay/anakin.py) — no collector threads, no queue, zero host
  # work in the steady state. The env draws scenes from an
  # oracle-rendered bank of `anakin_bank_scenes` (prerendered once at
  # startup by the numpy semantics oracle, cycled thereafter); each
  # dispatch scans `anakin_inner` control steps with one optimizer
  # step every `anakin_train_every`-th CONTROL step — one control step
  # advances the whole fleet, i.e. num_collectors * envs_per_collector
  # env steps (min-fill gated INSIDE the program). The VectorActor
  # path stays the measured fallback.
  anakin: bool = False
  anakin_inner: int = 40
  anakin_train_every: int = 8
  anakin_bank_scenes: int = 512
  # Pod-scale mesh (ISSUE 7): mesh_dp > 0 pins an explicit dp×tp mesh
  # ({"data": mesh_dp, "model": mesh_tp} over the first dp*tp devices)
  # instead of the Trainer default (ALL visible devices on the data
  # axis). On a dp > 1 mesh the anakin path runs fully sharded: env
  # fleet split per shard, replay ring capacity-sharded per device,
  # learn body data-parallel with gradient all-reduce. The fleet width
  # (num_collectors * envs_per_collector), batch_size, and capacity
  # must all divide mesh_dp — the loop refuses indivisible sizes with
  # the fix named. zero1=None resolves to (mesh_dp > 1): ZeRO-1
  # cross-replica weight-update sharding (Trainer's
  # shard_optimizer_state) is on for pod runs, off on the unchanged
  # single-device oracle path.
  mesh_dp: int = 0
  mesh_tp: int = 1
  zero1: Optional[bool] = None
  # CEM Q-scoring precision tier (ISSUE 13, cem.SCORING_PRECISIONS):
  # "f32" (default, the oracle — every path lowers exactly as r10) or
  # "bf16" (low-precision scoring matmuls for acting, Bellman labeling,
  # and the collectors' CEM policy; gradients, optimizer state, and
  # TD-priority arithmetic stay f32). Threaded into the host
  # BellmanUpdater's label path, the MegastepLearner's fused label
  # stage, the AnakinLoop's fused acting+labeling, and the collector
  # CEMFleetPolicy. The eval-vs-analytic-Q* TD metric is f32 on every
  # path (BellmanUpdater.td_errors — f32-updates territory), so the
  # TD-reduction bar compares tiers against ONE oracle metric.
  precision: str = "f32"
  # Learner crash-resume (ISSUE 14): checkpoint_every > 0 writes a
  # loop checkpoint every that-many OPTIMIZER steps — TrainState via
  # orbax (train/checkpoints.CheckpointManager, synchronous so the
  # sidecar can finalize after it) plus a tmp→mv sidecar carrying the
  # lagged target net, the full replay-ring state (storage, cursors,
  # priorities, sampling rng), label-seed counter, ingest accounting,
  # and the eval history — into <logdir>/checkpoints. resume=True
  # restores the NEWEST VALID checkpoint (corrupt/partial dirs are
  # rejected with a flightrec record and older steps tried) and
  # continues from its exact step; with nothing valid on disk it
  # starts fresh (the preemption-tolerant default: "resume if you
  # can"). Since ISSUE 19 the FUSED device paths checkpoint too: the
  # donated anakin/megastep state's only host seam is between
  # dispatches, so the loop barriers there and writes the whole
  # carried composite (TrainState + env fleet + replay ring + target
  # net) through the orbax manager — every process contributes its
  # shards — with a primary-only sidecar stamping counters, mesh
  # geometry, and process count (restore refuses a mismatched
  # geometry with the fix named). `checkpoint_dir` overrides the
  # default <logdir>/checkpoints root: multi-process runs keep
  # per-process logdirs but MUST share one checkpoint root (each
  # process holds only its shards of the global arrays).
  checkpoint_every: int = 0
  checkpoint_keep: int = 3
  resume: bool = False
  checkpoint_dir: Optional[str] = None
  # Training-health sentinel (ISSUE 15, obs/health.py). health=True
  # (the default: unattended operation is the ROADMAP item 1 operating
  # mode) computes the fixed per-learn-iteration health summary —
  # non-finite counts over grads/params/targets, grad/param norms,
  # TD/Q mean/max, priority entropy, sample age — IN-PROGRAM on the
  # fused paths (zero new executables; the summaries ride the existing
  # metrics D2H) and per optimizer step on the host path (one extra
  # tiny `health_summary` executable), and runs every observation
  # through a HealthMonitor with the default rules: breaches escalate
  # registry counters -> a `health_breach` flightrec dump carrying the
  # step -> (with checkpointing armed on the host path) an automatic
  # checkpoint snapshot of the breaching state. health_halt=True
  # additionally HALTS the loop (obs.health.HealthHalt) when a hard
  # rule — non-finite grads/params/targets — breaches, rather than
  # training on garbage.
  health: bool = True
  health_halt: bool = False
  # Windowed device-trace capture (ISSUE 11 satellite): (start, end)
  # OPTIMIZER steps handed to utils.profiling.ProfilerHook — the same
  # windowed jax.profiler capture train_eval runs, now available on
  # every replay path (`run_qtopt_replay --profile START,END`). Steps
  # are observed at the loop's cadence boundaries (per optimizer step
  # on the host path, per dispatch on the fused paths), so the realized
  # window snaps outward exactly as the hook documents; the guarded
  # start_trace means a concurrently armed train-side ProfilerHook
  # cannot double-start the profiler.
  profile_window: Optional[Tuple[int, int]] = None


class ReplayTrainLoop:
  """Owns every piece of the loop; `run(num_steps)` drives it.

  Args:
    model: any CriticModel with uint8 image + action features (must
      match `config.image_size`/`action_size`). Default: the flagship
      QTOptGraspingModel on the uint8 wire — the production loop. The
      CI smoke passes replay/smoke.TinyQCriticModel instead (see its
      docstring for why the flagship cannot witness learning at CI
      budgets).
  """

  def __init__(self, config: ReplayLoopConfig, logdir: str, model=None,
               flight_recorder: Optional[flight_lib.FlightRecorder] = None,
               watchdog: Optional[watchdog_lib.Watchdog] = None,
               fault_plan: Optional[faults_lib.FaultPlan] = None):
    from tensor2robot_tpu.train.trainer import Trainer
    from tensor2robot_tpu.utils.metric_writer import MetricWriter

    from tensor2robot_tpu.research.qtopt import cem as cem_lib

    self.config = config
    cem_lib.validate_precision(config.precision)  # fail at construction
    self.logdir = logdir
    # Fault seam (ISSUE 14): the ONE point a scheduled learner `crash`
    # enters this loop — checked per optimizer step on the host path.
    self._faults = fault_plan
    self.model = model if model is not None else self._default_model()
    # Observability spine (ISSUE 11): one ExecutableLedger per loop run
    # (every compiled program this loop owns registers + records
    # dispatch time into it — the attribution in the result's `obs`
    # block) and the process registry as the metric namespace. Since
    # round 13 each loop owns its OWN FlightRecorder pointed at THIS
    # logdir (subscribed to the process tracer only for the duration
    # of run()) — the old repoint-the-process-recorder wiring was
    # last-configured-wins, so two loops in one process silently stole
    # each other's dumps. The watchdog (default: the process one,
    # monitor not running unless the owner starts it) receives
    # learner/feeder heartbeats from every loop path.
    self.obs_ledger = obs_ledger.ExecutableLedger()
    self.registry = registry_lib.get_registry()
    self.recorder = flight_recorder or flight_lib.FlightRecorder(
        dump_dir=logdir)
    self.watchdog = watchdog or watchdog_lib.get_watchdog()
    # Training-health sentinel (ISSUE 15): one monitor per loop,
    # escalating through THIS loop's recorder (dumps land in the
    # logdir beside the metrics) and the process registry.
    self.health_monitor = None
    if config.health:
      from tensor2robot_tpu.obs import health as health_lib
      self.health_monitor = health_lib.HealthMonitor(
          rules=health_lib.default_rules(capacity=config.capacity),
          registry=self.registry, recorder=self.recorder,
          halt_on_breach=config.health_halt)
    self._health_exec = None
    self._pending_numeric: List[faults_lib.FaultSpec] = []
    mesh = None
    if config.mesh_dp:
      import jax
      from tensor2robot_tpu.parallel import mesh as mesh_lib
      needed = config.mesh_dp * config.mesh_tp
      devices = jax.devices()
      if len(devices) < needed:
        raise ValueError(
            f"mesh {config.mesh_dp}x{config.mesh_tp} needs {needed} "
            f"device(s), have {len(devices)}. On a chipless host run "
            "the smoke lane (which bootstraps a virtual CPU mesh) or "
            "shrink the mesh.")
      mesh = mesh_lib.create_mesh(
          {"data": config.mesh_dp, "model": config.mesh_tp},
          devices=devices[:needed])
    zero1 = (config.zero1 if config.zero1 is not None
             else config.mesh_dp > 1)
    # Rule-partitioned tensor parallelism (ISSUE 16): tp>1 asks the
    # model for its own partition rules and threads the resulting
    # PartitionSpecs through the trainer (and, via train_step_fn's
    # in-body constraints, the fused anakin/megastep executables), so
    # critic params genuinely split over the model axis. tp=1 passes
    # None — the trainer stays on its pure-DP/ZeRO paths and the
    # program lowers bit-identically to r09/r10 (the oracle).
    param_specs = None
    if mesh is not None and config.mesh_tp > 1:
      from tensor2robot_tpu.parallel import tp_rules
      param_specs = tp_rules.partition_specs_for_model(
          self.model, mesh, axis="model")
    self.trainer = Trainer(self.model, mesh=mesh, seed=config.seed,
                           param_specs=param_specs,
                           shard_optimizer_state=zero1)
    self.writer = MetricWriter(logdir)
    spec = transition_spec(config.image_size, config.action_size)
    if config.device_resident or config.anakin:
      # The device ring IS the sharded buffer on this path: storage
      # shards over the capacity axis via the trainer's mesh (the
      # num_buffer_shards host striping exists to relieve a host lock
      # the device path doesn't have). The anakin loop pins the ingest
      # chunk to the env fleet width: its fused extend runs at exactly
      # that one shape, inside the executable.
      from tensor2robot_tpu.replay.device_buffer import DeviceReplayBuffer
      chunk = (config.num_collectors * config.envs_per_collector
               if config.anakin else config.ingest_chunk)
      if config.anakin and config.capacity < chunk:
        # DeviceReplayBuffer silently clamps ingest_chunk to capacity,
        # which AnakinLoop would then reject with a chunk!=fleet error
        # that names the wrong knob — diagnose the real one here.
        raise ValueError(
            f"anakin=True needs capacity >= the env fleet width "
            f"(num_collectors {config.num_collectors} x "
            f"envs_per_collector {config.envs_per_collector} = {chunk}): "
            f"capacity {config.capacity} would clamp the fused extend "
            "chunk below the fleet")
      self.buffer = DeviceReplayBuffer(
          spec, config.capacity, config.batch_size, seed=config.seed,
          prioritized=config.prioritized,
          ingest_chunk=chunk, mesh=self.trainer.mesh,
          ledger=self.obs_ledger)
    elif config.num_buffer_shards > 1:
      self.buffer = ShardedReplayBuffer(
          spec, config.capacity, config.batch_size,
          num_shards=config.num_buffer_shards, seed=config.seed,
          prioritized=config.prioritized)
    else:
      self.buffer = ReplayBuffer(
          spec, config.capacity, config.batch_size, seed=config.seed,
          prioritized=config.prioritized)
    self.queue = TransitionQueue(config.queue_capacity,
                                 registry=self.registry,
                                 flight_recorder=self.recorder)
    self.feeder = ReplayFeeder(self.queue, self.buffer, config.min_fill)
    self.compile_counts: Dict[str, int] = {}
    self._collectors: List[CollectorWorker] = []
    self._ckpt_manager = None
    if config.checkpoint_every or config.resume:
      from tensor2robot_tpu.train.checkpoints import CheckpointManager
      self.checkpoint_root = (config.checkpoint_dir
                              or os.path.join(logdir, "checkpoints"))
      # Synchronous saves: the sidecar finalizes AFTER the orbax step
      # does, so sidecar-present implies whole-checkpoint-usable.
      self._ckpt_manager = CheckpointManager(
          self.checkpoint_root, max_to_keep=config.checkpoint_keep,
          save_interval_steps=0, async_checkpointing=False)

  # --- helpers -------------------------------------------------------------

  def _default_model(self):
    """The production model: flagship Q-fn, uint8 wire, GroupNorm.

    GroupNorm instead of reference BatchNorm because the loop serves
    PREDICT-mode params continuously from step 0, and BN's cold running
    statistics would poison every early Q-target in a way that
    self-heals too slowly for a continuous loop's warm-up."""
    from tensor2robot_tpu.research.qtopt.t2r_models import QTOptGraspingModel
    config = self.config
    return QTOptGraspingModel(
        image_size=config.image_size, action_size=config.action_size,
        uint8_images=True, norm="group",
        optimizer_fn=lambda: optax.adam(config.learning_rate),
        **config.model_kwargs)

  def _host_variables(self, state):
    from tensor2robot_tpu.export import export_utils
    return export_utils.fetch_variables_to_host(
        state.variables(use_ema=True))

  def _make_policy(self, predictor):
    from tensor2robot_tpu.serving.policy import CEMFleetPolicy
    c = self.config
    ladder = None
    if c.vector_actors:
      # Pin the ladder to the actor batch: acting compiles EXACTLY one
      # bucket executable (the ledger's cem_bucket_<N> == 1 claim), and
      # the fleet batch never pads.
      from tensor2robot_tpu.serving.bucketing import BucketLadder
      ladder = BucketLadder((c.num_collectors * c.envs_per_collector,))
    return CEMFleetPolicy(
        predictor, action_size=c.action_size,
        num_samples=c.cem_num_samples, num_elites=c.cem_num_elites,
        iterations=c.cem_iterations, seed=c.seed + 7, ladder=ladder,
        ledger=self.obs_ledger, precision=c.precision)

  def _eval_transitions(self):
    """Held-out random-action eval set WITH its analytic value targets.

    The retry env has a closed-form optimal Q (synthetic_grasping.
    GraspRetryEnv docstring): grasping at the object always succeeds,
    so V*(s) = 1 and

        Q*(s, a) = 1 if success(a) else gamma.

    Eval TD-error is measured against THIS fixed point, not the moving
    target network: the Bellman residual of a random init is near zero
    by self-consistency (q ≈ gamma·q everywhere), so it cannot witness
    learning — distance to Q* starts large and falls only if the
    updater actually propagates grasp reward through the CEM max.

    Returns (batches, q_star_per_batch).
    """
    from tensor2robot_tpu.research.qtopt import synthetic_grasping as sg
    c = self.config
    n = c.batch_size * c.eval_batches
    images, targets = sg.sample_scenes(
        n, image_size=c.image_size, seed=c.seed + 990_001,
        num_distractors=0, occlusion=False)
    rng = np.random.default_rng(c.seed + 990_002)
    # Class-balanced actions (synthetic_grasping.generate_grasps'
    # positive_fraction convention): half near-object, half uniform, so
    # the metric weighs the supervised arm (success -> 1) and the
    # bootstrap arm (fail -> gamma) comparably instead of being
    # dominated by whichever class random actions happen to produce.
    actions = rng.uniform(-1.0, 1.0,
                          (n, c.action_size)).astype(np.float32)
    near = rng.random(n) < 0.5
    noise = rng.normal(0.0, 0.12, (n, 2)).astype(np.float32)
    actions[near, :2] = np.clip(targets[near] + noise[near], -1.0, 1.0)
    success = sg.grasp_success(targets, actions,
                               c.grasp_radius).astype(np.float32)
    q_star = np.where(success > 0, 1.0, c.gamma).astype(np.float32)
    batches, stars = [], []
    for i in range(c.eval_batches):
      part = slice(i * c.batch_size, (i + 1) * c.batch_size)
      batches.append({
          "image": images[part],
          "action": actions[part],
          "reward": success[part],
          "done": success[part],
          "next_image": images[part],
      })
      stars.append(q_star[part])
    return batches, stars

  def _eval(self, updater: BellmanUpdater, variables, eval_batches,
            eval_q_stars) -> Dict[str, float]:
    """|Q - Q*| and its square on the held-out set (one TD executable,
    reused — targets here are the analytic constants, so eval adds no
    CEM work and no extra compiled program)."""
    tds = [updater.td_errors(variables, batch, q_star)
           for batch, q_star in zip(eval_batches, eval_q_stars)]
    td = np.concatenate(tds)
    return {
        "eval_td_error": float(np.mean(td)),
        "eval_q_loss": float(np.mean(np.square(td))),
    }

  # --- shared lifecycle (host + device paths) -------------------------------

  def _start_collectors(self, policy) -> None:
    c = self.config
    if c.vector_actors:
      # The Sebulba-style actor side: one VectorActor batches every env
      # the scalar path would spread over num_collectors threads. The
      # actor list IS self._collectors — the shared shutdown/stat paths
      # (episodes, successes, errors, request_stop/join) drive either
      # worker kind unchanged.
      from tensor2robot_tpu.replay.actor import ActorFleet
      self._fleet = ActorFleet(
          policy, self.queue, c.image_size,
          total_envs=c.num_collectors * c.envs_per_collector,
          max_attempts=c.max_attempts, seed=c.seed,
          grasp_radius=c.grasp_radius,
          exploration_epsilon=c.exploration_epsilon,
          scripted_fraction=c.scripted_fraction,
          flight_recorder=self.recorder, watchdog=self.watchdog)
      self._collectors = self._fleet.actors
      self._fleet.start()
      return
    self._collectors = [
        CollectorWorker(policy, self.queue, c.image_size,
                        num_envs=c.envs_per_collector,
                        max_attempts=c.max_attempts,
                        seed=c.seed + i, grasp_radius=c.grasp_radius,
                        exploration_epsilon=c.exploration_epsilon,
                        scripted_fraction=c.scripted_fraction,
                        flight_recorder=self.recorder,
                        watchdog=self.watchdog)
        for i in range(c.num_collectors)
    ]
    for collector in self._collectors:
      collector.start()

  def _shutdown_collectors(self) -> List[BaseException]:
    """Shutdown order matters: signal EVERY collector before joining
    any (one raising stop() must not leave siblings running and
    contending for CPU); errors are returned, not raised, so the
    caller can avoid masking an in-flight exception from the loop
    body. Always closes the writer."""
    for collector in self._collectors:
      collector.request_stop()
    errors: List[BaseException] = []
    for collector in self._collectors:
      collector._thread.join(30.0)
      errors.extend(collector.errors)
    self.writer.close()
    return errors

  def _emit(self, step: int, scalars: Dict[str, float]) -> None:
    """Metrics go THROUGH the process registry (gauges), then the one
    registry→MetricWriter bridge flushes exactly this block — JSONL/TB
    records keep the pre-registry schema while the registry holds the
    same series process-wide for the obs bench."""
    self.registry.set_gauges(scalars)
    self.registry.flush_to(self.writer, step, names=scalars.keys())

  def _profile_hook(self):
    """The --profile satellite: reuse ProfilerHook's windowed capture
    (train_eval's instrument) on the replay paths. The guarded
    start_trace in utils.profiling means this and a train-side hook
    cannot double-start the profiler."""
    if not self.config.profile_window:
      return None
    from tensor2robot_tpu.utils.profiling import ProfilerHook
    start, end = self.config.profile_window
    return ProfilerHook(start_step=start, end_step=end,
                        log_dir=os.path.join(self.logdir, "profile"))

  @staticmethod
  def _profile_step(hook, step: int, final: bool = False) -> None:
    if hook is None:
      return
    shim = types.SimpleNamespace(step=step)
    if final:
      hook.end(shim)
    else:
      hook.after_step(shim, {})

  def _host_param_health(self, state) -> Dict[str, float]:
    """Params' non-finite count + global norm for the host path's
    health summary — ONE tiny AOT executable (`health_summary` in the
    ledger), compiled once at the params' fixed avals. The fused paths
    compute the same reductions INSIDE their one executable instead."""
    import jax

    from tensor2robot_tpu.obs import health as health_lib
    if self._health_exec is None:
      def param_health(params):
        return (health_lib.tree_nonfinite_count(params),
                health_lib.tree_global_norm(params))

      self._health_exec = jax.jit(param_health).lower(
          state.params).compile()
      self.compile_counts["health_summary"] = (
          self.compile_counts.get("health_summary", 0) + 1)
      self.obs_ledger.register("health_summary",
                               compiled=self._health_exec)
    start = time.perf_counter()
    nonfinite, norm = jax.device_get(self._health_exec(state.params))
    self.obs_ledger.record_dispatch("health_summary",
                                    time.perf_counter() - start)
    return {"health/nonfinite_params": float(nonfinite),
            "health/param_norm": float(norm)}

  def _observe_health(self, step: int, summary: Dict[str, float],
                      snapshot_fn=None) -> None:
    """One summary through the monitor (no-op without one). Raises
    HealthHalt under config.health_halt — the caller's loop body lets
    it propagate through the normal shutdown path."""
    if self.health_monitor is None or not summary:
      return
    self.health_monitor.observe_with_snapshot(step, summary,
                                              snapshot_fn=snapshot_fn)

  def _fused_health_summary(self, metrics: Dict[str, float]
                            ) -> Dict[str, float]:
    """The health keys out of a fused dispatch's host metrics."""
    return {key: value for key, value in metrics.items()
            if key.startswith("health/")}

  def _obs_block(self) -> Dict:
    """Per-executable device-time attribution over this run's window."""
    import jax
    return {
        "attribution": self.obs_ledger.attribution(
            wall_seconds=time.perf_counter() - self._run_started,
            device_kind=jax.devices()[0].device_kind),
        "trace_stage_counts": trace_lib.get_tracer().stage_counts(),
    }

  def _assemble_result(self, steps: int, initial_eval, eval_history,
                       ledger, param_refreshes: int, **extra) -> Dict:
    """The result schema both loop paths share (one copy: a new field
    lands on host AND device results or neither)."""
    final_eval = eval_history[-1]
    reduction = 1.0 - (final_eval["eval_td_error"]
                       / max(initial_eval["eval_td_error"], 1e-9))
    return {
        "obs": self._obs_block(),
        "health": (self.health_monitor.snapshot()
                   if self.health_monitor is not None else None),
        "steps": steps,
        "initial_eval": initial_eval,
        "final_eval": {key: v for key, v in final_eval.items()
                       if key != "step"},
        "eval_history": eval_history,
        "eval_td_reduction": round(reduction, 4),
        "compile_counts": ledger,
        "queue": self.queue.stats(),
        "buffer": self.buffer.metrics(),
        "episodes_collected": sum(c_.episodes for c_ in self._collectors),
        "env_steps_collected": sum(c_.env_steps
                                   for c_ in self._collectors),
        "vector_actors": self.config.vector_actors,
        "precision": self.config.precision,
        "collector_success_rate": (
            sum(c_.successes for c_ in self._collectors)
            / max(1, sum(c_.episodes for c_ in self._collectors))),
        "param_refreshes": param_refreshes,
        "logdir": self.logdir,
        **extra,
    }

  # --- crash-resume checkpoints (ISSUE 14) ----------------------------------

  def _checkpoint_fingerprint(self) -> Dict:
    """The shape-critical config slice a resume must match exactly —
    a drifted batch/capacity would silently change every compiled
    shape, so it refuses instead."""
    c = self.config
    return {"image_size": c.image_size, "action_size": c.action_size,
            "batch_size": c.batch_size, "capacity": c.capacity,
            "num_buffer_shards": c.num_buffer_shards,
            "prioritized": c.prioritized, "gamma": c.gamma,
            "seed": c.seed, "precision": c.precision}

  def _save_checkpoint(self, step: int, state, updater,
                       initial_eval: Dict, eval_history: List) -> None:
    """One atomic loop checkpoint: orbax TrainState first
    (synchronous), then the tmp→mv sidecar — target net, full ring
    state, label-seed counter, ingest accounting, eval history — so a
    crash between the two leaves an orphaned orbax step the resume
    validation rejects, never a half-checkpoint."""
    from tensor2robot_tpu.train import checkpoints as checkpoints_lib
    with trace_lib.span("replay/checkpoint", step=step):
      self._ckpt_manager.save(step, state, force=True)
      self._ckpt_manager.wait()
      target_vars, target_meta = updater.target_state()
      buffer_arrays, buffer_meta = self.buffer.state_dict()
      trees = {} if target_vars is None else {"target": target_vars}
      meta = {
          "fingerprint": self._checkpoint_fingerprint(),
          "target": target_meta,
          "next_label_seed": updater.next_label_seed,
          "buffer_meta": buffer_meta,
          "queue_counters": {
              key: value for key, value in self.queue.stats().items()
              if key != "pending"},
          "initial_eval": initial_eval,
          "eval_history": eval_history,
          # Geometry stamp: a resume on a different mesh must refuse
          # up front (checkpoints.validate_restore_mesh), not fail
          # deep inside a device_put against missing axes.
          "mesh": checkpoints_lib.mesh_geometry(self.trainer.mesh),
      }
      # Drift baselines ride the sidecar (ISSUE 16 satellite): without
      # them a resumed loop re-warms its EWMA state, leaving warmup
      # steps of drift BLINDNESS right after the restart — the moment
      # a half-restored run most needs the drift rules armed. Hard
      # rules carry no state and stay always-armed either way.
      if self.health_monitor is not None:
        meta["health"] = self.health_monitor.state_dict()
      checkpoints_lib.save_sidecar(
          self.checkpoint_root, step, trees=trees,
          flats={"buffer": buffer_arrays}, meta=meta)
      checkpoints_lib.prune_sidecars(self.checkpoint_root,
                                     self._ckpt_manager.all_steps())
    self.recorder.record("event", "loop_checkpoint", step=step)

  def _restore_checkpoint(self, state):
    """Restores the newest VALID checkpoint into (state, sidecar);
    returns (state, trees, meta) or None when nothing valid exists
    (then the loop starts fresh — preemption-tolerant default).
    Rejected newer steps leave ``checkpoint_rejected`` flightrec
    records via latest_resumable_step."""
    from tensor2robot_tpu.train import checkpoints as checkpoints_lib
    step = checkpoints_lib.latest_resumable_step(
        self.checkpoint_root, recorder=self.recorder)
    if step is None:
      return None
    state = self._ckpt_manager.restore(state, step=step)
    trees, flats, meta = checkpoints_lib.load_sidecar(
        self.checkpoint_root, step)
    fingerprint = self._checkpoint_fingerprint()
    if meta.get("fingerprint") != fingerprint:
      raise ValueError(
          "resume fingerprint mismatch: checkpoint was written by "
          f"{meta.get('fingerprint')}, this loop is {fingerprint} — "
          "resume needs an identically configured loop (shapes would "
          "drift otherwise)")
    if int(np.asarray(state.step)) != int(step):
      raise ValueError(
          f"restored TrainState.step {int(np.asarray(state.step))} != "
          f"checkpoint step {step}")
    checkpoints_lib.validate_restore_mesh(meta.get("mesh"),
                                          self.trainer.mesh)
    if self.health_monitor is not None and meta.get("health"):
      # Re-seat the drift baselines the save captured: the resumed
      # loop's drift rules are armed from step 1, no re-warmup window.
      self.health_monitor.load_state_dict(meta["health"])
    self.buffer.load_state_dict(flats["buffer"], meta["buffer_meta"])
    counters = meta.get("queue_counters", {})
    if counters:
      self.queue.restore_counters(**counters)
    self.recorder.record("event", "loop_resumed", step=int(step))
    return state, trees, meta

  # --- fused-path checkpoints (ISSUE 19) -----------------------------------

  def _save_fused_checkpoint(self, step: int, state, learner,
                             initial_eval: Dict,
                             eval_history: List) -> None:
    """Between-dispatch checkpoint for the donated anakin/megastep
    state — the fused paths' ONLY host seam. Every process barriers,
    then writes its shards of the whole carried composite (TrainState
    + env/ring/target device pytrees) through the orbax manager; the
    primary alone stamps the sidecar meta (host counters, fingerprint,
    mesh geometry, process count) so sidecar-present still implies
    whole-checkpoint-usable."""
    import jax
    from tensor2robot_tpu.parallel import distributed as dist_lib
    from tensor2robot_tpu.train import checkpoints as checkpoints_lib
    with trace_lib.span("replay/fused_checkpoint", step=step):
      dist_lib.sync_global_devices(f"fused_ckpt_save_{step}")
      composite = {"train_state": state, **learner.checkpoint_state()}
      self._ckpt_manager.save(step, composite, force=True)
      self._ckpt_manager.wait()
      meta = {
          "fingerprint": self._checkpoint_fingerprint(),
          "fused": learner.checkpoint_meta(),
          "initial_eval": initial_eval,
          "eval_history": eval_history,
          # Geometry + process stamps: the device composite restores
          # shard-for-shard, so a different mesh OR process count must
          # refuse up front with the fix named.
          "mesh": checkpoints_lib.mesh_geometry(self.trainer.mesh),
          "processes": jax.process_count(),
      }
      if dist_lib.is_primary():
        checkpoints_lib.save_sidecar(self.checkpoint_root, step,
                                     meta=meta)
        checkpoints_lib.prune_sidecars(self.checkpoint_root,
                                       self._ckpt_manager.all_steps())
      dist_lib.sync_global_devices(f"fused_ckpt_done_{step}")
    self.recorder.record("event", "loop_checkpoint", step=step,
                         fused=True)

  def _restore_fused_checkpoint(self, state, learner):
    """Restores the newest VALID fused checkpoint into the learner's
    carried state; returns (state, step, meta) or None when nothing
    valid exists (fresh start — the preemption-tolerant default).
    The learner's freshly initialized checkpoint_state() is the
    restore TEMPLATE: its leaves carry THIS run's shardings, so orbax
    reassembles every process's shards onto exactly the placement the
    next dispatch lowers against."""
    import jax
    from tensor2robot_tpu.train import checkpoints as checkpoints_lib
    step = checkpoints_lib.latest_resumable_step(
        self.checkpoint_root, recorder=self.recorder)
    if step is None:
      return None
    _, _, meta = checkpoints_lib.load_sidecar(self.checkpoint_root, step)
    fingerprint = self._checkpoint_fingerprint()
    if meta.get("fingerprint") != fingerprint:
      raise ValueError(
          "resume fingerprint mismatch: checkpoint was written by "
          f"{meta.get('fingerprint')}, this loop is {fingerprint} — "
          "resume needs an identically configured loop (shapes would "
          "drift otherwise)")
    checkpoints_lib.validate_restore_mesh(meta.get("mesh"),
                                          self.trainer.mesh)
    saved_procs = int(meta.get("processes", 1))
    if saved_procs != jax.process_count():
      raise ValueError(
          f"fused checkpoint step {step} was written by {saved_procs} "
          f"process(es); this run has {jax.process_count()}. The "
          "device composite restores shard-for-shard, so relaunch "
          f"with {saved_procs} processes on the same mesh geometry "
          f"{meta.get('mesh')} (or start fresh with resume=False).")
    template = {"train_state": state, **learner.checkpoint_state()}
    composite = self._ckpt_manager.restore(template, step=step)
    state = composite.pop("train_state")
    learner.restore_checkpoint_state(composite, meta["fused"])
    self.recorder.record("event", "loop_resumed", step=int(step),
                         fused=True)
    return state, int(step), meta

  # --- the loop ------------------------------------------------------------

  def run(self, num_steps: int) -> Dict:
    """Runs the closed loop for `num_steps` optimizer steps."""
    self._run_started = time.perf_counter()
    # The loop's recorder rides the process tracer only while the run
    # is live — attach here, detach in the finally, so a process that
    # constructs many loops (benches, tests) doesn't accumulate dead
    # listeners paying a callback per span forever.
    self.recorder.attach(trace_lib.get_tracer())
    # Liveness heartbeats (ISSUE 12): the learner beats once per
    # optimizer-step boundary (per dispatch on the fused paths), the
    # feeder once per drain. Registered per run, unregistered on the
    # way out — a finished loop must never read as a stalled one.
    self._learner_hb = self.watchdog.register("replay/learner")
    self._feeder_hb = self.watchdog.register("replay/feeder")
    try:
      if self.config.anakin:
        return self._run_anakin(num_steps)
      if self.config.device_resident:
        return self._run_device_resident(num_steps)
      return self._run_host(num_steps)
    except Exception as e:
      # An unhandled loop exception is a flight-recorder trigger: dump
      # the last spans/events beside the run's metrics, then re-raise.
      self.recorder.trigger("replay_loop_exception",
                            error=f"{type(e).__name__}: {e}")
      raise
    finally:
      self.watchdog.unregister(self._learner_hb)
      self.watchdog.unregister(self._feeder_hb)
      self.recorder.detach(trace_lib.get_tracer())

  def _run_host(self, num_steps: int) -> Dict:
    """The PR 2 host-path loop (threaded collectors + per-step host
    sample/label/train) — the measured fallback."""
    c = self.config
    state = self.trainer.create_train_state(batch_size=c.batch_size)
    # Crash-resume (ISSUE 14): restore the newest valid checkpoint —
    # TrainState, lagged target, full ring state, counters, eval
    # history — and continue from its exact step; nothing valid on
    # disk means a fresh start.
    start_step = 0
    resume_trees = resume_meta = None
    if c.resume and self._ckpt_manager is not None:
      loaded = self._restore_checkpoint(state)
      if loaded is not None:
        state, resume_trees, resume_meta = loaded
        start_step = int(resume_meta["step"])
    # Host snapshot feeds the collector predictor and the target net
    # (refreshed every K steps); the PER-STEP TD/eval path reads the
    # live device-resident state.variables() instead — a full D2H
    # fetch per optimizer step would stall the train pipeline for data
    # discarded on refresh_every-1 of every refresh_every steps.
    host_variables = self._host_variables(state)

    predictor = _HotReloadPredictor(self.model, host_variables)
    policy = self._make_policy(predictor)
    # The host path's ONE updater both labels (compute_targets — runs
    # at the configured scoring tier) and evaluates (td_errors — f32 on
    # every tier by the updater's precision contract).
    updater = BellmanUpdater(
        self.model, host_variables, action_size=c.action_size,
        gamma=c.gamma,
        num_samples=c.cem_num_samples, num_elites=c.cem_num_elites,
        iterations=c.cem_iterations, seed=c.seed + 13,
        polyak_tau=c.polyak_tau, ledger=self.obs_ledger,
        precision=c.precision)
    if resume_meta is not None:
      # The constructor seeded the target with the restored ONLINE
      # params; re-seat the LAGGED target plus the label-seed counter
      # so post-resume labels continue the interrupted streams.
      updater.restore_target_state(resume_trees.get("target"),
                                   resume_meta["target"])
      updater.restore_label_seed(resume_meta["next_label_seed"])

    self._start_collectors(policy)
    profile_hook = self._profile_hook()

    try:
      self._wait_for_min_fill()
      eval_batches, eval_q_stars = self._eval_transitions()
      if resume_meta is None:
        online = state.variables(use_ema=True)
        initial_eval = self._eval(updater, online, eval_batches,
                                  eval_q_stars)
        self._emit(0, {"replay/" + k: v
                       for k, v in initial_eval.items()})
        eval_history = [dict(step=0, **initial_eval)]
      else:
        # The eval series continues the interrupted run's: the
        # TD-reduction math must keep its ORIGINAL step-0 baseline,
        # not re-baseline on already-trained params.
        initial_eval = dict(resume_meta["initial_eval"])
        eval_history = [dict(entry)
                        for entry in resume_meta["eval_history"]]

      train_step = None
      final_metrics: Dict[str, float] = {}
      for step in range(start_step + 1, num_steps + 1):
        with trace_lib.span("extend/drain"):
          self.feeder.drain()
        self._feeder_hb.beat()
        batch, info = self.buffer.sample()
        targets, q_next = updater.compute_targets(batch)
        # Numeric fault seam, apply half (ISSUE 15): specs returned by
        # the previous step's perturb corrupt THIS step's labels —
        # nan_grads poisons one target (the real backward then
        # produces genuinely non-finite grads), value_scale explodes
        # them finitely. Detection is the health monitor's job below.
        if self._pending_numeric:
          targets = faults_lib.apply_numeric_to_targets(
              targets, self._pending_numeric)
          self._pending_numeric = []
        features = {"image": np.asarray(batch["image"]),
                    "action": np.asarray(batch["action"])}
        labels = {"target_q": targets}
        sharded = self.trainer.shard_batch((features, labels))
        if train_step is None:
          # AOT once at the buffer's fixed shape: any later shape drift
          # raises inside XLA's executable check instead of recompiling
          # — this plus the ledger IS the "compiles exactly once" claim.
          train_step = self.trainer.aot_train_step(
              state, *sharded,
              with_health=self.health_monitor is not None)
          self.compile_counts["train_step"] = (
              self.compile_counts.get("train_step", 0) + 1)
          self.obs_ledger.register(
              "train_step", compiled=train_step,
              shapes={"batch": c.batch_size})
        with trace_lib.span("learn/train_step"):
          dispatch_start = time.perf_counter()
          state, metrics = train_step(state, *sharded)
          self.obs_ledger.record_dispatch(
              "train_step", time.perf_counter() - dispatch_start)
        self._learner_hb.beat()
        # Valid until the NEXT train_step donates these buffers away;
        # every read below happens before that.
        online = state.variables(use_ema=True)
        td = updater.td_errors(online, batch, targets)
        self.buffer.update_priorities(info.indices, td)
        self._profile_step(profile_hook, step)

        if self.health_monitor is not None:
          # The host loop's form of the fixed summary (the fused paths
          # compute the same keys in-program): grad stats ride the
          # health-instrumented train step's metrics, param stats the
          # one-off health_summary executable, the rest is host data
          # this step already produced. q here is the Bellman
          # bootstrap Q (q_next) — the value stream whose explosion
          # the drift rule watches on this path.
          summary = {
              "health/nonfinite_grads": float(metrics["grads_nonfinite"]),
              "health/grad_norm": float(metrics["grad_norm"]),
              "health/nonfinite_targets": float(
                  np.sum(~np.isfinite(np.asarray(targets)))),
              "health/td_mean": float(np.mean(td)),
              "health/td_max": float(np.max(td)),
              "health/q_mean": float(np.mean(q_next)),
              "health/q_max": float(np.max(q_next)),
              "health/priority_entropy": float(
                  self.buffer.priority_entropy()),
              "health/sample_age": float(np.mean(info.staleness)),
              **self._host_param_health(state),
          }
          snapshot_fn = None
          if self._ckpt_manager is not None and c.checkpoint_every:
            # The auto-action: freeze the breaching state with the
            # PR 11 checkpoint machinery before any halt, so the
            # post-mortem has the exact params that went bad.
            snapshot_fn = lambda: self._save_checkpoint(  # noqa: E731
                step, state, updater, initial_eval, eval_history)
          self._observe_health(step, summary, snapshot_fn=snapshot_fn)

        if step % c.refresh_every == 0:
          # The hot-reload path: collectors and the target net pull the
          # freshest params; CEM executables are untouched (bucket-keyed).
          host_variables = self._host_variables(state)
          predictor.update(host_variables)
          updater.refresh(host_variables, step)

        if step % c.log_every == 0 or step == num_steps:
          final_metrics = {
              "replay/train_loss": float(metrics["loss"]),
              "replay/train_td_error": float(np.mean(td)),
              "replay/train_q_next": float(np.mean(q_next)),
              "replay/sample_staleness": float(np.mean(info.staleness)),
              "replay/target_lag": float(updater.target_lag(step)),
              "replay/episodes": float(
                  sum(col.episodes for col in self._collectors)),
              **self.buffer.metrics(),
              **self.feeder.metrics(),
          }
          self._emit(step, final_metrics)
          if self.health_monitor is not None:
            # The health block rides its own registry-bridged flush
            # (a separate JSONL record: the replay/ records keep their
            # pre-health schema byte-for-byte).
            self._emit(step, dict(self.health_monitor.last_summary))
        if step % c.eval_every == 0 or step == num_steps:
          with trace_lib.span("replay/eval"):
            evals = self._eval(updater, online, eval_batches,
                               eval_q_stars)
          eval_history.append(dict(step=step, **evals))
          self._emit(step, {"replay/" + k: v for k, v in evals.items()})
        if (self._ckpt_manager is not None and c.checkpoint_every
            and step % c.checkpoint_every == 0):
          self._save_checkpoint(step, state, updater, initial_eval,
                                eval_history)
        # Fault seam (ISSUE 14): a scheduled learner `crash` fires
        # HERE, between optimizer steps — after any checkpoint this
        # step owed, exactly where a preemption would land. The raise
        # propagates through run()'s flightrec wrap; collectors shut
        # down via the finally below. Numeric kinds (ISSUE 15) return
        # instead of raising and corrupt the NEXT step's targets.
        if self._faults is not None:
          self._pending_numeric.extend(
              self._faults.perturb("learner_step", site="learner",
                                   index=step))
    finally:
      self._profile_step(profile_hook, num_steps, final=True)
      collector_errors = self._shutdown_collectors()
    if collector_errors:
      raise RuntimeError(
          f"{len(collector_errors)} collector error(s); first shown"
      ) from collector_errors[0]

    ledger = dict(self.compile_counts)
    ledger.update({f"bellman_{k}" if not k.startswith("bellman") else k: v
                   for k, v in updater.compile_counts.items()})
    ledger.update({f"cem_bucket_{k}": v
                   for k, v in sorted(policy.compile_counts.items())})
    return self._assemble_result(
        num_steps, initial_eval, eval_history, ledger,
        param_refreshes=updater.refresh_count)

  def _run_device_resident(self, num_steps: int) -> Dict:
    """The Anakin-shaped loop: host feeds transitions + reads metrics;
    everything else runs inside ONE donated megastep executable.

    Per outer iteration (= `megastep_inner` optimizer steps): the
    feeder stages fresh transitions to the device ring (fixed-chunk
    extend), one megastep dispatch scans K sample→CEM-label→train→
    reprioritize iterations on device, and the host reads back scalar
    metrics. Target refresh / collector param push / eval run between
    dispatches on their step cadences (rounded to megastep
    boundaries). `num_steps` rounds UP to a whole number of megasteps
    so the compiled K never changes.
    """
    from tensor2robot_tpu.replay.device_buffer import MegastepLearner
    c = self.config
    k = c.megastep_inner
    num_outer = max(1, -(-num_steps // k))  # ceil: whole megasteps only
    state = self.trainer.create_train_state(batch_size=c.batch_size)
    host_variables = self._host_variables(state)

    predictor = _HotReloadPredictor(self.model, host_variables)
    policy = self._make_policy(predictor)
    # EVAL-ONLY updater: the megastep owns targets/TD on the hot path;
    # the eval TD-vs-analytic-Q* metric reuses the host TD executable
    # (one compile, targets executable never built on this path).
    updater = BellmanUpdater(
        self.model, host_variables, action_size=c.action_size,
        gamma=c.gamma, num_samples=c.cem_num_samples,
        num_elites=c.cem_num_elites, iterations=c.cem_iterations,
        seed=c.seed + 13, polyak_tau=c.polyak_tau,
        ledger=self.obs_ledger)
    learner = MegastepLearner(
        self.model, self.trainer, self.buffer,
        action_size=c.action_size, gamma=c.gamma,
        num_samples=c.cem_num_samples, num_elites=c.cem_num_elites,
        iterations=c.cem_iterations, inner_steps=k, seed=c.seed + 13,
        polyak_tau=c.polyak_tau, ledger=self.obs_ledger,
        precision=c.precision,
        health=self.health_monitor is not None)
    # Cold-start target = initial online copy (BellmanUpdater parity);
    # this counts as refresh 0, not a loop refresh.
    learner.refresh(host_variables, step=0)

    # Fused crash-resume (ISSUE 19): the freshly initialized learner is
    # the restore template; nothing valid on disk means a fresh start.
    resume_step, resume_meta = 0, None
    if c.resume and self._ckpt_manager is not None:
      restored = self._restore_fused_checkpoint(state, learner)
      if restored is not None:
        state, resume_step, resume_meta = restored
        host_variables = self._host_variables(state)
        predictor.update(host_variables)

    self._start_collectors(policy)
    profile_hook = self._profile_hook()

    try:
      self._wait_for_min_fill()
      eval_batches, eval_q_stars = self._eval_transitions()
      if resume_meta is not None:
        initial_eval = resume_meta.get("initial_eval") or {}
        eval_history = list(resume_meta.get("eval_history") or [])
      else:
        online = state.variables(use_ema=True)
        initial_eval = self._eval(updater, online, eval_batches,
                                  eval_q_stars)
        self._emit(0, {"replay/" + key: v
                       for key, v in initial_eval.items()})
        eval_history = [dict(step=0, **initial_eval)]
      final_metrics: Dict[str, float] = {}
      prev_step = resume_step
      for outer in range(resume_step // k + 1, num_outer + 1):
        with trace_lib.span("extend/drain"):
          self.feeder.drain()
        self._feeder_hb.beat()
        state, metrics = learner.step(state)
        self._learner_hb.beat()
        step = outer * k
        self._profile_step(profile_hook, step)
        # In-program health summaries (ISSUE 15): the fused dispatch
        # already carried them back with the metrics — one observe per
        # dispatch, covering the K scanned iterations (spike keys are
        # scan-maxed inside the program).
        self._observe_health(step, self._fused_health_summary(metrics))
        # Numeric fault seam (ISSUE 15): corruption lands on the
        # carried params between dispatches — where a preemption-era
        # memory fault would. The NEXT dispatch's in-program summary
        # must detect it.
        if self._faults is not None:
          numeric = self._faults.perturb("learner_step",
                                         site="megastep", index=step)
          if numeric:
            state = faults_lib.corrupt_train_state(state, numeric)
        # Cadences count OPTIMIZER steps: an event fires when its
        # multiple falls inside this megastep's [prev_step+1, step].
        crossed = lambda every: (step // every) > (prev_step // every)

        if crossed(c.refresh_every):
          host_variables = self._host_variables(state)
          predictor.update(host_variables)
          learner.refresh(host_variables, step)
          updater.refresh(host_variables, step)
        if crossed(c.log_every) or outer == num_outer:
          final_metrics = {
              "replay/train_loss": metrics["loss"],
              "replay/train_td_error": metrics["td_error"],
              "replay/train_q_next": metrics["q_next"],
              "replay/sample_staleness": metrics["staleness"],
              "replay/target_lag": float(learner.target_lag(step)),
              "replay/episodes": float(
                  sum(col.episodes for col in self._collectors)),
              **self.buffer.metrics(),
              **self.feeder.metrics(),
          }
          self._emit(step, final_metrics)
          if self.health_monitor is not None:
            self._emit(step, dict(self.health_monitor.last_summary))
        if crossed(c.eval_every) or outer == num_outer:
          # Valid until the NEXT megastep donates the state away.
          online = state.variables(use_ema=True)
          with trace_lib.span("replay/eval"):
            evals = self._eval(updater, online, eval_batches,
                               eval_q_stars)
          eval_history.append(dict(step=step, **evals))
          self._emit(step,
                     {"replay/" + key: v for key, v in evals.items()})
        if (self._ckpt_manager is not None and c.checkpoint_every
            and crossed(c.checkpoint_every)):
          self._save_fused_checkpoint(step, state, learner,
                                      initial_eval, eval_history)
        prev_step = step
    finally:
      self._profile_step(profile_hook, num_outer * k, final=True)
      collector_errors = self._shutdown_collectors()
    if collector_errors:
      raise RuntimeError(
          f"{len(collector_errors)} collector error(s); first shown"
      ) from collector_errors[0]

    ledger = dict(self.compile_counts)
    ledger.update(learner.compile_counts)
    ledger.update(self.buffer.compile_counts)
    ledger.update({f"bellman_{key}" if not key.startswith("bellman")
                   else key: v
                   for key, v in updater.compile_counts.items()})
    ledger.update({f"cem_bucket_{key}": v
                   for key, v in sorted(policy.compile_counts.items())})
    return self._assemble_result(
        num_outer * k, initial_eval, eval_history, ledger,
        param_refreshes=learner.refresh_count - 1,  # minus cold-start
        device_resident=True,
        megastep_inner=k)

  def _run_anakin(self, num_steps: int) -> Dict:
    """The fully fused loop: act→env-step→extend→learn inside ONE
    donated executable (replay/anakin.py) — no collector threads, no
    queue, no host-side warm-up phase (the min-fill gate is a lax.cond
    inside the program). The host dispatches, reads scalar metrics,
    and runs the refresh/log/eval cadences between dispatches; it
    stops once `num_steps` optimizer steps have actually fired
    (warm-up dispatches collect without training, so dispatch count
    adapts instead of undershooting the training budget).
    """
    from tensor2robot_tpu.replay.anakin import AnakinLoop
    from tensor2robot_tpu.research.qtopt.jax_grasping import (
        JaxGraspEnv, make_scene_bank)
    c = self.config
    total_envs = c.num_collectors * c.envs_per_collector
    state = self.trainer.create_train_state(batch_size=c.batch_size)
    host_variables = self._host_variables(state)
    # EVAL-ONLY updater (device-path convention): the fused loop owns
    # targets/TD; this only compiles the one TD-vs-analytic-Q* metric.
    updater = BellmanUpdater(
        self.model, host_variables, action_size=c.action_size,
        gamma=c.gamma, num_samples=c.cem_num_samples,
        num_elites=c.cem_num_elites, iterations=c.cem_iterations,
        seed=c.seed + 13, polyak_tau=c.polyak_tau,
        ledger=self.obs_ledger)
    # Scene bank: the ONE-TIME host render (the oracle's own code);
    # after this the host never touches a scene again.
    bank = make_scene_bank(c.anakin_bank_scenes,
                           image_size=c.image_size, base_seed=c.seed)
    env = JaxGraspEnv(total_envs, image_size=c.image_size,
                      max_attempts=c.max_attempts,
                      radius=c.grasp_radius, bank=bank)
    loop = AnakinLoop(
        self.model, self.trainer, self.buffer, env,
        action_size=c.action_size, gamma=c.gamma,
        num_samples=c.cem_num_samples, num_elites=c.cem_num_elites,
        iterations=c.cem_iterations, inner_steps=c.anakin_inner,
        train_every=c.anakin_train_every, min_fill=c.min_fill,
        exploration_epsilon=c.exploration_epsilon,
        scripted_fraction=c.scripted_fraction, seed=c.seed + 13,
        polyak_tau=c.polyak_tau, ledger=self.obs_ledger,
        precision=c.precision,
        health=self.health_monitor is not None)
    loop.refresh(host_variables, step=0)
    profile_hook = self._profile_hook()

    # Fused crash-resume (ISSUE 19): the freshly initialized loop is
    # the restore template (its checkpoint_state() leaves carry this
    # run's shardings); nothing valid on disk means a fresh start.
    resume_step, resume_meta = 0, None
    if c.resume and self._ckpt_manager is not None:
      restored = self._restore_fused_checkpoint(state, loop)
      if restored is not None:
        state, resume_step, resume_meta = restored

    eval_batches, eval_q_stars = self._eval_transitions()
    if resume_meta is not None:
      initial_eval = resume_meta.get("initial_eval") or {}
      eval_history = list(resume_meta.get("eval_history") or [])
    else:
      initial_eval = self._eval(updater, state.variables(use_ema=True),
                                eval_batches, eval_q_stars)
      self._emit(0, {"replay/" + key: v
                     for key, v in initial_eval.items()})
      eval_history = [dict(step=0, **initial_eval)]
    prev_step = resume_step
    # Dispatch bound: warm-up (min-fill at total_envs per control
    # step) plus the training budget, doubled — a failure to progress
    # raises instead of spinning.
    steps_per_dispatch = c.anakin_inner // c.anakin_train_every
    max_dispatches = 2 * (
        -(-c.min_fill // (total_envs * c.anakin_inner))
        + -(-num_steps // steps_per_dispatch)) + 2
    dispatches = 0
    try:
      while loop.trained_steps < num_steps:
        if dispatches >= max_dispatches:
          raise RuntimeError(
              f"anakin loop stalled: {loop.trained_steps} optimizer "
              f"steps after {dispatches} dispatches "
              f"(min_fill={c.min_fill}, buffer size={self.buffer.size})")
        state, metrics = loop.step(state)
        self._learner_hb.beat()
        dispatches += 1
        step = loop.trained_steps
        self._profile_step(profile_hook, step)
        # In-program health summaries (ISSUE 15): observed only when
        # the dispatch actually trained (a warm-up dispatch's summary
        # is the zero placeholder, not evidence).
        if metrics.get("trained_steps"):
          self._observe_health(step,
                               self._fused_health_summary(metrics))
        # Numeric fault seam (ISSUE 15): between-dispatch param
        # corruption, same placement as the megastep path's.
        if self._faults is not None:
          numeric = self._faults.perturb("learner_step", site="anakin",
                                         index=step)
          if numeric:
            state = faults_lib.corrupt_train_state(state, numeric)
        crossed = lambda every: (step // every) > (prev_step // every)
        done = step >= num_steps

        if crossed(c.refresh_every):
          host_variables = self._host_variables(state)
          loop.refresh(host_variables, step)
          updater.refresh(host_variables, step)
        if (crossed(c.log_every) or done) and metrics["trained_steps"]:
          self._emit(step, {
              "replay/train_loss": metrics["loss"],
              "replay/train_td_error": metrics["td_error"],
              "replay/train_q_next": metrics["q_next"],
              "replay/sample_staleness": metrics["staleness"],
              "replay/target_lag": float(loop.target_lag(step)),
              "replay/episodes": float(loop.episodes),
              "replay/env_steps": float(loop.env_steps),
              **self.buffer.metrics(),
          })
          if self.health_monitor is not None:
            self._emit(step, dict(self.health_monitor.last_summary))
        if crossed(c.eval_every) or done:
          # Valid until the NEXT dispatch donates the state away.
          online = state.variables(use_ema=True)
          with trace_lib.span("replay/eval"):
            evals = self._eval(updater, online, eval_batches,
                               eval_q_stars)
          eval_history.append(dict(step=step, **evals))
          self._emit(step,
                     {"replay/" + key: v for key, v in evals.items()})
        if (self._ckpt_manager is not None and c.checkpoint_every
            and crossed(c.checkpoint_every)):
          self._save_fused_checkpoint(step, state, loop,
                                      initial_eval, eval_history)
        prev_step = step
    finally:
      self._profile_step(profile_hook, loop.trained_steps, final=True)
      self.writer.close()

    ledger = dict(self.compile_counts)
    ledger.update(loop.compile_counts)
    ledger.update(self.buffer.compile_counts)
    ledger.update({f"bellman_{key}" if not key.startswith("bellman")
                   else key: v
                   for key, v in updater.compile_counts.items()})
    return self._assemble_result(
        loop.trained_steps, initial_eval, eval_history, ledger,
        param_refreshes=loop.refresh_count - 1,  # minus cold-start
        device_resident=True,
        param_sharding=_param_sharding_summary(state.params),
        anakin=True,
        anakin_inner=c.anakin_inner,
        anakin_train_every=c.anakin_train_every,
        mesh_shape=loop.mesh_shape,
        zero1=self.trainer.shards_optimizer_state,
        episodes_collected=loop.episodes,
        env_steps_collected=loop.env_steps,
        collector_success_rate=(loop.successes
                                / max(1, loop.episodes)))

  def _wait_for_min_fill(self) -> None:
    """Gates the first optimizer step on buffer warm-up (min-fill),
    polling with the shared jittered backoff (utils/backoff.py) — and
    on timeout raises a PollTimeout that NAMES the gate and the fill
    it reached, instead of the old anonymous fixed-cadence spin."""
    from tensor2robot_tpu.utils import backoff

    def ready():
      self.feeder.drain()
      self._feeder_hb.beat()
      for collector in self._collectors:
        if collector.errors:
          raise RuntimeError("collector died during warm-up") from (
              collector.errors[0])
      return self.feeder.ready()

    try:
      backoff.poll_with_backoff(
          ready, self.config.min_fill_timeout_s,
          initial_s=0.02, max_s=0.25, seed=self.config.seed,
          description=(f"replay buffer min_fill="
                       f"{self.config.min_fill} under {self.logdir}"),
          raise_on_timeout=True)
    except backoff.PollTimeout as e:
      raise backoff.PollTimeout(
          f"{e.description} (reached size={self.buffer.size})",
          e.waited_s, e.attempts) from None

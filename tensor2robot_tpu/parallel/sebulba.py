"""Sebulba tier (ISSUE 20): decoupled actor PROCESSES feeding the
sharded learner with overlapped device ingest.

The Podracer paper (PAPERS.md, arXiv:2104.06272) names two TPU-native
architectures. Anakin — acting fused INTO the learn executable — landed
in PR 5/6 and scaled to the multi-controller mesh in PR 19, but it only
serves envs that compile. This module is the complement: Sebulba's
decoupled split, where N actor PROCESSES (each owning its own JAX
runtime and ONE acting executable pinned to its device slice) stream
fixed-shape transition chunks to a separate learner process whose
device ring and megastep stay the PR 3/16 sharded executables. Any env
that can step under numpy — pose_env, vrgripper, a real robot bridge —
can live in an actor process without ever entering XLA.

The wire is a filesystem chunk spool, deliberately dumb and inspectable:

  workdir/spool/actor<i>/chunk-<seq>.npz   fixed-shape transition chunks
                                           (atomic tmp -> rename, dense
                                           seq numbers — a gap means
                                           "not landed yet", never loss)
  workdir/spool/actor<i>/heartbeat.json    liveness ticks (advances on
                                           every chunk AND while the
                                           actor is backpressure-stalled,
                                           so "slow" never reads as
                                           "dead")
  workdir/spool/acks.json                  learner's consumed seq per
                                           actor — the bounded-backlog
                                           backpressure signal actors
                                           poll (the TransitionQueue
                                           drop-oldest policy's
                                           cross-process face)
  workdir/params/params-<v>.npz            learner-published variables;
                                           actors hot-reload through the
                                           `_HotReloadPredictor` contract
                                           (never recompiles acting)

Learner-side dataflow (all inside the learner process):

  SpoolReader.poll -> TransitionQueue.put_batch      (ingest thread)
  queue.drain_batch -> prefetch_to_device            (learner thread —
      the data/prefetch double-buffer: `depth` async device_put
      transfers in flight, so H2D DMA of chunk k+1..k+depth overlaps
      the megastep crunching chunk k's batch)
  -> DeviceReplayBuffer.extend_device_chunk          (ONE fixed-shape
      extend executable; chunks are already device-resident)
  -> MegastepLearner.step every `chunks_per_megastep` chunks.

Determinism contract (the bit-identity bar of
tests/test_sebulba.py::TestSebulbaLiveOracleParity): the learner
consumes chunks in QUEUE order and runs one megastep per fixed chunk
count, so its param evolution is a pure function of the arrival
manifest — the recorded `(actor, seq)` ingestion order. Replaying the
manifest against the spooled chunk files in ONE serial process (the
oracle, `_run_oracle`) reproduces the live learner's params bit for
bit; all the asynchrony lives in PRODUCTION, never in consumption.

Actor death is a handled regime, not an error path: the learner-side
watchdog (PR 9) holds one heartbeat per actor (armed on the actor's
first signal, beaten on every chunk/tick), and `ActorSupervisor` maps
stalls onto the PR 11 CircuitBreaker state machine — stall ->
record_failure -> open (QUARANTINE, the dead process is reaped) ->
quarantine window elapses -> allows() claims the half-open PROBE (the
actor is respawned continuing its seq numbering) -> first fresh chunk
-> record_success -> closed (REINSTATE). The learner keeps training on
the surviving stream throughout: every shape is fixed, so the megastep
ledger stays exactly-once across the whole outage.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

SCHEMA = "t2r-sebulba-1"
_WORKER_FLAG = "--worker"

# The loop's canonical transition keys (replay.ingest.TRANSITION_KEYS,
# restated locally so synthetic actor processes never import the jax
# chain before their backend env is settled).
CHUNK_KEYS = ("image", "action", "reward", "done", "next_image")

STOP_FILE = "STOP"
ACKS_FILE = "acks.json"
DONE_FILE = "DONE.json"
HEARTBEAT_FILE = "heartbeat.json"


def _repo_root() -> str:
  return os.path.dirname(os.path.dirname(
      os.path.dirname(os.path.abspath(__file__))))


def _atomic_write_json(path: str, payload: dict) -> None:
  tmp = path + ".tmp"
  with open(tmp, "w") as f:
    json.dump(payload, f)
  os.replace(tmp, path)


def _read_json(path: str) -> Optional[dict]:
  """Best-effort read: a missing file returns None (json files here are
  written atomically, so partial reads cannot happen)."""
  try:
    with open(path) as f:
      return json.load(f)
  except (FileNotFoundError, json.JSONDecodeError):
    return None


def actor_dir(spool_dir: str, actor_id: int) -> str:
  return os.path.join(spool_dir, f"actor{actor_id}")


def chunk_path(spool_dir: str, actor_id: int, seq: int) -> str:
  return os.path.join(actor_dir(spool_dir, actor_id),
                      f"chunk-{seq:08d}.npz")


# --- transport: actor-side producer ----------------------------------------


class ChunkWriter:
  """Actor-side spool producer: one fixed-shape chunk file per call.

  Duck-types ``TransitionQueue.put_batch`` so a stock ``VectorActor``
  drives the cross-process wire unchanged — its one put per lockstep
  control step becomes one atomically-landed npz file with a dense
  sequence number. Ownership semantics are STRICTER than the in-memory
  queue's zero-copy hand-through (the arrays are serialized on the
  spot), so the queue's "fresh arrays per put" producer rule is
  automatically satisfied.
  """

  def __init__(self, spool_dir: str, actor_id: int, start_seq: int = 0):
    self.spool_dir = spool_dir
    self.actor_id = actor_id
    self.seq = int(start_seq)
    self._tick = 0
    self.dir = actor_dir(spool_dir, actor_id)
    os.makedirs(self.dir, exist_ok=True)

  def put_batch(self, batch, provenance: str = "actor") -> int:
    del provenance  # the reader derives provenance from the directory
    chunk = {key: np.asarray(value) for key, value in batch.items()}
    sizes = {value.shape[0] for value in chunk.values()}
    if len(sizes) != 1:
      raise ValueError(f"inconsistent chunk leading dims: {sizes}")
    n = sizes.pop()
    path = chunk_path(self.spool_dir, self.actor_id, self.seq)
    tmp = os.path.join(self.dir, f".tmp-{self.seq:08d}.npz")
    with open(tmp, "wb") as f:
      np.savez(f, **chunk)
    os.replace(tmp, path)
    self.seq += 1
    self.write_heartbeat()
    return n

  def write_heartbeat(self) -> None:
    """Liveness tick: advances on every chunk AND during backpressure
    stalls, so the learner's watchdog can tell slow from dead."""
    self._tick += 1
    _atomic_write_json(os.path.join(self.dir, HEARTBEAT_FILE), {
        "seq": self.seq,
        "tick": self._tick,
        "wall_time": time.time(),
        "pid": os.getpid(),
    })

  def finish(self) -> None:
    _atomic_write_json(os.path.join(self.dir, DONE_FILE),
                       {"final_seq": self.seq})


# --- transport: learner-side tail ------------------------------------------


class SpoolReader:
  """Learner-side tail over the per-actor chunk streams.

  ``poll()`` returns every newly-landed chunk in dense per-actor seq
  order (a gap means "still being written", so the reader waits — the
  atomic rename guarantees a visible file is whole). ``write_acks``
  publishes the consumed frontier, which is the actors' backpressure
  signal.
  """

  def __init__(self, spool_dir: str, num_actors: int):
    self.spool_dir = spool_dir
    self.num_actors = num_actors
    self.next_seq: Dict[int, int] = {i: 0 for i in range(num_actors)}
    for i in range(num_actors):
      os.makedirs(actor_dir(spool_dir, i), exist_ok=True)

  def poll(self, max_per_actor: int = 32
           ) -> List[Tuple[int, int, Dict[str, np.ndarray]]]:
    out: List[Tuple[int, int, Dict[str, np.ndarray]]] = []
    for actor in range(self.num_actors):
      for _ in range(max_per_actor):
        seq = self.next_seq[actor]
        path = chunk_path(self.spool_dir, actor, seq)
        if not os.path.exists(path):
          break
        with np.load(path) as archive:
          chunk = {key: archive[key] for key in archive.files}
        out.append((actor, seq, chunk))
        self.next_seq[actor] = seq + 1
    return out

  def heartbeat(self, actor: int) -> Optional[dict]:
    return _read_json(os.path.join(actor_dir(self.spool_dir, actor),
                                   HEARTBEAT_FILE))

  def last_landed_seq(self, actor: int) -> int:
    """Highest chunk seq on disk + 1 (where a respawned actor must
    continue so the probe never overwrites landed experience)."""
    directory = actor_dir(self.spool_dir, actor)
    seqs = [int(name[len("chunk-"):-len(".npz")])
            for name in os.listdir(directory)
            if name.startswith("chunk-") and name.endswith(".npz")]
    return (max(seqs) + 1) if seqs else 0

  def write_acks(self) -> None:
    _atomic_write_json(
        os.path.join(self.spool_dir, ACKS_FILE),
        {str(actor): seq for actor, seq in self.next_seq.items()})


def load_chunk(spool_dir: str, actor_id: int, seq: int
               ) -> Dict[str, np.ndarray]:
  with np.load(chunk_path(spool_dir, actor_id, seq)) as archive:
    return {key: archive[key] for key in archive.files}


# --- params export/hot-reload (learner -> actors) --------------------------


def _params_path(params_dir: str, version: int) -> str:
  return os.path.join(params_dir, f"params-{version:06d}.npz")


def publish_params(params_dir: str, version: int, variables) -> str:
  """Atomically lands one versioned variables snapshot (tmp -> rename,
  the export_utils.publish discipline at npz granularity)."""
  from tensor2robot_tpu.export import variables_io
  os.makedirs(params_dir, exist_ok=True)
  path = _params_path(params_dir, version)
  tmp = os.path.join(params_dir, f".tmp-{version:06d}.npz")
  variables_io.save_variables(tmp, variables)
  os.replace(tmp, path)
  return path


def latest_params_version(params_dir: str) -> Optional[int]:
  try:
    names = os.listdir(params_dir)
  except FileNotFoundError:
    return None
  versions = [int(name[len("params-"):-len(".npz")]) for name in names
              if name.startswith("params-") and name.endswith(".npz")]
  return max(versions) if versions else None


def _wait_for_params(params_dir: str, timeout_s: float = 180.0):
  from tensor2robot_tpu.export import variables_io
  deadline = time.monotonic() + timeout_s
  while time.monotonic() < deadline:
    version = latest_params_version(params_dir)
    if version is not None:
      return version, variables_io.load_variables(
          _params_path(params_dir, version))
    time.sleep(0.05)
  raise TimeoutError(
      f"no params landed under {params_dir} within {timeout_s}s")


# --- the actor process worker ----------------------------------------------


def _synthetic_actor(spec: Dict, writer: ChunkWriter):
  """Chunk producer with NO jax dependency: random fixed-shape
  transitions at a configurable cadence. The supervisor/watchdog/crash
  tests use this mode so the quarantine protocol is provable in
  seconds (process startup is a numpy import, not a JAX runtime)."""
  rng = np.random.default_rng(spec["seed"] + 11 * spec["actor_id"])
  n = spec["num_envs"]
  size = spec["image_size"]
  action_size = spec["action_size"]
  sleep_s = spec.get("step_sleep_s", 0.01)
  busy = {"s": 0.0}

  def step() -> None:
    begin = time.perf_counter()
    image = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
    writer.put_batch({
        "image": image,
        "action": rng.uniform(-1.0, 1.0,
                              (n, action_size)).astype(np.float32),
        "reward": (rng.random(n) < 0.3).astype(np.float32),
        "done": (rng.random(n) < 0.2).astype(np.float32),
        "next_image": image,
    })
    # The sleep counts as busy on purpose: it stands in for env/policy
    # latency, which is exactly what the overlap instrument measures.
    if sleep_s:
      time.sleep(sleep_s)
    busy["s"] += time.perf_counter() - begin

  return step, lambda: {"mode": "synthetic",
                        "busy_seconds": round(busy["s"], 3)}


def _cem_actor(spec: Dict, writer: ChunkWriter):
  """The real acting half: ONE CEM bucket executable pinned to this
  process's device, a stock VectorActor driven thread-free (the
  PROCESS is the actor loop), params hot-reloaded from the learner's
  export dir through the never-recompile predictor contract."""
  import optax

  from tensor2robot_tpu.export import variables_io
  from tensor2robot_tpu.replay.actor import VectorActor
  from tensor2robot_tpu.replay.loop import _HotReloadPredictor
  from tensor2robot_tpu.replay.smoke import TinyQCriticModel
  from tensor2robot_tpu.serving.bucketing import BucketLadder
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy

  model = TinyQCriticModel(
      image_size=spec["image_size"], action_size=spec["action_size"],
      optimizer_fn=lambda: optax.adam(1e-3))
  version, variables = _wait_for_params(
      spec["params_dir"], timeout_s=spec.get("params_timeout_s", 180.0))
  predictor = _HotReloadPredictor(model, variables)
  policy = CEMFleetPolicy(
      predictor, action_size=spec["action_size"],
      num_samples=spec["cem_num_samples"],
      num_elites=spec["cem_num_elites"],
      iterations=spec["cem_iterations"], seed=spec["seed"] + 7,
      ladder=BucketLadder((spec["num_envs"],)))
  actor = VectorActor(
      policy, writer, spec["image_size"], num_envs=spec["num_envs"],
      max_attempts=spec.get("max_attempts", 3), seed=spec["seed"],
      grasp_radius=spec.get("grasp_radius", 0.4))
  # Thread-free drive: replicate start()'s reset, then call step_once
  # directly from the process main loop (the VectorActor thread stays
  # unstarted; step_once owns the busy accounting since ISSUE 20).
  actor._env.reset([actor._scene_seed()
                    for _ in range(actor.num_envs)])
  state = {"version": version, "reloads": 0, "steps": 0}
  reload_every = spec.get("reload_every", 4)

  def step() -> None:
    actor.step_once()
    state["steps"] += 1
    if reload_every and state["steps"] % reload_every == 0:
      latest = latest_params_version(spec["params_dir"])
      if latest is not None and latest > state["version"]:
        predictor.update(variables_io.load_variables(
            _params_path(spec["params_dir"], latest)))
        state["version"] = latest
        state["reloads"] += 1

  def summary() -> Dict:
    from tensor2robot_tpu.utils.device_info import device_summary
    return {
        "mode": "cem",
        **device_summary(),  # the device THIS actor process acted on
        "env_steps": actor.env_steps,
        "episodes": actor.episodes,
        "successes": actor.successes,
        "busy_seconds": round(actor.busy_seconds, 3),
        "params_version": state["version"],
        "param_reloads": state["reloads"],
        "compile_counts": {f"cem_bucket_{k}": v for k, v in
                           sorted(policy.compile_counts.items())},
    }

  return step, summary


def _run_actor(spec: Dict) -> None:
  """Actor process main: produce chunks under bounded backpressure
  until STOP (or the chunk cap, or the armed crash protocol fires)."""
  actor_id = spec["actor_id"]
  writer = ChunkWriter(spec["spool_dir"], actor_id,
                       start_seq=spec.get("start_seq", 0))
  stop_path = os.path.join(spec["spool_dir"], STOP_FILE)
  acks_path = os.path.join(spec["spool_dir"], ACKS_FILE)
  max_backlog = spec.get("max_backlog", 8)
  die_after = spec.get("die_after_chunks")
  max_chunks = spec.get("max_chunks", 10 ** 6)
  if spec.get("synthetic"):
    step_fn, summary_fn = _synthetic_actor(spec, writer)
  else:
    step_fn, summary_fn = _cem_actor(spec, writer)
  written = 0
  stall_s = 0.0
  while written < max_chunks and not os.path.exists(stop_path):
    # Bounded backpressure: never run more than max_backlog chunks
    # ahead of the learner's ack frontier. Heartbeats keep ticking
    # through the stall — slow consumption must not read as death.
    while not os.path.exists(stop_path):
      acks = _read_json(acks_path) or {}
      if writer.seq - int(acks.get(str(actor_id), 0)) < max_backlog:
        break
      writer.write_heartbeat()
      time.sleep(0.02)
      stall_s += 0.02
    if os.path.exists(stop_path):
      break
    step_fn()
    written += 1
    if die_after is not None and written >= die_after:
      # Crash protocol (the kill-one-actor phase): die silently with a
      # distinctive rc — no DONE marker, no result line, exactly what
      # a preempted/OOM-killed actor looks like to the learner.
      print(f"ACTOR{actor_id}_KILLED seq={writer.seq}", flush=True)
      os._exit(3)
  writer.finish()
  summary = {
      "actor_id": actor_id,
      "pid": os.getpid(),
      "chunks": written,
      "start_seq": spec.get("start_seq", 0),
      "final_seq": writer.seq,
      "backpressure_stall_s": round(stall_s, 3),
      **summary_fn(),
  }
  obs_logdir = spec.get("obs_logdir")
  if obs_logdir:
    # The PR 19 fleet-observability transport: each actor process
    # exports its registry snapshot under its own host label, and the
    # learner-side aggregate merges them into ONE fleet view (same
    # read side the multi-controller mesh uses).
    from tensor2robot_tpu.obs.registry import get_registry
    registry = get_registry()
    registry.gauge("sebulba_actor/chunks").set(written)
    registry.gauge("sebulba_actor/busy_s").set(
        summary.get("busy_seconds", 0.0))
    registry.gauge("sebulba_actor/backpressure_stall_s").set(
        round(stall_s, 3))
    registry.export_snapshot(
        os.path.join(obs_logdir,
                     f"registry-actor{actor_id}-{os.getpid()}.json"),
        host=f"actor{actor_id}")
  print(f"ACTOR{actor_id}_RESULT " + json.dumps(summary), flush=True)
  print(f"ACTOR{actor_id}_OK", flush=True)


# --- supervisor: quarantine -> probe -> reinstate over processes -----------


class ActorSupervisor:
  """Actor-process lifecycle + the PR 11 breaker regime for actors.

  One learner-side Heartbeat per actor (armed busy on the actor's
  FIRST observed signal so a slow JAX bring-up is idle, not stalled;
  beaten on every chunk arrival and heartbeat tick) and one
  CircuitBreaker per actor (failure_threshold=1 — a watchdog stall IS
  the failure evidence). ``check()`` drives watchdog detection and the
  breaker transitions; the owner calls ``observe()`` from its ingest
  loop with each poll's arrivals.
  """

  def __init__(self, spool_dir: str, specs: List[Dict],
               envs: Dict[int, Dict[str, str]],
               watchdog=None, recorder=None, registry=None,
               deadline_s: float = 1.0, quarantine_s: float = 0.75,
               max_respawns: int = 2):
    from tensor2robot_tpu.obs import flight_recorder as flight_lib
    from tensor2robot_tpu.obs import registry as registry_lib
    from tensor2robot_tpu.obs import watchdog as watchdog_lib
    from tensor2robot_tpu.serving.slo import CircuitBreaker
    self.spool_dir = spool_dir
    self._specs = {spec["actor_id"]: dict(spec) for spec in specs}
    # One environment PER actor, fixed before spawn: it decides which
    # device the child's runtime may open (a respawn gets the same one).
    self._envs = envs
    self._recorder = recorder or flight_lib.get_recorder()
    self._registry = registry or registry_lib.get_registry()
    self._watchdog = watchdog or watchdog_lib.Watchdog(
        poll_s=0.2, recorder=self._recorder, registry=self._registry)
    self._deadline_s = watchdog_lib.scaled_deadline(deadline_s)
    self._quarantine_s = quarantine_s
    self._max_respawns = max_respawns
    self._breakers = {actor_id: CircuitBreaker(
        failure_threshold=1, quarantine_s=quarantine_s)
        for actor_id in self._specs}
    self._heartbeats: Dict[int, object] = {}
    self._armed: Dict[int, bool] = {}
    self._last_tick: Dict[int, int] = {}
    self._procs: Dict[int, subprocess.Popen] = {}
    self._outputs: Dict[int, List[str]] = {
        actor_id: [] for actor_id in self._specs}
    self.respawns: Dict[int, int] = {
        actor_id: 0 for actor_id in self._specs}
    self.timeline: List[dict] = []
    self.watchdog_events: List[dict] = []
    self._epoch = time.monotonic()

  # -- lifecycle -----------------------------------------------------------

  def _event(self, event: str, actor_id: int, **fields) -> None:
    entry = {"event": event, "actor": actor_id,
             "t_s": round(time.monotonic() - self._epoch, 3), **fields}
    self.timeline.append(entry)
    self._recorder.record("sebulba", event, actor=actor_id, **fields)

  def _spawn(self, actor_id: int, start_seq: int) -> None:
    spec = dict(self._specs[actor_id], start_seq=start_seq)
    self._procs[actor_id] = subprocess.Popen(
        [sys.executable, "-m", "tensor2robot_tpu.parallel.sebulba",
         _WORKER_FLAG, json.dumps(spec)],
        env=self._envs[actor_id], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)

  def start(self) -> None:
    for actor_id in sorted(self._specs):
      heartbeat = self._watchdog.register(
          f"sebulba/actor{actor_id}", deadline_s=self._deadline_s)
      self._heartbeats[actor_id] = heartbeat
      self._armed[actor_id] = False
      self._last_tick[actor_id] = -1
      self._spawn(actor_id, start_seq=0)
      self._event("spawn", actor_id, pid=self._procs[actor_id].pid)

  def _reap(self, actor_id: int) -> Optional[int]:
    """Collects a finished process's output; returns its rc (None if
    still running — a stalled-but-alive actor is killed first: a
    quarantined actor must not keep producing)."""
    proc = self._procs.get(actor_id)
    if proc is None:
      return None
    if proc.poll() is None:
      proc.kill()
    out, _ = proc.communicate()
    if out:
      self._outputs[actor_id].append(out)
    del self._procs[actor_id]
    return proc.returncode

  # -- detection + state machine -------------------------------------------

  def observe(self, arrivals, reader: SpoolReader) -> None:
    """Feeds liveness evidence from one ingest poll: chunk arrivals
    and heartbeat-file ticks each beat the actor's heartbeat; a chunk
    from a non-closed breaker is the probe verdict (reinstate)."""
    fresh = {actor for actor, _, _ in arrivals}
    for actor_id, heartbeat in self._heartbeats.items():
      signal = actor_id in fresh
      record = reader.heartbeat(actor_id)
      if record is not None:
        tick = int(record.get("tick", 0))
        if tick != self._last_tick[actor_id]:
          self._last_tick[actor_id] = tick
          signal = True
      if not signal:
        continue
      if not self._armed[actor_id]:
        heartbeat.busy()
        self._armed[actor_id] = True
      heartbeat.beat()
      breaker = self._breakers[actor_id]
      if actor_id in fresh and breaker.state != "closed":
        # Fresh experience from the probed actor: conclusive health
        # evidence — the breaker closes and the actor is reinstated.
        breaker.record_success()
        if breaker.state == "closed":
          self._event("reinstate", actor_id,
                      respawns=self.respawns[actor_id])

  def check(self, reader: SpoolReader) -> List[dict]:
    """One supervision pass: watchdog stalls -> quarantine; elapsed
    quarantine windows -> claim the half-open probe and respawn."""
    new_events = self._watchdog.check_once()
    self.watchdog_events.extend(new_events)
    for event in new_events:
      name = event["component"]
      if (event["event"] != "watchdog_stall"
          or not name.startswith("sebulba/actor")):
        continue
      actor_id = int(name[len("sebulba/actor"):].split("#")[0])
      breaker = self._breakers[actor_id]
      breaker.record_failure()
      if breaker.state == "open":
        rc = self._reap(actor_id)
        self._event("quarantine", actor_id, rc=rc,
                    stalled_for_s=event["stalled_for_s"])
        self._recorder.trigger("sebulba_actor_quarantined",
                               actor=actor_id, rc=rc)
    for actor_id, breaker in self._breakers.items():
      if breaker.state != "open":
        continue
      if self.respawns[actor_id] >= self._max_respawns:
        continue
      if breaker.allows():  # claims the single half-open probe slot
        # The injected crash (die_after_chunks) is one-shot: the probe
        # incarnation must be healthy or reinstatement is unprovable.
        self._specs[actor_id].pop("die_after_chunks", None)
        start_seq = reader.last_landed_seq(actor_id)
        # Fresh heartbeat for the probe incarnation: the stalled entry
        # must not carry its stale clock into the new process.
        self._watchdog.unregister(self._heartbeats[actor_id])
        self._heartbeats[actor_id] = self._watchdog.register(
            f"sebulba/actor{actor_id}", deadline_s=self._deadline_s)
        self._armed[actor_id] = False
        # The dead incarnation's heartbeat file survives on disk; seed
        # the tick cursor with it so only the PROBE's own signal (a new
        # tick or a fresh chunk) arms stall detection — the probe gets
        # the same unbounded bring-up window as the initial spawn
        # instead of inheriting a deadline armed off stale evidence.
        stale = reader.heartbeat(actor_id)
        self._last_tick[actor_id] = (
            int(stale.get("tick", 0)) if stale else -1)
        self.respawns[actor_id] += 1
        self._spawn(actor_id, start_seq=start_seq)
        self._event("probe", actor_id, start_seq=start_seq,
                    pid=self._procs[actor_id].pid)
    return new_events

  # -- shutdown + results --------------------------------------------------

  def stop(self, timeout_s: float = 60.0) -> None:
    _atomic_write_json(os.path.join(self.spool_dir, STOP_FILE),
                       {"stopped_at": time.time()})
    deadline = time.monotonic() + timeout_s
    for actor_id, proc in list(self._procs.items()):
      remaining = max(0.1, deadline - time.monotonic())
      try:
        out, _ = proc.communicate(timeout=remaining)
      except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
      if out:
        self._outputs[actor_id].append(out)
      del self._procs[actor_id]
    for heartbeat in self._heartbeats.values():
      self._watchdog.unregister(heartbeat)

  def breaker_events(self) -> Dict[int, List[dict]]:
    return {actor_id: list(breaker.events)
            for actor_id, breaker in self._breakers.items()}

  def results(self) -> Dict[int, Optional[dict]]:
    """Each actor's LAST incarnation's parsed result line (None when
    that incarnation died resultless — the killed-actor case)."""
    parsed: Dict[int, Optional[dict]] = {}
    for actor_id, outputs in self._outputs.items():
      marker = f"ACTOR{actor_id}_RESULT "
      result = None
      for out in outputs:
        for line in out.splitlines():
          if line.startswith(marker):
            result = json.loads(line[len(marker):])
      parsed[actor_id] = result
    return parsed

  def raw_output(self, actor_id: int) -> str:
    return "\n".join(self._outputs.get(actor_id, []))


# --- the learner half ------------------------------------------------------


@dataclass
class SebulbaConfig:
  """One config drives the live run AND the serial oracle replay (the
  bit-identity bar depends on both halves building identical learner
  stacks — same seeds, same shapes, same megastep cadence)."""
  image_size: int = 8
  action_size: int = 4
  seed: int = 0
  num_actors: int = 2
  envs_per_actor: int = 16  # chunk rows == the device ring's ingest quantum
  capacity: int = 512
  batch_size: int = 32
  inner_steps: int = 4  # K optimizer steps per megastep dispatch
  chunks_per_megastep: int = 4
  num_megasteps: int = 6
  mesh_devices: int = 2  # the sharded learner's capacity/data axis
  gamma: float = 0.8
  learning_rate: float = 3e-3
  cem_num_samples: int = 16
  cem_num_elites: int = 4
  cem_iterations: int = 2
  queue_capacity: int = 1024
  prefetch_depth: int = 2
  publish_every: int = 2  # megasteps between param exports to actors
  target_refresh_every: int = 2
  actor_deadline_s: float = 1.0
  quarantine_s: float = 0.75
  max_backlog: int = 8
  actor_max_chunks: int = 4096
  synthetic_actors: bool = False
  actor_step_sleep_s: float = 0.0

  def to_json(self) -> Dict:
    return dataclasses.asdict(self)

  @classmethod
  def from_json(cls, payload: Dict) -> "SebulbaConfig":
    return cls(**payload)


class SebulbaLearner:
  """The learner process's device half: sharded ring + megastep,
  fed device-resident chunks through the prefetch seam."""

  def __init__(self, config: SebulbaConfig, workdir: str,
               registry=None, recorder=None):
    import jax
    import optax

    from tensor2robot_tpu.export import export_utils
    from tensor2robot_tpu.obs import flight_recorder as flight_lib
    from tensor2robot_tpu.obs import ledger as obs_ledger
    from tensor2robot_tpu.obs import registry as registry_lib
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.replay.device_buffer import (DeviceReplayBuffer,
                                                       MegastepLearner)
    from tensor2robot_tpu.replay.ingest import TransitionQueue
    from tensor2robot_tpu.replay.loop import transition_spec
    from tensor2robot_tpu.replay.smoke import TinyQCriticModel
    from tensor2robot_tpu.train.trainer import Trainer

    self.config = config
    self.workdir = workdir
    os.makedirs(workdir, exist_ok=True)
    devices = jax.devices()
    if len(devices) < config.mesh_devices:
      raise RuntimeError(
          f"sharded Sebulba learner needs {config.mesh_devices} "
          f"devices, found {len(devices)} — run under cpu_mesh_env "
          "(the bench CLI re-execs itself)")
    self.registry = registry or registry_lib.MetricRegistry()
    self.recorder = recorder or flight_lib.FlightRecorder(
        dump_dir=os.path.join(workdir, "flightrec"))
    self.ledger = obs_ledger.ExecutableLedger()
    self.mesh = mesh_lib.create_mesh(
        {"data": config.mesh_devices},
        devices=devices[:config.mesh_devices])
    self.replicated = mesh_lib.replicated_sharding(self.mesh)
    self.model = TinyQCriticModel(
        image_size=config.image_size, action_size=config.action_size,
        optimizer_fn=lambda: optax.adam(config.learning_rate))
    self.trainer = Trainer(self.model, mesh=self.mesh,
                           seed=config.seed)
    self.state = self.trainer.create_train_state(
        batch_size=config.batch_size)
    self.buffer = DeviceReplayBuffer(
        transition_spec(config.image_size, config.action_size),
        config.capacity, config.batch_size, seed=config.seed,
        prioritized=True, ingest_chunk=config.envs_per_actor,
        mesh=self.mesh, ledger=self.ledger)
    self.learner = MegastepLearner(
        self.model, self.trainer, self.buffer,
        action_size=config.action_size, gamma=config.gamma,
        num_samples=config.cem_num_samples,
        num_elites=config.cem_num_elites,
        iterations=config.cem_iterations,
        inner_steps=config.inner_steps, seed=config.seed + 13,
        ledger=self.ledger)
    self._export = export_utils.fetch_variables_to_host
    self.learner.refresh(self.host_variables(), step=0)
    self.queue = TransitionQueue(
        config.queue_capacity, registry=self.registry,
        flight_recorder=self.recorder)
    self.params_dir = os.path.join(workdir, "params")
    self.params_version = 0
    publish_params(self.params_dir, 0, self.host_variables())

  def host_variables(self):
    return self._export(self.state.variables(use_ema=True))

  def compile_counts(self) -> Dict[str, int]:
    return {**self.buffer.compile_counts,
            **self.learner.compile_counts}

  def drive(self, host_chunks: Iterator[Dict[str, np.ndarray]],
            publish: bool = True) -> Dict:
    """Consumes the chunk stream through the prefetch seam and runs
    the megastep cadence. THE shared consumption body: the live run
    and the serial oracle replay both land here, which is what makes
    the bit-identity bar a statement about transport/overlap and not
    about two subtly different learner loops.

    Per chunk: one async device_put is already in flight (the
    prefetch double-buffer), one ``extend_device_chunk`` dispatch
    lands it in the sharded ring; every ``chunks_per_megastep``-th
    chunk triggers one megastep dispatch. Param publish (actors'
    hot-reload feed) and target refresh run on their megastep
    cadences; publish is side-effect-only and the refresh schedule is
    a pure function of the megastep index, so determinism holds.
    """
    from tensor2robot_tpu.data.prefetch import (PrefetchExhausted,
                                                prefetch_to_device)
    from tensor2robot_tpu.obs import trace as trace_lib
    config = self.config
    stream: List[dict] = []
    megasteps = 0
    chunks = 0
    extend_busy_s = 0.0
    learn_busy_s = 0.0
    prefetched = prefetch_to_device(
        host_chunks, sharding=self.replicated,
        depth=config.prefetch_depth, registry=self.registry,
        name="sebulba_prefetch", exhaust_error=True)
    wall0 = time.perf_counter()
    while megasteps < config.num_megasteps:
      try:
        device_chunk = next(prefetched)
      except PrefetchExhausted:
        break  # the typed end-of-stream, not a bare StopIteration
      begin = time.perf_counter()
      with trace_lib.span("sebulba/extend",
                          rows=config.envs_per_actor):
        self.buffer.extend_device_chunk(device_chunk)
      extend_busy_s += time.perf_counter() - begin
      chunks += 1
      if chunks % config.chunks_per_megastep:
        continue
      begin = time.perf_counter()
      self.state, metrics = self.learner.step(self.state)
      learn_busy_s += time.perf_counter() - begin
      megasteps += 1
      # Full float64 precision through the JSON round-trip: equality
      # on these entries IS bit-identity (multihost_bench contract).
      stream.append({"megastep": megasteps, **metrics})
      if (config.target_refresh_every
          and megasteps % config.target_refresh_every == 0):
        self.learner.refresh(self.host_variables(), step=megasteps)
      if (publish and config.publish_every
          and megasteps % config.publish_every == 0):
        self.params_version += 1
        publish_params(self.params_dir, self.params_version,
                       self.host_variables())
    wall_s = time.perf_counter() - wall0
    self.registry.gauge("sebulba/learner_busy_fraction").set(
        learn_busy_s / wall_s if wall_s > 0 else 0.0)
    self.registry.gauge("sebulba/ingest_busy_fraction").set(
        extend_busy_s / wall_s if wall_s > 0 else 0.0)
    return {
        "megasteps": megasteps,
        "chunks_consumed": chunks,
        "optimizer_steps": megasteps * config.inner_steps,
        "stream": stream,
        "learn_busy_s": round(learn_busy_s, 4),
        "extend_busy_s": round(extend_busy_s, 4),
        "wall_s": round(wall_s, 4),
    }

  def save_final_params(self, path: str) -> str:
    from tensor2robot_tpu.export import variables_io
    tmp = path + ".tmp"
    variables_io.save_variables(tmp, self.host_variables())
    os.replace(tmp, path)
    return path


def _actor_specs(config: SebulbaConfig, spool_dir: str,
                 params_dir: str,
                 die_after: Optional[Dict[int, int]] = None,
                 obs_logdir: Optional[str] = None) -> List[Dict]:
  specs = []
  for actor_id in range(config.num_actors):
    spec = {
        "role": "actor",
        "actor_id": actor_id,
        "spool_dir": spool_dir,
        "params_dir": params_dir,
        "obs_logdir": obs_logdir,
        "seed": config.seed + actor_id,
        "image_size": config.image_size,
        "action_size": config.action_size,
        "num_envs": config.envs_per_actor,
        "cem_num_samples": config.cem_num_samples,
        "cem_num_elites": config.cem_num_elites,
        "cem_iterations": config.cem_iterations,
        "max_backlog": config.max_backlog,
        "max_chunks": config.actor_max_chunks,
        "synthetic": config.synthetic_actors,
        "step_sleep_s": config.actor_step_sleep_s,
    }
    if die_after and actor_id in die_after:
      spec["die_after_chunks"] = die_after[actor_id]
    specs.append(spec)
  return specs


def _actor_envs(config: SebulbaConfig) -> Dict[int, Dict[str, str]]:
  """Each actor owns its own single-device runtime — its acting
  executable is pinned to ITS device, never the learner mesh. Which
  device follows from what the (already initialized) learner runs on:
  cpu -> a 1-device CPU runtime per actor; tpu -> one chip per actor,
  outside the chips the learner holds. A learner that was not started
  under tpu_chip_env holds every chip of the host, and an actor that
  needs one would fail or hang — refuse before spawning any."""
  import jax

  from tensor2robot_tpu.utils import tpu_chip_env as chip_lib
  from tensor2robot_tpu.utils.cpu_mesh_env import cpu_mesh_env

  actor_ids = range(config.num_actors)
  if jax.devices()[0].platform != "tpu":
    envs = {actor_id: cpu_mesh_env(1) for actor_id in actor_ids}
  else:
    held = chip_lib.visible_chips()
    if held is None or len(jax.devices()) != config.mesh_devices:
      raise RuntimeError(
          f"the Sebulba learner holds {len(jax.devices())} TPU chip(s) "
          f"(visibility {held}) for a {config.mesh_devices}-device mesh; "
          "start the learner process under utils.tpu_chip_env.tpu_chip_env"
          f"(<its {config.mesh_devices} chips>) so the actor processes "
          "can open theirs")
    free = (chip for chip in itertools.count() if chip not in held)
    envs = {actor_id: chip_lib.tpu_chip_env([next(free)])
            for actor_id in actor_ids}
  for env in envs.values():
    env["PYTHONPATH"] = (_repo_root() + os.pathsep
                         + env.get("PYTHONPATH", ""))
  return envs


def run_live(config: SebulbaConfig, workdir: str,
             die_after: Optional[Dict[int, int]] = None,
             timeout_s: float = 600.0) -> Dict:
  """The live Sebulba window: THIS process is the learner; N actor
  processes stream chunks through the spool. Returns the result block
  (manifest, overlap instruments, supervisor timeline, actor results,
  compile ledger) plus the final params path for the parity check."""
  from tensor2robot_tpu.utils.device_info import device_summary
  os.makedirs(workdir, exist_ok=True)
  spool_dir = os.path.join(workdir, "spool")
  os.makedirs(spool_dir, exist_ok=True)
  obs_logdir = os.path.join(workdir, "obslog")
  os.makedirs(obs_logdir, exist_ok=True)
  learner = SebulbaLearner(config, workdir)
  specs = _actor_specs(config, spool_dir, learner.params_dir,
                       die_after=die_after, obs_logdir=obs_logdir)
  reader = SpoolReader(spool_dir, config.num_actors)
  supervisor = ActorSupervisor(
      spool_dir, specs, envs=_actor_envs(config),
      recorder=learner.recorder,
      registry=learner.registry, deadline_s=config.actor_deadline_s,
      quarantine_s=config.quarantine_s)
  arrivals: List[dict] = []
  needed = config.num_megasteps * config.chunks_per_megastep
  stop = threading.Event()
  occupancy = learner.registry.histogram("sebulba/queue_occupancy")
  occupancy_gauge = learner.registry.gauge(
      "sebulba/queue_occupancy_last")

  def ingest() -> None:
    # The ingest thread: disk tail -> bounded queue, plus all actor
    # supervision. Nothing here touches device state — the learner
    # thread owns every dispatch, so megastep/extend never race.
    # Admission control: only tail as many chunks as the queue has
    # room for, so the queue NEVER sheds during the parity window and
    # the ack frontier (what actors' backpressure watches) means
    # "admitted to the learner", not merely "seen on disk". Drops
    # remain a real regime at saturation — proven by the ingest unit
    # tests — but a dropped row would fork the live stream from the
    # recorded manifest.
    chunk_rows = config.envs_per_actor
    while not stop.is_set():
      room = learner.queue.capacity - len(learner.queue)
      per_actor = room // max(1, chunk_rows * config.num_actors)
      events = (reader.poll(max_per_actor=min(per_actor, 8))
                if per_actor > 0 else [])
      for actor, seq, chunk in events:
        learner.queue.put_batch(chunk, provenance=f"actor{actor}")
        arrivals.append({"actor": actor, "seq": seq})
      supervisor.observe(events, reader)
      supervisor.check(reader)
      reader.write_acks()
      fill = len(learner.queue) / learner.queue.capacity
      occupancy.record(fill)
      occupancy_gauge.set(fill)
      if not events:
        time.sleep(0.01)

  starved = {"s": 0.0}

  def host_chunks() -> Iterator[Dict[str, np.ndarray]]:
    yielded = 0
    deadline = time.monotonic() + timeout_s
    while yielded < needed:
      if time.monotonic() > deadline:
        raise TimeoutError(
            f"learner starved: {yielded}/{needed} chunks after "
            f"{timeout_s}s (actors dead without reinstatement?)")
      batch = learner.queue.drain_batch(config.envs_per_actor)
      if batch is None:
        begin = time.perf_counter()
        time.sleep(0.002)
        starved["s"] += time.perf_counter() - begin
        continue
      yield batch
      yielded += 1

  supervisor.start()
  thread = threading.Thread(target=ingest, daemon=True)
  thread.start()
  try:
    drive = learner.drive(host_chunks(), publish=True)
  finally:
    stop.set()
    thread.join(10.0)
    supervisor.stop()
  learner.registry.gauge("sebulba/learner_stall_s").set(starved["s"])
  actor_results = supervisor.results()
  actor_busy_s = sum(
      (result or {}).get("busy_seconds", 0.0)
      for result in actor_results.values())
  actor_stall_s = sum(
      (result or {}).get("backpressure_stall_s", 0.0)
      for result in actor_results.values())
  wall = max(drive["wall_s"], 1e-9)
  learner.registry.export_snapshot(
      os.path.join(obs_logdir, f"registry-learner-{os.getpid()}.json"),
      host="learner")
  params_path = learner.save_final_params(
      os.path.join(workdir, "final_params.npz"))
  queue_stats = learner.queue.stats()
  occ = occupancy.snapshot()
  return {
      "config": config.to_json(),
      "learner_pid": os.getpid(),
      "learner_device": device_summary(),
      "mesh_shape": {"data": config.mesh_devices},
      "drive": drive,
      "manifest": arrivals[:needed],
      "arrivals_total": len(arrivals),
      "queue": queue_stats,
      "overlap": {
          "learner_wall_s": drive["wall_s"],
          "learn_busy_s": drive["learn_busy_s"],
          "extend_busy_s": drive["extend_busy_s"],
          "learner_stall_s": round(starved["s"], 4),
          "actor_busy_s": round(actor_busy_s, 4),
          "actor_backpressure_stall_s": round(actor_stall_s, 4),
          # Acting/learning overlap: actor-process busy seconds per
          # learner wall second (the ActorFleet.busy_seconds instrument
          # lifted across the process boundary), capped at 1.
          "overlap_fraction": round(
              min(1.0, actor_busy_s / wall), 4),
          "learner_busy_fraction": round(
              drive["learn_busy_s"] / wall, 4),
          "queue_occupancy": {
              "max": occ.get("max"), "p50": occ.get("p50"),
              "samples": occ.get("count"),
          },
      },
      "actors": {str(actor_id): result
                 for actor_id, result in actor_results.items()},
      "watchdog_events": supervisor.watchdog_events,
      "supervisor": {
          "timeline": supervisor.timeline,
          "respawns": dict(supervisor.respawns),
          "breaker_events": {
              str(actor_id): events for actor_id, events in
              supervisor.breaker_events().items()},
      },
      "compile_counts": learner.compile_counts(),
      "final_params_path": params_path,
      "obs_logdir": obs_logdir,
  }


# --- the serial single-process oracle --------------------------------------


def _manifest_chunks(spool_dir: str, manifest: List[dict]
                     ) -> Iterator[Dict[str, np.ndarray]]:
  for entry in manifest:
    yield load_chunk(spool_dir, entry["actor"], entry["seq"])


def _run_oracle(spec: Dict) -> None:
  """Oracle worker: ONE serial process replays the recorded stream —
  the manifest's (actor, seq) order against the spooled chunk files —
  through the identical learner stack and consumption body. No queue,
  no threads, no actor processes: if the live learner's params match
  this bitwise, the decoupling added overlap and nothing else."""
  config = SebulbaConfig.from_json(spec["config"])
  manifest = _read_json(spec["manifest_path"])["manifest"]
  learner = SebulbaLearner(config, spec["workdir"])
  drive = learner.drive(
      _manifest_chunks(spec["spool_dir"], manifest), publish=False)
  params_path = learner.save_final_params(spec["params_out"])
  summary = {
      "drive": drive,
      "compile_counts": learner.compile_counts(),
      "params_path": params_path,
  }
  print("ORACLE_RESULT " + json.dumps(summary), flush=True)
  print("ORACLE_OK", flush=True)


def run_oracle_subprocess(config: SebulbaConfig, spool_dir: str,
                          manifest: List[dict], workdir: str,
                          timeout_s: float = 900.0) -> Dict:
  """Runs the oracle replay in a FRESH interpreter (no shared jit
  cache, no shared process state with the live learner) under the same
  virtual-device env, and returns its parsed summary."""
  from tensor2robot_tpu.utils.cpu_mesh_env import cpu_mesh_env
  import jax
  os.makedirs(workdir, exist_ok=True)
  manifest_path = os.path.join(workdir, "manifest.json")
  _atomic_write_json(manifest_path, {"manifest": manifest})
  spec = {
      "role": "oracle",
      "config": config.to_json(),
      "spool_dir": spool_dir,
      "manifest_path": manifest_path,
      "workdir": os.path.join(workdir, "oracle_learner"),
      "params_out": os.path.join(workdir, "oracle_params.npz"),
  }
  env = cpu_mesh_env(max(len(jax.devices()), config.mesh_devices))
  env["PYTHONPATH"] = (_repo_root() + os.pathsep
                       + env.get("PYTHONPATH", ""))
  proc = subprocess.Popen(
      [sys.executable, "-m", "tensor2robot_tpu.parallel.sebulba",
       _WORKER_FLAG, json.dumps(spec)],
      env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
      text=True)
  out, _ = proc.communicate(timeout=timeout_s)
  if proc.returncode != 0 or "ORACLE_OK" not in out:
    raise RuntimeError(
        f"sebulba oracle failed rc={proc.returncode}:\n{out}")
  marker = "ORACLE_RESULT "
  line = next(ln for ln in out.splitlines() if ln.startswith(marker))
  return json.loads(line[len(marker):])


def compare_params(path_a: str, path_b: str) -> Dict:
  """Leaf-for-leaf bitwise comparison of two saved variables npz."""
  import hashlib
  with np.load(path_a) as a, np.load(path_b) as b:
    keys_a, keys_b = sorted(a.files), sorted(b.files)
    mismatched = []
    digest = hashlib.sha256()
    if keys_a != keys_b:
      return {"bit_identical": False, "keys_a": len(keys_a),
              "keys_b": len(keys_b), "mismatched_keys": True}
    for key in keys_a:
      left, right = a[key], b[key]
      digest.update(left.tobytes())
      # equal_nan only exists for inexact dtypes (the manifest leaf is
      # uint8); bitwise identity is the claim either way.
      same = (left.dtype == right.dtype and left.shape == right.shape
              and left.tobytes() == right.tobytes())
      if not same:
        mismatched.append(key)
  return {
      "bit_identical": not mismatched,
      "leaves": len(keys_a),
      "mismatched": mismatched[:8],
      "sha256": digest.hexdigest()[:16],
  }


def main(argv=None) -> None:
  import argparse
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument(_WORKER_FLAG, dest="worker", default=None,
                      help=argparse.SUPPRESS)
  args = parser.parse_args(argv)
  if args.worker is None:
    parser.error("this module's CLI is the worker entry point; "
                 "sebulba.run_live starts it")
  from tensor2robot_tpu.utils import compile_cache
  compile_cache.configure()
  spec = json.loads(args.worker)
  if spec.get("role") == "oracle":
    _run_oracle(spec)
  else:
    _run_actor(spec)


if __name__ == "__main__":
  main()

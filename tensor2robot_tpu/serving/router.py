"""Least-loaded router: the bucket ladder replicated over every device.

One `FleetServer` keeps ONE chip busy for up to `max_batch` clients;
fleet traffic needs the sebulba split (Podracer, PAPERS.md): replicated
inference executables fed by a host-side router. This module is that
layer — each mesh device (parallel/mesh.mesh_devices enumeration) gets
its own *replica*: a `CEMFleetPolicy` pinned to the device (its ladder
compiles exactly one executable per bucket PER DEVICE, the compile
ledger the fleet artifact asserts) behind its own SLO-aware
`MicroBatcher`, and the router dispatches each request to the replica
with the shortest queue (pending + in-flight — joining the shortest
line, not round-robin, so one slow flush doesn't back up the fleet).

Per-request determinism survives routing: seeds are assigned at the
router's front door from one monotonic counter, and a request's action
depends on (image, seed) only (policy.py's fold_in contract) — which
replica served it is unobservable in the action, so the single-replica
`FleetServer` remains the semantics oracle for the whole fleet
(PARITY round-11 note).

Hot reload reaches every replica through the predictor: each flush
reads `predictor.device_fn()`, so a promotion's `set_variables` swap
(serving/rollout.py) is visible fleet-wide at the next flush — one
device_put per replica, zero recompiles.
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

import numpy as np

from tensor2robot_tpu.obs import context as context_lib
from tensor2robot_tpu.obs import faults as faults_lib
from tensor2robot_tpu.obs import flight_recorder as flight_lib
from tensor2robot_tpu.obs import ledger as ledger_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.serving.batcher import MicroBatcher
from tensor2robot_tpu.serving.policy import CEMFleetPolicy
from tensor2robot_tpu.serving import slo as slo_lib
from tensor2robot_tpu.serving.slo import (HealthConfig, RequestShed,
                                          SLOClass)
from tensor2robot_tpu.serving.stats import ServingStats


class PolicyReplica:
  """One device's slice of the fleet: pinned policy + its own batcher."""

  def __init__(self, policy: CEMFleetPolicy, max_batch: int,
               deadline_ms: float, stats: ServingStats,
               max_queue: Optional[int], dispatch_margin_ms: float,
               flight_recorder=None,
               fault_plan: Optional[faults_lib.FaultPlan] = None,
               restart_budget: int = 3,
               episode_recorder=None):
    self.policy = policy
    self.device = policy.device
    self.stats = stats
    self._faults = fault_plan
    self._episode_recorder = episode_recorder
    # corrupt_served_variables state (ISSUE 15): once the fault fires,
    # the replica serves a finite-but-wrong scaled copy of the live
    # params — STICKY, like the botched hot-swap it models — until the
    # Q-drift guard catches it. Cache keyed on the live tree identity
    # so a hot reload re-corrupts the NEW params (still corrupted, one
    # scale job per reload).
    self._corrupt_scale: Optional[float] = None
    self._corrupt_cache = None
    self.batcher = MicroBatcher(
        self._flush, max_batch=max_batch, deadline_ms=deadline_ms,
        stats=stats, bucket_for=policy.ladder.bucket_for,
        max_queue=max_queue, dispatch_margin_ms=dispatch_margin_ms,
        flight_recorder=flight_recorder,
        fault_plan=fault_plan, site=f"batcher@{policy.device}",
        restart_budget=restart_budget,
        # Two flushes open: the next full batch is stacked, padded and
        # put while the device runs the current one (the policy's call
        # path is asynchronous up to its device turn, and everything
        # `_flush` touches is per call or locked).
        flush_depth=2)

  def use_policy(self, policy: CEMFleetPolicy) -> None:
    """Hot-swaps this replica's policy (the precision-tier promotion
    path, serving/rollout.py): an atomic attribute swap under the GIL —
    in-flight flushes finish on the old policy's executables, the next
    flush dispatches through the new one. The ladder/bucket_for closure
    the batcher holds is shared (same ladder sizes by construction), and
    the device must match the replica's pin — a cross-device swap would
    silently re-place every request batch."""
    if policy.device is not self.device:
      raise ValueError(
          f"policy pinned to {policy.device} cannot serve replica on "
          f"{self.device}")
    self.policy = policy

  def _corrupted_variables(self):
    """The sticky corrupt_served_variables tree for the CURRENT live
    params (rebuilt after a hot reload; the scaled copy flows through
    the policy's identity-keyed placement cache like any candidate)."""
    _, live = self.policy._predictor.device_fn()
    if self._corrupt_cache is not None and self._corrupt_cache[0] is live:
      return self._corrupt_cache[1]
    corrupted = faults_lib.corrupt_variables(live, self._corrupt_scale)
    self._corrupt_cache = (live, corrupted)
    return corrupted

  def _flush(self, items):
    images = [item[0] for item in items]
    seeds = np.asarray([item[1] for item in items], np.uint32)
    # The replica-dispatch hop of the request timeline: runs inside
    # the batcher's serve/flush span (same thread), inheriting the
    # batch's bound request_ids, and names the device the batch
    # actually landed on.
    with trace_lib.span("serve/dispatch", batch=len(items),
                        device=str(self.device)):
      # Fault seam (ISSUE 14/15): the ONE point a scheduled
      # dispatch_error / latency_spike enters this replica — inside
      # the dispatch span, so the injected fault's flight-recorder
      # dump carries the batch's request_ids, and upstream sees
      # exactly what a real device failure produces (a raising flush).
      # A fired corrupt_served_variables spec (returned, not raised)
      # installs the sticky scaled-params corruption the fleet
      # Q-drift guard must detect.
      if self._faults is not None:
        for spec in self._faults.perturb("replica_dispatch",
                                         site=str(self.device)):
          if spec.kind == "corrupt_served_variables":
            self._corrupt_scale = spec.scale
            self._corrupt_cache = None
      override = (self._corrupted_variables()
                  if self._corrupt_scale is not None else None)
      policy = self.policy  # one read: use_policy may swap it meanwhile
      actions, scores = policy(images, seeds, variables=override,
                               return_scores=True)
      if scores is not None:
        # Served-Q sketch feed (ISSUE 15): free scores off the same
        # dispatch, whether its program encoded each frame once,
        # whether its frames were stacked into a staging array the
        # policy had kept and where its device turn went;
        # exception-isolated — diagnostics never fail a flush (the
        # listener contract).
        try:
          self.stats.record_q_values(str(self.device), scores)
          if policy.encode_once.get(policy.ladder.bucket_for(len(items))):
            self.stats.record_encode_once_flush()
          if policy.last_call_reused_staging:
            self.stats.record_staged_flush()
          self.stats.record_flush_phases(**policy.last_call_phases)
        except Exception:
          pass
      if self._episode_recorder is not None:
        # Capture seam (ISSUE 18): the flywheel's EpisodeRecorder logs
        # what this batch actually SERVED — the post-fault actions, the
        # CEM seeds, the batch's bound request_ids (the batcher binds
        # them in item order before calling us), and the params version
        # the dispatch ran under. Exception-isolated like the sketch
        # feed: capture never fails a flush.
        try:
          self._episode_recorder.record_served(
              items, actions, device=str(self.device),
              params_version=getattr(
                  self.policy._predictor, "model_version", None))
        except Exception:
          pass
      return list(actions)

  def warmup(self, make_image) -> None:
    """Compiles the full ladder on this replica's device (server
    startup, before traffic): the measured path then never compiles."""
    self.policy.warm(make_image)


class FleetRouter:
  """Routes fleet traffic to per-device policy replicas, least-loaded.

  Args:
    predictor: shared predictor (one set of live params; replicas place
      them per device). Must provide device_fn() — replication of a
      host-only predictor would serialize on the host anyway.
    devices: the replica devices. Pass `parallel.mesh.mesh_devices(mesh)`
      to replicate over a training mesh, or any explicit device list;
      None uses jax.devices() (every visible device).
    max_batch: per-replica flush threshold (defaults to the ladder top
      rung, same rule as FleetServer).
    deadline_ms: default-class budget for class-less submits.
    max_queue: per-replica admission bound; offered load beyond it
      sheds lowest-priority-first (serving/slo.py). None = unbounded.
    stats: shared ServingStats across ALL replicas (one is created if
      not given) — per-class latency/shed counters aggregate fleet-wide.
    precision: the fleet's serving Q-scoring tier (cem.
      SCORING_PRECISIONS; default "f32", the unchanged oracle). Every
      replica's bucket ladder compiles at this tier; `set_precision`
      hot-swaps the whole fleet to another tier (the rollout
      controller's promotion path for a precision candidate), and
      `make_policy` builds a single-device policy at an arbitrary tier
      for the shadow/canary phases. Non-f32 executables register
      tier-suffixed ledger keys, so the shared obs ledger proves
      exactly-once compilation per bucket per device PER TIER.
    health: replica self-healing knobs (serving/slo.HealthConfig,
      ISSUE 14). Always armed — with no failures the machinery is
      inert (each success is one counter reset) and dispatch behaves
      exactly as before: per replica, a consecutive-failure circuit
      breaker QUARANTINES a throwing replica out of the least-loaded
      candidate set; after `quarantine_s` ONE live request is routed
      to it as a half-open PROBE (success reinstates, failure
      re-quarantines); a failed dispatch re-routes to another replica
      only when the request's remaining deadline slack covers
      `retry_cost_ms` (else it resolves typed as
      ``RequestShed(class, "fault")``, counted per class); and with
      EVERY replica quarantined the router degrades — it keeps
      routing least-loaded over the quarantined fleet so the existing
      SLO machinery sheds lowest-priority-first instead of erroring.
    fault_plan: deterministic fault injection (obs/faults.py) threaded
      to every replica's dispatch seam and batcher. None (the
      default) is the oracle path: no plan, no new work on dispatch.
    tp_group (ISSUE 16): devices per tensor-parallel replica GROUP.
      1 (default) keeps one replica per device — the unchanged fleet.
      >1 chunks `devices` into consecutive groups of that size, builds
      ONE Mesh per group over a ``model`` axis, and pins one policy
      per GROUP: the served critic's params shard over the group per
      `param_specs` (the model's partition rules), request batches
      replicate within it — a critic too wide for one device serves
      from a group of them. len(devices) must divide evenly.
    param_specs: PartitionSpec pytree for the predictor's params
      subtree, forwarded to every replica policy (meaningful with
      tp_group > 1).
    cem / ladder kwargs: forwarded to each replica's CEMFleetPolicy.
  """

  def __init__(self, predictor, devices: Optional[Sequence] = None,
               action_size: int = 4, num_samples: int = 64,
               num_elites: int = 6, iterations: int = 3, seed: int = 0,
               ladder_sizes: Optional[Sequence[int]] = None,
               max_batch: Optional[int] = None, deadline_ms: float = 5.0,
               max_queue: Optional[int] = None,
               dispatch_margin_ms: float = 0.0,
               stats: Optional[ServingStats] = None,
               metric_writer=None,
               ledger: Optional[ledger_lib.ExecutableLedger] = None,
               flight_recorder=None,
               precision: str = "f32",
               health: Optional[HealthConfig] = None,
               fault_plan: Optional[faults_lib.FaultPlan] = None,
               tp_group: int = 1,
               param_specs=None,
               episode_recorder=None):
    import jax

    from tensor2robot_tpu.research.qtopt import cem

    devices = list(jax.devices() if devices is None else devices)
    if not devices:
      raise ValueError("FleetRouter needs at least one device.")
    self.tp_group = int(tp_group)
    self._param_specs = param_specs
    if self.tp_group > 1:
      # Tensor-parallel replica groups: consecutive device chunks, one
      # Mesh (→ one PolicyReplica) per chunk. Meshes are hashable and
      # identity-stable here (built once, reused for the fleet's
      # lifetime), so the policy cache and the replica identity check
      # keep working unchanged.
      import numpy as _np
      if len(devices) % self.tp_group:
        raise ValueError(
            f"{len(devices)} device(s) do not split into tensor-"
            f"parallel groups of {self.tp_group}; pass a device list "
            f"whose length {len(devices)} is a multiple of tp_group")
      devices = [
          jax.sharding.Mesh(
              _np.asarray(devices[i:i + self.tp_group]), ("model",))
          for i in range(0, len(devices), self.tp_group)]
    self.stats = stats or ServingStats()
    self._metric_writer = metric_writer
    self._metric_step = 0
    self._predictor = predictor
    self.precision = cem.validate_precision(precision)
    self._seed_lock = threading.Lock()
    self._next_seed = 0
    self._rr = itertools.count()  # least-loaded tie-break rotation
    # Policy construction parameters, kept so make_policy/set_precision
    # can rebuild a replica's policy at another tier with IDENTICAL CEM
    # hyperparameters and seed — the paired shadow comparison is only
    # sharp because (image, seed) -> action matches across tiers modulo
    # the numerics under test.
    self._policy_kwargs = dict(
        action_size=action_size, num_samples=num_samples,
        num_elites=num_elites, iterations=iterations, seed=seed)
    self._ladder_sizes = (tuple(ladder_sizes)
                          if ladder_sizes is not None else None)
    # Observability spine (ISSUE 11): one ExecutableLedger spanning all
    # replicas (per-device rows via the policies' @device keys) and one
    # flight recorder shared by every replica's batcher (default: the
    # process recorder — ring-only until a dump_dir is configured).
    self.ledger = ledger if ledger is not None else ledger_lib.ExecutableLedger()
    self._recorder = flight_recorder or flight_lib.get_recorder()
    # One policy per (device, tier) for the fleet's LIFETIME: repeat
    # make_policy calls (a re-offered precision candidate after a
    # rollback, a promote following its own shadow phase) reuse the
    # compiled bucket executables instead of re-registering them — the
    # per-tier exactly-once ledger claim holds across arbitrarily many
    # rollout cycles.
    self._policy_cache = {}
    self._policy_cache_lock = threading.Lock()
    # Replica self-healing (ISSUE 14): one circuit breaker per replica
    # under one health lock; the timeline feeds the chaos artifact's
    # quarantine→probe→reinstate bar.
    self.health = health or HealthConfig()
    self._faults = fault_plan
    self._health_lock = threading.Lock()
    self._health_events = []
    self._max_health_events = 1024
    self._degraded = False
    # Fleet Q-drift guard state (ISSUE 15): replicas currently flagged
    # divergent — transitions (not steady states) fire the
    # replica_divergent flightrec trigger and the timeline event.
    self._divergent_replicas = set()
    # Flywheel capture (ISSUE 18): one EpisodeRecorder shared by every
    # replica — the serving seam where fleet traffic becomes training
    # data. None (the default) keeps serving capture-free.
    self._episode_recorder = episode_recorder
    self._started_at = time.perf_counter()
    # Never-started guard (ISSUE 19): warmup() compiles but does not
    # start the batchers, so a submit before start() must raise typed
    # instead of shedding every request as an anonymous replica fault.
    self._started = False
    self.replicas = []
    self._breakers = []
    for device in devices:
      policy = self.make_policy(device)
      ladder = policy.ladder
      replica_max_batch = (ladder.max_batch if max_batch is None
                           else max_batch)
      if replica_max_batch > ladder.max_batch:
        raise ValueError(
            f"max_batch {replica_max_batch} exceeds ladder top rung "
            f"{ladder.max_batch}")
      self.replicas.append(PolicyReplica(
          policy, replica_max_batch, deadline_ms, self.stats, max_queue,
          dispatch_margin_ms, flight_recorder=self._recorder,
          fault_plan=fault_plan,
          restart_budget=self.health.restart_budget,
          episode_recorder=self._episode_recorder))
      self._breakers.append(slo_lib.CircuitBreaker(
          self.health.failure_threshold, self.health.quarantine_s))

  def make_policy(self, device, precision: Optional[str] = None
                  ) -> CEMFleetPolicy:
    """A CEMFleetPolicy pinned to `device` at `precision` (default: the
    fleet's tier), sharing the fleet's predictor, obs ledger, CEM
    hyperparameters, and seed. The rollout controller builds its
    shadow-tier policy here so a precision candidate's executables land
    in the SAME ledger under tier-suffixed keys, and its per-request
    fold_in stream matches the live tier's exactly. Memoized per
    (device, tier): a repeat request returns the SAME policy object and
    its already-compiled buckets."""
    from tensor2robot_tpu.serving.bucketing import BucketLadder

    if precision is None:
      precision = self.precision
    key = (device, precision)
    with self._policy_cache_lock:
      policy = self._policy_cache.get(key)
      if policy is None:
        ladder = (BucketLadder(self._ladder_sizes)
                  if self._ladder_sizes is not None else BucketLadder())
        policy = CEMFleetPolicy(
            self._predictor, ladder=ladder, device=device,
            ledger=self.ledger, precision=precision,
            param_specs=self._param_specs,
            **self._policy_kwargs)
        self._policy_cache[key] = policy
      return policy

  def set_precision(self, precision: str) -> None:
    """Hot-swaps EVERY replica to the `precision` scoring tier — the
    fleet-wide promotion of a numerics change (serving/rollout.py's
    precision-candidate promote). Each replica's tier policy is built
    AND WARMED (every ladder bucket compiled, on zeros from the
    predictor's image spec) BEFORE the atomic swap: a promote must not
    hand live traffic per-bucket compile stalls on the replicas the
    shadow phase never touched — the zero-recompile serving invariant
    holds through the cutover, with in-flight flushes finishing on the
    old tier. Executables land under tier-suffixed ledger keys exactly
    once each (memoized policies: the shadow device's warmup is a
    no-op walk over its already-compiled buckets). A same-tier call is
    a no-op (promoting the tier you already serve must not rebuild the
    fleet's executable cache)."""
    from concurrent.futures import ThreadPoolExecutor

    from tensor2robot_tpu.research.qtopt import cem

    cem.validate_precision(precision)
    if precision == self.precision:
      return
    # Warm all replicas CONCURRENTLY: each tier policy compiles under
    # its own lock for its own device, so the promote stall is ~one
    # ladder's compile time, not n_devices of them (the shadow
    # device's policy is already warm — a no-op walk).
    with ThreadPoolExecutor(max_workers=len(self.replicas)) as pool:
      swaps = list(zip(self.replicas, pool.map(
          lambda replica: self.warm_policy(replica.device, precision),
          self.replicas)))
    for replica, policy in swaps:
      replica.use_policy(policy)
    self.precision = precision

  def warm_policy(self, device, precision: Optional[str] = None
                  ) -> CEMFleetPolicy:
    """make_policy + the full-ladder warmup (CEMFleetPolicy.warm on
    zeros at the predictor's image spec — content is irrelevant, the
    answers are discarded; only the compiled shapes matter). THE one
    build-and-warm recipe both cutover paths share: set_precision's
    per-replica promote and the rollout controller's tier-candidate
    offer — so a shadow tier can never warm differently from the tier
    the promote later installs."""
    import numpy as np

    policy = self.make_policy(device, precision)
    spec = self._predictor.get_feature_specification()["image"]
    zero = np.zeros(tuple(spec.shape), spec.dtype)
    policy.warm(lambda i: zero)
    return policy

  # -- lifecycle -----------------------------------------------------------

  def start(self) -> "FleetRouter":
    self._started = True
    for replica in self.replicas:
      replica.batcher.start()
    return self

  def stop(self) -> None:
    for replica in self.replicas:
      replica.batcher.stop()

  def __enter__(self) -> "FleetRouter":
    return self.start()

  def __exit__(self, *exc_info) -> None:
    self.stop()

  def warmup(self, make_image) -> None:
    """Compiles every bucket on every replica before traffic (the
    fleet bench's precompile phase; the ledger then proves the measured
    sweep never compiled)."""
    for replica in self.replicas:
      replica.warmup(make_image)

  def use_stats(self, stats: ServingStats) -> None:
    """Swaps the shared stats sink (between sweep points, while idle):
    per-point artifact accounting without rebuilding replicas — a
    rebuild would recompile the whole ladder, which is exactly what the
    ledger forbids mid-run."""
    self.stats = stats
    for replica in self.replicas:
      replica.stats = stats
      replica.batcher.use_stats(stats)

  # -- client API ----------------------------------------------------------

  def assign_seed(self) -> int:
    with self._seed_lock:
      seed = self._next_seed
      self._next_seed += 1
    return seed

  def submit(self, image, slo: Optional[SLOClass] = None,
             seed: Optional[int] = None,
             deadline_at: Optional[float] = None,
             request_id: Optional[str] = None) -> Future:
    """Enqueues one frame on the least-loaded AVAILABLE replica.

    The request's absolute deadline is stamped HERE (router ingress),
    so replica queueing cannot silently extend a class budget: if the
    chosen replica's queue already ate the budget, the replica sheds it
    as expired (counted) instead of serving a dead answer.

    The correlation id is stamped here too (ISSUE 12): minted per
    request unless the caller passes one (the rollout controller's
    mirror copy inherits its parent's id), bound for the routing
    decision, and threaded onto the replica's pending record — every
    span and flight-recorder trigger the request touches carries it.

    Self-healing (ISSUE 14): the returned future is ROUTER-owned. A
    replica dispatch failure (not a shed) feeds that replica's circuit
    breaker and — when the request's remaining deadline slack covers
    ``health.retry_cost_ms`` and the retry budget allows — re-routes
    the request to another replica transparently; otherwise the future
    resolves ``RequestShed(class, "fault")``. Quarantined replicas are
    out of the candidate set; a due half-open probe routes ONE live
    request back to its replica; with the whole fleet quarantined the
    router degrades to least-loaded over everyone (the SLO machinery
    sheds lowest-priority-first) instead of erroring. A client only
    ever sees a result, a typed ``RequestShed``, or its own timeout —
    never a raw replica exception. (Per-class ServingStats request
    counters count dispatch ATTEMPTS — a retried request is two — and
    a request shed as "fault" after a synchronous submit failure may
    carry no matching attempt; ``stats.record_logical_request`` counts
    exactly one per submit — ISSUE 18 — so flywheel episode accounting
    reconciles against serving stats without client-side bookkeeping.)
    """
    if not self._started:
      raise slo_lib.RouterNotStarted()
    if slo is not None and deadline_at is None:
      deadline_at = time.perf_counter() + slo.deadline_ms / 1e3
    seed = self.assign_seed() if seed is None else int(seed)
    request_id = request_id or context_lib.new_request_id()
    self.stats.record_logical_request()
    outer: Future = Future()
    self._dispatch(outer, np.asarray(image), seed, slo, deadline_at,
                   request_id, excluded=frozenset(), retries=0)
    return outer

  # -- self-healing dispatch (ISSUE 14) ------------------------------------

  def _health_event(self, event: str, replica: Optional[int],
                    **fields) -> None:
    """Appends one entry to the health timeline. Caller holds the
    health lock; flight-recorder triggers for the entries that warrant
    one (quarantine) are fired by the caller AFTER releasing it."""
    entry = {
        "event": event,
        "t_s": round(time.perf_counter() - self._started_at, 3),
    }
    if replica is not None:
      entry["replica"] = str(self.replicas[replica].device)
    entry.update(fields)
    self._health_events.append(entry)
    # Bounded like the watchdog's event history: a long-lived router
    # under flapping faults must not grow its timeline without bound.
    if len(self._health_events) > self._max_health_events:
      del self._health_events[
          :len(self._health_events) - self._max_health_events]

  def _update_degraded_locked(self) -> None:
    degraded = all(b.state != "closed" for b in self._breakers)
    if degraded and not self._degraded:
      self._degraded = True
      self._health_event("degraded_enter", None)
    elif not degraded and self._degraded:
      self._degraded = False
      self._health_event("degraded_exit", None)

  def _record_result(self, index: int, ok: bool,
                     error: Optional[str] = None) -> None:
    """Feeds one dispatch outcome into the replica's breaker; emits
    timeline events + flightrec triggers on state transitions."""
    with self._health_lock:
      breaker = self._breakers[index]
      before = breaker.state
      if ok:
        # `from_degraded` gates the open->closed shortcut: only a
        # success of traffic the router ROUTED to an open replica
        # (degraded mode) reinstates without a probe — a stale
        # completion of a request queued before the quarantine must
        # not bypass the window (slo.CircuitBreaker.record_success).
        breaker.record_success(from_degraded=self._degraded)
      else:
        breaker.record_failure()
      after = breaker.state
      if before != "open" and after == "open":
        self._health_event(
            "requarantine" if before == "half_open" else "quarantine",
            index, failures=breaker.consecutive_failures,
            **({} if error is None else {"error": error}))
      elif before in ("open", "half_open") and after == "closed":
        self._health_event("reinstate", index)
      self._update_degraded_locked()
      quarantined = (before != "open" and after == "open")
      degraded = self._degraded
    if quarantined:
      # A replica leaving the fleet is a post-mortem trigger: the dump
      # carries the spans/faults that tripped the breaker.
      try:
        self._recorder.trigger(
            "replica_quarantined",
            replica=str(self.replicas[index].device),
            degraded=degraded)
      except Exception:
        pass

  def _choose_replica(self, excluded: frozenset) -> tuple:
    """(index, is_probe): a due half-open probe wins (one live request
    reinstates or re-quarantines its replica), else least-loaded over
    the CLOSED replicas, else — fleet fully quarantined — degraded
    least-loaded over everyone not excluded. `excluded` holds replicas
    this request already failed on (retries must actually re-route).
    """
    n = len(self.replicas)
    with self._health_lock:
      now = time.monotonic()
      for i in range(n):
        if i in excluded:
          continue
        breaker = self._breakers[i]
        if breaker.state != "closed" and breaker.allows(now):
          self._health_event("probe", i)
          return i, True
      candidates = [i for i in range(n)
                    if i not in excluded
                    and self._breakers[i].state == "closed"]
      if not candidates:
        # Degraded mode: everything quarantined (or excluded). Keep
        # serving — route over the quarantined fleet minus exclusions
        # and let the SLO machinery shed lowest-priority-first under
        # whatever capacity remains. Non-empty by construction: the
        # initial dispatch excludes nothing and _retry_or_shed only
        # re-dispatches while len(excluded) < n.
        self._update_degraded_locked()
        candidates = [i for i in range(n) if i not in excluded]
    # Least-loaded with the ROTATING tie-break: bare min() resolves
    # every tie to replica 0, hot-spotting one device whenever queues
    # are equal (an idle fleet, or all-full under overload — where it
    # also concentrates every eviction on one replica's queue).
    offset = next(self._rr)
    index = min(
        ((self.replicas[i].batcher.pending(), (i - offset) % n, i)
         for i in candidates),
        key=lambda entry: entry[:2])[2]
    return index, False

  def _dispatch(self, outer: Future, image, seed: int,
                slo: Optional[SLOClass], deadline_at: Optional[float],
                request_id: str, excluded: frozenset,
                retries: int) -> None:
    index, is_probe = self._choose_replica(excluded)
    replica = self.replicas[index]
    with context_lib.bind(request_id=request_id):
      try:
        inner = replica.batcher.submit(
            (image, seed), slo=slo, deadline_at=deadline_at,
            request_id=request_id)
      except Exception as e:
        # Synchronous failure (a dead batcher's DispatcherDead): the
        # same accounting as an async dispatch failure. RuntimeError
        # from a merely-stopped batcher counts too — a stopped replica
        # is as unavailable as a dead one.
        self._record_result(index, ok=False,
                            error=f"{type(e).__name__}: {e}")
        self._retry_or_shed(outer, image, seed, slo, deadline_at,
                            request_id, excluded | {index}, retries, e)
        return
    inner.add_done_callback(
        lambda f: self._on_dispatched(
            f, outer, index, is_probe, image, seed, slo, deadline_at,
            request_id, excluded, retries))

  def _on_dispatched(self, inner: Future, outer: Future, index: int,
                     is_probe: bool, image, seed, slo, deadline_at,
                     request_id, excluded: frozenset,
                     retries: int) -> None:
    try:
      result = inner.result()
    except RequestShed as e:
      # Admission-control sheds are NOT replica faults: the breaker
      # ignores them (an overloaded-but-correct replica must not end
      # up quarantined), and the shed passes through typed. A shed
      # PROBE produced no verdict either way — release the probe slot
      # or the replica stays half-open (and out of the fleet) forever.
      if is_probe:
        with self._health_lock:
          self._breakers[index].release_probe()
      self._resolve_outer(outer, error=e)
      return
    except Exception as e:
      self._record_result(index, ok=False,
                          error=f"{type(e).__name__}: {e}")
      self._retry_or_shed(outer, image, seed, slo, deadline_at,
                          request_id, excluded | {index}, retries, e)
      return
    self._record_result(index, ok=True)
    self._resolve_outer(outer, result=result)

  def _retry_or_shed(self, outer: Future, image, seed, slo, deadline_at,
                     request_id, excluded: frozenset, retries: int,
                     error: Exception) -> None:
    """Deadline-aware retry: re-route only when the remaining slack
    covers one more dispatch AND budget/replicas remain; else resolve
    the client typed (RequestShed "fault") — never a raw exception,
    never a doomed retry returning a dead answer late."""
    n = len(self.replicas)
    remaining_ms = (None if deadline_at is None
                    else (deadline_at - time.perf_counter()) * 1e3)
    slack_ok = (remaining_ms is None
                or remaining_ms >= self.health.retry_cost_ms)
    can_retry = (retries < self.health.max_retries and slack_ok
                 and len(excluded) < n)
    if can_retry:
      try:
        from tensor2robot_tpu.obs import registry as registry_lib
        registry_lib.get_registry().counter("serving/retries").inc()
      except Exception:
        pass
      with self._health_lock:
        self._health_event("retry", None, request_id=request_id,
                          attempt=retries + 1)
      self._dispatch(outer, image, seed, slo, deadline_at, request_id,
                     excluded, retries + 1)
      return
    class_name = slo.name if slo is not None else "default"
    reason_detail = (f"{type(error).__name__}: {error} "
                     f"(retries={retries}, slack_ms="
                     f"{None if remaining_ms is None else round(remaining_ms, 1)})")
    self.stats.record_shed(class_name, "fault")
    try:
      self._recorder.trigger("slo_breach", slo_class=class_name,
                             shed_reason="fault",
                             request_id=request_id)
    except Exception:
      pass
    self._resolve_outer(
        outer, error=RequestShed(class_name, "fault",
                                 detail=reason_detail))

  @staticmethod
  def _resolve_outer(outer: Future, result=None, error=None) -> None:
    if outer.done():
      return  # client cancelled; the answer has no audience
    if not outer.set_running_or_notify_cancel():
      return
    try:
      if error is not None:
        outer.set_exception(error)
      else:
        outer.set_result(result)
    except Exception:
      pass

  def check_q_drift(self) -> dict:
    """The fleet Q-drift guard (ISSUE 15): per-replica served-Q sketch
    medians vs the fleet median (obs/health.q_drift_report under the
    HealthConfig thresholds). A replica turning divergent fires the
    ``replica_divergent`` flightrec trigger, bumps
    ``health/replica_divergent``, and lands a timeline event; one
    recovering (after a fixing hot-swap refilled its sketch) lands a
    ``replica_converged`` event. This is the check that catches a
    corrupted replica or a botched ``set_variables`` that still
    returns finite numbers — no breaker trips, nothing raises, only
    the served VALUES are wrong."""
    from tensor2robot_tpu.obs import health as health_lib

    report = health_lib.q_drift_report(
        self.stats.q_sketch_summaries(),
        z_threshold=self.health.q_drift_z,
        min_samples=self.health.q_drift_min_samples,
        min_scale=self.health.q_drift_min_scale)
    divergent = set(report["divergent"])
    index_of = {str(replica.device): i
                for i, replica in enumerate(self.replicas)}
    with self._health_lock:
      newly = sorted(divergent - self._divergent_replicas)
      recovered = sorted(self._divergent_replicas - divergent)
      self._divergent_replicas = divergent
      for name in newly:
        self._health_event("replica_divergent", index_of.get(name),
                           delta=report["replicas"][name].get("delta"))
      for name in recovered:
        self._health_event("replica_converged", index_of.get(name))
    for name in newly:
      try:
        from tensor2robot_tpu.obs import registry as registry_lib
        registry_lib.get_registry().counter(
            "health/replica_divergent").inc()
      except Exception:
        pass
      try:
        self._recorder.trigger(
            "replica_divergent", replica=name,
            delta=report["replicas"][name].get("delta"),
            fleet_median=report.get("fleet_median"))
      except Exception:
        pass
    return report

  def health_snapshot(self) -> dict:
    """Per-replica breaker states + the transition timeline — the
    chaos artifact's quarantine→probe→reinstate evidence — plus the
    fleet Q-drift verdict (``health`` rolls up to "ok" only when no
    breaker is open AND no replica serves divergent Q-values)."""
    q_drift = self.check_q_drift()
    with self._health_lock:
      snapshot = {
          "replicas": {
              str(replica.device): {
                  "state": breaker.state,
                  "consecutive_failures": breaker.consecutive_failures,
                  "dispatcher_restarts":
                      replica.batcher.dispatcher_restarts,
                  "dispatcher_dead": replica.batcher.dispatcher_dead,
              }
              for replica, breaker in zip(self.replicas, self._breakers)
          },
          "degraded": self._degraded,
          "q_drift": q_drift,
          "timeline": [dict(entry) for entry in self._health_events],
      }
    all_closed = all(entry["state"] == "closed"
                     for entry in snapshot["replicas"].values())
    snapshot["health"] = (
        "ok" if all_closed and q_drift["verdict"] != "divergent"
        else "degraded")
    return snapshot

  def act(self, image, slo: Optional[SLOClass] = None,
          timeout: Optional[float] = None) -> np.ndarray:
    """Blocking control step through the routed fleet."""
    return self.submit(image, slo=slo).result(timeout)

  # -- observability -------------------------------------------------------

  def compile_ledger(self) -> dict:
    """{device_label: {bucket: compile_count}} over every replica — the
    fleet invariant is every inner value == 1 (one executable per
    bucket PER DEVICE, recompiled never). Reads the CURRENT serving
    tier's policies; across a set_precision swap the shared obs
    `ledger` is the cross-tier record (tier-suffixed keys, one row per
    bucket per device per tier, each compiled exactly once)."""
    return {
        str(replica.device): dict(replica.policy.compile_counts)
        for replica in self.replicas}

  def snapshot(self) -> dict:
    """Aggregated stats + the per-device executable ledger + depths."""
    out = self.stats.snapshot()
    out["replicas"] = len(self.replicas)
    out["precision"] = self.precision
    out["compile_ledger"] = self.compile_ledger()
    out["replica_pending"] = [replica.batcher.pending()
                              for replica in self.replicas]
    out["health"] = self.health_snapshot()
    return out

  def write_metrics(self, step: Optional[int] = None) -> None:
    if self._metric_writer is None:
      return
    if step is None:
      step = self._metric_step
      self._metric_step += 1
    self.stats.write_to(self._metric_writer, step)

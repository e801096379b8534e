"""CEMFleetPolicy: the QT-Opt control step batched across clients.

One compiled program per ladder bucket runs the whole fleet control
step — all CEM iterations, scoring through the Q-function, elite
refitting — for up to ``bucket`` clients at once (PAPER.md §3.3 ran the
reference's robot fleets through exactly such a batched session.run).
Where the predictor offers the model's factored pair
(``factored_device_fns``) each frame is encoded once and the search
runs over its code, expanded across its candidate actions inside the
Q function's first convolution over it; where it does not, each frame
is tiled across its candidate actions on the device. Executables are
AOT-compiled once per bucket and keyed on the bucket size only: model
hot-reloads swap the variables *argument*, never the executable, so
serving a fleet for days compiles ``len(ladder)`` programs total.

Per-request determinism: every request carries a uint32 seed; its CEM
key is ``fold_in(key(policy_seed), seed)`` inside the compiled program,
so the action for (image, seed) is independent of flush composition,
batch position, and bucket padding (see cem.fleet_cem_optimize).
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.obs import ledger as ledger_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.research.qtopt import cem
from tensor2robot_tpu.serving import bucketing
from tensor2robot_tpu.serving.bucketing import BucketLadder


# One hold in this many is split into its two waits (`__call__`): the
# wait for the inputs wakes the dispatcher once more inside its hold, on
# a thread that shares the interpreter lock with the other dispatcher's
# stack and put, and cost 1.4-1.8% of the serving rate when every flush
# took it (PERF.md, PR 40). Odd, so that two dispatchers that alternate
# both get their share.
_SPLIT_EVERY = 7


def _end_s(span_record) -> float:
  """When a closed span ended, on the tracer's clock."""
  return span_record["ts_s"] + span_record["dur_s"]


class _StagingPool:
  """Host arrays a flush's frames are stacked into, kept between calls.

  A rung-32 batch of 472x472x3 float32 frames is 85 MB: an array of
  that size made new per flush is mapped, faulted in page by page while
  it is copied into, and unmapped after, on the process's address-space
  lock and beside another dispatcher doing the same. `take` hands out a
  free array of the key or makes one; `give` takes it back. An array
  that is out belongs to its caller alone, so the pool holds as many
  per key as callers were ever inside the policy at once. A caller that
  fails keeps its array from the pool: it is dropped with the call.
  """

  def __init__(self):
    self._lock = threading.Lock()
    # ((bucket, *row shape), dtype) -> [arrays not in use]
    self._free = {}

  def take(self, shape, dtype):
    """(array of `shape` and `dtype`, whether the pool already held
    it)."""
    with self._lock:
      free = self._free.get((shape, dtype))
      if free:
        return free.pop(), True
    return np.empty(shape, dtype), False

  def give(self, array: np.ndarray) -> None:
    with self._lock:
      self._free.setdefault((array.shape, array.dtype), []).append(array)

  def sizes(self):
    """{key: arrays free now}: with no call open, all the pool holds."""
    with self._lock:
      return {key: len(free) for key, free in self._free.items()}


class CEMFleetPolicy:
  """Batched CEM serving policy over any predictor with ``q_predicted``.

  Callable: ``policy(images, seeds=None) -> (n, action_size) actions``,
  n = len(images) <= ladder.max_batch. Without a device-resident entry
  (``predictor.device_fn``) the policy falls back to a host loop that
  pads the request to its ladder bucket once and ships one ``predict``
  call per CEM iteration at that single flat bucket shape.
  """

  def __init__(self, predictor, action_size: int = 4,
               num_samples: int = 64, num_elites: int = 6,
               iterations: int = 3, seed: int = 0,
               ladder: Optional[BucketLadder] = None,
               device=None,
               ledger: Optional[ledger_lib.ExecutableLedger] = None,
               precision: str = "f32",
               param_specs=None):
    """See class docstring. `device` pins this policy's executables and
    inputs to ONE jax.Device — the fleet router's replica placement
    (serving/router.py): each mesh device gets its own policy whose
    ladder compiles exactly once per bucket PER DEVICE, and request
    batches are device_put onto that replica before dispatch — OR one
    jax.sharding.Mesh (ISSUE 16): a tensor-parallel replica GROUP.
    With a Mesh, request batches replicate over the group and the
    served params shard per `param_specs` (the model's partition
    rules), so one critic too wide for a single device serves from a
    group of them; ledger keys carry the group's ``mesh{...}`` label.
    None keeps the default placement (single-chip behavior,
    unchanged).
    `ledger` (optional): an obs.ledger.ExecutableLedger that each
    bucket registers into (cost_analysis joined) and whose dispatch
    wall time the call path records — entries are keyed
    ``cem_bucket_<n>`` plus ``@<device>`` when pinned, so a fleet's
    per-device replicas stay distinct rows.
    `precision` (ISSUE 13) is the Q-scoring tier of every bucket
    executable this policy compiles (cem.SCORING_PRECISIONS). One
    policy serves ONE tier — a fleet running two tiers (the rollout
    harness's bf16 or int8 candidate next to f32 live) builds one
    policy per tier, and the non-f32 ledger keys carry a ``_<tier>``
    suffix (``cem_bucket_4_int8@<device>``) so the fleet ledger proves
    exactly-once compilation PER TIER, not just per bucket. The f32
    default leaves keys and lowering exactly as r10 (the oracle).
    The int8 tier quantizes the served tree at PLACEMENT time
    (`_place`): what each replica keeps resident in HBM is the int8
    weights + per-channel scales — the param-bytes-per-replica
    reduction the TPQUANT artifact measures — and the compiled score
    body only dequantizes per dispatch.
    `param_specs`: optional PartitionSpec pytree over the predictor
    variables' ``params`` subtree, applied only when `device` is a
    Mesh and the served tree is dense (the int8-quantized wrapper tree
    replicates — its bytes are already small)."""
    self._predictor = predictor
    self.precision = cem.validate_precision(precision)
    self.param_specs = param_specs
    self._action_size = action_size
    self._num_samples = num_samples
    self._num_elites = num_elites
    self._iterations = iterations
    self._seed = seed
    self.ladder = ladder or BucketLadder()
    self.device = device
    self._ledger = ledger
    # (id -> (variables, placed)) single-digit cache: the live params
    # plus a rollout candidate sharing this replica's executables. The
    # stored variables ref pins the id (no reuse-after-GC aliasing);
    # re-placement happens once per hot reload, never per request.
    self._placed = {}
    self._executables = {}
    # bucket -> number of compilations; the serving invariant tests
    # assert every value stays exactly 1 for the life of the policy.
    self.compile_counts = {}
    # bucket -> whether its executable encodes each frame once (the
    # predictor offered the factored pair when the bucket compiled).
    self.encode_once = {}
    # bucket -> whether its executable expands each code across its
    # candidates inside the first post convolution: the score the
    # recipe returned when the bucket was traced (cem.MergedRowScore).
    self.expand_in_conv = {}
    # Separate locks: a first-time bucket compile holds _compile_lock
    # for seconds — clients assigning request seeds in submit() must
    # not stall fleet-wide behind it.
    self._compile_lock = threading.Lock()
    self._seed_lock = threading.Lock()
    self._place_lock = threading.Lock()
    # The device turn: one caller at a time between the enqueue of its
    # program and its answer. Concurrent callers (a replica with two
    # flushes open) stack, pad and put side by side and queue here, so
    # the device never holds two programs' temporaries (a rung-32
    # program's are 7 GB of a 16 GB chip). Never contended with one
    # caller at a time.
    self._turn = threading.Lock()
    self._next_seed = 0
    # The device turns taken, and when the last one's hold ended on
    # the tracer's clock (both written under the turn lock): the next
    # program ran no earlier.
    self._holds = 0
    self._hold_end_s = None
    # Where each flush's frames are stacked (see _StagingPool), and
    # whether this thread's last call found its array there and where
    # it spent its device turn: the replica's stats feed reads them
    # back after its own call (router.PolicyReplica._flush), beside
    # the other dispatcher's.
    self._staging = _StagingPool()
    self._last_call = threading.local()

  @property
  def executable_buckets(self) -> Sequence[int]:
    return sorted(self._executables)

  def assign_seeds(self, n: int) -> np.ndarray:
    """n fresh monotonic request seeds (thread-safe)."""
    with self._seed_lock:
      start = self._next_seed
      self._next_seed += n
    return np.arange(start, start + n, dtype=np.uint32)

  def warm(self, make_image) -> None:
    """Compiles the full bucket ladder by scoring `make_image(i)`
    frames at every rung (answers discarded) — THE shared warmup every
    zero-recompile cutover rides: replica startup
    (PolicyReplica.warmup), the fleet tier promotion
    (FleetRouter.set_precision), and a tier-candidate offer
    (RolloutController.offer_precision_candidate). Already-compiled
    buckets make this a no-op walk (the memoized-policy re-offer
    path)."""
    for bucket in self.ladder.sizes:
      self([make_image(i) for i in range(bucket)],
           np.arange(bucket, dtype=np.uint32))

  def __call__(self, images: Sequence[np.ndarray],
               seeds: Optional[Sequence[int]] = None, *,
               variables=None,
               return_scores: bool = False) -> np.ndarray:
    """Control step for `images`. `variables` overrides the predictor's
    live params THROUGH THE SAME compiled executables (params are an
    argument, never baked in) — the rollout controller's shadow path
    scores a candidate checkpoint on this replica's device without
    adding a single entry to the compile ledger.

    return_scores=True (ISSUE 15) additionally returns the selected
    actions' Q-scores as ``(actions, scores)`` — the bucket executable
    already computes them (CEM's final elite-mean score), so the fleet
    Q-drift sketches cost zero extra device work. The host fallback
    has no per-call score readout and returns ``(actions, None)``."""
    n = len(images)
    bucket = self.ladder.bucket_for(n)
    frames = [np.asarray(image) for image in images]
    first = frames[0]
    # Out of the pool until this call's program has consumed it: the
    # runtime reads the array after device_put has returned, and on the
    # CPU backend the device array may be the host memory itself. A
    # call that raises never gives it back.
    staged, reused = self._staging.take((bucket,) + first.shape,
                                        first.dtype)
    self._last_call.reused, self._last_call.phases = reused, None
    with trace_lib.span("serve/stack", rows=n, bytes=n * first.nbytes,
                        reused=int(reused)):
      if any(frame.dtype != first.dtype for frame in frames):
        raise ValueError(
            "all input arrays must have the same dtype, got "
            f"{sorted({str(frame.dtype) for frame in frames})}")
      np.stack(frames, out=staged[:n])
      seeds = (self.assign_seeds(n) if seeds is None
               else np.asarray(seeds, np.uint32))
      if seeds.shape != (n,):
        raise ValueError(f"need {n} seeds, got shape {seeds.shape}")
    try:
      fn, live_variables = self._predictor.device_fn()
    except NotImplementedError:
      if variables is not None:
        raise ValueError(
            "variables override requires the predictor's device path "
            "(the host fallback scores through predictor.predict, whose "
            "params cannot be swapped per call).")
      fn = None  # the host fallback, once the rows are padded
    else:
      variables = self._place(
          live_variables if variables is None else variables)
    with trace_lib.span("serve/pad", bucket=bucket):
      padded_seeds = seeds
      if n < bucket:  # bucketing.pad_to's rule, the frames' in place
        staged[n:] = staged[n - 1]
        padded_seeds = bucketing.pad_to(seeds, bucket)
    if fn is None:
      with self._turn:  # predictor.predict was never called concurrently
        actions = self._host_call(staged, padded_seeds)[:n]
      self._staging.give(staged)
      return (actions, None) if return_scores else actions
    # A compile or a weight upload (above) lies outside serve/put: it
    # must not read as H2D.
    compiled = self._executable_for(bucket, fn, variables, staged,
                                    padded_seeds)
    # Returns once the runtime has the transfer: its threads re-lay the
    # frames out and copy them while this thread goes on.
    with trace_lib.span("serve/put",
                        bytes=staged.nbytes + padded_seeds.nbytes) as put:
      device_images = self._put(staged)
      device_seeds = self._put(padded_seeds)
    # The wait for another caller's program, if one is on the device:
    # the transfer above goes on beside it.
    with trace_lib.span("serve/turn", bucket=bucket) as turn:
      self._turn.acquire()
    try:
      # Whether that transfer was over when the turn came (hidden under
      # the other flush's turn): set on the closed record, which the
      # ring keeps.
      turn["landed"] = int(device_images.is_ready())
      split = self._holds % _SPLIT_EVERY == 0
      self._holds += 1
      # Returns at enqueue.
      with trace_lib.span("serve/execute", bucket=bucket,
                          encode_once=int(self.encode_once[bucket]),
                          expand_in_conv=int(self.expand_in_conv[bucket])
                          ) as execute:
        actions, scores = compiled(variables, device_images, device_seeds)
      # The wait for that transfer and for the device, then D2H.
      with trace_lib.span("serve/readback") as readback:
        if split:
          # The hold, split on the thread that is blocked in it: what
          # of this flush's transfer the enqueued program still had to
          # wait for (the inputs are not donated: they are alive to
          # wait on), then the program with the actions' D2H; what is
          # left of the span is the scores' D2H.
          with trace_lib.span("serve/transfer_wait", bucket=bucket,
                              bytes=staged.nbytes + padded_seeds.nbytes
                              ) as transfer_wait:
            jax.block_until_ready((device_images, device_seeds))
          # One blocking call waits for the program and copies its
          # actions out, as in a hold that is not split: a wait of its
          # own for the program would hand the interpreter lock away
          # once more between this program's end and the next enqueue.
          with trace_lib.span("serve/program_wait",
                              bucket=bucket) as program_wait:
            actions = np.asarray(actions)[:n]
          # The program's time on the device (and its actions' way
          # back), by the spans' own clock reads: it ran from the
          # latest of its frames having landed, its enqueue and this
          # policy's previous hold having ended (which the turn lock
          # puts before the enqueue today).
          readback["device_ms"] = round(1e3 * (_end_s(program_wait) - max(
              _end_s(transfer_wait), execute["ts_s"], self._hold_end_s or 0.0
              )), 3)
        else:
          actions = np.asarray(actions)[:n]
        scores = np.asarray(scores)[:n]
      phases = {"turn_wait_ms": round(1e3 * turn["dur_s"], 3),
                "landed": turn["landed"]}
      if self._hold_end_s is not None:  # from hold's end to hold's end
        phases["period_ms"] = round(
            1e3 * (_end_s(readback) - self._hold_end_s), 3)
      self._hold_end_s = _end_s(readback)
      if split:
        phases.update(
            transfer_wait_ms=round(1e3 * transfer_wait["dur_s"], 3),
            program_ms=readback["device_ms"])
    finally:
      self._turn.release()
    # The program has run, so the transfer it waited for is over.
    self._staging.give(staged)
    self._last_call.phases = phases
    if self._ledger is not None:
      # Dispatch through completion, on the spans' own clock reads.
      self._ledger.record_dispatch(
          self._ledger_key(bucket), _end_s(readback) - put["ts_s"])
    return (actions, scores) if return_scores else actions

  @property
  def last_call_reused_staging(self) -> bool:
    """Whether the calling thread's last call stacked its frames into
    an array the pool already held (its `serve/stack` span's
    `reused`)."""
    return getattr(self._last_call, "reused", False)

  @property
  def last_call_phases(self) -> Optional[dict]:
    """Where the calling thread's last call spent its device turn, by
    its spans' clock reads: `turn_wait_ms` and `landed` (the
    `serve/turn` span's), `period_ms` (from the end of this policy's
    previous hold to the end of this one; not on its first) and, on the
    one hold in `_SPLIT_EVERY` that was split, `transfer_wait_ms`
    (`serve/transfer_wait`) and `program_ms` (the `serve/readback`
    span's `device_ms`). None after a call that took the host fallback
    or raised."""
    return getattr(self._last_call, "phases", None)

  @property
  def device_label(self) -> Optional[str]:
    """The ledger/registry label for this policy's placement: the
    device's own name, or ``mesh{axis: size}`` for a tensor-parallel
    replica group (a Mesh's repr is too verbose for a row key)."""
    if self.device is None:
      return None
    if isinstance(self.device, jax.sharding.Mesh):
      return f"mesh{dict(self.device.shape)}"
    return str(self.device)

  def _ledger_key(self, bucket: int) -> str:
    tier = f"_{self.precision}" if self.precision != "f32" else ""
    suffix = (f"@{self.device_label}" if self.device is not None else "")
    return f"cem_bucket_{bucket}{tier}{suffix}"

  # -- device placement ----------------------------------------------------

  def _put(self, array):
    if self.device is None:
      return jnp.asarray(array)
    if isinstance(self.device, jax.sharding.Mesh):
      from tensor2robot_tpu.parallel import mesh as mesh_lib
      # Request batches replicate over the replica group: every group
      # member scores the full bucket, with the model-axis split living
      # in the params (XLA partitions the matmuls, not the batch).
      return jax.device_put(array, mesh_lib.replicated_sharding(self.device))
    return jax.device_put(array, self.device)

  def _place(self, variables):
    """Device-placed (and, for int8, quantized) view of a variables
    pytree, cached per identity.

    Without a pinned device this is a no-op (jit moves host trees under
    the default placement exactly as before). With one, the tree is
    device_put ONCE per distinct params object: the live params after
    each hot reload, plus at most a rollout candidate — so a replica
    never re-uploads weights per request, and a param refresh costs one
    transfer, zero compiles. The int8 tier quantizes HERE, before the
    transfer, so what a replica keeps resident is the int8 tree (the
    HBM reduction is per replica, not just per dispatch) — the cast
    boundary inside the executable is idempotent on it. A Mesh device
    places dense trees per `param_specs` (params subtree sharded over
    the group's model axis, everything else replicated).
    """
    if self.device is None:
      return variables
    key = id(variables)
    with self._place_lock:
      entry = self._placed.get(key)
      if entry is not None and entry[0] is variables:
        return entry[1]
      if len(self._placed) >= 4:  # live + candidate + their priors
        self._placed.clear()
      to_place = (cem.cast_scoring_variables(variables, "int8")
                  if self.precision == "int8" else variables)
      placed = self._put_variables(to_place)
      self._placed[key] = (variables, placed)
      return placed

  def _put_variables(self, variables):
    if not isinstance(self.device, jax.sharding.Mesh):
      return jax.device_put(variables, self.device)
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.parallel import tp_rules
    replicated = mesh_lib.replicated_sharding(self.device)
    if (self.param_specs is None or cem.is_quantized_variables(variables)
        or not isinstance(variables, dict) or "params" not in variables):
      return jax.device_put(variables, replicated)
    placed = {key: jax.device_put(value, replicated)
              for key, value in variables.items() if key != "params"}
    placed["params"] = jax.device_put(
        variables["params"],
        tp_rules.specs_to_shardings(self.param_specs, self.device))
    return placed

  # -- compiled path -------------------------------------------------------

  def _build_control(self, fn, fns):
    """(variables, (B,...) images, (B,) seeds) → ((B, A) actions,
    (B,) selected-action Q-scores). The scores are CEM's own final
    readout — already computed inside the search — returned so the
    serving layer's per-replica Q sketches (the fleet drift guard,
    ISSUE 15) ride the same dispatch instead of a second forward.
    `fns` is the predictor's factored pair or None."""
    num_samples = self._num_samples

    def control(variables, images, seeds):
      base = jax.random.key(self._seed)
      keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(seeds)

      # One client's state is its frame or, with the pair, the frame's
      # code, encoded here for the whole bucket: outside the CEM loop.
      # The score expands that state across the client's candidate
      # actions: one (B*num_samples) Q call per CEM iteration — the
      # Podracer-style batched on-device step — reached through a
      # vmap over the clients where frames are tiled, written on the
      # merged row axis where codes are (cem.MergedRowScore), which
      # is noted as the bucket is traced. Shared with the
      # Bellman updater's target max (same wire contract, by
      # construction).
      # The scoring tier is part of the compiled program (params
      # quantize inside the executable), so a hot reload stays one
      # device_put, zero recompiles, any tier.
      states, score = cem.make_cem_states_and_score(
          fn, fns, variables, images, precision=self.precision)
      self.expand_in_conv[images.shape[0]] = isinstance(
          score, cem.MergedRowScore)

      best, best_scores = cem.fleet_cem_optimize(
          score, states, keys, self._action_size,
          num_samples=num_samples, num_elites=self._num_elites,
          iterations=self._iterations, precision=self.precision)
      return best, best_scores

    return control

  def _executable_for(self, bucket, fn, variables, padded, padded_seeds):
    with self._compile_lock:
      compiled = self._executables.get(bucket)
      if compiled is None:
        fns = self._predictor.factored_device_fns()
        with trace_lib.span("serve/compile", bucket=bucket,
                            encode_once=int(fns is not None)):
          lowered = jax.jit(self._build_control(fn, fns)).lower(
              variables, self._put(padded), self._put(padded_seeds))
          compiled = lowered.compile()
        self._executables[bucket] = compiled
        self.encode_once[bucket] = fns is not None
        self.compile_counts[bucket] = (
            self.compile_counts.get(bucket, 0) + 1)
        if self._ledger is not None:
          self._ledger.register(
              self._ledger_key(bucket), compiled=compiled,
              device=self.device_label, dtype=self.precision,
              shapes={"bucket": bucket,
                      "num_samples": self._num_samples,
                      "iterations": self._iterations})
    return compiled

  # -- host fallback -------------------------------------------------------

  def _host_call(self, batch: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """predict()-based fleet CEM: mirrors cem_optimize's sampling per
    state (same fold_in sequence), so host and device paths agree the
    way CEMPolicy's do.

    Shape discipline (ISSUE 5 satellite): `batch` and `seeds` come
    padded to their ladder bucket ONCE, before the CEM loop (__call__'s
    staging array; an exact-fit batch, n already a ladder rung, with
    ZERO padding work), and the bucket's rows are returned: every
    per-iteration scoring call carries the same (bucket * num_samples)
    flat shape, so predict() sees exactly one flat shape per bucket
    (the executable count stays ladder-bounded).
    The old path re-derived a power-of-two bucket for the flat batch
    inside predict_batched on EVERY CEM iteration, re-padding and
    re-slicing the tiled image stack each time even when the request
    count already fit a bucket exactly.
    """
    if self.precision != "f32":
      # Satellite fix (ISSUE 16): name the requested tier AND the
      # supported set, mirroring cem.validate_precision — "which tiers
      # exist" must not require a second error round-trip.
      raise ValueError(
          f"scoring precision {self.precision!r} requires the "
          "predictor's device path (device_fn): the host fallback "
          "scores through predictor.predict, whose compute dtype "
          "cannot be retiered per policy. Of the supported tiers "
          f"{cem.SCORING_PRECISIONS} only 'f32' can serve host-side — "
          "serve the f32 tier, or use a device-resident predictor.")
    num = self._num_samples
    b = batch.shape[0]
    base = jax.random.key(self._seed)
    keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(
        jnp.asarray(seeds))
    mean = jnp.zeros((b, self._action_size), jnp.float32)
    std = jnp.full((b, self._action_size), 0.5, jnp.float32)
    tiled = np.repeat(batch, num, axis=0)
    refit = jax.vmap(cem._refit, in_axes=(0, 0, None))
    for i in range(self._iterations):
      step_keys = jax.vmap(lambda k: jax.random.fold_in(k, i))(keys)
      noise = jax.vmap(
          lambda k: jax.random.normal(k, (num, self._action_size)))(
              step_keys)
      samples = jnp.clip(mean[:, None] + std[:, None] * noise, -1.0, 1.0)
      outputs = self._predictor.predict({
          "image": tiled,
          "action": np.asarray(samples, np.float32).reshape(b * num, -1)})
      scores = jnp.asarray(outputs["q_predicted"]).reshape(b, num)
      mean, std = refit(samples, scores, self._num_elites)
    return np.asarray(jnp.clip(mean, -1.0, 1.0))

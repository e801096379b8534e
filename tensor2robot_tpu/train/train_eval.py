"""train_eval_model — the single configured entry point.

Reference parity: utils/train_eval.py §train_eval_model (SURVEY.md §2,
§3.1/§3.2): wire input generators to the model's specs, build the
execution engine (Trainer over a mesh instead of (TPU)Estimator), run
train with interleaved eval, checkpoint on an interval and resume from
the latest on restart, drive hooks (async export), write metrics, dump
the operative config for reproducibility.

Host-loop design (TPU-first):
  - The step is dispatched asynchronously; the loop only syncs (pulls
    metrics to host) every `log_every_steps`, so device utilization is
    not gated on Python. In-flight dispatch is bounded by the sync
    cadence — an unbounded queue would just buffer stale batches.
  - Input batches ride `prefetch_to_device` under the trainer's batch
    sharding: H2D DMA for step N+1 overlaps compute for step N — the
    infeed-queue behaviour of TPUEstimator without infeed machinery.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from tensor2robot_tpu import modes
from tensor2robot_tpu.config import configurable, operative_config_str
from tensor2robot_tpu.data.prefetch import prefetch_to_device
from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder
from tensor2robot_tpu.obs import registry as registry_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.train.checkpoints import CheckpointManager
from tensor2robot_tpu.train.trainer import Trainer
from tensor2robot_tpu.train.train_state import TrainState
from tensor2robot_tpu.utils.metric_writer import MetricWriter

_log = logging.getLogger(__name__)


def _emit_metrics(metric_writer, step: int, scalars) -> None:
  """Trainer metrics go THROUGH the process-wide obs registry (gauges),
  then the one registry→MetricWriter bridge flushes exactly this block
  — the JSONL/TB records keep their schema, and the same series is
  readable process-wide (obs bench, flight-recorder context)."""
  registry = registry_lib.get_registry()
  registry.set_gauges(scalars)
  registry.flush_to(metric_writer, step, names=scalars.keys())


class _PreemptionGuard:
  """SIGTERM/SIGINT → finish the current loop iteration, checkpoint,
  exit cleanly (TPU-pod preemption notice; the reference's only story
  was losing everything since the last CheckpointSaverHook save).

  Installed only on the main thread and only for the duration of the
  train loop; prior handlers are restored on exit. Second signal falls
  through to the previous handler (so a double Ctrl-C still kills)."""

  def __init__(self, enabled: bool = True):
    self._enabled = enabled
    self.requested = False
    self._previous = {}

  def __enter__(self):
    if not self._enabled:
      return self
    import signal
    import threading
    if threading.current_thread() is not threading.main_thread():
      return self  # signal.signal is main-thread-only; run unguarded

    def handler(signum, frame):
      if self.requested:  # second signal: defer to the original handler
        previous = self._previous.get(signum)
        if callable(previous):
          previous(signum, frame)
          return
        raise KeyboardInterrupt
      self.requested = True
      _log.warning(
          "Signal %d received: checkpointing at the next loop boundary "
          "and exiting.", signum)

    for signum in (signal.SIGTERM, signal.SIGINT):
      try:
        self._previous[signum] = signal.signal(signum, handler)
      except (ValueError, OSError):  # non-main interpreter contexts
        pass
    return self

  def __exit__(self, *exc):
    import signal
    for signum, previous in self._previous.items():
      signal.signal(signum, previous)
    self._previous = {}
    return False

  def globally_requested(self) -> bool:
    """Whether ANY host has seen a signal — collectively agreed, so
    every host leaves the train loop at the SAME step boundary (a
    lone host exiting early would deadlock the others' collectives).
    Call at synchronized points only (all hosts, same step)."""
    if jax.process_count() == 1:
      return self.requested
    from jax.experimental import multihost_utils
    flag = multihost_utils.process_allgather(
        np.asarray(1 if self.requested else 0, np.int32))
    agreed = bool(np.max(flag))
    if agreed:
      self.requested = True
    return agreed


def _init_exporters(create_exporters_fn, model, model_dir: str):
  """Builds and binds eval-driven exporters; rejects root collisions."""
  if create_exporters_fn is None:
    return []
  exporters = list(create_exporters_fn(model))
  roots = set()
  for exporter in exporters:
    exporter.begin(model, model_dir)
    root = os.path.abspath(exporter.export_root)
    if root in roots:
      raise ValueError(
          f"Two exporters publish to the same root {root!r}; give them "
          "distinct names.")
    roots.add(root)
  return exporters


def _run_exporters_after_eval(exporters, state, eval_metrics) -> None:
  """Drives exporters with a lazy variables provider: the device→host
  transfer happens at most once, and only if a policy publishes."""
  if not exporters:
    return
  from tensor2robot_tpu.export.exporters import run_exporters
  from tensor2robot_tpu.export import export_utils
  run_exporters(
      exporters,
      lambda: export_utils.fetch_variables_to_host(
          state.variables(use_ema=True)),
      int(state.step), eval_metrics)


@dataclasses.dataclass
class TrainEvalResult:
  state: TrainState
  train_metrics: Dict[str, float]
  eval_metrics: Dict[str, float]
  model_dir: Optional[str]


@configurable
def train_eval_model(
    model,
    input_generator_train=None,
    input_generator_eval=None,
    max_train_steps: int = 1000,
    eval_steps: int = 10,
    eval_interval_steps: int = 0,
    model_dir: Optional[str] = None,
    save_checkpoints_steps: int = 0,
    keep_checkpoint_max: int = 5,
    export_generator=None,
    export_keep: int = 5,
    create_exporters_fn=None,
    hook_builders: Sequence[HookBuilder] = (),
    mesh=None,
    seed: int = 0,
    log_every_steps: int = 100,
    iterations_per_loop: int = 1,
    gradient_accumulation_steps: int = 1,
    prefetch_depth: int = 2,
    handle_preemption: bool = True,
    param_specs=None,
    shard_optimizer_state: bool = False,
    fsdp: bool = False,
    fsdp_min_size: int = 4096,
) -> TrainEvalResult:
  """Trains (and optionally evaluates/exports) `model`.

  Args mirror the reference's train_eval_model:
    max_train_steps: total global steps (resume-aware: counts from the
      restored step, like Estimator max_steps).
    eval_steps: eval batches per evaluation.
    eval_interval_steps: interleave eval every N train steps (0 = only a
      final eval if an eval generator is given).
    save_checkpoints_steps: checkpoint cadence (0 = only final).
    handle_preemption: trap SIGTERM/SIGINT during the train loop and
      exit through the normal final-checkpoint path at the next loop
      boundary, so a preempted run resumes exactly where it stopped.
    export_generator: exported at end; pair with AsyncExportHookBuilder
      for continuous exports.
    create_exporters_fn: model -> [export.exporters.Exporter]; each runs
      after every evaluation (LatestExporter/BestExporter policies — the
      reference's EvalSpec exporters).
    iterations_per_loop: steps fused into one compiled lax.scan dispatch
      (TPUConfig(iterations_per_loop)). Logging/checkpoint/eval cadences
      then fire at the first loop boundary that crosses their multiple.
    gradient_accumulation_steps: microbatches averaged into each
      optimizer step (Trainer.train_step_accum): effective batch =
      K × batch_size in one microbatch's activation memory. Each global
      step then consumes K generator batches. Mutually exclusive with
      iterations_per_loop > 1.
    param_specs: tensor-parallel parameter shardings (see
      Trainer/parallel.tp_rules); None = replicated params.
    shard_optimizer_state: ZeRO-1 weight-update sharding (see Trainer).
    fsdp: derive FSDP/ZeRO-3 parameter shardings from the model
      automatically (parallel.tp_rules.infer_fsdp_specs_from_model) —
      the config-file way to turn on fully-sharded training. Mutually
      exclusive with an explicit param_specs.
    fsdp_min_size: smallest parameter (elements) worth sharding under
      fsdp; smaller leaves stay replicated.
  """
  if fsdp:
    if param_specs is not None:
      raise ValueError("Pass either fsdp=True or explicit param_specs, "
                       "not both.")
    if shard_optimizer_state:
      raise ValueError(
          "fsdp=True already shards optimizer state with the params "
          "(ZeRO-3 subsumes ZeRO-1); drop shard_optimizer_state.")
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.parallel import tp_rules
    if mesh is None:
      mesh = mesh_lib.create_mesh()
    param_specs = tp_rules.infer_fsdp_specs_from_model(
        model, mesh, min_size=fsdp_min_size)
  trainer = Trainer(model, mesh=mesh, seed=seed, param_specs=param_specs,
                    shard_optimizer_state=shard_optimizer_state)
  state = trainer.create_train_state()

  # Side-effect ownership on multi-host (the reference's chief-worker
  # rule): checkpointing is ALL-process (orbax coordinates per-shard
  # writes and needs every host to participate); metric/event files and
  # the operative config are written by the primary only — N hosts
  # appending to the same files on shared storage interleave/corrupt
  # them. Export paths run on ALL hosts (their variable fetch is a
  # cross-process collective for sharded params); the file writes are
  # chief-gated inside export_utils.export_and_gc.
  from tensor2robot_tpu.parallel import distributed
  primary = distributed.is_primary()

  checkpoint_manager = None
  metric_writer = None
  if model_dir:
    os.makedirs(model_dir, exist_ok=True)
    checkpoint_manager = CheckpointManager(
        os.path.join(model_dir, "checkpoints"),
        max_to_keep=keep_checkpoint_max,
        save_interval_steps=save_checkpoints_steps)
    if checkpoint_manager.latest_step() is not None:
      state = checkpoint_manager.restore(state)
      _log.info("Resumed from step %d", int(state.step))
    if primary:
      metric_writer = MetricWriter(model_dir)
      with open(os.path.join(model_dir, "operative_config.txt"), "w") as f:
        f.write(operative_config_str())

  hooks: List[Hook] = []
  for builder in hook_builders:
    hooks.extend(builder.create_hooks(trainer, model_dir or ""))
  for hook in hooks:
    hook.begin(trainer, state, model_dir or "")

  exporters = _init_exporters(create_exporters_fn, model, model_dir or "")

  train_metrics: Dict[str, float] = {}
  eval_metrics: Dict[str, float] = {}

  def run_eval(state: TrainState) -> Dict[str, float]:
    if input_generator_eval is None:
      return {}
    metrics, images = _evaluate(trainer, model, input_generator_eval,
                                state, eval_steps, prefetch_depth)
    if metric_writer and images:
      metric_writer.write_images(
          int(state.step),
          {f"eval/{k}": v for k, v in images.items()})
    _run_exporters_after_eval(exporters, state, metrics)
    return metrics

  if iterations_per_loop < 1:
    raise ValueError(f"iterations_per_loop must be >= 1, got "
                     f"{iterations_per_loop}")
  if gradient_accumulation_steps < 1:
    raise ValueError(f"gradient_accumulation_steps must be >= 1, got "
                     f"{gradient_accumulation_steps}")
  if gradient_accumulation_steps > 1 and iterations_per_loop > 1:
    raise ValueError(
        "gradient_accumulation_steps and iterations_per_loop are mutually "
        "exclusive: one trades memory for compute, the other fuses "
        "dispatches — accumulate inside a scanned loop is not supported.")

  # The guard stays armed through the final checkpoint + close():
  # a signal landing during the save must not restore a default handler
  # that kills the writer mid-file. Second signal still force-kills.
  preemption = _PreemptionGuard(
      enabled=(handle_preemption and input_generator_train is not None
               and max_train_steps > 0))
  preemption.__enter__()
  single_host = jax.process_count() == 1
  try:
    if input_generator_train is not None and max_train_steps > 0:
      input_generator_train.set_specification_from_model(model, modes.TRAIN)
      host_iter = input_generator_train.create_dataset_fn(modes.TRAIN)()
      pipeline_stats = getattr(input_generator_train, "pipeline_stats",
                               None)
      if pipeline_stats:
        # Surfaces the native/python auto-calibration decision (record
        # generators) where an operator reading the run log looks first.
        _log.info("train input pipeline: %s", pipeline_stats)
      start_step = int(state.step)
      if iterations_per_loop > 1 or gradient_accumulation_steps > 1:
        # Both modes feed (K, batch, ...) stacks; they differ only in K
        # and in how many generator batches one global step consumes:
        # scan advances K steps per stack, accumulation folds K
        # microbatches into one step (so total batches = steps × K, and
        # every stack is full-K — one compiled executable).
        from tensor2robot_tpu.parallel import mesh as mesh_lib
        if iterations_per_loop > 1:
          stack_size, total = (iterations_per_loop,
                               max_train_steps - start_step)
        else:
          stack_size = gradient_accumulation_steps
          total = (max_train_steps - start_step) * stack_size
        train_iter = prefetch_to_device(
            _stack_batches(host_iter, stack_size, total),
            sharding=mesh_lib.stacked_batch_sharding(
                trainer.mesh, trainer.data_axis),
            depth=prefetch_depth)
      else:
        train_iter = prefetch_to_device(
            host_iter, sharding=trainer.batch_sharding, depth=prefetch_depth)

      step = start_step
      pending_metrics = None
      # Bound async dispatch: a deep queue of un-synced steps buys nothing
      # (the device is saturated after ~2) and on CPU-mesh test hosts it
      # can starve XLA's in-process collective rendezvous.
      import collections
      max_inflight = max(2, prefetch_depth)
      inflight = collections.deque()

      def crossed(cadence: int, prev: int, now: int) -> bool:
        return cadence > 0 and now // cadence > prev // cadence

      while step < max_train_steps and not (single_host
                                            and preemption.requested):
        features, labels = next(train_iter)
        if iterations_per_loop > 1:
          state, pending_metrics = trainer.train_steps(state, features, labels)
          advanced = jax.tree_util.tree_leaves(features)[0].shape[0]
        elif gradient_accumulation_steps > 1:
          state, pending_metrics = trainer.train_step_accum(
              state, features, labels)
          advanced = 1
        else:
          state, pending_metrics = trainer.train_step(state, features, labels)
          advanced = 1
        prev_step, step = step, step + advanced
        inflight.append(pending_metrics["loss"])
        if len(inflight) > max_inflight:
          inflight.popleft().block_until_ready()

        if crossed(log_every_steps, prev_step, step) or step == max_train_steps:
          with trace_lib.span("train/readback", step=step):
            # Scalars only: a model may also report arrays (an expert
            # layer's per-expert counts), which no scalar writer takes.
            host_metrics = {k: float(v) for k, v in pending_metrics.items()
                            if np.ndim(v) == 0}
          train_metrics = host_metrics
          if metric_writer:
            _emit_metrics(metric_writer, step, host_metrics)
          for hook in hooks:
            hook.after_step(state, host_metrics)
          _log.info("step %d: %s", step, host_metrics)

        # Multi-host preemption agreement: every host reaches this sync
        # boundary at the same step, so the collective decision makes all
        # hosts leave the loop together (a lone early exit would deadlock
        # the others' all-reduces).
        if not single_host and crossed(log_every_steps, prev_step, step):
          if preemption.globally_requested():
            break

        if checkpoint_manager and checkpoint_manager.should_save(
            step, last_step=prev_step):
          with trace_lib.span("train/checkpoint", step=step):
            checkpoint_manager.save(step, state)
          for hook in hooks:
            hook.after_checkpoint(step, state)

        if (crossed(eval_interval_steps, prev_step, step)
            and step < max_train_steps):
          eval_metrics = run_eval(state)
          if metric_writer and eval_metrics:
            _emit_metrics(
                metric_writer, step,
                {f"eval/{k}": v for k, v in eval_metrics.items()})
      if preemption.requested:
        _log.warning("Preempted at step %d; final checkpoint below is the "
                     "resume point.", step)

    # Final checkpoint (also the resume point for a follow-on run).
    if checkpoint_manager:
      final_step = int(state.step)
      if checkpoint_manager.latest_step() != final_step:
        with trace_lib.span("train/checkpoint", step=final_step):
          checkpoint_manager.save(final_step, state, force=True)
        for hook in hooks:
          hook.after_checkpoint(final_step, state)

    final_eval = run_eval(state)
    if final_eval:
      eval_metrics = final_eval
      if metric_writer:
        _emit_metrics(
            metric_writer, int(state.step),
            {f"eval/{k}": v for k, v in eval_metrics.items()})

    if export_generator is not None:
      from tensor2robot_tpu.export import export_utils
      export_utils.resolve_export_root(export_generator, model_dir)
      if any(os.path.abspath(e.export_root)
             == os.path.abspath(export_generator.export_root)
             for e in exporters):
        raise ValueError(
            f"export_generator and an eval exporter both publish to "
            f"{export_generator.export_root!r}; their GC policies would "
            "delete each other's versions. Give the exporter a different "
            "name or drop one of the two.")
      export_generator.set_specification_from_model(model)
      # Fetch on every host (collective for sharded params); the write
      # inside export_and_gc is primary-only (returns None elsewhere).
      export_dir = export_utils.export_and_gc(
          export_generator,
          export_utils.fetch_variables_to_host(
              state.variables(use_ema=True)),
          keep=export_keep, global_step=int(state.step))
      if export_dir is not None:
        _log.info("Exported final model to %s", export_dir)

    for hook in hooks:
      hook.end(state)
    if checkpoint_manager:
      checkpoint_manager.close()
    if metric_writer:
      metric_writer.close()

  finally:
    preemption.__exit__()

  return TrainEvalResult(
      state=state,
      train_metrics=train_metrics,
      eval_metrics=eval_metrics,
      model_dir=model_dir,
  )


def _stack_batches(host_iter, iterations_per_loop: int, total_steps: int):
  """Groups single host batches into (K, batch, ...) stacks for the
  scanned multi-step. All full-size stacks except possibly one final
  partial stack covering the remaining steps (that one compiles a second
  executable — unavoidable when total_steps % K != 0)."""
  remaining = total_steps
  while remaining > 0:
    size = min(iterations_per_loop, remaining)
    batches = [next(host_iter) for _ in range(size)]
    remaining -= size
    yield jax.tree_util.tree_map(
        lambda *leaves: np.stack(leaves), *batches)


def _evaluate(trainer, model, input_generator_eval, state,
              eval_steps: int, prefetch_depth: int):
  """Averages eval metrics over eval_steps batches (shared by the
  interleaved eval arm and the continuous evaluator).

  Returns (metrics, image_summaries): images from the model's optional
  model_image_summaries_fn rendered on the last eval batch ({} when the
  model declares none)."""
  input_generator_eval.set_specification_from_model(model, modes.EVAL)
  eval_iter = prefetch_to_device(
      input_generator_eval.create_dataset_fn(modes.EVAL)(),
      sharding=trainer.batch_sharding, depth=prefetch_depth)
  sums: Dict[str, float] = {}
  count = 0
  last_features = None
  for _, batch in zip(range(eval_steps), eval_iter):
    features, labels = batch
    metrics = trainer.eval_step(state, features, labels)
    for key, value in metrics.items():
      if np.ndim(value) == 0:
        sums[key] = sums.get(key, 0.0) + float(value)
    count += 1
    last_features = features
  metrics = {key: value / max(count, 1) for key, value in sums.items()}
  images = {}
  if last_features is not None:
    rendered = model.model_image_summaries_fn(
        state.variables(use_ema=True), last_features)
    if rendered:
      images = dict(rendered)
  return metrics, images


# Errors a FOLLOWER can see for a step that exists in the primary's
# broadcast view but is not yet (fully) visible on this host's shared
# storage: FileNotFoundError for a missing step dir, plus the
# ValueError/OSError orbax raises on a half-visible dir whose metadata
# has not finished replicating (ADVICE r3: catching only
# FileNotFoundError failed the eval job on first hit of those). The
# retry is bounded, so a genuinely corrupt checkpoint still raises
# after _RESTORE_ATTEMPTS. FileNotFoundError ⊂ OSError; listed for the
# reader.
_RESTORE_RETRY_EXCEPTIONS = (FileNotFoundError, ValueError, OSError)
_RESTORE_ATTEMPTS = 5


def _restore_with_retry(checkpoint_manager, template, step: int,
                        multi_host: bool, sleep_fn=time.sleep):
  """Restores `step`, re-listing with bounded backoff on a follower.

  Multi-host continuous eval: the pending-step list is the primary's
  broadcast view — the sync exists precisely because per-host directory
  listings lag on shared storage, so a follower may be told about a
  step its own filesystem view doesn't show yet. Single-host (or final
  attempt), every error propagates: there is no other writer whose
  lagging visibility a wait could fix.
  """
  for attempt in range(_RESTORE_ATTEMPTS):
    try:
      return checkpoint_manager.restore(template, step=step)
    except _RESTORE_RETRY_EXCEPTIONS as e:
      if not multi_host or attempt == _RESTORE_ATTEMPTS - 1:
        raise
      # repr(e) in the log (ADVICE r4): a PERMANENT error misclassified
      # as lag (wrong template structure/dtype) must be diagnosable from
      # the first attempt's line, not after 5 backoffs re-raise it.
      _log.info(
          "continuous eval: step %d not (fully) visible yet on this "
          "host (attempt %d, %r); re-listing after backoff", step,
          attempt + 1, e)
      sleep_fn(min(2.0 ** attempt, 10.0))
      checkpoint_manager.reload()
  raise AssertionError("unreachable: loop returns or raises")


@configurable
def continuous_eval_model(
    model,
    input_generator_eval,
    model_dir: str,
    eval_steps: int = 10,
    poll_interval_s: float = 10.0,
    timeout_s: float = 3600.0,
    stop_after_step: int = 0,
    max_evaluations: int = 0,
    create_exporters_fn=None,
    mesh=None,
    seed: int = 0,
    prefetch_depth: int = 2,
    param_specs=None,
    shard_optimizer_state: bool = False,
) -> Dict[int, Dict[str, float]]:
  """Separate-job evaluator: evaluate every checkpoint as it lands.

  Reference parity: the continuous-evaluation arm of SURVEY.md §3.2 — a
  dedicated eval job polling the trainer's model_dir, evaluating each
  new checkpoint (EMA-swapped via state.variables semantics baked into
  eval_step) and writing `eval/*` metrics under <model_dir>/eval for
  TensorBoard.

  Stops when: no new checkpoint appears within `timeout_s`; a
  checkpoint at step >= `stop_after_step` (if > 0) has been evaluated
  (the trainer is done); or `max_evaluations` (if > 0) checkpoints have
  been evaluated.

  Returns {checkpoint_step: eval metrics} for every evaluated step.
  """
  trainer = Trainer(model, mesh=mesh, seed=seed, param_specs=param_specs,
                    shard_optimizer_state=shard_optimizer_state)
  template = trainer.create_train_state()
  checkpoint_manager = CheckpointManager(
      os.path.join(model_dir, "checkpoints"))
  # Chief-worker rule (see train_eval_model): metric files belong to
  # the primary; restore/eval/export-fetch run on all hosts (the export
  # writes are chief-gated inside export_and_gc).
  from tensor2robot_tpu.parallel import distributed
  metric_writer = (MetricWriter(os.path.join(model_dir, "eval"))
                   if distributed.is_primary() else None)
  exporters = _init_exporters(create_exporters_fn, model, model_dir)
  results: Dict[int, Dict[str, float]] = {}
  stop = False
  last_new_checkpoint = time.monotonic()

  # Multi-host: per-host directory listings and clocks diverge (shared-
  # storage metadata lag), and _evaluate/export fetches are collectives
  # — every host must make the SAME evaluate/stop decisions. The
  # primary decides; the others follow its broadcast. Caps one poll's
  # batch at _SYNC_CAP steps (the next poll picks up the rest, order
  # preserved).
  _SYNC_CAP = 64
  multi_host = jax.process_count() > 1

  def agree_on_pending(pending, timed_out):
    if not multi_host:
      return pending, timed_out
    from jax.experimental import multihost_utils
    payload = np.full((_SYNC_CAP + 1,), -1, np.int64)
    payload[0] = 1 if timed_out else 0
    steps = pending[:_SYNC_CAP]
    payload[1:1 + len(steps)] = steps
    payload = multihost_utils.broadcast_one_to_all(payload)
    return [int(s) for s in payload[1:] if s >= 0], bool(payload[0])

  try:
    while not stop:
      # The trainer process writes the checkpoints; re-read the
      # directory (orbax caches the step list otherwise).
      checkpoint_manager.reload()
      pending = sorted(step for step in checkpoint_manager.all_steps()
                       if step not in results)
      timed_out = (not pending and
                   time.monotonic() - last_new_checkpoint > timeout_s)
      pending, timed_out = agree_on_pending(pending, timed_out)
      for step in pending:  # every checkpoint, oldest first — no holes
        last_new_checkpoint = time.monotonic()
        state = _restore_with_retry(checkpoint_manager, template, step,
                                    multi_host)
        metrics, images = _evaluate(trainer, model, input_generator_eval,
                                    state, eval_steps, prefetch_depth)
        results[step] = metrics
        if metric_writer:
          _emit_metrics(metric_writer, step,
                        {f"eval/{k}": v for k, v in metrics.items()})
          if images:
            metric_writer.write_images(
                step, {f"eval/{k}": v for k, v in images.items()})
        _log.info("continuous eval @ step %d: %s", step, metrics)
        _run_exporters_after_eval(exporters, state, metrics)
        if stop_after_step and step >= stop_after_step:
          stop = True
          break
        if max_evaluations and len(results) >= max_evaluations:
          stop = True
          break
      if stop:
        break
      if not pending:
        if timed_out:
          _log.info("continuous eval: no new checkpoint for %.0fs; "
                    "stopping.", timeout_s)
          break
        time.sleep(poll_interval_s)
  finally:
    if metric_writer:
      metric_writer.close()
    checkpoint_manager.close()
  return results

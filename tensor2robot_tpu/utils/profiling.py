"""Profiling: TPU trace capture wired into the train loop.

Reference parity: SURVEY.md §5.1 — the reference exposed nothing beyond
tf.summary + external TPU profiler capture; the rebuild makes tracing a
first-class, config-injectable hook. `ProfilerHookBuilder` captures a
window of train steps with `jax.profiler` (XLA device traces + host
annotations) into <model_dir>/profile, viewable in TensorBoard or
Perfetto.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import List, Optional

import jax

from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder

_log = logging.getLogger(__name__)

annotate = jax.profiler.TraceAnnotation

# Process-wide trace window guard (ISSUE 11 satellite): jax.profiler
# raises on a second start_trace, and two capture paths can now both be
# armed (the train ProfilerHook and the replay loop's --profile
# window). Every capture in this repo goes through start_trace /
# stop_trace below, so a second window logs-and-skips instead of
# killing the loop that lost the race. The obs tracer's spans are
# TraceAnnotations always, so any window opened here shows them.
_TRACE_LOCK = threading.Lock()
_TRACE_DIR: Optional[str] = None


def trace_active() -> bool:
  """True while a guarded device-trace window is open."""
  with _TRACE_LOCK:
    return _TRACE_DIR is not None


def start_trace(log_dir: str) -> bool:
  """Starts a device trace unless one is already active.

  Returns True on success; False (logged) when another window holds
  the profiler — the caller should skip its window, not crash.
  """
  global _TRACE_DIR
  with _TRACE_LOCK:
    if _TRACE_DIR is not None:
      _log.warning(
          "profiler trace already active (-> %s); skipping a second "
          "start_trace into %s", _TRACE_DIR, log_dir)
      return False
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _TRACE_DIR = log_dir
  return True


def stop_trace() -> Optional[str]:
  """Stops the guarded trace window; returns its log_dir (None if no
  window was active — safe to call unconditionally on shutdown)."""
  global _TRACE_DIR
  with _TRACE_LOCK:
    if _TRACE_DIR is None:
      return None
    log_dir, _TRACE_DIR = _TRACE_DIR, None
    jax.profiler.stop_trace()
  return log_dir


@contextlib.contextmanager
def trace(log_dir: str):
  """Guarded replacement for jax.profiler.trace: the body runs either
  way; the capture is skipped when another window is active."""
  started = start_trace(log_dir)
  try:
    yield
  finally:
    if started:
      stop_trace()


class ProfilerHook(Hook):
  """Captures a window of training steps into a trace dir.

  Steps are observed at metric sync points (after_step — every
  `log_every_steps`), so the realized window snaps outward to sync
  boundaries: the trace starts at the first sync step >= start_step and
  stops at the first sync step >= end_step. With log_every_steps=100
  and (start=10, end=13), that means one 100-step window starting at
  step 100 — align the window to log_every_steps for precision.
  """

  def __init__(self, start_step: int = 10, end_step: int = 13,
               log_dir: Optional[str] = None):
    if end_step <= start_step:
      raise ValueError(
          f"end_step ({end_step}) must be > start_step ({start_step}).")
    self._start_step = start_step
    self._end_step = end_step
    self._log_dir = log_dir
    self._tracing = False
    self._done = False

  def begin(self, trainer, state, model_dir: str) -> None:
    if self._log_dir is None:
      self._log_dir = os.path.join(model_dir or ".", "profile")

  def after_step(self, state, metrics: dict) -> None:
    if self._done:
      return
    step = int(state.step)
    if not self._tracing and step >= self._start_step:
      if not start_trace(self._log_dir):
        # Another capture path holds the profiler (the double-
        # start_trace guard): skip this hook's window entirely.
        self._done = True
        return
      self._tracing = True
      _log.info("Profiler trace started at step %d → %s", step,
                self._log_dir)
      # A single sync point at/past the whole window still captures
      # one sync interval rather than silently skipping.
      return
    if self._tracing and step >= self._end_step:
      stop_trace()
      self._tracing = False
      self._done = True
      _log.info("Profiler trace stopped at step %d.", step)

  def end(self, state) -> None:
    if self._tracing:
      stop_trace()
      self._tracing = False
      self._done = True
      _log.info("Profiler trace stopped at end of training.")
    elif not self._done:
      _log.warning(
          "ProfilerHook never started: no metric sync step reached "
          "start_step=%d (training ran %d steps).", self._start_step,
          int(state.step))


class ProfilerHookBuilder(HookBuilder):
  """Config-injectable profiler (SURVEY.md §5.1 rebuild note)."""

  def __init__(self, start_step: int = 10, end_step: int = 13,
               log_dir: Optional[str] = None):
    self._start_step = start_step
    self._end_step = end_step
    self._log_dir = log_dir

  def create_hooks(self, trainer, model_dir: str) -> List[Hook]:
    return [ProfilerHook(start_step=self._start_step,
                         end_step=self._end_step,
                         log_dir=self._log_dir)]

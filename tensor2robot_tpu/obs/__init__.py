"""One observability spine for the production loop (ISSUE 11).

Four layers, each usable alone, designed to compose:

- ``trace``: host-side structured spans (thread-safe, nestable) that
  double as ``jax.profiler.TraceAnnotation``s (seen by whatever
  profiler session is open), exportable as one Chrome-trace/Perfetto
  JSON per run.
- ``registry``: a process-wide typed metric registry (counters, gauges,
  bounded histograms with p50/p99 snapshots) with one bridge flushing
  snapshots through the existing ``utils.metric_writer.MetricWriter``
  (JSONL + TensorBoard stay the dashboards).
- ``ledger``: the compile-count dicts scattered through replay/ and
  serving/ promoted to a first-class ``ExecutableLedger`` that joins
  ``compiled.cost_analysis()`` FLOPs/bytes with dispatch counts and
  measured wall time into per-executable device-time attribution.
- ``flight_recorder``: a bounded in-memory ring of recent spans/events,
  dumped atomically to ``<logdir>/flightrec-*.json`` on SLO breach,
  rollout auto-rollback, watchdog stall, or an unhandled loop-thread
  exception.

Round 13 (ISSUE 12) extends the spine ACROSS processes:

- ``context``: contextvar-carried ``request_id``/``step_id``
  correlation ids minted at serving ingress, auto-attached to every
  span, exported as Perfetto flows — one clickable per-request
  timeline across threads and (via the aggregator) processes.
- ``aggregate``: the fleet merge — N processes' host/pid-stamped
  ``metrics.jsonl`` streams, registry snapshots, Chrome traces, and
  flightrec dumps from one shared logdir into one ``FLEETOBS`` view
  (reservoir-union percentiles, per-host step rates, SLO rollup,
  host-prefixed merged trace).
- ``watchdog``: named heartbeats for every loop thread; a monitor
  flags stalls (no progress within deadline) with counter → flightrec
  dump → callback escalation, and ``find_stragglers`` flags fleet
  members below a fraction of the median step rate.

Round 15 (ISSUE 14) adds ``faults`` — deterministic seeded fault
injection through explicit seams; round 16 (ISSUE 15) adds ``health``
— the silent-failure sentinel: in-program training-health summaries
computed inside the fused learn executables, a ``HealthMonitor`` of
declarative rules (hard nonfinite, EWMA drift, bound floors)
escalating through the rails above, and the fleet Q-drift guard over
per-replica served-Q sketches.

The Podracer analysis (PAPERS.md, arXiv:2104.06272) and the pjit/TPUv4
scaling study (arXiv:2204.06514) both justify their architectures with
exactly this per-executable utilization accounting; the multi-host and
bf16-CEM directions in ROADMAP.md will be measured through this layer.
"""

from tensor2robot_tpu.obs.aggregate import aggregate_logdir
from tensor2robot_tpu.obs.context import (bind, current_request_id,
                                          new_request_id)
from tensor2robot_tpu.obs.flight_recorder import (FlightRecorder,
                                                  get_recorder)
from tensor2robot_tpu.obs.health import (HealthHalt, HealthMonitor,
                                         HealthRule, default_rules,
                                         q_drift_report)
from tensor2robot_tpu.obs.ledger import (ExecutableLedger,
                                         check_compile_ledger,
                                         peak_flops_for)
from tensor2robot_tpu.obs.registry import MetricRegistry, get_registry
from tensor2robot_tpu.obs.trace import Tracer, get_tracer, span
from tensor2robot_tpu.obs.watchdog import (Watchdog, find_stragglers,
                                           get_watchdog)

__all__ = [
    "ExecutableLedger",
    "FlightRecorder",
    "HealthHalt",
    "HealthMonitor",
    "HealthRule",
    "MetricRegistry",
    "Tracer",
    "Watchdog",
    "aggregate_logdir",
    "bind",
    "check_compile_ledger",
    "current_request_id",
    "default_rules",
    "find_stragglers",
    "get_recorder",
    "get_registry",
    "get_tracer",
    "get_watchdog",
    "new_request_id",
    "peak_flops_for",
    "q_drift_report",
    "span",
]

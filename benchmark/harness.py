"""The harness: finds a cell's files by the names in BENCHMARK.json,
drives its driver through set-up, window and comparison, and builds the
result line. It knows no cell, configuration or metric by name.

  BENCHMARK.json workloads[i]  -> name, config, traffic, chips
  configs/<config>.json        sizes, as run; model class; optimizer
  traffic/<traffic>.json       {"driver": ..., parameters of the mix}
  limits/<workload>.json       the comparison's limits for that cell
  drivers/<driver>.py          class Session(cell, seed, devices, span)
  reference/<config>.py        the configuration's plain reference
  flops/<config>.py            operations from the configuration's shapes
  layer_metrics/<metric>.py    read(run) -> number or None
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Any

TRACED_SECONDS = 10.0
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(*parts):
  with open(os.path.join(HERE, *parts)) as f:
    return json.load(f)


def _load_module(kind, name):
  """benchmark/<kind>/<name>.py, whatever characters the name has."""
  path = os.path.join(HERE, kind, name + ".py")
  module_name = f"benchmark.{kind}.{name.replace('.', '_')}"
  if module_name in sys.modules:
    return sys.modules[module_name]
  spec = importlib.util.spec_from_file_location(module_name, path)
  module = importlib.util.module_from_spec(spec)
  sys.modules[module_name] = module
  spec.loader.exec_module(module)
  return module


@dataclasses.dataclass
class Cell:
  name: str
  chips: int
  config_name: str
  config: dict
  traffic: dict
  limits: dict
  reference: Any
  flops: Any
  spec: dict  # the whole BENCHMARK.json


def load_cell(workload, spec=None):
  if spec is None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
      spec = json.load(f)
  rows = [w for w in spec["workloads"] if w["name"] == workload]
  if not rows:
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in spec['workloads']]}")
  row = rows[0]
  config_row = [c for c in spec["configs"] if c["name"] == row["config"]][0]
  with open(os.path.join(ROOT, config_row["file"])) as f:
    config = json.load(f)
  return Cell(
      name=workload, chips=int(row["chips"]), config_name=row["config"],
      config=config,
      traffic=_load_json("traffic", row["traffic"] + ".json"),
      limits=_load_json("limits", workload + ".json"),
      reference=_load_module("reference", row["config"]),
      flops=_load_module("flops", row["config"]),
      spec=spec)


def configure_jax():
  """The persistent compile cache, at the program's own fixed place
  inside the checkout (or where JAX_COMPILATION_CACHE_DIR says), with
  every program admitted however quickly it compiled or large it is."""
  from tensor2robot_tpu.utils import compile_cache
  cache_dir = compile_cache.configure()
  import jax
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  # No size cap, whatever JAX_COMPILATION_CACHE_MAX_SIZE says: under a
  # cap the cache evicts the largest cells' programs and they compile
  # again in every run (192 MiB on the chip tool's machines, PR 29).
  jax.config.update("jax_compilation_cache_max_size", -1)
  return cache_dir


class CompileCounter:
  """Backend compiles (or cache loads) and their seconds, as JAX reports
  them through jax.monitoring; `mark()` starts a new count."""

  _COMPILE = "/jax/core/compile/backend_compile_duration"

  def __init__(self):
    import jax.monitoring
    self._lock = threading.Lock()
    self.reset()
    jax.monitoring.register_event_duration_secs_listener(self._duration)
    jax.monitoring.register_event_listener(self._event)

  def reset(self):
    with self._lock:
      self.compiles, self.compile_s = 0, 0.0
      self.cache_hits, self.cache_misses = 0, 0

  def _duration(self, event, seconds, **_):
    if event == self._COMPILE:
      with self._lock:
        self.compiles += 1
        self.compile_s += seconds

  def _event(self, event, **_):
    with self._lock:
      if event == "/jax/compilation_cache/cache_hits":
        self.cache_hits += 1
      elif event == "/jax/compilation_cache/cache_misses":
        self.cache_misses += 1

  def snapshot(self):
    with self._lock:
      return {"compiles": self.compiles,
              "compile_s": round(self.compile_s, 3),
              "cache_hits": self.cache_hits,
              "cache_misses": self.cache_misses}


class Phases:
  """Seconds of each part of a driver's set-up, printed on one line."""

  def __init__(self):
    self._last, self._rows = time.perf_counter(), {}

  def mark(self, name):
    now = time.perf_counter()
    self._rows[name], self._last = round(now - self._last, 3), now

  def say(self):
    say("setup_phases", self._rows)


def build_model(config):
  """The configuration's model class with its arguments and optimizer."""
  spec = config["model"]
  cls = getattr(importlib.import_module(spec["module"]), spec["class"])
  opt = config["optimizer"]
  factory = getattr(importlib.import_module(opt["factory"][0]),
                    opt["factory"][1])
  return cls(optimizer_fn=factory(**opt["kwargs"]), **spec["kwargs"])


def find_chips(cell):
  """The cell's TPU chips, or None (with the reason on standard error)
  where JAX finds another platform or fewer of them."""
  import jax
  devices = jax.devices()
  if devices[0].platform != "tpu" or len(devices) < cell.chips:
    print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); found "
          f"{len(devices)} device(s) of platform {devices[0].platform!r}",
          file=sys.stderr)
    return None
  return devices[:cell.chips]


def say(tag, evidence):
  print(f"[bench] {tag} {json.dumps(evidence)}", flush=True)


def device_record(devices):
  return {"platform": devices[0].platform, "kind": devices[0].device_kind,
          "count": len(devices)}


def memory_peak(devices):
  """Peak bytes on the fullest chip: the allocator's peak of live
  buffers plus its peak reservation for running programs' temporaries,
  which this runtime counts apart (`peak_bytes_reserved`)."""
  peaks = []
  for d in devices:
    stats = d.memory_stats()
    if stats and "peak_bytes_in_use" in stats:
      peaks.append(int(stats["peak_bytes_in_use"])
                   + int(stats.get("peak_bytes_reserved", 0)))
  return max(peaks) if peaks else 0


def metrics_for(cell, section):
  """Entries of BENCHMARK.json's `section` that this cell reports."""
  out = []
  for entry in cell.spec[section]:
    cells = entry.get("workloads")
    if cells is None or cell.name in cells:
      out.append(entry)
  return out


def run_cell(cell, seed, seconds, trace, devices, t0, peaks=None,
             out_dir=None):
  """Everything of a run after the look for a chip. Returns the result
  object; prints the lines that go before it."""
  import jax

  if peaks is None:
    table = _load_json("peaks.json")
    kind = devices[0].device_kind
    if kind not in table:
      raise SystemExit(f"device kind {kind!r} is not in benchmark/peaks.json")
    peaks = table[kind]
  out_dir = out_dir or os.path.join(ROOT, "benchmark_out")
  counter = CompileCounter()
  span = jax.profiler.TraceAnnotation
  driver = importlib.import_module(
      "benchmark.drivers." + cell.traffic["driver"])

  t_import = time.time()
  session = driver.Session(cell, seed, devices, span)
  setup = counter.snapshot()
  setup_s = time.time() - t0
  say("setup", {"setup_s": round(setup_s, 3),
                "import_s": round(t_import - t0, 3),
                "build_and_warm_s": round(time.time() - t_import, 3),
                **setup})

  counter.reset()
  trace_dir = os.path.join(out_dir, "trace", cell.name)
  if trace:
    # A traced window is short whatever the run's length: the trace of
    # 30 s of a ResNet-50 step takes minutes to read.
    seconds = min(seconds, TRACED_SECONDS)
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
  traced_from = time.perf_counter()
  try:
    window = session.run_window(seconds)
  finally:
    traced_s = time.perf_counter() - traced_from
    if trace:
      jax.profiler.stop_trace()
  in_window = counter.snapshot()
  say("window", {"window_s": window["window_s"],
                 "attempted": window["attempted"],
                 "failed": window["failed"],
                 "compiles_in_window": in_window["compiles"],
                 "counters": window.get("counters", {})})

  device = device_record(devices)
  device["memory_peak_bytes"] = memory_peak(devices)
  session.release()

  t_check = time.time()
  checks = list(session.check(cell.limits))
  say("check", {"check_s": round(time.time() - t_check, 3),
                **counter.snapshot()})
  checks.append(("compiles_in_window", in_window["compiles"], 0))
  checks.append(("failed_requests", window["failed"],
                 cell.limits.get("failed_requests", 0)))
  compared = {name: {"value": float(value), "limit": float(limit)}
              for name, value, limit in checks if limit is not None}
  say("read_not_compared", {name: float(value)
                            for name, value, limit in checks if limit is None})
  correct = all(v["value"] <= v["limit"] for v in compared.values())

  run = {"cell": cell, "window": window, "peaks": peaks, "device": device,
         "chips": len(devices), "trace": None}
  result = {"correct": bool(correct), "attempted": int(window["attempted"]),
            "failed": int(window["failed"])}
  if trace:
    from benchmark.trace import reduce as reduce_lib
    summary = reduce_lib.summarize(
        reduce_lib.load(reduce_lib.find_xplane(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if summary is None:
      raise RuntimeError("the trace shows no operation on any device")
    run["trace"] = dict(summary, window_s=traced_s)
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = traced_s
    metrics = {}
    for entry in metrics_for(cell, "per_layer"):
      value = _load_module("layer_metrics", entry["name"]).read(run)
      if value is not None:
        metrics[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    result["metrics"] = metrics
    result["breakdown"] = {"device_ops": summary["device_ops"],
                           "idle_gaps": summary["idle_gaps"]}
  else:
    values = dict(window["metrics"], setup_s=setup_s)
    result["metrics"] = {
        entry["name"]: {"value": float(values[entry["name"]]),
                        "unit": entry["unit"]}
        for entry in metrics_for(cell, "end_to_end")}
  result["device"] = device
  result["compared"] = compared
  return result


def print_compared(result, stream=sys.stderr):
  for name, row in result["compared"].items():
    verdict = "ok" if row["value"] <= row["limit"] else "OVER"
    print(f"[bench] compared {name} = {row['value']:.6g} "
          f"(limit {row['limit']:.6g}) {verdict}", file=stream, flush=True)

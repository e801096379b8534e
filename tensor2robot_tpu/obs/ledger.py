"""ExecutableLedger: compile counts + device-time attribution, first class.

Every compiled program in the production loop already kept an ad-hoc
``compile_counts`` dict (replay buffers, the megastep, the fused
anakin_step, the CEM bucket ladders, the Bellman updater) whose values
tier-1 asserts stay exactly 1 — the fixed-shape "compiles once, never
recompiles" discipline. This module promotes those dicts into one
ledger that ALSO answers the question the Podracer and pjit/TPUv4
papers (PAPERS.md) build their whole analyses on: *where does device
time go, per executable?*

Each AOT executable registers with name/device/shapes; the ledger joins
``compiled.cost_analysis()`` FLOPs/bytes with dispatch counts and
measured wall seconds into per-executable device-time share and an
estimated MFU. Chipless (virtual CPU mesh) the MFU is honestly null —
there is no peak-FLOPs model for this host — and the share numbers
measure host wall-clock attribution, the MULTICHIP virtual-mesh caveat
applied to time instead of throughput.

Timing honesty: ``record_dispatch`` seconds are measured host-side
around the dispatch. Call sites that synchronize on the result (the
anakin/megastep D2H metric reads) record true device+D2H time; staging
calls that fire and forget (the device ring's host extend) record
dispatch time only — attribution shares are therefore lower bounds for
async call sites, and on scanned executables ``cost_analysis`` reports
the scan body ONCE, so FLOPs-derived fields are
per-body, not per-dispatch-of-K.

``check_compile_ledger`` is the ONE shared assertion helper the replay,
anakin, and fleet smokes use in place of their per-test ``all(v == 1)``
copies.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Optional

# Chip peak FLOP/s keyed by substrings of jax device_kind (ROADMAP D9:
# a second copy of benchmark/peaks.json). v5e ("TPU v5 lite"): public
# spec bf16 peak.
CHIP_PEAKS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6": 918e12,
}


def peak_flops_for(device_kind: Optional[str]) -> Optional[float]:
  """Peak FLOP/s for a device kind. None for a host device (cpu): there
  is no peak model for it and MFU stays null. A TPU kind that matches
  no CHIP_PEAKS key raises — a chip missing from the table is an
  error, not a quietly null MFU."""
  if not device_kind:
    return None
  kind = device_kind.lower()
  for key, peak in CHIP_PEAKS.items():
    if key in kind:
      return peak
  if "tpu" in kind:
    raise KeyError(
        f"no peak FLOP/s for TPU device kind {device_kind!r}; add it to "
        f"obs.ledger.CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})")
  return None


class ExecutableEntry:
  """One executable's ledger row (guarded by the owning ledger's lock)."""

  __slots__ = ("name", "device", "shapes", "dtype", "compiles",
               "dispatches", "seconds", "flops_per_dispatch",
               "bytes_per_dispatch")

  def __init__(self, name: str):
    self.name = name
    self.device: Optional[str] = None
    self.shapes: Optional[dict] = None
    self.dtype: Optional[str] = None
    self.compiles = 0
    self.dispatches = 0
    self.seconds = 0.0
    self.flops_per_dispatch: Optional[float] = None
    self.bytes_per_dispatch: Optional[float] = None


def _cost_analysis(compiled):
  """(flops, bytes_accessed) from an AOT executable; (None, None) when
  the backend doesn't report them."""
  try:
    analysis = compiled.cost_analysis()
    flops = float(analysis.get("flops", 0.0)) or None
    nbytes = float(analysis.get("bytes accessed", 0.0)) or None
    return flops, nbytes
  except Exception:
    return None, None


class ExecutableLedger:
  """Thread-safe name → ExecutableEntry map with attribution readout."""

  def __init__(self):
    self._entries: Dict[str, ExecutableEntry] = {}
    self._lock = threading.Lock()
    self._window_start = time.perf_counter()

  # -- recording -----------------------------------------------------------

  def register(self, name: str, compiled=None, device=None,
               shapes: Optional[dict] = None,
               dtype: Optional[str] = None) -> str:
    """One compilation of ``name``; repeat registrations bump the
    compile count (the recompile regression the smokes assert against).
    ``compiled`` (an AOT executable) contributes cost_analysis
    FLOPs/bytes; ``device`` is any str()-able placement label.
    ``dtype`` tags the executable's SCORING precision tier ("f32" /
    "bf16", ISSUE 13) so ``attribution()`` can split device-time and
    MFU per tier — an untagged row groups under "untagged" (host
    bookkeeping executables that have no scoring tier)."""
    with self._lock:
      entry = self._entries.get(name)
      if entry is None:
        entry = self._entries[name] = ExecutableEntry(name)
      entry.compiles += 1
      if device is not None:
        entry.device = str(device)
      if shapes is not None:
        entry.shapes = dict(shapes)
      if dtype is not None:
        entry.dtype = str(dtype)
    if compiled is not None:
      flops, nbytes = _cost_analysis(compiled)
      with self._lock:
        if flops is not None:
          entry.flops_per_dispatch = flops
        if nbytes is not None:
          entry.bytes_per_dispatch = nbytes
    return name

  def record_dispatch(self, name: str, seconds: float,
                      count: int = 1) -> None:
    """Accumulates one (or ``count``) dispatches and their measured wall
    seconds. An unregistered name is created with compiles=0 so a
    dispatch-before-register wiring bug surfaces in the attribution
    instead of crashing the loop."""
    with self._lock:
      entry = self._entries.get(name)
      if entry is None:
        entry = self._entries[name] = ExecutableEntry(name)
      entry.dispatches += count
      entry.seconds += float(seconds)

  # -- readout -------------------------------------------------------------

  @property
  def compile_counts(self) -> Dict[str, int]:
    """The classic ledger dict view ({name: compiles})."""
    with self._lock:
      return {name: entry.compiles
              for name, entry in sorted(self._entries.items())}

  def names(self) -> List[str]:
    with self._lock:
      return sorted(self._entries)

  def attribution(self, wall_seconds: Optional[float] = None,
                  device_kind: Optional[str] = None) -> dict:
    """Per-executable device-time share + estimated MFU.

    With ``wall_seconds`` (the measured run window) shares are
    seconds/wall — they sum to <= 1.0 because the instrumented call
    sites are sequential host calls; the remainder is host work outside
    any executable. Without it shares are normalized over attributed
    seconds (sum == 1.0 when anything was dispatched).
    """
    with self._lock:
      entries = sorted(self._entries.values(),
                       key=lambda e: -e.seconds)
      rows = []
      attributed = sum(entry.seconds for entry in entries)
      denominator = wall_seconds if wall_seconds else attributed
      peak = peak_flops_for(device_kind)
      for entry in entries:
        mfu = None
        if peak and entry.flops_per_dispatch and entry.seconds > 0:
          mfu = round(entry.flops_per_dispatch * entry.dispatches
                      / entry.seconds / peak, 4)
        rows.append({
            "name": entry.name,
            "device": entry.device,
            "shapes": entry.shapes,
            "dtype": entry.dtype,
            "compiles": entry.compiles,
            "dispatches": entry.dispatches,
            "seconds_total": round(entry.seconds, 4),
            "device_time_share": round(
                entry.seconds / denominator, 4) if denominator else 0.0,
            "flops_per_dispatch": entry.flops_per_dispatch,
            "bytes_per_dispatch": entry.bytes_per_dispatch,
            "estimated_mfu": mfu,
        })
    shares = sum(row["device_time_share"] for row in rows)
    # Per-tier rollup (ISSUE 13): device-time split by scoring dtype, so
    # a mixed f32/bf16 fleet's attribution answers "where does time go,
    # per precision" — the Gemma-style serving-tier accounting.
    tiers: Dict[str, dict] = {}
    for row in rows:
      tier = tiers.setdefault(row["dtype"] or "untagged", {
          "executables": 0, "dispatches": 0, "seconds_total": 0.0,
          "device_time_share": 0.0})
      tier["executables"] += 1
      tier["dispatches"] += row["dispatches"]
      tier["seconds_total"] += row["seconds_total"]
      tier["device_time_share"] += row["device_time_share"]
    for tier in tiers.values():  # one rounding step, after the sums
      tier["seconds_total"] = round(tier["seconds_total"], 4)
      tier["device_time_share"] = round(tier["device_time_share"], 4)
    return {
        "wall_seconds": round(wall_seconds, 4) if wall_seconds else None,
        "attributed_seconds": round(attributed, 4),
        "attributed_share": round(shares, 4),
        "device_kind": device_kind,
        "peak_flops": peak,
        "tier_shares": tiers,
        "executables": rows,
        "note": (
            "device_time_share = measured dispatch seconds / "
            "wall_seconds (host-clock attribution; lower bound for "
            "async call sites). estimated_mfu is null without a known "
            "chip peak — on the virtual CPU mesh this mirrors the "
            "MULTICHIP caveat: shares are structural evidence, not "
            "chip rates. cost_analysis counts a scan body once, so "
            "flops_per_dispatch on scanned executables is per-body."),
    }


def _flatten_counts(counts: dict, prefix: str = "") -> Dict[str, int]:
  """Flattens the fleet's nested {device: {bucket: n}} ledgers."""
  flat: Dict[str, int] = {}
  for key, value in counts.items():
    label = f"{prefix}{key}"
    if isinstance(value, dict):
      flat.update(_flatten_counts(value, prefix=f"{label}/"))
    else:
      flat[label] = value
  return flat


def check_compile_ledger(counts: dict, require: Iterable[str] = (),
                         forbid: Iterable[str] = ()) -> Dict[str, int]:
  """THE shared smoke assertion: every executable compiled exactly once.

  Args:
    counts: a compile-count mapping — flat ({name: n}) or nested (the
      fleet router's {device: {bucket: n}}).
    require: names (or name prefixes ending in "*") that must be
      present.
    forbid: names that must be absent (executables a fused path
      subsumes).

  Returns the flattened counts for any further assertions; raises
  AssertionError naming the offending entries otherwise.
  """
  flat = _flatten_counts(dict(counts))
  assert flat, "empty compile ledger: nothing registered a compile"
  wrong = {name: n for name, n in flat.items() if n != 1}
  assert not wrong, f"executables not compiled exactly once: {wrong}"
  for name in require:
    if name.endswith("*"):
      prefix = name[:-1]
      assert any(key.startswith(prefix) for key in flat), (
          f"no executable matching {name!r} in ledger: {sorted(flat)}")
    else:
      assert name in flat, (
          f"required executable {name!r} missing from ledger: "
          f"{sorted(flat)}")
  for name in forbid:
    assert name not in flat, (
        f"forbidden executable {name!r} present in ledger "
        f"(a fused path should have subsumed it): {sorted(flat)}")
  return flat

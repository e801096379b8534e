"""Real requests / padded rows over all flushes of the window, from the
batcher's own counters (ServingStats.record_flush)."""


def read(run):
  counters = run["window"].get("counters", {})
  padded = counters.get("padded_slots")
  if not padded:
    return None
  return 100.0 * counters["occupied_slots"] / padded

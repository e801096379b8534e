"""Rings of flushes of the tests' own making for the three readers of
the turn's hold, and what each reader has to make of them: shared by
`test_hold_split.py` here and `tests/test_benchmark_contract.py`, which
tier-1 counts."""

WINDOW_S = 0.25
SPLIT = dict(turn=0.008, landed=0, transfer=0.007)
# metric: (layer, better, [(what each flush recorded, reading)])
CASES = {
    "flush_turn_wait_share.serve": ("CEM policy", "lower", [
        ([SPLIT, dict(SPLIT, landed=1)], 20.0),
        ([SPLIT, dict(turn=0.012)], 10.0),
        ([dict(turn=0.008)] * 2, None),
        ([], None),
    ]),
    # The flushes whose hold the policy split stand for the others.
    "flush_transfer_wait_share.serve": ("H2D / prefetch", "lower", [
        ([SPLIT, dict(SPLIT, transfer=0.009)], 20.0),
        ([dict(SPLIT, transfer=0.006), dict(landed=0)], 15.0),
        ([dict(landed=0)] * 6 + [dict(SPLIT, transfer=0.002)], 5.0),
        ([dict(landed=0), dict(landed=1)], None),
        ([], None),
    ]),
    "transfer_hidden_share.serve": ("H2D / prefetch", "higher", [
        ([dict(landed=1)] * 3, 100.0),
        ([dict(landed=0)] * 2, 0.0),
        ([dict(landed=1), dict(landed=0), {}, dict(landed=1)], 200.0 / 3),
        ([{}, {}], None),
        ([], None),
    ]),
}


class Ring:
  """What the readers use of the process's tracer."""

  def __init__(self, records, dropped=0):
    self._records, self._dropped = records, dropped

  def spans(self):
    return list(self._records)

  @property
  def total_spans(self):
    return len(self._records) + self._dropped


def ring_of(flushes, dropped=0, before=()):
  """A 40 ms `serve/flush` every 50 ms from t = 100 s, children first;
  the flush's own attrs of the readers' names are not theirs to read."""
  records = list(before)
  for i, flush in enumerate(flushes):
    t0 = 100.0 + 0.05 * i
    turn = {"name": "serve/turn", "ts_s": t0 + 0.010, "bucket": 32,
            "dur_s": flush.get("turn", 0.008)}
    if "landed" in flush:
      turn["landed"] = flush["landed"]
    records.append(turn)
    if "transfer" in flush:
      records.append({"name": "serve/transfer_wait", "ts_s": t0 + 0.022,
                      "dur_s": flush["transfer"], "bucket": 32, "bytes": 1})
    records.append({"name": "serve/flush", "ts_s": t0, "dur_s": 0.040,
                    "batch": 32, "landed": 1})
  return Ring(records, dropped)

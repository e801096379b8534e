"""The device's idle share of the traced window in the train cells
(moves train_examples_per_s); one reader for both kinds of cell."""

from benchmark.trace.reduce import idle_share_percent as read  # noqa: F401

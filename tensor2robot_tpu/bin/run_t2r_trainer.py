"""CLI trainer: config files + binding overrides → train_eval_model.

Reference parity: bin/run_t2r_trainer.py (SURVEY.md §3.1): the canonical
entry point —

    python -m tensor2robot_tpu.bin.run_t2r_trainer \
        --config research/pose_env/configs/train.cfg \
        --binding 'train_eval_model.max_train_steps = 100' \
        --model_dir /tmp/run1

Everything else (model, input generators, export, hooks) is injected via
the config system, exactly the reference's --gin_configs/--gin_bindings
two-level UX.
"""

from __future__ import annotations

import argparse
import importlib
import logging
import sys

from tensor2robot_tpu import config as t2r_config
from tensor2robot_tpu.train.train_eval import (
    continuous_eval_model,
    train_eval_model,
)
from tensor2robot_tpu.utils import compile_cache


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--config", action="append", default=[],
                      help="Config file path (repeatable; applied in order)")
  parser.add_argument("--binding", action="append", default=[],
                      help="Override binding, e.g. 'f.param = 1'"
                           " (repeatable; applied after files)")
  parser.add_argument("--model_dir", default=None,
                      help="Shortcut for train_eval_model.model_dir")
  parser.add_argument("--import_module", action="append", default=[],
                      help="Extra modules to import so their configurables "
                           "register (repeatable)")
  parser.add_argument("--mode", choices=("train_and_eval",
                                         "continuous_eval"),
                      default="train_and_eval",
                      help="train_and_eval runs train_eval_model; "
                           "continuous_eval runs the separate-job "
                           "evaluator polling model_dir's checkpoints "
                           "(configure continuous_eval_model.* bindings)")
  args = parser.parse_args(argv)
  compile_cache.configure()

  logging.basicConfig(
      level=logging.INFO,
      format="%(asctime)s %(levelname)s %(name)s: %(message)s")

  # Standard components + research-model modules register on import.
  importlib.import_module("tensor2robot_tpu.config.registrations")
  for module in args.import_module:
    importlib.import_module(module)

  t2r_config.parse_config_files_and_bindings(args.config, args.binding)
  if args.model_dir:
    target = ("continuous_eval_model.model_dir"
              if args.mode == "continuous_eval"
              else "train_eval_model.model_dir")
    t2r_config.bind(target, args.model_dir)

  if args.mode == "continuous_eval":
    results = continuous_eval_model()
    logging.info("Evaluated %d checkpoints: %s", len(results),
                 sorted(results))
    return 0
  result = train_eval_model()
  logging.info("Final train metrics: %s", result.train_metrics)
  logging.info("Final eval metrics: %s", result.eval_metrics)
  return 0


if __name__ == "__main__":
  sys.exit(main())

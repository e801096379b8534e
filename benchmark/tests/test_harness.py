"""A whole run of each driver at a tiny preset, past the harness's look
for a chip: the result line's keys, the traced run, and `correct`
coming out false when the timed path is broken underneath."""

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.tests import tiny

KEYS = ("correct", "attempted", "failed", "metrics", "device", "compared")


def _one():
  return jax.devices()[:1]


@pytest.mark.parametrize("workload, size", [
    ("qtopt_train_resident", dict(image=48, batch=8)),
    ("grasp2vec_train_resident", dict(image=32, batch=4))])
def test_train_run_end_to_end(workload, size):
  result = tiny.run(tiny.train_cell(workload, **size), _one())
  assert list(result)[:len(KEYS)] == list(KEYS[:4]) + ["device", "compared"]
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
  assert result["attempted"] > 0 and result["failed"] == 0


def test_serve_run_end_to_end_on_four_replicas():
  devices = jax.devices()[:4]
  assert len(devices) == 4
  result = tiny.run(tiny.serve_cell(), devices)
  assert result["correct"], result["compared"]
  assert set(result["metrics"]) == {
      "serve_actions_per_s", "serve_p95_ms", "setup_s"}
  assert result["device"]["count"] == 4
  assert result["compared"]["unscored_answers"]["value"] == 0


def test_state_returned_unchanged_is_not_correct(monkeypatch):
  from tensor2robot_tpu.train.trainer import Trainer
  real = Trainer.train_steps

  def lazy(self, state, features, labels=None):
    _, metrics = real(self, jax.tree_util.tree_map(lambda x: x + 0, state),
                      features, labels)
    return state, metrics

  monkeypatch.setattr(Trainer, "train_steps", lazy)
  result = tiny.run(tiny.train_cell("qtopt_train_resident"), _one())
  assert not result["correct"]
  assert result["compared"]["change_norm_gap"]["value"] > 0.9


def test_one_small_leaf_left_unmoved_is_not_correct(monkeypatch):
  """The leaf that moves least: whatever share of the median leaf it
  is, its own norm's gap reads 1."""
  from tensor2robot_tpu.train.trainer import Trainer
  real = Trainer.train_steps

  def lazy(self, state, features, labels=None):
    new, metrics = real(self, jax.tree_util.tree_map(lambda x: x + 0, state),
                        features, labels)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.linalg.norm(np.asarray(a - b))),
        new.params, state.params)
    norms = jax.tree_util.tree_leaves(moved)
    # not one that only round-off moves (a bias under a batch norm)
    least = min(v for v in norms if v > 1e-3 * float(np.median(norms)))
    params = jax.tree_util.tree_map(
        lambda a, b, m: b if m == least else a, new.params, state.params,
        moved)
    return new.replace(params=params), metrics

  monkeypatch.setattr(Trainer, "train_steps", lazy)
  result = tiny.run(tiny.train_cell("qtopt_train_resident"), _one())
  assert not result["correct"]
  assert result["compared"]["change_own_gap"]["value"] > 0.99


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
  from tensor2robot_tpu.models.critic_model import CriticModel
  real = CriticModel.loss_fn

  def half(self, outputs, features, labels):
    n = outputs["q_predicted"].shape[0] // 2
    cut = lambda tree: jax.tree_util.tree_map(lambda x: x[:n], tree)
    return real(self, cut(outputs), cut(features), cut(labels))

  monkeypatch.setattr(CriticModel, "loss_fn", half)
  result = tiny.run(tiny.train_cell("qtopt_train_resident", batch=16),
                    _one())
  assert not result["correct"], result["compared"]


@pytest.mark.parametrize("what", ["score", "action"])
def test_answer_altered_where_it_is_produced_is_not_correct(
    what, monkeypatch):
  from tensor2robot_tpu.serving.policy import CEMFleetPolicy
  real = CEMFleetPolicy.__call__

  def altered(self, images, seeds=None, **kwargs):
    if not kwargs.get("return_scores"):
      return real(self, images, seeds, **kwargs)  # warm-up
    actions, scores = real(self, images, seeds, **kwargs)
    if what == "score":
      return actions, scores + 0.5
    return -actions, scores

  monkeypatch.setattr(CEMFleetPolicy, "__call__", altered)
  result = tiny.run(tiny.serve_cell(), _one())
  assert not result["correct"], result["compared"]
  assert (result["compared"]["served_q_gap_ratio"]["value"]
          > result["compared"]["served_q_gap_ratio"]["limit"])


def test_search_cut_to_one_iteration_is_not_correct(monkeypatch):
  from tensor2robot_tpu.serving.router import FleetRouter
  real = FleetRouter.__init__

  def one_iteration(self, *args, **kwargs):
    real(self, *args, **dict(kwargs, iterations=1))

  monkeypatch.setattr(FleetRouter, "__init__", one_iteration)
  result = tiny.run(tiny.serve_cell(), _one())
  assert not result["correct"], result["compared"]
  compared = result["compared"]
  assert (compared["cem_refinement_shortfall"]["value"]
          > compared["cem_refinement_shortfall"]["limit"])
  assert (compared["served_q_gap_ratio"]["value"]
          <= compared["served_q_gap_ratio"]["limit"])


def test_traced_run_reports_per_layer_metrics_or_says_why():
  """On the CPU the trace holds no TPU plane, and the harness refuses
  the run instead of reporting an idle share of nothing."""
  with pytest.raises(RuntimeError, match="no operation on any device"):
    tiny.run(tiny.train_cell("qtopt_train_resident"), _one(), trace=True)


def test_no_tpu_means_no_result(capsys):
  from benchmark import run
  code = run.main(["--workload", "qtopt_train_resident", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
  assert code == 2
  out = capsys.readouterr().out.strip().splitlines()
  assert not out or not out[-1].startswith("{")

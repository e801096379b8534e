"""Direct tests for the environment helpers (cpu_mesh_env, the compile
cache placement, fetch_is_collective) and dryrun_multichip's
inline-or-bootstrap decision, which otherwise only have indirect
coverage through the bootstrap and export paths."""

import ast
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.export.export_utils import (
    fetch_is_collective,
    fetch_variables_to_host,
)
from tensor2robot_tpu.utils import compile_cache
from tensor2robot_tpu.utils.cpu_mesh_env import (
    cpu_mesh_env,
    is_cpu_mesh_env,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCpuMeshEnv:

  def test_constructs_bootstrap_env(self):
    env = cpu_mesh_env(8, base={"XLA_FLAGS": "--foo=1",
                                "JAX_PLATFORMS": "tpu,cpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "--foo=1" in env["XLA_FLAGS"]
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "2"

  def test_replaces_stale_count_flag(self):
    env = cpu_mesh_env(
        4, base={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert env["XLA_FLAGS"].count("xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]

  def test_round_trips_through_is_cpu_mesh_env(self):
    env = cpu_mesh_env(8, base={})
    assert is_cpu_mesh_env(8, env)
    assert is_cpu_mesh_env(4, env)      # more devices than needed: fine
    assert not is_cpu_mesh_env(16, env)  # fewer than needed: bootstrap

  @pytest.mark.parametrize("env", [
      {},                                     # nothing set
      {"JAX_PLATFORMS": "tpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
      {"JAX_PLATFORMS": "tpu,cpu",            # the chip host's setting
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
      {"JAX_PLATFORMS": "cpu"},               # no count flag
      {"JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=bogus"},
  ])
  def test_rejects_incomplete_envs(self, env):
    assert not is_cpu_mesh_env(8, env)

  def test_the_test_process_itself_is_the_cpu_mesh(self):
    """tests/conftest.py sets the mesh env before jax loads — no
    re-exec — so this very process must already be on 8 CPU devices."""
    assert is_cpu_mesh_env(8)
    assert jax.devices()[0].platform == "cpu"
    assert len(jax.devices()) == 8


class TestDryrunMultichipDecision:
  """dryrun_multichip: CPU-mesh env already set -> run inline (and an
  inline failure is a failure); otherwise a fresh interpreter under
  cpu_mesh_env, exit status propagated. No probe, no second chance."""

  def _import_entry(self):
    if _REPO_ROOT not in sys.path:
      sys.path.insert(0, _REPO_ROOT)
    import __graft_entry__
    return __graft_entry__

  def _forbid_subprocess(self, monkeypatch):
    def no_run(cmd, **kwargs):
      raise AssertionError(f"unexpected subprocess: {cmd}")
    monkeypatch.setattr(subprocess, "run", no_run)

  def test_cpu_mesh_env_runs_inline(self, monkeypatch):
    entry = self._import_entry()
    assert is_cpu_mesh_env(8)  # conftest
    calls = []
    monkeypatch.setattr(entry, "_dryrun_multichip_impl", calls.append)
    self._forbid_subprocess(monkeypatch)
    entry.dryrun_multichip(8)
    assert calls == [8]

  def test_inline_failure_is_a_failure(self, monkeypatch):
    entry = self._import_entry()

    def boom(n):
      raise RuntimeError("synthetic inline failure")
    monkeypatch.setattr(entry, "_dryrun_multichip_impl", boom)
    self._forbid_subprocess(monkeypatch)
    with pytest.raises(RuntimeError, match="synthetic inline failure"):
      entry.dryrun_multichip(8)

  def test_other_env_bootstraps_under_cpu_mesh_env(self, monkeypatch):
    """On the chip host (JAX_PLATFORMS=tpu,cpu) the dry run never runs
    inline: it goes to a fresh interpreter on the virtual CPU mesh."""
    entry = self._import_entry()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    calls = []

    def fake_run(cmd, **kwargs):
      calls.append("bootstrap")
      assert is_cpu_mesh_env(8, kwargs["env"])
      assert "_dryrun_multichip_impl(8)" in cmd[-1]
      return subprocess.CompletedProcess(cmd, 0)
    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(
        entry, "_dryrun_multichip_impl",
        lambda n: (_ for _ in ()).throw(AssertionError("inline must not run")))
    entry.dryrun_multichip(8)
    assert calls == ["bootstrap"]

  def test_bootstrap_failure_propagates(self, monkeypatch):
    entry = self._import_entry()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # force the bootstrap

    def fake_run(cmd, **kwargs):
      return subprocess.CompletedProcess(cmd, 1)
    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="subprocess failed"):
      entry.dryrun_multichip(8)


_PRINT_CACHE_DIR = (
    "from tensor2robot_tpu.utils import compile_cache; import jax; "
    "print(compile_cache.configure()); "
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_from(cwd, env_dir=None):
  env = {k: v for k, v in os.environ.items()
         if k != compile_cache.ENV_VAR}
  if env_dir is not None:
    env[compile_cache.ENV_VAR] = env_dir
  env["PYTHONPATH"] = _REPO_ROOT
  proc = subprocess.run(
      [sys.executable, "-c", _PRINT_CACHE_DIR], cwd=cwd, env=env,
      capture_output=True, text=True, timeout=120)
  assert proc.returncode == 0, proc.stderr[-800:]
  returned, configured = proc.stdout.strip().splitlines()[-2:]
  return returned, configured


class TestCompileCache:
  """One helper places the persistent compile cache: where
  JAX_COMPILATION_CACHE_DIR says, else one fixed in-checkout path."""

  def test_env_var_set_means_hands_off(self, monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))

    def no_update(*args, **kwargs):
      raise AssertionError(f"jax.config.update{args} with the env var set")
    monkeypatch.setattr(jax.config, "update", no_update)
    assert compile_cache.configure() == str(tmp_path)

  def test_env_var_is_what_jax_itself_uses(self, tmp_path):
    returned, configured = _cache_dir_from(str(tmp_path),
                                           env_dir=str(tmp_path / "c"))
    assert returned == configured == str(tmp_path / "c")

  def test_unset_gives_one_path_from_any_cwd_and_process(self, tmp_path):
    first = _cache_dir_from(str(tmp_path))
    second = _cache_dir_from(_REPO_ROOT)
    assert first == second
    assert first[0] == first[1] == compile_cache.DEFAULT_DIR

  def test_default_path_is_fixed_inside_the_checkout(self):
    path = compile_cache.DEFAULT_DIR
    assert os.path.isabs(path)
    assert path == os.path.join(_REPO_ROOT, ".jax_compile_cache")
    import tempfile
    assert not path.startswith(tempfile.gettempdir() + os.sep)
    assert str(os.getpid()) not in os.path.basename(path)
    with open(os.path.join(_REPO_ROOT, ".gitignore")) as f:
      assert ".jax_compile_cache/" in f.read().split()
    # Nothing the path could vary with is even imported.
    with open(compile_cache.__file__) as f:
      tree = ast.parse(f.read())
    imported = {alias.name for node in ast.walk(tree)
                if isinstance(node, (ast.Import, ast.ImportFrom))
                for alias in node.names}
    assert imported == {"annotations", "os", "jax"}

  @pytest.mark.parametrize("relpath,function", [
      ("chip_smoke.py", "main"),
      ("tensor2robot_tpu/bin/run_t2r_trainer.py", "main"),
      ("tensor2robot_tpu/bin/run_qtopt_replay.py", "main"),
      ("tensor2robot_tpu/bin/bench_serving.py", "main"),
      ("tensor2robot_tpu/parallel/sebulba.py", "main"),  # worker entry
      ("tensor2robot_tpu/parallel/multihost_bench.py", "main"),
  ])
  def test_entry_points_configure_the_cache(self, relpath, function):
    with open(os.path.join(_REPO_ROOT, relpath)) as f:
      tree = ast.parse(f.read())
    body = next(node for node in ast.walk(tree)
                if isinstance(node, ast.FunctionDef)
                and node.name == function)
    calls = [node for node in ast.walk(body)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Attribute)
             and node.func.attr == "configure"
             and isinstance(node.func.value, ast.Name)
             and node.func.value.id == "compile_cache"]
    assert calls, f"{relpath}:{function} never calls compile_cache.configure()"

  def test_nothing_else_sets_a_cache_directory(self):
    offenders = []
    for root in ("tensor2robot_tpu", "."):
      base = os.path.join(_REPO_ROOT, root)
      for dirpath, dirnames, filenames in os.walk(base):
        if root == ".":
          dirnames[:] = []  # top-level scripts only
        for name in filenames:
          path = os.path.join(dirpath, name)
          if (not name.endswith(".py")
              or path == compile_cache.__file__):
            continue
          with open(path) as f:
            if "compilation_cache_dir" in f.read():
              offenders.append(os.path.relpath(path, _REPO_ROOT))
    assert offenders == []


@pytest.mark.slow
class TestDryrunFullGeometryOptIn:
  """T2R_DRYRUN_FULL_GEOMETRY=1 adds one dp×tp
  train step at the 472x472 parity geometry (batch 8) to the virtual-
  mesh dry run — slow lane only, never the driver's gate (which runs
  without the variable and must stay unchanged)."""

  def test_full_geometry_step_runs_on_cpu_mesh(self):
    """Runs the full-geometry step directly, not the whole gate."""
    env = cpu_mesh_env(8)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_REPO_ROOT, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; "
         "__graft_entry__._dryrun_full_geometry(8)"],
        env=env, cwd=_REPO_ROOT, capture_output=True, text=True,
        timeout=1800)
    assert proc.returncode == 0, (
        f"full-geometry dryrun failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr[-2000:]}")
    assert "full-geometry step OK (image_size=472, batch=8" in proc.stdout

  def test_knob_gates_the_full_geometry_step(self):
    """The driver's gate pays for the full geometry ONLY under the env
    knob: the call site is guarded by the exact opt-in check."""
    with open(os.path.join(_REPO_ROOT, "__graft_entry__.py")) as f:
      src = f.read()
    idx = src.index("_dryrun_full_geometry(n_devices)")
    guard = src[:idx].rsplit("if ", 1)[1]
    assert 'os.environ.get("T2R_DRYRUN_FULL_GEOMETRY") == "1"' in guard


class TestFetchIsCollective:

  def test_replicated_and_host_arrays_are_local(self):
    variables = {"a": jnp.ones((4, 4)), "b": np.ones((2,))}
    assert not fetch_is_collective(variables)
    # And the fetch itself stays a plain device_get.
    fetched = fetch_variables_to_host(variables)
    np.testing.assert_allclose(fetched["a"], np.ones((4, 4)))
    np.testing.assert_allclose(fetched["b"], np.ones((2,)))

  def test_sharded_single_process_is_still_local(self):
    # Sharded across devices but fully addressable (single process):
    # no cross-process collective needed.
    from jax.sharding import NamedSharding, PartitionSpec
    from tensor2robot_tpu.parallel.mesh import create_mesh
    mesh = create_mesh({"data": -1})
    arr = jax.device_put(
        jnp.arange(16.0).reshape(8, 2),
        NamedSharding(mesh, PartitionSpec("data")))
    assert not arr.sharding.is_fully_replicated
    assert arr.is_fully_addressable
    assert not fetch_is_collective({"w": arr})

"""The hyper-connection op (`ops/hyper_connection.py`) at tiny sizes on
the CPU: its "xla" form against the plain reference's token-by-token
maps (benchmark/reference/xing4_0_29b_a4b_tp8ep8.py), forward and
gradients; the four Pallas programs (interpreted here) against the "xla"
form, forward and gradients; Sinkhorn's convergence and the clamp."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

reference = importlib.import_module(
    "benchmark.reference.xing4_0_29b_a4b_tp8ep8")
# `ops/__init__.py` style: the module, not a function of its name.
hc_lib = importlib.import_module("tensor2robot_tpu.ops.hyper_connection")

STREAMS = 4
TOKENS, WIDTH = 32, 32


def reference_config():
  """What `reference.token_maps` and `reference.hyper_step` read."""
  return dict(hc_mult=STREAMS, hidden_size=WIDTH, hc_sinkhorn_iters=20,
              hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
              rms_norm_eps=1e-6)


@pytest.fixture(scope="module")
def variables():
  """One sublayer's seeded `phi`, `alpha`, `base`, where `_hyper` looks."""
  maps = reference._hyper_params(reference._Draws(jax.random.key(7)),
                                 reference_config())
  return {"params": {"dense_block0": {"attn_hc": maps}}}


@pytest.fixture(scope="module")
def hidden():
  return jnp.asarray(np.random.default_rng(3).standard_normal(
      (2, TOKENS, WIDTH)), jnp.float32)


@pytest.fixture(scope="module")
def streams():
  """(B, T, n·D): a token's streams side by side, as the program holds
  them; `_apart` gives the reference's (B, T, n, D)."""
  return jnp.asarray(np.random.default_rng(5).standard_normal(
      (2, TOKENS, STREAMS * WIDTH)), jnp.float32)


def _apart(x):
  return x.reshape(x.shape[:-1] + (STREAMS, x.shape[-1] // STREAMS))


def _beside(x):
  return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _hyper(variables, name="attn_hc"):
  return variables["params"]["dense_block0"][name]


def _draw(tokens, width, dtype, seed=0, batch=1):
  """(x, y, phi, alpha, base, the cotangent of the written stream)."""
  keys = jax.random.split(jax.random.key(seed), 6)
  maps = STREAMS * STREAMS + 2 * STREAMS
  normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
  return (normal(keys[0], batch, tokens, STREAMS * width).astype(dtype),
          normal(keys[1], batch, tokens, width).astype(dtype),
          normal(keys[2], STREAMS * width, maps) * (STREAMS * width) ** -0.5,
          jax.random.uniform(keys[3], (3,), minval=0.5, maxval=1.5),
          normal(keys[4], maps),
          normal(keys[5], batch, tokens, STREAMS * width).astype(dtype))


def _sublayer_loss(implementation, x, y, phi, alpha, base, given):
  """A sublayer whose F adds y/2 to the read-out, weighted by `given`
  and by a little of H_pre so that every output has a cotangent."""
  u, h_pre, h_post, h_res = hc_lib.hyper_connection_pre(
      x, phi, alpha, base, implementation=implementation)
  inner = (0.5 * y.astype(jnp.float32) + u.astype(jnp.float32)).astype(
      y.dtype)
  written = hc_lib.hyper_connection_post(x, inner, h_post, h_res,
                                         implementation=implementation)
  return (jnp.sum(written.astype(jnp.float32) * given.astype(jnp.float32))
          + jnp.sum(h_pre * h_pre))


_OPERANDS = ("x", "y", "phi", "alpha", "base")


class TestHyperConnectionOp:

  def test_xla_form_matches_the_reference_s_token_maps(self, variables,
                                                       streams):
    p, config = _hyper(variables), reference_config()
    want = jax.vmap(jax.vmap(
        lambda x: reference.token_maps(x, p, config)))(_apart(streams))
    u, h_pre, h_post, h_res = hc_lib.hyper_connection_pre(
        streams, p["phi"], p["alpha"], p["base"], implementation="xla")
    for got, wanted in zip((h_pre, h_post, h_res), want):
      np.testing.assert_allclose(np.asarray(got), np.asarray(wanted),
                                 atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(u), np.einsum("btj,btjd->btd", want[0], _apart(streams)),
        atol=2e-5)

  @pytest.mark.parametrize("operand", range(len(_OPERANDS)),
                           ids=_OPERANDS)
  def test_xla_form_s_gradients_match_the_reference_s(self, variables,
                                                      streams, hidden,
                                                      operand):
    p, config = _hyper(variables), reference_config()
    given = jnp.cos(jnp.arange(streams.size, dtype=jnp.float32)).reshape(
        streams.shape)

    def ours(x, y, phi, alpha, base):
      u, _, h_post, h_res = hc_lib.hyper_connection_pre(
          x, phi, alpha, base, implementation="xla")
      return jnp.sum(given * hc_lib.hyper_connection_post(
          x, y + u, h_post, h_res, implementation="xla"))

    def theirs(x, y, phi, alpha, base):
      q = {"phi": phi, "alpha": alpha, "base": base}
      per_sequence = lambda x, y: reference.hyper_step(
          x, q, config, lambda u: (y + u, None), "f32")[0]
      return jnp.sum(given * _beside(jax.vmap(per_sequence)(_apart(x), y)))

    args = (streams, hidden, p["phi"], p["alpha"], p["base"])
    got = jax.grad(ours, argnums=operand)(*args)
    want = jax.grad(theirs, argnums=operand)(*args)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * max(scale, 1.0))

  @pytest.mark.parametrize("dtype, batch, tokens, atol", [
      (jnp.float32, 2, 16, 1e-5), (jnp.bfloat16, 1, 256, 2e-2),
      (jnp.bfloat16, 2, 32, 2e-2)])
  def test_pallas_forward_matches_the_xla_form(self, dtype, batch, tokens,
                                               atol):
    x, y, phi, alpha, base, _ = _draw(tokens, 128, dtype, batch=batch)
    want = hc_lib.hyper_connection_pre(x, phi, alpha, base,
                                       implementation="xla")
    got = hc_lib.hyper_connection_pre(x, phi, alpha, base,
                                      implementation="pallas")
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32), atol=atol)
    for g, w in zip(got[1:], want[1:]):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
    written = [hc_lib.hyper_connection_post(
        x, y, want[2], want[3], implementation=form)
               for form in ("pallas", "xla")]
    assert written[0].dtype == dtype and written[0].shape == x.shape
    np.testing.assert_allclose(np.asarray(written[0], np.float32),
                               np.asarray(written[1], np.float32), atol=atol)

  @pytest.mark.parametrize("operand", range(len(_OPERANDS)),
                           ids=_OPERANDS)
  def test_pallas_gradients_match_the_xla_form_s(self, operand):
    args = _draw(32, 128, jnp.float32, seed=3, batch=2)
    got, want = (jax.grad(functools.partial(_sublayer_loss, form),
                          argnums=operand)(*args)
                 for form in ("pallas", "xla"))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * scale)

  def test_pallas_gradients_in_bfloat16_stay_within_its_rounding(self):
    args = _draw(256, 128, jnp.bfloat16, seed=4)
    got, want = (jax.grad(functools.partial(_sublayer_loss, form),
                          argnums=(0, 1, 2, 3, 4))(*args)
                 for form in ("pallas", "xla"))
    for g, w, name in zip(got, want, _OPERANDS):
      g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
      gap = np.linalg.norm(g - w) / np.linalg.norm(w)
      assert gap < (1e-2 if name in ("x", "y") else 2e-3), (name, gap)

  def test_the_four_programs_are_the_names_the_benchmark_reads(self):
    assert hc_lib.KERNEL_NAMES == (
        "hyper_connection_pre_fwd", "hyper_connection_post_fwd",
        "hyper_connection_pre_bwd", "hyper_connection_post_bwd")
    x, y, phi, alpha, base, given = _draw(32, 128, jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        functools.partial(_sublayer_loss, "pallas"),
        argnums=(0, 1, 2)))(x, y, phi, alpha, base, given))
    for name in hc_lib.KERNEL_NAMES:
      assert name in text, name

  @pytest.mark.parametrize("shape, why", [
      ((1, 32, 4, 96), "width"), ((1, 24, 4, 128), "tokens"),
      ((1, 32, 9, 128), "streams")])
  def test_a_shape_the_programs_cannot_take_raises_when_forced(self, shape,
                                                               why):
    b, t, n, d = shape
    x = jnp.zeros((b, t, n * d), jnp.float32)
    phi = jnp.zeros((n * d, n * n + 2 * n), jnp.float32)
    with pytest.raises(ValueError, match="pallas path"):
      hc_lib.hyper_connection_pre(x, phi, jnp.ones((3,)),
                                  jnp.zeros((n * n + 2 * n,)),
                                  implementation="pallas")
    # "auto" takes the "xla" form there (and anywhere off a TPU).
    u, _, _, h_res = hc_lib.hyper_connection_pre(
        x, phi, jnp.ones((3,)), jnp.zeros((n * n + 2 * n,)))
    assert u.shape == (b, t, d) and h_res.shape == (b, t, n, n)
    with pytest.raises(ValueError, match="implementation"):
      hc_lib.hyper_connection_post(x, u, h_res[..., 0], h_res,
                                   implementation="mosaic")


class TestSinkhorn:

  def _res(self, variables, streams, iters):
    p = _hyper(variables)
    return hc_lib.hyper_connection_pre(
        streams, p["phi"], p["alpha"], p["base"],
        hc_lib.MapConfig(sinkhorn_iters=iters), implementation="xla")[3]

  @pytest.mark.parametrize("axis", [-1, -2], ids=["rows", "columns"])
  def test_twenty_iterations_make_it_doubly_stochastic_and_two_do_not(
      self, variables, streams, axis):
    gap = lambda iters: float(jnp.max(jnp.abs(
        jnp.sum(self._res(variables, streams, iters), axis=axis) - 1.0)))
    # The last division is the columns': they are exact, the rows carry
    # what is left.
    assert gap(20) < (1e-5 if axis == -2 else 2e-3), gap(20)
    if axis == -1:
      assert gap(2) > 2e-2 and gap(2) > 20 * gap(20), (gap(2), gap(20))
    assert float(jnp.min(self._res(variables, streams, 20))) > 0.0

  @pytest.mark.parametrize("implementation", ["xla", "pallas"])
  def test_entries_clamped_at_thirty_stay_finite(self, implementation):
    """A base of +-200 is e^+-200 without the clamp: inf and nought."""
    x, y, phi, alpha, _, given = _draw(32, 128, jnp.float32)
    n = STREAMS
    base = jnp.concatenate([jnp.zeros((2 * n,)), jnp.where(
        jnp.arange(n * n) % 3 == 0, 200.0, -200.0)])
    _, _, _, h_res = hc_lib.hyper_connection_pre(
        x, phi, alpha, base, implementation=implementation)
    assert bool(jnp.all(jnp.isfinite(h_res)))
    np.testing.assert_allclose(np.asarray(jnp.sum(h_res, axis=-2)), 1.0,
                               atol=1e-4)
    grads = jax.grad(functools.partial(_sublayer_loss, implementation),
                     argnums=(0, 2, 4))(x, y, phi, alpha, base, given)
    for g in grads:
      assert bool(jnp.all(jnp.isfinite(g)))
    # A clamped entry passes no gradient to its base.
    assert float(jnp.max(jnp.abs(grads[2][2 * n:]))) == 0.0

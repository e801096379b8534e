"""CEM action optimizer for serving-time Q maximization.

Reference parity: the QT-Opt CEM helper (SURVEY.md §2/§3.3): at each
control step sample N candidate actions, score them with the Q-function,
refit a Gaussian to the top-k, iterate, act with the final mean. ~64
samples × 2-3 iterations per control step.

TPU/JAX design: the whole loop is a `lax.fori_loop` over pure tensors —
jit once, no per-iteration host round-trips; batched over control states
via vmap. Scoring uses ONE batched Q call per iteration (the reference
did the same through batched session.run).

Precision tiers (ISSUE 13/16): Q scoring inside CEM dominates acting,
Bellman labeling, AND serving, and ran f32 end-to-end through r13. The
``precision`` policy ("f32" | "bf16" | "int8") threads one value
through the whole scoring stack — this module's score-fn builders, the Bellman
target recipe (replay/bellman.py), the serving bucket executables
(serving/policy.py), and the fused loops (replay/anakin.py,
replay/device_buffer.py). The mixed-precision convention follows the
pjit/TPUv4 scaling study (PAPERS.md): LOW-precision matmuls (params and
score inputs cast to bfloat16 at the score boundary, promotion-driven
modules compute in bf16), f32 ACCUMULATION AND UPDATES (scores return
to f32 before elite selection, the CEM search arithmetic — Gaussian
sampling, refit, clipping — is f32 under every tier, and gradients /
optimizer state / TD priorities never see bf16). "f32" is the oracle
tier: its builders return the exact pre-tier closures, so the default
path lowers bit-identically to r10 (the unchanged-semantics acceptance
bar).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

# The supported scoring tiers. f32 is the oracle (bit-identical to the
# pre-tier lowering); bf16 is the inference tier proved safe by parity
# bars (PRECISION_r14.json) and the shadow/canary rollout harness;
# int8 (ISSUE 16, the tier the PR 10 notes pre-wired) quantizes the
# SERVED params — per-channel symmetric weight-only int8, the
# HBM-bandwidth half of the Gemma-style serving win — while
# activations and the CEM search keep the existing tier contract
# (bf16 matmuls, scores back to f32 before top_k). Like bf16, int8
# enters a fleet only through the shadow→canary→promote gate.
SCORING_PRECISIONS = ("f32", "bf16", "int8")

# The dtype scoring ACTIVATIONS run in per tier. int8 is weight-only
# (w8a16): params live in HBM as int8 + per-channel scales and are
# dequantized to bf16 inside the compiled program, so its activation
# dtype is bf16 — the search contract is the bf16 tier's.
_SCORING_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                   "int8": jnp.bfloat16}

# Wrapper-dict sentinel keys marking one quantized weight leaf:
# {_QUANT_KEY: int8 array, _SCALE_KEY: f32 per-output-channel scales}.
_QUANT_KEY = "int8_q"
_SCALE_KEY = "int8_scale"


def validate_precision(precision: str) -> str:
  """Rejects unknown tiers with the valid set named (every layer of the
  scoring stack validates, so a typo'd tier fails at construction, not
  as a silent f32 fallback serving mislabeled numbers)."""
  if precision not in SCORING_PRECISIONS:
    raise ValueError(
        f"unknown scoring precision {precision!r}; supported tiers: "
        f"{SCORING_PRECISIONS}")
  return precision


def scoring_dtype(precision: str):
  """The jnp dtype Q-scoring matmuls run in under `precision`."""
  return _SCORING_DTYPES[validate_precision(precision)]


def cast_scoring_variables(variables, precision: str):
  """A `precision`-tier view of a params pytree for Q scoring.

  f32 returns the SAME object (zero ops, identity — the f32 path's
  bit-identical-lowering contract, and the serving policies' identity-
  keyed placed-variables cache keeps working). bf16 casts every
  floating leaf to bfloat16 (integer leaves — step counters, uint8
  tables — pass through); inside a jitted score program the cast is
  part of the executable, so a served tree is quantized once per
  dispatch, never mutated in place — the f32 master params are what
  gradients and promotions continue to see. int8 returns the
  quantized-wrapper tree (quantize_scoring_variables) — matmul weights
  become {int8, per-channel scale} pairs, everything else passes
  through — and is IDEMPOTENT on an already-quantized tree, so a
  serving policy can pre-quantize at placement time (the HBM win) and
  still route the tree through this one cast boundary.
  """
  if validate_precision(precision) == "f32":
    return variables
  if precision == "int8":
    return quantize_scoring_variables(variables)
  dtype = _SCORING_DTYPES[precision]
  return jax.tree_util.tree_map(
      lambda leaf: leaf.astype(dtype)
      if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating) else leaf,
      variables)


# -- int8 weight quantization (ISSUE 16) -------------------------------------


def _is_quant_wrapper(node) -> bool:
  return (isinstance(node, dict)
          and set(node.keys()) == {_QUANT_KEY, _SCALE_KEY})


def quantize_scoring_variables(variables):
  """Per-channel symmetric int8 quantization of the WEIGHT leaves.

  Every floating leaf with ndim >= 2 (conv/dense kernels — where the
  bytes are) becomes ``{int8_q, int8_scale}``: symmetric per-OUTPUT-
  channel scales (absmax over all dims but the last, floored at 1e-8
  so an all-zero channel quantizes to zeros instead of NaN), values
  rounded into [-127, 127]. Biases, norm vectors, and integer leaves
  pass through untouched — they are a rounding-error fraction of the
  bytes and keeping them exact keeps the tier's q-agreement tight.
  Idempotent: an already-wrapped leaf passes through, so the cast
  boundary can run inside a compiled program over a pre-quantized
  serving tree without double-quantizing.
  """
  def quant(node):
    if _is_quant_wrapper(node):
      return node
    arr = jnp.asarray(node)
    if not jnp.issubdtype(arr.dtype, jnp.floating) or arr.ndim < 2:
      return node
    w = arr.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=tuple(range(arr.ndim - 1)),
                     keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {_QUANT_KEY: q, _SCALE_KEY: scale}

  return jax.tree_util.tree_map(quant, variables,
                                is_leaf=_is_quant_wrapper)


def dequantize_scoring_variables(variables, dtype=jnp.bfloat16):
  """Dense `dtype` view of a (possibly) quantized tree: wrapped leaves
  expand ``int8 * scale`` (f32 multiply, then one cast — the scale
  stays exact), unwrapped floating leaves cast to `dtype`, integer
  leaves pass through. Inside a jitted score program this is the
  per-dispatch w8→bf16 expansion; the int8 residency in HBM is what
  the executable's params ARGUMENT keeps."""
  def dequant(node):
    if _is_quant_wrapper(node):
      return (node[_QUANT_KEY].astype(jnp.float32)
              * node[_SCALE_KEY]).astype(dtype)
    arr = jnp.asarray(node)
    if jnp.issubdtype(arr.dtype, jnp.floating):
      return arr.astype(dtype)
    return node

  return jax.tree_util.tree_map(dequant, variables,
                                is_leaf=_is_quant_wrapper)


def is_quantized_variables(variables) -> bool:
  """True when the tree holds at least one quantized-wrapper leaf."""
  leaves = jax.tree_util.tree_leaves(variables, is_leaf=_is_quant_wrapper)
  return any(_is_quant_wrapper(leaf) for leaf in leaves)


def scoring_weights_view(variables, precision: str):
  """A DENSE params tree a model fn can consume at `precision`.

  The factored-CEM consumers (replay/bellman.py's encode-once path)
  call model fns with a plain params tree; under int8 the tier's view
  is the quantize→dequantize ROUND TRIP — the same values the serving
  executables score with (weights snapped to the int8 grid, expanded
  to bf16) — so labeling and serving agree about what the tier
  computes. f32 is identity; bf16 is the plain cast."""
  if validate_precision(precision) == "f32":
    return variables
  if precision == "int8":
    return dequantize_scoring_variables(
        quantize_scoring_variables(variables), _SCORING_DTYPES[precision])
  return cast_scoring_variables(variables, precision)


def cem_optimize(
    score_fn: Callable[[jnp.ndarray], jnp.ndarray],
    rng: jax.Array,
    action_size: int,
    **kwargs,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Maximizes score_fn over a single state's action: the batch search
  (`fleet_cem_optimize`, whose CEM hyperparameters `kwargs` are) over a
  batch of one.

  Args:
    score_fn: (num_samples, action_size) → (num_samples,) scores; must be
      jittable (e.g. a batched Q-function with the state closed over).
    rng: PRNG key.
    action_size: action dimensionality.

  Returns:
    (best_action, best_score): the final elite mean and its score.
  """
  best, scores = fleet_cem_optimize(
      lambda _, actions: score_fn(actions[0])[None], None, rng[None],
      action_size, **kwargs)
  return best[0], scores[0]


def _refit(samples: jnp.ndarray, scores: jnp.ndarray,
           num_elites: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Elite selection + Gaussian refit (shared CEM iteration core)."""
  _, elite_idx = jax.lax.top_k(scores, num_elites)
  elites = samples[elite_idx]
  # Std floor avoids collapse to a point before the last iteration.
  return elites.mean(axis=0), elites.std(axis=0) + 1e-3


def batched_cem_optimize(
    score_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    states: jnp.ndarray,
    rng: jax.Array,
    action_size: int,
    **kwargs,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """CEM over a batch of states.

  Args:
    score_fn: (state, (N, A) actions) → (N,) scores for ONE state.
    states: (B, ...) batch of states (pytree leaves batched on axis 0).

  Returns:
    (B, A) best actions, (B,) their scores.
  """
  batch = jax.tree_util.tree_leaves(states)[0].shape[0]
  return fleet_cem_optimize(
      jax.vmap(score_fn), states, jax.random.split(rng, batch),
      action_size, **kwargs)


def make_tiled_q_score_fn(fn, variables, precision: str = "f32"):
  """The canonical per-state Q score_fn (`fleet_cem_optimize` takes it
  under a `jax.vmap` over the states).

  Tiles ONE state's image across its candidate actions and scores the
  batch through a ``(variables, features) -> {"q_predicted"}`` device
  fn. Serving's batched control step (serving/policy.py) and the
  Bellman updater's target max (replay/bellman.py) MUST score through
  the same wire contract — actions served and actions that label
  training targets diverging silently is the worst QT-Opt failure mode
  — so both build their score_fn here.

  precision="f32" (default) is the oracle tier: the returned closure is
  the exact pre-tier body — image dtype passes through untouched (the
  model's wire format: float32, or uint8 on the bandwidth-saving path),
  actions cast f32, scores returned in the model's head dtype. The f32
  program lowers bit-identically to r10.

  precision="bf16" applies the scoring cast at THIS boundary — the one
  place both serving and labeling already share: params' float leaves
  to bfloat16 (`cast_scoring_variables`), the state image to bfloat16
  BEFORE tiling (one small cast, the broadcast stays free; the uint8
  wire's 0..255 values are exact in bf16's 8-bit significand),
  candidate actions to bfloat16 — so promotion-driven modules run their
  matmuls in bf16 — and the scores back to float32 before they reach
  elite selection (f32 accumulation, the pjit/TPUv4 convention).

  precision="int8" is the bf16 body over w8-quantized params: the cast
  boundary quantizes the weights (idempotent on a pre-quantized
  serving tree — what a policy keeps resident in HBM), the score body
  expands them int8→bf16 per dispatch, and images/actions/score
  returns follow the bf16 contract exactly — activation numerics are
  the proven tier's, only the weights ride the int8 grid.
  """
  cast, score_rows = _row_scorer(fn, variables, precision)

  def score(image, actions):
    image = cast(image)
    tiled = jnp.broadcast_to(image[None],
                             (actions.shape[0],) + image.shape)
    return score_rows(tiled, actions)

  return score


def _row_scorer(fn, variables, precision: str):
  """The tier's cast boundary, apart from how a state reaches its rows:
  (cast, score_rows). `cast` brings ONE state (or a batch of them) to the
  tier's dtype before it is expanded; `score_rows` scores (R, ...)
  expanded states against (R, A) actions through `fn`, one row each."""
  if validate_precision(precision) == "f32":
    def score_rows(states, actions):
      outputs = fn(variables, {"image": states,
                               "action": actions.astype(jnp.float32)})
      return jnp.reshape(outputs["q_predicted"], (-1,))

    return (lambda state: state), score_rows

  dtype = _SCORING_DTYPES[precision]
  lp_variables = cast_scoring_variables(variables, precision)

  def score_rows_lp(states, actions):
    weights = (dequantize_scoring_variables(lp_variables, dtype)
               if precision == "int8" else lp_variables)
    outputs = fn(weights, {"image": states,
                           "action": actions.astype(dtype)})
    return jnp.reshape(outputs["q_predicted"], (-1,)).astype(jnp.float32)

  return (lambda state: state.astype(dtype)), score_rows_lp


# One MXU contraction depth: the most states whose codes the recipe
# expands by one product (MergedRowScore), whose FLOPs grow with the
# square of the states. Serving's buckets (1, 8, 32) lie under it and
# a cell measures them; Bellman labeling was measured at 64 and 128
# states, on one chip and sharded over four (PERF.md, PR 39). A batch
# above it keeps the per-state form.
_MAX_MERGED_STATES = 128


class MergedRowScore:
  """The factored recipe's score, over a whole batch of codes at once:
  (`states_of(codes)`, (B, N, A) actions) -> (B, N) scores in ONE Q
  call of B*N rows, same tiers and same Q function as
  `make_tiled_q_score_fn`.

  Row r of the call is code r // N, written as a product with a one-hot
  matrix over the merged (state, candidate) row axis. A per-state
  `broadcast_to` under a vmap over the states reaches the same rows as
  a (B, N, ...) tensor whose candidates the TPU pads to its 128 lanes,
  and merging (B, N) into the convolution's row axis is then a second
  pass over it: written and re-laid in every CEM iteration, 76% of a
  rung-32 serving program (PERF.md, PR 39). The product XLA lowers to
  a convolution that writes the first post convolution's own layout,
  and fuses into it: the expanded rows never reach HBM.

  The product is exact: each output is 1 * x plus zeros, summed in
  float32. A bfloat16 code (the flagship's) passes the MXU as it is;
  any wider one asks for `Precision.HIGHEST`, or the TPU would round
  it to bfloat16 on the way. But it mixes rows, and 0 * NaN is NaN, so
  a state whose code is not finite must not reach it: `states_of`
  zeroes such entries, once, and the scores of that state are made NaN
  after each call, which is what its own rows alone would have read.
  """

  def __init__(self, fn, variables, precision: str = "f32"):
    self._cast, self._score_rows = _row_scorer(fn, variables, precision)

  @staticmethod
  def states_of(codes):
    """(B, ...) codes -> the search's states: the codes with what is
    not finite zeroed, and (B,) whether a state's code was finite."""
    finite = jnp.isfinite(codes)
    return (jnp.where(finite, codes, jnp.zeros_like(codes)),
            jnp.all(jnp.reshape(finite, (codes.shape[0], -1)), axis=1))

  def __call__(self, states, actions):
    codes, finite = states
    batch, num = actions.shape[:2]
    codes = self._cast(codes)
    if num > 1:
      owner = jax.nn.one_hot(jnp.arange(batch * num) // num, batch,
                             dtype=codes.dtype)
      codes = jnp.einsum(
          "nf,f...->n...", owner, codes,
          precision=(None if codes.dtype == jnp.bfloat16
                     else jax.lax.Precision.HIGHEST))
    scores = self._score_rows(
        codes, jnp.reshape(actions, (batch * num, -1)))
    return jnp.where(finite[:, None], jnp.reshape(scores, (batch, num)),
                     jnp.nan)


def make_cem_states_and_score(fn, fns, variables, images,
                              precision: str = "f32"):
  """The ONE CEM scoring recipe: (states, score_fn) for
  fleet_cem_optimize, tiled or factored.

  Serving (serving/policy.py), acting (replay/anakin.py) and Bellman
  labeling (replay/bellman.py) all build their search through this
  helper, so the encode-once-then-score-the-code factored form can
  never drift from the tiled contract in one consumer but not the
  others. `fn` is the whole ``(variables, features) -> {"q_predicted"}``
  forward; `fns` is the factored pair where the model or predictor
  offers one (`CriticModel.factored_cem_fns`,
  `AbstractPredictor.factored_device_fns`): None → tiled, score full
  images through `fn`, each state's own rows; (encode_fn,
  q_from_code_fn) → encode the whole `images` batch once, here, outside
  the CEM loop, and score the codes: of up to _MAX_MERGED_STATES
  states in one call on the merged row axis (`MergedRowScore`), of more
  each state's own rows, as the tiled form does.

  `precision` is the scoring tier (SCORING_PRECISIONS). "f32" returns
  the exact pre-tier recipe. "bf16" runs the whole score path — the
  factored encode included, so the hoisted image tower enjoys the same
  low-precision matmuls the tiled path gets — at bfloat16, with the
  per-candidate scores cast back to float32 before elite selection
  (make_tiled_q_score_fn's contract)."""
  if fns is None:
    return images, jax.vmap(
        make_tiled_q_score_fn(fn, variables, precision=precision))
  encode_fn, q_from_code_fn = fns
  if validate_precision(precision) != "f32":
    # Encode once at the scoring dtype: the code then rides the tiled
    # score's "image" key already in bf16 (its floating-input cast is a
    # no-op), identical Q function and search to the tiled bf16 form.
    # scoring_weights_view keeps the encode DENSE under every tier —
    # int8's view is the quantize→dequantize round trip, so the hoisted
    # tower sees exactly the weights the serving executables score with.
    codes = encode_fn(
        scoring_weights_view(variables, precision),
        {"image": images.astype(scoring_dtype(precision))})
  else:
    codes = encode_fn(variables, {"image": images})
  if codes.shape[0] > _MAX_MERGED_STATES:
    return codes, jax.vmap(
        make_tiled_q_score_fn(q_from_code_fn, variables,
                              precision=precision))
  return (MergedRowScore.states_of(codes),
          MergedRowScore(q_from_code_fn, variables, precision=precision))


def fleet_cem_optimize(
    score_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    states: jnp.ndarray,
    keys: jax.Array,
    action_size: int,
    precision: str = "f32",
    num_samples: int = 64,
    num_elites: int = 6,
    iterations: int = 3,
    initial_mean: Optional[jnp.ndarray] = None,
    initial_std: float = 0.5,
    action_low: float = -1.0,
    action_high: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """CEM over a batch of states with CALLER-supplied per-state keys: the
  one search loop (`cem_optimize` is this over a batch of one).

  The serving micro-batcher's determinism contract hangs on this
  variant: each fleet request carries its own key, so its action
  depends only on (state, key, model) — never on which other requests
  shared the flush, the request's position in the batch, or how much
  bucket padding rode along. `batched_cem_optimize` derives keys by
  splitting one rng (fine for training-time sweeps); serving must not,
  or identical requests would change answers across flush compositions.

  Args:
    score_fn: ((B, ...) states, (B, N, A) actions) → (B, N) scores, each
      state's from its own row of `states` and `actions` alone: a
      per-state score (`make_tiled_q_score_fn`) under `jax.vmap`, or
      `MergedRowScore`. `make_cem_states_and_score` gives either with
      its states.
    states: what `score_fn` takes: (B, ...), pytree leaves batched on
      axis 0.
    keys: (B,) PRNG keys, one per state.
    precision: the scoring tier the caller built `score_fn` at
      (SCORING_PRECISIONS). Validated here so one `precision` value threads
      the whole stack and a typo fails at the optimizer call; the tier
      itself lives in score_fn (`make_tiled_q_score_fn(precision=)`) —
      the SEARCH arithmetic (Gaussian sampling, elite refit, clipping,
      the final mean) is float32 under every tier by the
      low-precision-matmuls / f32-updates convention, so candidate
      actions and the selected action never lose precision.
    num_samples/num_elites/iterations: CEM hyperparameters (reference
      defaults: 64 / ~10% / 2-3).
    initial_mean: optional warm-start mean (e.g. previous control step).
    initial_std: initial per-dim std.
    action_low/high: clipping box.

  Returns:
    (B, A) best actions, (B,) their scores.
  """
  validate_precision(precision)
  batch = keys.shape[0]
  if initial_mean is None:
    initial_mean = jnp.zeros((action_size,), jnp.float32)
  mean = jnp.broadcast_to(initial_mean, (batch, action_size))
  std = jnp.full((batch, action_size), initial_std, jnp.float32)

  def body(i, carry):
    def draw(key, mean, std):
      samples = mean + std * jax.random.normal(
          jax.random.fold_in(key, i), (num_samples, action_size))
      return jnp.clip(samples, action_low, action_high)

    samples = jax.vmap(draw)(keys, *carry)
    return jax.vmap(_refit, in_axes=(0, 0, None))(
        samples, score_fn(states, samples), num_elites)

  mean, _ = jax.lax.fori_loop(0, iterations, body, (mean, std))
  mean = jnp.clip(mean, action_low, action_high)
  return mean, score_fn(states, mean[:, None])[:, 0]


class CEMPolicy:
  """Serving-side policy: predictor + CEM (reference §3.3 robot loop).

  Wraps any predictor whose serving outputs expose the Q-value under
  ``q_predicted`` given (image, action) features.

  Latency design: when the predictor offers a device-resident entry
  (`device_fn` — native exports and checkpoint predictors do), the
  ENTIRE control step — on-device image tiling, all CEM iterations,
  scoring, elite refitting — compiles into one program, so per step the
  host moves one camera image in and one action out. The reference
  instead issued a batched session.run per CEM iteration, shipping the
  tiled image every time; that host path is kept as the fallback for
  predictors without a JAX computation (TF SavedModel).
  """

  def __init__(self, predictor, action_size: int = 4,
               num_samples: int = 64, num_elites: int = 6,
               iterations: int = 3, seed: int = 0):
    self._predictor = predictor
    self._action_size = action_size
    self._num_samples = num_samples
    self._num_elites = num_elites
    self._iterations = iterations
    self._rng = jax.random.key(seed)
    self._calls = 0
    self._device_control = None
    self._device_version = None

  def _build_device_control(self, fn):
    """One fused control step: (variables, image, rng) → action."""
    num_samples = self._num_samples

    def control(variables, image, rng):
      # Image dtype is the model's wire format (float32, or uint8 on
      # the bandwidth-saving path) — pass it through untouched.

      def score(actions):
        # Tile to the actions' (static) leading dim: cem_optimize scores
        # (num_samples, A) batches in the loop and a single (1, A)
        # action at the end, and exported computations bind image and
        # action to one shared symbolic batch.
        tiled = jnp.broadcast_to(image[None],
                                 (actions.shape[0],) + image.shape)
        outputs = fn(variables, {"image": tiled,
                                 "action": actions.astype(jnp.float32)})
        return jnp.reshape(outputs["q_predicted"], (-1,))

      best, _ = cem_optimize(
          score, rng, self._action_size, num_samples=num_samples,
          num_elites=self._num_elites, iterations=self._iterations)
      return best

    return jax.jit(control)

  def __call__(self, image) -> jnp.ndarray:
    """One control step: image (H, W, C) → best action (A,)."""
    self._calls += 1
    rng = jax.random.fold_in(self._rng, self._calls)
    try:
      fn, variables = self._predictor.device_fn()
    except NotImplementedError:
      return self._host_call(image, rng)
    version = self._predictor.model_version
    if self._device_control is None or self._device_version != version:
      # Rebuild on hot-reload; the jit cache keys on the new fn.
      self._device_control = self._build_device_control(fn)
      self._device_version = version
    return self._device_control(variables, jnp.asarray(image), rng)

  def _host_call(self, image, rng) -> jnp.ndarray:
    """predict()-based fallback: one batched call per CEM iteration."""
    import numpy as np
    predictor = self._predictor
    # One dense tile per control step, reused by every CEM iteration.
    # Dtype passes through: the model's wire format (float32 or uint8).
    image = np.asarray(image)
    tiled = np.ascontiguousarray(np.broadcast_to(
        image[None], (self._num_samples,) + image.shape))

    def score(actions: jnp.ndarray) -> jnp.ndarray:
      outputs = predictor.predict({
          "image": tiled,
          "action": np.asarray(actions, np.float32)})
      return jnp.asarray(outputs["q_predicted"].reshape(-1))

    # Host-side CEM loop sharing _refit with the on-device cem_optimize.
    mean = jnp.zeros((self._action_size,), jnp.float32)
    std = jnp.full((self._action_size,), 0.5, jnp.float32)
    for i in range(self._iterations):
      step_rng = jax.random.fold_in(rng, i)
      samples = mean + std * jax.random.normal(
          step_rng, (self._num_samples, self._action_size))
      samples = jnp.clip(samples, -1.0, 1.0)
      mean, std = _refit(samples, score(samples), self._num_elites)
    return jnp.clip(mean, -1.0, 1.0)

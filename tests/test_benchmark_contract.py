"""What the program owes the benchmark that is actually run, pinned on the CPU.

`BENCHMARK.json` runs `benchmark/run.py`; its per-layer readers find the
program's spans, counters and kernels BY NAME, and its drivers import the
program's classes by path. A rename on the program's side shows as `null`
in the ledger or as a cell that cannot start, and until this file only a
chip run found that out. Every case here reads the benchmark's own files
for the names (nothing is listed by hand), so a benchmark PR that reads
one more span gets one more case.

Also here, because it is the same kind of promise: the documents a
builder is sent to name no file that is gone, and the CPU artifact
generators and their round-stamped records can shrink and cannot grow
(ROADMAP D2b).
"""

import ast
import glob
import importlib
import inspect
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "tensor2robot_tpu")
BENCHMARK = os.path.join(ROOT, "benchmark")

_SPAN_NAME = re.compile(r"[a-z]+/[a-z_]+")


def _parse(path):
  with open(path) as f:
    return ast.parse(f.read(), filename=path)


def _package_files():
  return sorted(glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True))


def _called_name(call):
  """`f` of `f(...)`, `g` of `x.g(...)`."""
  func = call.func
  return func.attr if isinstance(func, ast.Attribute) else getattr(
      func, "id", None)


# --- 1. span names and attrs -------------------------------------------------


def _span_names_the_benchmark_reads():
  """Every string constant of the span readers that is a span name, whole
  (a docstring that mentions one is a longer string and is not taken)."""
  files = sorted(glob.glob(os.path.join(BENCHMARK, "layer_metrics", "*.py")))
  files.append(os.path.join(BENCHMARK, "trace", "program_spans.py"))
  names = set()
  for path in files:
    for node in ast.walk(_parse(path)):
      if (isinstance(node, ast.Constant) and isinstance(node.value, str)
          and _SPAN_NAME.fullmatch(node.value)):
        names.add(node.value)
  return sorted(names)


def _string_constants(tree):
  """{NAME: "value"} of a module's top-level `NAME = "value"` lines."""
  return {node.targets[0].id: node.value.value for node in tree.body
          if isinstance(node, ast.Assign)
          and isinstance(node.value, ast.Constant)
          and isinstance(node.value.value, str)}


def _spans_filtered_by_name(tree, shared):
  """Span names a reader compares `s["name"]` with: a literal, a constant
  of its own, or `program_spans.<CONSTANT>` (`shared`)."""
  own = _string_constants(tree)
  names = set()
  for node in ast.walk(tree):
    if not (isinstance(node, ast.Compare)
            and isinstance(node.left, ast.Subscript)
            and isinstance(node.left.value, ast.Name)
            and node.left.value.id == "s"
            and isinstance(node.left.slice, ast.Constant)
            and node.left.slice.value == "name"):
      continue
    other = node.comparators[0]
    if isinstance(other, ast.Constant):
      names.add(other.value)
    elif isinstance(other, ast.Name):
      names.add(own[other.id])
    elif isinstance(other, ast.Attribute):
      names.add(shared[other.attr])
  return names


def _attrs_read_off(span_name):
  """Keys the readers subscript or test on a span dict `s`, in the reader
  files that name `span_name` (or its constant in program_spans). A
  reader that picks its spans by `s["name"] == ...` reads attrs off those
  alone: the span that only closes its window owes it none."""
  shared = _string_constants(_parse(  # FLUSH = "serve/flush" and the like
      os.path.join(BENCHMARK, "trace", "program_spans.py")))
  constants = {value: name for name, value in shared.items()}
  span_keys = {"name", "ts_s", "dur_s"}
  attrs = set()
  for path in glob.glob(os.path.join(BENCHMARK, "layer_metrics", "*.py")):
    with open(path) as f:
      source = f.read()
    if (span_name not in source
        and f"program_spans.{constants.get(span_name)}" not in source):
      continue
    picked = _spans_filtered_by_name(ast.parse(source), shared)
    if picked and span_name not in picked:
      continue
    for node in ast.walk(ast.parse(source)):
      key = None
      if (isinstance(node, ast.Subscript)
          and isinstance(node.value, ast.Name) and node.value.id == "s"
          and isinstance(node.slice, ast.Constant)):
        key = node.slice.value
      elif (isinstance(node, ast.Compare)
            and isinstance(node.left, ast.Constant)
            and isinstance(node.ops[0], ast.In)
            and isinstance(node.comparators[0], ast.Name)
            and node.comparators[0].id == "s"):
        key = node.left.value
      if isinstance(key, str) and key not in span_keys:
        attrs.add(key)
  return attrs


def _set_on_the_record(tree):
  """{id of a `with *.span(...) as record:` call: the keys its function
  sets on the record the span yields (`record["key"] = ...`, inside the
  block or on the closed record after it)}: the ring keeps them beside
  the attrs the call passes."""
  found = {}
  for function in ast.walk(tree):
    if not isinstance(function, ast.FunctionDef):
      continue
    keys = {}  # the record's name: the keys set on it
    for stmt in ast.walk(function):
      if not isinstance(stmt, ast.Assign):
        continue
      for target in stmt.targets:
        if (isinstance(target, ast.Subscript)
            and isinstance(target.value, ast.Name)
            and isinstance(target.slice, ast.Constant)):
          keys.setdefault(target.value.id, set()).add(target.slice.value)
    for node in ast.walk(function):
      if isinstance(node, ast.With):
        for item in node.items:
          if isinstance(item.optional_vars, ast.Name):
            found[id(item.context_expr)] = keys.get(
                item.optional_vars.id, set())
  return found


def _span_call_sites(span_name):
  """`*.span("<name>", **attrs)` calls under tensor2robot_tpu/ whose first
  argument is that literal: [(path, lineno, {keyword names and keys set
  on the yielded record})]."""
  sites = []
  for path in _package_files():
    with open(path) as f:
      source = f.read()
    if span_name not in source:
      continue
    tree = ast.parse(source)
    on_record = _set_on_the_record(tree)
    for node in ast.walk(tree):
      if not (isinstance(node, ast.Call) and node.args):
        continue
      first = node.args[0]
      if (_called_name(node) == "span" and isinstance(first, ast.Constant)
          and first.value == span_name):
        sites.append((os.path.relpath(path, ROOT), node.lineno,
                      {kw.arg for kw in node.keywords}
                      | on_record.get(id(node), set())))
  return sites


_SPANS = _span_names_the_benchmark_reads()


def test_the_readers_name_spans_at_all():
  # Guards the parametrisation below: an empty list would pass in silence.
  assert {"serve/flush", "train/dispatch", "serve/turn",
          "serve/transfer_wait"} <= set(_SPANS), _SPANS


@pytest.mark.parametrize("span_name", _SPANS)
def test_span_the_benchmark_reads_is_opened_by_the_program(span_name):
  sites = _span_call_sites(span_name)
  assert sites, (
      f"benchmark/ reads the span {span_name!r} and no span(...) call under "
      "tensor2robot_tpu/ opens it by that literal: its per-layer metrics "
      "would read null in the ledger")
  wanted = _attrs_read_off(span_name)
  if span_name == "serve/flush":
    assert {"batch", "queue_wait_ms_sum", "in_flight"} <= wanted, wanted
  if span_name == "serve/execute":  # encode_once_ and expand_in_conv_share
    assert {"encode_once", "expand_in_conv"} <= wanted, wanted
  if span_name == "serve/turn":  # flush_turn_wait_ and transfer_hidden_share
    assert {"landed"} <= wanted, wanted
  for path, lineno, keywords in sites:
    assert wanted <= keywords, (
        f"{path}:{lineno} opens {span_name!r} without the attrs "
        f"{sorted(wanted - keywords)} that benchmark/layer_metrics reads")


@pytest.mark.parametrize("attrs, share", [
    ([1, 1, 1], 100.0), ([0, 0], 0.0), ([1, 0, None, 1], 200.0 / 3),
    ([None, None], None), ([], None)])
def test_expand_in_conv_share_over_a_ring_of_execute_spans(attrs, share):
  """Of the window's `serve/execute` spans that carry `expand_in_conv`
  the share with 1; nothing to read where none does (the parent
  commit's program, which the driver runs under this reader too)."""
  from benchmark import harness
  from tensor2robot_tpu.obs import trace as trace_lib
  tracer = trace_lib.get_tracer()
  tracer.clear()
  for value in attrs:
    with tracer.span("serve/flush", batch=32, expand_in_conv=1):
      kwargs = {} if value is None else {"expand_in_conv": value}
      with tracer.span("serve/execute", bucket=32, encode_once=1, **kwargs):
        pass
  read = harness._load_module(
      "layer_metrics", "expand_in_conv_share.serve").read
  value = read({"window": {"window_s": 5.0}, "chips": 1, "trace": None})
  assert value == (None if share is None else pytest.approx(share))
  declared = {m["name"]: m for m in harness.load_cell(
      "qtopt_serve_closed64").spec["per_layer"]}["expand_in_conv_share.serve"]
  assert (declared["layer"], declared["source"], declared["moves"],
          declared["workloads"]) == (
              "CEM policy", "program_span", "serve_actions_per_s",
              ["qtopt_serve_closed64"])


def _hold_cases():
  from benchmark.tests import hold_rings
  return [pytest.param(metric, flushes, share, id=f"{metric}-{i}")
          for metric, (_, _, cases) in sorted(hold_rings.CASES.items())
          for i, (flushes, share) in enumerate(cases)]


@pytest.mark.parametrize("metric, flushes, share", _hold_cases())
def test_hold_split_reader_over_a_ring_of_flushes(monkeypatch, metric,
                                                  flushes, share):
  """The three readers of the turn's hold (ISSUE 40) over a ring of
  flushes (`benchmark/tests/hold_rings.py`): the share where the program
  recorded what they read, of the spans that carry it where only some
  do (the policy splits one hold in a few: those flushes stand for the
  others), None where none does (the parent commit's program, which the
  driver runs under these readers too). The flush's own attrs of the
  same names are not theirs."""
  from benchmark import harness
  from benchmark.tests import hold_rings
  from tensor2robot_tpu.obs import trace as trace_lib
  monkeypatch.setattr(trace_lib, "get_tracer",
                      lambda: hold_rings.ring_of(flushes))
  read = harness._load_module("layer_metrics", metric).read
  value = read({"window": {"window_s": hold_rings.WINDOW_S}, "chips": 1,
                "trace": None})
  assert value == (None if share is None else pytest.approx(share))


@pytest.mark.parametrize("metric", [
    "flush_transfer_wait_share.serve", "flush_turn_wait_share.serve",
    "transfer_hidden_share.serve"])
def test_hold_split_reader_is_declared_and_refuses_a_wrapped_ring(
    monkeypatch, metric):
  """Declared on the serving cell; over a ring that has dropped spans it
  reads only if the oldest span still held ended before the window
  began, else nothing of the window is known to be whole."""
  from benchmark import harness
  from benchmark.tests import hold_rings
  from tensor2robot_tpu.obs import trace as trace_lib
  layer, better, _ = hold_rings.CASES[metric]
  per_layer = harness.load_cell("qtopt_serve_closed64").spec["per_layer"]
  assert metric in [m["name"] for m in per_layer]
  (declared,) = [m for m in per_layer if m["name"] == metric]
  assert declared == {
      "name": metric, "unit": "%", "better": better, "layer": layer,
      "source": "program_span", "moves": "serve_actions_per_s",
      "workloads": ["qtopt_serve_closed64"]}
  read = harness._load_module("layer_metrics", metric).read
  run = {"window": {"window_s": hold_rings.WINDOW_S}, "chips": 1,
         "trace": None}
  old = {"name": "serve/enqueue", "ts_s": 50.0, "dur_s": 0.001}
  flushes = [hold_rings.SPLIT] * 2
  rings = {"whole": hold_rings.ring_of(flushes),
           "dropped before the window": hold_rings.ring_of(
               flushes, dropped=7, before=[old]),
           "dropped inside it": hold_rings.ring_of(flushes, dropped=7)}
  values = {}
  for name, ring in rings.items():
    monkeypatch.setattr(trace_lib, "get_tracer", lambda ring=ring: ring)
    values[name] = read(run)
  assert values["whole"] is not None
  assert values["dropped before the window"] == values["whole"]
  assert values["dropped inside it"] is None


# --- 2. kernel names -----------------------------------------------------------


@pytest.mark.parametrize("kernel", ["flash_attention", "gated_delta_rule",
                                    "hyper_connection", "sparse_attention",
                                    "lightning_attention"])
def test_kernel_names_are_the_pallas_calls_names(kernel):
  """`mla_attention_roofline.train` finds the flash kernel's device events
  by `ops/flash_attention.KERNEL_NAMES`, `gated_delta_rule_roofline.train`
  and `linear_attention_time_share.train` the delta rule's by
  `ops/gated_delta_rule.KERNEL_NAMES`, `hyper_connection_roofline.train`
  the stream passes' by `ops/hyper_connection.KERNEL_NAMES`,
  `sparse_attention_roofline.train` and `lightning_attention_roofline.train`
  theirs by `ops/sparse_attention.KERNEL_NAMES` and
  `ops/lightning_attention.KERNEL_NAMES`; a program named otherwise, or
  one more the tuple lacks, makes them read None."""
  # `ops/__init__.py` re-exports the function under the module's name.
  module = importlib.import_module(f"tensor2robot_tpu.ops.{kernel}")
  tree = _parse(os.path.join(PACKAGE, "ops", f"{kernel}.py"))
  named = []
  for node in ast.walk(tree):
    if not (isinstance(node, ast.Call)
            and _called_name(node) == "pallas_call"):
      continue
    (name,) = [kw.value for kw in node.keywords if kw.arg == "name"]
    if isinstance(name, ast.Constant):
      named.append(name.value)
    else:  # KERNEL_NAMES[i]
      assert (isinstance(name, ast.Subscript)
              and name.value.id == "KERNEL_NAMES"), ast.dump(name)
      named.append(module.KERNEL_NAMES[name.slice.value])
  assert sorted(named) == sorted(module.KERNEL_NAMES)
  assert len(set(named)) == len(named)


def test_the_drivers_read_the_kernels_by_the_program_s_names():
  """The drivers' trace readers import the tuples, they do not spell the
  names: `train_resident_tokens` the flash kernel's,
  `train_resident_hybrid` the delta rule's, `train_resident_mhc` the
  hyper-connections', `train_resident_sala` the block-sparse kernel's and
  Lightning's."""
  for driver, module in (("train_resident_tokens", "flash_attention"),
                         ("train_resident_hybrid", "gated_delta_rule"),
                         ("train_resident_mhc", "hyper_connection"),
                         ("train_resident_sala", "sparse_attention"),
                         ("train_resident_sala", "lightning_attention")):
    imports = _program_imports(
        os.path.join(BENCHMARK, "drivers", f"{driver}.py"))
    assert (f"tensor2robot_tpu.ops.{module}", "KERNEL_NAMES") in imports


def test_the_conv_driver_times_the_mixer_by_the_program_s_scope():
  """`train_resident_conv` joins the trace's operations to the compiled
  step's by the scope `layers/sequence.SHORT_CONV_SCOPE`, imported, not
  spelled: the scope `ShortConv` opens, so a rename cannot make
  `short_conv_time_share.train` and `short_conv_roofline.train` read
  nothing."""
  imports = _program_imports(
      os.path.join(BENCHMARK, "drivers", "train_resident_conv.py"))
  assert ("tensor2robot_tpu.layers.sequence", "SHORT_CONV_SCOPE") in imports
  from tensor2robot_tpu.layers import sequence
  assert "jax.named_scope(SHORT_CONV_SCOPE)" in inspect.getsource(
      sequence.ShortConv)


@pytest.mark.parametrize("metric", sorted(
    os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(BENCHMARK, "layer_metrics", "*.py"))))
def test_layer_metric_reader_loads_and_reads_nothing_from_an_empty_run(
    metric):
  """Every reader BENCHMARK.json can name resolves to a `read(run)`, and
  a kernel's reader leaves its metric out (None, no raise) where the run
  has no record of that kernel: an untraced run, or a program without
  it."""
  from benchmark import harness
  read = harness._load_module("layer_metrics", metric).read
  assert callable(read)
  if ("roofline" in metric or "linear_attention" in metric
      or "hyper_connection" in metric):
    run = {"window": {}, "trace": None, "peaks": {}, "chips": 1,
           "cell": harness.load_cell("joyai_flash_train_seq8k")}
    assert read(run) is None


# --- 3. what the drivers and configurations import -----------------------------


def _program_imports(path):
  """(module, attribute or None) for every import of tensor2robot_tpu.* in
  the file, function-local ones included."""
  found = []
  for node in ast.walk(_parse(path)):
    if (isinstance(node, ast.ImportFrom) and node.module
        and node.module.split(".")[0] == "tensor2robot_tpu"):
      found.extend((node.module, alias.name) for alias in node.names)
    elif isinstance(node, ast.Import):
      found.extend((alias.name, None) for alias in node.names
                   if alias.name.split(".")[0] == "tensor2robot_tpu")
  return found


def _resolve(module, attribute):
  loaded = importlib.import_module(module)
  if attribute is None or hasattr(loaded, attribute):
    return
  importlib.import_module(f"{module}.{attribute}")  # `from pkg import mod`


@pytest.mark.parametrize("driver", sorted(
    os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(BENCHMARK, "drivers", "*.py"))
    if not os.path.basename(p).startswith("_")))
def test_driver_imports_resolve(driver):
  imports = _program_imports(
      os.path.join(BENCHMARK, "drivers", f"{driver}.py"))
  assert imports, f"{driver} imports nothing of the program?"
  for module, attribute in imports:
    try:
      _resolve(module, attribute)
    except (ImportError, AttributeError) as e:
      pytest.fail(f"benchmark/drivers/{driver}.py imports {attribute!r} "
                  f"from {module}: {type(e).__name__}: {e}")


@pytest.mark.parametrize("config", sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(BENCHMARK, "configs", "*.json"))))
def test_configuration_names_a_model_the_program_has(config):
  """`harness.build_model`: the class and the optimizer factory resolve
  and take the arguments the configuration's file gives them."""
  with open(os.path.join(BENCHMARK, "configs", f"{config}.json")) as f:
    spec = json.load(f)
  model, opt = spec["model"], spec["optimizer"]
  cls = getattr(importlib.import_module(model["module"]), model["class"])
  factory = getattr(importlib.import_module(opt["factory"][0]),
                    opt["factory"][1])
  inspect.signature(factory).bind(**opt["kwargs"])
  inspect.signature(cls).bind(optimizer_fn=None, **model["kwargs"])


# --- 4. documents name files that exist ----------------------------------------

_IGNORED_DIRS = {".git", "build", "dist", "chiprun_out", "benchmark_out",
                 "chip_smoke_out", ".jax_compile_cache", "__pycache__",
                 ".pytest_cache", ".hypothesis"}
_PATH = re.compile(r"`([\w./-]+\.(?:py|jsonl|json|md|cfg))(?::[\d,–-]+)?`")
# Names of files that are not the tree's: a model's published config, the
# driver's record outside the checkout, what a run writes into its logdir
# or spool, and upstream tensor2robot's files in README's Layout table.
_NOT_OURS = {
    "config.json", "TESTS_LAST_RUN.json",
    "metrics.jsonl", "fleet_trace.json", "acks.json", "heartbeat.json",
    "utils/tensorspec_utils.py", "utils/train_eval.py",
}
# Sections that name files on purpose that are not in the tree: what an
# earlier PR took away (PERF.md's Findings say "gone" of each) and what a
# later one would add.
_SKIPPED_SECTIONS = {
    "PERF.md": ("## 6. Findings", "## 7. Open questions"),
    "README.md": (),
    ".claude/skills/verify/SKILL.md": (),
    "docs/DESIGN.md": (),
}


def _tree_files():
  paths = []
  for directory, subdirs, files in os.walk(ROOT):
    subdirs[:] = [d for d in subdirs if d not in _IGNORED_DIRS]
    paths.extend(os.path.relpath(os.path.join(directory, name), ROOT)
                 for name in files)
  return paths


def _sections(text, skipped):
  """The document without the sections (heading to the next heading of the
  same or a higher level) whose heading starts with one of `skipped`."""
  kept, skipping = [], None
  for line in text.splitlines():
    if line.startswith("#"):
      level = len(line) - len(line.lstrip("#"))
      if skipping is not None and level <= skipping:
        skipping = None
      if skipping is None and line.startswith(tuple(skipped)):
        skipping = level
    if skipping is None:
      kept.append(line)
  return "\n".join(kept)


@pytest.mark.parametrize("document", sorted(_SKIPPED_SECTIONS))
def test_document_names_only_files_that_exist(document):
  with open(os.path.join(ROOT, document)) as f:
    text = _sections(f.read(), _SKIPPED_SECTIONS[document])
  files = ["/" + path for path in _tree_files()]
  missing = []
  for token in sorted({m.group(1) for m in _PATH.finditer(text)}):
    name = token.lstrip("./")
    if token.startswith("/") or name in _NOT_OURS:
      continue
    # `serving/policy.py` names tensor2robot_tpu/serving/policy.py, a bare
    # `cem.py` any file of that name: a path is there if some file of the
    # tree ends with it at a directory boundary.
    if not any(path.endswith("/" + name) for path in files):
      missing.append(token)
  assert not missing, (
      f"{document} names files that are not in the tree: {missing}")


# --- 5. the generators and their records can shrink, not grow (ROADMAP D2b) ----

_D2B_GENERATORS = {
    "obs/obs_bench.py",
    "parallel/multihost_bench.py",
    "replay/actor_bench.py",
    "replay/anakin_bench.py",
    "replay/anakin_multichip_bench.py",
    "replay/learner_bench.py",
    "replay/precision_bench.py",
    "replay/tpquant_bench.py",
    "serving/fault_bench.py",
    "serving/fleet_bench.py",
}
_D2B_RECORDS = {
    "FAULTS_r15.json", "FLEETOBS_r13.json", "FLEET_r11.json",
    "MULTICHIP_r06.json", "MULTIHOST_r19.json", "OBS_r13.json",
    "PRECISION_r14.json", "REPLAY_SMOKE_r07.json", "REPLAY_SMOKE_r08.json",
    "REPLAY_SMOKE_r09.json", "REPLAY_SMOKE_r10.json", "TPQUANT_r17.json",
}


def _generators_in_the_tree():
  return {os.path.relpath(path, PACKAGE) for path in _package_files()
          if path.endswith("_bench.py")}


def _records_at_the_root():
  return {name for name in os.listdir(ROOT)
          if re.fullmatch(r".*_r\d\d\w*\.jsonl?", name)}


@pytest.mark.parametrize("found, allowed", [
    pytest.param(_generators_in_the_tree, _D2B_GENERATORS, id="generators"),
    pytest.param(_records_at_the_root, _D2B_RECORDS, id="records"),
])
def test_cpu_artifact_set_only_shrinks(found, allowed):
  """A new measurement is a cell of BENCHMARK.json and a line of the
  ledger, not another `*_bench.py` with a round-stamped record beside it."""
  extra = found() - allowed
  assert not extra, f"not in ROADMAP D2b's lists: {sorted(extra)}"

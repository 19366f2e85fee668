"""What the benchmark reads of the program, held in tier-1.

`BENCHMARK.json`'s harness (benchmarks/, which a program PR may not edit)
drives `LLMEngine` through its constructor keywords, a few attributes,
keys of `metrics()`, the `GenRequest` stamps, the NAMES of the jitted
functions in models/paged_kv.py (device programs are found in a trace by
name) and the `llm.*` phase names. Renaming any of them passes every
other test and turns a per-layer metric into null on the chip, or stops
the cell. These tests read the benchmark's files and edit none; each
fails when the thing it names is renamed on either side.
"""

import ast
import dataclasses
import glob
import inspect
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import gpt, paged_kv, serving
from ray_tpu.serve import llm
from ray_tpu.serve.llm import GenRequest, LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
SERVE_CELL = os.path.join(BENCH, "harness", "serve_cell.py")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)


def _tree(path: str) -> ast.AST:
    with open(path) as f:
        return ast.parse(f.read())


def _reader_specs() -> dict[str, str]:
    """metric name -> its `reader` string, every JSON layer metric."""
    specs = {}
    for path in sorted(glob.glob(
            os.path.join(BENCH, "layer_metrics", "*.json"))):
        with open(path) as f:
            specs[os.path.basename(path)[:-5]] = json.load(f)["reader"]
    assert len(specs) > 10
    return specs


@pytest.fixture(scope="module")
def served():
    """A tiny paged, chunked engine after one request: (engine, request)."""
    eng = LLMEngine(CFG, gpt.init_params(CFG, jax.random.key(0)), n_slots=2,
                    max_len=64, kv_mode="paged", page_size=8, n_pages=24,
                    prefill_chunk=16, attn_impl="gather")
    req = eng.submit(list(range(1, 21)), max_tokens=12)
    for _ in range(200):
        if req.done.is_set():
            break
        eng.step()
    assert req.done.is_set() and req.error is None
    return eng, req


# ------------------------------------------- (i) constructor, attributes

def test_harness_builds_the_engine_with_keywords_it_has():
    calls = [n for n in ast.walk(_tree(SERVE_CELL))
             if isinstance(n, ast.Call)
             and getattr(n.func, "id", None) == "LLMEngine"]
    assert len(calls) == 1
    passed = [k.arg for k in calls[0].keywords]
    assert len(passed) >= 16 and None not in passed     # no **kwargs
    params = inspect.signature(LLMEngine.__init__).parameters
    assert [k for k in passed if k not in params] == []
    assert all(params[k].kind is inspect.Parameter.KEYWORD_ONLY
               for k in passed)


def test_engine_has_every_attribute_the_harness_touches(served):
    eng, _req = served
    read, written = set(), set()
    for node in ast.walk(_tree(SERVE_CELL)):
        if (isinstance(node, ast.Attribute)
                and getattr(node.value, "id", None) == "eng"):
            (written if isinstance(node.ctx, ast.Store) else read).add(
                node.attr)
    assert {"n_slots", "n_pages", "page_size", "attn_impl", "metrics",
            "reset_stats", "submit", "start", "stop"} <= read
    assert [a for a in sorted(read) if not hasattr(eng, a)] == []
    assert written == {"cache"}
    for attr in written:                # `eng.cache = None` frees the pool
        assert not isinstance(getattr(type(eng), attr, None), property)
    assert isinstance(eng.n_pages, int) and isinstance(eng.page_size, int)


def test_requests_carry_the_stamps_the_harness_reads(served):
    _eng, req = served
    fields = {f.name for f in dataclasses.fields(GenRequest)}
    touched = set()
    for node in ast.walk(_tree(SERVE_CELL)):
        if not isinstance(node, ast.Attribute):
            continue
        v = node.value
        if (getattr(v, "id", None) in ("q", "req")
                or (isinstance(v, ast.Subscript)
                    and getattr(v.slice, "value", None) == "req")):
            touched.add(node.attr)
    assert {"submitted_at", "first_chunk_at", "first_token_at",
            "finished_at", "n_prompt", "out_ids", "done"} <= touched
    assert sorted(touched - fields) == []
    for stamp in ("submitted_at", "first_chunk_at", "last_chunk_at",
                  "first_token_at", "finished_at"):
        assert isinstance(getattr(req, stamp), float), stamp


# ------------------------------------------------ (ii) keys of metrics()

def test_every_engine_metric_key_is_a_key_of_metrics(served):
    eng, _req = served
    keys = set()
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*")):
        with open(path) as f:
            keys |= set(re.findall(r"engine_metric:(\w+)", f.read()))
    assert len(keys) >= 10
    # The two kernels' block counters (PR 34, PR 37) among them, and
    # the share of its table the decode kernel fetches (PR 41).
    assert {"prefill_block_fill", "decode_block_fill",
            "decode_live_column_share"} <= keys
    keys.discard("compiles_in_window")      # the harness adds this one
    have = eng.metrics()
    assert sorted(keys - set(have)) == []
    assert all(isinstance(have[k], (int, float)) for k in keys)
    # What the harness reads of metrics() outside a layer metric.
    for key in ("queued", "completed", "preemptions", "phase_s", "phase_n",
                "ticks", "tick_s", "stalls", "tick_ms_max",
                "heartbeat_late_ms_max", "emit_gap_ms_p99"):
        assert key in have, key


def test_host_only_phases_are_the_engines():
    from harness import host_phases

    assert host_phases.HOST_ONLY == {
        host_phases.PREFIX + p for p in llm._HOST_PHASES}
    assert set(llm._PHASES) >= set(llm._HOST_PHASES + llm._DISPATCH_PHASES
                                   + llm._PULL_PHASES)


# ------------------------------------- (iii) the device programs' names

def _alternatives(regex: str) -> list[str]:
    """The literal names a program regex of words, `|` and groups
    spells; [] when it is any other kind of regex."""
    if not re.fullmatch(r"[\w|()]+", regex):
        return []
    depth, cut = 0, [-1]
    for i, ch in enumerate(regex):
        depth += (ch == "(") - (ch == ")")
        if ch == "|" and depth == 0:
            cut.append(i)
    if len(cut) > 1:
        cut.append(len(regex))
        return [alt for a, b in zip(cut, cut[1:])
                for alt in _alternatives(regex[a + 1:b])]
    if "(" not in regex:
        return [regex]
    start = regex.index("(")
    depth = 0
    for end in range(start, len(regex)):
        depth += (regex[end] == "(") - (regex[end] == ")")
        if depth == 0:
            break
    return [alt for inner in _alternatives(regex[start + 1:end])
            for alt in _alternatives(regex[:start] + inner + regex[end + 1:])]


def test_alternatives_spell_a_program_regex_out():
    assert _alternatives("decode_(sample|step)_paged|prefill_chunk_paged") == [
        "decode_sample_paged", "decode_step_paged", "prefill_chunk_paged"]
    assert _alternatives(".*") == [] == _alternatives("^%copy| copy$")


def test_every_program_regex_names_a_jitted_function():
    jitted = sorted(name for name, fn in vars(paged_kv).items()
                    if callable(fn) and hasattr(fn, "lower")
                    and hasattr(fn, "__wrapped__"))
    assert "decode_step_paged" in jitted and len(jitted) >= 10
    assert all(getattr(paged_kv, n).__name__ == n for n in jitted)
    from harness import readers

    seen = 0
    for metric, spec in _reader_specs().items():
        kind, args = readers._split_reader(spec)
        if kind not in ("trace_program_s", "trace_share"):
            continue
        seen += 1
        program_re = re.compile(args[0])
        assert any(program_re.search("jit_" + n) for n in jitted), metric
        for literal in _alternatives(args[0]):
            assert any(literal in n for n in jitted), (metric, literal)
    assert seen >= 4


@pytest.mark.parametrize("family", sorted(serving._FAMILIES))
def test_every_familys_programs_are_jitted_under_their_roles(family):
    """The trace's program regexes are the same for every cell: each
    family's chunk, decode-step and window-step program is a jitted
    function whose name IS its role (`jit_<name>` on the device)."""
    fam = serving._family(family)
    module = paged_kv if family == "gpt" else fam.model
    for role in ("prefill_chunk_paged", "decode_step_paged",
                 "_decode_sample_paged"):
        fn = getattr(module, role)
        assert hasattr(fn, "lower") and hasattr(fn, "__wrapped__"), role
        assert fn.__name__ == role
    bound = fam.programs(1, None)
    for role in ("prefill_chunk_paged", "decode_step_paged",
                 "decode_multi_paged"):
        assert bound[role] is getattr(module, role), role
    assert module.decode_multi_paged.__name__ == "decode_multi_paged"


def test_probes_patch_globals_that_the_model_modules_read():
    """The probe tools (benchmarks/tools/probe_*.py) put a fault in by
    rebinding a global of a model module. That works only while the
    module's own code reads the global by that name at trace time: a
    helper moved to another module, or called through another binding,
    turns the probe into a run of the true program that reports
    `correct`. Every `<module>.<name> = ...` names a global the module
    has and some function of it loads; every other `<module>.<name>` a
    probe reads exists."""
    import importlib

    rebound, read = set(), set()
    for path in sorted(glob.glob(os.path.join(BENCH, "tools", "probe_*.py"))):
        tree = _tree(path)
        modules = {a.asname or a.name for node in ast.walk(tree)
                   if isinstance(node, ast.ImportFrom)
                   and node.module == "ray_tpu.models" for a in node.names}
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute)
                    and getattr(node.value, "id", None) in modules):
                (rebound if isinstance(node.ctx, ast.Store) else read).add(
                    (node.value.id, node.attr))
    assert {("laguna", "_attend_fn"), ("zaya", "_route"),
            ("mimo_v2", "_sink"), ("qwen3_next", "gdn_decode_step")} <= rebound
    assert ("zaya", "_rms_norm") in read
    for module_name in sorted({m for m, _name in rebound | read}):
        module = importlib.import_module("ray_tpu.models." + module_name)
        source = ast.parse(inspect.getsource(module))
        loads = {n.id for fn in ast.walk(source)
                 if isinstance(fn, ast.FunctionDef) for n in ast.walk(fn)
                 if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
        for m, name in sorted(rebound | read):
            if m == module_name:
                assert hasattr(module, name), (m, name)
                assert (m, name) in read or name in loads, (m, name)


def test_tools_take_names_from_paged_kv_that_exist():
    taken = set()
    for path in glob.glob(os.path.join(BENCH, "tools", "*.py")):
        for node in ast.walk(_tree(path)):
            if (isinstance(node, ast.Attribute)
                    and getattr(node.value, "id", None) == "paged_kv"):
                taken.add(node.attr)
            elif (isinstance(node, ast.ImportFrom)
                  and node.module == "ray_tpu.models.paged_kv"):
                taken |= {a.name for a in node.names}
    assert {"init_paged_kv", "decode_step_paged",
            "prefill_chunk_paged"} <= taken
    assert sorted(n for n in taken if not hasattr(paged_kv, n)) == []


def test_scope_metrics_name_scopes_and_programs_that_exist():
    """The scope metrics (harness/scope_times.py and the layer_metrics
    files over it) find a part of a program by the NAME the program
    gives it (ops/scopes.py) inside a program found by its jitted
    function's name: every string they pass is one the program has."""
    from harness import scope_times
    from ray_tpu.ops import scopes

    assert scope_times.vocabulary() == scopes.ALL
    jitted = [n for n, fn in vars(paged_kv).items() if hasattr(fn, "lower")]
    for regex in (scope_times.DECODE, scope_times.CHUNK):
        for literal in _alternatives(regex):
            assert any(literal in n for n in jitted), literal
    passes = {"fwd", "bwd", "remat"}
    for path in ("jit(f)/transpose(jvp(mlp))/dot_general", "jit(f)/while/"
                 "body/checkpoint/rematted_computation/mlp/add", "mlp/add"):
        assert scope_times.scope_of(path, scopes.ALL)[1] in passes
    named = set()
    for path in sorted(glob.glob(
            os.path.join(BENCH, "layer_metrics", "*.py"))):
        for node in ast.walk(_tree(path)):
            if (isinstance(node, ast.Call) and getattr(
                    node.func, "value", None) is not None
                    and getattr(node.func.value, "id", "") == "scope_times"):
                for arg in node.args:
                    if isinstance(arg, ast.Tuple):
                        named |= {e.value for e in arg.elts}
    assert len(named) >= 8
    assert named <= set(scopes.ALL) | passes, named - set(scopes.ALL)


# -------------------------- (iv) benchmarks/tests/test_families.py's guards

def test_every_configuration_resolves_its_family_and_reference():
    from harness import configs, families

    bench = configs.load_benchmark(REPO)
    for entry in bench["configs"]:
        config = configs.load_config(REPO, bench, entry["name"])
        family, reference = families.load(REPO, bench, config)
        for fn in ("program_config", "model", "reference_config",
                   "serve_consts", "train_consts"):
            assert callable(getattr(family, fn)), (entry["name"], fn)
        for fn in ("logits", "loss"):
            assert callable(getattr(reference, fn)), (entry["name"], fn)
        hash(family.reference_config(config))       # a static argument
        if "serve" in config:
            assert callable(reference.paired_rows)
            consts = family.serve_consts(config)
            assert consts["decode_bytes_weights"] > 0
            assert consts["decode_bytes_per_kv_token"] > 0
        if "train" in config:
            assert family.train_consts(config, 1024)["train_flops_per_token"] > 0


def test_no_harness_file_names_a_model_class():
    """Only a family module imports the program's model class or a
    reference: the cells, the weights and run.py get both from the
    configuration's family."""
    named = []
    files = [os.path.join(BENCH, "run.py")] + sorted(
        glob.glob(os.path.join(BENCH, "harness", "*.py")))
    assert len(files) > 10
    for path in files:
        for node in ast.walk(_tree(path)):
            if isinstance(node, ast.ImportFrom):
                mods = [f"{node.module or ''}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            else:
                continue
            named += [(os.path.basename(path), m) for m in mods
                      if ("ray_tpu.models" in m
                          and not m.endswith(("models.partition", "models")))
                      or "reference" in m or m.endswith("_ref")]
    assert named == []

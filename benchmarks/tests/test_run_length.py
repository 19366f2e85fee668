"""A run's length is the cell's, not the seed's (PR 47): the reference
check is ONE program a configuration, whatever lengths the seed's
streams have.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_run_length.py -q

Parametrised over the four families and their reference files at tiny
size; nothing here is a device number.
"""

from __future__ import annotations

import copy
import types

import numpy as np
import pytest

import test_laguna_family
import test_qwen3_next_family
import util
from harness import configs, families, serve_cell, weights

ZAYA_TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "moe_intermediate_size": 32, "router_hidden_size": 16, "vocab_size": 256,
}
MAX_LEN = 512           # of the tiny references; the engine is not built


def tiny(name: str) -> dict:
    """The real configuration's file cut to a size the CPU runs."""
    if name == "opt-1.3b":
        return copy.deepcopy(util.TINY_CONFIG)
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, name)
    if name == "laguna-s-2.1":
        return test_laguna_family.tiny_config(config)
    if name == "qwen3-next-80b-a3b":
        return test_qwen3_next_family.tiny_config(config)
    config = copy.deepcopy(config)
    config.update(ZAYA_TINY, name="zaya-tiny")
    return config


# configuration, and the reference file it has to resolve to
FAMILIES = [("opt-1.3b", "gpt_ref"), ("zaya1-8b", "zaya_ref"),
            ("laguna-s-2.1", "laguna_ref"),
            ("qwen3-next-80b-a3b", "qwen3_next_ref")]


@pytest.fixture(scope="module", params=FAMILIES, ids=[r for _c, r in FAMILIES])
def made(request):
    """-> (reference module, its configuration, float32 weights, vocab)."""
    import jax.numpy as jnp

    name, ref_name = request.param
    config = tiny(name)
    bench = configs.load_benchmark(util.REPO)
    family, reference = families.load(util.REPO, bench, config)
    assert reference.__file__.endswith(ref_name + ".py")
    cfg = family.program_config(config, max_seq=MAX_LEN)
    params = weights.make_params(family.model(), cfg, 2**31 + 47, jnp.float32)
    return reference, family.reference_config(config), params, cfg.vocab_size


def test_rows_under_n_do_not_depend_on_the_padding(made):
    """`paired_rows` on a stream padded to `max_len` gives, on the rows
    under the stream's own length, what it gives on the stream padded to
    the next 256: the references are causal."""
    import jax
    import jax.numpy as jnp

    reference, ref_cfg, params, vocab = made
    n = 100
    stream = np.random.default_rng(5).integers(1, vocab, n)
    ref = jax.jit(reference.paired_rows, static_argnums=(2,))
    got = {}
    for length in (256, MAX_LEN):
        seq = np.zeros(length, np.int32)
        seq[:n] = stream
        got[length] = [np.asarray(a)[:n - 1]
                       for a in ref(params, jnp.asarray(seq), ref_cfg)]
    for short, long in zip(got[256], got[MAX_LEN]):
        if short.dtype.kind == "i":             # the argmax
            assert np.array_equal(short, long)
        else:
            assert float(np.max(np.abs(short - long))) < 1e-6
    assert float(np.ptp(got[256][0])) > 1e-3    # the rows say something


def test_the_check_traces_its_reference_once(made):
    """Five streams of five lengths (five padded lengths under the rule
    of the next 256) go through one trace of `paired_rows`, at
    `serve.max_len`, and the check's own count says one program."""
    import run

    reference, ref_cfg, params, vocab = made
    traces = []

    def counted(params, seq, rc):
        traces.append(seq.shape)
        return reference.paired_rows(params, seq, rc)

    rng = np.random.default_rng(7)
    records = []
    for i, n_out in enumerate((20, 150, 270, 330, 460)):
        prompt = rng.integers(1, vocab, 24 + i).tolist()
        records.append({"index": i, "req": types.SimpleNamespace(
            error=None, prompt_ids=prompt, n_prompt=len(prompt),
            out_ids=rng.integers(1, vocab, n_out).tolist())})
    config = {"serve": {"max_len": MAX_LEN, "ref_sample": 5,
                        "reference_factor": 3.0, "deficit_slack": 1e-4}}
    log = []
    got = serve_cell.check_streams(
        types.SimpleNamespace(paired_rows=counted), ref_cfg, params, config,
        records, 3, log.append, run.CompileCounter())
    assert traces == [(MAX_LEN,)]
    assert got["n_tokens"] == 20 + 150 + 270 + 330 + 460
    assert got["programs_compiled"] + got["programs_loaded"] == 1
    assert any("reference programs" in ln and f"padded to {MAX_LEN}" in ln
               for ln in log)
    # random tokens are not the reference's choice: the rule sees them
    assert not got["ok"] and got["top1_share"] < 0.5


@pytest.mark.parametrize("cell,phases,numbers", [
    ("tiny.batch", ["jax_open", "weights", "engine", "warm_up", "ramp",
                    "window", "drain", "check", "reduce"],
     ["mean_deficit", "worst_deficit", "tokens_checked", "requests_failed",
      "compiles_in_window"]),
    ("tiny.train", ["jax_open", "state", "reference", "warm_up", "window",
                    "reduce"],
     ["step1_loss_rel", "losses_not_finite", "compiles_in_window"])])
def test_a_run_says_where_its_seconds_went_and_what_correct_rests_on(
        cell, phases, numbers, tmp_path, capsys):
    """The line before the result: every phase's wall seconds, adding up
    to the run's. The result's LAST key and the last lines of standard
    error: each number compared, beside its limit."""
    import json

    root = util.make_root(str(tmp_path))
    got = util.rehearse(root, cell, seed=2**31 + 47, seconds=1.0)
    (line,) = [ln for ln in got["log"] if "phases, wall seconds:" in ln]
    assert line is got["log"][-1]
    body, total = line.split("phases, wall seconds: ")[1].split("; sum ")
    parts = [p.rsplit(" ", 1) for p in body.split(", ")]
    assert [name for name, _s in parts] == phases
    assert all(float(s) >= 0 for _n, s in parts)
    assert sum(float(s) for _n, s in parts) == pytest.approx(
        float(total), abs=0.06 * len(parts))
    window = dict(parts)["window"]
    assert 1.0 <= float(window) < 3.0
    out = got["line"]
    assert list(out)[-1] == "compared" and list(out["compared"]) == numbers
    for name, c in out["compared"].items():
        ok = c["value"] >= c["limit"] if c.get("at_least") \
            else c["value"] <= c["limit"]
        assert ok, name                       # the run was correct
    assert out["correct"] is True
    err = [ln for ln in capsys.readouterr().err.splitlines() if ln.strip()]
    assert err[-1] == "correct: True"
    assert [ln.split()[1].rstrip(":") for ln in err[-1 - len(numbers):-1]] \
        == numbers
    assert all(" limit " in ln for ln in err[-1 - len(numbers):-1])
    json.dumps(out["compared"])

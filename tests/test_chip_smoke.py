"""CPU rehearsal of chip_smoke.py's control flow, and the compile-cache
placement it relies on.

The phases run here as plain functions at `tiny` size on the CPU backend
(kernels in interpret mode): wrong arguments, paths and control flow show
up without chip time. What this cannot show — compile acceptance and
numbers on hardware — is tests/test_chip_compile.py and the chip run.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.SIZES["tiny"]


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ckpt"))
    nbytes = chip_smoke.write_bf16_checkpoint(TINY.model, path)
    assert nbytes > 0 and os.path.exists(
        os.path.join(path, "checkpoint.pkl"))
    return path


def _serve(attn_impl, ckpt):
    out = chip_smoke.phase_serve(TINY, attn_impl, ckpt, expect="cpu")
    assert len(out["tokens"]) == len(TINY.prompt_lens)
    assert all(len(t) == TINY.max_tokens for t in out["tokens"])
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"]["completed"] == len(TINY.prompt_lens)
    assert out["metrics"]["weight_bytes"] > 0
    # Every emitted token was held to the dense reference in the replica.
    assert 0.0 <= out["worst_deficit"] <= chip_smoke.TIE_TOL


@pytest.mark.parametrize("phase", [
    "kernels", "serve:kernel", "serve:gather", "train", "tp", "fsdp"])
def test_phase_runs_at_tiny_size_on_cpu(phase, tiny_checkpoint):
    if phase == "kernels":
        out = chip_smoke.phase_kernels(TINY, expect="cpu")
        assert set(out["kernels"]) == {
            "paged_attention[bf16]", "paged_attention[int8]",
            "paged_prefill_attention[bf16]", "flash_attention[fwd+bwd]"}
    elif phase.startswith("serve:"):
        _serve(phase.split(":")[1], tiny_checkpoint)
    elif phase == "train":
        out = chip_smoke.phase_train(TINY, expect="cpu")
        assert out["losses"][-1] < out["losses"][0]
    elif phase == "tp":
        out = chip_smoke.phase_tp(TINY, expect="cpu")
        assert len(out["prefixes"]) == 4
    else:
        out = chip_smoke.phase_fsdp(TINY, expect="cpu")
        assert set(out["first_losses"]) == {4, 1}


def test_agreement_rule_holds_every_token_to_the_reference():
    """A stream passes only if EVERY emitted token is within TIE_TOL of
    the reference row's best logit — not just the first difference."""
    prompts, outs = [[7, 8], [9]], [[1, 2, 3], [4, 5, 6]]
    seen = []

    def deficits(rows):
        def fn(prompt, out):
            seen.append((prompt, out))
            return {"deficits": rows[len(seen) - 1], "n_top1": 0}
        return fn

    tol = chip_smoke.TIE_TOL
    assert chip_smoke.check_streams(
        "x", prompts, outs,
        deficits([[0.0, tol / 2, 0.0], [0.0, 0.0, tol]])) == tol
    assert seen == [([7, 8], [1, 2, 3]), ([9], [4, 5, 6])]
    for bad in ([0.0, 0.0, 2 * tol], [0.0, float("nan"), 0.0]):
        seen.clear()
        with pytest.raises(AssertionError, match="not a bf16 tie"):
            chip_smoke.check_streams("x", prompts, outs,
                                     deficits([[0.0] * 3, bad]))
    seen.clear()
    with pytest.raises(AssertionError, match="reference rows"):
        chip_smoke.check_streams("x", prompts, outs, deficits([[0.0]] * 2))


def test_stream_deficits_read_the_dense_reference():
    """The reference's own greedy continuation has deficit 0 at every
    position; a token it ranks lower has exactly its logit gap."""
    import jax
    import numpy as np

    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig.by_name(TINY.model)
    params = gpt.init_params(cfg, jax.random.key(0))
    prompt, greedy = [3, 5, 7], []
    for _ in range(4):
        toks = np.zeros((1, 128), np.int32)
        toks[0, :len(prompt) + len(greedy)] = prompt + greedy
        logits = np.asarray(gpt.forward(
            params, toks, cfg)[0][len(prompt) + len(greedy) - 1], np.float32)
        greedy.append(int(logits.argmax()))
    r = chip_smoke.stream_deficits(params, cfg, prompt, greedy, 128)
    assert r["n_top1"] == 4 and np.allclose(r["deficits"], 0.0, atol=1e-5)
    worst = int(logits.argmin())             # last row: far from its best
    r = chip_smoke.stream_deficits(params, cfg, prompt,
                                   greedy[:3] + [worst], 128)
    assert r["n_top1"] == 3
    assert r["deficits"][3] == pytest.approx(
        float(logits.max() - logits.min()), abs=1e-4)
    assert r["deficits"][3] > chip_smoke.TIE_TOL


def _run_script(cwd, *args, drop=(), **env_extra):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("chips", [None, "4"])
def test_main_fails_without_a_tpu(chips):
    """On a non-tpu platform the script exits non-zero and never prints
    an ok line — no CPU fallback, with or without --chips."""
    args = ["chip_smoke.py"] + (["--chips", chips] if chips else [])
    proc = _run_script(REPO, *args, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "FAILED" in proc.stdout


def test_serve_phase_as_main_reaches_the_replica(tmp_path, tiny_checkpoint):
    """The chip run starts each phase as `python chip_smoke.py --phase ..`,
    so the deployment class travels to the replica worker pickled out of
    `__main__` — by value, helpers included. Anything in it that pickles
    by reference instead (a functools.cache wrapper did) leaves serve.run
    waiting on a replica that can never be built; the in-process phases
    above cannot see that. Run the real entry at tiny size: it must get
    all the way to the replica's report, and fail only there, on the
    platform check."""
    import json

    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        source = f.read()
    guard = 'if __name__ == "__main__":'
    assert source.count(guard) == 1
    script = tmp_path / "chip_smoke.py"
    script.write_text(source.replace(
        guard, 'SIZES["full"] = SIZES["tiny"]\n' + guard))
    proc = _run_script(
        str(tmp_path), str(script), "--phase", "serve", "--phase-args",
        json.dumps({"attn_impl": "gather", "ckpt": tiny_checkpoint}),
        "--phase-out", str(tmp_path / "out.json"),
        JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    assert proc.returncode != 0
    assert "replica ran on 'cpu'" in proc.stderr, proc.stderr[-2000:]


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it fails and prints no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path), "chip_smoke.py", drop=("PYTHONPATH",))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


class TestCompileCachePlacement:
    HELPER = ("import sys; from ray_tpu.utils.platform import "
              "place_compile_cache as p; print(p()); "
              "print('jax' in sys.modules)")

    def _call(self, env):
        out = subprocess.run(
            [sys.executable, "-c", self.HELPER], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=60, check=True)
        path, jax_imported = out.stdout.split()
        assert jax_imported == "False", "the helper must not import jax"
        return path

    def test_outside_value_wins(self, tmp_path):
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert self._call(env) == str(tmp_path)

    def test_unset_gives_one_fixed_path_in_the_checkout(self):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        first, second = self._call(env), self._call(env)
        assert first == second == os.path.join(REPO, ".jax_cache")

    def test_helper_sets_the_variable_for_children(self, monkeypatch):
        from ray_tpu.utils.platform import place_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = place_compile_cache()
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
        assert place_compile_cache() == path     # idempotent

    def test_blocklist_is_a_cpu_backend_guard_only(self, monkeypatch):
        """No program compiled for the tpu platform is kept out of the
        cache by the CPU deserialization-crash blocklist."""
        import jax

        from ray_tpu.utils import platform

        assert platform._blocked_key("jit_epoch-deadbeef")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert not platform._blocked_key("jit_epoch-deadbeef")

    def test_no_cache_directory_set_in_code(self):
        """Outside tests/conftest.py nothing sets the cache directory
        through jax.config — the environment variable is the one place."""
        needle = "jax_compilation_" + "cache_dir"
        offenders = []
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("__pycache__", "chiprun_out")]
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                if path == os.path.join(REPO, "tests", "conftest.py"):
                    continue
                with open(path, errors="replace") as f:
                    if needle in f.read():
                        offenders.append(os.path.relpath(path, REPO))
        assert not offenders, offenders

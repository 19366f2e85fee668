"""The plain reference the serving tests hold an engine's tokens to.

A family's full-sequence `forward` is teacher-forced over a request's
prompt and the tokens the engine emitted: every emitted token has to be
that forward's best at its position, up to `ATOL`, so the whole is the
forward's own greedy continuation. One forward a request, not one a token,
and independent of every engine path: no cache, no pages, no chunk, no
window. (Float32 configurations: at bfloat16 two near-equal logits swap
places between two orders of summation.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import gpt

ATOL = 3e-5


def gpt_forward(cfg, params, tokens):
    """`gpt.forward` in the argument order every other family's has."""
    return gpt.forward(params, tokens, cfg)


def deficits(forward, cfg, params, prompt_ids, out_ids) -> np.ndarray:
    """How far under the plain forward's best logit each of `out_ids`
    lies, at its position. `forward`: (cfg, params, tokens [1, S]) ->
    logits [1, S, V]."""
    prompt_ids, out_ids = list(prompt_ids), [int(t) for t in out_ids]
    seq = np.asarray(prompt_ids + out_ids, np.int32)
    with jax.default_matmul_precision("highest"):
        rows = np.asarray(forward(cfg, params, jnp.asarray(seq[None])))[
            0, len(prompt_ids) - 1:len(seq) - 1]
    return rows.max(axis=1) - rows[np.arange(len(out_ids)), out_ids]


def request_deficits(forward, cfg, params, req, out_ids=None) -> np.ndarray:
    """`deficits` of a finished `GenRequest` (its prompt as submitted: a
    preempted request's `prompt_ids` has grown by what it had emitted)."""
    return deficits(forward, cfg, params, req.prompt_ids[:req.n_prompt],
                    req.out_ids if out_ids is None else out_ids)


def assert_greedy(forward, cfg, params, prompt_ids, out_ids, *, n=None,
                  atol=ATOL) -> None:
    """`out_ids` (all `n` of them) are the plain forward's greedy
    continuation of `prompt_ids`."""
    if n is not None:
        assert len(out_ids) == n, (len(out_ids), n)
    worst = deficits(forward, cfg, params, prompt_ids, out_ids)
    assert worst.max(initial=0.0) <= atol, (
        f"token {int(worst.argmax())} of {len(worst)} lies "
        f"{float(worst.max())} under the plain forward's best")


def assert_gpt_greedy(cfg, params, prompts, outs, *, n=None) -> None:
    """Every stream of `outs` is the plain gpt forward's greedy
    continuation of its prompt (`n` tokens long, where given)."""
    for prompt, out in zip(prompts, outs, strict=True):
        assert_greedy(gpt_forward, cfg, params, prompt, out, n=n)


def lively(params: dict, *, as_is: tuple = (), scale: float = 8.0) -> dict:
    """Seeded weights with every matrix but the embedding `scale` times
    its initial size and moved off it, so that no projection is zero and
    a greedy continuation does not settle on one token (at the initial
    size almost every family repeats a single token: a dropped or doubled
    step would not show). `as_is`: leaves left as they came."""
    keys = jax.random.split(jax.random.key(1), len(params))
    return {
        n: (scale * v + 0.02 * jax.random.normal(k, v.shape, v.dtype)
            if v.ndim >= 2 and not n.startswith(("wte", "embed") + as_is)
            and jnp.issubdtype(v.dtype, jnp.floating) else v)
        for k, (n, v) in zip(keys, sorted(params.items()))}

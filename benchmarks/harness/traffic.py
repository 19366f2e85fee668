"""Seeded traffic generators: one general reader of the parameter files
under benchmarks/traffic/.

Every seed gets the same SET of work in ANOTHER ORDER. A mix's cycle of
lengths and arrival gaps sits at evenly spaced quantiles of the stated
distributions, so its minimum, median, mean and maximum are the same for
every seed; `--seed` shuffles the order of the gaps and of each length
list, and draws the token values (and the weights). A window that lasts
one cycle holds every request of the cycle exactly once.

Why quantiles and not free draws: two dozen free draws from a heavy
tail differ from seed to seed by more than any change a PR makes. Why
the order IS the seed's: a fixed order is one trace replayed, its spread
says nothing of the traffic, and a claim has to hold on a seed that was
not used while the change was written (PR 23's first design fixed the
order and was sent back for it). With the order drawn by the seed, six
seeds of the retired chat mix read `ttft_p90_ms` 11,255-11,778 ms, each
repeating to 0.02 % (PR 23, chip): that seed-to-seed spread is the
traffic's own, and a tail's bound has to be set from it.

Kinds:
  open_loop    arrivals on a schedule, whatever the server does.
               `rate_per_s`, `prompt_len`, `output_len`, `ramp_s`,
               `drain_cap_s`. One cycle lasts the measured window; the
               ramp plays the end of the previous cycle and arrivals go
               on (unmeasured) while measured requests drain.
  closed_loop  `clients` callers (or the config's n_slots when
               "n_slots"), each sending its next request when the last
               completes. `cycle_requests`, `prompt_len`, `output_len`,
               `ramp_s`.
  train_job    `batch`, `seq`: a fresh batch of seeded tokens per step.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """n integer lengths at evenly spaced quantiles of `spec`, shuffled."""
    u = _quantile_points(n)
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    if "min" in spec:
        vals = np.maximum(vals, spec["min"])
    if "max" in spec:
        vals = np.minimum(vals, spec["max"])
    out = np.rint(vals).astype(np.int64)
    rng.shuffle(out)
    return out


def draw_gaps(rate_per_s: float, n: int, total_s: float, rng) -> np.ndarray:
    """n inter-arrival gaps at evenly spaced quantiles of the exponential
    distribution (a Poisson process), shuffled, scaled to sum to total_s."""
    gaps = -np.log1p(-_quantile_points(n)) / rate_per_s
    rng.shuffle(gaps)
    return gaps * (total_s / gaps.sum())


def _big_rng(seed: int):
    return np.random.default_rng(int(seed))


def _tokens(rng, n: int, vocab: int) -> list[int]:
    return rng.integers(1, vocab, int(n)).tolist()


def length_stats(xs) -> dict:
    xs = np.asarray(xs)
    return {"n": int(xs.size), "min": int(xs.min()),
            "p50": float(np.median(xs)), "mean": float(xs.mean()),
            "max": int(xs.max())}


def open_loop_plan(mix: dict, window_s: float, seed: int, vocab: int) -> dict:
    """Requests as (due seconds relative to the window's start, prompt,
    max_tokens), ordered by due time, from -ramp_s until the window's end
    plus drain_cap_s. Those due in [0, window_s) are the measured ones:
    one whole cycle."""
    n = max(1, round(mix["rate_per_s"] * window_s))
    rng = _big_rng(seed)
    gaps = draw_gaps(mix["rate_per_s"], n, window_s, rng)
    p_len = draw_lengths(mix["prompt_len"], n, rng)
    o_len = draw_lengths(mix["output_len"], n, rng)
    at = np.cumsum(gaps)                 # position of request i in the cycle
    phase = at[0] - 0.5 * gaps[0]        # request 0 opens the window
    t_lo, t_hi = -float(mix["ramp_s"]), window_s + float(mix["drain_cap_s"])
    reqs = []
    k_lo = math.floor((t_lo + phase - at[-1]) / window_s) - 1
    k_hi = math.ceil((t_hi + phase) / window_s) + 1
    for k in range(k_lo, k_hi + 1):
        for i in range(n):
            due = k * window_s + at[i] - phase
            if t_lo <= due < t_hi:
                reqs.append((float(due), i))
    reqs.sort()
    plan = [{"due": due, "index": i,
             "prompt": _tokens(rng, p_len[i], vocab),
             "max_tokens": int(o_len[i]),
             "measured": 0.0 <= due < window_s} for due, i in reqs]
    measured = [r for r in plan if r["measured"]]
    return {"requests": plan,
            "stats": {"rate_per_s": mix["rate_per_s"],
                      "measured": len(measured), "planned": len(plan),
                      "prompt_len": length_stats([len(r["prompt"])
                                                  for r in measured]),
                      "output_len": length_stats([r["max_tokens"]
                                                  for r in measured]),
                      "gap_s": {"p50": float(np.median(gaps)),
                                "max": float(gaps.max())}}}


class ClosedLoopSource:
    """The cyclic list of requests the clients draw from, in the seed's
    order."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        n = int(mix["cycle_requests"])
        self._rng = _big_rng(seed)
        self.p_len = draw_lengths(mix["prompt_len"], n, self._rng)
        self.o_len = draw_lengths(mix["output_len"], n, self._rng)
        self._next = 0
        self._vocab = vocab
        self.stats = {"cycle_requests": n,
                      "prompt_len": length_stats(self.p_len),
                      "output_len": length_stats(self.o_len)}

    def next(self) -> dict:
        i = self._next % len(self.p_len)
        self._next += 1
        return {"index": i,
                "prompt": _tokens(self._rng, self.p_len[i], self._vocab),
                "max_tokens": int(self.o_len[i])}


class TrainBatches:
    """A fresh [batch, seq] of seeded tokens per call (numpy, on the host)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.batch, self.seq = int(mix["batch"]), int(mix["seq"])
        self._rng = _big_rng(seed)
        self._vocab = vocab

    def next(self) -> np.ndarray:
        return self._rng.integers(0, self._vocab, (self.batch, self.seq),
                                  dtype=np.int32)

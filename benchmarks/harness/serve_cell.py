"""A serving cell: `LLMEngine` driven in this process, open or closed loop.

The engine is the program's own (`ray_tpu.serve.llm.LLMEngine`), built
and driven through its public surface: the constructor, `start`,
`submit`, `metrics`, `reset_stats`, `stop`, and the timestamps it leaves
on each `GenRequest`. No cluster, no replica, no checkpoint.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from . import configs, stats, traffic, weights

now = time.perf_counter          # the engine stamps requests on this clock


def build_engine(family, config: dict, seed: int, log, mark, degrade=None):
    """-> (cfg, params, engine), the program's configuration object and
    weights as `family` (harness/families.py) makes them. `degrade`
    (benchmarks/tools only) maps the true weights to the ones the engine
    serves, so that the check can be shown to fail a lower-precision
    server; the reference keeps the true ones. The `serve` block's
    `prefix_cache`, `kv_dtype` and `spec_draft` are the engine's options
    of those names; a file without them runs with the prefix cache off,
    a bf16 pool and no draft model. `mark` (run.RunContext.mark) ends
    the run's phases `weights` and `engine`."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import LLMEngine

    geo = config["serve"]
    cfg = family.program_config(config, max_seq=geo["max_len"])
    t0 = now()
    params = weights.make_params(family.model(), cfg, seed, jnp.bfloat16,
                                 geo["tp"])
    log(f"weights: {sum(a.nbytes for a in params.values()) / 1e9:.2f} GB "
        f"bf16 made on the device in {now() - t0:.1f}s (tp={geo['tp']})")
    mark("weights")
    t0 = now()
    served = params if degrade is None else degrade(params)
    eng = LLMEngine(
        cfg, served, n_slots=geo["n_slots"], max_len=geo["max_len"], seed=0,
        kv_mode=geo["kv_mode"], page_size=geo["page_size"],
        n_pages=geo["n_pages"], attn_impl=geo["attn_impl"],
        prefill_chunk=geo["prefill_chunk"],
        prefix_cache=geo.get("prefix_cache", False), tp=geo["tp"],
        weight_dtype=geo["weight_dtype"], kv_dtype=geo.get("kv_dtype", "bf16"),
        kv_transfer=False, spec_draft=geo.get("spec_draft", ""), warmup=False,
        decode_block=geo.get("decode_block"))
    log(f"engine built in {now() - t0:.1f}s: attn_impl={eng.attn_impl} "
        f"n_slots={eng.n_slots} pages={eng.n_pages}x{eng.page_size}")
    mark("engine")
    return cfg, params, eng


def _pow2_width(n: int) -> int:
    width = 1
    while width < n:
        width *= 2
    return width


def programs_needed(p_lens, o_lens, geo: dict) -> tuple[list, list]:
    """The page-table widths this traffic's decode steps run at, and the
    widths at which a prompt's LAST chunk (the one with the head) runs.
    The engine's rule (`_decode_table_view`, `_chunk_width`): the pages
    covering the tokens so far, rounded up to a power of two, capped at
    max_len's pages. A decode window grows its slots up to 8 tokens
    ahead."""
    page, cap = geo["page_size"], -(-geo["max_len"] // geo["page_size"])
    width = lambda n_tokens: min(_pow2_width(-(-n_tokens // page)), cap)
    decode, last_chunk = set(), {}
    for p, o in zip(p_lens, o_lens):
        p, o = int(p), int(o)
        lo, hi = p + 1, min(p + o + 8, geo["max_len"])
        decode.update(width(n) for n in range(lo, hi + 1, page))
        decode.add(width(hi))
        w = width(p)
        last_chunk[w] = max(last_chunk.get(w, 0), p)
    return sorted(decode), sorted(last_chunk.values())


def warm_up(eng, geo: dict, p_lens, o_lens, vocab: int, seed: int, log) -> None:
    """Every program THIS traffic can dispatch, and no other, before the
    ramp, each by a short request alone in the engine. For each decode
    width: the shortest prompt whose pages round up to it, decoded through
    a window of 8 or 2, then of 2 and of 1 step, which are the two decode
    programs (`_decode_sample_paged` and `decode_step_paged`). For each
    width a prompt can END in: the longest such prompt of the mix, which
    runs the headless chunk program at every width on its way and that
    width's chunk program with the head. (The
    engine's own `warmup_compile` walks the whole ladder, 12 programs at
    max_len 2048; fixed-length traffic uses five of them.) A shape this
    misses compiles or loads inside the window, and the run exits
    non-zero."""
    t0 = now()
    eng.start()
    page, max_len = geo["page_size"], geo["max_len"]
    out = min(12, max(4, page // 2))
    rng = np.random.default_rng(seed)
    widths, last_chunk = programs_needed(p_lens, o_lens, geo)
    # the largest power of two under w pages, and one token more
    below = lambda w: (1 << ((w - 1).bit_length() - 1)) if w > 1 else 0
    prompts = [max(2, below(w) * page + 1) for w in widths] + last_chunk
    for n_prompt in prompts:
        req = eng.submit(rng.integers(1, vocab, n_prompt).tolist(),
                         max_tokens=out, temperature=0.0, eos_id=None)
        if not req.done.wait(600) or req.error:
            raise RuntimeError(f"warm-up request of {n_prompt} tokens "
                               f"failed: {req.error or 'timed out'}")
    log(f"warm-up: decode at table widths {widths}, prompts of {prompts} "
        f"tokens, in {now() - t0:.1f}s")


class Driver(threading.Thread):
    """The load generator: one thread, started at `t0`.

    open_loop: submits each planned request when it is due (due times are
    relative to the window's start, `t0 + ramp_s`), and goes on after the
    window so that measured requests finish under the same load.
    closed_loop: keeps `clients` requests in flight, each client's next
    sent when its last completes (polled every 2 ms)."""

    def __init__(self, eng, kind: str, t_window: float, plan=None,
                 source=None, clients: int = 0):
        super().__init__(name="bench-driver", daemon=True)
        self.eng, self.kind, self.t_window = eng, kind, t_window
        self.plan, self.source, self.clients = plan, source, clients
        self.records: list[dict] = []
        self.stop = threading.Event()

    def _submit(self, spec: dict, due: float) -> dict:
        rec = {"due": due, "index": spec["index"], "req": None,
               "error": None, "sent": now(),
               "measured": spec.get("measured", False)}
        try:
            rec["req"] = self.eng.submit(
                spec["prompt"], max_tokens=spec["max_tokens"],
                temperature=0.0, eos_id=None)
        except Exception as e:  # noqa: BLE001 — a refused request is a failed one
            rec["error"] = repr(e)
        self.records.append(rec)
        return rec

    def run(self) -> None:
        if self.kind == "open_loop":
            for spec in self.plan:
                due = self.t_window + spec["due"]
                if self.stop.wait(max(0.0, due - now())):
                    return
                self._submit(spec, due)
            return
        live = [self._submit(self.source.next(), now())
                for _ in range(self.clients)]
        while not self.stop.wait(0.002):
            for i, rec in enumerate(live):
                if rec["req"] is None or rec["req"].done.is_set():
                    live[i] = self._submit(self.source.next(), now())


def _tokens_out(records) -> int:
    return sum(len(r["req"].out_ids) for r in records if r["req"] is not None)


def _sample(eng, records, samples: dict) -> None:
    decoding = [r["req"] for r in records
                if r["req"] is not None and r["req"].first_token_at is not None
                and not r["req"].done.is_set()]
    samples["t"].append(now())
    samples["queued"].append(eng.metrics()["queued"])
    samples["awaiting_first_token"].append(sum(
        1 for r in records if r["req"] is not None
        and r["req"].first_token_at is None and not r["req"].done.is_set()))
    samples["decoding_slots"].append(len(decoding))
    samples["kv_tokens_decoding"].append(
        sum(q.n_prompt + len(q.out_ids) for q in decoding))


def make_traffic(kind: str, mix: dict, seconds: float, seed: int,
                 vocab: int, clients: int) -> dict:
    """-> plan or source, the generator's own statistics, and every
    (prompt, output) length it can send (what the warm-up is cut to)."""
    if kind == "open_loop":
        made = traffic.open_loop_plan(mix, seconds, seed, vocab)
        return {"plan": made["requests"], "source": None,
                "stats": made["stats"],
                "p_lens": [len(r["prompt"]) for r in made["requests"]],
                "o_lens": [r["max_tokens"] for r in made["requests"]]}
    source = traffic.ClosedLoopSource(mix, seed, vocab)
    return {"plan": None, "source": source,
            "stats": dict(source.stats, clients=clients),
            "p_lens": source.p_len, "o_lens": source.o_len}


def run_window(eng, kind: str, mix: dict, made: dict, seconds: float,
               clients: int, tracer, compiles, memory_stats) -> dict:
    """Ramp, then the measured window of `seconds`, then the drain.
    -> everything the metrics are made from."""
    t0 = now()
    t_window = t0 + float(mix["ramp_s"])
    t_end = t_window + seconds
    driver = Driver(eng, kind, t_window, made["plan"], made["source"], clients)
    driver.start()
    time.sleep(max(0.0, t_window - now()))
    # ---------------------------------------------------------- the window
    eng.reset_stats()
    c0 = compiles()
    t_window_real = now()
    tokens0 = last_total = _tokens_out(list(driver.records))
    samples = {"t": [], "queued": [], "awaiting_first_token": [],
               "decoding_slots": [], "kv_tokens_decoding": []}
    # The engine hands tokens over a decode window at a time (8 steps of
    # every decoding slot at once), so the count moves in jumps a second
    # or more apart. Each jump is noted to 10 ms: the rate is taken from
    # the first jump to the last, whole ticks of work over their own time.
    emissions = []              # (t, tokens emitted so far) at each jump
    next_sample = t_window_real
    worst_oversleep = 0.0       # a stalled process shows here too
    while now() < t_end:
        records = list(driver.records)
        total = _tokens_out(records)
        if total != last_total:
            emissions.append((now(), total))
            last_total = total
        if now() >= next_sample:
            tracer.maybe_start(now(), t_end)
            _sample(eng, records, samples)
            next_sample += 0.25
        nap = max(0.0, min(0.01, t_end - now()))
        t_nap = now()
        time.sleep(nap)
        worst_oversleep = max(worst_oversleep, now() - t_nap - nap)
    t_end_real = now()
    tokens1 = _tokens_out(list(driver.records))
    engine = eng.metrics()
    engine["compiles_in_window"] = compiles() - c0
    memory = memory_stats()     # before the drain and the reference
    tracer.stop()
    # ------------------------------------------------------------ the drain
    if kind == "open_loop":
        # Arrivals go on (unmeasured) while the measured requests finish.
        pick = lambda r: r["measured"]
        deadline = t_end + float(mix["drain_cap_s"])
        while now() < deadline and not all(
                r["req"] is None or r["req"].done.is_set()
                for r in list(driver.records) if pick(r)):
            time.sleep(0.05)
    else:
        # Answered (or refused) inside the window, whenever it was sent: a
        # saturated closed loop holds a request longer than a window lasts.
        def pick(r):
            q = r["req"]
            if q is None:
                return t_window_real <= r["sent"] < t_end_real
            return (q.done.is_set() and q.finished_at is not None
                    and t_window_real <= q.finished_at < t_end_real)
    all_records = list(driver.records)
    records = [r for r in all_records if pick(r)]
    driver.stop.set()
    driver.join(10)
    return {"records": records, "all_records": all_records,
            "window_s": t_end_real - t_window_real, "t_window": t_window_real,
            "worst_oversleep_s": worst_oversleep, "emissions": emissions,
            "engine": engine, "samples": samples, "memory": memory,
            "tokens_in_window": tokens1 - tokens0}


def emission_rate(win: dict) -> tuple[float, float]:
    """-> (tokens per second, the seconds it was taken over). From the
    first jump of the emitted-token count inside the window to the last:
    the tokens that appeared after the first, over the time between. A
    window with fewer than two jumps falls back to its two ends."""
    ev = win["emissions"]
    if len(ev) >= 2 and ev[-1][0] - ev[0][0] >= 0.5 * win["window_s"]:
        span = ev[-1][0] - ev[0][0]
        return (ev[-1][1] - ev[0][1]) / span, span
    return win["tokens_in_window"] / win["window_s"], win["window_s"]


def request_rows(records, t_window: float) -> list[dict]:
    """One dict per measured request, seconds relative to the window."""
    rows = []
    for r in records:
        q = r["req"]
        row = {"due": r["due"] - t_window, "sent": r["sent"] - t_window,
               "lateness": r["sent"] - r["due"], "index": r["index"],
               "ok": bool(q is not None and q.done.is_set()
                          and q.error is None and not q.truncated
                          and not q.migrated
                          and len(q.out_ids) == q.max_tokens)}
        if q is not None:
            rel = lambda t: None if t is None else t - t_window
            row.update(submitted_at=rel(q.submitted_at),
                       first_chunk_at=rel(q.first_chunk_at),
                       first_token_at=rel(q.first_token_at),
                       finished_at=rel(q.finished_at) if q.done.is_set()
                       else None,
                       n_prompt=q.n_prompt, n_out=len(q.out_ids))
            if q.first_token_at is not None:
                row["ttft"] = q.first_token_at - r["due"]
            if row["ok"] and row["n_out"] > 1:
                row["tpot"] = ((q.finished_at - q.first_token_at)
                               / (row["n_out"] - 1))
        rows.append(row)
    return rows


def tail_ms(rows, field: str, q: float) -> float | None:
    """q-quantile of `field` over ALL measured requests, in ms; a request
    that failed or never got there counts as the window's worst."""
    got = [w[field] for w in rows if w.get(field) is not None and w["ok"]]
    if not got:
        return None
    worst = max(got)
    vals = [w[field] if (w["ok"] and w.get(field) is not None) else worst
            for w in rows]
    return stats.quantile(vals, q) * 1000.0


def check_streams(reference, ref_cfg, params, config: dict, records,
                  seed: int, log, compiles) -> dict:
    """Is the served stream as close to the float32 reference as a plain
    bf16 forward of the same weights is?

    A seeded sample of requests; for every emitted token, in the float32
    reference's logits over the request's own prompt + output, its
    DEFICIT: the row's best logit minus the emitted token's (0 when it is
    the best). Beside it, on the same row, the deficit of the token a
    plain bfloat16 forward would have emitted (`reference.paired_rows`
    with the family's `ref_cfg`).
    Greedy decoding in bf16 may leave the float32 best at near-ties, and
    how often and how far is the arithmetic's own noise, which the plain
    bf16 forward shows. The served stream passes if its MEAN deficit and
    its WORST deficit are each at most `reference_factor` (3) times the
    plain bf16 forward's, plus `deficit_slack`. The mean catches lost
    precision and an emitter of runners-up (whose mean deficit is the
    mean gap between the two best logits, hundreds of times the noise);
    the worst catches a rare wrong token (a stale page, a mask off by
    one: logits move by whole units). No constant stands for "how noisy
    bf16 is": the run measures it. What it cannot see: a loss of
    precision under about a doubling of the noise (weights rounded
    through int8 read 1.97 x the plain forward's mean deficit on the
    chip); the configuration's file says why the factor is 3.

    Every stream is padded to ONE length, the configuration's
    `serve.max_len`: the references are causal, so the rows under a
    stream's own length do not depend on what follows them, and the
    reference is one program a configuration, compiled once a machine
    and found in the compile cache by every seed after: a program a
    padded length is 25-50 s each, and which lengths come up is the
    seed's, so a run's end would be a lottery against the driver's 360 s
    (PERF.md section 6, PR 47). The rows are cut on the host: a slice of
    a device array is a program a distinct slice. `compiles`
    (run.CompileCounter) counts the programs the check compiled and the
    ones it loaded, for its last log line."""
    import jax
    import jax.numpy as jnp

    geo = config["serve"]
    have = [r for r in records if r["req"] is not None
            and r["req"].error is None and len(r["req"].out_ids) > 0]
    rng = np.random.default_rng(seed)
    picks = [have[i] for i in rng.permutation(len(have))[:geo["ref_sample"]]]
    if not picks:
        return {"ok": False, "why": "no emitted token to check"}
    ref = jax.jit(reference.paired_rows, static_argnums=(2,))
    asked0, missed0 = compiles(), compiles.misses
    t0 = now()
    served, plain, n_top1 = [], [], 0
    for r in picks:
        q = r["req"]
        prompt, out = list(q.prompt_ids[:q.n_prompt]), list(q.out_ids)
        n = len(prompt) + len(out)
        seq = np.zeros(geo["max_len"], np.int32)
        seq[:n] = prompt + out
        rows = slice(len(prompt) - 1, n - 1)
        top, arg, at_served, at_plain = (
            np.asarray(a)[rows] for a in ref(params, jnp.asarray(seq), ref_cfg))
        top = top.astype(np.float32)
        d_served = top - at_served.astype(np.float32)
        d_plain = top - at_plain.astype(np.float32)
        if d_served.shape[0] != len(out) or not (
                np.all(np.isfinite(d_served)) and np.all(np.isfinite(d_plain))):
            return {"ok": False, "why": f"request {r['index']}: non-finite "
                                        "or missing reference rows"}
        served.append(d_served)
        plain.append(d_plain)
        n_top1 += int(np.sum(arg == np.asarray(out)))
    served, plain = np.concatenate(served), np.concatenate(plain)
    factor, slack = geo["reference_factor"], geo["deficit_slack"]
    got = {"n_tokens": int(served.size),
           "mean_deficit": float(served.mean()),
           "mean_deficit_plain_bf16": float(plain.mean()),
           "worst_deficit": float(served.max()),
           "worst_deficit_plain_bf16": float(plain.max()),
           "top1_share": n_top1 / served.size,
           "top1_share_plain_bf16": float(np.mean(plain == 0.0))}
    got["limits"] = {arm: factor * got[arm + "_plain_bf16"] + slack
                     for arm in ("mean_deficit", "worst_deficit")}
    got["ok"] = all(got[arm] <= limit for arm, limit in got["limits"].items())
    log(f"reference check over {len(picks)} requests, {served.size} emitted "
        f"tokens, in float32 logits; served stream / plain bf16 forward: "
        f"mean deficit {got['mean_deficit']:.6f} / "
        f"{got['mean_deficit_plain_bf16']:.6f}, worst {got['worst_deficit']:.4f}"
        f" / {got['worst_deficit_plain_bf16']:.4f}, top-1 share "
        f"{got['top1_share']:.4f} / {got['top1_share_plain_bf16']:.4f}; "
        f"allowed {factor} x plain + {slack}: "
        f"{'ok' if got['ok'] else 'FAILED'}")
    asked, missed = compiles() - asked0, compiles.misses - missed0
    got["programs_compiled"], got["programs_loaded"] = missed, asked - missed
    log(f"reference programs: every stream padded to {geo['max_len']} "
        f"tokens; {missed} compiled, {asked - missed} loaded from the "
        f"compile cache; the check took {now() - t0:.1f}s")
    return got


def run(rc, degrade=None) -> dict:
    """rc: run.RunContext. -> the cell's result (see run.main)."""
    config, mix, log = rc.config, rc.traffic, rc.log
    geo = config["serve"]
    kind = mix["kind"]
    cfg, params, eng = build_engine(rc.family, config, rc.seed, log, rc.mark,
                                    degrade)
    if rc.platform == "tpu" and eng.attn_impl != "kernel":
        raise SystemExit(f"engine resolved attn_impl={eng.attn_impl!r} on a "
                         "TPU; the cell measures the kernel path")
    clients = mix.get("clients")
    if clients == "n_slots":
        clients = geo["n_slots"]
    made = make_traffic(kind, mix, rc.seconds, rc.seed, cfg.vocab_size,
                        clients or 0)
    log(f"traffic {kind}: {made['stats']}")
    warm_up(eng, geo, made["p_lens"], made["o_lens"], cfg.vocab_size,
            rc.seed, log)
    rc.mark("warm_up")
    win = run_window(eng, kind, mix, made, rc.seconds, clients or 0,
                     rc.tracer, rc.compiles, rc.memory_stats)
    rc.mark_setup_end(win["t_window"])
    rc.mark("ramp", win["t_window"])
    rc.mark("window", win["t_window"] + win["window_s"])
    eng.stop()
    rows = request_rows(win["records"], win["t_window"])
    refused = [r["error"] for r in win["records"] if r["error"]]
    if refused:
        log(f"{len(refused)} request(s) refused at submit, the first: "
            f"{refused[0]}")
    late = [w["lateness"] for w in rows]
    if kind == "open_loop" and late:
        log(f"generator lateness (sent - due) over {len(late)} measured "
            f"requests: median {stats.quantile(late, 0.5) * 1e3:.2f} ms, "
            f"max {max(late) * 1e3:.2f} ms (due at "
            f"{max(rows, key=lambda w: w['lateness'])['due']:.1f}s)")
    for field in ("ttft", "tpot"):
        vals = [w[field] * 1e3 for w in rows if w.get(field) is not None]
        if vals:
            log(f"{field}_ms over {'measured' if kind == 'open_loop' else 'finished'} "
                f"requests: {stats.summary(vals)}; the highest quantile "
                "with ten samples beyond it is "
                f"{stats.highest_supported_quantile(len(vals))}")
    smp = win["samples"]
    mid = len(smp["t"]) // 2
    at = lambda key: (f"{smp[key][0]}/{smp[key][mid]}/{smp[key][-1]}"
                      if smp[key] else "-")
    rate, rate_s = emission_rate(win)
    log(f"window start/middle/end: queued {at('queued')}, awaiting a first "
        f"token {at('awaiting_first_token')}, decoding slots "
        f"{at('decoding_slots')}, KV tokens of decoding slots "
        f"{at('kv_tokens_decoding')}; engine: completed "
        f"{win['engine'].get('completed')} preemptions "
        f"{win['engine'].get('preemptions')} occupancy "
        f"{win['engine'].get('slot_occupancy')} decode step ms p50/p95 "
        f"{win['engine'].get('decode_step_ms_p50')}/"
        f"{win['engine'].get('decode_step_ms_p95')}; the sampling loop's "
        f"worst oversleep {win['worst_oversleep_s'] * 1e3:.1f} ms")
    log(f"emitted tokens: {win['tokens_in_window']} between the window's "
        f"ends ({win['window_s']:.3f}s), {len(win['emissions'])} jumps; "
        f"rate {rate:.4f}/s over the {rate_s:.3f}s from the first jump to "
        "the last")
    # Free the pool before the reference runs beside the weights.
    eng.cache = None
    del eng
    gc.collect()
    emitting = [r for r in win["all_records"] if r["req"] is not None
                and r["req"].first_token_at is not None
                and (r["req"].finished_at or win["t_window"]) >= win["t_window"]]
    rc.mark("drain")
    check = check_streams(rc.reference, rc.family.reference_config(config),
                          params, config, emitting, rc.seed, log, rc.compiles)
    rc.mark("check")
    failed = sum(1 for w in rows if not w["ok"])
    end_to_end = {"out_tokens_per_s": rate}
    if kind == "open_loop":
        for q in (0.5, 0.9):
            end_to_end[f"ttft_p{round(q * 100)}_ms"] = tail_ms(rows, "ttft", q)
            end_to_end[f"tpot_p{round(q * 100)}_ms"] = tail_ms(rows, "tpot", q)
    ctx = {"engine": win["engine"], "requests": rows,
           "samples": win["samples"], "memory": win["memory"],
           "trace_t0": rc.tracer.t_started,
           "consts": dict(configs.dims(config), chips=geo["chips"],
                          window_s=win["window_s"], n_pages=geo["n_pages"],
                          page_size=geo["page_size"],
                          **rc.family.serve_consts(config))}
    ok = (check["ok"] and failed == 0
          and win["engine"]["compiles_in_window"] == 0)
    # each number `correct` rests on, beside its limit (run.py prints them)
    compared = {arm: {"value": check[arm], "limit": limit}
                for arm, limit in check.get("limits", {}).items()}
    compared["tokens_checked"] = {"value": check.get("n_tokens", 0),
                                  "limit": 1, "at_least": True}
    compared["requests_failed"] = {"value": failed, "limit": 0}
    compared["compiles_in_window"] = {
        "value": win["engine"]["compiles_in_window"], "limit": 0}
    return {"end_to_end": end_to_end, "attempted": len(rows),
            "failed": failed, "correct": bool(ok), "ctx": ctx,
            "compared": compared,
            "memory": win["memory"],
            "compiles_in_window": win["engine"]["compiles_in_window"],
            "notes": {"check": check, "gen_stats": made["stats"],
                      "params": params, "emitting": emitting}}

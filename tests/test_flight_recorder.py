"""Flight recorder: compile watch, live engine load, SLO monitor.

Covers ISSUE 6: JAX compile/recompile observability (jax.monitoring
listener + wrapper attribution + recompile-storm alarm), the engine's
load_snapshot() surface and its replica→controller→dashboard/CLI
propagation, the SLO burn-rate monitor, and the prometheus_text
satellites (label escaping, merge-conflict accounting).

Everything here runs off-TPU: the tiny GPT model compiles on the CPU
backend, and the recompile storm is provoked deliberately by walking a
single request's decode page-table width through its power-of-two ladder
with the detector threshold lowered (see TESTING.md).
"""

import json
import threading
import time
import urllib.request

import pytest

import jax
import jax.numpy as jnp
import numpy as np

import ray_tpu
from ray_tpu import compile_watch, profiling, serve, state
from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.slo import Objective, SloMonitor

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(7))


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _drive(engine, reqs):
    while not all(r.done.is_set() for r in reqs):
        engine.step()


def _compile_spans(fn: str, events=None) -> list[dict]:
    """jax.compile spans attributed to `fn`. Clusterless, the local ring
    holds them; with a cluster up the driver flush loop drains the ring
    to the GCS, so cluster tests pass state.timeline() as `events`."""
    if events is None:
        with profiling._events_lock:
            events = list(profiling._events)
    return [e for e in events
            if e["name"] == "jax.compile"
            and e.get("args", {}).get("fn") == fn]


class TestCompileWatch:
    def test_wrap_attributes_compiles_and_spans(self):
        """Each new input shape through a wrapped jitted callable books
        one jax_compiles_total{fn} increment and a jax.compile span."""
        assert compile_watch.install(storm_threshold=1000)

        fn = compile_watch.wrap(jax.jit(lambda x: x * 3 + 1),
                                "flight_attr_fn")
        before = compile_watch.compiles_total("flight_attr_fn")
        fn(jnp.ones((3,)))
        fn(jnp.ones((5,)))   # new shape → second compile
        fn(jnp.ones((3,)))   # cached → no compile
        delta = compile_watch.compiles_total("flight_attr_fn") - before
        assert delta >= 2
        assert len(_compile_spans("flight_attr_fn")) >= 2

    def test_compiles_outside_wrapped_calls_label_jax(self):
        base = compile_watch.compiles_total("jax")
        jax.jit(lambda x: x - 2)(jnp.ones((11,)))
        assert compile_watch.compiles_total("jax") > base
        assert compile_watch.current_label() == "jax"

    def test_label_context_nests_and_restores(self):
        assert compile_watch.current_label() == "jax"
        with compile_watch.label("outer"):
            assert compile_watch.current_label() == "outer"
            with compile_watch.label("inner"):
                assert compile_watch.current_label() == "inner"
            assert compile_watch.current_label() == "outer"
        assert compile_watch.current_label() == "jax"

    def test_storm_detector_fires_once_then_rearms(self):
        det = compile_watch._StormDetector(threshold=3, window_s=0.2)
        for _ in range(5):
            det.observe("stormy")
        assert len(det.storms) == 1      # one alarm per storm, not per compile
        assert det.storms[0]["fn"] == "stormy"
        assert det.storms[0]["count"] >= 3
        time.sleep(0.25)                 # full window passes → re-armed
        for _ in range(3):
            det.observe("stormy")
        assert len(det.storms) == 2

    def test_storm_counter_and_histogram_rows_exist(self):
        det = compile_watch._StormDetector(threshold=1, window_s=60.0)
        det.observe("row_check_fn")
        rows = {r["name"] for r in profiling.metrics_snapshot()}
        assert "jax_recompile_storms_total" in rows
        assert "jax_compiles_total" in rows
        assert "jax_compile_seconds" in rows


class TestRecompileStorm:
    def test_decode_width_storm_fires_alarm(self, cluster, params):
        """The acceptance scenario: one long decode walks the page-table
        width ladder (1→2→4→…), each width re-lowering the decode
        program. With the threshold lowered the watch must book the
        compiles, the spans, AND the recompile.storm cluster event —
        the PR 4 class of bug as a production alarm."""
        assert compile_watch.install(storm_threshold=3,
                                     storm_window_s=600.0)
        # page_size=2 → 32 pages/slot at max_len=64: ~6 width buckets.
        # n_slots=5 keeps the program shapes unique to this test so jit
        # caches from other tests can't swallow the recompiles.
        engine = LLMEngine(CFG, params, n_slots=5, max_len=64,
                           page_size=2, n_pages=40)
        before = compile_watch.compiles_total("decode_multi_paged")
        _, latest = state.list_cluster_events(return_latest_seq=True)
        _drive(engine, [engine.submit([5, 9, 2], max_tokens=58)])

        # Counter: one compile per visited width bucket.
        delta = compile_watch.compiles_total("decode_multi_paged") - before
        assert delta >= 3, f"expected >=3 decode recompiles, saw {delta}"
        # Tracing span per compile, attributed to the owning program.
        assert len(_compile_spans("decode_multi_paged",
                                  events=state.timeline())) >= 3
        # Storm detector fired, locally and as a structured cluster event.
        storms = [s for s in compile_watch.storm_log()
                  if s["fn"] == "decode_multi_paged"]
        assert storms and storms[0]["count"] >= 3
        # The cluster event is emitted off the compile thread (a GCS
        # stall must not freeze the engine loop) — poll briefly.
        deadline = time.monotonic() + 30
        storm_events = []
        while time.monotonic() < deadline and not storm_events:
            events = state.list_cluster_events(after_seq=latest)
            storm_events = [e for e in events
                            if e["type"] == "recompile.storm"
                            and e.get("fn") == "decode_multi_paged"]
            if not storm_events:
                time.sleep(0.2)
        assert storm_events, f"no recompile.storm in {events}"
        ev = storm_events[0]
        assert ev["severity"] == "WARNING"
        assert ev["threshold"] == 3
        assert "re-lowering" in ev["message"]


class TestLoadSnapshot:
    def test_burst_snapshot_consistent_with_scheduler(self, params):
        """Mid-burst and at drain, load_snapshot() must agree with the
        scheduler's own bookkeeping — these numbers feed the router."""
        engine = LLMEngine(CFG, params, n_slots=4, max_len=64,
                           page_size=4, n_pages=24,
                           prefill_chunk=8, prefill_token_budget=8)
        reqs = [engine.submit(list(range(2, 18)), max_tokens=4)
                for _ in range(6)]
        for _ in range(3):   # a few ticks: slots mid-prefill, queue deep
            engine.step()
            snap = engine.load_snapshot()
            assert snap["queue_depth"] == (engine.pending.qsize()
                                           + len(engine._deferred))
            assert snap["active_slots"] == sum(
                r is not None for r in engine.slot_req)
            assert snap["prefilling_slots"] == len(engine._prefilling)
            assert snap["decoding_slots"] == (snap["active_slots"]
                                              - snap["prefilling_slots"])
            assert snap["slot_utilization"] == round(
                snap["active_slots"] / engine.n_slots, 4)
            # Page accounting closes: free + held == pool.
            held = int(engine.pool.slot_n_pages.sum())
            assert snap["pool_pages_free"] == engine.pool.n_free
            assert snap["pool_pages_free"] + held == snap["pool_pages_total"]
            assert snap["pool_pages_free_min"] <= snap["pool_pages_free"]
            assert snap["prefill_chunk"] == 8
            assert snap["prefill_token_budget"] == 8
        _drive(engine, reqs)
        snap = engine.load_snapshot()
        assert snap["active_slots"] == 0
        assert snap["queue_depth"] == 0
        assert snap["pool_pages_free"] == snap["pool_pages_total"]
        assert snap["ttft_ewma_ms"] > 0
        assert snap["decode_tok_s_ewma"] > 0
        assert 0.0 < snap["prefill_budget_util"] <= 1.0

    def test_snapshot_sets_gauges(self, params):
        engine = LLMEngine(CFG, params, n_slots=2, max_len=32,
                           page_size=4, n_pages=16)
        engine.load_snapshot()
        rows = {r["name"]: r for r in profiling.metrics_snapshot()
                if r["name"].startswith("llm_")}
        for name in ("llm_queue_depth", "llm_active_slots",
                     "llm_prefilling_slots", "llm_pool_pages_free",
                     "llm_pool_pages_total"):
            assert name in rows, f"{name} gauge missing"
            assert rows[name]["tags"]["replica"] == "local"
        assert rows["llm_pool_pages_total"]["value"] == 16.0

    def test_default_engine_snapshot_has_the_pool_fields(self, params):
        """An engine built with no option has a pool: half the slots'
        worst case, and its snapshot and gauges say so."""
        engine = LLMEngine(CFG, params, n_slots=2, max_len=32,
                           page_size=8)
        snap = engine.load_snapshot()
        assert snap["pool_pages_total"] == snap["pool_pages_free"] == 5
        assert snap["prefill_chunk"] == 32 and snap["active_slots"] == 0


def _hist_rows(name: str, buckets, boundaries=(0.5, 2.0)):
    return [{"name": name, "kind": "histogram", "tags": {"route": "/x"},
             "value": float(sum(buckets)), "sum": 1.0,
             "buckets": list(buckets), "boundaries": list(boundaries)}]


class TestSloMonitor:
    def test_burn_rate_math_and_violation_event(self, cluster):
        """10% of requests over a p95 threshold burns budget at 2x."""
        obj = Objective("flight_ttft_p95", "flight_slo_s", 0.95, 2.0,
                        window_s=60.0)
        mon = SloMonitor([obj], rows_fn=lambda: [])
        _, latest = state.list_cluster_events(return_latest_seq=True)
        # First evaluation = lifetime view: informative, never an alarm.
        st0, = mon.evaluate(rows=_hist_rows("flight_slo_s", (10, 0, 0)))
        assert st0["baseline"] == "lifetime" and not mon.events
        # Windowed: delta (85, 5, 10) → 10% bad of a 5% budget = 2x burn.
        st, = mon.evaluate(rows=_hist_rows("flight_slo_s", (95, 5, 10)))
        assert st["status"] == "violating" and st["violating"]
        assert st["baseline"] == "window"
        assert st["samples"] == 100
        assert st["burn_rate"] == pytest.approx(0.10 / 0.05)
        assert mon.events and mon.events[0]["slo"] == "flight_ttft_p95"
        events = state.list_cluster_events(after_seq=latest)
        viol = [e for e in events if e["type"] == "slo.violation"]
        assert viol and viol[0]["slo"] == "flight_ttft_p95"
        assert viol[0]["severity"] == "WARNING"
        # burn-rate gauge exported for scrapers
        rows = [r for r in profiling.metrics_snapshot()
                if r["name"] == "slo_burn_rate"
                and r["tags"].get("slo") == "flight_ttft_p95"]
        assert rows and rows[0]["value"] == pytest.approx(2.0)
        # Same cumulative snapshot again: the in-window baseline is still
        # the first snapshot, so the delta (and verdict) are unchanged —
        # and the ok→violating edge does not re-fire the event.
        st2, = mon.evaluate(rows=_hist_rows("flight_slo_s", (95, 5, 10)))
        assert st2["status"] == "violating"
        assert len(mon.events) == 1

    def test_windowed_delta_not_lifetime(self):
        """A violating past must not condemn a healthy present: the
        second evaluation scores only the delta since the first."""
        obj = Objective("flight_win", "flight_win_s", 0.95, 2.0,
                        window_s=60.0)
        mon = SloMonitor([obj], rows_fn=lambda: [])
        st, = mon.evaluate(rows=_hist_rows("flight_win_s", (0, 0, 50)))
        assert st["violating"]                  # lifetime READ still honest
        assert st["baseline"] == "lifetime"     # ...but labeled, no alarm
        assert not mon.events
        st, = mon.evaluate(rows=_hist_rows("flight_win_s", (1000, 0, 50)))
        assert not st["violating"]      # delta = 1000 good, 0 bad
        assert st["baseline"] == "window"
        assert st["samples"] == 1000

    def test_threshold_inside_bucket_counts_bad(self):
        """Conservative bucket math: a threshold strictly inside a bucket
        must not credit that bucket as good."""
        obj = Objective("flight_cons", "flight_cons_s", 0.5, 1.5,
                        window_s=60.0)
        mon = SloMonitor([obj], rows_fn=lambda: [])
        # boundaries (0.5, 2.0): threshold 1.5 lands inside (0.5, 2.0].
        st, = mon.evaluate(rows=_hist_rows("flight_cons_s", (50, 50, 0)))
        assert st["good_fraction"] == pytest.approx(0.5)

    def test_passive_monitor_reads_without_alarming(self, cluster):
        """export=False (the CLI's one-shot read): full evaluation, but
        no slo.violation cluster event and no slo_burn_rate gauge — a
        read-only command must not file alarms off lifetime totals."""
        obj = Objective("flight_passive", "flight_passive_s", 0.95, 2.0,
                        window_s=60.0)
        mon = SloMonitor([obj], rows_fn=lambda: [], export=False)
        _, latest = state.list_cluster_events(return_latest_seq=True)
        mon.evaluate(rows=_hist_rows("flight_passive_s", (10, 0, 0)))
        st, = mon.evaluate(rows=_hist_rows("flight_passive_s", (10, 0, 50)))
        assert st["violating"]                    # the READ still works
        assert mon.events                         # local mirror kept
        assert not [e for e in state.list_cluster_events(after_seq=latest)
                    if e["type"] == "slo.violation"
                    and e.get("slo") == "flight_passive"]
        assert not [r for r in profiling.metrics_snapshot()
                    if r["name"] == "slo_burn_rate"
                    and r["tags"].get("slo") == "flight_passive"]

    def test_tag_filter_and_no_data(self):
        obj = Objective("flight_tagged", "flight_tag_s", 0.95, 2.0,
                        tags={"route": "/other"})
        mon = SloMonitor([obj], rows_fn=lambda: [])
        st, = mon.evaluate(rows=_hist_rows("flight_tag_s", (10, 0, 0)))
        assert st["status"] == "no_data" and not st["violating"]

    def test_quantile_estimate_interpolates(self):
        obj = Objective("flight_q", "flight_q_s", 0.5, 10.0, window_s=60.0)
        mon = SloMonitor([obj], rows_fn=lambda: [])
        # All 100 obs in (0.5, 2.0]: p50 interpolates to the bucket middle.
        st, = mon.evaluate(rows=_hist_rows("flight_q_s", (0, 100, 0)))
        assert 0.5 < st["quantile_est_s"] < 2.0


class TestPrometheusSatellites:
    @staticmethod
    def _unescape(s: str) -> str:
        out, i = [], 0
        while i < len(s):
            if s[i] == "\\" and i + 1 < len(s):
                out.append({"n": "\n"}.get(s[i + 1], s[i + 1]))
                i += 2
            else:
                out.append(s[i])
                i += 1
        return "".join(out)

    def test_label_escaping_round_trip(self):
        hostile = 'a\\b"c\nd{e="f"}'
        text = profiling.prometheus_text(
            [{"name": "esc_check", "kind": "gauge",
              "tags": {"path": hostile}, "value": 1.0}])
        line, = [ln for ln in text.splitlines()
                 if ln.startswith("esc_check{")]
        assert "\n" not in line          # raw newline would split the row
        escaped = line[len('esc_check{path="'):-len('"} 1.0')]
        assert self._unescape(escaped) == hostile

    def test_histogram_le_labels_unaffected_by_escaping(self):
        h = profiling.Histogram("esc_hist_s", boundaries=(1.0,),
                                tag_keys=("q",))
        h.observe(0.5, tags={"q": 'x"y'})
        text = profiling.prometheus_text(profiling.metrics_snapshot())
        assert 'esc_hist_s_bucket{q="x\\"y",le="1.0"} 1' in text

    def test_merge_conflict_counted_in_exposition(self):
        """Boundary-mismatched histogram rows are dropped, but the drop is
        itself a visible series — no more silent shrinking totals."""
        a = {"name": "conf_lat_s", "kind": "histogram", "tags": {},
             "value": 2.0, "buckets": [1, 1, 0], "sum": 3.0,
             "boundaries": [1, 10]}
        b = {**a, "buckets": [1, 0, 1, 0], "boundaries": [1, 5, 10]}
        text = profiling.prometheus_text([a, b, dict(b)])
        assert 'metrics_merge_conflicts_total{metric="conf_lat_s"} 2' in text
        assert "# TYPE metrics_merge_conflicts_total counter" in text
        # the first-seen definition still renders
        assert 'conf_lat_s_bucket{le="1"} 1' in text
        # Counter semantics: the tally is process-cumulative and stays in
        # the exposition after the conflict clears (monotone — a vanished
        # or reset series would defeat increase()-style alerting).
        text_clean = profiling.prometheus_text([a])
        assert 'metrics_merge_conflicts_total{metric="conf_lat_s"} 2' \
            in text_clean


def _get_json(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        assert r.status == 200
        return json.loads(r.read())


class TestServeLoadSurface:
    @pytest.fixture(scope="class")
    def loaded_serve(self, cluster):
        """A deployment whose callable exposes load_snapshot(), plus a
        dashboard: the full replica→controller→HTTP propagation path."""

        @serve.deployment(name="flight_lb", num_replicas=2)
        class Loady:
            def __call__(self, req):
                return {"ok": True}

            def load_snapshot(self):
                return {"queue_depth": 1, "active_slots": 2,
                        "pool_pages_free": 7, "pool_pages_total": 8}

        handle = serve.run(Loady.bind())
        assert ray_tpu.get(handle.remote({}), timeout=60) == {"ok": True}
        from ray_tpu.dashboard import start_dashboard

        dash = start_dashboard(port=0)
        try:
            yield dash
        finally:
            dash.stop()

    def _wait_load(self, dash):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            deps = _get_json(dash.url + "/api/serve/load")["deployments"]
            reps = deps.get("flight_lb", {}).get("replicas", [])
            if reps and all(r.get("load") for r in reps):
                return deps
            time.sleep(0.5)
        pytest.fail(f"replica load never reached /api/serve/load: {deps}")

    def test_api_serve_load_propagates_engine_load(self, loaded_serve):
        deps = self._wait_load(loaded_serve)
        info = deps["flight_lb"]
        assert info["num_replicas"] == 2
        assert len(info["replicas"]) == 2
        for rep in info["replicas"]:
            assert rep["load"]["queue_depth"] == 1
            assert rep["load"]["pool_pages_free"] == 7
            assert "inflight" in rep and "processed" in rep

    def test_serve_status_carries_replica_load(self, loaded_serve):
        self._wait_load(loaded_serve)
        st = serve.status()["flight_lb"]
        assert len(st["replica_load"]) == 2
        for stats in st["replica_load"].values():
            assert stats["load"]["active_slots"] == 2

    def test_cli_status_serve_renders_load_and_slo(self, loaded_serve):
        self._wait_load(loaded_serve)
        from ray_tpu.scripts.cli import render_serve_status

        text = render_serve_status()
        assert "flight_lb" in text
        assert "2/2 replicas" in text
        assert "queue_depth=1" in text
        assert "pool_pages_free=7" in text
        assert "slo:" in text    # SLO table renders even with no traffic

    def test_api_slo_serves_objectives(self, loaded_serve):
        objs = _get_json(loaded_serve.url + "/api/slo")["objectives"]
        names = {o["name"] for o in objs}
        assert {"llm_ttft_p95", "http_request_p95"} <= names
        for o in objs:
            assert o["status"] in ("ok", "violating", "no_data")
            assert "burn_rate" in o

    def test_traces_and_timeline_still_serve(self, loaded_serve):
        """Smoke: the new routes must not shadow the PR 1 surfaces."""
        assert isinstance(_get_json(loaded_serve.url + "/api/traces"), list)
        assert isinstance(
            _get_json(loaded_serve.url + "/api/timeline"), list)

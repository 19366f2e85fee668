"""Chaos: deterministic fault injection against the serve tier + cluster.

Covers the zero-drop serving contract (ISSUE 9): the seeded chaos
harness (ray_tpu/chaos.py), the replica drain protocol (engine
continuation export + controller drain-before-kill), cross-replica
decode failover at the proxies/handles, controller kill -9 survival, and
the committed acceptance scenario (32 SSE streams through a replica
SIGKILL + a scale-down drain with cursor-exact token splices) shared
with bench_chaos.py. Plus the original random-node-kill task test."""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import chaos
from ray_tpu.cluster_utils import Cluster

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class TestChaosHarness:
    """ray_tpu/chaos.py unit behavior: deterministic, seeded, targeted."""

    def teardown_method(self):
        chaos.uninstall()

    def test_counter_rules_fire_deterministically(self):
        chaos.install([{"site": "serve.replica.probe", "action": "raise",
                        "after": 2, "count": 2}])
        fired = []
        for i in range(6):
            try:
                chaos.hit("serve.replica.probe")
            except chaos.ChaosError:
                fired.append(i)
        # hits 0,1 skipped (after=2); hits 2,3 fire (count=2); 4,5 pass.
        assert fired == [2, 3]
        assert chaos.hits("serve.replica.probe") == 6
        # untouched sites never fire
        chaos.hit("llm.decode_window")

    def test_seeded_probability_is_reproducible(self):
        def run(seed):
            chaos.install([{"site": "serve.replica.probe",
                            "action": "raise", "p": 0.5, "count": -1,
                            "seed": seed}])
            out = []
            for i in range(32):
                try:
                    chaos.hit("serve.replica.probe")
                    out.append(0)
                except chaos.ChaosError:
                    out.append(1)
            return out

        a, b, c = run(7), run(7), run(8)
        assert a == b, "same seed must fire on the same hits"
        assert a != c, "different seeds must differ"
        assert 0 < sum(a) < 32

    def test_delay_action_and_uninstall(self):
        chaos.install([{"site": "serve.replica.probe", "action": "delay",
                        "delay_s": 0.05, "count": 1}])
        t0 = time.perf_counter()
        chaos.hit("serve.replica.probe")
        assert time.perf_counter() - t0 >= 0.05
        chaos.uninstall()
        assert not chaos.active()
        chaos.hit("serve.replica.probe")  # disarmed: no-op

    def test_env_arming(self, monkeypatch):
        spec = json.dumps([{"site": "serve.replica.probe",
                            "action": "drop", "count": 1}])
        monkeypatch.setenv(chaos.ENV_SPEC, spec)
        chaos._arm_from_env()
        assert chaos.active()
        with pytest.raises(chaos.ChaosError):
            chaos.hit("serve.replica.probe")
        monkeypatch.setenv(chaos.ENV_SPEC, "not json")
        chaos._arm_from_env()  # malformed spec disarms loudly, no raise
        assert not chaos.active()


class TestEngineDrain:
    """LLMEngine.drain(): stop admission, finish in-flight, export the
    rest as continuations whose resume is byte-exact."""

    @pytest.fixture(scope="class")
    def setup(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt

        cfg = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)
        params = gpt.init_params(cfg, jax.random.key(42))
        return cfg, params

    def _mk(self, setup, **kw):
        from ray_tpu.serve.llm import LLMEngine

        cfg, params = setup
        kw.setdefault("n_slots", 2)
        kw.setdefault("max_len", 64)
        kw.setdefault("decode_block", 2)
        return LLMEngine(cfg, params, **kw)

    def test_drain_lets_inflight_finish(self, setup):
        eng = self._mk(setup)
        eng.start()
        try:
            req = eng.submit([5, 9, 2], max_tokens=6)
            out = eng.drain(30.0)
        finally:
            eng.stop()
        assert out["drained"] and out["exported"] == 0
        assert req.done.is_set() and not req.migrated
        assert len(req.out_ids) == 6 and req.error is None
        with pytest.raises(RuntimeError, match="draining"):
            eng.submit([1], max_tokens=1)

    def test_drain_timeout_exports_exact_continuations(self, setup):
        # Uninterrupted baseline for the same prompt.
        ref = self._mk(setup)
        base = ref.submit([5, 9, 2], max_tokens=12)
        while not base.done.is_set():
            ref.step()

        eng = self._mk(setup)
        req = eng.submit([5, 9, 2], max_tokens=12, stream=True)
        for _ in range(3):
            eng.step()
        assert not req.done.is_set()
        out = eng.drain(0.0)   # expired window: must export, not wait
        assert not out["drained"] and out["exported"] == 1
        assert req.migrated and req.done.is_set() and req.error is None
        # Stream readers see the sentinel (their replica leg ends).
        toks = []
        while True:
            t = req.stream.get(timeout=5)
            if t is None:
                break
            toks.append(t)
        assert toks == req.out_ids
        c = out["continuations"][0]
        assert c["prompt_ids"] == [5, 9, 2]
        assert c["generated_ids"] == req.out_ids
        assert c["max_tokens"] == 12 and c["request_id"] == req.request_id

        # Teacher-forced resume on a second engine: cursor-exact splice —
        # the already-emitted tokens are seeded, never re-emitted, and
        # the continuation equals the uninterrupted run exactly.
        eng2 = self._mk(setup)
        r2 = eng2.submit(c["prompt_ids"], max_tokens=c["max_tokens"],
                         temperature=c["temperature"], eos_id=c["eos_id"],
                         generated_ids=c["generated_ids"],
                         request_id=c["request_id"], stream=True)
        assert r2.out_ids == req.out_ids  # seeded, not re-emitted
        n_seeded = len(r2.out_ids)
        while not r2.done.is_set():
            eng2.step()
        assert r2.out_ids == base.out_ids
        streamed = []
        while True:
            t = r2.stream.get(timeout=5)
            if t is None:
                break
            streamed.append(t)
        # Only NEW tokens rode the stream: the splice point is exact.
        assert streamed == base.out_ids[n_seeded:]

    def test_already_complete_continuation_finishes_cleanly(self, setup):
        """A replica can die between emitting the FINAL token and the
        reader observing done — the resubmitted continuation is already
        complete (budget or eos reached) and must finish immediately:
        no error, and crucially no decoding PAST the budget/eos."""
        eng = self._mk(setup)
        r = eng.submit([5, 9], max_tokens=4, generated_ids=[1, 2, 3, 4])
        assert r.done.is_set() and r.error is None and not r.truncated
        assert r.out_ids == [1, 2, 3, 4]
        r2 = eng.submit([5, 9], max_tokens=8, eos_id=3,
                        generated_ids=[1, 2, 3])
        assert r2.done.is_set() and r2.out_ids == [1, 2, 3]

    def test_overgrown_continuation_truncates_not_errors(self, setup):
        """prompt + emitted can outgrow the destination engine's cache
        cap (max_len - 1) mid-stream; the resume must end the stream
        cleanly (truncated, like an unresumable in-replica preempt),
        never drop it with an error — while a FRESH oversized prompt
        still raises."""
        eng = self._mk(setup, max_len=9)
        r = eng.submit([1] * 6, max_tokens=16, generated_ids=[2, 3, 4])
        assert r.done.is_set() and r.truncated and r.error is None
        assert r.out_ids == [2, 3, 4]
        with pytest.raises(ValueError, match="prompt too long"):
            eng.submit([1] * 12, max_tokens=4)

    def test_preempted_request_exports_original_prompt(self, setup):
        """After preempt-by-recompute, prompt_ids regrows to prompt +
        generated — the export must still split at the ORIGINAL prompt
        (double-forcing generated tokens would duplicate them)."""
        eng = self._mk(setup, page_size=16)
        req = eng.submit([5, 9, 2], max_tokens=8)
        for _ in range(2):
            eng.step()
        eng._preempt(next(s for s, r in enumerate(eng.slot_req)
                          if r is req))
        out = eng.drain(0.0)
        c = out["continuations"][0]
        assert c["prompt_ids"] == [5, 9, 2]
        assert c["generated_ids"] == req.out_ids


class TestServeFailover:
    """Cluster-level: replica death / drain invisible to clients."""

    def test_unary_failover_on_replica_death(self):
        """A replica SIGKILLed MID-REQUEST costs the client nothing: the
        proxy maps ActorDiedError to one immediate failover retry on a
        re-picked replica before any 5xx (satellite: http_proxy
        _submit/_await_ref)."""
        from ray_tpu import serve
        from ray_tpu.serve.api import _get_controller

        ray_tpu.init(num_cpus=4)
        try:
            @serve.deployment(name="mortal", num_replicas=2)
            class Mortal:
                def __call__(self, req):
                    time.sleep(0.05)
                    return {"pid": os.getpid()}

            serve.run(Mortal.bind())
            _proxy, port = serve.start_proxy()
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/mortal", data=b"{}",
                        timeout=30)
                    break
                except Exception:
                    time.sleep(0.5)
            ctrl = _get_controller()
            table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=30)
            victim = table["routes"]["mortal"]["replicas"][0]
            # Seeded kill: the victim dies abruptly inside its NEXT
            # handle_request — exactly one request observes the death.
            ray_tpu.get(victim.install_chaos.remote(
                [{"site": "serve.replica.request", "action": "kill"}]),
                timeout=30)
            errors = []
            for _ in range(12):
                try:
                    r = urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/mortal", data=b"{}",
                        timeout=60)
                    assert r.status == 200
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
            assert not errors, f"client saw failures: {errors}"
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def test_scale_down_drains_instead_of_killing(self):
        """Scale-down routes through the drain protocol: the shed replica
        leaves the routing table immediately, finishes its in-flight
        work inside serve_drain_timeout_s, and only then is killed —
        in-flight unary requests on the drained replica complete."""
        from ray_tpu import serve

        ray_tpu.init(num_cpus=4,
                     _system_config={"serve_drain_timeout_s": 20.0})
        try:
            @serve.deployment(name="slowpoke", num_replicas=2,
                              max_concurrent_queries=8)
            class Slow:
                def __call__(self, req):
                    time.sleep(req.get("sleep", 0.0))
                    return {"pid": os.getpid()}

            dep = Slow.bind()
            handle = serve.run(dep)
            # Park slow requests on BOTH replicas, then scale down.
            refs = [handle.remote({"sleep": 3.0}) for _ in range(8)]
            time.sleep(0.5)
            serve.run(dep.options(num_replicas=1))
            # The shed replica is draining, not dead: every parked
            # request completes.
            outs = ray_tpu.get(refs, timeout=60)
            assert len({o["pid"] for o in outs}) == 2
            deadline = time.time() + 30
            while time.time() < deadline:
                st = serve.status()["slowpoke"]
                if (st["live_replicas"] == 1
                        and st["draining_replicas"] == 0):
                    break
                time.sleep(0.5)
            st = serve.status()["slowpoke"]
            assert st["live_replicas"] == 1
            assert st["draining_replicas"] == 0
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def test_controller_kill9_mid_reconcile_routes_keep_serving(self):
        """Pins the controller docstring's claim: requests keep flowing
        through a controller kill -9 (chaos: abrupt exit mid-reconcile),
        and the restarted controller ADOPTS the live replicas from its
        checkpoint instead of respawning them."""
        from ray_tpu import serve
        from ray_tpu.serve.api import _get_controller

        ray_tpu.init(num_cpus=4)
        try:
            @serve.deployment(name="steady", num_replicas=2)
            def steady(req):
                return {"ok": True}

            handle = serve.run(steady)
            ctrl = _get_controller()
            table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=30)
            aids_before = {h._actor_id.hex()
                           for h in table["routes"]["steady"]["replicas"]}

            stop = threading.Event()
            failures: list = []
            count = [0]

            def traffic():
                while not stop.is_set():
                    try:
                        out = ray_tpu.get(handle.remote({}), timeout=30)
                        assert out == {"ok": True}
                        count[0] += 1
                    except Exception as e:  # noqa: BLE001
                        failures.append(repr(e))
                    time.sleep(0.02)

            t = threading.Thread(target=traffic, daemon=True)
            t.start()
            # Abrupt controller death two reconcile ticks from now.
            ray_tpu.get(ctrl.install_chaos.remote(
                [{"site": "serve.controller.reconcile", "action": "kill",
                  "after": 2}]), timeout=30)
            # Wait through death + auto-restart: the restarted controller
            # answers get_routing again (fresh reconcile loop running).
            deadline = time.time() + 90
            restarted = False
            time.sleep(3.0)
            while time.time() < deadline:
                try:
                    ctrl2 = _get_controller()
                    if ray_tpu.get(ctrl2.get_routing.remote(-1),
                                   timeout=10):
                        restarted = True
                        break
                except Exception:  # noqa: BLE001 — mid-restart
                    time.sleep(0.5)
            assert restarted, "controller did not come back"
            time.sleep(2.0)  # a couple of post-restart reconcile ticks
            stop.set()
            t.join(timeout=30)
            assert not failures, f"requests failed during kill -9: " \
                                 f"{failures[:3]} (+{len(failures)})"
            assert count[0] > 0
            table = ray_tpu.get(
                _get_controller().get_routing.remote(-1), timeout=30)
            aids_after = {h._actor_id.hex()
                          for h in table["routes"]["steady"]["replicas"]}
            # Adoption, not respawn: the SAME replica actors serve on.
            assert aids_after == aids_before
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def test_ckpt_write_retry_survives_transient_gcs_blip(self):
        """Satellite: checkpoint writes retry with backoff — two injected
        consecutive write failures must not cost the next controller
        restart its state."""
        from ray_tpu import serve
        from ray_tpu.serve.api import _get_controller

        ray_tpu.init(num_cpus=4)
        try:
            @serve.deployment(name="durable")
            def durable(req):
                return 1

            serve.start()
            ctrl = _get_controller()
            # Every checkpoint's first two write ATTEMPTS fail (count=-1
            # with p=1 would kill all retries; after+count target exactly
            # the first two attempts of the FIRST write burst — later
            # writes all succeed, but the deploy right below must survive
            # its own write's blip via retry).
            ray_tpu.get(ctrl.install_chaos.remote(
                [{"site": "serve.controller.ckpt_write", "action": "raise",
                  "count": 2}]), timeout=30)
            serve.run(durable)
            time.sleep(2.0)  # let the retrying writer land
            ray_tpu.kill(ctrl, no_restart=False)
            deadline = time.time() + 90
            while time.time() < deadline:
                try:
                    if "durable" in serve.status():
                        break
                except Exception:  # noqa: BLE001 — mid-restart
                    pass
                time.sleep(0.5)
            assert "durable" in serve.status(), (
                "restarted controller lost the deployment — checkpoint "
                "write was dropped despite retry budget")
        finally:
            serve.shutdown()
            ray_tpu.shutdown()


class TestZeroDrop:
    """The committed acceptance scenario (same code path as
    bench_chaos.py): >=32 concurrent SSE streams, one replica SIGKILLed
    mid-decode, one drained by scale-down — zero dropped requests, zero
    duplicated/missing tokens vs the uninterrupted baseline."""

    def test_acceptance_32_streams_kill_plus_drain(self):
        import bench_chaos

        row = bench_chaos.run_scenario(
            clients=32, replicas=3, scale_down_to=2, max_tokens=12,
            drain_timeout_s=2.0, seed=0)
        assert row["dropped"] == 0, row
        assert row["mismatched_streams"] == 0, row
        assert row["completed"] == 32, row
        assert row["tokens_received"] == row["tokens_expected"], row
        assert row["final_live_replicas"] == 2, row
        assert row["final_draining_replicas"] == 0, row


def test_tasks_survive_random_node_kills():
    """Chaos: random node kills under task load — the cluster heals and
    every task completes (ref: _private/test_utils.py:1245
    NodeKillerActor + tests/test_chaos.py)."""
    cluster = Cluster(head_node_args={"num_cpus": 2})
    victims = [cluster.add_node(num_cpus=2) for _ in range(2)]
    cluster.wait_for_nodes(3)
    ray_tpu.init(address=cluster.address)
    try:
        @ray_tpu.remote(max_retries=5)
        def work(i):
            time.sleep(0.05)
            return np.full(1 << 14, i % 200, np.uint8)

        stop = threading.Event()

        def killer():
            # Kill a worker node mid-run, then add a replacement, then kill
            # that one too — two waves of failure.
            time.sleep(1.5)
            cluster.remove_node(victims[0])
            fresh = cluster.add_node(num_cpus=2)
            time.sleep(2.0)
            if not stop.is_set():
                cluster.remove_node(victims[1])

        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        refs = [work.remote(i) for i in range(120)]
        out = ray_tpu.get(refs, timeout=300)
        stop.set()
        kt.join(timeout=30)
        assert [int(a[0]) for a in out] == [i % 200 for i in range(120)]
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()

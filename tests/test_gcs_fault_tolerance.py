"""GCS fault tolerance: kill + restart the control plane; the cluster
heals. Mirrors `/root/reference/python/ray/tests/test_gcs_fault_tolerance.
py` + `gcs_client_reconnection_test.cc` behaviors."""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture()
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def _restart_gcs():
    from ray_tpu import api

    api._node.restart_gcs()


class TestGcsFailover:
    def test_tasks_survive_gcs_restart(self, cluster):
        @ray_tpu.remote
        def add(a, b):
            return a + b

        assert ray_tpu.get(add.remote(1, 2), timeout=60) == 3
        _restart_gcs()
        # New work flows as soon as everyone reconnects.
        out = ray_tpu.get([add.remote(i, i) for i in range(5)], timeout=120)
        assert out == [0, 2, 4, 6, 8]

    def test_actor_and_kv_state_survive(self, cluster):
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

        c = Counter.options(name="ft_counter").remote()
        assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
        from ray_tpu import api

        client = api._ensure_client()
        client.kv_put("userspace", b"k1", b"v1")
        time.sleep(1.5)  # let the snapshot loop persist the state
        _restart_gcs()
        # Actor directory recovered: the named handle still resolves and
        # the actor (which never died) kept its in-memory state.
        c2 = ray_tpu.get_actor("ft_counter")
        assert ray_tpu.get(c2.incr.remote(), timeout=120) == 2
        assert client.kv_get("userspace", b"k1") == b"v1"

    def test_objects_resolvable_after_restart(self, cluster):
        big = np.arange(200_000, dtype=np.float64)
        ref = ray_tpu.put(big)
        time.sleep(1.5)
        _restart_gcs()

        @ray_tpu.remote
        def total(x):
            return float(x.sum())

        # The object directory healed (snapshot + re-announce), so a task
        # can still consume the pre-restart object.
        out = ray_tpu.get(total.remote(ref), timeout=120)
        assert out == float(big.sum())


class TestWalDurability:
    """Per-mutation WAL (VERDICT r1 item 10): kill -9 the GCS immediately
    after mutations — before any snapshot tick — and nothing is lost."""

    def test_kv_and_pg_survive_immediate_kill(self):
        ray_tpu.init(num_cpus=4, _system_config={
            # Snapshot compaction effectively disabled: only the WAL can
            # preserve these mutations across the kill.
            "gcs_snapshot_interval_s": 3600.0,
        })
        try:
            from ray_tpu import api
            from ray_tpu.core.placement_group import placement_group

            client = api._client
            client.kv_put("t", b"k1", b"v1")
            pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
            pg.ready()
            _restart_gcs()
            assert client.kv_get("t", b"k1") == b"v1"
            pgs = client.list_placement_groups()
            assert any(p["pg_id"] == pg.id.binary() for p in pgs)
            # And the cluster still schedules through the recovered state.

            @ray_tpu.remote(placement_group=pg)
            def inside():
                return "ok"

            assert ray_tpu.get(inside.remote(), timeout=60) == "ok"
        finally:
            ray_tpu.shutdown()

    def test_named_actor_rebuilt_from_wal(self):
        ray_tpu.init(num_cpus=4, _system_config={
            "gcs_snapshot_interval_s": 3600.0,
        })
        try:
            @ray_tpu.remote
            class Counter:
                def __init__(self):
                    self.n = 0

                def incr(self):
                    self.n += 1
                    return self.n

            c = Counter.options(name="walled").remote()
            assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
            _restart_gcs()
            time.sleep(1.0)
            c2 = ray_tpu.get_actor("walled")
            assert ray_tpu.get(c2.incr.remote(), timeout=60) == 2
        finally:
            ray_tpu.shutdown()


class TestHeartbeatDetector:
    """The node failure detector at handler level (no cluster): a node is
    dead after `heartbeat_miss_limit` periods of ITS silence — time the
    GCS itself was not running does not count against it."""

    LIMIT_S = 0.2       # 4 periods of 0.05 s

    def _run(self, gcs_frozen_s: float, node_silent_s: float) -> bool:
        """Register a node, heartbeat once, then block the GCS's own loop
        for `gcs_frozen_s` and stay silent for a further `node_silent_s`
        of live GCS time. → is the node still alive?"""
        import asyncio

        from ray_tpu.core.config import Config
        from ray_tpu.core.gcs import GcsServer

        async def scenario():
            gcs = GcsServer(Config(heartbeat_period_s=0.05,
                                   heartbeat_miss_limit=4))
            nid = b"n" * 16
            await gcs._register_node(None, {
                "node_id": nid, "address": ("127.0.0.1", 1),
                "resources": {"CPU": 1}})
            health = asyncio.ensure_future(gcs._health_loop())
            try:
                await asyncio.sleep(0.06)            # detector is ticking
                await gcs._heartbeat(None, {
                    "node_id": nid, "resources_available": {"CPU": 1}})
                time.sleep(gcs_frozen_s)             # the whole GCS stops
                await asyncio.sleep(0.06 + node_silent_s)
                return gcs.nodes[nid].alive
            finally:
                health.cancel()

        return asyncio.run(scenario())

    @pytest.mark.parametrize("gcs_frozen_s, node_silent_s, alive", [
        (0.0, 0.0, True),        # control: heartbeating node, live GCS
        (0.0, 0.5, False),       # node silent past the limit: dead
        (0.6, 0.0, True),        # GCS frozen 3x the limit: not the node's
        (0.6, 0.5, False),       # ...and a node silent afterwards still dies
    ])
    def test_gcs_pause_is_not_node_silence(self, gcs_frozen_s,
                                           node_silent_s, alive):
        assert self._run(gcs_frozen_s, node_silent_s) is alive

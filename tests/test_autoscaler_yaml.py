"""Cluster YAML `up`/`down` + the GCP TPU-pod provider (faked gcloud).

Ref: autoscaler/ray-schema.json + `ray up`; gcp/node.py:108-116 TPU nodes.
"""

import json
import os
import stat
import time

import pytest

import ray_tpu
from ray_tpu.autoscaler.node_provider import NodeType


def test_yaml_up_scales_to_min_workers(tmp_path):
    cfg = tmp_path / "cluster.yaml"
    cfg.write_text("""
cluster_name: yaml-test
provider:
  type: local
head_resources: {CPU: 2}
node_types:
  small:
    resources: {CPU: 2}
    min_workers: 1
    max_workers: 2
""")
    from ray_tpu.autoscaler.yaml_config import up

    cluster = up(str(cfg))
    try:
        ray_tpu.init(address=cluster.address)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["Alive"]]
            if len(alive) >= 2:  # head + min_workers=1
                break
            time.sleep(0.5)
        assert len([n for n in ray_tpu.nodes() if n["Alive"]]) >= 2

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get(f.remote(41), timeout=120) == 42
    finally:
        ray_tpu.shutdown()
        cluster.down()


def test_gcp_tpu_provider_with_fake_gcloud(tmp_path, monkeypatch):
    """Provider drives `gcloud compute tpus tpu-vm ...`; a fake binary
    records calls and serves canned responses."""
    state = tmp_path / "state.json"
    state.write_text("[]")
    fake = tmp_path / "gcloud"
    # -S -E: a bare interpreter, no site packages — the fake is stdlib
    # only and the provider shells out to it several times.
    fake.write_text(f"""#!/usr/bin/env -S python3 -S -E
import json, sys
state_path = {str(state)!r}
args = sys.argv[1:]
nodes = json.load(open(state_path))
def save():
    json.dump(nodes, open(state_path, "w"))
if "create" in args:
    name = args[args.index("create") + 1]
    nodes.append({{"name": name, "state": "READY"}})
    save()
elif "delete" in args:
    name = args[args.index("delete") + 1]
    nodes[:] = [n for n in nodes if n["name"] != name]
    save()
elif "list" in args:
    print(json.dumps(nodes))
elif "describe" in args:
    name = args[args.index("describe") + 1]
    match = [n for n in nodes if n["name"] == name]
    print(json.dumps(match[0] if match else {{"state": "TERMINATED"}}))
""")
    fake.chmod(fake.stat().st_mode | stat.S_IEXEC)

    from ray_tpu.autoscaler.gcp_tpu import GcpTpuProvider

    provider = GcpTpuProvider(
        {"project": "proj", "zone": "us-central2-b"},
        ("10.0.0.1", 6379), gcloud_bin=str(fake))
    nt = NodeType(name="tpu_worker", resources={"CPU": 8, "TPU": 4},
                  topology="v5e-8")
    node_id = provider.create_node(nt)
    assert node_id.startswith("raytpu-")
    assert provider.non_terminated_nodes() == [node_id]
    assert provider.is_ready(node_id)
    assert provider.node_type(node_id) == "tpu_worker"
    provider.terminate_node(node_id)
    assert provider.non_terminated_nodes() == []


def test_gcp_tpu_requires_topology(tmp_path):
    fake = tmp_path / "gcloud"
    fake.write_text("#!/bin/sh\nexit 0\n")
    fake.chmod(0o755)
    from ray_tpu.autoscaler.gcp_tpu import GcpTpuProvider

    provider = GcpTpuProvider({}, ("h", 1), gcloud_bin=str(fake))
    with pytest.raises(ValueError):
        provider.create_node(NodeType(name="x", resources={"CPU": 1}))

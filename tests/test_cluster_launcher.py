"""Cluster launcher: YAML schema, command runners, TPU VM API client, and
the end-to-end ``up`` path (VERDICT r2 #3; reference:
``autoscaler/_private/commands.py``, ``gcp/node_provider.py:75-94``,
``tpu_command_runner.py``, ``ray-schema.json``)."""

import json
import time

import pytest

from ray_tpu.cluster_config import ConfigError, validate_config


# ------------------------------------------------------------------ schema


def test_config_defaults_and_validation():
    cfg = validate_config({"cluster_name": "demo"})
    assert cfg.provider.type == "fake_multinode"
    assert cfg.max_workers == 8

    cfg = validate_config({
        "provider": {"type": "tpu_vm", "project_id": "p",
                     "zone": "us-central2-b",
                     "accelerator_type": "v5litepod-16"},
        "worker": {"resources": {"TPU": 16, "CPU": 8},
                   "labels": {"pool": "tpu"}},
        "min_workers": 1, "max_workers": 4,
        "setup_commands": ["echo hi"],
        "dry_run": True,
    })
    assert cfg.provider.zone == "us-central2-b"
    assert cfg.worker.resources == {"TPU": 16.0, "CPU": 8.0}


@pytest.mark.parametrize("raw,frag", [
    ({"bogus_key": 1}, "unknown keys"),
    ({"provider": {"type": "aws"}}, "provider.type"),
    ({"provider": {"type": "tpu_vm", "zone": "z"}}, "project_id"),
    ({"min_workers": 5, "max_workers": 2}, "min_workers"),
    ({"worker": {"resources": {"CPU": -1}}}, "non-negative"),
    ({"setup_commands": "echo"}, "list of strings"),
])
def test_config_rejects_bad_input(raw, frag):
    with pytest.raises(ConfigError, match=frag):
        validate_config(raw)


# --------------------------------------------------------- command runners


def test_ssh_runner_builds_argv_dry_run():
    from ray_tpu.command_runner import SSHCommandRunner, TPUPodCommandRunner

    r = SSHCommandRunner("10.0.0.5", user="ray", key_file="/k.pem",
                         dry_run=True)
    r.run("echo hello")
    argv = r.history[0]
    assert argv[0] == "ssh" and "-i" in argv and "/k.pem" in argv
    assert "ray@10.0.0.5" in argv
    r.put("/tmp/a", "/tmp/b")
    assert r.history[1][0] == "scp"

    pod = TPUPodCommandRunner(["10.0.0.5", "10.0.0.6"], dry_run=True)
    pod.run("start")
    assert len(pod.history) == 2  # fanned out to every slice host
    pod.run_per_host("python -m ray_tpu start",
                     [{"RANK": "0"}, {"RANK": "1"}])
    assert any("RANK=1" in " ".join(argv) for argv in pod.history)


def test_subprocess_runner_executes():
    from ray_tpu.command_runner import CommandFailed, SubprocessCommandRunner

    r = SubprocessCommandRunner()
    assert r.run("echo ok").strip() == "ok"
    with pytest.raises(CommandFailed):
        r.run("exit 3")


# ----------------------------------------------------------- tpu_vm client


def _fake_cloud():
    """In-memory TPU API: nodes keyed by path, ops complete instantly."""
    state = {"nodes": {}, "counter": 0}

    def transport(verb, url, body, headers):
        path = url.split("/v2/", 1)[1]
        if verb == "POST":
            name = path.split("nodeId=")[1]
            node_path = path.split("?")[0] + "/" + name
            state["nodes"][node_path] = {
                "name": node_path, "state": "READY",
                "labels": (body or {}).get("labels", {}),
                "networkEndpoints": [{"ipAddress": f"10.0.0.{len(state['nodes']) + 1}"},
                                     {"ipAddress": f"10.0.1.{len(state['nodes']) + 1}"}],
            }
            return {"name": node_path + "/op", "done": True}
        if verb == "DELETE":
            state["nodes"].pop(path, None)
            return {"name": path + "/del", "done": True}
        if path.endswith("/nodes"):
            return {"nodes": list(state["nodes"].values())}
        return state["nodes"].get(path, {})

    return state, transport


def test_tpu_vm_client_crud_and_hosts():
    from ray_tpu.tpu_vm_api import TpuVmClient

    state, transport = _fake_cloud()
    client = TpuVmClient("proj", "us-central2-b", token_fn=lambda: "tok",
                         transport=transport)
    op = client.create_node("s1", "v5litepod-16", "v2-alpha-tpuv5-lite",
                            labels={"ray-cluster": "demo"})
    client.wait_operation(op)
    nodes = client.list_nodes()
    assert len(nodes) == 1 and nodes[0]["state"] == "READY"
    node = client.get_node(nodes[0]["name"])
    assert TpuVmClient.node_hosts(node) == ["10.0.0.1", "10.0.1.1"]
    client.delete_node(nodes[0]["name"])
    assert client.list_nodes() == []
    # Request bodies carried the gang-atomic slice shape.
    post = client.requests[0]
    assert post["body"]["acceleratorType"] == "v5litepod-16"


def test_tpu_vm_provider_slice_gang_bootstrap():
    """Provider creates a slice, waits READY, and hands every slice host to
    the bootstrap hook (the SSH fan-out path)."""
    from ray_tpu.autoscaler import TPUVMNodeProvider
    from ray_tpu.tpu_vm_api import TpuVmClient

    state, transport = _fake_cloud()
    client = TpuVmClient("proj", "us-central2-b", token_fn=lambda: "",
                         transport=transport)
    booted = []
    provider = TPUVMNodeProvider(
        client=client, accelerator_type="v5litepod-16",
        bootstrap=lambda node, labels: booted.append(
            (TpuVmClient.node_hosts(node), labels)))
    pid = provider.create_node({"TPU": 16.0}, {"pool": "tpu"})
    assert pid in provider.non_terminated_nodes()
    hosts, labels = booted[0]
    assert len(hosts) == 2 and labels["provider_node_id"] == pid
    provider.terminate_node(pid)
    assert provider.non_terminated_nodes() == []


# -------------------------------------------------------------- end-to-end


@pytest.mark.timeout_s(170)
def test_up_fake_multinode_autoscales_end_to_end(tmp_path):
    """``ray_tpu up`` on a fake_multinode YAML boots a real autoscaling
    cluster: demand appears -> workers launch -> tasks run on them ->
    idle timeout scales back down."""
    import yaml

    import ray_tpu
    from ray_tpu.cluster_launcher import up

    config = tmp_path / "cluster.yaml"
    config.write_text(yaml.safe_dump({
        "cluster_name": "fake-e2e",
        "provider": {"type": "fake_multinode"},
        "min_workers": 0,
        "max_workers": 3,
        "idle_timeout_minutes": 0.05,  # 3s
        "head": {"resources": {"CPU": 0.1}},
        "worker": {"resources": {"CPU": 2}, "labels": {"pool": "w"}},
    }))
    cluster = up(str(config))
    try:
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote
        def where():
            from ray_tpu.core.runtime import get_core_worker

            return get_core_worker().node_id.hex()

        # Head has 0.1 CPU: these need autoscaled workers.
        refs = [where.options(num_cpus=1).remote() for _ in range(6)]
        nodes = ray_tpu.get(refs, timeout=120)
        assert cluster.autoscaler.num_launches >= 1
        head_hex = cluster.head_node.node_id.hex()
        assert all(n != head_hex for n in nodes)

        # Scale-down: workers idle past the (3s) timeout get terminated.
        deadline = time.monotonic() + 60
        while cluster.provider.non_terminated_nodes():
            assert time.monotonic() < deadline, "idle workers never reaped"
            time.sleep(0.5)
        assert cluster.autoscaler.num_terminations >= 1
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_up_tpu_vm_dry_run_records_provisioning(tmp_path):
    """Dry-run tpu_vm ``up``/``down``: the exact REST requests and SSH argv
    are recorded without egress — head slice create, per-host setup +
    ray-start, teardown delete."""
    import yaml

    from ray_tpu.cluster_launcher import down, up

    config = tmp_path / "tpu.yaml"
    config.write_text(yaml.safe_dump({
        "cluster_name": "pod256",
        "provider": {"type": "tpu_vm", "project_id": "proj",
                     "zone": "us-central2-b",
                     "accelerator_type": "v5litepod-256"},
        "max_workers": 2,
        "worker": {"resources": {"TPU": 256, "CPU": 64}},
        "auth": {"ssh_user": "ray", "ssh_private_key": "/k.pem"},
        "setup_commands": ["pip install -e ."],
        "dry_run": True,
    }))
    cluster = up(str(config))
    try:
        reqs = cluster.provider._client.requests
        post = next(r for r in reqs if r["verb"] == "POST")
        assert post["body"]["acceleratorType"] == "v5litepod-256"
        assert "pod256-head" in post["path"]
        assert any("started head" in a for a in cluster.actions)
    finally:
        cluster.shutdown()
    assert down(str(config))  # records the delete intent


def test_cli_up_down_dry_run(tmp_path, capsys):
    import yaml

    from ray_tpu.scripts import main

    config = tmp_path / "c.yaml"
    config.write_text(yaml.safe_dump({
        "cluster_name": "cli",
        "provider": {"type": "tpu_vm", "project_id": "p", "zone": "z"},
        "dry_run": True,
    }))
    assert main(["up", str(config)]) == 0
    assert "dry run" in capsys.readouterr().out
    assert main(["down", str(config)]) == 0
    assert "cluster down" in capsys.readouterr().out

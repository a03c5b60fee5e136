"""SSH cluster launcher: `ray_tpu up` provisions worker hosts over SSH.

Shape parity: reference python/ray/tests/test_cli.py + the NodeUpdater
provisioning path of autoscaler/_private/commands.py — here driven end to end
with a fake ssh/rsync that executes locally, so the FULL phase sequence
(rsync file mounts -> setup commands -> remote start joined to the head) runs
against real node processes.
"""

import json
import os
import signal
import stat
import subprocess
import sys
import time

import pytest


FAKE_SSH = """#!/bin/sh
# fake ssh: drop the host argument, run the command locally.
echo "$1" >> {log}
shift
exec sh -c "$1"
"""

FAKE_RSYNC = """#!/bin/sh
# fake rsync: -az local host:remote -> cp
shift
src="$1"
dst="${2#*:}"
mkdir -p "$dst"
cp -r "$src" "$dst"
"""


@pytest.fixture
def fake_remote(tmp_path):
    ssh_log = tmp_path / "ssh_hosts.log"
    ssh = tmp_path / "fake_ssh"
    ssh.write_text(FAKE_SSH.format(log=ssh_log))
    ssh.chmod(ssh.stat().st_mode | stat.S_IEXEC)
    rsync = tmp_path / "fake_rsync"
    rsync.write_text(FAKE_RSYNC)
    rsync.chmod(rsync.stat().st_mode | stat.S_IEXEC)
    return {"ssh": str(ssh), "rsync": str(rsync), "log": str(ssh_log)}


def test_ssh_provider_provision_phases(fake_remote, tmp_path):
    """Unit: rsync mounts land in target_dir, setup commands run, the start
    command receives the substituted head address, terminate stops the node."""
    from ray_tpu.autoscaler.ssh import SSHNodeProvider

    target = tmp_path / "remote"
    payload = tmp_path / "payload"
    payload.mkdir()
    (payload / "data.txt").write_text("shipped")
    provider = SSHNodeProvider(
        {
            "hosts": ["hostA", "hostB"],
            "target_dir": str(target),
            "file_mounts": {"files": str(payload)},
            "setup_commands": ["echo setup-ran > setup.marker"],
            "worker_start_command": "echo started-{address} > start.marker",
            # The fake hosts are this machine: the default stop command would
            # pkill the raylets of every test running beside this one.
            "worker_stop_command": "true",
        },
        head_address="10.0.0.1:6379",
        ssh_cmd=[fake_remote["ssh"]],
        rsync_cmd=[fake_remote["rsync"]],
    )
    nid = provider.create_node({"CPU": 1})
    assert provider.non_terminated_nodes() == [nid]
    assert (target / "files" / "payload" / "data.txt").read_text() == "shipped"
    assert (target / "setup.marker").read_text().strip() == "setup-ran"
    deadline = time.time() + 10
    while time.time() < deadline and not (target / "start.marker").exists():
        time.sleep(0.1)
    assert (target / "start.marker").read_text().strip() == "started-10.0.0.1:6379"
    # both hosts provisioned distinctly
    nid2 = provider.create_node({"CPU": 1})
    assert provider.cluster_address(nid) == ("hostA", 0)
    assert provider.cluster_address(nid2) == ("hostB", 0)
    with pytest.raises(RuntimeError, match="exhausted"):
        provider.create_node({"CPU": 1})
    provider.terminate_node(nid)
    assert provider.non_terminated_nodes() == [nid2]
    hosts_seen = open(fake_remote["log"]).read()
    assert "hostA" in hosts_seen and "hostB" in hosts_seen


def test_ray_tpu_up_ssh_two_host_cluster(fake_remote, tmp_path):
    """E2E: `ray_tpu up` with an ssh provider brings a head + 2 fake-SSH
    "hosts" online from YAML; every provisioned node registers with the GCS."""
    import yaml

    target_a = tmp_path / "host_a"
    target_b = tmp_path / "host_b"
    # One target dir per "host": the fake ssh runs locally, so distinct dirs
    # stand in for distinct machines. worker_start uses this module's python.
    config = {
        "cluster_name": "ssh-e2e",
        # head.host pinned to loopback: the fake-ssh "hosts" run locally, and
        # this sandbox's egress-interface probe returns an unreachable IP.
        "head": {"num_cpus": 1, "host": "127.0.0.1"},
        "provider": {
            "type": "ssh",
            "hosts": ["127.0.0.1"],
            "ssh_cmd": [fake_remote["ssh"]],
            "rsync_cmd": [fake_remote["rsync"]],
            "target_dir": str(target_a),
            "setup_commands": ["echo setup-ran > setup.marker"],
            "worker_start_command": (
                f"{sys.executable} -m ray_tpu.scripts.scripts start "
                "--address={address} --num-cpus=1"
            ),
            # As above; the killpg at the end of the test stops the worker.
            "worker_stop_command": "true",
        },
        "workers": {"min_workers": 1, "max_workers": 1, "resources": {"CPU": 1}},
    }
    del target_b  # single remote host keeps the 1-core CI load sane
    cfg_path = tmp_path / "cluster.yaml"
    cfg_path.write_text(yaml.safe_dump(config))
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    env["TMPDIR"] = str(tmp_path)  # isolate the head address file
    env.setdefault("JAX_PLATFORMS", "cpu")
    up = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.scripts.scripts", "up", str(cfg_path)],
        env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        start_new_session=True,
    )
    addr_file = tmp_path / "ray_tpu" / "head_address.json"
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not addr_file.exists():
            if up.poll() is not None:
                pytest.fail(f"up exited early:\n{up.stdout.read()}")
            time.sleep(0.5)
        assert addr_file.exists(), "head never wrote its address file"
        addr = json.loads(addr_file.read_text())
        gcs_port = addr["gcs_port"]

        import ray_tpu

        os.environ["RAY_TPU_RAYLET_PORT"] = str(addr["raylet_port"])
        ray_tpu.init(address=f"127.0.0.1:{gcs_port}")
        try:
            deadline = time.time() + 120
            while time.time() < deadline:
                nodes = [n for n in ray_tpu.nodes() if n["alive"]]
                if len(nodes) >= 2:  # head + the SSH-provisioned worker
                    break
                time.sleep(1.0)
            assert len(nodes) >= 2, f"worker never joined: {nodes}"
            # the provisioning phases really ran on the "remote" host
            assert (target_a / "setup.marker").read_text().strip() == "setup-ran"
            # and the joined node is schedulable
            @ray_tpu.remote(num_cpus=1)
            def where():
                return "ok"

            assert ray_tpu.get(where.remote(), timeout=120) == "ok"
        finally:
            ray_tpu.shutdown()
            os.environ.pop("RAY_TPU_RAYLET_PORT", None)
    finally:
        try:
            os.killpg(up.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            up.wait(timeout=30)
        except subprocess.TimeoutExpired:
            os.killpg(up.pid, signal.SIGKILL)

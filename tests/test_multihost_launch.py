"""Multi-host launcher + elastic (VERDICT r3 item 4).

Reference pattern: test_dist_base.py:952 — multi-host simulated as
multi-process controllers on one machine.  Each "host" is a
``paddle_tpu.distributed.launch`` PodController process; the rank-0 host
serves the rendezvous KV; workers are plain python scripts that record
their env (no jax needed — the launcher contract is env + process
management)."""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


WORKER_OK = """
import json, os, sys, time
out = sys.argv[1]
rec = {k: os.environ.get(k) for k in (
    "PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM", "PADDLE_NODE_RANK",
    "PADDLE_NNODES", "PADDLE_LOCAL_RANK", "PADDLE_JOB_EPOCH",
    "JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID")}
time.sleep(0.5)
with open(os.path.join(
        out, f"w{rec['PADDLE_JOB_EPOCH']}_{rec['PADDLE_TRAINER_ID']}.json"
        ), "w") as f:
    json.dump(rec, f)
"""

WORKER_FAIL_ONCE = WORKER_OK + """
# rank 3 dies in epoch 0 only — the restart must succeed in epoch 1
if rec["PADDLE_TRAINER_ID"] == "3" and rec["PADDLE_JOB_EPOCH"] == "0":
    sys.exit(17)
"""


def _launch_host(master, nnodes, nproc, script, out_dir, max_restart=0,
                 rank=None):
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--master", master, "--nnodes", str(nnodes),
           "--nproc_per_node", str(nproc),
           "--max_restart", str(max_restart),
           "--heartbeat_ttl", "3", "--rdzv_timeout", "60",
           script, out_dir]
    if rank is not None:
        cmd[5:5] = ["--rank", str(rank)]
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(cmd, env=env, cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _write_script(tmp_path, body):
    p = tmp_path / "worker.py"
    p.write_text(body)
    return str(p)


class TestTwoHostLaunch:
    def test_2host_x_2proc_rendezvous(self, tmp_path):
        master = f"127.0.0.1:{_free_port()}"
        script = _write_script(tmp_path, WORKER_OK)
        out = tmp_path / "out"
        out.mkdir()
        hosts = [_launch_host(master, 2, 2, script, str(out))
                 for _ in range(2)]
        codes = [h.wait(timeout=90) for h in hosts]
        logs = [h.stdout.read() for h in hosts]
        assert codes == [0, 0], logs
        recs = sorted(out.glob("w0_*.json"))
        assert len(recs) == 4, (list(out.iterdir()), logs)
        seen = {}
        for r in recs:
            d = json.loads(r.read_text())
            seen[d["PADDLE_TRAINER_ID"]] = d
        # dense global ranks 0..3, world 4, two nodes x two locals
        assert sorted(seen) == ["0", "1", "2", "3"]
        assert all(d["PADDLE_TRAINERS_NUM"] == "4" for d in seen.values())
        assert all(d["JAX_NUM_PROCESSES"] == "4" for d in seen.values())
        assert {d["PADDLE_NODE_RANK"] for d in seen.values()} == \
            {"0", "1"}
        assert all(d["JAX_COORDINATOR_ADDRESS"] for d in seen.values())

    def test_failure_restart_recovers(self, tmp_path):
        master = f"127.0.0.1:{_free_port()}"
        script = _write_script(tmp_path, WORKER_FAIL_ONCE)
        out = tmp_path / "out"
        out.mkdir()
        hosts = [_launch_host(master, 2, 2, script, str(out),
                              max_restart=2) for _ in range(2)]
        codes = [h.wait(timeout=120) for h in hosts]
        logs = [h.stdout.read() for h in hosts]
        assert codes == [0, 0], logs
        # epoch 1 completed on all four ranks after the epoch-0 failure
        recs1 = sorted(out.glob("w1_*.json"))
        assert len(recs1) == 4, (list(out.iterdir()), logs)
        assert any("restart" in lg for lg in logs), logs

    def test_elastic_range_runs_with_min_hosts(self, tmp_path):
        # --nnodes 1:2 with only ONE host present: settles at 1 node
        master = f"127.0.0.1:{_free_port()}"
        script = _write_script(tmp_path, WORKER_OK)
        out = tmp_path / "out"
        out.mkdir()
        h = _launch_host(master, "1:2", 2, script, str(out))
        code = h.wait(timeout=90)
        assert code == 0, h.stdout.read()
        recs = sorted(out.glob("w0_*.json"))
        assert len(recs) == 2
        d = json.loads(recs[0].read_text())
        assert d["PADDLE_TRAINERS_NUM"] == "2"


class TestKVStore:
    def test_kv_ops(self):
        from paddle_tpu.distributed.launch.kv import (KVClient,
                                                      start_server)
        srv = start_server()
        kv = KVClient(f"127.0.0.1:{srv.port}")
        kv.set("a", {"x": 1})
        assert kv.get("a") == {"x": 1}
        assert kv.add("ctr") == 1 and kv.add("ctr") == 2
        assert kv.cas("epoch", None, 1) is True
        assert kv.cas("epoch", 0, 2) is False
        assert kv.cas("epoch", 1, 2) is True
        kv.set("lease/x", 1, ttl=0.3)
        assert "lease/x" in kv.list("lease/")
        time.sleep(0.4)
        assert "lease/x" not in kv.list("lease/")
        kv.close()
        srv.shutdown()

    def test_kv_lease_store(self):
        from paddle_tpu.distributed.elastic import KVLeaseStore
        from paddle_tpu.distributed.launch.kv import start_server
        srv = start_server()
        st = KVLeaseStore(f"127.0.0.1:{srv.port}", ttl=0.4)
        st.register("hostA")
        st.register("hostB")
        assert st.hosts() == ["hostA", "hostB"]
        time.sleep(0.5)
        st.register("hostA")            # only A renews its lease
        assert st.hosts() == ["hostA"]
        st.deregister("hostA")
        assert st.hosts() == []
        srv.shutdown()

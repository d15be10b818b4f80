"""Launcher CLI / elastic manager / RPC tests — the reference's
spawn-with-env localhost-cluster pattern (SURVEY §4: test_dist_base.py
spawns subprocesses with env-var fake clusters)."""

import os
import pickle
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_launch_spawns_workers_with_env(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        rank = os.environ["PADDLE_TRAINER_ID"]
        world = os.environ["PADDLE_TRAINERS_NUM"]
        local = os.environ["PADDLE_LOCAL_RANK"]
        print(f"rank={rank} world={world} local={local}", flush=True)
    """))
    log_dir = tmp_path / "logs"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(log_dir), str(script)],
        env=CPU_ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    seen = set()
    for lr in range(2):
        out = (log_dir / f"workerlog.{lr}").read_text()
        seen.add(out.strip())
    assert seen == {"rank=0 world=2 local=0", "rank=1 world=2 local=1"}


def test_launch_single_inprocess(tmp_path):
    script = tmp_path / "one.py"
    script.write_text("import os; print('id', os.environ['PADDLE_TRAINER_ID'])")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch", str(script)],
        env=CPU_ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "id 0" in r.stdout


def test_launch_elastic_restart(tmp_path):
    # worker fails on first attempt, succeeds on second (state via file)
    marker = tmp_path / "marker"
    script = tmp_path / "flaky.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        m = {str(marker)!r}
        if not os.path.exists(m):
            open(m, "w").write("x")
            sys.exit(1)
        print("recovered", flush=True)
    """))
    log_dir = tmp_path / "logs"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restart", "2",
         "--log_dir", str(log_dir), str(script)],
        env=CPU_ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "elastic restart" in r.stderr
    assert "recovered" in (log_dir / "workerlog.0").read_text()


def test_elastic_manager_api():
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)
    m = ElasticManager([sys.executable, "-c", "print('done')"],
                       max_restart=1, poll_interval=0.1)
    assert m.run() == ElasticStatus.COMPLETED
    m2 = ElasticManager([sys.executable, "-c", "import sys; sys.exit(3)"],
                        max_restart=1, poll_interval=0.1)
    assert m2.run() == ElasticStatus.ERROR
    assert m2.restarts == 2


def test_rpc_two_processes(tmp_path):
    port = _free_port()
    worker = tmp_path / "rpc_worker.py"
    done = tmp_path / "done"
    worker.write_text(textwrap.dedent(f"""
        import os, sys, time
        from paddle_tpu.distributed import rpc

        DONE = {str(done)!r}

        def square(x):
            return x * x

        rpc.init_rpc(f"worker{{os.environ['PADDLE_TRAINER_ID']}}")
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        if rank == 0:
            import numpy as np
            out = rpc.rpc_sync("worker1", square, args=(7,))
            assert out == 49, out
            fut = rpc.rpc_async("worker1", square,
                                args=(np.arange(4.0),))
            np.testing.assert_allclose(fut.wait(), [0., 1., 4., 9.])
            infos = rpc.get_all_worker_infos()
            assert {{i.name for i in infos}} == {{"worker0", "worker1"}}
            print("rpc-ok", flush=True)
            open(DONE, "w").write("x")
        else:
            deadline = time.time() + 60
            while not os.path.exists(DONE) and time.time() < deadline:
                time.sleep(0.1)  # keep serving until rank 0 finishes
        rpc.shutdown()
    """))
    env = dict(CPU_ENV, PADDLE_TRAINERS_NUM="2",
               PADDLE_MASTER_ENDPOINT=f"127.0.0.1:{port}")
    procs = []
    for rank in (1, 0):
        e = dict(env, PADDLE_TRAINER_ID=str(rank))
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=120)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert any("rpc-ok" in o for o in outs), outs


def test_membership_registry_scale_events():
    import time
    from paddle_tpu.distributed.fleet.elastic import MembershipRegistry
    from paddle_tpu.runtime import TCPStore, TCPStoreServer

    server = TCPStoreServer(0)
    try:
        mgr_reg = MembershipRegistry(
            TCPStore("127.0.0.1", server.port), node_id=-1, max_nodes=4,
            heartbeat_interval=0.05)
        n0 = MembershipRegistry(TCPStore("127.0.0.1", server.port), 0,
                                max_nodes=4, heartbeat_interval=0.05)
        n1 = MembershipRegistry(TCPStore("127.0.0.1", server.port), 1,
                                max_nodes=4, heartbeat_interval=0.05)
        mgr_reg.snapshot()
        n0.register()
        time.sleep(0.2)
        members, event = mgr_reg.poll([])
        assert members == [0] and event == "scale_up"

        n1.register()
        time.sleep(0.2)
        members, event = mgr_reg.poll(members)
        assert members == [0, 1] and event == "scale_up"

        # node 1 dies: heartbeats stop, next polls drop it
        n1.deregister()
        time.sleep(0.3)
        mgr_reg.members()           # settle the baseline past the last beat
        time.sleep(0.3)
        members, event = mgr_reg.poll([0, 1])
        assert members == [0] and event == "scale_down", (members, event)

        n0.deregister()
    finally:
        server.stop()


def test_elastic_manager_records_scale_event(tmp_path):
    import sys
    import textwrap
    import threading
    import time
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      MembershipRegistry)
    from paddle_tpu.runtime import TCPStore, TCPStoreServer

    server = TCPStoreServer(0)
    try:
        # a long-running worker script (killed by the scale restart)
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import time
            time.sleep(30)
        """))
        reg = MembershipRegistry(TCPStore("127.0.0.1", server.port), -1,
                                 max_nodes=4, heartbeat_interval=0.05)
        n0 = MembershipRegistry(TCPStore("127.0.0.1", server.port), 0,
                                max_nodes=4, heartbeat_interval=0.05)
        n0.register()
        mgr = ElasticManager([sys.executable, str(script)],
                             poll_interval=0.1, registry=reg)
        t = threading.Thread(target=mgr.run, daemon=True)
        t.start()
        time.sleep(0.8)
        n1 = MembershipRegistry(TCPStore("127.0.0.1", server.port), 1,
                                max_nodes=4, heartbeat_interval=0.05)
        n1.register()            # scale-up while the job runs
        deadline = time.time() + 10
        while not mgr.events and time.time() < deadline:
            time.sleep(0.1)
        assert mgr.events and mgr.events[0][0] == "scale_up"
        assert 1 in mgr.events[0][1]
        mgr.exit()
        n0.deregister()
        n1.deregister()
    finally:
        server.stop()

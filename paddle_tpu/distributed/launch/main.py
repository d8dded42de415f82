"""Cluster launcher CLI (ref: python/paddle/distributed/launch/main.py:18 +
controllers/collective.py:21 build_pod + job/ Pod/Container).

Usage parity:
    python -m paddle_tpu.distributed.launch [--nnodes N] [--master ip:port]
        [--nproc_per_node M] [--log_dir d] [--max_restart K] train.py args...

TPU semantics: one process drives all local chips, so nproc_per_node defaults
to 1 (the reference defaults to #GPUs) and more than one is refused on a TPU
host: every rank would claim every chip (no per-rank chip assignment exists),
and a chip belongs to one process at a time. Multi-node: rendezvous over the KV
master, then each process gets PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS env
(same contract as collective.py:75-78) and jax.distributed.initialize is
driven from them by init_parallel_env.
"""
from __future__ import annotations

import argparse
import glob
import os
import socket
import subprocess
import sys
import time
from typing import List

from .rendezvous import ETCDMaster, HTTPMaster


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _local_ip() -> str:
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def _ranks_would_use_tpu() -> bool:
    """True when launched ranks would initialise a TPU backend: the host
    exposes TPU device nodes and ``JAX_PLATFORMS`` does not pin another
    platform. Decided without importing jax — the launcher itself must
    never hold the chip its ranks need."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--master", default=None, help="master endpoint ip:port")
    p.add_argument("--nnodes", default="1", help="N or min:max (elastic)")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--rank", type=int, default=-1)
    p.add_argument("--log_dir", default="log")
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--job_id", default="default")
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("--elastic_timeout", type=float, default=0.0,
                   help="seconds without a trainer heartbeat before the rank "
                        "is declared hung and the pod restarted (0=off); "
                        "trainers beat via PADDLE_HEARTBEAT_FILE (set "
                        "automatically) — init_parallel_env or "
                        "fleet.elastic.start_file_heartbeat() starts the beat")
    p.add_argument("--devices", "--gpus", default=None)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


class Container:
    """One managed process (ref launch/job/container.py)."""

    def __init__(self, cmd: List[str], env: dict, log_path: str,
                 heartbeat_file: str | None = None):
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.heartbeat_file = heartbeat_file
        self.proc: subprocess.Popen | None = None

    def start(self):
        os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(self.cmd, env={**os.environ, **self.env},
                                     stdout=self._log, stderr=subprocess.STDOUT)

    def poll(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


class Pod:
    """All containers on this node (ref launch/job/pod.py)."""

    def __init__(self):
        self.containers: List[Container] = []

    def deploy(self):
        for c in self.containers:
            c.start()

    HANG_EXIT = 98  # pod killed by the heartbeat watcher

    @staticmethod
    def _norm(code: int) -> int:
        # signal deaths poll() as negative; normalize to 128+sig so a rank
        # killed by SIGKILL can never be masked by a sibling's exit 0
        return 128 - code if code < 0 else code

    def join(self, hang_timeout: float = 0.0) -> int:
        last_beat: dict = {}  # container -> (mtime, local time it changed)
        while True:
            codes = [c.poll() for c in self.containers]
            if all(code is not None for code in codes):
                return max(self._norm(code) for code in codes)
            if any(code not in (None, 0) for code in codes):
                for c in self.containers:
                    c.terminate()
                return max(self._norm(code) for code in codes
                           if code is not None)
            if hang_timeout > 0:
                # failure DETECTION beyond process exit (ref elastic
                # manager.py:260 lease heartbeats): a rank that stops
                # touching its heartbeat file while still running is hung —
                # kill the pod so the launcher's restart loop can recover.
                # Staleness = the mtime has not ADVANCED for hang_timeout by
                # the launcher's own clock (comparing successive mtimes, not
                # mtime-vs-wallclock, so a skewed NFS server clock cannot
                # fake staleness).
                now = time.time()
                for c in self.containers:
                    hb = c.heartbeat_file
                    if not (c.poll() is None and hb and os.path.exists(hb)):
                        continue
                    mtime = os.path.getmtime(hb)
                    prev = last_beat.get(c)
                    if prev is None or mtime != prev[0]:
                        last_beat[c] = (mtime, now)
                        continue
                    if now - prev[1] > hang_timeout:
                        print(f"[launch] rank heartbeat stale "
                              f"({hb}, >{hang_timeout}s): declaring hung",
                              file=sys.stderr)
                        for cc in self.containers:
                            cc.terminate()
                        return self.HANG_EXIT
            time.sleep(0.2 if hang_timeout > 0 else 1)

    def stop(self):
        for c in self.containers:
            c.terminate()


def build_pod(args, node_rank: int, endpoints: List[str]) -> Pod:
    """Ref controllers/collective.py:32: assign ranks + env per process."""
    pod = Pod()
    nnodes = len(endpoints)
    n = args.nproc_per_node
    for local_rank in range(n):
        global_rank = node_rank * n + local_rank
        env = {
            "PADDLE_TRAINER_ID": str(global_rank),
            "PADDLE_TRAINERS_NUM": str(nnodes * n),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[node_rank],
            "PADDLE_MASTER": endpoints[0],
            "FLAGS_selected_devices": str(local_rank),
        }
        log = os.path.join(args.log_dir, f"workerlog.{global_rank}")
        hb = None
        if args.elastic_timeout > 0:
            hb = os.path.join(args.log_dir, f"heartbeat.{global_rank}")
            env["PADDLE_HEARTBEAT_FILE"] = hb
            env["PADDLE_HEARTBEAT_INTERVAL"] = str(
                max(0.2, args.elastic_timeout / 4))
            try:
                os.remove(hb)  # stale beat from a previous attempt
            except OSError:
                pass
        if hb is None:
            # clear any inherited value: a nested launch must not alias an
            # outer launcher's heartbeat file
            env["PADDLE_HEARTBEAT_FILE"] = ""
        cmd = [sys.executable, "-u", args.training_script] + args.training_script_args
        pod.containers.append(Container(cmd, env, log, heartbeat_file=hb))
    return pod


def launch(argv=None) -> int:
    args = parse_args(argv)
    nnodes = int(str(args.nnodes).split(":")[0])
    if args.nproc_per_node > 1 and _ranks_would_use_tpu():
        print(f"[launch] refusing --nproc_per_node {args.nproc_per_node} on "
              f"a TPU host: each rank would claim every local chip, and a "
              f"chip belongs to one process at a time (the second rank fails "
              f"or hangs). One process drives all local chips — use "
              f"--nproc_per_node 1, or pin the ranks elsewhere with "
              f"JAX_PLATFORMS=cpu.", file=sys.stderr)
        return 2

    if nnodes <= 1 and args.master is None:
        endpoints = [f"127.0.0.1:{_free_port()}"]
        node_rank = 0
        master = None
    else:
        if args.master is not None and args.master.startswith("etcd://"):
            # external etcd rendezvous (ref controllers/master.py:177):
            # the cluster scheduler owns the store; nobody hosts anything
            master = ETCDMaster(args.master, nnodes)
        else:
            master_ep = args.master or f"{_local_ip()}:{_free_port()}"
            master_host = master_ep.rsplit(":", 1)[0]
            # the master host may be named by loopback, hostname, or LAN
            # ip — resolve spellings of "this machine" before deciding to
            # host. (0.0.0.0 is deliberately NOT local: with the wildcard
            # every node would claim mastership and split-brain its own
            # private store)
            local_names = {_local_ip(), "127.0.0.1", "localhost",
                           socket.gethostname()}
            try:
                local_names.add(socket.gethostbyname(socket.gethostname()))
            except OSError:
                pass
            is_master = args.rank in (0, -1) and (args.master is None or
                                                  master_host in local_names)
            master = HTTPMaster(master_ep, is_master, nnodes)
        my_ep = f"{_local_ip()}:{_free_port()}"
        # identity for slot claims: explicit env id (stable across elastic
        # restarts) > explicit rank (pins slot rank directly) > the unique
        # endpoint (same-host launchers can't collide; no restart rejoin)
        node_id = os.environ.get("PADDLE_NODE_ID") or (
            f"rank{args.rank}" if args.rank >= 0 else my_ep)
        endpoints = master.sync_peers(
            my_ep, args.job_id, node_id=node_id,
            preferred_slot=args.rank if args.rank >= 0 else None)
        node_rank = endpoints.index(my_ep) if args.rank < 0 else args.rank

    restarts = 0
    try:
        while True:
            pod = build_pod(args, node_rank, endpoints)
            pod.deploy()
            code = pod.join(hang_timeout=args.elastic_timeout)
            if code == 0:
                return 0
            restarts += 1
            if restarts > args.max_restart:
                print(f"[launch] giving up after {restarts - 1} restarts, exit {code}",
                      file=sys.stderr)
                return code
            print(f"[launch] restart {restarts}/{args.max_restart} (exit {code})",
                  file=sys.stderr)
    finally:
        if master is not None:
            master.stop()


if __name__ == "__main__":
    sys.exit(launch())

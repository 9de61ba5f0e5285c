"""Multi-process training launcher + elastic supervisor.

Capability map (reference):
- ``python -m paddle.distributed.launch``  ← distributed/launch.py:18 →
  fleet/launch.py:396 launch(): parse cluster env, spawn one worker process
  per device (launch_utils.py:453 start_local_trainers), env wiring
  (PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS / …).
- watch loop                               ← launch_utils.py:565
  watch_local_trainers — abort the job when any local rank dies.
- elastic restart                          ← fleet/elastic.py:99
  ElasticManager (etcd membership, relaunch on change; ElasticStatus
  HOLD/RESTART/EXIT). Here membership is the local process set and the
  jax.distributed coordinator replaces etcd: on worker death with
  ``--max_restarts`` left, the whole set is relaunched from the last
  checkpoint (deterministic resumable checkpoints are the TPU-idiomatic
  recovery path — SURVEY.md §5 failure detection row).

TPU notes: on one host ONE process drives all the chips
(single-controller JAX), and a chip belongs to one process at a time — N
local ranks would each claim every chip, and all but the first fail or
hang. So ``--nproc_per_node`` counts *host processes*, not chips: on a
TPU host it is 1 (one launcher rank per host of a multi-host job), and
``--nproc_per_node N > 1`` on one machine is the CPU simulation
(``JAX_PLATFORMS=cpu``, one virtual device per rank) that the tests use.
Workers read PADDLE_* + JAX coordinator vars and call
``paddle_tpu.distributed.init_parallel_env()`` /
``jax.distributed.initialize()`` with no arguments.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "get_cluster_env", "main"]


def _find_free_ports(n: int, start: int = 6170) -> List[int]:
    import socket
    ports, p = [], start
    while len(ports) < n:
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", p))
                ports.append(p)
            except OSError:
                pass
        p += 1
    return ports


def get_cluster_env(rank: int, nprocs: int, ports: List[int],
                    coordinator_port: int) -> dict:
    """Env block for one worker (reference: launch_utils.py:268 get_cluster +
    :453 env assembly)."""
    endpoints = ",".join(f"127.0.0.1:{p}" for p in ports)
    return {
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{ports[rank]}",
        "PADDLE_TRAINER_ENDPOINTS": endpoints,
        "PADDLE_RANK_IN_NODE": str(rank),
        # jax.distributed.initialize() reads these (replaces the TCP
        # ncclUniqueId broadcast of gen_comm_id_helper.cc:297)
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{coordinator_port}",
        "JAX_NUM_PROCESSES": str(nprocs),
        "JAX_PROCESS_ID": str(rank),
    }


class _Supervisor:
    def __init__(self, script: str, script_args: List[str], nprocs: int,
                 log_dir: Optional[str], max_restarts: int):
        self.script = script
        self.script_args = script_args
        self.nprocs = nprocs
        self.log_dir = log_dir
        self.max_restarts = max_restarts
        self.procs: List[subprocess.Popen] = []
        self.logs = []

    def start_local_trainers(self):
        ports = _find_free_ports(self.nprocs + 1)
        coord, ports = ports[0], ports[1:]
        if self.log_dir:
            os.makedirs(self.log_dir, exist_ok=True)
        self.procs, self.logs = [], []
        for rank in range(self.nprocs):
            env = dict(os.environ)
            env.update(get_cluster_env(rank, self.nprocs, ports, coord))
            if self.log_dir:
                log = open(os.path.join(self.log_dir,
                                        f"workerlog.{rank}"), "ab")
            else:
                log = None
            self.logs.append(log)
            self.procs.append(subprocess.Popen(
                [sys.executable, "-u", self.script] + self.script_args,
                env=env, stdout=log, stderr=subprocess.STDOUT if log else None))

    def terminate_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        for log in self.logs:
            if log:
                log.close()
        self.logs = []

    def watch(self, poll_interval: float = 0.5) -> int:
        """reference: launch_utils.py:565 watch_local_trainers. Returns exit
        code; relaunches the full set on failure while restarts remain
        (elastic.py ElasticStatus.RESTART semantics)."""
        restarts = 0
        while True:
            while True:
                codes = [p.poll() for p in self.procs]
                if all(c == 0 for c in codes):
                    self.terminate_all()
                    return 0
                failed = [(i, c) for i, c in enumerate(codes)
                          if c not in (None, 0)]
                if failed:
                    break
                time.sleep(poll_interval)
            rank, code = failed[0]
            print(f"[launch] rank {rank} exited with {code}", file=sys.stderr)
            self.terminate_all()
            if restarts >= self.max_restarts:
                print(f"[launch] aborting after {restarts} restarts",
                      file=sys.stderr)
                return code or 1
            restarts += 1
            print(f"[launch] elastic restart {restarts}/{self.max_restarts}",
                  file=sys.stderr)
            self.start_local_trainers()


def launch(script: str, script_args: Optional[List[str]] = None,
           nproc_per_node: int = 1, log_dir: Optional[str] = None,
           max_restarts: int = 0) -> int:
    sup = _Supervisor(script, list(script_args or []), nproc_per_node,
                      log_dir, max_restarts)

    def on_sig(signum, frame):
        sup.terminate_all()
        sys.exit(1)

    old_term = signal.signal(signal.SIGTERM, on_sig)
    try:
        sup.start_local_trainers()
        return sup.watch()
    finally:
        # on any exit path (incl. KeyboardInterrupt) no worker may be left
        # orphaned holding chips/ports; terminate_all is idempotent
        sup.terminate_all()
        signal.signal(signal.SIGTERM, old_term)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch one training process per host group with "
                    "cluster env + jax.distributed coordinator wiring.")
    ap.add_argument("--nproc_per_node", type=int,
                    default=int(os.environ.get("PADDLE_NPROC_PER_NODE", 1)))
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("--max_restarts", type=int, default=0,
                    help="elastic: relaunch the worker set up to N times "
                         "when a rank fails (0 = fail fast)")
    ap.add_argument("training_script")
    ap.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    return launch(args.training_script, args.training_script_args,
                  nproc_per_node=args.nproc_per_node, log_dir=args.log_dir,
                  max_restarts=args.max_restarts)


if __name__ == "__main__":
    sys.exit(main())

"""paddle.distributed.spawn equivalent (reference: distributed/spawn.py).

On TPU, one process drives all the chips of a host (single-controller
JAX) and a chip belongs to one process at a time, so spawn-per-device is
not only unnecessary but wrong there: every spawned rank would claim
every chip. This spawns one process per *host group* for the CPU
simulation of a multi-process job (``JAX_PLATFORMS=cpu``; the SURVEY.md
§4 TestDistBase pattern), setting PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM env vars.
"""
from __future__ import annotations

import multiprocessing as mp
import os


def _worker(func, rank, nprocs, args, env):
    os.environ.update(env)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    func(*args)


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    ctx = mp.get_context("spawn")
    procs = []
    env = {k: v for k, v in os.environ.items()}
    for rank in range(nprocs):
        p = ctx.Process(target=_worker, args=(func, rank, nprocs, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(f"spawned process failed with {p.exitcode}")
    return procs

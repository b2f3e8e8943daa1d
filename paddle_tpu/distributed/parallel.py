"""Process/rank environment + bootstrap.

Capability parity: reference `python/paddle/fluid/dygraph/parallel.py`
(`ParallelEnv:56` reads PADDLE_TRAINER_ID/PADDLE_CURRENT_ENDPOINT/
PADDLE_TRAINERS_NUM/PADDLE_TRAINER_ENDPOINTS), `imperative/nccl_context.cc`
(TCP rendezvous + NCCL comm init) and `paddle.distributed.launch` env
contract (launch.py:142-193).

TPU-first: rendezvous and communicator setup are `jax.distributed.
initialize` (coordinator address ≈ endpoint list); the env contract is kept
verbatim so reference launch scripts port unchanged.
"""

from __future__ import annotations

import os


class ParallelEnv:
    """cf. reference dygraph/parallel.py:ParallelEnv."""

    def __init__(self):
        self._rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
        self._current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")
        eps = os.getenv("PADDLE_TRAINER_ENDPOINTS", "")
        self._trainer_endpoints = eps.split(",") if eps else []

    @property
    def rank(self):
        return self._rank

    # reference aliases
    @property
    def local_rank(self):
        return self._rank

    @property
    def nranks(self):
        return self._world_size

    @property
    def world_size(self):
        return self._world_size

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._trainer_endpoints

    @property
    def dev_id(self):
        return int(os.getenv("FLAGS_selected_tpus", os.getenv("FLAGS_selected_gpus", "0")))


_initialized = False


def init_parallel_env(coordinator_address=None, num_processes=None, process_id=None):
    """Multi-host bootstrap (≈ reference prepare_context/init_parallel_env).

    Single-host (or already-initialized) is a no-op: one jax process sees
    all local devices.  Multi-host reads the reference env contract and
    calls jax.distributed.initialize so all hosts join one XLA runtime.
    """
    global _initialized
    if _initialized:
        return ParallelEnv()
    env = ParallelEnv()
    n = num_processes if num_processes is not None else env.world_size
    if n > 1:
        import jax

        coord = coordinator_address
        if coord is None and env.trainer_endpoints:
            coord = env.trainer_endpoints[0]
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=n,
            process_id=process_id if process_id is not None else env.rank,
        )
    _initialized = True
    return env


def get_rank():
    return ParallelEnv().rank


def get_world_size():
    return ParallelEnv().world_size


def spawn(func, args=(), nprocs=None, started_port=None):
    """cf. reference `paddle.distributed.spawn`: run `func(rank, *args)`
    in nprocs processes wired with the PADDLE_* env contract (the
    programmatic twin of `python -m paddle_tpu.distributed.launch`).
    Returns once every process exits; raises if any failed."""
    import multiprocessing as mp
    import os
    import socket

    nprocs = int(nprocs or os.getenv("PADDLE_TRAINERS_NUM", "1"))
    if started_port is None:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        started_port = s.getsockname()[1]
        s.close()
    from .launch import get_cluster_endpoints

    endpoints = ",".join(
        get_cluster_endpoints(["127.0.0.1"], started_port, nprocs))

    from ..fluid.core.place import check_children_can_take_chip

    check_children_can_take_chip("distributed.spawn ranks")
    ctx = mp.get_context("spawn")
    procs = [ctx.Process(target=_spawn_main,
                         args=(func, rank, args, nprocs, endpoints,
                               started_port),
             ) for rank in range(nprocs)]
    for p in procs:
        p.start()
    # monitored join: a crashed rank terminates the group and raises
    # instead of leaving siblings (and this parent) blocked at a
    # rendezvous forever
    import time as _time

    try:
        while any(p.is_alive() for p in procs):
            for i, p in enumerate(procs):
                if not p.is_alive() and p.exitcode not in (0, None):
                    for q in procs:
                        if q.is_alive():
                            q.terminate()
                    raise RuntimeError(
                        "spawned rank %d exited nonzero (%s); terminated "
                        "the remaining ranks" % (i, p.exitcode))
            _time.sleep(0.1)
    finally:
        for p in procs:
            p.join(timeout=5)
    bad = [i for i, p in enumerate(procs) if p.exitcode != 0]
    if bad:
        raise RuntimeError(
            "spawned ranks %s exited nonzero (%s)"
            % (bad, [procs[i].exitcode for i in bad]))


def _spawn_main(func, rank, args, nprocs, endpoints, started_port):
    """Module-level spawn target (picklable)."""
    import os

    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_TRAINER_ENDPOINTS"] = endpoints
    os.environ["PADDLE_CURRENT_ENDPOINT"] = (
        "127.0.0.1:%d" % (started_port + rank))
    func(rank, *args)

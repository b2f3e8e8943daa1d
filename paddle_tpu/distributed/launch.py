"""Multi-process launcher: `python -m paddle_tpu.distributed.launch train.py`.

Capability parity: reference `python/paddle/distributed/launch.py`
(`launch:193`, `get_cluster_from_args:142`) — spawns one worker process per
device/host, exporting PADDLE_TRAINER_ID / PADDLE_CURRENT_ENDPOINT /
PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS.

TPU note: on TPU pods one process per HOST (not per chip) is the rule; each
process drives all local chips via one jax runtime.  `--nproc_per_node`
therefore defaults to 1, and the spawned script should call
`distributed.init_parallel_env()` which maps the env contract onto
`jax.distributed.initialize`.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--cluster_node_ips", type=str, default="127.0.0.1")
    p.add_argument("--node_ip", type=str, default="127.0.0.1")
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--elastic_restarts", type=int, default=0,
                   help="> 0: supervise the gang with the elastic "
                        "controller — on a rank loss, drain, bump the "
                        "generation fence and relaunch (up to this many "
                        "times) instead of failing the job")
    p.add_argument("--elastic_workspace", type=str, default=None,
                   help="shared dir for heartbeats/fence/checkpoints "
                        "(required with --elastic_restarts)")
    p.add_argument("--heartbeat_timeout", type=float, default=30.0,
                   help="seconds of heartbeat silence before a rank "
                        "counts as lost (elastic mode; only ranks that "
                        "run a distributed.monitor.HeartBeatMonitor are "
                        "watched this way — others by process exit)")
    p.add_argument("--startup_timeout", type=float, default=300.0,
                   help="elastic mode: seconds a rank may stay "
                        "heartbeat-silent at startup when its peers DO "
                        "heartbeat, before it counts as wedged")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster_endpoints(node_ips, started_port, nproc_per_node):
    """cf. reference get_cluster_from_args:142."""
    eps = []
    for ip in node_ips:
        for i in range(nproc_per_node):
            eps.append("%s:%d" % (ip, started_port + i))
    return eps


def launch_elastic(args):
    """Supervised gang: the reference launcher's fail-fast loop becomes
    the elastic controller's detect -> drain -> fence -> relaunch cycle
    (single-node; world size stays `--nproc_per_node`).  Every worker
    sees the usual PADDLE_* env contract plus PADDLE_ELASTIC_GENERATION
    and PADDLE_ELASTIC_WORKSPACE for fencing and drain commits."""
    from .elastic.controller import ElasticController

    if not args.elastic_workspace:
        raise SystemExit(
            "--elastic_restarts needs --elastic_workspace (the shared "
            "dir heartbeats and the generation fence live in)")
    if len(args.cluster_node_ips.split(",")) > 1:
        # two per-node controllers over one workspace would collide on
        # rank ids, heartbeats and the generation fence — refuse instead
        # of silently supervising half a cluster
        raise SystemExit(
            "--elastic_restarts is single-node for now "
            "(--cluster_node_ips lists %s); run ONE elastic controller "
            "per job" % args.cluster_node_ips)
    nproc = args.nproc_per_node

    def worker_argv(rank, world, generation):
        return ([sys.executable, "-u", args.training_script]
                + args.training_script_args)

    def worker_env(rank, world, generation):
        # fresh ports per generation: the old gang's sockets may still
        # be in TIME_WAIT when the replacement comes up
        port = args.started_port + generation * world
        endpoints = get_cluster_endpoints([args.node_ip], port, world)
        return {
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        }

    ctrl = ElasticController(
        args.elastic_workspace, worker_argv, nproc,
        max_restarts=args.elastic_restarts,
        heartbeat_timeout_s=args.heartbeat_timeout,
        startup_timeout_s=args.startup_timeout,
        env=worker_env, log_dir=args.log_dir)
    report = ctrl.run()
    return 0 if report["state"] == "DONE" else 1


def launch(args=None):
    args = args or _parse_args()
    if args.elastic_restarts > 0:
        return launch_elastic(args)
    node_ips = args.cluster_node_ips.split(",")
    endpoints = get_cluster_endpoints(
        node_ips, args.started_port, args.nproc_per_node
    )
    node_idx = node_ips.index(args.node_ip)
    from ..fluid.core.place import check_children_can_take_chip

    check_children_can_take_chip("distributed.launch workers")
    procs = []
    log_files = []
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    for local_rank in range(args.nproc_per_node):
        rank = node_idx * args.nproc_per_node + local_rank
        env = dict(os.environ)
        env.update(
            {
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
                "PADDLE_TRAINERS_NUM": str(len(endpoints)),
                "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            }
        )
        cmd = [sys.executable, "-u", args.training_script] + args.training_script_args
        if args.log_dir:
            f = open(os.path.join(args.log_dir, "workerlog.%d" % rank), "w")
            log_files.append(f)
            procs.append(subprocess.Popen(cmd, env=env, stdout=f, stderr=f))
        else:
            procs.append(subprocess.Popen(cmd, env=env))

    try:
        rc = 0
        alive = True
        while alive:
            alive = False
            for p in procs:
                r = p.poll()
                if r is None:
                    alive = True
                elif r != 0:  # fail fast, kill the gang (reference behavior)
                    rc = r
                    for q in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    alive = False
                    break
            time.sleep(0.5)
        for p in procs:
            p.wait()
            rc = rc or p.returncode
        return rc
    finally:
        for f in log_files:
            f.close()


if __name__ == "__main__":
    sys.exit(launch())

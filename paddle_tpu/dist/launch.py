"""Distributed launcher (ref: python/paddle/distributed/launch.py).

``python -m paddle_tpu.dist.launch [--nproc_per_node=N] train.py args``
spawns one trainer process per rank with the PADDLE_TRAINER_* env the
role makers read (fluid/incubate.py PaddleCloudRoleMaker).

TPU semantics differ from the reference's one-process-per-GPU model:
one process drives ALL local chips (SPMD over the mesh), so
``--nproc_per_node`` defaults to 1 per host and exists mainly for
CPU-simulation runs (each child gets JAX_PLATFORMS=cpu +
xla_force_host_platform_device_count). Multi-host pods launch one
process per host with ``--ips`` listing the hosts; jax.distributed
wires the DCN side in dist/env.py.

Failure semantics: when any worker exits nonzero, the survivors are
TERMINATED (no orphaned gang) and the first failure's exact code is
propagated — a signal death becomes the shell's 128+signum. With
``--elastic`` the gang instead runs under
``resilience.elastic.GangSupervisor``: hung workers are detected via
heartbeat files and killed, preemptions (exit 75 from
``resilience.graceful_shutdown``) relaunch budget-free, and crashes
relaunch from the newest intact checkpoint under ``--max_restarts``
with jittered backoff.

Fleet observability: with ``--run_dir`` (default: the inherited
``PADDLE_TPU_RUN_DIR``) every worker journals into its own
``<run_dir>/rank_NN`` subdir with a ``PADDLE_TPU_RANK`` identity —
``tools/fleet_report.py`` aggregates the per-rank records into one
cross-rank skew/straggler view.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

__all__ = ["launch", "get_cluster_endpoints", "get_gpus",
           "get_cluster_from_args"]


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        "paddle_tpu.dist.launch",
        description="One trainer process per rank. A TPU chip belongs to "
                    "one process, and one process drives all of a host's "
                    "chips: with --nproc_per_node > 1 every child is "
                    "started with JAX_PLATFORMS=cpu on virtual host "
                    "devices (a simulation, not a way to share a host's "
                    "chips).")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="trainer processes on this host (TPU: keep 1; "
                        ">1 forces CPU simulation per child)")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma-separated host list (multi-host pods)")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--elastic", action="store_true",
                   help="supervise the gang elastically: watchdog-kill "
                        "hung workers, relaunch the whole gang from the "
                        "newest intact checkpoint on failure, treat "
                        "preemption exits (75) as budget-free restarts")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="crash/hang restart budget in --elastic mode")
    p.add_argument("--hang_timeout", type=float, default=300.0,
                   help="seconds without a worker heartbeat before the "
                        "watchdog kills it (--elastic; workers opt in "
                        "by beating resilience.Heartbeat.from_env())")
    p.add_argument("--ckpt_dir", type=str, default=None,
                   help="checkpoint dir the supervisor inspects to "
                        "journal each restart's resume step (--elastic)")
    p.add_argument("--run_dir", type=str,
                   default=os.environ.get("PADDLE_TPU_RUN_DIR") or None,
                   help="fleet flight-record root: each worker journals "
                        "into <run_dir>/rank_NN (PADDLE_TPU_RUN_DIR + "
                        "PADDLE_TPU_RANK per rank); defaults to "
                        "PADDLE_TPU_RUN_DIR so a journaled launch is "
                        "fleet-observable without extra flags "
                        "(tools/fleet_report.py aggregates)")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster_endpoints(ips, nproc_per_node, started_port):
    """All trainer endpoints, hosts-major (ref: get_cluster_from_args)."""
    eps = []
    for ip in ips.split(","):
        for i in range(nproc_per_node):
            eps.append(f"{ip}:{started_port + i}")
    return eps


def _trainer_env(args, eps, world, local, run_dir=None):
    """The PADDLE_TRAINER_* (+ CPU-simulation) env UPDATE for one local
    worker — shared by the plain and elastic paths. ``run_dir`` hands
    the worker its per-rank journal subdir + rank identity (the
    elastic path passes None: GangSupervisor owns that wiring)."""
    rank = args.node_rank * args.nproc_per_node + local
    env = {
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(eps),
        "PADDLE_CURRENT_ENDPOINT": eps[rank],
    }
    if run_dir:
        from ..obs.journal import RANK_ENV, rank_subdir

        env["PADDLE_TPU_RUN_DIR"] = os.path.join(run_dir,
                                                 rank_subdir(rank))
        env[RANK_ENV] = str(rank)
    if args.nproc_per_node > 1:
        # multiple processes cannot share the TPU client: children
        # run on the virtual-device CPU backend (test/sim mode)
        env["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # APPEND: the user's other XLA flags must survive
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
    return env


def _wait_gang(procs):
    """Wait for all workers; on the FIRST nonzero exit, terminate the
    survivors (no orphaned gang) and return that worker's exact exit
    code — a signal death becomes the shell's 128+signum, instead of
    the old OR-style collapse that garbled both."""
    from ..resilience.elastic import normalize_exit_code
    from .utils import terminate_local_procs

    try:
        while True:
            for p, _ in procs:
                rc = p.poll()
                if rc is not None and rc != 0:
                    terminate_local_procs([q for q, _ in procs
                                           if q is not p])
                    return normalize_exit_code(rc)
            if all(p.poll() is not None for p, _ in procs):
                return 0
            time.sleep(0.05)
    finally:
        for _, out in procs:
            if out:
                out.close()


def launch(args=None):
    args = args or _parse_args()
    eps = get_cluster_endpoints(args.ips, args.nproc_per_node,
                                args.started_port)
    world = len(eps)
    cmd = [sys.executable, args.training_script] + \
        args.training_script_args

    if getattr(args, "elastic", False):
        from ..resilience.elastic import ElasticBudgetError, GangSupervisor

        sup = GangSupervisor(
            cmd, nprocs=args.nproc_per_node,
            env_for_rank=lambda rank, attempt: _trainer_env(
                args, eps, world, rank),
            log_dir=args.log_dir, ckpt_dir=args.ckpt_dir,
            run_dir=getattr(args, "run_dir", None),
            # global rank identity: node 1's local rank 0 journals as
            # rank_NN of node_rank*nproc, never over node 0's rank_00
            rank_base=args.node_rank * args.nproc_per_node,
            max_restarts=args.max_restarts,
            hang_timeout_s=args.hang_timeout)
        try:
            return sup.run()
        except ElasticBudgetError as e:
            print(f"paddle_tpu.dist.launch: {e}", file=sys.stderr)
            return sup.state.get("exit_code") or 1

    procs = []
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    for local in range(args.nproc_per_node):
        rank = args.node_rank * args.nproc_per_node + local
        env = dict(os.environ)
        env.update(_trainer_env(args, eps, world, local,
                                run_dir=getattr(args, "run_dir", None)))
        out = None
        if args.log_dir:
            out = open(os.path.join(args.log_dir,
                                    f"worker.{rank}.log"), "w")
        procs.append((subprocess.Popen(cmd, env=env, stdout=out,
                                       stderr=subprocess.STDOUT
                                       if out else None), out))
    return _wait_gang(procs)


def get_gpus(selected_gpus):
    """ref: launch.py get_gpus — resolve the selected accelerator list
    against the visible-devices env (CUDA_VISIBLE_DEVICES there; the
    name is kept, the indices are whatever accelerators the runtime
    exposes). ``None`` enumerates every visible/local device, like the
    reference."""
    visible = os.getenv("CUDA_VISIBLE_DEVICES") or \
        os.getenv("TPU_VISIBLE_DEVICES")
    if selected_gpus is None or selected_gpus == "":
        if visible:
            return list(range(len(visible.split(","))))
        import jax

        return list(range(jax.local_device_count()))
    sel = [s.strip() for s in str(selected_gpus).split(",") if s.strip()]
    if not visible:
        return [int(s) for s in sel]
    vis = [v.strip() for v in visible.split(",")]
    for s in sel:
        if s not in vis:
            raise ValueError(
                f"selected device {s} not in visible devices {vis}")
    return [vis.index(s) for s in sel]


def get_cluster_from_args(args, selected_gpus):
    """ref: launch.py get_cluster_from_args — Cluster/Pod from parsed
    launcher args. Accepts this module's --ips spelling and the
    reference's cluster_node_ips/node_ip; unknown topology raises
    rather than silently defaulting."""
    from .utils import get_cluster

    ips_arg = getattr(args, "ips", None) or \
        getattr(args, "cluster_node_ips", None)
    if ips_arg is None:
        raise ValueError("args carries neither 'ips' nor "
                         "'cluster_node_ips'")
    node_ips = [ip.strip() for ip in str(ips_arg).split(",")]
    node_ip = getattr(args, "node_ip", None)
    if node_ip is None:
        rank = getattr(args, "node_rank", 0) or 0
        node_ip = node_ips[int(rank)]
    if node_ip not in node_ips:
        raise ValueError(
            f"this node's ip {node_ip!r} is not in the node list "
            f"{node_ips} (check --node_ip / --ips)")
    started = int(getattr(args, "started_port", 6170) or 6170)
    sel = get_gpus(None) if selected_gpus is None else list(selected_gpus)
    ports = [started + i for i in range(len(sel))]
    return get_cluster(node_ips, node_ip, ports, sel)



if __name__ == "__main__":
    sys.exit(launch())

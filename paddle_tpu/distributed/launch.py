"""Collective-mode multi-process launcher.

Reference analog: python/paddle/distributed/launch.py — one training
process per device per node, each told its rank and the full endpoint
list through env vars:

    PADDLE_TRAINER_ID, PADDLE_CURRENT_ENDPOINT,
    PADDLE_TRAINERS_NUM, PADDLE_TRAINER_ENDPOINTS

(the contract `fleet.init(PaddleCloudRoleMaker(is_collective=True))`
reads; multi-host jax.distributed coordination derives from the same
endpoints).  TPU differences from the reference: ONE process drives
every chip of its host (`with_data_parallel` / the mesh runners span
them), so `--nproc_per_node` defaults to 1; name a larger count
explicitly (`--selected_gpus` is accepted as an alias for script parity)
for CPU-mesh ranks.  Failure of any local rank tears the whole node's
group down instead of leaking survivors.

A chip belongs to one process at a time, so this parent never
initializes a JAX backend — it does not count devices, it only spawns
and supervises (tests/test_toplevel_parity.py runs it with a backend
tripwire).

Usage:
    python -m paddle_tpu.distributed.launch train.py --your-args
"""

from __future__ import annotations

import os
from argparse import REMAINDER, ArgumentParser

from ._proc_group import ProcGroup, str2bool

__all__ = ["launch", "start_procs"]


def _parse_args(argv=None):
    parser = ArgumentParser(
        description="Start one training process per device; processes "
                    "rendezvous via the PADDLE_TRAINER_* env contract.")
    parser.add_argument("--cluster_node_ips", type=str, default="127.0.0.1",
                        help="comma list of node ips in the job")
    parser.add_argument("--node_ip", type=str, default="127.0.0.1",
                        help="this node's ip")
    parser.add_argument("--started_port", type=int, default=6170,
                        help="first endpoint port on each node")
    parser.add_argument("--nproc_per_node", type=int, default=None,
                        help="processes per node; default 1 — one "
                             "process drives all of its host's chips")
    parser.add_argument("--selected_gpus", type=str, default=None,
                        help="reference-script alias: its length sets "
                             "nproc_per_node, values export "
                             "FLAGS_selected_gpus per rank")
    parser.add_argument("--log_dir", type=str, default=None,
                        help="write per-rank logs here (workerlog.N)")
    parser.add_argument("--aot_cache_dir", type=str, default=None,
                        help="persistent ahead-of-time executable cache "
                             "shared by every rank (exports "
                             "FLAGS_aot_cache_dir): a restarted or "
                             "replacement rank loads its executables "
                             "instead of recompiling")
    parser.add_argument("--print_config", type=str2bool, default=True)
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=REMAINDER)
    return parser.parse_args(argv)


def start_procs(args):
    node_ips = [ip.strip() for ip in args.cluster_node_ips.split(",") if ip]
    node_id = node_ips.index(args.node_ip)
    selected = ([g.strip() for g in args.selected_gpus.split(",")]
                if args.selected_gpus else None)
    nproc = args.nproc_per_node or (len(selected) if selected else 1)
    if selected and len(selected) < nproc:
        raise ValueError(
            f"--selected_gpus names {len(selected)} devices but "
            f"--nproc_per_node={nproc}")

    endpoints = [f"{ip}:{args.started_port + i}"
                 for ip in node_ips for i in range(nproc)]
    nranks = len(endpoints)
    if args.print_config:
        # observability: allow — opt-in launcher banner (--print_config)
        print(f"launch: nodes={node_ips} nproc_per_node={nproc} "
              f"nranks={nranks} endpoints={','.join(endpoints)}")

    from paddle_tpu.observability import tracing as _tracing

    base_env = dict(os.environ)
    base_env.pop("http_proxy", None)
    base_env.pop("https_proxy", None)
    # one job-wide trace id for every rank (tools/merge_traces.py keys
    # cross-process timelines on it)
    base_env["PT_TRACE_ID"] = _tracing.job_trace_id()
    if args.aot_cache_dir:
        # every rank shares one AOT executable cache: rank 0's compiles
        # are everyone else's (and every restart's) loads
        base_env["FLAGS_aot_cache_dir"] = args.aot_cache_dir

    with ProcGroup(args.log_dir) as group:
        for i in range(nproc):
            rank = node_id * nproc + i
            env = dict(base_env,
                       PADDLE_TRAINER_ID=str(rank),
                       PADDLE_CURRENT_ENDPOINT=endpoints[rank],
                       PADDLE_TRAINERS_NUM=str(nranks),
                       PADDLE_TRAINER_ENDPOINTS=",".join(endpoints))
            if selected:
                env["FLAGS_selected_gpus"] = selected[i]
            group.spawn(args.training_script, args.training_script_args,
                        env, f"workerlog.{i}")
        group.wait()  # resilience: allow — supervision loop, polls inside


def launch(argv=None):
    start_procs(_parse_args(argv))


if __name__ == "__main__":
    launch()

#!/usr/bin/env python
"""Launch a distributed kvstore job: scheduler + servers + workers.

Reference: tools/launch.py (DMLC launcher with ssh/mpi/sge/yarn/local
modes, :71-73 dispatches on --launcher) and dmlc-core's tracker. The
``local`` launcher — which the reference's own distributed tests run on
(tests/nightly/dist_sync_kvstore.py) — spawns every role as a process of
this host with the DMLC_* env contract.

TPU deployment note: on real pods each worker process owns that host's
TPU chips while servers/schedulers pin to CPU (kvstore_server.py sets
JAX_PLATFORMS=cpu for those roles). A chip belongs to one process at a
time, so the N workers `local` starts on one host cannot share it: the
launcher itself never touches JAX, but give at most one worker the chip
and pin the rest to the CPU through `worker_envs` (JAX_PLATFORMS=cpu), as
the tests do for all of them. Cluster launchers (gke/mpi) are out of
scope here — `local` covers the reference's own test matrix; ssh raises
with guidance.

Usage::

    python tools/launch.py -n 2 -s 2 python train_script.py [args...]
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_local(num_workers, num_servers, cmd, env_extra=None,
                 worker_envs=None, timeout=600):
    """Spawn scheduler, servers, and workers locally; wait for workers.

    Returns the list of worker exit codes. `worker_envs` optionally gives
    per-worker env overrides (e.g. to pin each worker to its own
    device set).

    ``num_servers=0`` launches a pure SPMD job: no scheduler or server
    processes — just N workers, each with its rank in DMLC_WORKER_ID,
    and the root URI/port free for `parallel.dist.initialize` to use as
    the jax.distributed coordinator (rank 0 binds it).
    """
    port = _free_port()
    base = dict(os.environ)
    base.update(env_extra or {})
    base.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_NUM_SERVER": str(num_servers),
    })
    procs = []

    def spawn(role, extra=None):
        env = dict(base)
        env["DMLC_ROLE"] = role
        env.update(extra or {})
        return subprocess.Popen(cmd, env=env)

    try:
        if num_servers > 0:
            procs.append(spawn("scheduler"))
            for _ in range(num_servers):
                procs.append(spawn("server"))
        workers = []
        for i in range(num_workers):
            extra = dict(worker_envs[i]) if worker_envs else {}
            extra.setdefault("DMLC_WORKER_ID", str(i))
            w = spawn("worker", extra)
            workers.append(w)
            procs.append(w)  # the finally below must reap hung workers too
        codes = [w.wait(timeout=timeout) for w in workers]
        return codes
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed training job.")
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=None,
                        help="number of server processes (default: workers)")
    parser.add_argument("--launcher", choices=["local", "ssh", "mpi", "sge",
                                               "yarn"], default="local")
    parser.add_argument("--timeout", type=int, default=600)
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the command to launch per role")
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.launcher != "local":
        raise SystemExit(
            "launcher %r is not supported: this environment is single-host; "
            "on a TPU pod use one process per host with jax.distributed + "
            "mxnet_tpu.parallel, or GKE/xpk for orchestration" % args.launcher)
    num_servers = (args.num_servers if args.num_servers is not None
                   else args.num_workers)
    codes = launch_local(args.num_workers, num_servers, args.command,
                         timeout=args.timeout)
    if any(codes):
        sys.exit("worker exit codes: %s" % codes)


if __name__ == "__main__":
    main()

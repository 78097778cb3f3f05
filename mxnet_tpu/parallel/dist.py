"""Multi-host SPMD process-group initialization (the DCN layer).

The reference spans hosts with a parameter server: ps-lite's scheduler
hands out node ranks and every worker opens ZMQ channels to every
server (/root/reference/src/kvstore/kvstore_dist.h:44-450). The
TPU-native equivalent keeps the *launch contract* (the ``DMLC_*``
environment variables that `tools/launch.py` exports) but replaces the
transport entirely: each host runs ONE process, rank 0 doubles as the
coordination service, and after :func:`initialize` the processes form a
single SPMD program — ``jax.devices()`` is the global device list, a
`Mesh` built over it spans hosts, and every gradient/optimizer exchange
rides XLA collectives (ICI within a host group, DCN across), not a
socket protocol of ours.

This is SURVEY §2.3's "Multi-host SPMD over DCN: jax.distributed-style
init + global collectives". The optimizer-on-server semantics of
`dist_sync` (kvstore_dist_server.h:325-348 — servers aggregate all
workers' gradients, apply the update once, workers pull) map onto
`TrainStep`: the gradient psum is the aggregation, and the sharded
optimizer state is the "server side" state, co-located with its weight
shard so the update is local after the reduce.

Env contract (exported by ``tools/launch.py -s 0``):

- ``DMLC_PS_ROOT_URI`` / ``DMLC_PS_ROOT_PORT`` — coordinator address
  (rank 0 binds it; the ps-lite scheduler's address, reused).
- ``DMLC_NUM_WORKER`` — number of processes in the group.
- ``DMLC_WORKER_ID`` — this process's rank.

Single-process runs (no env, or one worker) are a no-op, so the same
training script works from a laptop to a pod.
"""
from __future__ import annotations

import atexit
import os

import jax

__all__ = ["initialize", "shutdown", "is_initialized", "rank",
           "num_processes", "barrier", "local_slice", "env_spec"]

_initialized = False


def env_spec():
    """Read the DMLC_* contract; returns (coordinator, nproc, rank) with
    None for anything unset."""
    uri = os.environ.get("DMLC_PS_ROOT_URI")
    port = os.environ.get("DMLC_PS_ROOT_PORT")
    coord = "%s:%s" % (uri, port) if uri and port else None
    nproc = os.environ.get("DMLC_NUM_WORKER")
    rank_ = os.environ.get("DMLC_WORKER_ID")
    return (coord,
            int(nproc) if nproc is not None else None,
            int(rank_) if rank_ is not None else None)


def initialize(coordinator_address=None, num_processes=None,
               process_id=None, local_device_count=None, platform=None):
    """Join (or trivially skip) the multi-process SPMD group.

    Arguments default from the ``DMLC_*`` env contract. With one process
    (or no contract in the environment) this is a no-op and the program
    stays a normal single-controller JAX program.

    ``local_device_count`` forces N virtual CPU devices per process (the
    test/dryrun configuration — the same trick the suite's conftest uses
    for the 8-device mesh); it must be applied before JAX initializes
    its backends. ``platform`` pins the backend (e.g. "cpu") the same
    way `mx.util.pin_platform` does.

    Returns True when a multi-process group was actually formed.
    """
    global _initialized
    if _initialized:
        return True
    coord, nproc, rank_ = env_spec()
    coordinator_address = coordinator_address or coord
    num_processes = num_processes if num_processes is not None else nproc
    process_id = process_id if process_id is not None else rank_

    if local_device_count is not None:
        import re
        flags = os.environ.get("XLA_FLAGS", "")
        have = re.search(
            r"--xla_force_host_platform_device_count=(\d+)", flags)
        if have is None:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=%d"
                % local_device_count).strip()
        elif int(have.group(1)) != local_device_count:
            raise RuntimeError(
                "XLA_FLAGS already forces a different host device count: %r"
                % flags)
    if platform is not None:
        from ..util import pin_platform
        pin_platform(platform)

    if not num_processes or num_processes == 1:
        return False
    if coordinator_address is None or process_id is None:
        raise RuntimeError(
            "multi-process init needs a coordinator address and rank: set "
            "DMLC_PS_ROOT_URI/PORT + DMLC_WORKER_ID (tools/launch.py -s 0 "
            "exports them) or pass them explicitly")

    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    # Un-annotated eager work (parameter init, host preprocessing) must
    # stay on THIS process's devices: the global default device is rank
    # 0's first chip, which other ranks cannot address. Only explicitly
    # sharded arrays (TrainStep's mesh placements) are global.
    jax.config.update("jax_default_device", jax.local_devices()[0])
    _initialized = True
    atexit.register(shutdown)
    return True


def shutdown():
    """Leave the process group (idempotent)."""
    global _initialized
    if _initialized:
        _initialized = False
        jax.distributed.shutdown()


def is_initialized():
    return _initialized


def rank():
    """This process's index in the group (0 for single-process runs)."""
    return jax.process_index() if _initialized else 0


def num_processes():
    return jax.process_count() if _initialized else 1


def barrier(name="mx_barrier"):
    """Block until every process reaches the same point (the ps-lite
    Barrier analogue; kvstore.py exposes it as kv._barrier for dist)."""
    if _initialized:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def local_slice(n_rows):
    """The [start, stop) rows of a global batch this process should
    produce. Mirrors the reference's per-worker partition of an epoch
    (io.py num_parts/part_index contract)."""
    r, n = rank(), num_processes()
    if n_rows % n:
        raise ValueError("global batch %d not divisible by %d processes"
                         % (n_rows, n))
    per = n_rows // n
    return r * per, (r + 1) * per

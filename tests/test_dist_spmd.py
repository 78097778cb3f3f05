"""Multi-host SPMD over DCN — the dist_sync path that spans processes.

Reference analogue: tests/nightly/dist_sync_kvstore.py runs real
multi-process parameter-server traffic on one host via
`tools/launch.py --launcher local`. Here the same launcher (with
``-s 0``) spawns a pure SPMD group: 2 processes × 4 virtual CPU devices
joined by `parallel.dist.initialize` into one 8-device mesh, training
through `TrainStep` with gradient aggregation riding XLA collectives
(gloo across the process boundary — DCN's stand-in on a dev box).

The bar (VERDICT r4 #1): the 2-process run must match the 1-process
8-device run bit-for-bit on params, optimizer state, aux, and the loss
trace after N steps.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from launch import launch_local  # noqa: E402

PROG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "dist_spmd_prog.py")


def _clean_env():
    env = {k: v for k, v in os.environ.items() if not k.startswith("DMLC_")}
    # Override (not just drop): launch_local merges os.environ, where
    # conftest already forced the 8-device flag for THIS process.
    env["XLA_FLAGS"] = ""
    env["JAX_PLATFORMS"] = ""  # prog pins cpu itself
    return env


def _run_single(out, steps):
    env = _clean_env()
    rc = subprocess.call([sys.executable, PROG, out, str(steps)], env=env,
                         timeout=420)
    assert rc == 0


def _run_multi(out, steps, num_workers=2):
    codes = launch_local(
        num_workers, 0, [sys.executable, PROG, out, str(steps)],
        env_extra=_clean_env(), timeout=420)
    assert codes == [0] * num_workers, codes


STEPS = 6   # shared by the baseline fixture and every parametrization


@pytest.fixture(scope="module")
def single_proc_baseline(tmp_path_factory):
    """One deterministic 1-process reference run shared by every
    worker-count parametrization."""
    path = str(tmp_path_factory.mktemp("spmd") / "single.npz")
    _run_single(path, STEPS)
    return path


@pytest.mark.parametrize("num_workers", [2, 4])
def test_multi_process_spmd_matches_single_process(tmp_path, num_workers,
                                                   single_proc_baseline):
    a = single_proc_baseline
    b = str(tmp_path / "multi.npz")
    _run_multi(b, STEPS, num_workers=num_workers)
    za, zb = np.load(a), np.load(b)
    assert sorted(za.files) == sorted(zb.files)
    exact, close = [], []
    for k in za.files:
        if np.array_equal(za[k], zb[k]):
            exact.append(k)
        else:
            close.append(k)
            np.testing.assert_allclose(
                za[k], zb[k], rtol=1e-6, atol=1e-7,
                err_msg="%s diverged between 1-proc and 2-proc" % k)
    # The training state must be bitwise identical: same mesh, same
    # reduction shape — only the transport differs.
    assert not close, ("bitwise mismatch (within 1e-6) on: %s" % close)


def test_dist_initialize_noop_single():
    """Without a process-group contract, initialize() is a no-op and the
    same script stays single-controller."""
    env = _clean_env()
    code = ("import sys; sys.path.insert(0, %r); "
            "from mxnet_tpu.parallel import dist; "
            "assert dist.initialize(local_device_count=8, platform='cpu') "
            "is False; "
            "assert dist.rank() == 0 and dist.num_processes() == 1; "
            "assert dist.local_slice(64) == (0, 64)"
            % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    rc = subprocess.call([sys.executable, "-c", code], env=env, timeout=120)
    assert rc == 0

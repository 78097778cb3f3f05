"""chip_smoke.py's phases on the CPU at tiny sizes (the first rehearsal
before a chip run): the script's control flow, its checks and the
`--chips 4` path on four virtual devices. The real widths run on the
chip only; tests/test_tpu_compile.py asks the chip's compiler."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke  # noqa: E402

_TINY = dict(network="resnet18", thumbnail=True, image=(3, 32, 32),
             classes=10, seed=0)


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_train_phase_tiny(dtype):
    row = chip_smoke.phase_train(batch=2, dtype=dtype, steps=5,
                                 devices=jax.devices()[:1], **_TINY)
    assert row["phase"] == "train" and len(row["losses"]) == 5
    assert row["losses"][-1] < row["losses"][0]


@pytest.mark.parametrize("donate", ["0", "1"])
def test_gluon_phase_tiny(monkeypatch, donate):
    """Both branches of MXNET_FUSED_DONATE: "auto" takes the donating one
    on accelerators only, so the CPU suite has to ask for it."""
    monkeypatch.setenv("MXNET_FUSED_DONATE", donate)
    row = chip_smoke.phase_gluon(batch=2, steps=3, **_TINY)
    assert row["fused_compiles"] >= 1
    assert row["donate"] is (donate == "1")


def test_serve_phase_tiny():
    row = chip_smoke.phase_serve(buckets=(1, 4), request_rows=(1, 3, 4, 1),
                                 **_TINY)
    assert row["compiles"] == 2 and row["rel_err"] < 2e-2


def test_kernel_phase_interpret():
    row = chip_smoke.phase_kernel((1, 2, 256, 64), jnp.bfloat16, 0,
                                  interpret=True)
    assert not row["compiled"] and set(row["rel_err"]) == \
        {"out", "dq", "dk", "dv"}


def test_dp_phase_four_virtual_devices():
    # Two images per device and 1x1 feature maps at the last stage make
    # BatchNorm ill-conditioned: rounding carries the two trajectories
    # apart far faster than at the real size, hence the loose bound.
    row = chip_smoke.phase_dp(batch=8, dtype="bfloat16", steps=3,
                              devices=jax.devices()[:4], tol=0.1, **_TINY)
    assert row["devices"] == 4 and row["all_reduce"]
    assert row["bytes_per_device"]["arguments"] > 0


def test_a_failed_comparison_raises():
    """No phase swallows its own failure: the first loss of a 10-class
    net is nowhere near ln(1000)."""
    with pytest.raises(AssertionError, match="first loss"):
        chip_smoke._check_losses([2.3, 2.0], classes=1000)
    with pytest.raises(AssertionError, match="did not fall"):
        chip_smoke._check_losses([2.3, 2.4], classes=10)


def _run(args, **env):
    return subprocess.run(
        [sys.executable] + args, cwd=_ROOT, capture_output=True, text=True,
        timeout=240, env=dict(os.environ, **env))


@pytest.mark.parametrize("chips", ["1", "4"])
def test_no_chip_no_result(chips):
    """Where JAX finds no TPU the script exits non-zero and prints no
    result line."""
    res = _run(["chip_smoke.py", "--chips", chips], JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_pin_platform_tpu_fails_without_a_tpu():
    """`--device tpu` must not train on the CPU without a word."""
    res = _run(["-c", "from mxnet_tpu.util import pin_platform\n"
                "pin_platform('tpu')\n"
                "import jax\nprint('ran on', jax.devices())"],
               JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "ran on" not in res.stdout


def test_tpu_context_past_the_end_raises():
    import mxnet_tpu as mx

    with pytest.raises(RuntimeError):
        mx.tpu(3).jax_device       # no tpu here at all, let alone four
    assert mx.cpu(11).jax_device == jax.devices()[11 % len(jax.devices())]

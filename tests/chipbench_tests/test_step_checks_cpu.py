"""The checks that see what the timed steps did, on the CPU at tiny sizes:
the step's loss on a pool batch read twice has to fall between the two
reads (`harness.check_losses`),
and the masters have to move as the stated optimizer moves them
(`harness.check_motion`). A sound step passes both; bf16 masters, a
skipped update and a tenfold Adam rate are refused, and so are the logits
checks' controls: the reference in fp8 in the program's place, and the
fp32 Gluon cell's evaluation forward computing in bf16. Each fault
is planted under the runner the harness builds (`planted`), so the run is
the command's own from set-up to the result line. The fused flash backward's
count in `models/deepseek_v3.py:kernel_work` is held to its hand count.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_harness_cpu import _ROOT, _load, _dump, _run, root  # noqa: F401

from chipbench import harness


def _bf16_masters(runner):
    """Every update rounded to bf16 (8 exponent, 7 mantissa bits), as
    masters kept in bf16 would hold it."""
    st = runner.step_fn
    update = st._opt_update

    def rounded(*args):
        param, states = update(*args)
        return jax.lax.reduce_precision(param, exponent_bits=8,
                                        mantissa_bits=7), states

    st._opt_update = rounded


def _scale_rate(factor):
    def plant(runner):
        runner.step_fn.set_learning_rate(factor * runner.step_fn.lr)
    return plant


def fp8(a):
    """`a` rounded to float8 e4m3 under one scale a tensor (its largest
    magnitude at e4m3's largest, 448), as an fp8 product's operand."""
    a = jnp.asarray(a, jnp.float32)
    scale = jnp.max(jnp.abs(a)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _fp8_reference(runner):
    """The control of a bfloat16 cell's logits check: the plain reference
    put in the program's evaluation forward, every product's operands in
    fp8 (the precision below the stated one)."""
    model, cfg = runner.model, runner.cfg

    def eval_forward(x, y, train=False):
        logits = jax.jit(lambda p, a: model.reference_forward(
            cfg, p, a, train=train, operand=fp8))(runner.params(), x)
        return (np.asarray(logits, np.float32),
                float(model.reference_loss(logits, y)))

    runner.eval_forward = eval_forward


def _bf16_compute(runner):
    """The control of an fp32 Gluon cell's logits check: its evaluation
    forward computing in bf16, the type below the stated one, as
    `net.cast` would set it: every floating parameter, running statistic
    and input cast to bf16, the logits back to fp32."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.parameter import override
    from mxnet_tpu.ndarray import NDArray

    def cast(a):
        return a.astype(jnp.bfloat16) \
            if jnp.issubdtype(a.dtype, jnp.floating) else a

    def eval_forward(x, y, train=False):
        low = {p: NDArray(cast(p.data()._data))
               for p in runner.net.collect_params().values()}
        with autograd.pause(train_mode=train), override(low):
            out = runner.net(NDArray(cast(jnp.asarray(x))))
            out = NDArray(out._data.astype(jnp.float32))
            loss = runner.loss_fn(out, NDArray(y))
        return (np.asarray(out._data, np.float32),
                float(loss.mean().asnumpy()))

    runner.eval_forward = eval_forward


FAULTS = {"bf16_masters": _bf16_masters, "no_update": _scale_rate(0.0),
          "tenfold_rate": _scale_rate(10.0),
          "fp8_reference": _fp8_reference, "bf16_compute": _bf16_compute}


def planted(fault, load=None):
    """`harness.load_module`, with every runner it sets up carrying
    `fault` (a key of FAULTS) before its first step."""
    load = load or harness.load_module

    def load_module(root, kind, name):
        module = load(root, kind, name)
        if kind == "runners":
            setup = module.setup

            def faulty(*args, **kwargs):
                runner = setup(*args, **kwargs)
                FAULTS[fault](runner)
                return runner

            module.setup = faulty
        return module

    return load_module


_ADAM = "kanana2-30b-a3b-train-s4096"
_SGD = "ptb-lstm-train-b1024"
_BN = "resnet50-train-b256"
_GLUON = "resnet50-gluon-b32"


def _stated_rate(root, config):
    """The copy's configuration trained at the rate the real one states
    (the tiny table raises it so that the loss falls in a few steps)."""
    path = os.path.join(root, "chipbench", "configs", config + ".json")
    cfg = _load(path)
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           config + ".json")) as f:
        cfg["optimizer"] = json.load(f)["optimizer"]
    _dump(path, cfg)


def _refused_by(result):
    """The compared numbers that lie outside their limits."""
    return {name for name, c in result["checks"].items()
            if not (c.get("above", -float("inf")) < c["value"]
                    < c.get("below", float("inf")))}


# Adam's mean step over the updates falls as they grow (the gradients'
# signs wander), so its cell runs a window of no length, which closes at
# the first read that repeats a pool batch: four updates however fast the
# machine runs. The tiny LSTM needs more updates than that for nine in ten
# of its sampled masters to move.
_SECONDS = {_ADAM: 0.0, _SGD: 0.2, _BN: 0.0, _GLUON: 0.0}
_MOTION = {"masters_moved_share", "mean_step_over_lr"}
_ALL = None

# (cell, fault, train at the stated rate, checks judged (None: all),
#  checks that must refuse it)
_CASES = [
    (_ADAM, None, False, _ALL, set()),
    (_ADAM, None, True, _MOTION, set()),
    (_ADAM, "bf16_masters", True, _MOTION, _MOTION),
    (_ADAM, "no_update", False, _MOTION, _MOTION),
    (_ADAM, "tenfold_rate", False, _ALL, {"mean_step_over_lr"}),
    (_SGD, None, False, _ALL, set()),
    (_SGD, "no_update", False, _ALL, {"masters_moved_share",
                                      "same_batch_loss_fall"}),
    (_BN, None, False, _ALL, set()),
    (_BN, "fp8_reference", False, _ALL, {"logits_rms_over_bf16_operands"}),
    (_GLUON, None, False, _ALL, set()),
    (_GLUON, "bf16_compute", False, {"logits_rel_err"}, {"logits_rel_err"}),
]


@pytest.mark.parametrize("cell,fault,stated,judged,refused", _CASES)
def test_what_the_steps_did_is_checked(root, monkeypatch, cell, fault,
                                       stated, judged, refused):
    """At the stated 3e-7 a few thousand weights move their bf16 copy too
    little for the loss on a pool batch to fall in a handful of steps
    (its sign is then rounding's), while the masters move as Adam moves
    them: those runs are judged by the motion checks alone. bf16 masters
    are run at that rate, where an update is under half a bf16 step of
    most masters, as at full size. With the update skipped the selection
    bias, non-gradient state that every step still moves, moves the
    loss a little either way: the motion checks alone refuse it there."""
    bench = harness.load_bench(root)
    config = [c["config"] for c in bench["workloads"]
              if c["name"] == cell][0]
    if stated:
        _stated_rate(root, config)
    if fault:
        monkeypatch.setattr(harness, "load_module", planted(fault))
    result, lines = _run(root, cell, trace=0, seconds=_SECONDS[cell])
    judged = set(result["checks"]) if judged is _ALL else judged
    assert _refused_by(result) & judged == refused, result["checks"]
    assert lines[-1]["updates"] == result["attempted"]
    if refused:
        assert result["correct"] is False and lines[-1]["problems"]
    elif judged == set(result["checks"]):
        assert result["correct"] is True, lines[-1]
    if fault == "no_update":
        fall = result["checks"]["same_batch_loss_fall"]["value"]
        assert abs(fall) < 1e-3
    if fault == "tenfold_rate":
        # the mean step follows the rate: ten times the sound reading's
        # order, and not a rounding's worth over the limit
        assert result["checks"]["mean_step_over_lr"]["value"] > 3.0


def test_fused_flash_backward_count_by_hand():
    """`mx_flash_bwd` at cell 3's sizes, blocks of 1024: 10 of 16 blocks
    computed, 32 heads; five score-sized products a pair, three at the
    query-key width 192 (q k^T, dS^T q, dS k) and two at the value width
    128 (dO v^T, p^T dO); bytes of q, k, dQ, dK at 192 and v, dO, dV at
    128 once in bf16, and two fp32 row statistics. At 3.80 ms a call,
    as a TPU v5e ran it, that is 74.5 % of the peak."""
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    model = harness.load_module(_ROOT, "models", "deepseek_v3")
    work = model.kernel_work(cfg, 1, 1024, 1024)
    assert set(work) == {"mx_flash_fwd", "mx_flash_bwd"}
    pairs = 10 * 1024 * 1024 * 32
    flops, nbytes = work["mx_flash_bwd"]
    assert flops == 2 * pairs * (3 * 192 + 2 * 128)
    assert nbytes == 32 * 4096 * (2 * (4 * 192 + 3 * 128) + 2 * 4)
    assert flops / 197e12 / 3.80e-3 == pytest.approx(0.745, abs=0.002)
    # the forward's count is as it was
    assert work["mx_flash_fwd"][0] == 2 * pairs * (192 + 128)

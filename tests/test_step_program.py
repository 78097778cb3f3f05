"""`TrainStep.program_stats()` / `program_text()` and their gauges (ISSUE
39 (b)): the step executable accounts for itself, at no compile."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import compile as cc
from mxnet_tpu import gluon
from mxnet_tpu.compile.buildlog import Record
from mxnet_tpu.parallel import TrainStep, make_mesh
from mxnet_tpu.parallel import train_step as ts_mod
from mxnet_tpu.telemetry import metrics as tm


def _step(devices=1):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.BatchNorm(),
            gluon.nn.Dense(4))
    net.initialize()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3},
                     mesh=make_mesh({"dp": -1},
                                    devices=jax.devices()[:devices]),
                     dtype="bfloat16")
    x = np.random.RandomState(0).rand(8, 12).astype("float32")
    y = np.arange(8, dtype="float32") % 4
    return step, x, y


def _gauge(name):
    fams = {f.name: f for f in tm.REGISTRY.collect()}
    children = fams[name].collect()
    return children[0][1].value if children else None


def test_nothing_before_the_first_step():
    step, _, _ = _step()
    assert step.program_stats() is None
    assert step.program_text() is None
    ts_mod._fold_step_programs()          # and the hook takes that


@pytest.mark.parametrize("devices", [1, 4])
def test_the_demand_adds_no_lower_and_no_build_record(devices):
    step, x, y = _step(devices)
    for _ in range(3):
        step(x, y)
    # the last batch is remembered by shape, dtype and sharding alone: its
    # arrays are the caller's to drop
    assert len(step._last_args) == 5
    assert not any(isinstance(part, jax.Array)
                   for spec in step._last_args for part in spec)
    before = len(cc.build_log())
    recompiled = ts_mod._program_recompiled.value
    stats = step.program_stats()
    text = step.program_text()
    kinds = [r.kind for r in cc.build_log()[before:]]
    assert "lower" not in kinds and "build" not in kinds, kinds
    assert ts_mod._program_recompiled.value == recompiled
    assert stats["total_bytes"] > 0 and "mx_train_step" in text
    # kept: a second demand does not even look the jaxpr up
    before = len(cc.build_log())
    assert step.program_stats() == stats and step.program_text() is text
    assert len(cc.build_log()) == before
    # and the next step runs the program it ran
    step(x, y)
    assert [r.kind for r in cc.build_log()[before:]] == []


def test_stats_are_the_executables_memory_analysis():
    step, x, y = _step()
    step(x, y)
    stats = step.program_stats()
    m = step._jitted.lower(
        step._param_vals, step._opt_state, step._aux_vals,
        *step._last_structs()).compile().memory_analysis()
    assert stats == {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "code_bytes": m.generated_code_size_in_bytes,
        "total_bytes": m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
        + m.generated_code_size_in_bytes}
    # the donated parameters and state come back in place
    assert 0 < stats["alias_bytes"] <= stats["argument_bytes"]


def test_gauges_fold_when_the_registry_is_collected():
    step, x, y = _step()
    step(x, y)
    stats = step.program_stats()
    others = [s.program_stats() for s in list(ts_mod._live_steps)
              if s is not step and s._last_args is not None]
    largest = max([stats] + others, key=lambda s: s["total_bytes"])
    assert _gauge("mx_step_program_bytes") == largest["total_bytes"]
    assert _gauge("mx_step_program_temp_bytes") == largest["temp_bytes"]
    assert _gauge("mx_step_program_recompiled_total") in (None, 0)


def test_a_new_batch_shape_is_a_new_executable():
    step, x, y = _step()
    step(x, y)
    small = step.program_stats()
    step(np.concatenate([x, x]), np.concatenate([y, y]))
    large = step.program_stats()
    assert large["argument_bytes"] > small["argument_bytes"]
    assert step._program["sig"][0][0] == (16, 12)


def test_a_demand_that_compiles_is_counted_and_warned_of_once(
        monkeypatch, caplog):
    step, x, y = _step()
    step(x, y)
    real = cc.build_log
    far = Record("build", "jit(mx_train_step)", "uncached", 1e18, 1.0, 1,
                 False)
    monkeypatch.setattr(ts_mod._cc, "build_log", lambda: real() + [far])
    monkeypatch.setattr(ts_mod._program_recompiled, "_children", {})
    with caplog.at_level(logging.WARNING, logger=ts_mod.__name__):
        step.program_stats()
        step._program = None
        step.program_stats()
    assert ts_mod._program_recompiled.value == 2
    assert sum("compiled a program" in r.message
               for r in caplog.records) == 1

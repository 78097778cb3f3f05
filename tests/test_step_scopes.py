"""The names `TrainStep` and the Gluon blocks put on a step's device ops
(ISSUE 39 (a)): the four phases on every instruction, block names around
the ops' own scopes, and nothing but names changed in the program."""
import contextlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import gluon
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import TrainStep, make_mesh
from mxnet_tpu.telemetry import device_table as dt

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
from chipbench import harness  # noqa: E402

# tests/test_deepseek_v3.py's tiny sizes: the dense layer and 2 sparse
_TINY = {
    "hidden_size": 64, "num_attention_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_routed_experts": 2, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "vocab_size": 48, "bptt": 32,
    "published": {"num_hidden_layers": 48, "n_routed_experts": 8,
                  "vocab_size": 384},
}
_FOUR = {"forward", "loss", "backward", "optimizer_update"}


def _small(deterministic=False):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.BatchNorm(),
            gluon.nn.Dense(4))
    net.initialize()
    devices = jax.devices()[:2 if deterministic else 1]
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3},
                     mesh=make_mesh({"dp": -1}, devices=devices),
                     dtype="bfloat16",
                     deterministic_reduction=deterministic)
    x = np.random.RandomState(0).rand(8, 12).astype("float32")
    y = np.arange(8, dtype="float32") % 4
    return net, step, x, y


def _deepseek():
    model = harness.load_module(_ROOT, "models", "deepseek_v3")
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(_TINY)
    cfg["calibration"] = dict(cfg["calibration"], dtype=None)
    net, loss_fn = model.build(cfg, 5)
    step = TrainStep(net, loss_fn, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3},
                     mesh=make_mesh({"dp": -1}, devices=jax.devices()[:1]),
                     dtype="bfloat16")
    x, y = model.make_batch(cfg, jax.random.PRNGKey(5), 1)
    return net, step, x, y


def _lowered(step, x, y):
    """The step program lowered from the arguments a call would pass."""
    if not step._materialized:
        step._materialize(np.asarray(x)[:1])
    if step._jitted is None:
        step._build()
    return step._jitted.lower(
        step._param_vals, step._opt_state, step._aux_vals,
        jax.device_put(jnp.asarray(x), step._data_sharding),
        jax.device_put(jnp.asarray(y), step._data_sharding),
        jnp.float32(0.1), jnp.float32(1), jax.random.PRNGKey(0))


def _op_names(lowered):
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    return {part for name in names for part in name.split(";")
            if part.startswith("jit(mx_train_step)/")}


@pytest.mark.parametrize("build", [_small, _deepseek,
                                   lambda: _small(deterministic=True)],
                         ids=["small", "deepseek_v3", "deterministic"])
def test_every_instruction_of_the_step_carries_a_phase(build):
    _, step, x, y = build()
    names = _op_names(_lowered(step, x, y))
    assert len(names) > 20
    # shard_map's own plumbing (the deterministic path) is the compiler's
    phases = {name: dt.classify(name)[0] for name in names
              if not re.search(r"/shard_map(/broadcast\.\d+)?$", name)}
    assert set(phases.values()) == _FOUR, \
        sorted(n for n, p in phases.items() if p == "unscoped")


def test_every_equation_of_the_steps_jaxpr_is_under_a_phase():
    _, step, x, y = _small()
    step(x, y)
    jaxpr = step._jitted.trace(
        step._param_vals, step._opt_state, step._aux_vals,
        *step._last_structs()).jaxpr
    # jit's own laying-out of an output (`reshard`) is no op of the step's
    stacks = {str(eqn.source_info.name_stack) for eqn in jaxpr.eqns
              if eqn.primitive.name != "reshard"}
    assert {dt.classify(s + "/op")[0] for s in stacks} == _FOUR, stacks


def test_block_names_nest_the_ops_own_scopes():
    net, step, x, y = _deepseek()
    paths = {dt.classify(name) for name in _op_names(_lowered(step, x, y))}
    forward = {path for phase, path in paths if phase == "forward"}
    backward = {path for phase, path in paths if phase == "backward"}
    blocks = {}
    net.apply(lambda b: blocks.setdefault(b.name, b))
    scopes = ("mla_attention", "moe_experts", "moe_route", "moe_shared",
              "rotary_embedding")
    for name in scopes:             # an op's scope may lie in another's
        blocks[name] = None
    for scope in scopes:
        for side in (forward, backward):
            above = {path[:path.index(scope)] for path in side
                     if scope in path}
            assert above, scope
            # the model's name first, then blocks all the way down
            for names in above:
                assert names and names[0] == net.name
                assert all(name in blocks for name in names), names
    # three layers, each under its own name (the inline-jitted functions
    # of PR 35 and a cached trace do not lend one layer's name to the next)
    layers = {path[2] for path in forward if len(path) > 3
              and "mla_attention" in path}
    assert len(layers) == 3


def test_a_small_nets_blocks_are_on_its_ops():
    net, step, x, y = _small()
    paths = {dt.classify(n) for n in _op_names(_lowered(step, x, y))}
    seen = {path[:2] for phase, path in paths
            if phase in ("forward", "backward") and len(path) >= 2}
    children = [child.name for child in net._children.values()]
    assert {(net.name, name) for name in children} <= seen
    assert any(path[-1:] == ("batchnorm_train_bwd",)
               for phase, path in paths if phase == "backward")


@pytest.mark.parametrize("build", [_small, _deepseek],
                         ids=["small", "deepseek_v3"])
def test_the_scopes_change_nothing_but_names_and_locations(build,
                                                           monkeypatch):
    """The StableHLO without locations (where the names live) is, text
    for text, what the step lowers to with every scope taken out: the
    parent's program."""
    np.random.seed(0)
    _, step, x, y = build()
    with_names = _lowered(step, x, y)
    text = with_names.as_text()
    assert "forward" not in text and "optimizer_update" not in text
    assert "jvp(forward)" in with_names.as_text(debug_info=True)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    step._jitted = None
    jax.clear_caches()
    without = _lowered(step, x, y)
    assert "jvp(forward)" not in without.as_text(debug_info=True)
    assert without.as_text() == text


def test_the_eager_path_opens_no_scope(monkeypatch):
    """Outside a trace `Block.__call__` pays one check and no scope."""
    opened = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: opened.append(name) or real(name))
    net = gluon.nn.Dense(3, in_units=5)
    net.initialize()
    out = net(NDArray(jnp.ones((2, 5))))
    assert out.shape == (2, 3) and opened == []
    net.hybridize()
    net(NDArray(jnp.ones((2, 5))))        # the CachedOp's trace
    assert net.name in opened
    n = len(opened)
    net(NDArray(jnp.ones((2, 5))))        # the cached executable: no trace
    assert len(opened) == n

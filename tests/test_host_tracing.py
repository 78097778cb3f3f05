"""The host side of a step, measured inside the program (ISSUE 25): spans
mirrored into a jax.profiler capture, the ring's cheap overflow, the
spans through backward() and Trainer.step, the executables' names and
the compile log fed by JAX's own events. All on the CPU.
"""
import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu import compile as cc
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.compile import buildlog
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import registry as reg
from mxnet_tpu.parallel import TrainStep
from mxnet_tpu.telemetry import memstats, metrics, trace


# -- one clock with the capture -------------------------------------------------

def test_span_is_mirrored_into_a_running_capture(tmp_path):
    """While jax.profiler captures, a span is on the capture's
    /host:CPU plane under its own name with its args; before and after
    the capture nothing is mirrored, and complete() never is."""
    from jax.profiler import ProfileData

    with trace.span("mirror::before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("mirror::outer", op="probe", n=3):
            with trace.span("mirror::inner"):
                pass
        trace.complete("mirror::retro", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    with trace.span("mirror::after"):
        pass
    assert not trace._capture_running()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mirror::"):
                    found[ev.name] = (plane.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"mirror::outer", "mirror::inner"}
    plane, start, end, stats = found["mirror::outer"]
    assert plane == "/host:CPU"
    assert stats == {"op": "probe", "n": 3}
    assert start <= found["mirror::inner"][1] \
        and found["mirror::inner"][2] <= end
    # the rings hold all five, capture or not
    names = {e["name"] for e in trace.chrome_trace()["traceEvents"]}
    assert {"mirror::before", "mirror::outer", "mirror::inner",
            "mirror::retro", "mirror::after"} <= names


# -- a cheap overflow -----------------------------------------------------------

@pytest.mark.parametrize("read_first", ["take_dropped", "scrape"])
def test_full_ring_drops_without_touching_the_registry(monkeypatch,
                                                       read_first):
    """Appending to a full ring bumps the ring's own cell and does not
    go near the metrics registry; take_dropped() and the scrape both
    report the right count, whichever reads first."""
    touched = []
    real_labels = trace._dropped_fam.labels

    def labels(**kw):
        touched.append(kw)
        return real_labels(**kw)

    monkeypatch.setattr(trace._dropped_fam, "labels", labels)
    monkeypatch.setattr(metrics.REGISTRY, "counter",
                        lambda *a, **k: touched.append(a))
    trace.take_dropped()                    # harvest what others left
    touched.clear()
    name = "overflow-%s" % read_first
    prev = trace.capacity()
    trace.set_capacity(8)

    def emit():
        for i in range(8 + 5):
            with trace.span("overflow::span", i=i):
                pass

    try:
        t = threading.Thread(target=emit, name=name)
        t.start()
        t.join(30)
        assert not t.is_alive()
    finally:
        trace.set_capacity(prev)
    assert touched == []                    # 5 drops, registry untouched

    def scraped():
        text = metrics.REGISTRY.render_prometheus()
        m = re.search(r'mx_trace_dropped_spans_total\{thread="%s"\} (\S+)'
                      % name, text)
        return float(m.group(1)) if m else 0.0

    if read_first == "take_dropped":
        assert trace.take_dropped() == 5
        assert scraped() == 5
    else:
        assert scraped() == 5
        assert trace.take_dropped() == 5
    assert trace.take_dropped() == 0        # harvested once
    assert scraped() == 5                   # the counter is a total
    trace.clear()


# -- spans where the host time of a step goes -----------------------------------

def _events():
    return [e for e in trace.chrome_trace()["traceEvents"]
            if e["ph"] == "X"]


def _inside(inner, outer):
    return outer["tid"] == inner["tid"] and outer["ts"] <= inner["ts"] \
        and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_step_spans_nest():
    net = nn.Dense(4, in_units=8, prefix="hosttrace_")
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(np.ones((2, 8), np.float32))
    trace.clear()
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(2)
    by_name = {}
    for e in _events():
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["cached_op::execute"]) == 1
    (backward,) = by_name["autograd::backward"]
    vjps = by_name["autograd::vjp"]
    assert len(vjps) == 2                   # sum, then the cached op
    assert {e["args"]["op"] for e in vjps} == {"sum", net._cached_op._op.name}
    (commit,) = by_name["autograd::commit"]
    assert commit["args"]["leaves"] >= 2    # weight and bias
    assert all(_inside(e, backward) for e in vjps + [commit])
    assert all(e["ts"] + e["dur"] <= commit["ts"] + 1 for e in vjps)
    (step,) = by_name["trainer::step"]
    (update,) = by_name["trainer::update"]
    assert _inside(update, step)
    assert all(_inside(e, update) for e in by_name["trainer::fused_apply"])
    assert backward["ts"] + backward["dur"] <= step["ts"] + 1
    trace.clear()


# -- named executables ----------------------------------------------------------

def _module_name(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _lower_op():
    attrs = {"num_hidden": 4, "no_bias": True}
    op = reg.get("FullyConnected")
    return op.jitted(reg._freeze(attrs), attrs).lower(
        jnp.ones((2, 8)), jnp.ones((4, 8)))


def _lower_op_vjp():
    attrs = {"num_hidden": 4, "no_bias": True}
    op = reg.get("FullyConnected")
    return autograd._vjp_runner(op, reg._freeze(attrs), attrs).lower(
        (jnp.ones((2, 8)), jnp.ones((4, 8))), (jnp.ones((2, 4)),))


def _cached():
    op = CachedOp(lambda a: a * 2)
    attrs = {"training": False}
    key = jax.random.PRNGKey(0)
    return op._op, reg._freeze(attrs), attrs, key


def _lower_cached_fwd():
    op, akey, attrs, key = _cached()
    return op.jitted(akey, attrs).lower(key, jnp.ones((3,)))


def _lower_cached_vjp():
    op, akey, attrs, key = _cached()
    return autograd._vjp_runner(op, akey, attrs).lower(
        (key, jnp.ones((3,))), (jnp.ones((3,)),))


def _lower_train_step():
    net = nn.Dense(4, in_units=8, prefix="hosttrace_ts_")
    net.initialize()
    st = TrainStep(net, gloss.L2Loss(), optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1})
    x = np.ones((8, 8), np.float32)
    y = np.ones((8, 4), np.float32)
    st(x, y)
    return st._jitted.lower(st._param_vals, st._opt_state, st._aux_vals,
                            x, y, jnp.float32(0.1), jnp.float32(2),
                            jax.random.PRNGKey(0))


def _chunk():
    net = nn.Dense(4, in_units=8, prefix="hosttrace_fu_")
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    with autograd.record():
        loss = net(nd.array(np.ones((2, 8), np.float32))).sum()
    loss.backward()
    trainer.step(2)
    (ch,) = trainer._applier._chunks.values()
    return ch, tuple(jnp.zeros(s, jnp.float32) for s in ch.shapes)


def _lower_fused_chunk():
    ch, grads = _chunk()
    hyp = jnp.zeros((ch.n,), jnp.float32)
    return ch.exec_fn.lower(grads, ch.flat_w, tuple(ch.flat_s), hyp, hyp)


def _lower_flatten_chunk():
    ch, grads = _chunk()
    return ch.flatten_fn.lower(*grads)


def _bucket():
    from mxnet_tpu.fused_update import _Bucket

    bucket = _Bucket(0, [("a", (2, 3), "float32"), ("b", (4,), "float32")])
    arrays = [nd.array(np.ones((2, 3), np.float32)),
              nd.array(np.ones((4,), np.float32))]
    flat = bucket.flatten(arrays, arrays[0].context)
    bucket.unflatten(flat)
    bucket.sumsq(flat)
    return bucket, arrays, flat


def _lower_bucket_flatten():
    bucket, arrays, _ = _bucket()
    return bucket._flatten.lower(*[a._data for a in arrays])


def _lower_bucket_unflatten():
    bucket, _, flat = _bucket()
    return bucket._unflatten.lower(flat._data)


def _lower_bucket_sumsq():
    bucket, _, flat = _bucket()
    return bucket._sumsq.lower(flat._data)


@pytest.mark.parametrize("lower,name", [
    (_lower_op, "jit_mx_op_FullyConnected"),
    (_lower_op_vjp, "jit_mx_vjp_FullyConnected"),
    (_lower_cached_fwd, "jit_mx_cached_fwd"),
    (_lower_cached_vjp, "jit_mx_cached_vjp"),
    (_lower_train_step, "jit_mx_train_step"),
    (_lower_fused_chunk, "jit_mx_fused_sgd"),
    (_lower_flatten_chunk, "jit_mx_flatten_chunk"),
    (_lower_bucket_flatten, "jit_mx_bucket_flatten"),
    (_lower_bucket_unflatten, "jit_mx_bucket_unflatten"),
    (_lower_bucket_sumsq, "jit_mx_bucket_sumsq"),
], ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_seam_executables_are_named(lower, name):
    """The module text, and so the device trace's XLA Modules line and
    the persistent-cache key, names the seam: no counter, no closure's
    name."""
    assert _module_name(lower()) == name


def test_cached_op_names_hold_no_process_counter():
    a, b = CachedOp(lambda x: x + 1), CachedOp(lambda x: x + 1)
    assert a._op.name != b._op.name          # the counter is in the name
    assert a._op.fwd_name == b._op.fwd_name == "mx_cached_fwd"
    assert a._op.vjp_name == b._op.vjp_name == "mx_cached_vjp"


# -- the compile log ------------------------------------------------------------

def _records(needle):
    return [r for r in cc.build_log() if needle in r.fun_name]


def test_compile_log_holds_trace_lower_build_with_name_and_step():
    """One trace, one lower and one build record per program, under the
    executable's name, stamped with the training steps completed; with
    no persistent cache the outcome is `uncached` and the build is
    observed into mx_compile_seconds under the seam's site."""
    buildlog.clear()
    before = memstats.compile_stats().get("train_step", {"count": 0})
    net = nn.Dense(3, in_units=5, prefix="hosttrace_log_")
    net.initialize()
    st = TrainStep(net, gloss.L2Loss(), optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1})
    x = np.ones((8, 5), np.float32)
    y = np.ones((8, 3), np.float32)
    st(x, y)
    st(x, y)
    records = _records("mx_train_step")
    assert [r.kind for r in records] == ["trace", "lower", "build"]
    assert [r.fun_name for r in records] == [
        "mx_train_step", "jit(mx_train_step)", "jit(mx_train_step)"]
    assert [r.outcome for r in records] == ["", "", "uncached"]
    assert all(r.step == 0 and r.seconds > 0 and not r.inner
               for r in records)
    assert records[0].start <= records[1].start <= records[2].start
    after = memstats.compile_stats()["train_step"]
    assert after["count"] == before["count"] + 1
    # two steps are done: what compiles now says so
    CachedOp(lambda a: a * 3)(nd.array(np.ones(7, np.float32)))
    late = _records("mx_cached_fwd")
    assert {r.kind for r in late} == {"trace", "lower", "build"}
    assert all(r.step == 2 for r in late)
    # a Trainer.step counts as a step too
    p = gluon.Parameter("hosttrace_log_w", shape=(8,))
    p.initialize(init=mx.init.Constant(1.0))
    trainer = gluon.Trainer([p], "sgd", {"learning_rate": 0.1})
    p.grad()[:] = np.ones(8, np.float32)
    trainer.step(1)
    assert all(r.step == 2 for r in _records("mx_fused_sgd"))
    CachedOp(lambda a: a * 5)(nd.array(np.ones(9, np.float32)))
    assert max(r.step for r in _records("mx_cached_fwd")) == 3
    # the ring holds each record as a retroactive span
    spans = [e for e in trace.chrome_trace()["traceEvents"]
             if e["name"] == "xla::build"
             and e["args"]["fun"] == "jit(mx_train_step)"]
    assert spans and spans[-1]["args"]["outcome"] == "uncached"


def test_nested_traces_are_marked_inner():
    """A jitted function traced inside another's trace raises its own
    event; the log marks it inner so that seconds are summed once."""
    buildlog.clear()

    @jax.jit
    def hosttrace_leaf(a):
        return a + 1

    def hosttrace_outer(a):
        return hosttrace_leaf(a) * 2

    jax.jit(hosttrace_outer)(jnp.ones((3,)))
    (leaf,) = [r for r in _records("hosttrace_leaf") if r.kind == "trace"]
    (outer,) = [r for r in _records("hosttrace_outer") if r.kind == "trace"]
    assert leaf.inner and not outer.inner
    assert outer.start <= leaf.start
    assert leaf.start + leaf.seconds <= outer.start + outer.seconds


@pytest.fixture()
def jax_cache(tmp_path):
    """JAX's persistent cache in a temporary directory, storing every
    program whatever its size and compile time; switched off again after."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    compilation_cache.reset_cache()
    yield
    for n in names:
        jax.config.update(n, prev[n])
    compilation_cache.reset_cache()


def test_compile_log_outcomes_with_the_persistent_cache(jax_cache):
    """Cache on: the first build is a `miss` (compiled and written) and
    counts in mx_compile_seconds, the same program built again in this
    process is a `hit` and does not."""
    buildlog.clear()

    def site_count():
        return memstats.compile_stats().get("cached_op", {"count": 0})["count"]

    count = site_count()
    x = nd.array(np.arange(11, dtype=np.float32))
    CachedOp(lambda a: a * 7 + 1)(x)
    assert [r.outcome for r in _records("mx_cached_fwd")
            if r.kind == "build"] == ["miss"]
    assert site_count() == count + 1
    CachedOp(lambda a: a * 7 + 1)(x)         # same module text: a load
    assert [r.outcome for r in _records("mx_cached_fwd")
            if r.kind == "build"] == ["miss", "hit"]
    assert site_count() == count + 1


@pytest.mark.parametrize("operator", [None, "2.5"])
def test_enable_jax_cache_stores_every_program_unless_the_operator_says(
        monkeypatch, tmp_path, operator):
    """Set-up is mostly programs that compile in under JAX's default
    threshold of a second: the entry points' cache keeps them all, so
    that a warm start hits every one; an operator's own threshold
    stands."""
    var = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
    name = "jax_persistent_cache_min_compile_time_secs"
    prev = getattr(jax.config, name)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    if operator is None:
        monkeypatch.delenv(var, raising=False)
    else:
        monkeypatch.setenv(var, operator)
    try:
        assert cc.enable_jax_cache() == str(tmp_path)
        assert getattr(jax.config, name) == (
            0.0 if operator is None else prev)
    finally:
        jax.config.update(name, prev)


def test_listeners_are_registered_once_by_the_package_import():
    from jax._src import monitoring

    assert monitoring.get_event_duration_listeners().count(
        buildlog._on_duration) == 1
    buildlog.install()
    assert monitoring.get_event_duration_listeners().count(
        buildlog._on_duration) == 1

"""Persistent compilation cache (ISSUE 11): disk store durability +
corruption handling under fault_fs, warm-reload at all three compile
seams (cached_op / fused_apply / train_step), pad-to-bucket shape
canonicalization, LRU retention, the inspect/GC/verify CLI, and
pod-wide distribution (LocalBus + 2-process kvstore acceptance)."""
import importlib.util
import json
import os
import socket
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu import compile as cc
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.compile.distribute import CacheDistributor
from mxnet_tpu.compile.store import CompileCacheStore, make_key
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.parallel import TrainStep
from mxnet_tpu.telemetry import memstats
from mxnet_tpu.telemetry import metrics as tmetrics
from mxnet_tpu.telemetry.aggregate import LocalBus

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from launch import launch_local  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _cc_isolated():
    """Every test starts (and leaves) with the cache disabled and no
    distributor; tests that want it call cc.configure themselves."""
    cc.reset()
    yield
    cc.reset()


def _counter(name, **labels):
    fam = tmetrics.REGISTRY.get(name)
    if fam is None:
        return 0
    return fam.labels(**labels).value


def _site_count(site):
    return {s: r["count"]
            for s, r in memstats.compile_stats().items()}.get(site, 0)


def _jnp():
    import jax.numpy as jnp

    return jnp


# -- store durability ----------------------------------------------------------

def test_store_roundtrip_and_key_anatomy(tmp_path):
    store = CompileCacheStore(str(tmp_path))
    key = make_key([["site"], "fingerprint", {"jaxlib": "1"}])
    path = store.put(key, b"payload-bytes", {"site": "cached_op"})
    assert os.path.basename(path) == "cc.%s.bin" % key
    meta, payload = store.get(key)
    assert payload == b"payload-bytes"
    assert meta["site"] == "cached_op"
    # Any key ingredient changing — here the backend version — is a
    # different key: version skew can never load a stale executable.
    assert make_key([["site"], "fingerprint", {"jaxlib": "2"}]) != key
    assert store.get("0" * 32) is None          # absent = plain miss


def test_store_lru_gc_by_mtime(tmp_path):
    store = CompileCacheStore(str(tmp_path))
    now = time.time()
    for i in range(4):
        key = make_key(["entry", i])
        store.put(key, b"x" * 100, {"i": i})
        os.utime(store.path_for(key), (now - 100 + i, now - 100 + i))
    removed = store.gc(max_bytes=2 * (100 + 120))   # ~2 entries' worth
    assert removed                                   # oldest went first
    left = {store.get(k, touch=False)[0]["i"] for k in store.keys()}
    assert 3 in left and 0 not in left


def test_store_corruption_truncated_and_crc(tmp_path, fault_fs):
    store = CompileCacheStore(str(tmp_path))
    k1, k2 = make_key(["a"]), make_key(["b"])
    store.put(k1, b"p" * 64, {})
    store.put(k2, b"q" * 64, {})
    # Truncation (torn tail that survived to "commit").
    fault_fs.corrupt(store.path_for(k1), truncate_to=30)
    assert store.get(k1) is None
    assert not os.path.exists(store.path_for(k1))   # quarantined
    # Single-bit payload damage caught by CRC.
    fault_fs.corrupt(store.path_for(k2),
                     flip_byte_at=os.path.getsize(store.path_for(k2)) - 3)
    assert store.get(k2) is None
    assert not os.path.exists(store.path_for(k2))


def test_store_key_mismatch_never_serves_wrong_executable(tmp_path):
    """An entry file renamed/copied under another key (rsync of a
    half-GC'd dir, manual restore) fails the header key cross-check —
    payload CRC alone cannot catch a whole-file swap."""
    store = CompileCacheStore(str(tmp_path))
    k1, k2 = make_key(["one"]), make_key(["two"])
    store.put(k1, b"executable-one", {})
    os.rename(store.path_for(k1), store.path_for(k2))
    assert store.get(k2) is None
    assert not os.path.exists(store.path_for(k2))   # quarantined


def test_store_get_without_quarantine_keeps_evidence(tmp_path, fault_fs):
    store = CompileCacheStore(str(tmp_path))
    key = make_key(["ev"])
    store.put(key, b"payload" * 10, {})
    fault_fs.corrupt(store.path_for(key), truncate_to=40)
    assert store.get(key, quarantine=False) is None
    assert os.path.exists(store.path_for(key))      # evidence kept
    assert store.get(key) is None                   # runtime read GCs it
    assert not os.path.exists(store.path_for(key))


def test_store_version_skew_is_a_miss(tmp_path):
    store = CompileCacheStore(str(tmp_path))
    key = make_key(["v"])
    store.put(key, b"payload", {})
    path = store.path_for(key)
    with open(path, "rb") as f:
        header, payload = f.readline(), f.read()
    rec = json.loads(header)
    rec["format"] = "mxnet_tpu.compile_cache/999"
    with open(path, "wb") as f:
        f.write(json.dumps(rec).encode() + b"\n" + payload)
    assert store.get(key) is None                   # skew never loads


def test_kill_mid_commit_leaves_no_torn_entry(tmp_path, fault_fs):
    """A commit that dies at the rename (== a kill between write and
    rename) must leave the cache exactly as before: no entry, no
    staging litter, and the NEXT start commits cleanly."""
    store = CompileCacheStore(str(tmp_path))
    key = make_key(["torn"])
    fault_fs.fail_next_renames(1)
    with pytest.raises(OSError):
        store.put(key, b"payload", {})
    assert os.listdir(str(tmp_path)) == []          # nothing torn, no tmp
    assert store.get(key) is None
    store.put(key, b"payload", {})                  # next start is clean
    assert store.get(key)[1] == b"payload"


# -- the cached-jit wrapper ----------------------------------------------------

def test_cached_function_hit_miss_counters(tmp_path):
    cc.configure(str(tmp_path))
    jnp = _jnp()

    def f(x):
        return jnp.tanh(x) * 2

    x = jnp.ones((8,))
    miss0 = _counter("mx_compile_cache_misses_total", site="t1")
    cf1 = cc.cached_compile(f, "t1")
    out1 = cf1(x)
    assert cf1.num_compiles == 1 and cf1.num_hits == 0
    assert _counter("mx_compile_cache_misses_total", site="t1") \
        == miss0 + 1
    hit0 = _counter("mx_compile_cache_hits_total", site="t1",
                    source="local")
    cf2 = cc.cached_compile(f, "t1")
    out2 = cf2(x)
    assert cf2.num_compiles == 0 and cf2.num_hits == 1
    assert _counter("mx_compile_cache_hits_total", site="t1",
                    source="local") == hit0 + 1
    assert np.allclose(np.asarray(out1), np.asarray(out2))
    # Steady state: the second call of the same signature is a dict hit.
    cf2(x)
    assert cf2.num_hits == 1


@pytest.mark.parametrize("first,count", [(3, 1), (2, 4)])
def test_warm_load_runs_on_the_devices_it_was_compiled_for(tmp_path, first,
                                                           count):
    """A one-device (or sub-mesh) executable reloaded on the 8-device
    backend executes on ITS devices: the installed loader's default
    assigns every device of the backend and the first call then dies
    with a shard-count mismatch."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cc.configure(str(tmp_path))
    jnp = _jnp()
    devices = jax.devices()[first:first + count]
    sharding = NamedSharding(Mesh(np.asarray(devices), ("dp",)), P("dp"))
    x = jax.device_put(jnp.arange(8.0), sharding)

    def f(a):
        return a * 2 + 1

    kwargs = dict(in_shardings=(sharding,), out_shardings=sharding)
    want = np.asarray(cc.cached_compile(f, "t_dev", **kwargs)(x))
    warm = cc.cached_compile(f, "t_dev", **kwargs)
    out = warm(x)
    assert warm.num_compiles == 0 and warm.num_hits == 1
    assert {s.device for s in out.addressable_shards} == set(devices)
    assert np.array_equal(np.asarray(out), want)


def test_truncated_entry_is_counted_miss_and_recompiles(tmp_path,
                                                        fault_fs):
    """fault_fs truncate-on-close: the entry commits TORN; the next
    start detects it (CRC/size), counts a miss, recompiles and heals
    the cache."""
    cc.configure(str(tmp_path))
    jnp = _jnp()

    def f(x):
        return x * 3 + 1

    x = jnp.ones((4,))
    fault_fs.truncate_next_file(20)     # tears the entry's commit
    cf1 = cc.cached_compile(f, "t2")
    cf1(x)
    assert fault_fs.files_truncated == 1
    miss0 = _counter("mx_compile_cache_misses_total", site="t2")
    cf2 = cc.cached_compile(f, "t2")
    out = cf2(x)
    assert cf2.num_compiles == 1        # recompiled, didn't crash
    assert _counter("mx_compile_cache_misses_total", site="t2") \
        == miss0 + 1
    assert np.allclose(np.asarray(out), 4.0)
    cf3 = cc.cached_compile(f, "t2")    # healed: now a clean hit
    cf3(x)
    assert cf3.num_compiles == 0 and cf3.num_hits == 1


def test_serialize_unsupported_backend_falls_back(tmp_path, monkeypatch):
    """A backend that cannot serialize executables still computes —
    counted, and the cache simply stays cold."""
    cc.configure(str(tmp_path))
    jnp = _jnp()

    def boom(compiled):
        raise NotImplementedError("backend cannot serialize")

    monkeypatch.setattr(cc, "_serialize", boom)
    err0 = _counter("mx_compile_cache_errors_total", site="t3",
                    kind="serialize_unsupported")
    cf = cc.cached_compile(lambda x: x + 1, "t3")
    out = cf(jnp.ones((4,)))
    assert np.allclose(np.asarray(out), 2.0)
    assert _counter("mx_compile_cache_errors_total", site="t3",
                    kind="serialize_unsupported") == err0 + 1
    assert CompileCacheStore(str(tmp_path)).keys() == []


def test_deserialize_failure_recompiles(tmp_path, monkeypatch):
    cc.configure(str(tmp_path))
    jnp = _jnp()

    def f(x):
        return x - 5

    x = jnp.ones((4,))
    cc.cached_compile(f, "t4")(x)

    def boom(blob):
        raise ValueError("bitrot")

    monkeypatch.setattr(cc, "_deserialize", boom)
    err0 = _counter("mx_compile_cache_errors_total", site="t4",
                    kind="deserialize")
    cf = cc.cached_compile(f, "t4")
    out = cf(x)
    assert cf.num_compiles == 1
    assert np.allclose(np.asarray(out), -4.0)
    assert _counter("mx_compile_cache_errors_total", site="t4",
                    kind="deserialize") == err0 + 1


def test_disabled_cache_is_plain_jit(tmp_path):
    jnp = _jnp()
    fn = cc.maybe_cached_jit(lambda x: x * 2, "t5")
    assert not isinstance(fn, cc.CachedFunction)
    assert np.allclose(np.asarray(fn(jnp.ones((2,)))), 2.0)


# -- the three seams warm-reload -----------------------------------------------

def test_cached_op_warm_reload_compiles_nothing(tmp_path):
    cc.configure(str(tmp_path))
    w = nd.array(np.random.rand(6, 3).astype(np.float32))

    def fwd(w_, x):
        return nd.dot(x, w_)

    op1 = CachedOp(fwd, num_params=1)
    x = nd.array(np.random.rand(2, 6).astype(np.float32))
    out1 = op1.inference(w, x)
    count = _site_count("cached_op")
    assert count >= 1
    op2 = CachedOp(fwd, num_params=1)
    out2 = op2.inference(w, x)
    # The warm op TRACED (num_traces counts signatures for the serving
    # warmup contract) but did not COMPILE.
    assert op2.num_traces == 1
    assert _site_count("cached_op") == count
    assert np.allclose(out1.asnumpy(), out2.asnumpy())


def test_executor_warm_reload_compiles_nothing(tmp_path):
    """ISSUE 15 satellite: simple_bind Executors (the serving
    checkpoint-model path) build their whole-graph forward through the
    cached seam — a second Executor of the same symbol loads its
    executable instead of compiling, so gateway warmup after a warm
    restart compiles nothing."""
    cc.configure(str(tmp_path))
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=5, name="ccx_fc")
    args = {"ccx_fc_weight": nd.array(np.random.rand(5, 7)
                                      .astype(np.float32)),
            "ccx_fc_bias": nd.zeros((5,)),
            "data": nd.array(np.random.rand(3, 7).astype(np.float32))}

    ex1 = net.bind(mx.cpu(), args)
    out1 = ex1.forward(is_train=False)[0]
    fn1 = ex1._fwd_cache[False]
    assert fn1.num_compiles == 1 and fn1.num_hits == 0

    ex2 = net.bind(mx.cpu(), args)
    out2 = ex2.forward(is_train=False)[0]
    fn2 = ex2._fwd_cache[False]
    assert fn2.num_compiles == 0 and fn2.num_hits == 1
    np.testing.assert_array_equal(out1.asnumpy(), out2.asnumpy())


def test_fused_apply_warm_reload_compiles_nothing(tmp_path):
    cc.configure(str(tmp_path))

    def one_step():
        net = nn.Dense(8, in_units=16, prefix="cc_fused_")
        net.initialize(force_reinit=True)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        with autograd.record():
            loss = net(nd.array(
                np.random.rand(4, 16).astype(np.float32))).sum()
        loss.backward()
        trainer.step(4)

    one_step()
    count = _site_count("fused_apply")
    assert count >= 1
    one_step()
    assert _site_count("fused_apply") == count


def test_train_step_warm_reload_and_identical_math(tmp_path):
    """The warm TrainStep compiles nothing AND the deserialized
    executable computes the exact same training trajectory as the
    freshly compiled one."""
    cc.configure(str(tmp_path))
    x = np.random.rand(8, 8).astype(np.float32)
    y = np.random.rand(8, 4).astype(np.float32)

    def run(seed):
        mx.random.seed(seed)
        net = nn.Dense(4, in_units=8, prefix="cc_step_")
        net.initialize(force_reinit=True)
        step = TrainStep(net, gloss.L2Loss(), optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
        losses = [float(np.asarray(step(x, y))) for _ in range(3)]
        return losses

    cold = run(11)
    count = _site_count("train_step")
    assert count >= 1
    warm = run(11)
    assert _site_count("train_step") == count   # loaded, not compiled
    assert warm == cold                          # bit-identical math


# -- pad-to-bucket canonicalization --------------------------------------------

def test_pad_to_buckets_eliminates_off_ladder_traces(tmp_path):
    w = nd.array(np.random.rand(4, 3).astype(np.float32))

    def fwd(w_, x):
        return nd.dot(x, w_)

    op = CachedOp(fwd, num_params=1).pad_to_buckets(8)
    for rows in (1, 2, 4, 8):                   # warm the ladder
        op.inference(w, nd.array(
            np.random.rand(rows, 4).astype(np.float32)))
    warm = op.num_traces
    assert warm == 4
    for rows in (3, 5, 6, 7):                   # off-ladder shapes
        xv = np.random.rand(rows, 4).astype(np.float32)
        out = op.inference(w, nd.array(xv))
        assert out.shape == (rows, 3)
        assert np.allclose(out.asnumpy(), xv @ w.asnumpy(), atol=1e-5)
    assert op.num_traces == warm                # zero new traces


def test_pad_to_buckets_multi_output_and_overflow():
    w = nd.array(np.random.rand(4, 3).astype(np.float32))

    def fwd(w_, x):
        h = nd.dot(x, w_)
        return [h, h * 2]

    op = CachedOp(fwd, num_params=1).pad_to_buckets([2, 4])
    op.inference(w, nd.array(np.random.rand(4, 4).astype(np.float32)))
    t = op.num_traces
    xv = np.random.rand(3, 4).astype(np.float32)
    o1, o2 = op.inference(w, nd.array(xv))
    assert op.num_traces == t
    assert o1.shape == (3, 3) and o2.shape == (3, 3)
    assert np.allclose(o2.asnumpy(), 2 * o1.asnumpy(), atol=1e-6)
    # Above the ladder: runs unpadded (its own signature), never rejects.
    b1, _ = op.inference(w, nd.array(
        np.random.rand(6, 4).astype(np.float32)))
    assert b1.shape == (6, 3)
    assert op.num_traces == t + 1


# -- distribution --------------------------------------------------------------

def test_localbus_rank1_pulls_rank0_entries(tmp_path):
    jnp = _jnp()
    bus = LocalBus(num_workers=2)

    def f(x):
        return jnp.sqrt(x + 3)

    x = jnp.ones((8,))
    # Rank 0 compiles + publishes.
    cc.configure(str(tmp_path / "rank0"))
    cc.set_distributor(CacheDistributor(bus.endpoint(0)))
    cf0 = cc.cached_compile(f, "dist")
    out0 = cf0(x)
    assert cf0.num_compiles == 1
    assert len(bus._cc) == 1
    # Rank 1, empty local cache, pulls instead of compiling.
    cc.reset()
    cc.configure(str(tmp_path / "rank1"))
    cc.set_distributor(CacheDistributor(bus.endpoint(1)))
    hit0 = _counter("mx_compile_cache_hits_total", site="dist",
                    source="remote")
    cf1 = cc.cached_compile(f, "dist")
    out1 = cf1(x)
    assert cf1.num_compiles == 0 and cf1.num_hits == 1
    assert _counter("mx_compile_cache_hits_total", site="dist",
                    source="remote") == hit0 + 1
    assert np.allclose(np.asarray(out0), np.asarray(out1))
    # The pulled entry was committed locally: NEXT start needs no pod.
    cc.set_distributor(None)
    cf2 = cc.cached_compile(f, "dist")
    cf2(x)
    assert cf2.num_compiles == 0 and cf2.num_hits == 1


def test_attach_kvstore_prefetch_warms_joiner_store(tmp_path):
    """Pod prefetch: attach_kvstore runs ONE cc_probe(None) enumeration
    round and commits every missing entry to the joiner's disk store —
    so a later start hits disk with no pod traffic at all."""
    jnp = _jnp()
    bus = LocalBus(num_workers=2)

    def f(x):
        return jnp.sqrt(x + 3)

    def g(x):
        return jnp.cos(x) * 2

    x = jnp.ones((8,))
    # Rank 0 compiles + publishes two entries.
    cc.configure(str(tmp_path / "rank0"))
    cc.set_distributor(CacheDistributor(bus.endpoint(0)))
    cc.cached_compile(f, "pf_a")(x)
    cc.cached_compile(g, "pf_b")(x)
    assert len(bus._cc) == 2
    # cc_probe(None) enumerates every held key in one round.
    assert sorted(bus.cc_probe(None)) == sorted(bus._cc)
    # Rank 1 joins with an EMPTY store: attach prefetches both entries
    # onto disk before any trace happens.
    cc.reset()
    cc.configure(str(tmp_path / "rank1"))
    pre0 = _counter("mx_compile_cache_prefetched_total")
    dist = cc.attach_kvstore(bus.endpoint(1))
    assert dist is not None
    assert _counter("mx_compile_cache_prefetched_total") == pre0 + 2
    assert len(cc.active_store().keys()) == 2
    # Disk-only from here: drop the distributor, both sites still hit.
    cc.set_distributor(None)
    cf = cc.cached_compile(f, "pf_a")
    cf(x)
    assert cf.num_compiles == 0 and cf.num_hits == 1
    # Re-attach is idempotent: everything already local, nothing pulled.
    cc.attach_kvstore(bus.endpoint(1))
    assert _counter("mx_compile_cache_prefetched_total") == pre0 + 2


def test_shared_filesystem_mode_skips_kvstore_channel(tmp_path,
                                                      monkeypatch):
    """MXNET_COMPILE_CACHE_SHARED=1 (every rank's cache dir is one
    shared filesystem): attach_kvstore becomes a no-op — the common
    directory already distributes entries, and pushing them over the
    kvstore would only duplicate bytes."""
    bus = LocalBus(num_workers=2)
    cc.configure(str(tmp_path / "shared"))
    monkeypatch.setenv("MXNET_COMPILE_CACHE_SHARED", "1")
    assert cc.shared_filesystem()
    assert cc.attach_kvstore(bus.endpoint(0)) is None
    assert cc._active_distributor() is None
    jnp = _jnp()
    cf = cc.cached_compile(lambda x: jnp.cos(x) + 1, "shared_site")
    cf(jnp.ones((4,)))
    assert cf.num_compiles == 1
    assert bus._cc == {}, "entry leaked onto the kvstore channel"
    # Without the flag the same call wires a distributor.
    monkeypatch.setenv("MXNET_COMPILE_CACHE_SHARED", "0")
    assert cc.attach_kvstore(bus.endpoint(0)) is not None


def test_shared_directory_serves_two_ranks(tmp_path, monkeypatch):
    """Two 'ranks' (two stores) pointed at ONE directory: rank 0's
    commit is rank 1's local hit — the shared-filesystem distribution
    story, with no kvstore at all. Entries commit atomically, so a
    concurrent double-compile of the same key is just a benign
    double-commit of identical bytes."""
    jnp = _jnp()
    shared = str(tmp_path / "nfs")

    def f(x):
        return jnp.sqrt(x + 7)

    x = jnp.ones((8,))
    cc.configure(shared)
    cf0 = cc.cached_compile(f, "nfs_site")
    out0 = cf0(x)
    assert cf0.num_compiles == 1
    # "Another rank": fresh process-level state, same directory.
    cc.reset()
    cc.configure(shared)
    cf1 = cc.cached_compile(f, "nfs_site")
    out1 = cf1(x)
    assert cf1.num_compiles == 0 and cf1.num_hits == 1
    assert np.allclose(np.asarray(out0), np.asarray(out1))
    # Concurrent same-key commits (the NFS race): both writers go
    # through tmp+rename, the survivor is a valid entry.
    store = cc.active_store()
    key = make_key(["race"])
    import threading

    def put():
        store.put(key, b"payload-bytes", {"site": "race"})

    threads = [threading.Thread(target=put) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    meta, payload = store.get(key)
    assert payload == b"payload-bytes"


def test_distributor_entry_size_bound(tmp_path):
    bus = LocalBus(num_workers=2)
    dist = CacheDistributor(bus.endpoint(0), max_entry_bytes=64)
    assert not dist.publish("k" * 32, {}, b"x" * 100)   # over bound
    assert bus._cc == {}
    assert dist.publish("k" * 32, {}, b"x" * 10)
    assert dist.fetch("k" * 32)[1] == b"x" * 10
    assert dist.fetch("absent") is None


def test_localbus_cc_drop_oldest(monkeypatch):
    bus = LocalBus(num_workers=1)
    monkeypatch.setattr(LocalBus, "MAX_CC_BYTES", 250)
    for i in range(4):
        bus.cc_push("key%d" % i, {}, b"x" * 100)
    assert list(bus._cc) == ["key2", "key3"]    # oldest dropped
    assert bus.cc_probe(["key0", "key3"]) == ["key3"]


# -- the CLI -------------------------------------------------------------------

def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compile_cache_tool_inspect_verify_gc(tmp_path, fault_fs):
    cc.configure(str(tmp_path))
    jnp = _jnp()
    for i in range(3):
        cc.cached_compile(lambda x, i=i: x + i, "tool_site")(
            jnp.ones((4,)))
    tool = _tool("compile_cache")
    info = tool.inspect(str(tmp_path))
    assert info["entries"] == 3
    assert info["by_site"]["tool_site"]["entries"] == 3
    assert info["warm_restart_saves_seconds"] > 0
    # Damage one entry: inspect reports it WITHOUT deleting it (a
    # read-only diagnostic must keep the evidence for verify).
    store = CompileCacheStore(str(tmp_path))
    victim = store.keys()[0]
    fault_fs.corrupt(store.path_for(victim), flip_byte_at=200)
    info = tool.inspect(str(tmp_path))
    assert sum(1 for e in info["detail"] if e["damaged"]) == 1
    assert os.path.exists(store.path_for(victim))
    rep = tool.verify(str(tmp_path))
    assert rep["valid"] == 2 and rep["damaged"] == 1
    assert rep["damaged_keys"] == [victim]
    rep = tool.verify(str(tmp_path), remove=True)
    assert rep["damaged"] == 1
    assert len(store.keys()) == 2
    # GC down to (almost) nothing keeps the newest entry only.
    out = tool.gc(str(tmp_path), max_mb=0)
    assert out["bytes_after"] == 0 and out["removed_entries"] == 2


# -- 2-process acceptance ------------------------------------------------------

_PROG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "compile_cache_prog.py")
_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


def _can_bind_localhost():
    try:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


def test_two_process_rank1_compiles_nothing(tmp_path):
    """ISSUE 11 acceptance: rank 1 starts with an EMPTY local cache,
    pulls rank 0's entries over the kvstore cc channel, and performs
    ZERO local compiles at the shared sites — and the pulled entries
    land on rank 1's own disk for its next restart."""
    if not _can_bind_localhost():
        pytest.skip("localhost sockets unavailable (multi-process "
                    "kvstore needs them)")
    codes = launch_local(2, 1, [sys.executable, _PROG, str(tmp_path)],
                         env_extra=_ENV, timeout=300)
    assert codes == [0, 0], codes
    results = {}
    for rank in (0, 1):
        with open(str(tmp_path / ("result_rank%d.json" % rank))) as f:
            results[rank] = json.load(f)
    # Rank 0 paid the compiles (3 ladder buckets + 1 chunk + 1 step).
    r0 = results[0]["compile_counts"]
    assert r0.get("cached_op", 0) == 3
    assert r0.get("fused_apply", 0) == 1
    assert r0.get("train_step", 0) == 1
    # Rank 1 compiled NOTHING at the shared sites.
    r1 = results[1]["compile_counts"]
    assert r1.get("cached_op", 0) == 0, results[1]
    assert r1.get("fused_apply", 0) == 0, results[1]
    assert r1.get("train_step", 0) == 0, results[1]
    # Every executable was a remote hit (counted), committed to rank
    # 1's own disk: its entry set ends up identical to rank 0's, so
    # rank 1's NEXT restart doesn't even need the pod.
    remote_hits = sum(v for k, v in results[1]["hits"].items()
                      if k.endswith("/remote"))
    assert results[1]["local_entries"] == results[0]["local_entries"]
    assert remote_hits == len(results[1]["local_entries"]) >= 5, \
        results[1]["hits"]

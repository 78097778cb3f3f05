"""The one compile cache (JAX's persistent cache, placed by
`compile.enable_jax_cache()`): a second process start on the same
directory loads every program of every compile seam and returns the
first start's values to the bit; a cache directory that cannot be used
costs a compile, never a result; `compile` exports the cache and the
compile log and nothing else, and the parameter server carries no
executables. Also `CachedOp.pad_to_buckets`, the shape canonicalization
that keeps the bucket ladder's programs few."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, compile as cc, env, gluon, nd
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.compile import buildlog
from mxnet_tpu.gluon import loss as gloss, nn
from mxnet_tpu.kvstore_server import KVStoreServer
from mxnet_tpu.parallel import TrainStep

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROG = os.path.join(_ROOT, "tests", "compile_cache_prog.py")


# -- a second start loads every seam's programs ---------------------------------

@pytest.fixture(scope="module")
def two_starts(tmp_path_factory):
    """compile_cache_prog.py started twice on one cache directory:
    (cold, warm), each {group: {"values", "error", "builds"}}."""
    tmp = tmp_path_factory.mktemp("two_starts")
    env_ = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
                JAX_PLATFORMS="cpu")
    env_.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    runs = []
    for name in ("cold.json", "warm.json"):
        out = str(tmp / name)
        proc = subprocess.run([sys.executable, _PROG, out], env=env_,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        with open(out) as f:
            runs.append(json.load(f))
    assert runs[0]["cache_dir"] == runs[1]["cache_dir"] == str(tmp / "cache")
    return runs[0]["groups"], runs[1]["groups"]


# (seam, the group of compile_cache_prog.py that builds it, what the
# seam's executables are called in the compile log)
_SEAMS = [
    ("cached_op", "cached_op", ("jit(mx_cached_fwd)", "jit(mx_cached_vjp)")),
    ("executor", "executor", ("jit(fn)",)),
    ("fused_apply", "fused", ("jit(mx_fused_sgd)",)),
    ("fused_flatten", "fused", ("jit(mx_flatten_chunk)",)),
    ("train_step", "train_step", ("jit(mx_train_step)",)),
    ("decode_step", "decode", ("jit(step_pure)",)),
    ("decode_place", "decode", ("jit(place_pure)",)),
    ("decode_prefill", "decode", ("jit(prefill_pure)",)),
    ("serving_mesh", "serving_mesh", ("jit(pure)",)),
    ("inference_server", "inference_server", ("jit(mx_cached_fwd)",)),
]


@pytest.mark.parametrize("seam,group,names", _SEAMS,
                         ids=[s[0] for s in _SEAMS])
def test_second_start_loads_the_seams_programs(two_starts, seam, group,
                                               names):
    cold, warm = two_starts[0][group], two_starts[1][group]
    assert cold["error"] is None and warm["error"] is None
    for name in names:
        # the first start compiled the seam's program under its name and
        # stored it; the second built it too, by loading it
        assert [name, "miss"] in cold["builds"], cold["builds"]
        assert [name, "hit"] in warm["builds"], warm["builds"]
    assert warm["builds"] and \
        all(outcome == "hit" for _, outcome in warm["builds"]), warm["builds"]
    assert cold["values"] and warm["values"] == cold["values"]


# -- a cache directory that cannot be used costs a compile ----------------------

_JAX_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                      "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture()
def jax_cache_as_it_was(monkeypatch):
    """JAX's cache options and the variables `enable_jax_cache()` reads,
    unset for the test and as they were after it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = {n: getattr(jax.config, n) for n in _JAX_CACHE_OPTIONS}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    yield monkeypatch
    for n, v in prev.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.fixture()
def place_cache(jax_cache_as_it_was):
    """Point JAX's persistent cache at a directory, through
    `enable_jax_cache()` as an entry point would."""
    from jax.experimental.compilation_cache import compilation_cache

    def place(directory):
        # JAX read the variable when it was imported: set its option too
        jax_cache_as_it_was.setenv("JAX_COMPILATION_CACHE_DIR",
                                   str(directory))
        jax.config.update("jax_compilation_cache_dir", str(directory))
        assert cc.enable_jax_cache() == str(directory)
        compilation_cache.reset_cache()

    return place


def _absent(tmp_path):
    return tmp_path / "never" / "made"


def _unwritable(tmp_path):
    # under a regular file: no process, root's included, can create it
    (tmp_path / "a_file").write_bytes(b"x")
    return tmp_path / "a_file" / "cache"


def _truncated(tmp_path):
    return tmp_path / "cache"


_STATES = [_absent, _unwritable, _truncated]


def _seam_cached_op(tag):
    w = nd.array(np.arange(18, dtype=np.float32).reshape(6, 3))
    x = nd.array(np.arange(12, dtype=np.float32).reshape(2, 6))
    out = CachedOp(lambda w_, x_: nd.dot(x_, w_) + tag, num_params=1) \
        .inference(w, x)
    return out.asnumpy(), x.asnumpy() @ w.asnumpy() + tag, "mx_cached_fwd"


def _seam_train_step(tag):
    mx.random.seed(3)
    net = nn.Dense(4, in_units=8, prefix="cc_dir_%d_" % tag)
    net.initialize(mx.init.Constant(0.01 * tag), force_reinit=True)
    step = TrainStep(net, gloss.L2Loss(), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1})
    x = np.ones((8, 8), np.float32)
    y = np.zeros((8, 4), np.float32)
    loss = np.asarray(step(x, y))
    return loss, np.float32(0.5 * (8 * 0.01 * tag) ** 2), "mx_train_step"


def _seam_fused_apply(tag):
    p = gluon.Parameter("cc_dir_w%d" % tag, shape=(8 + tag,))
    p.initialize(init=mx.init.Constant(1.0))
    trainer = gluon.Trainer([p], "sgd", {"learning_rate": 0.5})
    p.grad()[:] = np.ones(8 + tag, np.float32)
    trainer.step(1)
    return p.data().asnumpy(), np.full(8 + tag, 0.5, np.float32), \
        "mx_fused_sgd"


@pytest.mark.parametrize("seam", [_seam_cached_op, _seam_train_step,
                                  _seam_fused_apply],
                         ids=["cached_op", "train_step", "fused_apply"])
@pytest.mark.parametrize("state", _STATES,
                         ids=["absent", "unwritable", "truncated_entry"])
def test_unusable_cache_directory_costs_a_compile_not_a_result(
        tmp_path, place_cache, state, seam):
    """The cache is never load-bearing: the seam compiles and is
    correct whatever the directory is."""
    tag = _STATES.index(state) + 1             # a program of its own
    directory = state(tmp_path)
    place_cache(directory)
    if state is _truncated:
        got, want, name = seam(tag)            # fills the directory
        np.testing.assert_allclose(got, want, rtol=1e-6)
        entries = [os.path.join(directory, f) for f in os.listdir(directory)
                   if not f.endswith("-atime")]
        assert entries
        for path in entries:
            with open(path, "r+b") as f:
                f.truncate(os.path.getsize(path) // 2)
        place_cache(directory)                 # forget what was read
    buildlog.clear()
    got, want, name = seam(tag)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    builds = [r for r in cc.build_log()
              if r.kind == "build" and name in r.fun_name]
    assert builds and all(r.outcome != "hit" for r in builds), builds
    if state is _absent:
        assert os.listdir(directory)           # made, and written to


# -- what is left, and what is gone ----------------------------------------------

def test_compile_exports_exactly_what_is_kept():
    assert sorted(cc.__all__) == ["build_log", "enable_jax_cache",
                                  "step_done"]
    from mxnet_tpu.compile.buildlog import Record

    assert Record._fields == ("kind", "fun_name", "outcome", "start",
                              "seconds", "step", "inner")
    assert sorted(f[:-3] for f in os.listdir(os.path.dirname(cc.__file__))
                  if f.endswith(".py")) == ["__init__", "buildlog"]


def test_enable_jax_cache_defaults_to_the_checkout(jax_cache_as_it_was):
    """No `JAX_COMPILATION_CACHE_DIR`: `<checkout>/.jax_cache`, set
    through the two JAX options the entry points always set."""
    default = os.path.join(_ROOT, ".jax_cache")
    assert cc.enable_jax_cache() == default
    assert jax.config.jax_compilation_cache_dir == default
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_catalogue_holds_57_knobs():
    assert len(env.CATALOGUE) == 57


@pytest.mark.parametrize("knob", [
    "MXNET_COMPILE_CACHE", "MXNET_COMPILE_CACHE_MB",
    "MXNET_COMPILE_CACHE_SHARED", "MXNET_PS_CC_ENTRY_MB",
    "MXNET_PS_CC_BUFFER_MB"])
def test_the_stores_knob_is_gone(knob):
    assert knob not in {k.name for k in env.CATALOGUE}
    assert knob not in env.describe()


class _Conn:
    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)


@pytest.mark.parametrize("msg", [
    ("cc_push", "k", {}, b"blob", None), ("cc_probe", None, None),
    ("cc_pull", "k", None)], ids=["cc_push", "cc_probe", "cc_pull"])
def test_server_answers_the_stores_commands_as_any_unknown_command(msg):
    server, conn, other = KVStoreServer(num_workers=1), _Conn(), _Conn()
    server._handle(conn, msg)
    server._handle(other, ("no_such_command",))
    assert conn.sent == [("error", "unknown command %r" % (msg[0],))]
    assert other.sent == [("error", "unknown command 'no_such_command'")]


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

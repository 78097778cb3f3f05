"""tools/mxlint — the project-aware static analysis suite.

Three layers:

1. Per-checker fixture tests: each rule fires on a seeded violation,
   stays quiet on the fixed form, and honors a justified suppression.
2. Regression fixtures reproducing real past bug classes (the pre-PR-6
   PrefetchingIter joinless worker; a torn non-atomic state dump — the
   class fixed in PRs 2/5/7/9).
3. ``test_tree_is_clean``: the full suite over ``mxnet_tpu/`` reports
   ZERO findings — every invariant the checkers encode is pinned
   tier-1 from here on.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.mxlint import run_suite  # noqa: E402
from tools.mxlint.core import render_json  # noqa: E402


def lint(tmp_path, source, checks=None, name="mod.py", root=None):
    """Write `source` as one module and run the (selected) suite."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    res = run_suite([str(p)], checks=checks, root=str(root or tmp_path))
    return res


def checks_of(res):
    return [f.check for f in res.findings]


# ---------------------------------------------------------------------------
# lock-blocking / lock-order
# ---------------------------------------------------------------------------

class TestLockBlocking:
    def test_sleep_under_with_lock_fires(self, tmp_path):
        res = lint(tmp_path, """
            import threading, time
            class A:
                def __init__(self):
                    self._lock = threading.Lock()
                def f(self):
                    with self._lock:
                        time.sleep(1)
            """, checks=["lock-blocking"])
        assert checks_of(res) == ["lock-blocking"]

    def test_sleep_outside_lock_quiet(self, tmp_path):
        res = lint(tmp_path, """
            import threading, time
            class A:
                def __init__(self):
                    self._lock = threading.Lock()
                def f(self):
                    with self._lock:
                        x = 1
                    time.sleep(1)
            """, checks=["lock-blocking"])
        assert res.findings == []

    def test_joinless_join_and_queue_get_under_lock(self, tmp_path):
        res = lint(tmp_path, """
            import threading
            class A:
                def __init__(self):
                    self._lock = threading.RLock()
                def f(self, t, q):
                    with self._lock:
                        t.join()
                        q.get()
            """, checks=["lock-blocking"])
        assert checks_of(res) == ["lock-blocking", "lock-blocking"]

    def test_bounded_waits_quiet(self, tmp_path):
        # timeout'd join/get and block=False are bounded — no finding.
        res = lint(tmp_path, """
            import threading
            class A:
                def __init__(self):
                    self._lock = threading.Lock()
                def f(self, t, q):
                    with self._lock:
                        t.join(timeout=5)
                        q.get(timeout=1)
                        q.get(block=False)
            """, checks=["lock-blocking"])
        assert res.findings == []

    def test_nested_def_resets_held_set(self, tmp_path):
        # A closure *defined* under the lock runs later, lock-free.
        res = lint(tmp_path, """
            import threading, time
            class A:
                def __init__(self):
                    self._lock = threading.Lock()
                def f(self):
                    with self._lock:
                        def worker():
                            time.sleep(1)
                        return worker
            """, checks=["lock-blocking"])
        assert res.findings == []

    def test_block_until_ready_and_subprocess(self, tmp_path):
        res = lint(tmp_path, """
            import subprocess, threading
            _lock = threading.Lock()
            def f(x):
                with _lock:
                    x.block_until_ready()
                    subprocess.run(["ls"])          # unbounded: fires
                    subprocess.run(["ls"], timeout=5)  # bounded: quiet
            """, checks=["lock-blocking"])
        assert checks_of(res) == ["lock-blocking", "lock-blocking"]

    def test_lock_order_inversion_across_functions(self, tmp_path):
        res = lint(tmp_path, """
            import threading
            class A:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._b:
                        with self._a:
                            pass
            """, checks=["lock-order"])
        # Both sites of the inversion are flagged.
        assert checks_of(res) == ["lock-order", "lock-order"]

    def test_lock_order_is_per_module(self, tmp_path):
        # 'self._a'/'self._b' in two different files are UNRELATED
        # locks — no cross-module pairing on bare attribute names.
        (tmp_path / "m1.py").write_text(textwrap.dedent("""
            import threading
            class A:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
            """))
        (tmp_path / "m2.py").write_text(textwrap.dedent("""
            import threading
            class Unrelated:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def g(self):
                    with self._b:
                        with self._a:
                            pass
            """))
        res = run_suite([str(tmp_path)], checks=["lock-order"],
                        root=str(tmp_path))
        assert res.findings == []

    def test_consistent_order_quiet(self, tmp_path):
        res = lint(tmp_path, """
            import threading
            class A:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._a:
                        with self._b:
                            pass
            """, checks=["lock-order"])
        assert res.findings == []

    def test_suppression_with_justification(self, tmp_path):
        res = lint(tmp_path, """
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    time.sleep(1)  # mxlint: disable=lock-blocking -- test fixture
            """, checks=["lock-blocking"])
        assert res.findings == [] and res.suppressed == 1


# ---------------------------------------------------------------------------
# signal-safety
# ---------------------------------------------------------------------------

class TestSignalSafety:
    def test_logging_in_handler_fires(self, tmp_path):
        res = lint(tmp_path, """
            import logging, signal
            log = logging.getLogger(__name__)
            def handler(signum, frame):
                log.warning("caught %d", signum)
            def install():
                signal.signal(signal.SIGTERM, handler)
            """, checks=["signal-safety"])
        assert checks_of(res) == ["signal-safety"]

    def test_transitive_reachability(self, tmp_path):
        # Violation two hops away via self.method chains still found.
        res = lint(tmp_path, """
            import signal, threading
            class H:
                def install(self):
                    signal.signal(signal.SIGTERM, self._handler)
                def _handler(self, signum, frame):
                    self._helper()
                def _helper(self):
                    self._deep()
                def _deep(self):
                    open("/tmp/x", "r")
            """, checks=["signal-safety"])
        assert checks_of(res) == ["signal-safety"]

    def test_os_write_pattern_quiet(self, tmp_path):
        # The sanctioned handler vocabulary (os.write, flag sets).
        res = lint(tmp_path, """
            import os, signal
            class H:
                def install(self):
                    signal.signal(signal.SIGTERM, self._handler)
                def _handler(self, signum, frame):
                    self.fired = True
                    os.write(2, b"preempted\\n")
            """, checks=["signal-safety"])
        assert res.findings == []

    def test_module_level_registration_checked(self, tmp_path):
        # The most common registration shape: signal.signal at module
        # level (no enclosing def) — the handler is still checked.
        res = lint(tmp_path, """
            import logging, signal
            log = logging.getLogger(__name__)
            def handler(signum, frame):
                log.warning("caught %d", signum)
            signal.signal(signal.SIGTERM, handler)
            """, checks=["signal-safety"])
        assert checks_of(res) == ["signal-safety"]

    def test_same_code_unregistered_quiet(self, tmp_path):
        # Identical body NOT registered as a handler: no findings.
        res = lint(tmp_path, """
            import logging
            log = logging.getLogger(__name__)
            def handler(signum, frame):
                log.warning("caught %d", signum)
            """, checks=["signal-safety"])
        assert res.findings == []


# ---------------------------------------------------------------------------
# atomic-write
# ---------------------------------------------------------------------------

class TestAtomicWrite:
    def test_write_mode_open_fires(self, tmp_path):
        res = lint(tmp_path, """
            def save(path, blob):
                with open(path, "wb") as f:
                    f.write(blob)
            """, checks=["atomic-write"])
        assert checks_of(res) == ["atomic-write"]

    def test_read_mode_quiet(self, tmp_path):
        res = lint(tmp_path, """
            def load(path):
                with open(path, "rb") as f:
                    return f.read()
            def load2(path):
                return open(path).read()
            """, checks=["atomic-write"])
        assert res.findings == []

    def test_append_and_plus_modes_fire(self, tmp_path):
        res = lint(tmp_path, """
            def f(path):
                a = open(path, "ab")
                b = open(path, "r+")
            """, checks=["atomic-write"])
        assert len(res.findings) == 2

    def test_sanctioned_seam_quiet(self, tmp_path):
        # Same code, but inside the real seam file+function: allowed.
        d = tmp_path / "mxnet_tpu" / "checkpoint"
        d.mkdir(parents=True)
        (tmp_path / "mxnet_tpu" / "env.py").write_text("CATALOGUE = []\n")
        (d / "manager.py").write_text(textwrap.dedent("""
            def _open_for_write(path):
                return open(path, "wb")
            """))
        res = run_suite([str(d / "manager.py")], checks=["atomic-write"],
                        root=str(tmp_path))
        assert res.findings == []

    def test_regression_torn_state_dump(self, tmp_path):
        # The bug class fixed in PRs 2/5/7/9 and again this PR
        # (kvstore_dist.save_optimizer_states): pickle straight into
        # the destination — a crash mid-dump leaves a torn file that
        # unpickles as garbage at restore.
        res = lint(tmp_path, """
            import pickle
            def save_optimizer_states(fname, blobs):
                with open(fname, "wb") as f:
                    pickle.dump(blobs, f)
            """, checks=["atomic-write"])
        assert checks_of(res) == ["atomic-write"]


# ---------------------------------------------------------------------------
# env-knob
# ---------------------------------------------------------------------------

@pytest.fixture
def knob_project(tmp_path):
    """Mini project: env.py declaring one knob, README documenting it."""
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "env.py").write_text(textwrap.dedent("""
        from collections import namedtuple
        Knob = namedtuple("Knob", "name typ default where doc subsumed")
        CATALOGUE = [
            Knob("MXNET_DECLARED", int, 1, "x.py", "a knob", False),
            Knob("MXNET_UNDOCUMENTED", int, 1, "x.py", "hidden", False),
        ]
        """))
    (tmp_path / "README.md").write_text("| `MXNET_DECLARED` | a knob |\n")
    return tmp_path


@pytest.fixture()
def stale_project(tmp_path):
    """Mini project for the stale-knob rule: env.py declares a read
    knob, a dead knob, and a subsumed knob; x.py reads only the first."""
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "env.py").write_text(textwrap.dedent("""
        from collections import namedtuple
        Knob = namedtuple("Knob", "name typ default where doc subsumed")
        CATALOGUE = [
            Knob("MXNET_LIVE", int, 1, "x.py", "still read", False),
            Knob("MXNET_DEAD", int, 1, "gone.py", "refactored", False),
            Knob("MXNET_INERT", int, 1, "(subsumed)", "PJRT owns it",
                 True),
        ]
        """))
    (pkg / "x.py").write_text(textwrap.dedent("""
        import os
        v = os.environ.get("MXNET_LIVE", "1")
        """))
    (tmp_path / "README.md").write_text(
        "| `MXNET_LIVE` | x | `MXNET_DEAD` | x | `MXNET_INERT` | x |\n")
    return tmp_path


class TestStaleKnob:
    def test_dead_knob_fires_subsumed_exempt(self, stale_project):
        res = run_suite([str(stale_project / "mxnet_tpu")],
                        checks=["stale-knob"], root=str(stale_project))
        assert checks_of(res) == ["stale-knob"]
        assert "MXNET_DEAD" in res.findings[0].message
        assert res.findings[0].path == "mxnet_tpu/env.py"

    def test_read_anywhere_in_tree_counts(self, stale_project):
        # a knob read only by a driver under tools/ is NOT stale — the
        # scan covers the whole project regardless of the run's paths
        tools = stale_project / "tools"
        tools.mkdir()
        (tools / "drv.py").write_text(textwrap.dedent("""
            import os
            v = os.environ.get("MXNET_DEAD")
            """))
        res = run_suite([str(stale_project / "mxnet_tpu")],
                        checks=["stale-knob"], root=str(stale_project))
        assert res.findings == []

    def test_justified_suppression_on_knob_line(self, stale_project):
        env_py = stale_project / "mxnet_tpu" / "env.py"
        src = env_py.read_text().replace(
            '"refactored", False),',
            '"refactored", False),  '
            '# mxlint: disable=stale-knob -- forward declaration')
        env_py.write_text(src)
        res = run_suite([str(stale_project / "mxnet_tpu")],
                        checks=["stale-knob"], root=str(stale_project))
        assert res.findings == []
        assert res.suppressed == 1

    def test_suppression_honored_outside_scanned_paths(self, stale_project):
        """Cross-module findings anchor to env.py even when env.py is
        NOT among the linted paths — its justified suppressions must
        still apply (run() parses the anchor file on demand)."""
        env_py = stale_project / "mxnet_tpu" / "env.py"
        src = env_py.read_text().replace(
            '"refactored", False),',
            '"refactored", False),  '
            '# mxlint: disable=stale-knob -- forward declaration')
        env_py.write_text(src)
        tools = stale_project / "tools"
        tools.mkdir()
        (tools / "t.py").write_text("x = 1\n")
        res = run_suite([str(tools)], checks=["stale-knob"],
                        root=str(stale_project))
        assert res.findings == []
        assert res.suppressed == 1


class TestEnvKnob:
    def test_undeclared_read_fires(self, knob_project):
        res = lint(knob_project, """
            import os
            x = os.environ.get("MXNET_NOT_DECLARED", "0")
            """, checks=["env-knob"], root=knob_project)
        assert checks_of(res) == ["env-knob"]
        assert "MXNET_NOT_DECLARED" in res.findings[0].message

    def test_declared_read_quiet(self, knob_project):
        res = lint(knob_project, """
            import os
            x = os.environ.get("MXNET_DECLARED", "0")
            y = os.environ["MXNET_DECLARED"]
            z = os.getenv("MXNET_DECLARED")
            """, checks=["env-knob"], root=knob_project)
        assert res.findings == []

    def test_typo_is_caught(self, knob_project):
        # The motivating failure: a typo silently reads its default.
        res = lint(knob_project, """
            import os
            x = os.environ.get("MXNET_DECLRED", "0")
            """, checks=["env-knob"], root=knob_project)
        assert len(res.findings) == 1

    def test_catalogue_entry_missing_from_readme(self, knob_project):
        env_py = knob_project / "mxnet_tpu" / "env.py"
        res = run_suite([str(env_py)], checks=["env-knob"],
                        root=str(knob_project))
        assert len(res.findings) == 1
        assert "MXNET_UNDOCUMENTED" in res.findings[0].message

    def test_dynamic_read_out_of_scope(self, knob_project):
        res = lint(knob_project, """
            import os
            def probe(name):
                return os.environ.get(name)
            """, checks=["env-knob"], root=knob_project)
        assert res.findings == []


# ---------------------------------------------------------------------------
# thread-lifecycle
# ---------------------------------------------------------------------------

class TestThreadLifecycle:
    def test_regression_pre_pr6_prefetching_iter(self, tmp_path):
        # The real pre-PR-6 shape: non-daemon workers started with no
        # join path — wedged interpreter at exit, swallowed errors.
        res = lint(tmp_path, """
            import threading
            class PrefetchingIter:
                def __init__(self, n):
                    self.threads = []
                    for i in range(n):
                        t = threading.Thread(target=self._worker)
                        t.start()
                        self.threads.append(t)
                def _worker(self):
                    pass
            """, checks=["thread-lifecycle"])
        assert checks_of(res) == ["thread-lifecycle"]

    def test_daemon_kwarg_quiet(self, tmp_path):
        res = lint(tmp_path, """
            import threading
            threading.Thread(target=print, daemon=True).start()
            """, checks=["thread-lifecycle"])
        assert res.findings == []

    def test_daemon_attr_quiet(self, tmp_path):
        res = lint(tmp_path, """
            import threading
            def go():
                t = threading.Thread(target=print)
                t.daemon = True
                t.start()
            """, checks=["thread-lifecycle"])
        assert res.findings == []

    def test_join_path_quiet(self, tmp_path):
        res = lint(tmp_path, """
            import threading
            class W:
                def start(self):
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()
                def close(self):
                    self._thread.join(timeout=5)
                def _run(self):
                    pass
            """, checks=["thread-lifecycle"])
        assert res.findings == []


# ---------------------------------------------------------------------------
# telemetry-naming
# ---------------------------------------------------------------------------

class TestTelemetryNaming:
    def test_bad_family_prefix_fires(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import metrics
            c = metrics.REGISTRY.counter("train_steps_total", "steps")
            """, checks=["telemetry-naming"])
        assert checks_of(res) == ["telemetry-naming"]

    def test_good_family_quiet(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import metrics
            c = metrics.REGISTRY.counter("mx_train_steps_total", "steps")
            """, checks=["telemetry-naming"])
        assert res.findings == []

    def test_bare_span_name_fires(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import trace
            def step():
                with trace.span("step"):
                    pass
            """, checks=["telemetry-naming"])
        assert checks_of(res) == ["telemetry-naming"]

    def test_span_format_template_followed(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import trace
            def f(i):
                with trace.span("serving::bucket_%d" % i):
                    pass
                with trace.span("bucket_%d" % i):
                    pass
            """, checks=["telemetry-naming"])
        assert len(res.findings) == 1

    def test_conflicting_label_sets_fire(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import metrics
            a = metrics.REGISTRY.counter("mx_foo_total", "x", labels=("site",))
            b = metrics.REGISTRY.counter("mx_foo_total", "x", labels=("rank",))
            """, checks=["telemetry-naming"])
        assert checks_of(res) == ["telemetry-naming"]
        assert "label" in res.findings[0].message

    def test_omitted_labels_is_empty_label_set(self, tmp_path):
        # The real API defaults labels=(): omitting it still conflicts
        # with a labeled registration of the same family.
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import metrics
            a = metrics.REGISTRY.counter("mx_foo_total", "x")
            b = metrics.REGISTRY.counter("mx_foo_total", "x", labels=("rank",))
            """, checks=["telemetry-naming"])
        assert checks_of(res) == ["telemetry-naming"]

    def test_same_label_set_quiet(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import metrics
            a = metrics.REGISTRY.counter("mx_foo_total", "x", labels=("site",))
            b = metrics.REGISTRY.counter("mx_foo_total", "x", labels=("site",))
            """, checks=["telemetry-naming"])
        assert res.findings == []


# ---------------------------------------------------------------------------
# trace-propagation
# ---------------------------------------------------------------------------

class TestTracePropagation:
    def test_payload_without_ctx_fires(self, tmp_path):
        res = lint(tmp_path, """
            class KV:
                def push(self, key, value):
                    self._post(0, ("push", key, value))
            """, checks=["trace-propagation"])
        assert checks_of(res) == ["trace-propagation"]
        assert "push" in res.findings[0].message

    def test_inject_call_quiet(self, tmp_path):
        res = lint(tmp_path, """
            from mxnet_tpu.telemetry import xtrace as _xtrace
            class KV:
                def push(self, key, value):
                    self._post(0, ("push", key, value, _xtrace.inject()))
                def pull(self, key):
                    return self._call(0, ("pull", key, _xtrace.inject()))
            """, checks=["trace-propagation"])
        assert res.findings == []

    def test_forwarded_ctx_name_quiet(self, tmp_path):
        # Re-sending an already-extracted wire context (the server's
        # pull-reply echo shape) counts as carrying one.
        res = lint(tmp_path, """
            class KV:
                def forward(self, key, value, wire_ctx):
                    self._post(0, ("push_rsp", key, value, wire_ctx))
                def echo(self, state):
                    self._post(0, ("val", state.value, state.applied_ctx))
            """, checks=["trace-propagation"])
        assert res.findings == []

    def test_call_without_ctx_fires(self, tmp_path):
        res = lint(tmp_path, """
            class KV:
                def pull(self, key):
                    return self._call(0, ("pull", key))
            """, checks=["trace-propagation"])
        assert checks_of(res) == ["trace-propagation"]

    def test_opaque_payload_quiet(self, tmp_path):
        # A payload built elsewhere and passed by name is opaque — the
        # build site is where the tuple literal (and a finding) lives.
        res = lint(tmp_path, """
            class KV:
                def send(self, msg):
                    self._post(0, msg)
                def splice(self, head, rest):
                    self._post(0, ("cmd", *rest))
            """, checks=["trace-propagation"])
        assert res.findings == []

    def test_non_command_tuple_quiet(self, tmp_path):
        # Only command tuples (string head) are framing; a bare data
        # tuple is not a payload this rule owns.
        res = lint(tmp_path, """
            class KV:
                def send(self, a, b):
                    self._post(0, (a, b))
            """, checks=["trace-propagation"])
        assert res.findings == []

    def test_justified_suppression_honored(self, tmp_path):
        res = lint(tmp_path, """
            class KV:
                def ping(self):
                    # mxlint: disable=trace-propagation -- liveness
                    # probe, never part of a causal chain
                    self._post(0, ("ping",))
            """, checks=["trace-propagation"])
        assert res.findings == [] and res.suppressed == 1


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

class TestRetraceHazard:
    def test_if_on_traced_arg_fires(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            def step(state, tokens):
                if tokens > 0:
                    return state + 1
                return state
            _step = jax.jit(step)
            """, checks=["retrace-hazard"])
        assert checks_of(res) == ["retrace-hazard"]
        assert "'tokens'" in res.findings[0].message

    def test_nested_closure_target_fires(self, tmp_path):
        # The dominant repo idiom: the pure fn is a closure built in
        # __init__ and handed to the jit seam by name.
        res = lint(tmp_path, """
            import jax
            class Backend:
                def __init__(self, cfg):
                    def step_pure(params, x):
                        if x.sum() > 0:
                            return x * params
                        return x
                    self._step = jax.jit(step_pure)
            """, checks=["retrace-hazard"])
        assert checks_of(res) == ["retrace-hazard"]

    def test_safe_predicates_quiet(self, tmp_path):
        # is-None pytree dispatch, isinstance/len, and static metadata
        # attributes are part of the trace SIGNATURE, not traced values.
        res = lint(tmp_path, """
            import jax
            def step(state, x, aux):
                if aux is None:
                    x = x + 1
                if isinstance(state, tuple) and len(state) > 1:
                    x = x * 2
                if x.ndim == 2 and x.shape[0] > 4:
                    x = x.sum(axis=0)
                if x.dtype == "float32" and not x.weak_type:
                    x = x * 3
                return state, x
            _f = jax.jit(step)
            """, checks=["retrace-hazard"])
        assert res.findings == []

    def test_static_argnames_exempt(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            def step(x, mode):
                if mode == "train":
                    return x * 2
                return x
            _f = jax.jit(step, static_argnames=("mode",))
            """, checks=["retrace-hazard"])
        assert res.findings == []

    def test_jit_decorator_fires(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            from functools import partial

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x

            @partial(jax.jit, static_argnums=(1,))
            def g(x, n):
                if n > 2:       # static by contract: quiet
                    x = x + 1
                return x
            """, checks=["retrace-hazard"])
        assert checks_of(res) == ["retrace-hazard"]
        assert res.findings[0].message.count("'x'") == 1

    def test_closure_and_free_names_quiet(self, tmp_path):
        # Branching on config captured by closure (not a traced arg)
        # is trace-time specialization by design.
        res = lint(tmp_path, """
            import jax
            def build(cfg):
                def step(state, x):
                    if cfg.single_state:
                        return state + x
                    return tuple(s + x for s in state)
                return jax.jit(step)
            """, checks=["retrace-hazard"])
        assert res.findings == []

    def test_justified_suppression_honored(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            def step(x):
                # mxlint: disable=retrace-hazard -- x is always a
                # concrete host scalar at this seam, two traces total
                if x > 0:
                    return x
                return -x
            _f = jax.jit(step)
            """, checks=["retrace-hazard"])
        assert res.findings == [] and res.suppressed == 1


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_unjustified_suppression_is_a_finding(self, tmp_path):
        res = lint(tmp_path, """
            def save(path, blob):
                f = open(path, "wb")  # mxlint: disable=atomic-write
            """, checks=["atomic-write"])
        assert checks_of(res) == ["bad-suppression"]

    def test_next_line_comment_form(self, tmp_path):
        res = lint(tmp_path, """
            def save(path, blob):
                # mxlint: disable=atomic-write -- streaming writer,
                # append semantics are the API
                f = open(path, "wb")
            """, checks=["atomic-write"])
        assert res.findings == [] and res.suppressed == 1

    def test_wrong_check_name_does_not_suppress(self, tmp_path):
        res = lint(tmp_path, """
            def save(path, blob):
                f = open(path, "wb")  # mxlint: disable=lock-blocking -- nope
            """, checks=["atomic-write"])
        assert checks_of(res) == ["atomic-write"]

    def test_stacked_suppression_comments_merge(self, tmp_path):
        # Two whole-line disables for the same next code line: both
        # apply (neither silently shadows the other).
        res = lint(tmp_path, """
            import threading, time
            _lock = threading.Lock()
            def f(path):
                with _lock:
                    # mxlint: disable=lock-blocking -- fixture
                    # mxlint: disable=atomic-write -- fixture
                    open(path, "wb") and time.sleep(1)
            """, checks=["atomic-write", "lock-blocking"])
        assert res.findings == [] and res.suppressed == 2


# ---------------------------------------------------------------------------
# CLI + tree gate
# ---------------------------------------------------------------------------

class TestCli:
    def _run(self, *args, cwd=REPO):
        return subprocess.run(
            [sys.executable, "-m", "tools.mxlint", *args],
            cwd=cwd, capture_output=True, text=True, timeout=120)

    def test_json_output_stable_and_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text('f = open("x", "wb")\n')
        proc = self._run("--format=json", str(bad))
        assert proc.returncode == 1
        out = json.loads(proc.stdout)
        assert out["version"] == 1
        assert out["counts"] == {"atomic-write": 1}
        assert [f["check"] for f in out["findings"]] == ["atomic-write"]
        # Byte-stable across runs (bench --compare-style diffing).
        assert proc.stdout == self._run("--format=json", str(bad)).stdout

    def test_check_subset_and_unknown_check(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text('f = open("x", "wb")\n')
        assert self._run("--check=thread-lifecycle",
                         str(bad)).returncode == 0
        assert self._run("--check=nonsense", str(bad)).returncode == 2

    def test_check_subset_filters_secondary_kinds(self, tmp_path):
        # --check=lock-blocking must not report lock-order findings.
        p = tmp_path / "inv.py"
        p.write_text(textwrap.dedent("""
            import threading
            class A:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._b:
                        with self._a:
                            pass
            """))
        res = run_suite([str(p)], checks=["lock-blocking"],
                        root=str(tmp_path))
        assert res.findings == []

    def test_zero_files_is_loud(self, tmp_path):
        # A clean report that analyzed nothing must not exit 0 (wrong
        # cwd would otherwise green-light CI forever).
        empty = tmp_path / "empty"
        empty.mkdir()
        proc = self._run(str(empty))
        assert proc.returncode == 2
        assert "no .py files" in proc.stderr

    def test_relative_project_root_still_checks_catalogue(self, tmp_path):
        # A RELATIVE --project-root must not silently skip the env.py
        # catalogue-vs-README check (abspath normalization): seed an
        # undocumented knob and demand the finding surfaces.
        pkg = tmp_path / "mxnet_tpu"
        pkg.mkdir()
        (pkg / "env.py").write_text(textwrap.dedent("""
            from collections import namedtuple
            Knob = namedtuple("Knob", "name typ default where doc subsumed")
            CATALOGUE = [Knob("MXNET_HIDDEN", int, 1, "x", "d", False)]
            """))
        # One unrelated knob token: an entirely token-free README reads
        # as "no env table yet" and skips the check by design.
        (tmp_path / "README.md").write_text("| `MXNET_OTHER` | x |\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.mxlint", "--project-root=.",
             "mxnet_tpu/env.py"],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": REPO})
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "MXNET_HIDDEN" in proc.stdout

    def test_syntax_error_reported_not_crash(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n")
        proc = self._run(str(bad))
        assert proc.returncode == 1
        assert "parse-error" in proc.stdout


def test_render_json_sorted(tmp_path):
    (tmp_path / "b.py").write_text('f = open("x", "wb")\n')
    (tmp_path / "a.py").write_text('g = open("y", "wb")\n')
    res = run_suite([str(tmp_path)], checks=["atomic-write"],
                    root=str(tmp_path))
    paths = [f.path for f in res.findings]
    assert paths == sorted(paths)
    json.loads(render_json(res))  # valid JSON


# ---------------------------------------------------------------------------
# stale-suppression
# ---------------------------------------------------------------------------

class TestStaleSuppression:
    def _lint(self, tmp_path, source):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(source))
        return run_suite([str(p)], checks=["stale-suppression"],
                         root=str(tmp_path))

    def test_dead_symbol_fires(self, tmp_path):
        res = self._lint(tmp_path, """
            f = open("x", "wb")  # mxlint: disable=atomic-write -- safe: GhostWriter re-frames on read
            """)
        assert checks_of(res) == ["stale-suppression"]
        assert "GhostWriter" in res.findings[0].message
        assert res.findings[0].line == 2

    def test_live_symbol_quiet(self, tmp_path):
        res = self._lint(tmp_path, """
            class FrameWriter:
                pass
            f = open("x", "wb")  # mxlint: disable=atomic-write -- safe: FrameWriter re-frames on read
            """)
        assert res.findings == []

    def test_prose_only_justification_quiet(self, tmp_path):
        # No concrete references => nothing to audit. This rule grades
        # reference freshness, not writing style.
        res = self._lint(tmp_path, """
            f = open("x", "wb")  # mxlint: disable=atomic-write -- a barrier blocks by definition
            """)
        assert res.findings == []

    def test_dead_file_path_fires(self, tmp_path):
        res = self._lint(tmp_path, """
            f = open("x", "wb")  # mxlint: disable=atomic-write -- tools/vanished_helper.py tails this
            """)
        assert checks_of(res) == ["stale-suppression"]
        assert "tools/vanished_helper.py" in res.findings[0].message

    def test_live_file_path_quiet(self, tmp_path):
        tools = tmp_path / "tools"
        tools.mkdir()
        (tools / "tailer.py").write_text("pass\n")
        res = self._lint(tmp_path, """
            f = open("x", "wb")  # mxlint: disable=atomic-write -- tools/tailer.py tails this
            """)
        assert res.findings == []

    def test_continuation_comment_lines_are_part_of_the_why(self, tmp_path):
        # The justification spans comment-only follow-on lines (that's
        # how multi-line whys are written in-tree); a live reference on
        # a continuation line keeps the suppression fresh.
        res = self._lint(tmp_path, """
            def framed_append():
                pass
            # mxlint: disable=atomic-write -- incremental append is
            # the API: framed_append() recovers torn tails on read
            f = open("x", "wb")
            """)
        assert res.findings == []

    def test_one_live_reference_keeps_it_alive(self, tmp_path):
        # none-resolve rule: prose words that merely look like symbols
        # must not flag a justification that still cites something real.
        res = self._lint(tmp_path, """
            class FrameWriter:
                pass
            f = open("x", "wb")  # mxlint: disable=atomic-write -- FrameWriter took over from OldGhostPath
            """)
        assert res.findings == []

    def test_dead_knob_reference_fires(self, tmp_path):
        pkg = tmp_path / "mxnet_tpu"
        pkg.mkdir()
        (pkg / "env.py").write_text(textwrap.dedent("""
            from collections import namedtuple
            Knob = namedtuple("Knob", "name typ default where doc subsumed")
            CATALOGUE = [
                Knob("MXNET_LIVE_KNOB", int, 1, "x.py", "a knob", False),
            ]
            """))
        res = self._lint(tmp_path, """
            f = open("x", "wb")  # mxlint: disable=atomic-write -- MXNET_VANISHED_KNOB gates this path
            """)
        assert checks_of(res) == ["stale-suppression"]
        assert "MXNET_VANISHED_KNOB" in res.findings[0].message


def test_tree_is_clean():
    """The tier-1 gate: the full suite over mxnet_tpu/ is ZERO findings.

    A finding here is a real invariant violation (or a new intentional
    pattern needing a justified `# mxlint: disable=<check> -- why`
    suppression) — run `python -m tools.mxlint mxnet_tpu/` for the
    annotated report.
    """
    res = run_suite([os.path.join(REPO, "mxnet_tpu")], root=REPO)
    msgs = ["%s:%d: [%s] %s" % (f.path, f.line, f.check, f.message)
            for f in res.findings]
    assert not msgs, "mxlint findings on the tree:\n" + "\n".join(msgs)
    assert not res.errors, res.errors
    assert res.files > 150  # the walk actually covered the tree

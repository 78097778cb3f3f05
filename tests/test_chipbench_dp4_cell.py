"""The four-chip cell `resnet50-train-dp4-b1024`: its workload file is in
place and BENCHMARK.json does not declare it yet (PERF.md section 7, row 1:
the bf16 reference check fails it about one run in seven on any tree), so
the command refuses it; and one traced and one untraced run of it on four
of the CPU's virtual devices at the tiny sizes of tests/chipbench_tests
(whose `root` fixture declares every workload file it finds, and whose
`_run` is used as it is: that directory belongs to the benchmark and gets
no new code)."""
import importlib.util
import json
import os

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chipbench_harness_cpu",
    os.path.join(_ROOT, "tests", "chipbench_tests", "test_harness_cpu.py"))
cpu = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cpu)
root = cpu.root          # the fixture: a tiny copy of the benchmark
harness = cpu.harness
# The fixture shrinks every declared configuration from the module's own
# table; the sizes of those declared after it was written are kept in
# that directory's conftest.py, which pytest applies only there.
_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_conftest",
    os.path.join(_ROOT, "tests", "chipbench_tests", "conftest.py"))
_later = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_later)
cpu._TINY_CFG.setdefault("kanana2_30b_a3b", _later.TINY_KANANA)

CELL, ONE_CHIP = "resnet50-train-dp4-b1024", "resnet50-train-b256"


def test_kept_beside_the_benchmark_as_cell_one_over_four_chips():
    bench = harness.load_bench(_ROOT)
    with open(os.path.join(_ROOT, "chipbench", "workloads",
                           CELL + ".json")) as f:
        wl = json.load(f)
    _, one, _ = harness.cell_files(_ROOT, bench, ONE_CHIP)
    # Not declared: a benchmark issue adds the one entry once the check
    # can hold it, and until then the command says so and runs nothing.
    assert CELL not in [c["name"] for c in bench["workloads"]]
    with pytest.raises(harness.BenchError, match="no cell"):
        harness.cell_files(_ROOT, bench, CELL)
    assert wl["chips"] == 4 and wl["traffic"] == "train-dp4-b1024"
    assert 0 < len(wl["why"]) <= 200
    # What a chip sees is cell 1: its batch, type, pool and read cadence.
    assert wl["batch"] == 4 * one["batch"]
    for key in ("config", "runner", "dtype", "pool_batches", "read_every",
                "trace_steps"):
        assert wl[key] == one[key], key
    # Declared, it would report every per-layer metric that lists no
    # cells: each has its file.
    for metric in bench["per_layer"]:
        if harness.applies(metric, CELL):
            assert os.path.exists(os.path.join(
                _ROOT, "chipbench", "layer_metrics",
                metric["name"] + ".json")), metric["name"]


@pytest.mark.parametrize("trace", [0, 1])
def test_runs_on_four_virtual_devices(root, trace):
    result, lines = cpu._run(root, CELL, trace=trace,
                             devices=jax.devices()[:4])
    assert result["correct"] is True, lines[-2:]
    assert result["failed"] == 0
    got = set(result["metrics"])
    if trace:
        assert {"import_s.setup", "first_step_s.setup",
                "trace_lower_s.setup"} <= got
        # The three host-span metrics keep their lists: cells 1 and 2.
        assert not got & {"host_step_ms.train", "data_put_ms.train",
                          "dispatch_ms.train"}
    else:
        assert got == {"train_rate", "setup_s"}
    json.dumps(result)

"""Goodput ledger (ISSUE 20): MECE wall-clock accounting, durability
across SIGKILL, rank-0 fleet aggregation, and the read surfaces
(/debug/goodput, flight-recorder bundles, tools/goodput_report.py)
all rendering the same ledger."""
import json
import os
import socket
import subprocess
import sys

import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import aggregate, goodput
from mxnet_tpu.telemetry import metrics as tmetrics

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
from launch import launch_local  # noqa: E402


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _ledger(tmp_path=None, **kw):
    kw.setdefault("registry", tmetrics.Registry())
    kw.setdefault("interval_s", 0.0)
    return goodput.GoodputLedger(
        directory=str(tmp_path) if tmp_path is not None else None,
        rank=kw.pop("rank", 0), **kw)


# -- categories + closure -----------------------------------------------------

def test_direct_mode_books_steps_and_derives_idle():
    clock = _FakeClock()
    led = _ledger(clock=clock)
    for i in range(4):
        clock.t += 1.0
        led.observe_step(i, seconds=1.0)
    led.book("checkpoint", 0.5)
    clock.t += 1.0                    # 0.5 checkpointing + 0.5 nothing
    snap = led.snapshot(serving=False)
    assert snap["wall_s"] == pytest.approx(5.0)
    assert snap["categories"]["device_compute"] == pytest.approx(4.0)
    assert snap["categories"]["checkpoint"] == pytest.approx(0.5)
    assert snap["categories"]["idle"] == pytest.approx(0.5)
    assert snap["goodput_ratio"] == pytest.approx(0.8)
    assert snap["closure_pct"] == 0.0 and snap["closure_ok"]
    # MECE: categories sum exactly to wall (idle is the derived rest)
    assert sum(snap["categories"].values()) == pytest.approx(
        snap["wall_s"])


def test_closure_detects_overcount_only():
    clock = _FakeClock()
    led = _ledger(clock=clock)
    clock.t += 1.0
    led.book("compile", 1.5)          # overcounts wall by 0.5s
    snap = led.snapshot(serving=False)
    assert snap["categories"]["idle"] == 0.0   # clamped, never negative
    assert snap["closure_pct"] == pytest.approx(50.0)
    assert not snap["closure_ok"]


def test_book_rejects_idle_and_unknown():
    led = _ledger()
    with pytest.raises(ValueError):
        led.book("idle", 1.0)         # derived — booking it would hide
    with pytest.raises(ValueError):   # double-counting
        led.book("naps", 1.0)


# -- attribution-mode folding -------------------------------------------------

class _StubAttr:
    def update(self):
        pass


def test_fold_maps_phases_and_deoverlaps_compile():
    clock = _FakeClock()
    reg = tmetrics.Registry()
    phase = reg.counter("mx_step_phase_seconds",
                        "per-phase step seconds", labels=("phase",))
    compile_h = reg.histogram("mx_compile_seconds", "compile seconds",
                              labels=("site",))
    led = _ledger(registry=reg, clock=clock, attribution=_StubAttr())
    # One attributed window: 6s compute, 1s data wait, 0.5s h2d,
    # 0.5s allreduce, 2s dispatch/other — of which 1.5s was really a
    # compile (recorded at the jit seam) that must not double-book.
    phase.labels(phase="device_compute").inc(6.0)
    phase.labels(phase="data_wait").inc(1.0)
    phase.labels(phase="h2d").inc(0.5)
    phase.labels(phase="allreduce").inc(0.5)
    phase.labels(phase="dispatch").inc(0.5)
    phase.labels(phase="other").inc(1.5)
    compile_h.labels(site="train_step").observe(1.5)
    clock.t += 10.0
    snap = led.update()
    cats = snap["categories"]
    assert cats["device_compute"] == pytest.approx(6.0)
    assert cats["input_stall"] == pytest.approx(1.0)
    assert cats["h2d"] == pytest.approx(0.5)
    assert cats["exposed_comm"] == pytest.approx(0.5)
    assert cats["compile"] == pytest.approx(1.5)
    assert cats["other"] == pytest.approx(0.5)  # 2.0 pool - 1.5 compile
    assert cats["idle"] == pytest.approx(0.0)
    assert snap["closure_pct"] == 0.0


def test_cursors_ignore_history_before_construction():
    reg = tmetrics.Registry()
    phase = reg.counter("mx_step_phase_seconds", "x", labels=("phase",))
    phase.labels(phase="device_compute").inc(100.0)   # pre-ledger past
    clock = _FakeClock()
    led = _ledger(registry=reg, clock=clock, attribution=_StubAttr())
    phase.labels(phase="device_compute").inc(2.0)
    clock.t += 2.0
    snap = led.update()
    assert snap["categories"]["device_compute"] == pytest.approx(2.0)


def test_exposed_comm_is_reduce_minus_hidden():
    reg = tmetrics.Registry()
    red = reg.counter("mx_trainer_reduce_seconds_total", "x")
    hid = reg.counter("mx_trainer_reduce_hidden_seconds_total", "x")
    clock = _FakeClock()
    led = _ledger(registry=reg, clock=clock, attribution=_StubAttr())
    red.inc(3.0)
    hid.inc(2.0)
    clock.t += 4.0
    snap = led.update()
    assert snap["categories"]["exposed_comm"] == pytest.approx(1.0)


def test_watchdog_fired_books_hang_recovery():
    class _WD:
        fired = [("step", "hang", 9.0)]   # consumed pre-construction

    clock = _FakeClock()
    led = _ledger(clock=clock, watchdog=_WD())
    _WD.fired.append(("data#0", "hang", 3.0))
    clock.t += 5.0
    snap = led.update()
    assert snap["categories"]["hang_recovery"] == pytest.approx(3.0)


# -- durability + replay ------------------------------------------------------

def test_commit_resume_baseline_roundtrip(tmp_path):
    clock = _FakeClock()
    led = _ledger(tmp_path, clock=clock)
    for i in range(3):
        clock.t += 1.0
        led.observe_step(i, seconds=1.0)
    path = led.commit()
    assert path and os.path.exists(path)
    assert os.path.basename(path) == goodput.ledger_name(0)

    led2 = _ledger(tmp_path, clock=clock)
    assert led2.loaded_last_step == 2
    snap = led2.snapshot(serving=False)
    assert snap["categories"]["device_compute"] == pytest.approx(3.0)
    assert snap["wall_s"] == pytest.approx(3.0)


def test_replay_window_books_restart_replay(tmp_path):
    clock = _FakeClock()
    led = _ledger(tmp_path, clock=clock)
    for i in range(5):
        clock.t += 1.0
        led.observe_step(i, seconds=1.0)
    led.commit()                       # last committed step: 4

    led2 = _ledger(tmp_path, clock=clock)
    assert led2.resume_from(2) == 4    # replay watermark armed
    for i in range(3, 8):
        clock.t += 1.0
        led2.observe_step(i, seconds=1.0)
    snap = led2.snapshot(serving=False)
    assert snap["restart_replay_steps"] == 2          # steps 3, 4
    assert snap["categories"]["restart_replay"] == pytest.approx(2.0)
    assert snap["categories"]["device_compute"] == pytest.approx(
        5.0 + 3.0)                     # baseline + steps 5..7
    assert snap["resumes"] == 1
    assert not snap["replaying"]


def test_corrupt_ledger_starts_fresh(tmp_path):
    p = tmp_path / goodput.ledger_name(0)
    p.write_text("{not json")
    led = _ledger(tmp_path)
    assert led.loaded_last_step is None
    snap = led.snapshot(serving=False)
    assert snap["categories"]["device_compute"] == 0.0


def test_commit_failure_warns_keeps_running(tmp_path, monkeypatch):
    led = _ledger(tmp_path)
    led.observe_step(0, seconds=0.1)
    from mxnet_tpu.telemetry import export

    def boom(path, data):
        raise OSError("disk full")

    monkeypatch.setattr(export, "commit_bytes", boom)
    assert led.commit() is None        # warned, not raised
    led.observe_step(1, seconds=0.1)   # ledger still books


def test_tick_respects_cadence(tmp_path):
    clock = _FakeClock()
    led = _ledger(tmp_path, interval_s=30.0, clock=clock)
    assert led.tick(step=0) is not None      # first tick commits
    clock.t += 1.0
    assert led.tick(step=1) is None          # within cadence
    clock.t += 30.0
    assert led.tick(step=2) is not None


# -- metric publication -------------------------------------------------------

def test_published_counters_monotonic_and_match_snapshot():
    clock = _FakeClock()
    reg = tmetrics.Registry()
    led = _ledger(registry=reg, clock=clock)
    clock.t += 2.0
    led.observe_step(0, seconds=1.5)
    led.update()
    fam = reg.get("mx_goodput_seconds_total")
    dc = fam.labels(category="device_compute")
    idle = fam.labels(category="idle")
    assert dc.value == pytest.approx(1.5)
    assert idle.value == pytest.approx(0.5)
    # a later fold claims previously-idle seconds: the idle counter is
    # a high-watermark (documented), it must not move backward
    led.book("checkpoint", 0.4)
    led.update()
    assert idle.value == pytest.approx(0.5)
    assert fam.labels(category="checkpoint").value == pytest.approx(0.4)
    wall = reg.get("mx_goodput_wall_seconds_total").labels()
    assert wall.value == pytest.approx(2.0)
    assert reg.get("mx_goodput_ratio").labels().value == pytest.approx(
        0.75)


# -- serving analog -----------------------------------------------------------

def test_serving_snapshot_none_without_serving_families():
    assert goodput.serving_snapshot(tmetrics.Registry()) is None


def test_serving_snapshot_padding_shed_and_slot_idle():
    reg = tmetrics.Registry()
    rows = reg.counter("mx_serving_gateway_rows_total", "x",
                       labels=("model",))
    batches = reg.counter("mx_serving_gateway_batches_total", "x",
                          labels=("model", "bucket"))
    shed = reg.counter("mx_serving_gateway_shed_total", "x",
                       labels=("model", "reason", "deadline_class"))
    occ = reg.gauge("mx_decode_slot_occupancy", "x", labels=("model",))
    slots = reg.gauge("mx_decode_slots", "x", labels=("model",))
    rows.labels(model="m").inc(12)
    batches.labels(model="m", bucket="8").inc(2)     # capacity 16
    shed.labels(model="m", reason="queue_full",
                deadline_class="batch").inc(3)
    occ.labels(model="m").set(2)
    slots.labels(model="m").set(8)
    s = goodput.serving_snapshot(reg)
    gw = s["gateway"]
    assert gw["rows_total"] == 12
    assert gw["padded_rows_total"] == pytest.approx(4)
    assert gw["padding_fraction"] == pytest.approx(4 / 16)
    assert gw["shed"] == {"queue_full": 3}
    dec = s["decode"]
    assert dec["models"]["m"]["idle_fraction"] == pytest.approx(0.75)
    assert dec["idle_fraction"] == pytest.approx(0.75)


# -- fleet aggregation (in-process) -------------------------------------------

def test_fleet_merge_sums_counters_and_rank_all():
    clock = _FakeClock()
    bus = aggregate.LocalBus(num_workers=2, clock=clock)
    regs, aggs = [], []
    for r in (0, 1):
        reg = tmetrics.Registry()
        led = goodput.GoodputLedger(rank=r, interval_s=0.0,
                                    registry=reg, clock=clock)
        regs.append((reg, led))
        aggs.append(aggregate.Aggregator(bus.endpoint(r), registry=reg,
                                         interval_s=0.0, clock=clock))
    clock.t += 2.0
    for r, (reg, led) in enumerate(regs):
        led.observe_step(0, seconds=1.0 + r)     # rank1 books 2s
        led.update()
    aggs[1].step()
    aggs[0].step()
    fleet = goodput.fleet_snapshot(aggs[0].fleet)
    assert set(fleet["ranks"]) == {"0", "1"}
    assert fleet["ranks"]["0"]["device_compute"] == pytest.approx(1.0)
    assert fleet["ranks"]["1"]["device_compute"] == pytest.approx(2.0)
    assert fleet["all"]["device_compute"] == pytest.approx(3.0)
    assert fleet["wall_all_s"] == pytest.approx(4.0)
    assert fleet["goodput_ratio"] == pytest.approx(3.0 / 4.0)
    text = aggs[0].render_prometheus()
    assert ('mx_goodput_seconds_total{category="device_compute",'
            'rank="all"}') in text
    assert ('mx_goodput_seconds_total{category="device_compute",'
            'rank="1"} 2') in text


def test_fleet_snapshot_none_before_any_publication():
    assert goodput.fleet_snapshot(None) is None
    assert goodput.fleet_snapshot(tmetrics.Registry()) is None


# -- read surfaces render the same ledger -------------------------------------

def test_debug_goodput_bundle_and_cli_render_same_numbers(tmp_path):
    from mxnet_tpu.telemetry import healthplane as hp
    from mxnet_tpu.telemetry import recorder as rec

    clock = _FakeClock()
    led = _ledger(tmp_path, clock=clock)
    clock.t += 2.0
    led.observe_step(0, seconds=1.0)
    led.book("compile", 0.5)
    path = led.commit()
    goodput.install(led)
    try:
        plane = hp.HealthPlane()
        status, body = plane.handle("GET", "/debug/goodput")
        assert status == 200
        assert body["categories"]["device_compute"] == pytest.approx(
            1.0)

        recorder = rec.FlightRecorder(str(tmp_path / "bundles"))
        bpath = recorder.capture(kind="manual")
        with open(bpath) as f:
            bundle = json.load(f)
        assert bundle["goodput"]["categories"]["compile"] == \
            pytest.approx(0.5)

        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools",
                                          "goodput_report.py"),
             "summary", path],
            capture_output=True, text=True, cwd=_ROOT, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "device_compute" in out.stdout
        # all three surfaces agree on the ratio from the same ledger
        ratio = body["goodput_ratio"]
        assert bundle["goodput"]["goodput_ratio"] == pytest.approx(
            ratio)
        assert ("%.1f %%" % (ratio * 100.0)) in out.stdout
    finally:
        goodput.uninstall(led)


def test_debug_goodput_404_without_ledger():
    from mxnet_tpu.telemetry import healthplane as hp

    assert goodput.active_ledger() is None
    status, body = hp.HealthPlane().handle("GET", "/debug/goodput")
    assert status == 404 and "error" in body


def test_report_cli_merge_and_compare(tmp_path):
    clock = _FakeClock()
    paths = []
    for r in (0, 1):
        led = goodput.GoodputLedger(directory=str(tmp_path), rank=r,
                                    interval_s=0.0,
                                    registry=tmetrics.Registry(),
                                    clock=clock)
        clock.t += 1.0
        led.observe_step(0, seconds=0.5 * (r + 1))
        paths.append(led.commit())

    def run(*argv):
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "tools", "goodput_report.py")]
            + list(argv),
            capture_output=True, text=True, cwd=_ROOT, timeout=120)
        assert out.returncode == 0, out.stderr
        return out.stdout

    merged = run("merge", *paths)
    assert "2 ranks merged" in merged
    # merge is the file analog of the fleet counter sum
    assert "1.500" in merged               # 0.5 + 1.0 device seconds
    cmp_out = run("compare", paths[0], paths[1])
    assert "goodput ratio" in cmp_out and "device_compute" in cmp_out


# -- SIGKILL mid-epoch resume (acceptance) ------------------------------------

_RESUME_PROG = os.path.join(_ROOT, "tests", "goodput_resume_prog.py")
_FLEET_PROG = os.path.join(_ROOT, "tests", "goodput_fleet_prog.py")
_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


def _run_prog(tmp_path, mode, expect):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _RESUME_PROG, "--dir", str(tmp_path),
         "--mode", mode, "--steps", "14", "--kill-after", "8",
         "--ckpt-every", "3"],
        env=env, cwd=_ROOT, timeout=180)
    assert proc.returncode in expect, proc.returncode


def test_sigkill_resume_books_restart_replay(tmp_path):
    """ISSUE 20 acceptance: SIGKILL mid-epoch, resume from the
    checkpoint, and the new incarnation books the re-run steps as
    restart_replay within one step of the true gap."""
    _run_prog(tmp_path, "kill", {-9})
    # kill-after=8: ledger committed through step 7; ckpt-every=3:
    # restore lands at step 5 -> true replay gap = 2 steps (6, 7).
    prior = goodput.load_ledger(
        os.path.join(str(tmp_path), goodput.ledger_name(0)))
    true_gap = prior["last_step"] - 5
    assert true_gap == 2

    _run_prog(tmp_path, "resume", {0})
    with open(os.path.join(str(tmp_path), "result.json")) as f:
        result = json.load(f)
    assert abs(result["restart_replay_steps"] - true_gap) <= 1
    assert result["categories"]["restart_replay"] > 0.0
    assert result["resumes"] == 1
    assert result["last_step"] == 13
    # the durable file agrees with the in-process snapshot
    final = goodput.load_ledger(
        os.path.join(str(tmp_path), goodput.ledger_name(0)))
    assert final["restart_replay_steps"] == \
        result["restart_replay_steps"]


# -- 2-process fleet ledger (acceptance) --------------------------------------

def _can_bind_localhost():
    try:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


def test_two_process_fleet_ledger(tmp_path):
    """ISSUE 20 acceptance: a 2-process dist job yields one rank-0
    fleet view with per-rank goodput series, the summed rank="all"
    series, and per-rank durable ledger files that agree with it."""
    if not _can_bind_localhost():
        pytest.skip("localhost sockets unavailable (multi-process "
                    "kvstore needs them)")
    codes = launch_local(
        2, 1, [sys.executable, _FLEET_PROG, str(tmp_path)],
        env_extra=_ENV, timeout=300)
    assert codes == [0, 0], codes

    text = (tmp_path / "scrape.txt").read_text()
    for rank in (0, 1):
        assert ('mx_goodput_seconds_total{category="device_compute",'
                'rank="%d"} 0.5' % rank) in text, text
    assert ('mx_goodput_seconds_total{category="device_compute",'
            'rank="all"} 1') in text
    assert ('mx_goodput_seconds_total{category="compile",rank="0"} 0.5'
            in text)
    assert ('mx_goodput_seconds_total{category="input_stall",'
            'rank="1"} 1') in text

    with open(os.path.join(str(tmp_path), "fleet.json")) as f:
        fleet = json.load(f)
    assert set(fleet["ranks"]) == {"0", "1"}
    assert fleet["all"]["device_compute"] == pytest.approx(1.0)
    assert fleet["all"]["compile"] == pytest.approx(0.5)
    assert fleet["all"]["input_stall"] == pytest.approx(1.0)

    # the durable per-rank files tell the same story as the fleet view
    for rank in (0, 1):
        led = goodput.load_ledger(os.path.join(
            str(tmp_path), goodput.ledger_name(rank)))
        assert led["categories"]["device_compute"] == pytest.approx(
            0.5)
        assert led["categories"][
            "compile" if rank == 0 else "input_stall"] == \
            pytest.approx(0.5 * (rank + 1))

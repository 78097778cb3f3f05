"""Examples and tools run end-to-end (reference: the drivers under
example/image-classification and tools/ — train_mnist, train_imagenet
--benchmark, im2rec, bandwidth/measure)."""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The drivers run on the CPU: MXNET_DEVICE=cpu is honored in-process by
# those that take a device (jax.config pin before backend init),
# JAX_PLATFORMS=cpu covers the rest.
_ENV = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_DEVICE="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2")


def _run(cmd, timeout=240, env=None):
    res = subprocess.run(cmd, capture_output=True, text=True,
                         env=env or _ENV, timeout=timeout, cwd=_ROOT)
    assert res.returncode == 0, \
        "cmd %s failed:\n%s\n%s" % (cmd, res.stdout[-2000:],
                                    res.stderr[-2000:])
    return res.stdout


def test_train_mnist_synthetic():
    out = _run([sys.executable, "examples/train_mnist.py", "--synthetic",
                "--num-examples", "1500", "--num-epochs", "4",
                "--network", "mlp", "--lr", "0.5"])
    line = [l for l in out.splitlines() if l.startswith("final-accuracy")]
    assert line, out
    acc = float(line[0].split()[1])
    assert acc > 0.8, "mnist driver accuracy %.3f" % acc


def test_train_telemetry_example(tmp_path):
    """README Observability snippet: TelemetryCallback + StepMonitor in
    a TrainStep loop, streaming trace segments merged to a chrome
    trace, fleet-view (rank-labeled) prometheus exposition."""
    import json

    out = _run([sys.executable, "examples/train_telemetry.py",
                "--num-batches", "12", "--batch-size", "32",
                "--out-dir", str(tmp_path)])
    assert "telemetry demo ok" in out
    assert 'mx_train_steps_total{rank="0"} 12' in out
    assert "mx_slo_burn_rate" in out
    with open(os.path.join(str(tmp_path), "chrome_trace.json")) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    assert any(n.startswith("train_step::") for n in names), names
    assert any(n.startswith("checkpoint::") for n in names), names
    # streamed segments were committed and survive in the out dir
    segs = os.listdir(os.path.join(str(tmp_path), "trace_segments"))
    assert any(s.startswith("trace.rank0.") for s in segs), segs


def test_train_imagenet_benchmark_mode():
    out = _run([sys.executable, "examples/train_imagenet.py",
                "--benchmark", "1", "--network", "resnet18",
                "--batch-size", "2", "--image-shape", "3,64,64"],
               timeout=400)
    line = [l for l in out.splitlines() if l.startswith("benchmark:")]
    assert line, out
    assert float(line[0].split()[-2]) > 0


def test_im2rec_roundtrip():
    cv2 = pytest.importorskip("cv2")
    import mxnet_tpu as mx

    with tempfile.TemporaryDirectory() as d:
        rng = np.random.RandomState(0)
        for cls in ("cat", "dog"):
            os.makedirs(os.path.join(d, "imgs", cls))
            for i in range(3):
                img = (rng.rand(20, 24, 3) * 255).astype(np.uint8)
                cv2.imwrite(os.path.join(d, "imgs", cls,
                                         "%d.jpg" % i), img)
        prefix = os.path.join(d, "set")
        _run([sys.executable, "tools/im2rec.py", prefix,
              os.path.join(d, "imgs")])
        assert os.path.exists(prefix + ".rec")
        assert os.path.exists(prefix + ".idx")
        # readable through the training-side iterator
        it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                                   batch_size=2, data_shape=(3, 20, 20))
        batch = next(iter(it))
        assert batch.data[0].shape == (2, 3, 20, 20)
        labels = set()
        it.reset()
        for b in it:
            labels.update(b.label[0].asnumpy().tolist())
        assert {0.0, 1.0} <= labels


def test_bandwidth_measure():
    sys.path.insert(0, os.path.join(_ROOT, "tools", "bandwidth"))
    from measure import measure

    rows = measure("device", num_devices=2, sizes=(4096,), repeat=2,
                   warmup=1)
    assert len(rows) == 1
    size, dt, gbs = rows[0]
    assert dt > 0 and gbs > 0


def test_train_rnn_lm_synthetic():
    """The LSTM PTB-style tracked config as a runnable driver
    (BASELINE.md; reference example/rnn/bucketing/lstm_bucketing.py)."""
    out = _run([sys.executable, "examples/train_rnn_lm.py", "--synthetic",
                "--num-sentences", "400", "--vocab-size", "50",
                "--num-hidden", "32", "--num-embed", "16",
                "--num-layers", "1", "--buckets", "6,10",
                "--batch-size", "16", "--num-epochs", "4"], timeout=500)
    line = [l for l in out.splitlines()
            if l.startswith("final-perplexity")]
    assert line, out
    # uniform guessing over the 50-word vocab would be ppl 50
    assert float(line[0].split()[1]) < 30


def test_train_ssd_synthetic():
    """The SSD tracked config as a runnable driver (BASELINE.md;
    reference example/ssd/train.py)."""
    out = _run([sys.executable, "examples/train_ssd.py",
                "--num-examples", "128", "--num-epochs", "8",
                "--batch-size", "16"], timeout=500)
    line = [l for l in out.splitlines() if l.startswith("final-loss")]
    assert line, out
    assert float(line[0].split()[3]) > 0.5, "recall too low: %s" % line


def test_gluon_image_classification_hybrid():
    """The Gluon imperative/hybrid driver (reference
    example/gluon/image_classification.py) trains to high accuracy in
    hybrid (compiled) mode."""
    out = _run([sys.executable, "examples/gluon_image_classification.py",
                "--model", "resnet18_v1", "--num-examples", "384",
                "--epochs", "8", "--batch-size", "32", "--lr", "0.1"],
               timeout=540)
    line = [l for l in out.splitlines() if l.startswith("final-accuracy")]
    assert line, out
    assert float(line[0].split()[1]) > 0.7


def test_rec2idx_roundtrip(tmp_path):
    """rec2idx rebuilds a usable index for an unindexed .rec
    (reference tools/rec2idx.py)."""
    import mxnet_tpu as mx

    rec_path = str(tmp_path / "data.rec")
    rec = mx.recordio.MXRecordIO(rec_path, "w")
    payloads = [("item%03d" % i).encode() * (i + 1) for i in range(7)]
    for p in payloads:
        rec.write(p)
    rec.close()

    _run([sys.executable, "tools/rec2idx.py", rec_path])
    idx_path = str(tmp_path / "data.idx")
    assert os.path.exists(idx_path)
    reader = mx.recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert sorted(reader.keys) == list(range(7))
    for i in (3, 0, 6):        # random access
        assert reader.read_idx(i) == payloads[i]
    reader.close()


def test_rec_shard_split_balanced_and_manifest(tmp_path):
    """tools/rec_shard.py splits a .rec into N balanced indexed shards
    with a manifest, and every record survives the split (ISSUE 6)."""
    import json

    import mxnet_tpu as mx

    rec_path = str(tmp_path / "full.rec")
    idx_path = str(tmp_path / "full.idx")
    w = mx.recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    payloads = [("rec%04d" % i).encode() * (1 + i % 5) for i in range(11)]
    for i, p in enumerate(payloads):
        w.write_idx(i, p)
    w.close()

    prefix = str(tmp_path / "shards" / "part")
    out = _run([sys.executable, "tools/rec_shard.py", "split", rec_path,
                "--num-shards", "3", "--out-prefix", prefix])
    manifest = json.loads(out)
    counts = [s["records"] for s in manifest["shards"]]
    assert manifest["total_records"] == 11
    assert sorted(counts) == [3, 4, 4]          # balanced to within 1
    # all records survive, ids stay recoverable (round-robin i%N)
    from mxnet_tpu.data import RecordDataset

    got = []
    for s in manifest["shards"]:
        shard = RecordDataset([str(tmp_path / "shards" / s["rec"])])
        assert len(shard) == s["records"]
        got.extend(shard.read(i) for i in range(len(shard)))
    assert sorted(got) == sorted(payloads)

    out = _run([sys.executable, "tools/rec_shard.py", "inspect",
                prefix + "-manifest.json"])
    assert json.loads(out)["balanced"] is True
    out = _run([sys.executable, "tools/rec_shard.py", "inspect", rec_path])
    assert json.loads(out)["records"] == 11


def test_parse_log(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(_ROOT, "tools"))
    from parse_log import parse, render

    lines = [
        "INFO Epoch[0] Train-accuracy=0.5\n",
        "INFO Epoch[0] Validation-accuracy=0.4\n",
        "INFO Epoch[0] Time cost=12.5\n",
        "INFO Epoch[1] Train-accuracy=0.8\n",
        "INFO Epoch[1] Validation-accuracy=0.7\n",
        "INFO Epoch[1] Time cost=11.0\n",
    ]
    data = parse(lines, ["accuracy"])
    out = render(data, ["accuracy"], "markdown")
    assert "| epoch |" in out and "0.800000" in out and "11.0" in out
    tsv = render(data, ["accuracy"], "none")
    assert tsv.splitlines()[0].startswith("epoch\t")


def test_flakiness_checker(tmp_path, monkeypatch):
    """Flakiness checker reports failing seeds reproducibly
    (reference tools/flakiness_checker.py)."""
    monkeypatch.syspath_prepend(os.path.join(_ROOT, "tools"))
    import flakiness_checker

    assert flakiness_checker.resolve_target("test_io.test_foo") == \
        "tests/test_io.py::test_foo"
    assert flakiness_checker.resolve_target(
        "tests/test_io.py::test_foo") == "tests/test_io.py::test_foo"
    out = _run([sys.executable, "tools/flakiness_checker.py",
                "tests/test_lr_callback.py::test_scheduler_warmup",
                "-n", "2"], timeout=300)
    assert "0/2 trials failed" in out


def test_train_gan_adversarial_loop():
    """Two-optimizer adversarial loop (reference example/gan)."""
    out = _run([sys.executable, "examples/train_gan.py",
                "--epochs", "1", "--batches", "4", "--batch-size", "16"],
               timeout=300)
    assert "d_loss" in out and "fake mean" in out


def test_train_matrix_factorization_sparse():
    """Sparse-embedding MF recommender (reference example/recommenders)."""
    out = _run([sys.executable, "examples/train_matrix_factorization.py",
                "--epochs", "2", "--samples", "1024",
                "--num-users", "80", "--num-items", "60"], timeout=300)
    assert "val_rmse" in out


def test_train_rcnn_rpn_proposal_head():
    """RPN training + Proposal + ROIPooling head (reference example/rcnn)."""
    out = _run([sys.executable, "examples/train_rcnn.py",
                "--steps", "6", "--batch-size", "2"], timeout=400)
    assert "rois" in out and "rpn_loss" in out


def test_benchmark_sparse_end2end():
    """Sparse end-to-end bench runs and reports all three modes
    (reference benchmark/python/sparse)."""
    out = _run([sys.executable, "benchmark/sparse_end2end.py",
                "--features", "2000", "--batches", "3",
                "--batch-size", "32"], timeout=300)
    assert out.count("sparse_end2end_samples_per_s") == 3
    assert "row_sparse" in out and "trainstep_fused" in out


def test_benchmark_control_flow():
    """foreach-vs-unrolled bench runs (reference benchmark/python/
    control_flow)."""
    out = _run([sys.executable, "benchmark/control_flow_bench.py",
                "--seq-len", "16", "--iters", "2"], timeout=300)
    assert "foreach_scan" in out and "unrolled" in out


def test_model_parallel_lstm_group2ctx():
    """Layer groups placed on distinct devices via group2ctx
    (reference example/model-parallel)."""
    env = dict(_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = _run([sys.executable, "examples/model_parallel_lstm.py",
                "--steps", "30"], timeout=400, env=env)
    assert "placement" in out and "nll" in out


def test_adversarial_fgsm_input_grads():
    """Input-gradient API: FGSM collapses accuracy (reference
    example/adversary)."""
    out = _run([sys.executable, "examples/adversarial_fgsm.py",
                "--epochs", "3", "--train", "256", "--test", "128"],
               timeout=400)
    assert "adversarial accuracy" in out


def test_train_ctc_ocr():
    """CTC loss over unaligned sequence labels (reference example/ctc,
    example/captcha)."""
    out = _run([sys.executable, "examples/train_ctc_ocr.py",
                "--steps", "40", "--batch-size", "16"], timeout=400)
    assert "ctc_loss" in out and "exact-sequence" in out


def test_bi_lstm_sort():
    """BidirectionalCell seq2seq sorting via Module.fit (reference
    example/bi-lstm-sort)."""
    out = _run([sys.executable, "examples/bi_lstm_sort.py",
                "--steps", "100", "--batch-size", "16"], timeout=400)
    assert "sorted-position accuracy" in out


def test_train_multi_task():
    """Shared trunk + two heads + joint backward (reference
    example/multi-task)."""
    out = _run([sys.executable, "examples/train_multi_task.py",
                "--epochs", "4"], timeout=400)
    assert "quad-acc" in out and "xpos-mae" in out


def test_neural_style_input_optimization():
    """Gatys-style input optimization with Gram losses (reference
    example/neural-style)."""
    out = _run([sys.executable, "examples/neural_style.py",
                "--steps", "40"], timeout=400)
    assert "total loss" in out


def test_kill_mxnet_finds_dmlc_processes():
    """tools/kill_mxnet.py sweeps processes carrying the DMLC_ROLE
    launch contract (reference tools/kill-mxnet.py)."""
    import time

    marker = "kill_mxnet_test_%d" % os.getpid()
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import time; time.sleep(60)  # " + marker],
        env=dict(os.environ, DMLC_ROLE="worker"))
    try:
        time.sleep(0.3)
        out = _run([sys.executable, "tools/kill_mxnet.py", "--dry-run",
                    "--match", marker])
        assert ("pid %d" % proc.pid) in out and "worker" in out
        # kill ONLY our marked sleeper — a parallel dist test's
        # scheduler/server/workers must survive this test.
        out = _run([sys.executable, "tools/kill_mxnet.py",
                    "--grace", "1", "--match", marker])
        assert "terminated" in out
        time.sleep(0.5)
        assert proc.poll() is not None, "stray process survived"
    finally:
        if proc.poll() is None:
            proc.kill()


def test_train_autoencoder():
    """Conv2DTranspose decoder + reconstruction training (reference
    example/autoencoder)."""
    out = _run([sys.executable, "examples/train_autoencoder.py",
                "--epochs", "5"], timeout=400)
    assert "recon_loss" in out


def test_cnn_text_classification():
    """Multi-width Conv1D + max-over-time text classifier (reference
    example/cnn_text_classification)."""
    out = _run([sys.executable, "examples/cnn_text_classification.py",
                "--epochs", "3", "--train", "1024"], timeout=400)
    assert "val-acc" in out


def test_train_fcn_segmentation():
    """Per-pixel classification + Conv2DTranspose upsampling (reference
    example/fcn-xs)."""
    out = _run([sys.executable, "examples/train_fcn_segmentation.py",
                "--epochs", "6"], timeout=500)
    assert "mean-IoU" in out


def test_serve_mnist_inference_server():
    """Serving driver: save_checkpoint -> bucketed warmup -> concurrent
    batched inference -> per-bucket stats (mxnet_tpu.serving)."""
    out = _run([sys.executable, "examples/serve_mnist.py",
                "--train-epochs", "2", "--num-examples", "1000",
                "--requests", "96", "--concurrency", "8",
                "--max-batch", "16", "--max-delay-ms", "5"],
               timeout=300)
    acc = [l for l in out.splitlines() if l.startswith("served-accuracy")]
    thr = [l for l in out.splitlines()
           if l.startswith("serving-throughput")]
    assert acc and thr, out
    assert float(acc[0].split()[1]) > 0.7
    assert float(thr[0].split()[1]) > 0
    # the shedding demo actually fired (the printed shed dict is
    # non-empty), not just the unconditional "shed:" label
    assert "bucket" in out and "'deadline'" in out


def test_train_resume_preemption_bit_exact():
    """Checkpoint driver (mxnet_tpu.checkpoint): train → SIGTERM
    mid-run → restart resumes from the latest atomic commit and finishes
    bit-exact vs an uninterrupted run (train_resume.py demo mode drives
    the kill itself and compares final state digests)."""
    out = _run([sys.executable, "examples/train_resume.py",
                "--steps", "10", "--kill-after", "4",
                "--step-delay", "0.05"], timeout=400)
    assert "phase-1 exit code 143" in out, out       # clean preempt save
    resumed = [l for l in out.splitlines()
               if l.startswith("resumed-from-step")]
    assert resumed, out
    assert int(resumed[0].split()[1]) >= 1           # really mid-run
    assert "bitexact True" in out, out
    # loss curve continued from the saved step, not from scratch: the
    # resumed phase printed its first step at the resume point
    steps2 = [l for l in out.splitlines() if l.startswith("  | step ")]
    assert steps2, out


def test_train_resnet_trainstep_blessed_path():
    """The TPU-blessed pipeline end to end: RecordIO -> decode team ->
    fused bf16 SPMD TrainStep -> checkpoint."""
    pytest.importorskip("cv2")
    out = _run([sys.executable, "examples/train_resnet_trainstep.py",
                "--steps", "18", "--batch-size", "16",
                "--samples", "128"], timeout=500)
    assert "img/s (post-compile)" in out and "checkpoint" in out

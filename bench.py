"""Benchmark: ResNet-50 v1 ImageNet-shape throughput, single chip —
against the reference's published numbers (docs/faq/perf.md; BASELINE.md):

- training  b32  fp32: 298.51 img/s (perf.md:214, 1x V100)
- training  b128 fp32: 363.69 img/s (perf.md:216)
- inference b32  fp32: 1076.81 img/s (perf.md:156)
- inference b32  fp16: 2085.51 img/s (perf.md:170) — our bf16 row
- training  b32  bf16: vs the same 298.51 fp32 row (reference published
  no fp16 training number; bf16-vs-their-best-fp32 is the honest compare)

Training steps are whole-step XLA executables (fwd + softmax CE + bwd +
SGD-momentum update, mxnet_tpu.parallel.TrainStep; bf16 rows use fp32
master weights — mp_sgd semantics). Inference is one jitted forward.

Measurement discipline (JAX dispatch is asynchronous: a call returns
before the device finishes): every timed window ends with a *host
readback* of a scalar that data-depends on the window's last step, and
inference calls are chained through a scalar carry so the whole window
is one dependency chain. Inputs are placed on device before timing (the
reference's numbers are likewise compute-bound, fed by a prefetching
iterator).

Needs a TPU: a host without one exits non-zero before any row. Prints
one JSON line per row; the LAST line is the headline metric (train b32
fp32). Each row carries est_mfu_bf16: achieved FLOP/s over the chip's
bf16 peak, looked up by ``device_kind`` in PEAK_TFLOPS_BF16 (an unknown
kind is an error), using 4.09 GFLOP/img forward and 3x that for
training. A failed section is reported on stderr and makes the exit
code non-zero at the end.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

WARMUP = 3
WINDOWS = 7   # median-of-windows: the chip's host shares its CPU cores
FWD_GFLOP_PER_IMG = 4.09          # ResNet-50 224x224 forward
TRAIN_GFLOP_PER_IMG = 3 * FWD_GFLOP_PER_IMG
# Published bf16 peak per chip, keyed by jax's device_kind. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_TFLOPS_BF16 = {"TPU v5 lite": 197.0}


def _peak_tflops_bf16(device):
    try:
        return PEAK_TFLOPS_BF16[device.device_kind]
    except KeyError:
        raise SystemExit(
            "bench.py: no bf16 peak recorded for device_kind %r; add it "
            "to PEAK_TFLOPS_BF16 with its source" % device.device_kind)


def _measure(run_once, read_scalar, batch, iters):
    """Median img/s over WINDOWS; each window = `iters` dependent calls
    closed by a host readback (`read_scalar`) proving completion."""
    for _ in range(WARMUP):
        out = run_once()
    read_scalar(out)
    rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = run_once()
        read_scalar(out)
        rates.append(batch * iters / (time.perf_counter() - t0))
    return sorted(rates)[len(rates) // 2]


def _emit(metric, value, unit):
    print(json.dumps({"metric": metric, "value": value, "unit": unit}),
          flush=True)


def _row(metric, img_s, baseline, gflop_per_img, peak_tflops):
    print(json.dumps({
        "metric": metric,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / baseline, 4),
        "est_mfu_bf16": round(img_s * gflop_per_img / 1e3
                              / peak_tflops, 4),
    }), flush=True)
    return img_s


def _train_rate(batch, dtype, device):
    """Training rows run THROUGH the example driver (the reference's
    numbers are measured through train_imagenet.py the same way)."""
    import sys

    examples_dir = os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples")
    if examples_dir not in sys.path:
        sys.path.insert(0, examples_dir)
    from train_imagenet import benchmark_rate

    # Small batches get longer windows: per-step host dispatch latency
    # is the noise floor.
    return benchmark_rate("resnet50", batch, dtype, devices=[device],
                          iters=16 if batch <= 32 else 10,
                          windows=WINDOWS, warmup=WARMUP)


def _infer_rate(batch, dtype, device):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.parameter import override
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = vision.resnet50_v1(classes=1000)
    net.initialize()
    with autograd.pause():
        net(mx.nd.ones((1, 3, 224, 224)))
    params = list(net.collect_params().values())
    cdt = jnp.dtype(dtype) if dtype else jnp.float32
    pvals = {p.name: jax.device_put(
        p.data()._data.astype(cdt)
        if jnp.issubdtype(p.data()._data.dtype, jnp.floating)
        else p.data()._data, device) for p in params}

    def fwd(pv, xb, carry):
        # carry chains successive calls into one dependency chain.
        xb = xb + jnp.asarray(carry, xb.dtype)
        mapping = {p: NDArray(pv[p.name]) for p in params}
        with autograd.pause(train_mode=False), override(mapping):
            out = net(NDArray(xb))._data
        return jnp.mean(out.astype(jnp.float32)) * 1e-6

    jfwd = jax.jit(fwd)
    rng = np.random.RandomState(0)
    xs = [jax.device_put(
        rng.rand(batch, 3, 224, 224).astype(np.float32), device).astype(cdt)
        for _ in range(4)]
    carry = {"i": 0, "v": jnp.float32(0)}

    def run_once():
        carry["v"] = jfwd(pvals, xs[carry["i"] % len(xs)], carry["v"])
        carry["i"] += 1
        return carry["v"]

    return _measure(run_once, lambda tap: float(tap), batch, iters=20)


def _serving_rows():
    """Serving section (mxnet_tpu.serving): single-request latency vs
    batched throughput at bucket sizes 1/8/32, plus the coalescing rate
    under concurrent batch-1 load. Rows ride the default device; the
    measured path includes host batch assembly + one upload per device
    call — the real serving hot path, not just the executable."""
    from concurrent.futures import ThreadPoolExecutor

    import mxnet_tpu as mx
    from mxnet_tpu import serving

    rng = np.random.RandomState(0)
    w1 = mx.nd.array(rng.randn(784, 256).astype(np.float32) * 0.05)
    b1 = mx.nd.zeros((256,))
    w2 = mx.nd.array(rng.randn(256, 10).astype(np.float32) * 0.05)

    def fwd(w1, b1, w2, x):
        return mx.nd.dot(mx.nd.relu(mx.nd.dot(x, w1) + b1), w2)

    # Per-bucket device throughput: a single-bucket server makes every
    # sequential full-bucket predict() dispatch immediately (rows ==
    # max_batch) — no max_delay_ms batching-window stall in the number.
    for b in (1, 8, 32):
        sb = serving.InferenceServer(fwd, [w1, b1, w2], item_shape=(784,),
                                     buckets=(b,), max_delay_ms=0)
        try:
            xb = rng.rand(b, 784).astype(np.float32)
            for _ in range(3):
                sb.predict(xb)                # warm the path
            t0 = time.perf_counter()
            n = 30
            for _ in range(n):
                sb.predict(xb)
            _emit("serving_mlp_rows_per_sec_b%d" % b,
                  round(b * n / (time.perf_counter() - t0), 1), "rows/s")
        finally:
            sb.shutdown()

    srv = serving.InferenceServer(fwd, [w1, b1, w2], item_shape=(784,),
                                  buckets=(1, 8, 32), max_delay_ms=2,
                                  max_queue=1024)
    try:
        # Single-request latency INCLUDES the batching window — the
        # real cost a lone client pays on a ladder server.
        lat = []
        x1 = rng.rand(1, 784).astype(np.float32)
        for _ in range(50):
            t0 = time.perf_counter()
            srv.predict(x1)
            lat.append(time.perf_counter() - t0)
        lat.sort()
        _emit("serving_mlp_single_request_p50_ms",
              round(lat[len(lat) // 2] * 1e3, 3), "ms")
        reqs = [rng.rand(1, 784).astype(np.float32) for _ in range(256)]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(16) as pool:
            futs = list(pool.map(srv.submit, reqs))
        for f in futs:
            f.result()
        _emit("serving_mlp_coalesced_req_per_sec",
              round(len(reqs) / (time.perf_counter() - t0), 1), "req/s")
    finally:
        srv.shutdown()


def _serving_gateway_rows():
    """Gateway section (mxnet_tpu.serving.gateway, ISSUE 15): 2-model
    mixed load with a mid-run zero-drop hot swap and SLO-coupled
    shedding. One model ("hot") is flooded past an unmeetable SLO so
    its lowest deadline class sheds; the other ("steady") runs moderate
    load and is hot-swapped mid-run. THE CONTRACT ROWS:

    - gateway_swap_dropped_requests == 0 — no request is dropped by
      the swap (sheds on the hot model's lowest class are the POLICY
      working, counted separately);
    - gateway_protected_p99_ms <= 250 — the non-overloaded model's p99
      stays pinned while the other model burns and sheds.
    """
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu.serving import ModelGateway, ModelSpec, \
        ServiceUnavailableError, QueueFullError, hot_swap

    rng = np.random.RandomState(0)

    def mlp_params(scale):
        return [mx.nd.array(rng.randn(784, 256).astype(np.float32)
                            * scale),
                mx.nd.zeros((256,)),
                mx.nd.array(rng.randn(256, 10).astype(np.float32)
                            * scale)]

    def fwd(w1, b1, w2, x):
        return mx.nd.dot(mx.nd.relu(mx.nd.dot(x, w1) + b1), w2)

    gw = ModelGateway(max_queue=512, max_delay_ms=2.0,
                      burn_windows=(0.5, 2.0), eval_interval_s=0.1,
                      shed_burn_rate=5.0)
    dropped = []        # hard failures (the contract quantity)
    sheds = []          # policy sheds on the hot model's lowest class
    results = {"hot": 0, "steady": 0}
    stop = threading.Event()
    lock = threading.Lock()
    try:
        gw.register(ModelSpec(
            "hot", fn=fwd, params=mlp_params(0.05), item_shape=(784,),
            max_batch=32, weight=1.0,
            deadline_classes=(("interactive", None), ("best_effort",
                                                      None)),
            slo=(0.99, 0.0005)))     # unmeetable: every request burns
        gw.register(ModelSpec(
            "steady", fn=fwd, params=mlp_params(0.05), item_shape=(784,),
            max_batch=32, weight=1.0))

        def hammer(model, cls, n_rows):
            x = rng.rand(n_rows, 784).astype(np.float32)
            while not stop.is_set():
                try:
                    gw.predict(model, x, deadline_class=cls)
                    with lock:
                        results[model] += 1
                except (ServiceUnavailableError, QueueFullError) as exc:
                    if model == "hot":
                        with lock:
                            sheds.append(exc)
                    else:
                        with lock:
                            dropped.append(exc)
                except Exception as exc:
                    with lock:
                        dropped.append(exc)

        threads = [threading.Thread(target=hammer,
                                    args=("hot", "interactive", 4))
                   for _ in range(2)]
        threads += [threading.Thread(target=hammer,
                                     args=("hot", "best_effort", 4))
                    for _ in range(2)]
        threads += [threading.Thread(target=hammer,
                                     args=("steady", "default", 4))
                    for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(1.5)              # let the burn monitor see the SLO
        t0 = time.perf_counter()
        gen = hot_swap(gw, "steady", params=mlp_params(0.07))
        swap_ms = (time.perf_counter() - t0) * 1e3
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(30)
        stats = gw.stats()
        shedding_seen = len(sheds) > 0 or \
            stats["hot"]["shed"].get("slo_burn:best_effort", 0) > 0
        # THE CONTRACT ROW: the swap (and the hot model's overload)
        # dropped nothing — every steady request and every non-shed hot
        # request completed.
        _emit("gateway_swap_dropped_requests", len(dropped), "req")
        _emit("gateway_swap_generation", gen, "gen")
        _emit("gateway_swap_total_ms", round(swap_ms, 1), "ms")
        # THE CONTRACT ROW: the healthy model's p99 while the other
        # model burned and shed.
        _emit("gateway_protected_p99_ms",
              round(stats["steady"]["p99_ms"], 2), "ms")
        _emit("gateway_hot_p99_ms", round(stats["hot"]["p99_ms"], 2),
              "ms")
        # registry counter only: the client-observed `sheds` list is
        # the SAME events (submit increments the counter, then raises).
        _emit("gateway_hot_sheds",
              int(stats["hot"]["shed"].get("slo_burn:best_effort", 0)),
              "req")
        _emit("gateway_slo_shedding_engaged", int(shedding_seen), "bool")
        _emit("gateway_steady_req_per_sec", round(results["steady"] / 3.0,
                                                  1), "req/s")
        _emit("gateway_hot_req_per_sec", round(results["hot"] / 3.0, 1),
              "req/s")
    finally:
        stop.set()
        gw.shutdown()


def _continuous_batching_rows():
    """Continuous batching section (mxnet_tpu.serving.continuous,
    ISSUE 19): iteration-level slot scheduling vs a static batch on the
    SAME backend at a geometric sequence-length mix. THE CONTRACT ROWS:

    - continuous_batching_tokens_per_sec_speedup >= 2.0 — the static
      regime steps every batch max(L) times to earn mean(L) tokens per
      slot; per-iteration retire/admit reclaims the difference;
    - decode_steady_state_retraces == 0 — compile count flat across
      the whole run (>= 100 steps of admit/retire churn) after warm().

    Plus an informative p99 TTFT row while the batch is saturated.
    """
    import mxnet_tpu as mx
    from mxnet_tpu.serving import DecodeConfig, DecodeLoop, ModelSpec
    from mxnet_tpu.telemetry import metrics as _tm

    H, B, N, REPS = 1536, 32, 384, 3
    rng = np.random.RandomState(3)
    w = mx.nd.array((rng.rand(H, H).astype(np.float32) - 0.5) * 0.05)

    def step(w_, state, tokens, pos):
        return mx.nd.tanh(mx.nd.dot(state, w_)), tokens + 1

    spec = ModelSpec(
        "bench_decode", params=[w], max_batch=B,
        decode=DecodeConfig(step, state_shape=(H,), page_slots=4,
                            max_tokens=128))
    backend = spec.build_backend()
    backend.warm()
    warm_compiles = backend.compile_count
    # Geometric length mix: many short, a heavy tail of long — the
    # regime static batching wastes (each batch runs max(L) steps over
    # the FULL batch width, mostly on rows that already finished).
    lengths = np.clip(
        np.random.RandomState(7).geometric(1 / 10.0, size=N), 1, 128)
    total_tokens = int(lengths.sum())

    def static_pass():
        # Static baseline: same backend, batch-synchronous — admit B
        # sequences, step until the LONGEST finishes, repeat.
        # Admission (slot-state init) is paid per sequence in both
        # regimes; past that the inline loop has strictly less host
        # overhead than the scheduler, so the comparison is
        # conservative.
        tokens = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        steps = 0
        t0 = time.perf_counter()
        for i in range(0, N, B):
            batch = lengths[i:i + B]
            n_pages = backend.page_count(len(batch))
            active = np.zeros(B, bool)
            for slot in range(len(batch)):
                tokens[slot] = backend.admit(
                    slot, np.asarray([1], np.int32))
            for s in range(int(batch.max())):
                active[:len(batch)] = s < batch
                backend.step(n_pages, tokens, pos, active)
                steps += 1
        return time.perf_counter() - t0, steps

    steps_fam = _tm.REGISTRY.get("mx_decode_steps_total")

    def continuous_pass():
        steps0 = steps_fam.labels(model="bench_decode").value
        loop = DecodeLoop(spec, backend)
        try:
            t0 = time.perf_counter()
            seqs = [loop.submit([int(n) % 97 + 1], max_tokens=int(n))
                    for n in lengths]
            for s in seqs:
                s.future.result(timeout=300)
            dt = time.perf_counter() - t0
            steps = int(steps_fam.labels(model="bench_decode").value
                        - steps0)
            p99 = loop.stats()["p99_ttft_ms"]
        finally:
            loop.close()
        return dt, steps, p99

    # Paired repetitions, median speedup — the same median-of-windows
    # discipline as the training rows (robust to shared-CPU noise).
    runs = []
    for _ in range(REPS):
        static_s, static_steps = static_pass()
        cont_s, cont_steps, p99_ttft = continuous_pass()
        runs.append((cont_s, static_s, cont_steps, static_steps,
                     p99_ttft))
    cont_s, static_s, cont_steps, static_steps, p99_ttft = sorted(
        runs, key=lambda r: r[1] / r[0])[REPS // 2]
    static_tps = total_tokens / static_s
    cont_tps = total_tokens / cont_s

    _emit("decode_tokens_per_sec_continuous", round(cont_tps, 1),
          "tok/s")
    _emit("decode_tokens_per_sec_static", round(static_tps, 1), "tok/s")
    # THE CONTRACT ROW (>= 2.0).
    _emit("continuous_batching_tokens_per_sec_speedup",
          round(cont_tps / static_tps, 3), "x")
    # THE CONTRACT ROW (== 0): zero retraces across every static sweep
    # AND >= 100 continuous steps of admit/retire churn per rep, all
    # post-warm.
    _emit("decode_steady_state_retraces",
          int(backend.compile_count - warm_compiles), "compiles")
    _emit("decode_churn_steps", cont_steps, "steps")
    _emit("decode_static_steps", static_steps, "steps")
    _emit("decode_warm_compiles", warm_compiles, "compiles")
    # Informative: admission latency while every slot is contended.
    _emit("decode_p99_ttft_ms", round(p99_ttft, 2), "ms")


def _telemetry_rows():
    """Telemetry section (mxnet_tpu.telemetry): instrumentation overhead
    on the step path. The SAME TrainStep loop is timed with telemetry
    fully disabled (set_enabled(False): spans and metric updates reduce
    to a boolean check) and fully enabled (registry histograms + trace
    rings + a StepMonitor fed each step — the production configuration).
    THE CONTRACT ROW: telemetry_step_overhead_pct <= 2%."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(13)
    rng = np.random.RandomState(13)
    net = gluon.nn.HybridSequential(prefix="bench_tel_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05,
                                       "momentum": 0.9},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                      # compile + settle
        float(np.asarray(step(x, y)))

    iters = 50
    monitor = telemetry.StepMonitor(warn_interval_s=3600)

    def timed(observe):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            loss = step(x, y)
            float(np.asarray(loss))         # close the step like a real loop
            if observe:
                # The monitor's own cost (EWMA, backlog poll, anomaly
                # path) is part of the configuration under contract, so
                # it lands INSIDE the timed window.
                monitor.observe_step(time.perf_counter() - t0)
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    prev = telemetry.set_enabled(False)
    try:
        off_ms = timed(observe=False) * 1e3
        telemetry.set_enabled(True)
        on_ms = timed(observe=True) * 1e3
    finally:
        telemetry.set_enabled(prev)

    _emit("telemetry_step_ms_off", round(off_ms, 3), "ms")
    _emit("telemetry_step_ms_on", round(on_ms, 3), "ms")
    # THE CONTRACT ROW: span recording + registry updates on the step
    # path must cost <= 2% of the step. Negative values are measurement
    # noise (the instrumentation is sub-µs against a ms-scale step).
    _emit("telemetry_step_overhead_pct",
          round((on_ms - off_ms) / off_ms * 100.0, 2), "%")


def _telemetry_dist_rows():
    """Pod-observability section (ISSUE 5): what the cross-process
    machinery costs on the step path. The SAME TrainStep loop is timed
    bare, then with (a) registry aggregation at a fixed every-10-steps
    cadence (snapshot + LocalBus push + rank-0 merge — the full
    per-round work a dist job pays, minus only the TCP hop, which is
    pipelined/ack-deferred on the real transport) and (b) streaming
    trace export ticked every step (ring drain + rotation check;
    commits amortized by the size/age budget). THE CONTRACT ROWS:
    telemetry_aggregation_overhead_pct <= 2%,
    trace_streaming_step_overhead_pct <= 1%."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.telemetry import aggregate, export
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(17)
    rng = np.random.RandomState(17)
    net = gluon.nn.HybridSequential(prefix="bench_teld_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                      # compile + settle
        float(np.asarray(step(x, y)))

    iters = 50

    def timed(per_step):
        times = []
        for i in range(iters):
            t0 = time.perf_counter()
            loss = step(x, y)
            float(np.asarray(loss))
            per_step(i)                     # cost under contract
            times.append(time.perf_counter() - t0)
        return times

    def _mean(ts):
        return sum(ts) / len(ts)

    base = timed(lambda i: None)

    bus = aggregate.LocalBus(num_workers=1)
    agg = aggregate.Aggregator(bus.endpoint(0), interval_s=1e9)
    agg_times = timed(lambda i: agg.step() if i % 10 == 0 else None)

    seg_dir = tempfile.mkdtemp(prefix="bench_trace_seg_")
    writer = export.StreamingTraceWriter(seg_dir)
    stream = timed(lambda i: writer.tick())
    writer.close()
    shutil.rmtree(seg_dir, ignore_errors=True)

    # Aggregation lands on 1 step in 10: the contract is on the MEAN
    # (the amortized per-step cost at the cadence — a median would
    # always pick one of the 9 untouched steps and could never fail).
    # Streaming ticks EVERY step, so its median is the honest center.
    base_mean_ms = _mean(base) * 1e3
    agg_mean_ms = _mean(agg_times) * 1e3
    base_med_ms = sorted(base)[len(base) // 2] * 1e3
    stream_med_ms = sorted(stream)[len(stream) // 2] * 1e3

    _emit("telemetry_dist_step_ms_base", round(base_mean_ms, 3), "ms")
    _emit("telemetry_dist_step_ms_aggregated",
          round(agg_mean_ms, 3), "ms")
    _emit("telemetry_dist_step_ms_streaming",
          round(stream_med_ms, 3), "ms")
    # THE CONTRACT ROWS (negatives are measurement noise: both hooks
    # are µs-scale against a ms-scale step).
    _emit("telemetry_aggregation_overhead_pct",
          round((agg_mean_ms - base_mean_ms) / base_mean_ms * 100.0, 2),
          "%")
    _emit("trace_streaming_step_overhead_pct",
          round((stream_med_ms - base_med_ms) / base_med_ms * 100.0, 2),
          "%")


def _xtrace_rows():
    """Causal-tracing section (ISSUE 18): what cross-process trace
    propagation costs on the trainer step path. The SAME
    ``gluon.Trainer`` loop (fused kvstore step: root context per step,
    context-carrying reduce tasks, per-key spans) is timed with head
    sampling OFF (``MXNET_TRACE_SAMPLE=0``: contexts still mint and
    propagate — the designed cheap path — but stamp nothing) and ON
    (rate 1.0 + trace-id exemplars: every span stamps
    trace_id/parent_span_id, the production forensics configuration).
    THE CONTRACT ROW: trace_propagation_step_overhead_pct <= 1%."""
    import time as _t

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu.telemetry import xtrace

    rng = np.random.RandomState(7)
    params = []
    for k in range(300):
        p = gluon.Parameter("xt_bench_%d" % k, shape=(1024,))
        p.initialize(init=mx.init.Constant(0.0))
        p.set_data(nd.array(rng.randn(1024).astype(np.float32)))
        params.append(p)
    trainer = gluon.Trainer(
        params, "sgd", {"learning_rate": 0.05, "momentum": 0.9},
        kvstore=kvs.KVStoreLocal(device_mode=True),
        update_on_kvstore=False)
    for p in params:
        p.grad()[:] = rng.randn(1024).astype(np.float32)
    trainer.step(1)                         # warmup: compile + init
    params[-1].data().asnumpy()

    iters = 30

    def timed():
        times = []
        for _ in range(iters):
            t0 = _t.perf_counter()
            trainer.step(1)
            params[-1].data().asnumpy()
            times.append(_t.perf_counter() - t0)
        return sorted(times)[len(times) // 2] * 1e3

    prev_rate = xtrace.set_sample_rate(0.0)
    try:
        off_ms = timed()
        xtrace.set_sample_rate(1.0)
        xtrace.install_exemplars(True)
        on_ms = timed()
    finally:
        xtrace.install_exemplars(False)
        xtrace.set_sample_rate(prev_rate)

    _emit("xtrace_step_ms_unsampled", round(off_ms, 3), "ms")
    _emit("xtrace_step_ms_sampled", round(on_ms, 3), "ms")
    # THE CONTRACT ROW: stamping every span with its trace context and
    # recording trace-id exemplars must cost <= 1% of the step path.
    # Negative values are measurement noise (the stamp is a dict
    # setdefault against a ms-scale step).
    _emit("trace_propagation_step_overhead_pct",
          round((on_ms - off_ms) / off_ms * 100.0, 2), "%")


def _diagnostics_rows():
    """Diagnostics section (ISSUE 7): what failure forensics costs when
    nothing is failing. THE CONTRACT ROWS:
    numeric_guard_step_overhead_pct <= 2 (an every-step NumericGuard
    loss check — the isfinite read piggybacks on the loss readback a
    real loop already pays) and watchdog_idle_overhead_pct <= 1 (a
    running HangWatchdog: TrainStep's begin/end heartbeats plus the
    4 Hz scan thread amortized over the step).

    Measurement discipline: an A/A interleaved-min experiment on this
    shared-core box shows a ±9% noise floor on the ms-scale step —
    loop-level A/B timing cannot resolve a 1-2% bound, it can only
    flap. The contract rows therefore measure the HOOKS directly
    (thousands of calls against a settled loss / armed lanes — they
    are µs-scale, trivially resolvable) and express the exact per-step
    addition as a percentage of the interleaved median step time; the
    wall-clock A/B rows stay as informative context. A
    flight-recorder capture is also timed (informative): the one-off
    cost of producing a bundle at the moment of failure."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(23)
    rng = np.random.RandomState(23)
    net = gluon.nn.HybridSequential(prefix="bench_diag_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                      # compile + settle
        float(np.asarray(step(x, y)))

    def one(per_step, i):
        t0 = time.perf_counter()
        loss = step(x, y)
        float(np.asarray(loss))             # close the step like a real loop
        per_step(i, loss)                   # cost under contract
        return time.perf_counter() - t0

    from mxnet_tpu.telemetry import watchdog as _wdmod

    noop = lambda i, loss: None             # noqa: E731

    # Informative wall rows: interleaved (alternating pair order, so
    # neither config owns a slot a periodic background load could
    # systematically tax), each config's median. Expect these to agree
    # within this box's noise floor — the contract rows below are the
    # resolvable measurement.
    guard = telemetry.NumericGuard(every=1)
    check = lambda i, loss: guard.check_loss(loss, step=i)  # noqa: E731
    watchdog = telemetry.HangWatchdog(min_deadline_s=30.0,
                                      poll_s=0.25).start()
    base_t, guard_t = [], []
    try:
        for i in range(30):
            for which in ((0, 1) if i % 2 == 0 else (1, 0)):
                if which == 0:
                    base_t.append(one(noop, i))
                else:
                    guard_t.append(one(check, i))
    finally:
        watchdog.close()
    base_ms = sorted(base_t)[len(base_t) // 2] * 1e3
    guard_ms = sorted(guard_t)[len(guard_t) // 2] * 1e3
    _emit("diagnostics_step_ms_base", round(base_ms, 3), "ms")
    _emit("diagnostics_step_ms_guarded_watchdogged",
          round(guard_ms, 3), "ms")

    # CONTRACT: numeric guard. Per step (every=1 cadence) the guard
    # adds exactly one check_loss call; measure it directly against a
    # settled loss (the real loop checks a loss it reads anyway).
    loss = step(x, y)
    float(np.asarray(loss))
    reps = 2000
    t0 = time.perf_counter()
    for i in range(reps):
        guard.check_loss(loss, step=i)
    check_ms = (time.perf_counter() - t0) / reps * 1e3
    _emit("numeric_guard_check_ms", round(check_ms, 5), "ms")
    _emit("numeric_guard_step_overhead_pct",
          round(check_ms / base_ms * 100.0, 3), "%")

    # CONTRACT: idle watchdog. Per step the lanes add one begin+end
    # pair; the 4 Hz scan thread adds scan cost amortized over the
    # steps that fit in a poll interval.
    t0 = time.perf_counter()
    for _ in range(reps):
        _wdmod.begin("step")
        _wdmod.end("step")
    hb_ms = (time.perf_counter() - t0) / reps * 1e3
    scanner = telemetry.HangWatchdog(min_deadline_s=30.0, poll_s=0.25)
    t0 = time.perf_counter()
    for _ in range(reps):
        scanner.check()
    scan_ms = (time.perf_counter() - t0) / reps * 1e3
    scan_per_step_ms = scan_ms * (base_ms / 1e3) / scanner.poll_s
    wd_step_ms = hb_ms + scan_per_step_ms
    _emit("watchdog_heartbeat_ms", round(hb_ms, 5), "ms")
    _emit("watchdog_scan_ms", round(scan_ms, 5), "ms")
    _emit("watchdog_idle_overhead_pct",
          round(wd_step_ms / base_ms * 100.0, 3), "%")

    # Bundle capture cost (off the hot path — paid once per rate-limited
    # anomaly, at the moment of failure).
    diag_dir = tempfile.mkdtemp(prefix="bench_diag_")
    try:
        recorder = telemetry.FlightRecorder(diag_dir, rank=0)
        t0 = time.perf_counter()
        path = recorder.capture("bench", "diagnostics bench capture")
        capture_ms = (time.perf_counter() - t0) * 1e3
        size_kb = os.path.getsize(path) / 1e3 if path else 0.0
        _emit("diag_bundle_capture_ms", round(capture_ms, 3), "ms")
        _emit("diag_bundle_size_kb", round(size_kb, 1), "KB")
    finally:
        shutil.rmtree(diag_dir, ignore_errors=True)


def _healthplane_rows():
    """Health-plane section (ISSUE 8): what operating the pod from
    outside costs the step path. THE CONTRACT ROW:
    push_export_step_overhead_pct <= 1 — a PushExporter snapshotting
    the whole registry and handing it to the transport every 10 steps
    (the gateway hop itself is network time off the critical path; an
    in-memory transport isolates the render+buffer cost the LOOP pays).

    Measurement discipline (the diagnostics-section rule): this box's
    ms-scale step has a ±9% A/B noise floor — a 1% bound is resolved by
    measuring the HOOK directly (hundreds of push() calls against the
    live registry) and expressing the amortized per-step cost at the
    every-10-steps cadence as a percentage of the median step; the
    wall-clock A/B row stays as informative context. Informative:
    health_endpoint_probe_ms — wall time of one GET /healthz against a
    live MetricsServer with the HealthPlane mounted (an orchestrator's
    liveness probe; served off-thread, so this is probe latency, not
    step cost)."""
    import urllib.request

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.telemetry import export
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(29)
    rng = np.random.RandomState(29)
    net = gluon.nn.HybridSequential(prefix="bench_hp_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                      # compile + settle
        float(np.asarray(step(x, y)))

    iters = 50

    def timed(per_step):
        times = []
        for i in range(iters):
            t0 = time.perf_counter()
            loss = step(x, y)
            float(np.asarray(loss))
            per_step(i)                     # cost under contract
            times.append(time.perf_counter() - t0)
        return times

    def _mean(ts):
        return sum(ts) / len(ts)

    base = timed(lambda i: None)

    sunk = []
    exporter = export.PushExporter(
        "http://bench.invalid:9091", interval_s=1e9,
        transport=lambda url, body: sunk.append(len(body)))
    pushed = timed(lambda i: exporter.push() if i % 10 == 0 else None)

    base_mean_ms = _mean(base) * 1e3
    base_med_ms = sorted(base)[len(base) // 2] * 1e3
    push_mean_ms = _mean(pushed) * 1e3
    _emit("healthplane_step_ms_base", round(base_mean_ms, 3), "ms")
    _emit("healthplane_step_ms_push_exported",
          round(push_mean_ms, 3), "ms")

    # THE CONTRACT ROW: direct hook measurement — render + bounded
    # buffer + in-memory transport per push, amortized over the
    # every-10-steps cadence against the median step.
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        exporter.push()
    push_ms = (time.perf_counter() - t0) / reps * 1e3
    _emit("push_export_snapshot_ms", round(push_ms, 4), "ms")
    _emit("push_export_step_overhead_pct",
          round(push_ms / 10.0 / base_med_ms * 100.0, 3), "%")

    # Probe latency against a real endpoint (informative).
    plane = telemetry.healthplane.HealthPlane()
    server = telemetry.start_http_server(0, health=plane)
    try:
        url = "http://%s:%d/healthz" % server.server_address
        urllib.request.urlopen(url, timeout=10).read()   # warm
        probes = []
        for _ in range(20):
            t0 = time.perf_counter()
            urllib.request.urlopen(url, timeout=10).read()
            probes.append(time.perf_counter() - t0)
        _emit("health_endpoint_probe_ms",
              round(sorted(probes)[len(probes) // 2] * 1e3, 3), "ms")
    finally:
        server.close()


def _profiling_rows():
    """Profiling section (ISSUE 12): what always-on continuous
    profiling costs the step path, plus the attribution plane's
    phase rows. THE CONTRACT ROW:
    continuous_profiler_step_overhead_pct <= 1 — the sampler at its
    default rate (MXNET_PROFILE_HZ) against the step path.

    Measurement discipline (the diagnostics/healthplane-section rule):
    this box's ms-scale step has a ±9% A/B noise floor, so the 1% bound
    is resolved by measuring the HOOK directly — hundreds of
    ``sample()`` calls against the live thread set — and expressing
    per-sample cost × default Hz as a percentage of wall time (the
    sampler's steady-state duty cycle; its window folding is part of
    the sampled call). The sampler-on vs sampler-off wall A/B stays as
    informative context. Also informative: attribution-derived phase
    shares + bound cause over an attributed run (device spans on, so
    each step is host-synchronous there — that bracket is attribution's
    documented price, not the profiler's)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.telemetry import attribution as tattr
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(31)
    rng = np.random.RandomState(31)
    net = gluon.nn.HybridSequential(prefix="bench_prof_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                  # compile + settle
        float(np.asarray(step(x, y)))

    iters = 50

    def timed():
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            loss = step(x, y)
            float(np.asarray(loss))
            times.append(time.perf_counter() - t0)
        return times

    base = timed()
    profiler = telemetry.ContinuousProfiler().start()
    profiled = timed()
    base_med_ms = sorted(base)[len(base) // 2] * 1e3
    prof_med_ms = sorted(profiled)[len(profiled) // 2] * 1e3
    _emit("profiling_step_ms_base", round(base_med_ms, 3), "ms")
    _emit("profiling_step_ms_sampled", round(prof_med_ms, 3), "ms")
    _emit("continuous_profiler_step_overhead_ab_pct",
          round((prof_med_ms - base_med_ms) / base_med_ms * 100.0,
                3), "%")

    # THE CONTRACT ROW: direct hook measurement — per-sample
    # capture+fold cost x the default sampling rate = the sampler's
    # steady-state share of wall time.
    reps = 300
    t0 = time.perf_counter()
    for _ in range(reps):
        profiler.sample()
    per_sample_s = (time.perf_counter() - t0) / reps
    profiler.close()
    _emit("continuous_profiler_sample_ms",
          round(per_sample_s * 1e3, 4), "ms")
    _emit("continuous_profiler_step_overhead_pct",
          round(per_sample_s * profiler.hz * 100.0, 3), "%")

    # Attribution (informative): phase shares + bound cause over an
    # attributed window.
    attr = telemetry.StepAttribution(interval_s=0.0)
    try:
        attr.update()                   # drain the span backlog
        for _ in range(20):
            float(np.asarray(step(x, y)))
        attr.update()
        shares = attr.last_shares or {}
        for phase in tattr.PHASES:
            _emit("step_phase_share[%s]" % phase,
                  round(shares.get(phase, 0.0), 4), "share")
        _emit("step_bound_cause", attr.bound_cause or "unknown",
              "cause")
    finally:
        attr.close()


def _goodput_rows():
    """Goodput section (ISSUE 20): does the ledger's category set
    actually close over wall-clock, and what does keeping it cost the step
    path. THE CONTRACT ROWS: goodput_closure_pct <= 2 (booked seconds
    may overcount wall-clock — the same second claimed by two sources —
    by at most the default tolerance, over a real attributed TrainStep
    run) and goodput_accounting_step_overhead_pct <= 1 (ledger
    bookkeeping on the step path at the default commit cadence).

    Measurement discipline (the diagnostics-section rule): the ms-scale
    step's ±9% A/B noise floor cannot resolve a 1% bound, so the
    overhead row measures the HOOKS directly — thousands of off-cadence
    ``tick()`` calls (a step-watermark write and a clock compare) plus
    timed full ``commit()`` folds amortized over the default 30 s
    cadence — and expresses the sum as a percentage of the median step.
    Informative rows: the run's goodput fraction and each category's
    share of wall-clock."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.telemetry import goodput as tgp
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(37)
    rng = np.random.RandomState(37)
    net = gluon.nn.HybridSequential(prefix="bench_gp_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                      # compile + settle
        float(np.asarray(step(x, y)))

    ldir = tempfile.mkdtemp(prefix="bench_goodput_")
    attr = telemetry.StepAttribution(interval_s=0.0)
    try:
        attr.update()                       # drain the span backlog so
        # the ledger's cursors start at "now", not at whatever earlier
        # bench sections left in the phase counters.
        ledger = tgp.GoodputLedger(directory=ldir, rank=0,
                                   interval_s=0.0, attribution=attr)
        iters = 40
        times = []
        for i in range(iters):
            t0 = time.perf_counter()
            loss = step(x, y)
            float(np.asarray(loss))
            times.append(time.perf_counter() - t0)
            ledger.tick(step=i)
        snap = ledger.snapshot(serving=False)
        med_step_s = sorted(times)[len(times) // 2]

        # THE CONTRACT ROW (<= 2): closure — overcounted seconds as a
        # percentage of this run's wall-clock. Idle is derived, so the
        # only way to miss closure is double-booking.
        _emit("goodput_closure_pct", round(snap["closure_pct"], 3), "%")
        _emit("goodput_fraction", round(snap["goodput_ratio"], 4),
              "share")
        wall = snap["wall_s"] or 1.0
        for cat in tgp.CATEGORIES:
            _emit("goodput_share[%s]" % cat,
                  round(snap["categories"].get(cat, 0.0) / wall, 4),
                  "share")

        # THE CONTRACT ROW (<= 1): direct hook measurement. Off-cadence
        # tick cost x 1 call/step, plus a full fold+commit amortized
        # over the default commit interval.
        ledger.interval_s = 3600.0          # ticks below never commit
        reps = 5000
        t0 = time.perf_counter()
        for r in range(reps):
            ledger.tick(step=iters + r)
        per_tick_s = (time.perf_counter() - t0) / reps
        commits = 5
        t0 = time.perf_counter()
        for _ in range(commits):
            ledger.commit()
        per_commit_s = (time.perf_counter() - t0) / commits
        from mxnet_tpu import env as _env

        default_interval = float(_env.get("MXNET_GOODPUT_INTERVAL_S"))
        amortized_s = per_tick_s + per_commit_s * (
            med_step_s / max(default_interval, 1e-9))
        _emit("goodput_tick_us", round(per_tick_s * 1e6, 3), "us")
        _emit("goodput_commit_ms", round(per_commit_s * 1e3, 3), "ms")
        _emit("goodput_accounting_step_overhead_pct",
              round(amortized_s / med_step_s * 100.0, 3), "%")
        ledger.close(commit=False)
    finally:
        attr.close()
        shutil.rmtree(ldir, ignore_errors=True)


def _compile_accounting_rows():
    """Compile-accounting rows (the ROADMAP direction-2 acceptance
    baseline): per-site executable-cache-fill count and total seconds
    accumulated by mx_compile_seconds{site} over THIS bench run. Two
    runs' outputs diff with `bench.py --compare A.json B.json` — a
    persistent compile cache is accepted when the second run's counts
    drop to ~0."""
    from mxnet_tpu.telemetry import memstats

    for site, rec in sorted(memstats.compile_stats().items()):
        _emit("compile_count[%s]" % site, rec["count"], "compiles")
        _emit("compile_seconds[%s]" % site, round(rec["total_s"], 3),
              "s")


def _load_rows(path):
    """Parse one bench output (JSON row per line; non-JSON lines — e.g.
    stderr interleave — are skipped) into {metric: row}."""
    rows = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                rows[rec["metric"]] = rec
    return rows


def compare(a_path, b_path):
    """`bench.py --compare A.json B.json`: emit per-site compile
    count/seconds DELTAS (B - A) from the two runs' compile-accounting
    rows. This is the acceptance measurement for recompile-elimination
    work: a persistent compile cache must drive every
    compile_count_delta row to -count (second run compiles nothing).
    Returns 0 when both files had accounting rows."""
    import re as _re

    a, b = _load_rows(a_path), _load_rows(b_path)
    # Perf-contract deltas first: the step-hot-path rows two runs are
    # most often compared on (overlap efficiency, fused speedup).
    for metric, unit in (("fused_overlap_efficiency", "share"),
                         ("trainer_fused_update_speedup", "x"),
                         ("gateway_swap_dropped_requests", "req"),
                         ("gateway_protected_p99_ms", "ms"),
                         ("continuous_batching_tokens_per_sec_speedup",
                          "x"),
                         ("decode_steady_state_retraces", "compiles"),
                         ("goodput_closure_pct", "%"),
                         ("goodput_accounting_step_overhead_pct", "%"),
                         ("goodput_fraction", "share")):
        if metric in a or metric in b:
            va = float(a.get(metric, {}).get("value", 0) or 0)
            vb = float(b.get(metric, {}).get("value", 0) or 0)
            print(json.dumps({"metric": metric + "_delta",
                              "value": round(vb - va, 4), "unit": unit,
                              "a": va, "b": vb}), flush=True)
    row_re = _re.compile(r"^compile_(count|seconds)\[(.+)\]$")
    sites = {}
    for metric in list(a) + list(b):
        m = row_re.match(metric)
        if m:
            sites.setdefault(m.group(2), set()).add(m.group(1))
    if not sites:
        print(json.dumps({"metric": "compile_compare_error", "value": 0,
                          "unit": "",
                          "detail": "no compile_count[site]/"
                                    "compile_seconds[site] rows in "
                                    "either input"}), flush=True)
        return 1
    total_count = total_s = 0.0
    for site in sorted(sites):
        for kind, unit in (("count", "compiles"), ("seconds", "s")):
            metric = "compile_%s[%s]" % (kind, site)
            va = float(a.get(metric, {}).get("value", 0) or 0)
            vb = float(b.get(metric, {}).get("value", 0) or 0)
            delta = vb - va
            if kind == "count":
                total_count += delta
            else:
                total_s += delta
            print(json.dumps({
                "metric": "compile_%s_delta[%s]" % (kind, site),
                "value": round(delta, 3), "unit": unit,
                "a": va, "b": vb}), flush=True)
    print(json.dumps({"metric": "compile_count_delta_total",
                      "value": round(total_count, 3),
                      "unit": "compiles"}), flush=True)
    print(json.dumps({"metric": "compile_seconds_delta_total",
                      "value": round(total_s, 3), "unit": "s"}),
          flush=True)
    return 0


def _data_pipeline_rows():
    """Data pipeline section (mxnet_tpu.data, ISSUE 6): per-batch decode
    cost, prefetch overlap, and the step-path input-stall fraction
    derived from the existing step/data_put trace spans.

    THE CONTRACT ROW: data_prefetch_hidden_decode_pct >= 90 — when the
    training step takes at least as long as a batch decodes, the decode
    pool + double-buffered prefetcher must hide >= 90% of the decode
    time (the consumer's wait per batch is <= 10% of the serial decode
    cost)."""
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import data, gluon, recordio, telemetry
    from mxnet_tpu.parallel import TrainStep, make_mesh
    from mxnet_tpu.telemetry import trace

    mx.random.seed(29)
    rng = np.random.RandomState(29)
    batch = 64        # big enough that fixed per-batch handoff cost is
    shape = (3, 48, 48)  # noise against the ~75ms decode it must hide

    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "ds.rec")
        idx = os.path.join(td, "ds.idx")
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for i in range(256):
            img = (rng.rand(56, 56, 3) * 255).astype(np.uint8)
            w.write_idx(i, recordio.pack_img(
                recordio.IRHeader(0, float(i % 4), i, 0), img,
                img_fmt=".jpg"))
        w.close()

        def make_pipe(prefetch):
            return data.DataPipeline(
                data.RecordDataset([rec]),
                data.ImageRecordDecoder(shape, rand_crop=True,
                                        rand_mirror=True),
                batch_size=batch, shuffle=True, seed=29, num_shards=1,
                shard_index=0, decode_threads=4, prefetch=prefetch,
                place=False)

        # Serial decode cost per batch (median): no prefetch thread, the
        # consumer pays the full pool-fed decode latency inline.
        with make_pipe(prefetch=0) as pipe:
            n = pipe.batches_per_epoch
            for _ in range(n):                  # warm page cache + pool
                next(pipe)
            costs = []
            for _ in range(2 * n):
                t0 = time.perf_counter()
                next(pipe)
                costs.append(time.perf_counter() - t0)
            decode_ms = sorted(costs)[len(costs) // 2] * 1e3

        # Prefetched: the consumer "trains" for >= the decode cost per
        # batch; its residual blocking wait (median) is what prefetch
        # failed to hide.
        step_s = decode_ms / 1e3 * 1.5
        with make_pipe(prefetch=2) as pipe:
            next(pipe)                          # spin the stages up
            time.sleep(step_s)
            waits = []
            for _ in range(2 * pipe.batches_per_epoch):
                t0 = time.perf_counter()
                next(pipe)
                waits.append(time.perf_counter() - t0)
                time.sleep(step_s)              # the simulated step
            wait_ms = sorted(waits)[len(waits) // 2] * 1e3

        hidden_pct = (1.0 - wait_ms / decode_ms) * 100.0
        _emit("data_decode_ms_per_batch", round(decode_ms, 3), "ms")
        _emit("data_prefetch_wait_ms_per_batch", round(wait_ms, 3), "ms")
        # THE CONTRACT ROW (>= 90).
        _emit("data_prefetch_hidden_decode_pct", round(hidden_pct, 2), "%")

        # Input-stall fraction of a REAL step loop, from the spans the
        # subsystems already emit (train_step::step / train_step::
        # data_put / data::wait) — the pod-observability view of "is
        # the input pipeline the ceiling?".
        net = gluon.nn.HybridSequential(prefix="bench_data_")
        net.add(gluon.nn.Flatten())
        net.add(gluon.nn.Dense(64, activation="relu",
                               in_units=int(np.prod(shape)),
                               prefix="fc1_"))
        net.add(gluon.nn.Dense(4, in_units=64, prefix="fc2_"))
        net.initialize(mx.init.Xavier())
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05},
                         mesh=make_mesh())
        prev = telemetry.set_enabled(True)
        try:
            with make_pipe(prefetch=2) as pipe:
                b = next(pipe)                  # compile outside the trace
                float(np.asarray(step(b.data[0], b.label[0])))
                trace.clear()
                for _ in range(2 * pipe.batches_per_epoch):
                    b = next(pipe)
                    float(np.asarray(step(b.data[0], b.label[0])))
                stall = data.stall_fraction()
        finally:
            telemetry.set_enabled(prev)
        _emit("data_input_stall_fraction", round(stall, 4), "fraction")


def _trainer_rows():
    """Trainer section (mxnet_tpu.fused_update): imperative update cost,
    per-param loop vs fused multi-tensor apply, at 10/100/1000
    parameters. The timed window is `trainer.step` with gradients
    already in place — exactly the O(num_params) host cost the fused
    path collapses to O(1) dispatches. THE CONTRACT ROW:
    trainer_fused_update_speedup >= 2x at 1000 params.

    CPU-backend honesty (the checkpoint-section discipline): on a
    shared-core CPU "device" the loop's many small executables and the
    fused path's one large executable contend for the same cores, so
    the measured ratio UNDERSTATES the win on a real accelerator, where
    per-launch host latency dominates and the fused path pays it once
    instead of N times.
    Each row ends with a host readback of one parameter so async
    dispatch can't leak work past the timer."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    def build(n, fused):
        rng = np.random.RandomState(17)
        params = []
        for k in range(n):
            p = gluon.Parameter("bench_fused_%d_%s_%d"
                                % (n, fused, k), shape=(64,))
            p.initialize(init=mx.init.Constant(0.0))
            p.set_data(nd.array(rng.randn(64).astype(np.float32)))
            params.append(p)
        trainer = gluon.Trainer(params, "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9},
                                fused=fused)
        for p in params:
            p.grad()[:] = rng.randn(64).astype(np.float32)
        return params, trainer

    def paired_ms(n, iters):
        """INTERLEAVED loop/fused timing: the two paths alternate
        step-by-step through the same contention regime, then each
        reports its best-of-N (the test_perf_evidence discipline) — a
        background burst on this shared-core box hits both paths
        instead of silently taxing whichever ran second."""
        lp, ltr = build(n, False)
        fp, ftr = build(n, True)
        for _ in range(3):                  # compile + settle
            ltr.step(1)
            ftr.step(1)
        lp[0].data().asnumpy()
        fp[0].data().asnumpy()
        lt, ft = [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            ltr.step(1)
            lp[-1].data().wait_to_read()
            lt.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            ftr.step(1)
            fp[-1].data().wait_to_read()
            ft.append(time.perf_counter() - t0)
        return min(lt) * 1e3, min(ft) * 1e3

    speedup_1000 = None
    for n, iters in ((10, 30), (100, 20), (1000, 16)):
        loop_ms, fused_ms = paired_ms(n, iters)
        _emit("trainer_step_ms_loop_p%d" % n, round(loop_ms, 3), "ms")
        _emit("trainer_step_ms_fused_p%d" % n, round(fused_ms, 3), "ms")
        if n == 1000:
            speedup_1000 = loop_ms / fused_ms
    # THE CONTRACT ROW: at 1000 params the coalesced apply must beat the
    # per-param loop by >= 2x — the enforced floor; the target since the
    # overlap work (ISSUE 13) is >= 3x, which this box typically
    # measures (the loop pays 1000 dispatches, the fused path pays 1).
    _emit("trainer_fused_update_speedup", round(speedup_1000, 2), "x")


def _trainer_overlap_rows():
    """Comm/compute overlap section (ISSUE 13): the fused step's
    pipelined reduce->apply (bucket i applies while bucket i+1 is
    still reducing). THE CONTRACT ROW: fused_overlap_efficiency >= 0.30
    — at the default-shaped workload at least 30% of total reduce time
    must be hidden behind the apply stream.

    CPU-backend honesty (the trainer-section discipline): this box has
    no DCN, so the transport is a latency-injecting local store (a
    sleep per push/pull leg standing in for the worker->server
    round-trip), and the compute that hides it is the HOST side of the
    apply stream (unflatten + fused dispatch + per-param commit). On a
    real pod the same pipeline additionally hides transport behind
    device compute, so this measurement *understates* the win. The
    efficiency is computed from the runtime's own accounting
    (mx_trainer_reduce_{seconds,hidden_seconds}_total deltas), i.e. the
    number an operator would scrape — and the serial (depth=0) row on
    the identical workload pins the no-overlap baseline near 0."""
    import time as _t

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu.telemetry import metrics as tm

    lat = 0.0012                  # one simulated DCN round-trip (s)

    class LatencyStore(kvs.KVStoreLocal):
        """Local store + synthetic wire latency per push/pull leg."""

        @property
        def type(self):
            # "dist" in the name makes the Trainer treat this like a
            # real multi-process store (kvstore engaged on 1 context).
            return "dist_bench_latency"

        def push(self, key, value, priority=0):
            _t.sleep(lat / 2)
            super().push(key, value, priority)

        def pull(self, key, out=None, priority=0, ignore_sparse=True):
            _t.sleep(lat / 2)
            super().pull(key, out=out, priority=priority,
                         ignore_sparse=ignore_sparse)

    saved = {k: os.environ.get(k) for k in
             ("MXNET_FUSED_OVERLAP_DEPTH", "MXNET_FUSED_BUCKET_MB")}

    def run(depth, steps=6, n=800, size=1024, clip=None):
        os.environ["MXNET_FUSED_OVERLAP_DEPTH"] = str(depth)
        os.environ["MXNET_FUSED_BUCKET_MB"] = "1"   # ~4 buckets
        rng = np.random.RandomState(5)
        params = []
        for k in range(n):
            p = gluon.Parameter("ov_bench_%d_%d" % (depth, k),
                                shape=(size,))
            p.initialize(init=mx.init.Constant(0.0))
            p.set_data(nd.array(rng.randn(size).astype(np.float32)))
            params.append(p)
        trainer = gluon.Trainer(
            params, "sgd", {"learning_rate": 0.05, "momentum": 0.9},
            kvstore=LatencyStore(device_mode=True),
            update_on_kvstore=False, global_norm_clip=clip)
        for p in params:
            p.grad()[:] = rng.randn(size).astype(np.float32)
        red = tm.REGISTRY.counter("mx_trainer_reduce_seconds_total", "")
        hid = tm.REGISTRY.counter(
            "mx_trainer_reduce_hidden_seconds_total", "")
        trainer.step(1)                     # warmup: compile + init
        params[-1].data().asnumpy()
        r0, h0 = red.value, hid.value
        t0 = _t.perf_counter()
        for _ in range(steps):
            trainer.step(1)
        params[-1].data().asnumpy()
        wall = (_t.perf_counter() - t0) / steps * 1e3
        r, h = red.value - r0, hid.value - h0
        return wall, r, h

    try:
        wall_s, red_s, hid_s = run(0)
        # The serial-ACCOUNTING row must exercise the pipelined step's
        # own hidden-time arithmetic, not the legacy path (which never
        # touches the counters): a no-op global-norm clip routes
        # depth=0 through _step_pipelined, where every reduce second
        # is inline main-thread wait. A broken accounting that
        # reported hidden time serially WOULD trip this row.
        _, red_s2, hid_s2 = run(0, clip=1e12)
        eff_serial = hid_s2 / red_s2 if red_s2 > 0 else 0.0
        wall_o, red_o, hid_o = run(4)
        eff = hid_o / red_o if red_o > 0 else 0.0
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    _emit("trainer_overlap_step_ms_serial", round(wall_s, 3), "ms")
    _emit("trainer_overlap_step_ms_depth4", round(wall_o, 3), "ms")
    _emit("fused_overlap_efficiency_serial", round(eff_serial, 4), "share")
    # THE CONTRACT ROW: >= 0.30 of reduce time hidden behind applies.
    _emit("fused_overlap_efficiency", round(eff, 4), "share")


def _checkpoint_rows():
    """Checkpoint section (mxnet_tpu.checkpoint): per-step wall time
    with no checkpointing, with the reference-style blocking sync save
    every step, and with the async CheckpointManager save every step.
    The async row is the subsystem's contract: snapshot-to-host at the
    step boundary, serialize+commit on a background thread — overhead
    must stay under 10% of the no-checkpoint step time."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.parallel import TrainStep, make_mesh

    mx.random.seed(11)
    rng = np.random.RandomState(11)
    net = gluon.nn.HybridSequential(prefix="bench_ckpt_")
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=784,
                           prefix="fc1_"))
    net.add(gluon.nn.Dense(1024, activation="relu", in_units=1024,
                           prefix="fc2_"))
    net.add(gluon.nn.Dense(10, in_units=1024, prefix="fc3_"))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.05,
                                       "momentum": 0.9},
                     mesh=make_mesh())
    x = rng.rand(256, 784).astype(np.float32)
    y = rng.randint(0, 10, 256)
    for _ in range(3):                      # compile + settle
        float(np.asarray(step(x, y)))

    # Median over a window long enough that the handful of steps a
    # background commit overlaps (CPU bench: writer and "device" share
    # cores) stay in the minority; on a real TPU the overlap vanishes.
    iters = 40

    def timed(save_fn):
        times = []
        for i in range(iters):
            t0 = time.perf_counter()
            loss = step(x, y)
            save_fn(i)
            float(np.asarray(loss))         # close the step like a real loop
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    base_ms = timed(lambda i: None) * 1e3

    d_sync = tempfile.mkdtemp(prefix="bench_ckpt_sync_")
    d_async = tempfile.mkdtemp(prefix="bench_ckpt_async_")
    d_async5 = tempfile.mkdtemp(prefix="bench_ckpt_async5_")
    d_async10 = tempfile.mkdtemp(prefix="bench_ckpt_async10_")
    try:
        # Reference-style blocking save EVERY step (the old
        # save_checkpoint behavior, worst case).
        m_sync = CheckpointManager(d_sync, keep_last=2)
        sync_ms = timed(lambda i: m_sync.save(
            i, step.state_dict(), sync=True)) * 1e3
        m_sync.close()

        # Async every step: stress row — the writer thread never drains
        # between saves, so on a CPU "device" it contends for cores.
        # save_path_costs captures the SYNCHRONOUS portion each save
        # adds to the step (snapshot device_get + enqueue) — the
        # contract quantity: everything else runs off the step path.
        m_async = CheckpointManager(d_async, keep_last=2)
        save_path_costs = []

        def _async_save(i):
            t0 = time.perf_counter()
            m_async.save(i, step.state_dict())
            save_path_costs.append(time.perf_counter() - t0)

        async_ms = timed(_async_save) * 1e3
        save_path_ms = sorted(save_path_costs)[len(save_path_costs) // 2] \
            * 1e3
        t0 = time.perf_counter()
        m_async.wait()                      # drain for the commit-rate row
        drain_s = time.perf_counter() - t0
        total_mb = m_async.total_bytes / 1e6
        commit_s = m_async.total_save_seconds
        m_async.close()

        # Cadence rows measured against ONE paired baseline taken
        # immediately before them (the every-1 sections above include
        # sync-save IO and writer drain, so the opening base_ms is
        # minutes stale by now and machine drift would masquerade as
        # checkpoint cost).
        base10_ms = timed(lambda i: None) * 1e3
        m5 = CheckpointManager(d_async5, keep_last=2)
        async5_ms = timed(lambda i: m5.save(i, step.state_dict())
                          if i % 5 == 0 else None) * 1e3
        m5.close()      # drain before the next timed section
        m10 = CheckpointManager(d_async10, keep_last=2)
        async10_ms = timed(lambda i: m10.save(i, step.state_dict())
                           if i % 10 == 0 else None) * 1e3
        m10.close()
    finally:
        shutil.rmtree(d_sync, ignore_errors=True)
        shutil.rmtree(d_async, ignore_errors=True)
        shutil.rmtree(d_async5, ignore_errors=True)
        shutil.rmtree(d_async10, ignore_errors=True)

    _emit("checkpoint_step_ms_none", round(base_ms, 3), "ms")
    _emit("checkpoint_step_ms_sync_every1", round(sync_ms, 3), "ms")
    _emit("checkpoint_step_ms_async_every1", round(async_ms, 3), "ms")
    _emit("checkpoint_step_ms_async_every5", round(async5_ms, 3), "ms")
    _emit("checkpoint_step_ms_none_paired", round(base10_ms, 3), "ms")
    _emit("checkpoint_step_ms_async_every10", round(async10_ms, 3), "ms")
    _emit("checkpoint_sync_overhead_pct_every1",
          round((sync_ms - base_ms) / base_ms * 100.0, 1), "%")
    _emit("checkpoint_async_overhead_pct_every1",
          round((async_ms - base_ms) / base_ms * 100.0, 1), "%")
    _emit("checkpoint_async_overhead_pct_every5",
          round((async5_ms - base10_ms) / base10_ms * 100.0, 1), "%")
    _emit("checkpoint_async_overhead_pct_every10",
          round((async10_ms - base10_ms) / base10_ms * 100.0, 1), "%")
    # THE CONTRACT ROW: what an async save synchronously adds to the
    # step path (host snapshot + enqueue), as % of the step — even at
    # every-step cadence this must stay <10%. The wall-clock rows above
    # additionally include background-writer CPU contention, a
    # shared-core bench artifact (the writer runs nice+10 and on a real
    # accelerator overlaps device compute instead of stealing it).
    _emit("checkpoint_async_step_path_ms", round(save_path_ms, 3), "ms")
    _emit("checkpoint_async_step_path_overhead_pct",
          round(save_path_ms / base_ms * 100.0, 1), "%")
    if commit_s > 0:
        _emit("checkpoint_commit_mb_per_s", round(total_mb / commit_s, 1),
              "MB/s")
    _emit("checkpoint_async_drain_ms", round(drain_s * 1e3, 3), "ms")


def _acquire_device():
    """The one chip this process measures on. No TPU is an error: a
    number taken on the CPU must never appear under a device row."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench.py needs a TPU; JAX found platform %r"
                         % dev.platform)
    return dev


def main():
    import argparse
    import sys
    import traceback

    parser = argparse.ArgumentParser(
        description="mxnet_tpu benchmark (JSON row per line); "
                    "--compare diffs two runs' compile accounting.")
    parser.add_argument("--compare", nargs=2,
                        metavar=("A.json", "B.json"),
                        help="emit per-site compile count/seconds "
                             "deltas (B - A) from two bench outputs "
                             "and exit (no device needed)")
    args = parser.parse_args()
    if args.compare:
        return compare(args.compare[0], args.compare[1])

    failed = []

    def section(name, fn):
        # Sections are independent: one that fails is reported and the
        # run goes on, but the exit code says so at the end.
        try:
            fn()
        except Exception:
            print("bench %s failed:" % name, file=sys.stderr)
            traceback.print_exc()
            failed.append(name)

    from mxnet_tpu.compile import enable_jax_cache

    enable_jax_cache()
    dev = _acquire_device()
    peak = _peak_tflops_bf16(dev)
    extra_rows = [
        ("resnet50_v1_infer_img_per_sec_b32_fp32",
         lambda: _infer_rate(32, None, dev), 1076.81, FWD_GFLOP_PER_IMG),
        ("resnet50_v1_infer_img_per_sec_b32_bf16",
         lambda: _infer_rate(32, "bfloat16", dev), 2085.51,
         FWD_GFLOP_PER_IMG),
        ("resnet50_v1_train_img_per_sec_b32_bf16",
         lambda: _train_rate(32, "bfloat16", dev), 298.51,
         TRAIN_GFLOP_PER_IMG),
        ("resnet50_v1_train_img_per_sec_b128_bf16",
         lambda: _train_rate(128, "bfloat16", dev), 363.69,
         TRAIN_GFLOP_PER_IMG),
        ("resnet50_v1_train_img_per_sec_b128_fp32",
         lambda: _train_rate(128, None, dev), 363.69, TRAIN_GFLOP_PER_IMG),
    ]
    for metric, rate_fn, baseline, gflop in extra_rows:
        section("row %s" % metric,
                lambda: _row(metric, rate_fn(), baseline, gflop, peak))
    for name, fn in (
            ("serving", _serving_rows),
            ("serving_gateway", _serving_gateway_rows),
            ("continuous_batching", _continuous_batching_rows),
            ("telemetry", _telemetry_rows),
            ("telemetry_dist", _telemetry_dist_rows),
            ("xtrace", _xtrace_rows),
            ("diagnostics", _diagnostics_rows),
            ("healthplane", _healthplane_rows),
            ("profiling", _profiling_rows),
            ("goodput", _goodput_rows),
            ("data_pipeline", _data_pipeline_rows),
            ("trainer", _trainer_rows),
            ("trainer_overlap", _trainer_overlap_rows),
            ("checkpoint", _checkpoint_rows)):
        section(name, fn)
    # Measure the headline BEFORE the compile accounting so its fresh
    # TrainStep compile (the largest single compile of the run) is in
    # the accounting; its row still prints LAST (the driver parses the
    # final JSON line).
    train32 = _train_rate(32, None, dev)
    # After every section: the accounting covers the whole run.
    section("compile accounting", _compile_accounting_rows)
    _row("resnet50_v1_train_img_per_sec_b32", train32, 298.51,
         TRAIN_GFLOP_PER_IMG, peak)
    if failed:
        print("bench: %d section(s) failed: %s"
              % (len(failed), ", ".join(failed)), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main() or 0)

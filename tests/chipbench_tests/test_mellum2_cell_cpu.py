"""The cell `mellum2-12b-train-s8192` rehearsed on the CPU at tiny widths
(the `root` fixture of test_harness_cpu.py with the sizes that
tests/conftest.py registers): the declaration and the published numbers,
the counts of operations and bytes term by term, steps against the plain
reference through the harness's own check, the check's three controls (a
bf16 router, a window of twice the stated one, the full layers'
`attention_factor` left at 1), the two roofline metrics on synthetic
runs, and every declared per-layer metric's reader on a run that has
nothing to read.
"""
import json
import os

import pytest

from test_harness_cpu import _ROOT, _run, root  # noqa: F401

from chipbench import harness

_CELL = "mellum2-12b-train-s8192"
_CONFIG = "mellum2_12b_a2_5b"
_METRICS = {"swa_flash_bwd_roofline_pct.train": "mx_flash_swa_bwd",
            "swa_flash_fwd_roofline_pct.train": "mx_flash_swa_fwd"}
_SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
           "blob/main/config.json")


def _config():
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           _CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module(_ROOT, "models", "mellum")


def test_declared_with_its_files_and_no_other_cell():
    bench = harness.load_bench(_ROOT)
    cell, wl, cfg = harness.cell_files(_ROOT, bench, _CELL)
    assert (cell["chips"], wl["batch"], wl["dtype"]) == (1, 1, "bfloat16")
    assert (wl["runner"], wl["pool_batches"], wl["read_every"],
            wl["trace_steps"]) == ("train_step_routed", 16, 8, 30)
    assert (cell["traffic"], wl["traffic"]) == ("train-s8192-b1",) * 2
    assert wl["batch"] * cfg["bptt"] == 8192
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert conf["source"] == cfg["source"] == _SOURCE
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == _CONFIG] == [_CELL]
    assert len(cell["why"]) <= 200 and cell["why"] == wl["why"]
    assert "1024 rows" in cell["why"] and "1/8" in cell["why"]
    # what this configuration adds to the benchmark names the new cell
    # alone, and sits at the end of its list
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in set(declared) & set(_METRICS):
        assert declared[name]["workloads"] == [_CELL]
        assert (declared[name]["layer"], declared[name]["moves"],
                declared[name]["source"], declared[name]["unit"]) \
            == ("kernels", "train_rate", "device_trace", "%")
    assert bench["workloads"][-1]["name"] == _CELL
    assert bench["configs"][-1]["name"] == _CONFIG
    # and the host spans and expert gauges that every TrainStep cell with
    # sparse layers reports name it beside cells 3 and 4
    shared = {"host_step_ms.train", "dispatch_ms.train", "data_put_ms.train",
              "moe_load_max_over_mean.train", "moe_buffer_fill_pct.train",
              "moe_overflow_steps.train"}
    for m in bench["per_layer"]:
        assert _CELL not in m.get("workloads", ()) \
            or m["name"] in set(_METRICS) | shared
    for name in shared:
        assert declared[name]["workloads"][-2:] == [
            "qwen3next-80b-a3b-train-s4096", _CELL]


def test_every_published_number_is_in_the_file():
    """The catalog row's `config`, key for key, but the three reduced."""
    pattern = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": pattern * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    cfg = _config()
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # the cut: one period in the published 3:1 order, the floor of 8
    # experts, an eighth of the vocabulary; the 8 chips that share a layer
    # hold all 64 experts between them; the context the config names
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 12288)
    assert cfg["layer_types"][:4] == pattern
    assert cfg["vocab_size"] * 8 == 98304
    assert cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"] \
        == 64
    assert cfg["bptt"] == 8192 == cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"]
    assert cfg["capacity_factor"] <= 2.5
    assert cfg["capacity_factor"] % 0.25 == 0
    for key in ("qk_norm", "window_edge", "yarn", "intermediate_size",
                "not_run", "initializer_range", "capacity_factor", "router",
                "optimizer", "precision", "input", "recomputation"):
        assert cfg["assumed"][key]
    assert "MTP" in cfg["assumed"]["not_run"]
    assert "no key" in cfg["assumed"]["qk_norm"]


def _brute_force_pairs(seq, window):
    return sum(1 for i in range(seq) for j in range(seq)
               if j <= i and (window is None or i - j < window))


def test_flops_per_item_term_by_term(model):
    """MFLOP a token forward at 8,192 tokens: a layer's projections 42.5
    (21.2 M multiply-accumulates), the full layer's scores and values
    67.1, a sliding layer's 15.7, a layer's router 0.3 and held experts
    12.4, the head 56.6: 391.5 in all, 9.62 TFLOP a training step."""
    cfg = _config()
    proj, full = model._attention_macs_per_token(cfg, sliding=False)
    same, sliding = model._attention_macs_per_token(cfg, sliding=True)
    assert proj == same == 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    assert proj / 1e6 == pytest.approx(21.23, abs=0.01)
    assert 2 * full / 1e6 == pytest.approx(67.1, abs=0.05)
    assert 2 * sliding / 1e6 == pytest.approx(15.73, abs=0.01)
    # exact pairs, no block rounding
    assert model.attention_pairs(8192) == 8192 * 8193 // 2
    assert model.attention_pairs(8192, 1024) == 7_864_832
    assert full == model.attention_pairs(8192) / 8192 * 32 * 2 * 128
    assert sliding == 7_864_832 / 8192 * 32 * 2 * 128
    assert full / sliding == pytest.approx(4.27, abs=0.01)
    for seq, window in ((64, None), (64, 7), (64, 64), (64, 100), (96, 32),
                        (33, 1)):
        assert model.attention_pairs(seq, window) \
            == _brute_force_pairs(seq, window), (seq, window)
    moe = model._moe_macs_per_token(cfg)
    assert moe == 2304 * 64 + 1.0 * 3 * 2304 * 896
    assert model._moe_macs_per_token(cfg, buffer=1.5) \
        == 2304 * 64 + 1.5 * 3 * 2304 * 896
    assert model.flops_per_item(cfg) == 3 * 2 * (
        4 * proj + full + 3 * sliding + 4 * moe + 2304 * 12288)
    assert model.flops_per_item(cfg) / 3 / 1e6 == pytest.approx(391.5,
                                                                abs=0.1)
    assert model.flops_per_item(cfg) * 8192 / 1e12 == pytest.approx(9.62,
                                                                    abs=0.01)
    assert [model.is_sliding_layer(cfg, i) for i in range(4)] \
        == [True, True, True, False]
    assert model.held_experts(cfg) == list(range(8))
    assert model.buffer_rows(cfg, 8192) == -(-int(
        cfg["capacity_factor"] * 8192 * 8 * 8 / 64) // 128) * 128
    # attention is three tenths of the step's operations
    attention = full + 3 * sliding
    assert 0.28 < 6 * attention / model.flops_per_item(cfg) < 0.31


def test_kernel_work_counts_the_least_the_mathematics_needs(model):
    cfg = _config()
    work = model.kernel_work(cfg, 1, 512, 512)
    # no choice of blocks changes the count
    assert work == model.kernel_work(cfg, 1, 1024, 128) \
        == model.kernel_work(cfg, 1)
    windowed, full = 7_864_832 * 32, 8192 * 8193 // 2 * 32
    assert work["mx_flash_swa_fwd"][0] == 2 * windowed * 2 * 128
    assert work["mx_flash_swa_bwd"][0] == 2 * windowed * 5 * 128
    assert work["mx_flash_fwd"][0] == 2 * full * 2 * 128
    assert work["mx_flash_bwd"][0] == 2 * full * 5 * 128
    # K, V, dK and dV once a key/value head: q, dO, dQ a query head, the
    # two rows in fp32
    assert work["mx_flash_swa_bwd"][1] == work["mx_flash_bwd"][1] \
        == 8192 * (2 * 128 * (32 * 3 + 4 * 4) + 32 * 8)
    assert work["mx_flash_swa_fwd"][1] == work["mx_flash_fwd"][1] \
        == 8192 * (2 * 128 * (32 * 2 + 4 * 2) + 32 * 8)
    # all four are bound by the arithmetic: over the chip's ridge of 240
    for flops, nbytes in work.values():
        assert flops / nbytes > 240
    # two sequences are twice the work
    assert model.kernel_work(cfg, 2)["mx_flash_swa_bwd"] \
        == tuple(2 * n for n in work["mx_flash_swa_bwd"])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal_against_the_reference(root, trace):
    from mxnet_tpu.telemetry import metrics as tm

    window = tm.REGISTRY.get("mx_flash_attention_window_traced_total")
    result, lines = _run(root, _CELL, trace=trace)
    assert lines[-1 - trace]["problems"] == [], lines[-1 - trace]
    assert result["correct"] is True and result["failed"] == 0
    assert lines[-1 - trace]["compiles_in_window"] == 0
    got = set(result["metrics"])
    if not trace:
        assert got == {"train_rate", "setup_s"}
        return
    # the rooflines need a device plane and stay out of a CPU line
    assert {"import_s.setup", "programs_built.setup"} <= got
    assert not got & set(_METRICS)
    spans = lines[-1]["program_spans_mean_ms"]
    assert {"train_step::dispatch", "train_step::data_put"} <= set(spans)
    # the expert layers' counters cover the new layers with no change
    tm.REGISTRY.collect()
    assert tm.REGISTRY.get("mx_moe_buffer_rows").value > 0
    assert tm.REGISTRY.get("mx_moe_overflow_steps_total").value == 0
    assert tm.REGISTRY.get("mx_softmax_router_traced_total").value >= 4
    # the step's build: three windowed calls to each of the full layer's
    assert window.labels(window="8").value \
        == 3 * window.labels(window="none").value > 0


def _tiny_runner(root, seed, program=None, **over):
    """(the configuration as stated, at the tiny widths with `over`; the
    workload; the runner built from it, or from it with `program`'s keys
    where the program is to depart from what is stated; the model)."""
    import jax

    bench = harness.load_bench(root)
    _, wl, cfg = harness.cell_files(root, bench, _CELL)
    cfg.update(over)
    wl = dict(wl, batch=1, pool_batches=2)
    model = harness.load_module(root, "models", cfg["model"])
    runner = harness.load_module(root, "runners", wl["runner"]).setup(
        dict(cfg, **(program or {})), wl, seed, jax.devices()[:1], model)
    runner.read_loss(runner.step())
    return cfg, wl, runner, model


def _bf16_router(data, weight, top_k=8, norm_topk_prob=True):
    """`softmax_topk_router` with product and softmax in bf16: the
    nearest precision below the configuration's."""
    import jax
    import jax.numpy as jnp

    low = jnp.bfloat16
    prob = jax.nn.softmax(jnp.einsum("th,eh->te", data.astype(low),
                                     weight.astype(low)), axis=-1)
    picked, ids = jax.lax.top_k(prob, top_k)
    picked = picked.astype(jnp.float32)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    counts = jnp.zeros((prob.shape[1],), jnp.int32).at[
        ids.reshape(-1)].add(1)
    return picked, ids.astype(jnp.int32), counts


def _full_layer_without_attention_factor(cfg):
    rope = dict(cfg["rope_parameters"])
    rope["full_attention"] = dict(rope["full_attention"],
                                  attention_factor=1.0)
    return {"rope_parameters": rope}


# At these widths a layer's output is a thousandth of the embedding's
# unless the weights are drawn large, so they are. Per control: the sizes
# at which the CPU can show it, and a limit between the sound reading and
# the control's there (seed 5: 0.039 and 0.29 with every expert held and
# two a token over 128 tokens; 0.011 and 0.61; 0.009 and 0.10).
_CONTROLS = {
    "bf16_router": (dict(num_experts=8, num_experts_per_tok=2, bptt=128),
                    0.1),
    "window_2x": ({}, 0.03),
    "no_attention_factor": (dict(bptt=128, sliding_window=16), 0.03),
}


@pytest.mark.parametrize("control", sorted(_CONTROLS))
def test_check_passes_as_stated_and_refuses_the_control(root, monkeypatch,
                                                        control):
    """The harness's own comparison, on the runner that hands the
    reference what each router saw. As stated the logits differ by
    rounding alone. A program whose router multiplies in bf16, whose
    sliding layers see twice the stated keys, or whose full layers
    leave cos and sin unscaled, is refused by the same limit."""
    from mxnet_tpu.ops import registry

    seed = 2 ** 31 + 5
    if control == "bf16_router":
        # as declared, at the tiny widths: rounding alone, well inside
        cfg, wl, runner, model = _tiny_runner(root, seed)
        facts, problems = harness.check_reference(cfg, wl, seed, runner,
                                                  model)
        assert problems == [], facts
        assert facts["logits_rel_err"] \
            < cfg["check"]["tolerance"]["bfloat16"] / 2
        assert len([k for k in runner.params()
                    if k.endswith("_selected")]) == 4
    sizes, limit = _CONTROLS[control]

    def tiny(program=None):
        made = _tiny_runner(root, 5, program=program,
                            initializer_range=0.3, **sizes)
        made[0]["check"]["tolerance"]["bfloat16"] = limit
        return made

    cfg, wl, runner, model = tiny()
    facts, problems = harness.check_reference(cfg, wl, 5, runner, model)
    assert problems == [], facts
    sound = facts["logits_rel_err"]

    op = registry.OP_REGISTRY["_contrib_softmax_topk_router"]
    program = None
    if control == "bf16_router":
        monkeypatch.setattr(op, "fn", _bf16_router)
    elif control == "window_2x":
        program = {"sliding_window": 2 * cfg["sliding_window"]}
    else:
        program = _full_layer_without_attention_factor(cfg)
    op._jit_cache.clear()
    try:
        cfg, wl, runner, model = tiny(program)
        facts, problems = harness.check_reference(cfg, wl, 5, runner, model)
    finally:
        op._jit_cache.clear()
    assert facts["logits_rel_err"] > 2 * sound, (sound, facts)
    assert any("logits differ" in p for p in problems), facts


def _synthetic_run(ops, steps=30):
    return {"trace": {"devices": 1, "steps": steps, "device_ops": ops},
            "items_per_step": 8192, "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric", sorted(_METRICS))
def test_metric_file_agrees_with_its_reader_on_a_synthetic_run(model,
                                                               metric):
    with open(os.path.join(_ROOT, "chipbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    kernel = _METRICS[metric]
    assert spec["cells"] == [_CELL] and spec["unit"] == "%"
    assert spec["args"]["kernels"] == [kernel]
    assert (spec["args"]["model"], spec["args"]["config"]) \
        == ("mellum", _CONFIG)
    reader = harness.load_module(_ROOT, "readers", spec["reader"])
    flops, nbytes = model.kernel_work(_config(), 1)[kernel]
    least = 30 * max(flops / 197e12, nbytes / 819e9)
    # each of the three sliding layers' calls is one op name; sites at
    # twice and at four times their least time read 50 % and 25 %; the
    # full layer's kernel of the like name is no site of this one
    ops = [["fusion.3", 1.0], [kernel + ".7", 2 * least],
           ["transpose_jvp_%s_.1" % kernel, 4 * least],
           [kernel.replace("_swa", "") + ".2", 1e-9]]
    assert reader.read(_synthetic_run(ops), **spec["args"]) \
        == pytest.approx(37.5)
    assert reader.read(_synthetic_run([["fusion.1", 1.0],
                                       ["mx_flash_bwd.1", 1.0]]),
                       **spec["args"]) is None
    assert reader.read({"trace": None}, **spec["args"]) is None
    # at its least time a site reads 100 %, never more by the count
    assert reader.read(_synthetic_run([[kernel + ".1", least]]),
                       **spec["args"]) == pytest.approx(100.0)


def _declared_metric_and_cell():
    bench = harness.load_bench(_ROOT)
    return [(m["name"], cell["name"]) for m in bench["per_layer"]
            for cell in bench["workloads"]
            if harness.applies(m, cell["name"])]


_NEW_FAMILIES = ("mx_flash_attention_window_traced_total",
                 "mx_rotary_embedding_scaling_traced_total")


@pytest.mark.parametrize("trace", [
    None, {"devices": 0, "steps": 0, "device_ops": [], "busy_ns": 0,
           "window_ns": 0, "programs": 0, "host_ms": {}}],
    ids=["no_trace", "empty_trace"])
@pytest.mark.parametrize("metric,cell", _declared_metric_and_cell())
def test_every_declared_reader_stands_a_run_with_nothing_to_read(
        monkeypatch, metric, cell, trace):
    """What a traced run of a program without this PR's spans, counters
    and kernels hands a reader (PR 36 was refused for a reader that took
    such a run down): every `per_layer` entry of BENCHMARK.json, in every
    cell it applies to, returns None or a number and does not raise."""
    from mxnet_tpu.telemetry import metrics as tm

    collect = tm.REGISTRY.collect
    monkeypatch.setattr(
        tm.REGISTRY, "collect", lambda *a, **k: [
            fam for fam in collect(*a, **k)
            if fam.name not in _NEW_FAMILIES])
    bench = harness.load_bench(_ROOT)
    _, wl, cfg = harness.cell_files(_ROOT, bench, cell)
    with open(os.path.join(_ROOT, "chipbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    reader = harness.load_module(_ROOT, "readers", spec["reader"])
    run = {"phases": {}, "trace": trace, "program_spans_ms": {},
           "items_per_step": wl["batch"] * cfg.get("bptt", 1),
           "flops_per_item": 1.0, "chips": wl["chips"],
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "memory_peak_bytes": 0}
    value = reader.read(run, **spec.get("args", {}))
    assert value is None or (isinstance(value, (int, float))
                             and not isinstance(value, bool))

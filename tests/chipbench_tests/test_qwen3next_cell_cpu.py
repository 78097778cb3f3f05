"""The cell `qwen3next-80b-a3b-train-s4096` rehearsed on the CPU at tiny
widths (the `root` fixture of test_harness_cpu.py with the sizes that
tests/conftest.py registers): the declaration and the published numbers,
the counts of operations and bytes against what ISSUE 34 writes out,
steps against the plain reference through the harness's own check, the
check's controls (a bf16 router is refused by the check; a bf16
delta-rule state by the operator's own comparison, since logits do not
show it), and the two roofline metrics on synthetic runs.
"""
import json
import os

import pytest

from test_harness_cpu import _ROOT, _run, root  # noqa: F401

from chipbench import harness

_CELL = "qwen3next-80b-a3b-train-s4096"
_CONFIG = "qwen3_next_80b_a3b"
_METRICS = {"gdn_roofline_pct.train": ["mx_gdn_fwd", "mx_gdn_bwd"],
            "gqa_flash_bwd_roofline_pct.train": ["mx_flash_bwd"]}


def _config():
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           _CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module(_ROOT, "models", "qwen3_next")


def test_declared_with_its_files_and_no_other_cell():
    bench = harness.load_bench(_ROOT)
    cell, wl, cfg = harness.cell_files(_ROOT, bench, _CELL)
    assert (cell["chips"], wl["batch"], wl["dtype"]) == (1, 1, "bfloat16")
    assert (wl["runner"], wl["pool_batches"], wl["read_every"],
            wl["trace_steps"]) == ("train_step_routed", 16, 8, 30)
    assert wl["batch"] * cfg["bptt"] == 4096
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert conf["source"] == cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == _CONFIG] == [_CELL]
    assert len(cell["why"]) <= 200 and "80 rows" in cell["why"]
    declared = {m["name"]: m for m in bench["per_layer"]}
    # the delta rule's kernels are not among the eight longest device
    # ops of the cell's traced run (PERF.md, PR 34), which is all a
    # reader is handed: its metric keeps its file and waits undeclared
    assert "gdn_roofline_pct.train" not in declared
    name = "gqa_flash_bwd_roofline_pct.train"
    assert declared[name]["workloads"] == [_CELL]
    assert (declared[name]["layer"], declared[name]["moves"],
            declared[name]["source"]) == ("kernels", "train_rate",
                                          "device_trace")


def test_every_published_number_is_in_the_file():
    """The catalog row's `config`, key for key, but the three reduced."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    cfg = _config()
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # the cut: one period in the published 3:1 order, at least 8 experts,
    # an eighth of the vocabulary; the chips that share a layer hold all
    # 512 experts between them
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 18992)
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["num_experts"] in (16, 32)
    assert cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"] \
        == 512
    assert cfg["capacity_factor"] <= 2.0
    assert cfg["capacity_factor"] % 0.25 == 0
    for key in ("initializer_range", "A_log_dt_bias", "storage_order",
                "capacity_factor", "not_run", "optimizer", "precision",
                "input"):
        assert cfg["assumed"][key]
    assert "MTP" in cfg["assumed"]["not_run"]


def test_flops_per_item_is_the_issues_count_term_by_term(model):
    """MFLOP a token forward: 73.3 a Gated DeltaNet layer, of which 5.9
    the delta rule at chunk 64; 88.1 the attention layer, 33.6 of it
    scores at 4,096; 14.3 a layer's router, shared expert and buffer at
    32 held and a factor of 1.5; 77.8 the head: 443 in all, 5.44 TFLOP a
    training step."""
    cfg = dict(_config(), num_experts=32, capacity_factor=1.5)
    proj, rule = model._delta_net_macs_per_token(cfg)
    assert 2 * rule / 1e6 == pytest.approx(5.9, abs=0.01)
    assert 2 * (proj + rule) / 1e6 == pytest.approx(73.3, abs=0.05)
    proj, core = model._attention_macs_per_token(cfg)
    assert 2 * core / 1e6 == pytest.approx(33.6, abs=0.05)
    assert 2 * (proj + core) / 1e6 == pytest.approx(88.1, abs=0.05)
    moe = model._moe_macs_per_token(cfg, buffer=1.5)
    assert 2 * moe / 1e6 == pytest.approx(14.3, abs=0.05)
    assert 2 * cfg["hidden_size"] * cfg["vocab_size"] / 1e6 \
        == pytest.approx(77.8, abs=0.05)
    # the issue's whole: every term with the buffer's rows
    padding = 4 * (moe - model._moe_macs_per_token(cfg))
    forward = model.flops_per_item(cfg) / 3 + 2 * padding
    assert forward / 1e6 == pytest.approx(443.0, abs=0.5)
    assert 3 * forward * 4096 / 1e12 == pytest.approx(5.44, abs=0.01)
    # what `device_mfu_pct.train` divides: the balanced rows, no padding
    assert model.flops_per_item(cfg) == 3 * 2 * (
        3 * sum(model._delta_net_macs_per_token(cfg))
        + sum(model._attention_macs_per_token(cfg))
        + 4 * model._moe_macs_per_token(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"])
    assert [model.is_attention_layer(cfg, i) for i in range(4)] \
        == [False, False, False, True]
    assert model.buffer_rows(cfg, 4096) == 3840
    # as declared: the same terms with the experts held here
    cfg = _config()
    held = cfg["num_experts"]
    assert model.held_experts(cfg) == list(range(held))
    assert model.buffer_rows(cfg, 4096) == -(-int(
        cfg["capacity_factor"] * 4096 * 10 * held / 512) // 128) * 128
    assert model.flops_per_item(cfg) < model.flops_per_item(
        dict(cfg, num_experts=2 * held))


def test_kernel_work_counts_the_least_the_mathematics_needs(model):
    cfg = _config()
    work = model.kernel_work(cfg, 1, 1024, 1024)
    chunks = 32 * 4096 // 64
    # the scan's products at chunk 64: W S, q S, k^T D and P D a chunk
    fwd = 2 * chunks * (3 * 64 * 128 * 128 + 64 * 64 * 128)
    assert work["mx_gdn_fwd"][0] == fwd
    assert work["mx_gdn_bwd"][0] == 2 * fwd         # D again is not counted
    # 3.67 of the delta rule's 5.9 MFLOP a token run inside the kernel
    assert fwd / 4096 / 1e6 == pytest.approx(3.67, abs=0.01)
    # bound by the bandwidth: under the chip's ridge of 240 FLOP a byte
    for name in ("mx_gdn_fwd", "mx_gdn_bwd"):
        flops, nbytes = work[name]
        assert 20 < flops / nbytes < 240, name
    # every operand and result once: four (c, 128) and one (c, c) bf16
    # operand, the decays, o and one fp32 state a chunk
    assert work["mx_gdn_fwd"][1] == chunks * (
        64 * 2 * (4 * 128 + 64) + 4 * 128 + 64 * 2 * 128 + 4 * 128 * 128)
    # flash at 16 query heads on 2 key/value heads, width 256
    pairs = model._flash_pairs(4096, 1024, 1024) * 16
    assert model._flash_pairs(4096, 1024, 1024) == 10 * 1024 * 1024
    assert work["mx_flash_bwd"][0] == 2 * pairs * 5 * 256
    assert work["mx_flash_fwd"][0] == 2 * pairs * 2 * 256
    # K, V, dK and dV once a group: q, dO, dQ a query head, rows in fp32
    assert work["mx_flash_bwd"][1] == 4096 * (
        2 * 256 * (16 * 3 + 2 * 4) + 16 * 8)
    once_a_head = 4096 * (2 * 256 * (16 * 3 + 16 * 4) + 16 * 8)
    assert work["mx_flash_bwd"][1] < once_a_head
    assert work["mx_flash_bwd"][0] / work["mx_flash_bwd"][1] > 240
    full = 2 * 4096 * 4096 * 16 * (256 + 256)
    assert 0.5 * full < work["mx_flash_fwd"][0] < 0.7 * full


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal_against_the_reference(root, trace):
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
    from mxnet_tpu.telemetry import metrics as tm

    # the expert layers' counters cover the new layers with no change
    tm.REGISTRY.collect()
    assert tm.REGISTRY.get("mx_moe_buffer_rows").value > 0
    assert tm.REGISTRY.get("mx_softmax_router_traced_total").value >= 4


def _tiny_runner(root, seed, **over):
    import jax

    bench = harness.load_bench(root)
    _, wl, cfg = harness.cell_files(root, bench, _CELL)
    cfg.update(over)
    wl = dict(wl, batch=1, pool_batches=2)
    model = harness.load_module(root, "models", cfg["model"])
    runner = harness.load_module(root, "runners", wl["runner"]).setup(
        cfg, wl, seed, jax.devices()[:1], model)
    runner.read_loss(runner.step())
    return cfg, wl, runner, model


def _bf16_router(data, weight, top_k=10, norm_topk_prob=True):
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


def test_check_passes_as_stated_and_refuses_a_bf16_router(root, monkeypatch):
    """The harness's own comparison, on the runner that hands the
    reference what each router saw: in the stated precision the logits
    differ by rounding alone; with the router's product and softmax in
    bf16 the system's choice no longer stands for many tokens and the
    same limit refuses the same program."""
    from mxnet_tpu.ops import registry

    # as declared, at the tiny widths: rounding alone, well inside
    seed = 2 ** 31 + 5
    cfg, wl, runner, model = _tiny_runner(root, seed)
    tol = cfg["check"]["tolerance"]["bfloat16"]
    facts, problems = harness.check_reference(cfg, wl, seed, runner, model)
    assert problems == [], facts
    assert facts["logits_rel_err"] < tol / 2
    told = [k for k in runner.params() if k.endswith("_selected")]
    assert len(told) == 4

    # at these widths an expert's output is a thousandth of the
    # embedding's unless the weights are drawn large; large weights make
    # the delta-rule layers' gated norm normalise (at 0.02 its epsilon
    # rules) and that multiplies their bf16 rounding, which would bury
    # the router's: four attention layers, and a limit above their
    # rounding (read 0.031 here, 0.146 with the bf16 router)
    def tiny():
        made = _tiny_runner(root, 5, initializer_range=0.3,
                            full_attention_interval=1)
        made[0]["check"]["tolerance"]["bfloat16"] = 0.1
        return made

    cfg, wl, runner, model = tiny()
    facts, problems = harness.check_reference(cfg, wl, 5, runner, model)
    assert problems == [], facts
    sound = facts["logits_rel_err"]

    op = registry.OP_REGISTRY["_contrib_softmax_topk_router"]
    monkeypatch.setattr(op, "fn", _bf16_router)
    op._jit_cache.clear()
    try:
        cfg, wl, runner, model = tiny()
        facts, problems = harness.check_reference(cfg, wl, 5, runner, model)
    finally:
        op._jit_cache.clear()
    assert facts["logits_rel_err"] > 2 * sound
    assert any("logits differ" in p for p in problems), facts


def test_a_bf16_delta_rule_state_is_refused_where_it_can_be_seen(
        root, monkeypatch):
    """The delta rule's state, decays and solve lowered to bf16. The
    cell's check compares logits, and there the lowered state is lost in
    the rounding of bf16 weights and activations (PERF.md, PR 34: the
    chip's readings, and the same at these sizes), so it is not that
    check which refuses it: the operator's own comparison with the
    recurrence does, at the tolerance it is held to in fp32, and by more
    than fifty times."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import linear_attention as la

    model = harness.load_module(_ROOT, "models", "qwen3_next")
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (128, 2, 16))) * 0.25
    k = unit(jax.random.normal(ks[1], (128, 2, 16)))
    v = jax.random.normal(ks[2], (128, 2, 16))
    g = -0.1 * jnp.exp(jax.random.normal(ks[3], (128, 2)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (128, 2)))
    with jax.default_matmul_precision("highest"):
        want = model.delta_rule_recurrence(q, k, v, g, beta)
    heads_first = lambda a: jnp.moveaxis(a, 0, 1)[None]

    def error():
        got = la.gated_delta_rule(*(heads_first(a)
                                    for a in (q, k, v, g, beta)), chunk=16)
        return float(jnp.abs(jnp.moveaxis(got[0], 0, 1) - want).max()
                     / jnp.abs(want).max())

    assert error() < 1e-4
    monkeypatch.setattr(la, "STATE_DTYPE", jnp.bfloat16)
    assert error() > 50 * 1e-4


def _synthetic_run(ops, steps=30):
    return {"trace": {"devices": 1, "steps": steps, "device_ops": ops},
            "items_per_step": 4096, "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric", sorted(_METRICS))
def test_metric_file_agrees_with_its_reader_on_a_synthetic_run(model,
                                                               metric):
    with open(os.path.join(_ROOT, "chipbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["cells"] == [_CELL] and spec["unit"] == "%"
    assert spec["args"]["kernels"] == _METRICS[metric]
    assert (spec["args"]["model"], spec["args"]["config"]) \
        == ("qwen3_next", _CONFIG)
    reader = harness.load_module(_ROOT, "readers", spec["reader"])
    work = model.kernel_work(_config(), 1, spec["args"]["block_q"],
                             spec["args"]["block_k"])
    peak = {"f": 197e12, "b": 819e9}
    least = {k: 30 * max(f / peak["f"], b / peak["b"])
             for k, (f, b) in work.items()}
    # each of a kernel's call sites is one op name; a site at twice its
    # least time reads 50 %, at four times 25 %
    ops = [["fusion.3", 1.0]]
    for i, kernel in enumerate(_METRICS[metric]):
        ops.append(["%s.%d" % (kernel, 7 + i), 2 * least[kernel]])
        ops.append(["transpose_jvp_%s_.%d" % (kernel, i), 4 * least[kernel]])
    ops.append(["mx_flash_bwd_dq.2", 1e-9])         # another kernel's site
    assert reader.read(_synthetic_run(ops), **spec["args"]) \
        == pytest.approx(37.5)
    assert reader.read(_synthetic_run([["fusion.1", 1.0]]),
                       **spec["args"]) is None
    assert reader.read({"trace": None}, **spec["args"]) is None
    # at its least time a site reads 100 %, never more by the count
    one = [[_METRICS[metric][0] + ".1", least[_METRICS[metric][0]]]]
    assert reader.read(_synthetic_run(one), **spec["args"]) \
        == pytest.approx(100.0)

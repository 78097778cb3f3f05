"""The cell `kanana2-30b-a3b-train-s4096` rehearsed on the CPU at tiny
widths (the `root` fixture of test_harness_cpu.py with conftest.py's
sizes): steps against the plain reference through the harness's own
check, the new readers on what the run hands them and on synthetic runs.
"""
import json
import os

import pytest

from test_harness_cpu import _TINY_CFG, _ROOT, _run, root  # noqa: F401

from chipbench import harness

_CELL = "kanana2-30b-a3b-train-s4096"


def test_declared_with_its_files_and_no_other_cell():
    bench = harness.load_bench(_ROOT)
    cell, wl, cfg = harness.cell_files(_ROOT, bench, _CELL)
    assert (cell["chips"], wl["batch"], wl["dtype"]) == (1, 1, "bfloat16")
    assert wl["batch"] * cfg["bptt"] == 4096
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == "kanana2_30b_a3b"] == [_CELL]


def test_every_published_number_is_in_the_file():
    """The catalog row's `config`, key for key, but the three reduced."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16032)
    model = harness.load_module(_ROOT, "models", "deepseek_v3")
    # 8.85 TFLOP a step, the issue's count
    assert 8.8e12 < model.flops_per_item(cfg) * 4096 < 8.9e12
    assert model.buffer_rows(cfg, 4096) == 16 * 288


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
    # program counters are read on any backend; the rooflines need a
    # device plane and stay out of a CPU line
    assert {"moe_load_max_over_mean.train", "moe_buffer_fill_pct.train",
            "moe_overflow_steps.train", "import_s.setup",
            "programs_built.setup"} <= got
    assert "flash_bwd_roofline_pct.train" not in got
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 1.0 <= metrics["moe_load_max_over_mean.train"] < 8.0
    assert 0.0 < metrics["moe_buffer_fill_pct.train"] <= 100.0


def _synthetic_run(ops, steps=30):
    return {"trace": {"devices": 1, "steps": steps, "device_ops": ops},
            "items_per_step": 4096, "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_roofline_reader_reads_call_sites_whole_or_not_at_all():
    reader = harness.load_module(_ROOT, "readers", "kernel_roofline_pct")
    model = harness.load_module(_ROOT, "models", "deepseek_v3")
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    work = model.kernel_work(cfg, 1, 1024, 1024)
    args = {"model": "deepseek_v3", "config": "kanana2_30b_a3b",
            "block_q": 1024, "block_k": 1024}
    with open(os.path.join(_ROOT, "chipbench", "layer_metrics",
                           "flash_bwd_roofline_pct.train.json")) as f:
        declared = json.load(f)["args"]
    assert {k: declared[k] for k in args} == args
    assert declared["kernels"] == ["mx_flash_bwd"]
    # 10 of the 16 blocks of 1024 x 1024 are computed, counted here
    assert model._flash_pairs(4096, 1024, 1024) == 10 * 1024 * 1024
    assert model._flash_pairs(4096, 512, 1024) == 20 * 512 * 1024
    assert model._flash_pairs(32, 1024, 1024) == 32 * 32
    at_peak = {k: 30 * f / 197e12 for k, (f, _) in work.items()}
    ops = [["mx_flash_bwd.7", 2 * at_peak["mx_flash_bwd"]],
           ["jvp_mx_flash_fwd_.1", 4 * at_peak["mx_flash_fwd"]],
           ["transpose_jvp_mx_flash_bwd__.3", 4 * at_peak["mx_flash_bwd"]],
           ["fusion.12", 1.0], ["mx_flash_bwd_dq.1", 1e-9]]
    run = _synthetic_run(ops)
    assert reader.read(run, kernels=["mx_flash_fwd"], **args) \
        == pytest.approx(25.0)
    assert reader.read(run, kernels=declared["kernels"], **args) \
        == pytest.approx((50.0 + 25.0) / 2)
    assert reader.read(_synthetic_run([["fusion.1", 1.0]]),
                       kernels=["mx_flash_fwd"], **args) is None
    assert reader.read({"trace": None}, kernels=["mx_flash_fwd"],
                       **args) is None
    # compute-bound: operations over the ridge of 240 FLOP a byte
    for flops, nbytes in work.values():
        assert flops / nbytes > 240
    # the causal kernels do between half of the full square and all of it
    full = 2 * 4096 * 4096 * 32 * (192 + 128)
    assert 0.5 * full < work["mx_flash_fwd"][0] < 0.7 * full


def test_program_metric_reader_on_families_present_and_absent():
    from mxnet_tpu.telemetry import metrics

    reader = harness.load_module(_ROOT, "readers", "program_metric")
    metrics.REGISTRY.gauge("mx_test_reader_num").set(3)
    metrics.REGISTRY.gauge("mx_test_reader_den").set(4)
    assert reader.read({}, name="mx_test_reader_num") == 3.0
    assert reader.read({}, name="mx_test_reader_num",
                       over="mx_test_reader_den", scale=100.0) == 75.0
    assert reader.read({}, name="mx_no_such_family") is None
    assert reader.read({}, name="mx_test_reader_num",
                       over="mx_no_such_family") is None


# ---- the check: a selection judged on what the router saw ---------------

def _tiny_runner(seed, dtype="bfloat16", bptt=256, **over):
    """The cell's runner at tiny widths and `bptt` tokens, one step in."""
    import jax

    bench = harness.load_bench(_ROOT)
    _, wl, cfg = harness.cell_files(_ROOT, bench, _CELL)
    from conftest import TINY_KANANA

    cfg.update({k: v for k, v in TINY_KANANA.items() if k != "classes"})
    cfg.update(bptt=bptt, **over)
    cfg["check"]["classes"] = cfg["vocab_size"]
    cfg["calibration"] = dict(cfg["calibration"], dtype=dtype)
    wl = dict(wl, dtype=dtype, pool_batches=2)
    model = harness.load_module(_ROOT, "models", cfg["model"])
    runner = harness.load_module(_ROOT, "runners", wl["runner"]).setup(
        cfg, wl, seed, jax.devices()[:1], model)
    runner.read_loss(runner.step())
    return cfg, wl, runner, model


def _bf16_router(data, weight, bias_steps, top_k=6, gamma=1e-3,
                 routed_scaling_factor=1.0, norm_topk_prob=True, n_group=1,
                 topk_group=1):
    """`noaux_tc_router` with product, scores and bias in bf16: the
    nearest precision below the configuration's."""
    import jax
    import jax.numpy as jnp

    low = jnp.bfloat16
    score = jax.nn.sigmoid(jnp.einsum("th,eh->te", data.astype(low),
                                      weight.astype(low)))
    choice = score + (bias_steps.astype(jnp.float32) * gamma).astype(low)
    _, ids = jax.lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(score, ids, axis=-1).astype(jnp.float32)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    counts = jnp.zeros((score.shape[1],), jnp.int32).at[
        ids.reshape(-1)].add(1)
    return picked * routed_scaling_factor, ids.astype(jnp.int32), counts


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_check_passes_as_stated_and_refuses_a_bf16_router(seed, monkeypatch):
    """The harness's own comparison, on the runner that hands the
    reference what each router saw: in the stated precision the logits
    differ by rounding alone; with the router's scores in bf16 the
    system's choice no longer stands for many tokens and the largest
    difference is a flipped expert's."""
    from mxnet_tpu.ops import registry

    # at these widths an expert's output is a thousandth of the
    # embedding's unless the weights are drawn large, and rounding then
    # reads 0.03 to 0.06: the limit for this test lies above that
    def tiny():
        made = _tiny_runner(seed, initializer_range=0.3)
        made[0]["check"]["tolerance"]["bfloat16"] = 0.1
        return made

    cfg, wl, runner, model = tiny()
    facts, problems = harness.check_reference(cfg, wl, seed, runner, model)
    assert problems == [], facts
    told = [k for k in runner.params() if k.endswith("_selected")]
    assert len(told) == 2
    sound = facts["logits_rel_err"]

    op = registry.OP_REGISTRY["_contrib_noaux_tc_router"]
    monkeypatch.setattr(op, "fn", _bf16_router)
    op._jit_cache.clear()
    try:
        cfg, wl, runner, model = tiny()
        facts, problems = harness.check_reference(cfg, wl, seed, runner,
                                                  model)
    finally:
        op._jit_cache.clear()
    assert facts["logits_rel_err"] > 2 * sound
    assert any("logits differ" in p for p in problems), facts


def test_reference_without_what_the_router_saw_decides_alone():
    """`runners/train_step` hands the trained values only: the
    reference then selects on its own hidden state, as before."""
    import jax
    import numpy as np

    cfg, wl, runner, model = _tiny_runner(4, dtype=None, bptt=32)
    x, y = model.make_batch(cfg, jax.random.PRNGKey(4), 1)
    got, _ = runner.eval_forward(x, y)
    params = runner.params()
    plain = {k: v for k, v in params.items()
             if not k.endswith(model._TOLD)}
    assert len(plain) == len(params) - 6
    for p in (params, plain):
        want = np.asarray(model.reference_forward(cfg, p, x))
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()

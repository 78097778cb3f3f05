"""The DeepSeek-V3-family decoder (latent attention, `noaux_tc` router,
held and shared experts) at tiny widths on the CPU: the system through
Gluon and `TrainStep` against the benchmark's plain fp32 reference, the
pieces of the expert layer against their definitions, and the properties
the benchmark cell stands on (a step whose program no seed changes, no
token dropped, a router that calibration balances).
"""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import deepseek_v3 as zoo
from mxnet_tpu.gluon.parameter import override
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import transformer_ops as tops
from mxnet_tpu.ops.pallas_attention import flash_attention
from mxnet_tpu.parallel import TrainStep, make_mesh
from mxnet_tpu.telemetry import metrics as tm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
from chipbench import harness  # noqa: E402

# hidden 64, 8 experts of which 2 held, 2 heads 12/8 wide, the dense
# layer and 2 sparse layers, 32 tokens
_TINY = {
    "hidden_size": 64, "num_attention_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_routed_experts": 2, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "vocab_size": 48, "bptt": 32,
    "published": {"num_hidden_layers": 48, "n_routed_experts": 8,
                  "vocab_size": 384},
}
# bf16 activations and weight copies against the fp32 reference: read
# 0.5e-2 to 2.4e-2 of the largest logit over these seeds, and up to
# 0.35 of a parameter's gradient norm where a near-tied third expert
# flips for a token
_BF16_LOGITS, _BF16_GRAD_NORM = 5e-2, 0.5


@pytest.fixture(scope="module")
def model():
    return harness.load_module(_ROOT, "models", "deepseek_v3")


def _cfg(**over):
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(_TINY)
    cfg["calibration"] = dict(cfg["calibration"], dtype=None)
    cfg.update(over)
    return cfg


def _float_and_int(params):
    floats = {k: v for k, v in params.items()
              if jnp.issubdtype(v.dtype, jnp.floating)}
    ints = {k: v for k, v in params.items() if k not in floats}
    return floats, ints


def _train_step(net, loss_fn, dtype, optimizer="sgd", lr=1.0):
    return TrainStep(net, loss_fn, optimizer=optimizer,
                     optimizer_params={"learning_rate": lr},
                     mesh=make_mesh({"dp": -1}, devices=jax.devices()[:1]),
                     dtype=dtype)


@pytest.mark.parametrize("dtype,seed", [(None, 5), (None, 6),
                                        ("bfloat16", 5), ("bfloat16", 6)])
def test_system_matches_reference_logits_loss_and_first_step_gradients(
        model, dtype, seed):
    cfg = _cfg()
    net, loss_fn = model.build(cfg, seed)
    x, y = model.make_batch(cfg, jax.random.PRNGKey(seed), 1)
    params = {n: p.data()._data for n, p in net.collect_params().items()}
    floats, ints = _float_and_int(params)

    def ref_loss(fl):
        return model.reference_loss(
            model.reference_forward(cfg, dict(fl, **ints), x), y)

    want_loss, want_grads = jax.value_and_grad(ref_loss)(floats)
    want_logits = np.asarray(model.reference_forward(cfg, params, x))

    # the evaluation forward in the compute type, then one step of plain
    # SGD at rate 1: the first step's gradient is old minus new
    cdt = jnp.float32 if dtype is None else jnp.bfloat16
    with autograd.pause(train_mode=False), override(
            {p: NDArray(params[p.name].astype(cdt)
                        if p.name in floats else params[p.name])
             for p in net.collect_params().values()}):
        got_logits = np.asarray(net(NDArray(x))._data.astype(jnp.float32))
    before = {k: np.asarray(v) for k, v in floats.items()}
    step = _train_step(net, loss_fn, dtype)
    got_loss = float(step(x, y))

    scale = np.abs(want_logits).max()
    if dtype is None:
        assert np.abs(got_logits - want_logits).max() <= 1e-5 * scale
        assert abs(got_loss - float(want_loss)) <= 1e-5 * float(want_loss)
    else:
        assert np.abs(got_logits - want_logits).max() \
            <= _BF16_LOGITS * scale
        assert abs(got_loss - float(want_loss)) <= 2 * _BF16_LOGITS * scale
    assert set(step._param_vals) == set(floats)
    for name, old in before.items():
        got = old - np.asarray(step._param_vals[name])
        want = np.asarray(want_grads[name])
        if dtype is None:
            # old - new at rate 1 rounds at the weight's own ulp (6e-8
            # at 1.0, where the norms start)
            assert np.abs(got - want).max() <= 1e-5 * max(
                np.abs(want).max(), 1e-3) + 1.2e-7, name
        else:
            assert np.linalg.norm(got - want) <= _BF16_GRAD_NORM * max(
                np.linalg.norm(want), 1e-3), name
    # the non-gradient state went through the step as integers
    for name, value in step._aux_vals.items():
        assert value.dtype == jnp.int32, name
    steps = [v for k, v in step._aux_vals.items()
             if k.endswith("e_score_correction_steps")]
    counts = [v for k, v in step._aux_vals.items()
              if k.endswith("expert_counts")]
    assert len(steps) == len(counts) == 2
    for c in counts:
        assert int(c.sum()) == 32 * 3


def test_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of all 4 shares of a layer plus the shared
    experts once equal the uncut reference's layer."""
    cfg = _cfg(n_routed_experts=8)          # the reference holds all 8
    rng = np.random.RandomState(0)
    hidden, width, experts, tokens = 64, 24, 8, 32

    def w(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)

    p = {"m_layers1_mlp_gate_weight": w(experts, hidden),
         "m_layers1_mlp_e_score_correction_steps":
             jnp.asarray(rng.randint(-20, 20, experts), jnp.int32),
         "m_layers1_mlp_experts_gate_proj_weight": w(experts, hidden, width),
         "m_layers1_mlp_experts_up_proj_weight": w(experts, hidden, width),
         "m_layers1_mlp_experts_down_proj_weight": w(experts, width, hidden),
         "m_layers1_mlp_shared_experts_gate_proj_weight": w(48, hidden),
         "m_layers1_mlp_shared_experts_up_proj_weight": w(48, hidden),
         "m_layers1_mlp_shared_experts_down_proj_weight": w(hidden, 48)}
    u = w(tokens, hidden) * 10
    with jax.default_matmul_precision("highest"):
        want = model._sparse_ffn(cfg, p, 1, u)
        weights, ids, counts = tops.noaux_tc_router(
            u, p["m_layers1_mlp_gate_weight"],
            p["m_layers1_mlp_e_score_correction_steps"], top_k=3,
            gamma=cfg["bias_update_rate"],
            routed_scaling_factor=cfg["routed_scaling_factor"])
        total = tops.gated_mlp(
            u, *(p["m_layers1_mlp_shared_experts_%s_weight" % part]
                 for part in ("gate_proj", "up_proj", "down_proj")))
        rows = 0
        for share in range(4):
            held = (2 * share, 2 * share + 1)
            part, n, over = moe_ops.moe_held_experts(
                u, ids, weights,
                *(p["m_layers1_mlp_experts_%s_weight" % name][jnp.asarray(held)]
                  for name in ("gate_proj", "up_proj", "down_proj")),
                held=held, num_experts=experts, capacity_factor=1.5)
            total = total + part
            rows += int(n)
    assert rows == tokens * 3 == int(counts.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _plain_attention(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        mask = jnp.arange(q.shape[2])[:, None] >= jnp.arange(k.shape[2])
        s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d_qk,d_v", [(12, 8), (8, 16), (16, 16)])
def test_flash_kernels_with_unequal_head_widths(d_qk, d_v, causal):
    """Forward and both gradients in interpret mode against plain
    attention, `scale` left at its default of d_qk ** -0.5."""
    rng = np.random.RandomState(d_qk + d_v)
    q, k = (jnp.asarray(rng.randn(2, 2, 32, d_qk).astype(np.float32))
            for _ in range(2))
    v, g = (jnp.asarray(rng.randn(2, 2, 32, d_v).astype(np.float32))
            for _ in range(2))
    got, vjp = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, block_q=8, block_k=16), q, k, v)
    want, vjp_ref = jax.vjp(lambda a, b, c: _plain_attention(
        a, b, c, causal, d_qk ** -0.5), q, k, v)
    assert got.shape == (2, 2, 32, d_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for a, b, name in zip(vjp(g), vjp_ref(g), "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg="d" + name)


def test_flash_rejects_q_and_k_of_different_width():
    q = jnp.ones((1, 1, 16, 8))
    with pytest.raises(ValueError, match="differ in width"):
        flash_attention(q, jnp.ones((1, 1, 16, 12)), q)


def test_bias_rule_counts_sign_and_gamma():
    """Selection uses score + bias, weights use the score alone; counts
    are the tokens that picked each expert; the rule moves the bias by
    one step of gamma against the sign of count minus mean."""
    rng = np.random.RandomState(1)
    tokens, hidden, experts, top_k, gamma = 64, 16, 8, 2, 1e-3
    u = jnp.asarray(rng.randn(tokens, hidden).astype(np.float32))
    w = jnp.asarray(rng.randn(experts, hidden).astype(np.float32) * 0.3)
    steps = jnp.asarray([3000, 0, 0, 0, 0, 0, 0, -3000], jnp.int32)
    weights, ids, counts = tops.noaux_tc_router(
        u, w, steps, top_k=top_k, gamma=gamma, routed_scaling_factor=2.0,
        norm_topk_prob=True)
    score = np.asarray(jax.nn.sigmoid(u @ w.T))
    # a bias of +3 puts expert 0 in every token's pick, -3 keeps 7 out
    ids_np = np.asarray(ids)
    assert (ids_np == 0).any(1).all() and not (ids_np == 7).any()
    want_ids = np.argsort(-(score + np.asarray(steps) * gamma), 1)[:, :top_k]
    assert (np.sort(ids_np, 1) == np.sort(want_ids, 1)).all()
    picked = np.take_along_axis(score, ids_np, 1)
    np.testing.assert_allclose(
        np.asarray(weights),
        2.0 * picked / (picked.sum(1, keepdims=True) + 1e-20), rtol=1e-5)
    want_counts = np.bincount(ids_np.ravel(), minlength=experts)
    assert (np.asarray(counts) == want_counts).all()
    new = np.asarray(tops.bias_steps_update(steps, counts))
    mean = tokens * top_k / experts
    assert (new - np.asarray(steps) == np.sign(mean - want_counts)).all()
    assert new[0] == 2999 and new[7] == -2999


def test_group_limited_selection_stays_inside_the_kept_groups():
    rng = np.random.RandomState(2)
    u = jnp.asarray(rng.randn(32, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    _, ids, _ = tops.noaux_tc_router(u, w, jnp.zeros((8,), jnp.int32),
                                     top_k=2, n_group=4, topk_group=1)
    groups = np.asarray(ids) // 2
    assert (groups[:, 0] == groups[:, 1]).all()


def _dense_held(u, ids, weights, gate, up, down, held):
    out = jnp.zeros(u.shape, jnp.float32)
    for slot, eid in enumerate(held):
        w = jnp.sum(jnp.where(ids == eid, weights, 0.0), -1)
        out = out + w[:, None] * tops.gated_mlp(
            u, gate[slot].T, up[slot].T, down[slot].T)
    return out


@pytest.mark.parametrize("forced", [False, True])
def test_no_token_is_dropped(forced):
    """`forced`: every token is sent to the held experts, twice what the
    buffer holds: the second pass computes the rest, exactly, and the
    step counts itself. Gradients too."""
    rng = np.random.RandomState(3)
    tokens, hidden, width, experts, top_k = 256, 16, 8, 8, 2
    held = (0, 1)
    u = jnp.asarray(rng.randn(tokens, hidden).astype(np.float32))
    gate, up = (jnp.asarray(rng.randn(2, hidden, width).astype(np.float32)
                            * 0.3) for _ in range(2))
    down = jnp.asarray(rng.randn(2, width, hidden).astype(np.float32) * 0.3)
    wr = jnp.asarray(rng.randn(experts, hidden).astype(np.float32) * 0.3)
    steps = jnp.asarray([9000, 9000] + [0] * 6 if forced else [0] * 8,
                        jnp.int32)
    weights, ids, _ = tops.noaux_tc_router(u, wr, steps, top_k=top_k)

    def system(u, weights, gate, up, down):
        out, rows, over = moe_ops.moe_held_experts(
            u, ids, weights, gate, up, down, held=held,
            num_experts=experts, capacity_factor=1.5)
        return jnp.sum(out * jnp.cos(out)), (out, rows, over)

    def dense(u, weights, gate, up, down):
        out = _dense_held(u, ids, weights, gate, up, down, held)
        return jnp.sum(out * jnp.cos(out)), out

    with jax.default_matmul_precision("highest"):
        (_, (got, rows, over)), got_g = jax.value_and_grad(
            system, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                u, weights, gate, up, down)
        (_, want), want_g = jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                u, weights, gate, up, down)
    assert moe_ops.buffer_rows(tokens, top_k, 2, experts, 1.5) == 256
    if forced:
        assert int(rows) == 2 * tokens and int(over) == 1
    else:
        assert 0 < int(rows) <= 256 and int(over) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=1e-4)


def test_overflow_counts_itself_through_train_step_and_the_registry(model):
    """One training step with the bias forcing every token onto the held
    experts: `overflow_steps` reads 1 a layer and the registry's counter
    moves by as much when it is collected, with no read inside the step.
    """
    cfg = _cfg(bptt=256)
    net, loss_fn = model.build(cfg, 11)
    for name, p in net.collect_params().items():
        if name.endswith("e_score_correction_steps"):
            p.set_data(NDArray(jnp.asarray([9000, 9000] + [0] * 6,
                                           jnp.int32)))
    x, y = model.make_batch(cfg, jax.random.PRNGKey(0), 1)

    def value(name):
        fam = {f.name: f for f in tm.REGISTRY.collect()}[name]
        return sum(child.value for _, child in fam.collect())

    before = value("mx_moe_overflow_steps_total")
    step = _train_step(net, loss_fn, None, lr=1e-3)
    assert np.isfinite(float(step(x, y)))
    over = [int(v[0]) for k, v in step._aux_vals.items()
            if k.endswith("overflow_steps")]
    assert over == [1, 1]
    assert value("mx_moe_overflow_steps_total") - before == 2
    assert value("mx_moe_rows_held") == 2 * 2 * 256    # every token, twice
    assert value("mx_moe_buffer_rows") == 2 * 384 == 2 * moe_ops.buffer_rows(
        256, 3, 2, 8, cfg["capacity_factor"])
    assert value("mx_moe_load_max_over_mean") == pytest.approx(8 / 3)
    # a second collect adds nothing
    assert value("mx_moe_overflow_steps_total") - before == 2


def test_registry_reads_the_mean_and_the_peak_over_the_steps(model):
    """The gauges are folded from what the steps accumulated on the
    device: rows held as the mean over the steps, the load as the largest
    any step saw; a read that finds the state given away is counted and
    leaves the gauges alone."""
    cfg = _cfg(bptt=256)
    net, loss_fn = model.build(cfg, 12)
    x, y = model.make_batch(cfg, jax.random.PRNGKey(1), 1)

    def value(name):
        fam = {f.name: f for f in tm.REGISTRY.collect()}[name]
        return sum(child.value for _, child in fam.collect())

    step = _train_step(net, loss_fn, None, lr=0.0)
    rows = []

    def one_step():
        step(x, y)
        rows.append(sum(int(v[0]) for k, v in step._aux_vals.items()
                        if k.endswith("held_rows")))

    one_step()
    calm = value("mx_moe_load_max_over_mean")
    assert value("mx_moe_rows_held") == rows[0]
    for name in step._aux_vals:          # every token onto held experts
        if name.endswith("e_score_correction_steps"):
            step._aux_vals[name] = jnp.asarray([9000, 9000] + [0] * 6,
                                               jnp.int32)
    one_step()
    assert rows[1] == 2 * 2 * 256 > rows[0]
    assert calm < 8 / 3
    assert value("mx_moe_load_max_over_mean") == pytest.approx(8 / 3)
    for name in step._aux_vals:
        if name.endswith("e_score_correction_steps"):
            step._aux_vals[name] = jnp.zeros((8,), jnp.int32)
    one_step()
    assert value("mx_moe_rows_held") == pytest.approx(sum(rows) / 3)
    assert value("mx_moe_load_max_over_mean") == pytest.approx(8 / 3)
    # the state given away to a running step: counted, gauges kept
    gone = jnp.zeros((1,), jnp.int32)
    gone.delete()
    layer = model.sparse_layers(net)[0]
    owner = layer.steps_seen._live
    skipped = value("mx_moe_state_reads_skipped_total")
    layer.steps_seen._bind_live(lambda: gone)
    try:
        assert value("mx_moe_rows_held") == pytest.approx(sum(rows) / 3)
        assert value("mx_moe_state_reads_skipped_total") >= skipped + 1
    finally:
        layer.steps_seen._bind_live(owner)


def _op_list(text):
    """(opcode, result type) of every StableHLO op, names and locations
    stripped."""
    ops = []
    for line in text.splitlines():
        m = re.search(r"= \"?([a-z_]+\.[a-z_.]+)\"?.*?(-> .*|: [^:]*)$",
                      line.strip())
        if m:
            ops.append((m.group(1), m.group(2)))
    return ops


def test_two_seeds_give_one_step_program(model):
    """Same op list, shapes and trip counts whatever the seed: nothing
    in the step's program is taken from the routing."""
    cfg = _cfg()
    texts = []
    for seed in (21, 22):
        net, loss_fn = model.build(cfg, seed)
        x, y = model.make_batch(cfg, jax.random.PRNGKey(seed), 1)
        step = _train_step(net, loss_fn, "bfloat16", optimizer="adam",
                           lr=1e-4)
        step._materialize(np.asarray(x)[:1])
        step._build()
        lowered = step._jitted.lower(
            step._param_vals, step._opt_state, step._aux_vals, x, y,
            jnp.float32(1e-4), jnp.float32(1), jax.random.PRNGKey(0))
        texts.append(lowered.as_text())
    first, second = (_op_list(t) for t in texts)
    assert len(first) > 500 and first == second
    # every loop's trip count is a constant of the program
    assert "stablehlo.while" not in texts[0] or \
        texts[0].count("stablehlo.while") == texts[1].count(
            "stablehlo.while")


def test_a_build_of_the_step_counts_one_fused_backward_a_layer(model):
    """Each layer's attention takes the one-pass flash backward (a head
    of the cell's 4,096 x 192 is 3 MiB of fp32 dQ, under the budget;
    the tiny head here is smaller still) and none the dK/dV and dQ
    pair: the registry says so after one build of the step's program."""
    from mxnet_tpu.ops import pallas_attention as pa

    def counts():
        return [pa._flash_bwd_traced.labels(path=p).value
                for p in ("fused", "split")]

    assert pa._bwd_path(4096, 192) == "fused"
    cfg = _cfg()
    net, loss_fn = model.build(cfg, 23)
    x, y = model.make_batch(cfg, jax.random.PRNGKey(23), 1)
    step = _train_step(net, loss_fn, "bfloat16", optimizer="adam", lr=1e-4)
    step._materialize(np.asarray(x)[:1])
    step._build()
    before = counts()
    step._jitted.lower(
        step._param_vals, step._opt_state, step._aux_vals, x, y,
        jnp.float32(1e-4), jnp.float32(1), jax.random.PRNGKey(0))
    fused, split = (b - a for a, b in zip(before, counts()))
    assert (fused, split) == (cfg["num_hidden_layers"], 0)


def test_calibration_balances_a_collapsed_residual_stream(model):
    """Tokens that share a large common component all pick the same few
    experts; the published rule, iterated on the batch, spreads them."""
    rng = np.random.RandomState(4)
    tokens, hidden, experts, top_k = 2048, 32, 16, 2
    common = rng.randn(hidden).astype(np.float32) * 1.5
    u = jnp.asarray(common + 0.5 * rng.randn(tokens, hidden)
                    .astype(np.float32))
    w = jnp.asarray(rng.randn(experts, hidden).astype(np.float32) * 0.2)
    zero = jnp.zeros((experts,), jnp.int32)

    def max_over_mean(steps):
        _, _, counts = tops.noaux_tc_router(u, w, steps, top_k=top_k)
        return float(counts.max() / counts.mean())

    def cfg(max_iters):
        return {"n_group": 1, "num_experts_per_tok": top_k,
                "bias_update_rate": 1e-3,
                "calibration": {"max_over_mean": 1.1,
                                "max_iters": max_iters}}

    assert max_over_mean(zero) > 3.0
    steps = model._balanced_bias_steps(cfg(4000), u, w, zero)
    assert max_over_mean(steps) < 1.1
    # capped iterations: stops, having moved every bias at most that far
    few = model._balanced_bias_steps(cfg(5), u, w, zero)
    assert int(jnp.abs(few).max()) == 5


def test_calibrate_selection_bias_writes_the_net_and_balances_it(model):
    # embedding at the other weights' scale: with 48 Zipf-like ids a
    # token-dominated stream routes in blocks no bias can split
    cfg = _cfg(bptt=256)
    cfg["calibration"].update(max_over_mean=1.2, max_iters=3000)
    mx.random.seed(9)
    net = zoo.deepseek_v3(model.zoo_config(cfg))
    net.initialize()
    x, _ = model.make_batch(cfg, jax.random.PRNGKey(9), 1)
    written = model.calibrate_selection_bias(cfg, net, x)
    assert len(written) == 2
    assert not any(b._forward_pre_hooks for b in model.sparse_layers(net))
    for name, p in net.collect_params().items():
        if name.endswith("e_score_correction_steps"):
            assert (np.asarray(p.data()._data)
                    == np.asarray(written[name])).all()
            assert np.abs(np.asarray(written[name])).max() > 0
    # a training step on that batch now routes under the target (the
    # step's own layer inputs equal the calibration's: same values)
    step = _train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(), None,
                       lr=0.0)
    step(x, jnp.zeros_like(x))
    for name, counts in step._aux_vals.items():
        if name.endswith("expert_counts"):
            counts = np.asarray(counts)
            assert counts.max() / counts.mean() < 1.2, name


def test_train_step_leaves_the_imperative_gradient_buffers_in_place():
    """A net handed to `TrainStep` can still be differentiated
    imperatively once its values are synced back."""
    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    step = _train_step(net, gluon.loss.L2Loss(), None, lr=0.1)
    step(jnp.ones((2, 4)), jnp.ones((2, 3)))
    assert all(p._grad is not None for p in net.collect_params().values())
    step.sync_to_net()
    with autograd.record():
        loss = net(mx.nd.ones((2, 4))).sum()
    loss.backward()
    assert float(np.abs(net.weight.grad().asnumpy()).sum()) > 0


def test_forward_hooks_detach_by_their_handle():
    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    calls = []
    before = net.register_forward_pre_hook(
        lambda block, args: calls.append(("pre", args[0].shape)))
    after = net.register_forward_hook(
        lambda block, args, out: calls.append(("post", out.shape)))
    net(mx.nd.ones((2, 4)))
    assert calls == [("pre", (2, 4)), ("post", (2, 3))]
    before.detach()
    after.detach()
    before.detach()                     # a second time is no error
    net(mx.nd.ones((2, 4)))
    assert len(calls) == 2


def test_rotary_interleaved_keeps_every_dot_product():
    """The op leaves a pair's results at i and i + d/2; every q.k equals
    that of the textbook interleaved rotation (complex multiplication)."""
    rng = np.random.RandomState(5)
    q, k = (jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))
            for _ in range(2))
    model = harness.load_module(_ROOT, "models", "deepseek_v3")
    got = jnp.einsum("hqd,hkd->hqk", tops.rotary_embedding(q, theta=1e4),
                     tops.rotary_embedding(k, theta=1e4))
    want = jnp.einsum("hqd,hkd->hqk", model._rope(q, 1e4),
                      model._rope(k, 1e4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)

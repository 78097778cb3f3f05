"""The Qwen3-Next-family decoder (Gated DeltaNet linear attention, gated
grouped-query attention, a softmax top-k router with held experts and a
gated shared expert) at tiny widths on the CPU: the new operators against
their definitions (the delta rule token by token, plain attention,
`jax.lax.top_k` of a softmax), each block and the whole model through
Gluon and `TrainStep` against the benchmark's plain fp32 reference, and
the shares of an expert layer adding up to the uncut layer. The Pallas
kernels run in interpret mode here.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.parameter import override
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import linear_attention as la
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import transformer_ops as tops
from mxnet_tpu.parallel import TrainStep, make_mesh
from mxnet_tpu.telemetry import metrics as tm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
from chipbench import harness  # noqa: E402

# bf16 operands against the fp32 recurrence: read 0.4e-2 to 1.3e-2 of the
# largest value (output and gradients) over these shapes and seeds
_BF16_RULE = 4e-2
# the whole model in bf16 against the fp32 reference, as in
# test_deepseek_v3.py: logits over their largest, a gradient over its norm
_BF16_LOGITS, _BF16_GRAD_NORM = 5e-2, 0.5


@pytest.fixture(scope="module")
def model():
    return harness.load_module(_ROOT, "models", "qwen3_next")


@pytest.fixture()
def cfg(tiny_qwen3next):
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        cfg = json.load(f)
    cfg.update({k: v for k, v in tiny_qwen3next.items() if k != "classes"})
    return cfg


# ---- the delta rule -------------------------------------------------------

def _rule_inputs(seed, heads_k, heads, seq, dk, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, heads_k, seq, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, heads_k, seq, dk)))
    v = jax.random.normal(ks[2], (1, heads, seq, dv))
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (1, heads, seq)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, heads, seq)))
    cot = jax.random.normal(ks[5], (1, heads, seq, dv))
    return [a.astype(dtype) for a in (q, k, v)] + [g, beta], cot


def _recurrence(model, q, k, v, g, beta):
    """`models/qwen3_next.py:delta_rule_recurrence` on (1, heads, T, d)
    operands, in fp32 at the highest precision."""
    rep = v.shape[1] // q.shape[1]
    tokens_first = lambda a: jnp.moveaxis(a[0].astype(jnp.float32), 0, 1)
    q, k = (jnp.repeat(tokens_first(a), rep, 1) for a in (q, k))
    with jax.default_matmul_precision("highest"):
        out = model.delta_rule_recurrence(
            q, k, tokens_first(v), g[0].T, beta[0].T)
    return jnp.moveaxis(out, 0, 1)[None]


def _rule_and_recurrence(model, args, cot, chunk):
    """(o, dq, dk, dv, dg, dbeta) of the operator and of the recurrence."""
    def both(fn):
        out, pull = jax.vjp(fn, *args)
        return (out,) + pull(cot.astype(out.dtype))

    got = jax.jit(lambda: both(
        lambda *a: la.gated_delta_rule(*a, chunk=chunk)))()
    want = jax.jit(lambda: both(
        lambda *a: _recurrence(model, *a).astype(args[2].dtype)))()
    return got, want


def _assert_rule_close(got, want, tol):
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-3), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq,chunk,dk,dv", [
    (64, 64, 16, 24), (128, 64, 16, 24), (16, 16, 16, 24), (48, 16, 16, 24),
    # the widths of `qwen3_next_80b_a3b`: two tiles of two chunks
    (256, 64, 128, 128)])
def test_gated_delta_rule_equals_the_recurrence(model, seq, chunk, dk, dv,
                                                dtype):
    """Values and all five gradients, at one chunk and at several, one
    key head under two value heads."""
    args, cot = _rule_inputs(seq + chunk, 1, 2, seq, dk, dv, dtype)
    got, want = _rule_and_recurrence(model, args, cot, chunk)
    _assert_rule_close(got, want, 1e-4 if dtype == "float32" else _BF16_RULE)


def test_gated_delta_rule_walks_a_grid_of_head_and_chunk_blocks(model):
    """Four key heads under eight value heads and sixteen chunks: the
    preparation's grid is four key heads by two blocks of four tiles, the
    scan's two blocks of four value heads by two blocks of eight chunks,
    so every index map is walked past its first block, q and k by key
    head. Each head and chunk has its own data: a block read from the
    wrong place shows."""
    args, cot = _rule_inputs(11, 4, 8, 1024, 16, 24, "float32")
    heads, chunks = la._grid(8, 1024 // 64)
    assert (8 // heads, (1024 // 64) // chunks) == (2, 2)
    got, want = _rule_and_recurrence(model, args, cot, 64)
    _assert_rule_close(got, want, 1e-4)


def test_gated_delta_rule_with_strong_decay_and_similar_keys_stays_finite(
        model):
    """Keys that nearly repeat and decays that underflow inside a chunk:
    the solve is forward substitution (nothing that cancels), the masked
    decays never form inf * 0."""
    (q, k, v, g, beta), cot = _rule_inputs(3, 1, 2, 128, 16, 16, "float32")
    k = k[:, :, :1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = g.at[:, 0].multiply(80.0)
    beta = jnp.full_like(beta, 0.97)
    @jax.jit
    def both(*args):
        out, pull = jax.vjp(lambda *a: la.gated_delta_rule(*a, chunk=64),
                            *args)
        return out, pull(cot)

    out, grads = both(q, k, v, g, beta)
    want = _recurrence(model, q, k, v, g, beta)
    assert all(bool(jnp.isfinite(a).all()) for a in (out,) + grads)
    assert float(jnp.abs(out - want).max()) <= 1e-4 * float(
        jnp.abs(want).max())


def test_gated_delta_rule_refuses_what_it_cannot_chunk():
    (q, k, v, g, beta), _ = _rule_inputs(0, 2, 4, 64, 16, 16, "float32")
    with pytest.raises(ValueError, match="divide by the chunk"):
        la.gated_delta_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="power of two"):
        la.gated_delta_rule(q[:, :, :48], k[:, :, :48], v[:, :, :48],
                            g[:, :, :48], beta[:, :, :48], chunk=48)
    with pytest.raises(ValueError, match="multiple of key heads"):
        la.gated_delta_rule(q[:, :1].repeat(3, 1), k[:, :1].repeat(3, 1),
                            v, g, beta)


def test_gated_delta_rule_counts_its_traces_by_chunk():
    read = lambda: la._traced.labels(chunk="16").value
    before = read()
    args, _ = _rule_inputs(0, 1, 1, 16, 8, 8, "float32")
    jax.eval_shape(lambda *a: la.gated_delta_rule(*a, chunk=16), *args)
    assert read() == before + 1
    assert tm.REGISTRY.get("mx_gated_delta_rule_traced_total") is la._traced


@pytest.mark.parametrize("width", [1, 4])
def test_causal_conv1d(width):
    rng = np.random.RandomState(width)
    x = rng.randn(2, 9, 6).astype(np.float32)
    w = rng.randn(6, width).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(width):
            src = t - (width - 1) + j
            if src >= 0:
                want[:, t] += w[:, j] * x[:, src]
    got, pull = jax.vjp(la.causal_conv1d, jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    # the future never reaches the past: d out[t] / d x[s] = 0 for s > t
    dx, _ = pull(jnp.zeros_like(got).at[:, 3].set(1.0))
    assert float(jnp.abs(dx[:, 4:]).max()) == 0.0
    assert got.dtype == jnp.float32
    assert la.causal_conv1d(jnp.asarray(x, jnp.bfloat16),
                            jnp.asarray(w)).dtype == jnp.bfloat16


# ---- grouped-query flash attention ---------------------------------------

def _plain_attention(q, k, v, causal):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, 1) for a in (k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
        if causal:
            t = q.shape[2]
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _qkv(group, kv_heads, seq=128, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, kv_heads * group, seq, d))
    k = jax.random.normal(ks[1], (2, kv_heads, seq, d))
    v = jax.random.normal(ks[2], (2, kv_heads, seq, d))
    return (q, k, v), jax.random.normal(ks[3], q.shape)


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("group", [8, 2])
def test_flash_attention_with_grouped_queries(group, causal, path,
                                              monkeypatch):
    """8 and 2 query heads a key/value head against plain attention,
    values and gradients, through the fused backward and through the
    dK/dV and dQ pair; dK and dV come out per key/value head."""
    if path == "split":
        monkeypatch.setattr(pa, "FUSED_DQ_BYTES", 0)
    (q, k, v), cot = _qkv(group, 2)

    def both(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(cot)

    got = jax.jit(lambda: both(lambda *a: pa.flash_attention(
        *a, causal=causal, block_q=32, block_k=64)))()
    want = both(lambda *a: _plain_attention(*a, causal))
    assert got[2].shape == k.shape and got[3].shape == v.shape
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())


def test_flash_attention_at_a_group_of_one_is_what_it_was():
    """With as many key/value heads as query heads the grouped call is
    the old one, bit for bit: the same kernels on the same operands as
    one call per key/value head gives."""
    (q, k, v), cot = _qkv(1, 3, seq=64)
    assert pa._bwd_path(4096, 192) == pa._bwd_path(4096, 192, 4096, 128, 1)

    def both(q, k, v, cot):
        out, pull = jax.vjp(lambda *a: pa.flash_attention(
            *a, causal=True, block_q=32, block_k=32), q, k, v)
        return (out,) + pull(cot)

    whole = both(q, k, v, cot)
    for h in range(3):
        one = both(*(a[:, h:h + 1] for a in (q, k, v, cot)))
        for a, b in zip(whole, one):
            assert np.array_equal(np.asarray(a[:, h:h + 1]), np.asarray(b))


def test_flash_attention_refuses_heads_that_do_not_group():
    (q, k, v), _ = _qkv(1, 3, seq=32)
    with pytest.raises(ValueError, match="query heads"):
        pa.flash_attention(q[:, :2], k, v)
    with pytest.raises(ValueError, match="query heads"):
        pa.flash_attention(q, k, v[:, :1])


def test_flash_attention_counts_its_groups():
    read = lambda: pa._flash_group_traced.labels(group="4").value
    before = read()
    (q, k, v), _ = _qkv(4, 1, seq=32)
    jax.eval_shape(lambda *a: pa.flash_attention(*a, causal=True), q, k, v)
    assert read() == before + 1


# ---- router and norms -----------------------------------------------------

@pytest.mark.parametrize("norm", [True, False])
def test_softmax_topk_router_is_top_k_of_a_softmax(norm):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(40, 24).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 24).astype(np.float32))
    weights, ids, counts = tops.softmax_topk_router(x, w, top_k=5,
                                                    norm_topk_prob=norm)
    with jax.default_matmul_precision("highest"):
        prob = jax.nn.softmax(x @ w.T, -1)
    want, want_ids = jax.lax.top_k(prob, 5)
    if norm:
        want = want / want.sum(-1, keepdims=True)
    assert np.array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want),
                               rtol=1e-6)
    assert weights.dtype == jnp.float32 and ids.dtype == jnp.int32
    assert np.array_equal(np.asarray(counts),
                          np.bincount(np.asarray(ids).ravel(), minlength=16))
    # bf16 operands are scored in fp32: the choice is that of their values
    _, low_ids, _ = tops.softmax_topk_router(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), top_k=5)
    with jax.default_matmul_precision("highest"):
        rounded = x.astype(jnp.bfloat16).astype(jnp.float32) \
            @ w.astype(jnp.bfloat16).astype(jnp.float32).T
    assert np.array_equal(np.asarray(low_ids),
                          np.asarray(jax.lax.top_k(rounded, 5)[1]))


def test_zero_centred_and_gated_norms():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(3, 5, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(8).astype(np.float32))
    z = jnp.asarray(rng.randn(3, 5, 8).astype(np.float32))
    unit = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(
        np.asarray(tops.rms_norm(x, w, zero_centered=True)),
        unit * (1 + np.asarray(w)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tops.rms_norm(x, w)),
                               unit * np.asarray(w), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tops.gated_rms_norm(x, z, w)),
        unit * np.asarray(w) * np.asarray(jax.nn.silu(z)), rtol=1e-5)
    assert tops.gated_rms_norm(x.astype(jnp.bfloat16), z, w).dtype \
        == jnp.bfloat16
    block = nn.RMSNorm(8, zero_centered=True)
    block.initialize()
    assert float(jnp.abs(block.weight.data()._data).max()) == 0.0
    np.testing.assert_allclose(np.asarray(block(NDArray(x))._data), unit,
                               rtol=1e-5)


# ---- blocks and the model against the reference --------------------------

def _values(block, seed):
    """Seeded values for every parameter of an initialized block: the
    norms' and biases' too, so that a (1 + w) taken for w shows."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, p in block.collect_params().items():
        v = p.data()._data
        if jnp.issubdtype(v.dtype, jnp.floating):
            scale = 0.3 if v.ndim < 2 else 0.15
            v = jnp.asarray(rng.randn(*v.shape).astype(np.float32) * scale)
        out[name] = v
    return out


def _run(block, values, x):
    with autograd.pause(train_mode=False), override(
            {p: NDArray(values[p.name])
             for p in block.collect_params().values()}):
        return np.asarray(block(NDArray(x))._data)


def test_gated_delta_net_block_equals_the_reference(model, cfg):
    block = nn.GatedDeltaNet(64, 2, 4, 16, 16, conv_kernel=4, chunk=16,
                             prefix="m_layers0_linear_attn_")
    block.initialize()
    values = _values(block, 0)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 32, 64), jnp.float32)
    got = _run(block, values, x)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(model._delta_net(cfg, values, 0, row))
                         for row in x])
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    a_log = np.asarray(block.A_log.data()._data)
    assert np.isfinite(a_log).all() and (a_log <= np.log(16.0)).all()
    assert float(block.dt_bias.data()._data.min()) == 1.0


def test_gated_attention_block_equals_the_reference(model, cfg):
    block = nn.GatedAttention(64, 4, 2, 32, rope_theta=1e7,
                              partial_rotary_factor=0.25,
                              prefix="m_layers3_self_attn_")
    block.initialize()
    values = _values(block, 2)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 64), jnp.float32)
    got = _run(block, values, x)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(model._attention(cfg, values, 3, row))
                         for row in x])
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_sparse_moe_with_the_softmax_router_equals_the_reference(model, cfg):
    block = nn.SparseMoE(64, 32, 8, held=(0, 1), top_k=3,
                         n_shared_experts=1, router="softmax",
                         shared_expert_gate=True, prefix="m_layers1_mlp_")
    block.initialize()
    assert not hasattr(block, "e_score_correction_steps")
    values = _values(block, 4)
    x = jnp.asarray(np.random.RandomState(5).randn(1, 32, 64), jnp.float32)
    got = _run(block, values, x)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model._sparse_ffn(cfg, values, 1, x[0]))
    assert np.abs(got[0] - want).max() <= 1e-5 * np.abs(want).max()
    with pytest.raises(ValueError, match="neither"):
        nn.SparseMoE(64, 32, 8, router="hash")


def test_shares_add_up_to_the_uncut_layer(model, cfg):
    """With 8 experts over 4 shares of 2, the four routed parts plus the
    gated shared expert counted once equal the uncut reference's layer."""
    cfg = dict(cfg, num_experts=8)           # the reference holds all 8
    rng = np.random.RandomState(0)
    hidden, width, experts, tokens = 64, 32, 8, 32

    def w(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)

    name = "m_layers1_mlp_"
    p = {name + "gate_weight": w(experts, hidden),
         name + "experts_gate_proj_weight": w(experts, hidden, width),
         name + "experts_up_proj_weight": w(experts, hidden, width),
         name + "experts_down_proj_weight": w(experts, width, hidden),
         name + "shared_expert_gate_weight": w(1, hidden),
         name + "shared_experts_gate_proj_weight": w(width, hidden),
         name + "shared_experts_up_proj_weight": w(width, hidden),
         name + "shared_experts_down_proj_weight": w(hidden, width)}
    u = w(tokens, hidden) * 10
    with jax.default_matmul_precision("highest"):
        want = model._sparse_ffn(cfg, p, 1, u)
        weights, ids, counts = tops.softmax_topk_router(
            u, p[name + "gate_weight"], top_k=3)
        total = tops.gated_mlp(
            u, *(p[name + "shared_experts_%s_weight" % part]
                 for part in ("gate_proj", "up_proj", "down_proj"))) \
            * jax.nn.sigmoid(u @ p[name + "shared_expert_gate_weight"].T)
        rows = 0
        for share in range(4):
            held = (2 * share, 2 * share + 1)
            part, n, _ = moe_ops.moe_held_experts(
                u, ids, weights,
                *(p[name + "experts_%s_weight" % part][jnp.asarray(held)]
                  for part in ("gate_proj", "up_proj", "down_proj")),
                held=held, num_experts=experts, capacity_factor=1.5)
            total = total + part
            rows += int(n)
    assert rows == tokens * 3 == int(counts.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _train_step(net, loss_fn, dtype, lr=1.0):
    return TrainStep(net, loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": lr},
                     mesh=make_mesh({"dp": -1}, devices=jax.devices()[:1]),
                     dtype=dtype)


def _float_and_int(params):
    floats = {k: v for k, v in params.items()
              if jnp.issubdtype(v.dtype, jnp.floating)}
    return floats, {k: v for k, v in params.items() if k not in floats}


def test_whole_model_forward_equals_the_reference(model, cfg):
    """The four layers of one period, 3 Gated DeltaNet and 1 attention,
    evaluation forward in fp32."""
    net, _ = model.build(cfg, 5)
    kinds = [type(layer.self_attn if layer._attention
                  else layer.linear_attn).__name__ for layer in net.layers]
    assert kinds == ["GatedDeltaNet"] * 3 + ["GatedAttention"]
    x, _ = model.make_batch(cfg, jax.random.PRNGKey(5), 2)
    params = {n: p.data()._data for n, p in net.collect_params().items()}
    want = np.asarray(jax.jit(
        lambda p: model.reference_forward(cfg, p, x))(params))
    with autograd.pause(train_mode=False):
        got = np.asarray(net(NDArray(x))._data)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def _two_layers(cfg):
    """One Gated DeltaNet layer and one attention layer: what a step's
    gradients have to cross, at half the compile time."""
    return dict(cfg, num_hidden_layers=2, full_attention_interval=2)


def test_bf16_step_matches_reference_logits_loss_and_gradients(model, cfg):
    cfg, seed = _two_layers(cfg), 6
    net, loss_fn = model.build(cfg, seed)
    x, y = model.make_batch(cfg, jax.random.PRNGKey(seed), 1)
    params = {n: p.data()._data for n, p in net.collect_params().items()}
    floats, ints = _float_and_int(params)

    def ref_loss(fl):
        return model.reference_loss(
            model.reference_forward(cfg, dict(fl, **ints), x), y)

    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(floats)
    want_logits = np.asarray(model.reference_forward(cfg, params, x))
    with autograd.pause(train_mode=False), override(
            {p: NDArray(params[p.name].astype(jnp.bfloat16)
                        if p.name in floats else params[p.name])
             for p in net.collect_params().values()}):
        got_logits = np.asarray(net(NDArray(x))._data.astype(jnp.float32))
    before = {k: np.asarray(v) for k, v in floats.items()}
    # one step of plain SGD at rate 1: the gradient is old minus new
    step = _train_step(net, loss_fn, "bfloat16")
    got_loss = float(step(x, y))

    scale = np.abs(want_logits).max()
    assert np.abs(got_logits - want_logits).max() <= _BF16_LOGITS * scale
    assert abs(got_loss - float(want_loss)) <= 2 * _BF16_LOGITS * scale
    assert set(step._param_vals) == set(floats)
    for name, old in before.items():
        got = old - np.asarray(step._param_vals[name])
        want = np.asarray(want_grads[name])
        assert np.linalg.norm(got - want) <= _BF16_GRAD_NORM * max(
            np.linalg.norm(want), 1e-3), name
    # the softmax router carries no bias state; the counters went through
    assert not [k for k in step._aux_vals if "e_score_correction" in k]
    counts = [v for k, v in step._aux_vals.items()
              if k.endswith("expert_counts")]
    assert len(counts) == 2
    for c in counts:
        assert c.dtype == jnp.int32 and int(c.sum()) == 32 * 3


def test_three_train_steps_follow_the_reference(model, cfg):
    """Three `TrainStep` steps of plain SGD against three steps of
    `jax.grad` of the reference's loss, from the same seeded weights on
    the same batches, in fp32: losses and every trained value."""
    cfg = _two_layers(cfg)
    net, loss_fn = model.build(cfg, 11)
    batches = [model.make_batch(cfg, jax.random.PRNGKey(100 + i), 2)
               for i in range(3)]
    params = {n: jnp.array(p.data()._data)
              for n, p in net.collect_params().items()}
    floats, ints = _float_and_int(params)
    lr = 0.05
    step = _train_step(net, loss_fn, None, lr=lr)
    got = [float(step(x, y)) for x, y in batches]

    @jax.jit
    def ref_step(fl, x, y):
        loss, grads = jax.value_and_grad(lambda f: model.reference_loss(
            model.reference_forward(cfg, dict(f, **ints), x), y))(fl)
        return loss, {k: fl[k] - lr * grads[k] for k in fl}

    want = []
    for x, y in batches:
        loss, floats = ref_step(floats, x, y)
        want.append(float(loss))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[0] != got[1]
    for name, value in floats.items():
        assert np.abs(np.asarray(step._param_vals[name])
                      - np.asarray(value)).max() <= 2e-5, name


def test_zoo_builds_the_published_pattern_and_refuses_what_is_not_built():
    from mxnet_tpu.gluon.model_zoo import qwen3_next as zoo

    cfg = {"hidden_size": 32, "num_hidden_layers": 8, "head_dim": 8,
           "full_attention_interval": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "partial_rotary_factor": 0.25,
           "rope_theta": 1e7, "rms_norm_eps": 1e-6,
           "linear_num_key_heads": 1, "linear_num_value_heads": 2,
           "linear_key_head_dim": 8, "linear_value_head_dim": 8,
           "linear_conv_kernel_dim": 4, "moe_intermediate_size": 16,
           "shared_expert_intermediate_size": 16, "num_experts": 4,
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "vocab_size": 20}
    net = zoo.qwen3_next(cfg)
    assert [zoo.is_attention_layer(cfg, i) for i in range(8)] \
        == [False, False, False, True] * 2
    assert [layer._attention for layer in net.layers] \
        == [False, False, False, True] * 2
    names = list(net.collect_params())
    assert any(n.endswith("layers3_self_attn_q_norm_weight") for n in names)
    assert any(n.endswith("layers4_linear_attn_A_log") for n in names)
    assert not any("e_score_correction" in n for n in names)
    for bad in ({"tie_word_embeddings": True}, {"mlp_only_layers": [0]},
                {"shared_expert_intermediate_size": 32}):
        with pytest.raises(ValueError):
            zoo.qwen3_next(dict(cfg, **bad))

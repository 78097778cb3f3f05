"""The Mellum-2-family decoder (grouped-query attention over a sliding
window and over every earlier key, YaRN rotary frequencies on the full
layers, a softmax top-k router with held experts and no shared expert)
at tiny widths on the CPU: the windowed flash kernels against the
two-edged mask, the grids against a brute-force count of the blocks that
hold an allowed pair, the YaRN table against the written-out formulas,
each block and the whole model through Gluon and `TrainStep` against the
benchmark's plain fp32 reference, and the eight shares of an expert
layer adding up to the uncut layer. The Pallas kernels run in interpret
mode here.
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
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import transformer_ops as tops
from mxnet_tpu.telemetry import metrics as tm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
from chipbench import harness  # noqa: E402
# seeded values for a block's parameters, a block run on them, a one-device
# plain-SGD `TrainStep`, and the split of float from integer parameters
from test_qwen3_next import (  # noqa: E402
    _float_and_int, _run, _train_step, _values)

# the whole model in bf16 against the fp32 reference, as in
# test_qwen3_next.py: logits over their largest, a gradient over its norm
_BF16_LOGITS, _BF16_GRAD_NORM = 5e-2, 0.5


@pytest.fixture(scope="module")
def model():
    return harness.load_module(_ROOT, "models", "mellum")


@pytest.fixture()
def cfg(tiny_mellum):
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "mellum2_12b_a2_5b.json")) as f:
        cfg = json.load(f)
    cfg.update({k: v for k, v in tiny_mellum.items() if k != "classes"})
    return cfg


# ---- the windowed flash kernels -------------------------------------------

def _masked_attention(q, k, v, window):
    """Plain attention under the explicit two-edged mask, fp32."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, 1) for a in (k, v))
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if window is not None:
        mask &= i - j < window
    s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _qkv(seed, heads, kv_heads, seq, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(1, heads, seq, d), (1, kv_heads, seq, d),
              (1, kv_heads, seq, d), (1, heads, seq, d)]
    return [jax.random.normal(k, s).astype(dtype)
            for k, s in zip(ks, shapes)]


# (window, block_q, block_k) on 64 positions: smaller than a block, equal,
# larger, not a multiple of either block, unequal blocks both ways, and at
# least the sequence
_WINDOWS = [(5, 16, 16), (16, 16, 16), (24, 16, 16), (40, 16, 16),
            (33, 8, 16), (20, 32, 8), (1, 16, 16), (64, 16, 16),
            (100, 16, 16)]


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 1)])
@pytest.mark.parametrize("window,block_q,block_k", _WINDOWS)
def test_windowed_flash_equals_the_two_edged_mask(window, block_q, block_k,
                                                  heads, kv_heads):
    """Value and all three gradients, at groups of 1 and of 8."""
    q, k, v, cot = _qkv(window, heads, kv_heads, 64, 8)

    def ours(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=block_q, block_k=block_k)

    got, pull = jax.vjp(ours, q, k, v)
    want, ref_pull = jax.vjp(
        lambda *a: _masked_attention(*a, window), q, k, v)
    assert float(jnp.abs(got - want).max()) < 2e-6
    for name, a, b in zip("qkv", pull(cot), ref_pull(cot)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 2e-5, "d" + name
    if window >= 64:
        # at least the sequence: the causal kernels, to the bit
        plain = pa.flash_attention(q, k, v, causal=True, block_q=block_q,
                                   block_k=block_k)
        assert np.array_equal(np.asarray(got), np.asarray(plain))


def test_windowed_flash_in_bf16_and_under_its_own_names():
    q, k, v, cot = _qkv(3, 8, 2, 128, 16, jnp.bfloat16)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * cot.astype(jnp.float32))

    ours = lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, window=40, block_q=32, block_k=32)
    ref = lambda q, k, v: _masked_attention(
        *(a.astype(jnp.float32) for a in (q, k, v)), 40)
    got = jax.grad(loss(ours), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        b = np.asarray(b, np.float32)
        assert np.abs(np.asarray(a, np.float32) - b).max() \
            <= 3e-2 * np.abs(b).max()
    text = str(jax.make_jaxpr(jax.grad(loss(ours), (0, 1, 2)))(q, k, v))
    assert "mx_flash_swa_fwd" in text and "mx_flash_swa_bwd" in text
    assert "mx_flash_fwd" not in text and "mx_flash_bwd" not in text
    full = lambda q, k, v: pa.flash_attention(q, k, v, causal=True,
                                              block_q=32, block_k=32)
    text = str(jax.make_jaxpr(jax.grad(loss(full), (0, 1, 2)))(q, k, v))
    assert "mx_flash_fwd" in text and "mx_flash_bwd" in text
    assert "swa" not in text


@pytest.mark.parametrize("seq,block_q,block_k,window", [
    (64, 16, 16, 5), (64, 16, 16, 16), (64, 8, 16, 33), (64, 32, 8, 20),
    (128, 16, 32, 40), (8192, 512, 512, 1024), (8192, 256, 1024, 1024),
    (8192, 1024, 256, 1000)])
def test_windowed_grids_visit_the_blocks_with_an_allowed_pair(
        seq, block_q, block_k, window):
    """Forward (k-blocks of a q-block) and backward (q-blocks of a
    k-block): the blocks between `_inner_blocks`' first and last are
    exactly those that hold a pair with j <= i < j + window, and the
    grid's inner extent is the most any outer block needs."""
    nq, nk = seq // block_q, seq // block_k

    def holds(i, j):
        """Block (i, j) holds an allowed pair: its nearest corner does."""
        q_hi, k_lo = (i + 1) * block_q - 1, j * block_k
        q_lo, k_hi = i * block_q, (j + 1) * block_k - 1
        return k_lo <= q_hi and q_lo - k_hi < window

    most = 0
    for i in range(nq):
        first, last = pa._inner_blocks(i, block_q, block_k, nk,
                                       window - 1, 0)
        assert [j for j in range(nk) if holds(i, j)] \
            == list(range(first, last + 1))
        most = max(most, last - first + 1)
    assert pa._window_geometry(nq, nk, block_q, block_k, window, keys=True) \
        == (most, {"window": window, "inner_blocks": nk})
    assert most < nk or window + block_q > seq
    most = 0
    for j in range(nk):
        first, last = pa._inner_blocks(j, block_k, block_q, nq, 0,
                                       window - 1)
        assert [i for i in range(nq) if holds(i, j)] \
            == list(range(first, last + 1))
        most = max(most, last - first + 1)
    assert pa._window_geometry(nk, nq, block_k, block_q, window,
                               keys=False)[0] == most
    assert pa._window_geometry(nk, nq, block_k, block_q, None, False) \
        == (nq, {})


def test_window_refusals_and_counter(monkeypatch):
    q, k, v, _ = _qkv(0, 2, 1, 32, 8)
    for bad in (dict(causal=False, window=4), dict(causal=True, window=0)):
        with pytest.raises(ValueError, match="window"):
            pa.flash_attention(q, k, v, **bad)
    # heads too long for the fused backward take the pair, which has no
    # window
    monkeypatch.setattr(pa, "FUSED_DQ_BYTES", 0)
    with pytest.raises(ValueError, match="no window"):
        jax.grad(lambda q: pa.flash_attention(
            q, k, v, causal=True, window=4).sum())(q)
    monkeypatch.undo()
    counter = tm.REGISTRY.get("mx_flash_attention_window_traced_total")
    before = {w: counter.labels(window=w).value for w in ("4", "none")}
    pa.flash_attention(q, k, v, causal=True, window=4)
    pa.flash_attention(q, k, v, causal=True, window=32)   # the sequence
    pa.flash_attention(q, k, v, causal=True)
    assert counter.labels(window="4").value == before["4"] + 1
    assert counter.labels(window="none").value == before["none"] + 2


# ---- the rotary tables ------------------------------------------------------

_PUBLISHED_YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                   "original_max_position_embeddings": 8192,
                   "beta_fast": 32, "beta_slow": 1,
                   "attention_factor": 1.2772588722239782}


def _turned(model, x, rope):
    cos, sin = model.rope_table(rope, x.shape[-1], x.shape[-2])
    return model._rope(x, cos, sin)


@pytest.mark.parametrize("d", [128, 16])
def test_yarn_table_equals_the_written_out_formulas(model, d):
    from mxnet_tpu.gluon.model_zoo import mellum as zoo

    x = jnp.asarray(np.random.RandomState(d).randn(2, 3, 256, d),
                    jnp.float32)
    args = zoo.rope_scaling(_PUBLISHED_YARN)
    assert args == {"scaling_factor": 16, "original_max_position": 8192,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.2772588722239782}
    got = tops.rotary_embedding(x, theta=500000.0, interleaved=False,
                                **args)
    want = _turned(model, x, _PUBLISHED_YARN)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # the default factor is the published one: 0.1 ln(16) + 1
    left_out = tops.rotary_embedding(
        x, theta=500000.0, interleaved=False,
        **{k: v for k, v in args.items() if k != "attention_factor"})
    np.testing.assert_allclose(np.asarray(left_out), np.asarray(got),
                               rtol=1e-6)
    if d == 128:
        # pairs that turn more than 32 times in 8,192 positions keep
        # their frequency, those under one turn have a sixteenth
        ramp = tops._yarn_ramp(128, 500000.0, 8192, 32, 1)
        assert ramp.shape == (64,) and ramp[18] == 0 and ramp[35] == 1
        assert 0 < ramp[19] < ramp[34] < 1
        np.testing.assert_allclose(ramp[19:35], np.arange(1, 17) / 17,
                                   rtol=1e-6)
    # a scaled table differs from the plain one, and by more than the
    # factor alone
    plain = tops.rotary_embedding(x, theta=500000.0, interleaved=False)
    assert float(jnp.abs(got - plain * 1.2772588722239782).max()) > 0.1


@pytest.mark.parametrize("interleaved", [False, True])
def test_rotary_without_scaling_is_what_it_was(model, interleaved):
    """No YaRN parameter given: the op's plain table (the reference's
    `default` entry), to the bit whatever the other scaling keywords'
    defaults are."""
    x = jnp.asarray(np.random.RandomState(1).randn(2, 64, 32), jnp.float32)
    got = tops.rotary_embedding(x, theta=1e4, interleaved=interleaved)
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    want = _turned(model, x, {"rope_type": "default", "rope_theta": 1e4})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    counter = tm.REGISTRY.get("mx_rotary_embedding_scaling_traced_total")
    old = tm.REGISTRY.get("mx_rotary_embedding_traced_total")
    before = (counter.labels(scaling="none").value,
              counter.labels(scaling="yarn").value,
              old.labels(interleaved="false").value)
    tops.rotary_embedding(x, theta=1e4, interleaved=False)
    tops.rotary_embedding(x, theta=1e4, interleaved=False,
                          scaling_factor=4, original_max_position=64)
    assert (counter.labels(scaling="none").value,
            counter.labels(scaling="yarn").value,
            old.labels(interleaved="false").value) \
        == (before[0] + 1, before[1] + 1, before[2] + 2)


# ---- blocks and the model against the reference --------------------------

@pytest.mark.parametrize("layer,kind", [(0, "sliding_attention"),
                                        (3, "full_attention")])
def test_attention_block_equals_the_reference(model, cfg, layer, kind):
    from mxnet_tpu.gluon.model_zoo import mellum as zoo

    assert cfg["layer_types"][layer] == kind
    rope = cfg["rope_parameters"][kind]
    block = nn.GroupedQueryAttention(
        64, 4, 2, 16, rope_theta=rope["rope_theta"],
        rope_scaling=zoo.rope_scaling(rope),
        window=8 if kind == "sliding_attention" else None,
        prefix="m_layers%d_self_attn_" % layer)
    block.initialize()
    assert block._scope == kind
    values = _values(block, layer)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 64), jnp.float32)
    got = _run(block, values, x)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(model._attention(cfg, values, layer, r))
                         for r in x])
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # the other kind's mask and table give another result
    other = dict(cfg, layer_types=cfg["layer_types"][::-1])
    with jax.default_matmul_precision("highest"):
        wrong = np.asarray(model._attention(other, values, layer, x[0]))
    assert np.abs(got[0] - wrong).max() > 1e-2 * np.abs(want).max()


def test_sparse_moe_without_a_shared_expert_equals_the_reference(model, cfg):
    block = nn.SparseMoE(64, 32, 8, held=(0, 1), top_k=3, router="softmax",
                         prefix="m_layers1_mlp_")
    block.initialize()
    assert block.shared_experts is None
    values = _values(block, 4)
    x = jnp.asarray(np.random.RandomState(5).randn(1, 32, 64), jnp.float32)
    got = _run(block, values, x)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model._sparse_ffn(cfg, values, 1, x[0]))
    assert np.abs(got[0] - want).max() <= 1e-5 * np.abs(want).max()


def test_eight_shares_add_up_to_the_uncut_layer(model, cfg):
    """With 16 experts over 8 shares of 2, the eight routed parts (there
    is nothing every chip computes alike: no shared expert) equal the
    uncut reference's layer."""
    experts, shares = 16, 8
    cfg = dict(cfg, num_experts=experts,
               published=dict(cfg["published"], num_experts=experts))
    rng = np.random.RandomState(0)
    hidden, width, tokens = 64, 32, 32

    def w(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)

    name = "m_layers1_mlp_"
    p = {name + "gate_weight": w(experts, hidden),
         name + "experts_gate_proj_weight": w(experts, hidden, width),
         name + "experts_up_proj_weight": w(experts, hidden, width),
         name + "experts_down_proj_weight": w(experts, width, hidden)}
    u = w(tokens, hidden) * 10
    with jax.default_matmul_precision("highest"):
        want = model._sparse_ffn(cfg, p, 1, u)
        weights, ids, counts = tops.softmax_topk_router(
            u, p[name + "gate_weight"], top_k=3)
        total, rows = 0.0, 0
        for share in range(shares):
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


def test_whole_model_forward_equals_the_reference(model, cfg):
    """The four layers of one period, 3 sliding and 1 full, evaluation
    forward in fp32."""
    net, _ = model.build(cfg, 5)
    assert [layer.self_attn._scope for layer in net.layers] \
        == ["sliding_attention"] * 3 + ["full_attention"]
    x, _ = model.make_batch(cfg, jax.random.PRNGKey(5), 2)
    params = {n: p.data()._data for n, p in net.collect_params().items()}
    want = np.asarray(jax.jit(
        lambda p: model.reference_forward(cfg, p, x))(params))
    with autograd.pause(train_mode=False):
        got = np.asarray(net(NDArray(x))._data)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_a_build_of_the_step_counts_its_windowed_calls(model, cfg):
    """In one build of the four-layer step the windowed calls read three
    times the full layer's, and the scaled tables (q and k of the full
    layer) a third of the plain ones."""
    window = tm.REGISTRY.get("mx_flash_attention_window_traced_total")
    scaling = tm.REGISTRY.get("mx_rotary_embedding_scaling_traced_total")

    def reads():
        return (window.labels(window="8").value,
                window.labels(window="none").value,
                scaling.labels(scaling="yarn").value,
                scaling.labels(scaling="none").value)

    net, loss_fn = model.build(cfg, 5)
    x, y = model.make_batch(cfg, jax.random.PRNGKey(5), 1)
    before = reads()
    float(_train_step(net, loss_fn, "bfloat16")(x, y))
    sliding, full, yarn, plain = (b - a for a, b in zip(before, reads()))
    assert full >= 1 and sliding == 3 * full
    assert yarn == 2 * full and plain == 3 * yarn


def _two_layers(cfg):
    """One sliding layer and one full layer: what a step's gradients have
    to cross, at half the compile time."""
    return dict(cfg, num_hidden_layers=2,
                layer_types=["sliding_attention", "full_attention"])


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


def test_zoo_reads_the_published_keys_and_refuses_what_is_not_built():
    from mxnet_tpu.gluon.model_zoo import mellum as zoo

    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "mellum2_12b_a2_5b.json")) as f:
        published = json.load(f)
    cfg = dict(published, hidden_size=32, head_dim=8, num_attention_heads=2,
               num_key_value_heads=1, moe_intermediate_size=16,
               num_experts=4, num_experts_per_tok=2, vocab_size=20,
               num_hidden_layers=8)
    net = zoo.mellum(cfg)
    assert [layer.self_attn._scope for layer in net.layers] \
        == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    for layer in net.layers:
        attn = layer.self_attn
        sliding = attn._scope == "sliding_attention"
        assert attn._flash_args == ({"window": 1024} if sliding else {})
        assert ("scaling_factor" in attn._rope_args) == (not sliding)
        assert attn._rope_args["theta"] == 500000.0
        assert layer.mlp.shared_experts is None
    names = list(net.collect_params())
    assert any(n.endswith("layers3_self_attn_q_norm_weight") for n in names)
    assert not any("e_score_correction" in n or "shared" in n
                   for n in names)
    kinds = dict(cfg["rope_parameters"])
    kinds["full_attention"] = dict(kinds["full_attention"],
                                   rope_type="longrope")
    for bad in ({"tie_word_embeddings": True}, {"attention_bias": True},
                {"mlp_layer_types": ["dense"] * 28},
                {"use_sliding_window": False},
                {"layer_types": ["chunked_attention"] * 28},
                {"rope_parameters": kinds}):
        with pytest.raises(ValueError):
            zoo.mellum(dict(cfg, **bad))

"""Pallas flash attention kernel (interpret mode on cpu; compiled on
TPU). TPU-first flagship kernel — no reference counterpart."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.pallas_attention import flash_attention


def _dense(q, k, v, causal=False, scale=None):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t = q.shape[2]
        s = np.where(np.tril(np.ones((t, t), bool)), s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(2, 2, 64, 16).astype(np.float32)
               for _ in range(3))
    got = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal,
                                     block_q=16, block_k=16))
    np.testing.assert_allclose(got, _dense(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_uneven_blocks_rejected():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 1, 48, 8).astype(np.float32))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, block_q=32, block_k=32)


def test_flash_attention_gradients_match_dense():
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 1, 32, 8).astype(np.float32))
               for _ in range(3))

    def flash_loss(q_, k_, v_):
        return (flash_attention(q_, k_, v_, causal=True, block_q=8,
                                block_k=8) ** 2).mean()

    def dense_loss(q_, k_, v_):
        scale = q_.shape[-1] ** -0.5
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * scale
        t = q_.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v_)
        return (out ** 2).mean()

    g = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


def test_flash_attention_nd_op_surface():
    rng = np.random.RandomState(3)
    q = mx.nd.array(rng.randn(1, 2, 32, 8).astype(np.float32))
    out = mx.nd.contrib.flash_attention(q, q, q, causal=True,
                                        block_q=16, block_k=16)
    want = _dense(q.asnumpy(), q.asnumpy(), q.asnumpy(), causal=True)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=2e-4, atol=2e-5)


def test_flash_attention_cross_attention_with_gradients():
    """tq != tk (decoder cross-attention): forward AND backward work."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 48, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 48, 8).astype(np.float32))
    got = np.asarray(flash_attention(q, k, v, block_q=8, block_k=16))
    want = _dense(np.asarray(q), np.asarray(k), np.asarray(v))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    g = jax.grad(lambda a, b, c: (flash_attention(
        a, b, c, block_q=8, block_k=16) ** 2).mean(),
        argnums=(0, 1, 2))(q, k, v)
    assert all(float(jnp.abs(x).sum()) > 0 for x in g)


def test_flash_backward_memory_is_sub_quadratic():
    """The flash backward's compiled artifact must NOT carry O(T²)
    temporaries — the old fallback (jax.vjp through blockwise_attention)
    stored per-block probabilities across scan steps, ~20× the memory at
    T=4k (VERDICT r4 #5). Asserted on XLA's buffer assignment."""
    import jax
    from mxnet_tpu.ops.pallas_attention import flash_attention
    from mxnet_tpu.parallel.ring_attention import blockwise_attention

    T, D = 2048, 32
    q = jnp.ones((1, 1, T, D), jnp.float32)

    flash = jax.jit(jax.grad(
        lambda a, b, c: flash_attention(a, b, c, causal=True).sum(),
        argnums=(0, 1, 2)))
    fallback = jax.jit(jax.grad(
        lambda a, b, c: blockwise_attention(a, b, c, block=128,
                                            causal=True).sum(),
        argnums=(0, 1, 2)))
    flash_tmp = flash.lower(q, q, q).compile() \
        .memory_analysis().temp_size_in_bytes
    fb_tmp = fallback.lower(q, q, q).compile() \
        .memory_analysis().temp_size_in_bytes
    # The O(T²) probability tensor alone is T*T*4 bytes.
    assert flash_tmp < T * T * 4, flash_tmp
    assert flash_tmp * 4 < fb_tmp, (flash_tmp, fb_tmp)


def test_flash_backward_matches_blockwise_vjp():
    """Interpret-mode parity of the Pallas backward against autodiff
    through the XLA blockwise formulation (same math, independent
    implementation)."""
    import jax
    from mxnet_tpu.ops.pallas_attention import flash_attention
    from mxnet_tpu.parallel.ring_attention import blockwise_attention

    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(2, 2, 64, 16).astype(np.float32))
               for _ in range(3))
    g = jnp.asarray(rng.randn(2, 2, 64, 16).astype(np.float32))
    for causal in (False, True):
        _, vjp_f = jax.vjp(lambda a, b, c: flash_attention(
            a, b, c, causal=causal, block_q=16, block_k=16), q, k, v)
        _, vjp_b = jax.vjp(lambda a, b, c: blockwise_attention(
            a, b, c, block=16, causal=causal), q, k, v)
        for gf, gb, name in zip(vjp_f(g), vjp_b(g), "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gb), rtol=2e-4, atol=2e-5,
                err_msg="d%s diverged (causal=%s)" % (name, causal))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2 ** -7)])
@pytest.mark.parametrize("tq,tk,block_q,block_k", [
    (256, 256, 64, 128),      # block_q != block_k
    (256, 256, 128, 64),
    (128, 384, 64, 128),      # tq != tk
    (256, 256, 256, 256),     # one block pair
])
@pytest.mark.parametrize("d_qk,d_v", [(64, 64), (48, 32)])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_matches_split(causal, d_qk, d_v, tq, tk, block_q,
                                      block_k, dtype, tol):
    """One pass against the dK/dV and dQ pair on the same operands: the
    same tiles in the same order, so dK and dV are the pair's to the
    bit, and dQ (whose product is written the other way round, dS^T
    contracted over its rows) to fp32 rounding: 1e-5, or one place of
    a bf16 result."""
    rng = np.random.RandomState(5)
    q, k, v, do = (jnp.asarray(rng.randn(2, t, d).astype(np.float32), dtype)
                   for t, d in ((tq, d_qk), (tk, d_qk), (tk, d_v),
                                (tq, d_v)))
    scale = d_qk ** -0.5
    # The logsumexp and delta a forward over the same q, k, v saves.
    out, lse = pa._flash_forward(q[None], k[None], v[None], scale, causal,
                                 block_q, block_k, True)
    delta = jnp.sum(do.astype(jnp.float32) * out[0].astype(jnp.float32),
                    axis=-1)[:, None, :]
    operands = (q, k, v, do, lse[0][:, None, :], delta)
    static = (scale, causal, block_q, block_k, True)
    dk, dv, dq = pa._flash_bwd_fused(*operands, *static)
    dk_ref, dv_ref = pa._flash_dkv(*operands, *static)
    dq_ref = pa._flash_dq(*operands, *static)
    for got, want in ((dk, dk_ref), (dv, dv_ref), (dq, dq_ref)):
        assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(dk, np.float32),
                                  np.asarray(dk_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(dv, np.float32),
                                  np.asarray(dv_ref, np.float32))
    np.testing.assert_allclose(np.asarray(dq, np.float32),
                               np.asarray(dq_ref, np.float32),
                               rtol=tol, atol=1e-5)


def test_backward_path_follows_the_accumulator_budget(monkeypatch):
    """The path is chosen from (tq, d_qk) alone: one head's fp32 dQ
    under `FUSED_DQ_BYTES` takes the fused kernel, over it the pair;
    the counter's labels say which, and both give the same gradients."""
    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 64, 16).astype(np.float32))
               for _ in range(3))

    def grads():
        return jax.grad(lambda a, b, c: (flash_attention(
            a, b, c, causal=True, block_q=16, block_k=32) ** 2).mean(),
            argnums=(0, 1, 2))(q, k, v)

    def counts():
        return {path: pa._flash_bwd_traced.labels(path=path).value
                for path in ("fused", "split")}

    assert pa._bwd_path(16384, 192) == "fused"
    before = counts()
    fused = grads()
    after = counts()
    assert (after["fused"] - before["fused"],
            after["split"] - before["split"]) == (1, 0)
    monkeypatch.setattr(pa, "FUSED_DQ_BYTES", 64 * 16 * 4 - 1)
    split = grads()
    last = counts()
    assert (last["fused"] - after["fused"],
            last["split"] - after["split"]) == (0, 1)
    for a, b in zip(fused, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

"""`ops/transformer_ops.py:rotary_embedding` against the stride-2 form
it replaced, kept here as the reference: the same bits, value and
gradient, and a lowering with nothing gathered or scattered in it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import transformer_ops as tops
from mxnet_tpu.telemetry import metrics as tm


def _rotary_by_strides(data, theta=10000.0, interleaved=True):
    """The op as it stood before the 0/1 product: the pairs picked by
    two stride-2 slices of the last axis (a gather each)."""
    d, seq = data.shape[-1], data.shape[-2]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = data.astype(jnp.float32)
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
    else:
        a, b = x[..., :d // 2], x[..., d // 2:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(data.dtype)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("lead", [(2, 4), (1, 1)],
                         ids=["heads", "shared_key"])
@pytest.mark.parametrize("d", [64, 32])
@pytest.mark.parametrize("interleaved", [True, False])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_rotary_equals_the_strided_form_to_the_bit(dtype, interleaved, d,
                                                   lead):
    rng = np.random.RandomState(d + len(lead) + int(interleaved))
    shape = lead + (16, d)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)
    g = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)
    got, got_vjp = jax.vjp(lambda a: tops.rotary_embedding(
        a, theta=1e6, interleaved=interleaved), x)
    want, want_vjp = jax.vjp(lambda a: _rotary_by_strides(
        a, theta=1e6, interleaved=interleaved), x)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))
    (dx,), (want_dx,) = got_vjp(g), want_vjp(g)
    assert dx.dtype == want_dx.dtype == dtype
    np.testing.assert_array_equal(_bits(dx), _bits(want_dx))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_rotary_lowers_without_gather_or_scatter(dtype):
    """A stride-2 slice lowers to `stablehlo.gather` on any backend and
    its transpose to a scatter; the op holds neither, forward or
    backward, and carries its scope's name."""
    x = jax.ShapeDtypeStruct((2, 4, 16, 64), dtype)

    def grad(a):
        return jax.grad(lambda b: jnp.sum(
            tops.rotary_embedding(b).astype(jnp.float32) ** 2))(a)

    strided = jax.jit(_rotary_by_strides).lower(x).as_text()
    assert "stablehlo.gather" in strided          # what the test is for
    for fn in (tops.rotary_embedding, grad):
        lowered = jax.jit(fn).lower(x)
        text = lowered.as_text()
        assert "stablehlo.gather" not in text
        assert "stablehlo.scatter" not in text
        assert "stablehlo.dot_general" in text
        assert "rotary_embedding" in lowered.as_text(debug_info=True)


def test_rotary_traces_are_counted_by_pairing():
    family = tm.REGISTRY.get("mx_rotary_embedding_traced_total")

    def read(label):
        return family.labels(interleaved=label).value

    before = read("true"), read("false")
    x = jnp.ones((1, 2, 8, 16), jnp.float32)
    step = jax.jit(lambda a: tops.rotary_embedding(a)
                   + tops.rotary_embedding(2 * a))
    step(x)
    step(x)                                # no second trace, no count
    assert (read("true"), read("false")) == (before[0] + 2, before[1])
    tops.rotary_embedding(x, interleaved=False)
    assert (read("true"), read("false")) == (before[0] + 2, before[1] + 1)

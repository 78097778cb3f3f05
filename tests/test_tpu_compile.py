"""The chip's compiler, asked without the chip: the flash-attention
kernels (forward, the fused backward, and the dK/dV and dQ pair of the
long-sequence path) at real widths (equal, and latent attention's
192/128 at the blocks the kernels default to), compiled for a described
TPU v5e (2x2). What
interpret mode cannot refuse — a block the lowering does not tile, more
VMEM than a kernel may use — is refused here, at no chip time.

One file, on purpose: only one process may hold the TPU's library, the
worker that is given this file loads it on the first test, and a second
file could land on another worker. The topology is described inside the
fixture, never at import, and the compile runs in the test's own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_attention as pa

_B, _H, _T = 4, 16, 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % exc)
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


_BACKWARD = {"dkv": pa._flash_dkv, "dq": pa._flash_dq,
             "fused": pa._flash_bwd_fused}


def _launch(kernel, d, scale):
    """(function, argument shapes) of one kernel's pallas_call at
    (4, 16, 2048, d) bf16, causal, blocks 128/128, interpret=False."""
    bh = _B * _H
    qkv = ((bh, _T, d), jnp.bfloat16)
    row = ((bh, 1, _T), jnp.float32)
    static = (scale, True, 128, 128, False)
    if kernel == "fwd":
        return (lambda q, k, v: pa._flash_forward(q, k, v, *static),
                [((_B, _H, _T, d), jnp.bfloat16)] * 3)
    return (lambda *a: _BACKWARD[kernel](*a, *static),
            [qkv] * 4 + [row] * 2)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq", "fused"])
def test_flash_kernel_compiles_for_v5e(one_chip, kernel, d):
    fn, shapes = _launch(kernel, d, d ** -0.5)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    # Flash: the (b*h, T, T) score tensor never exists, nor a fraction.
    scores = _B * _H * _T * _T * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores // 4


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq", "fused"])
def test_flash_kernel_compiles_for_v5e_with_unequal_widths(one_chip,
                                                           kernel):
    """(1, 32, 4096) heads 192 wide in q and k, 128 in v, causal, at the
    default blocks: the benchmark cell's call."""
    b, h, t, d_qk, d_v = 1, 32, 4096, 192, 128
    static = (d_qk ** -0.5, True) + pa.DEFAULT_BLOCK + (False,)
    qk, v = ((b * h, t, d_qk), jnp.bfloat16), ((b * h, t, d_v), jnp.bfloat16)
    row = ((b * h, 1, t), jnp.float32)
    if kernel == "fwd":
        fn = lambda q, k, v_: pa._flash_forward(q, k, v_, *static)
        shapes = [((b, h, t, d_qk), jnp.bfloat16)] * 2 \
            + [((b, h, t, d_v), jnp.bfloat16)]
    else:
        fn = lambda *a: _BACKWARD[kernel](*a, *static)
        shapes = [qk, qk, v, v, row, row]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < b * h * t * t * 4 // 4


def test_rotary_compiles_for_v5e_without_gather_or_scatter(one_chip):
    """Interleaved rotary at latent attention's q-rope, (1, 32, 4096, 64)
    bf16, value and gradient: the pairs are parted by a product, so the
    chip's compiler is left no gather, no scatter and no custom fusion
    (how it keeps a stride-2 pick of the minor axis). `bytes accessed`
    reads 473 MB, 7.0 times the four arrays a forward and backward must
    move (x and the cotangent in, the value and the gradient out), where
    the strided form read 1,210 MB: every fp32 intermediate is counted
    whole, so three times is out of XLA's reach and eight is the bound."""
    from mxnet_tpu.ops.transformer_ops import rotary_embedding

    shape = (1, 32, 4096, 64)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def value_and_gradient(a, g):
        out, vjp = jax.vjp(lambda b: rotary_embedding(b, theta=1e6), a)
        return out, vjp(g)[0]

    compiled = jax.jit(value_and_gradient).lower(x, x).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert " scatter(" not in text
    assert "kind=kCustom" not in text
    must = 4 * 2 * 32 * 4096 * 64
    assert compiled.cost_analysis()["bytes accessed"] < 8 * must

"""The chip's compiler, asked without the chip: the flash-attention
kernels (forward, the fused backward, and the dK/dV and dQ pair of the
long-sequence path) at real widths (equal, latent attention's
192/128, and 16 query heads on 2 key/value heads at 256, at the blocks
the kernels default to, and 32 on 4 at 128 over 8,192 tokens under a
window of 1,024), the delta rule's kernels at (1, 32, 4096, 128), the experts' combine
at the three cells' buffers and one sparse layer's value and gradient at
cell 5's shapes (no scatter over hidden-wide rows), and
the whole training steps of the `qwen3_next_80b_a3b` and
`mellum2_12b_a2_5b` configurations, compiled for a described TPU v5e
(2x2). What
interpret mode cannot refuse — a block the lowering does not tile, more
VMEM than a kernel may use — is refused here, at no chip time.

One file, on purpose: only one process may hold the TPU's library, the
worker that is given this file loads it on the first test, and a second
file could land on another worker. The topology is described inside the
fixture, never at import, and the compile runs in the test's own process.
"""
import collections
import os
import re

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


def test_grouped_flash_kernels_compile_for_v5e_at_width_256(one_chip):
    """16 query heads on 2 key/value heads, 256 wide, 4,096 tokens,
    causal, at the default blocks: the forward, the fused backward of a
    group and the dK/dV and dQ pair; dK and dV per key/value head."""
    b, h, kv, t, d = 1, 16, 2, 4096, 256
    static = (d ** -0.5, True) + pa.DEFAULT_BLOCK + (False,)
    assert pa._bwd_path(t, d, t, d, h // kv) == "fused"
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                           sharding=one_chip)
    row = jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32,
                               sharding=one_chip)
    q3, k3 = spec(b * h, t, d), spec(b * kv, t, d)
    calls = {"fwd": (lambda q, k, v: pa._flash_forward(q, k, v, *static),
                     [spec(b, h, t, d), spec(b, kv, t, d),
                      spec(b, kv, t, d)])}
    for name, fn in _BACKWARD.items():
        calls[name] = (lambda *a, fn=fn: fn(*a, *static),
                       [q3, k3, k3, q3, row, row])
    for name, (fn, args) in calls.items():
        lowered = jax.jit(fn).lower(*args)
        assert "tpu_custom_call" in lowered.as_text(), name
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes \
            < b * h * t * t * 4 // 4, name
        if name in ("dkv", "fused"):
            assert [o.shape for o in jax.eval_shape(fn, *args)][:2] \
                == [(b * kv, t, d)] * 2


def test_delta_rule_kernels_compile_for_v5e(one_chip):
    """The gated delta rule at cell 4's shape, (1, 32, 4096, 128) bf16 on
    16 key heads, chunk 64, value and all five gradients: four kernels
    (`mx_gdn_prepare`, once, forward: the backward is handed its operands
    and T; `mx_gdn_fwd`, `mx_gdn_bwd`, `mx_gdn_prepare_bwd`) and next to
    nothing around them. With the preparation and its pullback left to
    XLA the program read 1,359 entry instructions and 138 fusions here;
    with the preparation run again backward, 63 and 0; now 55 and 0. No
    residual grows with a state a token (8.6 GB in fp32; one a chunk is
    134 MB): the temporaries read 469,923,328 bytes, as they did with the
    preparation run again (the kept operands are live where the formed
    ones were)."""
    from mxnet_tpu.ops import linear_attention as la

    b, hk, h, t, d = 1, 16, 32, 4096, 128
    spec = jax.ShapeDtypeStruct
    qk = spec((b, hk, t, d), jnp.bfloat16, sharding=one_chip)
    x = spec((b, h, t, d), jnp.bfloat16, sharding=one_chip)
    g = spec((b, h, t), jnp.float32, sharding=one_chip)

    def value_and_gradients(q, k, v, g_, beta, cot):
        out, pull = jax.vjp(lambda *a: la.gated_delta_rule(
            *a, chunk=64, interpret=False), q, k, v, g_, beta)
        return out, pull(cot)

    lowered = jax.jit(value_and_gradients).lower(qk, qk, x, g, g, x)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("mx_gdn_prepare", "mx_gdn_prepare_bwd", "mx_gdn_fwd",
                 "mx_gdn_bwd"):
        assert text.count('kernel_name = "%s"' % name) == 1, name
    compiled = lowered.compile()
    entry = compiled.as_text().split("ENTRY", 1)[1]
    instructions = [line for line in entry.splitlines() if " = " in line]
    assert len(instructions) <= 80
    assert sum(" fusion(" in line for line in instructions) <= 8
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


_CELLS = {"mellum2": (20480, 8, 2304, 896), "kanana2": (4608, 16, 2048, 768),
          "qwen3next": (1920, 16, 2048, 512)}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_grouped_product_kernels_compile_for_v5e(one_chip, cell):
    """`mx_gmm`, `mx_gmm_t` and `mx_tgmm` at a cell's buffer (rows,
    held experts, hidden, expert width; bf16), the gate/up and the down
    product's shapes, at the tiles the shapes give: a block that is no
    multiple of the lanes or a tile set over VMEM is refused here. The
    results are the only arrays made: no copy of the weights."""
    from mxnet_tpu.ops import pallas_grouped_matmul as pg

    rows, groups, hidden, width = _CELLS[cell]
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                           sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    for k, n in ((hidden, width), (width, hidden)):
        calls = {
            "mx_gmm": (lambda a, b, s: pg.mx_gmm(a, b, s, interpret=False),
                       [spec(rows, k), spec(groups, k, n), sizes]),
            "mx_gmm_t": (lambda a, b, s: pg.mx_gmm(
                a, b, s, transpose_rhs=True, interpret=False),
                [spec(rows, n), spec(groups, k, n), sizes]),
            "mx_tgmm": (lambda a, b, s: pg.mx_tgmm(a, b, s, interpret=False),
                        [spec(rows, k), spec(rows, n), sizes])}
        for name, (fn, args) in calls.items():
            lowered = jax.jit(fn).lower(*args)
            assert 'kernel_name = "%s"' % name in lowered.as_text(), name
            compiled = lowered.compile()
            entry = compiled.as_text().split("ENTRY", 1)[1]
            assert not [line for line in entry.splitlines()
                        if " copy(" in line and "bf16[" in line], name
            assert compiled.memory_analysis().temp_size_in_bytes \
                < 2 ** 20, name


# tokens, hidden, width, held, experts, top_k, capacity factor
_LAYERS = {"mellum2": (8192, 2304, 896, 8, 64, 8, 2.5),
           "kanana2": (4096, 2048, 768, 16, 128, 6, 1.5),
           "qwen3next": (4096, 2048, 512, 16, 512, 10, 2.0)}


@pytest.mark.parametrize("cell", sorted(_LAYERS))
def test_moe_combine_kernel_compiles_for_v5e(one_chip, cell):
    """`mx_moe_combine` at a cell's tokens, hidden width, held experts
    and buffer (bf16 rows, fp32 result), at the tiles the shapes give."""
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops import pallas_moe_combine as pmc

    tokens, hidden, _, held, experts, top_k, cf = _LAYERS[cell]
    rows = moe.buffer_rows(tokens, top_k, held, experts, cf)
    spec = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    lowered = jax.jit(lambda x, p, w: pmc.mx_moe_combine(
        x, p, w, interpret=False)).lower(
            spec((rows, hidden), jnp.bfloat16),
            spec((tokens, held), jnp.int32),
            spec((tokens, held), jnp.float32))
    assert 'kernel_name = "mx_moe_combine"' in lowered.as_text()
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < tokens * hidden * 4


def test_sparse_layer_moves_rows_without_a_scatter(one_chip):
    """One sparse layer's value and gradient at cell 5's shapes (8,192
    tokens, 2,304 wide, 8 of 64 experts held, 8 a token, a buffer of
    20,480 rows): no `scatter` over hidden-wide rows in the compiled
    program (the combine and the dispatch's pullback are gathers), two
    `mx_moe_combine` calls (the result and the dispatch's pullback), and
    temporaries under the 1.19 GB that the scatter form took by ISSUE
    41's count."""
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops import pallas_grouped_matmul as pg

    tokens, hidden, width, held, experts, top_k, cf = _LAYERS["mellum2"]
    spec = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    bf16 = jnp.bfloat16

    def value_and_gradient(x, ids, w, gate, up, down, cot):
        def value(x, w, gate, up, down):
            out, _, _ = moe.moe_held_experts(
                x, ids, w, gate, up, down, held=tuple(range(held)),
                num_experts=experts, capacity_factor=cf)
            return jnp.sum(out.astype(jnp.float32) * cot)
        return jax.value_and_grad(value, argnums=(0, 1, 2, 3, 4))(
            x, w, gate, up, down)

    # off the TPU the kernels would take interpret mode
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        lowered = jax.jit(value_and_gradient).lower(
            spec((tokens, hidden), bf16), spec((tokens, top_k), jnp.int32),
            spec((tokens, top_k), jnp.float32),
            spec((held, hidden, width), bf16),
            spec((held, hidden, width), bf16),
            spec((held, width, hidden), bf16),
            spec((tokens, hidden), jnp.float32))
    finally:
        jax.default_backend = backend
    assert pg._interpret(None)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    wide = [line for line in hlo.splitlines()
            if re.search(r"= \S+\[[0-9,]*%d\]\S* scatter\(" % hidden, line)]
    assert not wide, wide
    assert len(re.findall(r"%mx_moe_combine(?:\.\d+)? = ", hlo)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.19e9


def _config_and_hbm(name):
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        return cfg, json.load(f)["TPU v5 lite"]["hbm_bytes"]


def _lowered_step(one_chip, net, cfg):
    """(`TrainStep` program of `net` lowered for the described chip from
    shapes alone: one sequence of `bptt` tokens, bf16 with fp32 masters,
    the configuration's optimizer; the trained parameters' count)."""
    import numpy as np

    from mxnet_tpu import gluon
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.parallel import TrainStep, make_mesh
    from mxnet_tpu.parallel.mesh import data_sharding

    # the parameters exist as shapes alone: nothing of their size is drawn
    shapes = {name: jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype))
              for name, p in net.collect_params().items()}
    count = sum(int(np.prod(p.shape))
                for p in net.collect_params().values()
                if p.grad_req != "null")
    device = one_chip._device
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer=cfg["optimizer"]["name"],
                     optimizer_params=dict(cfg["optimizer"]["params"]),
                     mesh=make_mesh({"dp": -1}, devices=[device]),
                     dtype="bfloat16")
    spec = jax.ShapeDtypeStruct

    class _Shaped:
        """What `_materialize` reads of a parameter's data."""

        def __init__(self, shape):
            self._data = shape

    for name, p in net.collect_params().items():
        p._data = {None: _Shaped(shapes[name])}
    step._place = lambda v, sharding: spec(v.shape, v.dtype,
                                           sharding=sharding)
    step._opt_init = lambda v: (spec(v.shape, jnp.float32),) * 2
    step._materialize(None)
    # off the TPU the kernels would take interpret mode: compile them
    real = (pa.flash_attention, la.gated_delta_rule)
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        step._build()
        tokens = spec((1, cfg["bptt"]), jnp.int32,
                      sharding=data_sharding(step.mesh))
        lowered = step._jitted.lower(
            step._param_vals, step._opt_state, step._aux_vals, tokens,
            tokens, spec((), jnp.float32), spec((), jnp.float32),
            spec((2,), jnp.uint32))
    finally:
        jax.default_backend = backend
    assert real == (pa.flash_attention, la.gated_delta_rule)
    return lowered, count


def _assert_fits(compiled, count, hbm):
    """Arguments and temporaries under the chip's memory, with room for
    the imperative gradient buffers that the process also holds (4 bytes
    a parameter); returns the program's bytes."""
    memory = compiled.memory_analysis()
    program = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    assert memory.argument_size_in_bytes >= 12 * count
    assert program + 4 * count < hbm
    return program


def test_qwen3_next_step_fits_the_chip(one_chip):
    """The whole `TrainStep` program of the `qwen3_next_80b_a3b`
    configuration (one sequence of 4,096 tokens, bf16 with fp32 masters,
    Adam), from shapes alone: arguments and temporaries stay under the
    16 GB of `peaks.json`, with room for the imperative gradient buffers
    that the process also holds (4 bytes a parameter). The delta rule's
    preparation runs once a layer: its operands and T are the backward's
    residuals (0.236 GB a layer, `p` padded to the lanes), and the
    program reads 11.16 GB (arguments 5.09, temporaries 6.07) where
    forming them again read 10.30 (5.09 and 5.20)."""
    from mxnet_tpu.gluon.model_zoo import qwen3_next as zoo

    cfg, hbm = _config_and_hbm("qwen3_next_80b_a3b")
    net = zoo.qwen3_next(dict(
        cfg, num_experts=cfg["published"]["num_experts"],
        held_experts=list(range(cfg["num_experts"]))))
    lowered, count = _lowered_step(one_chip, net, cfg)
    assert 420e6 < count < 630e6
    text = lowered.as_text()
    # three delta-rule layers and one attention layer, forward and back
    assert text.count("mx_gdn_fwd") >= 3 and text.count("mx_gdn_bwd") >= 3
    assert text.count('kernel_name = "mx_gdn_prepare"') == 3
    assert "mx_flash_bwd" in text
    assert "ragged" not in text and 'kernel_name = "mx_tgmm"' in text
    _assert_fits(lowered.compile(), count, hbm)


def test_windowed_flash_kernels_compile_for_v5e(one_chip):
    """32 query heads on 4 key/value heads, 128 wide, 8,192 tokens, a
    window of 1,024, at the windowed default blocks: the forward and the
    fused backward of a group under their own names, with grids of as
    many inner steps as a window touches."""
    b, h, kv, t, d, window = 1, 32, 4, 8192, 128, 1024
    static = (d ** -0.5, True) + pa.DEFAULT_WINDOW_BLOCK + (False, window)
    assert pa._bwd_path(t, d, t, d, h // kv) == "fused"
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                           sharding=one_chip)
    row = jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32,
                               sharding=one_chip)
    q3, k3 = spec(b * h, t, d), spec(b * kv, t, d)
    calls = {
        "mx_flash_swa_fwd": (
            lambda q, k, v: pa._flash_forward(q, k, v, *static),
            [spec(b, h, t, d), spec(b, kv, t, d), spec(b, kv, t, d)]),
        "mx_flash_swa_bwd": (
            lambda *a: pa._flash_bwd_fused(*a, *static),
            [q3, k3, k3, q3, row, row])}
    block_q, block_k = pa.DEFAULT_WINDOW_BLOCK
    for name, (fn, args) in calls.items():
        lowered = jax.jit(fn).lower(*args)
        text = lowered.as_text()
        assert 'kernel_name = "%s"' % name in text, name
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes \
            < b * h * t * t * 4 // 4, name
    steps, _ = pa._window_geometry(t // block_q, t // block_k, block_q,
                                   block_k, window, keys=True)
    assert steps == window // block_k + 1 < t // block_k
    dk, dv, dq = jax.eval_shape(calls["mx_flash_swa_bwd"][0],
                                *calls["mx_flash_swa_bwd"][1])
    assert dk.shape == dv.shape == (b * kv, t, d) and dq.shape == q3.shape


def test_mellum2_step_fits_the_chip(one_chip):
    """The whole `TrainStep` program of the `mellum2_12b_a2_5b`
    configuration (one sequence of 8,192 tokens, bf16 with fp32 masters,
    Adam, no recomputation), from shapes alone: arguments, temporaries
    and 4 bytes a parameter of gradient buffers under 15 GB."""
    from mxnet_tpu.gluon.model_zoo import mellum as zoo

    cfg, hbm = _config_and_hbm("mellum2_12b_a2_5b")
    net = zoo.mellum(dict(
        cfg, num_experts=cfg["published"]["num_experts"],
        held_experts=list(range(cfg["num_experts"]))))
    lowered, count = _lowered_step(one_chip, net, cfg)
    assert 335e6 < count < 345e6
    text = lowered.as_text()
    # three sliding layers and one full layer, forward and back
    for name, calls in (("mx_flash_swa_fwd", 3), ("mx_flash_swa_bwd", 3),
                        ("mx_flash_fwd", 1), ("mx_flash_bwd", 1)):
        assert text.count('kernel_name = "%s"' % name) == calls, name
    # the experts' products: six kernel bodies lowered (three kernels at
    # the gate/up and the down shapes), 36 call sites compiled (three
    # products forward, their six transposes backward, four layers), each
    # under a name of its own, and nothing left to XLA's ragged-dot
    assert "ragged" not in text
    for name in ("mx_gmm", "mx_gmm_t", "mx_tgmm"):
        assert text.count('kernel_name = "%s"' % name) == 2, name
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "ragged-dot" not in hlo
    sites = re.findall(r"%(mx_t?gmm(?:_t)?(?:\.\d+)?) = \S+ custom-call\(",
                       hlo)
    assert len(sites) == len(set(sites)) == 36
    assert sorted(collections.Counter(
        site.split(".")[0] for site in sites).items()) \
        == [("mx_gmm", 12), ("mx_gmm_t", 12), ("mx_tgmm", 12)]
    program = _assert_fits(compiled, count, hbm)
    assert program + 4 * count < 15e9

"""Pallas grouped matrix product: the experts' three products and their
six transposes (`ops/moe.py`).

The rows of a buffer lie sorted by group; row r of group e meets
``rhs[e]``. Two kernels, three forms:

`mx_gmm`      ``(C, K) x (n, K, N) -> (C, N)``: rows of group e against
              ``rhs[e]``;
`mx_gmm_t`    the same kernel with the weights held transposed,
              ``(n, N, K)``, read through the index map and the dot's
              dimension numbers: ``d_lhs = d_out @ w[e].T`` makes no copy
              of the expert weights;
`mx_tgmm`     ``d_w[e] = lhs[rows of e].T @ d_out[rows of e]``,
              ``(n, K, N)``, accumulated in fp32 over the row tiles of one
              group and written once a group; a group of no rows writes
              zeros.

The walk. The C rows are cut into tiles of `tile_m`. A tile that lies in
one group is visited once; a tile that group edges cross is visited once
for each group in it, the rows of the other groups masked; a group of no
rows has one visit that owns no row. That is at most ``C / tile_m + n -
1`` visits, and the grid always has exactly that many: what the groups
do not need repeats the last visit with no row its own. **Every visit
computes a whole tile**, so the count of tile products a call makes is a
function of C, n and `tile_m` alone and the device time does not follow
how the rows are dealt to the groups. Which group and tile a visit has
comes from `group_sizes` by scalar prefetch (`_visits`).

`group_sizes` is taken to cover the buffer: its last group runs to row C
whatever its size says (`moe_held_experts` joins the padding to the last
group itself), so every row is some group's and is written.

Tiles come from the shapes alone (`_tiles`): `tile_m` from the kernel and
the mean rows a group (`_tile_m`), and the weights' (K, N) block whole where it fits VMEM with the
row tile, so consecutive row tiles of one group do not fetch it again and
the contraction needs no accumulator; past `TILE_BYTES` the larger of K
and N is cut to a divisor that is a multiple of 128, with an fp32
accumulator over the K tiles. Operands keep their type (bf16 in a
training step), products accumulate in fp32, and the results have the
operands' type, as `jax.lax.ragged_dot` and its transposes give them.

`grouped_matmul` is the differentiable product (`jax.custom_vjp`: the
operands are the residuals); each launch is a `jax.jit` of its own, so a
model's layers that call it at one shape are lowered once, not once a
layer. Off the TPU the kernels run in interpret mode, as
`flash_attention` does. `mx_moe_grouped_product_traced_total{kernel,
tile_m}` counts the launches traced into a program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .pallas_attention import _NN, _NT, _TN, _dot

__all__ = ["grouped_matmul", "mx_gmm", "mx_tgmm", "visit_count"]

_LANES = 128
# What one launch's blocks may take of VMEM before K or N is cut, and the
# limit handed to the compiler (the chip has 128 MiB; 16 are the default).
TILE_BYTES = 40 * 2 ** 20
VMEM_LIMIT = 96 * 2 ** 20

_traced = _tm.REGISTRY.counter(
    "mx_moe_grouped_product_traced_total",
    "Grouped-product kernels traced into a program, by kernel (gmm: rows "
    "by their group's weights; gmm_t: the same with the weights read "
    "transposed; tgmm: the weights' gradient) and row tile",
    labels=("kernel", "tile_m"))


def visit_count(rows, groups, tile_m):
    """Grid steps along the rows of one launch: a function of the
    shapes alone."""
    return rows // tile_m + groups - 1


def _visits(group_sizes, rows, tile_m):
    """(4, visits) int32: the group, the row tile, and the first and
    one-past-last row a visit owns. Visits are ordered by group, then
    tile, so a tile's visits and a group's visits are each consecutive."""
    n = group_sizes.shape[0]
    tiles = rows // tile_m
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), rows)
    ends = ends.at[n - 1].set(rows)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = jnp.minimum(starts // tile_m, tiles - 1)
    count = jnp.where(ends > starts, -(-ends // tile_m) - first, 1)
    stop = jnp.cumsum(count)
    total = stop[n - 1]
    v = jnp.arange(visit_count(rows, n, tile_m), dtype=jnp.int32)
    at = jnp.minimum(v, total - 1)          # past the last: it again
    group = jnp.sum(stop[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    own = group[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]
    pick = lambda a: jnp.sum(jnp.where(own, a[None, :], 0), axis=1)
    tile = pick(first - (stop - count)) + at
    lo = jnp.maximum(pick(starts), tile * tile_m)
    hi = jnp.minimum(pick(ends), (tile + 1) * tile_m)
    hi = jnp.where(v < total, jnp.maximum(hi, lo), lo)
    return jnp.stack([group, tile, lo, hi])


def _cuts(dim):
    """`dim` and its divisors that are multiples of 128, largest first."""
    return [dim] + [d for d in range(dim - dim % _LANES, 0, -_LANES)
                    if d < dim and dim % d == 0]


def _step_bytes(kernel, tile_m, tk, tn, k, itemsize):
    """VMEM of one grid step: operand and result blocks twice (the
    pipeline's two buffers) and the fp32 product; `mx_gmm` adds an
    accumulator where K is cut, `mx_tgmm` always, with the masked copy
    of its narrower operand."""
    blocks = 2 * itemsize * (tile_m * tk + tk * tn + tile_m * tn)
    if kernel == "tgmm":
        return blocks + itemsize * tile_m * min(tk, tn) + 8 * tk * tn
    return blocks + 4 * tile_m * tn * (1 if tk == k else 2)


def _tile_m(kernel, rows, groups):
    """The row tile, from a sweep on a TPU v5e at the benchmark's three
    shapes (PERF.md, PR 38). With the weights' block resident, `mx_gmm`
    is fastest at 128 rows at every shape (the fewest rows computed
    twice at group edges). `mx_tgmm` pays its (K, N) accumulator at
    every visit, so it takes the largest of 512, 256 and 128 that the
    mean rows a group fill. All the rows where 128 does not divide
    them."""
    if rows % _LANES:
        return rows
    if kernel == "tgmm":
        for tile in (512, 256):
            if rows % tile == 0 and rows // groups >= tile:
                return tile
    return _LANES


def _tiles(kernel, rows, groups, k, n, itemsize):
    """(tile_m, tile_k, tile_n) of a launch from its shapes: K and N
    whole where the blocks fit `TILE_BYTES`, else the larger cut."""
    tile_m = _tile_m(kernel, rows, groups)
    fits = [(tk, tn) for tk in _cuts(k) for tn in _cuts(n)
            if _step_bytes(kernel, tile_m, tk, tn, k, itemsize)
            <= TILE_BYTES]
    if not fits:
        raise ValueError("no tiles of a (%d, %d) x (%d, %d) grouped product "
                         "fit %d bytes of VMEM" % (rows, k, k, n, TILE_BYTES))
    # the fewest grid steps; of those, K whole before N
    return (tile_m,) + max(fits, key=lambda t: (t[0] * t[1], t[0]))


def _own_rows(visits_ref, v, tile_m, shape):
    """Mask of `shape` (rows first): the rows of the tile that visit `v`
    owns."""
    rows = visits_ref[1, v] * tile_m \
        + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= visits_ref[2, v]) & (rows < visits_ref[3, v])


def _gmm_kernel(visits_ref, lhs_ref, rhs_ref, out_ref, *acc_ref, tile_m,
                transpose_rhs):
    import jax.experimental.pallas as pl

    v, kk, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    product = _dot(lhs_ref[...], rhs_ref[...], _NT if transpose_rhs else _NN)

    def _store(result):
        own = _own_rows(visits_ref, v, tile_m, result.shape)
        out_ref[...] = jnp.where(own, result.astype(out_ref.dtype),
                                 out_ref[...])

    if not acc_ref:                # K whole: nothing to carry
        _store(product)
        return
    acc_ref, = acc_ref

    @pl.when(kk == 0)
    def _first():
        acc_ref[...] = product

    @pl.when(kk > 0)
    def _accumulate():
        acc_ref[...] += product

    @pl.when(kk == nk - 1)
    def _finalize():
        _store(acc_ref[...])


def _tgmm_kernel(visits_ref, lhs_ref, dout_ref, out_ref, acc_ref, *, tile_m):
    import jax.experimental.pallas as pl

    v, nv = pl.program_id(2), pl.num_programs(2)
    group = visits_ref[0, v]
    opens = (v == 0) | (visits_ref[0, jnp.maximum(v - 1, 0)] != group)
    closes = (v == nv - 1) | (visits_ref[0, jnp.minimum(v + 1, nv - 1)]
                              != group)
    lhs, dout = lhs_ref[...], dout_ref[...]
    # rows of other groups leave the sum through the narrower operand
    if lhs.shape[1] <= dout.shape[1]:
        lhs = jnp.where(_own_rows(visits_ref, v, tile_m, lhs.shape), lhs, 0)
    else:
        dout = jnp.where(_own_rows(visits_ref, v, tile_m, dout.shape),
                         dout, 0)
    product = _dot(lhs, dout, _TN)

    @pl.when(opens)
    def _first():
        acc_ref[...] = product

    @pl.when(jnp.logical_not(opens))
    def _accumulate():
        acc_ref[...] += product

    @pl.when(closes)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _compiler_params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gmm_call(lhs, rhs, group_sizes, transpose_rhs, tiles, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tile_m, tk, tn = tiles
    dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, tk),
                                lambda j, v, kk, vis: (vis[0, v], j, kk))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn),
                                lambda j, v, kk, vis: (vis[0, v], kk, j))
    steps = visit_count(rows, groups, tile_m)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile_m=tile_m,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((rows, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, steps, k // tk),
            in_specs=[pl.BlockSpec((tile_m, tk),
                                   lambda j, v, kk, vis: (vis[1, v], kk)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tile_m, tn),
                                   lambda j, v, kk, vis: (vis[1, v], j)),
            scratch_shapes=[] if tk == k else
            [pltpu.VMEM((tile_m, tn), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "arbitrary",
                                         "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * steps * tile_m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * rows * k * (n // tn)
            + rhs.dtype.itemsize * groups * k * n
            + dtype.itemsize * rows * n),
        interpret=interpret,
        name="mx_gmm_t" if transpose_rhs else "mx_gmm",
    )(_visits(group_sizes, rows, tile_m), lhs, rhs)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _tgmm_call(lhs, dout, group_sizes, groups, tiles, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = lhs.shape
    n = dout.shape[1]
    tile_m, tk, tn = tiles
    dtype = jnp.result_type(lhs.dtype, dout.dtype)
    steps = visit_count(rows, groups, tile_m)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile_m=tile_m),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, n // tn, steps),
            in_specs=[pl.BlockSpec((tile_m, tk),
                                   lambda i, j, v, vis: (vis[1, v], i)),
                      pl.BlockSpec((tile_m, tn),
                                   lambda i, j, v, vis: (vis[1, v], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, v, vis: (vis[0, v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * steps * tile_m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * rows * k * (n // tn)
            + dout.dtype.itemsize * rows * n * (k // tk)
            + dtype.itemsize * groups * k * n),
        interpret=interpret,
        name="mx_tgmm",
    )(_visits(group_sizes, rows, tile_m), lhs, dout)


def _interpret(interpret):
    if interpret is None:
        return jax.default_backend() not in ("tpu",)
    return bool(interpret)


def _launch(kernel, rows, groups, k, n, itemsize, tiles):
    tiles = tuple(tiles) if tiles else _tiles(kernel, rows, groups, k, n,
                                              itemsize)
    if rows % tiles[0] or k % tiles[1] or n % tiles[2]:
        raise ValueError("tiles %s do not divide a (%d, %d) x (%d, %d) "
                         "grouped product" % (tiles, rows, k, k, n))
    _traced.labels(kernel=kernel, tile_m=str(tiles[0])).inc()
    return tiles


def mx_gmm(lhs, rhs, group_sizes, transpose_rhs=False, tiles=None,
           interpret=None):
    """Rows of group e of `lhs` (C, K) against ``rhs[e]``: rhs (n, K, N),
    or (n, N, K) under `transpose_rhs`; `group_sizes` (n,) int32, the
    last group running to row C. Result (C, N). `tiles`: (tile_m,
    tile_k, tile_n) for a sweep; None takes them from the shapes."""
    if transpose_rhs:
        groups, n, k = rhs.shape
    else:
        groups, k, n = rhs.shape
    if lhs.shape[1] != k or group_sizes.shape != (groups,):
        raise ValueError("a grouped product of %s by %s over %s groups"
                         % (lhs.shape, rhs.shape, group_sizes.shape))
    tiles = _launch("gmm_t" if transpose_rhs else "gmm", lhs.shape[0],
                    groups, k, n, lhs.dtype.itemsize, tiles)
    return _gmm_call(lhs, rhs, group_sizes, bool(transpose_rhs), tiles,
                     _interpret(interpret))


def mx_tgmm(lhs, dout, group_sizes, tiles=None, interpret=None):
    """``lhs[rows of e].T @ dout[rows of e]`` for each group e: lhs
    (C, K), dout (C, N), result (n, K, N); zeros for a group of no
    rows."""
    groups = group_sizes.shape[0]
    if lhs.shape[0] != dout.shape[0]:
        raise ValueError("operands of %d and %d rows"
                         % (lhs.shape[0], dout.shape[0]))
    tiles = _launch("tgmm", lhs.shape[0], groups, lhs.shape[1],
                    dout.shape[1], lhs.dtype.itemsize, tiles)
    return _tgmm_call(lhs, dout, group_sizes, groups, tiles,
                      _interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return mx_gmm(lhs, rhs, group_sizes, interpret=interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return mx_gmm(lhs, rhs, group_sizes, interpret=interpret), \
        (lhs, rhs, group_sizes)


def _grouped_bwd(interpret, residuals, g):
    lhs, rhs, group_sizes = residuals
    g = g.astype(lhs.dtype)
    d_lhs = mx_gmm(g, rhs, group_sizes, transpose_rhs=True,
                   interpret=interpret)
    d_rhs = mx_tgmm(lhs, g, group_sizes, interpret=interpret)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None):
    """``lhs[rows of e] @ rhs[e]`` for the groups as they lie in `lhs`
    (C, K), rhs (n, K, N), `group_sizes` (n,) int32 with the last group
    running to row C: what `jax.lax.ragged_dot` gives for sizes that sum
    to C, differentiable in both operands through `mx_gmm` on the
    transposed weights and `mx_tgmm`."""
    return _grouped(lhs, rhs, group_sizes, _interpret(interpret))

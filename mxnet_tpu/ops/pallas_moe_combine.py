"""Pallas combine of the expert buffer into token order (`ops/moe.py`).

`mx_moe_combine` ``(C, H) rows, (T, n) place, (T, n) weight -> (T, H)``
fp32: ``out[t] = sum_e weight[t, e] * rows[place[t, e]]`` over the held
experts e that token t has a row of (``place`` is -1 elsewhere). It is
the combine of the experts' results and, with the weights 1, the
pullback of the dispatch: both read the buffer in token order and write
no row twice, so nothing is scattered.

The walk. Tokens are cut into tiles of `tile_t`. The buffer lies sorted
by expert, then by token, so the rows that a tile's tokens hold of one
expert are one contiguous run; a visit is one block of `tile_r` rows of
such a run (a run that crosses a block's edge takes two). A visit picks
its rows by a 0/1 product on the MXU, ``(tile_t, tile_r) x (tile_r,
H)``, exact in fp32 accumulation, and adds them to the tile's fp32
result at the expert's weights. Visits are ordered by tile, so a tile's
fp32 sum stays in VMEM through its visits and is written once, in the
type asked for (the dispatch's pullback: the data's); a tile
whose tokens hold no row has one visit that adds nothing. The grid has a
fixed count of visits from the shapes (`visit_bound`); what the rows do
not need repeats the last visit and computes nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_attention import _NN, _dot
from .pallas_grouped_matmul import _compiler_params, _interpret

__all__ = ["mx_moe_combine", "visit_bound"]

TILE_T, TILE_R = 256, 128


def _tiles(tokens, rows):
    """(tile_t, tile_r): the largest of 256, 128, ... that divides the
    tokens (all of them where none does), and 128 rows where they divide
    the buffer (all of them elsewhere). A visit's product is then a whole
    MXU pass deep. 256 x 128 is the pair timed end to end on the chip;
    alone, 512 x 128 read 3-10 % faster a call and 128 x 128 or
    256 x 256 slower (PERF.md, PR 41)."""
    tile_t = next((t for t in (TILE_T, 128, 64, 32, 16, 8)
                   if tokens % t == 0), tokens)
    return tile_t, TILE_R if rows % TILE_R == 0 else rows


def visit_bound(tokens, rows, groups, tile_t, tile_r):
    """Grid steps of one launch: every run of ``L`` rows takes at most
    ``L / tile_r + 2`` blocks, the runs hold at most the C rows, and a
    tile has at most one run a group."""
    return rows // tile_r + (2 * groups + 1) * (tokens // tile_t)


def _visits(place, rows, tile_t, tile_r):
    """(4, visits) int32: the token tile, the group, the row block of
    each visit, and 1 where it has rows to add (0: a tile's empty visit,
    or a repeat past the last)."""
    tokens, n = place.shape
    tiles = tokens // tile_t
    held = place >= 0
    # rows of each group before each tile's first token, and the groups'
    # starts: the tile's run of group e is [lo, hi)
    before = jnp.cumsum(jnp.sum(held.reshape(tiles, tile_t, n), axis=1,
                                dtype=jnp.int32), axis=0)
    before = jnp.concatenate([jnp.zeros((1, n), jnp.int32), before])
    start = jnp.min(jnp.where(held, place, rows), axis=0)        # (n,)
    start = jnp.where(before[-1] > 0, start, 0)
    lo = jnp.minimum(start + before[:-1], rows)                  # (tiles, n)
    hi = jnp.minimum(start + before[1:], rows)
    first = lo // tile_r
    blocks = jnp.where(hi > lo, (hi - 1) // tile_r - first + 1, 0)
    per_tile = jnp.sum(blocks, axis=1)
    count = jnp.maximum(per_tile, 1)
    stop = jnp.cumsum(count)
    total = stop[-1]
    steps = visit_bound(tokens, rows, n, tile_t, tile_r)
    v = jnp.arange(steps, dtype=jnp.int32)
    at = jnp.minimum(v, total - 1)
    tile = jnp.sum(stop[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    offset = at - (stop - count)[tile]
    ends = jnp.cumsum(blocks, axis=1)[tile]                      # (V, n)
    group = jnp.minimum(jnp.sum(ends <= offset[:, None], axis=1,
                                dtype=jnp.int32), n - 1)
    own = group[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]
    pick = lambda a: jnp.sum(jnp.where(own, a, 0), axis=1)
    block = pick(first[tile]) + offset - pick(ends - blocks[tile])
    real = (v < total) & (per_tile[tile] > 0)
    block = jnp.where(real, block, pick(first[tile]))
    return jnp.stack([tile, group, jnp.clip(block, 0, rows // tile_r - 1),
                      real.astype(jnp.int32)])


def _combine_kernel(visits_ref, rows_ref, place_ref, weight_ref, out_ref,
                    acc_ref, *, tile_r, precision):
    import jax.experimental.pallas as pl

    v, nv = pl.program_id(0), pl.num_programs(0)
    tile = visits_ref[0, v]
    opens = (v == 0) | (visits_ref[0, jnp.maximum(v - 1, 0)] != tile)
    closes = (v == nv - 1) | (visits_ref[0, jnp.minimum(v + 1, nv - 1)]
                              != tile)

    @pl.when(opens)
    def _zero():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(visits_ref[3, v] == 1)
    def _add():
        place, weight = place_ref[...], weight_ref[...]
        own = jax.lax.broadcasted_iota(jnp.int32, place.shape, 1) \
            == visits_ref[1, v]
        col = jnp.sum(jnp.where(own, place, 0), axis=1, keepdims=True)
        w = jnp.sum(jnp.where(own, weight, 0.0), axis=1, keepdims=True)
        row = visits_ref[2, v] * tile_r + jax.lax.broadcasted_iota(
            jnp.int32, (place.shape[0], tile_r), 1)
        rows = rows_ref[...]
        pick = (col == row).astype(rows.dtype)
        part = jax.lax.dot_general(pick, rows, (_NN, ((), ())),
                                   precision=precision,
                                   preferred_element_type=jnp.float32) \
            if precision else _dot(pick, rows, _NN)
        acc_ref[...] += w * part

    @pl.when(closes)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _combine_call(rows, place, weight, dtype, tiles, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, hidden = rows.shape
    tokens, n = place.shape
    tile_t, tile_r = tiles
    steps = visit_bound(tokens, c, n, tile_t, tile_r)
    # fp32 rows: the 0/1 product must keep every bit of them
    precision = jax.lax.Precision.HIGHEST \
        if rows.dtype == jnp.float32 else None
    return pl.pallas_call(
        functools.partial(_combine_kernel, tile_r=tile_r,
                          precision=precision),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[pl.BlockSpec((tile_r, hidden),
                                   lambda v, vis: (vis[2, v], 0)),
                      pl.BlockSpec((tile_t, n),
                                   lambda v, vis: (vis[0, v], 0)),
                      pl.BlockSpec((tile_t, n),
                                   lambda v, vis: (vis[0, v], 0))],
            out_specs=pl.BlockSpec((tile_t, hidden),
                                   lambda v, vis: (vis[0, v], 0)),
            scratch_shapes=[pltpu.VMEM((tile_t, hidden), jnp.float32)]),
        compiler_params=_compiler_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * steps * tile_t * tile_r * hidden, transcendentals=0,
            bytes_accessed=rows.dtype.itemsize * steps * tile_r * hidden
            + jnp.dtype(dtype).itemsize * tokens * hidden),
        interpret=interpret,
        name="mx_moe_combine",
    )(_visits(place, c, tile_t, tile_r), rows, place,
      weight.astype(jnp.float32))


def mx_moe_combine(rows, place, weight, dtype=jnp.float32, tiles=None,
                   interpret=None):
    """``sum_e weight[t, e] * rows[place[t, e]]`` over the e with
    ``place[t, e] >= 0``, summed in fp32 and rounded once to `dtype`:
    rows (C, H) sorted by group and by token inside a group, place (T, n)
    int32, weight (T, n); (T, H)."""
    tiles = tuple(tiles) if tiles else _tiles(place.shape[0], rows.shape[0])
    return _combine_call(rows, place, weight, jnp.dtype(dtype), tiles,
                         _interpret(interpret))

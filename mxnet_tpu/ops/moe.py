"""The held experts' part of a mixture-of-experts layer.

An expert-parallel deployment gives each chip some of a layer's experts.
The router runs over all of them (`transformer_ops.noaux_tc_router`);
this operator is told which experts live here and computes their part of
the result for the tokens routed to them. What the absent experts would
add is another chip's to compute and is not in the result.

Layout. The rows routed to held experts are sorted by expert (then by
token) into one buffer of ``C = buffer_rows(...)`` rows; what the rows do
not fill is zero padding, counted to the last expert's group. The three
products (gate, up, down) are grouped products over the whole buffer, and
so are their six transposes in the backward: the repo's own Pallas kernels
(`ops/pallas_grouped_matmul.py`: `mx_gmm`, `mx_gmm_t`, `mx_tgmm`), which
walk the buffer tile by tile with a group's weights resident in VMEM.
Every tile of the C rows is still computed whether rows or padding fill
it, and a launch makes ``C / tile_m + n - 1`` tile products however the
groups' edges fall (a tile that edges cross is computed once for each
group in it; what the groups do not need is computed all the same): the
device time of a step does not depend on how the router filled the
buffer. Skipping the padding's tiles would save more and make the step
follow the fill (PERF.md, PRs 27, 28 and 38).

How rows move (PR 41): by gathers only; no row is scattered, forward or
backward. One slot table (`_route`) says where each assignment lies in
the buffer, both ways: the buffer's rows by assignment (one stable sort)
and each token's rows by held expert (``place``: the expert's start plus
the tokens before it that picked it too, no second sort). The dispatch
fills the buffer by ``data[token]``, and its pullback sums each token's
rows of the buffer's cotangent in fp32; the combine sums each token's
rows of the experts' result at the router's weights in fp32, and its
pullback reads the result's cotangent back by token (the buffer's
cotangent) and a dot a row (the weights'). Both sums are
`mx_moe_combine` (`ops/pallas_moe_combine.py`), which walks the tokens
tile by tile and reads each tile's run of rows of each group in the
buffer's order.

No token is dropped. A step whose held rows exceed C computes the rest
too, exactly, in a second pass that is taken only then (`lax.cond`; a
dense product over the held experts with a mask), and reports it in its
third result.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .pallas_grouped_matmul import grouped_matmul
from .pallas_moe_combine import mx_moe_combine
from .registry import register

__all__ = ["moe_held_experts", "buffer_rows"]

_moe_traced = _tm.REGISTRY.counter(
    "mx_moe_layer_traced_total",
    "Held-experts layers traced into a program (one per sparse layer of "
    "one build of a step program)")


def buffer_rows(tokens, top_k, held, num_experts, capacity_factor):
    """Rows of the buffer: ``ceil(cf * tokens * top_k * held /
    num_experts)``, raised to a whole number of 128-row tiles."""
    rows = math.ceil(capacity_factor * tokens * top_k * held / num_experts)
    return -(-rows // 128) * 128


def _silu_gated(gate, up):
    return jax.nn.silu(gate) * up


def _route(hit, rows):
    """Where each assignment lies in the buffer, both ways: one table.

    `hit` (T, K, n): assignment (t, k) is to held expert e. Returns
    ``((token_of, valid, group_of, place), count, over)``: the token,
    whether a row holds one, and its group, at each of the C rows of the
    buffer (sorted by group, then by token); ``place`` (T, n) the row of
    token t's assignment to held expert e, -1 where it has none: the
    inverse of the first three. ``count`` (n,) the assignments to each
    held expert, ``over`` (T, n) those that found no row. A token picks
    an expert at most once, so its row is the group's start plus the
    tokens before it that picked it too: one sort, not two."""
    tokens, top_k, n = hit.shape
    local = jnp.sum(jnp.where(hit, jnp.arange(n), 0), axis=2) \
        + jnp.where(jnp.any(hit, axis=2), 0, n)               # n: not held
    count = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    ends = jnp.cumsum(count)
    order = jnp.argsort(local.reshape(-1), stable=True)
    slots = order[:rows] if rows <= tokens * top_k else jnp.pad(
        order, (0, rows - tokens * top_k))
    row = jnp.arange(rows)
    group_of = jnp.minimum(jnp.sum(row[:, None] >= ends[None, :], axis=1,
                                   dtype=jnp.int32), n - 1)
    picked = jnp.any(hit, axis=1)                             # (T, n)
    place = (ends - count)[None, :] \
        + jnp.cumsum(picked, axis=0, dtype=jnp.int32) - 1
    return ((slots // top_k, row < jnp.minimum(ends[-1], rows), group_of,
             jnp.where(picked & (place < rows), place, -1)),
            count, picked & (place >= rows))


@jax.custom_vjp
def _dispatch(x, route):
    """The buffer: ``valid[s] * x[token_of[s]]``, (C, H)."""
    token_of, valid = route[:2]
    return jnp.where(valid[:, None], x[token_of], 0)


def _dispatch_fwd(x, route):
    return _dispatch(x, route), route


def _dispatch_bwd(route, d_buf):
    """Each token's rows of `d_buf` summed in fp32 and rounded once."""
    place = route[3]
    return mx_moe_combine(d_buf, place, jnp.ones(place.shape, jnp.float32),
                          d_buf.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, w_held, route):
    """Each token's rows of `out` (C, H) at its weights `w_held` (T, n),
    summed in fp32: ``sum_e w_held[t, e] * out[place[t, e]]``, (T, H)."""
    return mx_moe_combine(out, route[3], w_held)


def _combine_fwd(out, w_held, route):
    return _combine(out, w_held, route), (out, w_held, route)


def _combine_bwd(res, g):
    out, w_held, (token_of, valid, group_of, _) = res
    n = w_held.shape[1]
    at = token_of * n + group_of                  # (t, e) of each row
    w_slot = jnp.where(valid, w_held.reshape(-1)[at], 0.0)
    # `g` is the cotangent of a cast to the data's type (the operator's
    # result), so it is exact in `out`'s type: gather half the bytes
    g_slot = g.astype(out.dtype)[token_of].astype(jnp.float32)   # (C, H)
    d_out = (g_slot * w_slot[:, None]).astype(out.dtype)
    dot = jnp.sum(g_slot * out.astype(jnp.float32), axis=1)      # (C,)
    # back to (t, e): C scalars to distinct places; a gather of T * n
    # scalars by `place` takes the TPU several times as long
    d_w = jnp.zeros((w_held.size,), jnp.float32).at[at].add(
        jnp.where(valid, dot, 0.0))
    return d_out, d_w.reshape(w_held.shape).astype(w_held.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


@register("_contrib_moe_held_experts", aliases=("moe_held_experts",))
def moe_held_experts(data, ids, weights, gate_weight, up_weight,
                     down_weight, held=(), num_experts=0,
                     capacity_factor=1.5):
    """Sum over the selected experts that are held here of
    ``weight * Expert(data)``.

    data (tokens, hidden); ids, weights (tokens, top_k) from the router,
    ids over all `num_experts`; `held` the ids of the experts whose
    weights are given, in the order of the leading axis of gate_weight,
    up_weight (held, hidden, width) and down_weight (held, width,
    hidden): (in, out), the grouped product's layout.

    Returns (result (tokens, hidden) in `data`'s type, rows routed to
    held experts () int32, 1 if the second pass ran else 0 () int32).
    """
    _moe_traced.inc()
    tokens, top_k = ids.shape
    n = len(held)
    rows = buffer_rows(tokens, top_k, n, num_experts, capacity_factor)

    with jax.named_scope("moe_experts"):
        # (T, K, n): a comparison, not a gather of a table by the ids
        hit = ids[:, :, None] == jnp.asarray(held, ids.dtype)
        route, count, over = _route(hit, rows)
        total = jnp.sum(count)
        buf = _dispatch(data, route)                          # (C, H)
        # groups as they lie in the buffer; padding joins the last one
        ends = jnp.minimum(jnp.cumsum(count), rows)
        sizes = jnp.diff(ends, prepend=0)
        sizes = sizes.at[n - 1].add(rows - ends[n - 1])
        act = _silu_gated(grouped_matmul(buf, gate_weight, sizes),
                          grouped_matmul(buf, up_weight, sizes))
        out = grouped_matmul(act, down_weight, sizes)
        # each token's weight for each held expert (T, n); a weight whose
        # assignment found no row goes to the second pass
        w_held = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)
        result = _combine(out, w_held, route)                 # (T, H) f32

        @jax.checkpoint
        def second_pass(x, w_over, gate_w, up_w, down_w):
            act = _silu_gated(jnp.einsum("th,ehf->etf", x, gate_w),
                              jnp.einsum("th,ehf->etf", x, up_w))
            dense = jnp.einsum("etf,efh->eth", act, down_w)
            return jnp.einsum("eth,te->th", dense.astype(jnp.float32),
                              w_over)

        w_over = jnp.where(over, w_held, 0.0)
        overflow = total > rows
        result = jax.lax.cond(
            overflow, lambda r, *rest: r + second_pass(*rest),
            lambda r, *rest: r,
            result, data, w_over, gate_weight, up_weight, down_weight)
        return (result.astype(data.dtype), total,
                overflow.astype(jnp.int32))

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

No token is dropped. A step whose held rows exceed C computes the rest
too, exactly, in a second pass that is taken only then (`lax.cond`; a
dense product over the held experts with a mask), and reports it in its
third result.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .pallas_grouped_matmul import grouped_matmul
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
    tokens, hidden = data.shape
    top_k = ids.shape[1]
    n = len(held)
    rows = buffer_rows(tokens, top_k, n, num_experts, capacity_factor)
    table = np.full((num_experts,), n, np.int32)     # n: not held
    table[list(held)] = np.arange(n, dtype=np.int32)

    with jax.named_scope("moe_experts"):
        local = jnp.asarray(table)[ids]                       # (T, K)
        hit = local[:, :, None] == jnp.arange(n)[None, None, :]
        count = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)    # (n,)
        total = jnp.sum(count)
        # assignments (token, k) by expert then token; the held ones first
        order = jnp.argsort(local.reshape(-1), stable=True)
        slots = order[:rows] if rows <= tokens * top_k else jnp.pad(
            order, (0, rows - tokens * top_k))
        valid = jnp.arange(rows) < jnp.minimum(total, rows)
        token_of = slots // top_k
        w_slot = jnp.where(valid, weights.reshape(-1)[slots], 0.0)
        buf = jnp.where(valid[:, None], data[token_of], 0)    # (C, H)
        # groups as they lie in the buffer; padding joins the last one
        ends = jnp.minimum(jnp.cumsum(count), rows)
        sizes = jnp.diff(ends, prepend=0)
        sizes = sizes.at[n - 1].add(rows - ends[n - 1])
        act = _silu_gated(grouped_matmul(buf, gate_weight, sizes),
                          grouped_matmul(buf, up_weight, sizes))
        out = grouped_matmul(act, down_weight, sizes)
        out = out.astype(jnp.float32) * w_slot[:, None]
        result = jnp.zeros((tokens, hidden), jnp.float32).at[token_of].add(
            out)

        @jax.checkpoint
        def second_pass(x, w_over, gate_w, up_w, down_w):
            act = _silu_gated(jnp.einsum("th,ehf->etf", x, gate_w),
                              jnp.einsum("th,ehf->etf", x, up_w))
            dense = jnp.einsum("etf,efh->eth", act, down_w)
            return jnp.einsum("eth,te->th", dense.astype(jnp.float32),
                              w_over)

        # weights of the assignments that found no row in the buffer: a
        # token picks an expert at most once, so its place there is the
        # expert's start plus the tokens before it that picked it too
        picked = jnp.any(hit, axis=1)                         # (T, n)
        place = (jnp.cumsum(count) - count)[None, :] \
            + jnp.cumsum(picked, axis=0, dtype=jnp.int32) - 1
        w_over = jnp.sum(jnp.where(hit & (place >= rows)[:, None, :],
                                   weights[:, :, None], 0.0), axis=1)
        overflow = total > rows
        result = jax.lax.cond(
            overflow, lambda r, *rest: r + second_pass(*rest),
            lambda r, *rest: r,
            result, data, w_over, gate_weight, up_weight, down_weight)
        return (result.astype(data.dtype), total,
                overflow.astype(jnp.int32))

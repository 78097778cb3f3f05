"""How rows move between token order and the expert buffer
(`ops/moe.py`): the slot table, the gathering dispatch and combine with
their pullbacks, and the Pallas combine (`ops/pallas_moe_combine.py`,
interpret mode), against the scatter form that `moe_held_experts` had
before PR 41, kept here as the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_moe_combine as pmc
from mxnet_tpu.ops.pallas_grouped_matmul import grouped_matmul


def _scatter_form(data, ids, weights, gate_weight, up_weight, down_weight,
                  held, num_experts, capacity_factor):
    """`moe_held_experts` as PR 40's parent had it: the buffer filled by
    ``data[token_of]`` (a scatter-add of rows in its pullback) and the
    result by a scatter-add of ``out * w_slot``."""
    tokens, hidden = data.shape
    top_k = ids.shape[1]
    n = len(held)
    rows = moe.buffer_rows(tokens, top_k, n, num_experts, capacity_factor)
    table = np.full((num_experts,), n, np.int32)
    table[list(held)] = np.arange(n, dtype=np.int32)
    local = jnp.asarray(table)[ids]
    hit = local[:, :, None] == jnp.arange(n)[None, None, :]
    count = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    total = jnp.sum(count)
    order = jnp.argsort(local.reshape(-1), stable=True)
    slots = order[:rows] if rows <= tokens * top_k else jnp.pad(
        order, (0, rows - tokens * top_k))
    valid = jnp.arange(rows) < jnp.minimum(total, rows)
    token_of = slots // top_k
    w_slot = jnp.where(valid, weights.reshape(-1)[slots], 0.0)
    buf = jnp.where(valid[:, None], data[token_of], 0)
    ends = jnp.minimum(jnp.cumsum(count), rows)
    sizes = jnp.diff(ends, prepend=0)
    sizes = sizes.at[n - 1].add(rows - ends[n - 1])
    act = jax.nn.silu(grouped_matmul(buf, gate_weight, sizes)) \
        * grouped_matmul(buf, up_weight, sizes)
    out = grouped_matmul(act, down_weight, sizes)
    out = out.astype(jnp.float32) * w_slot[:, None]
    result = jnp.zeros((tokens, hidden), jnp.float32).at[token_of].add(out)
    picked = jnp.any(hit, axis=1)
    place = (jnp.cumsum(count) - count)[None, :] \
        + jnp.cumsum(picked, axis=0, dtype=jnp.int32) - 1
    w_over = jnp.sum(jnp.where(hit & (place >= rows)[:, None, :],
                               weights[:, :, None], 0.0), axis=1)
    act = jax.nn.silu(jnp.einsum("th,ehf->etf", data, gate_weight)) \
        * jnp.einsum("th,ehf->etf", data, up_weight)
    dense = jnp.einsum("etf,efh->eth", act, down_weight)
    result = result + jnp.einsum("eth,te->th", dense.astype(jnp.float32),
                                 w_over)
    return result.astype(data.dtype), total, (total > rows).astype(jnp.int32)


# tokens, hidden, width, experts, top_k, held, capacity factor, ids rule
_CASES = {
    # half the experts held: most tokens pick two or more of them
    "several_picks": (128, 32, 16, 8, 4, (0, 1, 2, 3), 1.5, "random"),
    # two of sixteen held, a buffer mostly padding, many tokens pick none
    "padding_and_none": (64, 32, 16, 16, 2, (0, 1), 1.5, "random"),
    # every token picks both held experts: twice what the buffer holds
    "overflow": (128, 32, 16, 8, 2, (0, 1), 1.0, "held_first"),
    # held ids that neither start at 0 nor follow each other
    "held_offset": (128, 32, 16, 16, 4, (2, 5, 6, 11), 1.5, "random"),
}


def _case(name, dtype):
    tokens, hidden, width, experts, top_k, held, cf, rule = _CASES[name]
    rng = np.random.RandomState(sorted(_CASES).index(name))
    if rule == "held_first":
        rest = np.argsort(rng.rand(tokens, experts - len(held)), 1) \
            + len(held)
        ids = np.concatenate([np.tile(held, (tokens, 1)), rest], 1)
        ids = np.take_along_axis(ids[:, :top_k], np.argsort(
            rng.rand(tokens, top_k), 1), 1)
    else:
        ids = np.argsort(rng.rand(tokens, experts), 1)[:, :top_k]
    n = len(held)
    arrays = dict(
        data=rng.randn(tokens, hidden),
        weights=rng.rand(tokens, top_k),
        gate_weight=rng.randn(n, hidden, width) * 0.3,
        up_weight=rng.randn(n, hidden, width) * 0.3,
        down_weight=rng.randn(n, width, hidden) * 0.3)
    arrays = {k: jnp.asarray(v, jnp.float32 if k == "weights" else dtype)
              for k, v in arrays.items()}
    static = dict(held=held, num_experts=experts, capacity_factor=cf)
    cot = jnp.asarray(rng.randn(tokens, hidden), dtype)
    return jnp.asarray(ids, jnp.int32), arrays, static, cot


def _value_and_vjp(fn, ids, arrays, static, cot):
    names = sorted(arrays)

    def call(*args):
        out, total, over = fn(ids=ids, **dict(zip(names, args)), **static)
        return out, (total, over)

    out, vjp, aux = jax.vjp(call, *(arrays[k] for k in names), has_aux=True)
    return out, aux, dict(zip(names, vjp(cot)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(_CASES))
def test_gathers_equal_the_scatter_form(name, dtype):
    """Value, the rows and overflow it reports, and the pullback to the
    data, the router's weights and the three expert weights."""
    ids, arrays, static, cot = _case(name, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        got, got_aux, got_g = _value_and_vjp(moe.moe_held_experts, ids,
                                             arrays, static, cot)
        want, want_aux, want_g = _value_and_vjp(_scatter_form, ids, arrays,
                                                static, cot)
    assert [int(a) for a in got_aux] == [int(a) for a in want_aux]
    if name == "overflow":
        assert int(got_aux[1]) == 1
    else:
        assert int(got_aux[1]) == 0
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)
    for k in want_g:
        assert got_g[k].dtype == want_g[k].dtype, k
        scale = float(jnp.max(jnp.abs(want_g[k].astype(jnp.float32))))
        np.testing.assert_allclose(
            np.asarray(got_g[k], np.float32),
            np.asarray(want_g[k], np.float32), rtol=tol["rtol"],
            atol=tol["atol"] * max(scale, 1.0), err_msg=k)


@pytest.mark.parametrize("name", sorted(_CASES))
def test_slot_table_is_the_inverse_of_the_buffer_order(name):
    """``place[token_of[s], group_of[s]] == s`` on every filled row, each
    held assignment has a row or is over, rows are sorted by group and by
    token inside a group, and nothing else has a place."""
    ids, _, static, _ = _case(name, jnp.float32)
    tokens, top_k = ids.shape
    held = static["held"]
    n = len(held)
    rows = moe.buffer_rows(tokens, top_k, n, static["num_experts"],
                           static["capacity_factor"])
    hit = np.asarray(ids)[:, :, None] == np.asarray(held)
    (token_of, valid, group_of, place), count, over = moe._route(
        jnp.asarray(hit), rows)
    token_of, valid, group_of, place, over = map(
        np.asarray, (token_of, valid, group_of, place, over))
    s = np.nonzero(valid)[0]
    assert (place[token_of[s], group_of[s]] == s).all()
    assert (place >= 0).sum() == valid.sum() == min(int(np.sum(count)),
                                                    rows)
    picked = hit.any(1)
    assert ((place >= 0) | over).sum() == picked.sum() == hit.sum()
    assert not ((place >= 0) & over).any()
    key = group_of[s] * tokens + token_of[s]
    assert (np.diff(key) > 0).all()


def test_bf16_data_gradient_is_no_further_from_fp32():
    """The dispatch's pullback sums a token's rows in fp32 and rounds
    once: its bf16 `d_data` is no further from the fp32 one than the
    scatter form's, which sums in bf16."""
    ids, arrays, static, cot = _case("several_picks", jnp.bfloat16)
    exact = {k: v.astype(jnp.float32) for k, v in arrays.items()}
    with jax.default_matmul_precision("highest"):
        _, _, ref = _value_and_vjp(moe.moe_held_experts, ids, exact, static,
                                   cot.astype(jnp.float32))
        _, _, got = _value_and_vjp(moe.moe_held_experts, ids, arrays, static,
                                   cot)
        _, _, old = _value_and_vjp(_scatter_form, ids, arrays, static, cot)
    err = lambda g: float(jnp.linalg.norm(
        g["data"].astype(jnp.float32) - ref["data"]))
    assert err(got) <= err(old)


def _combine_reference(rows, place, weight):
    rows, place, weight = (np.asarray(a, np.float64)
                           for a in (rows, place, weight))
    out = np.zeros((place.shape[0], rows.shape[1]))
    for t, e in zip(*np.nonzero(place >= 0)):
        out[t] += weight[t, e] * rows[int(place[t, e])]
    return out


def _layout(tokens, groups, rows, picks, rng):
    """`place` as `_route` gives it: each token picks each group with
    probability `picks`, rows by group then token, cut at `rows`."""
    picked = rng.rand(tokens, groups) < picks
    count = picked.sum(0)
    place = np.cumsum(count)[None, :] - count + np.cumsum(picked, 0) - 1
    return np.where(picked & (place < rows), place, -1).astype(np.int32)


@pytest.mark.parametrize("tokens,groups,rows,picks,tiles", [
    (64, 4, 128, 0.3, (16, 16)),     # runs across block edges, several tiles
    (64, 4, 128, 0.0, (16, 16)),     # no token holds a row
    (96, 3, 128, 0.9, (32, 16)),     # more picks than rows: the cut
    (48, 2, 256, 1.0, (16, 128)),    # every token both groups, one block
])
def test_pallas_combine_equals_the_sum(tokens, groups, rows, picks, tiles):
    """`mx_moe_combine` in interpret mode against the plain sum, fp32
    rows to the bit but for the order of a token's terms, bf16 rows
    exactly as fp32, a bf16 result the fp32 one rounded once; the visits
    it walks stay within `visit_bound`."""
    rng = np.random.RandomState(tokens + groups)
    place = _layout(tokens, groups, rows, picks, rng)
    weight = rng.rand(tokens, groups).astype(np.float32)
    data = rng.randn(rows, 24).astype(np.float32)
    want = _combine_reference(data, place, weight)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(data, dtype)
        got = pmc.mx_moe_combine(x, jnp.asarray(place), jnp.asarray(weight),
                                 tiles=tiles, interpret=True)
        assert got.dtype == jnp.float32 and got.shape == want.shape
        ref = _combine_reference(np.asarray(x, np.float32), place, weight)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6,
                                   atol=1e-6)
        # summed in fp32, rounded once to the type asked for
        low = pmc.mx_moe_combine(x, jnp.asarray(place), jnp.asarray(weight),
                                 jnp.bfloat16, tiles=tiles, interpret=True)
        assert low.dtype == jnp.bfloat16
        assert (low == got.astype(jnp.bfloat16)).all()
    visits = np.asarray(pmc._visits(jnp.asarray(place), rows, *tiles))
    assert visits.shape[1] == pmc.visit_bound(tokens, rows, groups, *tiles)
    # every tile is visited, in order, and the last visit has nothing
    # left to add (the bound held)
    assert (np.diff(visits[0]) >= 0).all()
    assert sorted(set(visits[0])) == list(range(tokens // tiles[0]))
    assert visits[3, -1] == 0 or visits.shape[1] == (visits[3] == 1).sum()

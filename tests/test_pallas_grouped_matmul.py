"""The grouped product's Pallas kernels in interpret mode against
`jax.lax.ragged_dot` and its `jax.vjp`: `mx_gmm`, the same kernel on
weights held transposed, `mx_tgmm`, and the `custom_vjp` that ties them
(`ops/pallas_grouped_matmul.py`); and the walk over the buffer that all
three share: every row owned once, and a grid whose steps follow the
shapes alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_grouped_matmul as pg


def _swollen(rows, counts):
    """Group sizes as `moe_held_experts` lays them: the padding joins
    the last group."""
    sizes = list(counts)
    sizes[-1] += rows - sum(counts)
    return sizes


# name: (rows, K, N, group sizes, tiles or None for the shapes' own)
CASES = {
    "no_rows_first": (512, 64, 32, [0, 200, 56, 256], None),
    "no_rows_in_the_middle": (512, 64, 32, [128, 0, 0, 384], None),
    "no_rows_last": (512, 32, 64, [100, 156, 256, 0], None),
    "groups_smaller_than_a_tile": (512, 64, 64, [5, 3, 1, 40, 463], None),
    "edge_on_a_boundary_and_inside": (512, 32, 32, [128, 200, 184], None),
    "groups_of_several_tiles": (1536, 32, 64, [900, 636], None),
    "last_group_swollen_by_padding": (640, 64, 32,
                                      _swollen(640, [60, 70, 0, 50]), None),
    "one_group": (256, 32, 32, [256], None),
    "mellum_widths": (256, 18 * 128, 7 * 128, [100, 156], None),
    "mellum_widths_down": (256, 7 * 128, 18 * 128, [156, 100], None),
    "tiny_widths_32_64": (128, 32, 64, [10, 0, 90, 28], None),
    "tiny_widths_64_32": (128, 64, 32, [64, 64], None),
    "rows_no_multiple_of_128": (192, 32, 32, [50, 100, 42], None),
    "k_and_n_cut": (512, 256, 384, [130, 126, 256], (128, 128, 128)),
    "row_tile_of_256": (512, 128, 128, [300, 212], (256, 128, 128)),
}


def _operands(rows, k, n, groups, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(rows + k + n), 3)
    lhs = jax.random.normal(keys[0], (rows, k), dtype)
    rhs = (jax.random.normal(keys[1], (groups, k, n)) * 0.1).astype(dtype)
    cot = jax.random.normal(keys[2], (rows, n), dtype)
    return lhs, rhs, cot


def _close(got, want, what):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert got.shape == want.shape and got.dtype == want.dtype, what
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * scale, what


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_equal_ragged_dot_and_its_vjp(case):
    rows, k, n, sizes, tiles = CASES[case]
    assert sum(sizes) == rows
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs, cot = _operands(rows, k, n, sizes.shape[0])
    want, pull = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                         lhs, rhs)
    want_lhs, want_rhs = pull(cot)
    # the three kernels, each alone
    _close(pg.mx_gmm(lhs, rhs, sizes, tiles=tiles), want, "mx_gmm")
    back = tiles and (tiles[0], tiles[2], tiles[1])
    _close(pg.mx_gmm(cot, rhs, sizes, transpose_rhs=True, tiles=back),
           want_lhs, "mx_gmm on transposed weights")
    _close(pg.mx_gmm(lhs, jnp.swapaxes(rhs, 1, 2), sizes,
                     transpose_rhs=True, tiles=tiles), want,
           "mx_gmm on weights stored transposed")
    _close(pg.mx_tgmm(lhs, cot, sizes, tiles=tiles), want_rhs, "mx_tgmm")
    # and through the custom_vjp
    got, pull = jax.vjp(lambda a, b: pg.grouped_matmul(a, b, sizes),
                        lhs, rhs)
    _close(got, want, "grouped_matmul")
    got_lhs, got_rhs = pull(cot)
    _close(got_lhs, want_lhs, "gradient to the rows")
    _close(got_rhs, want_rhs, "gradient to the weights")


def test_bf16_operands_give_what_ragged_dot_gives():
    """bf16 in, fp32 accumulation, bf16 out, gradients in the operands'
    type: within a bf16 rounding of the fp32 product."""
    rows, k, n = 384, 256, 128
    sizes = jnp.asarray([100, 0, 284], jnp.int32)
    lhs, rhs, cot = _operands(rows, k, n, 3, jnp.bfloat16)
    want, pull = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                         lhs, rhs)
    got, pull_got = jax.vjp(lambda a, b: pg.grouped_matmul(a, b, sizes),
                            lhs, rhs)
    exact = jax.lax.ragged_dot(lhs.astype(jnp.float32),
                               rhs.astype(jnp.float32), sizes)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - exact))) \
        <= 2 ** -8 * float(jnp.max(jnp.abs(exact)))
    for mine, theirs in zip(pull_got(cot), pull(cot)):
        assert mine.dtype == theirs.dtype == jnp.bfloat16
        assert mine.shape == theirs.shape
        assert float(jnp.max(jnp.abs(mine.astype(jnp.float32)
                                     - theirs.astype(jnp.float32)))) \
            <= 2 ** -6 * float(jnp.max(jnp.abs(theirs.astype(jnp.float32))))


def test_sizes_short_of_the_rows_run_the_last_group_to_the_end():
    """The stated departure from `ragged_dot`: rows past the sizes' sum
    are the last group's, not zeros."""
    lhs, rhs, _ = _operands(256, 32, 32, 2)
    short = jnp.asarray([100, 56], jnp.int32)
    whole = jnp.asarray([100, 156], jnp.int32)
    assert jnp.array_equal(pg.mx_gmm(lhs, rhs, short),
                           pg.mx_gmm(lhs, rhs, whole))


@pytest.mark.parametrize("seed", range(8))
def test_walk_owns_every_row_once(seed):
    """Random fills, empty groups and exact tile edges among them: the
    visits' row ranges part the rows, each inside its visit's tile and
    its group; a tile's visits and a group's visits are consecutive
    (Pallas writes a block back when its index changes); every group is
    visited (its gradient block is written, zeros if it has no rows)."""
    rng = np.random.RandomState(seed)
    n = int(rng.choice([1, 2, 5, 8, 16]))
    tile_m = int(rng.choice([128, 256]))
    rows = tile_m * int(rng.randint(1, 12))
    counts = rng.multinomial(int(rows * rng.uniform(0.2, 1.0)),
                             rng.dirichlet(np.ones(n) * 0.5))
    if seed % 2:            # edges on tile boundaries, groups of no rows
        counts = counts // tile_m * tile_m
    sizes = _swollen(rows, [int(c) for c in counts])
    group, tile, lo, hi = np.asarray(
        pg._visits(jnp.asarray(sizes, jnp.int32), rows, tile_m))
    assert len(group) == pg.visit_count(rows, n, tile_m)
    ends = np.cumsum(sizes)
    owner = np.full(rows, -1)
    for g, t, a, b in zip(group, tile, lo, hi):
        assert 0 <= t < rows // tile_m and a <= b
        if a < b:
            assert t * tile_m <= a and b <= (t + 1) * tile_m
            assert ends[g] - sizes[g] <= a and b <= ends[g]
            assert (owner[a:b] == -1).all()
            owner[a:b] = g
    assert (owner >= 0).all()
    assert set(group) == set(range(n))
    for walk in (group, tile):
        changes = np.flatnonzero(np.diff(walk)) + 1
        seen = walk[np.concatenate([[0], changes])]
        assert len(set(seen)) == len(seen)


def _grids(fn, *args):
    """The grids of the pallas_calls in `fn`'s jaxpr, nested ones too."""
    found = []

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    visit(inner)

    visit(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("kernel", ["gmm", "gmm_t", "tgmm"])
def test_grid_visits_follow_the_shapes_alone(kernel):
    """`C / tile_m + n - 1` steps along the rows whatever the sizes hold,
    traced or not: the device time of a launch cannot follow the fill."""
    rows, k, n, groups = 1024, 64, 32, 4
    lhs, rhs, cot = _operands(rows, k, n, groups)
    call = {"gmm": lambda s: pg.mx_gmm(lhs, rhs, s),
            "gmm_t": lambda s: pg.mx_gmm(cot, rhs, s, transpose_rhs=True),
            "tgmm": lambda s: pg.mx_tgmm(lhs, cot, s)}[kernel]
    tile_m = pg._tile_m(kernel, rows, groups)
    steps = rows // tile_m + groups - 1
    assert pg.visit_count(rows, groups, tile_m) == steps
    sizes = jnp.asarray([256, 256, 256, 256], jnp.int32)
    grid, = _grids(call, sizes)
    assert grid == ((1, 1, steps) if kernel == "tgmm" else (1, steps, 1))
    for fill in ([1024, 0, 0, 0], [1, 1, 1, 1021], [300, 300, 300, 124]):
        assert _grids(call, jnp.asarray(fill, jnp.int32)) == [grid]


def test_tiles_from_the_benchmark_cells_shapes():
    """What the sweep on the chip chose (PERF.md, PR 38), and that the
    weights' block stays whole there; a block past `TILE_BYTES` is cut
    to a divisor that is a multiple of 128."""
    for rows, groups, hidden, width, tgmm_tile in (
            (20480, 8, 2304, 896, 512), (4608, 16, 2048, 768, 256),
            (1920, 16, 2048, 512, 128)):
        for k, n in ((hidden, width), (width, hidden)):
            assert pg._tiles("gmm", rows, groups, k, n, 2) == (128, k, n)
            assert pg._tiles("gmm_t", rows, groups, k, n, 2) == (128, k, n)
            assert pg._tiles("tgmm", rows, groups, k, n, 2) \
                == (tgmm_tile, k, n)
    tile_m, tk, tn = pg._tiles("gmm", 8192, 8, 8192, 4096, 2)
    assert (tk, tn) != (8192, 4096)
    assert 8192 % tk == 0 and 4096 % tn == 0 and tk % 128 == tn % 128 == 0
    assert pg._step_bytes("gmm", tile_m, tk, tn, 8192, 2) <= pg.TILE_BYTES


def test_launches_are_counted_by_kernel_and_tile():
    lhs, rhs, cot = _operands(256, 32, 32, 2)
    sizes = jnp.asarray([100, 156], jnp.int32)
    read = lambda kernel: pg._traced.labels(kernel=kernel,
                                            tile_m="128").value
    before = [read(kernel) for kernel in ("gmm", "gmm_t", "tgmm")]
    jax.vjp(lambda a, b: pg.grouped_matmul(a, b, sizes), lhs, rhs)[1](cot)
    after = [read(kernel) for kernel in ("gmm", "gmm_t", "tgmm")]
    assert [b - a for a, b in zip(before, after)] == [1, 1, 1]

"""Linear attention with a matrix-valued state: the causal depthwise
convolution and the gated delta rule of Gated DeltaNet (the token mixer
of three in four layers of the `qwen3_next` family).

Per head, with a state S (d_k, d_v) that starts at 0, for each token t::

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t = S^T q_t

Token by token that is `seq` dependent steps. `gated_delta_rule` runs it
in chunks of `chunk` tokens. With G the running sum of g inside a chunk
and S the state at the chunk's start::

    (I + A) D = beta (V - exp(G) K S),
    A[t, s] = beta_t exp(G_t - G_s) k_t.k_s   for s < t, else 0
    O  = exp(G) Q S + (M . Q K^T) D,   M[t, s] = exp(G_t - G_s), s <= t
    S' = exp(G_last) S + (exp(G_last - G) K)^T D

All of it is Pallas. What has no dependency between chunks is done a
tile of chunks at a time where the tile lies in VMEM (`mx_gdn_prepare`,
parallel over key heads and tiles: q and k are read once a key head and
serve its value heads): the running decays, the inverse T of the unit
triangular I + A by elimination in 16-row blocks and block merges,
U = T beta V, W = T beta exp(G) K, the masked Q K^T. What has, the state
carried from chunk to chunk, is two kernels with the state in VMEM across
the chunk axis: `mx_gdn_fwd` (D = U - W S, O, S') and `mx_gdn_bwd`, the
same scan reversed, which is handed the state at each chunk's start (one
state a chunk, never one a token) and forms D again. Under
differentiation the forward's preparation also writes T, and the backward
is handed the six operands and T as residuals beside the arguments: they
are O(seq d), as the flash kernel's q, k, v and out are (0.24 GB a layer
at 32 heads of 128 over 4,096 tokens), so the preparation runs once a
call. Their cotangents are pulled back by hand from T
(`mx_gdn_prepare_bwd`), never through the elimination. An
undifferentiated call writes no T. Decays, beta, the solve and the state
are fp32; the products take their operands in the type of q (bf16 in
training) and accumulate in fp32.

Registered as `_contrib_gated_delta_rule` and `_contrib_causal_conv1d`.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .pallas_attention import _NN, _NT, _TN, _dot
from .registry import register

__all__ = ["causal_conv1d", "gated_delta_rule"]

# The type of the carried state, of the decays and of the solve. A
# constant of the module, not an argument: nothing in the program sets it
# (the benchmark's control lowers it to show that its check would notice).
# `gated_delta_rule` reads it when it is traced.
STATE_DTYPE = jnp.float32

_HIGHEST = jax.lax.Precision.HIGHEST


@register("_contrib_causal_conv1d", aliases=("causal_conv1d",))
def causal_conv1d(data, weight):
    """Depthwise causal convolution over the sequence: data (batch, seq,
    channels), weight (channels, width); ``out[t] = sum_j weight[:, j] *
    data[t - (width - 1) + j]``, positions before the sequence read 0.
    Products and the sum in fp32, the result in `data`'s type."""
    with jax.named_scope("gdn_conv"):
        width = weight.shape[1]
        seq = data.shape[1]
        x = jnp.pad(data, ((0, 0), (width - 1, 0), (0, 0)))
        w = weight.astype(jnp.float32)
        out = sum(x[:, j:j + seq].astype(jnp.float32) * w[:, j]
                  for j in range(width))
        return out.astype(data.dtype)


# ---- what is parallel over the chunks: a tile of chunks in VMEM ----------

_SOLVE_BLOCK = 16
# Rows of one tile: as many whole chunks as fill the 128 lanes of a
# (rows, rows) array, so the decays, A and T of two chunks of 64 are one
# lane-dense block-diagonal matrix and every product has the MXU's shape.
_TILE_ROWS = 128
_TILES_PER_STEP = 4
_PREPARE_VMEM_LIMIT = 64 * 1024 * 1024


def _largest_divisor(n, limit):
    return max(d for d in range(1, limit + 1) if n % d == 0)


def _mxu(a, b, contract):
    """`_dot`, at the highest precision where the operands are fp32 (the
    solve's own products, and every product of an fp32 call)."""
    precision = _HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _group(i, size):
    return jax.lax.div(i, jnp.int32(size))


def _col(row, eye):
    """(1, r) -> (r, 1): one term a sum, so exact."""
    return jnp.sum(jnp.where(eye, row, 0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0), axis=0, keepdims=True)


def _spread(x, j, pos, blk):
    """x (blk, r) holds one value a group of `blk` lanes, at place j of
    the group: the same value at every place of its group."""
    from jax.experimental.pallas import tpu as pltpu

    if j:
        x = pltpu.roll(x, x.shape[1] - j, 1)           # place j -> place 0
    width = 1
    while width < blk:
        step = min(width, blk - width)
        x = jnp.where(pos >= width, pltpu.roll(x, step, 1), x)
        width += step
    return x


def _odd_blocks(x, size):
    """The rows of every second block of `size` rows, the odd ones."""
    return jnp.concatenate([x[i:i + size]
                            for i in range(size, x.shape[0], 2 * size)], 0)


def _to_odd_blocks(x, size):
    """`_odd_blocks` undone: zeros where the even blocks were."""
    zero = jnp.zeros((size, x.shape[1]), x.dtype)
    return jnp.concatenate(
        [piece for i in range(0, x.shape[0], size)
         for piece in (zero, x[i:i + size])], 0)


def _thirds(x):
    """fp32 as three bf16 terms whose sum is x to its last bit."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _mxu_fp32(a, b):
    """a @ b of fp32 values handed over as `_thirds`: the six bf16 passes
    of the highest precision (hi hi, hi mid, mid hi, hi lo, lo hi, mid
    mid) as one product over six times the depth, so the MXU adds them
    (faster on the chip than a product a term of b with a's terms
    stacked: PERF.md, PR 35)."""
    (ah, am, al), (bh, bm, bl) = a, b
    return _dot(jnp.concatenate([ah, ah, am, ah, al, am], 1),
                jnp.concatenate([bh, bm, bh, bl, bh, bm], 0), _NN)


def _masks(rows, chunk):
    """Which (t, s) of a tile's (rows, rows) arrays belong to what: made
    once a grid step, from the shapes alone."""
    m = SimpleNamespace()
    t, s = _iota((rows, rows), 0), _iota((rows, rows), 1)
    same = _group(t, chunk) == _group(s, chunk)
    m.eye = t == s
    m.lower = same & (s <= t)               # s <= t inside one chunk
    m.strict = same & (s < t)
    m.upper = same & (t < s)
    m.last = same & (jax.lax.rem(s, jnp.int32(chunk)) == chunk - 1)
    # the solve: diagonal blocks side by side, then the blocks below them
    m.blk = blk = min(_SOLVE_BLOCK, chunk)
    m.diagonal = _group(t, blk) == _group(s, blk)
    m.pos = jax.lax.rem(_iota((blk, rows), 1), jnp.int32(blk))
    m.packed_eye = _iota((blk, rows), 0) == m.pos
    m.below = {}
    size = blk
    while size < chunk:
        # row r of the odd blocks' rows is row t of the tile
        r, s_ = _iota((rows // 2, rows), 0), _iota((rows // 2, rows), 1)
        t_ = r + (_group(r, size) + 1) * size
        m.below[size] = (_group(t_, chunk) == _group(s_, chunk)) \
            & (_group(s_, size) == _group(t_, size) - 1)
        size *= 2
    return m


def _tile_inverse(a, m):
    """(I + a)^-1 for `a` (r, r) fp32, strictly lower triangular inside
    each chunk of the tile and 0 between chunks.

    The diagonal blocks of 16 rows by elimination, column after column:
    ``X <- X - a[:, j] X[j, :]``, the terms of forward substitution in
    its order, nothing that cancels. All blocks of the tile at once, side
    by side on the lanes of one (16, r) array, so a step is two
    multiply-subtracts; the columns of `a`, spread over their block's
    lanes, do not depend on X and are formed off the chain. The blocks
    below the diagonal from ``inv([[L1, 0], [B, L2]]) = [[T1, 0],
    [-T2 B T1, T2]]``, for every pair of the tile in two products of the
    block-diagonal whole (only the rows of the lower blocks are pushed),
    at the highest precision, doubling the block until it is the
    chunk."""
    rows, blk = a.shape[0], m.blk
    count = rows // blk
    on_diagonal = jnp.where(m.diagonal, a, 0.0)
    packed = on_diagonal[:blk]
    for i in range(1, count):
        packed = packed + on_diagonal[i * blk:(i + 1) * blk]
    columns = [_spread(jnp.where(m.pos == j, packed, 0.0), j, m.pos, blk)
               for j in range(blk - 1)]
    x = m.packed_eye.astype(a.dtype)
    for j, column in enumerate(columns):
        x = x - column * x[j:j + 1]
    inv = jnp.where(m.diagonal, jnp.concatenate([x] * count, axis=0), 0.0)
    for size, below in m.below.items():
        parts = _thirds(inv)
        # B T1, then T2 (B T1), at the rows of the lower blocks
        right = _mxu_fp32(
            _thirds(jnp.where(below, _odd_blocks(a, size), 0.0)), parts)
        corner = _mxu_fp32(
            [_odd_blocks(part, size) for part in parts],
            [_to_odd_blocks(part, size) for part in _thirds(right)])
        inv = inv - _to_odd_blocks(corner, size)
    return inv


def _tile(q, k, v, g_row, beta_row, kk, qk, m, sdt, inv=None):
    """Decays, A, T and the scaled operands of one value head over one
    tile: q, k (r, d_k) and v (r, d_v) in the operand type, g and beta
    (1, r), kk = k k^T and qk = q k^T (r, r) fp32 (once a key head);
    decays and beta in `sdt`; T is formed unless it is handed over."""
    f32, op = jnp.float32, q.dtype
    x = SimpleNamespace()
    # G: the running sum of g inside each chunk, down a column
    big = jnp.sum(jnp.where(m.lower, g_row.astype(sdt), 0), axis=1,
                  keepdims=True)
    big_row = _row(big, m.eye)
    big_last = jnp.sum(jnp.where(m.last, big_row, 0), axis=1, keepdims=True)
    # exp(G_t - G_s) where s <= t in one chunk, else 0 (and no inf * 0)
    x.decay = jnp.exp(
        jnp.where(m.lower, big - big_row, -jnp.inf)).astype(f32)
    x.beta = _col(beta_row.astype(sdt), m.eye).astype(f32)
    x.inv = _tile_inverse(
        jnp.where(m.strict, x.beta * x.decay * kk, 0.0), m) \
        if inv is None else inv                                  # T
    x.inv_op = x.inv.astype(op)
    x.eg = jnp.exp(big).astype(f32)                              # exp(G)
    x.to_last = jnp.exp(big_last - big).astype(f32)
    x.whole = jnp.exp(big_last)                  # exp(G_last), down a column
    x.qf, x.kf, x.vf = q.astype(f32), k.astype(f32), v.astype(f32)
    x.bv = (x.beta * x.vf).astype(op)
    x.bek = (x.beta * x.eg * x.kf).astype(op)
    return x


def _fold(rows, chunk, dtype):
    """(chunk, r) 0/1: column c of a chunk under every chunk's lanes."""
    return (jax.lax.rem(_iota((chunk, rows), 1), jnp.int32(chunk))
            == _iota((chunk, rows), 0)).astype(dtype)


def _prepare_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, qg_ref, kd_ref,
                    w_ref, u_ref, p_ref, a_ref, *inv_ref, rep, tiles, rows,
                    chunk, state):
    """One key head, `rep` value heads, `tiles` tiles; `inv_ref`, where
    the call asks for it, takes T in fp32 (the pullback reads it)."""
    op = q_ref.dtype
    fold = _fold(rows, chunk, op)
    m = _masks(rows, chunk)
    for i in range(tiles):
        at = slice(i * rows, (i + 1) * rows)
        q, k = q_ref[0, at, :], k_ref[0, at, :]
        kk, qk = _mxu(k, k, _NT), _mxu(q, k, _NT)
        for hd in range(rep):
            x = _tile(q, k, v_ref[hd, at, :], g_ref[hd, i], beta_ref[hd, i],
                      kk, qk, m, state)
            qg_ref[hd, at, :] = (x.eg * x.qf).astype(op)
            kd_ref[hd, at, :] = (x.to_last * x.kf).astype(op)
            w_ref[hd, at, :] = _mxu(x.inv_op, x.bek, _NN).astype(op)
            u_ref[hd, at, :] = _mxu(x.inv_op, x.bv, _NN).astype(op)
            p = (x.decay * qk).astype(op)
            if rows > chunk:          # a chunk's columns from under its rows
                p = _mxu(p, fold, _NT).astype(op)
            p_ref[hd, at, :] = p
            for c in range(rows // chunk):
                a_ref[hd, i, c:c + 1, :] = jnp.broadcast_to(
                    x.whole[c * chunk:c * chunk + 1],
                    (1, a_ref.shape[-1])).astype(a_ref.dtype)
            for ref in inv_ref:
                ref[hd, at, :] = x.inv


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref,
                        dqg_ref, dkd_ref, dw_ref, du_ref, dp_ref, da_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, rep,
                        tiles, rows, chunk, state):
    """The pullback of `_prepare_kernel`, from T as that kernel formed it:
    never the elimination's own transpose. With M the masked decays, e =
    exp(G) and l = exp(G_last - G)::

        dT = dU (beta V)^T + dW (beta e K)^T
        d(beta V) = T^T dU;  d(beta e K) = T^T dW
        dA = -T^T dT T^T   below the diagonal
        dKK = dA . beta M;  dQK = dP . M;  dM = dA . beta KK + dP . QK

    and what follows from them for q, k, v, beta and G; dg is the sum of
    dG from a token to its chunk's end; dq and dk are summed over the
    value heads of the key head."""
    f32, op = jnp.float32, q_ref.dtype
    fold = _fold(rows, chunk, op)
    m = _masks(rows, chunk)
    lane_chunk = _group(_iota((1, rows), 1), chunk)
    inner = lambda a, b: jnp.sum(a * b, axis=1, keepdims=True)
    for i in range(tiles):
        at = slice(i * rows, (i + 1) * rows)
        q, k = q_ref[0, at, :], k_ref[0, at, :]
        kk, qk = _mxu(k, k, _NT), _mxu(q, k, _NT)
        dkk = dqk = dq = dk = 0.0
        for hd in range(rep):
            x = _tile(q, k, v_ref[hd, at, :], g_ref[hd, i], beta_ref[hd, i],
                      kk, qk, m, state, inv_ref[hd, at, :])
            dqg, dkd = (r[hd, at, :].astype(f32) for r in (dqg_ref, dkd_ref))
            dw, du, dp = dw_ref[hd, at, :], du_ref[hd, at, :], \
                dp_ref[hd, at, :]
            if rows > chunk:
                dp = _mxu(dp, fold, _NN)
            dp = dp.astype(f32)
            d_inv = _mxu(du, x.bv, _NT) + _mxu(dw, x.bek, _NT)
            d_bv = _mxu(x.inv_op, du, _TN)
            d_bek = _mxu(x.inv_op, dw, _TN)
            d_a = jnp.where(
                m.strict, -_mxu(_mxu(x.inv, d_inv, _TN), x.inv, _NT), 0.0)
            d_a, dp = d_a * x.decay, dp * x.decay         # dA . M, dP . M
            d_akk = d_a * kk
            through_decay = x.beta * d_akk + dp * qk              # dM . M
            dkk = dkk + x.beta * d_a
            dqk = dqk + dp
            k_bek, k_kd = inner(d_bek, x.kf), inner(dkd, x.kf)
            dbeta = jnp.sum(d_akk, axis=1, keepdims=True) \
                + inner(d_bv, x.vf) + x.eg * k_bek
            dbig = jnp.sum(through_decay, axis=1, keepdims=True) \
                - _col(jnp.sum(through_decay, axis=0, keepdims=True), m.eye) \
                + x.eg * (inner(dqg, x.qf) + x.beta * k_bek)
            # G_last's share reaches every token of its chunk; what
            # exp(G_last - G) takes from G_t it hands to G_last, so a
            # token's g is left with the tokens before it
            to_end = x.to_last * k_kd
            dg = jnp.sum(jnp.where(m.lower, dbig, 0.0)
                         + jnp.where(m.upper, to_end, 0.0),
                         axis=0, keepdims=True)
            for c in range(rows // chunk):
                share = x.whole[c * chunk:c * chunk + 1].astype(f32) \
                    * jnp.sum(da_ref[hd, i, c:c + 1, :].astype(f32),
                              axis=1, keepdims=True)
                dg = dg + jnp.where(lane_chunk == c, share, 0.0)
            dg_ref[hd, i] = dg.astype(dg_ref.dtype)
            dbeta_ref[hd, i] = _row(dbeta, m.eye).astype(dbeta_ref.dtype)
            dv_ref[hd, at, :] = (x.beta * d_bv).astype(op)
            dq = dq + x.eg * dqg
            dk = dk + x.beta * x.eg * d_bek + x.to_last * dkd
        dkk, dqk = dkk.astype(op), dqk.astype(op)
        dq_ref[0, at, :] = (dq + _mxu(dqk, k, _NN)).astype(op)
        dk_ref[0, at, :] = (dk + _mxu(dkk, k, _NN) + _mxu(dkk, k, _TN)
                            + _mxu(dqk, q, _TN)).astype(op)


def _tile_rows(t, chunk):
    """Whole chunks that fill `_TILE_ROWS`, as many as divide the
    sequence's chunks."""
    return chunk * _largest_divisor(t // chunk, max(_TILE_ROWS // chunk, 1))


def _prepare_call(kernel, name, q, k, v, g, beta, extra, out, chunk,
                  interpret, state):
    """`kernel` over (key head, block of tiles), both parallel. q and k
    stay at their key heads: a grid step holds one key head and the
    value heads it serves. `extra`: further operands, `out`: the results,
    each as (array or shape, kind), kind one of "key" (b * heads_k, t, d),
    "value" (b * heads, t, d), "token" (b * heads, t) and "chunk"
    (b * heads, t / chunk, d)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    keys, t = q.shape[0], q.shape[1]
    rep = v.shape[0] // keys
    rows = _tile_rows(t, chunk)
    per_tile, count = rows // chunk, t // rows
    tiles = _largest_divisor(count, _TILES_PER_STEP)

    def spec(x, kind):
        if kind == "token":
            return (x.shape[0], count, 1, rows), pl.BlockSpec(
                (rep, tiles, 1, rows), lambda i, j: (i, j, 0, 0))
        if kind == "chunk":
            return (x.shape[0], count, per_tile, x.shape[-1]), pl.BlockSpec(
                (rep, tiles, per_tile, x.shape[-1]),
                lambda i, j: (i, j, 0, 0))
        heads = 1 if kind == "key" else rep
        return x.shape, pl.BlockSpec((heads, tiles * rows, x.shape[-1]),
                                     lambda i, j: (i, j, 0))

    operands = [(q, "key"), (k, "key"), (v, "value"), (g, "token"),
                (beta, "token")] + list(extra)
    shaped = [spec(x, kind) for x, kind in operands]
    results = [spec(x, kind) for x, kind in out]
    got = pl.pallas_call(
        functools.partial(kernel, rep=rep, tiles=tiles, rows=rows,
                          chunk=chunk, state=state),
        out_shape=tuple(jax.ShapeDtypeStruct(shape, x.dtype)
                        for (shape, _), (x, _) in zip(results, out)),
        grid=(keys, count // tiles),
        in_specs=[block for _, block in shaped],
        out_specs=tuple(block for _, block in results),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_PREPARE_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*(x.reshape(shape) for (x, _), (shape, _) in zip(operands, shaped)))
    return tuple(y.reshape(x.shape) for y, (x, _) in zip(got, out))


def _flat(x):
    return x.reshape((-1,) + x.shape[2:])


def _chunk_operands(q, k, v, g, beta, chunk, interpret, state,
                    keep_inverse=False):
    """The per-chunk operands of the scan, from q, k (b * heads_k, t,
    d_k), v (b * heads, t, d_v) and g, beta (b * heads, t):

    qg = exp(G) q, kd = exp(G_last - G) k, w, u (b * heads, t, d), p = the
    masked q k^T (b * heads, t, chunk), all in q's type, and the chunk's
    whole decay exp(G_last) spread over a row, (b * heads, t / chunk,
    d_v), in the state's type `state`; `keep_inverse`: and T, fp32, a tile's
    chunks along its diagonal, (b * heads, t, rows of a tile)."""
    like = jax.ShapeDtypeStruct
    bh, t, dv = v.shape
    dk = q.shape[-1]
    out = [(like((bh, t, dk), q.dtype), "value")] * 3 + [
        (like((bh, t, dv), q.dtype), "value"),
        (like((bh, t, chunk), q.dtype), "value"),
        (like((bh, t // chunk, dv), state), "chunk")]
    if keep_inverse:
        out.append((like((bh, t, _tile_rows(t, chunk)), jnp.float32),
                    "value"))
    return _prepare_call(_prepare_kernel, "mx_gdn_prepare", q, k, v, g, beta,
                         [], out, chunk, interpret, state)


def _chunk_operands_pullback(q, k, v, g, beta, inv, cotangents, chunk,
                             interpret, state):
    """Cotangents of (q, k, v, g, beta) from those of `_chunk_operands`'
    six results, and T as it kept it."""
    kinds = ["value"] * 6 + ["chunk"]
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    out = [(like(q), "key"), (like(k), "key"), (like(v), "value"),
           (like(g), "token"), (like(beta), "token")]
    return _prepare_call(_prepare_bwd_kernel, "mx_gdn_prepare_bwd", q, k, v,
                         g, beta, list(zip((inv,) + tuple(cotangents), kinds)),
                         out, chunk, interpret, state)


# ---- what is carried from chunk to chunk: the state in VMEM ---------------

# Heads and chunks of one grid step of the scan: independent heads side by
# side give the scheduler more than one dependent chain, several chunks a
# step spread the step's fixed cost.
_HEADS_PER_STEP = 4
_CHUNKS_PER_STEP = 8


def _fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, a_ref, o_ref, st_ref,
                s_acc, *, heads, chunks, chunk):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)

    op = qg_ref.dtype
    for hd in range(heads):
        s = s_acc[hd]                                   # (d_k, d_v)
        for c in range(chunks):
            rows = slice(c * chunk, (c + 1) * chunk)
            st_ref[hd, c] = s.astype(st_ref.dtype)
            sb = s.astype(op)
            d = (u_ref[hd, rows, :].astype(jnp.float32)
                 - _dot(w_ref[hd, rows, :], sb, _NN)).astype(op)
            o = _dot(qg_ref[hd, rows, :], sb, _NN) \
                + _dot(p_ref[hd, rows, :], d, _NN)
            o_ref[hd, rows, :] = o.astype(o_ref.dtype)
            s = (a_ref[hd, c:c + 1, :].astype(jnp.float32) * s
                 + _dot(kd_ref[hd, rows, :], d, _TN)).astype(s_acc.dtype)
        s_acc[hd] = s


def _bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, a_ref, st_ref, do_ref,
                dqg_ref, dkd_ref, dw_ref, du_ref, dp_ref, da_ref, ds_acc, *,
                heads, chunks, chunk):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_acc[...] = jnp.zeros_like(ds_acc)

    op = qg_ref.dtype
    for hd in range(heads):
        ds = ds_acc[hd]                    # dL/dS', the next chunk's start
        for c in reversed(range(chunks)):
            rows = slice(c * chunk, (c + 1) * chunk)
            s = st_ref[hd, c]
            sb, dsb = s.astype(op), ds.astype(op)
            do = do_ref[hd, rows, :]
            w, qg, kd = w_ref[hd, rows, :], qg_ref[hd, rows, :], \
                kd_ref[hd, rows, :]
            d = (u_ref[hd, rows, :].astype(jnp.float32)
                 - _dot(w, sb, _NN)).astype(op)
            dd = _dot(p_ref[hd, rows, :], do, _TN) + _dot(kd, dsb, _NN)
            ddb = dd.astype(op)
            dqg_ref[hd, rows, :] = _dot(do, sb, _NT).astype(dqg_ref.dtype)
            dp_ref[hd, rows, :] = _dot(do, d, _NT).astype(dp_ref.dtype)
            dkd_ref[hd, rows, :] = _dot(d, dsb, _NT).astype(dkd_ref.dtype)
            du_ref[hd, rows, :] = ddb.astype(du_ref.dtype)
            dw_ref[hd, rows, :] = (-_dot(ddb, sb, _NT)).astype(dw_ref.dtype)
            da_ref[hd, c:c + 1, :] = jnp.sum(
                ds.astype(jnp.float32) * s.astype(jnp.float32), axis=0,
                keepdims=True).astype(da_ref.dtype)
            ds = (a_ref[hd, c:c + 1, :].astype(jnp.float32) * ds
                  + _dot(qg, do, _TN) - _dot(w, ddb, _TN)
                  ).astype(ds_acc.dtype)
        ds_acc[hd] = ds


def _grid(bh, n):
    """(heads, chunks) of one grid step of the scan (the preparation has
    its own: `_prepare_call`). The chunks of a step are the
    second-minor dimension of a block, which the TPU lowering takes in
    eights or whole."""
    chunks = _CHUNKS_PER_STEP if n % _CHUNKS_PER_STEP == 0 else n
    return _largest_divisor(bh, _HEADS_PER_STEP), chunks


def _compiler_params():
    """Of the scan: heads are independent, the chunk axis carries the
    state."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _scan_forward(qg, kd, w, u, p, a, chunk, interpret):
    """(o (bh, t, d_v), the state at each chunk's start (bh, n, d_k,
    d_v))."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, dk = qg.shape
    dv, n = u.shape[-1], a.shape[1]
    heads, chunks = _grid(bh, n)
    rows = chunks * chunk

    def tok(d):
        return pl.BlockSpec((heads, rows, d), lambda b_, j: (b_, j, 0))

    per_chunk = pl.BlockSpec((heads, chunks, dv), lambda b_, j: (b_, j, 0))
    states = pl.BlockSpec((heads, chunks, dk, dv),
                          lambda b_, j: (b_, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, chunks=chunks,
                          chunk=chunk),
        out_shape=(jax.ShapeDtypeStruct((bh, t, dv), qg.dtype),
                   jax.ShapeDtypeStruct((bh, n, dk, dv), a.dtype)),
        grid=(bh // heads, n // chunks),
        in_specs=[tok(dk), tok(dk), tok(dk), tok(dv), tok(chunk), per_chunk],
        out_specs=(tok(dv), states),
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), a.dtype)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mx_gdn_fwd",
    )(qg, kd, w, u, p, a)


def _scan_backward(qg, kd, w, u, p, a, states, do, chunk, interpret):
    """Cotangents of (qg, kd, w, u, p, a) from that of o."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, dk = qg.shape
    dv, n = u.shape[-1], a.shape[1]
    heads, chunks = _grid(bh, n)
    rows = chunks * chunk
    last = n // chunks - 1

    def tok(d):
        return pl.BlockSpec((heads, rows, d),
                            lambda b_, j: (b_, last - j, 0))

    per_chunk = pl.BlockSpec((heads, chunks, dv),
                             lambda b_, j: (b_, last - j, 0))
    st = pl.BlockSpec((heads, chunks, dk, dv),
                      lambda b_, j: (b_, last - j, 0, 0))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, chunks=chunks,
                          chunk=chunk),
        out_shape=tuple(like(x) for x in (qg, kd, w, u, p, a)),
        grid=(bh // heads, n // chunks),
        in_specs=[tok(dk), tok(dk), tok(dk), tok(dv), tok(chunk), per_chunk,
                  st, tok(dv)],
        out_specs=(tok(dk), tok(dk), tok(dk), tok(dv), tok(chunk),
                   per_chunk),
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), a.dtype)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mx_gdn_bwd",
    )(qg, kd, w, u, p, a, states, do)


# Forward and backward are jitted on their own: the layers of a model
# that call the rule at one shape are traced and lowered once, not once a
# layer (the kernels' bodies are long).

@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8), inline=True)
def _forward(q, k, v, g, beta, chunk, interpret, state, keep_inverse):
    """(o, what the backward reads: the six operands of the scan, T where
    `keep_inverse`, and the state at each chunk's start)."""
    prepared = _chunk_operands(q, k, v, g, beta, chunk, interpret, state,
                               keep_inverse)
    o, states = _scan_forward(*prepared[:6], chunk, interpret)
    return o, prepared + (states,)


@functools.partial(jax.jit, static_argnums=(7, 8, 9), inline=True)
def _backward(q, k, v, g, beta, kept, do, chunk, interpret, state):
    # the scan reversed on the forward's operands, then their pullback
    # from the forward's T: the preparation does not run again
    *operands, inv, states = kept
    cotangents = _scan_backward(*operands, states, do.astype(q.dtype), chunk,
                                interpret)
    return _chunk_operands_pullback(q, k, v, g, beta, inv, cotangents, chunk,
                                    interpret, state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _delta_rule(q, k, v, g, beta, chunk, interpret, state):
    return _forward(q, k, v, g, beta, chunk, interpret, state, False)[0]


def _delta_fwd(q, k, v, g, beta, chunk, interpret, state):
    # Kept: the operands and T, O(seq d) as the flash kernel's q, k, v and
    # out are (at 32 heads of 128 over 4,096 tokens, chunk 64: qg, kd, w
    # and u 33.5 MB each in bf16, p 16.8 and 33.5 on the TPU, whose lanes
    # pad its 64 columns to 128, the decays 1, T 67 in fp32: 0.24 GB a
    # layer), and a state a chunk, never one a token
    o, kept = _forward(q, k, v, g, beta, chunk, interpret, state, True)
    return o, (q, k, v, g, beta, kept)


def _delta_bwd(chunk, interpret, state, res, do):
    return _backward(*res, do, chunk, interpret, state)


_delta_rule.defvjp(_delta_fwd, _delta_bwd)

_traced = _tm.REGISTRY.counter(
    "mx_gated_delta_rule_traced_total",
    "gated_delta_rule calls traced into a program, by chunk size",
    labels=("chunk",))


@register("_contrib_gated_delta_rule", aliases=("gated_delta_rule",))
def gated_delta_rule(q, k, v, g, beta, chunk=64, interpret=None):
    """The gated delta rule over whole sequences, state 0 at the start.

    q, k (batch, heads_k, seq, d_k) as they enter the rule (normalised
    and scaled by the caller); v (batch, heads, seq, d_v), `heads` a
    multiple of `heads_k` (key head i serves value heads
    ``i * heads / heads_k`` onward); g (log decay, <= 0) and beta
    (batch, heads, seq). `seq` must divide by `chunk`. Result (batch,
    heads, seq, d_v) in v's type. Off the TPU the kernels run in
    interpret mode."""
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu",)
    heads, seq = v.shape[1], v.shape[2]
    if seq % chunk:
        raise ValueError("sequence length %d must divide by the chunk %d"
                         % (seq, chunk))
    blocks = max(chunk // _SOLVE_BLOCK, 1)
    if chunk % min(chunk, _SOLVE_BLOCK) or blocks & (blocks - 1):
        raise ValueError("chunk %d is not %d times a power of two"
                         % (chunk, _SOLVE_BLOCK))
    if heads % q.shape[1] or q.shape != k.shape:
        raise ValueError("q %s, k %s and v %s do not fit: value heads must "
                         "be a multiple of key heads"
                         % (q.shape, k.shape, v.shape))
    _traced.labels(chunk=str(chunk)).inc()
    with jax.named_scope("gdn_delta_rule"):
        out = _delta_rule(_flat(q), _flat(k.astype(q.dtype)),
                          _flat(v.astype(q.dtype)), _flat(g), _flat(beta),
                          int(chunk), bool(interpret), jnp.dtype(STATE_DTYPE))
        return out.reshape(v.shape).astype(v.dtype)

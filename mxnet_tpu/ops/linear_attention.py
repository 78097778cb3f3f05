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

What has no dependency between chunks is plain XLA (`_prepare`: the
running decays, the inverse T of the unit triangular I + A by forward
substitution in 16-row blocks, U = T beta V, W = T beta exp(G) K, the
masked Q K^T), differentiated by JAX. What has, the state carried from
chunk to chunk, is two Pallas kernels with the state in VMEM across the
chunk axis: `mx_gdn_fwd` (D = U - W S, O, S') and `mx_gdn_bwd`, the same
scan reversed, which is handed the state at each chunk's start (one state
a chunk is the only residual beside the operands, never one a token) and
forms D again. Decays, beta, the solve and the state are fp32; the
products take their operands in the type of q (bf16 in training) and
accumulate in fp32.

Registered as `_contrib_gated_delta_rule` and `_contrib_causal_conv1d`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .pallas_attention import _NN, _NT, _TN, _dot
from .registry import register

__all__ = ["causal_conv1d", "gated_delta_rule"]

# The type of the carried state, of the decays and of the solve. A
# constant of the module, not an argument: nothing in the program sets it
# (the benchmark's control lowers it to show that its check would notice).
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


# ---- what is parallel over the chunks: XLA --------------------------------

_SOLVE_BLOCK = 16


def _mm(a, b):
    return jnp.einsum("...ij,...jk->...ik", a, b, precision=_HIGHEST)


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular `a` (..., c, c), fp32.

    Diagonal blocks of 16 rows by forward substitution, row after row
    (the exact recurrence, nothing that cancels); the blocks below them
    from ``inv([[L1, 0], [B, L2]]) = [[T1, 0], [-T2 B T1, T2]]``, doubling
    the block until it is the chunk."""
    c = a.shape[-1]
    blk = min(_SOLVE_BLOCK, c)
    n = c // blk
    eye = jnp.eye(blk, dtype=a.dtype)
    # the diagonal blocks: (..., n, blk, blk)
    diag = jnp.stack([a[..., i * blk:(i + 1) * blk, i * blk:(i + 1) * blk]
                      for i in range(n)], axis=-3)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (blk,))]
    for i in range(1, blk):
        done = jnp.stack(rows, axis=-2)                   # (..., i, blk)
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], done, precision=_HIGHEST))
    inv = [jnp.stack(rows, axis=-2)[..., i, :, :] for i in range(n)]
    size = blk
    while len(inv) > 1:
        merged = []
        for i in range(0, len(inv), 2):
            lo = i * size
            below = a[..., lo + size:lo + 2 * size, lo:lo + size]
            corner = -_mm(inv[i + 1], _mm(below, inv[i]))
            top = jnp.concatenate(
                [inv[i], jnp.zeros_like(inv[i])], axis=-1)
            merged.append(jnp.concatenate(
                [top, jnp.concatenate([corner, inv[i + 1]], axis=-1)],
                axis=-2))
        inv, size = merged, size * 2
    return inv[0]


def _prepare(q, k, v, g, beta, chunk):
    """The per-chunk operands of the scan, from q, k (b, h, t, d_k), v
    (b, h, t, d_v) and g, beta (b, h, t):

    qg = exp(G) q, kd = exp(G_last - G) k, w, u (b*h, t, d), p = the
    masked q k^T (b*h, t, chunk), all in q's type, and the chunk's whole
    decay exp(G_last) spread over a row, (b*h, t / chunk, d_v), in the
    state's type."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    op = q.dtype
    sdt = STATE_DTYPE
    exact = dict(precision=_HIGHEST) if op == jnp.float32 else {}

    def chunks(x):
        return x.reshape((b * h, n, chunk) + x.shape[3:])

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    big = jnp.cumsum(chunks(g).astype(sdt), axis=-1)        # G: (bh, n, c)
    bc = chunks(beta).astype(sdt)
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # exp(G_t - G_s) where s <= t, 0 above the diagonal (and no inf * 0)
    decay = jnp.exp(jnp.where(lower, big[..., :, None] - big[..., None, :],
                              -jnp.inf)).astype(jnp.float32)
    kk = jnp.einsum("bnck,bnsk->bncs", kc, kc,
                    preferred_element_type=jnp.float32, **exact)
    qk = jnp.einsum("bnck,bnsk->bncs", qc, kc,
                    preferred_element_type=jnp.float32, **exact)
    bf = bc.astype(jnp.float32)
    a = jnp.where(idx[:, None] > idx[None, :],
                  bf[..., :, None] * decay * kk, 0.0)
    inv = _unit_lower_inverse(a).astype(op)                  # T
    eg = jnp.exp(big).astype(jnp.float32)                    # exp(G)
    to_last = jnp.exp(big[..., -1:] - big).astype(jnp.float32)
    kf, vf = kc.astype(jnp.float32), vc.astype(jnp.float32)
    u = jnp.einsum("bncs,bnsv->bncv", inv,
                   (bf[..., None] * vf).astype(op),
                   preferred_element_type=jnp.float32, **exact)
    w = jnp.einsum("bncs,bnsk->bnck", inv,
                   ((bf * eg)[..., None] * kf).astype(op),
                   preferred_element_type=jnp.float32, **exact)
    qg = eg[..., None] * qc.astype(jnp.float32)
    kd = to_last[..., None] * kf
    p = decay * qk

    def flat(x):
        return x.astype(op).reshape((b * h, t) + x.shape[3:])

    whole = jnp.broadcast_to(jnp.exp(big[..., -1:]), (b * h, n, dv))
    return flat(qg), flat(kd), flat(w), flat(u), flat(p), whole.astype(sdt)


# ---- what is carried from chunk to chunk: Pallas --------------------------

# Heads and chunks of one grid step: independent heads side by side give
# the scheduler more than one dependent chain, several chunks a step
# spread the step's fixed cost.
_HEADS_PER_STEP = 4
_CHUNKS_PER_STEP = 8


def _largest_divisor(n, limit):
    return max(d for d in range(1, limit + 1) if n % d == 0)


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
    """(heads, chunks) of one grid step. The chunks of a step are the
    second-minor dimension of a block, which the TPU lowering takes in
    eights or whole."""
    chunks = _CHUNKS_PER_STEP if n % _CHUNKS_PER_STEP == 0 else n
    return _largest_divisor(bh, _HEADS_PER_STEP), chunks


def _compiler_params():
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _delta_rule(q, k, v, g, beta, chunk, interpret):
    return _delta_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _delta_fwd(q, k, v, g, beta, chunk, interpret):
    operands = _prepare(q, k, v, g, beta, chunk)
    o, states = _scan_forward(*operands, chunk, interpret)
    return o.reshape(v.shape), (q, k, v, g, beta, states)


def _delta_bwd(chunk, interpret, res, do):
    q, k, v, g, beta, states = res
    # the operands again, and how they depend on the arguments
    operands, pullback = jax.vjp(
        lambda *args: _prepare(*args, chunk), q, k, v, g, beta)
    do = do.astype(q.dtype).reshape(operands[3].shape)
    return pullback(_scan_backward(*operands, states, do, chunk, interpret))


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
        rep = heads // q.shape[1]
        if rep > 1:
            q, k = (jnp.repeat(x, rep, axis=1) for x in (q, k))
        out = _delta_rule(q, k.astype(q.dtype), v.astype(q.dtype), g, beta,
                          int(chunk), bool(interpret))
        return out.astype(v.dtype)

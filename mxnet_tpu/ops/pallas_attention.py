"""Pallas flash attention — the hand-written TPU kernel for the hot op.

No reference counterpart (the reference's attention lives in fused RNN /
example transformer code on cuDNN); this is the TPU-first flagship
kernel: exact attention computed blockwise in VMEM with an online
softmax, so the (Tq, Tk) score matrix never materializes in HBM. Grid =
(batch*heads, q-blocks, k-blocks); the k dimension iterates innermost,
carrying running max / denominator / accumulator in VMEM scratch that
persists across k steps (the standard FlashAttention recurrence on the
MXU).

`flash_attention` runs the kernel compiled on TPU and in interpret mode
elsewhere (cpu tests). The backward is flash too (VERDICT r4 #5): a
custom_vjp saving only (q, k, v, out, logsumexp) — O(T·d) residuals —
and Pallas kernels that REGENERATE probability blocks from the saved
logsumexp (FlashAttention-2 backward), with the causal block-skip. Peak
memory stays O(T·d) where the old re-derived
`jax.vjp(blockwise_attention)` backward stored O(T²) of per-block
probabilities across scan steps.

One backward kernel, `mx_flash_bwd`, where it fits: grid (batch*heads,
k-blocks, q-blocks), q innermost; per block pair the scores,
probabilities, dP and dS are formed once (transposed, (block_k,
block_q)) and feed all three gradients — dK and dV accumulate over the
inner axis, dQ in a whole head's fp32 accumulator that stays in VMEM
across both inner axes: five score-sized products a pair. A head whose
(tq, d_qk) fp32 accumulator passes `FUSED_DQ_BYTES` takes two kernels
instead, each holding one block of each operand: a dK/dV pass
(`mx_flash_bwd_dkv`, q-blocks innermost) and a dQ pass
(`mx_flash_bwd_dq`, k-blocks innermost), which form scores and dS once
each, seven products a pair. `_flash_backward` chooses from the shapes,
and `mx_flash_attention_bwd_traced_total{path}` counts which.

Head widths: q and k share `d_qk`, v and the output have `d_v`, and the
two may differ (latent attention: 192 and 128). Each width is the whole
last dimension of its block, so any width the TPU lowering tiles is
accepted (multiples of 8 seen compiled: 64, 128, 192; 192 is not padded
to 256 in HBM); `scale` defaults to `d_qk ** -0.5`. Sequence lengths must
divide by the block sizes. Block sizes left at None take the defaults
below, chosen from sweeps on a TPU v5e at (1, 32, 4096, 192/128), causal
(PERF.md, PRs 28 and 29); a block longer than the sequence is cut to it.

Grouped queries: k and v may have fewer heads than q, a divisor; query
head h then reads key/value head ``h // group``. The forward and the dQ
kernel index their K and V blocks so; the dK/dV kernel walks the q-blocks
of all the group's heads in its inner axis; the fused backward of a group
(`_bwd_group_kernel`, the same tile under the same name `mx_flash_bwd`)
puts the group's heads in a grid axis of their own between the key/value
head and the k-blocks, with one key/value head's dK and dV in fp32 in
VMEM across it, so dK and dV come out per key/value head and no
per-query-head copy of them exists. At a group of one every kernel is
what it was.

Sliding window: with `window` = W a query at position i sees the keys
``i - W < j <= i`` (W keys, its own among them). The forward and the
fused backward (of one head or of a group) then run as
`mx_flash_swa_fwd` and `mx_flash_swa_bwd`: the inner grid axis has only
as many steps as one outer block's window can touch (`_inner_blocks`),
the index maps start it at the outer block's first allowed block, and a
block with no allowed pair is neither copied nor computed; every
computed block takes the two-edged mask (a variant that left it off the
blocks no edge crosses read the same times on the chip: PERF.md, PR 37).
The dK/dV and dQ pair takes no window. At `window=None` every kernel
is what it was.

Registered as `_contrib_flash_attention` for `nd`/`sym` access.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .registry import register

__all__ = ["flash_attention"]

_NEG = -1e30


# Row statistics (running max, denominator, logsumexp, delta) live as
# (block_q, 1) columns inside the kernels, where they broadcast against
# the (block_q, block_k) score tile, and as lane-dense (bh, 1, tq) rows
# in HBM: the TPU lowering only takes blocks whose last two dimensions
# are (8, 128)-divisible or the whole array, so a (1, block_q) block of
# a (bh, tq) array is refused, and a (bh, tq, 1) column would be padded
# to 128 lanes. The two helpers below move one block between the forms
# through a full (·, 128) tile, the transpose the hardware has.
_LANES = 128


def _col_to_row(col):
    """(n, 1) -> (1, n)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _row_to_col(row):
    """(1, n) -> (n, 1)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _causal_mask(i, j, block_q, block_k, transposed=False, window=None):
    """q_pos >= k_pos for block (i, j), and q_pos - k_pos < window under
    one; (block_k, block_q) if transposed."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _clamp(x, lo, hi):
    """min(max(x, lo), hi) of a Python int (a grid's extent) or of a
    traced index (inside a kernel or an index map)."""
    if isinstance(x, int):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _inner_blocks(o, block_o, block_i, n_inner, back, ahead):
    """(first, last) of the `n_inner` inner blocks that hold a position
    from `back` before outer block `o`'s first to `ahead` after its last.
    Keys of a q-block under a window W: back W - 1, ahead 0; queries of
    a k-block: back 0, ahead W - 1."""
    first = _clamp(o * block_o - back, 0, n_inner * block_i - 1) // block_i
    last = _clamp(((o + 1) * block_o - 1 + ahead) // block_i, 0, n_inner - 1)
    return first, last


def _windowed_block(o, step, *geometry):
    """The inner block that step `step` of outer block `o` names: its
    first allowed one and on, the last again past it (no copy for a
    block nobody reads)."""
    first, last = _inner_blocks(o, *geometry)
    return jnp.minimum(first + step, last)


def _window_geometry(n_outer, n_inner, block_o, block_i, window, keys):
    """(extent of a grid's inner axis; the keywords of its kernel and
    block specs). Under a window the most inner blocks any outer block
    needs, the inner blocks being a q-block's keys (`keys`) or a
    k-block's queries; at no window the whole axis and no keyword."""
    if window is None:
        return n_inner, {}
    reach = (window - 1, 0) if keys else (0, window - 1)
    spans = (_inner_blocks(o, block_o, block_i, n_inner, *reach)
             for o in range(n_outer))
    return (max(last - first + 1 for first, last in spans),
            dict(window=window, inner_blocks=n_inner))


def _dot(a, b, contract):
    """MXU matmul on the operands' own dtype, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))    # a @ b.T
_NN = ((1,), (0,))    # a @ b
_TN = ((0,), (0,))    # a.T @ b


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, block_q, block_k, window=None, inner_blocks=None):
    import jax.experimental.pallas as pl

    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    step = j
    if window is not None:
        # the inner axis counts from this q-block's first allowed k-block
        first, last_allowed = _inner_blocks(i, block_q, block_k,
                                            inner_blocks, window - 1, 0)
        j = first + step

    def _accumulate():
        q = q_ref[0]                            # (bq, d_qk)
        k = k_ref[0]                            # (bk, d_qk)
        v = v_ref[0]                            # (bk, d_v)
        s = _dot(q, k, _NT) * scale             # (bq, bk) fp32
        if causal:
            mask = _causal_mask(i, j, block_q, block_k, window=window)
            s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]                     # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(v.dtype), v, _NN)

    if window is not None:
        pl.when(j <= last_allowed)(_accumulate)
    elif causal:
        # k-blocks wholly above the diagonal (first key after this
        # q-block's last query) contribute nothing: skip their matmuls
        # (~2x causal throughput, standard FlashAttention pruning).
        pl.when(j * block_k <= (i + 1) * block_q - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # Per-row logsumexp: the single residual the backward needs to
        # regenerate any probability block (FlashAttention-2 eq. 5).
        lse_ref[0] = _col_to_row(m_ref[...] + jnp.log(l))


# (block_q, block_k) of every kernel where the caller names none. From
# two sweeps at (1, 32, 4096, 192/128) bf16 causal on a TPU v5e: the
# forward and the dK/dV and dQ pair (PERF.md, PR 28), the fused backward
# (PERF.md, PR 29), each fastest here.
DEFAULT_BLOCK = (1024, 1024)

# Under a window a block is computed whole where the window's edge
# crosses it, so smaller blocks waste less and cost more steps; from a
# sweep at (1, 32 on 4, 8192, 128), window 1024, on a TPU v5e: the
# fused backward fastest here (3.28 ms; 3.72 at 1024x1024), the forward
# second (2.93; 2.66 at 1024x1024) (PERF.md, PR 37).
DEFAULT_WINDOW_BLOCK = (512, 512)

# The fused backward keeps one head's dQ in VMEM as fp32, (tq, d_qk),
# from the head's first grid step to its last: 3 MiB at 4096 x 192. A
# head whose accumulator is larger than this takes the dK/dV and dQ
# kernels, which hold one block of each operand however long the
# sequence. 16 MiB admits 16k x 256 (16k x 192 is 12 MiB) and leaves,
# of a v5e core's 128 MiB, room for the output block's two buffers and
# the score-sized temporaries under the limit below: compiled for a v5e
# the kernel asks 17.4 MiB at 4096 x 192 and 48.5 MiB at 16k x 256 with
# 1024x1024 blocks, 80.3 MiB with 2048x2048 (the default scoped limit
# is 16 MiB).
FUSED_DQ_BYTES = 16 * 2 ** 20
FUSED_VMEM_LIMIT = 96 * 2 ** 20


def _block_sizes(tq, tk, block_q, block_k):
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(
            "sequence lengths (%d, %d) must divide by blocks (%d, %d); "
            "any block sizes that divide them are accepted, and the head "
            "widths d_qk and d_v are free" % (tq, tk, block_q, block_k))
    return block_q, block_k


def _compiler_params(middle="parallel", **more):
    from jax.experimental.pallas import tpu as pltpu

    # The innermost grid axis carries the accumulators; the other two
    # are independent, but for the fused backward's middle one.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", middle, "arbitrary"), **more)


def _last_k(i, block_q, block_k):
    """The last k-block a causal q-block `i` needs."""
    return ((i + 1) * block_q - 1) // block_k


def _q_of_k_specs(d_qk, d_v, block_q, block_k, causal, group=1,
                  window=None, inner_blocks=None):
    """Block specs of a (bh, q-blocks, k-blocks) grid. Under `causal`
    a skipped step names the block of the last computed one, so that
    no copy is started for a block nobody reads. Query head `b_` reads
    key/value head ``b_ // group``. Under a window the inner axis counts
    from the q-block's first allowed k-block (`_inner_blocks`)."""
    import jax.experimental.pallas as pl

    def kj(i, j):
        if window is not None:
            return _windowed_block(i, j, block_q, block_k, inner_blocks,
                                   window - 1, 0)
        return jnp.minimum(j, _last_k(i, block_q, block_k)) if causal else j

    def kv(b_):
        return b_ if group == 1 else b_ // group

    q = lambda d: pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0))
    k = lambda d: pl.BlockSpec((1, block_k, d),
                               lambda b_, i, j: (kv(b_), kj(i, j), 0))
    rowq = pl.BlockSpec((1, 1, block_q), lambda b_, i, j: (b_, 0, i))
    return q(d_qk), q(d_v), k(d_qk), k(d_v), rowq


def _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret,
                   window=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d_qk = q.shape
    tk, d_v = k.shape[2], v.shape[3]
    block_q, block_k = _block_sizes(tq, tk, block_q, block_k)
    bh = b * h
    q3 = q.reshape(bh, tq, d_qk)
    k3 = k.reshape(b * k.shape[1], tk, d_qk)
    v3 = v.reshape(b * k.shape[1], tk, d_v)

    k_steps, geometry = _window_geometry(
        tq // block_q, tk // block_k, block_q, block_k, window, keys=True)
    q_qk, q_v, k_qk, k_v, rowq = _q_of_k_specs(
        d_qk, d_v, block_q, block_k, causal, h // k.shape[1], **geometry)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, **geometry),
        out_shape=(jax.ShapeDtypeStruct((bh, tq, d_v), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32)),
        grid=(bh, tq // block_q, k_steps),
        in_specs=[q_qk, k_qk, k_v],
        out_specs=(q_v, rowq),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mx_flash_fwd" if window is None else "mx_flash_swa_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, tq, d_v), lse.reshape(b, h, tq)


def _transposed_tile(q, k, v, do, lse_row, dlt_row, i, j, *, scale, causal,
                     block_q, block_k, window=None):
    """(P^T, dS^T) of block pair (i, j), both (block_k, block_q) fp32:
    the scores regenerated from q and k with the saved logsumexp row,
    dP^T from v and dO, dS^T = P^T (dP^T - delta) scale."""
    st = _dot(k, q, _NT) * scale              # (bk, bq) = S^T
    if causal:
        st = jnp.where(_causal_mask(i, j, block_q, block_k,
                                    transposed=True, window=window),
                       st, _NEG)
    pt = jnp.exp(st - lse_row)                # exact probabilities, P^T
    dpt = _dot(v, do, _NT)                    # (bk, bq) = dP^T
    return pt, pt * (dpt - dlt_row) * scale


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, block_q, block_k, q_blocks=None):
    """dK/dV pass: grid (bh, k-blocks, q-blocks); the q dimension
    iterates innermost, accumulating this k-block's gradients in VMEM.
    With grouped queries the inner axis walks the `q_blocks` blocks of
    each of the group's heads in turn.
    Probabilities are REGENERATED from q/k + the saved logsumexp — no
    O(T²) residual ever exists (the whole point of a flash backward).
    The block is formed TRANSPOSED, (block_k, block_q): the saved rows
    then broadcast as they are stored and both accumulations are plain
    matmuls (FA2 eqs on S^T)."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)                      # k block (outer)
    step = pl.program_id(2)                   # q block (inner)
    last = pl.num_programs(2) - 1
    i = step if q_blocks is None else step % q_blocks   # within its head

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        q, do = q_ref[0], do_ref[0]           # (bq, d_qk), (bq, d_v)
        pt, dst = _transposed_tile(
            q, k_ref[0], v_ref[0], do, lse_ref[0], dlt_ref[0], i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        dv_acc[...] += _dot(pt.astype(do.dtype), do, _NN)     # (bk, d_v)
        dk_acc[...] += _dot(dst.astype(q.dtype), q, _NN)      # (bk, d_qk)

    if causal:
        # q-blocks entirely above the diagonal see zero probability
        # mass for this k-block: skip them (mirrors the forward's skip).
        pl.when((i + 1) * block_q - 1 >= j * block_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == last)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                   dq_ref, dq_acc, lse_col, dlt_col, *, scale, causal,
                   block_q, block_k):
    """dQ pass: grid (bh, q-blocks, k-blocks), k innermost."""
    import jax.experimental.pallas as pl

    i = pl.program_id(1)                      # q block (outer)
    j = pl.program_id(2)                      # k block (inner)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        # The saved rows as columns, once per q-block.
        lse_col[...] = _row_to_col(lse_ref[0])
        dlt_col[...] = _row_to_col(dlt_ref[0])

    def _accumulate():
        q = q_ref[0]                          # (bq, d_qk)
        k = k_ref[0]                          # (bk, d_qk)
        v = v_ref[0]                          # (bk, d_v)
        do = do_ref[0]                        # (bq, d_v)
        s = _dot(q, k, _NT) * scale           # (bq, bk)
        if causal:
            s = jnp.where(_causal_mask(i, j, block_q, block_k), s, _NEG)
        p = jnp.exp(s - lse_col[...])         # exact probabilities
        dp = _dot(do, v, _NT)                 # (bq, bk)
        ds = p * (dp - dlt_col[...]) * scale
        dq_acc[...] += _dot(ds.astype(k.dtype), k, _NN)       # (bq, d_qk)

    if causal:
        pl.when(j * block_k <= (i + 1) * block_q - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _q_blocks_of_k(j, step, block_q, block_k, window, q_blocks, k_blocks):
    """A windowed backward's step `step` at k-block `j`: (i, the q-block
    it names; whether that block is one of `j`'s; the first and the last
    k-block of q-block i, where its dQ rows start and end)."""
    first, last = _inner_blocks(j, block_k, block_q, q_blocks, 0, window - 1)
    i = jnp.minimum(first + step, q_blocks - 1)
    return (i, first + step <= last) + _inner_blocks(
        i, block_q, block_k, k_blocks, window - 1, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc, *,
                scale, causal, block_q, block_k, window=None,
                inner_blocks=None):
    """The whole backward in one pass: the dK/dV kernel's grid and
    body, and dQ out of the same dS^T tile. `dq_acc` holds the whole
    head's dQ in fp32 across both inner grid axes; a q-block's rows
    are zeroed at the first k-block, added to at every computed pair
    (k-blocks ascending, the dQ kernel's order) and written out at the
    last. Under a window the inner axis counts from the k-block's first
    allowed q-block, and a q-block's first and last k-blocks are its
    window's."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)                      # k block (outer)
    i = pl.program_id(2)                      # q block (inner)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)
    step = i
    if window is not None:
        i, computed, first_k, last_k = _q_blocks_of_k(
            j, step, block_q, block_k, window, inner_blocks, nk)
    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0 if window is None else computed & (j == first_k))
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    def _accumulate():
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        pt, dst = _transposed_tile(
            q, k, v_ref[0], do, lse_ref[0], dlt_ref[0], i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dst = dst.astype(q.dtype)
        dv_acc[...] += _dot(pt.astype(do.dtype), do, _NN)     # (bk, d_v)
        dk_acc[...] += _dot(dst, q, _NN)                      # (bk, d_qk)
        dq_acc[rows, :] += _dot(dst, k, _TN)                  # (bq, d_qk)

    if window is not None:
        pl.when(computed)(_accumulate)
    elif causal:
        pl.when((i + 1) * block_q - 1 >= j * block_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(j == nk - 1 if window is None else computed & (j == last_k))
    def _finalize_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _bwd_group_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                      dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc, *,
                      scale, causal, block_q, block_k, window=None,
                      inner_blocks=None):
    """`_bwd_kernel` for grouped queries: grid (key/value heads, group,
    k-blocks, q-blocks). One query head's dQ stays in `dq_acc` across
    the two inner axes, as there; one key/value head's dK and dV stay,
    whole and in fp32, in `dk_acc` and `dv_acc` across the three, each
    k-block's rows zeroed at the group's first head and written out at
    its last."""
    import jax.experimental.pallas as pl

    g = pl.program_id(1)                      # query head of the group
    j = pl.program_id(2)                      # k block
    i = pl.program_id(3)                      # q block (inner)
    ng, nk, nq = (pl.num_programs(a) for a in (1, 2, 3))
    step = i
    if window is not None:
        i, computed, first_k, last_k = _q_blocks_of_k(
            j, step, block_q, block_k, window, inner_blocks, nk)
    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
    krows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    @pl.when((g == 0) & (step == 0))
    def _init():
        dk_acc[krows, :] = jnp.zeros((block_k, dk_acc.shape[1]), jnp.float32)
        dv_acc[krows, :] = jnp.zeros((block_k, dv_acc.shape[1]), jnp.float32)

    @pl.when(j == 0 if window is None else computed & (j == first_k))
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    def _accumulate():
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        pt, dst = _transposed_tile(
            q, k, v_ref[0], do, lse_ref[0], dlt_ref[0], i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dst = dst.astype(q.dtype)
        dv_acc[krows, :] += _dot(pt.astype(do.dtype), do, _NN)
        dk_acc[krows, :] += _dot(dst, q, _NN)
        dq_acc[rows, :] += _dot(dst, k, _TN)

    if window is not None:
        pl.when(computed)(_accumulate)
    elif causal:
        pl.when((i + 1) * block_q - 1 >= j * block_k)(_accumulate)
    else:
        _accumulate()

    @pl.when((g == ng - 1) & (step == nq - 1))
    def _finalize():
        dk_ref[0, krows, :] = dk_acc[krows, :].astype(dk_ref.dtype)
        dv_ref[0, krows, :] = dv_acc[krows, :].astype(dv_ref.dtype)

    @pl.when(j == nk - 1 if window is None else computed & (j == last_k))
    def _finalize_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _k_of_q_specs(d_qk, d_v, block_q, block_k, causal, q_blocks=None,
                  window=None, inner_blocks=None):
    """Block specs of a (bh, k-blocks, q-blocks) grid. Under `causal`
    the q-blocks before the first computed one name that one: no copy
    for a block nobody reads. With grouped queries the q operands are
    seen as (key/value heads, group * tq, d) and the inner axis runs
    over the `q_blocks` blocks of each head of the group in turn. Under
    a window (one head a grid row, `inner_blocks` its q-blocks) the
    inner axis counts from the k-block's first allowed q-block."""
    import jax.experimental.pallas as pl

    def qi(j, i):
        if window is not None:
            return _windowed_block(j, i, block_k, block_q, inner_blocks, 0,
                                   window - 1)
        if not causal:
            return i
        first = (j * block_k) // block_q
        if q_blocks is None:
            return jnp.maximum(i, first)
        return i - i % q_blocks + jnp.maximum(i % q_blocks, first)

    q = lambda d: pl.BlockSpec((1, block_q, d),
                               lambda b_, j, i: (b_, qi(j, i), 0))
    k = lambda d: pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0))
    rowq = pl.BlockSpec((1, 1, block_q), lambda b_, j, i: (b_, 0, qi(j, i)))
    return q(d_qk), q(d_v), k(d_qk), k(d_v), rowq


def _flash_dkv(q3, k3, v3, do3, lse3, delta, scale, causal, block_q,
               block_k, interpret):
    """The dK/dV pallas_call on (bh, t, d_qk | d_v) operands and
    (bh, 1, tq) row statistics; k3 and v3 may have a divisor of the
    other operands' heads, and dK and dV have theirs."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tq, d_qk = q3.shape[1:]
    bh, tk, d_v = v3.shape
    group = q3.shape[0] // bh
    q_blocks = None
    if group > 1:
        # a key/value head's query heads end to end along the rows
        q_blocks = tq // block_q
        q3, do3 = (a.reshape(bh, group * tq, a.shape[2]) for a in (q3, do3))
        lse3, delta = (a.reshape(bh, 1, group * tq) for a in (lse3, delta))
    q_qk, q_v, k_qk, k_v, rowq = _k_of_q_specs(d_qk, d_v, block_q, block_k,
                                               causal, q_blocks)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_blocks=q_blocks),
        out_shape=(jax.ShapeDtypeStruct((bh, tk, d_qk), k3.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d_v), v3.dtype)),
        grid=(bh, tk // block_k, group * tq // block_q),
        in_specs=[q_qk, k_qk, k_v, q_v, rowq, rowq],
        out_specs=(k_qk, k_v),
        scratch_shapes=[pltpu.VMEM((block_k, d_qk), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mx_flash_bwd_dkv",
    )(q3, k3, v3, do3, lse3, delta)


def _flash_dq(q3, k3, v3, do3, lse3, delta, scale, causal, block_q,
              block_k, interpret):
    """The dQ pallas_call, same operands as :func:`_flash_dkv`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d_qk = q3.shape
    d_v = v3.shape[2]
    q_qk, q_v, k_qk, k_v, rowq = _q_of_k_specs(
        d_qk, d_v, block_q, block_k, causal, bh // k3.shape[0])
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d_qk), q3.dtype),
        grid=(bh, tq // block_q, k3.shape[1] // block_k),
        in_specs=[q_qk, k_qk, k_v, q_v, rowq, rowq],
        out_specs=q_qk,
        scratch_shapes=[pltpu.VMEM((block_q, d_qk), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mx_flash_bwd_dq",
    )(q3, k3, v3, do3, lse3, delta)


def _flash_bwd_fused(q3, k3, v3, do3, lse3, delta, scale, causal, block_q,
                     block_k, interpret, window=None):
    """dK, dV and dQ from one pallas_call, same operands as
    :func:`_flash_dkv`; returns (dk, dv, dq)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d_qk = q3.shape
    tk, d_v = k3.shape[1], v3.shape[2]
    if bh != k3.shape[0]:
        return _flash_bwd_group(q3, k3, v3, do3, lse3, delta, scale, causal,
                                block_q, block_k, interpret, window)
    q_steps, geometry = _window_geometry(
        tk // block_k, tq // block_q, block_k, block_q, window, keys=False)
    q_qk, q_v, k_qk, k_v, rowq = _k_of_q_specs(d_qk, d_v, block_q, block_k,
                                               causal, **geometry)
    # One head's dQ: the same block at every step of a head, so it is
    # written back once, when the head changes.
    head = pl.BlockSpec((1, tq, d_qk), lambda b_, j, i: (b_, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, **geometry),
        out_shape=(jax.ShapeDtypeStruct((bh, tk, d_qk), k3.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d_v), v3.dtype),
                   jax.ShapeDtypeStruct((bh, tq, d_qk), q3.dtype)),
        grid=(bh, tk // block_k, q_steps),
        in_specs=[q_qk, k_qk, k_v, q_v, rowq, rowq],
        out_specs=(k_qk, k_v, head),
        scratch_shapes=[pltpu.VMEM((block_k, d_qk), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32),
                        pltpu.VMEM((tq, d_qk), jnp.float32)],
        # dQ accumulates across the k-blocks too: only heads are
        # independent.
        compiler_params=_compiler_params(
            "arbitrary", vmem_limit_bytes=FUSED_VMEM_LIMIT),
        interpret=interpret,
        name="mx_flash_bwd" if window is None else "mx_flash_swa_bwd",
    )(q3, k3, v3, do3, lse3, delta)


def _flash_bwd_group(q3, k3, v3, do3, lse3, delta, scale, causal, block_q,
                     block_k, interpret, window=None):
    """The fused backward where k3 and v3 have a divisor of q3's heads:
    (dk, dv, dq), dk and dv per key/value head."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tq, d_qk = q3.shape[1:]
    bh, tk, d_v = v3.shape
    group = q3.shape[0] // bh
    q_steps, geometry = _window_geometry(
        tk // block_k, tq // block_q, block_k, block_q, window, keys=False)

    def qi(j, i):
        if window is not None:
            return _windowed_block(j, i, block_k, block_q, tq // block_q, 0,
                                   window - 1)
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    q = lambda d: pl.BlockSpec(
        (1, block_q, d), lambda b_, g, j, i: (b_ * group + g, qi(j, i), 0))
    k = lambda d: pl.BlockSpec((1, block_k, d),
                               lambda b_, g, j, i: (b_, j, 0))
    rowq = pl.BlockSpec(
        (1, 1, block_q), lambda b_, g, j, i: (b_ * group + g, 0, qi(j, i)))
    # whole heads: written back when the head they belong to changes
    whole = lambda t, d, head: pl.BlockSpec(
        (1, t, d), lambda b_, g, j, i: (head(b_, g), 0, 0))
    kv_head = lambda b_, g: b_
    return pl.pallas_call(
        functools.partial(_bwd_group_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, **geometry),
        out_shape=(jax.ShapeDtypeStruct((bh, tk, d_qk), k3.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d_v), v3.dtype),
                   jax.ShapeDtypeStruct(q3.shape, q3.dtype)),
        grid=(bh, group, tk // block_k, q_steps),
        in_specs=[q(d_qk), k(d_qk), k(d_v), q(d_v), rowq, rowq],
        out_specs=(whole(tk, d_qk, kv_head), whole(tk, d_v, kv_head),
                   whole(tq, d_qk, lambda b_, g: b_ * group + g)),
        scratch_shapes=[pltpu.VMEM((tk, d_qk), jnp.float32),
                        pltpu.VMEM((tk, d_v), jnp.float32),
                        pltpu.VMEM((tq, d_qk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=FUSED_VMEM_LIMIT),
        interpret=interpret,
        name="mx_flash_bwd" if window is None else "mx_flash_swa_bwd",
    )(q3, k3, v3, do3, lse3, delta)


def _bwd_path(tq, d_qk, tk=0, d_v=0, group=1):
    """Which backward a call takes, from its shapes alone: the fused
    one where its whole-head fp32 accumulators (dQ; with grouped
    queries dK and dV too) are within `FUSED_DQ_BYTES`."""
    held = tq * d_qk if group == 1 else tq * d_qk + tk * (d_qk + d_v)
    return "fused" if held * 4 <= FUSED_DQ_BYTES else "split"


def _flash_backward(q, k, v, out, lse, g, scale, causal, block_q,
                    block_k, interpret, window=None):
    b, h, tq, d_qk = q.shape
    block_q, block_k = _block_sizes(tq, k.shape[2], block_q, block_k)
    bh = b * h
    # delta_i = rowsum(dO_i * O_i) — O(T·d), fused by XLA.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)
    operands = tuple(a.reshape(b * a.shape[1], a.shape[2], a.shape[3])
                     for a in (q, k, v, g)) + (lse.reshape(bh, 1, tq), delta)
    static = (scale, causal, block_q, block_k, interpret)
    path = _bwd_path(tq, d_qk, k.shape[2], v.shape[3], h // k.shape[1])
    _flash_bwd_traced.labels(path=path).inc()
    if path == "fused":
        dk, dv, dq = _flash_bwd_fused(*operands, *static, window)
    elif window is not None:
        raise ValueError(
            "a window of %d on heads of (%d, %d): the dK/dV and dQ pair, "
            "which heads over FUSED_DQ_BYTES take, has no window"
            % (window, tq, d_qk))
    else:
        dk, dv = _flash_dkv(*operands, *static)
        dq = _flash_dq(*operands, *static)
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, blocks, interpret, window):
    out, _ = _flash_forward(q, k, v, scale, causal, *blocks, interpret,
                            window)
    return out


def _flash_fwd(q, k, v, scale, causal, blocks, interpret, window):
    out, lse = _flash_forward(q, k, v, scale, causal, *blocks, interpret,
                              window)
    # Residuals are O(T·d) (q/k/v/out) + O(T) (lse) — never O(T²).
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, blocks, interpret, window, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, scale, causal, *blocks,
                           interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)

_flash_bwd_traced = _tm.REGISTRY.counter(
    "mx_flash_attention_bwd_traced_total",
    "flash_attention backward passes traced into a program, by the "
    "kernels taken: one fused pass, or the dK/dV and dQ pair",
    labels=("path",))

_flash_traced = _tm.REGISTRY.counter(
    "mx_flash_attention_traced_total",
    "flash_attention calls traced into a program, by head widths",
    labels=("d_qk", "d_v"))

_flash_group_traced = _tm.REGISTRY.counter(
    "mx_flash_attention_group_traced_total",
    "flash_attention calls traced into a program, by query heads a "
    "key/value head",
    labels=("group",))


_flash_window_traced = _tm.REGISTRY.counter(
    "mx_flash_attention_window_traced_total",
    "flash_attention calls traced into a program, by the sliding window "
    "in keys; none: every earlier key",
    labels=("window",))


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, window=None):
    """Blockwise exact attention as one Pallas kernel.

    q: (batch, heads, seq, d_qk); k: (batch, kv_heads, seq, d_qk); v:
    (batch, kv_heads, seq, d_v), `kv_heads` a divisor of `heads` (query
    head h reads key/value head ``h // (heads / kv_heads)``); the
    result has q's heads and v's width. `scale` defaults to
    ``d_qk ** -0.5``. `window`: with `causal`, the query at position i
    sees the `window` keys ``i - window < j <= i`` (its own among them),
    and blocks with no such pair are skipped forward and backward; a
    window of at least the sequence is no window.
    `block_q`/`block_k` apply to the forward and the backward kernels;
    left at None each takes its default (`DEFAULT_BLOCK`, or
    `DEFAULT_WINDOW_BLOCK` under a window). On
    non-TPU backends the kernel runs in interpret mode (functional, for
    tests); pass `interpret` explicitly to override.
    """
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu",)
    if q.shape[-1] != k.shape[-1]:
        raise ValueError("q and k differ in width: %d, %d"
                         % (q.shape[-1], k.shape[-1]))
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError("%d query heads on %d key and %d value heads"
                         % (q.shape[1], k.shape[1], v.shape[1]))
    if window is not None:
        window = int(window)
        if not causal or window < 1 or q.shape[2] != k.shape[2]:
            raise ValueError(
                "a window (%d) is of at least one key, under causal=True, "
                "on queries and keys of one length" % window)
        if window >= k.shape[2]:
            window = None
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    blocks = tuple(int(given or default) for given, default
                   in zip((block_q, block_k),
                          DEFAULT_BLOCK if window is None
                          else DEFAULT_WINDOW_BLOCK))
    _flash_traced.labels(d_qk=str(q.shape[-1]), d_v=str(v.shape[-1])).inc()
    _flash_group_traced.labels(group=str(q.shape[1] // k.shape[1])).inc()
    _flash_window_traced.labels(window=str(window).lower()).inc()
    return _flash(q, k, v, float(scale), bool(causal), blocks,
                  bool(interpret), window)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None, window=None):
    """Exact attention of q (batch, heads, seq, d_qk), k (batch,
    kv_heads, seq, d_qk) and v (batch, kv_heads, seq, d_v), d_qk and d_v
    free of each other, kv_heads a divisor of heads; result (batch,
    heads, seq_q, d_v); `scale` defaults to ``d_qk ** -0.5``; `window`:
    under `causal`, the keys ``i - window < j <= i`` alone."""
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k, window=window)

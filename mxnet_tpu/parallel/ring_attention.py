"""Ring attention — sequence/context parallelism for long sequences.

The reference has no long-context story beyond bucketing (SURVEY.md
§5.7); this is TPU-first capability: shard the SEQUENCE axis over a
mesh axis ('sp') and compute exact attention with K/V blocks rotating
around the ring via `lax.ppermute` (Liu et al., Ring Attention;
blockwise online-softmax accumulation as in FlashAttention). Peak
memory per chip is O(T/n · T/n) score blocks instead of O(T·T), and
each rotation's collective overlaps the next block's compute on the
ICI — XLA pipelines the permute against the einsums.

Public entry points:
- `ring_attention(q, k, v, axis_name, causal)`: call INSIDE shard_map /
  a sharded jit where the sequence axis is split over `axis_name`.
- `ring_self_attention(mesh, q, k, v, causal)`: convenience wrapper
  that shard_maps over (dp, sp) for you and returns the gathered
  result.
- `blockwise_attention(q, k, v, block, causal)`: the same online-
  softmax math on ONE device (memory-tiled exact attention) — the
  single-chip long-context fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ring_self_attention",
           "blockwise_attention"]

_NEG = -1e30


def _accumulate_block(q, k, v, scale, m, l, acc, mask=None):
    """One online-softmax accumulation step (numerically stable).

    q: (..., Tq, D); k/v: (..., Tk, D); m/l: (..., Tq); acc like q.
    mask (..., Tq, Tk) True = attend. Fully-masked rows stay at their
    running (m, l, acc) — masked probabilities are zeroed explicitly,
    so no spurious exp(0) mass leaks in.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = p * mask
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Exact attention over a sequence sharded on `axis_name`.

    Call inside shard_map (or an equivalently sharded jit): q, k, v are
    the LOCAL sequence blocks, shape (batch, heads, T_local, head_dim).
    K/V travel the ring; after n-1 rotations every Q block has attended
    to the full sequence. Returns the local output block.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5

    q32 = q.astype(jnp.float32)
    # initial carries derive from q so they carry the same
    # varying-manual-axes type as the loop outputs (shard_map scan
    # requires matching vma annotations)
    m0 = q32.sum(axis=-1) * 0.0 + _NEG
    l0 = q32.sum(axis=-1) * 0.0
    acc0 = q32 * 0.0
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = idx * t_local + jnp.arange(t_local)

    def accum(step, m, l, acc, kb, vb):
        # at `step`, this device holds the block that originated on
        # ring neighbour src = (idx - step) mod n
        src = (idx - step) % n
        if not causal:
            return _accumulate_block(q32, kb.astype(jnp.float32),
                                     vb.astype(jnp.float32), scale,
                                     m, l, acc)

        def attend(args):
            m_, l_, acc_ = args
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = jnp.broadcast_to(mask, q.shape[:-2] + mask.shape)
            return _accumulate_block(q32, kb.astype(jnp.float32),
                                     vb.astype(jnp.float32), scale,
                                     m_, l_, acc_, mask)

        # blocks wholly in this device's future (src > idx) would be
        # all-masked: skip their einsums entirely (~2x causal FLOPs)
        return lax.cond(src <= idx, attend, lambda args: args,
                        (m, l, acc))

    def body(step, carry):
        m, l, acc, kb, vb = carry
        m, l, acc = accum(step, m, l, acc, kb, vb)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return m, l, acc, kb, vb

    # n-1 rotated steps; the last held block is accumulated OUTSIDE the
    # loop so its (discarded) rotation is never issued on the ring.
    m, l, acc, kb, vb = lax.fori_loop(0, n - 1, body,
                                      (m0, l0, acc0, k, v))
    m, l, acc = accum(n - 1, m, l, acc, kb, vb)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(mesh, q, k, v, causal=False, scale=None,
                        sp_axis="sp", dp_axis="dp"):
    """shard_map convenience wrapper: shards batch over `dp_axis` (if
    present in the mesh) and sequence over `sp_axis`, runs
    `ring_attention`, returns the assembled global result."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    dp = dp_axis if dp_axis in mesh.axis_names else None
    spec = P(dp, None, sp_axis, None)           # (B, H, T, D)

    fn = shard_map(
        functools.partial(ring_attention, axis_name=sp_axis,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def blockwise_attention(q, k, v, block=128, causal=False, scale=None):
    """Memory-tiled exact attention on one device: the same online-
    softmax accumulation scanned over K/V blocks. Handles sequences
    whose full score matrix would not fit in HBM."""
    b, h, tq, d = q.shape
    tk = k.shape[2]                     # cross-attention: tk may != tq
    scale = scale if scale is not None else d ** -0.5
    block = min(block, tk)
    if tk % block:
        raise ValueError("sequence length %d not divisible by block %d"
                         % (tk, block))
    nb = tk // block
    kb = k.astype(jnp.float32).reshape(b, h, nb, block, d)
    vb = v.astype(jnp.float32).reshape(b, h, nb, block, d)
    q32 = q.astype(jnp.float32)

    m0 = jnp.full((b, h, tq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros(q32.shape, jnp.float32)
    q_pos = jnp.arange(tq)

    def body(carry, inputs):
        m, l, acc = carry
        j, kj, vj = inputs
        mask = None
        if causal:
            k_pos = j * block + jnp.arange(block)
            mask = jnp.broadcast_to(q_pos[:, None] >= k_pos[None, :],
                                    (b, h, tq, block))
        m, l, acc = _accumulate_block(q32, kj, vj, scale, m, l, acc, mask)
        return (m, l, acc), None

    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0),
        (jnp.arange(nb), jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)

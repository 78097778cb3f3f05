"""The pieces of a decoder block that no other operator computes:
RMSNorm (plain, zero-centred, and gated per head), rotary position
embedding on interleaved pairs or halves with plain or YaRN-scaled
frequencies, the SiLU-gated MLP, the
`noaux_tc` router of the DeepSeek-V3 family and the softmax top-k router
of the `qwen3_next` family. Plain XLA ops; the attention core is
`pallas_attention.flash_attention`, the linear-attention core
`linear_attention.gated_delta_rule` and the held experts' product
`moe.moe_held_experts`.

Weights are laid out (out, in), as `FullyConnected`'s.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from .registry import register

__all__ = ["rms_norm", "gated_rms_norm", "rotary_embedding", "gated_mlp",
           "noaux_tc_router", "softmax_topk_router"]


@register("_contrib_RMSNorm", aliases=("RMSNorm",))
def rms_norm(data, gamma, eps=1e-6, zero_centered=False):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis; the
    statistics and the scaling in fp32, the result in `data`'s type.
    `zero_centered`: the scale is ``1 + gamma`` (a weight that starts
    at 0)."""
    x = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    scale = gamma.astype(jnp.float32)
    if zero_centered:
        scale = 1.0 + scale
    return (x * inv * scale).astype(data.dtype)


@register("_contrib_gated_rms_norm", aliases=("gated_rms_norm",))
def gated_rms_norm(data, gate, gamma, eps=1e-6):
    """``gamma * x / sqrt(mean(x^2) + eps) * silu(gate)`` over the last
    axis (a head's width), in fp32; the result in `data`'s type."""
    x = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    out = x * inv * gamma.astype(jnp.float32)
    return (out * jax.nn.silu(gate.astype(jnp.float32))).astype(data.dtype)


_rotary_traced = _tm.REGISTRY.counter(
    "mx_rotary_embedding_traced_total",
    "rotary_embedding calls traced into a program, by pairing",
    labels=("interleaved",))


def _pairs_apart(d, dtype):
    """The (d, d) 0/1 matrix that sends column 2i to i and 2i+1 to
    i + d/2."""
    return jnp.asarray(np.eye(d, dtype=np.float32)[:, np.r_[0:d:2, 1:d:2]],
                       dtype)


_rotary_scaling_traced = _tm.REGISTRY.counter(
    "mx_rotary_embedding_scaling_traced_total",
    "rotary_embedding calls traced into a program, by the scaling of "
    "their frequencies",
    labels=("scaling",))


def _yarn_ramp(d, theta, original_max_position, beta_fast, beta_slow):
    """YaRN's blend per pair, (d / 2,): 0 where a pair turns more than
    `beta_fast` times over the original length (its frequency stays), 1
    where fewer than `beta_slow` (its frequency is divided by the
    factor), linear between; the two correction dims truncated."""
    def correction_dim(rotations):
        return d * np.log(original_max_position / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    return np.clip((np.arange(d // 2) - low) / (high - low), 0, 1).astype(
        np.float32)


@register("_contrib_rotary_embedding", aliases=("rotary_embedding",))
def rotary_embedding(data, theta=10000.0, interleaved=True,
                     scaling_factor=None, original_max_position=None,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None):
    """Rotary embedding of `data` (..., seq, d) at positions 0..seq-1.

    `interleaved`: pair i is (x[2i], x[2i+1]) and turns by
    ``pos * theta ** (-2i/d)``; its two results come back at i and
    i + d/2 (the published `rope_interleave` path leaves them there: a
    fixed permutation of the width, the same for q and k, so every
    q.k is that of the interleaved result). Otherwise pair i is
    (x[i], x[i + d/2]). Angles and products in fp32.

    `scaling_factor` with `original_max_position`: YaRN. Pair i's
    frequency is ``theta ** (-2i/d)`` where it turns more than
    `beta_fast` times over the original length, that over the factor
    where fewer than `beta_slow`, a linear blend between (`_yarn_ramp`),
    at every length; cos and sin are both multiplied by
    `attention_factor` (``0.1 ln(factor) + 1`` where None), so every
    q.k is by its square.

    The interleaved pairs are parted by a product with a constant 0/1
    matrix, accumulated in fp32: every output is one input times 1.0, so
    the values are those of ``x[..., 0::2]`` and ``x[..., 1::2]`` to the
    bit, and nothing is gathered forward or scattered backward (a
    stride-2 pick of the minor axis is no lane operation on the TPU).
    That holds for finite input: as through any product, an Inf or NaN
    spreads over its row (`x * 0`), and a -0.0 may come back as 0.0."""
    _rotary_traced.labels(interleaved=str(bool(interleaved)).lower()).inc()
    _rotary_scaling_traced.labels(
        scaling="none" if scaling_factor is None else "yarn").inc()
    with jax.named_scope("rotary_embedding"):
        d = data.shape[-1]
        seq = data.shape[-2]
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        if scaling_factor is not None:
            ramp = _yarn_ramp(d, theta, original_max_position, beta_fast,
                              beta_slow)
            inv_freq = inv_freq / jnp.float32(scaling_factor) * ramp \
                + inv_freq * (1 - ramp)
        angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
            * inv_freq[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        if scaling_factor is not None:
            factor = jnp.float32(
                0.1 * np.log(scaling_factor) + 1.0
                if attention_factor is None else attention_factor)
            cos, sin = cos * factor, sin * factor
        x = data.astype(jnp.float32)
        if interleaved:
            # a bf16 operand goes in as it is and the product widens it;
            # any other as fp32 in as many passes as keep every bit
            narrow = data.dtype == jnp.bfloat16
            operand = data if narrow else x
            x = jnp.einsum(
                "...k,kj->...j", operand, _pairs_apart(d, operand.dtype),
                precision=None if narrow else jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        # split, not two slices: its transpose is one concatenate
        a, b = jnp.split(x, 2, axis=-1)
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
        return out.astype(data.dtype)


@register("_contrib_gated_mlp", aliases=("gated_mlp",))
def gated_mlp(data, gate_weight, up_weight, down_weight):
    """``down(silu(gate x) * up x)``, weights (out, in)."""
    gate = jnp.einsum("...h,fh->...f", data, gate_weight)
    up = jnp.einsum("...h,fh->...f", data, up_weight)
    return jnp.einsum("...f,hf->...h", jax.nn.silu(gate) * up, down_weight)


def _scores(data, weight):
    """Sigmoid scores (tokens, experts), fp32 at the highest precision."""
    return jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", data.astype(jnp.float32), weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


def _counts(ids, experts):
    """Tokens that picked each expert: (experts,) int32."""
    return jnp.sum(ids[:, :, None] == jnp.arange(experts)[None, None, :],
                   axis=(0, 1), dtype=jnp.int32)


def _group_limited(choice, n_group, topk_group):
    """DeepSeek-V3's node-limited routing: keep the `topk_group` groups
    whose two best scores sum highest, the rest of `choice` to 0."""
    tokens, experts = choice.shape
    grouped = choice.reshape(tokens, n_group, experts // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(group_score, topk_group)
    mask = jnp.zeros((tokens, n_group), bool).at[
        jnp.arange(tokens)[:, None], keep].set(True)
    return jnp.where(mask[:, :, None], grouped, 0.0).reshape(tokens, experts)


@register("_contrib_noaux_tc_router", aliases=("noaux_tc_router",),
          differentiable=True)
def noaux_tc_router(data, weight, bias_steps, top_k=6, gamma=1e-3,
                    routed_scaling_factor=1.0, norm_topk_prob=True,
                    n_group=1, topk_group=1):
    """The `noaux_tc` router: sigmoid scores over all experts, selection
    by score plus bias, weights from the score alone.

    data (tokens, hidden); weight (experts, hidden); `bias_steps`
    (experts,) int32, the selection bias `e_score_correction_bias` in
    whole steps of `gamma` (the published rule moves it by +-gamma a
    step, so the count is exact and no cast can move it). The product
    and the scores are fp32 at the highest matmul precision.

    Returns (weights (tokens, top_k) fp32, ids (tokens, top_k) int32,
    counts (experts,) int32: the tokens that selected each expert).
    """
    with jax.named_scope("moe_route"):
        score = _scores(data, weight)
        bias = bias_steps.astype(jnp.float32) * jnp.float32(gamma)
        choice = jax.lax.stop_gradient(score) + bias
        if n_group > 1:
            choice = _group_limited(choice, n_group, topk_group)
        _, ids = jax.lax.top_k(choice, top_k)
        picked = jnp.take_along_axis(score, ids, axis=-1)
        if norm_topk_prob:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                               + 1e-20)
        return (picked * jnp.float32(routed_scaling_factor),
                ids.astype(jnp.int32), _counts(ids, score.shape[1]))


_softmax_router_traced = _tm.REGISTRY.counter(
    "mx_softmax_router_traced_total",
    "softmax_topk_router calls traced into a program")


@register("_contrib_softmax_topk_router", aliases=("softmax_topk_router",),
          differentiable=True)
def softmax_topk_router(data, weight, top_k=10, norm_topk_prob=True):
    """Softmax over all experts, the `top_k` most probable, their
    probabilities divided by their sum where `norm_topk_prob`. No bias,
    no state.

    data (tokens, hidden); weight (experts, hidden). The product and the
    softmax are fp32 at the highest matmul precision. Returns what
    `noaux_tc_router` returns: (weights (tokens, top_k) fp32, ids
    (tokens, top_k) int32, counts (experts,) int32)."""
    _softmax_router_traced.inc()
    with jax.named_scope("moe_route"):
        prob = jax.nn.softmax(jnp.einsum(
            "th,eh->te", data.astype(jnp.float32),
            weight.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        picked, ids = jax.lax.top_k(prob, top_k)
        if norm_topk_prob:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        return picked, ids.astype(jnp.int32), _counts(ids, prob.shape[1])


@register("_contrib_noaux_tc_bias_update", differentiable=False)
def bias_steps_update(bias_steps, counts):
    """The published rule in whole steps: +1 where an expert was picked
    by fewer tokens than the mean, -1 where by more, 0 at the mean."""
    experts = counts.shape[0]
    total = jnp.sum(counts)
    return bias_steps + jnp.sign(total - counts * experts).astype(
        bias_steps.dtype)

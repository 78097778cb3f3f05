"""Decoder language model of the Mellum 2 family (`model_type`
``mellum``: grouped-query attention in every layer, three over a sliding
window to one over every earlier key, plain rotary frequencies on the
sliding layers and YaRN's on the full ones, per-head RMSNorm on q and k,
a softmax top-k router over routed experts and no shared expert in every
layer), built from the program's Gluon blocks (`gluon.model_zoo.mellum`),
and its plain fp32 reference.

The configuration file holds the published keys. Three of them are one
chip's share of the deployment it states: `num_hidden_layers` (the layers
kept, the first of `layer_types`), `num_experts` (the routed experts held
here; the router keeps the published width, `published.num_experts`) and
`vocab_size` (the rows of the vocabulary held: ids, logits and loss are
over that slice). `deployment.expert_shard` says which run of experts is
held. What the absent experts would add is left out, here and in the
reference alike.

A batch is (N, bptt) int32 token ids and the (N, bptt) next tokens.

Nothing of `mxnet_tpu` is imported at the top: the counts of operations
and bytes load on a program that has no such model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the helpers that do not know the family: a parameter by its name's tail,
# the gated MLP, the judged top-k selection, the loss in blocks, Zipf-like
# ids from the held slice, the held experts' ids
from chipbench.models.qwen3_next import (  # noqa: F401
    _TOLD, _find, _mlp, _selection, held_experts, make_batch,
    reference_loss)


def zoo_config(cfg):
    """The config dict `gluon.model_zoo.mellum` takes: published keys,
    the router at its published width, the held experts by id."""
    return dict(cfg, num_experts=cfg["published"]["num_experts"],
                held_experts=held_experts(cfg))


def is_sliding_layer(cfg, layer):
    return cfg["layer_types"][layer] == "sliding_attention"


def build(cfg, seed):
    """The net from the seed, its large weights drawn on the device
    (`initializer.DeviceNormal`), the token embedding drawn again at
    `embed_initializer_range` (`assumed`: random weights lack a trained
    checkpoint's embedding scale). The softmax router has no bias and
    nothing to calibrate."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, initializer
    from mxnet_tpu.gluon.model_zoo import mellum as zoo

    mx.random.seed(seed)
    net = zoo.mellum(dict(
        zoo_config(cfg),
        weight_initializer=initializer.DeviceNormal(
            cfg["initializer_range"])))
    net.initialize()
    embed = net.embed_tokens.weight
    embed.set_data(mx.nd.random.normal(
        0, cfg["embed_initializer_range"], shape=embed.shape))
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


# ---- what the mathematics needs ------------------------------------------

def attention_pairs(seq, window=None):
    """Query-key pairs one head of `seq` positions needs: every earlier
    key and its own, or the `window` nearest of them. No block rounding:
    sum of min(i + 1, window)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _attention_macs_per_token(cfg, sliding):
    h, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    d = cfg["head_dim"]
    proj = h * heads * d + 2 * h * kv * d + heads * d * h
    pairs = attention_pairs(cfg["bptt"],
                            cfg["sliding_window"] if sliding else None)
    core = pairs / cfg["bptt"] * heads * 2 * d
    return proj, core


def _moe_macs_per_token(cfg, buffer=1.0):
    """Router and the routed experts at `buffer` times the balanced
    ``top_k * held / experts`` rows a token (1: what the mathematics
    needs; the layer's `capacity_factor`: what the buffer, whose every
    tile is computed, costs)."""
    h, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = cfg["published"]["num_experts"]
    rows = buffer * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / experts
    return h * experts + rows * 3 * h * width


def flops_per_item(cfg):
    """Operations one token's training step requires: two per
    multiply-accumulate, forward once and backward twice. Term by term,
    at the published widths and 8,192 tokens, MFLOP a token forward: a
    layer's projections 42.5, the full layer's scores and values 67.1
    (T (T + 1) / 2 pairs a head), a sliding layer's 15.7 (sum of
    min(i + 1, 1024)), a layer's router 0.3 and the balanced rows of the
    experts held 12.4, the head 56.6; no recomputation, no embedding
    gather, no elementwise work, no block rounding."""
    layers = cfg["num_hidden_layers"]
    macs = sum(sum(_attention_macs_per_token(cfg, is_sliding_layer(cfg, i)))
               for i in range(layers))
    macs += layers * _moe_macs_per_token(cfg)
    macs += cfg["hidden_size"] * cfg["vocab_size"]
    return 3 * 2 * macs


def kernel_work(cfg, batch, block_q=None, block_k=None):
    """Pallas kernel name -> (operations, least HBM bytes) of one call
    at `batch` sequences of `bptt`: what `readers/kernel_roofline_pct`
    divides by the chip's peaks.

    The least the mathematics needs: `attention_pairs` a query head, the
    window's for the `mx_flash_swa_*` kernels (the sliding layers' call)
    and every earlier key's for `mx_flash_fwd` / `mx_flash_bwd` (the full
    layer's), 2 score-sized products a pair forward and 5 in the fused
    backward; bytes with q, dO, dQ, the output and the two fp32 rows once
    a query head and K, V, dK and dV once a key/value head. The blocks
    are accepted and ignored: a pair a block computes beside the allowed
    ones is no work the mathematics needs, so no choice of blocks reads
    over 100 %."""
    del block_q, block_k
    seq = cfg["bptt"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])

    def nbytes(per_q, per_kv):
        return batch * seq * (2 * d * (heads * per_q + kv * per_kv)
                              + heads * 4 * 2)

    work = {}
    for fwd, bwd, window in (
            ("mx_flash_fwd", "mx_flash_bwd", None),
            ("mx_flash_swa_fwd", "mx_flash_swa_bwd", cfg["sliding_window"])):
        pairs = attention_pairs(seq, window) * batch * heads
        work[fwd] = (2 * pairs * 2 * d, nbytes(2, 2))
        work[bwd] = (2 * pairs * 5 * d, nbytes(3, 4))
    return work


# ---- the plain reference -------------------------------------------------

def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope_table(rope, d, seq):
    """(cos, sin), each (seq, d / 2), of one `rope_parameters` entry at
    positions 0..seq-1, written out from the published formulas.

    default: ``inv_freq_m = theta^(-2m/d)``. yarn: extrapolation
    ``e_m = theta^(-2m/d)``, interpolation ``e_m / factor``;
    ``c(r) = d ln(L / (2 pi r)) / (2 ln theta)`` with L the original
    length; ``low = max(floor(c(beta_fast)), 0)``,
    ``high = min(ceil(c(beta_slow)), d - 1)``;
    ``ramp_m = clip((m - low) / (high - low), 0, 1)``;
    ``inv_freq_m = (e_m / factor) ramp_m + e_m (1 - ramp_m)``; cos and
    sin both times `attention_factor`. At every length (the published
    code scales whenever the type is yarn)."""
    theta = float(rope["rope_theta"])
    m = jnp.arange(d // 2, dtype=jnp.float32)
    inv_freq = theta ** (-2.0 * m / d)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        length = rope["original_max_position_embeddings"]

        def c(r):
            return d * math.log(length / (2 * math.pi * r)) \
                / (2 * math.log(theta))

        low = max(math.floor(c(rope["beta_fast"])), 0)
        high = min(math.ceil(c(rope["beta_slow"])), d - 1)
        ramp = jnp.clip((m - low) / (high - low), 0.0, 1.0)
        inv_freq = inv_freq / rope["factor"] * ramp + inv_freq * (1 - ramp)
        factor = rope["attention_factor"]
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _rope(x, cos, sin):
    """The whole width turned by halves, i with i + d / 2. x (..., T, d)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def allowed(cfg, layer, q_pos, k_pos):
    """The two-edged mask: key j is allowed for query i where j <= i and,
    in a sliding layer, i - sliding_window < j."""
    mask = k_pos[None, :] <= q_pos[:, None]
    if is_sliding_layer(cfg, layer):
        mask &= q_pos[:, None] - k_pos[None, :] < cfg["sliding_window"]
    return mask


def _attention(cfg, p, layer, u, block=512):
    """Grouped-query attention: u (T, hidden) -> (T, hidden), scores a
    block of queries at a time against every key under the explicit
    mask."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    seq = u.shape[0]
    name = ("layers%d" % layer, "self_attn")
    q = (u @ _find(p, *name, "q_proj_weight").T).reshape(seq, heads, d)
    k = (u @ _find(p, *name, "k_proj_weight").T).reshape(seq, kv, d)
    v = (u @ _find(p, *name, "v_proj_weight").T).reshape(seq, kv, d)
    q = _norm(q, _find(p, *name, "q_norm_weight"), eps)
    k = _norm(k, _find(p, *name, "k_norm_weight"), eps)
    cos, sin = rope_table(
        cfg["rope_parameters"][cfg["layer_types"][layer]], d, seq)
    q = _rope(q.transpose(1, 0, 2), cos, sin)               # (heads, T, d)
    k = _rope(k.transpose(1, 0, 2), cos, sin)               # (kv, T, d)
    k = jnp.repeat(k, heads // kv, 0)       # query head h reads h // group
    v = jnp.repeat(v.transpose(1, 0, 2), heads // kv, 0)
    block = min(block, seq)
    outs = []
    for start in range(0, seq, block):
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + block], k) \
            * d ** -0.5
        mask = allowed(cfg, layer, jnp.arange(start, start + block),
                       jnp.arange(seq))
        s = jnp.where(mask[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(s, -1), v))
    out = jnp.concatenate(outs, 0).reshape(seq, heads * d)
    return out @ _find(p, *name, "o_proj_weight").T


def _sparse_ffn(cfg, p, layer, u, told=None):
    """The held experts' part: a dense loop over the held experts with a
    mask, no sorting, no buffer. With `told`, the weights of the chosen
    experts come from the probabilities on the operands the system's
    router had, as its own do (`models/qwen3_next.py:_selection`)."""
    name = ("layers%d" % layer, "mlp")
    prob = jax.nn.softmax(u @ _find(p, *name, "gate_weight").T, -1)
    ids, prob = _selection(cfg, prob, told)
    picked = jnp.take_along_axis(prob, ids, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)

    def one(acc, expert):
        eid, gate, up, down = expert
        w = jnp.sum(jnp.where(ids == eid, picked, 0.0), -1)
        # the stacked experts are stored (in, out)
        return acc + w[:, None] * _mlp(u, gate.T, up.T, down.T), None

    stacked = tuple(_find(p, *name, "experts_%s_weight" % part)
                    for part in ("gate_proj", "up_proj", "down_proj"))
    held = jnp.asarray(held_experts(cfg), jnp.int32)
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (held,) + stacked)
    return out


def reference_forward(cfg, params, x, train=False):
    """Logits (N, T, vocab slice) in plain fp32 jax.numpy at the highest
    matmul precision, given the same share as the program: the held
    experts and the held rows of the vocabulary. `train` changes nothing
    (no dropout, no router state). `params` may hold, for a layer, what
    the system's router multiplied and chose on `x`
    (`..._mlp_router_input` (N, T, hidden), `..._mlp_router_weight`
    (experts, hidden), `..._mlp_selected` (N, T, k), from
    `runners/train_step_routed`)."""
    del train
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        told = {k: jnp.asarray(v) for k, v in params.items()
                if k.endswith(_TOLD)}
        params = {k: (v if jnp.issubdtype(jnp.asarray(v).dtype, jnp.integer)
                      else jnp.asarray(v, jnp.float32))
                  for k, v in params.items() if k not in told}
        embed = _find(params, "embed_tokens", "weight")
        rows = []
        for n, tokens in enumerate(x.astype(jnp.int32)):
            h = embed[tokens]
            for layer in range(cfg["num_hidden_layers"]):
                name = "layers%d" % layer
                h = h + _attention(cfg, params, layer, _norm(
                    h, _find(params, name, "input_layernorm_weight"), eps))
                u = _norm(h, _find(params, name,
                                   "post_attention_layernorm_weight"), eps)
                h = h + _sparse_ffn(
                    cfg, params, layer, u,
                    (_find(told, name, "mlp", "router_input")[n],
                     _find(told, name, "mlp", "router_weight"),
                     _find(told, name, "mlp", "selected")[n])
                    if told else None)
            h = _norm(h, _find(params, "norm", "weight", top=True), eps)
            rows.append(h @ _find(params, "lm_head", "weight").T)
        return jnp.stack(rows)


def buffer_rows(cfg, tokens):
    """Rows of one sparse layer's buffer (the program's own count)."""
    from mxnet_tpu.ops.moe import buffer_rows as rows

    return rows(tokens, cfg["num_experts_per_tok"], cfg["num_experts"],
                cfg["published"]["num_experts"], cfg["capacity_factor"])

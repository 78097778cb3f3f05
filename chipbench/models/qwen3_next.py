"""Decoder language model of the Qwen3-Next family (`model_type`
``qwen3_next``: three Gated DeltaNet linear-attention layers to one
gated grouped-query attention layer, a softmax top-k router with routed
experts and one gated shared expert in every layer, zero-centred
RMSNorm), built from the program's Gluon blocks
(`gluon.model_zoo.qwen3_next`), and its plain fp32 reference.

The configuration file holds the published keys. Three of them are one
chip's share of the deployment it states: `num_hidden_layers` (the layers
kept), `num_experts` (the routed experts held here; the router keeps the
published width, `published.num_experts`) and `vocab_size` (the rows of
the vocabulary held: ids, logits and loss are over that slice).
`deployment.expert_shard` says which run of experts is held. What the
absent experts would add is left out, here and in the reference alike.

A batch is (N, bptt) int32 token ids and the (N, bptt) next tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def held_experts(cfg):
    n = cfg["num_experts"]
    first = cfg["deployment"]["expert_shard"] * n
    return list(range(first, first + n))


def zoo_config(cfg):
    """The config dict `gluon.model_zoo.qwen3_next` takes: published
    keys, the router at its published width, the held experts by id."""
    return dict(cfg, num_experts=cfg["published"]["num_experts"],
                held_experts=held_experts(cfg))


def is_attention_layer(cfg, layer):
    return (layer + 1) % cfg["full_attention_interval"] == 0


def build(cfg, seed):
    """The net from the seed, its large weights drawn on the device
    (`initializer.DeviceNormal`), the token embedding drawn again at
    `embed_initializer_range` (`assumed`: random weights lack a trained
    checkpoint's embedding scale). The softmax router has no bias and
    nothing to calibrate."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, initializer
    from mxnet_tpu.gluon.model_zoo import qwen3_next as zoo

    mx.random.seed(seed)
    net = zoo.qwen3_next(dict(
        zoo_config(cfg),
        weight_initializer=initializer.DeviceNormal(
            cfg["initializer_range"])))
    net.initialize()
    embed = net.embed_tokens.weight
    embed.set_data(mx.nd.random.normal(
        0, cfg["embed_initializer_range"], shape=embed.shape))
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def make_batch(cfg, rng, batch):
    """Token ids drawn Zipf-like from the held slice of the vocabulary
    (rank r with weight 1/(r+1)), labels the next token."""
    logits = -jnp.log1p(jnp.arange(cfg["vocab_size"], dtype=jnp.float32))
    seq = jax.random.categorical(rng, logits, shape=(batch, cfg["bptt"] + 1))
    seq = seq.astype(jnp.int32)
    return seq[:, :-1], seq[:, 1:]


# ---- what the mathematics needs ------------------------------------------

def _delta_net_macs_per_token(cfg):
    """(projections and convolution, the delta rule in chunks of
    `gdn_chunk`) of one Gated DeltaNet layer."""
    h = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key, value = hk * dk, hv * dv
    proj = h * (2 * key + 2 * value) + h * 2 * hv + value * h
    proj += (2 * key + value) * cfg["linear_conv_kernel_dim"]
    c = cfg["gdn_chunk"]
    # a value head and token: k k^T and q k^T rows (c * dk each), the
    # forward substitution (c / 2 rows of c), U and W (c * dv, c * dk),
    # then the scan: W S, q S, (k^T D) (dk * dv each) and P D (c * dv)
    rule = hv * (2 * c * dk + c * c // 2 + c * dv + c * dk
                 + 3 * dk * dv + c * dv)
    return proj, rule


def _attention_macs_per_token(cfg):
    h, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    d = cfg["head_dim"]
    proj = h * heads * 2 * d + 2 * h * kv * d + heads * d * h
    # causal: S (S + 1) / 2 pairs a sequence, (S + 1) / 2 a token
    core = (cfg["bptt"] + 1) / 2 * heads * 2 * d
    return proj, core


def _moe_macs_per_token(cfg, buffer=1.0):
    """Router, the gated shared expert, and the routed experts at
    `buffer` times the balanced ``top_k * held / experts`` rows a token
    (1: what the mathematics needs; the layer's `capacity_factor`: what
    the buffer, whose every tile is computed, costs)."""
    h, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = cfg["published"]["num_experts"]
    rows = buffer * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / experts
    return h * experts + 3 * h * cfg["shared_expert_intermediate_size"] \
        + h + rows * 3 * h * width


def flops_per_item(cfg):
    """Operations one token's training step requires: two per
    multiply-accumulate, forward once and backward twice. Term by term,
    at the published widths, MFLOP a token forward: a Gated DeltaNet
    layer 73.3 (projections 67.4, convolution 0.07, the delta rule at
    chunk 64 5.9), the attention layer 88.1 (projections 54.5, causal
    scores and values at 4,096 33.6), a layer's router, shared expert
    and routed rows (ISSUE 34 counts 14.3 with 32 held at a buffer of
    1.5; here the balanced rows of the experts held, as
    `models/deepseek_v3.py` counts them: the buffer's padding is no work
    the mathematics needs), the head 77.8; no recomputation, no
    embedding gather, no elementwise work."""
    layers = cfg["num_hidden_layers"]
    attention = sum(is_attention_layer(cfg, i) for i in range(layers))
    macs = (layers - attention) * sum(_delta_net_macs_per_token(cfg))
    macs += attention * sum(_attention_macs_per_token(cfg))
    macs += layers * _moe_macs_per_token(cfg)
    macs += cfg["hidden_size"] * cfg["vocab_size"]
    return 3 * 2 * macs


def _flash_pairs(seq, block_q, block_k):
    """Query-key pairs a causal flash kernel computes over one head of
    `seq` positions at these blocks (a block the diagonal crosses is
    computed whole)."""
    block_q, block_k = min(block_q, seq), min(block_k, seq)
    blocks = sum(1 for i in range(seq // block_q)
                 for j in range(seq // block_k)
                 if j * block_k <= (i + 1) * block_q - 1)
    return blocks * block_q * block_k


def kernel_work(cfg, batch, block_q, block_k):
    """Pallas kernel name -> (operations, least HBM bytes) of one call
    at `batch` sequences of `bptt`: what `readers/kernel_roofline_pct`
    divides by the chip's peaks.

    The delta rule's kernels (`mx_gdn_*`), a value head and chunk of c
    tokens: forward W S, q S, k^T D (c dk dv each) and P D (c c dv);
    backward P^T dO and dO D^T (c c dv), k dS, dO S^T, D dS^T, dD S^T,
    q^T dO and W^T dD (c dk dv): twice the forward; D formed again in the
    backward is recomputation and is not counted. Bytes: every operand
    and result of the call once (bf16; the chunk decays and one state a
    chunk in fp32). 47 FLOP a byte forward: far under the chip's ridge of
    240, so these kernels are bound by the bandwidth.

    The flash kernels at `num_attention_heads` query heads on
    `num_key_value_heads` key/value heads, blocks (block_q, block_k):
    score-sized products a computed pair as in `models/deepseek_v3.py`
    (forward 2, the one-pass backward 5); bytes with K, V, dK and dV once
    a key/value head, not once a query head."""
    seq = cfg["bptt"]
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    c = cfg["gdn_chunk"]
    chunks = batch * hv * (seq // c)
    fwd = 2 * chunks * (3 * c * dk * dv + c * c * dv)
    operands = chunks * (c * 2 * (3 * dk + dv + c) + 4 * dv)
    states = chunks * 4 * dk * dv
    gdn_fwd = (fwd, operands + chunks * c * 2 * dv + states)
    gdn_bwd = (2 * fwd, 2 * operands + chunks * c * 2 * dv + states)

    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    pairs = _flash_pairs(seq, block_q, block_k) * batch * heads

    def nbytes(per_q, per_kv):
        return batch * seq * (2 * d * (heads * per_q + kv * per_kv)
                              + heads * 4 * 2)

    return {
        "mx_gdn_fwd": gdn_fwd,
        "mx_gdn_bwd": gdn_bwd,
        "mx_flash_fwd": (2 * pairs * 2 * d, nbytes(2, 2)),
        "mx_flash_bwd": (2 * pairs * 5 * d, nbytes(3, 4)),
    }


# ---- the plain reference -------------------------------------------------

def _find(params, *parts, top=False):
    """The one parameter whose name ends with the parts joined by '_'
    (the model's own prefix differs from process to process); `top`:
    among the model's own, outside its layers."""
    tail = "_".join(parts)
    hits = [v for k, v in params.items() if k.endswith("_" + tail)
            and not (top and "_layers" in k)]
    if len(hits) != 1:
        raise ValueError("%d parameters match %r" % (len(hits), tail))
    return jnp.asarray(hits[0])


def _norm(x, w, eps):
    """Zero-centred RMSNorm: x / rms(x) * (1 + w)."""
    return (1.0 + w) * x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta, rotary):
    """The first `rotary` of the width turned by halves: i with
    i + rotary / 2 by pos * theta^(-2i / rotary). x: (..., T, d)."""
    half = rotary // 2
    freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attention(cfg, p, layer, u, block=512):
    """Gated grouped-query attention: u (T, hidden) -> (T, hidden),
    causal, scores a query block at a time."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rotary = int(d * cfg["partial_rotary_factor"])
    seq = u.shape[0]
    name = ("layers%d" % layer, "self_attn")
    qg = (u @ _find(p, *name, "q_proj_weight").T).reshape(seq, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(seq, heads * d)
    k = (u @ _find(p, *name, "k_proj_weight").T).reshape(seq, kv, d)
    v = (u @ _find(p, *name, "v_proj_weight").T).reshape(seq, kv, d)
    q = _norm(q, _find(p, *name, "q_norm_weight"), eps)
    k = _norm(k, _find(p, *name, "k_norm_weight"), eps)
    q = _rope(q.transpose(1, 0, 2), theta, rotary)          # (heads, T, d)
    k = _rope(k.transpose(1, 0, 2), theta, rotary)          # (kv, T, d)
    k = jnp.repeat(k, heads // kv, 0)
    v = jnp.repeat(v.transpose(1, 0, 2), heads // kv, 0)
    block = min(block, seq)
    outs = []
    for start in range(0, seq, block):
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + block], k) \
            * d ** -0.5
        causal = (jnp.arange(start, start + block)[:, None]
                  >= jnp.arange(seq)[None, :])
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(s, -1), v))
    out = jnp.concatenate(outs, 0).reshape(seq, heads * d)
    return (out * jax.nn.sigmoid(gate)) @ _find(p, *name, "o_proj_weight").T


def delta_rule_recurrence(q, k, v, g, beta):
    """The gated delta rule token by token. q, k (T, heads, d_k) as they
    enter the rule, v (T, heads, d_v), g and beta (T, heads); the state
    (d_k, d_v) a head starts at 0:

        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
        o_t = S^T q_t
    """
    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[:, None, None] * state
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    start = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, start, (q, k, v, g, beta))[1]


def _delta_net(cfg, p, layer, u):
    """Gated DeltaNet: u (T, hidden) -> (T, hidden)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key, value = hk * dk, hv * dv
    width, eps = cfg["linear_conv_kernel_dim"], cfg["rms_norm_eps"]
    seq = u.shape[0]
    name = ("layers%d" % layer, "linear_attn")
    qkvz = u @ _find(p, *name, "in_proj_qkvz_weight").T
    ba = u @ _find(p, *name, "in_proj_ba_weight").T
    mixed, z = qkvz[:, :2 * key + value], qkvz[:, 2 * key + value:]
    conv = _find(p, *name, "conv1d_weight")                 # (channels, 4)
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[j:j + seq] * conv[:, j]
                            for j in range(width)))
    q = mixed[:, :key].reshape(seq, hk, dk)
    k = mixed[:, key:2 * key].reshape(seq, hk, dk)
    v = mixed[:, 2 * key:].reshape(seq, hv, dv)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, 1)
    k = jnp.repeat(unit(k), hv // hk, 1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_find(p, *name, "A_log")) * jax.nn.softplus(
        ba[:, hv:] + _find(p, *name, "dt_bias"))
    out = delta_rule_recurrence(q, k, v, g, beta)           # (T, hv, dv)
    out = _find(p, *name, "norm_weight") * out * jax.lax.rsqrt(
        jnp.mean(out * out, -1, keepdims=True) + eps)
    out = out * jax.nn.silu(z.reshape(seq, hv, dv))
    return out.reshape(seq, value) @ _find(p, *name, "out_proj_weight").T


def _mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


# what `runners/train_step_routed` hands over beside the trained values
_TOLD = ("_router_input", "_router_weight", "_selected")


def _selection(cfg, prob, told):
    """The experts each token selects: the `num_experts_per_tok` most
    probable.

    Without `told`, on the reference's own probabilities. A top-k is
    discontinuous: a hidden state that differs upstream by rounding swaps
    near-tied experts, which moves a token's logits far more than
    rounding does. A runner that records what each of the system's
    routers multiplied and chose hands it over as `told` (input
    (T, hidden) and weight (experts, hidden) in fp32 as the router
    consumed them, ids (T, k)); the probabilities that decide are then
    computed here, at the highest precision, on those operands, and the
    system's choice stands for a token only if every expert it names is
    within `check.selection_margin` of the k-th most probable. Anywhere
    else the reference's choice on those operands stands, and the logits
    differ. Returns (ids, the probabilities to weigh them by)."""
    k = cfg["num_experts_per_tok"]
    if told is None:
        return jax.lax.top_k(prob, k)[1], prob
    seen, weight, chosen = told
    prob = jax.nn.softmax(seen @ weight.T, -1)
    best, ids = jax.lax.top_k(prob, k)
    theirs = jnp.take_along_axis(prob, chosen, -1)
    stands = jnp.all(
        theirs >= best[:, -1:] - cfg["check"]["selection_margin"], -1)
    return jnp.where(stands[:, None], chosen, ids), prob


def _sparse_ffn(cfg, p, layer, u, told=None):
    """The gated shared expert, plus the held experts' part: a dense
    loop over the held experts with a mask, no sorting, no buffer. With
    `told`, the weights of the chosen experts come from the probabilities
    on the operands the system's router had, as its own do."""
    name = ("layers%d" % layer, "mlp")
    prob = jax.nn.softmax(u @ _find(p, *name, "gate_weight").T, -1)
    ids, prob = _selection(cfg, prob, told)
    picked = jnp.take_along_axis(prob, ids, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    out = _mlp(u, *(_find(p, *name, "shared_experts", part + "_weight")
                    for part in ("gate_proj", "up_proj", "down_proj")))
    out = out * jax.nn.sigmoid(
        u @ _find(p, *name, "shared_expert_gate_weight").T)

    def one(acc, expert):
        eid, gate, up, down = expert
        w = jnp.sum(jnp.where(ids == eid, picked, 0.0), -1)
        # the stacked experts are stored (in, out)
        return acc + w[:, None] * _mlp(u, gate.T, up.T, down.T), None

    stacked = tuple(_find(p, *name, "experts_%s_weight" % part)
                    for part in ("gate_proj", "up_proj", "down_proj"))
    held = jnp.asarray(held_experts(cfg), jnp.int32)
    out, _ = jax.lax.scan(one, out, (held,) + stacked)
    return out


def reference_forward(cfg, params, x, train=False):
    """Logits (N, T, vocab slice) in plain fp32 jax.numpy at the highest
    matmul precision, given the same share as the program: the held
    experts and the held rows of the vocabulary. The delta rule runs
    token by token (`delta_rule_recurrence`), attention a block of
    queries at a time. `train` changes nothing (no dropout, no router
    state). `params` may hold, for a layer, what the system's router
    multiplied and chose on `x` (`..._mlp_router_input` (N, T, hidden),
    `..._mlp_router_weight` (experts, hidden), `..._mlp_selected`
    (N, T, k), from `runners/train_step_routed`): see `_selection`."""
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
                mixer = _attention if is_attention_layer(cfg, layer) \
                    else _delta_net
                h = h + mixer(cfg, params, layer, _norm(
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


def reference_loss(logits, y, block=512):
    """Mean softmax cross-entropy of the next token over the slice, a
    block of positions at a time."""
    logits = logits.reshape(-1, logits.shape[-1]).astype(jnp.float32)
    idx = y.reshape(-1).astype(jnp.int32)
    total = 0.0
    for start in range(0, logits.shape[0], block):
        logp = jax.nn.log_softmax(logits[start:start + block], -1)
        total = total - jnp.sum(jnp.take_along_axis(
            logp, idx[start:start + block, None], -1))
    return total / logits.shape[0]


def buffer_rows(cfg, tokens):
    """Rows of one sparse layer's buffer (the program's own count)."""
    from mxnet_tpu.ops.moe import buffer_rows as rows

    return rows(tokens, cfg["num_experts_per_tok"], cfg["num_experts"],
                cfg["published"]["num_experts"], cfg["capacity_factor"])

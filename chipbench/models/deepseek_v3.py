"""Decoder language model of the DeepSeek-V3 family (`model_type`
``deepseek_v3``: latent attention, a leading dense layer, sparse layers
with a sigmoid `noaux_tc` router, routed and shared experts), built from
the program's Gluon blocks (`gluon.model_zoo.deepseek_v3`), and its plain
fp32 reference.

The configuration file holds the published keys. Three of them are one
chip's share of the deployment it states: `num_hidden_layers` (the layers
kept), `n_routed_experts` (the routed experts held here; the router keeps
the published width, `published.n_routed_experts`) and `vocab_size` (the
rows of the vocabulary held: ids, logits and loss are over that slice).
`deployment.expert_shard` says which run of experts is held. What the
absent experts would add is left out, here and in the reference alike.

A batch is (N, bptt) int32 token ids and the (N, bptt) next tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def held_experts(cfg):
    n = cfg["n_routed_experts"]
    first = cfg["deployment"]["expert_shard"] * n
    return list(range(first, first + n))


def zoo_config(cfg):
    """The config dict `gluon.model_zoo.deepseek_v3` takes: published
    keys, the router at its published width, the held experts by id."""
    return dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
                held_experts=held_experts(cfg))


def _first_batch_key(seed):
    # chipbench/pool.py's key of the pool's first batch
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)), 0)


def build(cfg, seed):
    """The net from the seed (the token embedding drawn again at
    `embed_initializer_range`), and the router's selection bias
    calibrated on the pool's first batch by the published rule. Both
    `assumed`: random weights lack a trained checkpoint's embedding
    scale and its balanced router."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import deepseek_v3 as zoo

    mx.random.seed(seed)
    net = zoo.deepseek_v3(zoo_config(cfg))
    net.initialize()
    embed = net.embed_tokens.weight
    embed.set_data(mx.nd.random.normal(
        0, cfg["embed_initializer_range"], shape=embed.shape))
    cal = cfg["calibration"]
    tokens, _ = make_batch(cfg, _first_batch_key(seed), cal["batch"])
    calibrate_selection_bias(cfg, net, tokens)
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def sparse_layers(net):
    """The net's `nn.SparseMoE` blocks, in the order they run."""
    from mxnet_tpu.gluon import nn

    found = []
    net.apply(lambda b: found.append(b) if isinstance(b, nn.SparseMoE)
              else None)
    return found


def _balanced_bias_steps(cfg, tokens, weight, steps):
    """The published rule `b += gamma * sign(mean(c) - c)` iterated on
    one batch's scores (tokens (rows, hidden), the router's weight),
    from `steps` (the bias in whole steps of gamma), until the fullest
    expert holds under `calibration.max_over_mean` times the mean or
    `calibration.max_iters` steps have run."""
    from mxnet_tpu.ops.transformer_ops import bias_steps_update

    if cfg["n_group"] != 1:
        raise NotImplementedError("calibration with group-limited routing")
    cal, top_k = cfg["calibration"], cfg["num_experts_per_tok"]
    gamma = jnp.float32(cfg["bias_update_rate"])
    score = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", tokens.astype(jnp.float32), weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    experts = score.shape[1]

    def counts_of(steps):
        _, ids = jax.lax.top_k(score + steps.astype(jnp.float32) * gamma,
                               top_k)
        return jnp.zeros((experts,), jnp.int32).at[ids.reshape(-1)].add(1)

    def unbalanced(state):
        it, _, counts = state
        return (it < cal["max_iters"]) & (
            jnp.max(counts) * experts
            >= cal["max_over_mean"] * jnp.sum(counts))

    def move(state):
        it, steps, counts = state
        steps = bias_steps_update(steps, counts)
        return it + 1, steps, counts_of(steps)

    return jax.lax.while_loop(
        unbalanced, move, (jnp.int32(0), steps, counts_of(steps)))[1]


def calibrate_selection_bias(cfg, net, tokens):
    """One evaluation forward of `tokens` (batch, seq) in the compute
    type `calibration.dtype`, layer by layer: before a sparse layer
    runs, its bias is moved by `_balanced_bias_steps` on what its
    router is about to see, and the layer routes by the new bias, so the
    next layer is calibrated on what this one lets through. One program;
    the biases are written into the net."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.parameter import override, tracing_overrides
    from mxnet_tpu.ndarray import NDArray

    params = list(net.collect_params().values())
    cdt = jnp.dtype(cfg["calibration"]["dtype"] or "float32")
    layers = sparse_layers(net)

    def forward(values, tokens):
        def cast(a):
            return a.astype(cdt) \
                if jnp.issubdtype(a.dtype, jnp.floating) else a

        moved = {}

        def calibrate(block, args):
            x = args[0]._data
            bias = block.e_score_correction_steps
            steps = _balanced_bias_steps(
                cfg, x.reshape(-1, x.shape[-1]),
                block.gate_weight.data()._data, bias.data()._data)
            tracing_overrides().mapping[bias] = NDArray(steps)
            moved[bias.name] = steps

        hooks = [b.register_forward_pre_hook(calibrate) for b in layers]
        try:
            with autograd.pause(train_mode=False), override(
                    {p: NDArray(cast(v)) for p, v in zip(params, values)}):
                net(NDArray(tokens))
        finally:
            for hook in hooks:
                hook.detach()
        return moved

    forward.__name__ = "chipbench_moe_calibrate"
    moved = jax.jit(forward)([p.data()._data for p in params],
                             jnp.asarray(tokens))
    for p in params:
        if p.name in moved:
            p.set_data(NDArray(moved[p.name]))
    return moved


def make_batch(cfg, rng, batch):
    """Token ids drawn Zipf-like from the held slice of the vocabulary
    (rank r with weight 1/(r+1)), labels the next token."""
    logits = -jnp.log1p(jnp.arange(cfg["vocab_size"], dtype=jnp.float32))
    seq = jax.random.categorical(rng, logits, shape=(batch, cfg["bptt"] + 1))
    seq = seq.astype(jnp.int32)
    return seq[:, :-1], seq[:, 1:]


# ---- what the mathematics needs ------------------------------------------

def _attention_macs_per_token(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    proj = h * heads * qk if cfg.get("q_lora_rank") is None else \
        cfg["q_lora_rank"] * (h + heads * qk)
    proj += h * (rank + cfg["qk_rope_head_dim"])
    proj += rank * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    proj += heads * cfg["v_head_dim"] * h
    # causal: S (S + 1) / 2 pairs a sequence, (S + 1) / 2 a token
    core = (cfg["bptt"] + 1) / 2 * heads * (qk + cfg["v_head_dim"])
    return proj, core


def flops_per_item(cfg):
    """Operations one token's training step requires: two per
    multiply-accumulate, forward once and backward twice. Causal
    attention as the S(S+1)/2 pairs the mathematics needs; routed
    experts at the balanced ``top_k * held / experts`` rows a token; no
    padding, no recomputation, no embedding gather, no elementwise work.
    """
    h = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    proj, core = _attention_macs_per_token(cfg)
    macs = layers * (proj + core)
    macs += dense * 3 * h * cfg["intermediate_size"]
    width = cfg["moe_intermediate_size"]
    rows = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["published"]["n_routed_experts"]
    macs += (layers - dense) * (
        h * cfg["published"]["n_routed_experts"]
        + 3 * h * width * cfg["n_shared_experts"] + rows * 3 * h * width)
    macs += h * cfg["vocab_size"]
    return 3 * 2 * macs


def _flash_pairs(seq, block_q, block_k):
    """Query-key pairs a causal flash kernel computes over one head of
    `seq` positions at these blocks: a block the diagonal crosses is
    computed whole, a block wholly above it is skipped and is not work.
    """
    block_q, block_k = min(block_q, seq), min(block_k, seq)
    blocks = sum(1 for i in range(seq // block_q)
                 for j in range(seq // block_k)
                 if j * block_k <= (i + 1) * block_q - 1)
    return blocks * block_q * block_k


def kernel_work(cfg, batch, block_q, block_k):
    """Pallas kernel name -> (operations, least HBM bytes) of one call
    at `batch` sequences of `bptt` with (block_q, block_k) blocks (the
    layer metric's `args` name them; counted here, not asked of the
    program): what `readers/kernel_roofline_pct` divides by the chip's
    peaks. Score-sized products a computed pair costs: forward q k^T
    and p v; the one-pass backward (`mx_flash_bwd`) q k^T, dO v^T,
    p^T dO, dS^T q and dS k. Bytes: each operand and result once in
    bf16 (backward: q, k, dQ, dK at d_qk; v, dO, dV at d_v), row
    statistics in fp32; far under the operations over the chip's ridge
    (240 FLOP a byte), so every flash kernel here is compute-bound."""
    seq, heads = cfg["bptt"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    pairs = _flash_pairs(seq, block_q, block_k) * batch * heads

    def flops(qk_products, v_products):
        return 2 * pairs * (qk_products * qk + v_products * v)

    def nbytes(widths):
        return batch * heads * seq * (2 * widths + 4 * 2)

    return {
        "mx_flash_fwd": (flops(1, 1), nbytes(2 * qk + 2 * v)),
        "mx_flash_bwd": (flops(3, 2), nbytes(4 * qk + 3 * v)),
    }


# ---- the plain reference -------------------------------------------------

def _find(params, *parts):
    """The one parameter whose name ends with the parts joined by '_'
    (the model's own prefix differs from process to process)."""
    tail = "_".join(parts)
    hits = [v for k, v in params.items() if k.endswith("_" + tail)]
    if len(hits) != 1:
        raise ValueError("%d parameters match %r" % (len(hits), tail))
    return jnp.asarray(hits[0])


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """Pairs (2i, 2i+1) turned by pos * theta^(-2i/d), as complex
    numbers, left interleaved. x: (..., T, d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) \
        * jax.lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.stack([jnp.real(z), jnp.imag(z)], -1).reshape(x.shape)


def _attention(cfg, p, layer, u, block=512):
    """u (T, hidden) -> (T, hidden), causal, scores a query block at a
    time."""
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        float(cfg["rope_theta"])
    seq = u.shape[0]
    name = ("layers%d" % layer, "self_attn")
    if cfg.get("q_lora_rank") is None:
        q = u @ _find(p, *name, "q_proj_weight").T
    else:
        q = _rms(u @ _find(p, *name, "q_a_proj_weight").T,
                 _find(p, *name, "q_a_layernorm_weight"), eps) \
            @ _find(p, *name, "q_b_proj_weight").T
    q = q.reshape(seq, heads, nope + rope).transpose(1, 0, 2)
    ckr = u @ _find(p, *name, "kv_a_proj_with_mqa_weight").T
    c = _rms(ckr[:, :rank], _find(p, *name, "kv_a_layernorm_weight"), eps)
    kv = (c @ _find(p, *name, "kv_b_proj_weight").T).reshape(
        seq, heads, nope + dv).transpose(1, 0, 2)
    kr = _rope(ckr[None, :, rank:], theta)                  # (1, T, rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kr, (heads, seq, rope))], -1)
    v = kv[..., nope:]
    block = min(block, seq)
    outs = []
    for start in range(0, seq, block):
        s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + block], k) \
            * (nope + rope) ** -0.5
        causal = (jnp.arange(start, start + block)[:, None]
                  >= jnp.arange(seq)[None, :])
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(s, -1), v))
    out = jnp.concatenate(outs, 0).reshape(seq, heads * dv)
    return out @ _find(p, *name, "o_proj_weight").T


def _mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


# what `runners/train_step_routed` hands over beside the trained values
_TOLD = ("_router_input", "_router_weight", "_selected")


def _selection(cfg, bias, score, told):
    """The experts each token selects: the top `num_experts_per_tok` of
    score plus bias.

    Without `told`, on the reference's own `score`. A top-k is
    discontinuous, and an evaluation forward in a lower compute type
    hands its router a hidden state that differs from the fp32 one by
    rounding, enough to swap near-tied experts, which moves a token's
    logits far more than rounding does. A runner that records what each
    of the system's routers multiplied and chose hands it over as `told`
    (input (T, hidden) and weight (experts, hidden) in fp32 as the
    router consumed them, ids (T, k)); the scores that decide are then
    computed here, at the highest precision, on those operands, and the
    system's choice stands for a token only if every expert it names
    scores within `check.selection_margin` of the sixth best. Anywhere
    else the reference's choice on those operands stands, and the
    logits differ."""
    k = cfg["num_experts_per_tok"]
    if told is None:
        return jax.lax.top_k(score + bias, k)[1]
    seen, weight, chosen = told
    choice = jax.nn.sigmoid(seen @ weight.T) + bias
    best, ids = jax.lax.top_k(choice, k)
    theirs = jnp.take_along_axis(choice, chosen, -1)
    stands = jnp.all(
        theirs >= best[:, -1:] - cfg["check"]["selection_margin"], -1)
    return jnp.where(stands[:, None], chosen, ids)


def _sparse_ffn(cfg, p, layer, u, told=None):
    """Shared experts, plus the held experts' part: a dense loop over
    the held experts with a mask, no sorting, no buffer."""
    name = ("layers%d" % layer, "mlp")
    score = jax.nn.sigmoid(u @ _find(p, *name, "gate_weight").T)
    bias = _find(p, *name, "e_score_correction_steps").astype(jnp.float32) \
        * jnp.float32(cfg["bias_update_rate"])
    if cfg["n_group"] != 1:
        raise NotImplementedError("group-limited routing in the reference")
    ids = _selection(cfg, bias, score, told)
    picked = jnp.take_along_axis(score, ids, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    out = _mlp(u, *(_find(p, *name, "shared_experts", part + "_weight")
                    for part in ("gate_proj", "up_proj", "down_proj")))

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
    experts and the held rows of the vocabulary. `train` changes
    nothing (no dropout; the selection bias is read, not moved).
    `params` may hold, for a sparse layer, what the system's router
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
                h = h + _attention(cfg, params, layer, _rms(
                    h, _find(params, name, "input_layernorm_weight"), eps))
                u = _rms(h, _find(params, name,
                                  "post_attention_layernorm_weight"), eps)
                if layer < cfg["first_k_dense_replace"]:
                    h = h + _mlp(u, *(_find(params, name, "mlp",
                                            part + "_weight")
                                      for part in ("gate_proj", "up_proj",
                                                   "down_proj")))
                else:
                    h = h + _sparse_ffn(
                        cfg, params, layer, u,
                        (_find(told, name, "mlp", "router_input")[n],
                         _find(told, name, "mlp", "router_weight"),
                         _find(told, name, "mlp", "selected")[n])
                        if told else None)
            h = _rms(h, _find(params, "norm", "weight"), eps)
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

    return rows(tokens, cfg["num_experts_per_tok"], cfg["n_routed_experts"],
                cfg["published"]["n_routed_experts"], cfg["capacity_factor"])


"""Decoder-block layers: RMSNorm, latent attention (MLA), grouped-query
attention (plain or over a sliding window, and with an output gate),
Gated DeltaNet linear attention, the SiLU-gated MLP and a sparse
mixture-of-experts layer that holds some of its experts.

No reference counterpart (MXNet 1.3 predates them); parameter names and
the equations follow the published `deepseek_v3`, `qwen3_next` and
`qwen3_moe` modeling code. Inputs are (batch, seq, hidden).
"""
from __future__ import annotations

import threading
import weakref

import numpy as np

import jax

from ..block import HybridBlock
from ...telemetry import metrics as _tm

__all__ = ["RMSNorm", "GatedMLP", "MLAttention", "GroupedQueryAttention",
           "GatedAttention", "GatedDeltaNet", "SparseMoE"]


class RMSNorm(HybridBlock):
    """``weight * x / sqrt(mean(x^2) + epsilon)`` over the last axis;
    `zero_centered`: ``(1 + weight)``, the weight starting at 0."""

    def __init__(self, in_channels, epsilon=1e-6, zero_centered=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._epsilon, self._zero_centered = epsilon, bool(zero_centered)
        self.weight = self.params.get(
            "weight", shape=(in_channels,),
            init="zeros" if zero_centered else "ones")

    def hybrid_forward(self, F, x, weight):
        return F.contrib.RMSNorm(x, weight, eps=self._epsilon,
                                 zero_centered=self._zero_centered)


class GatedMLP(HybridBlock):
    """``down(silu(gate x) * up x)``, no biases."""

    def __init__(self, hidden_size, intermediate_size,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        for name, shape in (
                ("gate_proj", (intermediate_size, hidden_size)),
                ("up_proj", (intermediate_size, hidden_size)),
                ("down_proj", (hidden_size, intermediate_size))):
            setattr(self, name + "_weight", self.params.get(
                name + "_weight", shape=shape, init=weight_initializer))

    def hybrid_forward(self, F, x, gate_proj_weight, up_proj_weight,
                       down_proj_weight):
        return F.contrib.gated_mlp(x, gate_proj_weight, up_proj_weight,
                                   down_proj_weight)


def _proj(F, a, w):
    return F.FullyConnected(a, w, no_bias=True, flatten=False,
                            num_hidden=w.shape[0])


def _heads_first(F, x, heads, width):
    """(B, T, heads * width) -> (B, heads, T, width)."""
    return F.transpose(F.reshape(x, shape=(0, 0, heads, width)),
                       axes=(0, 2, 1, 3))


class MLAttention(HybridBlock):
    """Multi-head latent attention, causal, as DeepSeek-V2/V3 train it.

    Keys and values come from one low-rank latent of `kv_lora_rank`
    (RMS-normed) and one rotary key of `qk_rope_head_dim` shared by all
    heads; a head's query and key are `qk_nope_head_dim` wide without
    position plus the rotary part, its value `v_head_dim`. Queries are
    projected directly, or through a latent of `q_lora_rank`. The core is
    `flash_attention` with unequal q/k and v widths."""

    def __init__(self, hidden_size, num_heads, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 q_lora_rank=None, rope_theta=10000.0, rope_interleave=True,
                 epsilon=1e-6, weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._heads = num_heads
        self._nope, self._rope, self._v = \
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        self._kv_rank = kv_lora_rank
        self._theta, self._interleave = float(rope_theta), rope_interleave
        qk = qk_nope_head_dim + qk_rope_head_dim

        def weight(name, shape):
            setattr(self, name + "_weight", self.params.get(
                name + "_weight", shape=shape, init=weight_initializer))

        if q_lora_rank is None:
            weight("q_proj", (num_heads * qk, hidden_size))
            self.q_a_layernorm = None
        else:
            weight("q_a_proj", (q_lora_rank, hidden_size))
            self.q_a_layernorm = RMSNorm(q_lora_rank, epsilon,
                                         prefix=self.prefix + "q_a_layernorm_")
            weight("q_b_proj", (num_heads * qk, q_lora_rank))
        weight("kv_a_proj_with_mqa",
               (kv_lora_rank + qk_rope_head_dim, hidden_size))
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, epsilon,
                                      prefix=self.prefix + "kv_a_layernorm_")
        weight("kv_b_proj",
               (num_heads * (qk_nope_head_dim + v_head_dim),
                kv_lora_rank))
        weight("o_proj", (hidden_size, num_heads * v_head_dim))

    def hybrid_forward(self, F, x, kv_a_proj_with_mqa_weight,
                       kv_b_proj_weight, o_proj_weight, q_proj_weight=None,
                       q_a_proj_weight=None, q_b_proj_weight=None):
        def proj(a, w):
            return _proj(F, a, w)

        def rope(a):
            return F.contrib.rotary_embedding(
                a, theta=self._theta, interleaved=self._interleave)

        with jax.named_scope("mla_attention"):
            if q_proj_weight is not None:
                q = proj(x, q_proj_weight)
            else:
                q = proj(self.q_a_layernorm(proj(x, q_a_proj_weight)),
                         q_b_proj_weight)
            q = _heads_first(F, q, self._heads, self._nope + self._rope)
            q_nope = F.slice_axis(q, axis=-1, begin=0, end=self._nope)
            q_rope = F.slice_axis(q, axis=-1, begin=self._nope, end=None)

            latent = proj(x, kv_a_proj_with_mqa_weight)
            k_rope = F.slice_axis(latent, axis=-1, begin=self._kv_rank,
                                  end=None)
            latent = self.kv_a_layernorm(
                F.slice_axis(latent, axis=-1, begin=0, end=self._kv_rank))
            kv = _heads_first(F, proj(latent, kv_b_proj_weight),
                              self._heads, self._nope + self._v)
            k_nope = F.slice_axis(kv, axis=-1, begin=0, end=self._nope)
            v = F.slice_axis(kv, axis=-1, begin=self._nope, end=None)

            # one rotary key for all heads
            k_rope = F.broadcast_axis(rope(F.expand_dims(k_rope, axis=1)),
                                      axis=1, size=self._heads)
            q = F.concat(q_nope, rope(q_rope), dim=-1)
            k = F.concat(k_nope, k_rope, dim=-1)
            out = F.contrib.flash_attention(q, k, v, causal=True)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            return proj(out, o_proj_weight)


class GroupedQueryAttention(HybridBlock):
    """Causal grouped-query attention, full or over a sliding window, as
    the Qwen3-MoE and `mellum` families have it.

    q, k and v are projected per head; q and k pass an RMSNorm over the
    head's width, then rotary embedding on the first
    `partial_rotary_factor` of it, halves paired, with the frequencies
    `rope_scaling` gives (None: plain; a dict of `rotary_embedding`'s
    YaRN keywords: `scaling_factor`, `original_max_position`,
    `beta_fast`, `beta_slow`, `attention_factor`); `num_heads` query
    heads read `num_kv_heads` key/value heads in groups
    (`flash_attention`), each query the `window` keys up to its own, or
    every earlier key; then the output projection. No bias anywhere.
    The scope in the program's metadata is `sliding_attention` under a
    window, else `full_attention`."""

    _gated = False                # an output gate beside each query head
    _zero_centered = False        # the q and k norms' scale is 1 + weight
    _scope = None                 # named by the window unless a subclass does

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 rope_theta=10000.0, partial_rotary_factor=1.0,
                 rope_scaling=None, window=None, epsilon=1e-6,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._width = \
            num_heads, num_kv_heads, head_dim
        self._rotary = int(head_dim * partial_rotary_factor)
        self._rope_args = dict(rope_scaling or {}, theta=float(rope_theta),
                               interleaved=False)
        self._flash_args = {} if window is None else {"window": int(window)}
        if self._scope is None:
            self._scope = "sliding_attention" if self._flash_args \
                else "full_attention"

        def weight(name, shape):
            setattr(self, name + "_weight", self.params.get(
                name + "_weight", shape=shape, init=weight_initializer))

        weight("q_proj", (num_heads * head_dim * (2 if self._gated else 1),
                          hidden_size))
        weight("k_proj", (num_kv_heads * head_dim, hidden_size))
        weight("v_proj", (num_kv_heads * head_dim, hidden_size))
        weight("o_proj", (hidden_size, num_heads * head_dim))
        self.q_norm = RMSNorm(head_dim, epsilon,
                              zero_centered=self._zero_centered,
                              prefix=self.prefix + "q_norm_")
        self.k_norm = RMSNorm(head_dim, epsilon,
                              zero_centered=self._zero_centered,
                              prefix=self.prefix + "k_norm_")

    def _rope(self, F, x):
        """Rotary embedding on the first `_rotary` of (B, heads, T, d)."""
        if self._rotary == self._width:
            return F.contrib.rotary_embedding(x, **self._rope_args)
        turned = F.contrib.rotary_embedding(
            F.slice_axis(x, axis=-1, begin=0, end=self._rotary),
            **self._rope_args)
        return F.concat(turned, F.slice_axis(
            x, axis=-1, begin=self._rotary, end=None), dim=-1)

    def hybrid_forward(self, F, x, q_proj_weight, k_proj_weight,
                       v_proj_weight, o_proj_weight):
        heads, kv, d = self._heads, self._kv_heads, self._width
        with jax.named_scope(self._scope):
            q = _proj(F, x, q_proj_weight)
            if self._gated:
                # [q | gate] per head
                qg = F.reshape(q, shape=(0, 0, heads, 2 * d))
                gate = F.reshape(
                    F.slice_axis(qg, axis=-1, begin=d, end=None),
                    shape=(0, 0, -1))
                q = F.slice_axis(qg, axis=-1, begin=0, end=d)
            else:
                q = F.reshape(q, shape=(0, 0, heads, d))
            q = self.q_norm(q)
            k = self.k_norm(F.reshape(_proj(F, x, k_proj_weight),
                                      shape=(0, 0, kv, d)))
            q = self._rope(F, F.transpose(q, axes=(0, 2, 1, 3)))
            k = self._rope(F, F.transpose(k, axes=(0, 2, 1, 3)))
            v = _heads_first(F, _proj(F, x, v_proj_weight), kv, d)
            out = F.contrib.flash_attention(q, k, v, causal=True,
                                            **self._flash_args)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            if self._gated:
                out = out * F.sigmoid(gate)
            return _proj(F, out, o_proj_weight)


class GatedAttention(GroupedQueryAttention):
    """Causal grouped-query attention with an output gate, as the
    `qwen3_next` family's full-attention layers have it:
    ``[q | gate] = x Wq`` per head, the q and k norms zero-centred, and
    the result times ``sigmoid(gate)`` goes through the output
    projection; the rest is `GroupedQueryAttention`'s."""

    _gated = True
    _zero_centered = True
    _scope = "gated_attention"


class GatedDeltaNet(HybridBlock):
    """Gated DeltaNet linear attention (`qwen3_next`'s other token
    mixer): one projection to ``[q | k | v | z]`` and one to ``[b | a]``,
    a causal depthwise convolution with SiLU over q, k and v, q and k
    L2-normalised per head, the gated delta rule with
    ``beta = sigmoid(b)`` and log decay
    ``g = -exp(A_log) * softplus(a + dt_bias)`` per value head
    (`ops/linear_attention.py`), a gated RMSNorm per head with
    ``silu(z)``, the output projection.

    `in_proj_qkvz_weight` holds q, k, v and z one after the other (the
    published checkpoint interleaves them per key head: a storage order);
    `in_proj_ba_weight` b then a; `conv1d_weight` is (channels, width)
    over q, k, v in that order. `A_log` starts at ``log U(0, 16)`` and
    `dt_bias` at 1, as the published code has them."""

    def __init__(self, hidden_size, num_k_heads, num_v_heads, head_k_dim,
                 head_v_dim, conv_kernel=4, epsilon=1e-6, chunk=64,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer as _init

        self._k_heads, self._v_heads = num_k_heads, num_v_heads
        self._dk, self._dv = head_k_dim, head_v_dim
        self._epsilon, self._chunk = epsilon, chunk
        key, value = num_k_heads * head_k_dim, num_v_heads * head_v_dim

        def param(name, shape, init=weight_initializer):
            setattr(self, name, self.params.get(name, shape=shape,
                                                init=init))

        param("in_proj_qkvz_weight", (2 * key + 2 * value, hidden_size))
        param("in_proj_ba_weight", (2 * num_v_heads, hidden_size))
        param("conv1d_weight", (2 * key + value, conv_kernel))
        param("A_log", (num_v_heads,), _init.LogUniform(0.0, 16.0))
        param("dt_bias", (num_v_heads,), "ones")
        param("norm_weight", (head_v_dim,), "ones")
        param("out_proj_weight", (hidden_size, value))

    def hybrid_forward(self, F, x, in_proj_qkvz_weight, in_proj_ba_weight,
                       conv1d_weight, A_log, dt_bias, norm_weight,
                       out_proj_weight):
        hk, hv, dk, dv = self._k_heads, self._v_heads, self._dk, self._dv
        key, value = hk * dk, hv * dv

        def unit(a, scale):
            """a / |a| over the head's width, in fp32, times scale."""
            a32 = F.cast(a, dtype="float32")
            inv = F.rsqrt(F.sum(F.square(a32), axis=-1, keepdims=True)
                          + 1e-6)
            return F.cast(a32 * inv * scale, dtype=a.dtype)

        with jax.named_scope("gated_delta_net"):
            qkvz = _proj(F, x, in_proj_qkvz_weight)
            mixed = F.contrib.causal_conv1d(
                F.slice_axis(qkvz, axis=-1, begin=0, end=2 * key + value),
                conv1d_weight)
            mixed = mixed * F.sigmoid(mixed)                    # SiLU
            z = F.slice_axis(qkvz, axis=-1, begin=2 * key + value, end=None)
            q = unit(_heads_first(F, F.slice_axis(
                mixed, axis=-1, begin=0, end=key), hk, dk), dk ** -0.5)
            k = unit(_heads_first(F, F.slice_axis(
                mixed, axis=-1, begin=key, end=2 * key), hk, dk), 1.0)
            v = _heads_first(F, F.slice_axis(
                mixed, axis=-1, begin=2 * key, end=None), hv, dv)
            # (B, T, 2 * hv) -> (B, hv, T) each, fp32
            ba = F.cast(F.transpose(_proj(F, x, in_proj_ba_weight),
                                    axes=(0, 2, 1)), dtype="float32")
            beta = F.sigmoid(F.slice_axis(ba, axis=1, begin=0, end=hv))
            a = F.slice_axis(ba, axis=1, begin=hv, end=None)
            per_head = lambda p: F.reshape(F.cast(p, dtype="float32"),
                                           shape=(1, -1, 1))
            g = -F.exp(per_head(A_log)) * F.Activation(
                a + per_head(dt_bias), act_type="softrelu")
            out = F.contrib.gated_delta_rule(q, k, v, g, beta,
                                             chunk=self._chunk)
            out = F.contrib.gated_rms_norm(
                F.transpose(out, axes=(0, 2, 1, 3)),
                F.reshape(z, shape=(0, 0, hv, dv)), norm_weight,
                eps=self._epsilon)
            return _proj(F, F.reshape(out, shape=(0, 0, -1)),
                         out_proj_weight)


_MOE_GAUGES = {
    "rows": _tm.REGISTRY.gauge(
        "mx_moe_rows_held", "Rows routed to held experts, mean over the "
        "training steps so far, summed over the sparse layers"),
    "buffer": _tm.REGISTRY.gauge(
        "mx_moe_buffer_rows", "Rows of the held experts' buffers, summed "
        "over the sparse layers"),
    "load": _tm.REGISTRY.gauge(
        "mx_moe_load_max_over_mean", "Tokens of the fullest expert over "
        "the mean, all experts: the largest over the training steps so "
        "far, worst sparse layer"),
}
_moe_overflow = _tm.REGISTRY.counter(
    "mx_moe_overflow_steps_total", "Training steps in which the rows "
    "routed to held experts exceeded the buffer (the second pass ran), "
    "summed over the sparse layers")
_moe_skipped = _tm.REGISTRY.counter(
    "mx_moe_state_reads_skipped_total", "Reads of a sparse layer's "
    "non-gradient state that found it given away to a running step; "
    "the gauges then hold the read before")
_moe_layers = []          # weak references to the live SparseMoE blocks
_moe_lock = threading.Lock()


def _fold_moe_counters():
    """Registry.on_collect hook: read the layers' non-gradient state
    (device values the steps accumulated) into the gauges. The only host
    read of them; nothing inside a step waits for it."""
    with _moe_lock:
        layers = [layer for layer in (ref() for ref in _moe_layers)
                  if layer is not None]
    read = []
    for layer in layers:
        state = (layer.expert_counts, layer.peak_count, layer.steps_seen,
                 layer.held_rows_sum, layer.overflow_steps)
        if any(p._data is None for p in state):
            continue                      # not initialized
        try:
            read.append((layer, jax.device_get(
                [p._live_data()._data for p in state])))
        except RuntimeError:              # deleted: a step holds it now
            _moe_skipped.inc()
            return
    rows = buffer = overflow = 0
    load = 0.0
    for layer, (counts, peak, steps, held, over) in read:
        now = (int(steps[0]), int(held[0]), int(over[0]))
        if now[0] < layer._folded[0]:     # the state was made anew
            layer._folded, layer._rows_total = (0, 0, 0), 0
        if not now[0]:
            continue                      # no training step yet
        # the device's sum of rows is int32 and wraps; the host's does not
        layer._rows_total += (now[1] - layer._folded[1]) % (1 << 32)
        overflow += now[2] - layer._folded[2]
        layer._folded = now
        rows += layer._rows_total / now[0]
        buffer += layer.buffer_rows(int(counts.sum()) // layer._top_k)
        load = max(load, float(peak[0] / counts.mean()))
    if buffer:
        _MOE_GAUGES["rows"].set(rows)
        _MOE_GAUGES["buffer"].set(buffer)
        _MOE_GAUGES["load"].set(load)
        _moe_overflow.inc(max(overflow, 0))


_tm.REGISTRY.on_collect(_fold_moe_counters)


class SparseMoE(HybridBlock):
    """Mixture-of-experts feed-forward that holds `held` of its
    `num_experts` routed experts (an expert-parallel chip's share), with
    `n_shared_experts` always-on experts fused into one wider MLP.

    The router (`router`: ``"noaux_tc"``, sigmoid scores with a
    selection bias, or ``"softmax"``, a softmax top-k with neither bias
    nor state) scores every expert; the held experts' part of
    the result is computed here (`ops/moe.py`), the rest is left to the
    chips that hold them. `shared_expert_gate`: the shared experts'
    result is multiplied by ``sigmoid(x w)``, ``w`` (1, hidden).
    Non-gradient state, written by the training
    forward as BatchNorm writes its running statistics:
    `e_score_correction_steps` (`noaux_tc` alone: the selection bias in
    whole steps of
    `bias_update_rate`; after each training step +1 for an expert picked
    by fewer tokens than the mean, -1 for more), and what telemetry
    reads: `expert_counts` (tokens that picked each expert) and
    `held_rows` (rows routed to held experts) of the last step, and over
    all steps `steps_seen`, `held_rows_sum` (wraps), `peak_count` (the
    most tokens one expert got in one step) and `overflow_steps` (steps
    whose second pass ran)."""

    def __init__(self, hidden_size, moe_intermediate_size, num_experts,
                 held=None, top_k=6, n_shared_experts=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True, n_group=1,
                 topk_group=1, bias_update_rate=1e-3, capacity_factor=1.5,
                 router="noaux_tc", shared_expert_gate=False,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        if router not in ("noaux_tc", "softmax"):
            raise ValueError("router %r is neither noaux_tc nor softmax"
                             % (router,))
        self._held = tuple(range(num_experts)) if held is None \
            else tuple(int(e) for e in held)
        self._num_experts, self._top_k = num_experts, top_k
        self._capacity_factor = float(capacity_factor)
        # what the telemetry hook has folded in: (steps_seen,
        # held_rows_sum, overflow_steps) as last read, and the rows in all
        self._folded, self._rows_total = (0, 0, 0), 0
        self._router_kind = router
        self._router = dict(top_k=top_k, norm_topk_prob=bool(norm_topk_prob))
        if router == "noaux_tc":
            self._router.update(
                gamma=float(bias_update_rate),
                routed_scaling_factor=float(routed_scaling_factor),
                n_group=n_group, topk_group=topk_group)
        n, width = len(self._held), moe_intermediate_size

        def param(name, shape, **kw):
            setattr(self, name, self.params.get(name, shape=shape, **kw))

        def state(name, shape):
            param(name, shape, dtype=np.int32, init="zeros",
                  grad_req="null")

        param("gate_weight", (num_experts, hidden_size),
              init=weight_initializer)
        if router == "noaux_tc":
            state("e_score_correction_steps", (num_experts,))
        state("expert_counts", (num_experts,))
        state("held_rows", (1,))
        state("steps_seen", (1,))
        state("held_rows_sum", (1,))
        state("peak_count", (1,))
        state("overflow_steps", (1,))
        # (held, in, out): the grouped product's layout
        param("experts_gate_proj_weight", (n, hidden_size, width),
              init=weight_initializer)
        param("experts_up_proj_weight", (n, hidden_size, width),
              init=weight_initializer)
        param("experts_down_proj_weight", (n, width, hidden_size),
              init=weight_initializer)
        self.shared_experts = GatedMLP(
            hidden_size, width * n_shared_experts,
            weight_initializer=weight_initializer,
            prefix=self.prefix + "shared_experts_") if n_shared_experts else None
        if shared_expert_gate:
            param("shared_expert_gate_weight", (1, hidden_size),
                  init=weight_initializer)
        with _moe_lock:
            _moe_layers[:] = [r for r in _moe_layers if r() is not None]
            _moe_layers.append(weakref.ref(self))

    def buffer_rows(self, tokens):
        from ...ops.moe import buffer_rows

        return buffer_rows(tokens, self._top_k, len(self._held),
                           self._num_experts, self._capacity_factor)

    def route(self, F, tokens, gate_weight, steps):
        """The router on `tokens` (rows, hidden): (weights (rows, top_k)
        fp32, ids (rows, top_k) int32, counts (num_experts,) int32).
        `steps` is the `noaux_tc` router's selection bias, None for the
        softmax router, which has none."""
        if self._router_kind == "softmax":
            return F.contrib.softmax_topk_router(tokens, gate_weight,
                                                 **self._router)
        return F.contrib.noaux_tc_router(tokens, gate_weight, steps,
                                         **self._router)

    def hybrid_forward(self, F, x, gate_weight, expert_counts, held_rows,
                       steps_seen, held_rows_sum, peak_count, overflow_steps,
                       experts_gate_proj_weight, experts_up_proj_weight,
                       experts_down_proj_weight,
                       e_score_correction_steps=None,
                       shared_expert_gate_weight=None):
        from ... import autograd

        tokens = F.reshape(x, shape=(-3, 0))
        weights, ids, counts = self.route(F, tokens, gate_weight,
                                          e_score_correction_steps)
        routed, rows, overflow = F.contrib.moe_held_experts(
            tokens, ids, weights, experts_gate_proj_weight,
            experts_up_proj_weight, experts_down_proj_weight,
            held=self._held, num_experts=self._num_experts,
            capacity_factor=self._capacity_factor)
        out = F.reshape_like(routed, x)
        if self.shared_experts is not None:
            with jax.named_scope("moe_shared"):
                shared = self.shared_experts(x)
                if shared_expert_gate_weight is not None:
                    shared = shared * F.sigmoid(
                        _proj(F, x, shared_expert_gate_weight))
                out = out + shared
        if autograd.is_training():
            rows = F.reshape(rows, shape=(1,))
            if e_score_correction_steps is not None:
                self.e_score_correction_steps.set_data(
                    F.contrib.noaux_tc_bias_update(e_score_correction_steps,
                                                   counts))
            self.expert_counts.set_data(counts)
            self.held_rows.set_data(rows)
            self.steps_seen.set_data(steps_seen + 1)
            self.held_rows_sum.set_data(held_rows_sum + rows)
            self.peak_count.set_data(F.maximum(
                peak_count, F.max(counts, keepdims=True)))
            self.overflow_steps.set_data(
                overflow_steps + F.reshape(overflow, shape=(1,)))
        return out

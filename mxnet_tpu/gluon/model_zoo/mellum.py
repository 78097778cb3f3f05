"""Decoder language model of the Mellum 2 family (`model_type`
``mellum``), built from a config dict with the published key names:
grouped-query attention in every layer, over a sliding window or over
every earlier key as `layer_types` says layer by layer, each kind with
the rotary frequencies `rope_parameters` gives it (plain, or YaRN); per
head RMSNorm on q and k before the rotary; every layer's feed-forward a
mixture of experts with a softmax top-k router and no shared expert
(`mlp_layer_types` all ``sparse``); plain RMSNorm.

Keys beside the published ones: ``held_experts`` (ids of the routed
experts this chip holds; all by default), ``capacity_factor`` of
`nn.SparseMoE`, and ``initializer_range`` (std of the normal
initializer) or ``weight_initializer`` (an `Initializer` for the
projections, experts and embeddings in its place). ``vocab_size`` may be
a slice of the published vocabulary: the model then embeds and scores
that slice alone. ``num_hidden_layers`` may be fewer than `layer_types`
lists: the first of them are built. Not built: the multi-token-prediction
head, an auxiliary load-balancing loss, dense layers, attention biases.
"""
from __future__ import annotations

from ... import initializer as _init
from ..block import HybridBlock
from .. import nn

__all__ = ["Mellum", "MellumDecoderLayer", "mellum", "rope_scaling"]

_YARN = {"factor": "scaling_factor",
         "original_max_position_embeddings": "original_max_position",
         "beta_fast": "beta_fast", "beta_slow": "beta_slow",
         "attention_factor": "attention_factor"}


def rope_scaling(rope):
    """One `rope_parameters` entry as `rotary_embedding`'s keywords: None
    for ``default``, YaRN's five for ``yarn``."""
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return None
    if kind != "yarn":
        raise ValueError("rope_type %r is neither default nor yarn" % kind)
    return {ours: rope[theirs] for theirs, ours in _YARN.items()
            if theirs in rope}


class MellumDecoderLayer(HybridBlock):
    """``h = x + Attention(norm(x)); out = h + MoE(norm(h))``."""

    def __init__(self, config, layer_idx, weight_initializer=None,
                 **kwargs):
        super().__init__(**kwargs)
        hidden, eps = config["hidden_size"], config["rms_norm_eps"]
        kind = config["layer_types"][layer_idx]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError("layer type %r is not built" % kind)
        rope = config["rope_parameters"][kind]
        self.input_layernorm = nn.RMSNorm(
            hidden, eps, prefix=self.prefix + "input_layernorm_")
        self.self_attn = nn.GroupedQueryAttention(
            hidden, config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            rope_theta=rope["rope_theta"], rope_scaling=rope_scaling(rope),
            window=config["sliding_window"]
            if kind == "sliding_attention" else None,
            epsilon=eps, weight_initializer=weight_initializer,
            prefix=self.prefix + "self_attn_")
        self.post_attention_layernorm = nn.RMSNorm(
            hidden, eps, prefix=self.prefix + "post_attention_layernorm_")
        self.mlp = nn.SparseMoE(
            hidden, config["moe_intermediate_size"], config["num_experts"],
            held=config.get("held_experts"),
            top_k=config["num_experts_per_tok"],
            norm_topk_prob=config["norm_topk_prob"],
            capacity_factor=config.get("capacity_factor", 1.5),
            router="softmax", weight_initializer=weight_initializer,
            prefix=self.prefix + "mlp_")

    def hybrid_forward(self, F, x):
        h = x + self.self_attn(self.input_layernorm(x))
        return h + self.mlp(self.post_attention_layernorm(h))


class Mellum(HybridBlock):
    """tokens (batch, seq) integer ids -> logits (batch, seq, vocab)."""

    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        layers = config["num_hidden_layers"]
        if config.get("tie_word_embeddings") or config.get("attention_bias"):
            raise ValueError("tied embeddings and attention biases are not "
                             "built")
        if set(config["mlp_layer_types"][:layers]) != {"sparse"}:
            raise ValueError("only a mixture of experts in every layer is "
                             "built")
        if "sliding_attention" in config["layer_types"][:layers] and \
                not config.get("use_sliding_window", True):
            raise ValueError("sliding layers with use_sliding_window off")
        self.config = dict(config)
        init = config.get("weight_initializer") or _init.Normal(
            config.get("initializer_range", 0.02))
        hidden, vocab = config["hidden_size"], config["vocab_size"]
        self.embed_tokens = nn.Embedding(
            vocab, hidden, weight_initializer=init,
            prefix=self.prefix + "embed_tokens_")
        self.layers = nn.HybridSequential(prefix=self.prefix + "layers_")
        for i in range(layers):
            self.layers.add(MellumDecoderLayer(
                config, i, weight_initializer=init,
                prefix=self.prefix + "layers%d_" % i))
        self.norm = nn.RMSNorm(hidden, config["rms_norm_eps"],
                               prefix=self.prefix + "norm_")
        self.lm_head = nn.Dense(vocab, use_bias=False, flatten=False,
                                in_units=hidden,
                                weight_initializer=init,
                                prefix=self.prefix + "lm_head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.layers(
            self.embed_tokens(tokens))))


def mellum(config, **kwargs):
    """A `Mellum` from a ``mellum`` config dict."""
    return Mellum(config, **kwargs)

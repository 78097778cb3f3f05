"""Decoder language model of the Qwen3-Next family (`model_type`
``qwen3_next``), built from a config dict with the published key names:
of every `full_attention_interval` layers the last is gated
grouped-query attention and the others Gated DeltaNet linear attention;
every layer's feed-forward is a mixture of experts with a softmax top-k
router, routed experts and one gated shared expert; RMSNorm is
zero-centred (``1 + weight``).

Keys beside the published ones: ``held_experts`` (ids of the routed
experts this chip holds; all by default), ``capacity_factor`` of
`nn.SparseMoE`, ``gdn_chunk`` (tokens a chunk of the delta rule, 64) and
``initializer_range`` (std of the normal initializer) or
``weight_initializer`` (an `Initializer` for the projections, experts and
embeddings in its place). ``vocab_size`` may
be a slice of the published vocabulary: the model then embeds and scores
that slice alone. Not built: the multi-token-prediction head, the
auxiliary load-balancing loss, dense (`mlp_only_layers`) layers.
"""
from __future__ import annotations

from ... import initializer as _init
from ..block import HybridBlock
from .. import nn

__all__ = ["Qwen3Next", "Qwen3NextDecoderLayer", "qwen3_next",
           "is_attention_layer"]


def is_attention_layer(config, layer_idx):
    """Whether layer `layer_idx` is full attention (else Gated DeltaNet)."""
    return (layer_idx + 1) % config.get("full_attention_interval", 4) == 0


class Qwen3NextDecoderLayer(HybridBlock):
    """``h = x + Mixer(norm(x)); out = h + MoE(norm(h))``."""

    def __init__(self, config, layer_idx, weight_initializer=None,
                 **kwargs):
        super().__init__(**kwargs)
        hidden, eps = config["hidden_size"], config["rms_norm_eps"]
        self.input_layernorm = nn.RMSNorm(
            hidden, eps, zero_centered=True,
            prefix=self.prefix + "input_layernorm_")
        self._attention = is_attention_layer(config, layer_idx)
        if self._attention:
            self.self_attn = nn.GatedAttention(
                hidden, config["num_attention_heads"],
                config["num_key_value_heads"], config["head_dim"],
                rope_theta=config["rope_theta"],
                partial_rotary_factor=config.get("partial_rotary_factor",
                                                 1.0),
                epsilon=eps, weight_initializer=weight_initializer,
                prefix=self.prefix + "self_attn_")
        else:
            self.linear_attn = nn.GatedDeltaNet(
                hidden, config["linear_num_key_heads"],
                config["linear_num_value_heads"],
                config["linear_key_head_dim"],
                config["linear_value_head_dim"],
                conv_kernel=config["linear_conv_kernel_dim"], epsilon=eps,
                chunk=config.get("gdn_chunk", 64),
                weight_initializer=weight_initializer,
                prefix=self.prefix + "linear_attn_")
        self.post_attention_layernorm = nn.RMSNorm(
            hidden, eps, zero_centered=True,
            prefix=self.prefix + "post_attention_layernorm_")
        self.mlp = nn.SparseMoE(
            hidden, config["moe_intermediate_size"], config["num_experts"],
            held=config.get("held_experts"),
            top_k=config["num_experts_per_tok"],
            n_shared_experts=1 if config.get(
                "shared_expert_intermediate_size") else 0,
            norm_topk_prob=config["norm_topk_prob"],
            capacity_factor=config.get("capacity_factor", 1.5),
            router="softmax", shared_expert_gate=True,
            weight_initializer=weight_initializer,
            prefix=self.prefix + "mlp_")

    def hybrid_forward(self, F, x):
        mixer = self.self_attn if self._attention else self.linear_attn
        h = x + mixer(self.input_layernorm(x))
        return h + self.mlp(self.post_attention_layernorm(h))


class Qwen3Next(HybridBlock):
    """tokens (batch, seq) integer ids -> logits (batch, seq, vocab)."""

    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        if config.get("tie_word_embeddings") or config.get("rope_scaling"):
            raise ValueError("tied embeddings and rope scaling are not "
                             "built")
        if config.get("mlp_only_layers") or \
                config.get("decoder_sparse_step", 1) != 1:
            raise ValueError("only a mixture of experts in every layer is "
                             "built")
        shared = config.get("shared_expert_intermediate_size")
        if shared and shared != config["moe_intermediate_size"]:
            raise ValueError("the shared expert is built at the routed "
                             "experts' width")
        self.config = dict(config)
        init = config.get("weight_initializer") or _init.Normal(
            config.get("initializer_range", 0.02))
        hidden, vocab = config["hidden_size"], config["vocab_size"]
        self.embed_tokens = nn.Embedding(
            vocab, hidden, weight_initializer=init,
            prefix=self.prefix + "embed_tokens_")
        self.layers = nn.HybridSequential(prefix=self.prefix + "layers_")
        for i in range(config["num_hidden_layers"]):
            self.layers.add(Qwen3NextDecoderLayer(
                config, i, weight_initializer=init,
                prefix=self.prefix + "layers%d_" % i))
        self.norm = nn.RMSNorm(hidden, config["rms_norm_eps"],
                               zero_centered=True,
                               prefix=self.prefix + "norm_")
        self.lm_head = nn.Dense(vocab, use_bias=False, flatten=False,
                                in_units=hidden,
                                weight_initializer=init,
                                prefix=self.prefix + "lm_head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.layers(
            self.embed_tokens(tokens))))


def qwen3_next(config, **kwargs):
    """A `Qwen3Next` from a ``qwen3_next`` config dict."""
    return Qwen3Next(config, **kwargs)

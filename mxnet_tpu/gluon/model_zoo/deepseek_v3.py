"""Decoder language model of the DeepSeek-V3 family (`model_type`
``deepseek_v3``), built from a config dict with the published key names:
latent attention, leading dense layers, then sparse layers with a
`noaux_tc` router, routed and shared experts.

Keys beside the published ones: ``held_experts`` (ids of the routed
experts this chip holds; all by default), ``capacity_factor`` and
``bias_update_rate`` of `nn.SparseMoE` and ``initializer_range`` (std of
the normal initializer). ``vocab_size`` may be a slice of the published
vocabulary: the model then embeds and scores that slice alone.
"""
from __future__ import annotations

from ... import initializer as _init
from ..block import HybridBlock
from .. import nn

__all__ = ["DeepseekV3", "DeepseekV3DecoderLayer", "deepseek_v3"]


class DeepseekV3DecoderLayer(HybridBlock):
    """``h = x + Attn(norm(x)); out = h + FFN(norm(h))``; the FFN is a
    dense gated MLP in the first `first_k_dense_replace` layers and a
    `SparseMoE` after."""

    def __init__(self, config, layer_idx, weight_initializer=None,
                 **kwargs):
        super().__init__(**kwargs)
        hidden, eps = config["hidden_size"], config["rms_norm_eps"]
        self.input_layernorm = nn.RMSNorm(
            hidden, eps, prefix=self.prefix + "input_layernorm_")
        self.self_attn = nn.MLAttention(
            hidden, config["num_attention_heads"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            q_lora_rank=config.get("q_lora_rank"),
            rope_theta=config["rope_theta"],
            rope_interleave=config.get("rope_interleave", True),
            epsilon=eps, weight_initializer=weight_initializer,
            prefix=self.prefix + "self_attn_")
        self.post_attention_layernorm = nn.RMSNorm(
            hidden, eps, prefix=self.prefix + "post_attention_layernorm_")
        sparse = layer_idx >= config["first_k_dense_replace"] and \
            layer_idx % config.get("moe_layer_freq", 1) == 0
        if sparse:
            self.mlp = nn.SparseMoE(
                hidden, config["moe_intermediate_size"],
                config["n_routed_experts"],
                held=config.get("held_experts"),
                top_k=config["num_experts_per_tok"],
                n_shared_experts=config.get("n_shared_experts", 0),
                routed_scaling_factor=config["routed_scaling_factor"],
                norm_topk_prob=config["norm_topk_prob"],
                n_group=config["n_group"],
                topk_group=config["topk_group"],
                bias_update_rate=config.get("bias_update_rate", 1e-3),
                capacity_factor=config.get("capacity_factor", 1.5),
                weight_initializer=weight_initializer, prefix=self.prefix + "mlp_")
        else:
            self.mlp = nn.GatedMLP(
                hidden, config["intermediate_size"],
                weight_initializer=weight_initializer, prefix=self.prefix + "mlp_")

    def hybrid_forward(self, F, x):
        h = x + self.self_attn(self.input_layernorm(x))
        return h + self.mlp(self.post_attention_layernorm(h))


class DeepseekV3(HybridBlock):
    """tokens (batch, seq) integer ids -> logits (batch, seq, vocab)."""

    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        if config.get("scoring_func", "sigmoid") != "sigmoid" or \
                config.get("topk_method", "noaux_tc") != "noaux_tc":
            raise ValueError("only sigmoid scoring with noaux_tc is built")
        if config.get("tie_word_embeddings") or config.get("rope_scaling"):
            raise ValueError("tied embeddings and rope scaling are not "
                             "built")
        self.config = dict(config)
        init = _init.Normal(config.get("initializer_range", 0.02))
        hidden, vocab = config["hidden_size"], config["vocab_size"]
        self.embed_tokens = nn.Embedding(
            vocab, hidden, weight_initializer=init,
            prefix=self.prefix + "embed_tokens_")
        self.layers = nn.HybridSequential(prefix=self.prefix + "layers_")
        for i in range(config["num_hidden_layers"]):
            self.layers.add(DeepseekV3DecoderLayer(
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


def deepseek_v3(config, **kwargs):
    """A `DeepseekV3` from a ``deepseek_v3`` config dict."""
    return DeepseekV3(config, **kwargs)

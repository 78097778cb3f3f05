"""Word-level LSTM language model: Embedding -> dropout -> multi-layer
LSTM (dropout between layers) -> dropout -> Dense over the vocabulary.
Zaremba, Sutskever and Vinyals 2014 (arXiv:1409.2329, section 4.1), as
upstream's Gluon `word_language_model` example builds it, from the
program's Gluon blocks.

The configuration file gives `vocab`, `embed`, `hidden`, `num_layers`,
`dropout`, `bptt`. A batch is (N, bptt) token ids and the (N, bptt) next
tokens; the state starts at zero in every step (`TrainStep` takes no
carried state), listed under the file's `assumed`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def build(cfg, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn, rnn

    class WordLM(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.drop = nn.Dropout(cfg["dropout"])
                self.encoder = nn.Embedding(cfg["vocab"], cfg["embed"])
                self.rnn = rnn.LSTM(cfg["hidden"], cfg["num_layers"],
                                    layout="NTC", dropout=cfg["dropout"],
                                    input_size=cfg["embed"])
                self.decoder = nn.Dense(cfg["vocab"], flatten=False,
                                        in_units=cfg["hidden"])

        def hybrid_forward(self, F, tokens):
            emb = self.drop(self.encoder(tokens))
            return self.decoder(self.drop(self.rnn(emb)))

    mx.random.seed(seed)
    net = WordLM()
    net.initialize()
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def make_batch(cfg, rng, batch):
    """Token ids drawn from a Zipf-like distribution (rank r with weight
    1/(r+1)), as words are, so that the unigram frequencies alone let the
    loss fall. Float ids, as the program's Embedding and losses take them.
    """
    logits = -jnp.log1p(jnp.arange(cfg["vocab"], dtype=jnp.float32))
    seq = jax.random.categorical(rng, logits, shape=(batch, cfg["bptt"] + 1))
    seq = seq.astype(jnp.float32)
    return seq[:, :-1], seq[:, 1:]


def flops_per_item(cfg):
    """Operations one token's training step requires: two per
    multiply-accumulate of the LSTM's gate products and the decoder,
    forward once and backward twice. The embedding is a gather, the
    gates' elementwise work and the softmax are not counted."""
    h, macs = cfg["hidden"], 0
    for layer in range(cfg["num_layers"]):
        in_sz = cfg["embed"] if layer == 0 else h
        macs += 4 * h * (in_sz + h)
    macs += h * cfg["vocab"]
    return 3 * 2 * macs


def _find(params, *parts):
    """The one parameter whose name holds every part (the blocks' name
    counters differ from process to process, the parts do not)."""
    hits = [v for k, v in params.items() if all(p in k for p in parts)]
    if len(hits) != 1:
        raise ValueError("%d parameters match %r" % (len(hits), parts))
    return jnp.asarray(hits[0], jnp.float32)


def reference_forward(cfg, params, x, train=False):
    """Forward without dropout in plain fp32 jax.numpy at the highest
    matmul precision: logits (N, T, vocab). Gates in the order i, f, g, o;
    `train` changes nothing here (the comparison runs at dropout 0)."""
    del train
    with jax.default_matmul_precision("highest"):
        h_size = cfg["hidden"]
        seq = _find(params, "embedding", "_weight")[x.astype(jnp.int32)]
        seq = jnp.swapaxes(seq, 0, 1)                       # (T, N, E)
        for layer in range(cfg["num_layers"]):
            wi = _find(params, "l%d_i2h_weight" % layer)
            wh = _find(params, "l%d_h2h_weight" % layer)
            b = _find(params, "l%d_i2h_bias" % layer) \
                + _find(params, "l%d_h2h_bias" % layer)
            h = jnp.zeros((seq.shape[1], h_size), jnp.float32)
            c = jnp.zeros_like(h)
            outs = []
            for t in range(seq.shape[0]):
                gates = seq[t] @ wi.T + h @ wh.T + b
                i, f, g, o = jnp.split(gates, 4, axis=-1)
                c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
                h = jax.nn.sigmoid(o) * jnp.tanh(c)
                outs.append(h)
            seq = jnp.stack(outs)
        out = jnp.swapaxes(seq, 0, 1)                       # (N, T, H)
        return out @ _find(params, "dense", "_weight").T \
            + _find(params, "dense", "_bias")


def reference_loss(logits, y):
    """Mean softmax cross-entropy over every position."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    idx = y.astype(jnp.int32)[..., None]
    return -jnp.mean(jnp.take_along_axis(logp, idx, axis=-1))

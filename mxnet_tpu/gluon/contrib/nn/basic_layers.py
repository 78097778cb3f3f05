"""Contrib basic layers.

Reference: python/mxnet/gluon/contrib/nn/basic_layers.py (Concurrent,
HybridConcurrent, Identity, SparseEmbedding, SyncBatchNorm).
"""
from __future__ import annotations

from .... import ndarray as nd
from ...block import HybridBlock
from ...nn import Sequential, HybridSequential, BatchNorm

__all__ = ["Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "SyncBatchNorm"]


class Concurrent(Sequential):
    """Feed the input to every child, concatenate outputs along `axis`
    (reference basic_layers.py:Concurrent — the Inception-branch
    combinator)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        out = [block(x) for block in self._children.values()]
        return nd.concat(*out, dim=self.axis)


class HybridConcurrent(HybridSequential):
    """Hybridizable Concurrent (reference basic_layers.py:
    HybridConcurrent). `forward` is overridden directly — the Sequential
    mixin's chaining forward would otherwise shadow the hybrid path —
    and traces into one executable under hybridize() like any block."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        out = [block(x) for block in self._children.values()]
        return nd.concat(*out, dim=self.axis)


class Identity(HybridBlock):
    """Pass-through (reference basic_layers.py:Identity — useful in
    Concurrent branches)."""

    def hybrid_forward(self, F, x):
        return x


class SparseEmbedding(HybridBlock):
    """Embedding whose gradient is row_sparse (reference
    basic_layers.py:SparseEmbedding). On TPU the lookup is the same
    XLA gather as Embedding; the row_sparse grad_stype matters for the
    kvstore path (pull only touched rows, kvstore_dist.row_sparse_pull).
    """

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self.weight = self.params.get(
            "weight", shape=(input_dim, output_dim), init=weight_initializer,
            dtype=dtype, grad_stype="row_sparse")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)

    def __repr__(self):
        return "SparseEmbedding(%d -> %d)" % (self._input_dim,
                                              self._output_dim)


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm (reference
    basic_layers.py:SyncBatchNorm over src/operator/contrib/sync_batch_norm).

    TPU-native: under SPMD (`mxnet_tpu.parallel.TrainStep` /
    `pjit`-traced steps) the batch axis is sharded over the mesh, and
    BatchNorm's training branch takes its statistics as two plain sums
    over the batch, `sum(x)` and `sum(x*x)` forward, `sum(dy)` and
    `sum(dy*xhat)` backward: XLA lowers each pair to one all-reduce over
    ICI, so the statistics are the global batch's with no extra
    machinery, which is the entire point of the reference's hand-written
    key-synchronized implementation. `num_devices` is accepted for API
    parity and unused.
    """

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, **kwargs):
        super().__init__(axis=1, momentum=momentum, epsilon=epsilon,
                         in_channels=in_channels, **kwargs)
        self._num_devices = num_devices

"""DLRM-style recsys model — the giant-embedding ladder workload.

The reference serves this shape of model (dense MLP + many multi-hot
sparse fields + dot interaction) from its host parameter-server tier;
here the sparse fields share ONE mesh-sharded table
(:class:`~paddle_tpu.distributed.embedding.ShardedEmbedding`, vocab
row-sharded over ``(fsdp, tp)``) so the capacity lives on chip. The
model doubles as the dense-path serving fixture:
:meth:`DLRM.serve_dense` scores a flat id batch in one forward, which
``PagedEngine`` runs behind the Router without any KV cache.

Architecture (Naumov et al., arXiv:1906.00091):

* bottom MLP over the dense features -> a ``D``-dim dense vector,
* per-field ``sum``-pooled embedding bags over the shared table
  (``ids`` is ``(B, F, L)`` multi-hot, pooled to ``(B, F, D)``),
* dot interaction: the full flattened Gram matrix of the ``F + 1``
  ``D``-dim vectors (fixed shape — no triangular gather needed),
* top MLP over ``[dense_vec, interactions]`` -> one CTR logit.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

from .. import nn
from .. import ops
from ..distributed.embedding import ShardedEmbedding
from ..nn import functional as F


@dataclass
class DLRMConfig:
    num_embeddings: int = 100_000     #: shared-table vocab (all fields)
    embedding_dim: int = 16
    n_dense: int = 4                  #: dense (continuous) features
    n_sparse: int = 8                 #: sparse fields F
    bag_size: int = 4                 #: multi-hot ids per field L
    bottom_mlp: Tuple[int, ...] = (32,)   #: hidden widths (out is D)
    top_mlp: Tuple[int, ...] = (64,)      #: hidden widths (out is 1)
    #: mesh axes the table's vocab dim shards over (axes missing from
    #: the mesh, or of size 1, are skipped)
    embedding_axes: Tuple[str, ...] = ("fsdp", "tp")
    dedup: bool = True                #: dedup ids before the exchange
    dedup_capacity: Optional[int] = None

    def __post_init__(self):
        if self.n_sparse < 1 or self.bag_size < 1:
            raise ValueError("n_sparse and bag_size must be >= 1")


def _mlp(widths: Sequence[int], sigmoid_last: bool = False) -> nn.Layer:
    layers = []
    for i in range(len(widths) - 1):
        layers.append(nn.Linear(widths[i], widths[i + 1]))
        last = i == len(widths) - 2
        layers.append(nn.Sigmoid() if (last and sigmoid_last)
                      else nn.ReLU())
    if not sigmoid_last:
        layers = layers[:-1]          # raw output on the last layer
    return nn.Sequential(*layers)


class DLRM(nn.Layer):
    """DLRM over one shared :class:`ShardedEmbedding` table.

    Pass ``mesh`` (or call :meth:`shard_` later) to row-shard the table
    over ``cfg.embedding_axes``; without a mesh the table is replicated
    — that is the loss-parity baseline the sharded table is tested
    against.
    """

    def __init__(self, cfg: DLRMConfig, mesh=None):
        super().__init__()
        self.cfg = cfg
        d = cfg.embedding_dim
        self.embedding = ShardedEmbedding(
            cfg.num_embeddings, d, mesh=mesh,
            axes=cfg.embedding_axes, dedup=cfg.dedup,
            dedup_capacity=cfg.dedup_capacity)
        self.bottom = _mlp((cfg.n_dense,) + tuple(cfg.bottom_mlp) + (d,))
        n_vec = cfg.n_sparse + 1
        top_in = d + n_vec * n_vec    # dense vec + flat Gram matrix
        self.top = _mlp((top_in,) + tuple(cfg.top_mlp) + (1,))
        #: flat-id width PagedEngine's dense path pads prompts to
        self.serve_dense_width = cfg.n_sparse * cfg.bag_size

    def shard_(self, mesh=None) -> "DLRM":
        self.embedding.shard_(mesh)
        return self

    def forward(self, dense, ids):
        """``dense``: (B, n_dense) float; ``ids``: (B, F, L) int.
        Returns the (B,) CTR logit."""
        cfg = self.cfg
        x = self.bottom(dense)                        # (B, D)
        pooled = self.embedding.bag(ids)              # (B, F, D)
        z = ops.concat(
            [ops.reshape(x, [-1, 1, cfg.embedding_dim]), pooled],
            axis=1)                                   # (B, F+1, D)
        gram = ops.matmul(z, ops.transpose(z, [0, 2, 1]))
        n_vec = cfg.n_sparse + 1
        feats = ops.concat(
            [x, ops.reshape(gram, [-1, n_vec * n_vec])], axis=1)
        logit = self.top(feats)                       # (B, 1)
        return ops.reshape(logit, [-1])

    def loss(self, dense, ids, labels):
        """Mean BCE-with-logits over the batch (the rung's parity
        metric)."""
        return F.binary_cross_entropy_with_logits(
            self.forward(dense, ids), labels)

    def serve_dense(self, flat_ids):
        """One-forward scoring for the serving dense path:
        ``flat_ids`` is (B, F*L) int (each row a request's ids padded
        to :attr:`serve_dense_width`), dense features are zero, and the
        result is the (B,) sigmoid click score."""
        cfg = self.cfg
        ids = ops.reshape(flat_ids, [-1, cfg.n_sparse, cfg.bag_size])
        b = ids.shape[0]
        dense = ops.zeros([b, cfg.n_dense], dtype="float32")
        return F.sigmoid(self.forward(dense, ids))


def dlrm_tiny(**kw) -> DLRMConfig:
    """Smoke-scale config (tests, the serving fixture)."""
    kw.setdefault("num_embeddings", 512)
    kw.setdefault("embedding_dim", 8)
    kw.setdefault("n_sparse", 4)
    kw.setdefault("bag_size", 2)
    kw.setdefault("bottom_mlp", (16,))
    kw.setdefault("top_mlp", (16,))
    return DLRMConfig(**kw)

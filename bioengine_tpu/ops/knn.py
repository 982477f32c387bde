"""Exact k-nearest-neighbour search on TPU.

The reference delegates similarity search to FAISS on CPU
(ref apps/cell-image-search/index_manager.py:36-183; published numbers:
<5 ms FlatIP at 100K vectors, <80 ms IVFPQ at 58M). On TPU, exact
inner-product search is a tall matmul — the MXU's best case — so the
flat path needs no quantization up to HBM capacity (bf16 corpus:
~10M x 768 vectors per chip), and shards across a mesh axis for more:
each device scores its corpus shard and a tiny (k-sized) all-gather
merges the per-shard top-k.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@functools.partial(jax.jit, static_argnames=("k",))
def topk_inner_product(
    corpus: jax.Array, queries: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k by inner product. corpus (N, d), queries (Q, d) →
    (scores (Q, k), indices (Q, k)). Matmul in the corpus dtype
    (bf16 doubles on-chip capacity), scores accumulated in f32."""
    scores = jax.lax.dot_general(
        queries.astype(corpus.dtype),
        corpus,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, N)
    return jax.lax.top_k(scores, k)


class ShardedKnnIndex:
    """Flat inner-product index with the corpus sharded over a mesh axis.

    Per-device partial top-k then a host-side merge of k*n_shards
    candidates — the collective payload is O(Q*k), not O(N).
    """

    def __init__(
        self,
        corpus: np.ndarray,
        mesh: Optional[Mesh] = None,
        axis: str = "dp",
        dtype=jnp.bfloat16,
    ):
        self.n, self.d = corpus.shape
        self.mesh = mesh
        self.axis = axis
        if mesh is not None:
            n_shards = mesh.shape[axis]
            pad = (-self.n) % n_shards
            self._pad = pad
            padded = np.pad(corpus, ((0, pad), (0, 0)))
            sharding = NamedSharding(mesh, P(axis, None))
            self.corpus = jax.device_put(
                jnp.asarray(padded, dtype), sharding
            )
        else:
            self._pad = 0
            self.corpus = jnp.asarray(corpus, dtype)

    def search(
        self, queries: np.ndarray, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """→ (scores (Q, k), indices (Q, k)) as numpy, global ids."""
        k = min(k, self.n)
        q = jnp.asarray(queries, jnp.float32)
        if q.ndim == 1:
            q = q[None]
        if self.mesh is None:
            s, i = topk_inner_product(self.corpus, q, k)
            return np.asarray(s), np.asarray(i)

        n_shards = self.mesh.shape[self.axis]
        shard_n = self.corpus.shape[0] // n_shards
        k_local = min(k, shard_n)

        @functools.partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(P(self.axis, None), P()),
            out_specs=(P(self.axis), P(self.axis)),
        )
        def _search(corpus_blk, q_blk):
            s, i = topk_inner_product(corpus_blk, q_blk, k_local)
            return s[None], i[None]  # leading shard axis

        s, i = _search(self.corpus, q)  # (n_shards, Q, k)
        s, i = np.asarray(s), np.asarray(i)
        # globalize ids and merge the n_shards * k candidates per query
        offsets = (np.arange(n_shards) * shard_n)[:, None, None]
        i = i + offsets
        s = np.moveaxis(s, 0, 1).reshape(q.shape[0], -1)  # (Q, n_shards*k)
        i = np.moveaxis(i, 0, 1).reshape(q.shape[0], -1)
        # padded rows score over zero-vectors; mask them out
        valid = i < self.n
        s = np.where(valid, s, -np.inf)
        order = np.argsort(-s, axis=1)[:, :k]
        rows = np.arange(q.shape[0])[:, None]
        return s[rows, order], i[rows, order]

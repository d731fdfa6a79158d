"""Exact (flat) search — JAX-accelerated blocked matmul top-k.

Used for ground truth, re-ranking, and as the 'flat' index kind. The blocked
formulation is the same tiling the Pallas distance kernel uses on TPU; on CPU
it keeps peak memory at block_rows × n instead of n × n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.base import SearchResult, VectorIndex
from repro.kernels.common import default_interpret


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_scores(data: jnp.ndarray, qvecs: jnp.ndarray, k: int):
    scores = qvecs @ data.T  # (Q, N)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx


def batch_exact_topk(data: np.ndarray, qvecs: np.ndarray, k: int,
                     block_rows: int = 8192,
                     use_kernel: bool | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k for a batch of queries over ``data`` (N, d).

    Returns (ids (Q, k), scores (Q, k)). Blocked over N with a running
    tournament merge so memory stays bounded.

    On an actual TPU backend (``use_kernel`` defaults to running on
    non-interpret backends) the whole scan is instead ONE
    ``streaming_fused_scan`` dispatch — distance + online top-k with no
    materialized score matrix, so N is not capped by the score block. The
    blocked XLA formulation stays the CPU/interpret default (interpret-mode
    Pallas executes its grid in Python).
    """
    data = np.asarray(data, dtype=np.float32)
    qvecs = np.atleast_2d(np.asarray(qvecs, dtype=np.float32))
    if use_kernel is None:
        use_kernel = not default_interpret()
    if use_kernel:
        from repro.kernels.streaming.ops import streaming_fused_scan
        vals, idx, _ = streaming_fused_scan(
            jnp.asarray(qvecs), jnp.asarray(data),
            k=min(k, data.shape[0]))
        return np.asarray(idx, dtype=np.int64), np.asarray(vals)
    n = data.shape[0]
    k = min(k, n)
    best_scores = None
    best_ids = None
    for start in range(0, n, block_rows):
        block = data[start:start + block_rows]
        kb = min(k, block.shape[0])
        vals, idx = _topk_scores(jnp.asarray(block), jnp.asarray(qvecs), kb)
        vals = np.asarray(vals)
        ids = np.asarray(idx) + start
        if best_scores is None:
            best_scores, best_ids = vals, ids
        else:
            cat_s = np.concatenate([best_scores, vals], axis=1)
            cat_i = np.concatenate([best_ids, ids], axis=1)
            sel = np.argsort(-cat_s, axis=1, kind="stable")[:, :k]
            best_scores = np.take_along_axis(cat_s, sel, axis=1)
            best_ids = np.take_along_axis(cat_i, sel, axis=1)
    return best_ids, best_scores


class FlatIndex(VectorIndex):
    """Exact scan; numDist = N (every row scored)."""

    kind = "flat"
    max_degree = 0

    def search(self, qvec: np.ndarray, ek: int) -> SearchResult:
        ids, scores = batch_exact_topk(self.data, qvec[None, :], ek)
        return SearchResult(ids=ids[0], scores=scores[0], num_dist=self.n)

    def storage_bytes(self, edge_bytes: int = 4) -> int:
        return 0  # no index structure beyond the vectors themselves

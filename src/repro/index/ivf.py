"""IVF-Flat — the TPU-native index kind (see DESIGN.md §3).

k-means partitions (Lloyd in JAX); a search probes the nprobe nearest
partitions and scores every row in them: a dense gather + matmul, which on
TPU maps onto the Pallas fused distance kernel (MXU) + blockwise top-k.
numDist = n_partitions (centroid pass) + rows scanned, exactly MINT's proxy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.base import SearchResult, VectorIndex
from repro.kernels.common import default_interpret


def _scan_gathered(sub: np.ndarray, qvec: np.ndarray, ek: int,
                   use_kernel: bool | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Score a gathered probe-union (m, d) against one query and return the
    local (positions, scores) of the ek best, best first. On a real TPU
    backend this is ONE ``streaming_fused_scan`` dispatch (distance +
    online top-k, no (1, m) score vector round-tripped through host numpy);
    on CPU/interpret the numpy argpartition path is kept — it is faster
    than a Python-interpreted Pallas grid and bit-stable for the tests.

    The kernel sees the union padded to a power-of-two row bucket with the
    true row count as its traced ``valid_n``: probe unions differ in size
    per query and per ek, and an unbucketed shape compiles once each."""
    if use_kernel is None:
        use_kernel = not default_interpret()
    m = sub.shape[0]
    ek = min(ek, m)
    if use_kernel:
        from repro.kernels.streaming.ops import streaming_fused_scan
        padded = np.zeros((max(128, 1 << (m - 1).bit_length()), sub.shape[1]),
                          dtype=np.float32)
        padded[:m] = sub
        vals, idx, _ = streaming_fused_scan(
            jnp.asarray(qvec[None, :]), jnp.asarray(padded), k=ek, valid_n=m)
        return np.asarray(idx[0], dtype=np.int64), np.asarray(vals[0])
    scores = sub @ qvec
    part = np.argpartition(-scores, ek - 1)[:ek]
    order = np.argsort(-scores[part], kind="stable")
    sel = part[order]
    return sel.astype(np.int64), scores[sel]


@functools.partial(jax.jit, static_argnames=("n_iters",))
def _lloyd(data: jnp.ndarray, init: jnp.ndarray, n_iters: int = 8):
    def step(centroids, _):
        # cosine k-means: assign to most-similar centroid, re-normalize means
        sims = data @ centroids.T
        assign = jnp.argmax(sims, axis=1)
        onehot = jax.nn.one_hot(assign, centroids.shape[0], dtype=data.dtype)
        sums = onehot.T @ data
        counts = onehot.sum(axis=0)[:, None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), centroids)
        norm = jnp.linalg.norm(new, axis=1, keepdims=True)
        return new / jnp.maximum(norm, 1e-12), None

    centroids, _ = jax.lax.scan(step, init, None, length=n_iters)
    sims = data @ centroids.T
    return centroids, jnp.argmax(sims, axis=1)


class IVFFlatIndex(VectorIndex):
    kind = "ivf"
    max_degree = 0

    def __init__(self, data: np.ndarray, n_lists: int | None = None,
                 n_iters: int = 8, seed: int = 0):
        super().__init__(data)
        if n_lists is None:
            n_lists = max(4, int(np.sqrt(self.n)))
        n_lists = min(n_lists, self.n)
        rng = np.random.default_rng(seed)
        init = self.data[rng.choice(self.n, size=n_lists, replace=False)]
        centroids, assign = _lloyd(jnp.asarray(self.data), jnp.asarray(init), n_iters)
        self.centroids = np.asarray(centroids)
        assign = np.asarray(assign)
        order = np.argsort(assign, kind="stable")
        self.row_ids = order.astype(np.int64)
        sorted_assign = assign[order]
        self.offsets = np.searchsorted(sorted_assign, np.arange(n_lists + 1))
        self.n_lists = n_lists

    def _nprobe_for(self, ek: int, overscan: float = 4.0) -> int:
        avg = max(self.n / self.n_lists, 1.0)
        return int(np.clip(np.ceil(overscan * ek / avg), 1, self.n_lists))

    def search(self, qvec: np.ndarray, ek: int, nprobe: int | None = None) -> SearchResult:
        qvec = np.asarray(qvec, dtype=np.float32)
        csims = self.centroids @ qvec
        num_dist = self.n_lists
        nprobe = nprobe if nprobe is not None else self._nprobe_for(ek)
        probe = np.argsort(-csims, kind="stable")[:nprobe]
        rows = np.concatenate([
            self.row_ids[self.offsets[p]:self.offsets[p + 1]] for p in probe
        ]) if nprobe else np.empty(0, dtype=np.int64)
        if rows.shape[0] == 0:
            return SearchResult(np.empty(0, np.int64), np.empty(0, np.float32), num_dist)
        num_dist += int(rows.shape[0])
        sel, scores = _scan_gathered(self.data[rows], qvec, ek)
        return SearchResult(ids=rows[sel], scores=scores, num_dist=num_dist)

    def storage_bytes(self, edge_bytes: int = 4) -> int:
        # centroid table + inverted-list row ids
        return int(self.centroids.size * 4 + self.row_ids.size * edge_bytes)

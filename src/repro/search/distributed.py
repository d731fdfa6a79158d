"""Distributed vector-search serving (DESIGN.md §3, §5).

The database rows are sharded across the data-parallel axis; every device
scans its shard with one XLA matmul + ``lax.top_k`` (f32 at ``EXACT``
precision, masked by ``valid_n`` and an optional sharded ``bad`` row
bitmap) and only the per-shard top-k (k values + global ids) crosses the
network — a tournament merge, never raw rows.

``search_step`` is jit/lower-able with ShapeDtypeStructs, so the same
multi-pod dry-run methodology applies to the serving plane
(``launch/search_dryrun.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.common import EXACT
from repro.kernels.topk.kernel import NEG_INF


def _local_scan(db_shard, qvecs, k, shard_offset, valid_n=None, bad=None):
    scores = jnp.matmul(qvecs, db_shard.T, precision=EXACT)  # (Q, N_local)
    masked = None
    if valid_n is not None:
        # rows at global index >= valid_n are column-store padding
        gids = shard_offset + jnp.arange(db_shard.shape[0])
        masked = (gids >= valid_n)[None, :]
    if bad is not None:
        # per-row bad mask (tombstones ∪ ¬predicate), sharded like db rows
        b = bad.astype(bool)[None, :]
        masked = b if masked is None else (masked | b)
    if masked is not None:
        scores = jnp.where(masked, NEG_INF, scores)
    vals, idx = jax.lax.top_k(scores, k)
    idx = idx + shard_offset
    if masked is not None:
        # masked tail slots report id 0 (same contract as the fused
        # kernels) so downstream stable-id gathers never index padding
        idx = jnp.where(vals <= NEG_INF / 2, 0, idx)
    return vals, idx


def make_search_step(mesh: Mesh, k: int, axis: str = "data",
                     valid_n: int | None = None, masked: bool = False):
    """Returns search_step(db_shard_view, qvecs) -> (vals (Q,k), ids (Q,k)).

    db is laid out (N, d) sharded on axis 0 over ``axis``; queries are
    replicated. The merge all-gathers only (Q, k) candidates per shard.
    ``valid_n`` marks trailing rows as column-store padding (masked out),
    so the serving engine can scan pre-padded device-resident columns.
    ``masked=True`` adds a third operand ``bad`` — a (N,) row bitmap
    (True/1 = tombstoned or filtered out), sharded exactly like the rows —
    so mesh cells mask in-cell instead of over-fetching past dead rows and
    score-killing them on the host. Bad rows come back at NEG_INF with id
    0, matching the fused-kernel contract.
    """
    n_shards = mesh.shape[axis]

    def step(db, qvecs, bad=None):
        def shard_fn(db_local, q_local, *rest):
            rank = jax.lax.axis_index(axis)
            n_local = db_local.shape[0]
            vals, ids = _local_scan(db_local, q_local, min(k, db_local.shape[0]),
                                    rank * n_local, valid_n=valid_n,
                                    bad=rest[0] if rest else None)
            # tournament merge: gather candidates only
            all_vals = jax.lax.all_gather(vals, axis)   # (S, Q, k)
            all_ids = jax.lax.all_gather(ids, axis)
            S, Q, kk = all_vals.shape
            flat_v = jnp.moveaxis(all_vals, 0, 1).reshape(Q, S * kk)
            flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(Q, S * kk)
            best_v, pos = jax.lax.top_k(flat_v, k)
            best_i = jnp.take_along_axis(flat_i, pos, axis=1)
            return best_v, best_i

        spec_db = P(axis, None)
        spec_q = P()
        in_specs = (spec_db, spec_q) + ((P(axis),) if masked else ())
        args = (db, qvecs) + ((bad,) if masked else ())
        # outputs are bitwise-identical on every shard after the gather +
        # top_k, but replication-rule inference can't see that — disable the check
        return jax.shard_map(shard_fn, mesh=mesh,
                             in_specs=in_specs,
                             out_specs=(P(), P()),
                             check_vma=False)(*args)

    # one compiled program per step: called eagerly, shard_map would
    # dispatch (and compile) its body op by op
    return jax.jit(step)


def distributed_rerank(mesh: Mesh, db, cand_ids, qvec, k: int,
                       axis: str = "data"):
    """Full-score rerank of candidate ids against a sharded database:
    each shard scores the candidates it owns; a masked all-reduce merges."""
    n_shards = mesh.shape[axis]

    def shard_fn(db_local, ids, q):
        rank = jax.lax.axis_index(axis)
        n_local = db_local.shape[0]
        local = ids - rank * n_local
        mine = (local >= 0) & (local < n_local)
        rows = db_local[jnp.clip(local, 0, n_local - 1)]
        scores = jnp.matmul(rows, q, precision=EXACT)
        scores = jnp.where(mine, scores, 0.0)
        scores = jax.lax.psum(scores, axis)  # exactly one shard owns each id
        return scores

    scores = jax.shard_map(shard_fn, mesh=mesh,
                           in_specs=(P(axis, None), P(), P()),
                           out_specs=P(), check_vma=False)(db, cand_ids, qvec)
    vals, pos = jax.lax.top_k(scores, k)
    return vals, cand_ids[pos]

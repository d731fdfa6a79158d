"""Streaming fused-scan Pallas kernel: distance + online top-k, one launch.

The two-pass design (``kernels/distance`` then ``kernels/topk``) writes the
full (B, N) score matrix to HBM, re-reads it for masking, and re-reads it
again for top-k — O(B·N) score bytes of HBM traffic on the hottest path in
the repo, and a hard cap on table size per dispatch. This kernel is the
memory-efficient-attention trick applied to search: stream row tiles of the
database through VMEM, compute each tile's scores on the MXU, apply padding
and tombstone masks in-register, and fold the tile into a running per-query
(k-best values, ids) buffer that lives in the revisited output blocks. The
score matrix never exists; HBM score traffic drops to O(B·k).

Grid: (B/bm, n_base_tiles + n_delta_tiles, d/bk), row-tile and d axes
sequential. A second (delta) row source rides the SAME grid: tiles past
``n_base_tiles`` read the delta operand instead of the base via piecewise
BlockSpec index maps (the inactive operand's block index is clamped, so the
pipeline never re-fetches it), which is how ``BatchEngine`` merges base +
delta-segment candidates in ONE launch instead of two dispatches + a host
merge. Delta rows report combined ids offset by the padded base row count.

Masking is in-register: per-source "bad" row masks (padding beyond
``valid_n`` ∪ tombstones) arrive as (1, N) f32 0/1 operands built by the
jitted wrapper from a TRACED ``valid_n`` — no per-table-size recompiles —
and masked columns are scored NEG_INF before the fold, so they can never
claim a top-k slot (strict-improvement fold + NEG_INF buffer init).

Tie-break contract: the fold extracts block maxima first-match-wins
(lowest column id within a tile) and only a STRICT improvement replaces a
buffer slot, so for distinct scores the result is bit-identical to the
two-pass oracle; equal-score ties follow ascending fold order exactly like
the two-pass top-k kernel. Sentinel ties (masked rows) never enter the
buffer in either path. The wrapper's final ``lax.top_k`` ordering pass is
identical to the two-pass wrapper's.

The fold runs only the rounds a tile can win: min(k, the most scores any
real query row has above its buffer's k-th best). Rows whose buffer the
tile cannot change cost their matmul, epilogue and that count, no rounds;
the result is the same as k rounds, ties included.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import EXACT
from repro.kernels.topk.kernel import NEG_INF


def streaming_kernel(*refs, n_base_tiles: int, n_k_blocks: int, bn: int,
                     k: int, metric: str, delta_id_offset: int,
                     has_delta: bool, n_queries: int):
    """Kernel body. Operand order (delta refs only when ``has_delta``):
    q, base, [delta], qsq, basesq, [deltasq], base_bad, [delta_bad] ->
    (vals, idxs, rounds) outputs + one (bm, bn) f32 accumulator scratch.
    ``rounds`` is an (8, 128) int32 block per query block, every element
    the fold rounds the block has run so far."""
    if has_delta:
        (q_ref, db_ref, dlt_ref, qsq_ref, bsq_ref, dsq_ref,
         bbad_ref, dbad_ref, vals_ref, idxs_ref, rounds_ref, acc_ref) = refs
    else:
        (q_ref, db_ref, qsq_ref, bsq_ref, bbad_ref,
         vals_ref, idxs_ref, rounds_ref, acc_ref) = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when((j == 0) & (kb == 0))
    def _init_topk():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idxs_ref[...] = jnp.zeros_like(idxs_ref)
        rounds_ref[...] = jnp.zeros_like(rounds_ref)

    @pl.when(kb == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    in_base = j < n_base_tiles
    q = q_ref[...].astype(jnp.float32)
    db = db_ref[...].astype(jnp.float32)
    if has_delta:
        db = jnp.where(in_base, db, dlt_ref[...].astype(jnp.float32))
    acc_ref[...] += jax.lax.dot_general(
        q, db, (((1,), (1,)), ((), ())), precision=EXACT,
        preferred_element_type=jnp.float32)

    @pl.when(kb == n_k_blocks - 1)
    def _fold_tile():
        acc = acc_ref[...]
        if has_delta:
            dbsq = jnp.where(in_base, bsq_ref[...], dsq_ref[...])
            bad = jnp.where(in_base, bbad_ref[...], dbad_ref[...])
        else:
            dbsq = bsq_ref[...]
            bad = bbad_ref[...]
        # metric epilogue — identical formulas to kernels/distance
        if metric == "dot":
            s = acc
        elif metric == "cosine":
            qn = jnp.sqrt(jnp.maximum(qsq_ref[...], 1e-24))   # (bm, 1)
            dn = jnp.sqrt(jnp.maximum(dbsq, 1e-24))           # (1, bn)
            s = acc / (qn * dn)
        else:  # l2 -> negative squared distance
            s = -(qsq_ref[...] - 2.0 * acc + dbsq)
        s = jnp.where(bad > 0, NEG_INF, s)                    # in-register mask

        bm = s.shape[0]
        iota_bn = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
        # combined physical id: base tiles count from 0, delta tiles from
        # delta_id_offset (= padded base rows; masked base padding can
        # never collide — it never claims a slot)
        local_j = jnp.where(in_base, j, j - n_base_tiles)
        offset = jnp.where(in_base, 0, delta_id_offset)
        col_ids = offset + local_j * bn + iota_bn

        def fold(_, carry):
            s, vals, idxs = carry
            m = jnp.max(s, axis=1)                            # (bm,)
            am = jnp.argmax(s, axis=1)                        # first max wins
            sel = iota_bn == am[:, None]
            cid = jnp.sum(jnp.where(sel, col_ids, 0), axis=1)
            vmin = jnp.min(vals, axis=1)
            pmin = jnp.argmin(vals, axis=1)
            improve = m > vmin                                # strict only
            hit = improve[:, None] & (iota_k == pmin[:, None])
            vals = jnp.where(hit, m[:, None], vals)
            idxs = jnp.where(hit, cid[:, None], idxs)
            s = jnp.where(sel, NEG_INF, s)
            return s, vals, idxs

        # Gate: a round improves a row only by consuming a score above the
        # row's k-th best as the tile begins (maxima come out descending,
        # vmin only rises), so min(k, that count) rounds do all k would.
        # Padding rows past n_queries are left out: under l2 their scores
        # vary and would hold the gate open for nothing.
        vals = vals_ref[...]
        thr = jnp.min(vals, axis=1, keepdims=True)            # (bm, 1)
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        wins = (s > thr) & (row < n_queries)
        cnt = jnp.sum(wins.astype(jnp.int32), axis=1)
        trip = jnp.minimum(jnp.max(cnt), k)
        rounds_ref[...] += trip
        _, vals, idxs = jax.lax.fori_loop(
            0, trip, fold, (s, vals, idxs_ref[...]))
        vals_ref[...] = vals
        idxs_ref[...] = idxs

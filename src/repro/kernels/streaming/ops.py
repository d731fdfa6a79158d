"""Jitted public op for the streaming fused scan (one launch, no score
matrix). See ``kernels/streaming/kernel.py`` for the kernel itself."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, cdiv, default_interpret, pad_to
from repro.kernels.streaming.kernel import streaming_kernel

# What a scan's blocks may take of a v5e core's 128 MiB of VMEM, and the
# limit the compiler is given (its default scoped limit is 16 MiB).
VMEM_BUDGET = 32 << 20
VMEM_LIMIT = 96 << 20
# Scores of a (bm, bn) tile. The fold's rounds sweep the whole tile and the
# compiled kernel grows with it: every one of the ~128 scan programs a
# server compiles (vid width × batch) sits in HBM. At 1 << 16 they held
# 64 MB more than 128 × 128 tiles (1.2 % of the cell's peak) for a scan at
# most ~4 % faster (v5e probe).
MAX_TILE = 1 << 15


def _round_up(n: int, m: int) -> int:
    return cdiv(n, m) * m


def _vmem_bytes(bm: int, bn: int, dp: int, k: int, itemsize: int,
                sources: int) -> int:
    """VMEM of one scan's blocks: double-buffered row tiles of each source
    and the query block; the (1, bn) norm and mask rows (8 sublanes each);
    the (bm, bn) accumulator with the epilogue's and the fold's tile-sized
    values; the (bm, k) outputs, double-buffered, and the fold's carry."""
    rows = 2 * sources * bn * dp * itemsize + 2 * bm * dp * itemsize
    row_ops = 2 * sources * 2 * 8 * bn * 4
    tile = 8 * bm * bn * 4
    topk = 8 * bm * _round_up(k, LANE) * 4
    return rows + row_ops + tile + topk


def scan_tiles(batch: int, n_rows: int, dim: int, k: int, itemsize: int = 4,
               sources: int = 1) -> tuple[int, int]:
    """(bm, bn) of a scan of ``batch`` queries over ``n_rows`` base rows
    of width ``dim`` (``sources`` = 2 with a delta). ``bm`` is the batch
    rounded up to the dtype's sublane tile, at most 128. ``bn`` is the
    largest power-of-two multiple of 128 rows whose (bm, bn) tile holds at
    most ``MAX_TILE`` scores and whose blocks fit ``VMEM_BUDGET``; never
    more than ``n_rows`` rounded up to 128, a single tile for a table
    smaller than that."""
    sublane = 8 * max(1, 4 // itemsize)
    bm = min(128, _round_up(max(batch, 1), sublane))
    dp = _round_up(dim, LANE)
    bn = LANE
    while (bm * bn * 2 <= MAX_TILE and _vmem_bytes(
            bm, bn * 2, dp, k, itemsize, sources) <= VMEM_BUDGET):
        bn *= 2
    return bm, min(bn, _round_up(max(n_rows, 1), LANE))


def _tiles(q, db, k: int, delta=None) -> tuple[int, int]:
    """``scan_tiles`` for the operands of one ``streaming_fused_scan``."""
    return scan_tiles(q.shape[0], db.shape[0], db.shape[1],
                      min(k, db.shape[0] + (0 if delta is None
                                            else delta.shape[0])),
                      jnp.dtype(db.dtype).itemsize,
                      1 if delta is None else 2)


def row_tiles(q, db, k: int, delta=None) -> int:
    """Row tiles ``streaming_fused_scan(q, db, k, delta=delta)`` visits per
    query block (arrays or shape structs: only shapes are read)."""
    _, bn = _tiles(q, db, k, delta)
    return cdiv(db.shape[0], bn) + (0 if delta is None
                                    else cdiv(delta.shape[0], bn))


def _bad_mask(n_padded: int, valid_n, dead_mask,
              keep_mask=None) -> jnp.ndarray:
    """(1, n_padded) f32 0/1 row mask: 1 = padding past ``valid_n`` (a
    TRACED scalar — no per-table-size recompiles), tombstoned, or filtered
    out by ``keep_mask`` (predicate bitmap, True = row matches). The
    keep ∧ ¬dead composition happens here, so predicate masking rides the
    same in-register (1, N) row operand as tombstones."""
    bad = jnp.arange(n_padded, dtype=jnp.int32) >= valid_n
    if dead_mask is not None:
        bad = bad | pad_to(dead_mask.astype(bool), 0, n_padded)[:n_padded]
    if keep_mask is not None:
        # pad_to pads with 0 = False = not kept, so padded rows stay bad
        bad = bad | ~pad_to(keep_mask.astype(bool), 0, n_padded)[:n_padded]
    return bad.astype(jnp.float32)[None, :]


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
def streaming_fused_scan(q: jnp.ndarray, db: jnp.ndarray, k: int,
                         metric: str = "dot",
                         valid_n=None, dead_mask: jnp.ndarray | None = None,
                         delta: jnp.ndarray | None = None,
                         delta_valid_n=None,
                         delta_dead_mask: jnp.ndarray | None = None,
                         keep_mask: jnp.ndarray | None = None,
                         delta_keep_mask: jnp.ndarray | None = None,
                         interpret: bool | None = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B, d) queries over (N, d) base rows — plus an optional (Nd, d)
    delta source — -> top-k (values, ids) in ONE kernel launch, never
    materializing the (B, N) score matrix, and ``rounds``: the top-k fold
    rounds each block of queries ran, (cdiv(B, 128),) int32, at most
    ``row_tiles(q, db, k, delta) * min(k, bn)`` each. The block shape
    comes from the operands' shapes (``scan_tiles``); callers pass none.

    ``valid_n`` / ``delta_valid_n`` are TRACED scalars (rows at or past
    them are masked in-register); ``dead_mask`` / ``delta_dead_mask`` are
    per-source tombstone bitmaps; ``keep_mask`` / ``delta_keep_mask`` are
    per-source predicate bitmaps (True = row matches the filter) composed
    into the same (1, N) row-mask operand. Ids are combined-physical: base
    row i is id i; delta row r is id ``round_up(db.shape[0], 128) + r``,
    which for a resident column (rows padded to 128) is ``db.shape[0] + r``:
    callers map delta ids back with that row count. When fewer than k live
    rows exist the tail slots come back at NEG_INF with id 0, exactly like
    the two-pass path — callers drop them by score.

    k is clamped to the combined row count only; callers that need the
    two-pass ``min(k, valid_n)`` narrowing clamp before calling
    (``valid_n`` may be traced here, so it cannot shape the output).
    """
    bm, bn = _tiles(q, db, k, delta)
    return _scan(q, db, k, metric, valid_n, dead_mask, delta, delta_valid_n,
                 delta_dead_mask, keep_mask, delta_keep_mask, bm=bm, bn=bn,
                 interpret=interpret)


def _scan(q, db, k, metric="dot", valid_n=None, dead_mask=None, delta=None,
          delta_valid_n=None, delta_dead_mask=None, keep_mask=None,
          delta_keep_mask=None, *, bm: int, bn: int,
          interpret: bool | None = None):
    """``streaming_fused_scan`` at the given (bm, bn) tiles."""
    if interpret is None:
        interpret = default_interpret()
    B, d = q.shape
    Nb, d2 = db.shape
    assert d == d2, (d, d2)
    assert bn % LANE == 0 and bm % 8 == 0, (bm, bn)
    has_delta = delta is not None
    Nd = delta.shape[0] if has_delta else 0
    if has_delta:
        assert delta.shape[1] == d, (delta.shape, d)

    qsq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    bsq = jnp.sum(db.astype(jnp.float32) ** 2, axis=-1)[None, :]

    # rows are never padded (the last tile is ragged, masked in-register);
    # width is, to whole lanes — a no-op for a resident column
    qp = pad_to(pad_to(q, 0, bm), 1, LANE)
    dbp = pad_to(db, 1, LANE)
    qsqp = pad_to(qsq, 0, bm, value=1.0)
    bsqp = pad_to(bsq, 1, bn, value=1.0)
    Bp, dp = qp.shape
    nbt = cdiv(Nb, bn)

    valid_b = Nb if valid_n is None else valid_n
    bbad = _bad_mask(nbt * bn, valid_b, dead_mask, keep_mask)

    k_eff = min(k, Nb + Nd)
    operands = [qp, dbp]
    in_specs = [
        pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, dp), lambda i, j: (jnp.minimum(j, nbt - 1), 0)),
    ]
    if has_delta:
        dsq = jnp.sum(delta.astype(jnp.float32) ** 2, axis=-1)[None, :]
        # a delta shorter than one tile is padded to it (a copy of at most
        # bn rows); a longer one ends in a ragged tile like the base
        dltp = pad_to(delta, 1, LANE)
        if Nd < bn:
            dltp = pad_to(dltp, 0, bn)
        ndt = cdiv(Nd, bn)
        valid_d = Nd if delta_valid_n is None else delta_valid_n
        dbad = _bad_mask(ndt * bn, valid_d, delta_dead_mask, delta_keep_mask)
        dsqp = pad_to(dsq, 1, bn, value=1.0)
        operands += [dltp, qsqp, bsqp, dsqp, bbad, dbad]
        in_specs += [
            pl.BlockSpec((bn, dp), lambda i, j: (jnp.maximum(j - nbt, 0), 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, jnp.minimum(j, nbt - 1))),
            pl.BlockSpec((1, bn), lambda i, j: (0, jnp.maximum(j - nbt, 0))),
            pl.BlockSpec((1, bn), lambda i, j: (0, jnp.minimum(j, nbt - 1))),
            pl.BlockSpec((1, bn), lambda i, j: (0, jnp.maximum(j - nbt, 0))),
        ]
    else:
        ndt = 0
        operands += [qsqp, bsqp, bbad]
        in_specs += [
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, jnp.minimum(j, nbt - 1))),
            pl.BlockSpec((1, bn), lambda i, j: (0, jnp.minimum(j, nbt - 1))),
        ]

    grid = (Bp // bm, nbt + ndt)
    vals, idxs, rounds = pl.pallas_call(
        functools.partial(
            streaming_kernel, n_base_tiles=nbt, bn=bn, k=k_eff, metric=metric,
            delta_id_offset=_round_up(Nb, LANE), has_delta=has_delta,
            n_queries=B),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k_eff), jnp.int32),
            jax.ShapeDtypeStruct((grid[0] * 8, 128), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    vals, idxs = vals[:B], idxs[:B]
    order_vals, order_pos = jax.lax.top_k(vals, k_eff)
    idxs = jnp.take_along_axis(idxs, order_pos, axis=1)
    return order_vals, idxs, rounds[::8, 0]


__all__ = ["row_tiles", "scan_tiles", "streaming_fused_scan"]

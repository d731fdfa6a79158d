"""Jitted public op for the streaming fused scan (one launch, no score
matrix). See ``kernels/streaming/kernel.py`` for the kernel itself."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret, pad_to
from repro.kernels.streaming.kernel import streaming_kernel


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


@functools.partial(jax.jit, static_argnames=(
    "k", "metric", "bm", "bn", "bk", "interpret"))
def streaming_fused_scan(q: jnp.ndarray, db: jnp.ndarray, k: int,
                         metric: str = "dot",
                         valid_n=None, dead_mask: jnp.ndarray | None = None,
                         delta: jnp.ndarray | None = None,
                         delta_valid_n=None,
                         delta_dead_mask: jnp.ndarray | None = None,
                         keep_mask: jnp.ndarray | None = None,
                         delta_keep_mask: jnp.ndarray | None = None,
                         bm: int = 128, bn: int = 128, bk: int = 128,
                         interpret: bool | None = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B, d) queries over (N, d) base rows — plus an optional (Nd, d)
    delta source — -> top-k (values, ids) in ONE kernel launch, never
    materializing the (B, N) score matrix, and ``rounds``: the top-k fold
    rounds each block of ``bm`` queries ran, (cdiv(B, bm),) int32, at most
    ``row_tiles(N, Nd, bn=bn) * min(k, bn)`` each.

    ``valid_n`` / ``delta_valid_n`` are TRACED scalars (rows at or past
    them are masked in-register); ``dead_mask`` / ``delta_dead_mask`` are
    per-source tombstone bitmaps; ``keep_mask`` / ``delta_keep_mask`` are
    per-source predicate bitmaps (True = row matches the filter) composed
    into the same (1, N) row-mask operand. Ids are combined-physical: base row i is
    id i; delta row r is id ``db.shape[0] + r`` (callers map delta ids back
    with the padded base row count). When fewer than k live rows exist the
    tail slots come back at NEG_INF with id 0, exactly like the two-pass
    path — callers drop them by score.

    k is clamped to the combined (padded) row count only; callers that
    need the two-pass ``min(k, valid_n)`` narrowing clamp before calling
    (``valid_n`` may be traced here, so it cannot shape the output).
    """
    if interpret is None:
        interpret = default_interpret()
    B, d = q.shape
    Nb, d2 = db.shape
    assert d == d2, (d, d2)
    has_delta = delta is not None
    Nd = delta.shape[0] if has_delta else 0
    if has_delta:
        assert delta.shape[1] == d, (delta.shape, d)

    qsq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    bsq = jnp.sum(db.astype(jnp.float32) ** 2, axis=-1)[None, :]

    qp = pad_to(pad_to(q, 0, bm), 1, bk)
    dbp = pad_to(pad_to(db, 0, bn), 1, bk)
    qsqp = pad_to(qsq, 0, bm, value=1.0)
    bsqp = pad_to(bsq, 1, bn, value=1.0)
    Bp, dp = qp.shape
    Nbp = dbp.shape[0]
    nbt = Nbp // bn

    valid_b = Nb if valid_n is None else valid_n
    bbad = pad_to(_bad_mask(Nbp, valid_b, dead_mask, keep_mask),
                  1, bn, value=1.0)

    k_eff = min(k, Nb + Nd)
    operands = [qp, dbp]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bn, bk),
                     lambda i, j, kb: (jnp.minimum(j, nbt - 1), kb)),
    ]
    if has_delta:
        dsq = jnp.sum(delta.astype(jnp.float32) ** 2, axis=-1)[None, :]
        dltp = pad_to(pad_to(delta, 0, bn), 1, bk)
        Ndp = dltp.shape[0]
        ndt = Ndp // bn
        valid_d = Nd if delta_valid_n is None else delta_valid_n
        dbad = pad_to(_bad_mask(Ndp, valid_d, delta_dead_mask,
                                delta_keep_mask),
                      1, bn, value=1.0)
        dsqp = pad_to(dsq, 1, bn, value=1.0)
        operands += [dltp, qsqp, bsqp, dsqp, bbad, dbad]
        in_specs += [
            pl.BlockSpec((bn, bk),
                         lambda i, j, kb: (jnp.maximum(j - nbt, 0), kb)),
            pl.BlockSpec((bm, 1), lambda i, j, kb: (i, 0)),
            pl.BlockSpec((1, bn),
                         lambda i, j, kb: (0, jnp.minimum(j, nbt - 1))),
            pl.BlockSpec((1, bn),
                         lambda i, j, kb: (0, jnp.maximum(j - nbt, 0))),
            pl.BlockSpec((1, bn),
                         lambda i, j, kb: (0, jnp.minimum(j, nbt - 1))),
            pl.BlockSpec((1, bn),
                         lambda i, j, kb: (0, jnp.maximum(j - nbt, 0))),
        ]
    else:
        ndt = 0
        operands += [qsqp, bsqp, bbad]
        in_specs += [
            pl.BlockSpec((bm, 1), lambda i, j, kb: (i, 0)),
            pl.BlockSpec((1, bn),
                         lambda i, j, kb: (0, jnp.minimum(j, nbt - 1))),
            pl.BlockSpec((1, bn),
                         lambda i, j, kb: (0, jnp.minimum(j, nbt - 1))),
        ]

    grid = (Bp // bm, nbt + ndt, dp // bk)
    vals, idxs, rounds = pl.pallas_call(
        functools.partial(
            streaming_kernel, n_base_tiles=nbt, n_k_blocks=grid[2], bn=bn,
            k=k_eff, metric=metric, delta_id_offset=Nbp,
            has_delta=has_delta, n_queries=B),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, k_eff), lambda i, j, kb: (i, 0)),
            pl.BlockSpec((bm, k_eff), lambda i, j, kb: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, j, kb: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k_eff), jnp.int32),
            jax.ShapeDtypeStruct((grid[0] * 8, 128), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    vals, idxs = vals[:B], idxs[:B]
    order_vals, order_pos = jax.lax.top_k(vals, k_eff)
    idxs = jnp.take_along_axis(idxs, order_pos, axis=1)
    return order_vals, idxs, rounds[::8, 0]


def row_tiles(*n_rows: int, bn: int = 128) -> int:
    """Row tiles one ``streaming_fused_scan`` visits per query block, for
    row sources of ``n_rows`` rows each (base, then delta)."""
    return sum(cdiv(n, bn) for n in n_rows)


__all__ = ["row_tiles", "streaming_fused_scan"]

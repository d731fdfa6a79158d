"""The plain reference, and the comparison that decides ``correct``.

The reference imports nothing of the program. It regenerates the table
from the seed (``bench/table.py``) and scores every row of the query's
columns in ``jax.numpy`` at ``Precision.HIGHEST``, in blocks of queries:
the score of a row is the sum over the vid's columns of the column dot
products, which is the concat dot the program serves. Its best
``SELECT`` rows are then rescored in float64 on the host, as are the
rows the program served.

Numbers compared, per checked query and then the worst over a run, each
at or below its limit:

  - ``bad_answers``: answers missing, of the wrong length, with repeated
    ids or ids outside the table (limit 0);
  - ``score_gap``:   the widest gap by which the float64 score of the
    served id at rank r lies below the r-th best float64 score, over
    every rank. A set that misses a row, and an order that swaps two
    rows, both show. An exact top-k read at float32 shows rounding only.

The control puts the reference in the program's place at the precision
below the configuration's: ``bf16x3`` scores, the three-pass bfloat16
product that ``Precision.HIGH`` computes on the TPU, spelt out so that it
means the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SELECT = 128   # reference rows rescored per query (k plus a margin)
BLOCK = 32     # queries per reference call

def _bf16(x: jnp.ndarray) -> jnp.ndarray:
    # rounded to bfloat16 but kept in float32: reduce_precision is never
    # elided as excess precision, where a float32 -> bfloat16 -> float32
    # round trip may be
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot(q: jnp.ndarray, x: jnp.ndarray, precision: str) -> jnp.ndarray:
    """(B, d) x (N, d) -> (B, N) scores."""
    f = functools.partial(jax.lax.dot_general,
                          dimension_numbers=(((1,), (1,)), ((), ())),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision == "highest":
        return f(q, x)
    if precision == "bf16x3":
        # x = hi + lo, each a bfloat16 number; the product drops lo·lo.
        # Products of bfloat16 numbers are exact at HIGHEST.
        qh, xh = _bf16(q), _bf16(x)
        ql, xl = _bf16(q - qh), _bf16(x - xh)
        return f(qh, xh) + f(qh, xl) + f(ql, xh)
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("select", "precision"))
def _topk(qs: tuple[jnp.ndarray, ...], cols: tuple[jnp.ndarray, ...],
          select: int, precision: str) -> jnp.ndarray:
    scores = sum(_dot(q, c, precision) for q, c in zip(qs, cols))
    return jax.lax.top_k(scores, select)[1]


@jax.jit
def _gather(cols: tuple[jnp.ndarray, ...], ids: jnp.ndarray):
    return tuple(c[ids] for c in cols)


def best_rows(cols, vid, qvecs: list[np.ndarray], select: int,
              precision: str = "highest") -> np.ndarray:
    """(B, select) best rows per query over every row, by ``precision``.
    ``qvecs[c]`` is the (B, width) query block of column ``vid[c]``."""
    vcols = tuple(cols[c] for c in vid)
    b = qvecs[0].shape[0]
    out = np.empty((b, select), dtype=np.int64)
    for s in range(0, b, BLOCK):
        blk = [np.zeros((BLOCK, q.shape[1]), np.float32) for q in qvecs]
        m = min(BLOCK, b - s)
        for dst, q in zip(blk, qvecs):
            dst[:m] = q[s:s + m]
        ids = _topk(tuple(jnp.asarray(q) for q in blk), vcols,
                    select=select, precision=precision)
        out[s:s + m] = np.asarray(ids)[:m]
    return out


def rescore(cols, vid, qvecs: list[np.ndarray], ids: np.ndarray) -> np.ndarray:
    """float64 scores of rows ``ids`` (B, m) against their queries."""
    vcols = tuple(cols[c] for c in vid)
    rows = _gather(vcols, jnp.asarray(ids.astype(np.int32)))
    total = np.zeros(ids.shape, dtype=np.float64)
    for r, q in zip(rows, qvecs):
        total += np.einsum("bmd,bd->bm", np.asarray(r, np.float64),
                           q.astype(np.float64))
    return total


def well_formed(ids, k: int, n_rows: int) -> bool:
    if ids is None:
        return False
    ids = np.asarray(ids)
    return (ids.shape == (k,) and len(np.unique(ids)) == k
            and int(ids.min()) >= 0 and int(ids.max()) < n_rows)


def judge(cols, vid, qvecs: list[np.ndarray], served: list, k: int,
          n_rows: int) -> dict:
    """Per-query readings of ``served`` (one id array or None per query)
    against the reference: ``ok`` (well formed) and ``gap``."""
    b = len(served)
    ok = np.asarray([well_formed(s, k, n_rows) for s in served])
    ref = best_rows(cols, vid, qvecs, select=max(SELECT, k))
    s = rescore(cols, vid, qvecs, ref)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    ref_s = np.take_along_axis(s, order, axis=1)
    gap = np.full(b, np.inf)
    idx = np.nonzero(ok)[0]
    if idx.size:
        got = np.stack([np.asarray(served[i], np.int64) for i in idx])
        got_s = rescore(cols, vid, [q[idx] for q in qvecs], got)
        gap[idx] = (ref_s[idx] - got_s).max(axis=1)
    return {"ok": ok, "gap": gap}

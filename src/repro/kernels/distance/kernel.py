"""Fused batched-score Pallas kernel (the MINT distance hot spot on TPU).

An IVF/flat index scan is exactly this kernel: Q (B, d) against a row block
DB (N, d), producing (B, N) similarity scores on the MXU. Tiled as a
K-accumulated matmul: grid (B/bm, N/bn, d/bk) with a VMEM f32 accumulator;
the metric epilogue (dot / cosine / −L2²) runs on the final K step.

Block shapes default to MXU-aligned (128, 128, 128(d)) and are overridable
for the shape sweep tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import EXACT, default_interpret, pad_to


def _distance_kernel(q_ref, db_ref, qsq_ref, dbsq_ref, out_ref, acc_ref, *,
                     n_k_blocks: int, metric: str):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    db = db_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        q, db, (((1,), (1,)), ((), ())), precision=EXACT,
        preferred_element_type=jnp.float32)

    @pl.when(kb == n_k_blocks - 1)
    def _epilogue():
        acc = acc_ref[...]
        if metric == "dot":
            out = acc
        elif metric == "cosine":
            qn = jnp.sqrt(jnp.maximum(qsq_ref[...], 1e-24))   # (bm, 1)
            dn = jnp.sqrt(jnp.maximum(dbsq_ref[...], 1e-24))  # (1, bn)
            out = acc / (qn * dn)
        else:  # l2 -> negative squared distance
            out = -(qsq_ref[...] - 2.0 * acc + dbsq_ref[...])
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("metric", "bm", "bn", "bk", "interpret"))
def batched_scores(q: jnp.ndarray, db: jnp.ndarray, metric: str = "dot",
                   bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: bool | None = None) -> jnp.ndarray:
    """(B, d) x (N, d) -> (B, N) scores via the Pallas kernel."""
    if interpret is None:
        interpret = default_interpret()
    B, d = q.shape
    N, d2 = db.shape
    assert d == d2, (d, d2)

    qsq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)   # (B, 1)
    dbsq = jnp.sum(db.astype(jnp.float32) ** 2, axis=-1)[None, :]       # (1, N)

    qp = pad_to(pad_to(q, 0, bm), 1, bk)
    dbp = pad_to(pad_to(db, 0, bn), 1, bk)
    qsqp = pad_to(qsq, 0, bm, value=1.0)
    dbsqp = pad_to(dbsq, 1, bn, value=1.0)
    Bp, dp = qp.shape
    Np = dbp.shape[0]
    grid = (Bp // bm, Np // bn, dp // bk)

    out = pl.pallas_call(
        functools.partial(_distance_kernel, n_k_blocks=grid[2], metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, dbp, qsqp, dbsqp)
    return out[:B, :N]

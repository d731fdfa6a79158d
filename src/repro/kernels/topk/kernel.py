"""Streaming top-k Pallas kernel.

Reduces (B, N) scores to per-row top-k without materializing a sort:
grid (B/bm, N/bn) with the column axis sequential; a running (bm, k)
value/index buffer lives in the output blocks (same index_map for every
column step — the standard TPU accumulation idiom). Each column block is
folded in by k rounds of (max, argmax, mask) — vectorized across rows, no
in-kernel sort required (Mosaic-friendly). The wrapper does a final
lax.top_k over (B, k) to order the buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, pad_to

NEG_INF = float(-3.0e38)


def neg_inf_for(dtype) -> float:
    """Masking/padding sentinel pinned per score dtype: the most negative
    FINITE value exactly representable in ``dtype`` that still lands at or
    below ``NEG_INF`` after the kernel's cast to f32 — or -inf when the
    dtype has no finite value that low (f16 tops out at -65504, far ABOVE
    the f32 buffer init, so a finite f16 sentinel would beat the empty
    buffer slots and let a masked row surface as a real candidate).
    Writing raw ``NEG_INF`` into a narrow dtype instead leaves the sentinel
    to the dtype's rounding — bf16 happens to round it away from zero
    today, but that is luck, not a contract."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        return NEG_INF
    fi = jnp.finfo(dt)
    lo = float(fi.min)
    return lo if lo <= NEG_INF else float("-inf")


def _topk_kernel(scores_ref, vals_ref, idxs_ref, *, k: int, bn: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idxs_ref[...] = jnp.zeros_like(idxs_ref)

    s = scores_ref[...].astype(jnp.float32)          # (bm, bn)
    bm = s.shape[0]
    col_ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    iota_bn = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)

    def fold(_, carry):
        s, vals, idxs = carry
        m = jnp.max(s, axis=1)                        # (bm,)
        am = jnp.argmax(s, axis=1)                    # (bm,)
        sel = iota_bn == am[:, None]
        cid = jnp.sum(jnp.where(sel, col_ids, 0), axis=1)
        vmin = jnp.min(vals, axis=1)
        pmin = jnp.argmin(vals, axis=1)
        improve = m > vmin                            # (bm,)
        hit = improve[:, None] & (iota_k == pmin[:, None])
        vals = jnp.where(hit, m[:, None], vals)
        idxs = jnp.where(hit, cid[:, None], idxs)
        s = jnp.where(sel, NEG_INF, s)
        return s, vals, idxs

    s, vals, idxs = jax.lax.fori_loop(
        0, k, fold, (s, vals_ref[...], idxs_ref[...]))
    vals_ref[...] = vals
    idxs_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "interpret"))
def topk_scores(scores: jnp.ndarray, k: int, bm: int = 128, bn: int = 512,
                interpret: bool | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(B, N) -> per-row (values, indices) of the k best, best first."""
    if interpret is None:
        interpret = default_interpret()
    B, N = scores.shape
    k_eff = min(k, N)
    sp = pad_to(pad_to(scores, 0, bm), 1, bn,
                value=neg_inf_for(scores.dtype))
    Bp, Np = sp.shape
    grid = (Bp // bm, Np // bn)

    vals, idxs = pl.pallas_call(
        functools.partial(_topk_kernel, k=k_eff, bn=bn),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k_eff), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k_eff), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(sp)
    vals, idxs = vals[:B], idxs[:B]
    order_vals, order_pos = jax.lax.top_k(vals, k_eff)
    idxs = jnp.take_along_axis(idxs, order_pos, axis=1)
    return order_vals, idxs

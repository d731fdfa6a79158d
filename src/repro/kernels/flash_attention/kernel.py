"""Flash attention (forward) Pallas kernel for the embedding-model substrate.

Online-softmax over KV blocks: grid (B, Hq, Sq/bq, Skv/bkv) with the KV axis
sequential; running (m, l, acc) live in VMEM scratch. GQA is free via the
K/V BlockSpec index map (h -> h // group) — no KV repetition in memory.
Supports causal masking, sliding windows (Gemma-2 local layers), and attn
logit softcapping. Masked-out blocks are computed-and-masked (a production
TPU kernel would skip them via the grid; noted in EXPERIMENTS §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, pad_to

NEG_INF = float(-3.0e38)


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                  n_kv_blocks: int, bq: int, bkv: int, sq: int, skv: int,
                  causal: bool, window: int, softcap: float, scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)      # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)      # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)      # (bkv, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    # positions: q rows are aligned to the END of the kv sequence
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + (skv - sq)
    kpos = kj * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < skv  # padding guard
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                   # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bkv", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0, softcap: float = 0.0,
                    scale: float | None = None, bq: int = 128, bkv: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, Hq, Sq, d); k, v: (B, Hkv, Skv, d) -> (B, Hq, Sq, d)."""
    if interpret is None:
        interpret = default_interpret()
    B, Hq, Sq, d = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale_f = float(scale if scale is not None else d ** -0.5)

    qp = pad_to(q, 2, bq)
    kp = pad_to(k, 2, bkv)
    vp = pad_to(v, 2, bkv)
    Sqp, Skvp = qp.shape[2], kp.shape[2]
    grid = (B, Hq, Sqp // bq, Skvp // bkv)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, n_kv_blocks=grid[3], bq=bq, bkv=bkv, sq=Sq, skv=Skv,
            causal=causal, window=window, softcap=softcap, scale=scale_f),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq]

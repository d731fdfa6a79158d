"""Shared Pallas kernel utilities.

TPU v5e is the compilation target (MXU 128×128, VMEM ~16MiB); on a CPU
backend every kernel runs through ``interpret=True``, which executes the
kernel body in Python and validates indexing/semantics exactly.

Exact-path matmuls (the scan kernels, the IVF gathered scoring, the
distributed shard scan) pass ``EXACT`` explicitly: on the TPU the default
f32 contraction may round operands to bf16, which moves top-k scores by
~1e-3 relative and breaks id parity with the numpy oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EXACT = jax.lax.Precision.HIGHEST
LANE = 128   # lanes of a vreg, and the MXU's width on a v5e


def default_interpret() -> bool:
    """The library's platform switch: Pallas interpret mode (and the
    XLA/numpy stand-ins keyed on it) whenever the backend is not a TPU.
    Entry points check the platform first (``repro.launch.entry.start``),
    so only an explicit CPU run takes this route."""
    return jax.default_backend() != "tpu"


def pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0.0) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)

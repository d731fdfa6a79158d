"""The benchmark's own data generator, on the device, from ``--seed``.

The same model as the program's ``data/vectors.make_database`` (MINT
§5.1's semi-synthetic columns): per column, unit vectors around
``clusters`` unit centroids with noise of norm ``spread``; with
probability ``correlation`` a row's column draws from the row's shared
cluster, else from a cluster of its own. Queries are a table row plus
per-column noise of norm ``query_noise``, renormalised. Every array is
made in one jitted call, in float32, the type the program serves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int) -> jax.Array:
    """A PRNG key holding all 64 bits of ``seed`` (``jax.random.key``
    keeps only 32 without x64, so 3 and 2**40 + 3 would collide)."""
    s = int(seed) % 2 ** 64
    return jax.random.wrap_key_data(
        jnp.asarray([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32))


def _normalize(x: jnp.ndarray) -> jnp.ndarray:
    n = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.maximum(n, 1e-12)


def n_clusters(rows: int) -> int:
    return max(16, int(np.sqrt(rows)))


@functools.partial(jax.jit, static_argnames=(
    "rows", "widths", "spread", "correlation"))
def make_table(k: jax.Array, rows: int, widths: tuple[int, ...],
               spread: float, correlation: float) -> tuple[jnp.ndarray, ...]:
    """One (rows, width) float32 column per entry of ``widths``."""
    c = n_clusters(rows)
    k_shared, *k_cols = jax.random.split(k, 1 + len(widths))
    shared = jax.random.randint(k_shared, (rows,), 0, c)
    cols = []
    for kc, w in zip(k_cols, widths):
        k_cent, k_own, k_use, k_noise = jax.random.split(kc, 4)
        cent = _normalize(jax.random.normal(k_cent, (c, w), jnp.float32))
        own = jax.random.randint(k_own, (rows,), 0, c)
        use = jax.random.uniform(k_use, (rows,)) < correlation
        assign = jnp.where(use, shared, own)
        noise = _normalize(jax.random.normal(k_noise, (rows, w), jnp.float32))
        cols.append(_normalize(cent[assign] + spread * noise))
    return tuple(cols)


@functools.partial(jax.jit, static_argnames=("noise",))
def make_queries(k: jax.Array, cols: tuple[jnp.ndarray, ...],
                 rows: jnp.ndarray, noise: float) -> tuple[jnp.ndarray, ...]:
    """Query vectors near table rows ``rows``: one (len(rows), width)
    array per column, every column drawn for every query."""
    out = []
    for kc, col in zip(jax.random.split(k, len(cols)), cols):
        g = _normalize(jax.random.normal(kc, (rows.shape[0], col.shape[1]),
                                         jnp.float32))
        out.append(_normalize(col[rows] + noise * g))
    return tuple(out)


def table_key(seed: int) -> jax.Array:
    return jax.random.fold_in(key(seed), 0)


def query_key(seed: int) -> jax.Array:
    return jax.random.fold_in(key(seed), 1)


def generate(config: dict, seed: int) -> tuple[jnp.ndarray, ...]:
    """The configuration's table on the device, from ``seed``."""
    g = config["generator"]
    return make_table(table_key(seed), rows=int(config["rows"]),
                      widths=tuple(int(w) for _, w in config["columns"]),
                      spread=float(g["spread"]),
                      correlation=float(g["correlation"]))

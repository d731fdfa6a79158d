"""Device-resident column store (DESIGN.md §Serving).

Every execution path used to rebuild ``db.concat(vid)`` per call — a host
concatenation (and, on the fused path, a host→device transfer plus a pad)
for every query. The column store materializes each vid's concatenated
matrix exactly once:

  - ``host(vid)``   — the numpy concat, cached (planner / CPU harness);
  - ``device(vid)`` — the same matrix padded to the kernel block shapes
    (rows → ``block_rows``, feature dim → ``block_dim``) and resident on
    device, so repeated ``fused_scan`` dispatches skip the transfer and the
    per-call pad.

Padding policy: pad rows/dims with zeros; zero feature padding is exact for
dot scores, and padded rows are masked to -inf inside ``fused_scan`` via its
``valid_n`` argument (they must never win a top-k slot). Under a mesh the
row count is additionally rounded up to a multiple of the data-axis size and
the array is placed with the row sharding from ``distributed.sharding`` so
the distributed tournament scan can consume it directly.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import Vid, norm_vid
from repro.data.vectors import MultiVectorDatabase
from repro.distributed.sharding import row_sharding


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def padded_device_bytes(n_rows: int, dim: int, block_rows: int = 128,
                        block_dim: int = 128, row_mult: int = 1,
                        itemsize: int = 4) -> int:
    """Device footprint of one resident column AFTER kernel-block padding —
    what a column actually pins on device, not its logical nbytes.
    ``row_mult`` is the mesh data-axis size when row-sharded (rows are
    additionally rounded to a multiple of it, matching ``device()``)."""
    rm = _round_up(block_rows, row_mult) if row_mult > 1 else block_rows
    return _round_up(n_rows, rm) * _round_up(dim, block_dim) * itemsize


@dataclass
class DeviceColumn:
    """One vid's device-resident concat, padded to kernel block shapes."""

    vid: Vid
    data: jnp.ndarray  # (n_padded, dim_padded), zero-padded
    n_rows: int        # valid rows (pass as fused_scan's valid_n)
    dim: int           # valid feature dim

    @property
    def padded_dim(self) -> int:
        return int(self.data.shape[1])

    @property
    def device_bytes(self) -> int:
        """PADDED device footprint (the governor's accounting unit) — the
        logical ``n_rows * dim`` undercounts what the column actually pins."""
        return int(self.data.size) * int(self.data.dtype.itemsize)

    def pad_queries(self, qmat: np.ndarray) -> jnp.ndarray:
        """(B, dim) host queries -> (B, padded_dim) device array."""
        qmat = np.asarray(qmat, dtype=np.float32)
        if qmat.shape[1] != self.dim:
            raise ValueError(f"query dim {qmat.shape[1]} != column dim {self.dim}")
        if self.padded_dim != self.dim:
            qmat = np.pad(qmat, ((0, 0), (0, self.padded_dim - self.dim)))
        return jnp.asarray(qmat)


class ColumnStore:
    """Per-vid concat cache over one MultiVectorDatabase (host + device)."""

    def __init__(self, db: MultiVectorDatabase, mesh=None, axis: str = "data",
                 block_rows: int = 128, block_dim: int = 128):
        self.db = db
        self.mesh = mesh
        self.axis = axis
        self.block_rows = block_rows
        self.block_dim = block_dim
        self._host: dict[Vid, np.ndarray] = {}
        self._device: dict[Vid, DeviceColumn] = {}

    @property
    def n_rows(self) -> int:
        return self.db.n_rows

    def host(self, vid: Vid) -> np.ndarray:
        """Cached ``db.concat(vid)`` (single columns alias the db storage)."""
        vid = norm_vid(vid)
        if vid not in self._host:
            self._host[vid] = self.db.concat(vid)
        return self._host[vid]

    def device(self, vid: Vid) -> DeviceColumn:
        vid = norm_vid(vid)
        if vid not in self._device:
            mat = self.host(vid)
            n, d = mat.shape
            row_mult = self.block_rows
            if self.mesh is not None:
                row_mult = _round_up(row_mult, int(self.mesh.shape[self.axis]))
            np_pad = _round_up(n, row_mult) - n
            nd_pad = _round_up(d, self.block_dim) - d
            if np_pad or nd_pad:
                mat = np.pad(mat, ((0, np_pad), (0, nd_pad)))
            # the host array goes straight to its placement: each device
            # receives only its row shard under a mesh
            sharding = (None if self.mesh is None
                        else row_sharding(self.mesh, self.axis))
            arr = jax.device_put(np.asarray(mat, dtype=np.float32), sharding)
            self._device[vid] = DeviceColumn(vid=vid, data=arr, n_rows=n, dim=d)
        return self._device[vid]

    def device_bytes(self, vid: Vid) -> int:
        """Padded device bytes ``device(vid)`` would pin — computable BEFORE
        materialization (the governor admits against this number), and equal
        to ``device(vid).device_bytes`` afterwards."""
        vid = norm_vid(vid)
        row_mult = 1
        if self.mesh is not None:
            row_mult = int(self.mesh.shape[self.axis])
        return padded_device_bytes(self.db.n_rows, self.db.dim(vid),
                                   block_rows=self.block_rows,
                                   block_dim=self.block_dim,
                                   row_mult=row_mult)

    def total_device_bytes(self) -> int:
        return sum(col.device_bytes for col in self._device.values())

    def evict_device(self, vid: Vid) -> bool:
        """Spill one resident column back to host: the device array is
        released (host concat cache is retained, so a later ``device()``
        re-pads and re-uploads bit-identically). Returns whether it was
        resident."""
        return self._device.pop(norm_vid(vid), None) is not None

    def resident(self) -> list[Vid]:
        """Vids currently resident on device."""
        return sorted(self._device)

    def materialized(self) -> list[Vid]:
        return sorted(set(self._host) | set(self._device))

"""Roofline-term extraction from compiled dry-run artifacts.

Terms, per chip of the device kind the program targets (``peaks``):
  compute    = FLOPs / peak_FLOPs
  memory     = bytes accessed / HBM_bw
  collective = collective bytes / link_bw

``cost_analysis`` describes the per-device SPMD program, so terms are
per-chip seconds directly. Collective bytes are parsed from the optimized
HLO text: the RESULT buffer size of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (documented proxy for
operand bytes; exact for all-reduce, upper bound for all-gather).
"""
from __future__ import annotations

import re
from dataclasses import dataclass

# ``jax.Device.device_kind`` of a TPU v5e chip — the dry runs' compile target
V5E = "TPU v5 lite"

# Published per-chip peaks, keyed by ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s chip-to-chip interconnect over 4 ICI links (50 GB/s each).
PEAKS = {
    V5E: {
        "peak_flops": 197e12,   # bf16 per chip
        "hbm_bw": 819e9,        # bytes/s per chip
        "link_bw": 50e9,        # bytes/s per ICI link
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; a kind without a
    published entry is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peak rates for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


VMEM_BYTES = 16 * 2 ** 20   # per-core VMEM — the old single-dispatch cap

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(pred|[sub]\d+|bf16|f\d+|c\d+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> dict:
    by_kind: dict[str, dict] = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        by_kind[kind]["count"] += 1
        by_kind[kind]["bytes"] += _shape_bytes(shape_str)
    total = sum(v["bytes"] for v in by_kind.values())
    return {"by_kind": by_kind, "total_bytes": total}


@dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    model_flops: float          # 6·N·D (train) or 2·N·D (decode), per chip
    chips: int
    hw = peaks(V5E)             # the dry runs compile for a v5e chip

    @property
    def t_compute(self) -> float:
        return self.flops / self.hw["peak_flops"]

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hw["hbm_bw"]

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.hw["link_bw"]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / max(self.flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """useful compute time / total bound time (the perf score)."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        return (self.model_flops / self.hw["peak_flops"]) / max(bound, 1e-12)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def modeled_scan_bytes(B: int, N: int, d: int, k: int, masked: bool = True,
                       dtype_bytes: int = 4, selectivity: float | None = None,
                       attr_bytes: int = 4,
                       gather_amplification: float = 2.0) -> dict:
    """Modeled HBM traffic for one (B, N, d) -> top-k scan dispatch.

    Both paths read the queries and database once and write the (vals, ids)
    pair. The two-pass path additionally round-trips the f32 (B, N) score
    matrix through HBM: one write from the distance kernel + one read by
    top-k, plus a read + write for the elementwise mask pass when padding /
    tombstones apply (``masked``). The streaming path replaces all of that
    with one (1, N) f32 row-mask read — the score matrix never exists, so
    its score-side traffic is O(B·k), not O(B·N).

    ``score_block_bytes`` is the f32 score matrix itself — the quantity
    that had to fit in VMEM (``VMEM_BYTES``) for the old single-dispatch
    two-pass scan to avoid spilling.

    With ``selectivity`` set (DESIGN.md §12), two filtered terms are added:
      masked_filtered_bytes : streaming scan + one extra (1, N) keep-bitmap
                              row read (the predicate mask kernel operand)
                              plus the host-side bitmap build — one
                              ``attr_bytes`` column pass over N rows;
      prefilter_bytes       : bitmap build + a gathered brute-force pass
                              over sel·N rows; the gather reads rows
                              non-contiguously, so its row bytes carry
                              ``gather_amplification`` (matches the
                              planner's GATHER_OVERHEAD term — both put
                              the pre/masked crossover at sel = 1/(1+γ)).
    """
    io = (B * d + N * d) * dtype_bytes + 2 * B * k * 4
    score_passes = 4 if masked else 2
    score_block = B * N * 4
    out = {
        "twopass_bytes": io + score_passes * score_block,
        "streaming_bytes": io + N * 4,
        "score_block_bytes": score_block,
    }
    if selectivity is not None:
        sel = min(max(float(selectivity), 0.0), 1.0)
        bitmap = N * attr_bytes + N  # column pass + packed bool bitmap out
        rows_kept = sel * N
        gathered_io = (B * d + gather_amplification * rows_kept * d
                       ) * dtype_bytes + 2 * B * k * 4
        out["selectivity"] = sel
        out["bitmap_bytes"] = bitmap
        out["masked_filtered_bytes"] = out["streaming_bytes"] + N + bitmap
        out["prefilter_bytes"] = gathered_io + bitmap
    return out


def streaming_vs_twopass(ns=(2048, 8192, 32768, 65536), B: int = 128,
                         d: int = 128, k: int = 16, masked: bool = True,
                         measure: bool = False, measure_n_cap: int = 4096,
                         interpret: bool | None = None, seed: int = 0) -> dict:
    """Sweep table size N from VMEM-resident to beyond the old
    single-dispatch VMEM limit, reporting modeled HBM bytes for the
    two-pass vs streaming scan plus (optionally) measured wall-clock per
    dispatch.

    Off-TPU the kernels run in interpret mode — a Python-stepped grid whose
    wall-clock says nothing about HBM traffic — so measurement is capped at
    ``measure_n_cap`` rows there and the modeled bytes carry the
    comparison; on TPU the cap is lifted and the timings are real. The
    modeled seconds use the v5e's published HBM bandwidth."""
    hbm_bw = peaks(V5E)["hbm_bw"]
    rows = []
    for n in ns:
        m = modeled_scan_bytes(B, n, d, k, masked=masked)
        row = {
            "n": int(n),
            **m,
            "hbm_ratio": m["twopass_bytes"] / m["streaming_bytes"],
            "t_memory_twopass_s": m["twopass_bytes"] / hbm_bw,
            "t_memory_streaming_s": m["streaming_bytes"] / hbm_bw,
            "exceeds_vmem": m["score_block_bytes"] > VMEM_BYTES,
        }
        if measure:
            row["measured"] = _measure_scan_pair(
                B, n, d, k, masked, measure_n_cap, interpret, seed)
        rows.append(row)
    largest = rows[-1]
    return {
        "B": B, "d": d, "k": k, "masked": masked,
        "vmem_bytes": VMEM_BYTES,
        "sweep": rows,
        "acceptance": {
            "largest_n": largest["n"],
            "hbm_ratio_at_largest_n": largest["hbm_ratio"],
            "largest_n_exceeds_vmem": largest["exceeds_vmem"],
            "ok": largest["hbm_ratio"] >= 2.0 and largest["exceeds_vmem"],
        },
    }


def _measure_scan_pair(B, n, d, k, masked, n_cap, interpret, seed,
                       reps: int = 3) -> dict:
    """Median wall-clock (ms) per dispatch for both scan paths at
    min(n, n_cap) rows (cap only applies in interpret mode)."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.common import default_interpret
    from repro.kernels.distance.ops import fused_scan
    from repro.kernels.streaming.ops import streaming_fused_scan

    if interpret is None:
        interpret = default_interpret()
    n_run = min(n, n_cap) if interpret else n
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, d)).astype(np.float32))
    db = jnp.asarray(rng.standard_normal((n_run, d)).astype(np.float32))
    kw = {}
    if masked:
        dead = np.zeros(n_run, dtype=bool)
        dead[:: max(n_run // 64, 1)] = True
        kw = dict(valid_n=n_run - 1, dead_mask=jnp.asarray(dead))

    def _time(fn):
        fn()[0].block_until_ready()  # warmup / compile
        ts = []
        for _ in range(reps):
            t0 = time.time()
            fn()[0].block_until_ready()
            ts.append((time.time() - t0) * 1e3)
        return float(np.median(ts))

    return {
        "n_measured": int(n_run),
        "interpret": bool(interpret),
        "streaming_ms": _time(lambda: streaming_fused_scan(
            q, db, k=k, interpret=interpret, **kw)),
        "twopass_ms": _time(lambda: fused_scan(
            q, db, k=k, interpret=interpret, **kw)),
    }


def extract_cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = dict(ca or {})
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "raw_keys": sorted(ca)[:40]}


def extract_memory(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_device_bytes"] = (out.get("argument_size_in_bytes", 0)
                                 + out.get("output_size_in_bytes", 0)
                                 + out.get("temp_size_in_bytes", 0)
                                 - out.get("alias_size_in_bytes", 0))
    return out

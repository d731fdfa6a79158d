"""Batched plan executor — the single execution path for MINT plans.

Runs compiled plan groups (``serve.compiler``) over the device-resident
column store (``serve.columnstore``):

  - flat scans: ONE ``fused_scan`` dispatch per (group, index) — the Pallas
    MXU distance kernel + streaming top-k over the padded resident matrix
    (or the distributed tournament step when a mesh is attached);
  - IVF: ONE batched centroid-scoring dispatch per (group, index) followed
    by a single gathered-row scoring dispatch over the padded probe union;
  - graph kinds (hnsw / diskann): per-query CPU search fallback (graph
    walks don't batch), but the rerank below still batches;
  - rerank: ONE ``batched_scores`` dispatch per group over the padded
    candidate union, skipped on the single-exact-vid fast path — the same
    rule ``planner._plan_cost`` uses, so executed cost matches planned cost
    structurally.

ek buckets pad *dispatch shapes* only; each query slices its own exact ek
from the best-first results, so batched top-k ids are identical to the
per-query paths. Cost/recall accounting (``ExecutionMetrics`` /
``WorkloadMetrics``) follows ``core.tuner.execute_plan`` exactly: cost =
Σ dim(x)·numDist + dim(q)·Σ ek (Eq. 4-6, duplicates counted), with wall
time amortized over the group batch.

Mutations (DESIGN.md §9): with a ``repro.ingest.MutationView`` attached,
execution serves the LIVE table instead of the frozen snapshot —

  - base scans thread the tombstone bitmap into the scan kernel as a score
    mask (deleted rows can never win a top-k slot; under a mesh the same
    bitmap rides the distributed step's sharded ``bad`` operand);
  - every index additionally brute-force scans the per-vid DELTA segment
    and merges base + delta candidates by partial score with the canonical
    (score desc, stable id asc) order — exactly the candidate list an
    index of the same kind would produce over a from-scratch rebuild
    whenever its candidate generation is exact (flat always; ANN kinds at
    exhaustive depth). On the streaming path a flat base + delta pair is
    ONE ``streaming_fused_scan`` launch (the kernel's second row source);
    graph/IVF kinds keep a separate delta dispatch because their base
    candidates are not a flat scan;

Scan kernels (DESIGN.md §11): flat scans default to the single-launch
``kernels/streaming`` kernel — distance + in-register masking + online
top-k with no materialized score matrix. ``streaming=False`` (or env
``REPRO_TWOPASS_SCAN=1``) falls back to the two-pass ``fused_scan``
reference path; both return identical (values, ids).
  - all returned ids are STABLE item ids (``view.translate``), and the
    rerank gathers each union id from whichever side — base column or
    delta segment — physically holds it;
  - recall ground truth comes from ``view.ground_truth`` (exact top-k over
    live rows), not the frozen base.

Filtered search (DESIGN.md §12): with an ``AttributeStore`` attached
(``attach_filters``), queries may carry a predicate and their plan an
access path —

  - ``pre``    gather exactly the matching live rows and brute-force score
               only those (one dispatch per side; wins at low selectivity);
  - ``masked`` full scan with the predicate's keep bitmap composed into
               the kernels' row masks (keep ∧ ¬dead in-register on the
               streaming path);
  - ``post``   the normal index probe at 1/selectivity-inflated eks with
               non-matching candidates score-killed before selection (flat
               specs push the keep mask into the kernel instead — exact at
               any depth, no escalation loop).

All three return the exact filtered top-k whenever their candidate
generation is exact (flat/pre always; ANN kinds at exhaustive depth),
matching the unfiltered contract. Predicates with ZERO live matches
return empty results without dispatching any kernel (an all-masked launch
would surface NEG_INF sentinels as hits). Plan groups are
predicate-uniform (``GroupKey.pred``), so the keep bitmap is one shared
(1, N) operand per launch.
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import Query, QueryPlan, Workload
from repro.data.vectors import MultiVectorDatabase
from repro.index.base import exact_topk
from repro.kernels.common import EXACT, default_interpret
from repro.kernels.distance.kernel import batched_scores
from repro.kernels.distance.ops import fused_scan
from repro.kernels.streaming.ops import row_tiles, streaming_fused_scan
from repro.kernels.topk.kernel import NEG_INF
from repro.obs import NULL_OBSERVER
from repro.serve.columnstore import ColumnStore, DeviceColumn, row_sharding
from repro.serve.compiler import PlanGroup, compile_batch

# scores below this are masked tombstones / padding — never real candidates
_DEAD_CUT = NEG_INF / 2


@dataclass
class StagedBatch:
    """Pre-staged device state for one micro-batch (DESIGN.md §10).

    ``stage_batch`` compiles the plan groups and dispatches every
    host→device transfer the batch will need — resident columns touched,
    padded query matrices uploaded — WITHOUT running any kernel. The async
    flush path stages batch N+1 on the submitting thread while a worker
    runs batch N's kernels, overlapping transfer with compute; execution
    then reuses the staged groups/qmats (same values, so results are
    bit-identical to an unstaged run). qmats are advisory: execution
    revalidates shapes against the live column store and recomputes on
    mismatch (a store swap may land between staging and execution)."""

    n: int                                   # batch size staged for
    groups: list[PlanGroup]
    qmats: dict[tuple, jnp.ndarray]          # (group_idx, slot) -> device qmat


@dataclass
class DispatchCounters:
    """Kernel-dispatch accounting: ``scan`` counts ONE per (group, index)
    batched dispatch (flat scan or IVF probe — a streaming base+delta
    merged launch is one ``scan``, its delta rides for free), ``delta``
    one per SEPARATE delta-segment dispatch (two-pass flat fallback and
    graph/IVF kinds), ``rerank`` one per group needing the union rerank,
    ``fallback`` one per per-query graph search that could not be
    batched."""

    scan: int = 0
    delta: int = 0
    rerank: int = 0
    fallback: int = 0

    def reset(self) -> None:
        self.scan = self.delta = self.rerank = self.fallback = 0

    def as_dict(self) -> dict:
        return {"scan": self.scan, "delta": self.delta,
                "rerank": self.rerank, "fallback": self.fallback}


@dataclass
class _FilterState:
    """Evaluated predicate bitmaps for the CURRENT table state, cached per
    (predicate, attribute version, table version, base rows). ``base_keep``
    / ``delta_keep`` are host bool bitmaps over base / delta PHYSICAL rows
    (the delta bitmap follows the table's global delta-row order, which
    every vid's delta column shares); device copies are built lazily per
    padded length for the kernel keep-mask operands."""

    pred: object
    base_keep: np.ndarray
    delta_keep: np.ndarray | None
    n_match: int        # live rows matching (base + delta)
    n_match_base: int   # live BASE rows matching (mesh over-fetch sizing)
    _dev: dict = field(default_factory=dict)

    def base_keep_dev(self, padded_n: int) -> jnp.ndarray:
        key = ("base", padded_n)
        if key not in self._dev:
            m = np.zeros(padded_n, dtype=bool)
            m[: self.base_keep.shape[0]] = self.base_keep
            self._dev[key] = jnp.asarray(m)
        return self._dev[key]

    def delta_keep_dev(self, padded_n: int) -> jnp.ndarray:
        key = ("delta", padded_n)
        if key not in self._dev:
            m = np.zeros(padded_n, dtype=bool)
            if self.delta_keep is not None:
                m[: self.delta_keep.shape[0]] = self.delta_keep
            self._dev[key] = jnp.asarray(m)
        return self._dev[key]


# device bytes one IVF gather step may materialise: (B, chunk, d) f32 rows
_GATHER_BYTES = 256 * 2 ** 20


def gather_chunk(B: int, R: int, d: int) -> int:
    """Rows per query that ``_gather_scores`` gathers per step: all R when
    (B, R, d) fits ``_GATHER_BYTES``, else the largest count that does —
    at the paper's 1M rows a probe union can cover most of the table, and
    one (B, R, d) gather would not fit the chip's HBM."""
    return max(1, min(R, _GATHER_BYTES // (B * d * 4)))


@functools.partial(jax.jit, static_argnames=("chunk",))
def _gather_scores(data: jnp.ndarray, rows: jnp.ndarray, qmat: jnp.ndarray,
                   chunk: int):
    """Per-query gathered-row scoring: (N,d), (B,R) int32, (B,d) -> (B,R),
    ``chunk`` rows per query at a time (R a multiple of ``chunk``)."""
    B, R = rows.shape
    steps = rows.reshape(B, R // chunk, chunk).transpose(1, 0, 2)
    out = jax.lax.map(lambda r: jnp.einsum("brd,bd->br", data[r], qmat,
                                           precision=EXACT), steps)
    return out.transpose(1, 0, 2).reshape(B, R)


@jax.jit
def _xla_scores(qmat: jnp.ndarray, sub: jnp.ndarray) -> jnp.ndarray:
    return qmat @ sub.T


@jax.jit
def _xla_cache_probe(qmat: jnp.ndarray, mat: jnp.ndarray, valid_n):
    """Interpret-mode mirror of the streaming l2 probe: (B, d) queries vs
    (C, d) cached query vectors -> nearest (neg squared distance, id) per
    row. ``valid_n`` is traced, so ring-buffer fill level never recompiles."""
    q = qmat.astype(jnp.float32)
    m = mat.astype(jnp.float32)
    qsq = jnp.sum(q * q, axis=1, keepdims=True)
    msq = jnp.sum(m * m, axis=1)[None, :]
    s = -(qsq - 2.0 * (q @ m.T) + msq)
    pad = jnp.arange(m.shape[0], dtype=jnp.int32)[None, :] >= valid_n
    s = jnp.where(pad, NEG_INF, s)
    return jax.lax.top_k(s, 1)


def cache_probe_scan(qmat, mat, valid_n, interpret: bool | None = None):
    """Batched semantic-cache probe (DESIGN.md §13): ONE brute-force L2
    dispatch of (B, d) query vectors against the cache's (C, d) query
    matrix — the streaming fused scan on TPU (the cache is just a tiny
    second table), a jitted XLA mirror under interpret mode (Pallas
    interpret runs its grid in Python). Returns host (vals, ids) with
    vals = -(squared L2); rows at or past ``valid_n`` are masked."""
    if interpret is None:
        interpret = default_interpret()
    qmat = jnp.asarray(qmat, dtype=jnp.float32)
    mat = jnp.asarray(mat, dtype=jnp.float32)
    if interpret:
        vals, ids = _xla_cache_probe(qmat, mat, valid_n)
    else:
        vals, ids, _ = streaming_fused_scan(qmat, mat, k=1, metric="l2",
                                            valid_n=valid_n, interpret=False)
    return np.asarray(vals), np.asarray(ids)


class BatchEngine:
    """Executes batches of (query, plan) pairs as compiled plan groups.

    ``store`` (an ``index.registry.IndexStore``) supplies materialized
    indexes; without one, every planned index is served as a device flat
    scan at its ek (the pure fused-kernel serving form). ``mesh`` switches
    flat scans to the distributed tournament step over row-sharded columns.
    """

    def __init__(self, db: MultiVectorDatabase, store=None,
                 cstore: ColumnStore | None = None, mesh=None,
                 axis: str = "data", interpret: bool | None = None,
                 streaming: bool | None = None, observer=None):
        self.db = db
        # observability (DESIGN.md §14): plan-group spans, and the fetch
        # spans of their blocking reads, nest under whatever span is current
        # on the executing thread (the scheduler's dispatch span);
        # NULL_OBSERVER keeps this free
        self.obs = observer if observer is not None else NULL_OBSERVER
        self.store = store
        self.mesh = mesh if mesh is not None else (cstore.mesh if cstore else None)
        self.axis = axis
        self.cstore = cstore or ColumnStore(db, mesh=self.mesh, axis=axis)
        self.interpret = interpret
        # single-launch streaming scan is the default; the two-pass path is
        # the reference oracle (streaming=False / REPRO_TWOPASS_SCAN=1)
        if streaming is None:
            streaming = os.environ.get("REPRO_TWOPASS_SCAN", "0") != "1"
        self.streaming = streaming
        self.counters = DispatchCounters()
        self.mview = None  # repro.ingest.MutationView when mutations flow
        self._dist_steps: dict[tuple, object] = {}
        # filtered search (attach_filters): attribute store + optional
        # selectivity estimator, and the per-predicate bitmap cache
        self.attrs = None
        self.selest = None
        self._filter_cache: dict[tuple, _FilterState] = {}

    # ---- public API -------------------------------------------------------

    def swap_store(self, store, cstore: ColumnStore | None = None,
                   db: MultiVectorDatabase | None = None) -> None:
        """Swap hook for the online runtime's drift → retune → swap
        lifecycle: replace the index store (and optionally the column
        store and database, when the underlying table itself changed —
        e.g. a compaction folded delta segments into a new base). Cached
        distributed search steps are keyed by (k, n_rows), so they survive
        an index-store-only swap; replacing the column store / database
        invalidates them (compactions change n_rows every time — keeping
        stale shapes would leak one compiled step per row-count)."""
        self.store = store
        if cstore is not None:
            self.cstore = cstore
        if db is not None:
            self.db = db
        if cstore is not None or db is not None:
            self._dist_steps.clear()

    def attach_filters(self, attrs, selectivity=None) -> None:
        """Attach a ``repro.filter.AttributeStore`` (and optionally a
        ``SelectivityEstimator``): queries carrying a ``predicate`` are
        served over exactly the live rows matching it. Without this call a
        filtered query raises — predicates are never silently ignored."""
        self.attrs = attrs
        self.selest = selectivity
        self._filter_cache.clear()

    def detach_filters(self) -> None:
        self.attrs = None
        self.selest = None
        self._filter_cache.clear()

    def attach_mutations(self, view) -> None:
        """Attach a ``repro.ingest.MutationView``: scans mask tombstoned
        rows, delta segments are scanned and merged, and returned ids are
        STABLE item ids (identical to base physical rows until the first
        compaction rebases the table)."""
        self.mview = view

    def detach_mutations(self) -> None:
        self.mview = None

    def _mv(self):
        """The active mutation view, or None when the attached table is
        still bit-identical to the frozen snapshot (fast path)."""
        mv = self.mview
        return mv if mv is not None and mv.mutated() else None

    def ground_truth(self, query: Query) -> np.ndarray:
        """Exact top-k ids for one query against the LIVE serving state —
        the same oracle ``execute_batch`` uses per plan group (filtered /
        mutated / frozen branches), exposed for callers that need recall
        for results served OUTSIDE a flush (e.g. semcache hits during
        trace replay)."""
        pred = getattr(query, "predicate", None)
        if pred is not None:
            return self._filtered_ground_truth(query, pred)
        mv = self._mv()
        if mv is not None:
            return mv.ground_truth(query)
        ids, _ = exact_topk(self.cstore.host(query.vid), query.concat(),
                            query.k)
        return ids

    def stage_batch(self, pairs: list[tuple[Query, QueryPlan]]) -> StagedBatch:
        """Compile the batch and dispatch its host→device transfers now
        (async flush pipelining). Pure staging: no kernel runs, no counter
        moves, no serving state changes — safe to call from the submitting
        thread while a worker executes the previous batch."""
        groups = compile_batch(pairs)
        qmats: dict[tuple, jnp.ndarray] = {}
        for gi, group in enumerate(groups):
            items = group.items
            if not group.specs:
                col = self.cstore.device(group.key.vid)
                qmats[(gi, -1)] = col.pad_queries(
                    np.stack([it.query.concat() for it in items]))
                continue
            for j, spec in enumerate(group.specs):
                kind = spec.kind if self.store is not None else "flat"
                if kind in ("flat", "ivf"):
                    col = self.cstore.device(spec.vid)
                    qmats[(gi, j)] = col.pad_queries(
                        np.stack([it.query.concat(spec.vid) for it in items]))
            if not group.single_exact:
                col = self.cstore.device(group.key.vid)
                qmats[(gi, "rerank")] = col.pad_queries(
                    np.stack([it.query.concat() for it in items]))
        return StagedBatch(n=len(pairs), groups=groups, qmats=qmats)

    def _staged_groups(self, pairs, staged: StagedBatch | None):
        """(groups, per-group staged-qmat dicts) — falling back to a fresh
        compile when the staged batch doesn't match the pairs."""
        if staged is not None and staged.n == len(pairs):
            sqs = [{} for _ in staged.groups]
            for (gi, slot), qmat in staged.qmats.items():
                sqs[gi][slot] = qmat
            return staged.groups, sqs
        groups = compile_batch(pairs)
        return groups, [None] * len(groups)

    def _staged_qmat(self, sq, slot, col: DeviceColumn):
        """A staged qmat for this slot, if it still matches the live column
        store's padded width (a swap between staging and execution changes
        ``cstore``; values are recomputed then)."""
        if sq is None:
            return None
        qmat = sq.get(slot)
        if qmat is not None and qmat.shape[1] == col.padded_dim:
            return qmat
        return None

    def search_batch(self, pairs: list[tuple[Query, QueryPlan]],
                     staged: StagedBatch | None = None) -> list[np.ndarray]:
        """Serving form: top-k ids per query, in batch order."""
        out: list[np.ndarray | None] = [None] * len(pairs)
        groups, sqs = self._staged_groups(pairs, staged)
        for group, sq in zip(groups, sqs):
            ids_list, _, _, _ = self._observed_group(group, sq)
            for item, ids in zip(group.items, ids_list):
                out[item.pos] = ids
        return out  # type: ignore[return-value]

    def execute_batch(self, pairs: list[tuple[Query, QueryPlan]],
                      gt_cache: dict[int, np.ndarray] | None = None,
                      staged: StagedBatch | None = None) -> list:
        """Measurement form: ``ExecutionMetrics`` per query, batch order."""
        from repro.core.tuner import ExecutionMetrics  # metrics stay in core
        out = [None] * len(pairs)
        groups, sqs = self._staged_groups(pairs, staged)
        for group, sq in zip(groups, sqs):
            t0 = time.time()
            ids_list, costs, ndists, eks_maps = self._observed_group(group, sq)
            gts = self._group_ground_truth(group, gt_cache)
            wall = (time.time() - t0) * 1e3 / max(group.batch, 1)
            for item, ids, cost, nd, eks, gt in zip(
                    group.items, ids_list, costs, ndists, eks_maps, gts):
                gtset = set(int(i) for i in gt)
                if gtset:
                    rec = len(gtset & set(int(i) for i in ids)) / len(gtset)
                else:  # empty oracle (zero-match predicate): empty is exact
                    rec = 1.0 if len(ids) == 0 else 0.0
                out[item.pos] = ExecutionMetrics(
                    item.query.qid, cost, wall, rec, nd, eks, ids=ids)
        return out

    def execute_workload(self, workload: Workload, result,
                         gt_cache: dict[int, np.ndarray] | None = None):
        from repro.core.tuner import WorkloadMetrics
        pairs = [(q, result.plans[q.qid]) for q, _ in workload]
        metrics = self.execute_batch(pairs, gt_cache=gt_cache)
        wc = sum(p * m.cost for (_, p), m in zip(workload, metrics))
        ww = sum(p * m.wall_ms for (_, p), m in zip(workload, metrics))
        recalls = [m.recall for m in metrics]
        return WorkloadMetrics(
            per_query=metrics, weighted_cost=float(wc), weighted_wall_ms=float(ww),
            min_recall=min(recalls), mean_recall=float(np.mean(recalls)),
            storage=result.storage)

    def execute_plan_single(self, query: Query, plan: QueryPlan):
        """One-query convenience (the ``search.engine`` shim): (ids, cost)."""
        ids_list, costs, _, _ = self._run_group(
            compile_batch([(query, plan)])[0])
        return ids_list[0], costs[0]

    # ---- group execution --------------------------------------------------

    def _observed_group(self, group: PlanGroup, sq: dict | None = None):
        """``_run_group`` wrapped in a live ``plan_group`` span carrying
        the kernel-level attribution: plan signature, index kinds, batch
        size. The span parents to the thread's current span — the
        scheduler's dispatch span when a flush is executing."""
        if not self.obs.enabled:
            return self._run_group(group, sq=sq)
        with self.obs.span("plan_group", **self._group_attrs(group)):
            out = self._run_group(group, sq=sq)
        self.obs.counter("plan_groups")
        return out

    def _group_attrs(self, group: PlanGroup) -> dict:
        """Host-metadata-only attribution (never touches device state)."""
        kinds: list[str] = []
        plansig: list[tuple] = []
        if not group.specs:  # flat plan: one scan of the concat column
            kinds.append("flat")
            plansig.append(("flat", group.key.vid, group.max_k))
        for spec, bucket in zip(group.specs, group.buckets):
            kind = spec.kind if self.store is not None else "flat"
            kinds.append(kind)
            plansig.append((kind, spec.vid, int(bucket)))
        return {"plan_sig": tuple(plansig), "index_kinds": tuple(kinds),
                "access": group.key.access, "batch": len(group.items),
                "rows": int(self.db.n_rows)}

    def _fetch(self, *arrays) -> list[np.ndarray]:
        """Host copies of device results: the blocking read that waits
        for the kernels producing them, in a live ``fetch`` span when
        observed."""
        if not self.obs.enabled:
            return [np.asarray(a) for a in arrays]
        with self.obs.span("fetch"):
            return [np.asarray(a) for a in arrays]

    def _fetch_scan(self, vals, ids, rounds, tiles: int):
        """``_fetch`` of one streaming scan's (vals, ids). When observed,
        its fold ``rounds`` come in the same blocking read and are counted
        against the ``tiles`` row tiles each query block visited: the
        fold engages ``scan_fold_rounds / (scan_row_tiles · min(k, bn))``
        of the time, bn the scan's row tile (``scan_tiles``)."""
        if not self.obs.enabled:
            return self._fetch(vals, ids)
        vals, ids, rounds = self._fetch(vals, ids, rounds)
        self.obs.counter("scan_fold_rounds", int(rounds.sum()))
        self.obs.counter("scan_row_tiles", rounds.size * tiles)
        return vals, ids

    def _run_group(self, group: PlanGroup, sq: dict | None = None):
        if group.key.pred is not None:
            return self._run_group_filtered(group, sq=sq)
        specs, buckets = group.specs, group.buckets
        items = group.items
        B = len(items)
        costs = [0.0] * B
        ndists = [0] * B
        eks_maps: list[dict] = [{} for _ in range(B)]
        mv = self._mv()

        if not specs:  # flat-scan fallback group (no useful index / all ek=0)
            col = self.cstore.device(group.key.vid)
            qmat = self._staged_qmat(sq, -1, col)
            if qmat is None:
                qmat = col.pad_queries(
                    np.stack([it.query.concat() for it in items]))
            if mv is None:
                ids = self._flat_scan(col, qmat, min(group.max_k, col.n_rows))
                out_ids = []
                for i, it in enumerate(items):
                    out_ids.append(ids[i, : min(it.query.k, col.n_rows)])
                    costs[i] = float(it.query.dim() * col.n_rows)
                    ndists[i] = col.n_rows
                return out_ids, costs, ndists, eks_maps
            # mutated table: base + delta merged exactly — ONE streaming
            # launch when available, else masked base scan + delta scan
            if self.streaming and self.mesh is None:
                ms, mids, n_delta = self._merged_scan_mv(
                    mv, col, qmat, group.key.vid, group.max_k)
                bs, bids, ds, dids = ms, mids, None, None
            else:
                bs, bids = self._base_scan_mv(mv, col, qmat,
                                              min(group.max_k, col.n_rows))
                ds, dids, n_delta = self._delta_scan(
                    mv, group.key.vid, items, group.max_k)
            out_ids = []
            for i, it in enumerate(items):
                k_i = min(it.query.k, mv.n_live)
                out_ids.append(self._merge_scored(
                    bs[i], bids[i],
                    None if ds is None else ds[i],
                    None if ds is None else dids[i], k_i))
                costs[i] = float(it.query.dim() * (col.n_rows + n_delta))
                ndists[i] = col.n_rows + n_delta
            return out_ids, costs, ndists, eks_maps

        cand: list[list[np.ndarray]] = [[np.empty(0, np.int64)] * len(specs)
                                        for _ in range(B)]
        for j, (spec, bucket) in enumerate(zip(specs, buckets)):
            kind = spec.kind if self.store is not None else "flat"
            for i, it in enumerate(items):
                eks_maps[i][spec.name] = it.eks[j]
            # with mutations, every branch produces best-first SCORED
            # candidates (stable ids) instead of writing cand directly;
            # the delta merge below finalizes cand[i][j]. A streaming flat
            # scan folds the delta into its own launch (delta_merged).
            scored: list | None = [None] * B if mv is not None else None
            delta_merged = False
            if kind == "ivf":
                self._ivf_scan(group, spec, j, cand, costs, ndists,
                               mv=mv, scored=scored, sq=sq)
            elif kind == "flat":
                col = self.cstore.device(spec.vid)
                qmat = self._staged_qmat(sq, j, col)
                if qmat is None:
                    qmat = col.pad_queries(
                        np.stack([it.query.concat(spec.vid) for it in items]))
                if mv is None:
                    ids = self._flat_scan(col, qmat, min(bucket, col.n_rows))
                    for i, it in enumerate(items):
                        cand[i][j] = ids[i, : min(it.eks[j], col.n_rows)]
                        costs[i] += float(col.dim * col.n_rows)
                        ndists[i] += col.n_rows
                elif self.streaming and self.mesh is None:
                    # base + delta in ONE launch (kernel second source)
                    s, stable, n_dj = self._merged_scan_mv(
                        mv, col, qmat, spec.vid, bucket)
                    for i, it in enumerate(items):
                        scored[i] = (stable[i], s[i])
                        costs[i] += float(col.dim * (col.n_rows + n_dj))
                        ndists[i] += col.n_rows + n_dj
                    delta_merged = True
                else:
                    s, stable = self._base_scan_mv(
                        mv, col, qmat, min(bucket, col.n_rows))
                    for i, it in enumerate(items):
                        scored[i] = (stable[i], s[i])
                        costs[i] += float(col.dim * col.n_rows)
                        ndists[i] += col.n_rows
            else:  # graph kinds: sequential walks — per-query fallback
                idx = self.store.get(spec)
                for i, it in enumerate(items):
                    res = idx.search(it.query.concat(spec.vid), it.eks[j])
                    if mv is None:
                        cand[i][j] = res.ids
                    else:  # drop tombstoned walk results, go stable
                        alive = mv.table.base_alive[res.ids]
                        scored[i] = (mv.translate(res.ids[alive]),
                                     res.scores[alive])
                    costs[i] += float(idx.dim * res.num_dist)
                    ndists[i] += res.num_dist
                    self.counters.fallback += 1
            if mv is not None:
                if delta_merged:  # one-launch scan already holds the delta
                    for i, it in enumerate(items):
                        sids, s = scored[i]
                        cand[i][j] = self._merge_scored(s, sids, None, None,
                                                        it.eks[j])
                else:
                    ds, dids, n_delta = self._delta_scan(
                        mv, spec.vid, items, bucket)
                    for i, it in enumerate(items):
                        sids, s = scored[i]
                        cand[i][j] = self._merge_scored(
                            s, sids, None if ds is None else ds[i],
                            None if ds is None else dids[i], it.eks[j])
                        if n_delta:
                            d = self.db.dim(spec.vid)
                            costs[i] += float(d * n_delta)
                            ndists[i] += n_delta

        if group.single_exact:  # scan output is the full-score order already
            out_ids = [cand[i][0][: items[i].query.k] for i in range(B)]
            return out_ids, costs, ndists, eks_maps

        out_ids = self._rerank(group, cand, mv=mv, sq=sq)
        for i, it in enumerate(items):
            total_ek = int(sum(it.eks))  # duplicates counted — Eq. 6
            costs[i] += float(it.query.dim() * total_ek)
            ndists[i] += total_ek
        return out_ids, costs, ndists, eks_maps

    # ---- filtered execution (DESIGN.md §12) -------------------------------

    def _filter_state(self, pred) -> _FilterState:
        """Evaluate (or fetch) the predicate's bitmaps for the current
        table state. Keyed by (pred, attribute version, table version,
        base rows), so attribute writes, mutations, compaction rebases and
        store swaps all invalidate naturally."""
        attrs = self.attrs
        mv = self._mv()
        tver = -1 if mv is None else mv.table.version
        key = (pred, attrs.version, tver, self.db.n_rows)
        st = self._filter_cache.get(key)
        if st is not None:
            return st
        if mv is None:
            base_keep = attrs.bitmap(pred, np.arange(self.db.n_rows))
            delta_keep = None
            n_match_base = int(base_keep.sum())
            n_match = n_match_base
        else:
            t = mv.table
            base_keep = attrs.bitmap(pred, t.base_ids)
            n_match_base = int((base_keep & t.base_alive).sum())
            n_match = n_match_base
            delta_keep = None
            if t.n_delta:
                delta_keep = attrs.bitmap(pred, t.delta_ids_arr())
                n_match += int((delta_keep & t.delta_alive_arr()).sum())
        st = _FilterState(pred, base_keep, delta_keep, n_match, n_match_base)
        if len(self._filter_cache) > 128:
            self._filter_cache.clear()
        self._filter_cache[key] = st
        return st

    def _run_group_filtered(self, group: PlanGroup, sq: dict | None = None):
        if self.attrs is None:
            raise ValueError(
                "query carries a predicate but no AttributeStore is "
                "attached (BatchEngine.attach_filters) — refusing to "
                "silently ignore the filter")
        fs = self._filter_state(group.key.pred)
        B = len(group.items)
        if fs.n_match == 0:
            # zero-match guard: empty top-k, NO kernel dispatch (an
            # all-masked launch surfaces NEG_INF sentinels as hits).
            # Covers every access path and index kind — the bitmap is the
            # only work done.
            return ([np.empty(0, np.int64) for _ in range(B)],
                    [0.0] * B, [0] * B, [{} for _ in range(B)])
        if group.key.access == "pre":
            return self._prefilter_group(group, fs, sq=sq)
        return self._masked_group(group, fs, sq=sq)

    def _prefilter_group(self, group: PlanGroup, fs: _FilterState,
                         sq: dict | None = None):
        """Pre-filter access path: gather exactly the matching LIVE rows
        (base side + delta side) and brute-force score only those — cost
        dim(q)·|match|, no index involved. Exact by construction: the
        candidate set IS the filtered row set."""
        items = group.items
        B = len(items)
        costs = [0.0] * B
        ndists = [0] * B
        eks_maps: list[dict] = [{} for _ in range(B)]
        vid = group.key.vid
        col = self.cstore.device(vid)
        qmat = self._staged_qmat(sq, -1, col)
        if qmat is None:
            qmat = col.pad_queries(
                np.stack([it.query.concat() for it in items]))
        mv = self._mv()
        parts_s: list[np.ndarray] = []
        parts_ids: list[np.ndarray] = []
        if mv is None:
            bphys = np.nonzero(fs.base_keep)[0]
            if bphys.size:
                sub = col.data[jnp.asarray(bphys.astype(np.int32))]
                parts_s.append(np.asarray(self._batched_scores(qmat, sub)))
                parts_ids.append(bphys.astype(np.int64))
                self.counters.scan += 1
        else:
            t = mv.table
            bphys = np.nonzero(fs.base_keep & t.base_alive)[0]
            if bphys.size:
                sub = col.data[jnp.asarray(bphys.astype(np.int32))]
                parts_s.append(np.asarray(self._batched_scores(qmat, sub)))
                parts_ids.append(mv.translate(bphys))
                self.counters.scan += 1
            if fs.delta_keep is not None:
                dphys = np.nonzero(fs.delta_keep & t.delta_alive_arr())[0]
                if dphys.size:
                    dcol = mv.delta(vid)
                    qd = dcol.col.pad_queries(
                        np.stack([it.query.concat() for it in items]))
                    sub = dcol.col.data[jnp.asarray(dphys.astype(np.int32))]
                    parts_s.append(np.asarray(self._batched_scores(qd, sub)))
                    parts_ids.append(dcol.ids[dphys])
                    self.counters.delta += 1
        scores = np.concatenate(parts_s, axis=1)
        stable = np.concatenate(parts_ids)
        m = int(stable.shape[0])
        out_ids = []
        for i, it in enumerate(items):
            s = scores[i]
            order = np.lexsort((stable, -s))[: min(it.query.k, m)]
            out_ids.append(stable[order].astype(np.int64))
            costs[i] = float(it.query.dim() * m)
            ndists[i] = m
        return out_ids, costs, ndists, eks_maps

    def _masked_group(self, group: PlanGroup, fs: _FilterState,
                      sq: dict | None = None):
        """Masked / post-filter access paths. Flat scans (including the
        no-spec fallback) push the keep bitmap into the kernel row mask
        (keep ∧ ¬dead in-register), so they are exact at any depth ≥ k —
        the "post" access differs only in planned dispatch depth. IVF
        probes score-kill non-matching rows before selection; graph walks
        filter their results; delta segments are keep-masked the same way
        as the base. Under a mesh the same bitmaps ride the distributed
        step's sharded ``bad`` operand — no over-fetch on any path."""
        specs, buckets = group.specs, group.buckets
        items = group.items
        B = len(items)
        costs = [0.0] * B
        ndists = [0] * B
        eks_maps: list[dict] = [{} for _ in range(B)]
        mv = self._mv()

        if not specs:  # keep-masked flat fallback scan
            col = self.cstore.device(group.key.vid)
            qmat = self._staged_qmat(sq, -1, col)
            if qmat is None:
                qmat = col.pad_queries(
                    np.stack([it.query.concat() for it in items]))
            if mv is None:
                s, ids = self._filtered_flat_scan(
                    col, qmat, min(group.max_k, col.n_rows), fs)
                out_ids = []
                for i, it in enumerate(items):
                    out_ids.append(self._merge_scored(
                        s[i], ids[i].astype(np.int64), None, None,
                        min(it.query.k, fs.n_match)))
                    costs[i] = float(it.query.dim() * col.n_rows)
                    ndists[i] = col.n_rows
                return out_ids, costs, ndists, eks_maps
            if self.streaming and self.mesh is None:
                bs, bids, n_delta = self._merged_scan_mv(
                    mv, col, qmat, group.key.vid, group.max_k, fstate=fs)
                ds, dids = None, None
            else:
                bs, bids = self._base_scan_mv(
                    mv, col, qmat, min(group.max_k, col.n_rows), fstate=fs)
                ds, dids, n_delta = self._delta_scan(
                    mv, group.key.vid, items, group.max_k, fstate=fs)
            out_ids = []
            for i, it in enumerate(items):
                k_i = min(it.query.k, fs.n_match)
                out_ids.append(self._merge_scored(
                    bs[i], bids[i],
                    None if ds is None else ds[i],
                    None if ds is None else dids[i], k_i))
                costs[i] = float(it.query.dim() * (col.n_rows + n_delta))
                ndists[i] = col.n_rows + n_delta
            return out_ids, costs, ndists, eks_maps

        cand: list[list[np.ndarray]] = [[np.empty(0, np.int64)] * len(specs)
                                        for _ in range(B)]
        for j, (spec, bucket) in enumerate(zip(specs, buckets)):
            kind = spec.kind if self.store is not None else "flat"
            for i, it in enumerate(items):
                eks_maps[i][spec.name] = it.eks[j]
            # every branch yields best-first (stable ids, scores) of
            # MATCHING candidates only; the delta merge finalizes cand
            scored: list = [None] * B
            delta_merged = False
            if kind == "ivf":
                self._ivf_scan(group, spec, j, cand, costs, ndists,
                               mv=mv, scored=scored, sq=sq, fstate=fs)
            elif kind == "flat":
                col = self.cstore.device(spec.vid)
                qmat = self._staged_qmat(sq, j, col)
                if qmat is None:
                    qmat = col.pad_queries(
                        np.stack([it.query.concat(spec.vid)
                                  for it in items]))
                if mv is None:
                    s, ids = self._filtered_flat_scan(
                        col, qmat, min(bucket, col.n_rows), fs)
                    for i, it in enumerate(items):
                        scored[i] = (ids[i].astype(np.int64), s[i])
                        costs[i] += float(col.dim * col.n_rows)
                        ndists[i] += col.n_rows
                elif self.streaming and self.mesh is None:
                    s, stable, n_dj = self._merged_scan_mv(
                        mv, col, qmat, spec.vid, bucket, fstate=fs)
                    for i, it in enumerate(items):
                        scored[i] = (stable[i], s[i])
                        costs[i] += float(col.dim * (col.n_rows + n_dj))
                        ndists[i] += col.n_rows + n_dj
                    delta_merged = True
                else:
                    s, stable = self._base_scan_mv(
                        mv, col, qmat, min(bucket, col.n_rows), fstate=fs)
                    for i, it in enumerate(items):
                        scored[i] = (stable[i], s[i])
                        costs[i] += float(col.dim * col.n_rows)
                        ndists[i] += col.n_rows
            else:  # graph kinds: walk, then drop non-matching/dead results
                idx = self.store.get(spec)
                for i, it in enumerate(items):
                    res = idx.search(it.query.concat(spec.vid), it.eks[j])
                    ok = fs.base_keep[res.ids]
                    if mv is not None:
                        ok = ok & mv.table.base_alive[res.ids]
                    rows = res.ids[ok]
                    stable = (mv.translate(rows) if mv is not None
                              else rows.astype(np.int64))
                    scored[i] = (stable, res.scores[ok])
                    costs[i] += float(idx.dim * res.num_dist)
                    ndists[i] += res.num_dist
                    self.counters.fallback += 1
            if delta_merged:  # one-launch scan already holds the delta
                for i, it in enumerate(items):
                    sids, s = scored[i]
                    cand[i][j] = self._merge_scored(s, sids, None, None,
                                                    it.eks[j])
            else:
                ds, dids, n_delta = (self._delta_scan(
                    mv, spec.vid, items, bucket, fstate=fs)
                    if mv is not None else (None, None, 0))
                for i, it in enumerate(items):
                    sids, s = scored[i]
                    cand[i][j] = self._merge_scored(
                        s, sids, None if ds is None else ds[i],
                        None if ds is None else dids[i], it.eks[j])
                    if n_delta:
                        d = self.db.dim(spec.vid)
                        costs[i] += float(d * n_delta)
                        ndists[i] += n_delta

        if group.single_exact:
            out_ids = [cand[i][0][: items[i].query.k] for i in range(B)]
            return out_ids, costs, ndists, eks_maps

        out_ids = self._rerank(group, cand, mv=mv, sq=sq)
        for i, it in enumerate(items):
            total_ek = int(sum(it.eks))
            costs[i] += float(it.query.dim() * total_ek)
            ndists[i] += total_ek
        return out_ids, costs, ndists, eks_maps

    def _filtered_flat_scan(self, col: DeviceColumn, qmat: jnp.ndarray,
                            depth: int, fs: _FilterState,
                            dead_mask=None) -> tuple[np.ndarray, np.ndarray]:
        """Keep-masked flat scan over an unmutated base: kernel paths get
        the device keep bitmap; the distributed step threads the same
        bitmap through its sharded ``bad`` operand, so mesh cells no
        longer over-fetch past non-matching rows and host-filter. Returns
        (scores, physical ids), best-first."""
        return self._flat_scan_scored(
            col, qmat, depth, dead_mask=dead_mask,
            keep_mask=fs.base_keep_dev(int(col.data.shape[0])))

    def _filtered_ground_truth(self, query: Query, pred) -> np.ndarray:
        """Brute-force oracle: exact top-k over exactly the live rows
        matching the predicate (canonical score desc, stable id asc
        order) — the bit-identity target for every access path."""
        fs = self._filter_state(pred)
        mv = self._mv()
        qvec = query.concat()
        if mv is None:
            data = self.cstore.host(query.vid)
            rows = np.nonzero(fs.base_keep)[0]
            s = data[rows] @ qvec
            order = np.lexsort((rows, -s))
            return rows[order][: min(query.k, rows.size)].astype(np.int64)
        t = mv.table
        ids_parts: list[np.ndarray] = []
        s_parts: list[np.ndarray] = []
        bphys = np.nonzero(fs.base_keep & t.base_alive)[0]
        if bphys.size:
            base = t.base.concat(query.vid)
            ids_parts.append(mv.translate(bphys))
            s_parts.append(base[bphys] @ qvec)
        if fs.delta_keep is not None:
            dphys = np.nonzero(fs.delta_keep & t.delta_alive_arr())[0]
            if dphys.size:
                dmat = t.delta_concat(query.vid)
                ids_parts.append(t.delta_ids_arr()[dphys])
                s_parts.append(dmat[dphys] @ qvec)
        if not ids_parts:
            return np.empty(0, np.int64)
        ids = np.concatenate(ids_parts)
        s = np.concatenate(s_parts)
        order = np.lexsort((ids, -s))
        return ids[order][: min(query.k, ids.size)].astype(np.int64)

    def _batched_scores(self, qmat: jnp.ndarray, sub: jnp.ndarray) -> jnp.ndarray:
        """One batched scoring dispatch. On TPU this is the Pallas MXU
        kernel; under interpret mode (CPU container) the same contraction
        goes through one jitted XLA matmul instead — interpret-mode kernels
        execute their grid in Python, which would serialize the batch and
        invert the benchmark."""
        interp = self.interpret if self.interpret is not None else default_interpret()
        if interp:
            return _xla_scores(qmat, sub)
        return batched_scores(qmat, sub, interpret=False)

    def cache_probe(self, qmat, mat, valid_n):
        """Semantic-cache probe hook (DESIGN.md §13): one batched L2
        dispatch of query vectors against the cache's query matrix, on
        this engine's kernel route (streaming on TPU, XLA under
        interpret). The ``SemanticCache`` is handed this bound method as
        its ``scan`` so the probe rides the same dispatch discipline as
        everything else the engine launches."""
        return cache_probe_scan(qmat, mat, valid_n, interpret=self.interpret)

    def _flat_scan(self, col: DeviceColumn, qmat: jnp.ndarray, k: int) -> np.ndarray:
        return self._flat_scan_scored(col, qmat, k)[1]

    def _flat_scan_scored(self, col: DeviceColumn, qmat: jnp.ndarray, k: int,
                          dead_mask=None, keep_mask=None,
                          counter: str = "scan"
                          ) -> tuple[np.ndarray, np.ndarray]:
        """One batched flat dispatch -> (scores, ids), best-first. The
        tombstone ``dead_mask`` and the predicate ``keep_mask`` are threaded
        into the kernel row mask — and, under a mesh, composed into the
        distributed step's sharded ``bad`` operand — so masked rows come
        back at -inf (id 0) and are dropped by the merge on every path."""
        setattr(self.counters, counter, getattr(self.counters, counter) + 1)
        if self.mesh is not None:
            bad = None
            if dead_mask is not None or keep_mask is not None:
                # compose tombstones ∪ ¬predicate into one (N,) f32 row
                # bitmap on the host and place it sharded P(axis) exactly
                # like the column rows — never whole on one device first
                bad = np.zeros(int(col.data.shape[0]), dtype=np.float32)
                if dead_mask is not None:
                    bad[np.asarray(dead_mask, dtype=bool)] = 1.0
                if keep_mask is not None:
                    bad[~np.asarray(keep_mask, dtype=bool)] = 1.0
                bad = jax.device_put(bad, row_sharding(self.mesh, self.axis))
            key = (k, col.n_rows, bad is not None)
            if key not in self._dist_steps:
                from repro.search.distributed import make_search_step
                self._dist_steps[key] = make_search_step(
                    self.mesh, k=k, axis=self.axis, valid_n=col.n_rows,
                    masked=bad is not None)
            step = self._dist_steps[key]
            vals, ids = (step(col.data, qmat, bad) if bad is not None
                         else step(col.data, qmat))
        elif self.streaming:
            vals, ids, rounds = streaming_fused_scan(
                qmat, col.data, k=min(k, col.n_rows), valid_n=col.n_rows,
                dead_mask=dead_mask, keep_mask=keep_mask,
                interpret=self.interpret)
            return self._fetch_scan(
                vals, ids, rounds,
                row_tiles(qmat, col.data, min(k, col.n_rows)))
        else:
            vals, ids = fused_scan(qmat, col.data, k=k, valid_n=col.n_rows,
                                   dead_mask=dead_mask, keep_mask=keep_mask,
                                   interpret=self.interpret)
        vals, ids = self._fetch(vals, ids)
        return vals, ids

    # ---- mutation-aware scanning (repro.ingest) ---------------------------

    def _base_scan_mv(self, mv, col: DeviceColumn, qmat: jnp.ndarray,
                      depth: int, fstate: _FilterState | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Masked base scan under mutations -> (scores, STABLE ids).
        Tombstones ∪ non-matching rows ride the kernel row mask on the
        single-device paths and the distributed step's sharded ``bad``
        operand under a mesh — every path returns the exact alive (and
        matching) top-``depth`` with no over-fetch."""
        dead = mv.base_dead_mask(int(col.data.shape[0]))
        keep = (None if fstate is None
                else fstate.base_keep_dev(int(col.data.shape[0])))
        s, ids = self._flat_scan_scored(col, qmat,
                                        min(depth, col.n_rows),
                                        dead_mask=dead, keep_mask=keep)
        return s, mv.translate(ids)

    def _delta_scan(self, mv, vid, items, depth: int,
                    fstate: _FilterState | None = None):
        """Brute-force delta-segment scan for one (group, index): one
        batched dispatch over the padded delta matrix -> (scores, STABLE
        ids, n_delta_rows); (None, None, 0) when the table has no delta.
        Tombstone and predicate masks ride the dispatch on every path —
        the distributed step takes them through its sharded ``bad``
        operand, so mesh cells no longer over-fetch the whole delta."""
        dcol = mv.delta(vid)
        if dcol is None:
            return None, None, 0
        qmat = dcol.col.pad_queries(
            np.stack([it.query.concat(vid) for it in items]))
        k_eff = min(depth, dcol.n_rows)
        keep = None
        if fstate is not None:
            keep = fstate.delta_keep_dev(int(dcol.col.data.shape[0]))
        s, ids = self._flat_scan_scored(dcol.col, qmat, k_eff,
                                        dead_mask=dcol.dead_mask,
                                        keep_mask=keep, counter="delta")
        return s, dcol.ids[ids], dcol.n_rows

    def _merged_scan_mv(self, mv, col: DeviceColumn, qmat: jnp.ndarray,
                        vid, depth: int, fstate: _FilterState | None = None):
        """ONE ``streaming_fused_scan`` launch over base + delta: the delta
        segment rides the kernel's second row source, tombstones on both
        sides are masked in-register, and the merged best-first candidates
        come back without ever materializing a score matrix or a separate
        delta dispatch. Returns (scores, STABLE ids, n_delta_rows) with the
        same contract as a ``_base_scan_mv`` + ``_delta_scan`` pair already
        merged; callers finalize with ``_merge_scored`` (lexsort + dead
        drop) exactly as before, so the (score desc, stable id asc) order
        is preserved. Requires the streaming path and no mesh — other
        configurations keep the two-dispatch scan-then-merge."""
        dcol = mv.delta(vid)
        dead = mv.base_dead_mask(int(col.data.shape[0]))
        bkeep = (None if fstate is None
                 else fstate.base_keep_dev(int(col.data.shape[0])))
        if dcol is None:  # no delta rows: plain masked base scan
            s, ids = self._flat_scan_scored(col, qmat,
                                            min(depth, col.n_rows),
                                            dead_mask=dead, keep_mask=bkeep)
            return s, mv.translate(ids), 0
        dkeep = (None if fstate is None
                 else fstate.delta_keep_dev(int(dcol.col.data.shape[0])))
        self.counters.scan += 1
        k_eff = min(depth, col.n_rows + dcol.n_rows)
        vals, ids, rounds = streaming_fused_scan(
            qmat, col.data, k=k_eff, valid_n=col.n_rows, dead_mask=dead,
            delta=dcol.col.data, delta_valid_n=dcol.n_rows,
            delta_dead_mask=dcol.dead_mask, keep_mask=bkeep,
            delta_keep_mask=dkeep, interpret=self.interpret)
        vals, ids = self._fetch_scan(
            vals, ids, rounds,
            row_tiles(qmat, col.data, k_eff, dcol.col.data))
        # combined-physical ids -> stable: delta rows are offset by the
        # PADDED base row count (the kernel's id space)
        base_pad_rows = int(col.data.shape[0])
        stable = np.empty(ids.shape, dtype=np.int64)
        on_base = ids < base_pad_rows
        stable[on_base] = mv.translate(ids[on_base])
        stable[~on_base] = dcol.ids[ids[~on_base] - base_pad_rows]
        return vals, stable, dcol.n_rows

    @staticmethod
    def _merge_scored(s_base, ids_base, s_delta, ids_delta, k: int) -> np.ndarray:
        """Best-first merge of scored candidate lists in the canonical
        rebuild order — score desc, stable id asc (a materialized rebuild
        lays rows out by ascending stable id, so its scan breaks ties the
        same way). Masked tombstones/padding (-inf) are dropped."""
        if s_delta is not None:
            s = np.concatenate([s_base, s_delta])
            ids = np.concatenate([ids_base, ids_delta])
        else:
            s, ids = s_base, ids_base
        keep = s > _DEAD_CUT
        s, ids = s[keep], ids[keep]
        order = np.lexsort((ids, -s))[:k]
        return ids[order].astype(np.int64)

    def _ivf_scan(self, group: PlanGroup, spec, j: int, cand, costs, ndists,
                  mv=None, scored=None, sq: dict | None = None,
                  fstate: _FilterState | None = None):
        """Batched IVF probe: one centroid-scoring dispatch for the whole
        group, then one gathered-row scoring dispatch over the padded probe
        union. Per-query nprobe / top-ek use each query's ACTUAL ek so the
        results match ``IVFFlatIndex.search`` exactly. Under mutations
        (``mv``), tombstoned rows are score-killed before selection and the
        surviving candidates land in ``scored`` as (stable ids, scores) for
        the delta merge; under a predicate (``fstate``) non-matching probe
        rows are score-killed the same way."""
        idx = self.store.get(spec)
        items = group.items
        col = self.cstore.device(spec.vid)
        qmat = self._staged_qmat(sq, j, col)
        if qmat is None:
            qmat = col.pad_queries(
                np.stack([it.query.concat(spec.vid) for it in items]))
        cent = np.asarray(idx.centroids, dtype=np.float32)
        if col.padded_dim != cent.shape[1]:
            cent = np.pad(cent, ((0, 0), (0, col.padded_dim - cent.shape[1])))
        csims, = self._fetch(self._batched_scores(qmat, jnp.asarray(cent)))
        self.counters.scan += 1

        rows_list = []
        for i, it in enumerate(items):
            ek = it.eks[j]
            nprobe = idx._nprobe_for(ek)
            probe = np.argsort(-csims[i], kind="stable")[:nprobe]
            rows = np.concatenate([
                idx.row_ids[idx.offsets[p]:idx.offsets[p + 1]] for p in probe
            ]) if nprobe else np.empty(0, dtype=np.int64)
            rows_list.append(rows)
            costs[i] += float(idx.dim * (idx.n_lists + rows.shape[0]))
            ndists[i] += idx.n_lists + int(rows.shape[0])

        R = max(max((r.shape[0] for r in rows_list), default=1), 1)
        chunk = gather_chunk(len(items), R, col.padded_dim)
        rows_mat = np.zeros((len(items), -(-R // chunk) * chunk),
                            dtype=np.int32)
        for i, rows in enumerate(rows_list):
            rows_mat[i, : rows.shape[0]] = rows
        scores, = self._fetch(_gather_scores(col.data, jnp.asarray(rows_mat),
                                             qmat, chunk=chunk))
        for i, (it, rows) in enumerate(zip(items, rows_list)):
            if rows.shape[0] == 0:
                if scored is not None:
                    scored[i] = (np.empty(0, np.int64),
                                 np.empty(0, np.float32))
                else:
                    cand[i][j] = np.empty(0, np.int64)
                continue
            s = scores[i, : rows.shape[0]]
            ok = None
            if mv is not None:  # tombstones: dead probe rows never rank
                ok = mv.table.base_alive[rows]
            if fstate is not None:  # predicate: non-matching rows neither
                keep_rows = fstate.base_keep[rows]
                ok = keep_rows if ok is None else ok & keep_rows
            if ok is not None:
                s = np.where(ok, s, NEG_INF).astype(np.float32)
            ek = min(it.eks[j], rows.shape[0])
            part = np.argpartition(-s, ek - 1)[:ek]
            order = np.argsort(-s[part], kind="stable")
            sel = part[order]
            if scored is not None:
                keep = s[sel] > _DEAD_CUT
                srows = rows[sel][keep]
                stable = (mv.translate(srows) if mv is not None
                          else srows.astype(np.int64))
                scored[i] = (stable, s[sel][keep])
            else:
                cand[i][j] = rows[sel]

    def _rerank(self, group: PlanGroup, cand, mv=None,
                sq: dict | None = None) -> list[np.ndarray]:
        """Full-score rerank over each query's candidate union, batched as
        ONE ``batched_scores`` dispatch over the group-wide union; per-query
        selection slices its own candidates (sorted ids + stable ordering —
        the same tie-breaking as the per-query numpy path). Under mutations
        the union holds stable ids and each is gathered from whichever side
        (base column / delta segment) physically stores it."""
        items = group.items
        col = self.cstore.device(group.key.vid)
        unions = []
        for i in range(len(items)):
            parts = [c for c in cand[i] if c.shape[0]]
            unions.append(np.unique(np.concatenate(parts)) if parts
                          else np.empty(0, np.int64))
        nonempty = [u for u in unions if u.shape[0]]
        if not nonempty:
            return [np.empty(0, np.int64) for _ in items]
        t_r0 = time.perf_counter() if self.obs.enabled else 0.0
        gunion = np.unique(np.concatenate(nonempty))
        qmat = self._staged_qmat(sq, "rerank", col)
        if qmat is None:
            qmat = col.pad_queries(
                np.stack([it.query.concat() for it in items]))
        if mv is None:
            sub = col.data[jnp.asarray(gunion.astype(np.int32))]
            scores, = self._fetch(self._batched_scores(qmat, sub))
        else:
            scores = self._mv_union_scores(mv, group, col, qmat, gunion)
        self.counters.rerank += 1
        if self.obs.enabled:
            self.obs.span_at("rerank", t_r0, time.perf_counter(),
                             parent=self.obs.current(), batch=len(items),
                             union=int(gunion.shape[0]))
        out = []
        for i, it in enumerate(items):
            if unions[i].shape[0] == 0:
                out.append(np.empty(0, np.int64))
                continue
            pos = np.searchsorted(gunion, unions[i])
            s = scores[i, pos]
            top = np.argsort(-s, kind="stable")[: it.query.k]
            out.append(unions[i][top])
        return out

    def _mv_union_scores(self, mv, group: PlanGroup, col: DeviceColumn,
                         qmat: jnp.ndarray, gunion: np.ndarray) -> np.ndarray:
        """Rerank scores for a STABLE-id union: base-located ids gather
        from the resident base column (one dispatch), delta-located from
        the delta segment (one more). Score values are bit-identical to a
        rebuild's single gather — each row's dot product only sees its own
        (identically padded) values."""
        is_delta, phys = mv.locate(gunion)
        out = np.empty((qmat.shape[0], gunion.shape[0]), dtype=np.float32)
        bpos = np.nonzero(~is_delta)[0]
        if bpos.size:
            sub = col.data[jnp.asarray(phys[bpos].astype(np.int32))]
            out[:, bpos] = np.asarray(self._batched_scores(qmat, sub))
        dpos = np.nonzero(is_delta)[0]
        if dpos.size:
            dcol = mv.delta(group.key.vid)
            qd = dcol.col.pad_queries(
                np.stack([it.query.concat() for it in group.items]))
            sub = dcol.col.data[jnp.asarray(phys[dpos].astype(np.int32))]
            out[:, dpos] = np.asarray(self._batched_scores(qd, sub))
        return out

    def _group_ground_truth(self, group: PlanGroup, gt_cache):
        items = group.items
        missing = [i for i, it in enumerate(items)
                   if gt_cache is None or it.query.qid not in gt_cache]
        gts: list[np.ndarray | None] = [
            None if gt_cache is None else gt_cache.get(it.query.qid)
            for it in items]
        if missing:
            if group.key.pred is not None:  # filtered oracle, stable ids
                for i in missing:
                    gts[i] = self._filtered_ground_truth(items[i].query,
                                                         group.key.pred)
                return gts
            mv = self._mv()
            if mv is not None:  # oracle over the LIVE table, stable ids
                for i in missing:
                    gts[i] = mv.ground_truth(items[i].query)
                return gts
            data = self.cstore.host(group.key.vid)
            for i in missing:
                q = items[i].query
                gts[i], _ = exact_topk(data, q.concat(), q.k)
        return gts

"""Streaming fused scan parity (DESIGN.md §11).

The one-launch kernel (distance + in-register masking + online top-k,
optional delta second source) must be BIT-IDENTICAL — values AND ids — to
the two-pass oracle (``streaming_fused_scan_ref``) across metric × dtype ×
ragged shapes, and ``BatchEngine``'s one-launch base+delta merged scan
must equal the two-dispatch merge for every index kind. The fast lane
keeps smoke cases; the CI ``kernels`` job runs the whole file with
``-m ""`` so the slow grid is exercised on every PR.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import IndexSpec, QueryPlan, Workload
from repro.data.vectors import make_database, make_queries
from repro.index.bruteforce import batch_exact_topk
from repro.index.ivf import _scan_gathered
from repro.index.registry import IndexStore
from repro.ingest import (DeleteBatch, InsertBatch, MutableTable,
                          MutationView, UpsertBatch)
from repro.kernels.distance.kernel import batched_scores
from repro.kernels.distance.ops import _mask_rows
from repro.kernels.streaming.ops import row_tiles, streaming_fused_scan
from repro.kernels.streaming.ref import streaming_fused_scan_ref
from repro.kernels.topk.kernel import NEG_INF, neg_inf_for, topk_scores
from repro.obs import NULL_OBSERVER, Observer
from repro.online.trace import row_batch
from repro.serve import engine as engine_mod
from repro.serve.engine import BatchEngine

# ---- kernel-level parity grid ---------------------------------------------

# ragged shape cases: N not a multiple of the 128 row tile, valid_n < N,
# k > live rows, all rows dead, B == 1, and B == max dispatch batch —
# with and without the delta second source
CASES = {
    "ragged_n": dict(B=4, N=300, d=48, k=20),
    "pad_and_dead": dict(B=17, N=384, d=100, k=25, valid_n=260, n_dead=30),
    "k_gt_live": dict(B=3, N=130, d=32, k=200, valid_n=100, n_dead=95),
    "all_dead": dict(B=2, N=200, d=16, k=10, n_dead=200),
    "b1_delta": dict(B=1, N=520, d=64, k=50, valid_n=500, n_dead=10,
                     delta=dict(N=70, valid_n=60, n_dead=5)),
    "maxbatch_delta": dict(B=128, N=256, d=64, k=10,
                           delta=dict(N=40, n_dead=0)),
}


def _mk(rng, n, d, dtype):
    return jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)
                       ).astype(dtype)


def _dead(rng, n, n_dead):
    if n_dead is None:
        return None
    m = np.zeros(n, dtype=bool)
    if n_dead:
        m[rng.choice(n, size=n_dead, replace=False)] = True
    return jnp.asarray(m)


def _assert_bit_identical(case, metric, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = _mk(rng, case["B"], case["d"], dtype)
    db = _mk(rng, case["N"], case["d"], dtype)
    kw = dict(valid_n=case.get("valid_n"),
              dead_mask=_dead(rng, case["N"], case.get("n_dead")))
    dl = case.get("delta")
    if dl:
        kw.update(delta=_mk(rng, dl["N"], case["d"], dtype),
                  delta_valid_n=dl.get("valid_n"),
                  delta_dead_mask=_dead(rng, dl["N"], dl.get("n_dead")))
    vals, ids, _ = streaming_fused_scan(q, db, k=case["k"], metric=metric,
                                        interpret=True, **kw)
    rvals, rids = streaming_fused_scan_ref(q, db, k=case["k"], metric=metric,
                                           interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


@pytest.mark.parametrize("name", ["pad_and_dead", "b1_delta"])
def test_streaming_parity_smoke(name):
    _assert_bit_identical(CASES[name], "dot", jnp.float32)


@pytest.mark.slow  # full interpret-mode grid; CI kernels job runs it
@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("metric", ["dot", "cosine", "l2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streaming_parity_grid(name, metric, dtype):
    _assert_bit_identical(CASES[name], metric, dtype,
                          seed=abs(hash(name)) % 1000)


def test_streaming_all_dead_tail_contract():
    """k slots over zero live rows: every slot comes back (NEG_INF, 0) —
    the contract callers use to drop masked tails."""
    rng = np.random.default_rng(3)
    q = _mk(rng, 2, 16, jnp.float32)
    db = _mk(rng, 200, 16, jnp.float32)
    vals, ids, rounds = streaming_fused_scan(
        q, db, k=10, dead_mask=jnp.ones(200, bool), interpret=True)
    assert np.all(np.asarray(vals) == NEG_INF)
    assert np.all(np.asarray(ids) == 0)
    assert np.asarray(rounds).tolist() == [0]  # no tile can win a slot


# ---- the gated fold: only the rounds a row tile can win --------------------

GATE_B, GATE_N, GATE_D, GATE_BN = 5, 1024, 32, 128   # B not a multiple of 8


def _gate_rows(rng, n, order):
    """(queries, rows) whose every query scores the rows in ``order``
    under dot, cosine and l2 alike: rows t·e0 + v with |v| constant and
    v ⟂ e0, queries c·e0 with c past every t, so a score rises with t.
    'ascending' hands every tile min(k, bn) winning scores, 'descending'
    none once the buffer is full; 'random' is plain Gaussian data."""
    if order == "random":
        return (rng.standard_normal((GATE_B, GATE_D)).astype(np.float32),
                rng.standard_normal((n, GATE_D)).astype(np.float32))
    t = np.arange(1, n + 1, dtype=np.float32)
    if order == "descending":
        t = t[::-1].copy()
    v = rng.standard_normal((n, GATE_D - 1))
    v *= n / np.linalg.norm(v, axis=1, keepdims=True)
    rows = np.concatenate([t[:, None], v], axis=1).astype(np.float32)
    q = np.zeros((GATE_B, GATE_D), np.float32)
    q[:, 0] = n + 16 + np.arange(GATE_B)
    return q, rows


def _gate_scan(metric, order, k, masked, seed=0):
    """Gated op and two-pass oracle on one ``_gate_rows`` table; masked
    adds tombstones, a keep mask, a valid_n cut and a delta source that
    continues the base's order."""
    rng = np.random.default_rng(seed)
    n_delta = 256 if masked else 0
    q, rows = _gate_rows(rng, GATE_N + n_delta, order)
    q, rows = jnp.asarray(q), jnp.asarray(rows)
    kw = {}
    if masked:
        kw = dict(valid_n=GATE_N - 40,
                  dead_mask=jnp.asarray(rng.random(GATE_N) < 0.1),
                  keep_mask=jnp.asarray(rng.random(GATE_N) < 0.8),
                  delta=rows[GATE_N:], delta_valid_n=n_delta - 10,
                  delta_dead_mask=jnp.asarray(rng.random(n_delta) < 0.1),
                  delta_keep_mask=jnp.asarray(rng.random(n_delta) < 0.8))
    got = streaming_fused_scan(q, rows[:GATE_N], k=k, metric=metric,
                               interpret=True, **kw)
    ref = streaming_fused_scan_ref(q, rows[:GATE_N], k=k, metric=metric,
                                   interpret=True, **kw)
    return [np.asarray(a) for a in got], [np.asarray(a) for a in ref]


@pytest.mark.parametrize("order", ["random", "ascending", "descending"])
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked_delta"])
@pytest.mark.parametrize("metric", ["dot", "cosine", "l2"])
def test_gated_fold_bit_identical(metric, masked, order):
    (vals, ids, _), (rvals, rids) = _gate_scan(metric, order, 20, masked)
    np.testing.assert_array_equal(vals, rvals)
    np.testing.assert_array_equal(ids, rids)


@pytest.mark.parametrize("order", ["random", "ascending", "descending"])
def test_gated_fold_k_past_row_tile(order):
    """k = 200 > bn: a tile runs at most bn rounds, and still every one
    the k-round fold would have used."""
    (vals, ids, rounds), (rvals, rids) = _gate_scan("l2", order, 200, False)
    np.testing.assert_array_equal(vals, rvals)
    np.testing.assert_array_equal(ids, rids)
    assert rounds.max() <= row_tiles(GATE_N) * GATE_BN


def _k_round_fold(scores, k, bn=GATE_BN):
    """The fold without its gate, on the host: k rounds in every row tile
    of a (B, N) score matrix (first max of the tile against the first
    slot holding the buffer's least value, strict improvement only), then
    the wrapper's best-first ordering."""
    B, n = scores.shape
    vals = np.full((B, k), NEG_INF, np.float32)
    idxs = np.zeros((B, k), np.int32)
    rows = np.arange(B)
    for j0 in range(0, n, bn):
        s = scores[:, j0:j0 + bn].copy()
        for _ in range(k):
            am = np.argmax(s, axis=1)
            m = s[rows, am]
            pmin = np.argmin(vals, axis=1)
            hit = m > vals[rows, pmin]
            vals[rows[hit], pmin[hit]] = m[hit]
            idxs[rows[hit], pmin[hit]] = j0 + am[hit]
            s[rows, am] = NEG_INF
    order = np.argsort(-vals, axis=1, kind="stable")
    return (np.take_along_axis(vals, order, 1),
            np.take_along_axis(idxs, order, 1))


@pytest.mark.parametrize("metric", ["dot", "cosine", "l2"])
def test_gated_fold_ties_bit_identical(metric):
    """Every row twice, within and across tiles. The gated op equals the
    k-round fold bit for bit, tie order included; against the two-pass
    oracle the values match bit for bit and the same ids win, while the
    order among equal scores follows each path's own buffer (bn 128 here,
    512 there) as it did before the gate."""
    rng = np.random.default_rng(7)
    q, rows = _gate_rows(rng, GATE_N, "random")
    rows[1::2] = rows[::2]
    rows[GATE_N // 2:] = rows[:GATE_N // 2]
    q, rows = jnp.asarray(q), jnp.asarray(rows)
    vals, ids, _ = [np.asarray(a) for a in streaming_fused_scan(
        q, rows, k=20, metric=metric, interpret=True)]
    scores = np.asarray(batched_scores(q, rows, metric=metric, bk=128,
                                       interpret=True))
    mvals, mids = _k_round_fold(scores, 20)
    np.testing.assert_array_equal(vals, mvals)
    np.testing.assert_array_equal(ids, mids)
    rvals, rids = [np.asarray(a) for a in streaming_fused_scan_ref(
        q, rows, k=20, metric=metric, interpret=True)]
    np.testing.assert_array_equal(vals, rvals)
    for b in range(GATE_B):
        assert sorted(zip(-vals[b], ids[b])) == sorted(zip(-rvals[b], rids[b]))


@pytest.mark.parametrize("k", [20, 200])
@pytest.mark.parametrize("order", ["ascending", "descending"])
@pytest.mark.parametrize("metric", ["dot", "cosine", "l2"])
def test_fold_rounds_exact(metric, order, k):
    """Ascending rows: every tile wins min(k, bn) rounds. Descending: only
    the tiles that fill the buffer fold. Under l2 the padding query rows'
    scores rise along a descending table: they must not open the gate."""
    (_, _, rounds), _ = _gate_scan(metric, order, k, False)
    per_tile = min(k, GATE_BN)
    tiles = row_tiles(GATE_N)
    want = tiles * per_tile if order == "ascending" else -(-k // GATE_BN) * per_tile
    assert rounds.tolist() == [want]


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked_delta"])
def test_fold_rounds_bounded(masked):
    (_, _, rounds), _ = _gate_scan("dot", "random", 20, masked, seed=3)
    tiles = row_tiles(GATE_N, 256 if masked else 0)
    assert rounds.shape == (1,)
    assert 20 <= rounds[0] <= tiles * 20   # the first tile always folds


# ---- satellite: per-dtype top-k sentinel -----------------------------------


def test_neg_inf_for_per_dtype():
    assert neg_inf_for(jnp.float32) == NEG_INF
    b = neg_inf_for(jnp.bfloat16)
    assert np.isfinite(b) and b <= NEG_INF          # finite, representable
    assert float(jnp.asarray(b, jnp.bfloat16)) == b  # exactly
    assert neg_inf_for(jnp.float16) == float("-inf")  # -65504 would win slots


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_topk_narrow_dtype_all_dead_tail(dtype):
    """Regression for the NEG_INF padding sentinel in non-f32 scores: with
    only 10 live rows and an all-dead tail masked at the dtype sentinel,
    k=16 must surface exactly the live ids; no masked row (or pad column)
    may beat an empty buffer slot."""
    rng = np.random.default_rng(4)
    s = jnp.asarray(rng.standard_normal((4, 100)).astype(np.float32)
                    ).astype(dtype)
    dead = np.zeros(100, dtype=bool)
    dead[10:] = True
    s = jnp.where(jnp.asarray(dead)[None, :], neg_inf_for(dtype), s)
    vals, idxs = topk_scores(s, 16, interpret=True)
    vals, idxs = np.asarray(vals), np.asarray(idxs)
    for b in range(4):
        assert set(idxs[b, :10]) == set(range(10))
        assert np.all(vals[b, 10:] <= NEG_INF)


# ---- satellite: traced valid_n does not recompile per table size ----------


def test_mask_rows_single_compile_across_valid_n():
    if not hasattr(_mask_rows, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    s = jnp.ones((4, 64), jnp.float32)
    _mask_rows(s, 10, None)
    base = _mask_rows._cache_size()
    _mask_rows(s, 33, None)
    _mask_rows(s, 64, None)
    assert _mask_rows._cache_size() == base  # valid_n is traced, not static


# ---- index entry points route through the kernel ---------------------------


def test_batch_exact_topk_kernel_route_matches_blocked():
    rng = np.random.default_rng(5)
    data = rng.standard_normal((300, 32)).astype(np.float32)
    q = rng.standard_normal((5, 32)).astype(np.float32)
    ids0, s0 = batch_exact_topk(data, q, 20, use_kernel=False)
    ids1, s1 = batch_exact_topk(data, q, 20, use_kernel=True)
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_allclose(s0, s1, rtol=1e-6)


def test_ivf_gathered_scan_kernel_route_matches_numpy():
    rng = np.random.default_rng(6)
    sub = rng.standard_normal((150, 24)).astype(np.float32)
    q = rng.standard_normal(24).astype(np.float32)
    sel0, s0 = _scan_gathered(sub, q, 17, use_kernel=False)
    sel1, s1 = _scan_gathered(sub, q, 17, use_kernel=True)
    np.testing.assert_array_equal(sel0, sel1)
    np.testing.assert_allclose(s0, s1, rtol=1e-6)


# ---- engine: one-launch merged scan == two-dispatch merge ------------------

COLS = [("a", 24), ("b", 32)]


@pytest.fixture(scope="module")
def db():
    return make_database(500, COLS, seed=0)


def _churned(db, seed=21):
    t = MutableTable(db)
    rng = np.random.default_rng(seed)
    t.apply(InsertBatch(row_batch(db, rng, 40)))
    t.apply(DeleteBatch(rng.choice(t.live_ids(), size=55, replace=False)))
    ids = rng.choice(t.live_ids(), size=6, replace=False)
    t.apply(UpsertBatch(ids, row_batch(db, rng, 6)))
    return t


def _pair_engines(db, t, seed=0, with_store=True):
    """Two engines over the SAME index structures and the SAME live table;
    only the scan implementation differs."""
    es = BatchEngine(db, store=IndexStore(db, seed=seed) if with_store else None,
                     streaming=True)
    et = BatchEngine(db, store=IndexStore(db, seed=seed) if with_store else None,
                     streaming=False)
    es.attach_mutations(MutationView(t))
    et.attach_mutations(MutationView(t))
    return es, et


def _assert_engines_equal(es, et, pairs):
    got = es.search_batch(pairs)
    ref = et.search_batch(pairs)
    for (q, _), g, r in zip(pairs, got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=f"vid={q.vid}")


@pytest.mark.parametrize("kind", ["flat", "ivf", "hnsw", "diskann"])
def test_engine_merged_scan_equals_two_dispatch(db, kind):
    """For every index kind, streaming=True (one merged base+delta launch
    on flat paths) and streaming=False (separate delta dispatch) must
    return identical stable ids."""
    t = _churned(db)
    es, et = _pair_engines(db, t)
    qs = make_queries(db, [(0, 1), (0, 1)], k=10, seed=13)
    pairs = [(qs[0], QueryPlan(qs[0].qid,
                               [IndexSpec((0,), kind), IndexSpec((1,), kind)],
                               [40, 40], 1.0, 1.0)),
             (qs[1], QueryPlan(qs[1].qid, [IndexSpec((0, 1), kind)],
                               [40], 1.0, 1.0))]
    _assert_engines_equal(es, et, pairs)
    if kind == "flat":
        # the merged launch absorbed the delta dispatches
        assert es.counters.delta == 0
        assert et.counters.delta > 0


def test_engine_fallback_group_merged_scan(db):
    """The no-spec (planless) group also rides the one-launch merge."""
    t = _churned(db, seed=22)
    es, et = _pair_engines(db, t, with_store=False)
    qs = make_queries(db, [(0,), (1,), (0, 1)], k=10, seed=14)
    pairs = [(q, QueryPlan(q.qid, [], [], 1.0, 1.0)) for q in qs]
    _assert_engines_equal(es, et, pairs)
    assert es.counters.delta == 0 and et.counters.delta > 0


def test_engine_env_flag_selects_two_pass(db, monkeypatch):
    monkeypatch.setenv("REPRO_TWOPASS_SCAN", "1")
    assert BatchEngine(db).streaming is False
    monkeypatch.delenv("REPRO_TWOPASS_SCAN")
    assert BatchEngine(db).streaming is True


@pytest.mark.slow
def test_engine_streaming_matches_workload_metrics(db):
    """execute_batch metrics (cost / ndists / recall inputs) are identical
    across scan implementations — the merged launch changes dispatch
    count, not accounting."""
    t = _churned(db, seed=23)
    es, et = _pair_engines(db, t)
    qs = make_queries(db, [(0,), (0, 1)], k=10, seed=15)
    wl = Workload(queries=qs, probs=np.ones(len(qs)))
    pairs = [(q, QueryPlan(q.qid, [IndexSpec(q.vid, "flat")], [30], 1.0, 1.0))
             for q in wl.queries]
    ms = es.execute_batch(pairs)
    mt = et.execute_batch(pairs)
    for a, b in zip(ms, mt):
        assert a.cost == b.cost and a.num_dist == b.num_dist
        np.testing.assert_array_equal(a.ids, b.ids)


# ---- engine: the fold's rounds on the observer -----------------------------


def _spy_scans(monkeypatch):
    """Record (rounds, row tiles × query blocks) of every streaming scan
    the engine launches."""
    seen = []

    def spy(q, db, *a, **kw):
        out = streaming_fused_scan(q, db, *a, **kw)
        delta = kw.get("delta")
        tiles = row_tiles(db.shape[0],
                          *(() if delta is None else (delta.shape[0],)))
        seen.append((int(np.asarray(out[2]).sum()), out[2].size * tiles,
                     delta is not None))
        return out

    monkeypatch.setattr(engine_mod, "streaming_fused_scan", spy)
    return seen


def _flat_pairs(db, seed):
    qs = make_queries(db, [(0,), (1,), (0, 1)], k=10, seed=seed)
    return [(q, QueryPlan(q.qid, [], [], 1.0, 1.0)) for q in qs]


@pytest.mark.parametrize("merged", [False, True], ids=["base", "base_delta"])
def test_engine_counts_fold_rounds(db, monkeypatch, merged):
    """An observed engine adds each scan's rounds to ``scan_fold_rounds``
    and its row tiles × query blocks to ``scan_row_tiles``."""
    seen = _spy_scans(monkeypatch)
    obs = Observer()
    eng = BatchEngine(db, streaming=True, observer=obs)
    if merged:
        eng.attach_mutations(MutationView(_churned(db, seed=24)))
    eng.search_batch(_flat_pairs(db, seed=16))
    assert seen and any(d for _, _, d in seen) == merged
    snap = obs.metrics.snapshot()
    assert snap.get("scan_fold_rounds")["value"] == sum(r for r, _, _ in seen)
    assert snap.get("scan_row_tiles")["value"] == sum(t for _, t, _ in seen)
    assert all(0 < r <= t * 10 for r, t, _ in seen)   # k = 10 < bn


def test_engine_fold_rounds_silent_unobserved(db, monkeypatch):
    """Under NULL_OBSERVER the rounds are neither read nor counted."""
    seen = _spy_scans(monkeypatch)
    counted, fetched = [], []
    monkeypatch.setattr(type(NULL_OBSERVER), "counter",
                        lambda self, name, *a, **kw: counted.append(name))
    fetch = BatchEngine._fetch
    monkeypatch.setattr(BatchEngine, "_fetch", lambda self, *arrays: (
        fetched.append(len(arrays)), fetch(self, *arrays))[1])
    eng = BatchEngine(db, streaming=True)
    assert eng.obs is NULL_OBSERVER
    eng.search_batch(_flat_pairs(db, seed=17))
    assert seen and fetched and set(fetched) == {2}
    assert not {"scan_fold_rounds", "scan_row_tiles"} & set(counted)

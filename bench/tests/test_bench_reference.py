"""The plain reference against the program's engine at a small size:
the same ids on the exact path; and the comparison that decides
``correct``."""
import numpy as np
import pytest

from bench import reference, serve, table
from repro.core.types import Query, QueryPlan
from repro.data.vectors import MultiVectorDatabase
from repro.serve.engine import BatchEngine

CFG = {"rows": 3000,
       "columns": [["glove100", 100], ["sift1m", 128], ["yandex_t2i", 200]],
       "vids": [[0], [0, 1], [1, 2], [0, 1, 2]],
       "generator": {"spread": 0.8, "correlation": 0.7}}
K = 100
SEED = 2 ** 32 + 77


@pytest.fixture(scope="module")
def data():
    cols = table.generate(CFG, SEED)
    host = [np.asarray(c) for c in cols]
    db = MultiVectorDatabase(host, [n for n, _ in CFG["columns"]])
    rows = np.random.default_rng(3).integers(0, CFG["rows"], 48)
    qv = [np.asarray(q) for q in table.make_queries(
        table.query_key(SEED), cols, rows.astype(np.int32), 0.5)]
    vids = [tuple(v) for v in CFG["vids"]]
    queries = [Query(qid=i, vid=vids[i % 4],
                     vectors={c: qv[c][i] for c in vids[i % 4]}, k=K)
               for i in range(len(rows))]
    return cols, db, qv, queries


def _by_vid(queries, qv, served):
    for vid in sorted({q.vid for q in queries}):
        idx = np.asarray([i for i, q in enumerate(queries) if q.vid == vid])
        yield vid, idx, [qv[c][idx] for c in vid], [served[i] for i in idx]


def test_reference_matches_the_exact_engine(data):
    cols, db, qv, queries = data
    eng = BatchEngine(db, store=None)
    served = eng.search_batch(
        [(q, QueryPlan(q.qid, [], [], 0.0, 1.0)) for q in queries])
    for vid, idx, qvecs, got in _by_vid(queries, qv, served):
        ref = reference.best_rows(cols, vid, qvecs, select=K)
        np.testing.assert_array_equal(np.stack(got), ref)
        r = reference.judge(cols, vid, qvecs, got, K, CFG["rows"])
        assert r["ok"].all()
        assert r["gap"].max() == 0.0


def test_a_served_list_is_judged_by_its_order_and_its_set(data):
    cols, _, qv, _ = data
    vid = (0, 1)
    qvecs = [qv[c][:2] for c in vid]
    best = reference.best_rows(cols, vid, qvecs, select=K)
    swapped = best[0].copy()
    swapped[[0, 1]] = swapped[[1, 0]]
    missing = np.concatenate([best[1][:-1], [best[0][-1]]])
    if missing[-1] in best[1]:
        missing[-1] = np.setdiff1d(np.arange(CFG["rows"]), best[1])[0]
    r = reference.judge(cols, vid, qvecs, [swapped, missing], K, CFG["rows"])
    assert r["ok"].all()
    assert r["gap"][0] > 0 and r["gap"][1] > 0
    bad = reference.judge(cols, vid, qvecs, [best[0][:-1], None], K,
                          CFG["rows"])
    assert not bad["ok"].any()


@pytest.mark.parametrize("ids, ok", [
    (np.arange(100), True), (np.arange(99), False),
    (np.r_[np.arange(99), 0], False), (np.r_[np.arange(99), 3000], False),
    (np.r_[np.arange(99), -1], False), (None, False)])
def test_well_formed(ids, ok):
    assert reference.well_formed(ids, 100, 3000) is ok


@pytest.mark.parametrize("value, limit, ok", [
    (0, 0, True), (1, 0, False), (4e-7, 5e-7, True), (5e-7, 5e-7, True),
    (6e-7, 5e-7, False), (None, 5e-7, False)])
def test_a_compared_number_passes_at_or_below_its_limit(value, limit, ok):
    assert serve.within({"value": value, "limit": limit}) is ok

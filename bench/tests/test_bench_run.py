"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, or
with the control in the program's place, ``correct`` comes out false."""
import time

import numpy as np
import pytest

from bench import serve, spec
from repro.serve.engine import BatchEngine

CELL = "naive-1m-exact.steady"


@pytest.fixture(scope="module")
def cell():
    c = spec.cell(spec.load(), CELL)
    c.config = dict(c.config, rows=2048,
                    runtime={"max_batch": 2, "max_delay_ms": 5.0})
    c.traffic = dict(c.traffic, rate_qps=4.0)
    return c


def _run(cell, seed=2 ** 33 + 5):
    return serve.run_cell(cell, seed, seconds=2.0, trace=False,
                          t_start=time.perf_counter())


def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == 8
    assert out["checks"]["bad_answers"] == {"value": 0, "limit": 0}
    assert out["checks"]["score_gap"]["value"] <= out["checks"]["score_gap"]["limit"]
    assert list(out)[-1] == "checks"
    assert {m["name"] for m in cell.end_to_end} == set(out["metrics"])
    assert out["device"]["count"] == 1


def test_an_altered_answer_fails(cell, monkeypatch):
    orig = BatchEngine.search_batch

    def altered(self, pairs, staged=None):
        out = orig(self, pairs, staged=staged)
        ids = out[0]
        other = np.setdiff1d(np.arange(self.db.n_rows), ids)[0]
        out[0] = np.concatenate([[other], ids[1:]])  # best row replaced
        return out

    monkeypatch.setattr(BatchEngine, "search_batch", altered)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["score_gap"]["value"] > out["checks"]["score_gap"]["limit"]
    assert out["failed"] > 0


def test_half_the_batch_left_out_fails(cell, monkeypatch):
    orig = BatchEngine.search_batch

    def half(self, pairs, staged=None):
        return orig(self, pairs[: len(pairs) // 2])

    monkeypatch.setattr(BatchEngine, "search_batch", half)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["bad_answers"]["value"] > 0


def test_the_control_fails_where_the_program_passes(cell):
    """The reference at bf16x3 in the program's place, over as many
    queries as a chip window checks: it misorders rows that HIGHEST
    scoring keeps apart, past the configuration's limit."""
    cfg = cell.config
    seed = 11
    n = 512
    vids = [tuple(v) for v in cfg["vids"]]
    vid_index = np.arange(n) % len(vids)
    from bench import table
    cols = table.generate(cfg, seed)
    rows = np.random.default_rng(seed).integers(0, cfg["rows"], n)
    qv = [np.asarray(q) for q in table.make_queries(
        table.query_key(seed), cols, rows.astype(np.int32), 0.5)]
    ctl, failed = serve.check(cfg, seed, vids, vid_index, qv, None)
    assert ctl["bad_answers"]["value"] == 0
    assert ctl["score_gap"]["value"] > cfg["checks"]["score_gap"]
    assert failed > 0

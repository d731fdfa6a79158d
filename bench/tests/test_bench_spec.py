"""A configuration, a traffic mix or a metric dropped into its directory
is found by its name in BENCHMARK.json, with no edit to the harness."""
import json
import shutil

import pytest

from bench import spec
from bench.spec import REPO


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark with one more configuration, mix, metric
    and cell, each only a file and an entry."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps({"name": "tiny", "rows": 64}))
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps({"arrivals": "poisson", "rate_qps": 1}))
    (tmp_path / "bench" / "metrics" / "rows_x2.json.py").write_text(
        "def read(run):\n    return 2 * run.cell.config['rows']\n")
    (tmp_path / "bench" / "metrics" / "tiny.rows.py").write_text(
        "def read(run):\n    return run.cell.config['rows']\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "https://example.org",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                           "traffic": "trickle", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "rows_x2.json", "unit": "rows",
                            "better": "lower", "bound": 0.01,
                            "source": "host_clock",
                            "workloads": ["tiny.trickle"]})
    b["per_layer"].append({"name": "tiny.rows", "unit": "rows",
                           "better": "lower", "source": "program_counter",
                           "layer": "test", "moves": "rows_x2.json"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path


def test_new_pieces_are_found_by_name(root):
    c = spec.cell(spec.load(root), "tiny.trickle", root)
    assert c.config == {"name": "tiny", "rows": 64}
    assert c.traffic["rate_qps"] == 1
    assert c.chips == 1
    e2e = [m["name"] for m in c.end_to_end]
    assert "rows_x2.json" in e2e
    # a per-layer metric without "workloads" goes wherever its end-to-end
    # metric is reported
    assert [m["name"] for m in c.per_layer if m["name"] == "tiny.rows"]
    run = type("Run", (), {"cell": c})()
    assert spec.reader("rows_x2.json", root)(run) == 128
    assert spec.reader("tiny.rows", root)(run) == 64


def test_cell_metrics_follow_their_workloads_key(root):
    b = spec.load(root)
    for w in b["workloads"]:
        if w["name"] == "tiny.trickle":
            continue
        c = spec.cell(b, w["name"], root)
        assert "rows_x2.json" not in [m["name"] for m in c.end_to_end]
        assert "tiny.rows" not in [m["name"] for m in c.per_layer]


def test_every_committed_cell_and_metric_resolves():
    b = spec.load(REPO)
    for w in b["workloads"]:
        c = spec.cell(b, w["name"], REPO)
        assert "setup_s" in [m["name"] for m in c.end_to_end]
        assert c.per_layer
        for m in c.end_to_end + c.per_layer:
            assert callable(spec.reader(m["name"], REPO))


def test_unknown_names_are_errors(root):
    with pytest.raises(KeyError):
        spec.cell(spec.load(root), "no.such.cell", root)
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric", root)
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary", root)
    assert spec.peaks("TPU v5 lite", root)["hbm_bytes_per_s"] == 819e9

"""The streaming scan's bytes, operations and roofline share, from
shapes and a synthesized run."""
from types import SimpleNamespace

import pytest

from bench import roofline
from bench import trace as btrace
from bench.spec import REPO, Cell
from bench.trace import Event

CONFIG = {"rows": 1_000_000,
          "columns": [["glove100", 100], ["sift1m", 128], ["yandex_t2i", 200]]}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.mark.parametrize("vid, width", [((0,), 128), ((0, 1), 256),
                                        ((1, 2), 384), ((0, 1, 2), 512)])
def test_scan_bytes_are_the_padded_resident_column(vid, width):
    # 1,000,000 rows pad to 7,813 blocks of 128; widths pad to 128 lanes
    assert roofline.scan_bytes(CONFIG, vid) == 1_000_064 * width * 4


def test_bytes_bound_the_scan_at_small_batches():
    sig = (("flat", (0, 1, 2), 128),)
    least = roofline.least_s(CONFIG, [(sig, 32)], PEAKS)
    assert least == pytest.approx(1_000_064 * 512 * 4 / 819e9)
    assert roofline.scan_flops(CONFIG, (0, 1, 2), 32) == 2 * 32 * 1_000_000 * 428


def test_only_flat_scans_count():
    groups = [((("flat", (0,), 128),), 4),
              ((("ivf", (0,), 256), ("flat", (1, 2), 128)), 2)]
    assert roofline.n_scans(groups) == 2
    assert roofline.least_s(CONFIG, groups, PEAKS) == pytest.approx(
        (1_000_064 * 128 * 4 + 1_000_064 * 384 * 4) / 819e9)


def _run(groups, scan_ns):
    host = ("/host:CPU", "python")
    tpu = "/device:TPU:0"
    evs = [Event(*host, btrace.WINDOW, 0.0, 1e9)]
    t = 0.0
    for ns in scan_ns:
        evs.append(Event(tpu, "XLA Modules", "jit_streaming_fused_scan", t, t + ns))
        evs.append(Event(tpu, "XLA Ops", "custom-call", t, t + ns))
        t += ns + 1000.0
    cell = Cell(name="c", chips=1, config=CONFIG, traffic={}, root=REPO)
    return SimpleNamespace(trace=btrace.reduce(evs), groups=groups,
                           device_kind="TPU v5 lite", cell=cell)


def test_scan_share_pairs_trace_and_spans():
    groups = [((("flat", (0,), 128),), 8), ((("flat", (0, 1, 2), 128),), 8)]
    run = _run(groups, [250e6, 250e6])
    least = (1_000_064 * 128 * 4 + 1_000_064 * 512 * 4) / 819e9
    assert roofline.scan_share(run) == pytest.approx(100 * least / 0.5)


def test_scan_share_reads_nothing_when_unpaired():
    groups = [((("flat", (0,), 128),), 8)]
    assert roofline.scan_share(_run(groups, [1e6, 1e6])) is None
    assert roofline.scan_share(_run([], [1e6])) is None

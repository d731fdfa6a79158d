"""The generator and the traffic mix are fixed by the seed."""
import numpy as np
import pytest

from bench import table, traffic

CONFIG = {"rows": 512, "columns": [["a", 16], ["b", 24]],
          "vids": [[0], [0, 1]], "vid_probs": [0.7, 0.3],
          "generator": {"spread": 0.8, "correlation": 0.7}}
MIX = {"arrivals": "poisson", "rate_qps": 50, "query_noise": 0.5}
BIG = 2 ** 31 + 12345  # seeds may pass 32 signed bits


def _host(cols):
    return [np.asarray(c) for c in cols]


def test_table_is_fixed_by_the_seed():
    a, b = _host(table.generate(CONFIG, BIG)), _host(table.generate(CONFIG, BIG))
    assert [x.shape for x in a] == [(512, 16), (512, 24)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)
    c = _host(table.generate(CONFIG, BIG + 1))
    assert not np.array_equal(a[0], c[0])


def test_all_64_bits_of_the_seed_count():
    s = 3
    a = np.asarray(table.generate(CONFIG, s)[0])
    b = np.asarray(table.generate(CONFIG, s + 2 ** 40)[0])
    assert not np.array_equal(a, b)


def test_queries_are_fixed_by_the_seed():
    cols = table.generate(CONFIG, BIG)
    rows = np.asarray([1, 5, 5, 9], np.int32)
    q1 = _host(table.make_queries(table.query_key(BIG), cols, rows, 0.5))
    q2 = _host(table.make_queries(table.query_key(BIG), cols, rows, 0.5))
    for x, y in zip(q1, q2):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(q1[0][1], q1[0][2])  # own noise per query


def test_window_is_fixed_by_the_seed():
    a = traffic.window(MIX, CONFIG, BIG, seconds=4.0)
    b = traffic.window(MIX, CONFIG, BIG, seconds=4.0)
    for x, y in zip((a.arrivals, a.vid_index, a.rows),
                    (b.arrivals, b.vid_index, b.rows)):
        np.testing.assert_array_equal(x, y)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.window(MIX, CONFIG, 1, seconds=4.0)
    b = traffic.window(MIX, CONFIG, BIG, seconds=4.0)
    assert len(a.arrivals) == len(b.arrivals) == 200
    # the n gaps, the last one up to the window's close, are one set
    np.testing.assert_allclose(np.sort(np.diff(a.arrivals, append=4.0)),
                               np.sort(np.diff(b.arrivals, append=4.0)),
                               rtol=1e-9)
    assert np.bincount(a.vid_index).tolist() == [140, 60]
    assert np.bincount(b.vid_index).tolist() == [140, 60]
    assert not np.array_equal(a.vid_index, b.vid_index)
    assert 0.0 == a.arrivals[0] and a.arrivals[-1] < 4.0
    assert np.all(np.diff(a.arrivals) > 0)


def test_rate_override_and_counts():
    s = traffic.window(MIX, CONFIG, 7, seconds=2.0, rate=10)
    assert len(s.arrivals) == 20
    assert traffic.counts([0.34, 0.17, 0.27, 0.22], 7).sum() == 7


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        traffic.window(dict(MIX, arrivals="closed"), CONFIG, 1, seconds=1.0)

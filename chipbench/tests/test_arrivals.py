"""Open-loop due times from a seed, and the closed loop's client count."""
import numpy as np

from chipbench.harness import open_loop_offsets


def test_open_loop_is_a_function_of_the_seed():
    a = open_loop_offsets(300.0, 10.0, 2**31 + 5)
    b = open_loop_offsets(300.0, 10.0, 2**31 + 5)
    np.testing.assert_array_equal(a, b)
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 10.0


def test_open_loop_seeds_share_count_and_gaps():
    """Every seed sends as many queries, with the same gaps in another
    order, so seeds differ only in their bursts."""
    a = open_loop_offsets(300.0, 10.0, 1)
    b = open_loop_offsets(300.0, 10.0, 2)
    assert len(a) == len(b) == 3000
    assert not np.array_equal(a, b)
    ga, gb = np.sort(np.diff(a)), np.sort(np.diff(b))
    assert np.allclose(np.sort(np.concatenate([ga, [0]]))[:100],
                       np.sort(np.concatenate([gb, [0]]))[:100])
    # exponential gaps: mean 1/rate, and a coefficient of variation near 1
    g = np.diff(a)
    assert abs(np.mean(g) * 300 - 1) < 0.01
    assert 0.9 < np.std(g) / np.mean(g) < 1.1


def test_closed_loop_keeps_its_clients_busy(tiny_cells):
    cell = tiny_cells("tiny.batch")
    clients = cell.traffic["clients"]
    rec = cell.window(3, 1.0)
    assert not rec.failed.any()
    # outstanding queries at every send: never more than the clients
    events = sorted([(t, 1) for t in rec.sent] + [(t, -1) for t in rec.done])
    depth = np.cumsum([d for _, d in events])
    assert depth.max() == clients
    # each caller's next query is due when its last answer came back
    assert np.sum(rec.due == rec.t0) == clients
    later = np.sort(rec.due[clients:])
    assert np.all(np.isin(later, rec.done))

"""The benchmark's arithmetic: tails over every request, failures as
missing, rates and recall."""
import numpy as np

from bench import stats


def test_percentile_is_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert stats.percentile(v, 99) == 99.0
    assert stats.percentile(v, 100) == 100.0
    assert stats.percentile([5.0], 99) == 5.0


def test_failures_count_as_missing_the_tail():
    due = np.zeros(200)
    done = np.full(200, 0.010)
    failed = np.zeros(200, bool)
    failed[:3] = True              # 1.5% failed: p99 lies among them
    lat = stats.latencies_ms(done, due, failed)
    assert stats.percentile(lat, 99) == np.inf
    failed[:] = False
    failed[0] = True               # 0.5% failed: p99 is a real latency
    lat = stats.latencies_ms(done, due, failed)
    assert abs(stats.percentile(lat, 99) - 10.0) < 1e-9


def test_tail_is_over_all_requests_not_medians_of_chunks():
    # nine calm chunks and one with a stall: the tail of all requests
    # sees the stall, the median of per-chunk tails would not
    lat = np.concatenate([np.full(900, 5.0), np.full(100, 80.0)])
    chunk_p99 = [stats.percentile(c, 99) for c in np.split(lat, 10)]
    assert np.median(chunk_p99) == 5.0
    assert stats.percentile(lat, 99) == 80.0


def test_latency_is_from_the_scheduled_send():
    lat = stats.latencies_ms([1.5, 2.0], [1.0, 1.0], [False, False])
    np.testing.assert_allclose(lat, [500.0, 1000.0])


def test_rate():
    assert stats.rate(300, 10.0) == 30.0


def test_recall_counts_each_true_neighbour_once():
    truth = np.array([[1, 2, 3], [4, 5, 6]])
    found = np.array([[3, 2, 9], [-1, -1, 4]])
    np.testing.assert_allclose(stats.recall_at_k(found, truth, 3),
                               [2 / 3, 1 / 3])

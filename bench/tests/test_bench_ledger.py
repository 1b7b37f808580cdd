"""The per-request ledger: each future's stamps and answer copied out once
it completes, in send order; a request that failed or never came back is
marked failed, and the ledger grows past its first capacity."""
import numpy as np

from bench import drivers


class FakeFuture:
    def __init__(self, i, done=True, failed=False):
        self._done, self.failed = done, failed
        self.cancelled = self.partial = False
        self.submitted_at, self.dispatched_at = float(i), i + 0.1
        self.device_done_at, self.completed_at = i + 0.2, i + 0.3
        self.flush_index = i // 4
        self.ids = np.arange(i, i + 5)
        self.dists = np.linspace(0, 1, 5)

    def done(self):
        return self._done

    def result(self, timeout=None):
        if not self._done:
            raise TimeoutError
        return self.ids, self.dists


def test_ledger_copies_out_what_completed_in_send_order():
    led = drivers.Ledger(k=3, capacity=2)
    futs = [FakeFuture(0), FakeFuture(1), FakeFuture(2, done=False),
            FakeFuture(3), FakeFuture(4, failed=True)]
    for f in futs:
        led.add(f)
    led.harvest()                       # stops at the first not done
    assert len(led.pending) == 3
    led.drain(deadline=0.0)             # a future that never came back
    assert not led.pending and led.n == 5
    run = drivers.Run()
    led.fill(run)
    ans = led.answers()
    np.testing.assert_array_equal(run.failed, [0, 0, 1, 0, 1])
    np.testing.assert_array_equal(run.submitted_at, [0, 1, 2, 3, 4])
    np.testing.assert_array_equal(run.flush_index, [0, 0, 0, 0, 1])
    np.testing.assert_array_equal(ans["ids"][3], [3, 4, 5])
    assert (ans["ids"][2] == -1).all() and np.isinf(ans["dists"][4]).all()
    np.testing.assert_array_equal(ans["ok"], ~run.failed)

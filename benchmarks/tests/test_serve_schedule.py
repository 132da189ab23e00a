"""The open loop: its schedule comes from the seed alone, every seed
offers the same work, and latency is counted from the due time."""

import concurrent.futures
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "drivers"))

import serve  # noqa: E402  (benchmarks/drivers/serve.py)

TRAFFIC = {"rate_per_s": 200, "pool_rows": 16,
           "rows_per_request": {"1": 0.7, "4": 0.2, "16": 0.1}}


def test_the_schedule_is_reproducible_from_its_seed():
    a = serve.schedule(TRAFFIC, 2 ** 31 - 5, 2.0)
    b = serve.schedule(TRAFFIC, 2 ** 31 - 5, 2.0)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    due, rows, offsets = a
    assert len(due) == 400 and due[0] == 0.0 and due[-1] < 2.0
    assert np.all(np.diff(due) >= 0)
    assert set(rows) <= {1, 4, 16} and offsets.max() < 16


def test_every_seed_offers_the_same_work_in_another_order():
    due1, rows1, _ = serve.schedule(TRAFFIC, 1, 2.0)
    due2, rows2, _ = serve.schedule(TRAFFIC, 2, 2.0)
    assert not np.array_equal(rows1, rows2)
    assert np.array_equal(np.sort(rows1), np.sort(rows2))
    gaps1 = np.sort(np.diff(np.append(due1, 2.0)))
    gaps2 = np.sort(np.diff(np.append(due2, 2.0)))
    assert np.allclose(gaps1, gaps2)


class StallingSession:
    """Answers at once, but its door is shut for the first 0.2 s: the
    first submit blocks the generator, as a stalled server would."""

    def __init__(self):
        self.first = True

    def submit(self, rows):
        if self.first:
            self.first = False
            time.sleep(0.2)
        fut = concurrent.futures.Future()
        fut.set_result(np.full((rows.shape[0], 3), 1 / 3, np.float32))
        return fut


class FakeServed:
    def __init__(self):
        self.session = StallingSession()
        self.pool = np.zeros((16, 2), np.float32)

    rows = serve.Served.rows


def test_latency_is_counted_from_the_due_time():
    # 20 requests due every 10 ms; the stall holds the generator 200 ms
    due = np.arange(20) * 0.01
    rows = np.ones(20, int)
    t0, sent_at, done_at, results = serve.open_loop(
        FakeServed(), due, rows, np.zeros(20, int))
    assert all(isinstance(r, np.ndarray) for r in results)
    from_due = done_at - (t0 + due)
    from_send = done_at - sent_at
    late = sent_at - (t0 + due)
    # the request due at 10 ms was sent about 190 ms late: its latency
    # from the due time says so, its latency from the send hides it
    assert from_due[1] > 0.15 and from_send[1] < 0.05
    assert late[1] > 0.15 and late[-1] < 0.05

"""The traffic generator: seeded, reproducible, and true to its mix."""

import numpy as np
import pytest

from bench import traffic
from bench.drivers import serve

MIX = {"arrivals": [{"rate_rps": 800.0, "seconds": 1}], "keep_queued": 0,
       "sizes": {"law": "power", "exponent": 1.5, "min": 1, "max": 32}}


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 2**31 + 17, 10)
    b = traffic.schedule(MIX, 2**31 + 17, 10)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_another_seed_differs_in_order_not_in_work():
    due_a, sizes_a = traffic.schedule(MIX, 1, 10)
    due_b, sizes_b = traffic.schedule(MIX, 2, 10)
    assert not np.array_equal(sizes_a, sizes_b)
    assert not np.array_equal(due_a, due_b)
    # the same multiset of sizes, so every seed holds the same work
    assert len(sizes_a) == len(sizes_b)
    np.testing.assert_array_equal(np.sort(sizes_a), np.sort(sizes_b))


def test_schedule_fills_the_window_at_the_rate():
    due, sizes = traffic.schedule(MIX, 5, 10)
    assert np.all(np.diff(due) >= 0) and due[0] == 0 and due[-1] < 10
    assert len(due) == pytest.approx(8000, rel=0.01)


@pytest.mark.parametrize("seed", [0, 3, 2**33 + 1])
def test_size_law_mean_and_single_image_share(seed):
    ks, p = traffic.size_pmf(MIX["sizes"])
    mean, p1 = float((ks * p).sum()), float(p[0])
    assert mean == pytest.approx(4.396, abs=1e-3)
    assert traffic.mean_size(MIX) == pytest.approx(mean)
    assert p1 == pytest.approx(0.4422, abs=1e-4)
    _, sizes = traffic.schedule(MIX, seed, 10)
    n = len(sizes)
    # quantile draws: within the sampling error of n iid draws, and tighter
    sd = float(np.sqrt(((ks - mean) ** 2 * p).sum()))
    assert sizes.mean() == pytest.approx(mean, abs=3 * sd / np.sqrt(n))
    assert (sizes == 1).mean() == pytest.approx(
        p1, abs=3 * np.sqrt(p1 * (1 - p1) / n))
    assert sizes.min() >= 1 and sizes.max() <= 32


def test_committed_bursty_mix_cycles_its_phases():
    """A mix is data alone: the committed on/off file runs at its phases'
    rates, in its phases' windows, at the poisson mix's mean."""
    mix = traffic.load("bursty")
    due, _ = traffic.schedule(mix, 11, 10)
    on, off = mix["arrivals"]
    period = on["seconds"] + off["seconds"]
    in_on = (due % period) < on["seconds"]
    assert in_on.sum() == pytest.approx(on["rate_rps"] * on["seconds"] * 10,
                                        rel=0.05)
    assert (~in_on).sum() == pytest.approx(
        off["rate_rps"] * off["seconds"] * 10, rel=0.05)
    mean = len(due) / 10
    assert mean == pytest.approx(
        traffic.load("poisson")["arrivals"][0]["rate_rps"], rel=0.01)


def test_idle_phase_sends_nothing():
    mix = dict(MIX, arrivals=[{"rate_rps": 500, "seconds": 0.5},
                              {"rate_rps": 0, "seconds": 0.5}])
    due, _ = traffic.schedule(mix, 4, 4)
    assert len(due) == pytest.approx(1000, abs=2)
    assert np.all(due % 1.0 <= 0.5)


def test_no_arrivals_means_no_timed_requests():
    due, sizes = traffic.schedule(traffic.load("backlog"), 4, 10)
    assert len(due) == 0 and len(sizes) == 0


@pytest.mark.parametrize("mix,max_rows,rows", [
    ("backlog", 32, [32]),
    ("backlog", 128, [128]),
    ("poisson", 32, list(range(1, 33))),
    ("bursty", 32, list(range(1, 33))),
])
def test_warm_up_rows_cover_every_dispatch(mix, max_rows, rows):
    assert traffic.dispatch_rows(traffic.load(mix), max_rows) == rows


def test_partial_dispatch_possible_warms_every_extent():
    # 3 x 32 rows do not fill 64-row dispatches evenly
    mix = dict(traffic.load("backlog"), keep_queued=3)
    assert traffic.dispatch_rows(mix, 64) == list(range(1, 65))


def test_pool_cursor_restarts_at_the_head():
    pool = np.arange(10)[:, None]
    cur = traffic.PoolCursor(pool)
    assert cur.take(4)[0] == 0
    assert cur.take(4)[0] == 4
    lo, x = cur.take(4)
    assert lo == 0 and len(x) == 4


class _FakeEngine:
    """Stands in for the serving engine: records the queue it is stepped
    with and serves up to three queued requests a step."""

    def __init__(self):
        from repro.serve.stats import ServeStats
        self.stats = ServeStats()
        self.queue, self.seen, self.next = [], [], 0

    def submit(self, x):
        self.next += 1
        self.queue.append(self.next)
        return self.next

    def step(self):
        self.seen.append(len(self.queue))
        done, self.queue = self.queue[:3], self.queue[3:]
        return done

    def take(self, rid):
        return np.zeros((32, 10), np.float32)


def test_backlog_never_lets_the_queue_fall_under_its_depth():
    mix = traffic.load("backlog")
    eng = _FakeEngine()
    win = serve.Window(eng, False)
    pool = np.zeros((64, 32, 32, 3), np.float32)
    due, sizes = traffic.schedule(mix, 1, 0.05)
    win.drive(traffic.PoolCursor(pool), due, sizes, mix["keep_queued"],
              traffic.refill_sizes(mix, 1), 0.05)
    assert len(eng.seen) > 10
    assert min(eng.seen) >= mix["keep_queued"]
    assert {k for _, _, k, _ in win.requests} == {32}

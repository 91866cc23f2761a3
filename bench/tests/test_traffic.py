"""Inputs from the seed: reproducible, the same work for every seed, and
open-loop schedules ordered by due time."""
import numpy as np
import pytest

from bench import gen, harness

# the serve mix, at a trial rate: its cell's rate comes from a knee sweep
SERVE = dict(harness.load_json(harness.BENCH / "traffic" / "serve.json"),
             rate_per_s=1000)


def test_rows_are_reproducible_and_hit_the_nonzero_share():
    cfg = {"dim": 254, "n_classes": 2, "nnz_share": 0.33, "spike_prob": 0.05}
    x1, y1 = gen.rows_for(cfg, 2 ** 40 + 5, 4000)
    x2, y2 = gen.rows_for(cfg, 2 ** 40 + 5, 4000)
    x3, _ = gen.rows_for(cfg, 5, 4000)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    x = np.asarray(x1)
    assert x.min() >= 0 and x.max() < 1
    assert np.mean(x > 0) == pytest.approx(0.33, abs=0.02)


def test_large_seeds_keep_all_their_bits():
    a = np.asarray(gen.root_key(7))
    b = np.asarray(gen.root_key(2 ** 33 + 7))
    assert not np.array_equal(a, b)
    with pytest.raises(ValueError):
        gen.root_key(-1)


def test_schedule_is_seeded_open_loop_and_the_same_work_for_every_seed():
    due1, s1 = gen.request_schedule(SERVE, 11, 3.0)
    due2, s2 = gen.request_schedule(SERVE, 11, 3.0)
    due3, s3 = gen.request_schedule(SERVE, 12, 3.0)
    np.testing.assert_array_equal(due1, due2)
    np.testing.assert_array_equal(s1, s2)
    assert not np.array_equal(due1, due3)
    # one sequence of gaps, rotated: each seed's gaps are the other's,
    # but for the one that falls out where the rotation wraps
    a, g3 = np.sort(np.diff(due1)), np.diff(due3)
    j = np.clip(np.searchsorted(a, g3), 1, len(a) - 1)
    near = np.minimum(np.abs(a[j] - g3), np.abs(a[j - 1] - g3))
    assert np.sum(near > 1e-12) <= 1
    # due times are fixed in advance, in order, spanning the window
    assert len(due1) == int(np.ceil(SERVE["rate_per_s"] * 3.0))
    assert np.all(np.diff(due1) >= 0) and due1[0] == 0 and due1[-1] < 3.0
    assert np.all(s1 == SERVE["rows_per_request"])


def test_open_loop_sender_keeps_due_times_when_the_server_stalls():
    from bench.drivers.serve import send
    import time

    class Slow:
        """Answers each request 20 ms after it arrives, one at a time."""

        def __init__(self):
            self.free = time.perf_counter()

        def submit(self, x):
            from repro.serving.gateway import ServeFuture
            fut = ServeFuture()
            self.free = max(self.free, time.perf_counter()) + 0.02
            threading_timer(self.free - time.perf_counter(),
                            lambda: fut._set_result(x))
            return fut

    import threading

    def threading_timer(delay, fn):
        t = threading.Timer(max(delay, 0), fn)
        t.start()

    due = np.arange(10) * 0.005          # 200/s against 50/s served
    views = [np.zeros((1, 2), np.float32)] * 10
    t0 = time.perf_counter() + 0.01
    lat, lag, got = send(Slow(), views, due, t0, 5.0)
    # sends kept their schedule; the queue made later requests wait
    assert np.all(lag < 0.004)
    assert lat[-1] > lat[0] + 0.1
    assert set(got) == set(range(10))

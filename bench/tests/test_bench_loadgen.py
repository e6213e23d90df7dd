"""The open-loop generator times each request from its due time: on a
fake clock, a stall in one submit delays every later request, and that
delay shows in their measured latency and in how late they were sent."""
from concurrent.futures import Future

import numpy as np
from tinycell import ROOT  # noqa: F401

from bench import loadgen


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def _server(clock, stall_at=None, stall_s=0.0, service_s=0.001):
    def submit(i):
        if i == stall_at:
            clock.t += stall_s                  # the host stalls in submit
        clock.t += service_s
        f = Future()
        f.set_result(i)
        return f
    return submit


def test_a_stall_delays_the_measured_latency_of_later_requests():
    due = np.arange(10) * 0.010                 # one request every 10 ms
    clock = FakeClock()
    recs = loadgen.run_open(_server(clock, stall_at=3, stall_s=0.050), due,
                            clock=clock, sleep=clock.sleep)
    lat = loadgen.latencies_ms(recs)
    late = loadgen.late_ms(recs)
    assert np.allclose(lat[:3], 1.0)            # service time only
    # requests 4..8 were due during the stall: sent late, and their
    # latency counts the wait from their due time
    assert late[4] > 35 and lat[4] > 35
    assert np.all(lat[4:8] > lat[:3].max())
    assert np.allclose(late[:4], 0.0, atol=1e-6)


def test_without_a_stall_nothing_is_late():
    due = np.arange(5) * 0.010
    clock = FakeClock()
    recs = loadgen.run_open(_server(clock), due, clock=clock,
                            sleep=clock.sleep)
    assert np.allclose(loadgen.late_ms(recs), 0.0, atol=1e-6)
    assert np.allclose(loadgen.latencies_ms(recs), 1.0)


def test_refused_request_is_recorded_as_error():
    def submit(i):
        if i == 1:
            raise RuntimeError("queue full")
        f = Future()
        f.set_result(i)
        return f
    clock = FakeClock()
    recs = loadgen.run_open(submit, np.array([0.0, 0.01, 0.02]), clock=clock,
                            sleep=clock.sleep)
    assert recs[1]["error"].startswith("RuntimeError")
    assert len(loadgen.latencies_ms(recs)) == 2


def test_due_times_keep_the_same_gaps_for_every_seed():
    a = loadgen.poisson_due_times(500.0, 2.0, gap_seed=1, order_seed=10)
    b = loadgen.poisson_due_times(500.0, 2.0, gap_seed=1, order_seed=11)
    assert not np.array_equal(a, b)
    assert abs(len(a) - len(b)) < 0.1 * len(a)
    assert abs(len(a) - 1000) < 150 and a[0] == 0.0
    assert np.all(np.diff(a) >= 0) and a[-1] < 2.0


def test_closed_loop_counts_the_call_that_crosses_the_mark():
    clock = FakeClock()

    def call(i):
        clock.t += 0.3
        return i
    out, elapsed = loadgen.run_closed(call, 1.0, clock=clock)
    assert out == [0, 1, 2, 3] and abs(elapsed - 1.2) < 1e-9

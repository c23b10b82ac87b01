"""The engine's wait counters: ``ack_latency_s`` split into eager ACKs and
synchronous waits, callers blocked on the in-flight budget, and the time
ready operations wait for a worker."""
import pytest

from repro.core import (CannyFS, EagerFlags, InMemoryBackend, LatencyBackend,
                        LatencyModel, SimClock)


def slow(ms=2.0, clock=None):
    return LatencyBackend(InMemoryBackend(),
                          LatencyModel(meta_ms=ms, data_ms=ms,
                                       jitter_sigma=0.0), clock=clock)


def test_eager_acks_and_sync_waits_sum_to_ack_latency():
    # directories are made synchronously, files eagerly
    fs = CannyFS(slow(1.0), flags=EagerFlags().replace(mkdir=False),
                 max_inflight=100, workers=4)
    for d in range(3):
        fs.mkdir(f"d{d}")
        for i in range(5):
            fs.write_file(f"d{d}/f{i}", b"x" * 10)
    fs.drain()
    st = fs.stats
    assert st.eager_acks > 0 and st.sync_ops >= 3
    assert st.sync_wait_s > 0 and st.eager_ack_s > 0
    # a synchronous mkdir waits for the backend's millisecond
    assert st.sync_wait_s / st.sync_ops >= 1e-3
    assert st.eager_ack_s + st.sync_wait_s == \
        pytest.approx(st.ack_latency_s, rel=1e-12)
    fs.close()


def test_callers_blocked_at_the_budget_are_counted():
    fs = CannyFS(slow(2.0), max_inflight=1, workers=2)
    for i in range(10):
        fs.create(f"f{i}")
    fs.drain()
    st = fs.stats
    assert st.max_queue_depth == 1
    assert st.budget_waits >= 1
    assert st.budget_wait_s > 0
    fs.close()


def test_no_budget_wait_below_the_budget():
    fs = CannyFS(slow(2.0), max_inflight=100, workers=2)
    for i in range(10):
        fs.create(f"f{i}")
    fs.drain()
    assert fs.stats.budget_waits == 0 and fs.stats.budget_wait_s == 0.0
    fs.close()


def test_ready_ops_wait_for_the_one_worker():
    fs = CannyFS(slow(2.0), max_inflight=100, workers=1, fusion=False)
    for i in range(10):              # independent paths: all ready at once
        fs.create(f"f{i}")
    fs.drain()
    st = fs.stats
    assert st.executed == 10
    # the k-th op waits for the k-1 before it, 2 ms each: ~90 ms in all
    assert st.queue_wait_s > 0.02
    assert st.queue_wait_s < 10 * st.exec_latency_s
    fs.close()


def _sim_run():
    clock = SimClock()
    fs = CannyFS(slow(3.0, clock), max_inflight=3, workers=2)
    for i in range(30):
        fs.create(f"f{i}")
    fs.drain()
    fs.close()
    st = fs.stats
    return {k: getattr(st, k) for k in ("submitted", "executed", "steals",
                                        "parks", "max_queue_depth",
                                        "budget_waits")}


def test_budget_waits_are_exact_under_the_sim_clock():
    first, second = _sim_run(), _sim_run()
    assert first == second
    assert first["submitted"] == first["executed"] == 30
    assert first["max_queue_depth"] == 3
    assert first["budget_waits"] > 0

"""The readers of the program's own spans and wait counters: their
arithmetic on fake runs, and that they read nothing from a program
without them."""
import sys
import time
import types

import pytest

import _paths  # noqa: F401
import spec
from repro.trace import span, spans

LOOP = {"loop.batch_ms": "train.batch", "loop.schedule_ms": "train.schedule",
        "loop.state_ms": "train.state", "loop.log_ms": "train.log"}
SAVE = {"loop.fetch_s": "train.fetch_state", "ckpt.join_s": "ckpt.join",
        "ckpt.serialize_s": "ckpt.serialize", "ckpt.submit_s": "ckpt.submit"}
COUNTERS = ["fs.budget_wait_us.ckpt", "fs.budget_wait_us.tree",
            "fs.sync_wait_us.tree", "engine.queue_wait_us.tree"]


def fake_run(t0, t1, steps=(), saves=(), stats=None):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(t0=t0, t1=t1), steps=list(steps),
        saves=list(saves), stats=stats or {})


def spans_in_a_window(name, n):
    """``n`` spans called ``name`` inside a window, one before and one
    after it; returns the window and the seconds inside."""
    with span(name):
        pass
    t0 = time.perf_counter()
    for _ in range(n):
        with span(name):
            time.sleep(1e-3)
    t1 = time.perf_counter()
    with span(name):
        time.sleep(1e-3)
    inside = spans(name, t0, t1)
    assert len(inside) == n
    return t0, t1, sum(s.t1 - s.t0 for s in inside)


@pytest.mark.parametrize("metric", sorted(LOOP))
def test_loop_readers_give_ms_per_window_step(metric):
    t0, t1, secs = spans_in_a_window(LOOP[metric], 3)
    run = fake_run(t0, t1, steps=[7, 8, 9, 10])
    assert spec.reader(metric)(run) == pytest.approx(1e3 * secs / 4)
    assert secs >= 3e-3
    assert spec.reader(metric)(fake_run(t0, t1)) is None


@pytest.mark.parametrize("metric", sorted(SAVE))
def test_save_readers_give_seconds_per_window_save(metric):
    t0, t1, secs = spans_in_a_window(SAVE[metric], 4)
    run = fake_run(t0, t1, steps=range(32), saves=["s16", "s32"])
    assert spec.reader(metric)(run) == pytest.approx(secs / 2)
    assert spec.reader(metric)(fake_run(t0, t1, steps=range(32))) is None


@pytest.mark.parametrize("metric", sorted(LOOP) + sorted(SAVE))
def test_span_readers_read_zero_where_nothing_was_spent(metric):
    t = time.perf_counter()
    run = fake_run(t, t + 1e-9, steps=[1], saves=["s1"])
    assert spec.reader(metric)(run) == 0.0


@pytest.mark.parametrize("metric", sorted(LOOP) + sorted(SAVE))
def test_span_readers_read_nothing_from_a_program_without_spans(
        metric, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    run = fake_run(0.0, 1e9, steps=[1], saves=["s1"])
    assert spec.reader(metric)(run) is None


def test_counter_readers():
    stats = {"eager_acks": 30, "sync_ops": 10, "executed": 50,
             "budget_wait_s": 2e-3, "sync_wait_s": 1e-3,
             "queue_wait_s": 5e-3}
    run = fake_run(0.0, 1.0, stats=stats)
    assert spec.reader("fs.budget_wait_us.ckpt")(run) == pytest.approx(50.0)
    assert spec.reader("fs.budget_wait_us.tree")(run) == pytest.approx(50.0)
    assert spec.reader("fs.sync_wait_us.tree")(run) == pytest.approx(100.0)
    assert spec.reader("engine.queue_wait_us.tree")(run) == \
        pytest.approx(100.0)
    # the work happened and nothing waited
    idle = dict(stats, budget_wait_s=0.0, sync_wait_s=0.0, queue_wait_s=0.0)
    for m in COUNTERS:
        assert spec.reader(m)(fake_run(0.0, 1.0, stats=idle)) == 0.0


@pytest.mark.parametrize("metric", COUNTERS)
def test_counter_readers_read_nothing_without_a_divisor_or_a_counter(
        metric):
    # nothing to divide by
    zero = {"eager_acks": 0, "sync_ops": 0, "executed": 0,
            "budget_wait_s": 0.0, "sync_wait_s": 0.0, "queue_wait_s": 0.0}
    assert spec.reader(metric)(fake_run(0.0, 1.0, stats=zero)) is None
    # an engine without the counter
    older = {"eager_acks": 30, "sync_ops": 10, "executed": 50,
             "ack_latency_s": 1e-3}
    assert spec.reader(metric)(fake_run(0.0, 1.0, stats=older)) is None


def test_split_counters_have_one_reader():
    for name in ("fs.budget_wait_us.ckpt", "fs.budget_wait_us.tree"):
        assert spec.reader(name).__code__.co_filename.endswith(
            "/metrics/fs.budget_wait_us.py")
    assert spec.reader("fs.sync_wait_us.tree").__code__.co_filename \
        .endswith("/metrics/fs.sync_wait_us.py")
    assert spec.reader("engine.queue_wait_us.tree").__code__.co_filename \
        .endswith("/metrics/engine.queue_wait_us.py")

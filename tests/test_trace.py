"""The host span recorder (``repro.trace``): nesting, threads, the ring's
bound, window cuts, and that the engine package stays free of jax."""
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from repro import trace
from repro.trace import Recorder

SRC = Path(__file__).resolve().parents[1] / "src"


def test_nesting_records_the_enclosing_span_as_parent():
    rec = Recorder()
    with rec.span("outer"):
        with rec.span("inner"):
            pass
        with rec.span("inner"):
            pass
    with rec.span("alone"):
        pass
    got = [(s.name, s.parent) for s in rec.spans()]
    # a span is recorded when it ends
    assert got == [("inner", "outer"), ("inner", "outer"), ("outer", None),
                   ("alone", None)]
    outer = rec.spans("outer")[0]
    for s in rec.spans("inner"):
        assert outer.t0 <= s.t0 <= s.t1 <= outer.t1
        assert s.thread == outer.thread == threading.get_ident()


def test_a_span_is_recorded_when_its_body_raises():
    rec = Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("fails"):
                raise ValueError("x")
    with rec.span("after"):
        pass
    assert [(s.name, s.parent) for s in rec.spans()] == [
        ("fails", "outer"), ("outer", None), ("after", None)]


def test_each_thread_has_its_own_stack():
    rec = Recorder()
    inside = threading.Barrier(2)

    def work(name):
        with rec.span(name):
            inside.wait()           # both outer spans open at once
            with rec.span(name + ".child"):
                pass

    threads = [threading.Thread(target=work, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    by_name = {s.name: s for s in rec.spans()}
    assert by_name["a.child"].parent == "a"
    assert by_name["b.child"].parent == "b"
    assert by_name["a"].parent is None and by_name["b"].parent is None
    assert by_name["a"].thread == by_name["a.child"].thread
    assert by_name["a"].thread != by_name["b"].thread


def test_the_ring_keeps_the_newest_and_counts_the_dropped():
    rec = Recorder(capacity=3)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.spans()] == ["s2", "s3", "s4"]
    assert rec.dropped == 2


@pytest.mark.parametrize("capacity", [1 << 16, 1000])
def test_many_threads_lose_no_span_and_count_every_drop(capacity):
    rec = Recorder(capacity=capacity)
    threads, per = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(per):
                with rec.span(f"t{k}"):
                    with rec.span(f"t{k}.inner"):
                        pass
        ts = [threading.Thread(target=work, args=(k,))
              for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    total = 2 * threads * per
    got = rec.spans()
    assert len(got) == min(total, capacity)
    assert rec.dropped == total - len(got)
    assert all(s.parent == s.name[:-len(".inner")] for s in got
               if s.name.endswith(".inner"))


def test_spans_are_cut_to_a_window_by_name():
    rec = Recorder()
    with rec.span("x"):
        pass
    t0 = time.perf_counter()
    with rec.span("x"):
        pass
    with rec.span("y"):
        pass
    t1 = time.perf_counter()
    with rec.span("x"):
        pass
    assert len(rec.spans("x")) == 3
    inside = rec.spans("x", t0, t1)
    assert len(inside) == 1 and t0 <= inside[0].t0 <= inside[0].t1 <= t1
    assert [s.name for s in rec.spans(t0=t0, t1=t1)] == ["x", "y"]
    assert len(rec.spans("x", t0=t0)) == 2
    assert len(rec.spans("x", t1=t1)) == 2


def test_the_process_recorder():
    t0 = time.perf_counter()
    with trace.span("test.process"):
        pass
    got = trace.spans("test.process", t0)
    assert len(got) == 1 and got[0].parent is None
    assert trace.RECORDER.dropped == 0


def test_a_span_enters_a_trace_annotation_once_jax_is_imported(monkeypatch):
    import jax
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    rec = Recorder()
    with rec.span("a"):
        with rec.span("b"):
            pass
    assert seen == [("enter", "a"), ("enter", "b"), ("exit", "b"),
                    ("exit", "a")]


def test_the_engine_and_the_recorder_import_no_jax():
    code = ("import sys, repro.core, repro.trace\n"
            "with repro.trace.span('x'):\n"
            "    pass\n"
            "assert len(repro.trace.spans('x')) == 1\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr

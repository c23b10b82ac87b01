"""Property-based tests (hypothesis): the engine's invariants.

The central property is the paper's implicit correctness claim: for a
single-writer batch task, replaying any operation stream through the eager
engine yields EXACTLY the filesystem state (and read values) of a fully
synchronous execution — eagerness may only change *when* things happen,
never *what*.
"""
from __future__ import annotations

import hypothesis.strategies as stx
from hypothesis import HealthCheck, given, settings

from repro.core import CannyFS, EagerFlags, InMemoryBackend

DIRS = ["a", "b", "a/sub"]
FILES = [f"{d}/f{i}" for d in DIRS for i in range(3)]


def op_strategy():
    write = stx.tuples(stx.just("write"), stx.sampled_from(FILES),
                       stx.binary(min_size=0, max_size=24))
    append = stx.tuples(stx.just("append"), stx.sampled_from(FILES),
                        stx.binary(min_size=1, max_size=8))
    read = stx.tuples(stx.just("read"), stx.sampled_from(FILES),
                      stx.just(b""))
    unlink = stx.tuples(stx.just("unlink"), stx.sampled_from(FILES),
                        stx.just(b""))
    rename = stx.tuples(stx.just("rename"), stx.sampled_from(FILES),
                        stx.sampled_from(FILES).map(lambda s: s.encode()))
    statop = stx.tuples(stx.just("stat"), stx.sampled_from(FILES),
                        stx.just(b""))
    readdir = stx.tuples(stx.just("readdir"), stx.sampled_from(DIRS),
                         stx.just(b""))
    chmod = stx.tuples(stx.just("chmod"), stx.sampled_from(FILES),
                       stx.just(b""))
    return stx.lists(stx.one_of(write, append, read, unlink, rename, statop,
                                readdir, chmod),
                     min_size=1, max_size=40)


class Oracle:
    """Synchronous in-memory reference semantics."""

    def __init__(self):
        self.files: dict[str, bytes] = {}

    def apply(self, op, path, arg):
        if op == "write":
            self.files[path] = arg
        elif op == "append":
            self.files[path] = self.files.get(path, b"") + arg
        elif op == "read":
            return self.files.get(path)
        elif op == "unlink":
            self.files.pop(path, None)
        elif op == "rename":
            dst = arg.decode()
            if path in self.files and path != dst:
                self.files[dst] = self.files.pop(path)
        elif op == "stat":
            f = self.files.get(path)
            return None if f is None else len(f)
        elif op == "readdir":
            return sorted({p.split("/")[-1] for p in self.files
                           if p.rsplit("/", 1)[0] == path}
                          | ({"sub"} if path == "a" else set()))
        return None


def drive(fs: CannyFS, ops):
    """Replay ops, checking every read-class result against the oracle
    *inline* (this is the read-barrier property).

    Destructive ops on missing paths are pre-filtered against the oracle —
    the paper's workload model is a valid single-writer task, and an eager
    engine would (correctly) report such mistakes only via the ledger."""
    oracle = Oracle()
    for op, path, arg in ops:
        if op in ("unlink", "chmod") and path not in oracle.files:
            continue
        if op == "rename" and (path not in oracle.files
                               or arg.decode() == path):
            continue
        expect = oracle.apply(op, path, arg)
        if op == "write":
            fs.write_file(path, arg)
        elif op == "append":
            with fs.open(path, "ab") as h:
                h.write(arg)
        elif op == "read":
            try:
                got = fs.read_file(path)
            except FileNotFoundError:
                got = None
            assert got == expect, (op, path, got, expect)
        elif op == "unlink":
            fs.unlink(path)
        elif op == "rename":
            fs.rename(path, arg.decode())
        elif op == "stat":
            st = fs.stat(path)
            got = st.size if st.exists else None
            assert got == expect, (op, path, got, expect)
        elif op == "readdir":
            got = [n for n in fs.readdir(path)]
            assert got == expect, (op, path, got, expect)
        elif op == "chmod":
            fs.chmod(path, 0o600)
    return oracle


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=op_strategy(), workers=stx.sampled_from([1, 4, 16]))
def test_eager_equals_synchronous(ops, workers):
    """Final state identical to synchronous semantics; reads always
    observe all previously ACKed writes."""
    be = InMemoryBackend()
    fs = CannyFS(be, workers=workers, max_inflight=64)
    for d in DIRS:
        fs.makedirs(d)
    oracle = drive(fs, ops)
    fs.drain()
    # ledger clean: unlink/rename of missing paths were pre-filtered, so
    # any deferred error is a real ordering bug
    errors = [e for e in fs.ledger.entries()]
    assert not errors, errors
    snap = be.snapshot()
    assert snap["files"] == oracle.files
    fs.close()


@settings(max_examples=30, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=op_strategy(), budget=stx.sampled_from([1, 2, 8, 300]))
def test_budget_bound_holds(ops, budget):
    be = InMemoryBackend()
    fs = CannyFS(be, workers=4, max_inflight=budget)
    for d in DIRS:
        fs.makedirs(d)
    drive(fs, ops)
    fs.drain()
    assert fs.engine.stats.max_queue_depth <= budget
    fs.close()


@settings(max_examples=30, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=op_strategy())
def test_sync_mode_equals_eager_mode(ops):
    """all_off (fully synchronous) and default (fully eager) produce the
    same final filesystem."""
    final = []
    for flags in (EagerFlags(), EagerFlags.all_off()):
        be = InMemoryBackend()
        fs = CannyFS(be, flags=flags, workers=8)
        for d in DIRS:
            fs.makedirs(d)
        drive(fs, ops)
        fs.drain()
        final.append(be.snapshot()["files"])
        fs.close()
    assert final[0] == final[1]


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(fail_at=stx.integers(min_value=0, max_value=19),
       n=stx.integers(min_value=5, max_value=20))
def test_error_always_surfaces_by_commit(fail_at, n):
    """An injected failure on any write is (a) recorded in the ledger and
    (b) fails the transaction commit — never silently swallowed."""
    fail_at = fail_at % n

    class Bad(InMemoryBackend):
        def write_at(self, p, o, d):
            if p.endswith(f"f{fail_at}"):
                raise OSError(5, "injected")
            return super().write_at(p, o, d)

    from repro.core import Transaction, TransactionFailedError
    be = Bad()
    fs = CannyFS(be)
    txn = Transaction(fs)
    try:
        with txn:
            fs.makedirs("out")
            for i in range(n):
                fs.write_file(f"out/f{i}", b"data")
        raise AssertionError("commit should have failed")
    except TransactionFailedError as e:
        assert any(f"f{fail_at}" in str(en) for en in e.entries)
    txn.rollback()
    assert be.snapshot()["files"] == {}
    fs.close()

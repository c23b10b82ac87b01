"""Prefetch-on-vs-off oracle property tests: for any op stream over a
cold pre-populated tree — walks, readdirs, stats, writes, removals,
whole-subtree rmtrees — running with the speculative metadata prefetcher
enabled and disabled leaves the InMemory backend in the identical final
state with identical read results and ledger outcomes, including under
seeded fault plans.  Mirrors the fusion/overlay equivalence suites;
the streams are hypothesis's shrinking examples."""
import hypothesis.strategies as stx
from hypothesis import HealthCheck, given, settings

from repro.core import (CannyFS, FaultInjectingBackend, FaultPlan,
                        FaultRule, InMemoryBackend)

# the cold tree every run starts from (populated directly on the
# backend, so the mount must discover it — prefetch's whole domain)
COLD_DIRS = ["pre", "pre/d0", "pre/d1", "pre/d0/g0"]
COLD_FILES = [f"{d}/c{i}" for d in COLD_DIRS for i in range(2)]
# in-window namespace the driver mutates
DIRS = ["pre", "pre/d0", "pre/d1", "pre/d0/g0", "live"]
FILES = [f"{d}/f{i}" for d in DIRS for i in range(2)] + COLD_FILES


def _populate(be):
    be.mkdir("live")
    for d in COLD_DIRS:
        be.mkdir(d)
    for f in COLD_FILES:
        be.create(f)
        be.write_at(f, 0, f.encode())


def _drive(fs, ops):
    """Replay ops, collecting every read-class answer.  Destructive ops
    on missing paths are filtered against live-set bookkeeping (the
    valid single-writer task model, as in the sibling suites); the cold
    tree counts as live from the start."""
    observed = []
    live = set(COLD_FILES)
    live_dirs = set(COLD_DIRS) | {"live"}
    for op, path, arg in ops:
        if op == "write":
            if path.rsplit("/", 1)[0] not in live_dirs:
                continue
            fs.write_file(path, arg)
            live.add(path)
        elif op == "unlink" and path in live:
            fs.unlink(path)
            live.discard(path)
        elif op == "rename":
            dst = arg
            if path not in live or dst == path or dst in live_dirs:
                continue
            if dst.rsplit("/", 1)[0] not in live_dirs:
                continue
            fs.rename(path, dst)
            live.discard(path)
            live.add(dst)
        elif op == "readdir" and path in live_dirs:
            observed.append(("readdir", path, fs.readdir(path)))
        elif op == "walk" and (not path or path in live_dirs):
            observed.append(("walk", path,
                             [(d, list(s), list(f))
                              for d, s, f in fs.walk(path)]))
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


def check_equivalent(ops, workers):
    """The acceptance property: identical final backend state, identical
    readdir/walk/stat/read answers, identical (empty) ledger."""
    results = []
    for prefetch in (None, False):    # None -> default policy (enabled)
        be = InMemoryBackend()
        _populate(be)
        fs = CannyFS(be, workers=workers, prefetch=prefetch,
                     echo_errors=False)
        observed = _drive(fs, ops)
        fs.drain()
        sig = sorted((e.kind, e.paths, getattr(e.error, "errno", None))
                     for e in fs.ledger.entries())
        results.append((be.snapshot(), observed, sig))
        fs.close()
    assert results[0] == results[1]


def check_fault_equivalent(ops, seed):
    """Under a seeded fault plan the two modes may fail *different*
    backend calls (speculative batches consume readdir matches the
    unprefetched run never issues, and batch faults are advisory), but a
    clean run (no injected faults in either mode) must produce identical
    state, and no run may ledger more faults than were injected."""
    outcome = []
    for prefetch in (None, False):
        plan = FaultPlan([FaultRule(error="EIO",
                                    ops=("write", "unlink", "rmdir",
                                         "readdir", "remove_tree"),
                                    probability=0.15, max_failures=3)],
                         seed=seed)
        be = InMemoryBackend()
        _populate(be)
        fs = CannyFS(FaultInjectingBackend(be, plan), workers=2,
                     prefetch=prefetch, echo_errors=False)
        try:
            _drive(fs, ops)
        except OSError:
            pass   # a sync read path may surface an injected fault
        fs.drain()
        n_ledgered = sum(getattr(e.error, "injected", False)
                         for e in fs.ledger.entries())
        outcome.append((plan.injected, n_ledgered, be.snapshot()))
        fs.close()
    for injected, ledgered, _ in outcome:
        # sync-surfaced faults skip the ledger; speculative-batch faults
        # are advisory and must NEVER be ledgered
        assert ledgered <= injected
    if outcome[0][0] == 0 and outcome[1][0] == 0:
        assert outcome[0][2] == outcome[1][2]


def _op_strategy():
    write = stx.tuples(stx.just("write"), stx.sampled_from(FILES),
                       stx.binary(min_size=0, max_size=12))
    rename = stx.tuples(stx.just("rename"), stx.sampled_from(FILES),
                        stx.sampled_from(FILES))
    walk = stx.tuples(stx.just("walk"), stx.sampled_from(["", "pre"]),
                      stx.none())
    readdir = stx.tuples(stx.just("readdir"), stx.sampled_from(DIRS),
                         stx.none())
    statop = stx.tuples(stx.just("stat"),
                        stx.sampled_from(FILES + DIRS), stx.none())
    read = stx.tuples(stx.just("read"), stx.sampled_from(FILES),
                      stx.none())
    unlink = stx.tuples(stx.just("unlink"), stx.sampled_from(FILES),
                        stx.none())
    rmtree = stx.tuples(stx.just("rmtree"), stx.sampled_from(DIRS),
                        stx.none())
    remake = stx.tuples(stx.just("remake"), stx.sampled_from(DIRS),
                        stx.none())
    return stx.lists(stx.one_of(write, rename, walk, readdir, statop,
                                read, unlink, rmtree, remake),
                     min_size=1, max_size=25)

@settings(max_examples=50, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), workers=stx.sampled_from([1, 4]))
def test_prefetch_on_and_off_execution_identical(ops, workers):
    check_equivalent(ops, workers)

@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), seed=stx.integers(0, 3))
def test_prefetch_modes_agree_under_fault_plans(ops, seed):
    check_fault_equivalent(ops, seed)

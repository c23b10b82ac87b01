"""Fused-vs-unfused oracle property tests (hypothesis): for any op
stream and any seed, fusion on/off leaves the InMemory backend in the
identical final state with identical read results and ledger outcomes."""
import hypothesis.strategies as stx
from hypothesis import HealthCheck, given, settings

from repro.core import (CannyFS, FaultInjectingBackend, FaultPlan, FaultRule,
                        FusionPolicy, InMemoryBackend, LatencyBackend,
                        LatencyModel, VirtualClock)


DIRS = ["a", "b"]
FILES = [f"{d}/f{i}" for d in DIRS for i in range(3)]


def fusion_op_strategy():
    """Chain-heavy streams: chunked writes, metadata bursts, unlinks that
    land inside the pending window, reads as observation points."""
    chunks = stx.tuples(stx.just("chunks"), stx.sampled_from(FILES),
                        stx.lists(stx.binary(min_size=1, max_size=12),
                                  min_size=1, max_size=6))
    meta = stx.tuples(stx.just("chmod"), stx.sampled_from(FILES),
                      stx.sampled_from([0o600, 0o640, 0o644]))
    trunc = stx.tuples(stx.just("truncate"), stx.sampled_from(FILES),
                       stx.integers(min_value=0, max_value=30))
    unlink = stx.tuples(stx.just("unlink"), stx.sampled_from(FILES),
                        stx.none())
    read = stx.tuples(stx.just("read"), stx.sampled_from(FILES), stx.none())
    return stx.lists(stx.one_of(chunks, meta, trunc, unlink, read),
                     min_size=1, max_size=30)


def _drive(fs, ops):
    reads = []
    live = set()
    for op, path, arg in ops:
        if op == "chunks":
            with fs.open(path, "wb") as h:
                for c in arg:
                    h.write(c)
            live.add(path)
        elif op in ("chmod", "truncate") and path in live:
            (fs.chmod if op == "chmod" else fs.truncate)(path, arg)
        elif op == "unlink" and path in live:
            fs.unlink(path)
            live.discard(path)
        elif op == "read" and path in live:
            reads.append(fs.read_file(path))
    return reads


@settings(max_examples=50, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=fusion_op_strategy(), workers=stx.sampled_from([1, 4]))
def test_fused_and_unfused_execution_identical(ops, workers):
    """The satellite property: for any op stream, fusion on/off leaves the
    InMemory oracle in the identical final state with identical reads and
    identical (empty) ledgers."""
    results = []
    for fusion in (True, False):
        be = InMemoryBackend()
        fs = CannyFS(be, workers=workers, fusion=fusion, echo_errors=False)
        for d in DIRS:
            fs.makedirs(d)
        reads = _drive(fs, ops)
        fs.drain()
        sig = sorted((e.kind, e.paths,
                      getattr(e.error, "errno", None))
                     for e in fs.ledger.entries())
        results.append((be.snapshot(), reads, sig))
        fs.close()
    assert results[0] == results[1]


@settings(max_examples=50, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=fusion_op_strategy(), workers=stx.sampled_from([1, 4, 8]))
def test_work_stealing_on_and_off_execution_identical(ops, workers):
    """PR 4 acceptance property: sharded dispatch with work stealing
    enabled vs disabled is purely a scheduling difference — for any op
    stream and worker count the InMemory oracle ends in the identical
    final state with identical reads and identical (empty) ledgers."""
    results = []
    for stealing in (True, False):
        be = InMemoryBackend()
        fs = CannyFS(be, workers=workers, work_stealing=stealing,
                     echo_errors=False)
        for d in DIRS:
            fs.makedirs(d)
        reads = _drive(fs, ops)
        fs.drain()
        sig = sorted((e.kind, e.paths, getattr(e.error, "errno", None))
                     for e in fs.ledger.entries())
        results.append((be.snapshot(), reads, sig))
        fs.close()
    assert results[0] == results[1]


@settings(max_examples=30, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=fusion_op_strategy(), workers=stx.sampled_from([1, 4]),
       seed=stx.integers(0, 3))
def test_adaptive_and_fixed_max_bytes_execution_identical(ops, workers, seed):
    """PR 4 acceptance property: sizing write coalescing from the
    latency backend's measured bandwidth-delay product (adaptive) vs the
    fixed FusionPolicy cap only changes *where* vectors rotate, never
    commit-visible state — identical final backend state, reads and
    ledger, on a latency stack so the BDP source is genuinely live."""
    results = []
    for adaptive in (True, False):
        inner = InMemoryBackend()
        remote = LatencyBackend(
            inner, LatencyModel(meta_ms=1.0, data_ms=1.0, jitter_sigma=0.3,
                                seed=seed), clock=VirtualClock())
        fs = CannyFS(remote, workers=workers, echo_errors=False,
                     fusion=FusionPolicy(adaptive_max_bytes=adaptive,
                                         # tiny floor/cap so the adaptive
                                         # clamp genuinely binds mid-stream
                                         min_adaptive_bytes=8,
                                         max_bytes=64))
        for d in DIRS:
            fs.makedirs(d)
        reads = _drive(fs, ops)
        fs.drain()
        sig = sorted((e.kind, e.paths, getattr(e.error, "errno", None))
                     for e in fs.ledger.entries())
        results.append((inner.snapshot(), reads, sig))
        fs.close()
    assert results[0] == results[1]


@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=fusion_op_strategy(), seed=stx.integers(0, 3))
def test_stealing_and_adaptive_agree_under_fault_plans(ops, seed):
    """Both PR 4 knobs together under a seeded fault plan: the two
    configurations may fail different backend calls (fault matching is
    per fused call and vector rotation points differ), but every
    injected fault surfaces in its run's ledger and a clean run (no
    faults fired in either mode) leaves identical state."""
    outcome = []
    for stealing, adaptive in ((True, True), (False, False)):
        plan = FaultPlan([FaultRule(error="EIO", ops=("write",),
                                    probability=0.25, max_failures=2)],
                         seed=seed)
        inner = InMemoryBackend()
        remote = LatencyBackend(
            inner, LatencyModel(meta_ms=1.0, data_ms=1.0, jitter_sigma=0.3,
                                seed=seed), clock=VirtualClock())
        fs = CannyFS(FaultInjectingBackend(remote, plan), workers=4,
                     work_stealing=stealing, echo_errors=False,
                     fusion=FusionPolicy(adaptive_max_bytes=adaptive,
                                         min_adaptive_bytes=8,
                                         max_bytes=64))
        for d in DIRS:
            fs.makedirs(d)
        _drive(fs, ops)
        fs.drain()
        n_write_errs = sum(e.kind == "write" for e in fs.ledger.entries())
        outcome.append((plan.injected, n_write_errs, inner.snapshot()))
        fs.close()
    for injected, write_errs, _ in outcome:
        assert write_errs == injected   # every fault is ledgered, none lost
    if outcome[0][0] == 0 and outcome[1][0] == 0:
        assert outcome[0][2] == outcome[1][2]


@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=fusion_op_strategy(), seed=stx.integers(0, 3))
def test_fused_and_unfused_ledger_outcomes_match_under_faults(ops, seed):
    """With a seeded fault plan the two modes may fail *different* backend
    calls (fault matching is per fused call, by design) — but a clean run
    (no injected faults in either mode) must produce identical state, and
    every injected fault must surface in its run's ledger."""
    outcome = []
    for fusion in (True, False):
        plan = FaultPlan([FaultRule(error="EIO", ops=("write",),
                                    probability=0.25, max_failures=2)],
                         seed=seed)
        be = InMemoryBackend()
        fs = CannyFS(FaultInjectingBackend(be, plan), workers=2,
                     fusion=fusion, echo_errors=False)
        for d in DIRS:
            fs.makedirs(d)
        _drive(fs, ops)
        fs.drain()
        n_write_errs = sum(e.kind == "write" for e in fs.ledger.entries())
        outcome.append((plan.injected, n_write_errs, be.snapshot()))
        fs.close()
    for injected, write_errs, _ in outcome:
        assert write_errs == injected   # every fault is ledgered, none lost
    if outcome[0][0] == 0 and outcome[1][0] == 0:
        assert outcome[0][2] == outcome[1][2]



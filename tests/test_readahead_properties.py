"""Read-ahead-on-vs-off oracle property tests: for any op stream over
pre-populated files — sequential streams, random preads, writes,
truncates, renames, removals, transactional write bursts — running with
the read-side data plane enabled (tiny windows, so several are in
flight per file) and disabled leaves the InMemory backend in the
identical final state with identical read results and ledger outcomes,
including under seeded fault plans.  Mirrors the prefetch/fusion/
overlay equivalence suites; the streams are hypothesis's shrinking
examples."""
import hypothesis.strategies as stx
from hypothesis import HealthCheck, given, settings

from repro.core import (CannyFS, FaultInjectingBackend, FaultPlan, FaultRule,
                        InMemoryBackend, ReadPolicy, Transaction,
                        TransactionFailedError)

# tiny windows force several speculative fetches per streamed file;
# a small batch width forces frequent stat_vec flushes
ON_POLICY = ReadPolicy(adaptive=False, min_bytes=256, max_bytes=1024,
                       max_files=4, stat_batch=3)

# the pre-populated files every run starts from; sizes straddle the
# window (multi-window streams, single-window files, sub-chunk files)
COLD_SIZES = {"pre/s0": 5000, "pre/s1": 300, "pre/s2": 2048, "pre/s3": 9000}
COLD_FILES = sorted(COLD_SIZES)
DIRS = ["pre", "live"]
FILES = COLD_FILES + [f"{d}/f{i}" for d in DIRS for i in range(2)]


def _payload(path: str, size: int) -> bytes:
    seed = sum(path.encode())
    return bytes((seed + j) & 0xFF for j in range(size))


def _populate(be):
    be.mkdir("live")
    be.mkdir("pre")
    for f, size in COLD_SIZES.items():
        be.create(f)
        be.write_at(f, 0, _payload(f, size))


def _drive(fs, ops):
    """Replay ops, collecting every read-class answer.  Destructive ops
    on missing paths are filtered against live-set bookkeeping (the
    valid single-writer task model, as in the sibling suites)."""
    observed = []
    live = set(COLD_FILES)
    live_dirs = {"pre", "live"}
    for i, (op, path, arg) in enumerate(ops):
        if op == "stream" and path in live:
            # the plane's domain: stat for the size, then an exact
            # sequential chunked read — never past EOF
            size = fs.stat(path).size
            chunks, off = [], 0
            while off < size:
                piece = fs.pread(path, off, min(arg, size - off))
                if not piece:
                    break
                chunks.append(piece)
                off += len(piece)
            observed.append(("stream", path, b"".join(chunks)))
        elif op == "pread" and path in live:
            off, size = arg
            observed.append(("pread", path, off, fs.pread(path, off, size)))
        elif op == "write":
            if path.rsplit("/", 1)[0] not in live_dirs:
                continue
            fs.write_file(path, arg)
            live.add(path)
        elif op == "trunc" and path in live:
            fs.truncate(path, arg)
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
        elif op == "stat":
            st = fs.stat(path)
            observed.append(("stat", path, st.exists, st.is_dir))
        elif op == "readdir" and path in live_dirs:
            observed.append(("readdir", path, fs.readdir(path)))
        elif op == "rmtree" and path in live_dirs:
            fs.rmtree(path)
            live_dirs.discard(path)
            for f in [f for f in live if f.startswith(path + "/")]:
                live.discard(f)
        elif op == "remake" and path not in live_dirs:
            fs.makedirs(path)
            live_dirs.add(path)
        elif op == "txn" and path in live_dirs:
            # transactional write burst: the stat batcher's domain
            # (journaling existence probes fuse into stat_vec batches)
            with Transaction(fs):
                for k in range(arg):
                    fs.write_file(f"{path}/t{i}_{k}", b"txn-%d-%d" % (i, k))
            for k in range(arg):
                live.add(f"{path}/t{i}_{k}")
    return observed


def check_equivalent(ops, workers):
    """The acceptance property: identical final backend state, identical
    stream/pread/stat/readdir answers, identical (empty) ledger."""
    results = []
    for readahead in (ON_POLICY, False):
        be = InMemoryBackend()
        _populate(be)
        fs = CannyFS(be, workers=workers, readahead=readahead,
                     echo_errors=False)
        observed = _drive(fs, ops)
        fs.drain()
        sig = sorted((e.kind, e.paths, getattr(e.error, "errno", None))
                     for e in fs.ledger.entries())
        results.append((be.snapshot(), observed, sig))
        fs.close()
    assert results[0] == results[1]
    assert results[0][2] == []      # clean streams never ledger


def check_fault_equivalent(ops, seed):
    """Under a seeded fault plan the two modes may fail *different*
    backend calls (speculative windows/batches consume read/stat
    matches the unbuffered run never issues, and batch faults are
    advisory), but a clean run (no injected faults in either mode) must
    produce identical state, and no run may ledger more faults than
    were injected."""
    outcome = []
    for readahead in (ON_POLICY, False):
        plan = FaultPlan([FaultRule(error="EIO",
                                    ops=("read", "stat", "write", "unlink",
                                         "remove_tree"),
                                    probability=0.15, max_failures=3)],
                         seed=seed)
        be = InMemoryBackend()
        _populate(be)
        fs = CannyFS(FaultInjectingBackend(be, plan), workers=2,
                     readahead=readahead, echo_errors=False)
        try:
            _drive(fs, ops)
        except (OSError, TransactionFailedError):
            pass   # a sync path may surface an injected fault
        fs.drain()
        n_ledgered = sum(getattr(e.error, "injected", False)
                         for e in fs.ledger.entries())
        outcome.append((plan.injected, n_ledgered, be.snapshot()))
        fs.close()
    for injected, ledgered, _ in outcome:
        # sync-surfaced faults skip the ledger; speculative window and
        # batch faults are advisory and must NEVER be ledgered
        assert ledgered <= injected
    if outcome[0][0] == 0 and outcome[1][0] == 0:
        assert outcome[0][2] == outcome[1][2]


def _op_strategy():
    stream = stx.tuples(stx.just("stream"), stx.sampled_from(FILES),
                        stx.sampled_from([300, 700, 1024]))
    pread = stx.tuples(stx.just("pread"), stx.sampled_from(FILES),
                       stx.tuples(stx.integers(0, 10000),
                                  stx.integers(0, 1500)))
    write = stx.tuples(stx.just("write"), stx.sampled_from(FILES),
                       stx.binary(min_size=0, max_size=2000))
    trunc = stx.tuples(stx.just("trunc"), stx.sampled_from(FILES),
                       stx.integers(0, 6000))
    rename = stx.tuples(stx.just("rename"), stx.sampled_from(FILES),
                        stx.sampled_from(FILES))
    statop = stx.tuples(stx.just("stat"),
                        stx.sampled_from(FILES + DIRS), stx.none())
    readdir = stx.tuples(stx.just("readdir"), stx.sampled_from(DIRS),
                         stx.none())
    unlink = stx.tuples(stx.just("unlink"), stx.sampled_from(FILES),
                        stx.none())
    rmtree = stx.tuples(stx.just("rmtree"), stx.sampled_from(DIRS),
                        stx.none())
    remake = stx.tuples(stx.just("remake"), stx.sampled_from(DIRS),
                        stx.none())
    txn = stx.tuples(stx.just("txn"), stx.sampled_from(DIRS),
                     stx.integers(2, 5))
    return stx.lists(stx.one_of(stream, pread, write, trunc, rename,
                                statop, readdir, unlink, rmtree, remake,
                                txn),
                     min_size=1, max_size=20)

@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), workers=stx.sampled_from([1, 4]))
def test_readahead_on_and_off_execution_identical(ops, workers):
    check_equivalent(ops, workers)

@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), seed=stx.integers(0, 3))
def test_readahead_modes_agree_under_fault_plans(ops, seed):
    check_fault_equivalent(ops, seed)

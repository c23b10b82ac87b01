"""Backend-zoo equivalence property tests (PR 8): for any op stream —
creates, chunked writes, renames (the retarget rule's domain), unlinks,
rmtrees, readdirs, stats, reads — running through ``CannyFS`` over the
S3-shaped ``ObjectStoreBackend`` or the SFTP-shaped
``RemoteStreamBackend`` leaves the identical final state, returns the
identical read-class answers, and ledgers the identical error signature
as the same stream over the plain ``InMemoryBackend`` oracle.  Billing
diverges wildly (that is the whole point of the zoo); semantics may
not — in particular, rename-as-copy+delete plus the cost-gated retarget
rewrite must be observationally indistinguishable from a native rename.

Also composes the fault/quota decorators over both new backends: the
existing property contracts (ledgered <= injected; clean runs byte-
identical) must hold with a cost-modelled backend at the bottom of the
stack.

Mirrors the op-stream replay of ``test_prefetch_properties``.
"""
import hypothesis.strategies as stx
from hypothesis import HealthCheck, given, settings

from repro.core import (CannyFS, FaultInjectingBackend, FaultPlan,
                        FaultRule, InMemoryBackend, ObjectStoreBackend,
                        ObjectStoreModel, QuotaBackend, RemoteStreamBackend)

# pre-existing state (populated on the oracle, bypassing billing) — gives
# renames both pre-existing sources (plain copy+delete path) and
# in-window sources (the retarget path)
COLD_DIRS = ["pre", "pre/d0", "pre/d1"]
COLD_FILES = [f"{d}/c{i}" for d in COLD_DIRS for i in range(2)]
DIRS = COLD_DIRS + ["live"]
FILES = [f"{d}/f{i}" for d in DIRS for i in range(2)] + COLD_FILES


def _make_backend(kind: str):
    """-> (engine backend, oracle to pre-populate / snapshot)."""
    if kind == "posix":
        be = InMemoryBackend()
        return be, be
    if kind == "object":
        # tiny LIST page: remove_tree/readdir genuinely paginate
        be = ObjectStoreBackend(model=ObjectStoreModel(list_page_size=4))
        return be, be.inner
    be = RemoteStreamBackend()
    return be, be.inner


def _populate(oracle):
    oracle.mkdir("live")
    for d in COLD_DIRS:
        oracle.mkdir(d)
    for f in COLD_FILES:
        oracle.create(f)
        oracle.write_at(f, 0, f.encode())


def _drive(fs, ops):
    """Replay ops, collecting every read-class answer; destructive ops on
    missing paths filtered against live-set bookkeeping (the valid
    single-writer task model, as in the sibling suites)."""
    observed = []
    live = set(COLD_FILES)
    live_dirs = set(DIRS)
    for op, path, arg in ops:
        parent = path.rsplit("/", 1)[0] if "/" in path else ""
        if op in ("write", "append"):
            if parent not in live_dirs:
                continue
            if op == "append" and path in live:
                with fs.open(path, "ab") as f:
                    f.write(arg)
            else:
                with fs.open(path, "wb") as f:   # chunked: exercises fusion
                    f.write(arg[: len(arg) // 2])
                    f.write(arg[len(arg) // 2:])
            live.add(path)
        elif op == "chmod" and path in live:
            fs.chmod(path, arg)
        elif op == "unlink" and path in live:
            fs.unlink(path)
            live.discard(path)
        elif op == "rename":
            dst = arg
            dparent = dst.rsplit("/", 1)[0] if "/" in dst else ""
            if (path not in live or dst == path or dst in live_dirs
                    or dparent not in live_dirs):
                continue
            fs.rename(path, dst)
            live.discard(path)
            live.add(dst)
        elif op == "readdir" and path in live_dirs:
            observed.append(("readdir", path, fs.readdir(path)))
        elif op == "stat":
            st = fs.stat(path)
            observed.append(("stat", path, st.exists, st.is_dir, st.size))
        elif op == "read" and path in live:
            observed.append(("read", path, fs.read_file(path)))
        elif op == "rmtree" and path in live_dirs:
            fs.rmtree(path)
            for d in [d for d in live_dirs
                      if d == path or d.startswith(path + "/")]:
                live_dirs.discard(d)
            for f in [f for f in live if f.startswith(path + "/")]:
                live.discard(f)
        elif op == "remake" and path not in live_dirs:
            if parent and parent not in live_dirs:
                continue
            fs.makedirs(path)
            live_dirs.add(path)
    return observed


def _run(kind, ops, workers, decorate=None):
    be, oracle = _make_backend(kind)
    _populate(oracle)
    engine_be = decorate(be) if decorate is not None else be
    fs = CannyFS(engine_be, workers=workers, echo_errors=False)
    observed = _drive(fs, ops)
    fs.drain()
    sig = sorted((e.kind, e.paths, getattr(e.error, "errno", None))
                 for e in fs.ledger.entries())
    out = (oracle.snapshot(), observed, sig)
    fs.close()
    return out


def check_equivalent(ops, workers):
    """The acceptance property: every zoo member is observationally
    identical to the POSIX oracle for the same stream."""
    baseline = _run("posix", ops, workers)
    for kind in ("object", "remote"):
        assert _run(kind, ops, workers) == baseline, kind


def check_quota_equivalent(ops, workers):
    """A generous quota layer composes over every zoo member without
    changing a byte of semantics."""
    def decorate(be):
        return QuotaBackend(be, budget_bytes=64 << 20)
    baseline = _run("posix", ops, workers, decorate=decorate)
    for kind in ("object", "remote"):
        assert _run(kind, ops, workers, decorate=decorate) == baseline, kind


def check_fault_contract(ops, seed):
    """Under a seeded fault plan the backends may diverge in *which* call
    a fault lands on (the engine sends different call streams to
    different media — that is the optimizer working), but each run must
    honor the ledger contract, and when no fault fired anywhere the
    final states must be identical."""
    outcome = {}
    for kind in ("posix", "object", "remote"):
        plan = FaultPlan([FaultRule(error="EIO",
                                    ops=("write", "unlink", "rmdir",
                                         "rename", "remove_tree"),
                                    probability=0.12, max_failures=3)],
                         seed=seed)
        be, oracle = _make_backend(kind)
        _populate(oracle)
        fs = CannyFS(FaultInjectingBackend(be, plan), workers=2,
                     echo_errors=False)
        try:
            _drive(fs, ops)
        except OSError:
            pass   # a sync path may surface an injected fault
        fs.drain()
        n_ledgered = sum(getattr(e.error, "injected", False)
                         for e in fs.ledger.entries())
        assert n_ledgered <= plan.injected, kind
        outcome[kind] = (plan.injected, oracle.snapshot())
        fs.close()
    if all(injected == 0 for injected, _ in outcome.values()):
        assert (outcome["object"][1] == outcome["posix"][1]
                == outcome["remote"][1])


def _op_strategy():
    payload = stx.binary(min_size=0, max_size=24)
    write = stx.tuples(stx.sampled_from(["write", "append"]),
                       stx.sampled_from(FILES), payload)
    rename = stx.tuples(stx.just("rename"), stx.sampled_from(FILES),
                        stx.sampled_from(FILES))
    chmod = stx.tuples(stx.just("chmod"), stx.sampled_from(FILES),
                       stx.just(0o600))
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
    return stx.lists(stx.one_of(write, rename, chmod, readdir, statop,
                                read, unlink, rmtree, remake),
                     min_size=1, max_size=26)

@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), workers=stx.sampled_from([1, 4]))
def test_zoo_backends_execution_identical_to_oracle(ops, workers):
    check_equivalent(ops, workers)

@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), workers=stx.sampled_from([1, 4]))
def test_zoo_backends_identical_under_quota(ops, workers):
    check_quota_equivalent(ops, workers)

@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=_op_strategy(), seed=stx.integers(0, 3))
def test_zoo_backends_honor_fault_contract(ops, seed):
    check_fault_contract(ops, seed)

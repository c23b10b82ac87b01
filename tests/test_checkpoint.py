"""Transactional checkpoint tests: roundtrip, commit semantics, rollback,
failure injection, reshard-on-restore, and hypothesis pytree roundtrips."""
import threading

import jax
import jax.numpy as jnp
import numpy as np

import hypothesis.strategies as stx
import ml_dtypes
import pytest
from hypothesis import HealthCheck, given, settings

from repro.checkpoint import (COMMIT_FILE, TransactionalCheckpointManager)
from repro.checkpoint.serialization import parse_manifest
from repro.core import (CannyFS, FaultInjectingBackend, FaultPlan, FaultRule,
                        InMemoryBackend, LatencyBackend, LatencyModel)


def make_fs(backend=None):
    return CannyFS(backend or InMemoryBackend(), max_inflight=1000,
                   workers=8)


def test_roundtrip_dtypes_and_structure():
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = {
        "params": {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                   "layers": [{"a": np.ones((2, 2), np.float32)},
                              {"a": np.zeros((2, 2), np.float32)}]},
        "bf16": jnp.ones((5,), jnp.bfloat16) * 1.5,
        "step": np.asarray(3, np.int32),
    }
    mgr.save(3, state, block=True)
    step, out = mgr.restore(state)
    assert step == 3
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(state)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fs.close()


def test_commit_marker_written_last():
    """COMMIT must not exist until every shard is durable: inject latency
    and poll the backing store while the save drains."""
    inner = InMemoryBackend()
    lat = LatencyBackend(inner, LatencyModel(meta_ms=2.0, data_ms=2.0,
                                             jitter_sigma=0.0))
    fs = CannyFS(lat, max_inflight=1000, workers=8)
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = {"w": np.ones(512, np.float32)}
    res = mgr.save(1, state)
    seen_commit_early = False
    while mgr._finalizer is not None and mgr._finalizer.is_alive():
        snap = inner.snapshot()
        if any(COMMIT_FILE in p for p in snap["files"]):
            shard = [p for p in snap["files"] if p.endswith("w.bin")]
            if not shard:
                seen_commit_early = True
    mgr.wait_for_save()
    assert not seen_commit_early
    assert mgr.results[-1].ok
    fs.close()


def test_failed_save_rolls_back_and_next_succeeds():
    class Flaky(InMemoryBackend):
        fail = True

        def write_at(self, p, o, d):
            if self.fail and p.endswith("w.bin"):
                raise OSError(5, "io")
            return super().write_at(p, o, d)

    be = Flaky()
    fs = CannyFS(be)
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = {"w": np.ones(16, np.float32)}
    mgr.save(1, state, block=True)
    assert not mgr.results[-1].ok
    assert mgr.list_steps() == []
    # the partial dir was rolled back
    assert all("step_" not in p for p in be.snapshot()["files"])
    be.fail = False
    fs.ledger.clear()
    mgr.save(2, state, block=True)
    assert mgr.results[-1].ok and mgr.list_steps() == [2]
    step, out = mgr.restore(state)
    assert step == 2
    fs.close()


def mixed_state():
    """fp32 larger than one 4 MB write chunk, bf16, a 0-d int32, and three
    leaves that are not C-contiguous: a transposed one, one laid out with
    its last two axes swapped (the host copy of a device array whose
    layout is major-to-minor (0, 2, 1)) and a strided slice."""
    rng = np.random.default_rng(0)
    swapped = rng.standard_normal((3, 5, 4)).astype(np.float32)
    return {
        "big": rng.standard_normal(5 << 18).astype(np.float32),   # 5 MB
        "bf16": rng.standard_normal((7, 3)).astype(ml_dtypes.bfloat16),
        "step": np.asarray(11, np.int32),
        "t": rng.standard_normal((6, 4)).astype(np.float32).T,
        "swapped": swapped.transpose(0, 2, 1),
        "strided": rng.standard_normal((4, 6)).astype(np.float32)[:, ::2],
    }


def test_mixed_leaves_restore_byte_identically():
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = mixed_state()
    assert not any(state[k].flags.c_contiguous
                   for k in ("t", "swapped", "strided"))
    mgr.save(5, state, block=True)
    assert mgr.results[-1].ok
    assert mgr.results[-1].bytes == sum(a.nbytes for a in state.values())
    _, out = mgr.restore(state)
    for k, a in state.items():
        assert out[k].dtype == a.dtype and out[k].shape == a.shape
        assert out[k].tobytes() == a.tobytes()
    fs.close()


def test_leaf_is_stored_in_its_memory_order():
    """A dense leaf whose axes lie in memory in another order than C order
    is written as it lies, its order named in the manifest; a strided
    leaf is written in C order."""
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = mixed_state()
    mgr.save(5, state, block=True)
    d = mgr._step_dir(5)
    leaves = parse_manifest(fs.read_file(f"{d}/manifest.json"))["leaves"]
    assert leaves["swapped"]["order"] == [0, 2, 1]
    assert leaves["t"]["order"] == [1, 0]
    assert all("order" not in leaves[k]
               for k in ("big", "bf16", "step", "strided"))
    assert fs.read_file(f"{d}/swapped.bin") == \
        state["swapped"].transpose(0, 2, 1).tobytes()
    assert fs.read_file(f"{d}/strided.bin") == state["strided"].tobytes()
    fs.close()


def test_save_copies_no_leaf_at_the_facade():
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck")
    before = fs.stats.write_copied_bytes
    mgr.save(1, mixed_state(), block=True)
    assert mgr.results[-1].ok
    assert fs.stats.write_copied_bytes == before
    fs.close()


@pytest.mark.parametrize("rule", [
    FaultRule(error="EIO", ops=("write",), path_glob="*big.bin",
              max_failures=1),
    FaultRule(outcome="short", ops=("write",), path_glob="*big.bin",
              short_fraction=0.5, max_failures=1),
], ids=["eio", "torn"])
def test_write_fault_rolls_the_save_back(rule):
    inner = InMemoryBackend()
    plan = FaultPlan([rule])
    fs = CannyFS(FaultInjectingBackend(inner, plan), max_inflight=1000,
                 workers=8, echo_errors=False)
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = mixed_state()
    res = mgr.save(1, state, block=True)
    assert plan.injected == 1
    assert not res.ok and "big.bin" in res.error
    assert mgr.list_steps() == []
    assert all("step_" not in p for p in inner.snapshot()["files"])
    assert not fs.ledger
    # the fault has expired: the next save commits and reads back
    res = mgr.save(2, state, block=True)
    assert res.ok and mgr.list_steps() == [2]
    _, out = mgr.restore(state)
    for k, a in state.items():
        assert out[k].tobytes() == a.tobytes()
    fs.close()


def test_gc_keeps_latest():
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck", keep=2)
    state = {"w": np.ones(4, np.float32)}
    for s in (1, 2, 3, 4):
        mgr.save(s, state, block=True)
    assert mgr.list_steps() == [3, 4]
    fs.close()


def test_restore_with_resharding():
    """Saved artifact is mesh-agnostic: restore onto explicit shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_debug_mesh
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck")
    state = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    mgr.save(1, state, block=True)
    mesh = make_debug_mesh(1)
    sh = {"w": NamedSharding(mesh, P(None, None))}
    step, out = mgr.restore(state, shardings=sh)
    assert isinstance(out["w"], jax.Array)
    np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])
    fs.close()


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(data=stx.dictionaries(
    keys=stx.text(alphabet="abcdef", min_size=1, max_size=6),
    values=stx.one_of(
        stx.integers(0, 255).map(lambda n: np.arange(n, dtype=np.float32)),
        stx.integers(1, 16).map(
            lambda n: np.ones((n, 3), np.int32)),
    ),
    min_size=1, max_size=6))
def test_checkpoint_roundtrip_property(data):
    fs = make_fs()
    mgr = TransactionalCheckpointManager(fs, "ck")
    mgr.save(1, data, block=True)
    assert mgr.results[-1].ok
    _, out = mgr.restore(data)
    for k in data:
        np.testing.assert_array_equal(out[k], data[k])
        assert out[k].dtype == data[k].dtype
    fs.close()

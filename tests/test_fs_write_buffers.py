"""What ``CannyFile.write`` does with the caller's buffer: ``bytes`` and
read-only views are borrowed without a copy, writable buffers are frozen
at the call and the copy is counted in ``write_copied_bytes``."""
import numpy as np
import pytest

from repro.core import (CannyFS, InMemoryBackend, LatencyBackend,
                        LatencyModel, LocalBackend)


def record_write_vecs(backend):
    """Remember each ``write_vec``'s segments as ``backend`` receives
    them: (path, [(offset, type, length)])."""
    vecs, inner = [], backend.write_vec

    def write_vec(p, segments):
        vecs.append((p, [(off, type(d), len(d)) for off, d in segments]))
        return inner(p, segments)
    backend.write_vec = write_vec
    return vecs


@pytest.fixture(params=["memory", "local"])
def mount(request, tmp_path):
    """A mount whose create takes 30 ms, so that a write queued behind it
    on the same path has not run when the caller returns."""
    bottom = (InMemoryBackend() if request.param == "memory"
              else LocalBackend(str(tmp_path / "root")))
    vecs = record_write_vecs(bottom)
    slow = LatencyBackend(bottom, LatencyModel(meta_ms=30.0, data_ms=0.0,
                                               jitter_sigma=0.0))
    fs = CannyFS(slow, max_inflight=100, workers=4)
    yield fs, bottom, vecs
    fs.close()


def _payload(n=4096, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


@pytest.mark.parametrize("kind", ["bytearray", "writable_view"])
def test_writable_buffer_is_frozen_at_the_call(mount, kind):
    fs, bottom, _ = mount
    want = _payload()
    buf = bytearray(want)
    arg = buf if kind == "bytearray" else memoryview(buf)
    before = fs.stats.write_copied_bytes
    with fs.open("f", "wb") as f:
        assert f.write(arg) == len(want)
        buf[:] = b"\xff" * len(buf)     # the write is still queued
    assert fs.stats.write_copied_bytes == before + len(want)
    fs.drain()
    assert bottom.read_at("f", 0, -1) == want
    assert not fs.ledger


def test_bytes_are_neither_copied_nor_counted(mount):
    fs, bottom, vecs = mount
    want = _payload()
    fs.write_file("f", want)
    fs.drain()
    assert fs.stats.write_copied_bytes == 0
    assert bottom.read_at("f", 0, -1) == want
    assert [v for p, v in vecs if p == "f"] == [[(0, bytes, len(want))]]


def test_read_only_view_is_borrowed(mount):
    fs, bottom, vecs = mount
    want = _payload()
    arr = np.frombuffer(want, np.uint8).view(np.float32).reshape(32, 32)
    view = memoryview(arr)               # read-only, format "f", 2-D
    assert view.readonly and view.format == "f"
    with fs.open("f", "wb") as f:
        assert f.write(view) == len(want)
    fs.drain()
    assert fs.stats.write_copied_bytes == 0
    assert bottom.read_at("f", 0, -1) == want
    # the backend received the caller's memory, cast to bytes, not a copy
    assert [v for p, v in vecs if p == "f"] == \
        [[(0, memoryview, len(want))]]
    assert not fs.ledger


def test_contiguous_read_only_slices_fuse_into_one_write_vec(mount):
    fs, bottom, vecs = mount
    want = _payload(64 << 10, seed=1)
    v = np.frombuffer(want, np.uint8)
    assert not v.flags.writeable
    mv = memoryview(v)
    chunk = 8 << 10
    fused0 = fs.stats.fused_writes
    with fs.open("f", "wb") as f:
        for lo in range(0, len(mv), chunk):
            f.write(mv[lo:lo + chunk])
    fs.drain()
    assert fs.stats.fused_writes - fused0 == len(want) // chunk - 1
    assert fs.stats.write_copied_bytes == 0
    assert bottom.read_at("f", 0, -1) == want
    # one vectored call; the eight borrowed slices go out as consecutive
    # segments, never joined into a copy
    assert [v for p, v in vecs if p == "f"] == \
        [[(lo, memoryview, chunk) for lo in range(0, len(want), chunk)]]
    assert not fs.ledger


def test_contiguous_bytes_writes_fuse_into_one_joined_segment(mount):
    fs, bottom, vecs = mount
    want = _payload(64 << 10, seed=2)
    chunk = 8 << 10
    with fs.open("f", "wb") as f:
        for lo in range(0, len(want), chunk):
            f.write(want[lo:lo + chunk])
    fs.drain()
    assert fs.stats.write_copied_bytes == 0
    assert bottom.read_at("f", 0, -1) == want
    assert [v for p, v in vecs if p == "f"] == [[(0, bytes, len(want))]]


def test_non_contiguous_read_only_view_is_copied(mount):
    fs, bottom, _ = mount
    base = np.arange(64, dtype=np.uint8).reshape(8, 8)
    base.flags.writeable = False
    view = memoryview(base[:, ::2])
    assert view.readonly and not view.c_contiguous
    with fs.open("f", "wb") as f:
        assert f.write(view) == 32
    fs.drain()
    assert fs.stats.write_copied_bytes == 32
    assert bottom.read_at("f", 0, -1) == base[:, ::2].tobytes()

"""The paper's archive tree, made from the seed, and its extraction and
removal through a CannyFS mount.

The paper extracts the Linux kernel's archive (59,259 entries, ~2.1 GB,
mean file 36 kB) and then removes the tree (arXiv:1612.06830).  The shape
generator is that of ``benchmarks/workloads.py::synth_tree``: directories
hang off a random earlier directory, files land in a random directory,
sizes are lognormal and capped.  Here the tree and the sizes are fixed,
so that every seed does the same work; the seed draws which file has
which size and directory, and the contents: a slice, at an offset drawn
from the seed, of one random block.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

BLOCK = 4 << 20  # the random block every file's contents are cut from


@dataclass
class Archive:
    dirs: list          # relative directory paths, parents first
    files: list         # (relative path, offset into blob, size)
    blob: bytes
    mtime: float

    @property
    def entries(self) -> int:
        return len(self.dirs) + len(self.files)

    @property
    def nbytes(self) -> int:
        return sum(s for _, _, s in self.files)

    def data(self, i: int) -> bytes:
        _, off, size = self.files[i]
        return self.blob[off:off + size]


def lognormal_sizes(n: int, mean: float, sigma: float, cap: int):
    """``n`` file sizes at the quantiles (i + 1/2) / n of a lognormal with
    the given sigma, capped, whose median is set so that their mean is
    ``mean``: the same sizes for every seed."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = lambda med: np.minimum(np.round(med * np.exp(sigma * z)), cap)
    lo, hi = 1.0, float(mean)
    for _ in range(100):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if sizes(mid).mean() < mean else (lo, mid)
    return np.maximum(sizes(hi), 1).astype(np.int64)


def make_archive(seed: int, spec: dict) -> Archive:
    """The directory tree and the multiset of file sizes are the same for
    every seed; the seed draws which file gets which size and directory,
    and the contents."""
    entries, per_dir = spec["entries"], spec["files_per_dir"]
    n_dirs = round(entries / (per_dir + 1))
    n_files = entries - n_dirs
    shape = np.random.default_rng(0)
    dirs = ["src"]
    for i in range(n_dirs - 1):
        parent = dirs[shape.integers(0, len(dirs))]
        dirs.append(f"{parent}/d{i:05d}")
    cap = spec["cap_bytes"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    sizes = rng.permutation(lognormal_sizes(n_files, spec["mean_bytes"],
                                            spec["sigma"], cap))
    offsets = rng.integers(0, BLOCK - sizes + 1)
    where = rng.integers(0, n_dirs, n_files)
    files = [(f"{dirs[w]}/f{i:05d}.c", int(o), int(s))
             for i, (w, o, s) in enumerate(zip(where, offsets, sizes))]
    blob = rng.integers(0, 256, BLOCK + cap, dtype=np.uint8).tobytes()
    mtime = float(1_500_000_000 + rng.integers(0, 10 ** 8))
    return Archive(dirs, files, blob, mtime)


class CallTimer:
    """Caller-side durations of fs calls made while ``on`` is true."""

    def __init__(self):
        self.on = False
        self.ms: list[float] = []

    def __call__(self, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        if self.on:
            self.ms.append((time.perf_counter() - t0) * 1e3)
        return out


def extract(fs, transaction, archive: Archive, root: str,
            timer: CallTimer) -> None:
    """unzip into ``root`` inside one transaction: directories, then each
    file created, written, closed and its mode and times restored."""
    t = archive.mtime
    with transaction(fs, name=f"extract {root}"):
        for d in archive.dirs:
            timer(fs.makedirs, f"{root}/{d}")
        for i, (path, _, _) in enumerate(archive.files):
            p = f"{root}/{path}"
            f = timer(fs.open, p, "wb")
            timer(f.write, archive.data(i))
            timer(f.close)
            timer(fs.utimens, p, t, t)
            timer(fs.chmod, p, 0o644)
        # leaving the block commits: a drain and a check of the ledger
        t0 = time.perf_counter()
    if timer.on:
        timer.ms.append((time.perf_counter() - t0) * 1e3)


def oracle(archive: Archive, root: str):
    """The tree as POSIX says it is after the extraction: the same
    operations applied one by one to an in-memory file system."""
    from repro.core import InMemoryBackend
    be = InMemoryBackend()
    parts = root.split("/")
    for i in range(1, len(parts) + 1):
        be.mkdir("/".join(parts[:i]))
    for d in archive.dirs:
        be.mkdir(f"{root}/{d}")
    for i, (path, _, _) in enumerate(archive.files):
        p = f"{root}/{path}"
        be.create(p)
        be.write_at(p, 0, archive.data(i))
    snap = be.snapshot()
    return snap["dirs"], snap["files"]


def bottom(backend):
    while hasattr(backend, "inner"):
        backend = backend.inner
    return backend


def stored_tree(backend, root: str):
    """(dirs, files) under ``root`` as the storage under the mount holds
    them, read directly (no engine, no modelled latency)."""
    be = bottom(backend)
    if hasattr(be, "snapshot"):
        snap = be.snapshot()
        under = lambda p: p == root or p.startswith(root + "/")
        return ({d for d in snap["dirs"] if under(d)},
                {p: b for p, b in snap["files"].items() if under(p)})
    base = be.root
    dirs, files = set(), {}
    top = os.path.join(base, root)
    if not os.path.exists(top):
        return dirs, files
    for dirpath, dnames, fnames in os.walk(top):
        rel = os.path.relpath(dirpath, base)
        dirs.add(rel)
        for f in fnames:
            with open(os.path.join(dirpath, f), "rb") as fh:
                files[f"{rel}/{f}"] = fh.read()
    return dirs, files


def compare_tree(archive: Archive, backend, root: str) -> int:
    """Entries that differ from the oracle: missing, extra, or with other
    bytes (0 when the tree is exactly the archive)."""
    want_dirs, want_files = oracle(archive, root)
    want_dirs = {d for d in want_dirs if d == root or d.startswith(root + "/")}
    got_dirs, got_files = stored_tree(backend, root)
    bad = len(want_dirs ^ got_dirs)
    bad += len(set(want_files) ^ set(got_files))
    bad += sum(1 for p, b in want_files.items()
               if p in got_files and got_files[p] != b)
    return bad

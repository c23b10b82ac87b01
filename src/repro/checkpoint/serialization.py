"""Pytree <-> bytes codec for checkpoints.

Leaves are stored raw (each written from a read-only byte view of its
host array, ``leaf_bytes``; no copy) with dtype/shape in a JSON manifest —
no pickle, bf16-safe via ml_dtypes, mmap-friendly.  A leaf whose host array
lies in memory with its axes in another order than C order (the host copy
of a device array with a non-default layout) is stored in that order, named
by the manifest's ``order``, rather than transposed on the host.  Keys are
'/'-joined pytree paths so a manifest diff is human-readable.
"""
from __future__ import annotations

import json
from typing import Any

import jax
import ml_dtypes
import numpy as np

# numpy cannot name these dtypes on its own
_EXTRA = {"bfloat16": ml_dtypes.bfloat16,
          "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
          "float8_e5m2": ml_dtypes.float8_e5m2}


def dtype_name(dt) -> str:
    return np.dtype(dt).name


def name_to_dtype(name: str):
    return np.dtype(_EXTRA.get(name, name))


def leaf_path_str(kp) -> str:
    parts = []
    for e in kp:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def flatten_for_save(tree: Any) -> tuple[dict, list[tuple[str, np.ndarray]]]:
    """-> (manifest dict, [(key, host ndarray)]).  Device arrays are fetched
    to host here (the only blocking device interaction of a save)."""
    leaves_kp = jax.tree_util.tree_flatten_with_path(tree)[0]
    manifest = {"leaves": {}, "version": 1}
    out = []
    for kp, leaf in leaves_kp:
        key = leaf_path_str(kp)
        arr = np.asarray(leaf)
        meta = manifest["leaves"][key] = {
            "dtype": dtype_name(arr.dtype),
            "shape": list(arr.shape),
            "nbytes": int(arr.nbytes),
        }
        order = memory_order(arr)
        if order is not None:
            meta["order"] = order
            arr = arr.transpose(order)     # a C-contiguous view, no copy
        out.append((key, arr))
    return manifest, out


def memory_order(arr: np.ndarray) -> list[int] | None:
    """The axes of ``arr`` from the outermost in memory to the innermost,
    where that is not C order and ``arr`` is dense in it; else None."""
    if arr.flags.c_contiguous:
        return None
    order = sorted(range(arr.ndim), key=lambda i: -arr.strides[i])
    if not arr.transpose(order).flags.c_contiguous:
        return None
    return order


def leaf_bytes(arr: np.ndarray) -> memoryview:
    """A read-only 1-D byte view of ``arr``'s C-order bytes, the same bytes
    ``arr.tobytes()`` gives.  Copies only a non-contiguous array."""
    v = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
    v.flags.writeable = False
    return memoryview(v)


def tree_def_of(tree: Any):
    return jax.tree_util.tree_structure(tree)


def unflatten_from(manifest: dict, blobs: dict[str, bytes], like: Any):
    """Rebuild a pytree with the structure of ``like`` from manifest +
    raw blobs."""
    leaves_kp, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for kp, ref_leaf in leaves_kp:
        key = leaf_path_str(kp)
        meta = manifest["leaves"][key]
        arr = np.frombuffer(blobs[key], dtype=name_to_dtype(meta["dtype"]))
        order = meta.get("order")
        if order is None:
            arr = arr.reshape(meta["shape"])
        else:
            arr = arr.reshape([meta["shape"][i] for i in order]).transpose(
                np.argsort(order))
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def manifest_bytes(manifest: dict) -> bytes:
    return json.dumps(manifest, indent=1).encode()


def parse_manifest(raw: bytes) -> dict:
    return json.loads(raw.decode())

"""The comparison that decides ``correct``.

Training, the first three steps against the float32 reference from the
same seeded weights and batches:

* ``grad_gap``: the clipped first gradient, as the optimizer took it
  (its first moment after one step over 1 - b1), by the worst leaf:
  | ||g_prog|| - ||g_ref|| | over the larger of ||g_ref|| and the median
  leaf's ||g_ref||;
* ``change_gap``: the same for the change of the weights after the three
  steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (the others move by round-off alone);
* ``grad_gap_median``: the first gradient's gap as for ``grad_gap``, but
  the median over the layers' own slices of the leaves (each layer of a
  stacked leaf counted apart, 218 of them) instead of the worst leaf.
  Sound runs read it alike on every seed, and it is the number the
  float8 control fails on every seed (PERF.md); the worst leaf's gap
  swings too much from seed to seed, in sound runs and in the control.

``loss_gap``, the largest | program loss - reference loss | over the
steps, is logged and not compared: the control reads only 2.4x what
sound runs do, and no fault 10x.

A leaf is one of the program's parameter arrays; the layers are stacked
in them, so ``A_log`` of all 24 layers is one leaf.  (Per layer, the
24-element leaves ``A_log``, ``D`` and ``dt_bias`` set the worst gap on
almost every seed and swing by 3x from seed to seed: the noise of one
small leaf.)

Storage, exactly: every retained checkpoint read back through a fresh
mount, byte for byte, against the state it was given; the last extracted
tree against the POSIX oracle, and nothing left of the removed ones; and
the mount's error ledger empty.
"""
from __future__ import annotations

import numpy as np
import jax

MOVING = 1e-3   # a leaf moves if its reference gradient is this share of
#                 the median leaf's or more


def leaf_norms(tree) -> dict:
    """Norm of each leaf, and of each layer of a leaf stacked over layers
    (``name[i]``), so that either can be compared."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        a = np.asarray(leaf, np.float64)
        if name.startswith("['blocks']"):
            for i in range(a.shape[0]):
                out[f"{name}[{i}]"] = float(np.linalg.norm(a[i]))
        else:
            out[name] = float(np.linalg.norm(a))
    return out


def by_leaf(norms: dict) -> dict:
    """Per-layer norms summed back (in squares) into the program's leaves."""
    out: dict = {}
    for k, v in norms.items():
        leaf = k.rsplit("[", 1)[0] if k.startswith("['blocks']") else k
        out[leaf] = out.get(leaf, 0.0) + v * v
    return {k: float(np.sqrt(v)) for k, v in out.items()}


def leaf_gaps(prog: dict, ref: dict, leaves=None) -> dict:
    """Per leaf, | ||prog|| - ||ref|| | over the larger of ||ref|| and the
    median leaf's ||ref||."""
    leaves = sorted(ref) if leaves is None else leaves
    median = float(np.median([ref[k] for k in ref]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
            for k in leaves}


def norm_gap(prog: dict, ref: dict, leaves=None) -> float:
    return max(leaf_gaps(prog, ref, leaves).values())


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: losses (list), first_grad and change (per-layer
    leaf norms).  The gradient and the change are compared leaf by leaf
    as the program holds its parameters (a stacked leaf is one leaf)."""
    g_prog, g_ref = by_leaf(prog["first_grad"]), by_leaf(ref["first_grad"])
    c_prog, c_ref = by_leaf(prog["change"]), by_leaf(ref["change"])
    median = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= MOVING * median]
    per_layer = leaf_gaps(prog["first_grad"], ref["first_grad"])
    return {"grad_gap": norm_gap(g_prog, g_ref),
            "change_gap": norm_gap(c_prog, c_ref, moving),
            "grad_gap_median": float(np.median(list(per_layer.values())))}


def within(checks: dict) -> bool:
    """``checks``: name -> (number, limit).  True where every number is at
    or under its limit: the comparison's part of ``correct``."""
    return all(value <= limit for value, limit in checks.values())


def training_checks(prog: dict, ref: dict, limits: dict) -> dict:
    """The training numbers beside the configuration's limits."""
    return {name: (value, limits[name])
            for name, value in training_numbers(prog, ref).items()}


def loss_gap(prog: dict, ref: dict) -> float:
    """The largest |program loss - reference loss| over the steps."""
    return max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))


def checkpoint_mismatches(fs_factory, ckpt_dir: str, saved, keep: int,
                          steps_saved: list) -> int:
    """Leaves of retained checkpoints that are missing or differ from the
    state each was given, plus retained steps other than the last
    ``keep`` saved (a fresh manager on a fresh mount reads them)."""
    from repro.checkpoint import TransactionalCheckpointManager
    fs = fs_factory()
    try:
        mgr = TransactionalCheckpointManager(fs, ckpt_dir, keep=keep)
        listed = mgr.list_steps()
        bad = len(set(listed) ^ set(steps_saved[-keep:]))
        for step, state in saved:
            if step not in listed:
                continue
            like = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
                state)
            _, got = mgr.restore(like, step=step)
            a = jax.tree.leaves(state)
            b = jax.tree.leaves(got)
            bad += abs(len(a) - len(b))
            bad += sum(1 for x, y in zip(a, b)
                       if np.asarray(x).tobytes() != np.asarray(y).tobytes())
    finally:
        fs.close()
    return bad

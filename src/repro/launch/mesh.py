"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests, which must
see the real single CPU device, while the dry-run forces 512 host devices
before first jax init.

Every axis is ``AxisType.Auto``: the sharding rules (``parallel/sharding``)
place parameters, batches and activations with ``NamedSharding`` and
``with_sharding_constraint`` and leave the rest to GSPMD.  ``jax.make_mesh``
defaults to Explicit axes, under which an op whose output sharding is
ambiguous (the embedding gather of a vocab-sharded table by data-sharded
tokens) is a type error instead of a partitioner decision.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model); 'pod' carries
    only gradient reduction (or pipeline stages) over the slow links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh over the first ``n_devices`` devices (tests / examples)."""
    n = n_devices or len(jax.devices())
    if multi_pod and n >= 8:
        return make_mesh((2, 2, n // 4), ("pod", "data", "model"))
    if n == 1:
        return make_mesh((1, 1), ("data", "model"))
    d = 2 if n % 2 == 0 else 1
    return make_mesh((d, n // d), ("data", "model"))

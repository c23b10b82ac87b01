"""Dispatch wrappers: the models call these; we pick Pallas-on-TPU,
Pallas-interpret (kernel tests), or the jnp reference (CPU / dry-run).

Env override: REPRO_KERNELS = auto | jnp | pallas | interpret

The shape gates below fall back to the reference without a word, so a
caller that must run the kernels checks the compiled program with
``tpu_kernel_counts``.

A Mosaic kernel cannot be partitioned by GSPMD.  Under a multi-device
context mesh (``jax.set_mesh``) each Pallas call therefore runs inside a
``shard_map``: batch on the data-parallel axes, heads / width on ``model``
where they divide, everything else replicated.
"""
from __future__ import annotations

import math
import os
import re
from collections import Counter

import jax
from jax.sharding import PartitionSpec as P

_KERNEL_OP = re.compile(r'op_name="(?:[^"]*/)?([^"/]+)/pallas_call"')


def kernel_mode() -> str:
    mode = os.environ.get("REPRO_KERNELS", "auto")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return mode


def tpu_kernel_counts(hlo_text: str) -> Counter:
    """``tpu_custom_call``s in a compiled TPU program, by kernel name (the
    ``name`` each ``pallas_call`` here passes: rmsnorm, ssd_scan,
    flash_attention, rglru_scan)."""
    counts: Counter = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_OP.search(line)
            counts[m.group(1) if m else "unnamed"] += 1
    return counts


def _interpret() -> bool:
    return kernel_mode() == "interpret"


def _use_pallas() -> bool:
    return kernel_mode() in ("pallas", "interpret")


def _mesh():
    """The context mesh when it spans several devices, else None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _batch_axes(mesh, n: int):
    """The data-parallel axes a leading dim of size ``n`` shards over."""
    if mesh is None:
        return None
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return dp if dp and n % math.prod(mesh.shape[a] for a in dp) == 0 \
        else None


def _model_axis(mesh, *dims: int, align: int = 1):
    """'model' when every dim splits over it into ``align`` multiples."""
    m = 1 if mesh is None else mesh.shape.get("model", 1)
    ok = m > 1 and all(d % (m * align) == 0 for d in dims)
    return "model" if ok else None


def _per_shard(fn, mesh, in_specs, out_specs):
    """``fn`` itself without a multi-device mesh, else ``fn`` per shard."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, *, eps: float = 1e-6, residual=None):
    from .rmsnorm.ref import rmsnorm_ref
    if _use_pallas() and x.ndim >= 2 and x.shape[-1] % 128 == 0:
        from .rmsnorm.kernel import rmsnorm_pallas
        mesh = _mesh()
        rows = P(_batch_axes(mesh, x.shape[0]))

        def run(x, scale, *res):
            return rmsnorm_pallas(x, scale, eps=eps, residual=res[0] if res
                                  else None, interpret=_interpret())
        args = (x, scale) if residual is None else (x, scale, residual)
        return _per_shard(run, mesh, (rows, P()) + (rows,) * (len(args) - 2),
                          rows)(*args)
    return rmsnorm_ref(x, scale, eps=eps, residual=residual)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=0, chunk=0,
                    q_positions=None, k_positions=None, softcap=0.0,
                    scale=None):
    """q (B,Sq,H,dh), k/v (B,Sk,K,dh) -> (B,Sq,H,dh).

    The Pallas path requires static self-attention layout (Sq == Sk,
    positions defaulted, 128-aligned seq) — exactly the training/prefill
    shapes; everything else (decode, ragged cache) falls back to the ref.
    """
    from .flash_attention.ref import mha_blocked, mha_ref
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    pallas_ok = (_use_pallas() and q_positions is None and k_positions is None
                 and Sq == Sk and Sq % 256 == 0 and dh % 128 == 0
                 and softcap == 0.0)
    if pallas_ok:
        from .flash_attention.kernel import flash_attention_pallas
        mesh = _mesh()
        spec = P(_batch_axes(mesh, B), None, _model_axis(mesh, H, k.shape[2]))

        def run(q, k, v):
            return flash_attention_pallas(q, k, v, causal=causal,
                                          window=window, chunk=chunk,
                                          scale=scale,
                                          interpret=_interpret())
        return _per_shard(run, mesh, (spec,) * 3, spec)(q, k, v)
    # self-attention on the jnp path: query-blocked exact attention so the
    # lowered HLO never holds an O(S²) buffer (the flash-like production
    # schedule — the dry-run's memory analysis reflects this)
    if (q_positions is None and k_positions is None and Sq == Sk
            and Sq >= 2048 and Sq % 1024 == 0):
        # hillclimbed variant: slice K/V to the mask's reach per q-block
        if ((window or chunk) and
                os.environ.get("REPRO_WINDOWED_ATTN") == "1"):
            from .flash_attention.ref import mha_blocked_windowed
            return mha_blocked_windowed(q, k, v, causal=causal,
                                        window=window, chunk=chunk,
                                        softcap=softcap, scale=scale)
        return mha_blocked(q, k, v, causal=causal, window=window, chunk=chunk,
                           softcap=softcap, scale=scale)
    return mha_ref(q, k, v, causal=causal, window=window, chunk=chunk,
                   q_positions=q_positions, k_positions=k_positions,
                   softcap=softcap, scale=scale)


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan
# ---------------------------------------------------------------------------

def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128):
    from .ssd.ref import ssd_chunked
    S = x.shape[1]
    if _use_pallas() and S % chunk == 0 and x.shape[-1] % 8 == 0:
        from .ssd.kernel import ssd_pallas
        mesh = _mesh()
        b = P(_batch_axes(mesh, x.shape[0]))

        def run(x, dt, A, Bm, Cm, D):
            return ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk,
                              interpret=_interpret())
        return _per_shard(run, mesh, (b, b, P(), b, b, P()), b)(
            x, dt, A, Bm, Cm, D)
    if S % chunk == 0:
        return ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk)
    from .ssd.ref import ssd_sequential
    return ssd_sequential(x, dt, A, Bm, Cm, D)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

def rglru_scan(log_a, gx, h0=None):
    """log_a, gx (B,S,W) -> (y, h_last)."""
    from .rglru.ref import rglru_assoc, rglru_sequential
    B, S, W = gx.shape
    if _use_pallas() and S % 128 == 0 and W % 128 == 0:
        from .rglru.kernel import rglru_pallas
        mesh = _mesh()
        b, w = _batch_axes(mesh, B), _model_axis(mesh, W, align=128)
        seq, state = P(b, None, w), P(b, w)

        def run(log_a, gx, *h0):
            return rglru_pallas(log_a, gx, h0=h0[0] if h0 else None,
                                interpret=_interpret())
        args = (log_a, gx) if h0 is None else (log_a, gx, h0)
        return _per_shard(run, mesh, (seq, seq, state)[:len(args)],
                          (seq, state))(*args)
    if S >= 64:
        return rglru_assoc(log_a, gx, h0=h0)
    return rglru_sequential(log_a, gx, h0=h0)

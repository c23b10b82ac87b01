"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation (vs the Triton SSD kernels in the Mamba-2 release):

* the chunk axis is the last (sequential) grid dimension; the carried
  (N × P) recurrent state lives in fp32 VMEM scratch and persists across
  chunk steps — replacing the GPU's separate state-passing kernel launch
  with a single fused pass;
* everything cheap and awkward for the MXU (softplus, cumsums of the
  log-decay within fixed chunk boundaries, dt scaling) is precomputed
  outside with jnp elementwise ops — the kernel keeps only the three
  matmuls (C·Bᵀ, scores·X, Bᵀ·X) that dominate FLOPs, sized so chunk Q is
  lane-aligned (128);
* the log-decay cumsum enters twice, as a (1, Q) row and a (Q, 1) column,
  so every block's last two dims are either tile-aligned or the array's
  full extent and the kernel never transposes a vector;
* numerically the intra-chunk factor uses exp(cum_i − cum_j) with i ≥ j
  only (argument ≤ 0 — stable), matching the reference.

Backward: ``ssd_pallas`` is a ``jax.custom_vjp`` whose backward is the VJP
of the jnp reference ``ssd_chunked`` (recomputed from the saved inputs).
The forward runs the kernel; the gradient is the reference's.

Inputs are pre-arranged per (batch·head): see ``_ssd_forward``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import _expand_groups, ssd_chunked


def _ssd_kernel(cum_row_ref, cum_col_ref, xdt_ref, xe_ref, b_ref, c_ref,
                y_ref, state_scr, *, q):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    cum_r = cum_row_ref[0]                            # (1, Q) log-decay cumsum
    cum_c = cum_col_ref[0]                            # (Q, 1) the same, column
    xdt = xdt_ref[0]                                  # (Q, P)  dt*x
    xe = xe_ref[0]                                    # (Q, P)  exp(tot-cum)*dt*x
    Bc = b_ref[0]                                     # (Q, N)
    Cc = c_ref[0]                                     # (Q, N)

    # intra-chunk: (C Bᵀ ⊙ decay ⊙ causal) @ (dt x)
    cb = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # mask the argument, not the output (matches ref.py; avoids inf)
    att = jnp.exp(jnp.where(ii >= jj, cum_c - cum_r, -1e30))
    y = jax.lax.dot_general(cb * att, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)

    # inter-chunk: exp(cum) * (C @ state_in)
    state = state_scr[...]                            # (N, P)
    y += jnp.exp(cum_c) * jax.lax.dot_general(
        Cc, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: exp(total) * state + Bᵀ @ xe; the (1, 1) total widens
    # along sublanes, then lanes (Mosaic has no one-step 2-D broadcast)
    total = jnp.broadcast_to(cum_c[q - 1:q, :], (state.shape[0], 1))
    state_scr[...] = jnp.exp(total) * state + jax.lax.dot_general(
        Bc, xe, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)


def _ssd_forward(x, dt, A, Bm, Cm, D, chunk, interpret):
    """Same contract as ssd_chunked: x (B,S,H,P), dt (B,S,H), A (H,),
    Bm/Cm (B,S,G,N), D (H,)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    f32 = jnp.float32

    # ---- jnp-side precompute (elementwise; negligible FLOPs) ----
    dtf = dt.astype(f32)
    dA = (dtf * A.astype(f32)).reshape(B, nc, chunk, H)
    cum = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum
    total = cum[:, :, -1:, :]
    xdt = (x.astype(f32) * dtf[..., None])
    xe = xdt * jnp.exp((total - cum)).reshape(B, S, H)[..., None]
    Bh = _expand_groups(Bm.astype(f32), H)             # (B,S,H,N)
    Ch = _expand_groups(Cm.astype(f32), H)

    # ---- per (batch·head) layout ----
    def bh(a):   # (B,S,H,...) -> (B*H, S, ...)
        return jnp.moveaxis(a, 2, 1).reshape((B * H, S) + a.shape[3:])

    cum_bh = bh(cum.reshape(B, S, H))                  # (BH, S)
    args = (cum_bh[:, None, :], cum_bh[:, :, None], bh(xdt), bh(xe), bh(Bh),
            bh(Ch))

    kernel = functools.partial(_ssd_kernel, q=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk), lambda g, c: (g, 0, c)),
            pl.BlockSpec((1, chunk, 1), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, c: (g, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda g, c: (g, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(*args)

    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)      # (B,S,H,P)
    y = y + x * D.astype(x.dtype)[None, None, :, None]
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, A, Bm, Cm, D, chunk, interpret):
    return _ssd_forward(x, dt, A, Bm, Cm, D, chunk, interpret)


def _ssd_fwd(x, dt, A, Bm, Cm, D, chunk, interpret):
    y = _ssd_forward(x, dt, A, Bm, Cm, D, chunk, interpret)
    return y, (x, dt, A, Bm, Cm, D)


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(ssd_chunked, chunk=chunk), *res)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bm, Cm, D, *, chunk: int = 128, interpret=False):
    """Same contract as ssd_chunked; differentiable (reference backward)."""
    return _ssd(x, dt, A, Bm, Cm, D, chunk, interpret)

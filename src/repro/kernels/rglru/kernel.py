"""Pallas TPU kernel for the RG-LRU linear-recurrence scan.

TPU adaptation (vs Griffin's custom GPU linear-scan kernel): the recurrence
is strictly sequential in time, so the win is purely memory-locality — keep
the (lane-block of the) hidden state resident in VMEM across the whole
sequence instead of round-tripping HBM per step.  Grid is
(batch, width-blocks, time-blocks) with time last (sequential); each step
consumes a (T_blk × 128) tile and runs a fori loop over its rows, state in
fp32 scratch.  Width is vectorized across the 128-lane dimension.  The
initial state enters as (B, 1, W) so its (1, 128) block spans the array's
full second-to-last dim.

Backward: ``rglru_pallas`` is a ``jax.custom_vjp`` whose backward is the VJP
of the jnp reference ``rglru_assoc`` (recomputed from the saved inputs).
The forward runs the kernel; the gradient is the reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import rglru_assoc

LANES = 128


def _rglru_kernel(la_ref, gx_ref, h0_ref, y_ref, h_scr, *, t_blk):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        la = la_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # (1, 128)
        gx = gx_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * la), 1e-12))
        h = jnp.exp(la) * h + beta * gx
        y_ref[0, pl.ds(t, 1), :] = h.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, t_blk, step, h_scr[...])


def _rglru_forward(log_a, gx, h0, t_blk, interpret):
    B, S, W = gx.shape
    assert S % t_blk == 0 and W % LANES == 0, (S, W)
    n_w = W // LANES
    n_t = S // t_blk

    kernel = functools.partial(_rglru_kernel, t_blk=t_blk)
    y = pl.pallas_call(
        kernel,
        grid=(B, n_w, n_t),
        in_specs=[
            pl.BlockSpec((1, t_blk, LANES), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, t_blk, LANES), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, 1, LANES), lambda b, w, t: (b, 0, w)),
        ],
        out_specs=pl.BlockSpec((1, t_blk, LANES), lambda b, w, t: (b, t, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), gx.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(log_a, gx, h0[:, None, :])
    return y, y[:, -1].astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rglru(log_a, gx, h0, t_blk, interpret):
    return _rglru_forward(log_a, gx, h0, t_blk, interpret)


def _rglru_fwd(log_a, gx, h0, t_blk, interpret):
    return _rglru_forward(log_a, gx, h0, t_blk, interpret), (log_a, gx, h0)


def _rglru_bwd(t_blk, interpret, res, g):
    _, vjp = jax.vjp(rglru_assoc, *res)
    return vjp(g)


_rglru.defvjp(_rglru_fwd, _rglru_bwd)


@functools.partial(jax.jit, static_argnames=("t_blk", "interpret"))
def rglru_pallas(log_a, gx, h0=None, *, t_blk: int = 128, interpret=False):
    """log_a, gx (B,S,W) -> (y (B,S,W), h_last (B,W)).  W, S 128-aligned;
    differentiable (reference backward)."""
    if h0 is None:
        h0 = jnp.zeros(gx.shape[::2], jnp.float32)
    return _rglru(log_a, gx, h0, t_blk, interpret)

"""Pallas TPU fused RMSNorm (+ optional residual add).

Bandwidth-bound epilogue: one HBM read of x (+residual), one write of y,
fp32 statistics in-register.  Rows are tiled (block_rows × D) so the full
feature dimension sits in VMEM per tile (D ≤ 8192 fp32 = 32 KiB/row).

Backward: ``rmsnorm_pallas`` is a ``jax.custom_vjp`` whose backward is the
VJP of the jnp reference ``rmsnorm_ref`` (recomputed from the saved
inputs).  The forward runs the kernel; the gradient is the reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import rmsnorm_ref


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


def _rmsnorm_res_kernel(x_ref, s_ref, r_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)[None, :]
    y = y + r_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rmsnorm_forward(x, scale, residual, eps, block_rows, interpret):
    shape = x.shape
    D = shape[-1]
    xr = x.reshape(-1, D)
    R = xr.shape[0]
    rb = min(block_rows, R)
    pad = (-R) % rb
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    rows = xr.shape[0]

    if residual is None:
        kernel = functools.partial(_rmsnorm_kernel, eps=eps)
        operands = (xr, scale)
    else:
        rr = residual.reshape(-1, D)
        if pad:
            rr = jnp.pad(rr, ((0, pad), (0, 0)))
        kernel = functools.partial(_rmsnorm_res_kernel, eps=eps)
        operands = (xr, scale, rr)
    row_block = pl.BlockSpec((rb, D), lambda i: (i, 0))
    in_specs = [row_block, pl.BlockSpec((D,), lambda i: (0,))]
    out = pl.pallas_call(
        kernel,
        grid=(rows // rb,),
        in_specs=in_specs + [row_block] * (len(operands) - 2),
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(*operands)
    if pad:
        out = out[:R]
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rmsnorm(x, scale, residual, eps, block_rows, interpret):
    return _rmsnorm_forward(x, scale, residual, eps, block_rows, interpret)


def _rmsnorm_fwd(x, scale, residual, eps, block_rows, interpret):
    out = _rmsnorm_forward(x, scale, residual, eps, block_rows, interpret)
    return out, (x, scale, residual)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    _, vjp = jax.vjp(lambda x, s, r: rmsnorm_ref(x, s, eps=eps, residual=r),
                     *res)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x, scale, *, eps: float = 1e-6, residual=None,
                   block_rows: int = 256, interpret=False):
    """Same contract as rmsnorm_ref; differentiable (reference backward)."""
    return _rmsnorm(x, scale, residual, eps, block_rows, interpret)

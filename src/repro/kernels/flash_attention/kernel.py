"""Pallas TPU flash attention (forward) with GQA + causal/sliding/chunked
masking.

TPU adaptation notes (vs the CUDA FlashAttention algorithm):

* blocking is over (q-block, kv-block) with the kv dimension as the *last,
  sequential* grid axis — running max/denominator/accumulator live in VMEM
  scratch and persist across kv steps (the Pallas-TPU "revisiting output"
  pattern), instead of CUDA's per-SM shared-memory tiles;
* block shapes are 128-aligned so the MXU sees full tiles; softmax
  statistics are fp32 in scratch regardless of io dtype;
* fully-masked kv blocks are skipped via ``pl.when`` on block-index
  arithmetic (causal upper triangle, out-of-window, out-of-chunk) — this is
  the structural analogue of FlashAttention's early-exit;
* GQA shares each kv-head block across its q-head group through the k/v
  index maps (no KV replication in VMEM).

Backward: ``flash_attention_pallas`` is a ``jax.custom_vjp`` whose backward
is the VJP of the jnp reference (``mha_blocked`` where its 1024-row query
blocks divide S, else ``mha_ref``), recomputed from the saved q, k, v.  The
forward runs the kernel; the gradient is the reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import mha_blocked, mha_ref

NEG_INF = -1e30
LANES = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, causal, window, chunk, bq, bk, n_kv):
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0 = iq * bq            # first q position of this block
    k0 = jk * bk            # first kv position of this block

    # --- block-level skip: is any (i, j) pair in this tile visible?
    live = jnp.bool_(True)
    if causal:
        live &= (q0 + bq - 1) >= k0                  # not above diagonal
    if window:
        live &= q0 < (k0 + bk + window)              # not fully aged out
    if chunk:
        live &= (q0 // chunk) <= ((k0 + bk - 1) // chunk)
        live &= ((q0 + bq - 1) // chunk) >= (k0 // chunk)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qi >= kj
        if window:
            mask &= (qi - kj) < window
        if chunk:
            mask &= (qi // chunk) == (kj // chunk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                        # (bq, 1) replicated
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)              # rescale old stats
        p = jnp.exp(s - m_new)                       # (bq, bk)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jk == n_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal, window, chunk, scale, block_q, block_k,
                   interpret):
    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)

    qt = q.transpose(0, 2, 1, 3)                     # (B,H,S,dh)
    kt = k.transpose(0, 2, 1, 3)                     # (B,K,S,dh)
    vt = v.transpose(0, 2, 1, 3)
    n_q = S // block_q
    n_kv = S // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window, chunk=chunk,
        bq=block_q, bk=block_k, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, iq, jk: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, jk: (b, h // G, jk, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, jk: (b, h // G, jk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, iq, jk: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running denom
            pltpu.VMEM((block_q, dh), jnp.float32),      # output accum
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 10)))
def _flash(q, k, v, causal, window, chunk, scale, block_q, block_k,
           interpret):
    return _flash_forward(q, k, v, causal, window, chunk, scale, block_q,
                          block_k, interpret)


def _flash_fwd(q, k, v, *static):
    return _flash_forward(q, k, v, *static), (q, k, v)


def _flash_bwd(causal, window, chunk, scale, block_q, block_k, interpret,
               res, g):
    S = res[0].shape[1]
    ref = (functools.partial(mha_blocked, block_q=1024) if S % 1024 == 0
           else mha_ref)
    _, vjp = jax.vjp(functools.partial(ref, causal=causal, window=window,
                                       chunk=chunk, scale=scale), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "scale", "block_q",
                     "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, chunk=0,
                           scale=None, block_q=128, block_k=128,
                           interpret=False):
    """q (B,S,H,dh); k,v (B,S,K,dh) -> (B,S,H,dh).  Self-attention layout
    (training / prefill); decode uses the jnp path.  Differentiable
    (reference backward)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash(q, k, v, causal, window, chunk, scale, block_q, block_k,
                  interpret)

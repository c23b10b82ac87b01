"""Every Pallas kernel is differentiable: its custom VJP (the jnp
reference's backward) gives the reference's gradients.  Interpret mode, small
shapes; what the chip's compiler accepts is tests/test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose


def _grads(fn, args):
    def loss(*a):
        out = fn(*a)
        w = [jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
             for o in jax.tree.leaves(out)]       # non-uniform cotangent
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(jax.tree.leaves(out), w))
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


def _assert_same(got, want, tol=2e-4):
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert_allclose(np.asarray(g), np.asarray(w), rtol=tol, atol=tol)


@pytest.mark.parametrize("with_residual", [False, True])
def test_rmsnorm_grad_matches_reference(with_residual):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    args = [jax.random.normal(ks[0], (3, 5, 128)),
            jax.random.normal(ks[1], (128,))]
    if with_residual:
        args.append(jax.random.normal(ks[2], (3, 5, 128)))

    def kernel(x, s, *r):
        return rmsnorm_pallas(x, s, residual=r[0] if r else None,
                              interpret=True)

    def ref(x, s, *r):
        return rmsnorm_ref(x, s, residual=r[0] if r else None)
    _assert_same(_grads(kernel, args), _grads(ref, args))


def test_ssd_grad_matches_reference():
    from repro.kernels.ssd.kernel import ssd_pallas
    from repro.kernels.ssd.ref import ssd_chunked
    B, S, H, P, G, N, chunk = 1, 128, 2, 16, 1, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    args = [jax.random.normal(ks[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5),
            jax.random.normal(ks[3], (B, S, G, N)) * 0.3,
            jax.random.normal(ks[4], (B, S, G, N)) * 0.3,
            jax.random.normal(ks[5], (H,))]
    got = _grads(lambda *a: ssd_pallas(*a, chunk=chunk, interpret=True),
                 args)
    want = _grads(lambda *a: ssd_chunked(*a, chunk=chunk), args)
    _assert_same(got, want)


def test_flash_attention_grad_matches_reference():
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import mha_ref
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    args = [jax.random.normal(ks[0], (1, 256, 4, 128)),
            jax.random.normal(ks[1], (1, 256, 2, 128)),
            jax.random.normal(ks[2], (1, 256, 2, 128))]
    got = _grads(lambda *a: flash_attention_pallas(*a, window=100,
                                                   interpret=True), args)
    want = _grads(lambda *a: mha_ref(*a, window=100), args)
    _assert_same(got, want)


@pytest.mark.parametrize("with_h0", [False, True])
def test_rglru_grad_matches_reference(with_h0):
    from repro.kernels.rglru.kernel import rglru_pallas
    from repro.kernels.rglru.ref import rglru_assoc
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    args = [-jax.nn.softplus(jax.random.normal(ks[0], (2, 128, 128))),
            jax.random.normal(ks[1], (2, 128, 128))]
    if with_h0:
        args.append(jax.random.normal(ks[2], (2, 128)))

    def kernel(la, gx, *h0):
        return rglru_pallas(la, gx, h0[0] if h0 else None, interpret=True)

    def ref(la, gx, *h0):
        return rglru_assoc(la, gx, h0[0] if h0 else None)
    _assert_same(_grads(kernel, args), _grads(ref, args))

"""The plain reference against the program at a tiny size, and the
control (the reference in float8, put in the program's place) against the
limits of the configuration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import check
from _tiny import tiny_cell
from arch import mamba2 as ref
from arch import mamba2_program as prog

CELL = "mamba2-130m.local.ckpt"
SEED = 2 ** 31 + 5


def batches(cfg, n=3, rows=2, seq=64, seed=SEED):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = rng.integers(0, cfg["vocab_size"], (rows, seq + 1),
                         dtype=np.int32)
        out.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    return out


def test_layout_is_the_programs():
    from repro.models import init_params
    c = tiny_cell(CELL).config
    shapes = jax.eval_shape(lambda k: init_params(k, prog.model_config(c)),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    is_shape = lambda x: isinstance(x, tuple)
    got = jax.tree.map(lambda a: tuple(a.shape), shapes)
    assert jax.tree.structure(got, is_leaf=is_shape) == \
        jax.tree.structure(ref.layout(c), is_leaf=is_shape)
    assert jax.tree.leaves(got, is_leaf=is_shape) == \
        jax.tree.leaves(ref.layout(c), is_leaf=is_shape)


def test_loss_and_gradient_match_the_program_in_float32():
    from repro.models import cross_entropy, forward_train
    c = tiny_cell(CELL).config
    mc = prog.model_config(c)
    params = ref.make_params(SEED, c)
    b = batches(c, n=1)[0]
    z = c["optimizer"]["z_loss"]

    def program_loss(p):
        logits, _ = forward_train(p, b, mc, dtype=jnp.float32)
        return cross_entropy(logits, jnp.asarray(b["labels"]), z_loss=z)[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(params)
    lr, gr = jax.value_and_grad(ref.loss)(params, jnp.asarray(b["tokens"]),
                                          jnp.asarray(b["labels"]), c, z)
    assert float(lr) == pytest.approx(float(lp), rel=1e-6)
    for a, b_ in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=2e-4, atol=1e-6)


def test_weights_come_from_the_seed():
    c = tiny_cell(CELL).config
    a, b, d = (jax.tree.leaves(ref.make_params(s, c))
               for s in (SEED, SEED, SEED + 1))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], d[0])


def test_the_control_fails_the_limits():
    """float8 compute inputs, in the program's place, read above at least
    one limit of the configuration: the harness's comparison finds them
    not correct."""
    cell = tiny_cell(CELL)
    c, opt = cell.config, cell.config["optimizer"]
    bs = batches(c)
    norms = lambda o: {"losses": o["losses"],
                       "first_grad": check.leaf_norms(o["first_grad"]),
                       "change": check.leaf_norms(o["change"])}
    exact = norms(ref.train_steps(SEED, c, opt, bs))
    control = norms(ref.train_steps(SEED, c, opt, bs, prec="fp8"))
    checks = check.training_checks(control, exact, c["limits"])
    assert not check.within(checks), checks
    same = check.training_checks(exact, exact, c["limits"])
    assert check.within(same)
    assert all(v == 0 for v, _ in same.values())

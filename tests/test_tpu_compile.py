"""Compile the Pallas kernels (forward + backward) and the mamba2-130m train
step with the TPU compiler for a described — not attached — v5e chip.

Nothing runs, so this checks what interpret mode cannot: block tiling the
chip accepts, a differentiable kernel, a step that fits 16 GB, and that the
compiled program really holds the kernels as ``tpu_custom_call``s.  The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.kernels.ops import tpu_kernel_counts
from repro.launch.roofline import chip_peaks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_grad(fn, args, sharding):
    """Compile value-and-grad of sum(fn(*args)) w.r.t. every argument."""
    def loss(*a):
        out = fn(*a)
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree.leaves(out))
    specs = [_spec(s, d, sharding) for s, d in args]
    grad = jax.value_and_grad(loss, argnums=tuple(range(len(args))))
    return jax.jit(grad).lower(*specs).compile()


def test_ssd_compiles_fwd_bwd_mamba2_width(one_chip):
    from repro.kernels.ssd.kernel import ssd_pallas
    B, S, H, P, G, N = 8, 2048, 24, 64, 1, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    c = _compile_grad(lambda *a: ssd_pallas(*a, chunk=128),
                      [((B, S, H, P), bf16), ((B, S, H), f32), ((H,), f32),
                       ((B, S, G, N), bf16), ((B, S, G, N), bf16),
                       ((H,), f32)], one_chip)
    assert tpu_kernel_counts(c.as_text())["ssd_scan"] >= 1


@pytest.mark.parametrize("d", [768, 4096])
def test_rmsnorm_compiles_fwd_bwd(one_chip, d):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
    c = _compile_grad(rmsnorm_pallas,
                      [((8, 2048, d), jnp.bfloat16), ((d,), jnp.float32)],
                      one_chip)
    assert tpu_kernel_counts(c.as_text())["rmsnorm"] >= 1


def test_flash_attention_compiles_fwd_bwd_qwen2_heads(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    B, S, H, K, dh = 1, 4096, 28, 4, 128
    bf16 = jnp.bfloat16
    c = _compile_grad(flash_attention_pallas,
                      [((B, S, H, dh), bf16), ((B, S, K, dh), bf16),
                       ((B, S, K, dh), bf16)], one_chip)
    assert tpu_kernel_counts(c.as_text())["flash_attention"] >= 1


def test_rglru_compiles_fwd_bwd_recurrentgemma_width(one_chip):
    from repro.configs import get_config
    from repro.kernels.rglru.kernel import rglru_pallas
    W = get_config("recurrentgemma-9b").resolved_lru_width
    B, S = 2, 2048
    f32 = jnp.float32
    c = _compile_grad(rglru_pallas,
                      [((B, S, W), f32), ((B, S, W), f32), ((B, W), f32)],
                      one_chip)
    assert tpu_kernel_counts(c.as_text())["rglru_scan"] >= 1


@pytest.mark.parametrize("n_chips", [1, 4])
def test_mamba2_130m_train_step_compiles_and_fits(topo, monkeypatch, n_chips):
    """The step ``chip_smoke.py`` runs: mamba2-130m at published widths,
    batch 8 x 2048, bf16 compute, on one described chip and on the 2x2
    (data x model) mesh its ``--chips 4`` path compares against it."""
    from repro.configs import get_config
    from repro.models import param_specs
    from repro.optim import init_opt_state
    from repro.train.steps import (TrainConfig, make_train_step,
                                   train_shardings)
    # the dispatch asks jax.default_backend(), which is the CPU here
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    cfg = get_config("mamba2-130m")
    shape = (1, 1) if n_chips == 1 else (2, 2)
    mesh = Mesh(np.array(topo.devices[:n_chips]).reshape(shape),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    pshape = param_specs(cfg)
    bshape = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32)
              for k in ("tokens", "labels")}
    tc = TrainConfig()
    sh = train_shardings(cfg, mesh, pshape, bshape, zero1=tc.zero1)

    def with_sharding(tree, shardings):
        return jax.tree.map(
            lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
            tree, shardings)

    step = jax.jit(make_train_step(cfg, mesh, tc),
                   in_shardings=(sh["params"], sh["opt"], sh["batch"], None),
                   out_shardings=(sh["params"], sh["opt"], None),
                   donate_argnums=(0, 1))
    with jax.set_mesh(mesh):
        compiled = step.lower(
            with_sharding(pshape, sh["params"]),
            with_sharding(jax.eval_shape(init_opt_state, pshape), sh["opt"]),
            with_sharding(bshape, sh["batch"]),
            jax.ShapeDtypeStruct((), jnp.float32)).compile()
    kernels = tpu_kernel_counts(compiled.as_text())
    assert kernels["ssd_scan"] >= 1 and kernels["rmsnorm"] >= 1, kernels
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < chip_peaks(topo.devices[0].device_kind).hbm_bytes, ma

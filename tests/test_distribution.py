"""Distribution tests that need multiple devices: run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count (jax pins device count at
first init, so the main pytest process stays single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # multi-device subprocess runs: opt-in

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(code: str, n: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    env.pop("REPRO_KERNELS", None)
    code = "from repro.launch.mesh import make_mesh\n" + textwrap.dedent(code)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


def test_flash_decode_matches_ref():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.parallel.flash_decode import seq_sharded_decode_attention
        from repro.kernels.flash_attention.ref import mha_ref
        B, Sc, H, K, dh = 2, 64, 8, 1, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, 1, H, dh))
        k = jax.random.normal(ks[1], (B, Sc, K, dh))
        v = jax.random.normal(ks[2], (B, Sc, K, dh))
        # half-filled ring cache
        k_pos = jnp.where(jnp.arange(Sc) < 40, jnp.arange(Sc), -1)
        t = jnp.asarray(39, jnp.int32)
        got = jax.jit(lambda *a: seq_sharded_decode_attention(
            mesh, ("model",), *a, batch_axes=("data",), causal=True))(
            q, k, v, k_pos, t)
        want = mha_ref(q, k, v, causal=True,
                       q_positions=jnp.full((B, 1), 39, jnp.int32),
                       k_positions=jnp.broadcast_to(k_pos[None], (B, Sc)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("flash_decode ok")
    """)


def test_sharded_train_step_matches_single_device():
    """The 4x2 GSPMD train step computes the same loss/update as 1 device."""
    run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import init_params, param_specs
        from repro.optim import init_opt_state
        from repro.train.steps import TrainConfig, make_train_step, train_shardings
        cfg = dataclasses.replace(get_smoke_config("qwen2-7b"),
                                  d_model=128, num_heads=8, num_kv_heads=4,
                                  d_ff=256, vocab_size=256)
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = init_opt_state(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 256)}
        tc = TrainConfig(dtype=jnp.float32, remat_policy="none", z_loss=0.0)
        outs = {}
        for name, mesh in [("multi", make_mesh((4, 2), ("data", "model"))),
                           ("single", make_mesh((1, 1), ("data", "model")))]:
            bshape = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
            sh = train_shardings(cfg, mesh, jax.eval_shape(lambda: params), bshape)
            step = jax.jit(make_train_step(cfg, mesh, tc),
                           in_shardings=(sh["params"], sh["opt"], sh["batch"], None),
                           out_shardings=(sh["params"], sh["opt"], None))
            with mesh:
                p2, o2, m = step(params, opt, batch, jnp.float32(1e-3))
            outs[name] = (float(m["loss"]), jax.device_get(p2))
        assert abs(outs["multi"][0] - outs["single"][0]) < 1e-4, outs
        for a, b in zip(jax.tree.leaves(outs["multi"][1]),
                        jax.tree.leaves(outs["single"][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)
        print("sharded == single ok")
    """)


def test_pod_grad_compress_close_to_exact():
    """int8-compressed cross-pod DP stays within quantization error of the
    exact GSPMD step, and the compiled HLO carries s16 all-reduces."""
    run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import init_params
        from repro.optim import init_opt_state
        from repro.train.steps import TrainConfig, make_train_step, train_shardings
        cfg = dataclasses.replace(get_smoke_config("stablelm-3b"),
                                  d_model=128, num_heads=4, num_kv_heads=4,
                                  d_ff=256, vocab_size=256)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = init_opt_state(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 256)}
        losses = {}
        for compress in (False, True):
            tc = TrainConfig(dtype=jnp.float32, remat_policy="none",
                             z_loss=0.0, pod_grad_compress=compress)
            step = make_train_step(cfg, mesh, tc)
            bshape = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
            sh = train_shardings(cfg, mesh, jax.eval_shape(lambda: params),
                                 bshape, replicate_embed=compress)
            jitted = jax.jit(step, in_shardings=(sh["params"], sh["opt"], sh["batch"], None),
                             out_shardings=(sh["params"], sh["opt"], None))
            with mesh:
                lowered = jitted.lower(params, opt, batch, jnp.float32(1e-3))
                comp = lowered.compile()
                p2, o2, m = jitted(params, opt, batch, jnp.float32(1e-3))
            losses[compress] = (float(m["loss"]), jax.device_get(p2))
            if compress:
                assert "s16" in comp.as_text(), "no int16 wire traffic found"
        assert abs(losses[True][0] - losses[False][0]) < 1e-3
        for a, b in zip(jax.tree.leaves(losses[True][1]),
                        jax.tree.leaves(losses[False][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-2, atol=3e-3)
        print("pod compress ok")
    """)


def test_param_spec_rules_cover_all_archs():
    run_with_devices("""
        import jax
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.configs import ARCH_IDS, get_config
        from repro.models import param_specs
        from repro.parallel.sharding import param_pspecs, zero1_specs
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            pshape = param_specs(cfg)
            specs = param_pspecs(cfg, pshape, mesh)
            # every spec must divide its dims
            def check(leaf, spec):
                for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 9):
                    if part is None: continue
                    parts = part if isinstance(part, tuple) else (part,)
                    n = 1
                    for p in parts: n *= mesh.shape[p]
                    assert dim % n == 0, (arch, leaf.shape, spec)
            jax.tree.map(check, pshape, specs,
                         is_leaf=lambda x: hasattr(x, "shape"))
            zspecs = zero1_specs(specs, pshape, mesh)
            jax.tree.map(check, pshape, zspecs,
                         is_leaf=lambda x: hasattr(x, "shape"))
        print("specs ok")
    """)


def test_dryrun_cell_mini():
    """Exercise the actual dryrun run_cell machinery on a tiny mesh by
    monkeypatching the production mesh (structure identical, 16 devices)."""
    run_with_devices("""
        import jax
        import repro.launch.mesh as M
        M.make_production_mesh = lambda multi_pod=False: (
            make_mesh((2, 2, 4) if multi_pod else (4, 4),
                          ("pod", "data", "model") if multi_pod
                          else ("data", "model")))
        import repro.launch.dryrun as D
        import repro.configs as C, repro.launch.specs as S
        import dataclasses
        # shrink the shape cells so a 16-device compile is fast
        S.SHAPES = {"train_4k": S.ShapeCell("train_4k", 256, 16, "train"),
                    "decode_32k": S.ShapeCell("decode_32k", 256, 16, "decode")}
        real_get = C.get_config
        C.get_config = lambda name: C.get_smoke_config(name)
        D.get_config = C.get_config
        for mp in (False, True):
            rec = D.run_cell("qwen2-7b", "train_4k", multi_pod=mp, verbose=False)
            assert rec["status"] == "ok", rec
            rec = D.run_cell("recurrentgemma-9b", "decode_32k", multi_pod=mp, verbose=False)
            assert rec["status"] == "ok", rec
        print("mini dryrun ok")
    """, n=16)


def test_pipeline_parallel_forward_matches_sequential():
    """GPipe over the pod axis == the sequential superblock stack."""
    run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import init_params
        from repro.models.model import _make_ctx, _run_stack
        from repro.parallel.pipeline import pp_forward, pp_stage_body
        cfg = dataclasses.replace(get_smoke_config("stablelm-3b"),
                                  num_layers=4, d_model=64)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        n_micro, mb, S = 4, 2, 16
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, S, cfg.d_model))
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
        ctx = _make_ctx(cfg, pos, None, jnp.float32, jnp.zeros((), jnp.int32), None)
        body = pp_stage_body(cfg, ctx, jnp.float32)
        stacked = tuple(params["blocks"])
        with mesh:
            got = jax.jit(lambda p, xm: pp_forward(mesh, body, p, xm))(stacked, x)
        # sequential reference: run each microbatch through the full stack
        ref = []
        for i in range(n_micro):
            y, _, _ = _run_stack(params, x[i], cfg, ctx, None, dtype=jnp.float32)
            ref.append(y)
        ref = jnp.stack(ref)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        print("pipeline ok")
    """)

"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, and its
phases run end to end at a tiny size with the Pallas kernels in interpret
mode (the one-device path here, the 2x2-mesh path on four virtual CPU
devices in a subprocess).  What the chip itself gives is only known from a
run on the chip."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return dict(env, JAX_PLATFORMS="cpu", **extra)


def test_exits_nonzero_without_a_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=_cpu_env(), cwd=ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not 'tpu'" in out.stderr


def test_one_chip_path_at_tiny_size(tmp_path, monkeypatch):
    from repro.configs import get_smoke_config
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    cs = _load_chip_smoke()
    workdir = tmp_path / "work"
    cs.one_chip(get_smoke_config("mamba2-130m"), jax.devices()[0], batch=4,
                seq=64, steps=2, workdir=workdir, required=())
    assert not workdir.exists()            # rmtree'd through the engine


def test_four_chip_path_at_tiny_size(tmp_path):
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_smoke_config
        cs.four_chips(get_smoke_config("mamba2-130m"), batch=8, seq=64,
                      steps=2, workdir=cs.Path({str(tmp_path / "w")!r}),
                      required=())
        print("four-chip path ok")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_cpu_env(REPRO_KERNELS="interpret",
                     XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    assert "four-chip path ok" in out.stdout
    assert "4->1 restore" in out.stdout and "byte-identical" in out.stdout

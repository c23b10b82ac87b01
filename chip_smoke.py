#!/usr/bin/env python3
"""One pass of the training job's main path on a TPU, at the published
size of mamba2-130m (24 layers, d_model 768, vocab 50280, ssm_state 128).

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded path, against one chip

One chip: ``Trainer`` → ``TransactionalCheckpointManager`` → ``CannyFS``
over ``LocalBackend`` on a directory of the checkout (``.chip_smoke_work``,
git-ignored).  In one process it

1. checks that the device is a TPU and that the kernels dispatch to Pallas;
2. checks the Pallas forward against the jnp reference on the first batch;
3. compiles the train step (set-up time), checks that it holds the SSD and
   RMSNorm kernels as ``tpu_custom_call``s and fits the chip's memory;
4. takes a few steps, each bounded by ``block_until_ready``;
5. saves a committed checkpoint;
6. restores it into a fresh ``Trainer`` on a fresh mount, checks every leaf
   is byte-identical to the saved state, and takes one more step;
7. removes the work directory through the engine (``rmtree``, the paper's
   second model task) and checks the error ledger is empty.

Four chips: mamba2-130m on the 2 (data) x 2 (model) mesh with ZeRO-1
optimizer sharding takes the same steps as on a one-device mesh in the same
process; the per-step losses must agree within ``LOSS_TOL``.  A checkpoint
saved from the four-chip mesh is restored onto the one-chip mesh,
byte-identical.  Nothing else runs.

Weights come from ``LoopConfig.seed`` and data from ``SyntheticLM`` seeds.
Any failed check raises, so the exit code is non-zero and no result line is
printed.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import CannyFS, LocalBackend  # noqa: E402
from repro.data import Prefetcher, SyntheticLM  # noqa: E402
from repro.kernels.ops import kernel_mode, tpu_kernel_counts  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.launch.roofline import chip_peaks  # noqa: E402
from repro.train.loop import LoopConfig, Trainer  # noqa: E402
from repro.train.steps import TrainConfig, make_eval_step  # noqa: E402

ARCH = "mamba2-130m"
BATCH, SEQ = 8, 2048
STEPS = 3
WORKDIR = ROOT / ".chip_smoke_work"
REQUIRED_KERNELS = ("ssd_scan", "rmsnorm")
# bf16 compute: |loss_a - loss_b| for the same step on two layouts (4-chip
# check) and for the Pallas vs jnp forward (reference check)
LOSS_TOL = 2e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def require_tpu(n_chips: int):
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"platform is {dev.platform!r}, not 'tpu'")
    check(kernel_mode() == "pallas",
          f"REPRO_KERNELS resolves to {kernel_mode()!r}, not 'pallas'")
    check(len(jax.devices()) >= n_chips,
          f"{len(jax.devices())} devices, {n_chips} needed")
    return dev


class TimedStep:
    """The compiled step; each call is bounded by ``block_until_ready`` and
    its time and loss recorded."""

    def __init__(self, compiled):
        self.compiled = compiled
        self.times: list[float] = []
        self.losses: list[float] = []

    def __call__(self, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.compiled(*args))
        self.times.append(time.perf_counter() - t0)
        self.losses.append(float(out[2]["loss"]))
        return out


def open_trainer(cfg, mesh, workdir: Path, batch: int, seq: int,
                 total_steps: int):
    """A ``Trainer`` on a fresh mount; init_state restores the directory's
    last committed checkpoint if there is one, else initializes from the
    seed."""
    fs = CannyFS(LocalBackend(str(workdir)), max_inflight=4000, workers=32)
    data = Prefetcher(iter(SyntheticLM(cfg, batch=batch, seq_len=seq,
                                       seed=0)), depth=2)
    tr = Trainer(cfg, mesh, fs, data, tc=TrainConfig(),
                 lc=LoopConfig(total_steps=total_steps,
                               ckpt_every=total_steps, log_every=1,
                               warmup=1))
    t0 = time.perf_counter()
    tr.init_state(next(tr.data))
    return tr, time.perf_counter() - t0


def compile_step(tr: Trainer, batch: dict, required=REQUIRED_KERNELS):
    """AOT-compile the trainer's step for ``batch`` and run the loop on the
    compiled program.  Returns (compile seconds, bytes the program needs on
    a device)."""
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    t0 = time.perf_counter()
    with jax.set_mesh(tr.mesh):
        compiled = tr.step_fn.lower(tr.state["params"], tr.state["opt"],
                                    batch, lr).compile()
    compile_s = time.perf_counter() - t0
    kernels = tpu_kernel_counts(compiled.as_text())
    log(f"tpu_custom_calls in the step: {dict(sorted(kernels.items()))}")
    for name in required:
        check(kernels[name] > 0, f"kernel {name} missing from the step")
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    log(f"step memory_analysis: arguments {ma.argument_size_in_bytes} B, "
        f"temp {ma.temp_size_in_bytes} B, needs {need} B per device")
    tr.step_fn = TimedStep(compiled)
    return compile_s, need


def reference_loss_check(tr: Trainer, batch: dict) -> None:
    """Forward loss through the kernels vs the jnp reference, same params,
    same batch."""
    losses = {}
    kernels = kernel_mode()
    for mode in (kernels, "jnp"):
        with _kernels(mode), jax.set_mesh(tr.mesh):
            # a fresh step function per mode: jit caches its trace by the
            # function, and the dispatch mode is not part of that key
            fn = jax.jit(make_eval_step(tr.cfg, tr.mesh, tr.tc))
            t0 = time.perf_counter()
            losses[mode] = float(fn(tr.state["params"], batch)["loss"])
        log(f"forward loss ({mode}): {losses[mode]!r} "
            f"({time.perf_counter() - t0:.3f} s incl. compile)")
    diff = abs(losses[kernels] - losses["jnp"])
    check(math.isfinite(losses[kernels]) and diff <= LOSS_TOL,
          f"{kernels} forward loss differs from the reference by {diff}")


@contextlib.contextmanager
def _kernels(mode: str):
    """Trace under REPRO_KERNELS=mode (the dispatch reads it at trace)."""
    old = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = mode
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_KERNELS"]
        else:
            os.environ["REPRO_KERNELS"] = old


def check_identical(saved: dict, restored: dict) -> int:
    """Every leaf byte-identical; returns the bytes compared."""
    a_leaves = jax.tree_util.tree_leaves_with_path(saved)
    b_leaves = jax.tree.leaves(restored)
    check(len(a_leaves) == len(b_leaves), "restored tree has other leaves")
    total = 0
    for (path, a), b in zip(a_leaves, b_leaves):
        a, b = np.asarray(a), np.asarray(b)
        where = jax.tree_util.keystr(path)
        check(a.dtype == b.dtype and a.shape == b.shape,
              f"{where}: {b.dtype}{b.shape} restored, {a.dtype}{a.shape} saved")
        check(a.tobytes() == b.tobytes(), f"{where}: bytes differ")
        total += a.nbytes
    return total


def close_trainer(tr: Trainer) -> None:
    tr.metrics.close()
    tr.fs.close()
    tr.state = {}


def remove_workdir(workdir: Path) -> float:
    """rmtree every top-level entry through a fresh mount; the ledger must
    stay empty and the directory end empty."""
    fs = CannyFS(LocalBackend(str(workdir)), max_inflight=4000, workers=32)
    t0 = time.perf_counter()
    for name in fs.readdir(""):
        fs.rmtree(name)
    fs.drain()
    rm_s = time.perf_counter() - t0
    check(len(fs.ledger) == 0, f"rmtree left {len(fs.ledger)} ledger errors")
    check(fs.readdir("") == [], "work directory not empty after rmtree")
    fs.close()
    workdir.rmdir()
    return rm_s


def fresh_workdir(path: Path) -> Path:
    if path.exists():
        shutil.rmtree(path)   # a previous run's leftovers, ours alone
    path.mkdir(parents=True)
    return path


def memory_stats(dev) -> dict:
    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit",
            "largest_alloc_size")
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in keys if k in stats}


def one_chip(cfg, dev, *, batch=BATCH, seq=SEQ, steps=STEPS,
             workdir=WORKDIR, required=REQUIRED_KERNELS, hbm_bytes=None):
    mesh = make_debug_mesh(1)
    while True:
        workdir = fresh_workdir(workdir)
        tr, init_s = open_trainer(cfg, mesh, workdir, batch, seq, steps + 1)
        n_params = sum(x.size for x in jax.tree.leaves(tr.state["params"]))
        log(f"params: {n_params} ({cfg.name}, batch {batch} x seq {seq}); "
            f"init_state (first batch, param init compile + run) "
            f"{init_s:.3f} s")
        first = tr.put_batch(next(tr.data))
        compile_s, need = compile_step(tr, first, required)
        if hbm_bytes is None or need < hbm_bytes:
            break
        check(batch > 1, f"batch 1 needs {need} B > {hbm_bytes} B")
        log(f"batch {batch} needs {need} B > {hbm_bytes} B of HBM: "
            f"halving it to {batch // 2}")
        close_trainer(tr)
        batch //= 2
    log(f"compile (set-up): {compile_s:.3f} s")
    reference_loss_check(tr, first)

    t0 = time.perf_counter()
    tr.run(max_steps=steps)               # steps, then a committed save
    run_s = time.perf_counter() - t0
    timed = tr.step_fn
    log(f"step times (s): {timed.times}")
    log(f"losses: {timed.losses}")
    check(len(timed.losses) == steps
          and all(math.isfinite(x) for x in timed.losses), "bad losses")
    check(abs(timed.losses[0] - math.log(cfg.vocab_size)) < 2.0,
          f"first loss {timed.losses[0]} far from ln(vocab)")
    tr.fs.drain()
    logged = [json.loads(line) for line in
              tr.fs.read_file("logs/metrics.jsonl").decode().splitlines()]
    check([r["loss"] for r in logged if "loss" in r] == timed.losses,
          "metrics log through the engine disagrees with the step outputs")
    save = tr.ckpt.results[-1]
    check(save.ok and save.step == steps, f"save failed: {save}")
    log(f"save @ step {steps}: ACK {save.ack_s:.6f} s, commit "
        f"{save.commit_s:.6f} s, {save.bytes} bytes; steps+save wall "
        f"{run_s:.3f} s")
    log(f"memory_stats after steps: {memory_stats(dev)}")
    saved = jax.device_get(tr.state)
    close_trainer(tr)

    tr2, restore_s = open_trainer(cfg, mesh, workdir, batch, seq, steps + 1)
    check(tr2.step == steps, f"restored step {tr2.step}, saved {steps}")
    n = check_identical(saved, jax.device_get(tr2.state))
    log(f"restore into a fresh Trainer: {restore_s:.3f} s, {n} bytes "
        "byte-identical")
    del saved
    compile2_s, _ = compile_step(tr2, tr2.put_batch(next(tr2.data)),
                                 required)
    tr2.run()
    log(f"after restore: compile {compile2_s:.3f} s, step time (s) "
        f"{tr2.step_fn.times}, loss {tr2.step_fn.losses}")
    check(tr2.step == steps + 1 and math.isfinite(tr2.step_fn.losses[-1]),
          "no step after restore")
    check(tr2.ckpt.results[-1].ok, f"save failed: {tr2.ckpt.results[-1]}")
    close_trainer(tr2)

    rm_s = remove_workdir(workdir)
    log(f"rmtree through the engine: {rm_s:.3f} s, ledger empty")
    log(f"memory_stats at the end: {memory_stats(dev)}")


def four_chips(cfg, *, batch=BATCH, seq=SEQ, steps=STEPS, workdir=WORKDIR,
               required=REQUIRED_KERNELS, hbm_bytes=None):
    losses = {}
    saved = None
    for n in (1, 4):
        wd = fresh_workdir(workdir / f"mesh{n}")
        mesh = make_debug_mesh(n)
        tr, init_s = open_trainer(cfg, mesh, wd, batch, seq, steps)
        compile_s, need = compile_step(tr, tr.put_batch(next(tr.data)),
                                       required)
        check(hbm_bytes is None or need < hbm_bytes,
              f"step on {n} chips needs {need} B > {hbm_bytes} B")
        tr.run()                           # steps, then a committed save
        losses[n] = tr.step_fn.losses
        log(f"mesh {dict(mesh.shape)}: init_state {init_s:.3f} s, compile "
            f"{compile_s:.3f} s, step times (s) {tr.step_fn.times}, "
            f"losses {losses[n]}; device 0 {memory_stats(jax.devices()[0])}")
        check(tr.ckpt.results[-1].ok, f"save failed: {tr.ckpt.results[-1]}")
        if n == 4:
            saved = jax.device_get(tr.state)
        close_trainer(tr)
    diffs = [abs(a - b) for a, b in zip(losses[1], losses[4])]
    log(f"|loss(2x2) - loss(1)| per step: {diffs} (tolerance {LOSS_TOL})")
    check(len(diffs) == steps and all(math.isfinite(x) for x in losses[4])
          and max(diffs) <= LOSS_TOL, "4-chip losses disagree with 1 chip")

    # elastic restore: the 2x2 mesh's checkpoint onto one device
    tr, restore_s = open_trainer(cfg, make_debug_mesh(1), workdir / "mesh4",
                                 batch, seq, steps)
    check(tr.step == steps, f"restored step {tr.step}, saved {steps}")
    nbytes = check_identical(saved, jax.device_get(tr.state))
    log(f"4->1 restore: {restore_s:.3f} s, {nbytes} bytes byte-identical")
    close_trainer(tr)
    for n in (1, 4):
        remove_workdir(workdir / f"mesh{n}")
    workdir.rmdir()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    dev = require_tpu(args.chips)
    use_compile_cache()
    log(f"device_kind: {dev.device_kind}; devices: {len(jax.devices())}")
    hbm = chip_peaks(dev.device_kind).hbm_bytes
    cfg = get_config(ARCH)
    if args.chips == 4:
        four_chips(cfg, hbm_bytes=hbm)
    else:
        one_chip(cfg, dev, hbm_bytes=hbm)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

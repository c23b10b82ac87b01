#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload mamba2-130m.local.ckpt \
        --seed 12345 --seconds 30 --trace 0

The cell is found by name in ``BENCHMARK.json`` (configuration file,
traffic mix, metrics).  Weights and inputs come from ``--seed``.  Set-up
(process start to the window, compilation included) is ``setup_s``; the
window runs ``--seconds`` or a little more, to the end of a save cycle;
then the run is checked against the plain float32 reference and the
storage oracles, and its last line on standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics, or with ``--trace 1`` the per-layer ones read from a profiler
trace of the window), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``: each number compared, with its limit.  The same
numbers are the last lines on standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(CHECKOUT / "src"))
# the TPU runtime's logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

import archive  # noqa: E402
import check  # noqa: E402
import flops  # noqa: E402
import job as jobmod  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402

WORK = CHECKOUT / ".bench_work"


class NoChip(SystemExit):
    def __init__(self, msg: str):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Run:
    """What a metric reader reads: the window's host timings, the engine's
    counters, the saves and extraction cycles in the window, and the
    reduced trace (None in an untraced run)."""

    def __init__(self, job, trace, peaks_, flops_):
        self.trace, self.peaks, self.flops = trace, peaks_, flops_
        self.config, self.traffic = job.config, job.traffic
        w = self.window = job.win
        self.window_s = w.t1 - w.t0
        self.steps = list(range(w.first_step, w.last_step + 1))
        self.tokens_per_step = job.traffic["batch"] * job.traffic["seq"]
        self.setup_s = job.setup_s
        self.saves = [r for s, r in job.saves
                      if w.first_step <= s <= w.last_step]
        self.save_steps = {r.step for r in self.saves}
        self.cycles = [c for c in job.cycles if c[0] <= w.t1]
        self.call_ms = job.timer.ms
        self.stats = {k: w.stats1[k] - w.stats0[k] for k in w.stats0
                      if isinstance(w.stats0[k], (int, float))}

    def gap_after(self, k: int) -> float:
        """Host seconds from step k's return to step k+1's call."""
        nxt = self.window.calls.get(k + 1, self.window.t1)
        return nxt - self.window.returns[k]


def require_chips(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"{len(devs)} chips, the cell asks for {n}")
    from repro.kernels.ops import kernel_mode
    if kernel_mode() != "pallas":
        raise NoChip(f"REPRO_KERNELS resolves to {kernel_mode()!r}")
    return devs[0]


def storage_checks(job) -> dict:
    """Exact: retained checkpoints read back through a fresh mount, the
    last tree against the oracle and nothing left of the others, and an
    empty error ledger.  Leaves the mount drained."""
    fs, traffic, checks = job.fs, job.traffic, {}
    fs.drain()
    if traffic["save_every"]:
        from repro.core import CannyFS
        bottom = archive.bottom(fs.backend)
        checks["ckpt_bad_leaves"] = (check.checkpoint_mismatches(
            lambda: CannyFS(bottom, max_inflight=4000, workers=8), "ckpt",
            list(job.saved_states), traffic["keep"],
            [s for s, r in job.saves if r.ok]), 0)
    if traffic.get("extract"):
        root = job.checked_root
        bad = job.archive.entries
        if root:
            bad = archive.compare_tree(job.archive, fs.backend, root)
            fs.rmtree(root)
            fs.drain()
        dirs, files = archive.stored_tree(fs.backend, "scratch")
        checks["tree_bad_entries"] = (bad, 0)
        checks["tree_left_entries"] = (len(dirs - {"scratch"}) + len(files),
                                       0)
    checks["ledger_errors"] = (len(fs.ledger), 0)
    return checks


def training_checks(job, seed: int) -> dict:
    """The program's first steps against the reference; frees the
    program's state before the reference runs."""
    steps = range(1, jobmod.SETUP_STEPS + 1)
    prog = {"losses": [job.losses[k] for k in steps],
            "first_grad": check.leaf_norms(job.first_grad),
            "change": check.leaf_norms(job.change)}
    batches = [job.data.batch(job.batch_of_step[k]) for k in steps]
    job.trainer.state = {}
    job.first_grad = job.change = None
    job.saved_states.clear()
    gc.collect()
    t = time.perf_counter()
    c = job.config
    out = job.ref.train_steps(seed, c, c["optimizer"], batches)
    ref = {"losses": out["losses"],
           "first_grad": check.leaf_norms(out["first_grad"]),
           "change": check.leaf_norms(out["change"])}
    log(f"reference {time.perf_counter() - t:.3f} s; losses program "
        f"{prog['losses']} reference {ref['losses']}; loss_gap "
        f"{check.loss_gap(prog, ref)!r} (not compared)")
    return check.training_checks(prog, ref, c["limits"])


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             on_chip: bool = True, work: Path = WORK) -> dict:
    """One run of ``cell``.  ``on_chip=False`` (the harness's own tests)
    skips the look for a chip and the persistent compilation cache."""
    dev = require_chips(cell.chips) if on_chip else jax.devices()[0]
    if on_chip:
        from repro.launch.cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    work.mkdir(parents=True, exist_ok=True)
    trace_dir = str(work / "trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    job = jobmod.Job(cell, seed, str(work / "mount"), trace_dir=trace_dir,
                     t_process=T_PROCESS)
    job.setup()
    log(f"set-up {time.perf_counter() - T_PROCESS:.3f} s; window opens")
    job.run(seconds)
    w = job.win
    log(f"window {w.t1 - w.t0:.3f} s, steps {w.first_step}..{w.last_step}, "
        f"saves {len(job.saves)}, cycles {len(job.cycles)}, "
        f"fs calls timed {len(job.timer.ms)}")
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    checks = storage_checks(job)
    for e in job.errors:
        log(f"error: {e}")
    job.fs.close()
    checks.update(training_checks(job, seed))

    reduced = None
    if trace_dir:
        t = time.perf_counter()
        reduced = xplane.reduce(xplane.load(xplane.find_xplane(trace_dir)))
        log(f"trace read in {time.perf_counter() - t:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(job, reduced,
              peaks.peaks(dev.device_kind) if dev.platform == "tpu" else None,
              flops)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = spec.reader(m["name"])(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"{m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"fs calls timed in the window: {len(run.call_ms)}; cycles "
        f"completed: {len(run.cycles)}; saves: {len(run.saves)}")

    failed = sum(1 for _, r in job.saves if not r.ok) + len(job.errors)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": failed == 0 and check.within(checks),
              "attempted": len(run.steps) + len(run.saves) + len(run.cycles),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    os._exit(0)   # the engine's worker threads are daemons; leave now


if __name__ == "__main__":
    main()

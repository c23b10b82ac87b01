#!/usr/bin/env python3
"""Readings that the limits of the training comparison are set from.

    python3 benchmarks/chip/calibrate.py --config mamba2-130m.local \
        --seeds 101 102 ... --control-seeds 101 102 103 --out FILE

For each seed, in one process on the chip: the program's first three
steps through the harness's own set-up (``job.Job``, the window's call
and feed), then the float32 reference, and the numbers that ``check``
compares.  For each control seed also, in the program's place:

* ``fp8``: the reference computed with float8_e4m3fn compute inputs, the
  precision below the configuration's bfloat16 (the control);
* ``half_batch``: the reference on the first half of each batch, its mean
  taken over those rows (a fault a step can have).

A step that returns its state unchanged reads 1 on ``grad_gap`` and
``change_gap`` by their definition and needs no run.  One JSON line per
seed and kind goes to ``--out``, with ``correct`` as the harness's
comparison decides it: true for the program, false for the control and
the fault.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import check  # noqa: E402
import spec  # noqa: E402


def numbers(prog: dict, ref: dict, limits: dict) -> dict:
    """The compared numbers and the harness's verdict on them (``correct``,
    by ``check.within`` against the configuration's limits), the leaves
    that set them, and every leaf's norms (for reading other statistics
    from the same runs)."""
    checks = check.training_checks(prog, ref, limits)
    out = {k: v for k, (v, _) in checks.items()}
    out["correct"] = check.within(checks)
    out["loss_gap"] = check.loss_gap(prog, ref)
    g = check.leaf_gaps(check.by_leaf(prog["first_grad"]),
                        check.by_leaf(ref["first_grad"]))
    c = check.leaf_gaps(check.by_leaf(prog["change"]),
                        check.by_leaf(ref["change"]))
    out["worst_grad_leaf"] = max(g, key=g.get)
    out["worst_change_leaf"] = max(c, key=c.get)
    out["norms"] = {k: {"grad": [prog["first_grad"][k], ref["first_grad"][k]],
                        "change": [prog["change"][k], ref["change"][k]]}
                    for k in ref["first_grad"]}
    return out


def as_norms(out: dict) -> dict:
    return {"losses": out["losses"],
            "first_grad": check.leaf_norms(out["first_grad"]),
            "change": check.leaf_norms(out["change"])}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import run as runmod
    runmod.require_chips(1)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import job as jobmod

    bench = spec.load_benchmark()
    tree_cell = next(w["name"] for w in bench["workloads"]
                     if w["config"] == args.config)
    out = open(args.out, "a")
    for seed in args.seeds:
        cell = spec.load_cell(tree_cell, bench)
        cell.traffic.update(save_every=0, extract=None)
        t = time.perf_counter()
        job = jobmod.Job(cell, seed, str(runmod.WORK / "mount"))
        job.setup()
        job.run(0.0)
        prog = {"losses": [job.losses[k] for k in (1, 2, 3)],
                "first_grad": check.leaf_norms(job.first_grad),
                "change": check.leaf_norms(job.change)}
        batches = [job.data.batch(job.batch_of_step[k]) for k in (1, 2, 3)]
        job.fs.close()
        job.trainer.state = {}
        del job
        gc.collect()
        setup_s = time.perf_counter() - t
        c, opt = cell.config, cell.config["optimizer"]
        t = time.perf_counter()
        ref = as_norms(jobmod.arch(c)[0].train_steps(seed, c, opt, batches))
        rec = {"seed": seed, "kind": "program", "setup_s": setup_s,
               "reference_s": time.perf_counter() - t,
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        rec.update(numbers(prog, ref, c["limits"]))
        print(json.dumps(rec), file=out, flush=True)
        print(json.dumps(rec), flush=True)
        if seed not in args.control_seeds:
            continue
        refmod = jobmod.arch(c)[0]
        for kind, kw in (("fp8", {"prec": "fp8"}),
                         ("half_batch", {"rows": cell.traffic["batch"] // 2})):
            other = as_norms(refmod.train_steps(seed, c, opt, batches, **kw))
            rec = {"seed": seed, "kind": kind, "losses": other["losses"]}
            rec.update(numbers(other, ref, c["limits"]))
            print(json.dumps(rec), file=out, flush=True)
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()

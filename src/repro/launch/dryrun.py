import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (architecture × input-shape)
cell on the production meshes, prove the distribution config is coherent,
and extract roofline terms from the compiled artifacts.

Usage:
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    python -m repro.launch.dryrun --all --out benchmarks/results/dryrun

Per cell this runs
    jax.jit(step, in_shardings=..., out_shardings=...)
       .lower(**input_specs).compile()
prints memory_analysis() (fits-on-device proof) and cost_analysis()
(FLOPs/bytes for the roofline), parses collective bytes from the compiled
HLO, and writes a JSON record consumed by EXPERIMENTS.md §Roofline.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import RooflineTerms, collective_bytes
from repro.launch.specs import (SHAPES, batch_specs, cache_specs,
                                cell_supported, decode_token_specs)
from repro.models import param_specs
from repro.optim import init_opt_state
from repro.parallel.sharding import batch_pspecs, make_shardings
from repro.train.steps import (TrainConfig, make_decode_step,
                               make_encode_step, make_prefill_step,
                               make_train_step, serve_shardings,
                               train_shardings)

# the chip the production meshes model (the dry-run itself lowers on host
# CPU devices, so the roofline is priced at this kind's peaks)
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mem_stats(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes",
                  "peak_memory_in_bytes", "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception as e:  # pragma: no cover - backend-specific
        out["error"] = repr(e)
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             train_cfg: TrainConfig | None = None,
             scan_layers: bool = False,
             cfg_overrides: dict | None = None,
             verbose: bool = True) -> dict:
    """One (arch × shape × mesh) cell.

    scan_layers=False (default) lowers with the layer loop unrolled so
    cost_analysis counts every layer (XLA counts a while body once);
    the scanned variant is the production path and compiles too.
    cfg_overrides: dataclasses.replace overrides on the ModelConfig
    (hillclimb knobs such as ssm_chunk)."""
    import dataclasses
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    cell = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ok, reason = cell_supported(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "skipped", "reason": reason}
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    tc = train_cfg or TrainConfig()
    tc = TrainConfig(**{**tc.__dict__, "scan_layers": scan_layers})
    t0 = time.monotonic()

    pshape = param_specs(cfg)
    n_params = sum(int(jnp.prod(jnp.array(l.shape)))
                   for l in jax.tree.leaves(pshape))

    if cell.step == "train":
        bspec = batch_specs(cfg, cell.seq_len, cell.global_batch,
                            training=True)
        sh = train_shardings(cfg, mesh, pshape, bspec, zero1=tc.zero1)
        opt_shape = jax.eval_shape(init_opt_state, pshape)
        step = make_train_step(cfg, mesh, tc)
        jitted = jax.jit(
            step,
            in_shardings=(sh["params"], sh["opt"], sh["batch"], None),
            out_shardings=(sh["params"], sh["opt"], None),
            donate_argnums=(0, 1))
        with mesh:
            lowered = jitted.lower(pshape, opt_shape, bspec,
                                   jax.ShapeDtypeStruct((), jnp.float32))
            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
        tokens = cell.global_batch * cell.seq_len
        model_flops = cfg.model_flops(tokens, training=True,
                                      seq_len=cell.seq_len)
    elif cell.step == "prefill" and not cfg.supports_decode():
        # encoder-only: prefill_32k is a pure encode forward (no cache)
        bspec = batch_specs(cfg, cell.seq_len, cell.global_batch,
                            training=False)
        sh = train_shardings(cfg, mesh, pshape, bspec, zero1=False)
        step = make_encode_step(cfg, mesh, scan_layers=scan_layers)
        jitted = jax.jit(step, in_shardings=(sh["params"], sh["batch"]),
                         out_shardings=None)
        with mesh:
            lowered = jitted.lower(pshape, bspec)
            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
        tokens = cell.global_batch * cell.seq_len
        model_flops = cfg.model_flops(tokens, training=False,
                                      seq_len=cell.seq_len)
    else:
        bspec = batch_specs(cfg, cell.seq_len, cell.global_batch,
                            training=False)
        cshape = cache_specs(cfg, cell.global_batch, cell.seq_len)
        sh = serve_shardings(cfg, mesh, pshape, cshape, cell.global_batch,
                             cell.seq_len)
        bsh = make_shardings(mesh, batch_pspecs(cfg, bspec, mesh))
        if cell.step == "prefill":
            step = make_prefill_step(cfg, mesh, batch=cell.global_batch,
                                     max_len=cell.seq_len,
                                     scan_layers=scan_layers)
            jitted = jax.jit(step,
                             in_shardings=(sh["params"], bsh, sh["cache"]),
                             out_shardings=(None, sh["cache"]),
                             donate_argnums=(2,))
            with mesh:
                lowered = jitted.lower(pshape, bspec, cshape)
                t_lower = time.monotonic() - t0
                compiled = lowered.compile()
            tokens = cell.global_batch * cell.seq_len
            model_flops = cfg.model_flops(tokens, training=False,
                                          seq_len=cell.seq_len)
        else:
            step = make_decode_step(cfg, mesh, batch=cell.global_batch,
                                    max_len=cell.seq_len,
                                    scan_layers=scan_layers)
            tok = decode_token_specs(cell.global_batch)
            jitted = jax.jit(step,
                             in_shardings=(sh["params"], None, sh["cache"]),
                             out_shardings=(None, None, sh["cache"]),
                             donate_argnums=(2,))
            with mesh:
                lowered = jitted.lower(pshape, tok, cshape)
                t_lower = time.monotonic() - t0
                compiled = lowered.compile()
            tokens = cell.global_batch
            model_flops = cfg.model_flops(tokens, training=False,
                                          kv_len=cell.seq_len)

    t_compile = time.monotonic() - t0 - t_lower
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    flops_raw = flops
    # The query-blocked attention path (self-attn, S >= 2048) runs nq chunks
    # inside one lax.map whose body XLA counts once — add the analytic
    # remainder (methodology: EXPERIMENTS.md §Roofline).  This mirrors the
    # TPU target, where the Pallas flash kernel's FLOPs are likewise
    # invisible to cost_analysis and accounted analytically.
    attn_corr = 0.0
    if cell.step != "decode" and cell.seq_len >= 2048:
        nq = cell.seq_len // 1024
        attn_flops = cfg.flops_parts(
            cell.global_batch * cell.seq_len,
            training=(cell.step == "train"), seq_len=cell.seq_len)["attn"]
        attn_corr = attn_flops * (nq - 1) / nq / chips
        flops += attn_corr
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    coll_total = sum(v for k, v in coll.items() if not k.startswith("n_"))
    mem = _mem_stats(compiled)

    terms = RooflineTerms(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        device_kind=TARGET_DEVICE_KIND,
        flops_per_device=flops, bytes_per_device=bytes_acc,
        coll_bytes_per_device=coll_total, coll_breakdown=coll,
        model_flops=model_flops,
        peak_memory_per_device=float(mem.get("temp_size_in_bytes", 0)
                                     + mem.get("argument_size_in_bytes", 0)))
    rec.update(terms.to_dict())
    rec.update({
        "status": "ok", "n_params": int(n_params),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "hlo_size": len(hlo),
        "scan_layers": scan_layers,
        "flops_per_device_raw": flops_raw,
        "attn_flops_correction_per_device": attn_corr,
    })
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: "
              f"compile ok in {t_lower + t_compile:.1f}s; "
              f"bottleneck={terms.bottleneck} "
              f"compute={terms.compute_s * 1e3:.2f}ms "
              f"memory={terms.memory_s * 1e3:.2f}ms "
              f"collective={terms.collective_s * 1e3:.2f}ms "
              f"useful_flops={terms.useful_flops_ratio:.2f}")
        print(f"[dryrun]   memory_analysis: {mem}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="dots_no_batch")
    ap.add_argument("--act-mode", default="dp")
    ap.add_argument("--scan-layers", action="store_true",
                    help="lower the production scan-over-layers variant "
                         "(compact HLO) instead of the unrolled analysis "
                         "variant")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)
    if not (args.all or args.arch):
        ap.error("pass --all or --arch")

    tc = TrainConfig(remat_policy=args.remat, activation_mode=args.act_mode)
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                path = outdir / f"{arch}__{shape}__{mesh_name}.json"
                if path.exists() and not args.force:
                    print(f"[dryrun] cached: {path}")
                    continue
                try:
                    rec = run_cell(arch, shape, multi_pod=mp, train_cfg=tc,
                                   scan_layers=args.scan_layers)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "failed", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures.append((arch, shape, mesh_name, repr(e)))
                    print(f"[dryrun] FAILED {arch} × {shape} × {mesh_name}: "
                          f"{e!r}")
                path.write_text(json.dumps(rec, indent=2))
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("  ", *f)
        raise SystemExit(1)
    print("\n[dryrun] all requested cells compiled OK")


if __name__ == "__main__":
    main()

"""End-to-end training driver: a ~100M-param LM on synthetic data with
transactional checkpointing, metrics streaming and crash recovery.

    PYTHONPATH=src python examples/train_lm.py --preset small --steps 120
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The '100m' preset is the deliverable configuration (intended pace on
accelerators; it runs — slowly — on this CPU container).  'small' (~10M)
demonstrates the identical pipeline in a few minutes on CPU.
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

import jax.numpy as jnp

from repro.core import CannyFS, LatencyBackend, LatencyModel, LocalBackend
from repro.data import Prefetcher, SyntheticLM
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.config import ModelConfig
from repro.train.loop import LoopConfig, Trainer, run_with_restarts
from repro.train.steps import TrainConfig

PRESETS = {
    "small": dict(num_layers=6, d_model=256, num_heads=8, num_kv_heads=4,
                  d_ff=1024, vocab_size=4096, batch=8, seq=128),
    "100m": dict(num_layers=12, d_model=512, num_heads=8, num_kv_heads=4,
                 d_ff=2048, vocab_size=32768, batch=16, seq=256),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="small")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--ckpt-every", type=int, default=40)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--io-latency-ms", type=float, default=1.0,
                    help="simulated remote-storage latency (0 = local)")
    args = ap.parse_args()
    use_compile_cache()

    p = PRESETS[args.preset]
    cfg = ModelConfig(name=f"lm-{args.preset}", family="dense",
                      num_layers=p["num_layers"], d_model=p["d_model"],
                      num_heads=p["num_heads"], num_kv_heads=p["num_kv_heads"],
                      d_ff=p["d_ff"], vocab_size=p["vocab_size"],
                      block_pattern=("attn",))
    print(f"model: {cfg.name}  params≈{cfg.param_count() / 1e6:.1f}M")

    workdir = args.workdir or tempfile.mkdtemp(prefix="repro_train_")
    backend = LocalBackend(workdir)
    if args.io_latency_ms:
        backend = LatencyBackend(backend, LatencyModel(
            meta_ms=args.io_latency_ms, data_ms=args.io_latency_ms,
            jitter_sigma=0.2))
    fs = CannyFS(backend, max_inflight=4000, workers=32)
    print(f"workdir: {workdir} (transactional I/O via CannyFS engine)")

    def factory():
        data = Prefetcher(iter(SyntheticLM(cfg, batch=p["batch"],
                                           seq_len=p["seq"], seed=0)),
                          depth=2)
        return Trainer(
            cfg, make_debug_mesh(1), fs, data,
            tc=TrainConfig(dtype=jnp.float32, remat_policy="none",
                           peak_lr=3e-3, z_loss=1e-4),
            lc=LoopConfig(total_steps=args.steps,
                          ckpt_every=args.ckpt_every, log_every=10,
                          warmup=20))

    metrics = run_with_restarts(factory, max_restarts=1)
    print("final metrics:", {k: round(v, 4) for k, v in metrics.items()})
    fs.drain()
    print("metrics log:")
    for line in fs.read_file("logs/metrics.jsonl").decode().splitlines()[-5:]:
        print("  ", line)
    fs.close()


if __name__ == "__main__":
    main()

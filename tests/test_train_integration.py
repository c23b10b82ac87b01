"""End-to-end integration: tiny model trains (loss falls), checkpoints
through the transactional engine, survives a mid-run crash, and serves."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import CannyFS, InMemoryBackend, ProcessKilled
from repro.data import Prefetcher, SyntheticLM
from repro.launch.mesh import make_debug_mesh
from repro.train.loop import LoopConfig, Trainer, run_with_restarts
from repro.train.steps import TrainConfig

pytestmark = pytest.mark.slow  # jax train integration: opt-in (see pytest.ini)


def make_trainer(fs, cfg, total=30, ckpt_every=10):
    mesh = make_debug_mesh(1)
    data = Prefetcher(iter(SyntheticLM(cfg, batch=8, seq_len=32, seed=1)),
                      depth=2)
    return Trainer(cfg, mesh, fs, data,
                   tc=TrainConfig(dtype=jnp.float32, remat_policy="none",
                                  peak_lr=1e-2, z_loss=0.0),
                   lc=LoopConfig(total_steps=total, ckpt_every=ckpt_every,
                                 log_every=5, warmup=5))


def test_loss_decreases_and_checkpoints():
    cfg = get_smoke_config("stablelm-3b")
    fs = CannyFS(InMemoryBackend(), max_inflight=1000, workers=8)
    tr = make_trainer(fs, cfg, total=30)
    tr.init_state(next(tr.data))
    metrics = tr.run()
    assert np.isfinite(metrics["loss"])
    assert tr.ckpt.list_steps(), "no committed checkpoints"
    # metrics stream was written; loss fell monotonically-ish
    fs.drain()
    import json
    log = [json.loads(l) for l in
           fs.read_file("logs/metrics.jsonl").decode().strip().splitlines()]
    losses = [r["loss"] for r in log if "loss" in r]
    assert len(losses) >= 3
    assert losses[-1] < losses[0] - 0.05, losses
    assert losses[-1] < np.log(cfg.vocab_size), losses
    fs.close()


def test_resume_from_committed_checkpoint():
    cfg = get_smoke_config("stablelm-3b")
    fs = CannyFS(InMemoryBackend(), max_inflight=1000, workers=8)
    tr = make_trainer(fs, cfg, total=20, ckpt_every=10)
    tr.init_state(next(tr.data))
    tr.run(max_steps=10)
    assert tr.ckpt.list_steps() == [10]
    # new trainer on the same fs resumes at step 10
    tr2 = make_trainer(fs, cfg, total=20, ckpt_every=10)
    tr2.init_state(next(tr2.data))
    assert tr2.step == 10
    tr2.run()
    assert tr2.step == 20
    fs.close()


def test_run_with_restarts_recovers_from_crash():
    cfg = get_smoke_config("stablelm-3b")
    fs = CannyFS(InMemoryBackend(), max_inflight=1000, workers=8)
    crashed = {"done": False}

    class CrashingTrainer(Trainer):
        def run(self, max_steps=None):
            if not crashed["done"] and self.step >= 0:
                # train a bit, checkpoint, then die mid-job
                super().run(max_steps=10)
                crashed["done"] = True
                raise ProcessKilled("simulated node failure")
            return super().run(max_steps=max_steps)

    def factory():
        tr = make_trainer(fs, cfg, total=20, ckpt_every=5)
        tr.__class__ = CrashingTrainer
        return tr

    metrics = run_with_restarts(factory, max_restarts=2)
    assert np.isfinite(metrics["loss"])
    fs.close()


def test_serve_prefill_decode_small():
    from repro.models import init_cache, init_params
    from repro.train.steps import make_decode_step, make_prefill_step
    cfg = get_smoke_config("qwen2-7b")
    mesh = make_debug_mesh(1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 2, 64, jnp.float32)
    pre = make_prefill_step(cfg, mesh, batch=2, max_len=64,
                            dtype=jnp.float32)
    dec = make_decode_step(cfg, mesh, batch=2, max_len=64, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    with mesh:
        last, cache = jax.jit(pre)(params, {"tokens": toks}, cache)
        out = []
        tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
        for _ in range(4):
            tok, logits, cache = jax.jit(dec)(params, tok, cache)
            out.append(tok)
    assert all(o.shape == (2, 1) for o in out)
    assert int(cache["t"]) == 20

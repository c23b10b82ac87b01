"""The training loop: steps + transactional checkpointing + metrics +
fault-tolerant restart.

Fault model (the paper's, applied to training):

* all host I/O (checkpoints, metrics, staged data) goes through CannyFS —
  eagerly ACKed, so the accelerator never stalls on storage latency;
* a checkpoint is a transaction: COMMIT marker last, rollback of partial
  output, restart from the last committed step;
* ``run_with_restarts`` is the job harness: on an I/O or transaction
  failure it rolls the engine back, restores the last committed checkpoint
  (possibly onto a different mesh — elasticity) and continues.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import TransactionalCheckpointManager
from repro.core import CannyFS, ProcessKilled, TransactionFailedError
from repro.models import ModelConfig, init_params
from repro.optim import init_opt_state
from repro.trace import span
from repro.train.metrics import MetricsWriter
from repro.train.steps import TrainConfig, make_train_step, train_shardings
from repro.optim.schedule import cosine_with_warmup


@dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    warmup: int = 10
    seed: int = 0
    keep_ckpts: int = 3


class Trainer:
    def __init__(self, cfg: ModelConfig, mesh, fs: CannyFS,
                 data: Iterator[dict], tc: TrainConfig = TrainConfig(),
                 lc: LoopConfig = LoopConfig(), ckpt_dir: str = "ckpt"):
        self.cfg = cfg
        self.mesh = mesh
        self.fs = fs
        self.data = data
        self.tc = tc
        self.lc = lc
        self.ckpt = TransactionalCheckpointManager(fs, ckpt_dir,
                                                   keep=lc.keep_ckpts)
        self.metrics = MetricsWriter(fs)
        self.step_fn: Optional[Callable] = None
        self.shardings = None
        self.state: dict[str, Any] = {}
        self.step = 0

    # ------------------------------------------------------------------

    def init_state(self, sample_batch: dict) -> None:
        cfg, mesh = self.cfg, self.mesh
        pshape = jax.eval_shape(
            lambda k: init_params(k, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        bshape = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in sample_batch.items()}
        sh = train_shardings(cfg, mesh, pshape, bshape, zero1=self.tc.zero1)
        self.shardings = sh
        step = make_train_step(cfg, mesh, self.tc)
        self.step_fn = jax.jit(
            step,
            in_shardings=(sh["params"], sh["opt"], sh["batch"], None),
            out_shardings=(sh["params"], sh["opt"], None),
            donate_argnums=(0, 1))

        # resume or cold start
        try:
            like = {"params": pshape, "opt": jax.eval_shape(init_opt_state,
                                                            pshape),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}
            step_no, restored = self.ckpt.restore(
                like, shardings={"params": sh["params"], "opt": sh["opt"],
                                 "step": None})
            self.state = restored
            self.step = int(np.asarray(restored["step"]))
            print(f"[trainer] restored committed checkpoint @ {step_no}")
        except FileNotFoundError:
            with jax.set_mesh(self.mesh):
                params = jax.jit(
                    lambda k: init_params(k, cfg),
                    out_shardings=sh["params"])(
                        jax.random.PRNGKey(self.lc.seed))
                opt = jax.jit(init_opt_state,
                              out_shardings=sh["opt"])(params)
            self.state = {"params": params, "opt": opt,
                          "step": jnp.zeros((), jnp.int32)}
            self.step = 0

    # ------------------------------------------------------------------

    def put_batch(self, batch: dict):
        return {k: jax.device_put(np.asarray(v), self.shardings["batch"][k])
                for k, v in batch.items()}

    def run(self, max_steps: Optional[int] = None) -> dict:
        lc = self.lc
        target = min(self.lc.total_steps,
                     self.step + (max_steps or self.lc.total_steps))
        last_metrics: dict = {}
        while self.step < target:
            # host spans tile the loop body around the step call
            with span("train.batch"):
                batch = self.put_batch(next(self.data))
            with span("train.schedule"):
                lr = cosine_with_warmup(jnp.asarray(self.step, jnp.float32),
                                        peak_lr=self.tc.peak_lr,
                                        warmup=lc.warmup,
                                        total=lc.total_steps)
                step_no = jnp.asarray(self.step + 1, jnp.int32)
            with jax.set_mesh(self.mesh):
                params, opt, m = self.step_fn(
                    self.state["params"], self.state["opt"], batch, lr)
            with span("train.state"):
                # drops the previous state, whose donated arrays each give
                # up the GIL as they are freed
                self.state = {"params": params, "opt": opt, "step": step_no}
            self.step += 1
            if self.step % lc.log_every == 0 or self.step == target:
                with span("train.log"):
                    m = {k: float(np.asarray(v)) for k, v in m.items()}
                    self.metrics.write(self.step, m)
                last_metrics = m
            if self.step % lc.ckpt_every == 0 or self.step == target:
                with span("train.fetch_state"):
                    host_state = jax.device_get(self.state)
                with span("train.save"):
                    res = self.ckpt.save(self.step, host_state)
                    self.metrics.write(self.step, {"ckpt_ack_s": res.ack_s})
        self.ckpt.wait_for_save()
        return last_metrics


def run_with_restarts(make_trainer: Callable[[], Trainer], *,
                      max_restarts: int = 2) -> dict:
    """The job harness: run; on an I/O or transaction failure, roll back
    and resubmit (restore from last committed checkpoint).  Matches the
    paper's transaction retry loop at job granularity.  Any other
    exception is a program error and propagates on the first attempt, as
    ``run_transaction`` treats a failing body."""
    attempt = 0
    while True:
        trainer = make_trainer()
        try:
            sample = next(trainer.data)
            trainer.init_state(sample)
            return trainer.run()
        except (OSError, TransactionFailedError, ProcessKilled):
            attempt += 1
            trainer.fs.engine.reset_poison()
            trainer.fs.ledger.clear()
            if attempt > max_restarts:
                raise
            print(f"[trainer] step failure; restart {attempt}/{max_restarts}"
                  " from last committed checkpoint")

"""The training job's main path on the CPU: Trainer steps, a committed
checkpoint through CannyFS, a restore into a fresh Trainer on a fresh
mount, one more step; and which failures the job harness restarts on."""
import errno
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import CannyFS, InMemoryBackend
from repro.data import SyntheticLM
from repro.launch.mesh import make_debug_mesh
from repro.trace import spans
from repro.train.loop import LoopConfig, Trainer, run_with_restarts
from repro.train.steps import TrainConfig

CFG = get_smoke_config("mamba2-130m")


def make_trainer(backend, total=4):
    fs = CannyFS(backend, max_inflight=1000, workers=8)
    data = iter(SyntheticLM(CFG, batch=2, seq_len=32, seed=1))
    return Trainer(CFG, make_debug_mesh(1), fs, data,
                   tc=TrainConfig(dtype=jnp.float32, remat_policy="none"),
                   lc=LoopConfig(total_steps=total, ckpt_every=total,
                                 log_every=1, warmup=1))


def test_trainer_steps_save_restore_step():
    backend = InMemoryBackend()
    tr = make_trainer(backend)
    tr.init_state(next(tr.data))
    assert tr.step == 0
    metrics = tr.run(max_steps=3)          # 3 steps, then a committed save
    assert tr.step == 3 and np.isfinite(metrics["loss"])
    assert tr.ckpt.list_steps() == [3]
    save = tr.ckpt.results[-1]
    assert save.ok and save.step == 3 and save.bytes > 0
    saved = jax.device_get(tr.state)
    tr.metrics.close()
    tr.fs.close()

    tr2 = make_trainer(backend)            # fresh mount over the same store
    tr2.init_state(next(tr2.data))
    assert tr2.step == 3
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(tr2.state)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    metrics = tr2.run()
    assert tr2.step == 4 and np.isfinite(metrics["loss"])
    assert tr2.ckpt.list_steps() == [3, 4]
    tr2.fs.close()


class _StubTrainer:
    """Enough of a Trainer for the harness: data, init_state, run, fs."""

    def __init__(self, fail_with):
        self.fs = CannyFS(InMemoryBackend())
        self.data = iter([{}] * 2)
        self.fail_with = fail_with

    def init_state(self, sample):
        pass

    def run(self):
        if self.fail_with is not None:
            raise self.fail_with
        return {"loss": 1.0}


@pytest.mark.parametrize("error", [
    ValueError("bug in the step"),
    TypeError("bad argument"),
])
def test_run_with_restarts_reraises_program_error_at_once(error):
    made = []

    def factory():
        made.append(_StubTrainer(error))
        return made[-1]

    with pytest.raises(type(error)):
        run_with_restarts(factory, max_restarts=2)
    assert len(made) == 1                  # no restart


@pytest.mark.parametrize("error", [
    OSError(errno.EIO, "injected I/O error"),
    FileNotFoundError(errno.ENOENT, "injected missing shard"),
])
def test_run_with_restarts_restarts_on_io_error(error):
    made = []

    def factory():
        made.append(_StubTrainer(error if not made else None))
        return made[-1]

    assert run_with_restarts(factory, max_restarts=2) == {"loss": 1.0}
    assert len(made) == 2


def test_run_with_restarts_gives_up_after_max_restarts():
    made = []

    def factory():
        made.append(_StubTrainer(OSError(errno.EIO, "always")))
        return made[-1]

    with pytest.raises(OSError):
        run_with_restarts(factory, max_restarts=2)
    assert len(made) == 3


def test_trainer_spans_tile_the_loop_and_the_save():
    tr = make_trainer(InMemoryBackend(), total=3)
    tr.init_state(next(tr.data))
    t0 = time.perf_counter()
    tr.run()                               # 3 steps, then one save
    t1 = time.perf_counter()
    got = spans(t0=t0, t1=t1)
    names = [s.name for s in got]
    for name in ("train.batch", "train.schedule", "train.state",
                 "train.log"):
        assert names.count(name) == 3
        assert all(s.parent is None for s in got if s.name == name)
    assert names.count("train.fetch_state") == names.count("train.save") == 1
    save = next(s for s in got if s.name == "train.save")
    assert next(s for s in got if s.name == "train.fetch_state").t1 \
        <= save.t0
    parts = [s for s in got if s.name.startswith("ckpt.")]
    assert {s.name for s in parts} == {"ckpt.join", "ckpt.serialize",
                                       "ckpt.submit"}
    for s in parts:
        assert s.parent == "train.save"
        assert save.t0 <= s.t0 <= s.t1 <= save.t1
    # one serialize and one submit per leaf, besides the flatten and the
    # directory with its manifest
    leaves = len(jax.tree.leaves(tr.state))
    assert names.count("ckpt.serialize") == names.count("ckpt.submit") \
        == leaves + 1
    res = tr.ckpt.results[-1]
    assert res.ok
    # ack_s is timed from after the join: the parts lie inside it, and
    # cover most of it
    covered = sum(s.t1 - s.t0 for s in parts)
    assert covered <= res.ack_s + 1e-3
    assert covered >= 0.5 * res.ack_s
    tr.fs.close()

"""Drive the system under test: the program's ``Trainer`` on a CannyFS
mount, and beside it, where the traffic asks, an extractor thread on the
same mount.

The window is ``Trainer.run`` itself.  The benchmark replaces the loop's
step function by ``Step``, which runs the compiled step to
``block_until_ready`` and notes when each step was called and returned;
set-up is the loop's first steps (the first compiles), the window opens
at the call of the first step after them and closes at the first call,
``seconds`` later, that follows a whole save cycle, by raising
``StopWindow`` out of the loop.  Host spans (``bench.*``) around the
benchmark's wrappers put the device's idle gaps down to what the host was
doing.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import itertools
import shutil
import threading
import time

import jax
import numpy as np

import archive as archives
from repro.core import (CannyFS, InMemoryBackend, LatencyBackend,
                        LatencyModel, LocalBackend, Transaction)
from repro.launch.mesh import make_debug_mesh
from repro.train.loop import Trainer

SETUP_STEPS = 3   # the steps the comparison with the reference follows


class StopWindow(Exception):
    """Raised from the step wrapper to end ``Trainer.run`` at the close."""


def arch(config: dict):
    name = config["architecture"]
    return (importlib.import_module(f"arch.{name}"),
            importlib.import_module(f"arch.{name}_program"))


def make_mount(config: dict, workdir: str, seed: int) -> CannyFS:
    m = config["mount"]
    if m["backend"] == "local":
        backend = LocalBackend(workdir)
    elif m["backend"] == "latency_model":
        backend = LatencyBackend(InMemoryBackend(),
                                 LatencyModel(seed=seed, **config["latency"]))
    else:
        raise ValueError(f"unknown backend {m['backend']!r}")
    return CannyFS(backend, max_inflight=m["max_inflight"],
                   workers=m["workers"])


class Batches:
    """Token batches from the seed: batch ``i`` is the same for every run
    of the seed, and its rows differ.  ``served`` lists what was handed
    out, in order."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.shape, self.vocab = seed, (batch, seq + 1), vocab
        self.served: list[int] = []

    def batch(self, i: int) -> dict:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 2, i]))
        t = rng.integers(0, self.vocab, self.shape, dtype=np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        i = len(self.served)
        self.served.append(i)
        return self.batch(i)


def span(name, fn):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


class Step:
    """The loop's step function, bounded by ``block_until_ready``."""

    def __init__(self, inner, job: "Job"):
        self.inner, self.job = inner, job
        self.calls = 0

    def __call__(self, params, opt, batch, lr):
        t = time.perf_counter()
        self.calls += 1
        self.job.on_call(self.calls, t, params, opt)
        with jax.profiler.TraceAnnotation("bench.step"):
            out = jax.block_until_ready(self.inner(params, opt, batch, lr))
        self.job.on_return(self.calls, t, time.perf_counter(), out)
        return out


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    first_step: int = 0
    last_step: int = 0                       # the last step run in it
    calls: dict = dataclasses.field(default_factory=dict)    # step -> t
    returns: dict = dataclasses.field(default_factory=dict)  # step -> t
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)


class Job:
    def __init__(self, cell, seed: int, workdir: str, *, trace_dir=None,
                 t_process: float | None = None):
        self.seed, self.workdir = seed, workdir
        self.config, self.traffic = cell.config, cell.traffic
        self.trace_dir = trace_dir
        self.t_process = t_process or time.perf_counter()
        self.ref, self.prog = arch(self.config)
        self.save_every = self.traffic["save_every"]
        # the window opens after the set-up steps, and after the first
        # save where the traffic saves
        self.first_window_step = max(SETUP_STEPS, self.save_every) + 1
        self.win = Window()
        self.seconds = 0.0
        self.setup_s = None
        self.losses: dict[int, float] = {}
        self.batch_of_step: dict[int, int] = {}
        self.first_grad = None                 # host tree
        self.change = None                     # host tree
        self.saves: list = []                  # (step, SaveResult)
        self.saved_states = collections.deque(maxlen=self.traffic.get("keep",
                                                                      3))
        self.errors: list[str] = []
        self.cycles: list = []                 # (t_done, entries)
        self.timer = archives.CallTimer()
        self.go, self.stop = threading.Event(), threading.Event()
        self.checked_root = None
        self.extractor = None
        self.win_ann = None

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        c, t = self.config, self.traffic
        shutil.rmtree(self.workdir, ignore_errors=True)
        self.fs = make_mount(c, self.workdir, self.seed)
        self.data = Batches(self.seed, t["batch"], t["seq"], c["vocab_size"])
        tr = self.trainer = Trainer(
            self.prog.model_config(c), make_debug_mesh(1), self.fs,
            self.data, tc=self.prog.train_config(c),
            lc=self.prog.loop_config(c, t, self.seed))
        tr.init_state(next(self.data))
        self._seed_weights()
        tr.step_fn = Step(tr.step_fn, self)
        tr.put_batch = span("bench.put_batch", tr.put_batch)
        tr.metrics.write = span("bench.metrics_write", tr.metrics.write)
        save = tr.ckpt.save

        def saving(step, state, **kw):
            with jax.profiler.TraceAnnotation("bench.ckpt_save"):
                res = save(step, state, **kw)
            self.saves.append((step, res))
            self.saved_states.append((step, state))
            return res
        tr.ckpt.save = saving
        ex = t.get("extract")
        if ex:
            self.archive = archives.make_archive(
                self.seed, dict(ex, entries=c["archive_entries"]))
            # one whole cycle before the window: no first-time path in it
            self._extract("scratch/warm")
            self._remove("scratch/warm")
            self.extractor = threading.Thread(target=self._extractor,
                                              name="extractor", daemon=True)
            self.extractor.start()

    def _seed_weights(self) -> None:
        """The benchmark's seeded weights in place of the program's."""
        tr = self.trainer
        is_shape = lambda x: isinstance(x, tuple)
        got = jax.tree.map(lambda a: tuple(a.shape), tr.state["params"])
        if jax.tree.flatten(got, is_leaf=is_shape) != \
                jax.tree.flatten(self.ref.layout(self.config), is_leaf=is_shape):
            raise RuntimeError("the program's parameter layout is not the "
                               "reference's")
        params = self.ref.make_params(self.seed, self.config)
        tr.state["params"] = jax.device_put(params, tr.shardings["params"])
        self.p0 = jax.device_get(tr.state["params"])

    # -- the loop's hooks -------------------------------------------------

    def on_call(self, k: int, t: float, params, opt) -> None:
        w = self.win
        self.batch_of_step[k] = self.data.served[-1]
        if k == 2:
            b1 = self.config["optimizer"]["b1"]
            self.first_grad = jax.tree.map(lambda m: np.asarray(m) / (1 - b1),
                                           jax.device_get(opt["m"]))
        if k == SETUP_STEPS + 1:
            self.change = jax.tree.map(lambda a, b: np.asarray(a) - b,
                                       jax.device_get(params), self.p0)
            del self.p0
        if k == self.first_window_step - 1 and self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        if k == self.first_window_step:
            self.setup_s = t - self.t_process
            w.t0, w.first_step = t, k
            w.stats0 = dataclasses.asdict(self.fs.stats)
            self.win_ann = jax.profiler.TraceAnnotation("bench.window")
            self.win_ann.__enter__()
            self.timer.on = True
            self.go.set()
        if k > self.first_window_step:
            w.calls[k] = t
            cycle_done = not self.save_every or (k - 1) % self.save_every == 0
            if t - w.t0 >= self.seconds and cycle_done:
                self._close(k, t)
                raise StopWindow

    def on_return(self, k: int, t_call: float, t_ret: float, out) -> None:
        if k <= SETUP_STEPS:
            self.losses[k] = float(out[2]["loss"])
        if k >= self.first_window_step:
            self.win.calls.setdefault(k, t_call)
            self.win.returns[k] = t_ret

    def _close(self, k: int, t: float) -> None:
        w = self.win
        w.t1, w.last_step = t, k - 1
        del w.calls[k]
        self.timer.on = False
        self.stop.set()
        self.win_ann.__exit__(None, None, None)
        w.stats1 = dataclasses.asdict(self.fs.stats)

    # -- the window -------------------------------------------------------

    def run(self, seconds: float) -> None:
        self.seconds = seconds
        try:
            self.trainer.run(max_steps=10 ** 9)
        except StopWindow:
            pass
        else:
            raise RuntimeError("the loop ended before the window closed")
        finally:
            self.go.set()
            self.stop.set()
            if self.trace_dir and self.win.t1:
                jax.profiler.stop_trace()
        self.trainer.ckpt.wait_for_save()
        if self.extractor is not None:
            self.extractor.join(timeout=300)
            if self.extractor.is_alive():
                self.errors.append("the extractor did not finish")

    # -- the extractor ----------------------------------------------------

    def _extract(self, root: str) -> None:
        with jax.profiler.TraceAnnotation("bench.extract"):
            archives.extract(self.fs, Transaction, self.archive, root,
                             self.timer)
            self.timer(self.fs.drain)

    def _remove(self, root: str) -> None:
        with jax.profiler.TraceAnnotation("bench.rmtree"):
            self.timer(self.fs.rmtree, root)
            self.timer(self.fs.drain)

    def _extractor(self) -> None:
        """Extract and remove, back to back, from the window's opening.  At
        the close the phase under way runs to its end, and the last tree
        extracted stays for the check (one more if the close came during
        a removal)."""
        self.go.wait()
        try:
            for i in itertools.count():
                root = f"scratch/c{i:05d}"
                self._extract(root)
                if self.stop.is_set():
                    break
                self._remove(root)
                self.cycles.append((time.perf_counter(), self.archive.entries))
                if self.stop.is_set():
                    root = f"scratch/c{i + 1:05d}"
                    self._extract(root)
                    break
            self.checked_root = root
        except Exception as e:   # reported as a failed answer
            self.errors.append(f"extractor: {e!r}")

"""A whole run, less the look for a chip, at a tiny size: sound, it is
correct; with the training step broken underneath, it is not."""
import numpy as np
import pytest

import _paths  # noqa: F401
from _tiny import run_tiny

CELL = "mamba2-130m.local.ckpt"


def broken_step(monkeypatch, change):
    import repro.train.loop as loop
    real = loop.make_train_step

    def make(cfg, mesh, tc):
        step = real(cfg, mesh, tc)
        return lambda params, opt, batch, lr: change(step, params, opt,
                                                     batch, lr)
    monkeypatch.setattr(loop, "make_train_step", make)


def test_sound_run_is_correct(tmp_path):
    r = run_tiny(CELL, tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "save_stall_s",
                                 "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", [CELL, "mamba2-130m.nfs.tree"])
def test_step_that_returns_its_state_unchanged(cell, tmp_path, monkeypatch):
    def unchanged(step, params, opt, batch, lr):
        _, _, metrics = step(params, opt, batch, lr)
        return params, opt, metrics
    broken_step(monkeypatch, unchanged)
    r = run_tiny(cell, tmp_path)
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] == pytest.approx(1.0)
    assert r["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", [CELL, "mamba2-130m.nfs.tree"])
def test_half_of_the_batch_left_out(cell, tmp_path, monkeypatch):
    def half(step, params, opt, batch, lr):
        rows = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(params, opt, rows, lr)
    broken_step(monkeypatch, half)
    r = run_tiny(cell, tmp_path)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for k, c in r["checks"].items()
               if k.endswith("_gap"))


def test_checkpoint_byte_altered(tmp_path, monkeypatch):
    import repro.checkpoint.manager as manager
    real = manager.flatten_for_save

    def flip(state):
        meta, leaves = real(state)
        i = next(i for i, (_, a) in enumerate(leaves) if np.size(a) > 1)
        key, arr = leaves[i]
        arr = np.array(arr, copy=True)
        arr.reshape(-1).view(np.uint8)[0] ^= 1
        leaves[i] = (key, arr)
        return meta, leaves
    monkeypatch.setattr(manager, "flatten_for_save", flip)
    r = run_tiny(CELL, tmp_path)
    assert not r["correct"]
    assert r["checks"]["ckpt_bad_leaves"]["value"] > 0

"""The tree cells' comparison at a tiny size: sound, correct; with a byte
altered where the engine writes it, not."""
import _paths  # noqa: F401
from _tiny import run_tiny

CELL = "mamba2-130m.nfs.tree"


def test_sound_run_is_correct(tmp_path):
    r = run_tiny(CELL, tmp_path)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "tree_entries_per_s",
                                 "tree_call_ms_p99", "setup_s"}
    assert r["checks"]["tree_bad_entries"]["value"] == 0


def test_tree_mix_on_local_disk_is_correct(tmp_path):
    r = run_tiny(CELL, tmp_path, config="mamba2-130m.local")
    assert r["correct"], r["checks"]
    assert r["metrics"]["tree_entries_per_s"]["value"] > 0


def test_written_byte_altered(tmp_path, monkeypatch):
    from repro.core.fs import CannyFile
    real = CannyFile.write

    def write(self, data):
        if self.path.startswith("scratch/c"):
            data = bytes([data[0] ^ 1]) + bytes(data[1:])
        return real(self, data)
    monkeypatch.setattr(CannyFile, "write", write)
    r = run_tiny(CELL, tmp_path)
    assert not r["correct"]
    assert r["checks"]["tree_bad_entries"]["value"] > 0

"""A cell at a size a test run on the CPU can hold: the configuration's
architecture with tiny widths, and a short window."""
import json

import _paths
import run
import spec

TINY = dict(d_model=64, n_layer=2, vocab_size=128, d_state=16, headdim=16,
            chunk_size=16)


def tiny_cell(name: str, config: str | None = None):
    """``config`` puts another configuration file under the cell's mix."""
    cell = spec.load_cell(name)
    if config:
        cell.config = json.loads(
            (_paths.CHIP / "configs" / f"{config}.json").read_text())
    cell.config.update(TINY)
    cell.traffic.update(batch=2, seq=64)
    if cell.traffic["save_every"]:
        cell.traffic["save_every"] = 2
    cell.config["archive_entries"] = 66
    return cell


def run_tiny(name: str, tmp_path, seed: int = 2 ** 32 + 11,
             seconds: float = 1.0, config: str | None = None) -> dict:
    return run.run_cell(tiny_cell(name, config), seed, seconds, False,
                        on_chip=False, work=tmp_path)

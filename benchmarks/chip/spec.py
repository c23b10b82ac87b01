"""Find a cell's configuration, traffic mix and metrics by name.

Everything is data: ``BENCHMARK.json`` at the checkout root names the
cells; a configuration is the JSON file it names, a traffic mix is
``traffic/<name>.json`` here, and a metric is read by
``metrics/<name>.py`` here, whose ``read(run)`` returns a number or None.
A quantity split by the cells it is read in, because it moves another
end-to-end metric in each (``fs.ack_us.ckpt``, ``fs.ack_us.tree``), has
one reader under its stem (``metrics/fs.ack_us.py``).
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # ... and with --trace 1


def load_benchmark(path: Path | None = None) -> dict:
    with open(path or CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` is read in those cells; one without, in
    every cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric, in every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(name: str, bench: dict | None = None,
              root: Path = CHECKOUT) -> Cell:
    bench = bench or load_benchmark(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, name, reported)]
    return Cell(name, config, traffic, w["chips"], e2e, per_layer)


def reader(metric_name: str):
    """The ``read`` function of ``metrics/<metric_name>.py``, or of the
    stem's reader, ``metric_name`` less its last dotted part."""
    path = HERE / "metrics" / f"{metric_name}.py"
    if not path.is_file():
        path = HERE / "metrics" / f"{metric_name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

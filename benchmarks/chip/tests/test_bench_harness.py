"""The harness's own pieces: finding a cell by name, the archive
generator, and the arithmetic of the metric readers."""
import json
import re
import types

import numpy as np
import pytest

import _paths  # noqa: F401
import archive
import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = spec.load_cell(cell, BENCH)
    assert c.chips == 1
    assert c.config["architecture"] == "mamba2"
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    # every per-layer metric moves an end-to-end metric the cell reports
    assert all(m["moves"] in names for m in c.per_layer)
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m["name"]))


def test_unknown_cell():
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", BENCH)


def test_benchmark_names_and_files():
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert (_paths.CHECKOUT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert (_paths.CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("config", ["mamba2-130m.local", "mamba2-130m.nfs"])
def test_archive_shape_at_a_sixteenth_of_the_papers(config):
    spec_ = json.loads((_paths.CHIP / "traffic" / "tree.json").read_text())
    conf = json.loads((_paths.CHIP / "configs" / f"{config}.json")
                      .read_text())
    assert conf["source_config"]["archive_entries"] == 59_259
    assert "archive_entries" in conf["reduced"]
    a = archive.make_archive(2 ** 33 + 7, dict(
        spec_["extract"], entries=conf["archive_entries"]))
    assert a.entries == 3_704 == round(59_259 / 16)
    assert len(a.dirs) == 337 and len(a.files) == 3_367
    assert a.nbytes / len(a.files) == pytest.approx(36_000, rel=1e-3)
    assert max(s for _, _, s in a.files) <= spec_["extract"]["cap_bytes"]
    assert len({p for p, _, _ in a.files}) == len(a.files)
    # parents come first
    seen = set()
    for d in a.dirs:
        parent = d.rsplit("/", 1)[0]
        assert "/" not in d or parent in seen
        seen.add(d)


def test_archive_comes_from_the_seed_with_the_same_work():
    ex = {"entries": 220, "files_per_dir": 10, "mean_bytes": 36_000,
          "sigma": 1.0, "cap_bytes": 524_288}
    a, b, c = (archive.make_archive(s, ex) for s in (5, 5, 6))
    assert a.files == b.files and a.blob == b.blob
    assert a.files != c.files and a.blob != c.blob
    assert a.data(0) != a.data(1)
    # the same directories and the same sizes, in another order
    assert a.dirs == c.dirs
    assert sorted(s for _, _, s in a.files) == \
        sorted(s for _, _, s in c.files)


def test_sizes_are_lognormal_with_the_mean_asked():
    sizes = archive.lognormal_sizes(10_000, 36_000, 1.0, 524_288)
    assert sizes.mean() == pytest.approx(36_000, rel=1e-3)
    # the median of a lognormal of sigma 1 is its mean over e^(1/2)
    assert float(np.median(sizes)) == pytest.approx(36_000 / 1.6487,
                                                    rel=0.01)
    assert sizes.max() <= 524_288


def fake_run(**kw):
    window = types.SimpleNamespace(t0=10.0, t1=20.0, calls={}, returns={})
    base = dict(window=window, window_s=10.0, steps=[], save_steps=set(),
                saves=[], cycles=[], call_ms=[], stats={}, trace=None,
                peaks=None, tokens_per_step=16_384, setup_s=42.0)
    base.update(kw)
    run = types.SimpleNamespace(**base)
    run.gap_after = lambda k: (window.calls.get(k + 1, window.t1)
                               - window.returns[k])
    return run


@pytest.mark.parametrize("n,rank", [(1, 1), (99, 99), (100, 99),
                                    (101, 100), (30_000, 29_700)])
def test_p99_nearest_rank(n, rank):
    p99 = spec.reader("tree_call_ms_p99")
    run = fake_run(call_ms=[float(i) for i in range(n, 0, -1)])
    assert p99(run) == float(rank)


def test_p99_without_samples():
    assert spec.reader("tree_call_ms_p99")(fake_run()) is None


def test_tree_rate_counts_whole_cycles_only():
    read = spec.reader("tree_entries_per_s")
    # the run keeps cycles completed by the close; two of 100 entries,
    # the last done 8 s after the window opened
    run = fake_run(cycles=[(14.0, 100), (18.0, 100)])
    assert read(run) == pytest.approx(200 / 8.0)
    assert read(fake_run()) is None


def test_stall_and_host_gap():
    w = types.SimpleNamespace(t0=0.0, t1=10.0,
                              calls={1: 0.0, 2: 1.1, 3: 7.2, 4: 8.3},
                              returns={1: 1.0, 2: 2.1, 3: 8.2, 4: 9.2})
    run = fake_run(window=w, steps=[1, 2, 3, 4], save_steps={2})
    run.gap_after = lambda k: w.calls.get(k + 1, w.t1) - w.returns[k]
    assert spec.reader("save_stall_s")(run) == pytest.approx(5.1)
    gaps = [0.1, 0.1, 0.8]          # steps 1, 3 and the last, 4
    assert spec.reader("loop.host_gap_ms")(run) == \
        pytest.approx(1e3 * sum(gaps) / 3)
    assert spec.reader("train_tokens_per_s")(run) == \
        pytest.approx(4 * 16_384 / 10.0)


def test_engine_ratios():
    run = fake_run(stats={"eager_acks": 4, "ack_latency_s": 2e-4,
                          "submitted": 10, "executed": 6})
    assert spec.reader("fs.ack_us.tree")(run) == pytest.approx(50.0)
    assert spec.reader("fs.ack_us.ckpt")(run) == pytest.approx(50.0)
    assert spec.reader("engine.exec_per_op.tree")(run) == pytest.approx(0.6)
    assert spec.reader("fs.ack_us.tree")(fake_run()) is None
    # the engine sums the waits of synchronous operations into the same
    # counter: they count below the line too
    run = fake_run(stats={"eager_acks": 3, "sync_ops": 1,
                          "ack_latency_s": 2e-4})
    assert spec.reader("fs.ack_us.ckpt")(run) == pytest.approx(50.0)


def test_a_split_metric_has_one_reader():
    """``fs.ack_us.ckpt`` and ``fs.ack_us.tree`` are read by
    ``metrics/fs.ack_us.py``; a metric with a file of its own keeps it."""
    run = fake_run(stats={"eager_acks": 2, "ack_latency_s": 1e-4})
    assert not (_paths.CHIP / "metrics" / "fs.ack_us.tree.py").exists()
    assert spec.reader("fs.ack_us.ckpt")(run) == \
        spec.reader("fs.ack_us")(run) == pytest.approx(50.0)
    assert spec.reader("fs.ack_us.tree").__code__.co_filename.endswith(
        "/metrics/fs.ack_us.py")
    assert spec.reader("step.mfu").__code__.co_filename.endswith(
        "/metrics/step.mfu.py")


@pytest.mark.parametrize("name", ["step.mfu", "ssd_scan_roofline",
                                  "device.idle_share"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert spec.reader(name)(fake_run()) is None


def test_device_readers_on_a_reduced_trace():
    import flops
    import peaks
    config = json.loads((_paths.CHIP / "configs" /
                         "mamba2-130m.local.json").read_text())
    fl, by = flops.ssd_scan_cost(config, 8, 2048)
    least = by / 819e9
    trace = types.SimpleNamespace(
        modules={"jit_train_step": [2, 1.2]},
        ops={"ssd_scan": [96, 96 * least * 2, 0.0]}, idle_share=0.25)
    run = fake_run(trace=trace, peaks=peaks.peaks("TPU v5 lite"),
                   flops=flops, config=config,
                   traffic={"batch": 8, "seq": 2048})
    assert spec.reader("ssd_scan_roofline")(run) == pytest.approx(50.0)
    assert spec.reader("device.idle_share")(run) == pytest.approx(25.0)
    step = flops.mamba2_step_flops(config, 8, 2048)
    assert spec.reader("step.mfu")(run) == \
        pytest.approx(100 * 2 * step / (1.2 * 197e12))
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")

"""The reduction from a trace to device numbers, on a synthesised trace
(one chip, known answers) and on one recorded on the CPU."""
import pytest

import _paths  # noqa: F401
import xplane

MS = 1_000_000  # ns


def synth():
    """Window 0..100 ms.  A step program 10..40 ms whose ``while`` holds
    two kernels, a lone copy 60..70 ms.  Host: a step span over the
    program, a save span 40..58, nothing 70..100."""
    tr = xplane.Trace()
    tr.ops["/device:TPU:0"] = [
        ("while", 10 * MS, 40 * MS),
        ("ssd_scan", 12 * MS, 20 * MS),
        ("ssd_scan", 22 * MS, 30 * MS),
        ("copy", 60 * MS, 70 * MS),
        ("fusion", -5 * MS, 2 * MS),          # starts before the window
    ]
    tr.modules["/device:TPU:0"] = [("jit_train_step", 10 * MS, 40 * MS)]
    tr.spans = [("bench.window", 0, 100 * MS),
                ("bench.step", 9 * MS, 41 * MS),
                ("bench.ckpt_save", 40 * MS, 58 * MS)]
    return tr


def test_busy_and_idle_share():
    r = xplane.reduce(synth())
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.002 + 0.030 + 0.010)
    assert r.idle_share == pytest.approx(1 - 0.042 / 0.100)
    assert r.chips == 1


def test_counts_totals_and_self_times():
    r = xplane.reduce(synth())
    assert r.ops["ssd_scan"] == pytest.approx([2, 0.016, 0.016])
    assert r.ops["while"] == pytest.approx([1, 0.030, 0.014])
    assert r.ops["fusion"] == pytest.approx([1, 0.002, 0.002])
    assert r.modules["jit_train_step"] == pytest.approx([1, 0.030])


def test_gaps_go_to_the_span_that_covers_them():
    r = xplane.reduce(synth())
    # 2..10 ms: the step span covers 1 of 8 ms -> no span; 40..60: the
    # save covers 18 of 20 ms; 70..100: nothing
    assert r.idle_by_span["bench.ckpt_save"] == pytest.approx(0.020)
    assert r.idle_by_span[xplane.NO_SPAN] == pytest.approx(0.008 + 0.030)
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s
                                                         - r.busy_s)
    b = r.breakdown(n=2)
    assert b["idle_gaps"][0][0] == xplane.NO_SPAN
    assert [k for k, _ in b["device_ops"]] == ["ssd_scan", "while"]


def test_no_window_or_no_device_is_an_error():
    tr = synth()
    tr.spans = tr.spans[1:]
    with pytest.raises(ValueError):
        xplane.reduce(tr)
    tr = synth()
    tr.ops = {}
    with pytest.raises(ValueError):
        xplane.reduce(tr)


@pytest.mark.parametrize("event,name", [
    ("%ssd_scan.18 = bf16[192,2048,64]{2,1,0} custom-call(f32[192,1,2048])",
     "ssd_scan"),
    ("%fusion.451 = (f32[8,16,128,24]) fusion(f32[8,16,128,2])", "fusion"),
    ("%bitcast_dynamic-update-slice_fusion.21 = (bf16[24,8])",
     "bitcast_dynamic-update-slice_fusion"),
    ("%while.117 = (s32[], bf16[8,2048,768])", "while"),
    ("copy-start", "copy-start"),
])
def test_op_names(event, name):
    assert xplane.op_name(event) == name


def test_module_name():
    assert xplane.module_name("jit_train_step(5931182564783298064)") \
        == "jit_train_step"


def test_recorded_host_spans(tmp_path):
    """A trace recorded here holds the benchmark's spans on the host
    plane; with no chip in it, reducing it is an error."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(str(tmp_path)))
    names = {s[0] for s in tr.spans}
    assert {"bench.window", "bench.step"} <= names
    win = next(s for s in tr.spans if s[0] == "bench.window")
    step = next(s for s in tr.spans if s[0] == "bench.step")
    assert win[1] <= step[1] <= step[2] <= win[2]
    with pytest.raises(ValueError):
        xplane.reduce(tr)

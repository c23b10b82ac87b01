"""The benchmark's own FLOP and byte counts, pinned to the shapes."""
import json

import pytest

import _paths  # noqa: F401
import flops

CONFIG = json.loads((_paths.CHIP / "configs" /
                     "mamba2-130m.local.json").read_text())


def test_scan_flops_per_token():
    # G*2QN + H*(2QP + 4NP), Q=256, N=128, P=64, H=24, G=1
    assert flops.scan_flops_per_token(CONFIG) == 65_536 + 24 * 65_536


def test_forward_counts_the_tied_unembedding_and_the_scan():
    per_layer = (2 * 768 * 3352 + 2 * 1536 * 768 + 2 * 1792 * 4
                 + 1_638_400)
    unembed = 2 * 50288 * 768
    assert flops.mamba2_forward_flops_per_token(CONFIG) == \
        24 * per_layer + unembed == 297_099_264


def test_step_flops_at_8x2048():
    f = flops.mamba2_step_flops(CONFIG, 8, 2048)
    assert f == 3 * 297_099_264 * 8 * 2048 == 14_603_023_024_128


def test_step_flops_exceed_the_programs_own_count():
    """``ModelConfig.model_flops`` leaves out the tied unembedding and the
    scan (8.88 TFLOP at 8 x 2048); the benchmark keeps its own count."""
    from repro.configs import get_config
    prog = get_config("mamba2-130m").model_flops(8 * 2048, training=True)
    ours = flops.mamba2_step_flops(CONFIG, 8, 2048)
    assert prog == pytest.approx(8.88e12, rel=1e-2)
    assert ours - prog == pytest.approx(
        3 * 8 * 2048 * (2 * 50288 * 768 + 24 * (1_638_400 + 2 * 1792 * 4)),
        rel=1e-2)


def test_ssd_scan_cost_at_8x2048():
    fl, by = flops.ssd_scan_cost(CONFIG, 8, 2048, out_bytes=2)
    assert fl == 1_638_400 * 8 * 2048 == 26_843_545_600
    rows = 8 * 24 * 2048
    assert by == rows * 4 * (2 + 2 * 64 + 2 * 128) + rows * 64 * 2 \
        == 657_457_152
    # bytes bound on a v5e: 0.80 ms at 819 GB/s against 0.14 ms of FLOPs
    assert by / 819e9 > fl / 197e12


def test_counts_scale_with_the_tokens():
    a = flops.ssd_scan_cost(CONFIG, 1, 256)
    b = flops.ssd_scan_cost(CONFIG, 2, 512)
    assert b[0] == 4 * a[0] and b[1] == 4 * a[1]

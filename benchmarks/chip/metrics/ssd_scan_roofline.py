"""The ``ssd_scan`` kernel's share of its roofline, in %: per call the
least time the chip could take, the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth (``flops.ssd_scan_cost``; at
the benchmark's shapes the bytes bound), times the calls in the traced
window, over their device time."""

OUT_BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    count, seconds, _ = run.trace.ops.get("ssd_scan", (0, 0.0, 0.0))
    if not count:
        return None
    fl, by = run.flops.ssd_scan_cost(
        run.config, run.traffic["batch"], run.traffic["seq"],
        OUT_BYTES[run.config["precision"]["compute"]])
    least = max(fl / run.peaks.flops, by / run.peaks.hbm_bw)
    return 100.0 * least * count / seconds

"""The model FLOPs of each train step run in the traced window (the
benchmark's own count, ``flops.py``) over the device time of the
``jit_train_step`` program in the trace times the chip's bf16 peak, in %."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    count, seconds = run.trace.modules.get("jit_train_step", (0, 0.0))
    if not count:
        return None
    per_step = run.flops.mamba2_step_flops(run.config, run.traffic["batch"],
                                           run.traffic["seq"])
    return 100.0 * per_step * count / (seconds * run.peaks.flops)

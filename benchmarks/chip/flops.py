"""Operations and bytes computed from shapes, kept with the benchmark.

``mamba2_step_flops`` counts what a training step of a Mamba-2 language
model needs: the forward pass and a backward pass of twice its cost
(recomputation not counted), with

* every projection (``in_proj``, ``out_proj``) and the tied unembedding,
  which is a V x D matmul whether or not its table is shared with the
  embedding (the embedding gather itself is no matmul);
* the depthwise conv taps;
* the SSD scan in its chunked form: per chunk, the group's C B^T scores
  (Q x Q x N), each head's scores-times-inputs (Q x Q x P), its chunk end
  state (Q x N x P) and its output from the carried state (Q x N x P).

``ssd_scan_cost`` is one call of the ``ssd_scan`` Pallas kernel: the same
scan terms for the whole batch, and the bytes of the operands and result
as the kernel receives them (float32 log-decay row and column, dt*x and
its decayed copy, B and C expanded per head; the result in the compute
type).
"""
from __future__ import annotations

from arch.mamba2 import dims


def scan_flops_per_token(c: dict) -> float:
    """SSD scan, forward, per token and layer."""
    d = dims(c)
    Q, N, P = d["Q"], d["N"], d["P"]
    return d["G"] * 2 * Q * N + d["H"] * (2 * Q * P + 4 * N * P)


def mamba2_forward_flops_per_token(c: dict) -> float:
    d = dims(c)
    per_layer = (2 * d["D"] * d["proj"] + 2 * d["di"] * d["D"]
                 + 2 * d["conv_ch"] * d["K"] + scan_flops_per_token(c))
    return d["L"] * per_layer + 2 * d["V"] * d["D"]


def mamba2_step_flops(c: dict, batch: int, seq: int) -> float:
    """Forward + backward (3x the forward) over ``batch`` x ``seq`` tokens."""
    return 3.0 * mamba2_forward_flops_per_token(c) * batch * seq


def ssd_scan_cost(c: dict, batch: int, seq: int,
                  out_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``ssd_scan`` kernel call over the batch."""
    d = dims(c)
    flops = scan_flops_per_token(c) * batch * seq
    rows = batch * d["H"] * seq                  # (batch*head, position)
    f32 = 4
    reads = rows * f32 * (2 + 2 * d["P"] + 2 * d["N"])
    writes = rows * d["P"] * out_bytes
    return float(flops), float(reads + writes)

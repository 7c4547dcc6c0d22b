"""The bytes the xBC convolution's two kernels (ops/lm_kda_kernels.py
`conv_fwd_call` / `conv_bwd_call` at a Mamba-2 site: instructions
`ssd_conv_fwd.N` / `ssd_conv_bwd.N`) MUST move through HBM, a call, counted
from shapes alone: the benchmark's own count, kept here so that no later PR
can move the roofline share by moving the arithmetic.

A forward call reads the stream z (B, S, C) and writes its result, both in the
compute dtype, and reads the float32 filter (taps, C) and bias (C,). A
backward call reads z and the cotangent and writes dz (the compute dtype),
reads the filter and bias, and writes their float32 gradients once. What a
kernel moves beyond that (each tile's halo rows, each tile's partial sums of
the filter's and bias's gradients) is not counted: the share is of the
least a call could move, so it cannot pass 100% unless the time is short of
the work.
"""

from __future__ import annotations

FLOAT32 = 4


def conv_bytes(batch: int, seq: int, channels: int, taps: int, itemsize: int = 2) -> dict[str, int]:
    """{"fwd": bytes a forward call must move, "bwd": a backward call's}."""
    stream = batch * seq * channels * itemsize
    weights = (taps + 1) * channels * FLOAT32  # the filter and the bias
    return {"fwd": 2 * stream + weights, "bwd": 3 * stream + 2 * weights}

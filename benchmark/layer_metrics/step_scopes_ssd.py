"""The lm.ssd_*.train metrics: device time inside a token model's compiled
train step under the Mamba-2 scopes (yet_another_mobilenet_series_tpu/
obs/scopes.py: ssd_proj, ssd_conv, ssd_gate, ssd_core, ssd_norm).

Nothing is measured or compiled here. step_scopes_lm.py computes the whole
scope x phase table of a run once (its `metric` leaves it on
`ctx.step_scopes_lm`, and prints it as the `step_scopes_lm` commentary line);
this reader sums the `ssd_*` rows of that table. With the six `lm.*` times the
three partition the Mamba-2 layers' share of the step's op time:

- `lm.ssd_core_ms.train`: the chunked scan (C B^T, the in-chunk decays and
  products, each chunk's state, the scan over chunks, the D skip) AND the
  gated norm, forward, the backward's second making of the in-chunk
  matrices, and the backward. The norm is counted here because XLA fuses
  the SSD output's assembly (the states' way in, the sum of the in-chunk
  products, the D skip) and its backward into the norm's fusions, whose
  roots carry `ssd_norm`, or not, as the fusion falls (all heads at once:
  most of the assembly under `ssd_norm`; head groups of 8: under
  `ssd_core`). Only the sum of the two follows the work;
- `lm.ssd_proj_ms.train`: in_proj (z, xBC, dt) and out_proj;
- `lm.ssd_pointwise_ms.train`: the xBC convolution with its bias and SiLU,
  softplus of dt with Delta A and the cumulative sums.

Returns None, and the line leaves the metric out, where there is nothing to
read: no table (a CPU rehearsal, no whole step in the stretch, a program
without the token family) or a table without an `ssd_*` row (a program, or a
model, without Mamba-2).
"""

from __future__ import annotations

from benchmark.layer_metrics import step_scopes_lm

# metric -> the scopes it sums, every phase
METRICS = {
    "lm.ssd_core_ms.train": ("ssd_core", "ssd_norm"),
    "lm.ssd_proj_ms.train": ("ssd_proj",),
    "lm.ssd_pointwise_ms.train": ("ssd_conv", "ssd_gate"),
}


def metric(ctx, name: str):
    if not hasattr(ctx, "step_scopes_lm"):
        step_scopes_lm.metric(ctx, step_scopes_lm.UNSCOPED_SHARE)  # makes the table, on a run's first call, and keeps it
    found = ctx.step_scopes_lm
    if found is None:
        return None
    rows = found["table"]["ms_per_step"]  # {"<scope>.<phase>": ms a step}
    if not any(key.startswith("ssd_") for key in rows):
        return None
    return sum(ms for key, ms in rows.items() if key.rsplit(".", 1)[0] in METRICS[name])

"""The lm.kda_*.train metrics: device time inside a token model's compiled
train step under the Kimi Delta Attention scopes (yet_another_mobilenet_series_tpu/
obs/scopes.py: kda_proj, kda_conv, kda_gate, kda_core, kda_norm).

Nothing is measured or compiled here. step_scopes_lm.py computes the whole
scope x phase table of a run once (its `metric` leaves it on
`ctx.step_scopes_lm`, and prints it as the `step_scopes_lm` commentary line);
this reader sums the `kda_*` rows of that table. With the six `lm.*` times the
three partition the step's op time:

- `lm.kda_core_ms.train`: the chunked gated delta rule (in-chunk decayed
  scores, the triangular solve, the scan over chunks), forward, the backward's
  second making of the in-chunk matrices, and the backward;
- `lm.kda_proj_ms.train`: the seven projections (q, k, v, the decay gate's and
  the output gate's low-rank pairs, beta, o);
- `lm.kda_pointwise_ms.train`: the short convolutions, the gates, the L2 and
  output norms.

Returns None, and the line leaves the metric out, where there is nothing to
read: no table (a CPU rehearsal, no whole step in the stretch, a program
without the token family) or a table without a `kda_*` row (a program, or a
model, without KDA).
"""

from __future__ import annotations

from benchmark.layer_metrics import step_scopes_lm

# metric -> the scopes it sums, every phase
METRICS = {
    "lm.kda_core_ms.train": ("kda_core",),
    "lm.kda_proj_ms.train": ("kda_proj",),
    "lm.kda_pointwise_ms.train": ("kda_conv", "kda_gate", "kda_norm"),
}


def metric(ctx, name: str):
    if not hasattr(ctx, "step_scopes_lm"):
        step_scopes_lm.metric(ctx, step_scopes_lm.UNSCOPED_SHARE)  # makes the table, on a run's first call, and keeps it
    found = ctx.step_scopes_lm
    if found is None:
        return None
    rows = found["table"]["ms_per_step"]  # {"<scope>.<phase>": ms a step}
    if not any(key.startswith("kda_") for key in rows):
        return None
    return sum(ms for key, ms in rows.items() if key.rsplit(".", 1)[0] in METRICS[name])

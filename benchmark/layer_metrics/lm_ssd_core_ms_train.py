"""lm.ssd_core_ms.train: see step_scopes_ssd.py, which sums the rows of the table step_scopes_lm.py makes once a run."""

from benchmark.layer_metrics import step_scopes_ssd


def read(ctx):
    return step_scopes_ssd.metric(ctx, "lm.ssd_core_ms.train")

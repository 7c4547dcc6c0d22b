"""lm.kda_core_ms.train: see step_scopes_kda.py, which sums the rows of the table step_scopes_lm.py makes once a run."""

from benchmark.layer_metrics import step_scopes_kda


def read(ctx):
    return step_scopes_kda.metric(ctx, "lm.kda_core_ms.train")

"""lm.exit_gate_ms.train: see step_scopes_ouro.py, which sums the rows of the table step_scopes_lm.py makes once a run."""

from benchmark.layer_metrics import step_scopes_ouro


def read(ctx):
    return step_scopes_ouro.metric(ctx, "lm.exit_gate_ms.train")

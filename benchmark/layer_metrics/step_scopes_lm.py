"""The lm.*.train metrics: device time INSIDE a token model's compiled train
step by the program's own named scopes (yet_another_mobilenet_series_tpu/
obs/scopes.py: embed, norm, rope, attn_proj, attn_core, mlp, moe_router,
moe_dispatch, moe_experts, moe_combine, mtp_merge, lm_head, loss, optim),
where step_scopes_train.py reads a CNN step.

The method is that module's and its functions are used as they are
(`step_ops`: device 0, whole executions of the step inside the traced
stretch, synchronous ops of the `XLA Ops` line; `obs.scopes` for the
instruction -> (scope, phase) table and the sums). What differs is the step:
it is lowered and compiled AGAIN here, after the window, exactly as
runners/train_tokens_resident.py builds it (its `build`), from shapes alone:
one trace and one read from the compile cache, in `--trace 1` runs only,
outside the window and `setup_s`.

And two rules that this reader adds to `obs.scopes.scope_table`, whose own
rule (a fusion is its root's) the CNN cells' `step.*` metrics keep as it was
(:func:`lm_scope_table`): XLA:TPU makes `lax.ragged_dot` a grouped-matmul
kernel and its tile metadata, names both itself (`ragged-dot-none.2`) and
drops the scope, so they are read as `moe_experts` by instruction name; and
a fusion whose root the compiler made and named itself (a split
`reduce_sum`, a rewritten gather) takes the listed scope most of its
members carry. `lm.unscoped_share.train` is the share after both; the table
line gives the share under the plain rule beside it
(`unscoped_share_pct_plain_rule`).

The seven metrics partition the step's op time: attn_core; dense (attn_proj +
mlp + mtp_merge: the plain matmuls); moe_experts; moe_route (router +
dispatch + combine); head_loss (lm_head + loss); optim (+ grad_sync, ema);
and everything else (embed, norm, rope, residual adds, compiler-made copies)
is in none of the six times but in `lm.unscoped_share.train` only as far as
it resolved to NO listed scope. The whole scope x phase table goes out on an
earlier stdout line, `step_scopes_lm`, as commentary.

Returns None, and the line leaves the metric out, where there is nothing to
read: no device plane (a CPU rehearsal), no whole step in the stretch, or a
program without obs/scopes.py or without the token-model family (the parent
commit of the PR that added it cannot even run the cell).
"""

from __future__ import annotations

import time

from benchmark import harness, trace_reduce
from benchmark.layer_metrics import step_scopes_train

# metric -> the scopes it sums, every phase
METRICS = {
    "lm.attn_core_ms.train": ("attn_core",),
    "lm.dense_ms.train": ("attn_proj", "mlp", "mtp_merge"),
    "lm.moe_experts_ms.train": ("moe_experts",),
    "lm.moe_route_ms.train": ("moe_router", "moe_dispatch", "moe_combine"),
    "lm.head_loss_ms.train": ("lm_head", "loss"),
    "lm.optim_ms.train": ("optim", "grad_sync", "ema"),
}
UNSCOPED_SHARE = "lm.unscoped_share.train"
# what XLA:TPU names itself, scope dropped: instruction-name prefix -> scope (phase unknown)
COMPILER_NAMED = (("ragged-dot", "moe_experts"),)
# ops that CONTAIN the ops of their bodies on the `XLA Ops` line (the attention's and the loss's loops):
# their time is their bodies', which is counted there
CONTAINERS = ("while", "conditional", "call")


def lm_scope_table(scopes, text: str) -> tuple[dict, dict]:
    """(the table this reader sums by, `obs.scopes.scope_table`'s own): the
    module docstring's two rules on top of the program's."""
    parsed = scopes.parse_hlo(text)
    plain = scopes.scope_table(parsed)
    _, calls, _, members, _ = parsed
    table = dict(plain)
    for name, computation in calls.items():
        if table[name][0] == scopes.UNSCOPED:
            inside = [plain[m] for m in members.get(computation, ())
                      if plain.get(m, (scopes.UNSCOPED,))[0] != scopes.UNSCOPED]
            if inside:
                table[name] = max(sorted(set(inside)), key=inside.count)
    for names in members.values():
        for name in names:
            for prefix, scope_name in COMPILER_NAMED:
                if name.startswith(prefix) and table.get(name, (scopes.UNSCOPED, "-"))[0] == scopes.UNSCOPED:
                    table[name] = (scope_name, "-")
    return table, plain


def compiled_step_text(ctx) -> str:
    """The cell's train step as the runner builds it, as compiled HLO text."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.parallel import mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import steps

    from benchmark.runners import train_tokens_resident as runner

    cfg, net, mesh, optimizer, step_fn, batch, seq_len, _ = runner.build(ctx)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    key = harness.seed_key(ctx.seed)
    # shapes only: nothing is initialised or placed a second time
    ts = jax.jit(lambda k: steps.init_train_state(net, cfg, optimizer, harness.init_key(k)),
                 out_shardings=replicated).eval_shape(key)
    b = {"tokens": jax.ShapeDtypeStruct((batch, seq_len + 2), jnp.int32, sharding=sharded)}
    rng = jax.random.fold_in(jnp.asarray(key), 2)
    return step_fn.lower(ts, b, rng).compile().as_text()


def compute(ctx) -> dict | None:
    """{"metrics": {name: value}, "table": commentary} for this run, or None."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    try:
        from yet_another_mobilenet_series_tpu.models import lm  # noqa: F401
        from yet_another_mobilenet_series_tpu.obs import scopes
    except ImportError:
        return None  # a program from before the family: nothing to read
    found = step_scopes_train.step_ops(ctx.trace)
    if found is None:
        return None
    ops, n_steps, busy_s = found
    ops = [(name, duration) for name, duration in ops if trace_reduce.op_kind(name) not in CONTAINERS]
    t0 = time.perf_counter()
    table, plain = lm_scope_table(scopes, compiled_step_text(ctx))
    table_s = time.perf_counter() - t0
    by = scopes.time_by_scope(ops, table)
    total = sum(by.values())
    if total <= 0:
        return None
    per_step_ms = {key: v / n_steps / 1e6 for key, v in by.items()}
    metrics = {name: sum(v for (sc, _), v in per_step_ms.items() if sc in names)
               for name, names in METRICS.items()}
    metrics[UNSCOPED_SHARE] = 100.0 * scopes.unscoped_share(by)
    rows = sorted(per_step_ms.items(), key=lambda kv: -kv[1])
    return {"metrics": metrics, "table": {
        "device": 0, "whole_steps": n_steps, "table_build_s": table_s,
        "op_ms_per_step": total / n_steps / 1e6, "busy_ms_per_step": 1e3 * busy_s / n_steps,
        "unscoped_share_pct_plain_rule": 100.0 * scopes.unscoped_share(scopes.time_by_scope(ops, plain)),
        "ms_per_step": {f"{sc}.{ph}": ms for (sc, ph), ms in rows},
        "share_pct": {f"{sc}.{ph}": 100.0 * ms * n_steps * 1e6 / total for (sc, ph), ms in rows}}}


def metric(ctx, name: str):
    """One metric of this module by name; the table is computed, and printed
    as a commentary line, on the first call of a run."""
    if not hasattr(ctx, "step_scopes_lm"):
        ctx.step_scopes_lm = compute(ctx)
        if ctx.step_scopes_lm is not None:
            harness.emit({"step_scopes_lm": ctx.step_scopes_lm["table"]})
    return None if ctx.step_scopes_lm is None else ctx.step_scopes_lm["metrics"][name]

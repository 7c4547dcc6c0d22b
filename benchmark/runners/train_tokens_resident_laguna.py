"""Runner `train_tokens_resident_laguna`: runners/train_tokens_resident.py's
method for a `laguna` share (Laguna-S-2.1: sliding-window and full
grouped-query attention mixed 3:1 with per-head output gates and two rotary
embeddings, a dense first layer, then experts under a softmax router beside a
gated shared expert; no router state, one target a token): the program's full
train step on ONE device-resident batch of token ids, steps dispatched back to
back.

What is THAT runner's is used as it is: `build` (the recipe read from the app
the configuration names, the refusal where the app's shapes disagree with the
configuration file's, the step as `parallel/dp.py` makes it), `make_tokens`
(the Zipf ids from the seed) and `fingerprint`; its docstring says why the
cell holds 1e-6 from its first step. What is this architecture's is here: the
refusal where the app's `laguna` keys (`LAGUNA_KEYS`: the layer pattern, the
heads by layer, the window, the rotary embeddings, the shared expert) are not
the file's or the file names a layer without the per-head gate that the arch
always builds, the reference (benchmark/reference_laguna.py), the MAC count
(benchmark/macs_laguna.py), and no router bias to balance: a softmax router
holds none, and the train state is the seed's, as `cli/train.py` starts it.

Facts beside the GLM runner's (its `first_ce_mtp` is absent): `attn_sites`,
`attn_fused_sites`, `attn_window_sites`, `attn_window_fused_sites` (the
program's gauges `train.*`, read from its registry), and the expert layers'
load through the window (`moe_assignments_per_expert`,
`moe_load_max_over_mean`, `moe_dropped`, by step).

`correct`: every loss finite; the first cross-entropy within 3% of
ln(vocabulary) + hidden * init_std^2 / 2 (the untied head's logits over
unit-rms states); no window loss above 1.01 x the first and the last below
it; the step counter; no held assignment dropped; the parameter count; every
attention layer through the fused kernels and every sliding layer through
the window's (`attn_window_fused_sites` = the sliding layers); and, OUTSIDE
the window and `setup_s`, the plain float32 reference at the published widths
on the timed batch and the seed's initial parameters, under the program's
own expert selection (its `forward` on those parameters in the compute
dtype, once), against the FIRST timed-shape step: the loss, the gradient norm
of every parameter group, the share of the program's assignments the
reference's own top-k would not make, and the norm of what the step's
optimizer added to every parameter (that step run once more from the seed
after the window; the reference applies AdamW's first step, written out, to
its own gradients), each within its limit (reference_laguna.py `LIMITS`);
and the loss once more against the reference under its OWN selection, which
nothing the program made feeds, within the loss's limit (its routers'
gradient norms are reported beside it, `own_selection`, and not compared:
another selection moves them by more than rounding).
`BENCH_REFERENCE_LOWER=1` adds, as commentary, the same comparison for a
reference whose operands are rounded to float8_e4m3fn: how the limits were
set (it must fail one), never part of `correct`.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import time

from benchmark import harness
from benchmark.runners.train_tokens_resident import build, fingerprint, make_tokens

# configuration-file key -> the program's `model.lm` key of the same name: laguna's, which `build` does not know
LAGUNA_KEYS = ("layer_types", "num_attention_heads_per_layer", "num_key_value_heads", "head_dim", "sliding_window",
               "rope_parameters", "shared_expert_intermediate_size", "tie_word_embeddings")
GAUGES = ("attn_sites", "attn_fused_sites", "attn_window_sites", "attn_window_fused_sites", "moe_sites")


def laguna_keys(lm) -> dict:
    """The app's `LAGUNA_KEYS` as the configuration file writes them (lists, and the rotary groups as mappings)."""
    def plain(value):
        if dataclasses.is_dataclass(value):
            return {k: plain(v) for k, v in dataclasses.asdict(value).items()}
        return list(value) if isinstance(value, tuple) else value

    return {k: plain(getattr(lm, k)) for k in LAGUNA_KEYS}


def same_rope(app: dict, published: dict) -> bool:
    """The app's rotary groups carry every key of the published ones, each number equal."""
    return all(set(published[kind]) <= set(app[kind]) and all(
        float(app[kind][k]) == float(v) if not isinstance(v, str) else app[kind][k] == v
        for k, v in published[kind].items()) for kind in published)


def change_norms(net, key_data, params):
    """Inside jit: {"change/<leaf>": |params - the seed's initial parameters|}."""
    import jax

    from benchmark import reference_laguna as ref

    initial = net.init(harness.init_key(key_data))[0]
    return ref.leaf_norms(jax.tree.map(lambda now, was: now - was, params, initial), "change")


def reference_scalars(params, lm, tokens, rows_at_once, adamw: dict, chosen=None, operand_dtype=None):
    """({"loss", "gnorm/...", "change/<leaf>"}, {"selection/<block>"}) of the
    plain reference on `params`, a sequence at a time: the loss, gradient
    norms by group, the norm of what AdamW's first step (`adamw`: lr, b1, b2,
    eps, clip) adds to each parameter; and, of the assignments `chosen`
    ({expert block: (B * S, k) ids}; None = its own), the share its own
    top-k does not make."""
    import jax

    from benchmark import reference_laguna as ref

    d = ref.dims_of(lm, rows_at_once=rows_at_once, operand_dtype=operand_dtype)
    seq = lm.seq_len
    n_tokens = tokens.shape[0] * seq
    one = jax.jit(lambda p, ids, picked: ref.sequence_loss_and_grads(p, ids, d, n_tokens, picked))
    add = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y, a, b), donate_argnums=(0,))
    loss, differing, grads = 0.0, {}, None
    for row in range(tokens.shape[0]):
        picked = None if chosen is None else {k: v[row * seq:(row + 1) * seq] for k, v in chosen.items()}
        (part, (_, loads)), g = one(params, tokens[row], picked)
        loss = loss + float(part)
        differing = {k: differing.get(k, 0.0) + float(v[1]) for k, v in loads.items()}
        grads = g if grads is None else add(grads, g)
    norms = jax.jit(lambda p, g: {**ref.group_norms(g),
                                  **ref.leaf_norms(ref.adamw_first_step(p, g, **adamw), "change")})(params, grads)
    return ({"loss": loss, **{k: float(v) for k, v in jax.device_get(norms).items()}},
            {f"selection/{k}": v / (n_tokens * lm.num_experts_per_tok) for k, v in differing.items()})


def held_against(program: dict, shares: dict, reference: dict) -> dict:
    """The comparison's verdict: `program`'s scalars, and the shares of ITS
    assignments that the reference would make otherwise (against 0), within
    the reference module's limits."""
    from benchmark.reference_laguna import compare

    return compare({**program, **shares}, {**reference, **dict.fromkeys(shares, 0.0)})


def run(ctx) -> dict:
    """ctx: run.Context. Returns {"end_to_end": {...}, "facts": {...},
    "attempted", "failed", "correct", "t_window_start"}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.parallel import mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import steps

    from benchmark import macs_laguna
    from benchmark.reference_laguna import LIMITS

    config, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    ctx.phases.done("import_program")
    cfg, net, mesh, optimizer, step_fn, batch, seq_len, shapes = build(ctx)
    lm = cfg.model.lm
    mine = laguna_keys(lm)
    if not ctx.rehearsal:
        theirs = {k: config[k] for k in LAGUNA_KEYS}
        same = {k: v for k, v in mine.items() if k != "rope_parameters"} == {
            k: v for k, v in theirs.items() if k != "rope_parameters"}
        if not (same and same_rope(mine["rope_parameters"], theirs["rope_parameters"])
                and set(config["gating_types"]) == {"per_head"}):
            raise SystemExit(f"benchmark: {config['train_app']} now has {mine!r}; the configuration file says "
                             "otherwise: this cell measures the file's")
    per_chip = batch // chips
    parameters = net.param_count()
    macs_per_sequence = macs_laguna.forward_macs({**shapes, **mine}, seq_len, lm.n_routed_experts)
    ctx.phases.done("build_trainer")

    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    key = harness.seed_key(ctx.seed)
    init_state = jax.jit(lambda k: steps.init_train_state(net, cfg, optimizer, harness.init_key(k)),
                         out_shardings=replicated)
    ts = init_state(key)
    mark = jax.jit(fingerprint)(ts.params)
    jax.block_until_ready(mark)
    ctx.phases.done("init_state")
    b = jax.jit(lambda k: make_tokens(k, batch, seq_len, net.vocab, float(traffic["zipf_exponent"])),
                out_shardings=sharded)(key)
    jax.block_until_ready(b)
    ctx.phases.done("make_batch")

    rng = jax.random.fold_in(jnp.asarray(key), 2)  # the step takes a raw key; a token step draws nothing from it
    # compiled ahead of the first call so that the program's own account of its temporaries can be read
    step_fn = step_fn.lower(ts, b, rng).compile()
    program_temp_bytes = int(step_fn.memory_analysis().temp_size_in_bytes)
    ts, metrics = step_fn(ts, b, rng)
    first = {k: float(v) for k, v in jax.device_get(metrics).items()}  # the step the reference is held against
    ctx.phases.done("first_step")
    for _ in range(int(traffic.get("warm_steps", 1))):
        ts, metrics = step_fn(ts, b, rng)
    jax.block_until_ready(metrics["loss"])
    step0 = int(jax.device_get(ts.step))
    ctx.phases.done("warm_steps")

    # ---- the window -------------------------------------------------------
    sync_every = int(traffic.get("sync_every", 1))
    lag = int(traffic.get("sync_lag", 1))
    kept: list = []
    pending: collections.deque = collections.deque()
    spans = ctx.spans
    ctx.window_opens()
    t0 = time.perf_counter()
    n = 0
    while True:
        with spans.span("dispatch"):
            ts, metrics = step_fn(ts, b, rng)
        n += 1
        kept.append((metrics["loss"], metrics["moe_dropped"], metrics["moe_assignments_here"],
                     metrics["moe_load_max_over_mean"]))
        pending.append(metrics["loss"])
        if n % sync_every == 0:
            # the clock is read behind a sync `lag` steps back: the queue the device works from is never empty
            while len(pending) > lag + 1:
                pending.popleft()
            with spans.span("sync"):
                jax.block_until_ready(pending[0])
            elapsed = time.perf_counter() - t0
            ctx.tick(elapsed)
            if elapsed >= ctx.seconds:
                break
    with spans.span("sync"):
        jax.block_until_ready(metrics["loss"])
    t1 = time.perf_counter()
    ctx.window_closes()
    # ----------------------------------------------------------------------

    window_s = t1 - t0
    values = np.asarray(jax.device_get(kept), np.float64)
    losses, dropped = values[:, 0], values[:, 1]
    last = {k: float(v) for k, v in jax.device_get(metrics).items()}
    failed = int(np.sum(~np.isfinite(losses)))
    advanced = int(jax.device_get(ts.step)) - step0
    expect = math.log(net.vocab) + 0.5 * lm.hidden_size * lm.init_std ** 2
    gauges = {k: ctx.registry_after.get("train." + k) for k in GAUGES}
    sliding = sum(t == "sliding_attention" for t in lm.layer_types)
    checks = {
        "losses_finite": failed == 0 and all(math.isfinite(v) for v in first.values()),
        "first_loss_near_its_initial_value": abs(first["ce"] - expect) <= 0.03 * expect,
        # the same batch again and again: no loss above the first (1% for bfloat16's noise), the last below it
        "loss_not_above_first": bool(np.all(losses <= 1.01 * first["loss"]) and losses[-1] < first["loss"]),
        "step_counter_advanced_by_attempted": advanced == n,
        "no_assignment_dropped": bool(np.all(dropped == 0.0)) and first["moe_dropped"] == 0.0,
        "parameter_count_is_the_files": ctx.rehearsal or parameters == config["parameters_here"],
        "every_sliding_layer_is_counted": gauges["attn_window_sites"] == sliding > 0,
        "every_attention_site_is_fused": ctx.rehearsal or gauges["attn_fused_sites"] == gauges["attn_sites"] > 0,
        "every_window_site_is_fused": ctx.rehearsal or gauges["attn_window_fused_sites"] == sliding,
    }
    expert_blocks = net.expert_sites
    facts = {"first_loss": first["loss"], "first_ce": first["ce"], "expected_first_ce": expect,
             "last_loss": float(losses[-1]), "losses": losses.tolist(), "steps": n, "window_s": window_s,
             "global_batch": batch, "per_chip_batch": per_chip, "chips": chips, "arch": cfg.model.arch,
             "seq_len": seq_len, "tokens_per_step": batch * seq_len, "compute_dtype": cfg.train.compute_dtype,
             "parameters": parameters, "macs_per_image": macs_per_sequence,
             "program_temp_bytes": program_temp_bytes, "step_ms_host": 1e3 * window_s / n, **gauges,
             "moe_assignments_per_expert": last["moe_assignments_here"] / (expert_blocks * net.experts_held),
             "moe_load_max_over_mean": last["moe_load_max_over_mean"], "moe_dropped": float(np.sum(dropped)),
             "moe_assignments_here_by_step": [first["moe_assignments_here"], *values[:, 2].tolist()],
             "moe_load_max_over_mean_by_step": [first["moe_load_max_over_mean"], *values[:, 3].tolist()],
             "moe_bounded_sites": last["moe_bounded_sites"]}
    images_per_s_per_chip = n * per_chip / window_s
    facts["images_per_s_per_chip"] = images_per_s_per_chip
    facts["tokens_per_s"] = images_per_s_per_chip * chips * seq_len

    # ---- the reference, outside the window and setup_s --------------------
    del ts, metrics, kept, pending
    t_ref = time.perf_counter()
    # what the first step's optimizer added to every parameter: that step once more, from the seed
    ts, again = step_fn(init_state(key), b, rng)
    first.update({k: float(v) for k, v in jax.device_get(
        jax.jit(lambda k, p: change_norms(net, k, p))(key, ts.params)).items()})
    facts["first_loss_again"] = float(again["loss"])
    del ts, again  # the optimizer's moments go first: the reference does not fit beside them
    tokens = jax.device_get(b["tokens"])
    rows = int(traffic.get("reference_rows_at_once", 256))
    params = jax.jit(lambda k: net.init(harness.init_key(k))[0])(key)
    checks["reference_saw_the_programs_initial_parameters"] = bool(
        np.array_equal(jax.device_get(mark), jax.device_get(jax.jit(fingerprint)(params))))
    # the program's own selection on those parameters, which the reference computes under
    compute_dtype = jnp.dtype(cfg.train.compute_dtype)
    chosen = jax.jit(lambda p, ids: net.forward(p, {}, ids, compute_dtype=compute_dtype)[3])(params, b["tokens"])
    adamw = {"lr": cfg.schedule.base_lr, "b1": cfg.optim.adam_b1, "b2": cfg.optim.adam_b2, "eps": 1e-8,
             "clip": cfg.optim.grad_clip_norm}
    reference, shares = reference_scalars(params, lm, tokens, rows, adamw, chosen)
    verdict = held_against(first, shares, reference)
    checks["first_step_agrees_with_the_float32_reference"] = bool(verdict["ok"])
    # and under its OWN selection, which nothing of the program's feeds: the loss held to the same limit, the
    # routers' gradient norms beside it as commentary (another selection moves them by more than rounding)
    own, _ = reference_scalars(params, lm, tokens, rows, adamw)
    own_deviation = {k: abs(first[k] - v) / abs(v) for k, v in own.items() if k == "loss" or k.startswith("gnorm/") and k.endswith("/router")}
    checks["first_loss_agrees_with_the_reference_under_its_own_selection"] = own_deviation["loss"] <= LIMITS["loss"]
    facts["reference"] = {**verdict, "seconds": time.perf_counter() - t_ref, "rows_at_once": rows, "adamw": adamw,
                          "values": reference, "program": {k: first[k] for k in reference if k in first},
                          "own_selection": {"values": {k: own[k] for k in own_deviation}, "deviations": own_deviation}}
    if os.environ.get("BENCH_REFERENCE_LOWER") == "1":
        lower, lower_shares = reference_scalars(params, lm, tokens, rows, adamw, chosen,
                                                operand_dtype=jnp.float8_e4m3fn)
        would = held_against(lower, lower_shares, reference)
        facts["reference_float8_e4m3fn"] = {"fails": not would["ok"], "worst": would["worst"], "values": lower}
    facts["checks"] = checks
    return {"end_to_end": {"train_images_per_s_per_chip": images_per_s_per_chip},
            "facts": facts, "attempted": n, "failed": failed, "correct": all(checks.values()),
            "t_window_start": t0}

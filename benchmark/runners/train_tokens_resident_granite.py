"""Runner `train_tokens_resident_granite`: runners/train_tokens_resident.py's
method for a `granitemoehybrid` model (Granite 4.0-H: Mamba-2 state-space
mixers beside grouped-query attention without rotation, every MLP dense, the
scaling multipliers, the head tied to the embedding; no expert layer, no
router state, one target a token): the program's full train step on ONE
device-resident batch of token ids, steps dispatched back to back.

What is THAT runner's is used as it is: `build` (the recipe read from the app
the configuration names, the refusal where the app's shapes disagree with the
configuration file's, the step as `parallel/dp.py` makes it), `make_tokens`
(the Zipf ids from the seed) and `fingerprint`; its docstring says why the
cell holds 1e-6 from its first step. What is this architecture's is here: the
refusal where the app's hybrid keys (`HYBRID_KEYS`: the layer pattern, the
grouped heads, the multipliers, the tie, the Mamba-2 sizes) are not the
file's, the reference (benchmark/reference_granite.py), the MAC count
(benchmark/macs_granite.py), and no router bias to balance: the train state
is the seed's, as `cli/train.py` starts it.

Facts beside the GLM runner's (its `first_ce_mtp` and `moe_*` facts are
absent): `ssd_sites`, `ssd_kept_sites`, `ssd_conv_fused_sites`, `attn_sites`,
`attn_fused_sites` (the program's gauges `train.*`, read from its registry),
and the last window step's `ssd_min_chunk_log_decay` (the most negative
in-chunk cumulative Delta A of the step: a fact, the hazard the chunked form
must survive).

`correct`: every loss finite; the first cross-entropy within 3% of
ln(vocabulary) + hidden * init_std^2 / (2 logits_scaling^2) (the logits of a
tied head over unit-rms states are E rms(h) / s, of variance hidden
init_std^2 / s^2); no window loss above 1.01 x the first and the last below
it; the step counter; the parameter count; the attention layer through the
fused kernels and every Mamba-2 layer's convolution through the conv kernels
(`ssd_conv_fused_sites` = `ssd_sites`); and, OUTSIDE the window and
`setup_s`, the plain float32 reference at the published widths on the timed
batch and the seed's initial parameters against the FIRST timed-shape step:
the loss, the gradient norm of every parameter group by kind (Mamba mixers,
attention, MLP, tied vocabulary, norms) and the norm of what the step's
optimizer added to every parameter (that step run once more from the seed
after the window; the reference applies AdamW's first step, written out, to
its own gradients), each within its limit (reference_granite.py `LIMITS`).
`BENCH_REFERENCE_LOWER=1` adds, as commentary, the same comparison for a
reference whose operands are rounded to float8_e4m3fn: how the limits were
set (it must fail one), never part of `correct`.
"""

from __future__ import annotations

import collections
import math
import os
import time

from benchmark import harness
from benchmark.runners.train_tokens_resident import build, fingerprint, make_tokens

# configuration-file key -> the program's `model.lm` key of the same name: the hybrid's, which `build` does not know
HYBRID_KEYS = ("layer_types", "num_key_value_heads", "head_dim", "attention_multiplier", "embedding_multiplier",
               "residual_multiplier", "logits_scaling", "tie_word_embeddings", "mamba_n_heads", "mamba_d_head",
               "mamba_d_state", "mamba_d_conv", "mamba_chunk_size")
GAUGES = ("ssd_sites", "ssd_kept_sites", "ssd_conv_fused_sites", "attn_sites", "attn_fused_sites")


def change_norms(net, key_data, params):
    """Inside jit: {"change/<leaf>": |params - the seed's initial parameters|}."""
    import jax

    from benchmark import reference_granite as ref

    initial = net.init(harness.init_key(key_data))[0]
    return ref.leaf_norms(jax.tree.map(lambda now, was: now - was, params, initial), "change")


def reference_scalars(params, lm, tokens, rows_at_once, adamw: dict, operand_dtype=None) -> dict:
    """{"loss", "gnorm/...", "change/<leaf>"} of the plain reference on
    `params`, a sequence at a time: the loss, gradient norms by group, the
    norm of what AdamW's first step (`adamw`: lr, b1, b2, eps, clip) adds to
    each parameter."""
    import jax

    from benchmark import reference_granite as ref

    d = ref.dims_of(lm, rows_at_once=rows_at_once, operand_dtype=operand_dtype)
    n_tokens = tokens.shape[0] * lm.seq_len
    one = jax.jit(lambda p, ids: ref.sequence_loss_and_grads(p, ids, d, n_tokens))
    add = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y, a, b), donate_argnums=(0,))
    total = None
    for row in range(tokens.shape[0]):
        (loss, _), g = one(params, tokens[row])
        total = (loss, g) if total is None else add(total, (loss, g))
    loss, grads = total
    norms = jax.jit(lambda p, g: {**ref.group_norms(g),
                                  **ref.leaf_norms(ref.adamw_first_step(p, g, **adamw), "change")})(params, grads)
    return {k: float(v) for k, v in jax.device_get({"loss": loss, **norms}).items()}


def run(ctx) -> dict:
    """ctx: run.Context. Returns {"end_to_end": {...}, "facts": {...},
    "attempted", "failed", "correct", "t_window_start"}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.parallel import mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import steps

    from benchmark import macs_granite, reference_granite as ref

    config, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    ctx.phases.done("import_program")
    cfg, net, mesh, optimizer, step_fn, batch, seq_len, shapes = build(ctx)
    lm = cfg.model.lm
    hybrid = {k: list(v) if isinstance(v, tuple) else v for k, v in ((k, getattr(lm, k)) for k in HYBRID_KEYS)}
    if not ctx.rehearsal and hybrid != {k: config[k] for k in HYBRID_KEYS}:
        raise SystemExit(f"benchmark: {config['train_app']} now has {hybrid!r}; the configuration file says otherwise: "
                         "this cell measures the file's")
    per_chip = batch // chips
    parameters = net.param_count()
    macs_per_sequence = macs_granite.forward_macs({**shapes, **hybrid}, seq_len)
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
        kept.append(metrics["loss"])
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
    losses = np.asarray(jax.device_get(kept), np.float64)
    last = {k: float(v) for k, v in jax.device_get(metrics).items()}
    failed = int(np.sum(~np.isfinite(losses)))
    advanced = int(jax.device_get(ts.step)) - step0
    expect = math.log(net.vocab) + 0.5 * lm.hidden_size * lm.init_std ** 2 / lm.logits_scaling ** 2
    gauges = {k: ctx.registry_after.get("train." + k) for k in GAUGES}
    checks = {
        "losses_finite": failed == 0 and all(math.isfinite(v) for v in first.values()),
        "first_loss_near_its_initial_value": abs(first["ce"] - expect) <= 0.03 * expect,
        # the same batch again and again: no loss above the first (1% for bfloat16's noise), the last below it
        "loss_not_above_first": bool(np.all(losses <= 1.01 * first["loss"]) and losses[-1] < first["loss"]),
        "step_counter_advanced_by_attempted": advanced == n,
        "parameter_count_is_the_files": ctx.rehearsal or parameters == config["parameters_here"],
        "every_mamba_layer_is_counted": gauges["ssd_sites"] == gauges["ssd_kept_sites"]
                                        == sum(t == "mamba" for t in lm.layer_types) > 0,
        "every_attention_site_is_fused": ctx.rehearsal or gauges["attn_fused_sites"] == gauges["attn_sites"] > 0,
        "every_xbc_convolution_is_fused": ctx.rehearsal or gauges["ssd_conv_fused_sites"] == gauges["ssd_sites"],
    }
    facts = {"first_loss": first["loss"], "first_ce": first["ce"], "expected_first_ce": expect,
             "last_loss": float(losses[-1]), "losses": losses.tolist(), "steps": n, "window_s": window_s,
             "global_batch": batch, "per_chip_batch": per_chip, "chips": chips, "arch": cfg.model.arch,
             "seq_len": seq_len, "tokens_per_step": batch * seq_len, "compute_dtype": cfg.train.compute_dtype,
             "parameters": parameters, "macs_per_image": macs_per_sequence,
             "program_temp_bytes": program_temp_bytes, "step_ms_host": 1e3 * window_s / n,
             **gauges, "ssd_min_chunk_log_decay": last["ssd_min_chunk_log_decay"],
             "first_ssd_min_chunk_log_decay": first["ssd_min_chunk_log_decay"]}
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
    rows = int(traffic.get("reference_rows_at_once", 512))
    params = jax.jit(lambda k: net.init(harness.init_key(k))[0])(key)
    checks["reference_saw_the_programs_initial_parameters"] = bool(
        np.array_equal(jax.device_get(mark), jax.device_get(jax.jit(fingerprint)(params))))
    adamw = {"lr": cfg.schedule.base_lr, "b1": cfg.optim.adam_b1, "b2": cfg.optim.adam_b2, "eps": 1e-8,
             "clip": cfg.optim.grad_clip_norm}
    reference = reference_scalars(params, lm, tokens, rows, adamw)
    verdict = ref.compare(first, reference)
    checks["first_step_agrees_with_the_float32_reference"] = bool(verdict["ok"])
    facts["reference"] = {**verdict, "seconds": time.perf_counter() - t_ref, "rows_at_once": rows, "adamw": adamw,
                          "values": reference, "program": {k: first[k] for k in reference if k in first}}
    if os.environ.get("BENCH_REFERENCE_LOWER") == "1":
        lower = reference_scalars(params, lm, tokens, rows, adamw, operand_dtype=jnp.float8_e4m3fn)
        would = ref.compare(lower, reference)
        facts["reference_float8_e4m3fn"] = {"fails": not would["ok"], "worst": would["worst"], "values": lower}
    facts["checks"] = checks
    return {"end_to_end": {"train_images_per_s_per_chip": images_per_s_per_chip},
            "facts": facts, "attempted": n, "failed": failed, "correct": all(checks.values()),
            "t_window_start": t0}

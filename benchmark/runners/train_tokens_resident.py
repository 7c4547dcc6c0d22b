"""Runner `train_tokens_resident`: the program's full train step of a TOKEN
model (forward of both heads, backward, global-norm clip, AdamW, the routers'
bias update) on ONE device-resident batch of token ids, steps dispatched back
to back. No input pipeline: this is the compiled step's cell.

The method is runners/train_resident.py's: the recipe is READ from the app
file the configuration names and the runner refuses to run where the app's
shapes disagree with the configuration file's; the state and the batch are
made on the device from --seed in one jitted call each; the step is lowered
and compiled ahead for `program_temp_bytes`; the clock is read at a lagged
sync. It imports the program's step, optimizer, schedule, mesh and model
modules, never cli.train / ckpt / data.

Parameters (the traffic file): `sequences_per_chip`, `seq_len`,
`zipf_exponent` (ids are drawn by p(id) ~ (id + 1)^-exponent over the
vocabulary slice, each row one document of seq_len + 2 ids: the two heads'
targets are the next and the next-but-one id), `warm_steps`, `sync_every`,
`sync_lag` (a step is about a second, so 1 and 1), `trace_for_s`,
`reference_rows_at_once`, and a `rehearsal` group of toy values.

The configuration file holds the published `config.json` keys as they are run
(`n_routed_experts` = experts HELD, `vocab_size` = rows HELD), `published`
counts, `expert_shares`, `parameters_here`, `train_app`, `compute_dtype`, and
`overrides` laid over the app: the cell holds the learning rate that the
app's warm-up passes in the middle of such a window, 1e-6 (its 7th step),
from its first step (`schedule.warmup_epochs: 0`, `schedule.base_lr: 1e-6`).
The app's own first step has learning rate 0, and a step at 0 cannot show
whether the optimizer moved anything. A fresh model cannot be timed at a
larger one: at 3e-4 its first Adam steps overshoot (the loss read 13.3,
16.3, 13.8, ... 8.9), and already at 3e-5 ONE step moves every hidden state
by more than the routers' margins, so the routing collapses again whatever
biases it started from and the held experts' load, 150 to 2,000
assignments an expert, is the seed's luck through the window
(`train_images_per_s_per_chip` spread 1.07% over six seeds; PERF.md).

What a `--trace 1` reader finds in `facts`, and must keep finding:
`macs_per_image` (benchmark/macs_lm.py: MACs of ONE SEQUENCE, which is this
cell's "image") and `images_per_s_per_chip` (sequences a second), the two
facts layer_metrics/step_mfu_train.py reads; `tokens_per_s`;
`moe_assignments_per_expert`, `moe_load_max_over_mean`, `moe_dropped` (the
last window step's scalars); `reference` (the comparison below).

The weights are the seed's; the router biases are NOT zero, as `cli/train.py`
starts, but what a job that has been running holds, made at set-up by the
benchmark's reference from its own float32 scores of the timed batch
(reference_glm4_moe_lite.py `balanced_state`; no program code is involved:
the train state is handed them). With zeros, random weights send nearly
every token to the same four of the 64 experts, the load of the 8 held here
is the seed's luck (535 to 1,964 assignments an expert in six runs), and
`train_images_per_s_per_chip` spread by 0.77% over those seeds where half
its bound is 0.5% (PERF.md, PR 27).

`correct`: every loss finite; each head's first loss within 3% of its value
at initialisation, ln(vocabulary) + hidden * init_std^2 / 2 (logits of
variance hidden * init_std^2; the most frequent id is a tenth of the Zipf
targets and its logit is ONE draw of that variance, so a head's first loss
moves by about 1% with the seed; a head that ignores its input reads
ln(vocabulary), 4% below, and fails); no loss of the window above 1.01 x the
first and the last below it (the same batch again and again: it falls by
about a tenth, and an update with the wrong sign climbs); the step counter;
`train.moe_dropped` 0 in every step (counted in the step from the rows the
grouped matmul wrote); the parameter count; and, OUTSIDE the window and
`setup_s`, the plain float32 reference (benchmark/
reference_glm4_moe_lite.py) at the published widths on the timed batch, the
seed's initial parameters and the same biases against the FIRST timed-shape step:
both heads' loss, the gradient norm of every parameter group, the share of
the program's expert assignments that the reference would make otherwise,
and the norm of what the step's optimizer added to every parameter (that
step run once more from the seed after the window, against the seed's
parameters made again; the reference applies AdamW's first step, written
out, to its own gradients), each within its limit. The train state is released first
(reference and parameters do not fit beside the optimizer's moments) and the
initial parameters are made again from the seed; a fingerprint taken before
the first step proves they are the same numbers. The program's selection is
its `forward` on those parameters in the compute dtype, once.
`BENCH_REFERENCE_LOWER=1` adds, as commentary, the same comparison for a
reference whose matmul operands are rounded to float8_e4m3fn: how the limits
were set (it must fail one), never part of `correct`.
"""

from __future__ import annotations

import collections
import math
import os
import time

from benchmark import harness

# configuration-file key -> how the program's config says the same thing
APP_KEYS = ("hidden_size", "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor", "num_nextn_predict_layers",
            "rms_norm_eps", "rope_theta", "num_hidden_layers")


def build(ctx):
    """(cfg, net, mesh, optimizer, step_fn, batch, seq_len, shapes): the
    cell's train step exactly as this runner times it (layer_metrics/
    step_scopes_lm.py builds the same to read the compiled step's names), and
    the program's shapes under the configuration file's keys."""
    import jax

    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import optim, schedules

    config, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    batch = int(traffic["sequences_per_chip"]) * chips
    seq_len = int(traffic["seq_len"])
    overrides = {"train.batch_size": batch, "dist.num_devices": chips, "model.lm.seq_len": seq_len,
                 **config.get("overrides", {})}
    cfg = harness.load_app_config(config["train_app"], overrides)
    lm = cfg.model.lm
    have = {**{k: getattr(lm, k) for k in APP_KEYS}, "n_routed_experts": lm.n_routed_experts // lm.expert_shares,
            "vocab_size": cfg.model.num_classes, "model_type": cfg.model.arch, "expert_shares": lm.expert_shares,
            "compute_dtype": cfg.train.compute_dtype}
    for key, value in have.items():
        if not ctx.rehearsal and config[key] != value:
            raise SystemExit(f"benchmark: {config['train_app']} now has {key}={value!r}, the configuration "
                             f"file says {config[key]!r}: this cell measures the file's")
    if not ctx.rehearsal and lm.n_routed_experts != config["published"]["n_routed_experts"]:
        raise SystemExit("benchmark: the router's width is not the published number of routed experts")
    net = get_model(cfg.model)
    mesh = mesh_lib.make_mesh(chips, devices=ctx.devices)
    steps_per_epoch = max(cfg.data.fake_train_size // batch, 1)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, batch, steps_per_epoch, cfg.train.epochs)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step_fn = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh, params_example=params_example)
    return cfg, net, mesh, optimizer, step_fn, batch, seq_len, have


def make_tokens(key_data, batch: int, seq_len: int, vocab: int, exponent: float):
    """Inside jit: (batch, seq_len + 2) ids by the Zipf law, from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    cdf = jnp.asarray(np.cumsum(weights / weights.sum()), jnp.float32)
    u = jax.random.uniform(jax.random.fold_in(harness.init_key(key_data), 1), (batch, seq_len + 2), jnp.float32)
    return {"tokens": jnp.minimum(jnp.searchsorted(cdf, u, side="right"), vocab - 1).astype(jnp.int32)}


def fingerprint(params):
    """One float32 a leaf: the same numbers give the same bits."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sum(leaf * jnp.cos(jnp.arange(leaf.size, dtype=jnp.float32)).reshape(leaf.shape))
                      for leaf in jax.tree.leaves(params)])


def change_norms(net, key_data, params):
    """Inside jit: {"change/<leaf>": |params - the seed's initial parameters|},
    the initial parameters made again here (nothing is kept beside the train
    state for it)."""
    import jax

    from benchmark import reference_glm4_moe_lite as ref

    initial = net.init(harness.init_key(key_data))[0]
    return ref.leaf_norms(jax.tree.map(lambda now, was: now - was, params, initial), "change")


def reference_scalars(params, state, lm, tokens, rows_at_once, adamw: dict, chosen=None, operand_dtype=None):
    """({"ce", "ce_mtp", "gnorm/...", "change/<leaf>"}, {"selection/<block>"})
    of the plain reference on `params` and the router biases `state`, a
    sequence at a time: losses, gradient norms by group, the norm of what AdamW's first
    step (`adamw`: lr, b1, b2, eps, clip) adds to each parameter; and, of the
    assignments `chosen` ({expert block: (B * S, k) ids}; None = its own), the
    share its own top-k does not make."""
    import jax

    from benchmark import reference_glm4_moe_lite as ref

    d = ref.dims_of(lm, rows_at_once=rows_at_once, operand_dtype=operand_dtype)
    seq = lm.seq_len
    n_tokens = tokens.shape[0] * seq
    # the biases are an ARGUMENT: closed over they would be constants of the program, and every seed a new compile
    one = jax.jit(lambda p, s, ids, picked: ref.sequence_loss_and_grads(p, s, ids, d, n_tokens, picked))
    add = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y, a, b), donate_argnums=(0,))
    ce = ce_mtp = 0.0
    differing = dict.fromkeys(state, 0.0)
    grads = None
    for row in range(tokens.shape[0]):
        picked = None if chosen is None else {k: v[row * seq:(row + 1) * seq] for k, v in chosen.items()}
        (_, (ce_row, ce_mtp_row, loads)), g = one(params, state, tokens[row], picked)
        ce, ce_mtp = ce + float(ce_row) / n_tokens, ce_mtp + float(ce_mtp_row) / n_tokens
        differing = {k: differing[k] + float(loads[k][1]) for k in differing}
        grads = g if grads is None else add(grads, g)
    norms = jax.jit(lambda p, g: {**ref.group_norms(g),
                                  **ref.leaf_norms(ref.adamw_first_step(p, g, **adamw), "change")})(params, grads)
    return ({"ce": ce, "ce_mtp": ce_mtp, **{k: float(v) for k, v in jax.device_get(norms).items()}},
            {f"selection/{k}": v / (n_tokens * lm.num_experts_per_tok) for k, v in differing.items()})


def held_against(program: dict, shares: dict, reference: dict) -> dict:
    """The comparison's verdict: `program`'s scalars, and the shares of ITS
    assignments that the reference would make otherwise (against 0), within
    the reference module's limits."""
    from benchmark.reference_glm4_moe_lite import compare

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

    from benchmark import macs_lm

    config, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    ctx.phases.done("import_program")
    cfg, net, mesh, optimizer, step_fn, batch, seq_len, shapes = build(ctx)
    lm = cfg.model.lm
    per_chip = batch // chips
    parameters = net.param_count()
    macs_per_sequence = macs_lm.forward_macs(shapes, seq_len, lm.n_routed_experts)
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
    # the router biases a running job holds, made by the REFERENCE from its own float32 scores of the
    # timed batch (its balanced_state says why zeros will not do); the program is handed them as state
    from benchmark import reference_glm4_moe_lite as ref

    d = ref.dims_of(lm, rows_at_once=int(traffic.get("reference_rows_at_once", 1024)))
    state0 = jax.device_get(jax.jit(lambda p, ids: ref.balanced_state(p, ids, d))(ts.params, b["tokens"]))
    if set(state0) != set(ts.state):
        raise SystemExit(f"benchmark: the reference balanced {sorted(state0)}, the program holds {sorted(ts.state)}")
    ts = ts.replace(state=jax.device_put(state0, replicated))  # a copy: the step donates its state
    ctx.phases.done("reference_balances_router_bias")

    rng = jax.random.fold_in(jnp.asarray(key), 2)  # the step takes a raw key; a token step draws nothing from it
    # compiled ahead of the first call so that the program's own account of
    # its temporaries can be read (harness.device_facts)
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
            # the clock is read behind a sync `lag` steps back: the queue the
            # device works from is never empty
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
    heads = [first["ce"]] + ([first["ce_mtp"]] if lm.num_nextn_predict_layers else [])
    expert_blocks = lm.num_hidden_layers - lm.first_k_dense_replace + lm.num_nextn_predict_layers
    checks = {
        "losses_finite": failed == 0 and all(math.isfinite(v) for v in first.values()),
        "first_loss_near_its_initial_value": all(abs(v - expect) <= 0.03 * expect for v in heads),
        # the same batch again and again: no loss above the first (1% for bfloat16's noise), the last
        # below it (by about a tenth at this learning rate; an update with the wrong sign climbs)
        "loss_not_above_first": bool(np.all(losses <= 1.01 * first["loss"]) and losses[-1] < first["loss"]),
        "step_counter_advanced_by_attempted": advanced == n,
        "no_assignment_dropped": bool(np.all(dropped == 0.0)) and first["moe_dropped"] == 0.0,
        "parameter_count_is_the_files": ctx.rehearsal or parameters == config["parameters_here"],
    }
    facts = {"first_loss": first["loss"], "first_ce": first["ce"], "first_ce_mtp": first.get("ce_mtp"),
             "expected_first_ce": expect, "last_loss": float(losses[-1]), "losses": losses.tolist(), "steps": n,
             "window_s": window_s,
             "global_batch": batch, "per_chip_batch": per_chip, "chips": chips, "arch": cfg.model.arch,
             "seq_len": seq_len, "tokens_per_step": batch * seq_len, "compute_dtype": cfg.train.compute_dtype,
             "parameters": parameters, "macs_per_image": macs_per_sequence,
             "program_temp_bytes": program_temp_bytes, "step_ms_host": 1e3 * window_s / n,
             "moe_assignments_per_expert": last["moe_assignments_here"] / (expert_blocks * net.experts_held),
             "moe_load_max_over_mean": last["moe_load_max_over_mean"], "moe_dropped": float(np.sum(dropped)),
             # how the routing held through the window: the first step's and every window step's
             "moe_assignments_here_by_step": [first["moe_assignments_here"], *values[:, 2].tolist()],
             "moe_load_max_over_mean_by_step": [first["moe_load_max_over_mean"], *values[:, 3].tolist()]}
    images_per_s_per_chip = n * per_chip / window_s
    facts["images_per_s_per_chip"] = images_per_s_per_chip
    facts["tokens_per_s"] = images_per_s_per_chip * chips * seq_len

    # ---- the reference, outside the window and setup_s --------------------
    del ts, metrics, kept, pending
    t_ref = time.perf_counter()
    # what the first step's optimizer added to every parameter: that step once more, from the seed
    # (the window's state is a dozen steps on, and nothing was kept beside it)
    ts, again = step_fn(init_state(key).replace(state=jax.device_put(state0, replicated)), b, rng)
    first.update({k: float(v) for k, v in jax.device_get(
        jax.jit(lambda k, p: change_norms(net, k, p))(key, ts.params)).items()})
    facts["first_loss_again"] = float(again["loss"])
    del ts, again  # the optimizer's moments go first: the reference does not fit beside them
    tokens = jax.device_get(b["tokens"])
    rows = int(traffic.get("reference_rows_at_once", 1024))
    params = jax.jit(lambda k: net.init(harness.init_key(k))[0])(key)
    checks["reference_saw_the_programs_initial_parameters"] = bool(
        np.array_equal(jax.device_get(mark), jax.device_get(jax.jit(fingerprint)(params))))
    # the program's own selection on those parameters, which the reference computes under
    compute_dtype = jnp.dtype(cfg.train.compute_dtype)
    chosen = jax.jit(lambda p, s, ids: net.forward(p, s, ids, compute_dtype=compute_dtype)[3])(
        params, state0, b["tokens"])
    adamw = {"lr": cfg.schedule.base_lr, "b1": cfg.optim.adam_b1, "b2": cfg.optim.adam_b2, "eps": 1e-8,
             "clip": cfg.optim.grad_clip_norm}
    reference, shares = reference_scalars(params, state0, lm, tokens, rows, adamw, chosen)
    verdict = held_against(first, shares, reference)
    checks["first_step_agrees_with_the_float32_reference"] = bool(verdict["ok"])
    facts["reference"] = {**verdict, "seconds": time.perf_counter() - t_ref, "rows_at_once": rows, "adamw": adamw,
                          "values": reference, "program": {k: first[k] for k in reference}}
    if os.environ.get("BENCH_REFERENCE_LOWER") == "1":
        lower, lower_shares = reference_scalars(params, state0, lm, tokens, rows, adamw, chosen,
                                                operand_dtype=jnp.float8_e4m3fn)
        would = held_against(lower, lower_shares, reference)
        facts["reference_float8_e4m3fn"] = {"fails": not would["ok"], "worst": would["worst"], "values": lower}
    facts["checks"] = checks
    return {"end_to_end": {"train_images_per_s_per_chip": images_per_s_per_chip},
            "facts": facts, "attempted": n, "failed": failed, "correct": all(checks.values()),
            "t_window_start": t0}

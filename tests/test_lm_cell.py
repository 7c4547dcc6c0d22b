"""The token training cell `glm47flash_train_2x8k`, the heavy half (CPU only,
nothing timed): its `--rehearsal` run and last line, and the benchmark's copy
of the reference (benchmark/reference_glm4_moe_lite.py) against the package's
(models/lm_reference.py), with the comparison's limits against a lower
precision. The light half is tests/benchmark_tests/test_glm_cell.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness, reference_glm4_moe_lite as bench_ref  # noqa: E402
from benchmark.runners import train_tokens_resident as runner  # noqa: E402

CELL = "glm47flash_train_2x8k"


def toy():
    """(TokenModel, LMConfig) at the configuration's own rehearsal sizes."""
    from yet_another_mobilenet_series_tpu.models import get_model

    with open(os.path.join(REPO, "benchmark", "configs", "glm_4_7_flash_ep8_share.json")) as f:
        config = harness.with_rehearsal(json.load(f), True)
    cfg = harness.load_app_config(config["train_app"], {**config["overrides"], "model.lm.seq_len": 32})
    return get_model(cfg.model), cfg.model.lm


# -- the rehearsal run ---------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 29),
                           "--seconds", "1", "--trace", "0", "--rehearsal"], cwd=REPO, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_last_line_keys_and_checks(rehearsal):
    last = rehearsal[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"train_images_per_s_per_chip", "setup_s"}
    assert last["device"]["platform"] == "cpu"
    run = next(ln["run"] for ln in rehearsal if "run" in ln)
    assert all(run["checks"].values()) and set(run["checks"]) >= {
        "losses_finite", "first_loss_near_its_initial_value", "loss_not_above_first", "no_assignment_dropped",
        "step_counter_advanced_by_attempted", "reference_saw_the_programs_initial_parameters",
        "first_step_agrees_with_the_float32_reference", "no_compile_in_window"}
    assert run["moe_dropped"] == 0.0 and run["tokens_per_step"] == 64 and run["seq_len"] == 32
    # the two facts step_mfu_train.py reads, and the sequence as the "image"
    assert run["macs_per_image"] > 0 and run["images_per_s_per_chip"] * 32 == pytest.approx(run["tokens_per_s"])
    assert run["reference"]["ok"] and set(run["reference"]["worst"]) == set(bench_ref.LIMITS)
    notes = next(ln for ln in rehearsal if "setup_phases" in ln)
    assert notes["compile_window"]["compiles"] == 0 and notes["heavy_imports"] == []



def test_the_benchmarks_reference_is_the_packages_and_its_limits_catch_float8():
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.models import lm_reference as package_ref

    net, lm = toy()
    params, state = net.init(jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, lm.seq_len + 2), 0, net.vocab)
    (loss, aux), grads = jax.jit(lambda p: package_ref.loss_and_grads(p, state, tokens, package_ref.dims_of(lm)))(params)
    want = {"ce": aux["ce"], "ce_mtp": aux["ce_mtp"], **bench_ref.group_norms(grads)}
    adamw = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "clip": 1.0}

    def scalars(operand_dtype, rows, chosen=None):
        lm_rows = types.SimpleNamespace(**{k: getattr(lm, k) for k in bench_ref.DIM_KEYS}, seq_len=lm.seq_len)
        return runner.reference_scalars(params, state, lm_rows, tokens, rows, adamw, chosen, operand_dtype)

    own, shares = scalars(None, 8)  # in row blocks, a sequence at a time: the same numbers
    same = bench_ref.compare(own, want)
    assert same["ok"] and max(same["deviations"].values()) < 1e-4, same
    assert set(want) == set(net.grad_scalars(grads)) | {"ce", "ce_mtp"}  # the step reports every compared group
    assert set(shares) == {"selection/layer_1", "selection/layer_2", "selection/mtp"} and not any(shares.values())
    assert sum(k.startswith("change/") for k in own) == len(jax.tree.leaves(params))
    # the program's selection fed back: its own float32 forward chooses as the reference does
    chosen = jax.jit(lambda p: net.forward(p, state, tokens)[3])(params)
    fed, shares = scalars(None, 8, chosen)
    assert runner.held_against(fed, shares, own)["ok"] and not any(shares.values())
    # a selection that sends every token's first pick elsewhere is counted, and moves the experts' gradients
    moved = {k: v.at[:, 0].set((v[:, 0] + 1) % lm.n_routed_experts) for k, v in chosen.items()}
    other, shares = scalars(None, 8, moved)
    assert min(shares.values()) > 0.2
    elsewhere = runner.held_against(other, shares, own)
    assert not elsewhere["ok"] and elsewhere["worst"]["selection"][1] > bench_ref.LIMITS["selection"]
    low = bench_ref.compare(scalars(jnp.float8_e4m3fn, None)[0], want)
    assert not low["ok"], low  # the nearest precision below bfloat16 fails at least one limit
    assert not bench_ref.compare({k: 0.0 for k in want}, want)["ok"]
    assert not bench_ref.compare({k: v for k, v in want.items() if k != "gnorm/head"}, want)["ok"]


def test_the_comparison_sees_an_optimizer_that_did_not_move_the_parameters():
    """The cell's first step at the toy size, as the runner takes it: what the
    optimizer added to every parameter against the reference's AdamW step
    from its own gradients. Sound, every kind passes; a state LEFT UNCHANGED
    (the parameters after the step are the parameters before it), or moved
    twice as far, fails `change` and nothing else."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.train import steps

    with open(os.path.join(REPO, "benchmark", "configs", "glm_4_7_flash_ep8_share.json")) as f:
        config = harness.with_rehearsal(json.load(f), True)
    with open(os.path.join(REPO, "benchmark", "traffic", "train_tokens_resident_2x8k.json")) as f:
        traffic = harness.with_rehearsal(json.load(f), True)
    ctx = types.SimpleNamespace(config=config, traffic=traffic, chips=1, devices=jax.devices()[:1], rehearsal=True)
    cfg, net, _, optimizer, step_fn, batch, seq_len, _ = runner.build(ctx)
    key = harness.seed_key(7)
    ts = steps.init_train_state(net, cfg, optimizer, harness.init_key(key))
    params = jax.tree.map(jnp.copy, ts.params)  # the step donates its state
    b = runner.make_tokens(key, batch, seq_len, net.vocab, 1.0)
    d = bench_ref.dims_of(cfg.model.lm, rows_at_once=16)
    state0 = jax.device_get(jax.jit(lambda p, ids: bench_ref.balanced_state(p, ids, d))(params, b["tokens"]))
    ts, metrics = step_fn(ts.replace(state=jax.tree.map(jnp.asarray, state0)), b, jax.random.PRNGKey(0))
    scalars = {k: float(v) for k, v in metrics.items()}
    assert scalars["lr"] == pytest.approx(cfg.schedule.base_lr)  # the cell's first step has a learning rate
    change = jax.jit(lambda k, p: runner.change_norms(net, k, p))
    moved = {k: float(v) for k, v in change(key, ts.params).items()}
    chosen = jax.jit(lambda p: net.forward(p, state0, b["tokens"], compute_dtype=jnp.bfloat16)[3])(params)
    adamw = {"lr": cfg.schedule.base_lr, "b1": cfg.optim.adam_b1, "b2": cfg.optim.adam_b2, "eps": 1e-8,
             "clip": cfg.optim.grad_clip_norm}
    reference, shares = runner.reference_scalars(params, state0, cfg.model.lm, jax.device_get(b["tokens"]), 16, adamw,
                                                 chosen)

    def failing(change_norms):
        worst = runner.held_against({**scalars, **change_norms}, shares, reference)["worst"]
        return {kind for kind, (_, dev) in worst.items() if not dev <= bench_ref.LIMITS[kind]}, worst

    assert failing(moved)[0] == set(), failing(moved)[1]
    unchanged = {k: float(v) for k, v in change(key, params).items()}  # the parameters as they were before the step
    kinds, worst = failing(unchanged)
    assert kinds == {"change"} and worst["change"][1] == 1.0  # what a state left unchanged reads
    assert failing({k: 2.0 * v for k, v in moved.items()})[0] == {"change"}


def test_the_references_balanced_biases_spread_the_programs_load():
    """The cell's router biases are made by the REFERENCE from its own scores
    (`balanced_state`), never by program code: over all experts its loads come
    out even, block after block, and the program, handed them as its state,
    routes as evenly, where zeros leave a few experts with most tokens."""
    import jax
    import jax.numpy as jnp

    net, lm = toy()
    params, zeros = net.init(jax.random.PRNGKey(3))
    tokens = runner.make_tokens(harness.seed_key(5), 2, lm.seq_len, net.vocab, 1.0)["tokens"]
    d = bench_ref.dims_of(lm, rows_at_once=8)
    state = jax.jit(lambda p, ids: bench_ref.balanced_state(p, ids, d))(params, tokens)
    assert set(state) == set(zeros) and all(v["router_bias"].shape == (lm.n_routed_experts,) for v in state.values())

    def fullest_over_mean(biases):
        """Over ALL experts, worst block: the reference's own counts of one sequence each, summed."""
        loads = [bench_ref.sequence_cross_entropy(params, biases, ids, d)[2] for ids in tokens]
        return max(float(jnp.max(a[0] + b[0]) / jnp.mean(a[0] + b[0])) for a, b in zip(*(ld.values() for ld in loads)))

    assert fullest_over_mean(zeros) > 2.0 and fullest_over_mean(state) < 1.5
    held = lambda biases: float(net.forward(params, biases, tokens)[2]["moe_load_max_over_mean"])  # noqa: E731
    assert held(state) < 1.5

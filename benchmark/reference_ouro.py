"""The plain float32 reference of an `ouro` model (Ouro-2.6B, a LOOPED language
model) for the comparison that decides `correct` in its training cell: the
loss, every loop step's cross-entropy, the exit statistics, gradient norms by
parameter group (the exit gate's among them) and the change AdamW's first step
makes to every parameter, at the published widths, on the timed batch and the
seed's initial parameters.

A copy of the `ouro_*` equations of yet_another_mobilenet_series_tpu/models/
lm_reference.py (a tier-1 test holds the two equal at a toy size), kept here so
that no later PR can move the yardstick by moving the program. Straightforward
`jax.numpy` in float32 under `default_matmul_precision("highest")`:

- a block is the SANDWICH: `y += N(Attn(N(y)))`, `y += N(MLP(N(y)))`, four
  gains; attention is three plain projections, every channel of q and k
  rotated (pairs (i, i + d/2), theta from the file), a dense causal mask;
- the loop runs `total_ut_steps` times over the SAME parameters, the final
  norm INSIDE it (step r + 1 reads the normed state); the cross-entropy and
  the exit probability `g_r = sigmoid(x_r w + b)` after every step;
- the exit distribution is the product written out (`p_1 = g_1`, `p_r = g_r
  prod_{j<r}(1 - g_j)`, `p_R = prod_{j<R}(1 - g_j)`; `g_R` is not read), the
  loss a token `sum_r p_r CE_r - beta H(p)`, the step's its mean.

RMSNorm, RoPE, the gated MLP, the matmul with its optional rounding, AdamW's
first step written out and the norms by leaf are the functions of benchmark/
reference_glm4_moe_lite.py themselves, imported: the archs share them in the
program too, and nothing of the program's is in them.

Three things are added so that 8,192 tokens at the published widths fit
beside the parameters on one chip, none of which changes a number:
`rows_at_once` (attention and the head go through their rows a block at a
time, each block still seeing ALL keys under the dense mask's rows); a
`jax.checkpoint` around every block application and every block of rows; and
the loop over the steps is ONE `lax.scan` body where the package's reference
has a Python `for`, so that the backward adds each use's weight gradients as
it goes (written out, XLA holds all 32 applications' float32 contributions
until the end: 12.5 GiB of temporaries, compiled for a v5e, and the chip
refused it beside the parameters).

ASSUMED, where `config.json` leaves it to the code (each is a line of the
configuration file's `assumed`): the four norms' placement; the final norm
inside the loop; the gate one row of h weights and a bias; beta; no bias on a
projection; plain multi-head attention (`num_key_value_heads` =
`num_attention_heads`).

`operand_dtype` rounds BOTH operands of every matmul (the gate's row too) to a
lower precision (float8_e4m3fn is the nearest below the configuration's
bfloat16) and is how the comparison's limits were set: such a step must fail
one of them (PERF.md; the runner's `BENCH_REFERENCE_LOWER=1`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference_glm4_moe_lite import (  # noqa: F401 - `mm`, `gated_mlp`: this module's API too
    Sizes, adamw_first_step, gated_mlp, leaf_norms, mm, rms_norm, rope, row_step)

DIM_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "total_ut_steps", "exit_entropy_weight")


def dims_of(lm_config, rows_at_once: int | None = None, operand_dtype=None) -> Sizes:
    """The sizes the reference reads, from the program's `model.lm` section or anything shaped like it."""
    return Sizes({k: getattr(lm_config, k) for k in DIM_KEYS}, rows_at_once=rows_at_once,
                 operand_dtype=operand_dtype)


def attention(p, x, d):
    """One sequence x (S, h) through plain multi-head attention."""
    seq = x.shape[0]
    heads, width = d["num_attention_heads"], d["head_dim"]
    q, k, v = (mm(x, p[n], d).reshape(seq, heads, width) for n in ("q", "k", "v"))
    q, k = rope(q, d["rope_theta"]), rope(k, d["rope_theta"])
    k_t, v_t = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (heads, D, S), (heads, S, D)

    def rows(q_rows, first):
        scores = mm(q_rows.transpose(1, 0, 2), k_t, d) / math.sqrt(width)  # (heads, rows, S)
        mask = (jnp.arange(seq)[None, :] <= first + jnp.arange(q_rows.shape[0])[:, None])  # rows of the dense S x S mask
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return mm(probs, v_t, d).transpose(1, 0, 2).reshape(q_rows.shape[0], heads * width)

    step = row_step(seq, d)  # the same rows a block at a time, as ONE loop body
    out = jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs), (q.reshape(seq // step, step, heads, width),
                                                              jnp.arange(0, seq, step)))
    return mm(out.reshape(seq, heads * width), p["o"], d)


def block(p, y, d):
    eps = d["rms_norm_eps"]
    y = y + rms_norm(attention(p["attn"], rms_norm(y, p["attn_norm"], eps), d), p["attn_out_norm"], eps)
    m = p["mlp"]
    return y + rms_norm(gated_mlp(m["gate"], m["up"], m["down"], rms_norm(y, p["mlp_norm"], eps), d),
                        p["mlp_out_norm"], eps)


def head_nll(head, hidden, targets, d):
    """Every token's cross-entropy, (S,), of (S, h) hidden states against (S,) targets."""
    def rows(hid, tgt):
        logits = mm(hid, head, d)
        return jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(tgt.shape[0]), tgt]

    n = hidden.shape[0]
    step = row_step(n, d)
    return jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs),
                       (hidden.reshape(n // step, step, -1), targets.reshape(n // step, step))).reshape(n)


def exit_distribution(gates):
    """[g_1..g_R], each (S,) -> [p_1..p_R]."""
    p, reached = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        p.append(g * reached)
        reached = reached * (1.0 - g)
    return p + [reached]


def sequence_loss(params, ids, d):
    """One row of S + 2 ids (the last is not read) -> (the sequence's SUMMED
    loss, {"ce_step": [R sums], "exit_entropy", "exit_p_last",
    "expected_exit_step": sums over its tokens})."""
    with jax.default_matmul_precision("highest"):
        seq = ids.shape[0] - 2
        run = jax.checkpoint(block, static_argnums=(2,))
        targets = ids[1:seq + 1]

        def loop_step(x, _):  # the SAME parameters every time
            for i in range(d["num_hidden_layers"]):
                x = run(params[f"layer_{i}"], x, d)
            x = rms_norm(x, params["final_norm"], d["rms_norm_eps"])  # inside the loop
            gate = jax.nn.sigmoid(mm(x, params["exit_gate"]["w"][:, None], d)[:, 0] + params["exit_gate"]["b"])
            return x, (head_nll(params["head"], x, targets, d), gate)

        _, (nll, gates) = jax.lax.scan(loop_step, params["embed"][ids[:seq]], None, length=d["total_ut_steps"])
        nll, gates = list(nll), list(gates)
        p = exit_distribution(gates)
        entropy = -sum(jnp.where(q > 0, q * jnp.log(jnp.where(q > 0, q, 1.0)), 0.0) for q in p)
        loss = jnp.sum(sum(q * c for q, c in zip(p, nll)) - d["exit_entropy_weight"] * entropy)
        return loss, {"ce_step": [jnp.sum(c) for c in nll], "exit_entropy": jnp.sum(entropy),
                      "exit_p_last": jnp.sum(p[-1]),
                      "expected_exit_step": jnp.sum(sum((r + 1) * q for r, q in enumerate(p)))}


def sequence_loss_and_grads(params, ids, d, n_tokens: int):
    """One sequence's part of the batch loss and of its gradients: ((loss /
    n_tokens, the sums above), gradients by parameter). Sum over the batch's
    sequences."""
    def loss(p):
        total, sums = sequence_loss(p, ids, d)
        return total / n_tokens, sums

    return jax.value_and_grad(loss, has_aux=True)(params)


def scalars_of(loss, sums: dict, n_tokens: int) -> dict:
    """The step's scalars under the program's names, from the batch's summed `sequence_loss`es."""
    return {"loss": loss, "exit_entropy": sums["exit_entropy"] / n_tokens, "exit_p_last": sums["exit_p_last"] / n_tokens,
            "expected_exit_step": sums["expected_exit_step"] / n_tokens,
            **{f"ce_step_{r + 1}": c / n_tokens for r, c in enumerate(sums["ce_step"])}}


def group_norms(grads: dict) -> dict:
    """Gradient norms under the names of the step's `gnorm/...` scalars
    (models/lm.py `TokenModel.grad_scalars`): embed, head, final_norm,
    exit_gate, and per block attn, mlp and norms (the sandwich's four gains)."""
    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))

    out = {f"gnorm/{k}": norm(grads[k]) for k in ("embed", "head", "final_norm", "exit_gate")}
    for name, g in grads.items():
        if name.startswith("layer_"):
            out[f"gnorm/{name}/attn"], out[f"gnorm/{name}/mlp"] = norm(g["attn"]), norm(g["mlp"])
            out[f"gnorm/{name}/norms"] = norm([v for k, v in g.items() if k.endswith("norm")])
    return out


# The limits. Each is |program - reference| / |reference|, on the chip, at the
# published widths, and lies between two readings (PR 35's builder, TPU v5
# lite; PERF.md section 4): the largest the bfloat16 program gave over 18 runs
# of 10 seeds, and what this reference gives with float8_e4m3fn operands
# (`operand_dtype`), the nearest precision below (seed 2400000011): it fails
# every limit but `exit`'s, most of all the gradients, which underflow.
LIMITS = {
    # loss = E_exit[CE] - beta H: a mean over 8,192 tokens of four losses that are ~ln(vocabulary) at
    # initialisation, under a gate that is exactly (1/2, 1/4, 1/8, 1/8) on both sides: rounding of the logits
    # averages out. bfloat16 3.6e-5 to 8.9e-5; float8 8.9e-4
    "loss": 3e-4,
    # one loop step's cross-entropy, worst step. bfloat16 <= 2.2e-4; float8 3.7e-3
    "ce_step": 8e-4,
    # the fresh gate's exit distribution is the same constants in any precision: the entropy, the last
    # step's probability and the expected exit step read 9.8e-8 (float32 rounding) in bfloat16 AND 0.0 in
    # float8. No reading to lie between: this one holds a program whose gate is not where the file says it starts
    "exit": 1e-4,
    # norms of sums over 8,192 tokens x 4 uses of bfloat16 products. bfloat16 <= 2.3e-3; float8 1.18
    "gnorm": 2e-2,
    # the gate's gradient is sum_tokens (dloss/dlogit_r) x_r, and dloss/dlogit_r is a DIFFERENCE of the steps'
    # cross-entropies (plus the entropy's constant): four nearly equal losses at initialisation, so the
    # cancellation keeps bfloat16's rounding of the logits in it, and it moves with the seed.
    # bfloat16 5.9e-3 to 3.5e-2; float8 1.02
    "gnorm_exit_gate": 1.5e-1,
    # the norm of what the first optimizer step added to a parameter, worst leaf (a norm's 2,048 gains).
    # AdamW's first step is lr * g / (|g| + eps) element by element, so the precision of g hardly moves it
    # (float8's gradients underflow to 0, so it reads 1 all the same); a state left unchanged reads 1.
    # bfloat16 <= 1.1e-3
    "change": 1e-1,
}


def kind_of(name: str) -> str:
    if name.startswith("change/"):
        return "change"
    if name.startswith("ce_step_"):
        return "ce_step"
    if name.startswith("exit_") or name == "expected_exit_step":
        return "exit"
    if name.startswith("gnorm/"):
        return "gnorm_exit_gate" if name == "gnorm/exit_gate" else "gnorm"
    return name


def compare(program: dict, reference: dict) -> dict:
    """`program`: the first timed-shape step's scalars and `change/<leaf>`
    (the norm of what that step added to each parameter); `reference`: the
    same names from the functions above. -> {"ok", "worst": {kind: [name,
    deviation]}, "limits", "deviations"}."""
    deviations = {}
    for name, ref in reference.items():
        if name not in program:
            return {"ok": False, "missing": name}
        ref = float(ref)
        deviations[name] = abs(float(program[name]) - ref) / max(abs(ref), 1e-30)
    worst: dict = {}
    for name, dev in deviations.items():
        kind = kind_of(name)
        if kind not in worst or not dev <= worst[kind][1]:
            worst[kind] = [name, dev]
    ok = all(math.isfinite(dev) and dev <= LIMITS[kind] for kind, (_, dev) in worst.items())
    # a reference whose gradients or whose step vanish proves nothing
    ok = ok and all(float(v) > 0 for k, v in reference.items() if k.startswith(("gnorm/", "change/")))
    return {"ok": ok, "worst": worst, "limits": LIMITS, "deviations": deviations}

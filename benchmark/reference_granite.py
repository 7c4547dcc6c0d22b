"""The plain float32 reference of a `granitemoehybrid` stage (Granite 4.0-H
Micro) for the comparison that decides `correct` in its training cell: the
loss, gradient norms by parameter group and the change AdamW's first step
makes to every parameter, at the published widths, on the timed batch and the
seed's initial parameters.

A copy of the `granite_*` equations of yet_another_mobilenet_series_tpu/
models/lm_reference.py (a tier-1 test holds the two equal at a toy size), kept
here so that no later PR can move the yardstick by moving the program.
Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`:

- **the Mamba-2 mixer is its RECURRENCE, token by token** (`mamba`), in the
  Hugging Face module's order: in_proj -> [z | xBC | dt]; the causal
  depthwise convolution as `taps` shifted multiplies plus the bias, SiLU;
  Delta = softplus(dt + dt_bias); every head's (64 x 128) state decayed by
  e^{Delta A} and written with Delta x B^T one position after another in a
  `lax.scan`, read with C, plus D x; RMSNorm(y * SiLU(z)) over all heads'
  channels; out_proj. Nothing is chunked, no decay is multiplied up over a
  chunk, so there is nothing to overflow and nothing to clamp;
- attention (`attention`): q, k, v three projections, query head i reading
  key/value head i // (heads / kv heads) (the key/value heads indexed, not
  repeated by the program's means), no rotation, scores times
  `attention_multiplier`, a dense causal mask;
- the embedding times `embedding_multiplier`; each branch times
  `residual_multiplier` before its residual add; the logits E^T N(x) /
  `logits_scaling` with the head the embedding itself.

RMSNorm, the gated MLP, the matmul with its optional rounding, AdamW's first
step written out and the norms by leaf are the functions of benchmark/
reference_glm4_moe_lite.py themselves, imported: the archs share them in the
program too, and nothing of the program's is in them.

Two things are added so that 8,192 tokens at the published widths fit beside
the parameters on one chip, neither of which changes a number: `rows_at_once`
(attention and the head go through their rows a block at a time, each block
still seeing ALL keys under the dense mask's rows; the recurrence's scan is
two scans, the outer over blocks of `rows_at_once` positions, each a
`jax.checkpoint`, the state carried through both: the same positions in the
same order) and a `jax.checkpoint` around every layer.

ASSUMED, where `config.json` leaves it to the code (each is a line of the
configuration file's `assumed`): the gated norm one group, the gate before
the norm; no `time_step_limit` clamp; the MLP's [a | b] order.

`operand_dtype` rounds BOTH operands of every matmul AND of the recurrence's
two products (the write Delta x B^T and the read S C) to a lower precision
(float8_e4m3fn is the nearest below the configuration's bfloat16) and is how
the comparison's limits were set: such a step must fail one of them (PERF.md;
the runner's `BENCH_REFERENCE_LOWER=1`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference_glm4_moe_lite import (  # noqa: F401 - `mm`, `gated_mlp`: this module's API too
    Sizes, adamw_first_step, gated_mlp, leaf_norms, mm, rms_norm, row_step)

DIM_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling")


def dims_of(lm_config, rows_at_once: int | None = None, operand_dtype=None) -> Sizes:
    """The sizes the reference reads, from the program's `model.lm` section or anything shaped like it."""
    return Sizes({k: getattr(lm_config, k) for k in DIM_KEYS}, rows_at_once=rows_at_once,
                 operand_dtype=operand_dtype)


def low(x, d):
    """x as a product's operand: rounded to `operand_dtype` and back where one is set."""
    return x if d["operand_dtype"] is None else x.astype(d["operand_dtype"]).astype(jnp.float32)


def mamba(p, x, d):
    """One sequence x (S, h) through a Mamba-2 mixer, as the recurrence."""
    seq = x.shape[0]
    heads, width, n = d["mamba_n_heads"], d["mamba_d_head"], d["mamba_d_state"]
    inner = heads * width
    proj = mm(x, p["in_proj"], d)
    z, xbc, dt = proj[:, :inner], proj[:, inner:2 * inner + 2 * n], proj[:, 2 * inner + 2 * n:]
    taps = p["conv"].shape[0]
    total = p["conv_bias"] + jnp.zeros_like(xbc)
    for i in range(taps):
        back = taps - 1 - i
        total = total + p["conv"][i] * jnp.concatenate([jnp.zeros_like(xbc[:back]), xbc[:seq - back]], axis=0)
    xbc = jax.nn.silu(total)
    xs, b, c = xbc[:, :inner].reshape(seq, heads, width), xbc[:, inner:inner + n], xbc[:, inner + n:]
    delta = jax.nn.softplus(dt + p["dt_bias"])  # (S, heads)
    a = -jnp.exp(p["A_log"])

    def token(state, inputs):  # state (heads, width, n)
        x_t, b_t, c_t, dt_t = inputs
        write = low(dt_t[:, None] * x_t, d)[:, :, None] * low(b_t, d)[None, None, :]
        state = jnp.exp(dt_t * a)[:, None, None] * state + write
        return state, jnp.einsum("hpn,n->hp", low(state, d), low(c_t, d)) + p["D"][:, None] * x_t

    def rows(state, inputs):  # a block of positions, in order, the state carried in and out
        return jax.lax.scan(token, state, inputs)

    step = row_step(seq, d)
    blocks = tuple(t.reshape(seq // step, step, *t.shape[1:]) for t in (xs, b, c, delta))
    _, y = jax.lax.scan(jax.checkpoint(rows), jnp.zeros((heads, width, n), jnp.float32), blocks)
    y = rms_norm(y.reshape(seq, inner) * jax.nn.silu(z), p["norm"], d["rms_norm_eps"])
    return mm(y, p["out_proj"], d)


def attention(p, x, d):
    """One sequence x (S, h) through grouped-query attention without rotation."""
    seq = x.shape[0]
    heads, kv, width = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    reads = jnp.arange(heads) // (heads // kv)
    q = mm(x, p["q"], d).reshape(seq, heads, width)
    k, v = (mm(x, p[n], d).reshape(seq, kv, width)[:, reads] for n in ("k", "v"))
    k_t, v_t = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (heads, D, S), (heads, S, D)

    def rows(q_rows, first):
        scores = mm(q_rows.transpose(1, 0, 2), k_t, d) * d["attention_multiplier"]  # (heads, rows, S)
        mask = (jnp.arange(seq)[None, :] <= first + jnp.arange(q_rows.shape[0])[:, None])  # rows of the dense S x S mask
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return mm(probs, v_t, d).transpose(1, 0, 2).reshape(q_rows.shape[0], heads * width)

    step = row_step(seq, d)  # the same rows a block at a time, as ONE loop body
    out = jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs), (q.reshape(seq // step, step, heads, width),
                                                              jnp.arange(0, seq, step)))
    return mm(out.reshape(seq, heads * width), p["o"], d)


def block(p, x, d):
    eps, r = d["rms_norm_eps"], d["residual_multiplier"]
    mixed = rms_norm(x, p["attn_norm"], eps)
    x = x + r * (mamba(p["mamba"], mixed, d) if "mamba" in p else attention(p["attn"], mixed, d))
    m = p["mlp"]
    return x + r * gated_mlp(m["gate"], m["up"], m["down"], rms_norm(x, p["mlp_norm"], eps), d)


def head_cross_entropy(embed, hidden, targets, d):
    """Summed cross-entropy of (S, h) hidden states against (S,) targets, the
    logits hidden E^T / `logits_scaling` (the head is the embedding)."""
    def rows(hid, tgt):
        logits = mm(hid, embed.T, d) / d["logits_scaling"]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(tgt.shape[0]), tgt])

    n = hidden.shape[0]
    step = row_step(n, d)
    return jnp.sum(jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs),
                               (hidden.reshape(n // step, step, -1), targets.reshape(n // step, step))))


def sequence_cross_entropy(params, ids, d):
    """One row of S + 2 ids (the last is not read) -> its summed cross-entropy."""
    with jax.default_matmul_precision("highest"):
        seq = ids.shape[0] - 2
        run = jax.checkpoint(block, static_argnums=(2,))
        x = params["embed"][ids[:seq]] * d["embedding_multiplier"]
        for i in range(d["num_hidden_layers"]):
            x = run(params[f"layer_{i}"], x, d)
        return head_cross_entropy(params["embed"], rms_norm(x, params["final_norm"], d["rms_norm_eps"]),
                                  ids[1:seq + 1], d)


def sequence_loss_and_grads(params, ids, d, n_tokens: int):
    """One sequence's part of the batch loss and of its gradients: (CE /
    n_tokens, CE sum), gradients by parameter. Sum over the batch's sequences."""
    def loss(p):
        ce = sequence_cross_entropy(p, ids, d)
        return ce / n_tokens, ce

    return jax.value_and_grad(loss, has_aux=True)(params)


def group_norms(grads: dict) -> dict:
    """Gradient norms under the names of the step's `gnorm/...` scalars
    (models/lm.py `TokenModel.grad_scalars`): embed (the tied vocabulary: both
    uses' gradients summed), final_norm, and per block its mixer (`mamba` or
    `attn`), mlp and norms (the block's two pre-norm gains)."""
    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))

    out = {f"gnorm/{k}": norm(grads[k]) for k in ("embed", "final_norm")}
    for name, g in grads.items():
        if name.startswith("layer_"):
            for part in ("mamba", "attn", "mlp"):
                if part in g:
                    out[f"gnorm/{name}/{part}"] = norm(g[part])
            out[f"gnorm/{name}/norms"] = norm([v for k, v in g.items() if k.endswith("norm")])
    return out


# The limits. Each is |program - reference| / |reference|, on the chip, at the
# published widths, and each but the loss's lies between two readings (chip
# runs on a TPU v5 lite; PERF.md section 4): the largest the bfloat16 program
# gave over 12 runs of 12 seeds, and what this reference gives with
# float8_e4m3fn operands (`operand_dtype`), the nearest precision below (seeds
# 3900000023, 3900000101, 3900000104): it fails every limit but the loss's,
# most of all the gradients, which underflow.
LIMITS = {
    # NOT a precision limit: a sanity bound on the loss's value (a wrong model, mask or head reads far above it).
    # A mean over 8,192 tokens of a loss that is ~ln(vocabulary) at initialisation: rounding of the logits averages
    # out, in float8 too, so no upper reading exists (bfloat16 <= 5.2e-6; float8 6.1e-6 to 3.1e-5, under 3 times
    # the sound maximum on some seeds). Held at the accepted token cells' limit; the gradient kinds catch float8
    "loss": 5e-4,
    # norms of sums over 8,192 tokens of bfloat16 products; a Mamba-2 mixer's group holds the chunked SSD's
    # in-chunk products (bfloat16 operands) where the recurrence has none. bfloat16 <= 6.7e-4; float8 0.77 to 0.78
    "gnorm_mamba": 1e-2,
    # bfloat16 <= 2.7e-4 (the kernels at 128 / 128, v filled); float8 0.84 to 0.87
    "gnorm_attn": 1e-2,
    # bfloat16 <= 4.6e-4; float8 0.85
    "gnorm_mlp": 1e-2,
    # the tied vocabulary: both uses' gradients summed. bfloat16 <= 2.1e-4; float8 0.40 to 0.41
    "gnorm_embed": 1e-2,
    # a block's two gains, the final norm: 2,048 numbers a group. bfloat16 <= 1.3e-3; float8 0.998
    "gnorm_norms": 2e-2,
    # the norm of what the first optimizer step added to a parameter, worst leaf (a Mamba-2 layer's `dt_bias` or
    # `A_log`: 64 numbers, where one sign that rounds the other way shows). AdamW's first step is lr * g / (|g| + eps)
    # element by element, so the precision of g hardly moves it (float8's gradients underflow to 0, so it reads 1 all
    # the same); a state left unchanged reads 1. The more room above the reading, which fresh seeds move:
    # bfloat16 4.0e-3 to 1.75e-2
    "change": 2e-1,
}


def kind_of(name: str) -> str:
    if name.startswith("change/"):
        return "change"
    if not name.startswith("gnorm/"):
        return name
    last = name.rsplit("/", 1)[1]
    if last in ("mamba", "attn", "mlp", "embed"):
        return "gnorm_" + last
    return "gnorm_norms"  # a block's two gains, the final norm


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

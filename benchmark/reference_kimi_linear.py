"""The plain float32 reference of a `kimi_linear` share (Kimi-Linear-48B-A3B)
for the comparison that decides `correct` in its training cell: the loss,
gradient norms by parameter group and the change AdamW's first step makes to
every parameter, at the published widths, on the timed batch and the seed's
initial parameters.

A copy of the `kimi_linear` equations of yet_another_mobilenet_series_tpu/
models/lm_reference.py (a tier-1 test holds the two equal at a toy size), kept
here so that no later PR can move the yardstick by moving the program. The
equations are the same, straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`:

- **Kimi Delta Attention is its RECURRENCE, token by token** (`kda`): the
  state of every head is decayed channel by channel, read with the key, written
  with the key and the corrected value, read with the query, one position
  after another in a `lax.scan`. Nothing is chunked, no decay is ever
  multiplied up over a chunk, so there is nothing to overflow and nothing to
  clamp. The short convolution is `taps` shifted multiplies.
- latent attention (`mla`) has q as ONE projection and rotates nothing
  (`q_lora_rank: null`, `mla_use_nope`): per-head keys with the one shared
  64-channel head repeated, a dense causal mask;
- the expert layer (a loop over the held experts in which every expert sees
  every token under a 0/1 x score weight), the gated MLP, the head's
  cross-entropy and AdamW's first step written out are the functions of
  benchmark/reference_glm4_moe_lite.py themselves, imported: the two archs
  share them in the program too, and nothing of the program's is in them.

Two things are added so that 16,384 tokens at the published widths fit beside
the parameters on one chip, neither of which changes a number:

- `rows_at_once`: attention and the output head go through their rows a block
  at a time (each block still sees ALL keys under the dense mask's rows), and
  the recurrence's scan is two scans, the outer over blocks of `rows_at_once`
  positions, each a `jax.checkpoint` (the state is carried through both:
  the same positions in the same order);
- every layer and every block of rows is a `jax.checkpoint`.

Which mixer a layer has is read from the parameter tree the program's
`TokenModel.init` made (a `kda` group, or an `attn` group), not from a list.

ASSUMED, where `config.json` and the paper's text leave it to the code (each
is a line of the configuration file's `assumed`): the low-rank width of the
decay gate and of the output gate is the head dim; no bias on any projection
or convolution; SiLU after the convolution; L2 norm of q and k over a head's
channels with eps 1e-6 inside the root; q scaled by head_dim^-0.5; the output
norm's gain shared by the heads and its gate a sigmoid; the decay
`-exp(A_log) softplus(f + dt_bias)`.

`chosen`, `balanced_state`, `operand_dtype`: as benchmark/
reference_glm4_moe_lite.py says. With `operand_dtype` BOTH operands of every
matmul AND of the recurrence's three products are rounded.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# What the two token archs share is ONE copy in the benchmark, as it is one in the program (`ops/lm.py`): the
# matmul with its optional rounding, RMSNorm, the gated MLP, the expert layer of a share, a block's second half,
# the head's cross-entropy in row blocks, AdamW's first step written out, the norms by leaf.
from benchmark.reference_glm4_moe_lite import (  # noqa: F401 - `mm`, `gated_mlp`, `experts`: this module's API too
    Sizes, adamw_first_step, experts, fed_forward, gated_mlp, head_cross_entropy, leaf_norms, mm, rms_norm, row_step)

DIM_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "rms_norm_eps", "expert_shares", "expert_share_index")
L2_EPS = 1e-6


def dims_of(lm_config, rows_at_once: int | None = None, operand_dtype=None) -> Sizes:
    """The sizes the reference reads, from the program's `model.lm` section
    (n_routed_experts is the ROUTER's width there) or anything shaped like it."""
    return Sizes({k: getattr(lm_config, k) for k in DIM_KEYS}, rows_at_once=rows_at_once,
                 operand_dtype=operand_dtype)


def low(x, d):
    """x as a matmul operand: rounded to `operand_dtype` and back where one is set."""
    return x if d["operand_dtype"] is None else x.astype(d["operand_dtype"]).astype(jnp.float32)


def mla(p, x, d):
    """One sequence x (S, h) through multi-head latent attention: q ONE
    projection, nothing rotated (the 64 `rope` channels stay, one head of them
    shared by all the keys)."""
    seq = x.shape[0]
    heads, nope, rope_d, v_d = (d["num_attention_heads"], d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                                d["v_head_dim"])
    q = mm(x, p["q"], d).reshape(seq, heads, nope + rope_d)
    kv_a = mm(x, p["kv_a"], d)
    c_kv = rms_norm(kv_a[:, :d["kv_lora_rank"]], p["kv_norm"], d["rms_norm_eps"])
    kv = mm(c_kv, p["kv_b"], d).reshape(seq, heads, nope + v_d)
    shared = jnp.repeat(kv_a[:, None, d["kv_lora_rank"]:], heads, axis=1)  # one head, shared by all
    k = jnp.concatenate([kv[..., :nope], shared], axis=-1)
    v = kv[..., nope:]
    k_t, v_t = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (heads, D, S), (heads, S, Dv)

    def rows(q_rows, first):
        scores = mm(q_rows.transpose(1, 0, 2), k_t, d) / math.sqrt(nope + rope_d)  # (heads, rows, S)
        mask = (jnp.arange(seq)[None, :] <= first + jnp.arange(q_rows.shape[0])[:, None])  # rows of the dense S x S mask
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return mm(probs, v_t, d).transpose(1, 0, 2).reshape(q_rows.shape[0], heads * v_d)

    step = row_step(seq, d)  # the same rows a block at a time, as ONE loop body
    out = jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs), (q.reshape(seq // step, step, heads, nope + rope_d),
                                                              jnp.arange(0, seq, step)))
    return mm(out.reshape(seq, heads * v_d), p["o"], d)


def short_conv(z, w):
    """z (S, D), one filter of `taps` weights a channel, w (taps, D): SiLU(sum_i
    w_i z_{t - (taps-1) + i}), positions before the document's first read 0."""
    taps = w.shape[0]
    total = jnp.zeros_like(z)
    for i in range(taps):
        back = taps - 1 - i
        total = total + w[i] * jnp.concatenate([jnp.zeros_like(z[:back]), z[:z.shape[0] - back]], axis=0)
    return jax.nn.silu(total)


def kda(p, x, d):
    """One sequence x (S, h) through Kimi Delta Attention, as the recurrence:
    S'_t = Diag(alpha_t) S_{t-1}; S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;
    o_t = S_t^T q_t / sqrt(head_dim); a head's state is (key, value)."""
    seq = x.shape[0]
    heads = p["A_log"].shape[0]
    width = p["q"].shape[1] // heads
    by_head = lambda z: z.reshape(seq, heads, width)  # noqa: E731
    q, k, v = (by_head(short_conv(mm(x, p[n], d), p["conv_" + n])) for n in ("q", "k", "v"))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    log_decay = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        by_head(mm(mm(x, p["f_a"], d), p["f_b"], d) + p["dt_bias"]))
    beta = jax.nn.sigmoid(mm(x, p["b"], d))  # (S, heads)

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", low(state, d), low(k_t, d))
        state = state + b_t[:, None, None] * low(k_t, d)[:, :, None] * low(v_t - read, d)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", low(state, d), low(q_t, d)) / math.sqrt(width)

    def rows(state, xs):  # a block of positions, in order, the state carried in and out
        return jax.lax.scan(token, state, xs)

    step = row_step(seq, d)
    blocks = tuple(t.reshape(seq // step, step, *t.shape[1:]) for t in (q, k, v, log_decay, beta))
    _, out = jax.lax.scan(jax.checkpoint(rows), jnp.zeros((heads, width, width), jnp.float32), blocks)
    out = out.reshape(seq, heads, width)
    gate = jax.nn.sigmoid(by_head(mm(mm(x, p["g_a"], d), p["g_b"], d)))
    out = rms_norm(out, p["o_norm"], d["rms_norm_eps"]) * gate
    return mm(out.reshape(seq, heads * width), p["o"], d)


def mixed(p, x, d):
    normed = rms_norm(x, p["attn_norm"], d["rms_norm_eps"])
    return x + (kda(p["kda"], normed, d) if "kda" in p else mla(p["attn"], normed, d))


def block(p, bias, x, d, dense, chosen=None):
    return fed_forward(p, bias, mixed(p, x, d), d, dense, chosen)


def balanced_state(params, tokens, d, rounds: int = 200) -> dict:
    """The router biases of a job that has been RUNNING on such batches, where
    a fresh model holds zeros: {expert block: {"router_bias": (E,)}}. One
    forward pass of THIS reference over the batch (tokens (B, S + 2)), block
    after block, each expert block first moving its bias by `rounds` rounds of
    the architecture's own sign rule on its float32 scores of the whole batch,
    at a rate falling geometrically from 0.1 to 1e-4, then routing by it.
    (Why zeros will not do: reference_glm4_moe_lite.py `balanced_state`; a
    share of 8 of 256 experts makes the held load more the seed's luck, not
    less.)"""
    k, eps = d["num_experts_per_tok"], d["rms_norm_eps"]

    def balance(scores):
        def one_round(i, bias):
            rate = 0.1 * 1e-3 ** (i / max(rounds - 1, 1))
            _, own = jax.lax.top_k(scores + bias, k)
            load = jax.nn.one_hot(own, scores.shape[-1]).sum(axis=(0, 1))
            return bias + rate * jnp.sign(jnp.mean(load) - load)

        return jax.lax.fori_loop(0, rounds, one_round, jnp.zeros((scores.shape[-1],), jnp.float32))

    def through(p, xs, dense, state, name):
        xs = jax.lax.map(lambda x: mixed(p, x, d), xs)  # a sequence at a time
        if dense:
            return jax.lax.map(lambda x: fed_forward(p, None, x, d, True)[0], xs)
        scores = jax.lax.map(lambda x: jax.nn.sigmoid(mm(rms_norm(x, p["mlp_norm"], eps), p["router"], d)), xs)
        bias = balance(scores.reshape(-1, scores.shape[-1]))
        state[name] = {"router_bias": bias}
        return jax.lax.map(lambda x: fed_forward(p, bias, x, d, False)[0], xs)

    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1] - 2
        state: dict = {}
        xs = params["embed"][tokens[:, :seq]]
        for i in range(d["num_hidden_layers"]):
            xs = through(params[f"layer_{i}"], xs, i < d["first_k_dense_replace"], state, f"layer_{i}")
        return state


def sequence_cross_entropy(params, state, ids, d, chosen=None):
    """One row of S + 2 ids (the last is not read: there is one head) ->
    (summed CE of the head, by expert block (assignments per expert,
    assignments of `chosen` that differ from this reference's own)).
    `chosen`: {expert block: (S, k) expert ids} to compute under, or None."""
    with jax.default_matmul_precision("highest"):
        seq = ids.shape[0] - 2
        run = jax.checkpoint(block, static_argnums=(3, 4))
        loads = {}
        pick = (lambda name: None) if chosen is None else chosen.get
        x = params["embed"][ids[:seq]]
        for i in range(d["num_hidden_layers"]):
            name = f"layer_{i}"
            dense = i < d["first_k_dense_replace"]
            x, load = run(params[name], None if dense else state[name]["router_bias"], x, d, dense, pick(name))
            if load is not None:
                loads[name] = load
        ce = head_cross_entropy(params["head"], rms_norm(x, params["final_norm"], d["rms_norm_eps"]),
                                ids[1:seq + 1], d)
        return ce, loads


def sequence_loss_and_grads(params, state, ids, d, n_tokens: int, chosen=None):
    """One sequence's part of the batch loss and of its gradients: (CE /
    n_tokens, (CE sum, loads)), gradients by parameter. Sum over the batch's
    sequences."""
    def loss(p):
        ce, loads = sequence_cross_entropy(p, state, ids, d, chosen)
        return ce / n_tokens, (ce, loads)

    return jax.value_and_grad(loss, has_aux=True)(params)


def group_norms(grads: dict) -> dict:
    """Gradient norms under the names of the step's `gnorm/...` scalars
    (models/lm.py `TokenModel.grad_scalars`): embed, head, final_norm, and per
    block its mixer (`kda` or `attn`), mlp, router, shared, experts and norms
    (the block's two pre-norm gains)."""
    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))

    out = {f"gnorm/{k}": norm(grads[k]) for k in ("embed", "head", "final_norm")}
    for name, g in grads.items():
        if isinstance(g, dict):
            for part in ("attn", "kda", "mlp", "router", "shared", "experts"):
                if part in g:
                    out[f"gnorm/{name}/{part}"] = norm(g[part])
            out[f"gnorm/{name}/norms"] = norm([v for k, v in g.items() if k.endswith("norm")])
    return out


# The limits. Each is |program - reference| / reference (`selection`: the share
# itself), on the chip, at the published widths, and lies between two readings
# (PR 33's builder, TPU v5 lite; PERF.md section 4): the largest the bfloat16
# program gave over 9 runs of 9 seeds from the balanced biases, and what this
# reference gives with float8_e4m3fn operands (`operand_dtype`), the nearest
# precision below, under the same selection (seed 1000000007): it fails every
# limit but `ce`'s, most of all the gradients, which underflow.
LIMITS = {
    # a mean over 16,384 tokens of a loss that is ~ln(vocabulary) at
    # initialisation: rounding of the logits averages out, in float8 too.
    # bfloat16 <= 3.7e-5; float8 3.7e-5: this one alone does not catch it (the
    # accepted token cell's limit, 13 times the reading)
    "ce": 5e-4,
    # norms of sums over 16,384 tokens of bfloat16 products, the reference
    # computing under the program's selection. bfloat16 <= 1.5e-3; float8 1.0
    "gnorm": 2e-2,
    # a KDA mixer's group: the chunked form's in-chunk matrices are bfloat16
    # operands of a float32 triangular solve, where the recurrence has none.
    # bfloat16 <= 2.8e-4; float8 0.99
    "gnorm_kda": 1e-2,
    # bfloat16 <= 1.7e-3; float8 0.37
    "gnorm_experts": 2e-2,
    # the router's gradient is the small difference that the normalisation of
    # the eight weights leaves: the cancellation keeps bfloat16's rounding in
    # it. bfloat16 <= 4.4e-3; float8 0.21
    "gnorm_router": 3e-2,
    # share of the program's (token, expert) assignments that this reference's
    # own float32 top-k does not make, worst expert block (always the last,
    # four bfloat16 layers deep). bfloat16 0.0197 to 0.0208; float8 0.127
    "selection": 5e-2,
    # the norm of what the first optimizer step added to a parameter, worst
    # leaf (a KDA layer's `A_log`, `dt_bias` or `o_norm`: a few dozen to a few
    # thousand numbers, where one sign that rounds the other way shows).
    # AdamW's first step is lr * g / (|g| + eps) element by element, so the
    # precision of g hardly moves it (float8's gradients underflow to 0, so it
    # reads 1 all the same); a state left unchanged reads 1.
    # bfloat16 <= 1.2e-2
    "change": 1e-1,
}


def kind_of(name: str) -> str:
    if name.startswith(("change/", "selection/")):
        return name.split("/", 1)[0]
    if not name.startswith("gnorm/"):
        return name
    last = name.rsplit("/", 1)[1]
    return "gnorm_" + last if last in ("router", "experts", "kda") else "gnorm"


def compare(program: dict, reference: dict) -> dict:
    """`program`: the first timed-shape step's scalars, `change/<leaf>` (the
    norm of what that step added to each parameter) and `selection/<block>`
    (the share of its assignments the reference would not make);
    `reference`: the same names from the functions above (`selection/...` 0).
    -> {"ok", "worst": {kind: [name, deviation]}, "limits", "deviations"}."""
    deviations = {}
    for name, ref in reference.items():
        if name not in program:
            return {"ok": False, "missing": name}
        ref = float(ref)
        gap = abs(float(program[name]) - ref)
        deviations[name] = gap if name.startswith("selection/") else gap / max(abs(ref), 1e-30)
    worst: dict = {}
    for name, dev in deviations.items():
        kind = kind_of(name)
        if kind not in worst or not dev <= worst[kind][1]:
            worst[kind] = [name, dev]
    ok = all(math.isfinite(dev) and dev <= LIMITS[kind] for kind, (_, dev) in worst.items())
    # a reference whose gradients or whose step vanish proves nothing
    ok = ok and all(float(v) > 0 for k, v in reference.items() if k.startswith(("gnorm/", "change/")))
    return {"ok": ok, "worst": worst, "limits": LIMITS, "deviations": deviations}

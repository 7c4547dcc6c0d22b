"""The plain float32 reference of a `laguna` share (Laguna-S-2.1) for the
comparison that decides `correct` in its training cell: the loss, gradient
norms by parameter group, the share of the program's expert assignments that
this reference's own top-k would make otherwise, and the change AdamW's first
step makes to every parameter, at the published widths, on the timed batch
and the seed's initial parameters.

A copy of the `laguna_*` equations of yet_another_mobilenet_series_tpu/
models/lm_reference.py (a tier-1 test holds the two equal at a toy size), kept
here so that no later PR can move the yardstick by moving the program.
Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`:

- **attention** (`attention`): q, k, v three projections, query head i
  reading key/value head i // (heads / kv heads) (indexed, not repeated by
  the program's means), the layer type's rotation written out from its
  closed form (`inv_freq`: YaRN's blend of each frequency with itself /
  factor by a ramp between whole channels, in float64; cos and sin times the
  attention factor; the first `partial_rotary_factor` of a head's channels),
  scores q . k / sqrt(head_dim), a DENSE mask (causal, and on a sliding layer
  within the window: key k visible to query q where q - window < k <= q),
  each head's output times sigmoid(x W_g), then o. No tile, no skipped block:
  every key position is scored and the mask removes what the window does not
  admit;
- **the expert layer** (`experts`): softmax over all 256 experts in float32,
  the top-10, the selected scores renormalised and times the routed scaling
  factor; a loop over the held experts in which every expert sees every
  token under its 0/1 x weight; the shared expert times sigmoid(x . w_s);
- the untied head over the vocabulary slice.

RMSNorm, the gated MLP, the matmul with its optional rounding, AdamW's first
step written out and the norms by leaf are the functions of benchmark/
reference_glm4_moe_lite.py themselves, imported: the archs share them in the
program too, and nothing of the program's is in them.

`chosen` (the PROGRAM's expert ids, by expert block) makes the reference
compute its gradients under the program's selection and COUNT the
assignments its own top-k would have made otherwise, as the GLM reference
does and for its reason: a token whose 10th and 11th softmax scores lie
within bfloat16's rounding goes to another expert in the program than in
float32, which says nothing about either side's arithmetic.

Two things are added so that 8,192 tokens at the published widths fit beside
the parameters on one chip, neither of which changes a number: `rows_at_once`
(attention and the head go through their rows a block at a time, each block
still scoring ALL keys under the dense mask's rows) and a `jax.checkpoint`
around every layer and every block of rows.

ASSUMED, where `config.json` leaves it to the code (each is a line of the
configuration file's `assumed`): the router's reading (Qwen2-MoE's softmax,
`norm_topk_prob`), the shared expert's sigmoid gate, no q/k norm, the gate's
input the normed layer input, the rotated channels the first of a head.

`operand_dtype` rounds BOTH operands of every matmul to a lower precision
(float8_e4m3fn is the nearest below the configuration's bfloat16) and is how
the comparison's limits were set: such a step must fail one of them (PERF.md;
the runner's `BENCH_REFERENCE_LOWER=1`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_glm4_moe_lite import (  # noqa: F401 - `mm`, `gated_mlp`: this module's API too
    Sizes, adamw_first_step, gated_mlp, leaf_norms, mm, rms_norm, row_step)

DIM_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "layer_types", "num_attention_heads_per_layer", "sliding_window", "rope_parameters",
            "n_routed_experts", "num_experts_per_tok", "routed_scaling_factor", "expert_shares",
            "expert_share_index")


def dims_of(lm_config, rows_at_once: int | None = None, operand_dtype=None) -> Sizes:
    """The sizes the reference reads, from the program's `model.lm` section
    (n_routed_experts is the ROUTER's width there) or anything shaped like it."""
    return Sizes({k: getattr(lm_config, k) for k in DIM_KEYS}, rows_at_once=rows_at_once,
                 operand_dtype=operand_dtype)


def inv_freq(spec, head_dim):
    """The rotated channels' frequencies of a `rope_parameters` entry, in
    float64 then float32: `default`, theta^(-2i/r) over the r rotated
    channels; `yarn`, each kept where it turns more than beta_fast times in
    the original context, divided by `factor` where it turns fewer than
    beta_slow times, blended linearly between, the channel bounds rounded
    outwards."""
    r = int(head_dim * spec.partial_rotary_factor)
    i = np.arange(r // 2, dtype=np.float64)
    freq = spec.rope_theta ** (-2.0 * i / r)
    if spec.rope_type == "default":
        return freq.astype(np.float32)
    turns = lambda n: r * math.log(spec.original_max_position_embeddings / (2 * math.pi * n)) / (2 * math.log(spec.rope_theta))  # noqa: E731
    lo, hi = max(math.floor(turns(spec.beta_fast)), 0), min(math.ceil(turns(spec.beta_slow)), r - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (freq * (1.0 - ramp) + freq / spec.factor * ramp).astype(np.float32)


def rope(x, spec):
    """x (S, heads, d): the first r channels turned, channel i with i + r/2."""
    seq, _, d = x.shape
    freq = jnp.asarray(inv_freq(spec, d))
    r = 2 * freq.shape[0]
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * freq[None, None, :]
    scale = spec.attention_factor if spec.rope_type == "yarn" else 1.0
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], -1)


def attention(p, x, d, kind, heads):
    """One sequence x (S, h) through a `laguna` attention layer of `heads` query heads and type `kind`."""
    seq = x.shape[0]
    kv, width = d["num_key_value_heads"], d["head_dim"]
    spec = getattr(d["rope_parameters"], kind)
    reads = jnp.arange(heads) // (heads // kv)
    q = rope(mm(x, p["q"], d).reshape(seq, heads, width), spec)
    k = rope(mm(x, p["k"], d).reshape(seq, kv, width), spec)[:, reads]
    v = mm(x, p["v"], d).reshape(seq, kv, width)[:, reads]
    k_t, v_t = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (heads, D, S), (heads, S, D)
    window = d["sliding_window"] if kind == "sliding_attention" else seq

    def rows(q_rows, first):
        scores = mm(q_rows.transpose(1, 0, 2), k_t, d) / math.sqrt(width)  # (heads, rows, S)
        at, of = first + jnp.arange(q_rows.shape[0])[:, None], jnp.arange(seq)[None, :]  # rows of the dense S x S mask
        probs = jax.nn.softmax(jnp.where(((of <= at) & (of > at - window))[None], scores, -jnp.inf), axis=-1)
        return mm(probs, v_t, d).transpose(1, 0, 2)

    step = row_step(seq, d)  # the same rows a block at a time, as ONE loop body
    out = jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs), (q.reshape(seq // step, step, heads, width),
                                                              jnp.arange(0, seq, step))).reshape(seq, heads, width)
    out = out * jax.nn.sigmoid(mm(x, p["gate"], d))[:, :, None]
    return mm(out.reshape(seq, heads * width), p["o"], d)


def experts(p, x, d, chosen=None):
    """(routed output of this share (S, h), assignments per expert (E,),
    assignments of `chosen` (S, k) that this reference's own top-k does not
    make). With `chosen` the weights and the output follow IT."""
    n, k = d["n_routed_experts"], d["num_experts_per_tok"]
    held = n // d["expert_shares"]
    first = d["expert_share_index"] * held
    scores = jax.nn.softmax(mm(x, p["router"], d), axis=-1)
    _, own = jax.lax.top_k(scores, k)
    own = jax.nn.one_hot(own, n).sum(axis=1)  # (S, E) 0/1
    chosen = own if chosen is None else jax.nn.one_hot(chosen, n).sum(axis=1)
    differing = jnp.sum(chosen * (1.0 - own))
    weight = chosen * scores
    weight = weight / weight.sum(axis=-1, keepdims=True) * d["routed_scaling_factor"]

    def one_expert(out, xs):  # the loop over the held experts: each sees every token under its 0/1 x score weight
        gate, up, down, w = xs
        return out + w[:, None] * gated_mlp(gate, up, down, x, d), None

    e = p["experts"]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (e["gate"], e["up"], e["down"], weight[:, first:first + held].T))
    return out, chosen.sum(axis=0), differing


def block(p, x, d, i: int, chosen=None):
    """Layer i: x + Attn(N(x)); then x + MLP(N(x)) (dense) or x + sigmoid(N(x) . w_s) Shared(N(x)) + Routed(N(x))."""
    eps = d["rms_norm_eps"]
    x = x + attention(p["attn"], rms_norm(x, p["attn_norm"], eps), d, d["layer_types"][i],
                      d["num_attention_heads_per_layer"][i])
    y = rms_norm(x, p["mlp_norm"], eps)
    if i < d["first_k_dense_replace"]:
        return x + gated_mlp(p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], y, d), None
    s = p["shared"]
    routed, load, differing = experts(p, y, d, chosen)
    shared = jax.nn.sigmoid(mm(y, s["sigmoid_gate"][:, None], d)) * gated_mlp(s["gate"], s["up"], s["down"], y, d)
    return x + shared + routed, (load, differing)


def head_cross_entropy(head, hidden, targets, d):
    """Summed cross-entropy of (S, h) hidden states against (S,) targets."""
    def rows(hid, tgt):
        logits = mm(hid, head, d)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(tgt.shape[0]), tgt])

    n = hidden.shape[0]
    step = row_step(n, d)
    return jnp.sum(jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs),
                               (hidden.reshape(n // step, step, -1), targets.reshape(n // step, step))))


def sequence_cross_entropy(params, ids, d, chosen=None):
    """One row of S + 2 ids (the last is not read) -> (summed CE, by expert
    block (assignments per expert, assignments of `chosen` that differ from
    this reference's own)). `chosen`: {expert block: (S, k) expert ids} to
    compute under, or None for its own."""
    with jax.default_matmul_precision("highest"):
        seq = ids.shape[0] - 2
        run = jax.checkpoint(block, static_argnums=(2, 3))
        loads = {}
        x = params["embed"][ids[:seq]]
        for i in range(d["num_hidden_layers"]):
            name = f"layer_{i}"
            x, load = run(params[name], x, d, i, None if chosen is None else chosen.get(name))
            if load is not None:
                loads[name] = load
        ce = head_cross_entropy(params["head"], rms_norm(x, params["final_norm"], d["rms_norm_eps"]), ids[1:seq + 1], d)
        return ce, loads


def sequence_loss_and_grads(params, ids, d, n_tokens: int, chosen=None):
    """One sequence's part of the batch loss and of its gradients: (CE /
    n_tokens, (CE sum, loads)), gradients by parameter. Sum over the batch's
    sequences."""
    def loss(p):
        ce, loads = sequence_cross_entropy(p, ids, d, chosen)
        return ce / n_tokens, (ce, loads)

    return jax.value_and_grad(loss, has_aux=True)(params)


def group_norms(grads: dict) -> dict:
    """Gradient norms under the names of the step's `gnorm/...` scalars
    (models/lm.py `TokenModel.grad_scalars`): embed, head, final_norm, and per
    block attn (its gate included), mlp, router, shared (its gate included),
    experts and norms (the block's two gains)."""
    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))

    out = {f"gnorm/{k}": norm(grads[k]) for k in ("embed", "head", "final_norm")}
    for name, g in grads.items():
        if name.startswith("layer_"):
            for part in ("attn", "mlp", "router", "shared", "experts"):
                if part in g:
                    out[f"gnorm/{name}/{part}"] = norm(g[part])
            out[f"gnorm/{name}/norms"] = norm([v for k, v in g.items() if k.endswith("norm")])
    return out


# The limits. Each is |program - reference| / |reference| (`selection`: the
# share itself), on the chip, at the published widths, and each but the
# loss's lies between two readings (chip runs on a TPU v5 lite; PERF.md
# section 4): the largest the bfloat16 program gave over 23 runs of 21 seeds,
# and what this reference gives with float8_e4m3fn operands (`operand_dtype`),
# the nearest precision below, under the program's selection (seeds
# 3141592653 and 2400000067): it fails every limit but the loss's on one.
LIMITS = {
    # a mean over 8,192 tokens of a loss that is ~ln(vocabulary) at initialisation: held at the accepted token cells'
    # limit, which leaves the bfloat16 reading three times of room. bfloat16 <= 1.1e-4; float8 5.8e-4 and 2.8e-3
    "loss": 5e-4,
    # norms of sums over 8,192 tokens of bfloat16 products: attention (its gate within), the dense MLP, the shared
    # expert, the vocabulary, the norm gains. bfloat16 <= 3.6e-3; float8 0.996 and 0.997
    "gnorm": 2e-2,
    # the held experts' group, under the program's selection. bfloat16 <= 6.5e-3; float8 0.33 and 0.39
    "gnorm_experts": 5e-2,
    # the router's gradient is the small difference the renormalisation of the ten weights leaves, and bfloat16's
    # rounding stays in it. bfloat16 <= 2.9e-2; float8 0.11 and 0.23
    "gnorm_router": 8e-2,
    # share of the program's (token, expert) assignments that this reference's own float32 top-k does not make,
    # worst expert block (near-ties of the 10th and 11th scores). bfloat16 <= 1.8e-2; float8 0.29 and 0.29
    "selection": 1e-1,
    # the norm of what the first optimizer step added to a parameter, worst leaf: AdamW's first step is
    # lr * g / (|g| + eps) element by element, so the precision of g hardly moves it; a state left unchanged reads 1
    # (and float8 does: its gradients underflow). The more room above the reading, which fresh seeds move.
    # bfloat16 <= 1.1e-3
    "change": 1e-1,
}


def kind_of(name: str) -> str:
    if name.startswith(("change/", "selection/")):
        return name.split("/", 1)[0]
    if not name.startswith("gnorm/"):
        return name
    last = name.rsplit("/", 1)[1]
    return {"router": "gnorm_router", "experts": "gnorm_experts"}.get(last, "gnorm")


def compare(program: dict, reference: dict) -> dict:
    """`program`: the first timed-shape step's scalars, `change/<leaf>` and
    `selection/<block>`; `reference`: the same names from the functions above
    (`selection/...` 0). -> {"ok", "worst": {kind: [name, deviation]},
    "limits", "deviations"}."""
    deviations = {}
    for name, ref in reference.items():
        if name not in program:
            return {"ok": False, "missing": name}
        ref = float(ref)
        got = float(program[name])
        deviations[name] = got if name.startswith("selection/") else abs(got - ref) / max(abs(ref), 1e-30)
    worst: dict = {}
    for name, dev in deviations.items():
        kind = kind_of(name)
        if kind not in worst or not dev <= worst[kind][1]:
            worst[kind] = [name, dev]
    ok = all(math.isfinite(dev) and dev <= LIMITS[kind] for kind, (_, dev) in worst.items())
    # a reference whose gradients or whose step vanish proves nothing
    ok = ok and all(float(v) > 0 for k, v in reference.items() if k.startswith(("gnorm/", "change/")))
    return {"ok": ok, "worst": worst, "limits": LIMITS, "deviations": deviations}

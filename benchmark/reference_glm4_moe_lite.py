"""The plain float32 reference of a `glm4_moe_lite` share (GLM-4.7-Flash) for
the comparison that decides `correct` in a token training cell: loss of both
heads, gradient norms by parameter group and the change AdamW's first step
makes to every parameter, at the published widths, on the timed batch and
the seed's initial parameters.

A copy of yet_another_mobilenet_series_tpu/models/lm_reference.py (a tier-1
test holds the two equal at a toy size), kept here so that no later PR can
move the yardstick by moving the program. The equations are the same,
straightforward `jax.numpy` in float32 under `default_matmul_precision
("highest")`: per-head keys and values with the one `k_rope` head repeated, a
dense causal mask, a loop over the held experts in which every expert
sees every token under a 0/1 weight; no sort, no grouped matmul, nothing of
the program's; the optimizer's step is AdamW's equations written out
(:func:`adamw_first_step`), not optax. Two things are added so that 8,192
tokens at the published widths fit beside the parameters on one chip,
neither of which changes a number:

- `rows_at_once`: attention and the output head go through their rows a block
  at a time (each block still sees ALL keys under the dense mask's rows);
- every block of layers and of rows is a `jax.checkpoint`, and the runner
  calls :func:`sequence_loss_and_grads` a sequence at a time and adds up.

`chosen` (the PROGRAM's expert ids, by expert block) makes the reference
compute its gradients under the program's selection and COUNT the
assignments its own top-k would have made otherwise. A token whose 4th and
5th scores lie within bfloat16's rounding goes to another expert in the
program than in float32; a handful of such tokens moves the router's and the
held experts' gradient norms by a tenth (PERF.md section 4 has both
readings), which says nothing about either side's arithmetic. So the
selection is held by the share of assignments that differ, and the
gradients by their norms under ONE selection.

`balanced_state` makes the router biases the cell starts from (what a
running job holds, from this reference's own scores; its docstring says why
zeros will not do).

`operand_dtype` rounds BOTH operands of every matmul to a lower precision
(float8_e4m3fn is the nearest below the configuration's bfloat16) and is how
the comparison's limits were set: such a step must fail one of them
(PERF.md; the runner's `BENCH_REFERENCE_LOWER=1`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DIM_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "num_nextn_predict_layers", "rms_norm_eps", "rope_theta", "expert_shares",
            "expert_share_index", "mtp_loss_weight")


class Sizes(dict):
    """The sizes as a mapping that hashes: a static argument of jax.checkpoint."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def dims_of(lm_config, rows_at_once: int | None = None, operand_dtype=None) -> Sizes:
    """The sizes the reference reads, from the program's `model.lm` section
    (n_routed_experts is the ROUTER's width there) or anything shaped like it."""
    return Sizes({k: getattr(lm_config, k) for k in DIM_KEYS}, rows_at_once=rows_at_once,
                 operand_dtype=operand_dtype)


def mm(a, b, d):
    if d["operand_dtype"] is not None:
        a, b = (t.astype(d["operand_dtype"]).astype(jnp.float32) for t in (a, b))
    return a @ b


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x (S, heads, d): position s rotates the pair (i, i + d/2) by s * theta^(-2i/d)."""
    seq, _, dim = x.shape
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * freq[None, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def row_step(n: int, d: dict) -> int:
    step = min(d["rows_at_once"] or n, n)
    if n % step:
        raise ValueError(f"{n} rows are not a multiple of rows_at_once {step}")
    return step


def mla(p, x, d):
    """One sequence x (S, h) through multi-head latent attention."""
    seq = x.shape[0]
    heads, nope, rope_d, v_d = (d["num_attention_heads"], d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                                d["v_head_dim"])
    c_q = rms_norm(mm(x, p["q_a"], d), p["q_norm"], d["rms_norm_eps"])
    q = mm(c_q, p["q_b"], d).reshape(seq, heads, nope + rope_d)
    kv_a = mm(x, p["kv_a"], d)
    c_kv = rms_norm(kv_a[:, :d["kv_lora_rank"]], p["kv_norm"], d["rms_norm_eps"])
    kv = mm(c_kv, p["kv_b"], d).reshape(seq, heads, nope + v_d)
    k_rope = rope(kv_a[:, None, d["kv_lora_rank"]:], d["rope_theta"])  # one head
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_rope, heads, axis=1)], axis=-1)  # ... shared by all
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], d["rope_theta"])], axis=-1)
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


def gated_mlp(gate, up, down, x, d):
    return mm(jax.nn.silu(mm(x, gate, d)) * mm(x, up, d), down, d)


def experts(p, bias, x, d, chosen=None):
    """(routed output of this share (S, h), assignments per expert (E,),
    assignments of `chosen` (S, k) that this reference's own top-k does not
    make). With `chosen` the weights and the output follow IT."""
    n, k = d["n_routed_experts"], d["num_experts_per_tok"]
    held = n // d["expert_shares"]
    first = d["expert_share_index"] * held
    scores = jax.nn.sigmoid(mm(x, p["router"], d))
    _, own = jax.lax.top_k(scores + bias, k)  # selection: scores + bias
    own = jax.nn.one_hot(own, n).sum(axis=1)  # (S, E) 0/1
    chosen = own if chosen is None else jax.nn.one_hot(chosen, n).sum(axis=1)
    differing = jnp.sum(chosen * (1.0 - own))
    weight = chosen * scores  # weights: the scores themselves
    weight = weight / weight.sum(axis=-1, keepdims=True) * d["routed_scaling_factor"]
    def one_expert(out, xs):  # the loop over the held experts: each sees every token under its 0/1 x score weight
        gate, up, down, w = xs
        return out + w[:, None] * gated_mlp(gate, up, down, x, d), None

    e = p["experts"]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (e["gate"], e["up"], e["down"], weight[:, first:first + held].T))
    return out, chosen.sum(axis=0), differing


def attended(p, x, d):
    return x + mla(p["attn"], rms_norm(x, p["attn_norm"], d["rms_norm_eps"]), d)


def fed_forward(p, bias, x, d, dense, chosen=None):
    y = rms_norm(x, p["mlp_norm"], d["rms_norm_eps"])
    if dense:
        return x + gated_mlp(p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], y, d), None
    routed, load, differing = experts(p, bias, y, d, chosen)
    shared = gated_mlp(p["shared"]["gate"], p["shared"]["up"], p["shared"]["down"], y, d)
    return x + shared + routed, (load, differing)


def block(p, bias, x, d, dense, chosen=None):
    return fed_forward(p, bias, attended(p, x, d), d, dense, chosen)


def head_cross_entropy(head, hidden, targets, d):
    """Summed cross-entropy of (S, h) hidden states against (S,) targets."""
    def rows(hid, tgt):
        logits = mm(hid, head, d)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(tgt.shape[0]), tgt])

    n = hidden.shape[0]
    step = row_step(n, d)
    return jnp.sum(jax.lax.map(lambda xs: jax.checkpoint(rows)(*xs),
                               (hidden.reshape(n // step, step, -1), targets.reshape(n // step, step))))


def balanced_state(params, tokens, d, rounds: int = 200) -> dict:
    """The router biases of a job that has been RUNNING on such batches,
    where a fresh model holds zeros: {expert block: {"router_bias": (E,)}}.

    At random initialisation near-uniform attention leaves every position
    almost one hidden state, so with zero biases nearly all tokens pick the
    same four experts, and whether those are among the eight held here is the
    seed's luck: the held experts' load, and with it the step's time, moved
    by a factor of four from seed to seed (PERF.md, PR 27). A deployment's
    biases have long spread the tokens. So, one forward pass of THIS
    reference over the batch (tokens (B, S + 2)), block after block, each
    expert block first moving its bias by `rounds` rounds of the
    architecture's own sign rule on its float32 scores of the whole batch, at
    a rate falling geometrically from 0.1 to 1e-4, then routing by it."""
    k, eps = d["num_experts_per_tok"], d["rms_norm_eps"]

    def balance(scores):
        def one_round(i, bias):
            rate = 0.1 * 1e-3 ** (i / max(rounds - 1, 1))
            _, own = jax.lax.top_k(scores + bias, k)
            load = jax.nn.one_hot(own, scores.shape[-1]).sum(axis=(0, 1))
            return bias + rate * jnp.sign(jnp.mean(load) - load)

        return jax.lax.fori_loop(0, rounds, one_round, jnp.zeros((scores.shape[-1],), jnp.float32))

    def through(p, xs, dense, state, name):
        xs = jax.lax.map(lambda x: attended(p, x, d), xs)  # a sequence at a time
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
        if d["num_nextn_predict_layers"]:
            m = params["mtp"]
            merged = jnp.concatenate([rms_norm(xs, m["h_norm"], eps),
                                      rms_norm(params["embed"][tokens[:, 1:seq + 1]], m["e_norm"], eps)], -1)
            through(m, jax.lax.map(lambda x: mm(x, m["eh_proj"], d), merged), False, state, "mtp")
        return state


def sequence_cross_entropy(params, state, ids, d, chosen=None):
    """One row of S + 2 ids -> (summed CE of the main head, of the MTP head
    (0.0 without one), by expert block (assignments per expert, assignments
    of `chosen` that differ from this reference's own)). `chosen`: {expert
    block: (S, k) expert ids} to compute under, or None for its own."""
    with jax.default_matmul_precision("highest"):
        seq = ids.shape[0] - 2
        eps = d["rms_norm_eps"]
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
        ce = head_cross_entropy(params["head"], rms_norm(x, params["final_norm"], eps), ids[1:seq + 1], d)
        ce_mtp = 0.0
        if d["num_nextn_predict_layers"]:
            m = params["mtp"]
            merged = jnp.concatenate([rms_norm(x, m["h_norm"], eps),
                                      rms_norm(params["embed"][ids[1:seq + 1]], m["e_norm"], eps)], -1)
            y, loads["mtp"] = run(m, state["mtp"]["router_bias"], mm(merged, m["eh_proj"], d), d, False, pick("mtp"))
            ce_mtp = head_cross_entropy(params["head"], rms_norm(y, m["final_norm"], eps), ids[2:seq + 2], d)
        return ce, ce_mtp, loads


def sequence_loss_and_grads(params, state, ids, d, n_tokens: int, chosen=None):
    """One sequence's part of the batch loss and of its gradients:
    ((CE_main + mtp_loss_weight * CE_mtp) / n_tokens, (CE_main, CE_mtp sums,
    loads)), gradients by parameter. Sum over the batch's sequences."""
    def loss(p):
        ce, ce_mtp, loads = sequence_cross_entropy(p, state, ids, d, chosen)
        return (ce + d["mtp_loss_weight"] * ce_mtp) / n_tokens, (ce, ce_mtp, loads)

    return jax.value_and_grad(loss, has_aux=True)(params)


def group_norms(grads: dict) -> dict:
    """Gradient norms under the names of the step's `gnorm/...` scalars
    (models/lm.py `TokenModel.grad_scalars`): embed, head, final_norm, and per
    block attn, mlp, router, shared, experts, eh_proj and norms (every gain of
    the block)."""
    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))

    out = {f"gnorm/{k}": norm(grads[k]) for k in ("embed", "head", "final_norm")}
    for name, g in grads.items():
        if isinstance(g, dict):
            for part in ("attn", "mlp", "router", "shared", "experts", "eh_proj"):
                if part in g:
                    out[f"gnorm/{name}/{part}"] = norm(g[part])
            out[f"gnorm/{name}/norms"] = norm([v for k, v in g.items() if k.endswith("norm")])
    return out


def adamw_first_step(params, grads, *, lr: float, b1: float, b2: float, eps: float, clip: float):
    """What AdamW's FIRST step (zero moments, no weight decay) adds to every
    parameter, written out: the gradients scaled so that their global norm is
    at most `clip`; m = (1 - b1) g, v = (1 - b2) g^2; both divided by their
    bias corrections 1 - b1 and 1 - b2; change = (p - lr m / (sqrt(v) + eps)) - p
    in float32, as a float32 parameter takes it."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-16)) if clip > 0 else 1.0

    def leaf(p, g):
        g = g * scale
        m_hat = (1.0 - b1) * g / (1.0 - b1)
        v_hat = (1.0 - b2) * g * g / (1.0 - b2)
        return (p - lr * m_hat / (jnp.sqrt(v_hat) + eps)) - p

    return jax.tree.map(leaf, params, grads)


def leaf_norms(tree: dict, prefix: str) -> dict:
    """{"<prefix>/<path of the leaf>": its norm}, every leaf of a parameter tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {prefix + "/" + "/".join(str(k.key) for k in path): jnp.sqrt(jnp.sum(jnp.square(leaf)))
            for path, leaf in flat}


# The limits. Each is |program - reference| / reference (`selection`: the share
# itself), on the chip, at the published widths, and lies between two readings
# (PR 27's builder, TPU v5 lite; PERF.md section 4): the largest the bfloat16
# program gave over 13 runs of 7 seeds from the balanced biases (and, where it
# says so, 7 more from zero biases), and what this reference gives with
# float8_e4m3fn operands (`operand_dtype`), the nearest precision below, under
# the same selection: it fails every limit but `ce_mtp`'s, most of all the
# gradients, which underflow.
LIMITS = {
    # a mean over 16,384 tokens of a loss that is ~ln(vocabulary) at
    # initialisation: rounding of the logits averages out.
    # bfloat16 <= 1.2e-4 (1.9e-4 in the first diff's 14 runs); float8 2.7e-3 and 6.3e-3
    "ce": 5e-4,
    # bfloat16 <= 1.0e-4; float8 1.9e-4 to 8.1e-3: this one alone does not catch it
    "ce_mtp": 5e-4,
    # norms of sums over 16,384 tokens of bfloat16 products, the reference
    # computing under the program's selection. bfloat16 <= 3.1e-3; float8 1.0
    "gnorm": 2e-2,
    # bfloat16 <= 6.6e-3 (7.5e-3 from zero biases; 1.4e-2 when the reference is
    # left to its OWN selection, 5.2e-2 in the first diff's runs); float8 0.71
    "gnorm_experts": 5e-2,
    # the router's gradient is the small difference that the normalisation of
    # the four weights leaves: the cancellation keeps bfloat16's rounding in
    # it, under the program's own selection too (2.6e-2 -> 1.6e-2 on the one
    # seed read both ways). bfloat16 <= 4.5e-2 (9.5e-2 from zero biases); float8 0.42
    "gnorm_router": 2e-1,
    # share of the program's (token, expert) assignments that this reference's
    # own float32 top-k does not make, worst expert block.
    # bfloat16 <= 3.2e-2 (2.0e-2 from zero biases, fewer near-ties); float8 0.84
    "selection": 1e-1,
    # the norm of what the first optimizer step added to a parameter, worst
    # leaf. AdamW's first step is lr * g / (|g| + eps) element by element, so
    # the precision of g hardly moves it (float8's gradients underflow to 0,
    # so it reads 1 all the same); a state left unchanged reads 1.
    # bfloat16 <= 2.2e-3 (1.2e-2 at a learning rate of 3e-5)
    "change": 1e-1,
}


def kind_of(name: str) -> str:
    if name.startswith(("change/", "selection/")):
        return name.split("/", 1)[0]
    if not name.startswith("gnorm/"):
        return name
    return "gnorm_router" if name.endswith("/router") else "gnorm_experts" if name.endswith("/experts") else "gnorm"


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

"""The plain reference of the token family (`glm4_moe_lite`: GLM-4.7-Flash;
`kimi_linear`: Kimi-Linear-48B-A3B; `ouro`: Ouro-2.6B, the `ouro_*`
functions; `granitemoehybrid`: Granite 4.0-H, the `granite_*` functions; `laguna`:
Laguna-S-2.1, the `laguna_*` functions at the end): forward, loss and, through `jax.grad`, gradients, in
straightforward `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`.

It follows the published layer equations and shares no function with the
program (models/lm.py, ops/lm.py). Where the program is clever this is not:
keys and values are expanded per head, with the one shared `k_rope` head
repeated; the causal mask is a dense S x S array; the held experts are a
Python loop in which every expert sees every token under a 0/1 mask; nothing
is sorted, blocked or recomputed; Kimi Delta Attention is its recurrence,
token by token (a `lax.scan` over the positions, never a chunk), its short
convolution four shifted multiplies. It reads the program's parameter tree
(`TokenModel.init`: a block with a `kda` group mixes by KDA, one with `attn`
by latent attention; `attn` with `q` has no low-rank q) and a plain dict of
sizes (:func:`dims_of`).

Given a share (`expert_shares`, `expert_share_index`) it leaves out the same
absent experts as the program; with `expert_shares=1` it is the uncut layer.

Departures from the published description: none in the equations. Not in
`config.json`, and therefore assumed (the configuration's file lists them):
the RoPE pairing (channel i with i + d/2), the MTP loss weight, the bias
update rate; for `kimi_linear` what KDA's description leaves to the code
(:func:`kda` names each); for `ouro` what :func:`ouro_loss_and_aux` names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DIM_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "num_nextn_predict_layers", "rms_norm_eps", "rope_theta", "expert_shares",
            "expert_share_index", "mtp_loss_weight", "router_bias_rate", "mla_use_nope")


def dims_of(lm_config) -> dict:
    """The sizes the reference reads, from anything with those attributes."""
    return {k: getattr(lm_config, k) for k in DIM_KEYS}


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x (S, heads, d): position s rotates the pair (i, i + d/2) by s * theta^(-2i/d)."""
    seq, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * freq[None, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def mla(p, x, d):
    """One sequence x (S, h) through multi-head latent attention. `q` in `p`:
    q is one projection (no low-rank pair); `mla_use_nope`: nothing is rotated."""
    seq = x.shape[0]
    heads, nope, rope_d, v_d = (d["num_attention_heads"], d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                                d["v_head_dim"])
    turn = (lambda t: t) if d["mla_use_nope"] else (lambda t: rope(t, d["rope_theta"]))
    if "q" in p:
        q = (x @ p["q"]).reshape(seq, heads, nope + rope_d)
    else:
        c_q = rms_norm(x @ p["q_a"], p["q_norm"], d["rms_norm_eps"])
        q = (c_q @ p["q_b"]).reshape(seq, heads, nope + rope_d)
    kv_a = x @ p["kv_a"]
    c_kv = rms_norm(kv_a[:, :d["kv_lora_rank"]], p["kv_norm"], d["rms_norm_eps"])
    kv = (c_kv @ p["kv_b"]).reshape(seq, heads, nope + v_d)
    q_rope = turn(q[..., nope:])
    k_rope = turn(kv_a[:, None, d["kv_lora_rank"]:])  # one head
    k_rope = jnp.repeat(k_rope, heads, axis=1)  # ... shared by all
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)  # per-head keys (S, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(nope + rope_d)
    mask = jnp.tril(jnp.ones((seq, seq), bool))  # dense S x S
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, kv[..., nope:])
    return out.reshape(seq, heads * v_d) @ p["o"]


def short_conv(z, w):
    """z (S, D), one filter of `taps` weights a channel, w (taps, D): SiLU(sum_i
    w_i z_{t - (taps-1) + i}), positions before the document's first read 0;
    `taps` shifted multiplies. ASSUMED: no bias, SiLU after the sum."""
    taps = w.shape[0]
    total = jnp.zeros_like(z)
    for i in range(taps):
        back = taps - 1 - i
        total = total + w[i] * jnp.concatenate([jnp.zeros_like(z[:back]), z[:z.shape[0] - back]], axis=0)
    return jax.nn.silu(total)


def kda(p, x, d):
    """One sequence x (S, h) through Kimi Delta Attention, as the RECURRENCE,
    a token at a time: S'_t = Diag(alpha_t) S_{t-1}; S_t = S'_t + beta_t k_t
    (v_t - S'_t^T k_t)^T; o_t = S_t^T q_t / sqrt(head_dim). ASSUMED (not in
    `config.json`): the low-rank width of the decay and output gates is the
    head dim (read off `f_a`, `g_a`); L2 norm with eps 1e-6 INSIDE the root;
    the output norm's gain is shared by the heads; sigmoid on the output gate."""
    seq = x.shape[0]
    heads = p["A_log"].shape[0]
    width = p["q"].shape[1] // heads
    by_head = lambda z: z.reshape(seq, heads, width)  # noqa: E731
    q, k, v = (by_head(short_conv(x @ p[n], p["conv_" + n])) for n in ("q", "k", "v"))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    log_decay = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(by_head(x @ p["f_a"] @ p["f_b"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["b"])  # (S, heads)

    def token(state, xs):  # state (heads, key, value)
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - read)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t) / math.sqrt(width)

    _, out = jax.lax.scan(token, jnp.zeros((heads, width, width), x.dtype), (q, k, v, log_decay, beta))
    out = rms_norm(out, p["o_norm"], d["rms_norm_eps"]) * jax.nn.sigmoid(by_head(x @ p["g_a"] @ p["g_b"]))
    return out.reshape(seq, heads * width) @ p["o"]


def gated_mlp(gate, up, down, x):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts(p, bias, x, d):
    """(routed output of this share (S, h), assignments per expert (E,))."""
    n, k = d["n_routed_experts"], d["num_experts_per_tok"]
    held = n // d["expert_shares"]
    first = d["expert_share_index"] * held
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + bias, k)  # selection: scores + bias
    chosen = jax.nn.one_hot(chosen, n).sum(axis=1)  # (S, E) 0/1
    weight = chosen * scores  # weights: the scores themselves
    weight = weight / weight.sum(axis=-1, keepdims=True) * d["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for j in range(held):
        e = p["experts"]
        out = out + weight[:, first + j, None] * gated_mlp(e["gate"][j], e["up"][j], e["down"][j], x)
    return out, chosen.sum(axis=0)


def block(p, bias, x, d, dense):
    mixed = rms_norm(x, p["attn_norm"], d["rms_norm_eps"])
    x = x + (kda(p["kda"], mixed, d) if "kda" in p else mla(p["attn"], mixed, d))
    y = rms_norm(x, p["mlp_norm"], d["rms_norm_eps"])
    if dense:
        return x + gated_mlp(p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], y), None
    routed, load = experts(p, bias, y, d)
    return x + gated_mlp(p["shared"]["gate"], p["shared"]["up"], p["shared"]["down"], y) + routed, load


def cross_entropy(logits, targets):
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - logits[jnp.arange(targets.shape[0]), targets])


def sequence(params, state, ids, d):
    """One row of seq + 2 ids -> (main logits (S, V), MTP logits or None, load by expert block)."""
    seq = ids.shape[0] - 2
    loads = {}
    x = params["embed"][ids[:seq]]
    for i in range(d["num_hidden_layers"]):
        name = f"layer_{i}"
        dense = i < d["first_k_dense_replace"]
        x, load = block(params[name], None if dense else state[name]["router_bias"], x, d, dense)
        if load is not None:
            loads[name] = load
    main = rms_norm(x, params["final_norm"], d["rms_norm_eps"]) @ params["head"]
    mtp = None
    if d["num_nextn_predict_layers"]:
        m = params["mtp"]
        merged = jnp.concatenate([rms_norm(x, m["h_norm"], d["rms_norm_eps"]),
                                  rms_norm(params["embed"][ids[1:seq + 1]], m["e_norm"], d["rms_norm_eps"])], -1)
        y, loads["mtp"] = block(m, state["mtp"]["router_bias"], merged @ m["eh_proj"], d, False)
        mtp = rms_norm(y, m["final_norm"], d["rms_norm_eps"]) @ params["head"]
    return main, mtp, loads


def loss_and_aux(params, state, tokens, d):
    """tokens (B, S + 2) -> (loss, {"ce", "ce_mtp", "new_state", "logits",
    "mtp_logits"}): loss = CE_main + mtp_loss_weight * CE_mtp, means over all
    B * S tokens; new_state = the router biases after the sign rule."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1] - 2
        n = tokens.shape[0] * seq
        ce = ce_mtp = 0.0
        loads: dict = {}
        logits, mtp_logits = [], []
        for ids in tokens:
            main, mtp, load = sequence(params, state, ids, d)
            ce = ce + cross_entropy(main, ids[1:seq + 1]) / n
            if mtp is not None:
                ce_mtp = ce_mtp + cross_entropy(mtp, ids[2:seq + 2]) / n
                mtp_logits.append(mtp)
            logits.append(main)
            loads = {k: loads.get(k, 0.0) + v for k, v in load.items()}
        new_state = {k: {"router_bias": state[k]["router_bias"]
                         + d["router_bias_rate"] * jnp.sign(jnp.mean(v) - v)} for k, v in loads.items()}
        loss = ce + d["mtp_loss_weight"] * ce_mtp
        return loss, {"ce": ce, "ce_mtp": ce_mtp, "new_state": new_state, "loads": loads,
                      "logits": jnp.stack(logits), "mtp_logits": jnp.stack(mtp_logits) if mtp_logits else None}


def loss_and_grads(params, state, tokens, d):
    """((loss, aux), gradients of the loss by parameter)."""
    return jax.value_and_grad(loss_and_aux, has_aux=True)(params, state, tokens, d)


# ---- `ouro`: a looped model (Ouro-2.6B; "Scaling Latent Reasoning via Looped Language Models") ----------------

OURO_DIM_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim", "rms_norm_eps", "rope_theta",
                 "total_ut_steps", "exit_entropy_weight")


def ouro_dims_of(lm_config) -> dict:
    return {k: getattr(lm_config, k) for k in OURO_DIM_KEYS}


def ouro_attention(p, x, d):
    """One sequence x (S, h) through plain multi-head attention: q, k, v three
    projections, every channel of q and k rotated, a dense S x S causal mask."""
    seq = x.shape[0]
    heads, width = d["num_attention_heads"], d["head_dim"]
    q, k, v = ((x @ p[n]).reshape(seq, heads, width) for n in ("q", "k", "v"))
    q, k = rope(q, d["rope_theta"]), rope(k, d["rope_theta"])
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(width)
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(seq, heads * width) @ p["o"]


def ouro_block(p, y, d):
    """The sandwich: a norm before AND after each sub-layer, four gains."""
    eps = d["rms_norm_eps"]
    y = y + rms_norm(ouro_attention(p["attn"], rms_norm(y, p["attn_norm"], eps), d), p["attn_out_norm"], eps)
    m = p["mlp"]
    return y + rms_norm(gated_mlp(m["gate"], m["up"], m["down"], rms_norm(y, p["mlp_norm"], eps)), p["mlp_out_norm"], eps)


def ouro_exit_distribution(gates):
    """gates: the R exit probabilities g_r a token, a list of (S,) arrays ->
    [p_1..p_R]: p_1 = g_1, p_r = g_r prod_{j<r}(1 - g_j), p_R = prod_{j<R}(1 -
    g_j); g_R is not read."""
    p, reached = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        p.append(g * reached)
        reached = reached * (1.0 - g)
    return p + [reached]


def ouro_sequence(params, ids, d):
    """One row of S + 2 ids (the last is not read) -> per loop step: logits
    (S, V), the exit probability g_r (S,). The final norm is INSIDE the loop:
    step r + 1 reads the normed state."""
    seq = ids.shape[0] - 2
    x = params["embed"][ids[:seq]]
    logits, gates = [], []
    for _ in range(d["total_ut_steps"]):  # the SAME parameters every time
        for i in range(d["num_hidden_layers"]):
            x = ouro_block(params[f"layer_{i}"], x, d)
        x = rms_norm(x, params["final_norm"], d["rms_norm_eps"])
        logits.append(x @ params["head"])
        gates.append(jax.nn.sigmoid(x @ params["exit_gate"]["w"] + params["exit_gate"]["b"]))
    return logits, gates


def ouro_loss_and_aux(params, tokens, d):
    """tokens (B, S + 2) -> (loss, {"ce_step": [R], "exit_entropy",
    "exit_p_last", "expected_exit_step", "logits": (B, R, S, V)}): a token's loss is sum_r p_r CE(logits^r, next id) - beta H(p), the
    step's its mean over all B * S tokens. ASSUMED (the configuration file
    lists each; none is a key of `config.json`): the four norms' placement; the
    final norm inside the loop; the gate one row of h weights and a bias; beta
    0.1; no bias on a projection; plain multi-head attention."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1] - 2
        n = tokens.shape[0] * seq
        steps = d["total_ut_steps"]
        loss = entropy = p_last = expected_step = 0.0
        ce_step = [0.0] * steps
        all_logits = []
        for ids in tokens:
            logits, gates = ouro_sequence(params, ids, d)
            p = ouro_exit_distribution(gates)
            targets = ids[1:seq + 1]
            nll = [jax.nn.logsumexp(z, axis=-1) - z[jnp.arange(seq), targets] for z in logits]
            h = -sum(jnp.where(q > 0, q * jnp.log(jnp.where(q > 0, q, 1.0)), 0.0) for q in p)
            loss = loss + jnp.sum(sum(q * c for q, c in zip(p, nll)) - d["exit_entropy_weight"] * h) / n
            entropy, p_last = entropy + jnp.sum(h) / n, p_last + jnp.sum(p[-1]) / n
            expected_step = expected_step + jnp.sum(sum((r + 1) * q for r, q in enumerate(p))) / n
            ce_step = [total + jnp.sum(c) / n for total, c in zip(ce_step, nll)]
            all_logits.append(jnp.stack(logits))
        return loss, {"ce_step": ce_step, "exit_entropy": entropy, "exit_p_last": p_last,
                      "expected_exit_step": expected_step, "logits": jnp.stack(all_logits)}


def ouro_loss_and_grads(params, tokens, d):
    """((loss, aux), gradients of the loss by parameter)."""
    return jax.value_and_grad(ouro_loss_and_aux, has_aux=True)(params, tokens, d)


# ---- `granitemoehybrid`: Mamba-2 mixers beside grouped-query attention (Granite 4.0-H) ------------------------------

GRANITE_DIM_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
                    "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "attention_multiplier",
                    "embedding_multiplier", "residual_multiplier", "logits_scaling")


def granite_dims_of(lm_config) -> dict:
    return {k: getattr(lm_config, k) for k in GRANITE_DIM_KEYS}


def granite_mamba(p, x, d):
    """One sequence x (S, h) through a Mamba-2 mixer, in the order of the Hugging
    Face module's own (non-kernel) forward: in_proj -> [z | xBC | dt]; xBC <-
    SiLU(causal depthwise conv + bias); split x, B, C (ONE group of
    `mamba_d_state`); Delta = softplus(dt + dt_bias), A = -exp(A_log); the
    RECURRENCE a token at a time (a `lax.scan` over the positions, never a
    chunk): S_t = e^{Delta_t A} S_{t-1} + Delta_t x_t B_t^T, y_t = S_t C_t + D
    x_t; y <- RMSNorm(y * SiLU(z)) * gain over all heads' channels; out_proj.
    Departures: none in the equations (`time_step_limit` (0, inf) clamps
    nothing and is left out)."""
    seq = x.shape[0]
    heads, width, n = d["mamba_n_heads"], d["mamba_d_head"], d["mamba_d_state"]
    inner = heads * width
    proj = x @ p["in_proj"]
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
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, n), x.dtype), (xs, b, c, delta))
    y = rms_norm(y.reshape(seq, inner) * jax.nn.silu(z), p["norm"], d["rms_norm_eps"])
    return y @ p["out_proj"]


def granite_attention(p, x, d):
    """One sequence x (S, h) through grouped-query attention without rotation:
    query head i reads key/value head i // (heads / kv heads); scores q . k
    times `attention_multiplier`; a dense S x S causal mask."""
    seq = x.shape[0]
    heads, kv, width = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    q = (x @ p["q"]).reshape(seq, heads, width)
    k, v = ((x @ p[n]).reshape(seq, kv, width) for n in ("k", "v"))
    reads = jnp.arange(heads) // (heads // kv)
    scores = jnp.einsum("qhd,khd->hqk", q, k[:, reads]) * d["attention_multiplier"]
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v[:, reads]).reshape(seq, heads * width) @ p["o"]


def granite_sequence(params, ids, d):
    """One row of S + 2 ids (the last is not read) -> logits (S, V): the
    embedding times `embedding_multiplier`; each layer x + r Mixer(N(x)), then
    x + r MLP(N(x)) (r = `residual_multiplier`), the mixer Mamba-2 where the
    layer holds a `mamba` group, else attention; logits = N(x) E^T /
    `logits_scaling`, the head being the embedding."""
    seq = ids.shape[0] - 2
    eps, r = d["rms_norm_eps"], d["residual_multiplier"]
    x = params["embed"][ids[:seq]] * d["embedding_multiplier"]
    for i in range(d["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        mixed = rms_norm(x, p["attn_norm"], eps)
        x = x + r * (granite_mamba(p["mamba"], mixed, d) if "mamba" in p else granite_attention(p["attn"], mixed, d))
        m = p["mlp"]
        x = x + r * gated_mlp(m["gate"], m["up"], m["down"], rms_norm(x, p["mlp_norm"], eps))
    return rms_norm(x, params["final_norm"], eps) @ params["embed"].T / d["logits_scaling"]


def granite_loss_and_aux(params, tokens, d):
    """tokens (B, S + 2) -> (loss, {"ce", "logits"}): the mean cross-entropy
    of the next token over all B * S tokens."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1] - 2
        logits = jnp.stack([granite_sequence(params, ids, d) for ids in tokens])
        ce = sum(cross_entropy(z, ids[1:seq + 1]) for z, ids in zip(logits, tokens)) / (tokens.shape[0] * seq)
        return ce, {"ce": ce, "logits": logits}


def granite_loss_and_grads(params, tokens, d):
    """((loss, aux), gradients of the loss by parameter)."""
    return jax.value_and_grad(granite_loss_and_aux, has_aux=True)(params, tokens, d)


# ---- `laguna`: sliding-window and full grouped-query attention, per-head gates, softmax-routed experts ----------
# (Laguna-S-2.1)

LAGUNA_DIM_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_key_value_heads", "head_dim",
                   "rms_norm_eps", "layer_types", "num_attention_heads_per_layer", "sliding_window", "rope_parameters",
                   "n_routed_experts", "num_experts_per_tok", "routed_scaling_factor", "expert_shares",
                   "expert_share_index")


def laguna_dims_of(lm_config) -> dict:
    return {k: getattr(lm_config, k) for k in LAGUNA_DIM_KEYS}


def laguna_inv_freq(spec, head_dim):
    """The rotated channels' frequencies of a `rope_parameters` entry, in
    float64 then float32: `default`, theta^(-2i/r) over the r rotated
    channels; `yarn` (arXiv:2309.00071 section 3.2, "NTK-by-parts"), each
    frequency theta^(-2i/r) kept where its wavelength is short (it turns more
    than beta_fast times in the original context), divided by `factor` where
    it is long (fewer than beta_slow turns), and blended linearly between the
    two in the channels between, the channel bounds rounded outwards to whole
    channels (floor below, ceil above)."""
    r = int(head_dim * spec.partial_rotary_factor)
    i = np.arange(r // 2, dtype=np.float64)
    freq = spec.rope_theta ** (-2.0 * i / r)
    if spec.rope_type == "default":
        return freq.astype(np.float32)
    turns = lambda n: r * math.log(spec.original_max_position_embeddings / (2 * math.pi * n)) / (2 * math.log(spec.rope_theta))  # noqa: E731
    lo, hi = max(math.floor(turns(spec.beta_fast)), 0), min(math.ceil(turns(spec.beta_slow)), r - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)  # 0: extrapolated (kept), 1: interpolated (/ factor)
    return (freq * (1.0 - ramp) + freq / spec.factor * ramp).astype(np.float32)


def laguna_rope(x, spec):
    """x (S, heads, d): the first r = partial_rotary_factor x d channels
    turned, channel i with i + r/2, position s by s x frequency i; cos and
    sin times `attention_factor` where the type is `yarn`; the rest as they
    are. ASSUMED: the rotated channels are the FIRST r of a head."""
    seq, _, d = x.shape
    freq = jnp.asarray(laguna_inv_freq(spec, d))
    r = 2 * freq.shape[0]
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * freq[None, None, :]
    scale = spec.attention_factor if spec.rope_type == "yarn" else 1.0
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], -1)


def laguna_attention(p, x, d, kind, heads):
    """One sequence x (S, h) through a `laguna` attention layer: q, k, v three
    projections (no bias, no q/k norm: ASSUMED), query head i reading
    key/value head i // (heads / kv heads), the layer type's rotation of q
    and k, scores q . k / sqrt(head_dim), a dense S x S mask (causal, and
    within the window where `kind` is `sliding_attention`: key k visible to
    query q where q - window < k <= q), each head's output times
    sigmoid(x W_g), then o."""
    seq = x.shape[0]
    kv, width = d["num_key_value_heads"], d["head_dim"]
    spec = getattr(d["rope_parameters"], kind)
    q = laguna_rope((x @ p["q"]).reshape(seq, heads, width), spec)
    k = laguna_rope((x @ p["k"]).reshape(seq, kv, width), spec)
    v = (x @ p["v"]).reshape(seq, kv, width)
    reads = jnp.arange(heads) // (heads // kv)
    scores = jnp.einsum("qhd,khd->hqk", q, k[:, reads]) / math.sqrt(width)
    at, of = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    mask = of <= at
    if kind == "sliding_attention":
        mask = mask & (of > at - d["sliding_window"])
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v[:, reads])
    out = out * jax.nn.sigmoid(x @ p["gate"])[:, :, None]
    return out.reshape(seq, heads * width) @ p["o"]


def laguna_experts(p, x, d):
    """(routed output of this share (S, h), assignments per expert (E,)):
    softmax over ALL experts in float32, the top-k of those scores, their
    weights the selected scores divided by their sum (`norm_topk_prob`) times
    `routed_scaling_factor` (the published `moe_routed_scaling_factor`); no
    selection bias. ASSUMED (the published config names the Qwen2-MoE
    family's keys and no scoring function)."""
    n, k = d["n_routed_experts"], d["num_experts_per_tok"]
    held = n // d["expert_shares"]
    first = d["expert_share_index"] * held
    scores = jax.nn.softmax(x @ p["router"], axis=-1)
    _, chosen = jax.lax.top_k(scores, k)
    chosen = jax.nn.one_hot(chosen, n).sum(axis=1)  # (S, E) 0/1
    weight = chosen * scores
    weight = weight / weight.sum(axis=-1, keepdims=True) * d["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for j in range(held):
        e = p["experts"]
        out = out + weight[:, first + j, None] * gated_mlp(e["gate"][j], e["up"][j], e["down"][j], x)
    return out, chosen.sum(axis=0)


def laguna_sequence(params, ids, d):
    """One row of S + 2 ids (the last is not read) -> (logits (S, V), load
    by expert block): each layer x + Attn(N(x)) with the layer's own type
    and head count, then x + MLP(N(x)) where the layer is dense, else x +
    sigmoid(N(x) . w_s) Shared(N(x)) + Routed(N(x)); logits N(x) W_head."""
    seq = ids.shape[0] - 2
    eps = d["rms_norm_eps"]
    x = params["embed"][ids[:seq]]
    loads = {}
    for i in range(d["num_hidden_layers"]):
        name = f"layer_{i}"
        p = params[name]
        x = x + laguna_attention(p["attn"], rms_norm(x, p["attn_norm"], eps), d, d["layer_types"][i],
                                 d["num_attention_heads_per_layer"][i])
        y = rms_norm(x, p["mlp_norm"], eps)
        if i < d["first_k_dense_replace"]:
            x = x + gated_mlp(p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], y)
            continue
        s = p["shared"]
        routed, loads[name] = laguna_experts(p, y, d)
        x = x + jax.nn.sigmoid(y @ s["sigmoid_gate"])[:, None] * gated_mlp(s["gate"], s["up"], s["down"], y) + routed
    return rms_norm(x, params["final_norm"], eps) @ params["head"], loads


def laguna_loss_and_aux(params, tokens, d):
    """tokens (B, S + 2) -> (loss, {"ce", "logits", "loads"}): the mean
    cross-entropy of the next token over all B * S tokens."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1] - 2
        ce, logits, loads = 0.0, [], {}
        for ids in tokens:
            z, load = laguna_sequence(params, ids, d)
            ce = ce + cross_entropy(z, ids[1:seq + 1]) / (tokens.shape[0] * seq)
            logits.append(z)
            loads = {k: loads.get(k, 0.0) + v for k, v in load.items()}
        return ce, {"ce": ce, "logits": jnp.stack(logits), "loads": loads}


def laguna_loss_and_grads(params, tokens, d):
    """((loss, aux), gradients of the loss by parameter)."""
    return jax.value_and_grad(laguna_loss_and_aux, has_aux=True)(params, tokens, d)

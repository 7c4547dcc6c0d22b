"""Multiply-accumulates of one forward pass of a `laguna` share (Laguna-S-2.1:
sliding-window and full grouped-query attention with per-head output gates,
a dense first layer, then softmax-routed experts beside a gated shared
expert) over ONE sequence, counted from shapes alone: the benchmark's own
count for this architecture, kept here so that no later PR can move the MFU
by moving the arithmetic. Input is the configuration file's own keys, nothing
of the program's.

Convention: benchmark/macs_lm.py's. Matmuls only (norms, the rotation, the
sigmoids, the softmax, the router's top-k and the embedding gather are free).
A full layer's attention counts its CAUSAL pairs, S (S + 1) / 2 a head, a
sliding layer's the pairs its WINDOW admits (benchmark/roofline_window.py
`window_pairs`: sum over positions p of min(p + 1, W)), for scores and for
values, whatever implements them: a masked causal pass over the window's
layers does 8.3 times that work at 8,192 positions and reads no higher.
Routed experts count at their EXPECTED load, `num_experts_per_tok * held /
router_width` experts a token (10 x 8/256 here). Recomputation does not
count. One "image" of `train_images_per_s_per_chip` is one sequence, so
`forward_macs` is the cell's `macs_per_image`.
"""

from __future__ import annotations

from benchmark.roofline_window import window_pairs


def parts(config: dict, seq_len: int, router_width: int) -> dict[str, int]:
    """MACs of one sequence by kind of work. `config`: the configuration file
    (`n_routed_experts` = experts HELD, `vocab_size` = rows HELD,
    `num_hidden_layers` and the per-layer lists = layers held);
    `router_width`: the published number of routed experts, which the router
    still scores."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * d
    layers = config["num_hidden_layers"]
    types = list(config["layer_types"])[:layers]
    heads = list(config["num_attention_heads_per_layer"])[:layers]
    dense_layers = config["first_k_dense_replace"]
    expert_layers = layers - dense_layers
    routed_x256 = 3 * h * config["moe_intermediate_size"] * config["num_experts_per_tok"] * config["n_routed_experts"] * 256
    assert routed_x256 % router_width == 0
    causal, windowed = seq_len * (seq_len + 1) // 2, window_pairs(seq_len, config["sliding_window"])
    return {
        # q, o (heads x d wide), k, v (the key/value heads), the per-head gate
        "attn_proj": seq_len * sum(h * (2 * n * d + 2 * kv + n) for n in heads),
        "attn_core_full": sum(causal * n * 2 * d for n, t in zip(heads, types) if t == "full_attention"),
        "attn_core_window": sum(windowed * n * 2 * d for n, t in zip(heads, types) if t == "sliding_attention"),
        "dense_mlp": dense_layers * seq_len * 3 * h * config["intermediate_size"],
        # the shared expert and its sigmoid gate (h a token)
        "shared_experts": expert_layers * seq_len * (3 * h * config["shared_expert_intermediate_size"] + h),
        "routed_experts_expected": expert_layers * seq_len * (routed_x256 // router_width) // 256,
        "router": expert_layers * seq_len * h * router_width,
        "lm_head": seq_len * h * config["vocab_size"],
    }


def forward_macs(config: dict, seq_len: int, router_width: int) -> int:
    return sum(parts(config, seq_len, router_width).values())

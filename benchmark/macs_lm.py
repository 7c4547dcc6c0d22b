"""Multiply-accumulates of one forward pass of a `glm4_moe_lite` share over
ONE sequence, counted from shapes alone: the benchmark's own count for token
models, kept here so that no later PR can move the MFU by moving the
arithmetic. Input is the configuration file's own keys (the published
`config.json` names), nothing of the program's.

Convention: matmuls only (norms, RoPE, softmax, gates, the router's top-k and
the embedding gather are free). Attention counts the CAUSAL pairs, S (S + 1) / 2
a head, for scores and for values. Routed experts count at their EXPECTED
load: `num_experts_per_tok * held / router_width` experts a token (4 x 8/64 =
0.5 here); what a run's routing really sent is a counter of the program, not
part of this count. Recomputation (jax.checkpoint) does not count. One
"image" of `train_images_per_s_per_chip` is one sequence in a token cell, so
`forward_macs` is that cell's `macs_per_image`; a train step is
`macs.TRAIN_FLOPS_PER_MAC` FLOPs a MAC, as for the CNN cells.
"""

from __future__ import annotations


def parts(config: dict, seq_len: int, router_width: int) -> dict[str, int]:
    """MACs of one sequence by kind of work. `config`: the configuration
    file (`n_routed_experts` = experts HELD, `vocab_size` = rows HELD,
    `num_hidden_layers` = dense + expert layers held); `router_width`: the
    published number of routed experts, which the router still scores."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    dense_layers = config["first_k_dense_replace"]
    expert_blocks = config["num_hidden_layers"] - dense_layers + config["num_nextn_predict_layers"]
    blocks = dense_layers + expert_blocks
    width = config["moe_intermediate_size"]
    heads_out = 1 + config["num_nextn_predict_layers"]

    proj_per_token = (h * q_rank + q_rank * heads * (nope + rope) + h * (kv_rank + rope)
                      + kv_rank * heads * (nope + v) + heads * v * h)
    causal_pairs = seq_len * (seq_len + 1) // 2
    routed_per_token_x64 = 3 * h * width * config["num_experts_per_tok"] * config["n_routed_experts"] * 64
    assert routed_per_token_x64 % router_width == 0
    return {
        "attn_proj": blocks * seq_len * proj_per_token,
        "attn_core": blocks * causal_pairs * heads * ((nope + rope) + v),
        "dense_mlp": dense_layers * seq_len * 3 * h * config["intermediate_size"],
        "shared_experts": expert_blocks * seq_len * 3 * h * width * config["n_shared_experts"],
        "routed_experts_expected": expert_blocks * seq_len * (routed_per_token_x64 // router_width) // 64,
        "router": expert_blocks * seq_len * h * router_width,
        "mtp_merge": config["num_nextn_predict_layers"] * seq_len * 2 * h * h,
        "lm_head": heads_out * seq_len * h * config["vocab_size"],
    }


def forward_macs(config: dict, seq_len: int, router_width: int) -> int:
    return sum(parts(config, seq_len, router_width).values())

"""Multiply-accumulates of one forward pass of a `kimi_linear` share over ONE
sequence, counted from shapes alone: the benchmark's own count for this
architecture, kept here so that no later PR can move the MFU by moving the
arithmetic. Input is the configuration file's own keys, nothing of the
program's.

Convention: benchmark/macs_lm.py's. Matmuls only (norms, the short
convolutions, L2 norms, gates, softmax, the router's top-k and the embedding
gather are free). Latent attention counts the CAUSAL pairs, S (S + 1) / 2 a
head, for scores and for values. Routed experts count at their EXPECTED load,
`num_experts_per_tok * held / router_width` experts a token (8 x 8/256 = 0.25
here). Recomputation does not count. **Kimi Delta Attention's recurrence counts
3 x head_dim x head_dim a head a token** (the state read with the key, written
with key x value, read with the query), WHATEVER implements it: the program's
chunked form does other work (in-chunk scores, a triangular solve, the
state's matmuls once a chunk), and none of that is what the model requires.
One "image" of `train_images_per_s_per_chip` is one sequence, so
`forward_macs` is the cell's `macs_per_image`.
"""

from __future__ import annotations


def mixers(config: dict) -> tuple[int, int]:
    """(KDA layers, latent-attention layers) among the layers held: layers 1
    to `num_hidden_layers`, as `linear_attn_config` numbers them."""
    held = range(1, config["num_hidden_layers"] + 1)
    kda = sum(1 for layer in held if layer in config["linear_attn_config"]["kda_layers"])
    return kda, len(held) - kda


def parts(config: dict, seq_len: int, router_width: int) -> dict[str, int]:
    """MACs of one sequence by kind of work. `config`: the configuration file
    (`n_routed_experts` = experts HELD, `vocab_size` = rows HELD,
    `num_hidden_layers` = layers held); `router_width`: the published number
    of routed experts, which the router still scores."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    kv_rank = config["kv_lora_rank"]
    la = config["linear_attn_config"]
    kda_wide = la["num_heads"] * la["head_dim"]
    kda_layers, mla_layers = mixers(config)
    dense_layers = config["first_k_dense_replace"]
    expert_blocks = config["num_hidden_layers"] - dense_layers
    width = config["moe_intermediate_size"]

    mla_proj_per_token = (h * heads * (nope + rope) + h * (kv_rank + rope) + kv_rank * heads * (nope + v)
                          + heads * v * h)
    # q, k, v, o; the decay gate's and the output gate's low-rank pairs (width head_dim); beta
    kda_proj_per_token = 4 * h * kda_wide + 2 * (h * la["head_dim"] + la["head_dim"] * kda_wide) + h * la["num_heads"]
    causal_pairs = seq_len * (seq_len + 1) // 2
    routed_per_token_x256 = 3 * h * width * config["num_experts_per_tok"] * config["n_routed_experts"] * 256
    assert routed_per_token_x256 % router_width == 0
    return {
        "kda_proj": kda_layers * seq_len * kda_proj_per_token,
        "kda_recurrence": kda_layers * seq_len * la["num_heads"] * 3 * la["head_dim"] ** 2,
        "attn_proj": mla_layers * seq_len * mla_proj_per_token,
        "attn_core": mla_layers * causal_pairs * heads * ((nope + rope) + v),
        "dense_mlp": dense_layers * seq_len * 3 * h * config["intermediate_size"],
        "shared_experts": expert_blocks * seq_len * 3 * h * width * config["n_shared_experts"],
        "routed_experts_expected": expert_blocks * seq_len * (routed_per_token_x256 // router_width) // 256,
        "router": expert_blocks * seq_len * h * router_width,
        "lm_head": seq_len * h * config["vocab_size"],
    }


def forward_macs(config: dict, seq_len: int, router_width: int) -> int:
    return sum(parts(config, seq_len, router_width).values())

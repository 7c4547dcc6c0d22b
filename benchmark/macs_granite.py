"""Multiply-accumulates of one forward pass of a `granitemoehybrid` stage
(Granite 4.0-H: Mamba-2 mixers beside grouped-query attention, every MLP
dense, the head tied to the embedding) over ONE sequence, counted from shapes
alone: the benchmark's own count for this architecture, kept here so that no
later PR can move the MFU by moving the arithmetic. Input is the configuration
file's own keys, nothing of the program's.

Convention: benchmark/macs_lm.py's. Matmuls, and the depthwise convolution's
taps; the norms, SiLU, softplus, the decays, the softmax and the embedding
gather are free. Attention counts the CAUSAL pairs, S (S + 1) / 2 a head, for
scores and for values. **The state-space layer counts its RECURRENCE**, 2 x
heads x head_dim x state a token (the write Delta x B^T and the read S C),
whatever implements it: the chunked form's in-chunk products, which are more,
do not count, so a faster form of the same recurrence is a higher MFU and a
form that does more work is not. Recomputation (jax.checkpoint) does not
count. One "image" of `train_images_per_s_per_chip` is one sequence, so
`forward_macs` is the cell's `macs_per_image`; a train step is
`macs.TRAIN_FLOPS_PER_MAC` FLOPs a MAC.
"""

from __future__ import annotations


def parts(config: dict, seq_len: int) -> dict[str, int]:
    """MACs of one sequence by kind of work. `config`: the configuration file
    (`num_hidden_layers` and `layer_types` = layers HELD, `vocab_size` = rows
    HELD)."""
    h = config["hidden_size"]
    types = list(config["layer_types"])[:config["num_hidden_layers"]]
    mamba, attention = types.count("mamba"), types.count("attention")
    heads, kv, head_dim = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    channels = inner + 2 * config["mamba_d_state"]  # x, and ONE group of B and C (mamba_n_groups 1)
    causal_pairs = seq_len * (seq_len + 1) // 2
    return {
        "ssd_proj": mamba * seq_len * (h * (inner + channels + config["mamba_n_heads"]) + inner * h),
        "ssd_conv": mamba * seq_len * channels * config["mamba_d_conv"],
        "ssd_recurrence": mamba * seq_len * 2 * inner * config["mamba_d_state"],
        "attn_proj": attention * seq_len * h * (2 * heads * head_dim + 2 * kv * head_dim),
        "attn_core": attention * causal_pairs * heads * 2 * head_dim,
        "mlp": len(types) * seq_len * 3 * h * config["intermediate_size"],
        "lm_head": seq_len * h * config["vocab_size"],
    }


def forward_macs(config: dict, seq_len: int) -> int:
    return sum(parts(config, seq_len).values())

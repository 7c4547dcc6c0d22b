"""Multiply-accumulates of one forward pass of an `ouro` model (a LOOPED
model: the layers held run `total_ut_steps` times a step with the same
weights, the head after every run) over ONE sequence, counted from shapes
alone: the benchmark's own count for this architecture, kept here so that no
later PR can move the MFU by moving the arithmetic. Input is the configuration
file's own keys, nothing of the program's.

Convention: benchmark/macs_lm.py's. Matmuls only (the norms, four a block and
one a loop step, RoPE, softmax, the sigmoid and the exit distribution and the
embedding gather are free). Attention counts the CAUSAL pairs, S (S + 1) / 2 a
head, for scores and for values. A layer counts once an APPLICATION, a weight
read four times is four matmuls; the head and the exit gate's one row count
once a loop step (the last step's gate is computed and not read: it counts, as
it runs). Recomputation (jax.checkpoint) does not count. One "image" of
`train_images_per_s_per_chip` is one sequence, so `forward_macs` is the cell's
`macs_per_image`; a train step is `macs.TRAIN_FLOPS_PER_MAC` FLOPs a MAC.
"""

from __future__ import annotations


def parts(config: dict, seq_len: int) -> dict[str, int]:
    """MACs of one sequence by kind of work. `config`: the configuration file
    (`num_hidden_layers` = layers HELD, `vocab_size` = the whole vocabulary)."""
    h = config["hidden_size"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    applications = config["num_hidden_layers"] * config["total_ut_steps"]
    causal_pairs = seq_len * (seq_len + 1) // 2
    return {
        "attn_proj": applications * seq_len * 4 * h * heads * head_dim,  # q, k, v, o
        "attn_core": applications * causal_pairs * heads * 2 * head_dim,  # scores, values
        "mlp": applications * seq_len * 3 * h * config["intermediate_size"],
        "lm_head": config["total_ut_steps"] * seq_len * h * config["vocab_size"],
        "exit_gate": config["total_ut_steps"] * seq_len * h,
    }


def forward_macs(config: dict, seq_len: int) -> int:
    return sum(parts(config, seq_len).values())

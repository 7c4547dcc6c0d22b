"""The operations a sliding window's attention kernels (ops/lm_attention_kernels.py
`window_fwd_call` / `window_bwd_call`: instructions `window_attention_fwd.N` /
`window_attention_bwd.N`) MUST do, a call, counted from shapes alone: the
benchmark's own count, kept here so that no later PR can move the roofline
share by moving the arithmetic.

Only the (query, key) pairs the window admits count: key k is visible to
query q where q - W < k <= q, so a head of S positions has sum over p of
min(p + 1, W) pairs (`window_pairs`). A forward call makes each pair's score
(D multiply-adds) and its share of the output (D more): 4 D FLOPs a pair. A
backward call makes dV, dP, dK and dQ: 8 D FLOPs a pair; making the scores
again, which the backward does rather than keep them, is not counted. What a
kernel does beyond that (the masked part of the tiles the window's edge
crosses, the filled channels of a head narrower than the lanes) is not
counted either: the share is of the least a call must do, so it cannot pass
100% unless the time is short of the work.
"""

from __future__ import annotations


def window_pairs(seq: int, window: int) -> int:
    """sum over positions p < seq of min(p + 1, window): the (query, key) pairs a head's window admits."""
    inside = min(seq, window)
    return inside * (inside + 1) // 2 + (seq - inside) * window


def window_flops(batch: int, heads: int, seq: int, head_dim: int, window: int) -> dict[str, int]:
    """{"fwd": FLOPs a forward call must do, "bwd": a backward call's}."""
    pairs = batch * heads * window_pairs(seq, window)
    return {"fwd": 4 * head_dim * pairs, "bwd": 8 * head_dim * pairs}

"""Multiply-accumulates of one forward pass, counted from shapes: the
benchmark's own count, kept here so that no later PR can move the MFU by
moving the arithmetic. Input is the network's serialized spec
(`models.serialize.network_to_dict`: plain sizes), not the program's objects.

Convention (the usual MobileNet accounting, and `utils/profiling.py`'s, which
a tier-1 test pins this equal to): convolutions and fully-connected layers
only; batch norm, activations, pooling and the SE gate's multiply are free.
A conv of kernel k, stride s and padding k//2 maps hw to (hw - 1)//s + 1.
"""

from __future__ import annotations

# A train step's FLOPs per forward MAC: 2 FLOPs a MAC, and the backward pass
# costs two forwards (input and weight gradients). Recomputation does not count.
TRAIN_FLOPS_PER_MAC = 6


def _out_hw(hw: int, stride: int) -> int:
    return (hw - 1) // stride + 1


def _conv(hw: int, spec: dict) -> tuple[int, int]:
    out = _out_hw(hw, spec["stride"])
    per_pixel = spec["kernel_size"] ** 2 * (spec["in_channels"] // spec["groups"]) * spec["out_channels"]
    return out * out * per_pixel, out


def _block(hw: int, b: dict) -> tuple[int, int]:
    e = b["expanded_channels"]
    out = _out_hw(hw, b["stride"])
    macs = 0
    if b["force_expand"] or e != b["in_channels"]:
        macs += hw * hw * b["in_channels"] * e  # 1x1 expand at the input resolution
    for k, g in zip(b["kernel_sizes"], b["group_channels"]):
        macs += out * out * k * k * g  # depthwise, one filter per channel
    if b["se_channels"]:
        macs += 2 * e * b["se_channels"]  # squeeze FC and excite FC on the pooled vector
    macs += out * out * e * b["out_channels"]  # 1x1 project
    return macs, out


def forward_macs(spec: dict, image_size: int | None = None) -> int:
    hw = image_size or spec["image_size"]
    total, hw = _conv(hw, spec["stem"])
    for b in spec["blocks"]:
        macs, hw = _block(hw, b)
        total += macs
    if spec["head"] is not None:
        macs, hw = _conv(hw, spec["head"])
        total += macs
    for dense in (spec["feature"], spec["classifier"]):
        if dense is not None:
            total += dense["in_features"] * dense["out_features"]
    return total

#!/usr/bin/env python3
"""One Kimi Delta Attention layer's pieces on the chip, at the token cell's
shapes (1 x 16,384 tokens, 32 heads of 128, bfloat16): what each part of
ops/lm_kda.py costs alone, forward and forward + backward, and how the core's
time moves with its three module constants. What chose them (PERF.md, PR 33).

    python scripts/bench_kda.py [--iters 5] [--chunks 64,128] [--subs 16] [--groups 8] [--core-only]
    python scripts/bench_kda.py --conv-only [--conv-tiles 512x512,1024x512]
    python scripts/bench_kda.py --scan-only [--scan-heads 1,2,4,8] [--scan-chunks 8,16]

Beside the plain pieces it times the two fused kernels of ops/lm_kda_kernels.py
(PR 36) wherever `lm_kda.fuses` takes the shape: the forward kernel, the
kernel pair, and `kda_core` through each form (`--core-only` stops there).
And the short convolution with its SiLU and, for q and k, the L2 norm of a
head (`lm_kda.conv_and_norm`), plain and as the pair of conv kernels, at each
tile of `--conv-tiles` (rows x lanes; `--conv-only` times nothing else), with
the kernels' largest deviation from the plain form on the chip. And the scan
over the chunks alone (`--scan-only`): the plain `lax` scan, the same with
its output laid out as (B, S, H x D) as `kda_core` needs it, and the scan
kernels (`lm_kda._fused_scan`) at each pair of heads and chunks a program
instance carries, forward and forward + backward, with the kernels' largest
deviation from the plain scan on the chip; then each with the gated output
norm that reads it (over heads, and `lm_kda._banded_gated_norm` band by band).

Measures on a TPU or exits 3. Prints one JSON line a piece: ms a call (host
clock around `iters` calls ending in a sync).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from yet_another_mobilenet_series_tpu.ops import lm_kda  # noqa: E402

B, S, H, D, HIDDEN = 1, 16384, 32, 128, 2304  # kimilinear_train_1x16k's KDA layer


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))  # compiles
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def say(name, ms, **more):
    print(json.dumps({"piece": name, "ms": round(ms, 3), **more}), flush=True)


def total(tree):
    return sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(tree))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--chunks", default=str(lm_kda.KDA_CHUNK))
    ap.add_argument("--subs", default=str(lm_kda.KDA_SUBCHUNK))
    ap.add_argument("--groups", default=str(lm_kda.KDA_HEAD_GROUP))
    ap.add_argument("--core-only", action="store_true", help="kda_core and its operands through both forms, nothing else")
    ap.add_argument("--conv-only", action="store_true", help="the short convolution and L2 norm through both forms, nothing else")
    ap.add_argument("--conv-tiles", default="", help="rows x lanes of the conv kernels' tile, comma-separated (default: the module's)")
    ap.add_argument("--scan-only", action="store_true", help="the scan over the chunks through both forms, nothing else")
    ap.add_argument("--scan-heads", default="", help="heads a scan kernel's program instance carries, comma-separated (default: the module's)")
    ap.add_argument("--scan-chunks", default="", help="chunks a program instance walks, comma-separated (default: the module's)")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print(f"bench_kda: no TPU (platform {jax.devices()[0].platform!r}): this script measures on the chip", file=sys.stderr)
        return 3
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q, k, v = (jax.random.normal(key, (B, S, H, D), jnp.float32) for key in ks[:3])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5).astype(jnp.bfloat16)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, D), minval=jnp.log(1e-3), maxval=jnp.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    core_args = (q, k, v, g, beta)
    it = args.iters
    if args.conv_only:
        return conv(args, ks, it)
    if args.scan_only:
        return scan(args, core_args, ks, it)

    for chunk in map(int, args.chunks.split(",")):
        for sub in map(int, args.subs.split(",")):
            for group in map(int, args.groups.split(",")):
                lm_kda.KDA_CHUNK, lm_kda.KDA_SUBCHUNK, lm_kda.KDA_HEAD_GROUP = chunk, sub, group
                tag = {"chunk": chunk, "sub": sub, "heads_at_once": group}
                grads = lambda fn: jax.jit(jax.grad(lambda *a: total(fn(*a)), argnums=(0, 1, 2, 3, 4)))  # noqa: E731
                fits = lm_kda.fuses(S, chunk, D, q.dtype)
                takes = lm_kda.fuses
                for form in ("plain", "fused kernels") if fits else ("plain",):
                    # the dispatch reads `fuses` while it traces: the plain form at a shape the kernels take is that of a refusing predicate
                    lm_kda.fuses = takes if form != "plain" else (lambda *a: False)
                    core = lambda *a: lm_kda.kda_core(*a)[0]  # noqa: E731 - a new function a form: jit caches by the function
                    say(f"kda_core, {form}, fwd", timed(jax.jit(core), core_args, it), **tag)
                    say(f"kda_core, {form}, fwd+bwd", timed(grads(core), core_args, it), **tag)
                lm_kda.fuses = takes
                # what depends on a chunk alone, the whole layer: the plain form (head groups, regrouping copies and all) and the kernels
                whole = (q, k, v, g, beta)
                say("chunk operands, plain, all heads, fwd", timed(jax.jit(lambda *a: lm_kda._plain_operands(*a)[0]), whole, it), **tag)
                say("chunk operands, plain, all heads, fwd+bwd", timed(grads(lambda *a: lm_kda._plain_operands(*a)[0]), whole, it), **tag)
                if fits:
                    say("chunk operands, forward kernel, fwd", timed(jax.jit(lambda *a: lm_kda.operands_fwd(*a)[0]), whole, it), **tag)
                    say("chunk operands, kernel pair, fwd+bwd", timed(grads(lambda *a: lm_kda._fused_operands(*a)[0]), whole, it), **tag)
                    # the two backwards alone, from the same cotangents: what decides whether the second kernel ships
                    cts = jax.tree.map(lambda x: jnp.ones(x.shape, x.dtype), jax.eval_shape(lambda *a: lm_kda.operands_fwd(*a)[0], *whole))
                    say("chunk operands, backward kernel alone", timed(jax.jit(lm_kda.operands_bwd), (*whole, cts), it), **tag)
                    say("chunk operands, the plain form's vjp alone", timed(jax.jit(lm_kda._plain_operands_bwd), (*whole, cts), it), **tag)
                if args.core_only:
                    continue
                # the in-chunk work of ONE head group, and its backward
                n = S // chunk

                def one_group(x):  # (B, S, H, ...) -> (B, h, N, C, ...)
                    return jnp.moveaxis(x[:, :, :group].reshape(B, n, chunk, group, *x.shape[3:]), 3, 1)

                grouped = tuple(jax.jit(one_group)(x) for x in (q, k, v, g, beta[..., None]))
                say("in-chunk operands, one head group, fwd", timed(jax.jit(lambda *a: lm_kda._chunk_operands(*a)[0]), grouped, it), **tag,
                    groups=H // group)
                say("in-chunk operands, one head group, fwd+bwd",
                    timed(jax.jit(jax.grad(lambda *a: total(lm_kda._chunk_operands(*a)[0]), argnums=(0, 1, 2, 3, 4))), grouped, it),
                    **tag, groups=H // group)
                # pieces of it
                kf, gf = grouped[1].astype(jnp.float32), jnp.cumsum(grouped[3], axis=-2)
                say("decayed scores (A and B), one head group, fwd",
                    timed(jax.jit(lambda q_, k_, g_: lm_kda._decayed_scores(q_, k_, g_, min(sub, chunk), jnp.bfloat16)),
                          (grouped[0].astype(jnp.float32), kf, gf), it), **tag)
                a = jax.random.normal(ks[5], (B, group, n, chunk, chunk)) * 0.1
                rhs = jax.random.normal(ks[6], (B, group, n, chunk, 2 * D))
                solve = jax.jit(lambda a_, r_: lax.linalg.triangular_solve(jnp.eye(chunk) + jnp.tril(a_, -1), r_, left_side=True,
                                                                            lower=True, unit_diagonal=True))
                say("triangular solve, one head group, fwd", timed(solve, (a, rhs), it), **tag)
                # the scan over chunks, all heads
                operands = jax.jit(lambda *a_: tuple(jnp.moveaxis(x, 2, 0) for x in lm_kda._chunk_operands(*a_)[0]))(
                    *(jnp.moveaxis(x.reshape(B, n, chunk, H, *x.shape[3:]), 3, 1) for x in (q, k, v, g, beta[..., None])))
                say("scan over chunks, all heads, fwd", timed(jax.jit(lm_kda._state_scan), operands, it), **tag, steps=n)
                say("scan over chunks, all heads, fwd+bwd",
                    timed(jax.jit(jax.grad(lambda *a_: total(lm_kda._state_scan(*a_)), argnums=(0, 1, 2, 3, 4, 5))), operands, it),
                    **tag, steps=n)

    if args.core_only:
        return 0
    # the rest of the mixer
    z = jax.random.normal(ks[5], (B, S, H * D), jnp.bfloat16)
    w = jax.random.normal(ks[6], (4, H * D), jnp.float32) * 0.02
    say("short conv, one of three, fwd", timed(jax.jit(lm_kda.short_conv), (z, w), it))
    say("short conv, one of three, fwd+bwd", timed(jax.jit(jax.grad(lambda z_, w_: total(lm_kda.short_conv(z_, w_)), argnums=(0, 1))),
                                                   (z, w), it))

    def conv_op(z_, w_):  # the same convolution as ONE depthwise lax convolution, for comparison
        out = lax.conv_general_dilated(z_, w_.astype(z_.dtype)[:, None, :], (1,), [(w_.shape[0] - 1, 0)],
                                       dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=z_.shape[-1])
        return jax.nn.silu(out.astype(jnp.float32)).astype(z_.dtype)

    say("short conv as lax.conv_general_dilated, fwd", timed(jax.jit(conv_op), (z, w), it))
    say("short conv as lax.conv_general_dilated, fwd+bwd",
        timed(jax.jit(jax.grad(lambda z_, w_: total(conv_op(z_, w_)), argnums=(0, 1))), (z, w), it))
    def conv_bf16_pad(z_, w_):  # the padding in the operand's dtype, the taps' sum in float32
        taps, seq = w_.shape[0], z_.shape[1]
        padded = jnp.pad(z_, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, i:i + seq].astype(jnp.float32) * w_[i] for i in range(taps))).astype(z_.dtype)

    def conv_roll(z_, w_):  # rotations along the sequence, the rows that wrapped around zeroed
        taps, seq = w_.shape[0], z_.shape[1]
        z32 = z_.astype(jnp.float32)
        row = lax.broadcasted_iota(jnp.int32, (1, seq, 1), 1)
        acc = z32 * w_[taps - 1]
        for back in range(1, taps):
            acc = acc + jnp.where(row >= back, jnp.roll(z32, back, axis=1), 0.0) * w_[taps - 1 - back]
        return jax.nn.silu(acc).astype(z_.dtype)

    for name, fn in (("bf16 pad", conv_bf16_pad), ("roll", conv_roll)):
        say(f"short conv, {name}, fwd", timed(jax.jit(fn), (z, w), it))
        say(f"short conv, {name}, fwd+bwd", timed(jax.jit(jax.grad(lambda z_, w_, fn=fn: total(fn(z_, w_)), argnums=(0, 1))), (z, w), it))

    # the cell's ONE latent-attention layer: head dims 192 / 128 in the tile loops, and zero-padded to 256 / 128 in the kernels
    from yet_another_mobilenet_series_tpu.ops import lm as ops

    aq, ak = (jax.random.normal(key, (B, S, H, 192), jnp.bfloat16) for key in ks[:2])
    av = jax.random.normal(ks[2], (B, S, H, 128), jnp.bfloat16)

    def attention(pad):
        def fn(q_, k_, v_):
            if pad:
                q_, k_ = (jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 64))) for x in (q_, k_))
            return ops.causal_attention(q_, k_, v_, scale=192 ** -0.5)
        return fn

    for name, fn in (("loops 192/128", attention(False)), ("kernels, q k padded to 256", attention(True))):
        try:
            say(f"latent attention core, {name}, fwd", timed(jax.jit(fn), (aq, ak, av), it))
            say(f"latent attention core, {name}, fwd+bwd",
                timed(jax.jit(jax.grad(lambda *a, fn=fn: total(fn(*a)), argnums=(0, 1, 2))), (aq, ak, av), it))
        except Exception as e:  # noqa: BLE001 - a kernel the compiler refuses is a finding, not a crash
            say(f"latent attention core, {name}: refused", -1.0, error=str(e)[:400])
    say("l2 norm, one of two, fwd+bwd",
        timed(jax.jit(jax.grad(lambda x: total(lm_kda.l2_normalise(x)))), (q,), it))
    return conv(args, ks, it)


def conv(args, ks, it) -> int:
    """q's and v's short convolution (+ q's L2 norm), plain and through the conv kernels: what `kda_conv` costs a tensor."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    z = jax.random.normal(ks[5], (B, S, H * D), jnp.bfloat16)
    w = jax.random.normal(ks[6], (4, H * D), jnp.float32) * 0.5
    ct = jax.random.normal(ks[7], (B, S, H * D), jnp.bfloat16)
    # the cotangent an argument, not a constant the executable would carry; the loss is linear in the output, so XLA
    # drops the forward kernel here and "fwd+bwd" times the backward
    grads = lambda fn: jax.jit(jax.grad(lambda z_, w_, c_: total(fn(z_, w_) * c_), argnums=(0, 1)))  # noqa: E731
    deviation = lambda got, want: max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))  # noqa: E731
                                            / jnp.max(jnp.abs(b.astype(jnp.float32)))) for a, b in zip(got, want))
    tiles = [tuple(map(int, t.split("x"))) for t in args.conv_tiles.split(",") if t] or [(kernels.CONV_ROWS, kernels.CONV_LANES)]
    for name, scale in (("q: conv + L2 norm", D ** -0.5), ("v: conv", None)):
        plain = lambda z_, w_, scale=scale: lm_kda._plain_conv(z_, w_, D, scale)  # noqa: E731
        say(f"{name}, plain, fwd", timed(jax.jit(plain), (z, w), it))
        say(f"{name}, plain, fwd+bwd", timed(grads(plain), (z, w, ct), it))
        want = (jax.jit(plain)(z, w), *grads(plain)(z, w, ct))
        for rows, lanes in tiles:
            kernels.CONV_ROWS, kernels.CONV_LANES = rows, lanes
            jax.clear_caches()  # `lm_kda.conv_fwd` / `conv_bwd` are jitted: a trace of another tile would be reused
            fused = lambda z_, w_, scale=scale: lm_kda._fused_conv(z_, w_, D, scale)  # noqa: E731 - a new function a tile
            tag = {"rows": rows, "lanes": lanes}
            say(f"{name}, conv kernels, fwd", timed(jax.jit(fused), (z, w), it), **tag)
            say(f"{name}, conv kernels, fwd+bwd", timed(grads(fused), (z, w, ct), it), **tag)
            say(f"{name}, backward kernel alone", timed(jax.jit(lambda z_, w_, c_, scale=scale: lm_kda.conv_bwd(z_, w_, c_, D, scale)),
                                                       (z, w, ct), it), **tag)
            print(json.dumps({"piece": f"{name}, conv kernels against plain", **tag,
                              "deviation": deviation((jax.jit(fused)(z, w), *grads(fused)(z, w, ct)), want)}), flush=True)
    return 0


def scan(args, core_args, ks, it) -> int:
    """One layer's scan over its 256 chunks (32 heads of 128), from the in-chunk kernels' six operands: the plain `lax`
    scan, the same with `kda_core`'s layout change of its output, and the scan kernels at each (heads, chunks) cut."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    operands = jax.jit(lambda *a: lm_kda.operands_fwd(*a)[0])(*core_args)
    ct = jax.random.normal(ks[7], (B, S, H * D), jnp.bfloat16)
    by_chunk = jnp.moveaxis(ct.reshape(B, S // lm_kda.KDA_CHUNK, lm_kda.KDA_CHUNK, H, D), (1, 3), (0, 2))
    # the cotangent an argument, not a constant the executable would carry
    grads = lambda fn: jax.jit(jax.grad(lambda *a: total(fn(*a[:6]) * a[6]), argnums=tuple(range(6))))  # noqa: E731
    deviation = lambda got, want: max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))  # noqa: E731
                                            / jnp.max(jnp.abs(b.astype(jnp.float32)))) for a, b in zip(got, want))
    plain = lambda *a: lm_kda._by_position(lm_kda._state_scan(*a))  # noqa: E731
    say("scan, plain lax, fwd", timed(jax.jit(lm_kda._state_scan), operands, it), steps=S // lm_kda.KDA_CHUNK)
    say("scan, plain lax, fwd+bwd", timed(grads(lm_kda._state_scan), (*operands, by_chunk), it))
    say("scan, plain lax + layout to (B, S, H x D), fwd", timed(jax.jit(plain), operands, it))
    say("scan, plain lax + layout to (B, S, H x D), fwd+bwd", timed(grads(plain), (*operands, ct), it))
    want = (jax.jit(plain)(*operands), *grads(plain)(*operands, ct))
    heads = [int(h) for h in args.scan_heads.split(",") if h] or [kernels.SCAN_HEADS]
    chunks = [int(c) for c in args.scan_chunks.split(",") if c] or [kernels.CHUNKS_AT_ONCE]
    for at_once in chunks:
        for group in heads:
            kernels.SCAN_HEADS, kernels.CHUNKS_AT_ONCE = group, at_once
            jax.clear_caches()  # `lm_kda.scan_fwd` / `scan_bwd` are jitted: a trace of another cut would be reused
            fused = lambda *a: lm_kda._fused_scan(*a)  # noqa: E731 - a new function a cut
            tag = {"heads_at_once": group, "chunks_at_once": at_once}
            try:
                say("scan, kernels, fwd", timed(jax.jit(fused), operands, it), **tag)
                say("scan, kernels, fwd+bwd", timed(grads(fused), (*operands, ct), it), **tag)
                print(json.dumps({"piece": "scan, kernels against plain", **tag,
                                  "deviation": deviation((jax.jit(fused)(*operands), *grads(fused)(*operands, ct)), want)}),
                      flush=True)
            except Exception as e:  # noqa: BLE001 - a cut the compiler refuses is a finding, not a crash
                say("scan, kernels: refused", -1.0, **tag, error=str(e)[:400])
    # with the gated output norm that reads the scan's output (`kda_attention`): over heads after the plain scan's layout
    # change, and band by band on the kernels' (B, S, H x D)
    gate = jax.random.normal(ks[5], (B, S, H * D), jnp.bfloat16)
    gain = 1.0 + 0.1 * jax.random.normal(ks[6], (D,))

    def by_heads(out):
        o32 = out.reshape(B, S, H, D).astype(jnp.float32)
        normed = o32 * lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + 1e-5) * gain
        return (normed * jax.nn.sigmoid(gate.reshape(B, S, H, D).astype(jnp.float32))).astype(jnp.bfloat16).reshape(B, S, H * D)

    kernels.SCAN_HEADS, kernels.CHUNKS_AT_ONCE = heads[-1], chunks[-1]
    jax.clear_caches()
    normed = {"plain scan + the norm over heads": lambda *a: by_heads(plain(*a)),
              "scan kernels + the norm over heads": lambda *a: by_heads(lm_kda._fused_scan(*a)),
              "scan kernels + the banded norm": lambda *a: lm_kda._banded_gated_norm(lm_kda._fused_scan(*a), gate, gain, H,
                                                                                    1e-5).astype(jnp.bfloat16)}
    want = None
    for name, fn in normed.items():
        got = (jax.jit(fn)(*operands), *grads(fn)(*operands, ct))
        want = want or got
        say(f"{name}, fwd", timed(jax.jit(fn), operands, it), deviation=deviation(got, want))
        say(f"{name}, fwd+bwd", timed(grads(fn), (*operands, ct), it))
    return 0


if __name__ == "__main__":
    sys.exit(main())

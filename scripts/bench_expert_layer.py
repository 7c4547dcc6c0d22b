#!/usr/bin/env python3
"""One expert layer alone on the chip (ops/lm.py `expert_layer`), at the two
token cells' shapes: 16,384 tokens of width 2,048 through top-4 of 64 experts,
8 held (`glm47flash_train_2x8k`), and of width 2,304 through top-8 of 256, 8
held (`kimilinear_train_1x16k`); bfloat16, a fresh router, so the share holds
about its mean (an eighth, a thirty-second of the assignments). Forward, and
`fwd+bwd`: `jax.grad` of the layer under a `jax.checkpoint`, as a layer of the
step runs it (nothing reads the forward's result there, so in the bounded
variants it is the backward's `cond` alone: its own forward and backward; a
site costs a step the two lines' sum):

- `full-length`: every assignment's row sorted, gathered and multiplied (the
  capacity read as the number of assignments: the one branch a share that
  holds half the experts traces, and the fallback of every other);
- `bounded`: the shipped layer, whose `lax.cond` takes the branch over
  `capacity_rows` rows here;
- `bounded, scatter-add`: the same with the sum into token rows as a
  scatter-add of the held rows into a float32 (tokens, h), the lowering that
  lost to the gather (PERF.md, PR 34); it lives here, not in the program.

    python scripts/bench_expert_layer.py [--iters 10] [--cells glm,kimi]

In a checkout from before the bounded branch it times that checkout's layer as
`full-length` and nothing else. Measures on a TPU or exits 3. Prints one JSON
line a piece: ms a call (host clock around `iters` calls ending in a sync).
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

from yet_another_mobilenet_series_tpu.ops import lm as ops  # noqa: E402

TOKENS = 16384
# hidden, expert width, router width, held, top_k, routed_scaling_factor
CELLS = {"glm": (2048, 1536, 64, 8, 4, 1.8), "kimi": (2304, 1024, 256, 8, 8, 2.446)}


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


def scatter_add_sum(x, order, at, repeat):
    """`ops._sum_of_rows` as a scatter-add (rows that stand for no assignment
    come in zeroed); autodiff's transpose of it is the gather `x[order // repeat]`."""
    out = jnp.zeros((at.shape[0] // repeat, x.shape[-1]), jnp.float32)
    return out.at[order // repeat].add(x.astype(jnp.float32)).astype(x.dtype)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cells", default="glm,kimi")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print(f"bench_expert_layer: no TPU (platform {jax.devices()[0].platform!r}): this script measures on the chip",
              file=sys.stderr)
        return 3
    it = args.iters
    bounded_here = hasattr(ops, "capacity_rows")
    shipped = {name: getattr(ops, name) for name in ("capacity_rows", "_sum_of_rows") if bounded_here}
    for cell in args.cells.split(","):
        h, m, n_experts, held, top_k, scaling = CELLS[cell]
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        p = {"router": 0.02 * jax.random.normal(ks[0], (h, n_experts)),
             "experts": {"gate": 0.02 * jax.random.normal(ks[1], (held, h, m)), "up": 0.02 * jax.random.normal(ks[2], (held, h, m)),
                         "down": 0.02 * jax.random.normal(ks[3], (held, m, h))}}
        x = jax.random.normal(ks[4], (1, TOKENS, h), jnp.bfloat16)
        bias = jnp.zeros((n_experts,), jnp.float32)
        n = TOKENS * top_k
        tag = {"cell": cell, "assignments": n, "width": h}

        def layer(p_, x_):
            return ops.expert_layer(p_, bias, x_, top_k=top_k, scaling=scaling, held=held, share_index=0)

        variants = [("full-length", {})]  # a checkout from before the bounded branch: its layer as it is
        if bounded_here:
            variants = [("full-length", {"capacity_rows": lambda *a: a[0]}), ("bounded", {}),
                        ("bounded, scatter-add", {"_sum_of_rows": scatter_add_sum})]
            tag["capacity_rows"] = ops.capacity_rows(n, held, n_experts)
        for name, patch in variants:
            for key, value in {**shipped, **patch}.items():
                setattr(ops, key, value)
            jax.clear_caches()  # the layer's halves are jitted: a cached trace would hold the last variant's lowering
            counters = jax.jit(lambda p_, x_: layer(p_, x_)[2])(p, x)
            say(f"expert layer, {name}, fwd", timed(jax.jit(lambda p_, x_: layer(p_, x_)[0]), (p, x), it), **tag,
                counters={k: float(v) for k, v in counters.items()})
            grad = jax.jit(jax.grad(lambda p_, x_: total(jax.checkpoint(lambda a, b: layer(a, b)[0])(p_, x_)), argnums=(0, 1)))
            say(f"expert layer, {name}, fwd+bwd", timed(grad, (p, x), it), **tag)
        for key, value in shipped.items():
            setattr(ops, key, value)
        # what stays at full length in every variant: the router, and the two sorts of all assignments
        xf = x.reshape(TOKENS, h)
        say("router, fwd", timed(jax.jit(lambda w, x_: ops.route(w, bias, x_, top_k=top_k, scaling=scaling)), (p["router"], xf), it), **tag)
        ids = ops.route(p["router"], bias, xf, top_k=top_k, scaling=scaling)[0]

        def sorts(ids_):
            flat = ids_.reshape(-1)
            order = jnp.argsort(jnp.where(flat < held, flat, held), stable=True)
            return order, jnp.argsort(order)

        say("the two sorts of all assignments", timed(jax.jit(sorts), (ids,), it), **tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())

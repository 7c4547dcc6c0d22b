"""The program's own names for the work inside a compiled step
(docs/OBSERVABILITY.md "Named scopes").

A device trace names operations the way XLA does (``convert_reduce_fusion.15``):
numbers of one compilation, renumbered by any change to the program. The
layers, the step and the optimizer therefore wrap their work in
``jax.named_scope`` with a name from ONE closed list, :data:`SCOPES`. A scope
is metadata: it lands in the ``op_name`` of every HLO instruction traced under
it (``jit(step)/jvp(bn_stats)/reduce_sum``), survives into the compiled
module, on fusions too, and adds no operation (tests/test_obs_scopes.py
compares the compiled step with and without them). Autodiff marks the phase
for free: ``jvp(...)`` is the forward pass, ``transpose(jvp(...))`` the
backward.

Reading the names back is this module's too, so that the operator's tool
(scripts/trace_ops.py), ``cli/train.py`` (``scope_table.json`` beside a
profiler window's trace) and the benchmark's reader share one rule:

- :func:`scope_of` — ``op_name`` -> ``(scope, phase)``. **Nesting rule: the
  innermost listed scope wins, except that everything inside ``se`` is
  ``se``** (its pool, its two small matmuls and its gate are one unit of
  work that no optimisation would split).
- :func:`scope_table` — compiled HLO text -> ``{instruction: (scope, phase)}``.
  A fusion carries one ``op_name``, its root's, so attribution is by fusion
  root; a fusion without one takes its fused computation's root's.
- :func:`scopes_inside` / :func:`time_containing` — what else rides in a
  fusion: on the chip a convolution is fused with the BatchNorm reductions
  around it, so "whose fusion" (the table) and "whose reductions it
  contains" are both needed to read where the time goes.
- :func:`time_by_scope` — ``(instruction, duration)`` pairs from a device
  trace's ``XLA Ops`` line -> duration by ``(scope, phase)``. Operations that
  resolve to no listed scope are summed under :data:`UNSCOPED`, so an
  executable without the names in it (one read from a compile cache that an
  older checkout filled) is never read silently: its unscoped share is ~100%.
"""

from __future__ import annotations

import json
import os
import re
from typing import Iterable

import jax

# The closed list. The step's compute, by layer kind:
SCOPES = (
    "input",        # train/steps.py: cast / uint8 normalize of the batch, mixup and cutmix
    "conv_dw",      # ops/layers.py Conv2D: depthwise (groups == channels), the VPU convs
    "conv_pw",      # Conv2D: 1x1 ungrouped, the MXU matmuls
    "conv_full",    # Conv2D: the stem and any dense or grouped k x k
    "dense",        # Dense: feature layer and classifier
    "bn_stats",     # BatchNorm: batch moments, running-stat update, and in the
                    # conv + BN pair's backward the dgamma/dbeta reductions
    "bn_apply",     # BatchNorm: the normalize, and its backward's elementwise pass
    "act",          # ops/activations.py: every non-identity activation
    "se",           # ops/blocks.py SqueezeExcite, whole (see the nesting rule)
    "drop",         # dropout and drop-connect (stochastic depth)
    "pool",         # global average pool (outside SE)
    "residual",     # the residual add of an inverted-residual block, and of a token model's blocks
    "nas_mask",     # AtomNAS channel masks over the expanded channels
    # the token models' compute (ops/lm.py, models/lm.py):
    "embed",        # token embedding gather (both heads' inputs), and its scatter-add backward
    "norm",         # RMSNorm, every one: pre-attention, pre-MLP, the latents', final, the MTP merge's, and the
                    # two that a sandwich block (`ouro`) puts AFTER attention and MLP
    "rope",         # rotary tables and the rotation of q_rope and the shared k_rope (`ouro`: of all of q and k)
    "attn_proj",    # MLA's matmuls: q_a, q_b, kv_a, kv_b, o; plain multi-head attention's: q, k, v, o
    "attn_core",    # scores, causal mask, float32 softmax, values: a query block at a time
    "attn_window",  # the same within a sliding window (`laguna`'s sliding layers): the tiles the window reaches
    "attn_gate",    # a per-head output gate (`laguna`): sigmoid(x W_g) and each head's output times it
    "mlp",          # SiLU-gated MLP: the dense layer's and every shared expert's
    "moe_router",   # gate matmul, sigmoid, top-k of scores + bias, weights, counts, the bias update
    "moe_dispatch", # sort of the assignments, held experts first, and the gather of their rows
    "moe_experts",  # the held experts' grouped matmuls (lax.ragged_dot) and their SiLU gate
    "moe_combine",  # un-sort, weight and sum over the selected experts; the layer's counters
    "mtp_merge",    # the MTP module's W_eh over [norm(h) ; norm(emb)]
    # Kimi Delta Attention (ops/lm_kda.py):
    "kda_proj",     # its seven projections: q, k, v, the decay gate's and the output gate's low-rank pairs, beta, o
    "kda_conv",     # the short causal depthwise convolutions on q, k, v and their SiLU
    "kda_gate",     # the log decay of every key channel, the write strength beta; the step's lowest chunk decay
    "kda_core",     # the chunked gated delta rule: in-chunk decayed scores, the triangular solve, the scan over chunks
    "kda_norm",     # L2 norm of q and k, the sigmoid-gated RMSNorm of the output
    # Mamba-2 (ops/lm_mamba.py; `granitemoehybrid`):
    "ssd_proj",     # its two projections: in_proj (z, xBC, dt) and out_proj
    "ssd_conv",     # the causal depthwise convolution over xBC, its bias and SiLU
    "ssd_gate",     # softplus of dt, Delta A, the in-chunk cumulative sums; the step's lowest chunk decay
    "ssd_core",     # the chunked scan: C B^T, in-chunk decays and products, chunk states, the scan over chunks, D x
    "ssd_norm",     # the gated RMSNorm of the output (gate before the norm)
    "exit_gate",    # a looped model's exit gate (`ouro`): its projection after every loop step, the exit
                    # distribution over the steps, the expected loss and the entropy term
    "lm_head",      # the output head over the vocabulary slice, a block of tokens at a time
    "loss",         # label-smoothed CE and the step's reported scalars (top-1, lr, their pmean)
    "nas_penalty",  # AtomNAS FLOPs-weighted BN-gamma L1
    "optim",        # optimizer update and apply, global gradient norm (plain and ZeRO shard)
    "ema",          # EMA of parameters and BN state
    "guard",        # train/guard.py: the non-finite roll-back select
    # the collectives, which exist only across chips:
    "syncbn",       # psum of BN moments (and the rank-0 broadcast of non-SyncBN mode)
    "grad_sync",    # gradient pmean; ZeRO's psum_scatter and all_gather
)
# Hashed into the persistent compile cache's key (utils/compile_cache.py, "The
# scope stamp"): JAX leaves metadata out of that key, so without it a cache
# filled before a scope was added, renamed or moved hands back an executable
# with the old names in it. BUMP IT with any such change;
# tests/test_obs_scopes.py pins it to the list of scope sites.
TAXONOMY_VERSION = 5
UNSCOPED = "unscoped"
PHASES = ("fwd", "bwd", "-")

_SCOPE_SET = frozenset(SCOPES)
_WORD = re.compile(r"[A-Za-z_][\w.\-]*")
# `%fusion.3 = ...` or `ROOT %add.1 = ...`: an instruction and the rest of its line
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# the opcodes that pass over a whole operand: what "rides in a fusion" means
_HEAVY = re.compile(r"[\s)](convolution|dot|reduce|reduce-window|scatter|select-and-scatter)\(")
# `%fused_computation.7 (param_0: f32[8], /*index=1*/param_1: f32[8]) -> f32[8] {`
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`; any other name
    is a programming error, raised where the step is traced."""
    if name not in _SCOPE_SET:
        raise ValueError(f"{name!r} is not in obs.scopes.SCOPES: add it there (and to "
                         "docs/OBSERVABILITY.md) before timing work under it")
    return jax.named_scope(name)


def scope_of(op_name: str) -> tuple[str, str]:
    """``(scope, phase)`` of an HLO ``op_name``: the innermost path component
    that is a listed scope (``se`` anywhere on the path wins), or
    :data:`UNSCOPED`; phase ``bwd`` under a ``transpose(...)``, ``fwd`` under
    a ``jvp(...)`` alone, ``-`` outside autodiff (optimizer, EMA, loss
    scalars). Transform wrappers (``jvp(bn_stats)``, ``transpose(jvp(se))``)
    and SPMD prefixes (``jit(shard_fn)/shard_map/...``) are seen through."""
    found = UNSCOPED
    for component in op_name.split("/"):
        words = _WORD.findall(component)
        if words and words[-1] in _SCOPE_SET:
            if words[-1] == "se":
                found = "se"
                break
            found = words[-1]
    phase = "bwd" if "transpose(" in op_name else "fwd" if "jvp(" in op_name else "-"
    return found, phase


def parse_hlo(compiled_or_hlo_text):
    """(op_name by instruction, called computation by instruction, ROOT by
    computation, instructions by computation, the instructions that reduce or
    contract) of a compiled module's text (``Compiled.as_text()``, or a
    ``jax.stages.Compiled`` itself). :func:`scope_table` and
    :func:`scopes_inside` take the text or this result: a caller that wants
    both parses a step's several megabytes once."""
    if isinstance(compiled_or_hlo_text, tuple):
        return compiled_or_hlo_text
    text = compiled_or_hlo_text if isinstance(compiled_or_hlo_text, str) else compiled_or_hlo_text.as_text()
    op_names: dict[str, str] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    members: dict[str, list[str]] = {}
    heavy: set[str] = set()
    computation = ""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        is_root, name, rest = m.groups()
        members.setdefault(computation, []).append(name)
        if is_root:
            roots[computation] = name
        found = _OP_NAME.search(rest)
        if found is not None:
            op_names[name] = found.group(1)
        called = _CALLS.search(rest)
        if called is not None:
            calls[name] = called.group(1)
        if _HEAVY.search(rest.split(", metadata=", 1)[0]) is not None:
            heavy.add(name)
    return op_names, calls, roots, members, heavy


def scope_table(compiled_or_hlo_text) -> dict[str, tuple[str, str]]:
    """``{instruction name: (scope, phase)}`` for every instruction of a
    compiled module, from its text (``Compiled.as_text()``, or a
    ``jax.stages.Compiled`` itself). The names are those a device trace's
    ``XLA Ops`` events start with (``%fusion.3 = ...``)."""
    op_names, calls, roots, _, _ = parse_hlo(compiled_or_hlo_text)
    op_names = dict(op_names)  # filled in below: a shared parse stays as it was
    for name, computation in calls.items():
        # a fusion that lost its own op_name: its fused computation's root's
        if name not in op_names and roots.get(computation) in op_names:
            op_names[name] = op_names[roots[computation]]
    table = {name: scope_of(op) for name, op in op_names.items()}
    for name in calls:
        table.setdefault(name, (UNSCOPED, "-"))
    return table


def scopes_inside(compiled_or_hlo_text) -> dict[str, tuple[str, ...]]:
    """``{fusion name: the listed scopes of the reductions and contractions
    fused into it}`` (reduce, convolution, dot, scatter; nested fusions
    included), for every fusion that holds such work of more than one scope.
    XLA:TPU fuses a convolution with the reductions around it (the next
    BatchNorm's statistics in the forward, the previous one's gradient sums
    in the backward), and the fusion's one ``op_name`` is the convolution's:
    the table says whose fusion it is, this says whose passes over the
    activation ride in it. Elementwise work (activations, the normalize, the
    optimizer's update of a weight inside its gradient's fusion) is not
    listed: it adds no pass of its own."""
    op_names, calls, _, members, heavy = parse_hlo(compiled_or_hlo_text)
    memo: dict[str, frozenset] = {}

    def inside(computation: str) -> frozenset:
        if computation not in memo:
            memo[computation] = frozenset()  # HLO has no recursion; a mis-parse must not hang
            found = set()
            for name in members.get(computation, ()):
                if name in heavy and name in op_names:
                    found.add(scope_of(op_names[name])[0])
                if name in calls:
                    found |= inside(calls[name])
            memo[computation] = frozenset(found - {UNSCOPED})
        return memo[computation]

    out = {}
    for name, computation in calls.items():
        scopes_here = inside(computation)
        if len(scopes_here) > 1:
            out[name] = tuple(sorted(scopes_here))
    return out


def time_by_scope(op_events: Iterable[tuple[str, float]],
                  table: dict[str, tuple[str, str]]) -> dict[tuple[str, str], float]:
    """Sum ``(instruction name, duration)`` pairs by ``(scope, phase)``. An
    instruction the table does not know counts as :data:`UNSCOPED`: a table
    from another compilation must show, not hide."""
    out: dict[tuple[str, str], float] = {}
    for name, duration in op_events:
        key = table.get(name, (UNSCOPED, "-"))
        out[key] = out.get(key, 0.0) + duration
    return out


def time_containing(op_events: Iterable[tuple[str, float]], table: dict[str, tuple[str, str]],
                    inside: dict[str, tuple[str, ...]]) -> dict[str, float]:
    """Duration of the operations that CONTAIN a pass of each scope: an
    instruction counts for its own scope and, a fusion, for every scope whose
    reduction or contraction is fused into it (:func:`scopes_inside`). The
    sums overlap and can pass the total: beside :func:`time_by_scope` ("whose
    fusion") this is "how much time could this scope's passes be responsible
    for, at most"."""
    out: dict[str, float] = {}
    for name, duration in op_events:
        own = table.get(name, (UNSCOPED, "-"))[0]
        for scope_name in set(inside.get(name, ())) | {own}:
            out[scope_name] = out.get(scope_name, 0.0) + duration
    return out


def unscoped_share(by_scope: dict[tuple[str, str], float]) -> float | None:
    """Share (0..1) of the summed time that resolved to no listed scope."""
    total = sum(by_scope.values())
    if total <= 0:
        return None
    return sum(v for (name, _), v in by_scope.items() if name == UNSCOPED) / total


SCOPE_TABLE_FILE = "scope_table.json"


def write_scope_table(trace_dir: str, compiled_or_hlo_text) -> str:
    """``<trace_dir>/scope_table.json`` for one compiled program: what
    cli/train.py leaves beside its profiler window's trace."""
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, SCOPE_TABLE_FILE)
    parsed = parse_hlo(compiled_or_hlo_text)
    with open(path, "w") as f:
        json.dump({"taxonomy_version": TAXONOMY_VERSION, "instructions": scope_table(parsed),
                   "inside": scopes_inside(parsed)}, f)
    return path


def read_scope_table(trace_dir: str):
    """``(table, inside)`` as :func:`write_scope_table` left them in
    ``trace_dir`` (:func:`scope_table`'s and :func:`scopes_inside`'s), or None."""
    try:
        with open(os.path.join(trace_dir, SCOPE_TABLE_FILE)) as f:
            doc = json.load(f)
    except OSError:
        return None
    return ({name: (sc, phase) for name, (sc, phase) in doc["instructions"].items()},
            {name: tuple(found) for name, found in doc["inside"].items()})

"""Aggregate device op times from a jax.profiler xplane trace.

The sandbox's tensorboard_plugin_profile can't convert xplane dumps (protobuf
generation mismatch), so this reads the XSpace proto directly and prints the
op-level breakdown the Pallas/optimization decisions need (VERDICT r1 #4).
Works on the train CLI's step-indexed window AND on the serving frontend's
HTTP-triggered capture (``POST /profile/start|stop`` — docs/SERVING.md).

``--check-table LATENCY_TABLE.json`` cross-checks a measured-latency table
(scripts/latency_table.py) against the trace: the table's predicted
per-image block total next to the trace's aggregated op time, so a table
whose provenance doesn't match the traced hardware shows up as a gross
ratio mismatch instead of silently mis-weighting the NAS penalty.

**By scope.** Where the directory holds a ``scope_table.json`` (cli/train.py
writes one beside its profiler window's trace; obs/scopes.py), each device's
op time is also printed by the program's own names — scope x phase
(``conv_dw`` ``bwd``, ``bn_apply`` ``bwd``, ...: whose fusion it is) — with
the share that resolved to no scope, then the time of the ops that CONTAIN
each scope's reductions (XLA:TPU fuses a convolution with the BatchNorm sums
around it, so both views are needed), and, as an UPPER bound only, each scope's share of the
HBM roofline with bytes counted from the operand and result shapes in the
event names (it over-counts what a fusion reads in part or keeps in fast
memory, so it can pass 100%: a bound for ranking, not a metric).

**Idle gaps by host span.** With ``obs.trace`` on, the program's host spans
are in the same xplane file (``serve/stage``, ``data/next``, ...:
obs/trace.py), so the longest stretches in which a device ran nothing are
labelled with the spans open at their middle: "idle 41.0 ms: serve/stage+
serve/h2d", not a bare gap.

Usage: python scripts/trace_ops.py /path/to/trace_dir [top_n]
           [--check-table LATENCY_TABLE_r01_cpu_rehearsal.json]
(finds the newest */vm.xplane.pb under the dir)
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from yet_another_mobilenet_series_tpu.obs import scopes  # noqa: E402
from yet_another_mobilenet_series_tpu.obs.trace import SPAN_CATEGORIES  # noqa: E402

# a v5e chip's published HBM bandwidth (benchmark/peaks.json): the roofline
# bound printed by scope is an upper bound against THIS number
V5E_HBM_BYTES_PER_S = 819e9
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def instruction_name(event_name: str) -> str:
    """'%fusion.233 = f32[64]{0} fusion(...)' -> 'fusion.233': the HLO
    instruction a device event ran, the key of a scope table. (A TPU event's
    name is the instruction's whole HLO text; a CPU thunk's is the bare name.)"""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """Collapse op numbering: 'fusion.123' -> 'fusion'. ONE definition for
    every backend's aggregation — the TPU and CPU rankings must never
    diverge on the collapse rule."""
    return re.split(r"[.\d]", instruction_name(name), maxsplit=1)[0]


def load_xspace(root: str):
    """Newest ``*.xplane.pb`` under ``root`` as a parsed XSpace proto;
    returns (xspace, path). Raises FileNotFoundError when none exists."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    files = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    xs = xplane_pb2.XSpace()
    with open(files[-1], "rb") as f:
        xs.ParseFromString(f.read())
    return xs, files[-1]


def aggregate_device(plane) -> dict | None:
    """Synchronous-op aggregation of one ``/device:TPU*`` plane: total ps,
    per-op and per-kind sums, async DMA windows (overlapping — tracked
    separately, NOT occupancy), and XLA-module execution stats."""
    events_meta = plane.event_metadata
    modules = []
    for line in plane.lines:
        if "XLA Modules" in line.name:
            durs = sorted(ev.duration_ps / 1e9 for ev in line.events)
            if durs:
                modules.append({"line": line.name, "n": len(durs),
                                "total_ms": sum(durs), "durs_ms": durs})
    per_op: collections.Counter = collections.Counter()
    per_cat: collections.Counter = collections.Counter()
    async_cat: collections.Counter = collections.Counter()
    total_ps = 0
    n_events = 0
    for line in plane.lines:
        if "XLA Ops" not in line.name:
            continue
        for ev in line.events:
            meta = events_meta.get(ev.metadata_id)
            name = meta.name if meta else "?"
            kind = op_kind(name)
            dur = ev.duration_ps
            n_events += 1
            if kind.endswith("-start"):
                # async DMA window, overlaps compute: not occupancy —
                # summing these reported 85% 'copy' on a step that is
                # actually reduce-bound
                async_cat[kind] += dur
                continue
            total_ps += dur
            per_op[name] += dur
            per_cat[kind] += dur
    if not per_op:
        return None
    return {"plane": plane.name, "n_events": n_events,
            # all-zero-duration sync events would divide by zero downstream
            "total_ps": max(total_ps, 1),
            "per_op": per_op, "per_cat": per_cat, "async_cat": async_cat,
            "modules": modules}


def shape_bytes(event_name: str) -> int:
    """Bytes of every array shape in a device event's name (the instruction's
    HLO text: its result and its operands). An UPPER bound on what the
    operation moves through HBM: a fusion may read an operand in part, and
    an array in fast memory (``S(1)``) is not HBM traffic at all."""
    total = 0
    for dtype, dims in _SHAPE.findall(event_name):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def device_ops(plane) -> list[tuple[str, int, int]]:
    """(event name, start_ps, duration_ps) of every synchronous op on a
    device plane's ``XLA Ops`` line(s); async ``-start`` windows left out, as
    in :func:`aggregate_device`."""
    out = []
    for line in plane.lines:
        if "XLA Ops" not in line.name:
            continue
        t0 = line.timestamp_ns * 1000
        for ev in line.events:
            meta = plane.event_metadata.get(ev.metadata_id)
            name = meta.name if meta else "?"
            if not op_kind(name).endswith("-start"):
                out.append((name, t0 + ev.offset_ps, ev.duration_ps))
    return out


def program_spans(xs) -> list[tuple[str, int, int]]:
    """(name, start_ps, duration_ps) of the program's host spans in the
    trace: ``/host:CPU`` events named ``<cat>/<name>`` with a category of the
    span taxonomy (obs/trace.py SPAN_CATEGORIES), from every thread."""
    out = []
    for plane in xs.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                meta = plane.event_metadata.get(ev.metadata_id)
                name = meta.name if meta else ""
                if name.split("/", 1)[0] in SPAN_CATEGORIES and "/" in name:
                    out.append((name, t0 + ev.offset_ps, ev.duration_ps))
    return out


NO_SPAN = "(no program span open)"


def idle_gaps(ops, spans, n: int = 5) -> list[tuple[float, str]]:
    """[(milliseconds, label)]: the longest stretches between a device's
    first and last op in which it ran nothing, each labelled with the
    program's host spans open at the gap's middle, joined by '+'
    (benchmark/trace_reduce.py idle_gaps's method, the program's names)."""
    busy: list[list[int]] = []
    for _, start, dur in sorted(ops, key=lambda e: e[1]):
        if dur <= 0:
            continue
        if busy and start <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], start + dur)
        else:
            busy.append([start, start + dur])
    gaps = sorted(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])), reverse=True)[:n]
    out = []
    for length, start in gaps:
        mid = start + length / 2
        names = sorted({name for name, s, d in spans if s <= mid <= s + d})
        out.append((length / 1e9, "+".join(names) or NO_SPAN))
    return out


def scope_rows(ops, table, hbm_bytes_per_s: float | None = None) -> list[dict]:
    """One row per (scope, phase), most time first: ms, share of the summed
    op time and, with a peak bandwidth, the roofline upper bound in percent
    (bytes from shapes / bandwidth / time: see :func:`shape_bytes`)."""
    times = scopes.time_by_scope(((instruction_name(n), d) for n, _, d in ops), table)
    nbytes = scopes.time_by_scope(((instruction_name(n), shape_bytes(n)) for n, _, _ in ops), table)
    total = sum(times.values()) or 1
    rows = []
    for key, ps in sorted(times.items(), key=lambda kv: -kv[1]):
        row = {"scope": key[0], "phase": key[1], "ms": ps / 1e9, "share_pct": 100.0 * ps / total}
        if hbm_bytes_per_s and ps > 0:
            row["hbm_roofline_upper_pct"] = 100.0 * (nbytes[key] / hbm_bytes_per_s) / (ps / 1e12)
        rows.append(row)
    return rows


def aggregate_host(xs) -> dict:
    """XLA-CPU fallback: thunk events on the ``/host:CPU`` client threadpool
    lines (thread-summed host time, not a device timeline — rehearsal sanity
    and rough op ranking only, never TPU decisions). Client line names vary
    by jaxlib vintage — ``XLAEigen``, ``PjRtCpuClient``, ``tf_XLATfrtCpuClient``
    — so anything carrying ``CpuClient`` or ``XLAEigen`` counts; the old
    exact-two-names match silently aggregated ZERO events on jaxlib 0.4.36."""
    per_cat: collections.Counter = collections.Counter()
    n_events = 0
    for plane in xs.planes:
        if plane.name != "/host:CPU":
            continue
        events_meta = plane.event_metadata
        for line in plane.lines:
            if "CpuClient" not in line.name and "XLAEigen" not in line.name:
                continue
            for ev in line.events:
                meta = events_meta.get(ev.metadata_id)
                name = meta.name if meta else "?"
                if name.startswith(("end:", "ThunkExecutor", "ThreadpoolListener")):
                    continue  # paired markers / executor bookkeeping
                if ev.duration_ps <= 0:
                    continue
                per_cat[op_kind(name)] += ev.duration_ps
                n_events += 1
    return {"per_cat": per_cat, "n_events": n_events,
            "total_ps": max(sum(per_cat.values()), 1)}


def table_prediction(table_path: str) -> dict:
    """Predicted per-image latency of a LATENCY_TABLE artifact at full width
    (sum over entries), plus its provenance — the cross-check baseline."""
    with open(table_path) as f:
        doc = json.load(f)
    entries = doc.get("entries", [])
    total_s = 0.0
    for e in entries:
        ch = e["alive_channels"]
        lat = e["latency_s"]
        # full-width point: the ladder's largest alive-channel measurement
        total_s += lat[max(range(len(ch)), key=lambda i: ch[i])]
    return {"entries": len(entries), "blocks_total_ms": total_s * 1e3,
            "provenance": doc.get("provenance", {})}


def print_ranked(per_cat: collections.Counter, total_ps: int, top_n: int) -> None:
    for k, v in per_cat.most_common(top_n):
        print(f"  {k:<40} {v/total_ps*100:6.2f}%  {v/1e12*1000:8.3f} ms")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    table_path = ""
    if "--check-table" in argv:
        i = argv.index("--check-table")
        table_path = argv[i + 1]
        del argv[i : i + 2]
    root = argv[0] if argv else "/tmp/tpu_trace"
    top_n = int(argv[1]) if len(argv) > 1 else 40

    xs, path = load_xspace(root)
    scope_tables = scopes.read_scope_table(root)
    spans = program_spans(xs)
    measured_ms = None
    printed_any = False
    for plane in xs.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        agg = aggregate_device(plane)
        if agg is None:
            continue
        printed_any = True
        import statistics

        for m in agg["modules"]:
            print(f"-- {m['line']}: {m['n']} module executions, "
                  f"median {statistics.median(m['durs_ms']):.2f} ms, total {m['total_ms']:.2f} ms")
        total_ps = agg["total_ps"]
        measured_ms = total_ps / 1e12 * 1000
        print(f"\n== {agg['plane']}: {agg['n_events']} op events, "
              f"{measured_ms:.2f} ms synchronous device op time")
        print("\n-- by op kind (sync only) --")
        print_ranked(agg["per_cat"], total_ps, 20)
        print("\n-- async DMA windows (overlapping; not occupancy) --")
        for k, v in agg["async_cat"].most_common(5):
            print(f"  {k:<40} {'':8}{v/1e12*1000:10.3f} ms")
        print(f"\n-- top {top_n} individual sync ops --")
        for k, v in agg["per_op"].most_common(top_n):
            print(f"  {k[:98]:<100} {v/total_ps*100:6.2f}%  {v/1e12*1000:8.3f} ms")
        ops = device_ops(plane)
        if scope_tables is not None:
            table, inside = scope_tables
            print(f"\n-- by scope and phase: whose fusion ({scopes.SCOPE_TABLE_FILE}; roofline column: HBM "
                  "bytes from shapes, an UPPER bound) --")
            for r in scope_rows(ops, table, V5E_HBM_BYTES_PER_S):
                print(f"  {r['scope']:<12} {r['phase']:<4} {r['share_pct']:6.2f}%  {r['ms']:9.3f} ms"
                      f"  roofline <= {r.get('hbm_roofline_upper_pct', 0.0):6.1f}%")
            print("\n-- time of the ops that CONTAIN a scope's reduction or contraction (a convolution's "
                  "fusion carries the BatchNorm sums around it; rows overlap) --")
            named = [(instruction_name(n), d) for n, _, d in ops]
            for name, ps in sorted(scopes.time_containing(named, table, inside).items(), key=lambda kv: -kv[1]):
                print(f"  {name:<12}      {ps/total_ps*100:6.2f}%  {ps/1e9:9.3f} ms")
        else:
            print(f"\n(no {scopes.SCOPE_TABLE_FILE} under {root}: no table by scope; cli.train "
                  "writes one beside its profiler window's trace)")
        print(f"\n-- longest idle gaps, by the program's host spans ({len(spans)} span events "
              "in the trace; obs.trace=true puts them there) --")
        for ms, label in idle_gaps(ops, spans, 5):
            print(f"  idle {ms:9.3f} ms: {label}")
    if not printed_any:
        # CPU-backend traces (a rehearsal, the serving frontend's capture
        # on a host without a TPU) have no /device:TPU plane. The
        # planes list stays in the output so a trace with NO recognizable
        # plane (GPU backend, malformed dump) is still diagnosable, not a
        # silent zero.
        print(f"no /device:TPU plane in {os.path.basename(path)} — "
              f"falling back to HOST-thread XLA-CPU op times "
              f"(thread-summed, CPU backend; not comparable to TPU ranks); "
              f"planes present: {[p.name for p in xs.planes]}")
        host = aggregate_host(xs)
        measured_ms = host["total_ps"] / 1e12 * 1000
        print(f"\n== /host:CPU: {host['n_events']} thunk events, "
              f"{measured_ms:.2f} ms summed host op time")
        print_ranked(host["per_cat"], host["total_ps"], top_n)

    if table_path:
        pred = table_prediction(table_path)
        prov = pred["provenance"]
        print(f"\n-- latency-table cross-check ({os.path.basename(table_path)}) --")
        print(f"  table: {pred['entries']} entries, predicted "
              f"{pred['blocks_total_ms']:.3f} ms/image over all blocks at full width "
              f"(measured on {prov.get('device_kind', '?')}, "
              f"cpu_rehearsal={prov.get('cpu_rehearsal', '?')})")
        if measured_ms is not None:
            print(f"  trace: {measured_ms:.3f} ms aggregated op time "
                  f"(whole window — divide by traced image count before judging)")
        print("  a gross ratio mismatch means the table's provenance does not "
              "match the traced hardware — regenerate before searching on it")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run sets of runs of cells and say how widely they spread: the numbers a
`benchmark` PR sets its bounds from (README.md, "Setting a bound").

    chiprun -- python3 benchmark/prove.py --cells mbv3l_train_b512 --sets 2 --runs 6 \
        --traced 1 --out chiprun_out/proof_train.jsonl

Each run is the manifest's command in a process of its own (this parent never
touches JAX, so it never holds a chip). Both sets use the same seeds; the
first run of the first cell in a fresh checkout compiles and is reported
apart. Every run's last stdout line goes to --out as
{"cell", "set", "seed", "trace", "rc", "wall_s", "result", "notes"}; the
summary at the end gives, per cell and metric, each set's median and spread
(interquartile distance over the median, `statistics.quantiles(n=4)`), the
wider spread x 5 (the bound it asks for), and the second set's median
against the first's.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [11, 2147483659, 3000000019, 44, 55555, 666, 7777777, 4294967311]


def run_once(command, cell, seed, seconds, trace, extra):
    argv = command + ["--workload", cell, "--seed", str(seed), "--trace", str(trace)] + extra
    if seconds is not None:
        argv += ["--seconds", str(seconds)]
    t0 = time.perf_counter()
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    row = {"rc": proc.returncode, "wall_s": time.perf_counter() - t0, "result": None, "notes": []}
    try:
        row["result"] = json.loads(lines[-1])
        row["notes"] = [json.loads(ln) for ln in lines[:-1] if ln.startswith("{")]
    except (IndexError, ValueError):
        row["stderr_tail"] = proc.stderr[-2000:]
    return row


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--cells", required=True, help="comma-separated workload names")
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--traced", type=int, default=1, help="traced runs per cell, after the sets")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--cold", type=int, default=1, help="1: an extra first run per cell, reported apart")
    p.add_argument("--out", default="chiprun_out/proof.jsonl")
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    extra = ["--rehearsal"] if args.rehearsal else []
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)) or ".", exist_ok=True)
    rows = []

    def record(cell, set_id, seed, trace):
        row = {"cell": cell, "set": set_id, "seed": seed, "trace": trace,
               **run_once(command, cell, seed, args.seconds, trace, extra)}
        rows.append(row)
        with open(os.path.join(ROOT, args.out), "a") as f:
            f.write(json.dumps(row) + "\n")
        r = row["result"] or {}
        vals = {k: round(v["value"], 4) for k, v in r.get("metrics", {}).items()}
        phases = next((n["setup_phases"] for n in row["notes"] if "setup_phases" in n), {})
        print(f"{cell} set={set_id} seed={seed} trace={trace} rc={row['rc']} wall={row['wall_s']:.0f}s "
              f"correct={r.get('correct')} attempted={r.get('attempted')} failed={r.get('failed')} "
              f"mem={r.get('device', {}).get('memory_peak_bytes')} {vals}", flush=True)
        print("    phases " + json.dumps({k: round(v, 1) for k, v in phases.items()}), flush=True)
        if row["result"] is None:
            print("    STDERR " + row.get("stderr_tail", "")[-1500:], flush=True)
        elif trace:
            print("    device " + json.dumps(r.get("device")) + " breakdown " + json.dumps(r.get("breakdown")),
                  flush=True)

    for cell in args.cells.split(","):
        if args.cold:
            record(cell, "cold", SEEDS[-1], 0)
        for s in range(args.sets):
            for seed in SEEDS[: args.runs]:
                record(cell, s, seed, 0)
        for i in range(args.traced):
            record(cell, "traced", SEEDS[i], 1)

    print("\n== summary (interquartile spread over median; bound asked = 5 x the wider set's) ==")
    for cell in args.cells.split(","):
        by_set = {}
        for row in rows:
            if row["cell"] == cell and isinstance(row["set"], int) and row["result"]:
                for k, v in row["result"]["metrics"].items():
                    by_set.setdefault(k, {}).setdefault(row["set"], []).append(v["value"])
        for metric, sets in by_set.items():
            parts, spreads, medians = [], [], []
            for s, values in sorted(sets.items()):
                medians.append(statistics.median(values))
                if len(values) >= 2:
                    spreads.append(spread(values))
                    parts.append(f"set{s}: median {medians[-1]:.6g} spread {100 * spreads[-1]:.3f}% n={len(values)}")
            drift = f" second/first {100 * (medians[1] / medians[0] - 1):+.3f}%" if len(medians) > 1 else ""
            ask = f" -> 5x widest = {500 * max(spreads):.2f}%" if spreads else ""
            print(f"{cell} {metric}: " + "; ".join(parts) + drift + ask)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A cell's test file in this directory pins BENCHMARK.json as its PR left it:
its cell is the last of `workloads`, the lists it joined end with it, the
metrics it brought list it alone. A later PR may only APPEND to the manifest
(a configuration, a cell, a metric, a name at the end of a metric's
`workloads`) and may not edit a file of this directory, so what such a file
goes on checking is the manifest CUT BACK to its own cell: everything up to
and including that cell, in order, with what later PRs appended left out.
Anything a later PR moved, removed or put before the cell still shows, and
still fails.

A module opts in by having a module-level `CELL` and a `manifest()`; for the
newest cell's file the cut is the whole manifest.
"""

from __future__ import annotations

import pytest


def cut_back_to(manifest: dict, cell: str) -> dict:
    """`manifest` without what was appended after `cell`: later cells, their
    names in every metric's `workloads`, the configurations only they use, and
    the metrics that list only them."""
    names = [w["name"] for w in manifest["workloads"]]
    if cell not in names:
        return manifest
    later = set(names[names.index(cell) + 1:])
    workloads = [w for w in manifest["workloads"] if w["name"] not in later]
    used = {w["config"] for w in workloads}

    def metrics(group):
        kept = []
        for m in manifest[group]:
            if "workloads" in m:
                if set(m["workloads"]) <= later:
                    continue
                m = {**m, "workloads": [w for w in m["workloads"] if w not in later]}
            kept.append(m)
        return kept

    return {**manifest, "workloads": workloads, "configs": [c for c in manifest["configs"] if c["name"] in used],
            "end_to_end": metrics("end_to_end"), "per_layer": metrics("per_layer")}


@pytest.fixture(autouse=True)
def manifest_as_the_cells_pr_left_it(request, monkeypatch):
    module = request.module
    cell, read = getattr(module, "CELL", None), getattr(module, "manifest", None)
    if isinstance(cell, str) and callable(read):
        monkeypatch.setattr(module, "manifest", lambda: cut_back_to(read(), cell))
    yield

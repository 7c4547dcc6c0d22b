"""YAMT006 — version-fragile jax imports.

``jax._src.*`` is private and reshuffles every minor release;
``jax.experimental.maps`` (xmap) was deleted; ``jax.experimental.shard_map``
is the old home of what is now ``jax.shard_map``. The package is written for
the one jax it is installed with (0.9: ``jax.shard_map(..., check_vma=...)``)
and carries no version switch; an import inside an explicit ``try/except
ImportError`` guard is exempt, since such a guard says the author knew.
"""

from __future__ import annotations

import ast

from .core import Finding, Project, Rule, SourceFile, qualified_name, register

# `from jax import X` names that only exist in some jax versions
_FRAGILE_FROM_JAX = {
    "maps": "jax.experimental.maps (xmap) was removed from jax",
}
# fragile module prefixes for `import X` / `from X import ...`
_FRAGILE_MODULES = {
    "jax._src": "private jax internals, reshuffled every minor release",
    "jax.experimental.maps": "removed from jax (xmap is gone)",
    "jax.experimental.shard_map": "old home of shard_map; spell it jax.shard_map",
}
_GUARD_EXCEPTIONS = {"ImportError", "ModuleNotFoundError", "Exception", "AttributeError"}


def _module_matches(module: str) -> str | None:
    for prefix, why in _FRAGILE_MODULES.items():
        if module == prefix or module.startswith(prefix + "."):
            return why
    return None


@register
class FragileJaxImport(Rule):
    id = "YAMT006"
    name = "version-fragile-jax-import"
    description = (
        "an import that only resolves on some jax versions (jax._src.*, "
        "jax.experimental.maps/shard_map) outside a try/except version guard"
    )

    def check_file(self, src: SourceFile, project: Project) -> list[Finding]:
        # imports anywhere inside a try/except that catches ImportError are
        # an explicit version guard — exempt
        guarded: set[int] = set()
        for node in src.nodes:
            if not isinstance(node, ast.Try):
                continue
            catches = set()
            for h in node.handlers:
                t = h.type
                for n in t.elts if isinstance(t, ast.Tuple) else ([t] if t else []):
                    name = n.id if isinstance(n, ast.Name) else getattr(n, "attr", "")
                    catches.add(name)
            if not (catches & _GUARD_EXCEPTIONS) and not (None in [h.type for h in node.handlers]):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, (ast.Import, ast.ImportFrom)):
                    guarded.add(id(sub))

        findings: list[Finding] = []

        def flag(node, what, why):
            findings.append(
                Finding(
                    src.path, node.lineno, node.col_offset, self.id,
                    f"version-fragile jax import `{what}`: {why}",
                )
            )

        for node in src.nodes:
            if isinstance(node, ast.Import) and id(node) not in guarded:
                for a in node.names:
                    why = _module_matches(a.name)
                    if why:
                        flag(node, f"import {a.name}", why)
            elif isinstance(node, ast.ImportFrom) and id(node) not in guarded and node.level == 0:
                mod = node.module or ""
                why = _module_matches(mod)
                if why:
                    flag(node, f"from {mod} import ...", why)
                elif mod == "jax":
                    for a in node.names:
                        if a.name in _FRAGILE_FROM_JAX:
                            flag(node, f"from jax import {a.name}", _FRAGILE_FROM_JAX[a.name])
                elif mod == "jax.experimental":
                    for a in node.names:
                        why = _module_matches(f"jax.experimental.{a.name}")
                        if why:
                            flag(node, f"from jax.experimental import {a.name}", why)
            elif isinstance(node, ast.Attribute):
                q = qualified_name(node, src.aliases)
                if q and _module_matches(q) and not isinstance(getattr(node, "ctx", None), ast.Store):
                    # flag only the full chain once: skip if the parent chain
                    # would also match (handled by dedupe below)
                    findings.append(
                        Finding(
                            src.path, node.lineno, node.col_offset, self.id,
                            f"version-fragile jax attribute access `{q}`: {_module_matches(q)}",
                        )
                    )
        # attribute chains yield one hit per sub-chain; keep one per location
        seen: set[tuple[int, int]] = set()
        out = []
        for f in findings:
            key = (f.line, f.col)
            if key not in seen:
                seen.add(key)
                out.append(f)
        return out

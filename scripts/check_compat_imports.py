#!/usr/bin/env python
"""Repo lint: jax APIs outside jax's stable core live only in
src/repro/compat.py.

Every experimental, private or recently renamed jax surface the repo
uses goes through one module, so a jax upgrade is a one-file change
(ROADMAP.md, "Supported jax versions").  This ast-based check enforces
it: outside compat.py no module may

  * import ``shard_map`` from jax or touch ``jax.shard_map``;
  * use ``lax.pcast`` (the shard_map replication-typing cast);
  * build element-indexed BlockSpecs from ``pl.Element`` instead of
    ``repro.compat.element_block_spec``;
  * touch ``jax.experimental.serialize_executable`` or ``jax.export``
    instead of ``repro.compat.aot_compile`` / ``aot_serialize`` /
    ``aot_deserialize`` (the persistent design store's AOT surface);
  * import from ``jax._src`` (private; ``compat.tpu_chips_on_host`` is
    the one sanctioned use).

Exit 1 with file:line findings on violation, 0 when clean.
"""
from __future__ import annotations

import ast
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCAN_DIRS = ("src", "tests", "benchmarks", "examples", "scripts")
ALLOWED = {ROOT / "src" / "repro" / "compat.py"}

_AOT = "repro.compat.aot_serialize/aot_deserialize"


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an attribute/name chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _module_finding(mod: str, name: str = "") -> str | None:
    """What is wrong with importing ``name`` from module ``mod`` (or the
    module itself when ``name`` is empty), if anything."""
    if mod.startswith("jax._src"):
        return f"private {mod!r} import; go through repro.compat"
    if "shard_map" in mod or name == "shard_map":
        return f"direct shard_map import from {mod!r}; use repro.compat.shard_map"
    if name in ("pcast", "pvary"):
        return f"direct {name} import from {mod!r}; use repro.compat.pvary"
    if "serialize_executable" in mod or name == "serialize_executable":
        return f"direct serialize_executable import from {mod!r}; use {_AOT}"
    if mod in ("jax.export", "jax.experimental.export") or (
        name == "export" and mod in ("jax", "jax.experimental")
    ):
        return f"direct jax export import from {mod!r}; use {_AOT}"
    return None


def check_file(path: pathlib.Path) -> list[str]:
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = path.relative_to(ROOT)
    findings: list[str] = []

    def flag(node: ast.AST, msg: str | None) -> None:
        if msg:
            findings.append(f"{rel}:{node.lineno}: {msg}")

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith("jax"):
                for a in node.names:
                    flag(node, _module_finding(mod, a.name))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax"):
                    flag(node, _module_finding(a.name))
        elif isinstance(node, ast.Attribute):
            dotted = _dotted(node)
            if dotted == "jax.shard_map" or dotted.endswith(
                "experimental.shard_map"
            ):
                flag(node, f"direct use of {dotted}; use repro.compat.shard_map")
            elif dotted.endswith("experimental.serialize_executable") or (
                dotted in ("jax.export", "jax.experimental.export")
            ):
                flag(node, f"direct use of {dotted}; use {_AOT}")
            elif node.attr in ("pcast", "pvary") and dotted.startswith(
                ("lax.", "jax.lax.")
            ):
                flag(node, f"direct use of {dotted}; use repro.compat.pvary")
            elif node.attr == "Element" and (
                dotted.split(".")[0] in ("pl", "pallas")
                or dotted.endswith("pallas.Element")
            ):
                flag(node, (
                    f"direct use of {dotted}; use "
                    "repro.compat.element_block_spec"
                ))
    return findings


def main() -> int:
    findings: list[str] = []
    for d in SCAN_DIRS:
        base = ROOT / d
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            if path in ALLOWED:
                continue
            findings.extend(check_file(path))
    for f in findings:
        print(f)
    print(
        "check_compat_imports:",
        "OK" if not findings else f"{len(findings)} violation(s)",
    )
    return 0 if not findings else 1


if __name__ == "__main__":
    sys.exit(main())

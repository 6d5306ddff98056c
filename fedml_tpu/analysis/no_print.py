"""no-print checker: forbid bare ``print(...)`` calls in library code.

Library output must go through ``logging`` or the telemetry sinks
(``fedml_tpu/core/telemetry.py``) so deployments can route/silence it —
a stray print in a hot path is invisible to log collectors and can stall
under redirected stdout. Only CALLS of the builtin name ``print`` are
flagged, so passing ``print`` as a callback default (``log_fn=print``)
stays legal.

This started life as the standalone 78-line ``scripts/check_no_print.py``
lint; that script is now a thin shim over this checker (same allowlist,
same exit semantics), and ``tests/test_no_print.py`` keeps both honest.

Allowlist: ``fedml_tpu/cli/`` (a CLI's job is to print).
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Tuple

from .core import Checker, Finding, Module

ALLOWLIST_DIRS = ("fedml_tpu/cli/",)


def find_print_calls(path: str) -> List[Tuple[int, str]]:
    """(lineno, source-line) for every bare ``print(...)`` call.

    Kept as a standalone helper because ``scripts/check_no_print.py`` (and
    its test) import it directly."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    lines = src.splitlines()
    hits = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            text = lines[node.lineno - 1].strip() if node.lineno <= len(lines) else ""
            hits.append((node.lineno, text))
    return hits


class NoPrintChecker(Checker):
    id = "no-print"
    description = "bare print() calls in library code (use logging/telemetry)"

    def interested(self, relpath: str) -> bool:
        return not relpath.startswith(ALLOWLIST_DIRS)

    def visit_module(self, module: Module) -> Iterable[Finding]:
        findings = []
        count = 0
        for node in ast.walk(module.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"
            ):
                count += 1
                findings.append(Finding(
                    checker=self.id, path=module.relpath, line=node.lineno,
                    message=("bare print() call in library code — use logging "
                             "or the telemetry sinks"),
                    key=f"print:{count}"))
        return findings

"""config-drift checker: conflicting defaults, ghost keys, stale docs.

The framework's config surface is one flat ``Arguments`` bag read through
``getattr(args, key, default)`` at ~400 sites; nothing ties those sites
together. Three drift classes are reported, on top of the shared AST
scanner in :mod:`fedml_tpu.analysis.config_scan` (the same scanner that
generates ``docs/config_reference.md``):

- **conflicting defaults** — the same key read with different non-None
  defaults at different sites means behaviour silently depends on WHICH
  subsystem reads the key first when the user leaves it unset (e.g. one
  site assuming ``0`` retries and another ``3``). ``None`` probes
  (``if getattr(args, k, None) is None``) and ``getattr``-chain fallbacks
  are exempt: they delegate, not decide.
- **documented-but-never-read** — a key row in the reference doc with no
  surviving read site (the doc is generated, so this means it's stale).
- **read-but-undocumented** — a key the code reads that the committed doc
  doesn't list (same staleness, from the other side; both disappear when
  ``scripts/gen_config_reference.py`` is re-run).
- **phase-name drift** — every phase the simulator times, ``with
  self._phase("<name>") as ph:`` and the ``ph.name = "<name>"`` its body
  may set, must appear in ``docs/observability.md``; dashboards and the
  anomaly detector key on these names, so an undocumented phase is an
  invisible one.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Tuple

from .config_scan import KeyRecord, merge_read, scan_tree
from .core import Checker, Finding, Module

_DOC_KEY_RE = re.compile(r"^\|\s*`([a-z_][a-z0-9_]*)`\s*\|")


def _phase_names(tree: ast.AST) -> Iterable[Tuple[str, int]]:
    """Yield ``(phase_name, lineno)`` for every ``*._phase("x", ...)`` call
    and, where a ``with`` binds that call's handle to a name, for every
    ``<handle>.name = "y"`` in its body. A name that is not a string
    constant is not seen."""
    def is_str(node) -> bool:
        return isinstance(node, ast.Constant) and isinstance(node.value, str)

    def is_phase_call(node) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "_phase")

    for node in ast.walk(tree):
        if is_phase_call(node) and node.args and is_str(node.args[0]):
            yield node.args[0].value, node.lineno
        if not isinstance(node, ast.With):
            continue
        handles = {item.optional_vars.id for item in node.items
                   if is_phase_call(item.context_expr)
                   and isinstance(item.optional_vars, ast.Name)}
        if not handles:
            continue
        for inner in ast.walk(node):
            if isinstance(inner, ast.Assign) and is_str(inner.value):
                for tgt in inner.targets:
                    if (isinstance(tgt, ast.Attribute) and tgt.attr == "name"
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id in handles):
                        yield inner.value.value, inner.lineno


def _literal(text: str):
    import ast as _ast

    return _ast.literal_eval(text)


def _is_literal(text: str) -> bool:
    try:
        _literal(text)
    except (ValueError, SyntaxError):
        return False
    return True


class ConfigDriftChecker(Checker):
    id = "config-drift"
    description = ("config keys with conflicting defaults across read sites, "
                   "plus doc/code drift against docs/config_reference.md")
    # cross-file by construction: a subset scan would report every key
    # whose read sites didn't change as doc-only drift
    whole_package_only = True
    cache_scope = "package"
    cache_extra_files = ("docs/config_reference.md", "docs/observability.md")

    def __init__(self, ctx):
        super().__init__(ctx)
        self._records: Dict[str, KeyRecord] = {}
        self._phases: Dict[str, Tuple[str, int]] = {}

    def visit_module(self, module: Module) -> Iterable[Finding]:
        for read in scan_tree(module.tree, module.relpath):
            # a read site suppressed inline opts out of the cross-file
            # conflict computation (the aggregate finding lands on a
            # different file, where a line suppression couldn't reach)
            ids = module.suppressions.get(read.line, ())
            if "*" in ids or self.id in ids:
                continue
            merge_read(self._records, read)
        for name, lineno in _phase_names(module.tree):
            ids = module.suppressions.get(lineno, ())
            if "*" in ids or self.id in ids:
                continue
            self._phases.setdefault(name, (module.relpath, lineno))
        return ()

    def finalize(self) -> Iterable[Finding]:
        findings: List[Finding] = []
        findings.extend(self._conflicting_defaults())
        findings.extend(self._doc_drift())
        findings.extend(self._phase_drift())
        return findings

    def _conflicting_defaults(self) -> List[Finding]:
        findings: List[Finding] = []
        for key, rec in sorted(self._records.items()):
            # Only top-level literal defaults "decide" an unset key's value.
            # None probes delegate the decision; runtime-derived fallbacks
            # (self.client_num, fed.client_num) forward to state configured
            # elsewhere; and a getattr nested in another getattr's default
            # position carries the CHAIN's last-resort value, not this key's.
            deciding_reads = [
                r for r in rec.reads
                if r.default not in (None, "None") and not r.chained
                and _is_literal(r.default)]
            if len({repr(_literal(r.default)) for r in deciding_reads}) < 2:
                continue
            sites_by_default = {}
            for read in sorted(deciding_reads, key=lambda r: (r.relpath, r.line)):
                sites_by_default.setdefault(
                    read.default, f"{read.relpath}:{read.line}")
            # anchor the finding at the LAST deciding site: when defaults
            # drifted, the later addition is usually the divergence (and the
            # natural home for an inline suppression if it is intentional)
            anchor = max(deciding_reads, key=lambda r: (r.relpath, r.line))
            detail = "; ".join(
                f"{d!r} at {site}" for d, site in sorted(sites_by_default.items()))
            findings.append(Finding(
                checker=self.id, path=anchor.relpath, line=anchor.line,
                message=(f"config key '{key}' read with conflicting defaults: "
                         f"{detail} — unset-key behaviour depends on which "
                         "site reads it first"),
                key=f"conflicting-default:{key}"))
        return findings

    def _doc_drift(self) -> List[Finding]:
        doc_path = os.path.join(self.ctx.repo_root, "docs", "config_reference.md")
        doc_rel = "docs/config_reference.md"
        if not os.path.exists(doc_path):
            return []
        documented: Dict[str, int] = {}
        with open(doc_path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                m = _DOC_KEY_RE.match(line)
                if m:
                    documented.setdefault(m.group(1), lineno)
        findings: List[Finding] = []
        for key, lineno in sorted(documented.items()):
            if key not in self._records:
                findings.append(Finding(
                    checker=self.id, path=doc_rel, line=lineno,
                    message=(f"key '{key}' is documented but no code reads it "
                             "— re-run scripts/gen_config_reference.py"),
                    key=f"doc-only:{key}"))
        for key, rec in sorted(self._records.items()):
            if key not in documented:
                first = min(rec.reads, key=lambda r: (r.relpath, r.line))
                findings.append(Finding(
                    checker=self.id, path=first.relpath, line=first.line,
                    message=(f"key '{key}' is read here but missing from "
                             f"{doc_rel} — re-run scripts/gen_config_reference.py"),
                    key=f"undocumented:{key}"))
        return findings

    def _phase_drift(self) -> List[Finding]:
        doc_path = os.path.join(self.ctx.repo_root, "docs", "observability.md")
        doc_rel = "docs/observability.md"
        if not os.path.exists(doc_path):
            return []
        with open(doc_path, encoding="utf-8") as f:
            doc_text = f.read()
        findings: List[Finding] = []
        for name, (relpath, lineno) in sorted(self._phases.items()):
            if re.search(rf"\b{re.escape(name)}\b", doc_text):
                continue
            findings.append(Finding(
                checker=self.id, path=relpath, line=lineno,
                message=(f"phase '{name}' is emitted here but never mentioned "
                         f"in {doc_rel} — dashboards and the phase-anomaly "
                         "detector key on phase names; document it"),
                key=f"phase-undocumented:{name}"))
        return findings

"""Shared infrastructure for the graftlint passes.

graftlint is deliberately AST-only: no pass imports jax (or any
framework module), so the whole suite parses the tree and runs in
single-digit seconds on the 2-core tier-1 box, and a syntactically
valid file with a broken import still lints.  Every pass consumes the
same :class:`ScanContext` — one parse per file, shared — and returns
:class:`Finding` objects; the driver (``tools/graftlint/cli.py``)
renders, filters against the baseline and picks the exit code.

Suppression grammar (documented in README "Static analysis"):

- ``# graftlint: disable=<rule>[,<rule>...]`` on the flagged line or
  the line directly above suppresses findings of those rules at that
  site.  Use it for deliberate exceptions the surrounding comment
  justifies (e.g. a vocabulary entry kept as structural proof with no
  emit site).
- ``# sync: <reason>`` is the host-sync pass's annotation (see
  ``hostsync.py``), not a suppression: the reason must come from the
  ``ASYNC_SYNC_REASONS`` closed vocabulary.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

# tools/graftlint/core.py -> repo root is three levels up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the default scan surface, mirroring tools/check_metrics_names.py:
# the serving/observability tree and the lint/profiling tooling
DEFAULT_PATHS = ("paddle_tpu", "tools")

_DISABLE_RE = re.compile(r"#\s*graftlint:\s*disable=([a-z0-9_,\-]+)")
_PLAN_PHASE_RE = re.compile(r"#\s*graftlint:\s*plan-phase\b")


@dataclass(frozen=True)
class Finding:
    """One lint finding.  ``fingerprint`` (rule + path + message, no
    line number) is what the baseline file stores, so a finding
    survives unrelated edits shifting it up or down the file."""
    rule: str
    path: str          # root-relative, '/'-separated
    lineno: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.lineno}: [{self.rule}] {self.message}"

    def fingerprint(self) -> str:
        return f"{self.rule}::{self.path}::{self.message}"

    def as_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path,
                "line": self.lineno, "message": self.message}


def indexed_fingerprints(findings) -> List[str]:
    """One baseline key per finding: the bare fingerprint for the
    first occurrence, ``<fp>#2``/``#3``… for repeats — two identical
    violations in one file (same rule, path and message) must cost
    two baseline entries, so fixing one can never hide the other.
    Deterministic because run_lint sorts findings."""
    counts: Dict[str, int] = {}
    out = []
    for f in findings:
        fp = f.fingerprint()
        n = counts.get(fp, 0) + 1
        counts[fp] = n
        out.append(fp if n == 1 else f"{fp}#{n}")
    return out


class SourceFile:
    """One parsed file: source text, split lines and AST (``tree`` is
    None for files that do not parse — passes skip those; the
    instruments pass keeps check_metrics_names' identical skip)."""

    def __init__(self, root: str, path: str):
        self.abspath = path
        self.path = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, encoding="utf-8") as f:
            self.source = f.read()
        self.lines = self.source.splitlines()
        try:
            self.tree: Optional[ast.Module] = ast.parse(self.source)
        except SyntaxError:
            self.tree = None

    def line(self, n: int) -> str:
        """1-based, safe: out-of-range returns ''."""
        return self.lines[n - 1] if 1 <= n <= len(self.lines) else ""

    def disabled_at(self, lineno: int) -> set:
        """Rules suppressed at this line (the line itself or the line
        directly above)."""
        out: set = set()
        for n in (lineno, lineno - 1):
            m = _DISABLE_RE.search(self.line(n))
            if m:
                out |= set(m.group(1).split(","))
        return out

    def plan_phase_defs(self) -> List[ast.FunctionDef]:
        """Function defs marked ``# graftlint: plan-phase`` (marker on
        the ``def`` line or the line directly above it)."""
        if self.tree is None:
            return []
        out = []
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _PLAN_PHASE_RE.search(self.line(node.lineno)) or \
                        _PLAN_PHASE_RE.search(self.line(node.lineno - 1)):
                    out.append(node)
        return out


def discover_files(root: str,
                   paths: Optional[Sequence[str]] = None) -> List[str]:
    """Resolve scan paths (files or directories, relative to ``root``)
    into a sorted list of .py file paths; ``__pycache__`` excluded.
    Missing paths are skipped silently — synthetic lint-test trees
    rarely carry the full default surface."""
    out: List[str] = []
    for p in (paths if paths else DEFAULT_PATHS):
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap) and ap.endswith(".py"):
            out.append(ap)
        elif os.path.isdir(ap):
            for dirpath, _dirnames, filenames in os.walk(ap):
                if "__pycache__" in dirpath:
                    continue
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        out.append(os.path.join(dirpath, fn))
    return sorted(set(out))


class ScanContext:
    """The parsed tree every pass shares: one :class:`SourceFile` per
    scanned .py file, plus cross-file vocabulary declarations (see
    :func:`vocab_declarations`)."""

    def __init__(self, root: Optional[str] = None,
                 paths: Optional[Sequence[str]] = None):
        self.root = os.path.abspath(root or REPO_ROOT)
        self.paths = list(paths) if paths else list(DEFAULT_PATHS)
        self.files = [SourceFile(self.root, p)
                      for p in discover_files(self.root, self.paths)]
        self._vocab_cache: Optional[Dict[str, "VocabDecl"]] = None

    def by_path(self, rel: str) -> Optional[SourceFile]:
        for sf in self.files:
            if sf.path == rel:
                return sf
        return None

    def filter_disabled(self, findings: List[Finding]) -> List[Finding]:
        """Drop findings whose rule is suppressed at their site."""
        out = []
        for f in findings:
            sf = self.by_path(f.path)
            if sf is not None and f.rule in sf.disabled_at(f.lineno):
                continue
            out.append(f)
        return out


@dataclass
class VocabDecl:
    """One closed-vocabulary declaration: the literal entries plus,
    per entry, the declaration line (dead-entry findings anchor there
    so a ``# graftlint: disable=vocab`` on the entry's line exempts
    exactly that entry)."""
    name: str
    path: str
    lineno: int
    entries: Dict[str, int]      # value -> declaration lineno


def _literal_strings(node: ast.AST) -> Optional[Dict[str, int]]:
    """``{value: lineno}`` for a literal tuple/list/set/frozenset of
    string constants; None when the node is anything else."""
    if isinstance(node, ast.Call) and not node.keywords \
            and len(node.args) == 1 \
            and isinstance(node.func, ast.Name) \
            and node.func.id in ("frozenset", "set", "tuple", "list"):
        node = node.args[0]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out: Dict[str, int] = {}
        for e in node.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, str)):
                return None
            out[e.value] = e.lineno
        return out
    return None


def vocab_declarations(ctx: ScanContext,
                       names: Sequence[str]) -> Dict[str, VocabDecl]:
    """Find the (unique) module-level declaration of each closed
    vocabulary in the scanned tree.  A vocabulary declared in two
    files would silently fork the closed set, so duplicates are
    dropped and reported by the vocab pass."""
    decls: Dict[str, List[VocabDecl]] = {}
    wanted = set(names)
    for sf in ctx.files:
        if sf.tree is None:
            continue
        for node in sf.tree.body:
            if not isinstance(node, ast.Assign) \
                    or len(node.targets) != 1 \
                    or not isinstance(node.targets[0], ast.Name):
                continue
            nm = node.targets[0].id
            if nm not in wanted:
                continue
            entries = _literal_strings(node.value)
            if entries is None:
                continue
            decls.setdefault(nm, []).append(
                VocabDecl(nm, sf.path, node.lineno, entries))
    return {k: v[0] for k, v in decls.items() if len(v) == 1}


def duplicate_vocab_findings(ctx: ScanContext,
                             names: Sequence[str]) -> List[Finding]:
    """Findings for vocabularies declared in more than one file."""
    decls: Dict[str, List[VocabDecl]] = {}
    for sf in ctx.files:
        if sf.tree is None:
            continue
        for node in sf.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id in set(names) \
                    and _literal_strings(node.value) is not None:
                decls.setdefault(node.targets[0].id, []).append(
                    VocabDecl(node.targets[0].id, sf.path, node.lineno,
                              {}))
    out = []
    for nm, ds in decls.items():
        if len(ds) > 1:
            sites = ", ".join(f"{d.path}:{d.lineno}" for d in ds[1:])
            out.append(Finding(
                "vocab", ds[0].path, ds[0].lineno,
                f"closed vocabulary {nm} is declared more than once "
                f"(also at {sites}) — a forked declaration silently "
                f"splits the closed set"))
    return out


def dotted_name(node: ast.AST) -> str:
    """``a.b.c`` for an Attribute/Name chain, '' otherwise.  Calls in
    the chain resolve through their func (``get_registry().counter``
    -> ``get_registry.counter``)."""
    parts: List[str] = []
    while True:
        if isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        else:
            return ""

"""graftlint pass ``vocab``: closed vocabularies stay closed — and
alive.

The serving stack's contracts hang off a handful of hand-maintained
closed string sets: flight-recorder event kinds (``EVENT_KINDS``),
forced-sync reasons (``ASYNC_SYNC_REASONS``), goodput waste reasons
(``GOODPUT_REASONS``), routing-decision reasons (``ROUTE_REASONS``)
and the shed/swap/cancel counter label values.  The runtime guards
(``FlightRecorder.emit``, ``_flush_async``, ``_ledger``) catch a
typo'd literal only when that code path actually executes; this pass
catches it at lint time, on every path, and adds the check the
runtime cannot do at all: **dead-entry detection** — a declared entry
with no emit site is either cruft or a vanished code path, and both
deserve a finding (a deliberate structural-proof entry carries a
``# graftlint: disable=vocab`` on its declaration line).

Mechanics (all AST, declaration-driven):

- the vocabularies themselves are discovered from the scanned tree's
  module-level literal assignments, not hard-coded here — editing
  ``ASYNC_SYNC_REASONS`` re-scopes the lint with no lint change;
- each emit-site matcher below names the call shape that charges a
  vocabulary: ``<r>.emit("<kind>", ...)``, ``_flush_async("<r>")``,
  ``<counter>.inc(reason=...)``, ``_ledger(**waste_kwargs)``;
- a site's string argument resolves when it is a literal, or a local
  name assigned from literals / conditional-expression chains of
  literals (the router's ``reason = "a" if .. else "b"`` idiom).
  Membership is checked against the lexically LAST assignment before
  the use (a reused local's dead earlier value must not flag);
  dead-entry liveness counts the union of ALL resolvable assignments
  (over-counting liveness only suppresses findings).  Unresolvable
  sites (a parameter, an attribute) are skipped — the runtime guards
  own those — so the pass has no false positives by construction;
- producer functions (``_block_sync_reason``) contribute their
  literal ``return`` values as emit sites, membership-checked.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, ScanContext, duplicate_vocab_findings,
                   vocab_declarations)

RULE = "vocab"


@dataclass(frozen=True)
class VocabSpec:
    """dead=False opts a vocabulary out of dead-entry detection (the
    cancel phases flow through ``req.state`` dynamically — the lint
    cannot prove them live, and flagging them would teach people to
    scatter disables)."""
    name: str
    dead: bool = True
    producers: Tuple[str, ...] = ()


VOCABS: Tuple[VocabSpec, ...] = (
    VocabSpec("EVENT_KINDS"),
    VocabSpec("ASYNC_SYNC_REASONS", producers=("_block_sync_reason",)),
    VocabSpec("GOODPUT_REASONS"),
    VocabSpec("ROUTE_REASONS"),
    VocabSpec("SWAP_REASONS"),
    VocabSpec("SHED_REASONS"),
    VocabSpec("CANCEL_PHASES", dead=False),
    # the failover layer (PR 15): replica fault kinds flow through the
    # _classify_fault producer; recovery paths and probe outcomes are
    # literal counter labels
    VocabSpec("REPLICA_FAULTS", producers=("_classify_fault",)),
    VocabSpec("FAILOVER_PATHS"),
    VocabSpec("PROBE_OUTCOMES"),
    # quantized-matmul routing reasons (PR 16): every label the
    # pallas.quantized_matmul.route counter can carry flows through the
    # _qmm_route_reason producer's literal returns
    VocabSpec("QMM_ROUTE_REASONS", producers=("_qmm_route_reason",)),
    # fleet monitor alerts (PR 17, observability/fleet.py): every
    # alert kind has a literal serving.alerts{kind=...} inc site in
    # SLOBurnRateMonitor.observe
    VocabSpec("ALERT_KINDS"),
    # paged flash-decode routing reasons (PR 18,
    # ops/pallas/decode_attention.py): every reason the
    # pallas.decode_attention.route counter can carry — the gate/
    # dispatch reasons are string literals threaded into _count_route
    # through non-literal locals the lint cannot chase (dead=False),
    # and the sharded-dispatch overlay flows through the
    # _shard_route_reason producer's literal returns
    VocabSpec("DECODE_ROUTE_REASONS", dead=False,
              producers=("_shard_route_reason",)),
    # grouped-matmul routing reasons of the routed-expert layer (PR 34,
    # ops/pallas/grouped_matmul.py): every label the
    # pallas.moe_experts.route counter can carry is a literal return of
    # the _moe_route_reason / _geometry_reason producers
    VocabSpec("MOE_ROUTE_REASONS",
              producers=("_moe_route_reason", "_geometry_reason")),
    # routing reasons of the gated delta-rule decode step (PR 37,
    # ops/pallas/kda.py): every label the pallas.kda_decode.route counter
    # can carry is a literal return of the _kda_route_reason producer
    VocabSpec("KDA_ROUTE_REASONS", producers=("_kda_route_reason",)),
    # wire-transport frame kinds (PR 19, inference/transport.py):
    # every request kind has a literal transport.rpc("<kind>", ...)
    # site (RemoteReplica and friends), every reply kind a literal
    # EngineHost._reply("<kind>", ...) site — dead-entry detection
    # stays ON, so a frame kind nothing emits is a lint failure
    VocabSpec("FRAME_KINDS"),
    # disaggregated chunk-final handoffs (PR 20): every reason label
    # the serving.handoff.requests counter can carry has a literal
    # inc site in ServingEngine._handoff_out
    VocabSpec("HANDOFF_REASONS"),
    # per-engine phase roles (PR 20): asserted at construction, set
    # once on the serving.role gauge — flows through self.role
    # dynamically, so dead-entry detection cannot prove entries live
    VocabSpec("ENGINE_ROLES", dead=False),
)


@dataclass(frozen=True)
class Matcher:
    """One emit-site shape.  Exactly one of the three forms is set:

    - ``method`` + ``arg``: positional string argument of a call to
      ``<anything>.<method>(...)`` or bare ``<method>(...)``;
    - ``receivers`` + ``methods`` + ``kwarg``: keyword string argument
      of ``<x>.<recv>.<method>(...)`` where ``recv`` names the
      instrument handle (``self._m.shed.inc(reason=...)``);
    - ``kwargs_of`` + ``exclude``: the KEYWORD NAMES of a call to
      ``kwargs_of`` are themselves the vocabulary entries
      (``_ledger(useful, tenant=..., spec_reject=n)``).
    """
    vocab: str
    method: Optional[str] = None
    arg: int = 0
    receivers: frozenset = frozenset()
    methods: frozenset = frozenset()
    kwarg: Optional[str] = None
    kwargs_of: Optional[str] = None
    exclude: frozenset = frozenset()


MATCHERS: Tuple[Matcher, ...] = (
    # FlightRecorder.emit(kind, ...) — receiver-agnostic: every .emit
    # in the scanned tree is the flight recorder's (HostTracer's
    # counter lane has no emit method)
    Matcher("EVENT_KINDS", method="emit", arg=0),
    # the dispatch-ahead pipeline's forced-sync charges
    Matcher("ASYNC_SYNC_REASONS", method="_flush_async", arg=0),
    Matcher("ASYNC_SYNC_REASONS", receivers=frozenset({"async_syncs"}),
            methods=frozenset({"inc"}), kwarg="reason"),
    # the goodput ledger's waste classification — both the raw counter
    # and the _ledger(**wasted) call-site idiom
    Matcher("GOODPUT_REASONS", receivers=frozenset({"goodput_wasted"}),
            methods=frozenset({"inc"}), kwarg="reason"),
    Matcher("GOODPUT_REASONS", kwargs_of="_ledger",
            exclude=frozenset({"tenant"})),
    # router decisions
    Matcher("ROUTE_REASONS", receivers=frozenset({"routed"}),
            methods=frozenset({"inc"}), kwarg="reason"),
    # shed/swap/cancel counter labels (engine + router share shapes)
    Matcher("SHED_REASONS", receivers=frozenset({"shed"}),
            methods=frozenset({"inc"}), kwarg="reason"),
    Matcher("SWAP_REASONS",
            receivers=frozenset({"swap_out_blocks", "swap_in_blocks",
                                 "swap_out_bytes", "swap_in_bytes",
                                 "swap_host_blocks"}),
            methods=frozenset({"inc", "set"}), kwarg="reason"),
    Matcher("CANCEL_PHASES",
            receivers=frozenset({"requests_cancelled", "cancelled"}),
            methods=frozenset({"inc"}), kwarg="phase"),
    # failover counters (router health model)
    Matcher("REPLICA_FAULTS", receivers=frozenset({"replica_faults"}),
            methods=frozenset({"inc"}), kwarg="fault"),
    Matcher("FAILOVER_PATHS",
            receivers=frozenset({"failover_requests"}),
            methods=frozenset({"inc"}), kwarg="path"),
    Matcher("PROBE_OUTCOMES", receivers=frozenset({"probes"}),
            methods=frozenset({"inc"}), kwarg="outcome"),
    # fleet alerts (SLOBurnRateMonitor): serving.alerts{kind=...}
    Matcher("ALERT_KINDS", receivers=frozenset({"alerts"}),
            methods=frozenset({"inc"}), kwarg="kind"),
    # wire-transport frames (PR 19): request kinds at the client's
    # rpc() sites, reply kinds at the host's _reply() sites, and any
    # hand-framed encode_frame() call (bench/tools) — all positional
    Matcher("FRAME_KINDS", method="rpc", arg=0),
    Matcher("FRAME_KINDS", method="_reply", arg=0),
    Matcher("FRAME_KINDS", method="encode_frame", arg=0),
    # chunk-final handoff counter labels (PR 20)
    Matcher("HANDOFF_REASONS",
            receivers=frozenset({"handoff_requests"}),
            methods=frozenset({"inc"}), kwarg="reason"),
    Matcher("ENGINE_ROLES", receivers=frozenset({"role"}),
            methods=frozenset({"set"}), kwarg="role"),
)


def _resolve_expr(node: ast.AST) -> Optional[Set[str]]:
    """All string values an expression can take, when they are fully
    enumerable: a literal, or an ``a if c else b`` chain of literals.
    None = not enumerable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, ast.IfExp):
        a = _resolve_expr(node.body)
        b = _resolve_expr(node.orelse)
        if a is not None and b is not None:
            return a | b
    return None


class _FuncIndex(ast.NodeVisitor):
    """Per-file map: every Name node -> its enclosing function def,
    plus per-function assignment lists for local literal resolution."""

    def __init__(self):
        self.enclosing: Dict[int, ast.AST] = {}    # id(node) -> funcdef
        self.assigns: Dict[int, List[ast.Assign]] = {}
        self._stack: List[ast.AST] = []

    def _visit_func(self, node):
        self._stack.append(node)
        self.assigns[id(node)] = []
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Assign(self, node: ast.Assign):
        if self._stack:
            self.assigns[id(self._stack[-1])].append(node)
        self.generic_visit(node)

    def generic_visit(self, node):
        if self._stack:
            self.enclosing[id(node)] = self._stack[-1]
        super().generic_visit(node)


def _resolve_site(node: ast.AST, idx: _FuncIndex):
    """Resolve a string argument at an emit site.  Returns
    ``(check_vals, live_vals)`` — both None-able sets:

    - ``check_vals``: values to membership-CHECK.  For a local name,
      only the lexically LAST assignment at-or-before the use — a
      reused name (``reason = "x"; log(reason); reason = "eos";
      charge(reason)``) must not flag the dead earlier value.  A
      flow-insensitive union here would false-positive, and false
      negatives (a branch-assigned value the last-before heuristic
      misses) fall back to the runtime guards.
    - ``live_vals``: values counted as EMITTED for dead-entry
      detection — the union of every resolvable assignment, because
      over-counting liveness only ever suppresses a dead-entry
      finding (conservative in the no-false-positive direction).
    """
    direct = _resolve_expr(node)
    if direct is not None:
        return direct, direct
    if not isinstance(node, ast.Name):
        return None, None
    fn = idx.enclosing.get(id(node))
    if fn is None:
        return None, None
    live: Set[str] = set()
    last_before = None
    for a in idx.assigns.get(id(fn), []):
        if not any(isinstance(t, ast.Name) and t.id == node.id
                   for t in a.targets):
            continue
        vals = _resolve_expr(a.value)
        if vals is not None:
            live |= vals
        if a.lineno <= node.lineno and (
                last_before is None or a.lineno >= last_before[0]):
            last_before = (a.lineno, vals)
    check = last_before[1] if last_before is not None else None
    return check, (live if live else None)


def _receiver_attr(func: ast.Attribute) -> str:
    """The instrument-handle name of ``self._m.shed.inc`` -> ``shed``
    (the attribute one level below the method)."""
    v = func.value
    if isinstance(v, ast.Attribute):
        return v.attr
    if isinstance(v, ast.Name):
        return v.id
    return ""


def run_pass(ctx: ScanContext) -> List[Finding]:
    names = [v.name for v in VOCABS]
    decls = vocab_declarations(ctx, names)
    findings: List[Finding] = list(duplicate_vocab_findings(ctx, names))
    # value -> emitted? per vocabulary
    emitted: Dict[str, Set[str]] = {v.name: set() for v in VOCABS}
    sites_seen: Dict[str, int] = {v.name: 0 for v in VOCABS}
    producers = {p: v.name for v in VOCABS for p in v.producers}

    def check_value(vocab: str, check_vals, live_vals, sf,
                    lineno: int, what: str):
        """Flag non-members among ``check_vals``; record
        ``live_vals`` members as emitted (dead-entry liveness)."""
        decl = decls.get(vocab)
        if decl is None or (check_vals is None and live_vals is None):
            return
        sites_seen[vocab] += 1
        for val in sorted(live_vals or ()):
            if val in decl.entries:
                emitted[vocab].add(val)
        for val in sorted(check_vals or ()):
            if val not in decl.entries:
                findings.append(Finding(
                    RULE, sf.path, lineno,
                    f"{what} {val!r} is not in the closed vocabulary "
                    f"{vocab} ({decl.path}:{decl.lineno}) — known: "
                    f"{sorted(decl.entries)}"))

    for sf in ctx.files:
        if sf.tree is None:
            continue
        idx = _FuncIndex()
        idx.visit(sf.tree)
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in producers:
                vocab = producers[node.name]
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Return) \
                            and sub.value is not None:
                        vals = _resolve_expr(sub.value)
                        if vals is not None:
                            check_value(
                                vocab, vals, vals, sf, sub.lineno,
                                f"reason returned by producer "
                                f"{node.name}()")
                continue
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            call_name = (func.attr if isinstance(func, ast.Attribute)
                         else func.id if isinstance(func, ast.Name)
                         else "")
            for m in MATCHERS:
                if m.kwargs_of is not None:
                    if call_name != m.kwargs_of:
                        continue
                    decl = decls.get(m.vocab)
                    if decl is None:
                        continue
                    for kw in node.keywords:
                        if kw.arg is None or kw.arg in m.exclude:
                            continue
                        check_value(m.vocab, {kw.arg}, {kw.arg}, sf,
                                    node.lineno,
                                    f"waste-kwarg of {call_name}()")
                elif m.kwarg is not None:
                    if call_name not in m.methods \
                            or not isinstance(func, ast.Attribute) \
                            or _receiver_attr(func) not in m.receivers:
                        continue
                    for kw in node.keywords:
                        if kw.arg != m.kwarg:
                            continue
                        chk, live = _resolve_site(kw.value, idx)
                        check_value(
                            m.vocab, chk, live, sf, node.lineno,
                            f"{m.kwarg}= label of "
                            f"{_receiver_attr(func)}.{call_name}()")
                else:
                    if call_name != m.method \
                            or len(node.args) <= m.arg:
                        continue
                    chk, live = _resolve_site(node.args[m.arg], idx)
                    check_value(m.vocab, chk, live, sf, node.lineno,
                                f"argument of {call_name}()")

    # dead-entry detection: a declared value no resolvable site emits
    for spec in VOCABS:
        decl = decls.get(spec.name)
        if decl is None or not spec.dead:
            continue
        if sites_seen[spec.name] == 0:
            continue      # partial scan: no sites at all -> no verdict
        for val, lineno in sorted(decl.entries.items()):
            if val not in emitted[spec.name]:
                findings.append(Finding(
                    RULE, decl.path, lineno,
                    f"vocabulary entry {val!r} of {spec.name} has no "
                    f"emit site in the scanned tree (dead reason) — "
                    f"delete it, or mark the declaration line "
                    f"'# graftlint: disable=vocab' with a comment "
                    f"saying why it is load-bearing"))
    return findings

"""graftlint pass ``instruments``: observability instrument names.

The full ``tools/check_metrics_names.py`` lint, moved here so the
shim can stay a re-export and the graftlint driver can run it as one
of its passes.  The five rules (see :func:`check`'s docstring) and
their error strings are UNCHANGED — the shim's CLI output is
byte-compatible with the pre-graftlint lint:

1. instrument names must match ``^[a-z][a-z0-9_.]*$``;
2. one name, one instrument kind across all static call sites;
3. one name, one literal label tuple across all static call sites;
4. every ``REQUIRED_INSTRUMENTS`` entry keeps a registration site
   with the expected kind and label tuple;
5. every required instrument is named in ``README.md`` (docs-sync;
   skipped when the scanned root has no README).

Rules 4 and 5 key on this repo's serving stack, so the graftlint
driver applies them only when the scanned root actually contains it
(``paddle_tpu/inference/serving.py``) — a synthetic lint-test tree
exercises rules 1–3 without dragging in the whole required set.  The
shim path (``check()``/``main()``) keeps the old unconditional
behavior.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import List

from .core import Finding, ScanContext

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_KINDS = {"counter", "gauge", "histogram"}
_SKIP_RECEIVERS = {"HostTracer"}

NAME_RE = re.compile(r"^[a-z][a-z0-9_.]*$")

# instrument names external consumers (bench JSON ``metrics``
# sub-object, dashboards) key on; the lint fails when any loses its
# last registration site.  Each entry is ``name: (kind, labels)`` —
# kind is asserted (a histogram silently re-registered as a counter
# would break its consumers) and so is the label tuple (re-labeling
# re-keys every exported series); ``None`` labels opt a name out of
# the label assertion.
REQUIRED_INSTRUMENTS = {
    # speculative decoding (inference/serving.py _ServingInstruments):
    # acceptance-length distribution, draft hit/miss, verify route
    "serving.spec.accepted_length": ("histogram", ()),
    "serving.spec.accepted_tokens": ("counter", ()),
    "serving.spec.draft_hits": ("counter", ()),
    "serving.spec.draft_misses": ("counter", ()),
    "serving.spec.draft_tokens": ("counter", ()),
    "serving.spec.verify_steps": ("counter", ()),
    # int8 KV cache (inference/serving.py _ServingInstruments): the
    # modeled arena-sweep counter behind the bench's achieved_GBps and
    # the per-dtype presence gauge
    "serving.kv.bytes_swept": ("counter", ()),
    "serving.kv.quant_dtype": ("gauge", ("dtype",)),
    # quantized weight arenas (PR 16, inference/serving.py
    # _ServingInstruments + ops/pallas/quantized_matmul.py): the
    # weight-side twins of the KV pair — modeled weight-plane sweep
    # bytes per forward and the engine weight-dtype presence gauge —
    # plus the dequant-matmul dispatch route counter the bench's
    # weight_quant arm gates on (pallas kernel vs XLA fallback, with
    # the gating reason, mirroring pallas.decode_attention.route)
    "serving.weights.bytes_swept": ("counter", ()),
    "serving.weights.quant_dtype": ("gauge", ("dtype",)),
    "pallas.quantized_matmul.route": ("counter", ("decision", "reason")),
    # per-request sampling (inference/serving.py _ServingInstruments):
    # the sampled-vs-greedy route split, the constrained-decoding
    # masked-token count, and the speculative-sampling residual
    # resamples the bench's sampling arm keys on
    "serving.sample.sampled_tokens": ("counter", ()),
    "serving.sample.greedy_tokens": ("counter", ()),
    "serving.sample.masked_tokens": ("counter", ()),
    "serving.sample.resamples": ("counter", ()),
    # overload resilience (inference/serving.py _ServingInstruments):
    # the preempt/swap/shed/timeout set the bench's overload arm and
    # SLO dashboards key on — preemption + host-RAM swap traffic, the
    # swap tier's live footprint, bounded-queue sheds and queue-delay
    # timeouts
    "serving.preempt.requests": ("counter", ()),
    "serving.preempt.resumes": ("counter", ()),
    "serving.swap.blocks_out": ("counter", ("reason",)),
    "serving.swap.blocks_in": ("counter", ("reason",)),
    "serving.swap.bytes_out": ("counter", ("reason",)),
    "serving.swap.bytes_in": ("counter", ("reason",)),
    "serving.swap.host_blocks": ("gauge", ("reason",)),
    "serving.shed.requests": ("counter", ("reason",)),
    "serving.timeout.requests": ("counter", ()),
    # tiered radix prefix cache (inference/serving.py
    # _ServingInstruments): token-granular hit volume, partial-match
    # and host-tier-hit counts the bench's prefix_tiered arm keys on
    "serving.prefix.hit_tokens": ("counter", ()),
    "serving.prefix.partial_hits": ("counter", ()),
    "serving.prefix.host_hits": ("counter", ()),
    "serving.prefix.host_swapin_blocks": ("counter", ()),
    # goodput ledger + latency attribution + SLO accounting (PR 9,
    # inference/serving.py _ServingInstruments): the conservation-
    # gated token classification (useful + wasted == dispatched,
    # wasted by closed reason vocabulary), the host-vs-dispatch step
    # split the dispatch-ahead pipeline will be judged against, the
    # per-output-token latency histogram and the per-class SLO
    # outcome counters the bench's goodput sub-objects key on
    # (PR 11 relabeled the goodput/SLO set per tenant: the tenant
    # label attributes every dispatched token-position and SLO outcome
    # to the submitting tenant — 'default' for tenant-less requests,
    # so single-tenant dashboards group-by away one constant label)
    "serving.goodput.useful_tokens": ("counter", ("tenant",)),
    "serving.goodput.wasted_tokens": ("counter", ("reason", "tenant")),
    "serving.goodput.dispatched_tokens": ("counter", ("tenant",)),
    "serving.step.host_seconds": ("histogram", ()),
    "serving.step.dispatch_seconds": ("histogram", ()),
    "serving.tpot_seconds": ("histogram", ()),
    "serving.slo.attained": ("counter", ("class", "tenant")),
    "serving.slo.missed": ("counter", ("class", "tenant")),
    # dispatch-ahead step pipeline (PR 10, inference/serving.py
    # _ServingInstruments): the plan/harvest split's observable
    # surface — forced-sync iterations by closed reason vocabulary
    # (the bench's async A/B arm gates on these), completed deferred
    # harvests, the pipeline-depth gauge, the overlap histogram
    # (time blocked on a PREVIOUS iteration's arrays, carved out of
    # host_seconds) and the fault-stall histogram that keeps injected
    # sleeps out of the host-scheduler baseline
    "serving.async.syncs": ("counter", ("reason",)),
    "serving.async.harvests": ("counter", ()),
    "serving.async.depth": ("gauge", ()),
    "serving.step.overlap_seconds": ("histogram", ()),
    "serving.fault.stall_seconds": ("histogram", ()),
    # multi-tenant batched LoRA serving (PR 11, inference/lora.py
    # AdapterStore + inference/serving.py _ServingInstruments):
    # adapter residency across the HBM arena / host-RAM tiers, swap-in
    # traffic at exact at-rest bytes, the gathered-einsum dispatch
    # route split, and the fair-share (deficit-weighted round-robin)
    # service ledger the bench's lora arm keys on
    "serving.lora.hbm_adapters": ("gauge", ()),
    "serving.lora.host_adapters": ("gauge", ()),
    "serving.lora.swap_ins": ("counter", ()),
    "serving.lora.swap_in_bytes": ("counter", ()),
    "serving.lora.gathers": ("counter", ()),
    "serving.fairshare.served_tokens": ("counter", ("tenant",)),
    "serving.fairshare.deficit": ("gauge", ("tenant",)),
    "serving.fairshare.reorders": ("counter", ()),
    # front-door router (PR 12, inference/router.py
    # _RouterInstruments): intake by workload policy, routing
    # decisions by closed reason vocabulary, the affinity signal
    # magnitudes the bench's router arm gates against round-robin,
    # the router-held queue gauge/replica-count gauge and the
    # PR-7-semantics shed/timeout counters lifted above the engines
    "serving.router.requests": ("counter", ("policy",)),
    "serving.router.routed": ("counter", ("reason",)),
    "serving.router.prefix_affinity_tokens": ("counter", ()),
    "serving.router.adapter_affinity_hits": ("counter", ()),
    "serving.router.shed": ("counter", ("reason",)),
    "serving.router.timeouts": ("counter", ()),
    "serving.router.queue_depth": ("gauge", ()),
    "serving.router.engines": ("gauge", ()),
    # replica failover (PR 15, inference/router.py
    # _RouterInstruments): the health model's observable surface —
    # replica-fatal faults by kind, recovered requests by path,
    # exhausted-budget terminals, probe outcomes / readmissions, the
    # routable-replica gauge, and the cross-replica exact-bytes KV
    # migration volume the bench's failover arm gates on
    "serving.router.healthy_engines": ("gauge", ()),
    "serving.router.failover.replica_faults": ("counter", ("fault",)),
    "serving.router.failover.requests": ("counter", ("path",)),
    "serving.router.failover.failed": ("counter", ()),
    "serving.router.failover.probes": ("counter", ("outcome",)),
    "serving.router.failover.readmissions": ("counter", ()),
    "serving.migrate.blocks": ("counter", ()),
    "serving.migrate.bytes": ("counter", ()),
    # fleet observability plane (PR 17, observability/fleet.py
    # _MonitorInstruments + inference/router.py _RouterInstruments):
    # the SLO burn-rate monitor's windowed per-tenant gauge, its
    # closed-vocabulary alert counter (ALERT_KINDS — the vocab pass
    # keeps it closed and alive), the monitor's own liveness counter,
    # and the fleet_snapshot() call counter
    "serving.slo.burn_rate": ("gauge", ("tenant",)),
    "serving.alerts": ("counter", ("kind",)),
    "serving.fleet.monitor_steps": ("counter", ()),
    "serving.fleet.snapshots": ("counter", ()),
    # mesh-sharded serving (PR 18, inference/serving.py
    # _ServingInstruments + ops/pallas/decode_attention.py): the
    # shard-group presence/width gauges the multichip bench arm and
    # fleet_snapshot() key on, and the kernel route counter whose
    # sharded_ok/mesh_geom reasons (DECODE_ROUTE_REASONS) prove the
    # tensor-parallel paged path actually dispatched
    "serving.shard.groups": ("gauge", ()),
    "serving.shard.width": ("gauge", ()),
    "pallas.decode_attention.route": ("counter", ("decision", "reason")),
    # the routed-expert layer's grouped matmul (PR 34,
    # ops/pallas/grouped_matmul.py): kernel vs jax.lax.ragged_dot with
    # the gating reason (MOE_ROUTE_REASONS); chip_smoke.py's moe_experts
    # arm and the serving harness's route check key on it
    "pallas.moe_experts.route": ("counter", ("decision", "reason")),
    # the gated delta-rule decode step (PR 37, ops/pallas/kda.py): the
    # kernel in place in the state arena vs its jnp body, with the gating
    # reason (KDA_ROUTE_REASONS); the serving harness's route check keys
    # on it
    "pallas.kda_decode.route": ("counter", ("decision", "reason")),
    # wire transport (PR 19, inference/transport.py
    # _TransportInstruments): frames moved per kind (the determinism
    # surface the bench multiproc arm gates on), encoded byte totals
    # both directions, and the report-only rpc round-trip wall
    "serving.transport.frames": ("counter", ("kind",)),
    "serving.transport.bytes_out": ("counter", ()),
    "serving.transport.bytes_in": ("counter", ()),
    "serving.transport.rpc_seconds": ("histogram", ()),
    # disaggregated prefill/decode serving (PR 20, inference/serving.py
    # _ServingInstruments): chunk-final handoff volume by closed reason
    # vocabulary (HANDOFF_REASONS), the exact-bytes parcel footprint
    # the bench disagg arm gates on, and the per-engine phase-role
    # presence gauge (ENGINE_ROLES label values)
    "serving.handoff.requests": ("counter", ("reason",)),
    "serving.handoff.blocks": ("counter", ()),
    "serving.handoff.bytes": ("counter", ()),
    "serving.role": ("gauge", ("role",)),
}


def _receiver_name(func: ast.Attribute) -> str:
    """Leftmost identifier of the attribute's value: ``r.counter`` ->
    ``r``; ``get_registry().counter`` -> ``get_registry``;
    ``HostTracer.counter`` -> ``HostTracer``."""
    v = func.value
    while isinstance(v, ast.Call):
        v = v.func
    if isinstance(v, ast.Attribute):
        return v.attr
    if isinstance(v, ast.Name):
        return v.id
    return ""


def _literal_labels(call: ast.Call):
    """The ``labels=`` argument as a tuple of strings: ``()`` when the
    argument is absent (the registry's unlabeled default — an unlabeled
    site genuinely conflicts with a labeled one), a tuple of names when
    it is a literal tuple/list of string constants, and None only when
    it is present but DYNAMIC (dynamic labels opt out of the conflict
    rule — the lint cannot know their value)."""
    node = None
    for kw in call.keywords:
        if kw.arg == "labels":
            node = kw.value
    if node is None and len(call.args) >= 3:   # counter(name, help, labels)
        node = call.args[2]
    if node is None:
        return ()
    if isinstance(node, (ast.Tuple, ast.List)) and all(
            isinstance(e, ast.Constant) and isinstance(e.value, str)
            for e in node.elts):
        return tuple(e.value for e in node.elts)
    return None


def _tree_registrations(relpath: str, tree: ast.Module):
    """Yield (path, lineno, kind, name, labels) for every static
    registration with a literal name in one parsed module."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _KINDS):
            continue
        if _receiver_name(node.func) in _SKIP_RECEIVERS:
            continue
        if not (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        yield (relpath, node.lineno, node.func.attr,
               node.args[0].value, _literal_labels(node))


def iter_registrations(root: str = REPO_ROOT):
    """Yield (path, lineno, kind, name, labels) for every static
    registration with a literal name over the legacy scan surface
    (paddle_tpu/, tools/ — the shim path; the graftlint
    driver goes through :func:`run_pass` and the shared parse
    instead); ``labels`` is a tuple of label names or None when
    unlabeled/dynamic."""
    scan_dirs = [os.path.join(root, "paddle_tpu"),
                 os.path.join(root, "tools")]
    paths = []
    for d in scan_dirs:
        for dirpath, _dirnames, filenames in os.walk(d):
            if "__pycache__" in dirpath:
                continue
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    paths.append(os.path.join(dirpath, fn))
    for path in paths:
        if not os.path.exists(path):
            continue
        with open(path, encoding="utf-8") as f:
            src = f.read()
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        yield from _tree_registrations(os.path.relpath(path, root),
                                       tree)


def check(root: str = REPO_ROOT, required: bool = True):
    """Returns (errors, registrations) — errors is a list of strings.
    ``required=False`` limits the check to rules 1–3 (the graftlint
    driver sets it for trees without the serving stack)."""
    return _evaluate(list(iter_registrations(root)), root, required)


def _evaluate(regs, root: str, required: bool):
    errors = []
    seen = {}  # name -> (kind, first site, labels)
    for path, lineno, kind, name, labels in regs:
        site = f"{path}:{lineno}"
        if not NAME_RE.match(name):
            errors.append(
                f"{site}: instrument name {name!r} does not match "
                f"{NAME_RE.pattern}")
            continue
        prev = seen.get(name)
        if prev is None:
            seen[name] = (kind, site, labels)
            continue
        if prev[0] != kind:
            errors.append(
                f"{site}: {name!r} registered as {kind} but "
                f"{prev[1]} registers it as {prev[0]}")
        elif (labels is not None and prev[2] is not None
                and labels != prev[2]):
            errors.append(
                f"{site}: {name!r} registered with labels "
                f"{list(labels)} but {prev[1]} registers it with "
                f"{list(prev[2])}")
    if not required:
        return errors, regs
    for name, (kind, labels) in sorted(REQUIRED_INSTRUMENTS.items()):
        got = seen.get(name)
        if got is None:
            errors.append(
                f"required instrument {name!r} ({kind}) has no "
                f"registration site — dashboards/bench key on it; "
                f"update REQUIRED_INSTRUMENTS if the rename is "
                f"deliberate")
            continue
        if got[0] != kind:
            errors.append(
                f"{got[1]}: required instrument {name!r} is registered "
                f"as {got[0]}, expected {kind}")
        if labels is not None and got[2] is not None \
                and tuple(got[2]) != tuple(labels):
            errors.append(
                f"{got[1]}: required instrument {name!r} is registered "
                f"with labels {list(got[2])}, expected {list(labels)} "
                f"— relabeling re-keys every exported series")
    # rule 5 (docs-sync): every required instrument must be named in
    # the README's observability docs.  Skipped when the scanned root
    # carries no README (the synthetic trees the lint tests build).
    readme = os.path.join(root, "README.md")
    if os.path.exists(readme):
        with open(readme, encoding="utf-8") as f:
            readme_text = f.read()
        for name in sorted(REQUIRED_INSTRUMENTS):
            if name not in readme_text:
                errors.append(
                    f"required instrument {name!r} is not documented "
                    f"in README.md — the observability docs must name "
                    f"every instrument external consumers key on")
    return errors, regs


_SITE_RE = re.compile(r"^([^:]+):(\d+): (.*)$", re.S)


def run_pass(ctx: ScanContext) -> List[Finding]:
    """The graftlint-pass adapter: rules 1–3 over the context's
    ALREADY-PARSED files (one parse, shared with every other pass,
    honoring the requested scan paths); rules 4–5 only when the scan
    actually covers the serving stack that declares the required set
    — a narrow ``--rule instruments somefile.py`` run checks that
    file, not the whole surface.  Site-less errors (a required
    instrument with NO registration anywhere) anchor at line 0 of the
    declaring module."""
    regs = []
    for sf in ctx.files:
        if sf.tree is not None:
            regs.extend(_tree_registrations(sf.path, sf.tree))
    required = any(sf.path == "paddle_tpu/inference/serving.py"
                   for sf in ctx.files)
    errors, _regs = _evaluate(regs, ctx.root, required)
    out = []
    for e in errors:
        m = _SITE_RE.match(e)
        if m and m.group(1).endswith(".py"):
            out.append(Finding(
                "instruments", m.group(1).replace(os.sep, "/"),
                int(m.group(2)), m.group(3)))
        else:
            out.append(Finding(
                "instruments", "tools/graftlint/instruments.py", 0, e))
    return out


def main(argv=None) -> int:
    errors, regs = check()
    if errors:
        print(f"check_metrics_names: {len(errors)} error(s) over "
              f"{len(regs)} registration(s)")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"check_metrics_names: OK ({len(regs)} registrations, "
          f"{len({r[3] for r in regs})} distinct names)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

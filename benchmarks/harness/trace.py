"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PR 27): one plane ``/device:TPU:n``
per chip with the lines ``XLA Modules`` (one event per program execution, named
``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per HLO operation, named by its
HLO text) and ``Async XLA Ops``; one plane ``/host:CPU`` whose ``python3`` line
carries ``TraceAnnotation`` spans.  All on one clock, in nanoseconds.

An operation's metadata carries what the program called it (looked at by hand,
PR 30): ``tf_op``, the HLO metadata's ``op_name`` (``jit(block)/pallas_call:``;
every ``jax.named_scope`` and a ``pallas_call(name=)`` are components of that
path), and ``source``, the file and line of the program that made the call.
Both are kept beside the HLO text as the operation's given name,
``<op_name> @ <file>:<line>``, the file relative to the checkout; a Pallas
kernel is a ``pallas_call`` made in its own file under its own name, whatever
program holds it and whatever its shapes.

Busy time is the union of the ``XLA Ops`` intervals; an asynchronous copy or
collective that overlaps compute adds nothing to it.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right

from . import xplane
from .spec import ROOT

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINER = re.compile(r"%?(while|conditional|call)[.\d]* = ")
KERNEL_CALL = re.compile(r"pallas_call @ (?:\S*/)?([^/\s]+)$")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(text):
    """``%fusion.12 = bf16[48,2048]{...} fusion(...)`` to
    ``fusion:bf16[48,2048]``; a custom call keeps its target."""
    m = re.match(r"%?([\w.\-]+?)(?:\.\d+)* = (\(?[\w\[\],{} ]*?\]?)[{ (]", text)
    if not m:
        return text[:80]
    name, shape = m.group(1), m.group(2).strip(" ({")
    target = re.search(r'custom_call_target="([^"]+)"', text)
    if target:
        name += ":" + target.group(1)
    return f"{name}:{shape}"


def given_name(stats):
    """``<op_name> @ <file>:<line>`` from an operation's metadata stats, or
    whichever of the two it has; empty where the program named nothing."""
    source = stats.get("source") or ""
    if source.startswith(ROOT + os.sep):
        source = os.path.relpath(source, ROOT)
    op_name = (stats.get("tf_op") or "").rstrip(":")
    return " @ ".join(part for part in (op_name, source) if part)


class Trace:
    """Events of one trace, cut to the ``bench.window`` span.  An operation
    is ``(HLO text, start_s, end_s, given name)``, a program execution and a
    span ``(name, start_s, end_s)``."""

    def __init__(self, path):
        self.devices = []      # per chip: {"ops": [...], "modules": [...]}
        self.spans = []        # (name, start_s, end_s) of bench.* annotations
        for plane, lines in xplane.read(
                path, lambda n: n.startswith("/device:TPU:") or n == "/host:CPU"):
            if plane == "/host:CPU":
                for _, events in lines:
                    self.spans += [(n, s, e) for n, _, s, e in events
                                   if n.startswith(SPAN_PREFIX)]
                continue
            dev = {"ops": [], "modules": []}
            for line, events in lines:
                if line == "XLA Ops":
                    dev["ops"] = sorted(
                        ((n, s, e, given_name(st)) for n, st, s, e in events),
                        key=lambda x: x[1])
                elif line == "XLA Modules":
                    dev["modules"] = sorted(
                        ((n, s, e) for n, _, s, e in events),
                        key=lambda x: x[1])
            if dev["ops"]:
                self.devices.append(dev)
        win = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        self.t0, self.t1 = win[0][1], win[0][2]
        self.window_s = self.t1 - self.t0
        for dev in self.devices:
            for key in ("ops", "modules"):
                dev[key] = [(ev[0], max(ev[1], self.t0), min(ev[2], self.t1))
                            + ev[3:] for ev in dev[key]
                            if ev[2] > self.t0 and ev[1] < self.t1]
            dev["module_starts"] = [m[1] for m in dev["modules"]]
        self.spans = [s for s in self.spans
                      if s[0] != WINDOW_SPAN and s[2] > self.t0 and s[1] < self.t1]

    # -- busy and idle ------------------------------------------------------
    @staticmethod
    def _union(events):
        """Merged (start, end) intervals of events sorted by start."""
        merged = []
        for ev in events:
            s, e = ev[1], ev[2]
            if merged and s <= merged[-1][1]:
                if e > merged[-1][1]:
                    merged[-1][1] = e
            else:
                merged.append([s, e])
        return merged

    def busy_by_device(self):
        return [sum(e - s for s, e in self._union(d["ops"]))
                for d in self.devices]

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        per = self.busy_by_device()
        return sum(per) / len(per) if per else 0.0

    def idle_pct(self, worst=False):
        per = self.busy_by_device()
        if not per or self.window_s <= 0:
            return None
        busy = min(per) if worst else sum(per) / len(per)
        return 100.0 * (1.0 - busy / self.window_s)

    # -- time by pattern ----------------------------------------------------
    def _module_of(self, dev, t):
        i = bisect_right(dev["module_starts"], t) - 1
        if i >= 0 and dev["modules"][i][2] >= t:
            return dev["modules"][i][0]
        return None

    def module_seconds(self, module_pattern, device=0):
        """(seconds, executions) of the programs whose name matches."""
        rx = re.compile(module_pattern)
        hits = [e - s for n, s, e in self.devices[device]["modules"]
                if rx.search(n)]
        return sum(hits), len(hits)

    def op_seconds(self, op_pattern=None, module_pattern=None, device=0,
                   kernel=None):
        """(seconds, events) of the operations whose HLO text matches
        ``op_pattern`` and whose given name matches ``kernel`` (either may be
        left out), inside programs whose name matches."""
        rx = re.compile(op_pattern) if op_pattern else None
        krx = re.compile(kernel) if kernel else None
        mrx = re.compile(module_pattern) if module_pattern else None
        dev = self.devices[device]
        total, count = 0.0, 0
        for n, s, e, given in dev["ops"]:
            if (rx and not rx.search(n)) or (krx and not krx.search(given)):
                continue
            if mrx is not None:
                mod = self._module_of(dev, s)
                if mod is None or not mrx.search(mod):
                    continue
            total += e - s
            count += 1
        return total, count

    def matching_seconds(self, module, op=None, kernel=None):
        """(seconds, events) of the programs whose name matches ``module``,
        or, where ``op`` or ``kernel`` is given, of the matching operations
        inside them."""
        if op is None and kernel is None:
            return self.module_seconds(module)
        return self.op_seconds(op, module, kernel=kernel)

    # -- the breakdown --------------------------------------------------------
    def top_ops(self, n=10, device=0):
        dev = self.devices[device]
        agg = {}
        for name, s, e, given in dev["ops"]:
            if CONTAINER.match(name):
                continue          # its body's operations are events of their own
            mod = self._module_of(dev, s) or "_no_module_"
            key = re.sub(r"\(\d+\)$", "", mod) + ":" + short_name(name)
            kernel = KERNEL_CALL.search(given)
            if kernel:            # a Pallas kernel: say which, by its call site
                key += "@" + kernel.group(1)
            agg[key] = agg.get(key, 0.0) + (e - s)
        return sorted(agg.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n=10, device=0):
        """Idle seconds summed by what the host was doing: the innermost
        ``bench.*`` span over the gap's middle, the program the gap sits
        inside, or ``_no_host_span_``."""
        dev = self.devices[device]
        merged = self._union(dev["ops"])
        edges = [self.t0] + [t for iv in merged for t in iv] + [self.t1]
        spans = sorted(self.spans, key=lambda s: s[1])
        agg = {}
        for i in range(0, len(edges), 2):
            s, e = edges[i], edges[i + 1]
            if e - s <= 0:
                continue
            mid = 0.5 * (s + e)
            mod = self._module_of(dev, mid)
            if mod is not None:
                key = "_inside_" + re.sub(r"\(\d+\)$", "", mod) + "_"
            else:
                cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
                key = (min(cover, key=lambda sp: sp[2] - sp[1])[0]
                       if cover else "_no_host_span_")
            agg[key] = agg.get(key, 0.0) + (e - s)
        return sorted(agg.items(), key=lambda kv: -kv[1])[:n]

    def span_seconds(self, name):
        hits = [e - s for n, s, e in self.spans if n == name]
        return sum(hits), len(hits)

    def idle_under(self, name, device=0):
        """Idle seconds of the chip while the host was inside span ``name``."""
        merged = self._union(self.devices[device]["ops"])
        busy_edges = [t for iv in merged for t in iv]
        total = 0.0
        for n, s, e in self.spans:
            if n != name:
                continue
            s, e = max(s, self.t0), min(e, self.t1)
            busy = 0.0
            i = bisect_right(busy_edges, s)
            i -= i % 2           # start of the interval at or after s's pair
            while i < len(busy_edges) and busy_edges[i] < e:
                busy += max(0.0, min(busy_edges[i + 1], e) - max(busy_edges[i], s))
                i += 2
            total += (e - s) - busy
        return total

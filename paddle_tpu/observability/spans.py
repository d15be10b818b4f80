"""Structured spans on the profiler's clock, recorded in the host tracer.

``span(name, **attrs)`` is *live* while a ``jax.profiler`` session is
live (``TraceAnnotation.is_enabled()``: ``jax.profiler.start_trace``, a
TensorBoard capture) or while ``runtime.HostTracer`` is enabled (the
``paddle_tpu.profiler.Profiler`` path).  A live span does two things:

- it opens a ``jax.profiler.TraceAnnotation(name, **attrs)``, so it sits
  in the profiler's own ``.xplane.pb`` / Perfetto file on the host's
  ``python`` line, on ONE clock with the device's ``XLA Ops`` by
  construction, its attributes as event stats;
- it is recorded in the ONE in-memory buffer, ``HostTracer``'s, on that
  tracer's monotonic clock.  The tracer's native event tuple has no
  args field (native C++ and Python fallback share the
  ``(kind, t0, t1, tid, value, name)`` schema), so attributes are
  encoded into the event name with ``;k=v`` suffixes; ``recorded()``
  and the chrome-trace writer decode them back.

Going live because of a profiler session alone starts a new trace
generation and clears the buffer, exactly as ``Profiler`` does at a
record window's start; when the session has ended, the next span or
instant sees it and goes quiet.  When nothing is live ``__enter__`` is
one attribute load and one ``is_enabled()`` -- attrs are never
formatted, no object beyond the ``span`` itself is made -- so
instrumented hot loops (the serving scheduler) pay nothing outside a
profiling window.

``merge_chrome_traces`` writes host lanes (the tracer's events, the
flight recorder's per-request lanes, the fleet's per-replica lanes)
into one Perfetto-loadable JSON.  The device's events are not pasted
in: the profiler's own trace already holds them and the spans on one
clock.
"""

from __future__ import annotations

import json
import threading

from jax.profiler import TraceAnnotation as _Annotation

from .. import runtime as rt

_ATTR_SEP = ";"

# tracing-window generation: bumped at every record-window start (after
# HostTracer.clear()).  Ranges opened in an earlier window no longer
# exist on the tracer, so a close crossing a window boundary must
# become a no-op instead of popping an unrelated range.
_trace_gen = 0
# True while the tracer records on behalf of a jax.profiler session
# alone (no Profiler enabled it): the one state ``_live`` has to undo
_session_owned = False
_window_mu = threading.Lock()


def current_trace_generation() -> int:
    return _trace_gen


def start_recording(for_session: bool = False):
    """Start a record window: clear the buffer, invalidate the ranges
    any previous window left open (their tracer stack entries did not
    survive the clear) and enable the tracer.  ``Profiler`` calls it
    at a window's start; ``_live`` calls it ``for_session`` when a
    span finds a profiler session live (and undoes it when the session
    has ended)."""
    global _trace_gen, _session_owned
    with _window_mu:
        if for_session and rt.HostTracer.enabled:
            return              # another thread's span went live first
        rt.HostTracer.clear()
        _trace_gen += 1
        _session_owned = for_session
        rt.HostTracer.enable()


def _live() -> bool:
    """Whether spans record right now; follows a ``jax.profiler``
    session going live and ending."""
    global _session_owned
    if rt.HostTracer.enabled:
        if _session_owned and not _Annotation.is_enabled():
            with _window_mu:
                if _session_owned:
                    _session_owned = False
                    rt.HostTracer.disable()
            return rt.HostTracer.enabled
        return True
    if _Annotation.is_enabled():
        start_recording(for_session=True)
        return True
    return False


def _esc_attr(v) -> str:
    """Escape ``;``/``=`` in attr values so a value cannot fabricate
    extra attrs on re-parse (same contract as the metrics label-key
    escaping; inverse is ``_unesc_attr``)."""
    return (str(v).replace("%", "%25").replace(";", "%3B")
            .replace("=", "%3D"))


def _unesc_attr(v: str) -> str:
    return v.replace("%3D", "=").replace("%3B", ";").replace("%25", "%")


def format_span_name(name: str, attrs: dict) -> str:
    if not attrs:
        return name
    return name + _ATTR_SEP + _ATTR_SEP.join(
        f"{k}={_esc_attr(v)}" for k, v in attrs.items())


def parse_span_name(encoded: str):
    """Inverse of ``format_span_name``: ``(name, attrs_dict)``."""
    if _ATTR_SEP not in encoded:
        return encoded, {}
    name, *parts = encoded.split(_ATTR_SEP)
    attrs = {}
    for p in parts:
        k, _, v = p.partition("=")
        if k:
            attrs[k] = _unesc_attr(v)
    return name, attrs


class span:
    """Context manager recording a named host range with attributes.

    with span("serving.decode_block", steps=4, active=7):
        run_block()

    Re-entrant per instance is NOT supported (one range per ``with``);
    nesting distinct spans is (the tracer keeps a per-thread stack).
    """

    __slots__ = ("_name", "_attrs", "_ann", "_gen")

    def __init__(self, name: str, **attrs):
        self._name = name
        self._attrs = attrs
        self._ann = None
        self._gen = 0

    def __enter__(self):
        if _live():
            self._gen = _trace_gen
            self._ann = _Annotation(self._name, **self._attrs)
            self._ann.__enter__()
            rt.HostTracer.begin(format_span_name(self._name, self._attrs))
        return self

    def __exit__(self, *exc):
        ann = self._ann
        if ann is not None:
            self._ann = None
            # a window boundary between enter and exit invalidated the
            # opened range — closing now would pop someone else's
            if self._gen == _trace_gen:
                rt.HostTracer.end()
            ann.__exit__(*exc)
        return False


def instant(name: str, **attrs):
    """Zero-duration marker (request queued / finished) with attrs."""
    if _live():
        with _Annotation(name, **attrs):
            rt.HostTracer.instant(format_span_name(name, attrs))


def recorded() -> list:
    """The closed spans of the current trace generation as
    ``(name, t0_ns, t1_ns, thread, attrs)``, times on the tracer's
    monotonic clock: what ``HostTracer.events()`` holds, decoded."""
    out = []
    for kind, t0, t1, tid, _value, raw in rt.HostTracer.events():
        if kind == 0:
            name, attrs = parse_span_name(raw)
            out.append((name, t0, t1, tid, attrs))
    return out


def _host_events_as_chrome(events) -> list:
    """HostTracer event tuples -> chrome trace events, span-attr names
    decoded into ``args``."""
    out = []
    for kind, t0, t1, tid, value, raw in events:
        name, attrs = parse_span_name(raw)
        e = {"name": name, "pid": 0, "tid": tid, "ts": t0 / 1e3}
        if attrs:
            e["args"] = attrs
        if kind == 0:
            e.update(ph="X", dur=(t1 - t0) / 1e3)
        elif kind == 1:
            e.update(ph="i", s="t")
        else:
            e.update(ph="C", args={"value": value, **attrs})
        out.append(e)
    return out


def merge_chrome_traces(out_path: str, host=None, extra=None) -> dict:
    """Write one chrome/Perfetto JSON of host lanes.

    ``host``: path to an exported host chrome trace, a list of
    HostTracer event tuples, or None (= the live tracer buffer).
    ``extra``: already-formed chrome event dicts appended verbatim —
    the hook fleet exports use to add one process lane per replica
    (their own pids + process_name metadata) without re-implementing
    the writer.

    Returns summary counts: ``{"host_events", "extra_events",
    "path"}``.
    """
    events = [{"ph": "M", "pid": 0, "name": "process_name",
               "args": {"name": "host (paddle_tpu.runtime.HostTracer)"}}]
    if host is None:
        host_events = _host_events_as_chrome(rt.HostTracer.events())
    elif isinstance(host, str):
        with open(host) as f:
            host_events = json.load(f).get("traceEvents", [])
        # an exported host trace carries raw encoded names — decode the
        # span-attr suffixes here too, so all three input forms honor
        # the "attrs land as Perfetto args" contract
        for e in host_events:
            raw = e.get("name", "")
            if _ATTR_SEP in raw:
                e["name"], attrs = parse_span_name(raw)
                if attrs:
                    e["args"] = {**attrs, **e.get("args", {})}
    else:
        host_events = _host_events_as_chrome(host)
    events.extend(host_events)
    n_extra = 0
    if extra is not None:
        for e in extra:
            events.append(e)
            if e.get("ph") != "M":
                n_extra += 1
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return {"host_events": len(host_events), "extra_events": n_extra,
            "path": out_path}

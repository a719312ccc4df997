"""Host spans of the program, on the profiler's clock.

Off by default: :func:`span` then returns one shared no-op context
manager and nothing is recorded. :func:`enable` and :func:`disable` are
the only switches; no flag or environment variable turns the recorder on.

A span is ``[name, start_ns, end_ns, parent, attrs]`` on
``time.time_ns()``, the wall clock of the profiler's
``profile_start_time``, so program spans line up with a device trace and
with any other host spans taken on that clock. ``parent`` is the
sequence number of the enclosing span (``-1`` at top level); the serving
loop has one thread, so one stack gives the nesting. Spans live in a
``deque`` of fixed length (``MAX_SPANS``), so a server left recording
does not grow.

While enabled, JAX's compile events are spans too: ``jax.trace`` (jaxpr
tracing) and ``jax.compile`` (an XLA compile, or a load from the
persistent compilation cache), each with the function's name, and
``jax.cache_load``, the cache retrieval, as a child of the compile that
it served.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Deque, List, Optional

MAX_SPANS = 1 << 16

_TIME_SPAN_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
                     "/jax/core/compile/backend_compile_duration":
                         "jax.compile"}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_on = False
_spans: Deque[list] = collections.deque(maxlen=MAX_SPANS)
_stack: List[int] = []      # sequence numbers of the open spans
_seq = 0                    # sequence number of the next span
_loads: List[list] = []     # cache loads not yet given their compile


class _Span:
    __slots__ = ("rec",)

    def __init__(self, rec: list):
        self.rec = rec

    def __enter__(self):
        _stack.append(self.rec[5])
        return self

    def __exit__(self, *exc):
        self.rec[2] = time.time_ns()
        if _stack and _stack[-1] == self.rec[5]:
            _stack.pop()
        return False


_NOOP = contextlib.nullcontext()


def _record(name: str, start_ns: int, end_ns: int, attrs: dict) -> list:
    global _seq
    rec = [name, start_ns, end_ns, _stack[-1] if _stack else -1, attrs, _seq]
    _seq += 1
    _spans.append(rec)
    return rec


def span(name: str, start_ns: Optional[int] = None, **attrs):
    """A context manager that records ``name`` from entry (or from
    ``start_ns``) to exit; the shared no-op one while disabled."""
    if not _on:
        return _NOOP
    return _Span(_record(name, time.time_ns() if start_ns is None
                         else start_ns, 0, attrs))


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    name = _TIME_SPAN_EVENTS.get(event)
    if name is None:
        return
    rec = _record(name, int(start * 1e9), int(end * 1e9),
                  {"fun_name": kw.get("fun_name", "")})
    if name == "jax.compile":
        for load in [l for l in _loads if rec[1] <= l[1] <= rec[2]]:
            load[3] = rec[5]
            _loads.remove(load)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _CACHE_LOAD_EVENT:
        end = time.time_ns()
        _loads.append(_record("jax.cache_load", end - int(duration * 1e9),
                              end, {}))


def enable() -> None:
    """Start recording afresh, JAX's compile events included."""
    global _on, _spans, _seq
    import jax
    disable()
    _spans = collections.deque(maxlen=MAX_SPANS)
    _stack.clear()
    _loads.clear()
    _seq = 0
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays for :func:`snapshot`."""
    global _on
    if not _on:
        return
    import jax
    jax.monitoring.unregister_event_time_span_listener(_on_time_span)
    jax.monitoring.unregister_event_duration_listener(_on_duration)
    _on = False


def snapshot() -> dict:
    """The finished spans in start order, each ``{"name", "start_ns",
    "end_ns", "parent", "attrs", "seq"}``."""
    spans = [dict(name=n, start_ns=s, end_ns=e, parent=p, attrs=dict(a),
                  seq=q) for n, s, e, p, a, q in _spans if e]
    spans.sort(key=lambda r: (r["start_ns"], r["seq"]))
    return {"spans": spans}


def plane(origin_ns: int) -> dict:
    """The finished spans as a host plane ``/host:program`` of a compact
    trace that starts at ``origin_ns``: ``[name, start, duration]``."""
    return {"name": "/host:program", "lines": [{"name": "program", "events": [
        [n, float(s - origin_ns), float(e - s)]
        for n, s, e, *_ in _spans if e]}]}

"""In-memory spans and counters of the program's host path.

    from repro import obs
    obs.enable()
    ...                      # run the simulator, a KD epoch loop, ...
    rec = obs.disable()      # {"spans": [...], "counts": {...}}

Off by default. Off, ``span(name)`` returns one shared no-op context
manager and ``count`` returns at once: a site costs one module-global
check, no allocation and no clock read.

On, each ``with span(name):`` records ``(start_ns, dur_ns, name, parent,
update)``: the start on the host's wall clock (``time.time_ns()``, the
clock a JAX profiler trace states as ``profile_start_time``, so spans and
device ops share one axis), the duration in ns, the index in ``spans``
of the enclosing span (-1 at top level), and the server update the span
works toward: 1 + the ``updates`` counter at its start, so spans of one
update share that id. ``count(name, n)`` adds ``n`` to a counter.
Everything stays in memory until ``disable()`` hands it out.

A span opened while the recorder was off records nothing when it closes,
nor does one opened in an earlier ``enable()`` session. A span still open
at ``disable()`` is dropped; its closed children take its parent.
Recording never waits for the device (no ``block_until_ready``, no
read-back): a span around an enqueue measures the enqueue.

Single-threaded, like the simulator it records: the open-span stack is
module state. The names in use are listed in docs/fed_engine.md
("Spans and counters").
"""
from __future__ import annotations

import time

UPDATES = "updates"

_on = False
_session = 0
_spans: list = []      # [start_ns, dur_ns or -1 while open, name, parent,
                       #  update]
_stack: list = []      # indexes of the open spans, innermost last
_counts: dict = {}


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP = _Noop()


class _Span:
    __slots__ = ("index", "session")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if _on and self.session == _session:
            rec = _spans[self.index]
            rec[1] = time.time_ns() - rec[0]
            if self.index in _stack:
                _stack.remove(self.index)
        return False


def span(name: str):
    """Context manager recording one span named ``name`` (see the module
    docstring); the shared no-op when the recorder is off."""
    if not _on:
        return NOOP
    s = _Span()
    s.index, s.session = len(_spans), _session
    _spans.append([time.time_ns(), -1, name, _stack[-1] if _stack else -1,
                   _counts.get(UPDATES, 0) + 1])
    _stack.append(s.index)
    return s


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``; nothing when the recorder is
    off."""
    if _on:
        _counts[name] = _counts.get(name, 0) + n


def enabled() -> bool:
    return _on


def enable() -> None:
    """Start recording, from no spans and no counts."""
    global _on, _session
    _session += 1
    _spans.clear()
    _stack.clear()
    _counts.clear()
    _on = True


def disable() -> dict:
    """Stop recording and hand out what was recorded: ``{"spans":
    [(start_ns, dur_ns, name, parent, update), ...], "counts": {name:
    total}}``, spans in order of their start. Spans still open are
    dropped, their closed children taking the nearest kept ancestor."""
    global _on
    _on = False
    keep = [i for i, rec in enumerate(_spans) if rec[1] >= 0]
    index = {old: new for new, old in enumerate(keep)}

    def kept_parent(i):
        while i >= 0 and i not in index:
            i = _spans[i][3]
        return index.get(i, -1)

    spans = [(s, d, name, kept_parent(parent), update)
             for s, d, name, parent, update in (_spans[i] for i in keep)]
    out = {"spans": spans, "counts": dict(_counts)}
    _spans.clear()
    _stack.clear()
    _counts.clear()
    return out

"""In-process spans and counters for the live FL path.

``span(name, **attrs)`` is a context manager that times the block it
wraps on ``time.perf_counter`` and exposes the duration as ``.seconds``;
the program's own host timings (local training, aggregation, the
round walls ``fl_train`` prints) are read from these objects, so there
is one clock.

With no recorder installed (the default) that is all a span does: it
records nothing and touches no profiler. ``enable()`` installs a
``Recorder``; from then on every span appends a ``SpanRecord`` when it
closes and, while open, is a ``jax.profiler.TraceAnnotation`` named
``fl:<name>``, which puts it on the device trace's clock whenever the
profiler runs. ``count(name, n, **attrs)`` appends a ``CountRecord``
(a no-op with no recorder). Records stay in memory until a caller reads
them or writes them out (``Recorder.write_jsonl``).

Spans nest per thread: a record names its enclosing span (``parent``).
A span given ``update=`` starts a client update's group and its
descendants inherit that id (``SpanRecord.update``), so the spans of one
update share it wherever they run.

The recorder also owns the program's one listener for JAX's compile
events, recorded as the counters ``jax.compiles``, ``jax.compile_s`` and
``jax.cache_hits`` (persistent-cache hits).
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from typing import List, Optional

_perf = time.perf_counter
_recorder: Optional["Recorder"] = None
_listening = False

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SpanRecord:
    """One closed span: ``id`` is unique within its recorder, ``parent``
    the enclosing span's id (None at the root), ``update`` the client
    update it belongs to (None outside any), times on ``perf_counter``."""

    __slots__ = ("name", "id", "parent", "start", "end", "update", "attrs")

    def __init__(self, name, id, parent, start, end, update, attrs):
        self.name = name
        self.id = id
        self.parent = parent
        self.start = start
        self.end = end
        self.update = update
        self.attrs = attrs

    @property
    def seconds(self) -> float:
        return self.end - self.start


class CountRecord:
    """One counter event: ``n`` added to ``name`` at time ``t``, inside
    the span ``parent`` (None outside any)."""

    __slots__ = ("t", "name", "n", "parent", "attrs")

    def __init__(self, t, name, n, parent, attrs):
        self.t = t
        self.name = name
        self.n = n
        self.parent = parent
        self.attrs = attrs


class Recorder:
    """The spans and counter events recorded since ``enable()``."""

    def __init__(self):
        import jax
        self.spans: List[SpanRecord] = []
        self.counts: List[CountRecord] = []
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._annotation = jax.profiler.TraceAnnotation

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _open(self, sp: "span") -> None:
        stack = self._stack()
        parent = stack[-1] if stack else None
        sp._id = next(self._ids)
        sp._parent = parent._id if parent is not None else None
        update = sp.attrs.pop("update", None)
        sp._update = (update if update is not None
                      else parent._update if parent is not None else None)
        sp._ann = self._annotation("fl:" + sp.name)
        sp._ann.__enter__()
        stack.append(sp)

    def _close(self, sp: "span") -> None:
        sp._ann.__exit__(None, None, None)
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        self.spans.append(SpanRecord(sp.name, sp._id, sp._parent, sp.start,
                                     sp.end, sp._update, sp.attrs))

    def _count(self, name: str, n, attrs: dict) -> None:
        stack = self._stack()
        self.counts.append(CountRecord(_perf(), name, n,
                                       stack[-1]._id if stack else None,
                                       attrs))

    def total(self, name: str) -> float:
        """Sum of every ``n`` counted under ``name``."""
        return sum(c.n for c in self.counts if c.name == name)

    def named(self, name: str) -> List[SpanRecord]:
        """The closed spans called ``name``, in the order they closed."""
        return [s for s in self.spans if s.name == name]

    def write_jsonl(self, path) -> None:
        """One JSON object per line, in time order: a span as
        ``{"span", "id", "parent", "update", "start", "end", "attrs"}``,
        a counter event as ``{"count", "n", "t", "parent", "attrs"}``."""
        lines = [(s.start, {"span": s.name, "id": s.id, "parent": s.parent,
                            "update": s.update, "start": s.start,
                            "end": s.end, "attrs": s.attrs})
                 for s in self.spans]
        lines += [(c.t, {"count": c.name, "n": c.n, "t": c.t,
                         "parent": c.parent, "attrs": c.attrs})
                  for c in self.counts]
        lines.sort(key=lambda kv: kv[0])
        with open(path, "w") as f:
            for _, obj in lines:
                f.write(json.dumps(obj, default=str) + "\n")


class span:
    """Times a block; records it where a recorder is installed.

    ``with obs.span("hub.fedavg", updates=7) as sp: ...`` then
    ``sp.seconds``. Keyword arguments are the span's attributes;
    ``update=`` names the client update the span and its descendants
    belong to."""

    __slots__ = ("name", "attrs", "start", "end", "_rec", "_ann", "_id",
                 "_parent", "_update")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.start = self.end = 0.0
        self._rec = None

    def __enter__(self) -> "span":
        rec = _recorder
        if rec is not None:
            self._rec = rec
            rec._open(self)
        self.start = _perf()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = _perf()
        if self._rec is not None:
            self._rec._close(self)
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start


def count(name: str, n, **attrs) -> None:
    """Add ``n`` to the counter ``name``; a no-op with no recorder."""
    rec = _recorder
    if rec is not None:
        rec._count(name, n, attrs)


def recording() -> bool:
    """Is a recorder installed? Callers test it before computing a
    counter's value where that costs more than the call."""
    return _recorder is not None


def enable() -> Recorder:
    """Install a fresh recorder (replacing any) and return it."""
    global _recorder
    _listen()
    _recorder = Recorder()
    return _recorder


def disable() -> Optional[Recorder]:
    """Uninstall the recorder; -> the one that was installed."""
    global _recorder
    rec, _recorder = _recorder, None
    return rec


def _listen() -> None:
    """Register the compile-event listeners once per process; they count
    into whichever recorder is installed when an event arrives."""
    global _listening
    if _listening:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _listening = True


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        count("jax.compiles", 1)
        count("jax.compile_s", duration)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        count("jax.cache_hits", 1)

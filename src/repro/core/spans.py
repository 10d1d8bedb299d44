"""Spans: named intervals of host work, on the profiler trace's clock and
in a campaign's ``timings`` dict.

``span(name, key, **args)`` always emits a ``jax.profiler.TraceAnnotation``
(a no-op unless a profiler runs), so the interval lands on the trace's host
plane beside the device's operations; ``args`` (a ``uid``, say) become event
stats. Where ``collect(timings)`` made a dict the active sink and ``key`` is
given, the span's seconds are added to ``timings[key]``, so code deep in the
call tree reports into the campaign's dict without new parameters;
``count(key)`` adds a count there the same way. ``ProgramCache`` keeps the
jitted programs of a process and counts ``programs_built`` and
``programs_reused``.

JAX is never imported here: the annotation is emitted only once something
else has imported ``jax``, so the ``cost_model`` census stays jax-free.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Callable, Dict, Hashable, Iterator, Mapping, Optional

_SINK: ContextVar[Optional[Dict[str, float]]] = ContextVar("repro_span_sink", default=None)


@contextmanager
def collect(timings: Dict[str, float]) -> Iterator[Dict[str, float]]:
    """Make ``timings`` the sink of every keyed span inside the block."""
    token = _SINK.set(timings)
    try:
        yield timings
    finally:
        _SINK.reset(token)


class span:
    """``with span("session.sample", "sample_s", uid=uid) as s:`` times the
    block into the active sink's ``sample_s`` and the trace; ``s.seconds``
    is set on exit, also when the block raises."""

    __slots__ = ("name", "key", "args", "seconds", "_note", "_t0")

    def __init__(self, name: str, key: Optional[str] = None, **args: Any) -> None:
        self.name, self.key, self.args = name, key, args
        self.seconds = 0.0

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        self._note = None if jax is None else jax.profiler.TraceAnnotation(self.name, **self.args)
        if self._note is not None:
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)
        sink = _SINK.get()
        if self.key is not None and sink is not None:
            sink[self.key] = sink.get(self.key, 0.0) + self.seconds


def instance_args(uid: str, params: Mapping[str, Any]) -> Dict[str, Any]:
    """The stats of a census instance's spans: its ``uid``, and the model
    ``layer`` where the instance is one."""
    return {"uid": uid, **({"layer": params["layer"]} if "layer" in params else {})}


def count(key: str, n: float = 1) -> None:
    """Add ``n`` to the active sink's ``key``; nothing without a sink."""
    sink = _SINK.get()
    if sink is not None:
        sink[key] = sink.get(key, 0.0) + n


def named(name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """``fn`` under ``name``, so that ``jax.jit`` calls its program
    ``jit_<name>`` and the device trace says which algorithm ran. Each call
    makes a new function, and ``jax.jit`` caches compiled programs by
    function: name a shared function once and keep the result, as
    :class:`ProgramCache` does for the chain and generalized programs."""

    def program(*args: Any) -> Any:
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


class ProgramCache:
    """The jitted programs of a process, one per key, least recently used
    first out beyond ``maxsize``.

    ``get(key, build)`` returns the program kept under ``key``, or keeps and
    returns ``build()``; it counts ``programs_built`` or ``programs_reused``
    into the active sink. Keep under one key only programs that differ in
    nothing but their arguments: ``jax.jit`` keeps one executable per shape
    signature under each, so nothing else belongs in the key."""

    def __init__(self, maxsize: int) -> None:
        self.maxsize = maxsize
        self._programs: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: Hashable, build: Callable[[], Any]) -> Any:
        with self._lock:
            program = self._programs.get(key)
            if program is not None:
                self._programs.move_to_end(key)
        if program is not None:
            count("programs_reused")
            return program
        program = build()
        with self._lock:
            program = self._programs.setdefault(key, program)
            self._programs.move_to_end(key)
            while len(self._programs) > self.maxsize:
                self._programs.popitem(last=False)
        count("programs_built")
        return program

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()

"""Spans: named intervals of host work, on the profiler trace's clock and
in a campaign's ``timings`` dict.

``span(name, key, **args)`` always emits a ``jax.profiler.TraceAnnotation``
(a no-op unless a profiler runs), so the interval lands on the trace's host
plane beside the device's operations; ``args`` (a ``uid``, say) become event
stats. Where ``collect(timings)`` made a dict the active sink and ``key`` is
given, the span's seconds are added to ``timings[key]``, so code deep in the
call tree reports into the campaign's dict without new parameters;
``count(key)`` adds a count there the same way; the program cache
(:mod:`repro.core.programs`) counts ``programs_built`` and
``programs_reused`` through it.

JAX is never imported here: the annotation is emitted only once something
else has imported ``jax``, so the ``cost_model`` census stays jax-free.
"""

from __future__ import annotations

import sys
import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, Iterator, Mapping, Optional

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

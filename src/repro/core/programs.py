"""Programs: how a set of equivalent algorithms becomes a table of timed
programs.

Every family the census ranks (the paper's chains, the identity families,
the kernel and model-layer sites) is a :class:`VariantSite`: named
:class:`Variant` s with their analytic FLOPs, and the inputs of a seed. Its
:meth:`~VariantSite.workloads` is the one table builder: each variant's
``build`` returns an unwarmed :func:`runner` of a :func:`program`, and the
table makes the one warm call per algorithm (:func:`warm`).

A program is ``jax.jit`` of a function under a trace name, kept once per
process under ``(name, *key)`` (:func:`program`): a second instance of a
site finds every program built, and ``jax.jit`` compiles one executable
per shape signature under it. The key names what changes the program
beyond its arguments' shapes (a chain's steps, a tiling, a window, a
chunk), never a function object: builders make fresh closures per
instance.

JAX is imported only inside the functions that run it, so a site's FLOP
table and the ``cost_model`` census stay jax-free.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Hashable, Iterable, Mapping, Sequence, Tuple

from .spans import count

Thunk = Callable[[], Any]


@dataclass(frozen=True)
class Variant:
    name: str
    flops: float                     # analytic, per execution
    build: Callable[..., Thunk]      # (*inputs) -> unwarmed runner


@dataclass(frozen=True)
class VariantSite:
    name: str
    variants: Tuple[Variant, ...]
    make_inputs: Callable[[int], Sequence[Any]]   # seed -> inputs

    def flops_table(self) -> Dict[str, float]:
        return {v.name: v.flops for v in self.variants}

    def only(self, names: Iterable[str]) -> "VariantSite":
        """The site with the variants ``names`` alone, in that order."""
        by_name = {v.name: v for v in self.variants}
        return replace(self, variants=tuple(by_name[n] for n in names))

    def workloads(self, seed: int = 0) -> Dict[str, Thunk]:
        """name -> blocking thunk on the inputs of ``seed``, each warmed
        here by one call (:func:`warm`), the only call before the timer's."""
        inputs = self.make_inputs(seed)
        table = {v.name: v.build(*inputs) for v in self.variants}
        warm(table)
        return table


def warm(workloads: Mapping[str, Thunk]) -> None:
    """The single warm run per algorithm (paper Sec. I step 1): each
    workload called once, untimed, so that compilation ("library
    overheads") never lands in a timed region. Counts ``warm_calls`` into
    the active span sink, one per workload."""
    for fn in workloads.values():
        fn()
        count("warm_calls")


def named(name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """``fn`` under ``name``, so that ``jax.jit`` calls its program
    ``jit_<name>`` and the device trace says which algorithm ran. Each call
    makes a new function: :func:`program` keeps the jitted result."""

    def program(*args: Any) -> Any:
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


class ProgramCache:
    """The jitted programs of a process, one per key, least recently used
    first out beyond ``maxsize``.

    ``get(key, build)`` returns the program kept under ``key``, or keeps and
    returns ``build()``; it counts ``programs_built`` or ``programs_reused``
    into the active sink."""

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


#: Every jitted program of the process. A 4-matrix chain's six names map to
#: a few dozen step sequences across dims; the sites add a few each.
PROGRAMS = ProgramCache(maxsize=512)


def program(name: str, fn: Callable[..., Any], *key: Hashable) -> Callable[..., Any]:
    """``jax.jit`` of ``fn`` as the program ``jit_<name>``, built once per
    process under ``(name, *key)``: a later ``fn`` under the same key is
    not looked at, so ``key`` holds every static value ``fn`` closes over
    that changes what it computes."""
    import jax

    return PROGRAMS.get((name, *key), lambda: jax.jit(named(name, fn)))


def runner(program: Callable[..., Any], *args: Any) -> Thunk:
    """The zero-argument thunk that runs ``program`` on ``args``, waits for
    the device and returns the result, unwarmed: it compiles on its first
    call, the table's warm run."""
    import jax

    def run() -> Any:
        return jax.block_until_ready(program(*args))

    return run

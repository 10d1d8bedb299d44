"""Shared helpers for the paper-table benchmarks.

Benchmarks run their Procedure-4 loops through the core ExperimentEngine:
:func:`run_campaign` interleaves many sessions under one scheduler and —
when the harness passes a state directory — persists every campaign to
JSON so an interrupted benchmark invocation resumes (``--resume``) instead
of re-measuring from scratch.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core import (
    DiscriminantReport,
    ExperimentEngine,
    MeasurementSession,
    RankingResult,
    WallClockTimer,
    relative_flops,
)
from repro.expressions import (
    ChainInstance,
    chain_site,
    flops_table,
    get_instance,
)


@dataclasses.dataclass
class BenchContext:
    """Harness-level campaign options threaded into every bench module."""

    state_dir: Optional[str] = None
    resume: bool = False

    def state_path(self, name: str) -> Optional[str]:
        if not self.state_dir:
            return None
        return os.path.join(self.state_dir, f"{name}.json")


def run_campaign(
    make_sessions: Callable[[], Sequence[MeasurementSession]],
    name: str,
    ctx: Optional[BenchContext] = None,
    *,
    policy: str = "least_converged_first",
    max_steps: Optional[int] = None,
) -> ExperimentEngine:
    """One interleaved measurement campaign, persisted when the harness
    provides a state directory. ``make_sessions`` is a thunk so a resumed
    campaign (simulated / cost-model backends, which serialize their RNG
    state) skips session construction entirely."""
    path = ctx.state_path(name) if ctx else None
    engine: Optional[ExperimentEngine] = None
    if ctx and ctx.resume and path and os.path.exists(path):
        try:
            engine = ExperimentEngine.load(path)
        except (ValueError, KeyError) as e:  # stale/incompatible state
            print(f"# campaign {name}: ignoring stale state ({e})")
            engine = None
    if engine is None:
        engine = ExperimentEngine(policy=policy)
        for session in make_sessions():
            engine.add_session(session)
    try:
        engine.run(max_steps=max_steps)
    finally:
        # persist even when the invocation is interrupted mid-campaign, so
        # --resume honors its contract (a SIGKILL still loses the state)
        if path:
            engine.save(path)
    return engine


def chain_setup(instance_name: str, smoke: bool, seed: int = 0):
    """(instance, algorithms, workloads table, flops table)."""
    inst = get_instance(instance_name, smoke=smoke)
    algs = inst.algorithms()
    workloads = chain_site(inst.dims).workloads(seed)
    return inst, algs, workloads, flops_table(algs)


def fmt_ranking(res: RankingResult, rf: Dict[str, float]) -> str:
    cells = [
        f"{a.name}[r{a.rank} mr={a.mean_rank:.2f} RF={rf.get(a.name, float('nan')):.2f}]"
        for a in res.sequence
    ]
    return " ".join(cells)


def fmt_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"


def median_ranking(workloads, n: int = 10) -> List[str]:
    """Paper Sec. I style: rank by median of n measurements (the UNSTABLE
    baseline the methodology replaces)."""
    timer = WallClockTimer(workloads)
    meds = {
        name: float(np.median([timer.measure(name) for _ in range(n)]))
        for name in workloads
    }
    return sorted(meds, key=meds.get)

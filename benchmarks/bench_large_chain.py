"""Paper Sec. IV last paragraph: compilers generate 100s of variants — the
candidate set must be filtered before measuring.

Chain of 6 matrices -> 42 parenthesizations -> 120 algorithms (instruction
orders included). Pipeline: single warm run each -> RT filter (threshold
1.5, the paper's suggested value) -> Procedure 4 on the survivors ->
discriminant verdict. Reports the filter ratio and total measurement budget
(the quantity the paper's incremental design minimises).
"""

from __future__ import annotations

import time
from typing import List

from repro.core import (
    WallClockTimer,
    filter_candidates,
    flops_discriminant_test,
    initial_hypothesis_by_time,
    measure_and_rank,
)
from repro.expressions import (
    chain_site,
    flops_table,
    generate_chain_algorithms,
)


def run(smoke: bool, out: List[str], ctx=None) -> None:
    t0 = time.time()
    # skewed dims make the variant space performance-diverse
    scale = 1 if smoke else 2
    dims = tuple(d * scale for d in (48, 96, 12, 128, 24, 96, 48))
    algs = generate_chain_algorithms(dims)
    flops = flops_table(algs)
    workloads = chain_site(dims).workloads(seed=0)
    timer = WallClockTimer(workloads)

    single = {n: timer.measure(n) for n in workloads}
    cand = filter_candidates(flops, single, rt_threshold=1.5)
    out.append(
        f"large_chain.filter,{(time.time()-t0)*1e6:.0f},"
        f"{len(algs)} algorithms -> {len(cand.names)} candidates "
        f"({len(cand.dropped)} dropped by RT>=1.5)"
    )

    h0 = [n for n in initial_hypothesis_by_time(single) if n in cand.names]
    res = measure_and_rank(h0, timer, m_per_iteration=3, eps=0.03,
                           max_measurements=21)
    rep = flops_discriminant_test(res, flops)
    best = res.best_class()
    budget_naive = 21 * len(algs)
    budget_used = res.measurements_per_alg * len(cand.names) + len(algs)
    out.append(
        f"large_chain.ranked,0,candidates={len(cand.names)} "
        f"N={res.measurements_per_alg} classes={max(res.ranks.values())} "
        f"best_class_size={len(best)} anomaly={rep.is_anomaly}({rep.reason})"
    )
    out.append(
        f"large_chain.measurement_budget,0,{budget_used} runs vs "
        f"{budget_naive} naive (x{budget_naive/max(budget_used,1):.1f} saved)"
    )

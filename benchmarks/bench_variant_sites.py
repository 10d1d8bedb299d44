"""Beyond-paper: the ranking methodology over the FRAMEWORK's variant sites.

Each site is a set of mathematically equivalent implementations inside the
training/serving stack (repro.autotune.variants); the paper's pipeline
(filter -> Procedure 4 -> FLOPs test) selects the production variant and
reports whether FLOPs discriminated. Expression families beyond chains
(solve/gram/distributive) exercise identities the chain instances cannot.

All sites are ranked as ONE interleaved ``rank_sites`` campaign (each site
one engine session), and the expression families as a second campaign —
the engine spends iterations where ranks are still moving instead of
running each site to convergence serially.
"""

from __future__ import annotations

import time
from typing import List

from repro.autotune import (
    attention_site,
    matmul_blocks_site,
    moe_dispatch_site,
    prepare_site,
    rank_sites,
    ssd_chunk_site,
)
from repro.core import (
    MeasurementSession,
    WallClockTimer,
    flops_discriminant_test,
    initial_hypothesis_by_time,
)
from repro.expressions import FAMILIES

from .common import run_campaign


def _emit(out: List[str], rep) -> None:
    tag = rep.site.split("[")[0]
    seq = "|".join(
        f"{a.name}:r{a.rank}" for a in rep.ranking.sequence
    )
    out.append(f"variants.{tag},{rep.wall_time_s*1e6:.0f},{seq} "
               f"selected={rep.selected} anomaly={rep.discriminant.is_anomaly}"
               f"({rep.discriminant.reason})")


def run(smoke: bool, out: List[str], ctx=None) -> None:
    scale = 0.5 if smoke else 1.0
    sites = [
        moe_dispatch_site(tokens=int(4096 * scale), d=256, e=16, top_k=2, d_ff=256),
        attention_site(b=2, s=int(2048 * scale), h=8, kv=2, d=64),
        ssd_chunk_site(b=2, s=int(2048 * scale), h=8, p=32, n=32,
                       chunks=(64, 128, 256)),
    ]
    prepared = [prepare_site(site) for site in sites]
    if not smoke:
        # interpreted Pallas matmul is the slowest site: reduced budget
        matmul = prepare_site(
            matmul_blocks_site(m=512, k=512, n=512,
                               blocks=((128, 128, 128), (256, 256, 256)))
        )
        matmul.max_measurements = 9
        prepared.append(matmul)
    # One interleaved campaign across every site (wall-clock backends do not
    # resume across processes, so no state file here).
    reports = rank_sites(prepared, max_measurements=18,
                         policy="least_converged_first")
    for site in prepared:
        _emit(out, reports[site.name])

    # expression families (beyond-chain identities) — second campaign
    t0 = time.time()
    fams = ("solve", "distributive", "gram", "bilinear")
    flops_by_fam = {}
    sessions = []
    for fam_name in fams:
        size = int(512 * scale) if fam_name != "bilinear" else int(1024 * scale)
        fam = FAMILIES[fam_name](size)
        workloads = fam.workloads()
        flops_by_fam[fam_name] = fam.flops_table()
        timer = WallClockTimer(workloads)
        single = {n: timer.measure(n) for n in workloads}
        sessions.append(
            MeasurementSession(
                fam_name, initial_hypothesis_by_time(single), timer,
                m_per_iteration=3, eps=0.03, max_measurements=18,
            )
        )
    engine = run_campaign(lambda: sessions, "families", ctx=None,
                          policy="least_converged_first")
    campaign_us = (time.time() - t0) * 1e6
    for fam_name in fams:
        res = engine.session(fam_name).result()
        repd = flops_discriminant_test(res, flops_by_fam[fam_name])
        seq = "|".join(f"{a.name}:r{a.rank}" for a in res.sequence)
        out.append(
            f"variants.family_{fam_name},0,{seq} "
            f"anomaly={repd.is_anomaly}({repd.reason})"
        )
    out.append(
        f"variants.families_campaign,{campaign_us:.0f},"
        f"{engine.steps_taken} engine iterations across {len(fams)} families"
    )

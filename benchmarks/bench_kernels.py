"""Kernel-variants census throughput — the repo's own Pallas kernels
ranked on wall clock.

The kernel_variants family censuses the repo's actual kernel variants
(Pallas matmul tile shapes, fused vs unfused attention blocks, SSD chunk
lengths — FLOP-identical by construction) through the ordinary resumable
census pipeline on the ``wall_clock`` backend, interpret mode on CPU. The
numbers that matter:

* ``kernels.census`` — census instances/minute end-to-end through
  plan + queue-drain + merge (the CI smoke lane's cost), and
* one ``kernels.site.*`` row per site — mean per-call wall time of the
  site's variants at the benchmark shape, straight through the same
  WallClockTimer the census uses (inner-repeat guard included), so a
  kernel regression shows up as its own row rather than hiding inside
  the aggregate.

Interpret-mode Pallas is orders of magnitude slower than compiled XLA —
these rows gate the *harness and kernels* on CPU; the compiled TPU run is
``chip_smoke.py`` (README "Running on a TPU").
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import List


def _env() -> dict:
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    parts = [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS"):
        env.setdefault(var, "1")
    return env


def _grid_flags(smoke: bool) -> List[str]:
    sizes = "32" if smoke else "32,64"
    per_size = "1" if smoke else "2"
    return [
        "--chains", "0", "--families", "kernel_variants",
        "--kernel-sites", "matmul,attention,ssd",
        "--sizes", sizes, "--per-size", per_size,
        "--shards", "2", "--backend", "wall_clock",
        "--max-measurements", "9",
    ]


def _checked(cmd: List[str], env: dict) -> None:
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{' '.join(cmd[2:5])} failed ({proc.returncode}): "
            f"{proc.stderr[-500:]}"
        )


def _census_row(out: List[str], smoke: bool) -> None:
    env = _env()
    with tempfile.TemporaryDirectory(prefix="bench_kernels_") as tmp:
        store = os.path.join(tmp, "census")
        t0 = time.time()
        _checked(
            [sys.executable, "-m", "repro.launch.sweep", "plan",
             "--out", store] + _grid_flags(smoke),
            env,
        )
        _checked(
            [sys.executable, "-m", "repro.launch.queue", "run",
             "--out", store, "--hosts", "1", "--poll", "0.2"],
            env,
        )
        seconds = time.time() - t0
        records = [json.loads(l)
                   for l in open(os.path.join(store, "merged.jsonl"))]
    n = len(records)
    anomalies = sum(1 for r in records if r["is_anomaly"])
    per_min = 60.0 * n / seconds if seconds > 0 else 0.0
    out.append(
        f"kernels.census,{1e6 * seconds / max(1, n):.0f},"
        f"{per_min:.1f} instances/min ({n} instances {anomalies} anomalies "
        f"wall_clock interpret)"
    )


def _site_rows(out: List[str], smoke: bool) -> None:
    from repro.core.family import InstanceSpec
    from repro.core.measure import WallClockTimer
    from repro.core.sweep import instance_entry

    size = 32 if smoke else 64
    reps = 3 if smoke else 9
    for site in ("matmul", "attention", "ssd"):
        inst = InstanceSpec(
            index=0, uid=f"kernel_variants-{site}-n{size}-s000",
            family="kernel_variants",
            params={"site": site, "size": size, "seed": 0},
        )
        flops, _, build = instance_entry(inst)
        timer = WallClockTimer(build())
        means = {}
        for name in sorted(flops):
            samples = timer.measure_many(name, reps)
            means[name] = sum(samples) / len(samples)
        worst = max(means, key=means.get)
        mean_us = 1e6 * sum(means.values()) / len(means)
        out.append(
            f"kernels.site.{site},{mean_us:.1f},"
            f"n={size} {len(means)} variants worst={worst} "
            f"{1e6 * means[worst]:.1f}us"
        )


def run(smoke: bool, out: List[str], ctx=None) -> None:
    _census_row(out, smoke)
    _site_rows(out, smoke)

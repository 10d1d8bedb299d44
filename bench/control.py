#!/usr/bin/env python3
"""Readings the check's limit is set from, at a cell's own size, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it takes the instances a run with that seed checks, builds each
through the program's timed path (``build_sweep_session``, the callables the
timer measures) and reads their answers' error against the reference:
the program's reading. Then it puts the control in the program's place, the
reference computed in bfloat16 (operands and products), and reads its error
the same way. One JSON line per seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seed: int):
    import numpy as np

    from bench.harness import answer_errors, sample_round_keeps, sweep_spec
    from repro.core.family import InstanceSpec
    from repro.core.sweep import build_sweep_session

    spec, family = sweep_spec(cell), cell.family
    operands = cell.config["operands"]
    program, control = [], []
    for round_no, index in sorted(sample_round_keeps(cell, seed).items()):
        uid, params = family.rows(cell.config, cell.traffic, seed, round_no)[index]
        session = build_sweep_session(
            spec, InstanceSpec(index=index, uid=uid, family=family.FAMILY, params=params))
        answers = {name: np.asarray(fn(), np.float32)
                   for name, fn in session.timer._workloads.items()}
        del session
        program.append(max(answer_errors(family, params, operands, answers).values()))
        control.append(max(answer_errors(family, params, operands,
                                         family.control(params)).values()))
    return program, control


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench/control.py", description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    args = p.parse_args(argv)
    from repro.launch.cli import use_compile_cache

    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import load_cell

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU; nothing read", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        program, control = readings(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed, "program_err": program,
                          "control_err": control, "limit": cell.config["check"]["err_max"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
